"""Record the small DGEMM trace that test_chipbench_dgemm.py reads.

  python3 tests/chipbench/record_dgemm_trace.py [out_dir]

On one TPU: ``repro.dot_general(..., precision="ozaki2-m16")`` on float64
operands of HPL's law under x64, at M = K = N = 2048 (so the CRT runs
its rows in blocks), compiled, warmed up, and then two calls traced
inside a ``window`` span, each in a ``call`` span, as the ``dgemm_loop``
driver runs them.  Writes the trace, ``dgemm_2048.xplane.pb.gz``, and
the lines of the compiled text whose instructions appear in the trace,
with the computations that those without an op_name call,
``dgemm_2048_hlo.txt.gz``, to ``out_dir`` (default: ``data/`` beside
this file).  Exits 1 without a TPU.
"""

import gzip
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

N = 2048
SEED = 20261019


def kept_lines(text: str, heads: set) -> list:
    """The instructions of ``text`` that ran (their heads are in
    ``heads``), and the computations called by those of them that carry
    no op_name, which the stage reader looks into."""
    import re

    from chipbench import trace_reduce as tr
    lines = text.splitlines()
    kept = [ln for ln in lines if tr.head(ln)[1] in heads]
    want = {m.group(1) for ln in kept if "op_name=" not in ln
            for m in [re.search(r"calls=%?([\w.\-]+)", ln)] if m}
    body, keep = [], False
    for ln in lines:
        m = re.match(r"^%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$", ln)
        if m:
            keep = m.group(1) in want
        if keep:
            body.append(ln)
        if ln.strip() == "}":
            keep = False
    return kept + body


def main(out: pathlib.Path) -> int:
    import jax
    import jax.numpy as jnp

    from chipbench import common
    from chipbench import trace_reduce as tr
    from chipbench.compile_cache import place_compile_cache
    from chipbench.drivers import dgemm_loop
    from chipbench.reference import dgemm as ref
    from repro.telemetry import trace as rtrace

    if jax.devices()[0].platform != "tpu":
        print("record_dgemm_trace: needs a TPU", file=sys.stderr)
        return 1
    place_compile_cache()
    rtrace.keep_op_names_in_compile_cache()
    with jax.enable_x64(True):
        a_host, b_host = ref.operands(common.rng_of(SEED, 1), N, N, N)
        a, b = jnp.asarray(a_host), jnp.asarray(b_host)
        compiled = jax.jit(dgemm_loop.program("ozaki2-m16")).lower(
            a, b).compile()
        for _ in range(2):
            compiled(a, b).block_until_ready()
        tmp = tempfile.mkdtemp(prefix="dgemm-trace-")
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(common.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("call"):
                    compiled(a, b).block_until_ready()
        jax.profiler.stop_trace()
    path = tr.find_xplane(tmp)
    trace = path.read_bytes()
    from jax.profiler import ProfileData
    heads = {tr.head(ev.name)[1]
             for plane in ProfileData.from_file(str(path)).planes
             if tr.DEVICE_PLANE.match(plane.name)
             for line in plane.lines if line.name == tr.OPS_LINE
             for ev in line.events}
    kept = kept_lines(compiled.as_text(), heads)
    out.mkdir(parents=True, exist_ok=True)
    (out / "dgemm_2048.xplane.pb.gz").write_bytes(
        gzip.compress(trace, mtime=0))
    (out / "dgemm_2048_hlo.txt.gz").write_bytes(
        gzip.compress("\n".join(kept).encode(), mtime=0))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded 2 calls, {len(trace)} bytes of trace, {len(kept)} "
          f"lines of compiled text")
    return 0


if __name__ == "__main__":
    sys.exit(main(pathlib.Path(sys.argv[1]) if len(sys.argv) > 1
                  else HERE / "data"))
