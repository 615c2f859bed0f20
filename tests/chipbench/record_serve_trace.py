"""Record the small serving trace that test_chipbench_serve_trace.py reads.

  python3 tests/chipbench/record_serve_trace.py [out_dir]

On one TPU: the tiny olmo-1b-emu of the tests' configuration
(``chipbench_testlib.TINY_OLMO``) is served by ``ContinuousEngine``, warmed
up, and then two requests are traced to the end inside a ``window`` span:
one mixed step and one decode step.  Writes the trace,
``serve_tiny.xplane.pb.gz``, and the compiled text of both step shapes,
``serve_tiny_hlo.txt.gz``, to ``out_dir`` (default: ``data/`` beside
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

SPLIT = "\n// ---- next step program ----\n"


def main(out: pathlib.Path) -> int:
    import jax
    import numpy as np

    import chipbench_testlib as lib
    from chipbench import common
    from chipbench.drivers import _serve
    from repro.launch.mesh import make_host_mesh
    from repro.serving import ContinuousEngine
    from repro.serving.queue import Request

    if jax.devices()[0].platform != "tpu":
        print("record_serve_trace: needs a TPU", file=sys.stderr)
        return 1
    cfg = lib.TINY_OLMO
    eng_cfg = cfg["engine"]
    mesh = make_host_mesh()
    with mesh:
        eng = ContinuousEngine(
            _serve.arch_of(cfg), mesh, max_seq=eng_cfg["max_seq"],
            max_lanes=eng_cfg["lanes"], chunk=eng_cfg["chunk"],
            page_size=eng_cfg["page_size"], seed=1)
    rng = np.random.default_rng(1)

    def serve(n, prompt, new):
        for _ in range(n):
            eng.submit(Request(prompt=tuple(rng.integers(0, cfg["vocab"],
                                                         prompt)),
                               max_new_tokens=new, arrival=0.0))
        calls = 0
        with mesh:
            while eng.sched.has_work():
                eng.step_once(0.0)
                calls += 1
        return calls

    serve(2, eng_cfg["chunk"] + 1, 3)          # compile both step shapes
    tmp = tempfile.mkdtemp(prefix="serve-trace-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(common.WINDOW):
        calls = serve(2, eng_cfg["chunk"] - 4, 2)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    trace = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1].read_bytes()
    (out / "serve_tiny.xplane.pb.gz").write_bytes(
        gzip.compress(trace, mtime=0))
    texts = eng.step_hlo_texts()
    (out / "serve_tiny_hlo.txt.gz").write_bytes(gzip.compress(
        SPLIT.join(texts[c] for c in sorted(texts)).encode(), mtime=0))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {calls} step_once calls, {len(trace)} bytes of "
          f"trace; utilization {eng.utilization()}")
    return 0


if __name__ == "__main__":
    sys.exit(main(pathlib.Path(sys.argv[1]) if len(sys.argv) > 1
                  else HERE / "data"))
