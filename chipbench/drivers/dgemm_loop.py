"""FP64 DGEMM calls one after another, each ending in ``block_until_ready``.

The configuration file gives the precision (a Scheme-II spec) and the
operand dtype, float64; the traffic file gives the shape.  Everything
runs under ``jax.enable_x64``.  The operands follow HPL's law
(``reference/dgemm.py``), are made on the host from the seed and put on
the device in set-up.  The program is compiled once, ahead of the
window; the window runs whole calls of that executable: it closes at
the end of the first call that ends after ``--seconds``, and the rate
is the float64-grade work (2MNK per call) of every call over that time.

Before the compile, the program is traced once more with telemetry on,
which records its route without running it: the check requires that no
Scheme-II call fell back from the fused kernels to the XLA expansion.
The traced run reduces its own trace into encode, kernel and CRT time
(``scheme2_trace``), and prints XLA's own float64 dot as a baseline line.

Run as a script, it reads the check's numbers at another precision (the
configuration's ``control``) or at another size, on whatever platform
JAX finds:

  python3 chipbench/drivers/dgemm_loop.py --workload <cell> \
      --seeds 1 2 3 [--precision ozaki2-m8] [--size 2048]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import common, harness, scheme2_trace, scheme2_work, work
from chipbench.reference import dgemm as ref
from chipbench.reference import gemm as gemm_ref

DIMS = (((1,), (0,)), ((), ()))
OPERAND_STREAM = 1
ROWS_STREAM = 5
BASELINE_MAX_S = 20.0   # longest XLA f64 call the baseline line times whole


def run(ctx):
    import jax
    from repro.telemetry import trace as rtrace
    rtrace.keep_op_names_in_compile_cache()
    with jax.enable_x64(True):
        return _run(ctx)


def route(fn, a, b) -> dict:
    """Trace ``fn`` with telemetry on, without running it: the fallbacks
    the trace recorded and the operand budget it took (None where the
    program records none).  The probe's own wrapper keeps its telemetry
    callbacks out of the program that is timed."""
    import jax
    from repro import telemetry
    from repro.telemetry import record
    telemetry.REGISTRY.clear()
    with telemetry.recording():
        jax.jit(lambda x, y: fn(x, y)).lower(a, b)
    snap = telemetry.REGISTRY.snapshot()
    fallbacks = int(telemetry.REGISTRY.total(record.FALLBACK_EVENTS))
    budget = [g["value"] for g in snap["gauges"]
              if g["name"] == getattr(record, "SCHEME2_BUDGET_BITS", None)]
    telemetry.REGISTRY.clear()
    return {"fallbacks": fallbacks,
            "budget_bits": int(budget[-1]) if budget else None}


def program(spec: str):
    import repro
    return lambda x, y: repro.dot_general(x, y, DIMS, precision=spec)


def _run(ctx):
    import jax
    import jax.numpy as jnp
    cfg, tr = ctx.config, ctx.traffic
    m, k, n = int(tr["m"]), int(tr["k"]), int(tr["n"])
    spec = cfg["precision"]
    a_host, b_host = ref.operands(common.rng_of(ctx.seed, OPERAND_STREAM),
                                  m, k, n)
    a = jnp.asarray(a_host, cfg["dtype"])
    b = jnp.asarray(b_host, cfg["dtype"])
    fn = program(spec)
    path = route(fn, a, b)
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(a, b).compile()
    compile_s = time.perf_counter() - t0
    for _ in range(2):
        c = compiled(a, b).block_until_ready()
    ctx.log(f"compile {compile_s:.1f} s, route {path}")

    spans = common.Spans(False)
    moduli = work.int8_products(spec)
    trace_s = float(tr.get("trace_seconds", 4))
    trace_at = max(0.0, ctx.seconds / 2 - trace_s / 2)
    tracing, traced, t_tr = False, 0, 0.0
    win = None

    t0 = ctx.start_window()
    calls = 0
    while time.perf_counter() - t0 < ctx.seconds:
        if ctx.trace and not tracing and not traced and \
                time.perf_counter() - t0 >= trace_at:
            jax.profiler.start_trace(str(ctx.trace_dir))
            spans.on, tracing, t_tr = True, True, time.perf_counter()
            win = jax.profiler.TraceAnnotation(common.WINDOW)
            win.__enter__()
        with spans("call"):
            c = compiled(a, b).block_until_ready()
        calls += 1
        if tracing:
            traced += 1
            if time.perf_counter() - t_tr >= trace_s:
                win.__exit__(None, None, None)
                spans.on, tracing = False, False
                jax.profiler.stop_trace()
    elapsed = time.perf_counter() - t0
    if tracing:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()

    mem = harness.memory_peak(ctx.devices)
    rows = gemm_ref.sample_rows(common.rng_of(ctx.seed, ROWS_STREAM), m,
                                int(tr["check"]["rows"]))
    idx = jnp.asarray(rows)
    a_rows, c_rows = np.asarray(a[idx]), np.asarray(c[idx])
    b_dev = np.asarray(b)
    r = ref.rows_product(a_rows, b_dev)
    err = gemm_ref.normalized_error(c_rows, r, a_rows, b_dev)
    got_bits = ref.bits(c_rows, r)
    lines, texts, stage_work = [], [], {}
    lines.append(json.dumps({
        "dgemm": spec, "shape": [m, k, n], "compile_s": compile_s,
        "norm_err": err, "bits": got_bits, **path,
        "operands_kept": bool(np.array_equal(b_dev, b_host)
                              and np.array_equal(a_rows, a_host[rows])),
        "operand_low_zero_bits": int(ref.trailing_zero_bits(
            b_dev[:64]).min()),
        "memory_peak_bytes": mem}))
    if ctx.trace:
        texts.append(compiled.as_text())
        red = scheme2_trace.reduce_dir(ctx.trace_dir, texts,
                                       chips=len(ctx.devices))
        stage_work = {f"{s}_s": v for s, v in red.stage_s.items()}
        lines.append(json.dumps({
            "stages_per_call_ms": {s: 1e3 * v / max(1, traced)
                                   for s, v in red.stage_s.items()},
            "stages_share_of_busy": red.share(), "busy_s": red.busy_s,
            "window_s": red.window_s, "traced_calls": traced}))
        del c
        lines.append(baseline_line(a, b, a_rows, b_dev, r, rows, m, k, n))
    ctx.log(f"calls={calls} elapsed={elapsed:.4f}s traced_calls={traced} "
            f"norm_err={err:.3e} bits={got_bits:.2f}")
    return harness.Outcome(
        attempted=calls, failed=0,
        metrics={"gemm_tflops": calls * work.gemm_flops(m, k, n)
                 / elapsed / 1e12},
        checks={"norm_err": harness.Check(err, float(tr["check"]["norm_err"])),
                "fallbacks": harness.Check(path["fallbacks"], 0)},
        memory_peak_bytes=mem,
        work={"calls": traced,
              "int8_ops": traced * moduli * work.gemm_flops(m, k, n),
              "kernel_least_s": traced * scheme2_work.kernel_least_s(
                  moduli, m, k, n, ctx.peaks),
              **stage_work},
        hlo_texts=texts, lines=lines,
        readings={"norm_err": err, "bits": got_bits},
        spans={"call": traced} if ctx.trace else {})


def baseline_line(a, b, a_rows, b_host, r, rows, m, k, n) -> str:
    """XLA's own float64 dot, what the emulation replaces: timed on the
    sampled rows of A, and whole when that projects to at most
    BASELINE_MAX_S a call.  Printed, not a metric."""
    import jax
    import jax.numpy as jnp

    def timed(x):
        f = jax.jit(lambda x, y: jnp.dot(
            x, y, precision=jax.lax.Precision.HIGHEST))
        out = f(x, b).block_until_ready()
        t0 = time.perf_counter()
        out = f(x, b).block_until_ready()
        return out, time.perf_counter() - t0

    c_rows, t_rows = timed(jnp.asarray(a_rows))
    err = gemm_ref.normalized_error(np.asarray(c_rows), r, a_rows, b_host)
    line = {"baseline": "xla_f64_dot", "shape": [m, k, n],
            "rows_timed": len(rows), "rows_call_s": t_rows,
            "norm_err": err, "bits": ref.bits(np.asarray(c_rows), r)}
    projected = t_rows * m / len(rows)
    if projected <= BASELINE_MAX_S:
        _, t_full = timed(a)
        line.update(call_s=t_full,
                    tflops=work.gemm_flops(m, k, n) / t_full / 1e12)
    else:
        line.update(call_s=None, projected_call_s=projected,
                    note=f"a whole call projects to over {BASELINE_MAX_S} s;"
                         " timed on the sampled rows of A only")
    return json.dumps(line)


# ---------------------------------------------------------------------------
# Readings of the check at another precision or size (not the benchmark's
# runs).
# ---------------------------------------------------------------------------

def readings(config: dict, traffic: dict, seed: int, precision: str,
             size: int | None = None) -> dict:
    """The check's numbers for one seed: ``precision`` in place of the
    configuration's, at the cell's shape or a square ``size``."""
    import jax
    import jax.numpy as jnp
    m, k, n = ((size,) * 3 if size else
               (int(traffic["m"]), int(traffic["k"]), int(traffic["n"])))
    a_host, b_host = ref.operands(common.rng_of(seed, OPERAND_STREAM),
                                  m, k, n)
    with jax.enable_x64(True):
        a = jnp.asarray(a_host, config["dtype"])
        b = jnp.asarray(b_host, config["dtype"])
        fn = program(precision)
        path = route(fn, a, b)
        c = jax.jit(fn)(a, b).block_until_ready()
        rows = gemm_ref.sample_rows(common.rng_of(seed, ROWS_STREAM), m,
                                    int(traffic["check"]["rows"]))
        c_rows = np.asarray(c[jnp.asarray(rows)])
    a_rows = a_host[rows]
    r = ref.rows_product(a_rows, b_host)
    err = gemm_ref.normalized_error(c_rows, r, a_rows, b_host)
    limit = float(traffic["check"]["norm_err"])
    return {"precision": precision, "shape": [m, k, n], "norm_err": err,
            "bits": ref.bits(c_rows, r), "limit": limit,
            "correct": harness.Check(err, limit).ok, **path,
            "platform": jax.devices()[0].platform}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Readings of a DGEMM cell's "
                                 "check at another precision or size.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default=None,
                    help="default: the configuration's control")
    ap.add_argument("--size", type=int, default=None,
                    help="square M = K = N in place of the cell's shape")
    args = ap.parse_args(argv)
    from chipbench.compile_cache import place_compile_cache
    from repro.telemetry import trace as rtrace
    place_compile_cache()
    rtrace.keep_op_names_in_compile_cache()
    _, _, config, traffic = harness.find_cell(harness.ROOT, args.workload)
    precision = args.precision or config["control"]["precision"]
    for seed in args.seeds:
        got = readings(config, traffic, seed, precision, args.size)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
