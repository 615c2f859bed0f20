"""One GEMM call after another, each ending in ``block_until_ready``.

The configuration file gives the precision and the operands'
conditioning; the traffic file gives the shape.  The operands are made
on the device from the seed.  The window runs whole calls: it closes at
the end of the first call that ends after ``--seconds``, and the rate is
the f32-grade work (2MNK per call) of every call over that time."""

from __future__ import annotations

import time

import numpy as np

from chipbench import common, harness, work
from chipbench.reference import gemm as ref

DIMS = (((1,), (0,)), ((), ()))


def run(ctx):
    import jax
    import jax.numpy as jnp
    import repro
    cfg, tr = ctx.config, ctx.traffic
    m, k, n = int(tr["m"]), int(tr["k"]), int(tr["n"])
    spec = cfg["precision"]
    a, b = jax.jit(lambda key: ref.operands(key, m, k, n, cfg["phi"]))(
        common.key_of(ctx.seed, 1))
    fn = jax.jit(lambda x, y: repro.dot_general(x, y, DIMS, precision=spec))
    for _ in range(2):
        c = fn(a, b).block_until_ready()
    spans = common.Spans(False)
    site = work.Site("gemm", spec, m, k, n)
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
            c = fn(a, b).block_until_ready()
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
    lines, texts = [], []
    if ctx.trace:
        texts.append(fn.lower(a, b).compile().as_text())
        lines.append(baseline_line(a, b, m, k, n))
    rows = ref.sample_rows(common.rng_of(ctx.seed, 5), m,
                           int(tr["check"]["rows"]))
    a_rows = np.asarray(a[jnp.asarray(rows)])
    c_rows = np.asarray(c[jnp.asarray(rows)])
    b_host = np.asarray(b)
    del a, b, c
    err = ref.normalized_error(c_rows, ref.rows_product(a_rows, b_host),
                               a_rows, b_host)
    ctx.log(f"calls={calls} elapsed={elapsed:.4f}s traced_calls={traced}")
    return harness.Outcome(
        attempted=calls, failed=0,
        metrics={"gemm_tflops": calls * work.gemm_flops(m, k, n)
                 / elapsed / 1e12},
        checks={"norm_err": harness.Check(err, float(tr["check"]["norm_err"]))},
        memory_peak_bytes=mem,
        work={"calls": traced, "emugemm_least_s": traced
              * site.least_s(ctx.peaks), "int8_ops": traced * site.int8_ops},
        hlo_texts=texts, lines=lines, readings={"norm_err": err},
        spans={"call": traced} if ctx.trace else {})


def baseline_line(a, b, m, k, n) -> str:
    """XLA's f32 HIGHEST dot at the same shape: what the emulation
    replaces.  Printed, not a metric."""
    import json
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x, y: jnp.dot(x, y,
                                     precision=jax.lax.Precision.HIGHEST))
    f(a, b).block_until_ready()
    reps, t0 = 0, time.perf_counter()
    while reps < 3 or time.perf_counter() - t0 < 1.0:
        f(a, b).block_until_ready()
        reps += 1
    dt = (time.perf_counter() - t0) / reps
    return json.dumps({"baseline": "xla_f32_highest", "shape": [m, k, n],
                       "call_s": dt,
                       "tflops": work.gemm_flops(m, k, n) / dt / 1e12})
