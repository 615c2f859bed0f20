"""The lower-precision control of each cell's check, and its readings.

  python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \
      [--seconds 51] [--gemm-sites SPEC]

For each seed it prints one JSON line with the numbers the cell's check
reads and whether the check passes them.

- GEMM cells (f32 at HIGHEST stated): the product in three bf16 passes
  (``a_hi b_hi + a_hi b_lo + a_lo b_hi``, what XLA's ``Precision.HIGH``
  runs on a TPU), written out so that it computes the same on any
  backend, read beside the program's own output.
- Serving cells (bfloat16 stated): the program itself, with the path the
  configuration file names under ``control`` switched on at every GEMM
  site (``--gemm-sites`` names another), driven by the cell's own driver
  and judged by its own check.  The program's readings are those of the
  benchmark's runs.

The benchmark's own runs never run this.  A check's limit lies between
the program's largest reading and the control's smallest.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def bf16x3(a, b):
    """f32 product in three bf16 passes, f32 sums."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    a_hi = a.astype(bf)
    b_hi = b.astype(bf)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def gemm_readings(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from chipbench import common, harness
    from chipbench.drivers.gemm_loop import DIMS
    from chipbench.reference import gemm as ref
    cfg, tr = ctx.config, ctx.traffic
    m, k, n = int(tr["m"]), int(tr["k"]), int(tr["n"])
    a, b = jax.jit(lambda key: ref.operands(key, m, k, n, cfg["phi"]))(
        common.key_of(ctx.seed, 1))
    prog = jax.jit(lambda x, y: repro.dot_general(
        x, y, DIMS, precision=cfg["precision"]))(a, b)
    ctrl = jax.jit(bf16x3)(a, b)
    rows = ref.sample_rows(common.rng_of(ctx.seed, 5), m,
                           int(tr["check"]["rows"]))
    idx = jnp.asarray(rows)
    a_rows, b_host = np.asarray(a[idx]), np.asarray(b)
    r = ref.rows_product(a_rows, b_host)
    got = {name: ref.normalized_error(np.asarray(c[idx]), r, a_rows, b_host)
           for name, c in (("program", prog), ("control", ctrl))}
    limit = float(tr["check"]["norm_err"])
    return {"norm_err": got, "limit": limit,
            "correct": {k: harness.Check(v, limit).ok
                        for k, v in got.items()}}


def serve_readings(ctx, driver) -> dict:
    """One run of the cell with ``ctx.config``'s sites, and its check."""
    out = driver.run(ctx)
    return {"gemm_sites": ctx.config["gemm_sites"],
            "readings": out.readings,
            "checks": {k: {"value": c.value, "limit": c.limit}
                       for k, c in out.checks.items()},
            "correct": all(c.ok for c in out.checks.values())}


def main(argv=None, devices_fn=None, root=None) -> int:
    from chipbench import harness, work
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--gemm-sites", default=None,
                    help="serving cells: the spec put at every GEMM site "
                    "(default: the configuration's control)")
    args = ap.parse_args(argv)
    root = root or harness.ROOT
    spec, cell, config, traffic = harness.find_cell(root, args.workload)
    devices = (devices_fn or harness.tpu_devices)(int(cell["chips"]))
    from chipbench.compile_cache import place_compile_cache
    place_compile_cache()
    driver = harness.load_module(
        root / "chipbench" / "drivers" / f"{traffic['driver']}.py",
        f"chipbench_driver_{traffic['driver']}")
    if traffic["driver"] != "gemm_loop":
        sites = args.gemm_sites or config["control"]["gemm_sites"]
        config = dict(config, gemm_sites={k: sites
                                          for k in config["gemm_sites"]})
    for seed in args.seeds:
        ctx = harness.Context(
            root=root, name=args.workload, cell=cell, config=config,
            traffic=traffic, seed=seed, seconds=args.seconds, trace=False,
            devices=devices, peaks=work.peaks(devices[0].device_kind),
            process_start=time.perf_counter())
        if traffic["driver"] == "gemm_loop":
            got = gemm_readings(ctx)
        else:
            got = serve_readings(ctx, driver)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
