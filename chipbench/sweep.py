"""Find the knee of an open-loop serving cell: the highest rate served
without a growing backlog.  Run once, when the cell is defined; the
cell's traffic file then holds a fixed rate below it.

  python3 chipbench/sweep.py --workload <cell> --rates 0.3 0.4 0.5 \
      --seconds 51 --seed 1

One process; for each rate a fresh engine serves the cell's traffic at
that rate for one window, with no check against the reference, and one
JSON line gives the requests due and finished and the backlog (due and
not finished) at the window's middle and end.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

PROCESS_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None, devices_fn=None, root=None) -> int:
    from chipbench import harness, work
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    root = root or harness.ROOT
    _, cell, config, traffic = harness.find_cell(root, args.workload)
    devices = (devices_fn or harness.tpu_devices)(int(cell["chips"]))
    from chipbench.compile_cache import place_compile_cache
    place_compile_cache()
    driver = harness.load_module(
        root / "chipbench" / "drivers" / f"{traffic['driver']}.py",
        f"chipbench_driver_{traffic['driver']}")
    for rate in args.rates:
        ctx = harness.Context(
            root=root, name=args.workload, cell=cell, config=config,
            traffic=dict(traffic, rate_per_s=rate), seed=args.seed,
            seconds=args.seconds, trace=False, devices=devices,
            peaks=work.peaks(devices[0].device_kind),
            process_start=time.perf_counter(), check=False)
        out = driver.run(ctx)
        print(json.dumps({"workload": args.workload, "rate_per_s": rate,
                          **out.metrics}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
