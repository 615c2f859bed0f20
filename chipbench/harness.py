"""The harness: find a cell's files by name, run its driver on the chip,
reduce the trace, and print the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
The harness reads ``traffic/<cell>.json`` (which names the driver kind),
the configuration's file, and runs ``drivers/<kind>.py``.  With
``--trace 1`` it reads each per-layer metric the cell reports from
``metrics/<metric>.py``.  Nothing here knows a cell, a configuration or
a metric by name.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

from chipbench import work

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared, and the limit it must not pass."""
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""
    attempted: int
    failed: int
    metrics: dict                 # end-to-end metric name -> value
    checks: dict                  # name -> Check
    memory_peak_bytes: int
    work: dict = dataclasses.field(default_factory=dict)  # traced window
    hlo_texts: list = dataclasses.field(default_factory=list)
    lines: list = dataclasses.field(default_factory=list)
    # every number the check read, compared or not
    readings: dict = dataclasses.field(default_factory=dict)
    # harness span -> how many the traced window must hold
    spans: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    root: pathlib.Path
    name: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: work.Peaks
    process_start: float
    trace_dir: pathlib.Path | None = None
    window_start: float | None = None
    check: bool = True            # False: no comparison after the window

    def start_window(self) -> float:
        """Mark the end of set-up; returns the window's start time."""
        self.window_start = time.perf_counter()
        self.log("the window opens")
        return self.window_start

    def log(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Lookup by name.
# ---------------------------------------------------------------------------

def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not pathlib.Path(path).is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: pathlib.Path, name: str):
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{name}.json")
    if traffic.get("config") != cell["config"]:
        raise ValueError(f"traffic/{name}.json is for config "
                         f"{traffic.get('config')!r}, the cell for "
                         f"{cell['config']!r}")
    return spec, cell, config, traffic


# ---------------------------------------------------------------------------
# The chip.
# ---------------------------------------------------------------------------

def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, or NoChip."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:           # no backend could start
        raise NoChip(str(e)) from e
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chips; found {len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, *, root: pathlib.Path = ROOT, process_start: float,
             devices_fn=tpu_devices) -> dict:
    """Run one cell and return the result object (printed by main)."""
    spec, cell, config, traffic = find_cell(root, args.workload)
    devices = devices_fn(int(cell["chips"]))
    from chipbench.compile_cache import place_compile_cache
    place_compile_cache()
    ctx = Context(root=root, name=args.workload, cell=cell, config=config,
                  traffic=traffic, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices,
                  peaks=work.peaks(devices[0].device_kind),
                  process_start=process_start)
    driver = load_module(root / "chipbench" / "drivers"
                         / f"{traffic['driver']}.py",
                         f"chipbench_driver_{traffic['driver']}")
    if ctx.trace:
        ctx.trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="chipbench-"))
    try:
        out: Outcome = driver.run(ctx)
        if ctx.window_start is None:
            raise RuntimeError("the driver never started its window")
        ctx.log(f"setup {ctx.window_start - ctx.process_start:.2f} s, "
                f"whole run {time.perf_counter() - ctx.process_start:.2f} s")
        device = dict(device_info(devices),
                      memory_peak_bytes=int(out.memory_peak_bytes))
        result = {"correct": all(c.ok for c in out.checks.values()),
                  "attempted": int(out.attempted), "failed": int(out.failed)}
        if ctx.trace:
            metrics, red = traced_metrics(ctx, spec, out)
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            breakdown = red.breakdown()
        else:
            metrics = end_to_end_metrics(ctx, spec, out)
            breakdown = None
    finally:
        if ctx.trace_dir is not None:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c.value, "limit": c.limit}
                        for k, c in out.checks.items()}
    result["_lines"] = out.lines
    return result


def end_to_end_metrics(ctx: Context, spec: dict, out: Outcome) -> dict:
    values = dict(out.metrics,
                  setup_s=ctx.window_start - ctx.process_start)
    metrics = {}
    for m in spec["end_to_end"]:
        if not applies(m, ctx.name):
            continue
        if m["name"] not in values:
            raise RuntimeError(f"driver {ctx.traffic['driver']!r} gave no "
                               f"{m['name']!r} for {ctx.name}")
        metrics[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return metrics


def traced_metrics(ctx: Context, spec: dict, out: Outcome):
    from chipbench import trace_reduce
    red = trace_reduce.reduce_dir(ctx.trace_dir, out.hlo_texts,
                                  chips=len(ctx.devices))
    for span, n in out.spans.items():
        if red.span_count.get(span, 0) != n:
            raise RuntimeError(
                f"the traced window holds {red.span_count.get(span, 0)} "
                f"{span!r} spans where the driver ran {n}: the spans no "
                "longer wrap what the per-layer metrics read")
    red.work = dict(out.work)
    red.peaks = ctx.peaks
    metrics = {}
    for m in spec["per_layer"]:
        if not applies(m, ctx.name):
            continue
        mod = load_module(ctx.root / "chipbench" / "metrics"
                          / f"{m['name']}.py",
                          f"chipbench_metric_{m['name']}")
        value = mod.read(red)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, red


def main(argv=None, *, process_start: float | None = None,
         root: pathlib.Path = ROOT, devices_fn=tpu_devices) -> int:
    if process_start is None:
        process_start = time.perf_counter()
    args = parse_args(argv)
    try:
        result = run_cell(args, root=root, process_start=process_start,
                          devices_fn=devices_fn)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for line in result.pop("_lines"):
        print(line, flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
