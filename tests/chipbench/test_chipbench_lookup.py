"""Cells, configurations and metrics are found by name: a new one is new
files and new BENCHMARK.json entries, with no edit to a file that is
there.  Off a TPU the entry point exits nonzero and prints no result."""

import json
import os
import subprocess
import sys

import chipbench_testlib as lib

NEW_METRIC = '''"""Calls inside the traced window (a test's metric)."""


def read(r):
    return r.work.get("calls") or None
'''


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "chipbench").rglob("*") if p.is_file()}


def test_a_new_config_traffic_and_metric_need_new_files_only(
        tmp_path, monkeypatch, capsys):
    root = lib.bench_copy(tmp_path)
    before = _snapshot(root)
    lib.add_config(root, lib.TINY_GEMM)
    cell = lib.add_cell(root, "tiny-gemm", "sq", end_to_end=["gemm_tflops"],
                        per_layer=["mfu.gemm"])
    lib.add_metric(root, "traced_calls.gemm", NEW_METRIC, "gemm_tflops",
                   [cell])
    after = _snapshot(root)
    assert all(after[p] == b for p, b in before.items())   # nothing edited

    rc, res, _ = lib.run(root, lib.argv(cell, seconds=1.0), monkeypatch,
                         capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"gemm_tflops", "setup_s"}
    assert res["metrics"]["gemm_tflops"]["unit"] == "TFLOP/s"
    assert res["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 1}
    assert list(res)[-1] == "checks"

    rc, res, _ = lib.run(root, lib.argv(cell, seconds=1.5, trace=1),
                         monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["traced_calls.gemm"]["value"] >= 1
    assert "mfu.gemm" in res["metrics"]
    # no device plane on the CPU: the device readers find nothing
    assert "device_idle.gemm" not in res["metrics"]
    assert res["device"]["window_s"] > 0


def test_every_cell_of_the_benchmark_has_its_files():
    spec = json.loads((lib.ROOT / "BENCHMARK.json").read_text())
    for cell in spec["workloads"]:
        traffic = json.loads((lib.ROOT / "chipbench" / "traffic"
                              / f"{cell['name']}.json").read_text())
        assert traffic["config"] == cell["config"]
        assert (lib.ROOT / "chipbench" / "drivers"
                / f"{traffic['driver']}.py").is_file()
    for m in spec["per_layer"]:
        assert (lib.ROOT / "chipbench" / "metrics"
                / f"{m['name']}.py").is_file()
    for c in spec["configs"]:
        assert json.loads((lib.ROOT / c["file"]).read_text())["name"] \
            == c["name"]


def test_off_a_tpu_run_py_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "sgemm-ozaki1-p4.square-16k", "--seed", "1", "--seconds", "1"],
        cwd=lib.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr
