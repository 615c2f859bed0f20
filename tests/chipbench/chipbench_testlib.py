"""What the benchmark's tests share: a copy of the benchmark in a
temporary directory, tiny cells added to it as files, and a device shim
that makes the CPU pass for a TPU."""

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

TINY_GEMM = {"name": "tiny-gemm", "precision": "ozaki1-p4",
             "dtype": "float32", "phi": 4.0, "reduced": []}
TINY_OLMO = {"name": "tiny-olmo", "arch": "olmo-1b-emu", "n_layers": 2,
             "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
             "vocab": 500, "rope_theta": 10000.0, "layer_norm_eps": 1e-6,
             "dtype": "bfloat16",
             "gemm_sites": {"default": "ozaki1-p4+cached",
                            "attn_qk": "ozaki2-m6", "attn_av": "ozaki1-p4"},
             "engine": {"lanes": 2, "chunk": 16, "page_size": 8,
                        "max_seq": 128},
             "control": {"gemm_sites": "ozaki1-p1"},
             "reduced": ["n_layers", "d_model"]}
# The tiny serving limit, 0.001, lies between the readings of the tiny
# model on a CPU: 0 to 0.00057 for the program, 0.0018 to 0.0084 for its
# one-slice int8 control (seeds 4-7 and 21).
TINY_TRAFFIC = {
    "sq": {"driver": "gemm_loop", "m": 256, "k": 256, "n": 256,
           "trace_seconds": 0.5, "check": {"rows": 8, "norm_err": 1e-8}},
    "open": {"driver": "serve_open_loop", "rate_per_s": 3.0,
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 100},
             "output_tokens": {"dist": "uniform", "min": 3, "max": 8},
             "trace_seconds": 1,
             "check": {"requests": 4, "mean_logit_gap": 0.001}},
    "closed": {"driver": "serve_closed_loop", "clients": 4,
               "requests_per_client": 30,
               "prompt_tokens": {"dist": "uniform", "min": 8, "max": 20},
               "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
               "trace_seconds": 1,
               "check": {"requests": 3, "mean_logit_gap": 0.001}},
}


class FakeTpu:
    """A CPU device that reports itself as a TPU v5e."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def fake_devices(n):
    return [FakeTpu()] * n


def bench_copy(tmp_path) -> pathlib.Path:
    """BENCHMARK.json and chipbench/ copied, nothing else."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def add_config(root, config: dict) -> None:
    """A configuration: its file, and its entry in BENCHMARK.json."""
    path = f"chipbench/configs/{config['name']}.json"
    (root / path).write_text(json.dumps(config))
    _edit(root, lambda s: s["configs"].append(
        {"name": config["name"], "source": "test", "file": path,
         "reduced": config["reduced"], "why": "a test's"}))


def add_cell(root, config: str, traffic: str, end_to_end=(),
             per_layer=()) -> str:
    """A cell: its traffic file, its entry, and the metrics it reports."""
    name = f"{config}.{traffic}"
    (root / "chipbench" / "traffic" / f"{name}.json").write_text(
        json.dumps(dict(TINY_TRAFFIC[traffic], config=config)))

    def edit(s):
        s["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "a test's"})
        for m in s["end_to_end"] + s["per_layer"]:
            if m["name"] in tuple(end_to_end) + tuple(per_layer):
                m["workloads"].append(name)
    _edit(root, edit)
    return name


def add_metric(root, name: str, source: str, moves: str, cells) -> None:
    (root / "chipbench" / "metrics" / f"{name}.py").write_text(source)
    _edit(root, lambda s: s["per_layer"].append(
        {"name": name, "unit": "count", "better": "higher",
         "source": "device_trace", "layer": "test", "moves": moves,
         "workloads": list(cells)}))


def _edit(root, fn) -> None:
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    fn(spec)
    path.write_text(json.dumps(spec))


def run(root, argv, monkeypatch, capsys):
    """harness.main in this process on the fake TPU; returns the exit
    code, the result object (or None) and the standard output."""
    from chipbench import compile_cache, harness
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "off")
    rc = harness.main(argv, root=root, devices_fn=fake_devices)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1]) if lines and rc == 0 else None
    return rc, result, out


def argv(cell, seed=11, seconds=1.0, trace=0):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
