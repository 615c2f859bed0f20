"""The serving engine's spans and KV scopes read from a trace
(``chipbench/engine_trace.py``): on hand-made pieces, and on a small
serving trace recorded on one TPU v5e (``record_serve_trace.py``: the
tiny olmo-1b-emu, one mixed step and one decode step inside a ``window``
span), kept gzipped beside this file."""

import gzip
import pathlib
import shutil

import pytest

import chipbench_testlib as lib
from chipbench import engine_trace as et, harness, trace_reduce as tr, work

DATA = pathlib.Path(__file__).resolve().parent / "data"
SPLIT = "\n// ---- next step program ----\n"
NEW_METRICS = ("kv_copy_ms.decode", "kv_copy_ms.prefill",
               "host_gap_ms.decode", "host_gap_ms.prefill")


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("serve") / "serve_tiny.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "serve_tiny.xplane.pb.gz").read_bytes()))
    texts = gzip.decompress(
        (DATA / "serve_tiny_hlo.txt.gz").read_bytes()).decode().split(SPLIT)
    return path, texts


def traced_metrics(trace_dir, texts, cell):
    """What the harness's traced run reads for ``cell`` from the trace in
    ``trace_dir``, through the benchmark's own metric files."""
    ctx = harness.Context(root=lib.ROOT, name=cell, cell={}, config={},
                          traffic={}, seed=0, seconds=1.0, trace=True,
                          devices=lib.fake_devices(1),
                          peaks=work.peaks("TPU v5 lite"), process_start=0.0,
                          trace_dir=pathlib.Path(trace_dir))
    out = harness.Outcome(attempted=0, failed=0, metrics={}, checks={},
                          memory_peak_bytes=0, hlo_texts=texts)
    spec = harness.load_json(lib.ROOT / "BENCHMARK.json")
    metrics, _ = harness.traced_metrics(ctx, spec, out)
    return metrics


def test_the_recorded_steps_carry_the_kv_scopes(serve_trace):
    _, texts = serve_trace
    assert len(texts) == 2
    for text in texts:
        assert et.kv_heads([text])
        assert tr.names_an_emulated_site(text)


def test_the_engine_spans_of_the_recorded_trace(serve_trace):
    path, texts = serve_trace
    red = et.reduce(path, texts)
    harness_red = tr.reduce(path, texts)
    count = red.span_count
    assert count["serve.step"] == count["serve.dispatch"] == 2
    assert count["serve.admit"] == count["serve.retire"] == 2
    assert harness_red.span_count == {}  # no harness span was recorded
    assert red.window_s == pytest.approx(harness_red.window_s)
    assert red.busy_s == pytest.approx(harness_red.busy_s)
    assert 0 < red.span_s["serve.step"] <= red.window_s
    assert 0 < red.busy_s < red.window_s
    # every idle stretch of chip 0 is placed once
    assert sum(red.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert 0 < red.host_gap_s <= red.window_s - red.busy_s
    assert red.host_gap_s == pytest.approx(sum(
        v for k, v in red.idle_by_span.items()
        if k.startswith("serve.")), rel=1e-6)
    assert 0 < red.kv_copy_s < red.busy_s


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_metric_files_read_the_recorded_trace_in_a_traced_run(
        serve_trace, kind):
    path, texts = serve_trace
    metrics = traced_metrics(path.parent, texts, f"olmo-1b-emu.{kind}-heavy")
    red = et.reduce(path, texts)
    steps = red.span_count["serve.dispatch"]
    assert metrics[f"kv_copy_ms.{kind}"] == {
        "value": pytest.approx(1e3 * red.kv_copy_s / steps), "unit": "ms"}
    assert metrics[f"host_gap_ms.{kind}"] == {
        "value": pytest.approx(1e3 * red.host_gap_s / steps), "unit": "ms"}
    other = "prefill" if kind == "decode" else "decode"
    assert f"kv_copy_ms.{other}" not in metrics


def test_the_new_metrics_read_the_cells_they_list():
    spec = harness.load_json(lib.ROOT / "BENCHMARK.json")
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "device_trace", "serving engine")
        kind = name.split(".")[1]
        assert m["workloads"] == [f"olmo-1b-emu.{kind}-heavy"]


def test_a_program_without_engine_spans_reads_nothing(tmp_path):
    """The GEMM trace holds no serve.* span and no KV scope, as a
    program that does not trace itself: the readers return None, and a
    traced run leaves the metrics out of its line."""
    hlo = (DATA / "sgemm_2048_hlo.txt").read_text()
    shutil.copy(DATA / "sgemm_2048.xplane.pb", tmp_path)
    red = et.reduce(tmp_path / "sgemm_2048.xplane.pb", [hlo])
    assert red.span_count == {} and red.kv_copy_s == 0
    assert et.kv_copy_ms(red) is None
    assert et.host_gap_ms(red) is None
    assert et.kv_copy_ms(et.EngineReduction()) is None
    assert et.host_gap_ms(et.EngineReduction()) is None
    metrics = traced_metrics(tmp_path, [hlo], "olmo-1b-emu.decode-heavy")
    assert not set(NEW_METRICS) & set(metrics)
    assert "device_idle.decode" in metrics


def test_outside_a_traced_run_the_metric_files_read_nothing():
    for name in NEW_METRICS:
        mod = harness.load_module(lib.ROOT / "chipbench" / "metrics"
                                  / f"{name}.py", f"test_metric_{name}")
        assert mod.read(None) is None, name


def test_kv_heads_read_the_scopes_from_the_compiled_text():
    hlo = ('  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name='
           '"jit(step)/serve/kv_gather/take"}\n'
           '  %dynamic-update-slice.2 = f32[8]{0} dynamic-update-slice(%q),'
           ' metadata={op_name="jit(step)/serve/kv_scatter/scatter"}\n'
           '  %fusion.3 = f32[8]{0} fusion(%r), metadata={op_name='
           '"jit(step)/emugemm/ozaki1-p4/xla/xla/dot"}\n'
           '  %copy.4 = f32[8]{0} copy(%s)\n')
    heads = et.kv_heads([hlo])
    assert heads == {"%fusion.1 = f32[8]{0} fusion",
                     "%dynamic-update-slice.2 = f32[8]{0} "
                     "dynamic-update-slice"}


def test_idle_is_split_by_the_innermost_engine_span():
    spans = [("serve.step", 0, 100), ("serve.sync", 10, 60),
             ("serve.commit", 70, 80), ("repro.gc", 120, 130)]
    pieces = et.segments(spans, -10, 140)
    assert pieces == [(-10, 0, "none"), (0, 10, "serve.step"),
                      (10, 60, "serve.sync"), (60, 70, "serve.step"),
                      (70, 80, "serve.commit"), (80, 100, "serve.step"),
                      (100, 120, "none"), (120, 130, "repro.gc"),
                      (130, 140, "none")]
    gaps = [(5, 15), (55, 75), (110, 125)]
    got = et.overlap_by_label(gaps, pieces)
    assert got == pytest.approx({"serve.step": 15e-9, "serve.sync": 10e-9,
                                 "serve.commit": 5e-9, "none": 10e-9,
                                 "repro.gc": 5e-9})
