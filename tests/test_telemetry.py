"""repro.telemetry: registry semantics, the strict disabled-mode no-op
contract, instrumented dispatch on all three backends, JSONL round-trips
through the report aggregator, Prometheus text validity, thread safety,
and the guard/fallback shims that now ride on the one registry."""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import guard, telemetry
from repro.core.precision import EmulationConfig
from repro.kernels import dispatch, prepared
from repro.telemetry import record as tele_rec
from repro.telemetry import report as tele_report
from repro.telemetry.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _restore_enabled_state():
    """Every test leaves the process-wide enabled flag as it found it."""
    was = telemetry.enabled()
    yield
    (telemetry.enable if was else telemetry.disable)()


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# MetricsRegistry semantics.
# ---------------------------------------------------------------------------

def test_registry_counter_label_aggregation():
    reg = MetricsRegistry()
    reg.inc("calls", 1, {"site": "attn", "backend": "tpu"})
    reg.inc("calls", 2, {"backend": "tpu", "site": "attn"})  # order-free
    reg.inc("calls", 4, {"site": "ffn", "backend": "tpu"})
    assert reg.total("calls") == 7
    assert reg.total("calls", site="attn") == 3
    assert reg.total("calls", site="ffn", backend="tpu") == 4
    assert reg.total("calls", site="logits") == 0
    rows = list(reg.series("calls", site="attn"))
    assert rows == [({"site": "attn", "backend": "tpu"}, 3.0)]


def test_registry_labels_stringified():
    reg = MetricsRegistry()
    reg.inc("c", 1, {"p": 4})
    reg.inc("c", 1, {"p": "4"})
    assert reg.total("c", p=4) == 2
    assert reg.total("c", p="4") == 2


def test_registry_gauge_and_histogram():
    reg = MetricsRegistry()
    reg.set_gauge("g", 1.5, {"kind": "train"})
    reg.set_gauge("g", 2.5, {"kind": "train"})  # gauges overwrite
    for v in (0.1, 0.3, 0.2):
        reg.observe("h", v)
    snap = reg.snapshot()
    assert snap["gauges"] == [
        {"name": "g", "labels": {"kind": "train"}, "value": 2.5}]
    (h,) = snap["histograms"]
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(0.6)
    assert h["min"] == pytest.approx(0.1)
    assert h["max"] == pytest.approx(0.3)


def test_registry_clear_by_prefix():
    reg = MetricsRegistry()
    reg.inc("repro_guard_events_total", 1, {"event": "calls"})
    reg.inc("repro_emulated_calls_total", 1)
    reg.clear("repro_guard")
    assert reg.total("repro_guard_events_total") == 0
    assert reg.total("repro_emulated_calls_total") == 1
    reg.clear()
    assert reg.total("repro_emulated_calls_total") == 0


def test_registry_once_and_forget():
    reg = MetricsRegistry()
    assert reg.once(("fallback", "gpu", "256x256x256"))
    assert not reg.once(("fallback", "gpu", "256x256x256"))
    assert reg.once(("other", "x"))
    reg.forget_once("fallback")
    assert reg.once(("fallback", "gpu", "256x256x256"))
    assert not reg.once(("other", "x"))  # untouched by the prefix forget


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def worker(i):
        for _ in range(500):
            reg.inc("c", 1, {"w": i % 2})
            reg.observe("h", 1.0)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.total("c") == 8 * 500
    assert reg.snapshot()["histograms"][0]["count"] == 8 * 500


# ---------------------------------------------------------------------------
# Label helpers.
# ---------------------------------------------------------------------------

def test_gemm_tag_units():
    assert telemetry.gemm_tag("ozaki1", 4, "tpu", "pallas") \
        == "emugemm/ozaki1-p4/tpu/pallas"
    assert telemetry.gemm_tag("ozaki2", 6, "gpu", "prepared-pallas") \
        == "emugemm/ozaki2-m6/gpu/prepared-pallas"
    assert telemetry.gemm_tag("ozaki2-3m", 8, "xla", "xla") \
        == "emugemm/ozaki2-3m-m8/xla/xla"


def test_call_site_stack():
    assert telemetry.current_site() == "-"
    with telemetry.call_site("attn"):
        assert telemetry.current_site() == "attn"
        with telemetry.call_site("ffn"):
            assert telemetry.current_site() == "ffn"
        assert telemetry.current_site() == "attn"
    assert telemetry.current_site() == "-"


def test_mesh_label():
    assert telemetry.mesh_label(None) == "-"
    assert telemetry.mesh_label((("data", 2), ("model", 4))) \
        == "data=2,model=4"
    assert telemetry.mesh_label({"model": 8}) == "model=8"


def test_modeled_gemm_bytes_matches_traffic():
    from repro.core import traffic
    s = traffic.GemmShape(128, 64, 256)  # (m, n, k)
    assert telemetry.modeled_gemm_bytes("ozaki1", 4, 128, 256, 64) \
        == traffic.scheme1_fused_bytes(s, 4, 4)
    per_mod = traffic.scheme2_fused_bytes_per_modulus(s)
    assert telemetry.modeled_gemm_bytes("ozaki2", 6, 128, 256, 64) \
        == 6 * per_mod + 4 * 128 * 64


# ---------------------------------------------------------------------------
# Disabled mode: strict no-op.
# ---------------------------------------------------------------------------

def test_disabled_mode_stages_no_callbacks():
    telemetry.disable()
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    a, b = _rand((128, 128), 1), _rand((128, 128), 2)
    jaxpr = str(jax.make_jaxpr(
        lambda a, b: dispatch.emulated_matmul(a, b, cfg=cfg))(a, b))
    assert "debug_callback" not in jaxpr


def test_disabled_mode_records_nothing():
    telemetry.disable()
    before = telemetry.REGISTRY.counter_snapshot()
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    dispatch.emulated_matmul(_rand((128, 128), 1), _rand((128, 128), 2),
                             cfg=cfg)
    after = telemetry.REGISTRY.counter_snapshot()
    changed = {k for k in set(before) | set(after)
               if before.get(k) != after.get(k)
               # guard counters are always-on by design
               and not k[0].startswith("repro_guard")}
    assert not changed, changed


def test_enabled_vs_disabled_bit_identical():
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    a, b = _rand((128, 128), 1), _rand((128, 128), 2)
    telemetry.disable()
    off = dispatch.emulated_matmul(a, b, cfg=cfg)
    telemetry.enable()
    on = dispatch.emulated_matmul(a, b, cfg=cfg)
    assert jnp.array_equal(off, on)


# ---------------------------------------------------------------------------
# Instrumented dispatch: counters on all three backends, under jit.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["tpu", "gpu", "xla"])
def test_counters_under_jit(backend):
    telemetry.enable()
    cfg = EmulationConfig(scheme="ozaki1", p=3, backend=backend)
    a, b = _rand((128, 128), 3), _rand((128, 128), 4)

    reg = telemetry.REGISTRY
    calls0 = reg.total(tele_rec.EMULATED_CALLS, backend=backend)
    traces0 = reg.total(tele_rec.EMULATED_TRACES, backend=backend)
    bytes0 = reg.total(tele_rec.MODELED_HBM_BYTES, backend=backend)

    f = jax.jit(lambda a, b: dispatch.emulated_matmul(a, b, cfg=cfg))
    jax.block_until_ready(f(a, b))
    jax.block_until_ready(f(a, b))  # second execution, no retrace
    jax.effects_barrier()  # debug callbacks run async; flush them

    assert reg.total(tele_rec.EMULATED_TRACES, backend=backend) > traces0
    assert reg.total(tele_rec.EMULATED_CALLS, backend=backend) \
        >= calls0 + 2
    assert reg.total(tele_rec.MODELED_HBM_BYTES, backend=backend) > bytes0


def test_site_label_attached():
    telemetry.enable()
    reg = telemetry.REGISTRY
    before = reg.total(tele_rec.EMULATED_TRACES, site="attn")
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    with telemetry.call_site("attn"):
        dispatch.emulated_matmul(_rand((128, 128), 5), _rand((128, 128), 6),
                                 cfg=cfg)
    assert reg.total(tele_rec.EMULATED_TRACES, site="attn") > before


def test_site_label_survives_grad_and_remat():
    # custom-VJP rules are re-traced at partial-eval/transpose time,
    # after the call_site block has exited; the site rides along as a
    # static argument so the re-traces re-enter the scope.
    from repro.core.emulated import emulated_dot
    telemetry.enable()
    reg = telemetry.REGISTRY
    cfg = EmulationConfig(scheme="ozaki1", p=3, backend="tpu")
    a, b = _rand((128, 128), 30), _rand((128, 128), 31)

    def layer(a, b):
        with telemetry.call_site("attn"):
            return emulated_dot(a, b, cfg).sum()

    calls0 = reg.total(tele_rec.EMULATED_CALLS, site="attn")
    unsited0 = reg.total(tele_rec.EMULATED_CALLS, site="-")
    f = jax.jit(jax.grad(jax.checkpoint(layer)))
    jax.block_until_ready(f(a, b))
    jax.effects_barrier()
    # remat forward + both backward GEMMs all carry the site.
    assert reg.total(tele_rec.EMULATED_CALLS, site="attn") >= calls0 + 3
    assert reg.total(tele_rec.EMULATED_CALLS, site="-") == unsited0


def test_block_cache_counters():
    telemetry.enable()
    reg = telemetry.REGISTRY
    hits0 = reg.total(tele_rec.BLOCK_CACHE, result="hit")
    miss0 = reg.total(tele_rec.BLOCK_CACHE, result="miss")
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    a, b = _rand((160, 128), 7), _rand((128, 160), 8)
    dispatch.emulated_matmul(a, b, cfg=cfg)
    dispatch.emulated_matmul(a, b, cfg=cfg)
    hits = reg.total(tele_rec.BLOCK_CACHE, result="hit") - hits0
    miss = reg.total(tele_rec.BLOCK_CACHE, result="miss") - miss0
    assert hits + miss >= 2
    assert hits >= 1  # second call reuses the cached block choice


def test_block_wide_counter():
    """One event per new shape whose block came from the wide candidates,
    on the cache miss only; none for 256-multiple shapes, none when off."""
    wide = {"n": (128, 2176, 128, 1), "k": (128, 128, 2176, 1)}
    dispatch.block_cache_clear("tpu")
    with telemetry.recording() as reg:
        before = {d: reg.total(tele_rec.BLOCK_WIDE, backend="tpu", dim=d)
                  for d in "mnk"}
        for shape in (*wide.values(), *wide.values(), (512, 512, 512, 1)):
            dispatch.select_blocks(*shape, backend="tpu")
        fired = {d: reg.total(tele_rec.BLOCK_WIDE, backend="tpu", dim=d)
                 - before[d] for d in "mnk"}
    assert fired == {"m": 0, "n": 1, "k": 1}
    telemetry.disable()
    dispatch.block_cache_clear("tpu")
    total = telemetry.REGISTRY.total(tele_rec.BLOCK_WIDE)
    for shape in wide.values():
        dispatch.select_blocks(*shape, backend="tpu")
    assert telemetry.REGISTRY.total(tele_rec.BLOCK_WIDE) == total


def test_prepared_consume_counters():
    telemetry.enable()
    reg = telemetry.REGISTRY
    built0 = reg.total(tele_rec.PREPARED_BUILD, scheme="ozaki1")
    consumed0 = reg.total(tele_rec.PREPARED_CONSUME, scheme="ozaki1")
    cfg = EmulationConfig(scheme="ozaki1", p=3)
    b = _rand((128, 128), 11)
    prep = prepared.prepare_rhs(b, cfg)
    dispatch.emulated_matmul(_rand((128, 128), 12), prep, cfg=cfg)
    assert reg.total(tele_rec.PREPARED_BUILD, scheme="ozaki1") == built0 + 1
    assert reg.total(tele_rec.PREPARED_CONSUME, scheme="ozaki1") \
        == consumed0 + 1


def test_emugemm_scope_in_compiled_hlo():
    cfg = EmulationConfig(scheme="ozaki1", p=3, backend="tpu")
    a, b = _rand((128, 128), 13), _rand((128, 128), 14)
    txt = jax.jit(
        lambda a, b: dispatch.emulated_matmul(a, b, cfg=cfg)
    ).lower(a, b).compile().as_text()
    assert "emugemm/ozaki1-p3/tpu/pallas" in txt


# ---------------------------------------------------------------------------
# Step records: JSONL round-trip through the report aggregator.
# ---------------------------------------------------------------------------

def test_step_tracker_jsonl_roundtrip(tmp_path, capsys):
    path = tmp_path / "steps.jsonl"
    with telemetry.recording(str(path)):
        tracker = telemetry.StepTracker()
        cfg = EmulationConfig(scheme="ozaki1", p=3)
        with telemetry.call_site("ffn"):
            dispatch.emulated_matmul(_rand((128, 128), 15),
                                     _rand((128, 128), 16), cfg=cfg)
        tracker.step_metrics(0, 0.5, kind="train", tokens=1024, loss=3.25)
        dispatch.emulated_matmul(_rand((128, 128), 17),
                                 _rand((128, 128), 18), cfg=cfg)
        tracker.step_metrics(1, 0.25, kind="train", tokens=1024)

    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(records) == 2
    assert all(r["record"] == "repro.telemetry/v1" for r in records)
    assert records[0]["loss"] == 3.25
    assert records[0]["tokens_per_s"] == pytest.approx(2048.0)
    assert records[0]["emulated_calls"] >= 1
    assert records[0]["modeled_hbm_bytes"] > 0

    summary = tele_report.aggregate(records)
    assert summary["steps"] == 2
    assert summary["kinds"] == {"train": 2}
    sites = {row["site"] for row in summary["sites"]}
    assert "ffn" in sites
    ffn = [r for r in summary["sites"] if r["site"] == "ffn"][0]
    assert ffn["scheme"] == "ozaki1"
    assert ffn["calls"] >= 1
    assert ffn["hbm_bytes"] > 0

    # The CLI renders the same file without error.
    assert tele_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "ffn" in out and "steps=2" in out


def test_recording_scope_restores_state():
    telemetry.disable()
    with telemetry.recording():
        assert telemetry.enabled()
    assert not telemetry.enabled()
    telemetry.enable()
    with telemetry.recording():
        pass
    assert telemetry.enabled()


# ---------------------------------------------------------------------------
# Prometheus exposition.
# ---------------------------------------------------------------------------

def test_render_prometheus_text_format():
    reg = MetricsRegistry()
    reg.inc(tele_rec.EMULATED_CALLS, 3, {"site": "attn", "scheme": "ozaki1",
                                         "backend": "tpu"})
    reg.set_gauge(tele_rec.STEP_TOKENS_PER_S, 512.5, {"kind": "train"})
    reg.observe(tele_rec.STEP_SECONDS, 0.25, {"kind": "train"})
    text = telemetry.render_prometheus(reg)
    assert "# TYPE repro_emulated_calls_total counter" in text
    assert ('repro_emulated_calls_total{backend="tpu",scheme="ozaki1",'
            'site="attn"} 3') in text
    assert "# TYPE repro_step_tokens_per_s gauge" in text
    assert "repro_step_seconds_count" in text
    assert "repro_step_seconds_sum" in text
    # every non-comment line is `name{labels} value`
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)
        assert name_part[0].isalpha()


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.inc("c", 1, {"reason": 'say "hi"\nback\\slash'})
    text = telemetry.render_prometheus(reg)
    assert r'reason="say \"hi\"\nback\\slash"' in text


def test_metrics_server_serves_registry():
    reg = MetricsRegistry()
    reg.inc(tele_rec.EMULATED_CALLS, 7, {"backend": "xla"})
    server = telemetry.serve_metrics(0, reg)
    try:
        url = f"http://127.0.0.1:{server.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.status == 200
            assert "0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode("utf-8")
        assert 'repro_emulated_calls_total{backend="xla"} 7' in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/nope", timeout=5)
    finally:
        server.close()


# ---------------------------------------------------------------------------
# Guard + fallback shims over the registry.
# ---------------------------------------------------------------------------

def test_guard_stats_ride_on_registry():
    guard.stats_clear()
    from repro.guard import policy
    policy.record("calls")
    policy.record("trips", 2)
    assert guard.stats().calls == 1
    assert guard.stats().trips == 2
    assert telemetry.REGISTRY.total(tele_rec.GUARD_EVENTS, event="calls") \
        == 1


def test_guard_stats_clear_leaves_other_counters():
    telemetry.enable()
    telemetry.REGISTRY.inc(tele_rec.EMULATED_CALLS, 1, {"backend": "xla"})
    base = telemetry.REGISTRY.total(tele_rec.EMULATED_CALLS)
    from repro.guard import policy
    policy.record("calls")
    guard.stats_clear()
    assert guard.stats() == type(guard.stats())()  # all-zero dataclass
    assert telemetry.REGISTRY.total(tele_rec.EMULATED_CALLS) == base


def test_guard_events_carry_site_label():
    guard.stats_clear()
    from repro.guard import policy
    with telemetry.call_site("logits"):
        policy.record("trips")
    assert telemetry.REGISTRY.total(
        tele_rec.GUARD_EVENTS, event="trips", site="logits") == 1
    guard.stats_clear()


def test_fallback_warning_once_via_registry():
    import warnings
    dispatch.fallback_warnings_clear()
    reason = ("gpu", "ozaki2", "float32", "float32")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dispatch._warn_fallback_once(reason, ((128, 128), (128, 128)), "m")
        dispatch._warn_fallback_once(reason, ((128, 128), (128, 128)), "m")
        dispatch._warn_fallback_once(reason, ((256, 256), (256, 256)), "m")
    assert len(w) == 2  # deduped per (reason, shape)
    dispatch.fallback_warnings_clear()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dispatch._warn_fallback_once(reason, ((128, 128), (128, 128)), "m")
    assert len(w) == 1


def test_fallback_event_counter():
    telemetry.enable()
    reg = telemetry.REGISTRY
    before = reg.total(tele_rec.FALLBACK_EVENTS, reason="unsupported")
    # a modulus above the fused gpu kernel's <=256 cap -> xla fallback.
    cfg = EmulationConfig(scheme="ozaki2", moduli=(521, 251, 247),
                          backend="gpu")
    a, b = _rand((128, 128), 19), _rand((128, 128), 20)
    plan = dispatch.plan_emulated(a, b, cfg)
    assert plan.backend == "xla"
    assert reg.total(tele_rec.FALLBACK_EVENTS, reason="unsupported") \
        == before + 1
