"""The serving engine traces itself: ``serve.*`` host spans on the
profiler's clock, KV scopes in the compiled step, and per-execution
counts (emulated sites, compiles, GC pauses, queue wait).

Spans are read back from a profiler trace recorded on the CPU with
``jax.profiler.ProfileData``."""

import dataclasses
import gc
import pathlib
import sys
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, telemetry
from repro.launch.mesh import make_host_mesh
from repro.serving import ContinuousEngine
from repro.serving.queue import Request
from repro.telemetry import record as tele_rec
from repro.telemetry import trace as tele_trace

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

ENGINE_SPANS = {"serve.step", "serve.plan", "serve.admit", "serve.tables",
                "serve.inputs", "serve.dispatch", "serve.sync", "serve.guard",
                "serve.commit", "serve.retire", "serve.record"}
ENGINE = dict(max_seq=48, max_lanes=2, chunk=8, page_size=8, seed=0)


@pytest.fixture(autouse=True)
def _restore_enabled_state():
    was = telemetry.enabled()
    yield
    (telemetry.enable if was else telemetry.disable)()


@pytest.fixture(scope="module")
def arch():
    return configs.get_smoke_config("olmo-1b-emu")


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def _requests(arch, n=3, seed=3, arrival=0.0):
    r = np.random.default_rng(seed)
    return [Request(prompt=r.integers(1, arch.model.vocab,
                                      r.integers(4, 20)).tolist(),
                    max_new_tokens=int(r.integers(3, 6)), arrival=arrival)
            for _ in range(n)]


def _serve(eng, mesh, reqs, now=0.0):
    """Every request to the end, one step_once at a time; returns
    (step_once calls, executed steps, {rid: tokens})."""
    states = [eng.submit(Request(prompt=q.prompt,
                                 max_new_tokens=q.max_new_tokens,
                                 arrival=q.arrival)) for q in reqs]
    calls = ran = 0
    with mesh:
        while eng.sched.has_work():
            ran += eng.step_once(now) is not None
            calls += 1
    return calls, ran, {s.rid: list(s.generated) for s in states}


def _events(trace_dir):
    """(name, start, end, stats) of every engine span in the trace."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        # the stats' binding type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("serve.", "repro.")):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(arch, mesh, tmp_path_factory):
    """One engine served with no profiler, then again while a profiler
    records; a fresh engine serves the same requests with no profiler."""
    reqs = _requests(arch)
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)
    _serve(eng, mesh, _requests(arch, seed=9))          # compile both
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        calls, ran, tokens = _serve(eng, mesh, reqs)
    with mesh:
        plain = ContinuousEngine(arch, mesh, **ENGINE)
    _, _, plain_tokens = _serve(plain, mesh, reqs)
    return types.SimpleNamespace(eng=eng, calls=calls, ran=ran,
                                 tokens=tokens, plain_tokens=plain_tokens,
                                 events=_events(d), n=len(reqs))


def _inside(ev, outer):
    return any(o[1] <= ev[1] and ev[2] <= o[2] for o in outer)


def test_every_engine_span_is_written_nested_as_documented(traced):
    names = {e[0] for e in traced.events}
    assert ENGINE_SPANS <= names
    by = lambda n: [e for e in traced.events if e[0] == n]
    steps = by("serve.step")
    for e in traced.events:
        if e[0].startswith("serve.") and e[0] != "serve.step":
            assert _inside(e, steps), e[0]
    assert all(_inside(e, by("serve.plan")) for e in by("serve.admit"))
    assert all(_inside(e, by("serve.commit")) for e in by("serve.retire"))


def test_step_spans_count_the_calls_and_dispatch_spans_the_steps(traced):
    count = lambda n: sum(e[0] == n for e in traced.events)
    assert count("serve.step") == traced.calls
    assert count("serve.plan") == traced.calls
    assert count("serve.dispatch") == traced.ran
    assert count("serve.sync") == count("serve.guard") == traced.ran
    assert count("serve.commit") == count("serve.tables") == traced.ran
    assert count("serve.inputs") == traced.ran
    assert count("serve.admit") == count("serve.retire") == traced.n


def test_spans_carry_what_the_table_says(traced):
    steps = [e[3] for e in traced.events if e[0] == "serve.step"]
    assert [s["step_num"] for s in steps] == sorted(s["step_num"]
                                                    for s in steps)
    assert {s["chunk"] for s in steps} <= {0, 1, ENGINE["chunk"]}
    assert sum(s["tokens"] for s in steps) > 0
    assert all(0 <= s["lanes"] <= ENGINE["max_lanes"] for s in steps)
    admits = {e[3]["rid"]: e[3] for e in traced.events
              if e[0] == "serve.admit"}
    retires = {e[3]["rid"]: e[3] for e in traced.events
               if e[0] == "serve.retire"}
    assert set(admits) == set(retires)
    for rid, r in retires.items():
        assert r["status"] == "done"
        assert r["tokens"] == len(traced.tokens[rid])
        assert admits[rid]["wait_ms"] == 0.0


def test_no_engine_span_is_named_as_a_harness_span(traced):
    from chipbench import common
    names = {e[0] for e in traced.events}
    assert not names & (set(common.SPANS) | {common.WINDOW})


def test_tokens_are_the_same_with_the_profiler_recording(traced):
    assert traced.tokens
    assert list(traced.tokens.values()) == list(
        traced.plain_tokens.values())


def _step_args(eng, c):
    b = eng.sched.cfg.max_lanes
    return (eng.params, eng.pools, eng.kv.tables_for([None] * b),
            jnp.zeros((b, c), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32))


def test_step_holds_no_callback_with_telemetry_off(arch, mesh):
    telemetry.disable()
    jax.clear_caches()
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)
        for c, fn in eng._jit_fns.items():
            jaxpr = str(jax.make_jaxpr(fn)(*_step_args(eng, c)))
            assert "callback" not in jaxpr


def test_compiled_steps_carry_the_kv_scopes(traced):
    from chipbench import engine_trace, trace_reduce
    texts = traced.eng.step_hlo_texts()
    assert sorted(texts) == [1, ENGINE["chunk"]]
    for text in texts.values():
        assert "serve/kv_gather" in text and "serve/kv_scatter" in text
        assert trace_reduce.names_an_emulated_site(text)
        assert engine_trace.kv_heads([text])


def test_step_hlo_texts_equal_what_the_harness_builds(traced, mesh):
    from chipbench.drivers import _serve
    server = types.SimpleNamespace(eng=traced.eng, mesh=mesh,
                                   lanes=ENGINE["max_lanes"])
    texts = traced.eng.step_hlo_texts()
    assert _serve.Server.hlo_texts(server) == [texts[c]
                                               for c in sorted(texts)]


def _sites_per_step(arch, mesh, n_layers):
    arch = dataclasses.replace(arch, model=dataclasses.replace(
        arch.model, n_layers=n_layers))
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)
    _, ran, _ = _serve(eng, mesh, _requests(arch, n=2, seed=5))
    return eng, ran


def test_site_executions_count_every_layer_of_every_step(arch, mesh):
    """Sites traced once in the layer scan count once per layer: the
    table grows by the same amount per layer, and what is left over is
    the one logits projection."""
    two, ran = _sites_per_step(arch, mesh, 2)
    four, _ = _sites_per_step(arch, mesh, 4)
    t2, t4 = two._sites, four._sites
    assert sorted(t2) == [1, ENGINE["chunk"]]
    for c in t2:
        assert set(t2[c]) == set(t4[c])
        outside = {tag: 2 * t2[c][tag] - t4[c][tag] for tag in t2[c]}
        qk = [t for t in outside if t.startswith("emugemm/ozaki2-m6/")]
        assert qk and all(outside[t] == 0 for t in qk)   # attn_qk: layers
        assert sum(outside.values()) == 1                # the logits
    util = two.utilization()
    steps = {c: 0 for c in t2}
    executed = util["site_executions"]
    for c, tags in executed.items():
        tag = next(iter(tags))
        steps[c] = tags[tag] // t2[c][tag]
        assert tags == {t: steps[c] * n for t, n in t2[c].items()}
    assert sum(steps.values()) == ran


def test_counts_reach_the_registry_when_telemetry_is_on(arch, mesh):
    telemetry.enable()
    reg = telemetry.REGISTRY
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)
    _serve(eng, mesh, _requests(arch, n=2, seed=6), now=0.5)
    gc.collect()
    util = eng.utilization()
    for c, tags in util["site_executions"].items():
        for tag, n in tags.items():
            assert reg.total(tele_rec.SERVE_SITE_EXECUTIONS, chunk=c,
                             tag=tag) >= n
    for c in util["step_compiles"]:
        assert reg.total(tele_rec.SERVE_STEP_COMPILES, chunk=c) >= 1
    text = telemetry.render_prometheus()
    for name in (tele_rec.SERVE_SITE_EXECUTIONS, tele_rec.COMPILES,
                 tele_rec.GC_PAUSE_SECONDS, tele_rec.SERVE_QUEUE_WAIT_SECONDS,
                 tele_rec.SERVE_STEP_COMPILES):
        assert f"\n{name}" in text, name


def test_only_a_dispatch_that_compiles_counts_as_a_step_compile(arch, mesh):
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)
    _serve(eng, mesh, _requests(arch, n=2, seed=7))
    first = eng.utilization()
    assert first["step_compiles"] == {1: 1, ENGINE["chunk"]: 1}
    assert first["compiles"] >= 2 and first["compile_s"] > 0
    _serve(eng, mesh, _requests(arch, n=2, seed=8))
    assert eng.utilization()["step_compiles"] == first["step_compiles"]


def test_gc_pauses_are_counted_and_traced(tmp_path):
    tele_trace.install_host_hooks()
    before = tele_trace.HOST.gc_pauses
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    assert tele_trace.HOST.gc_pauses > before
    gen = [e[3]["generation"] for e in _events(tmp_path)
           if e[0] == tele_trace.GC_SPAN]
    assert 2 in gen


def test_queue_wait_runs_from_arrival_to_admission(arch, mesh):
    with mesh:
        eng = ContinuousEngine(arch, mesh, clock=lambda: 0.0, **ENGINE)
    reqs = _requests(arch, n=2, seed=10, arrival=0.25)
    _serve(eng, mesh, reqs, now=1.0)
    util = eng.utilization()
    assert util["admissions"] == 2
    assert util["queue_wait_s"] == pytest.approx(2 * 0.75)
    assert util["queue_wait_max_s"] == pytest.approx(0.75)


def test_a_tripped_step_replays_under_its_span(arch, mesh, tmp_path):
    from repro.core.precision import EmulationAccuracyError
    with mesh:
        eng = ContinuousEngine(arch, mesh, **ENGINE)

    def tripping(*a, **k):
        raise EmulationAccuracyError("synthetic trip")

    eng._jit_fns = {c: tripping for c in eng._jit_fns}
    with jax.profiler.trace(str(tmp_path)):
        _, ran, tokens = _serve(eng, mesh, _requests(arch, n=1, seed=11))
    names = [e[0] for e in _events(tmp_path)]
    assert names.count("serve.replay") == ran > 0
    assert names.count("serve.dispatch") == ran
    assert eng.utilization()["site_executions"] == {}
    assert all(tokens.values())


def test_a_cached_step_keeps_its_own_op_names(tmp_path):
    """A persistent compile cache keyed without metadata would hand a
    program the op names of an earlier program that differs only in its
    scopes; the engine keys the cache on them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    tele_trace.keep_op_names_in_compile_cache()

    def text(scope, where):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 3
        f.__code__ = f.__code__.replace(co_filename=str(where))
        return jax.jit(f).lower(jnp.ones(8)).compile().as_text()

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "c"))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        assert "scope_a" in text("scope_a", tmp_path / "one.py")
        b = text("scope_b", tmp_path / "one.py")
        assert "scope_b" in b and "scope_a" not in b
        entries = len(list((tmp_path / "c").iterdir()))
        # the same program from another checkout is found again
        assert "scope_b" in text("scope_b", tmp_path / "two.py")
        assert len(list((tmp_path / "c").iterdir())) == entries
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()
