"""Continuous-batching engine over the fused emulated GEMMs.

:class:`ContinuousEngine` executes the scheduler's fixed-shape plans with
exactly two jit-compiled step functions — a mixed ``(max_lanes, chunk)``
prefill+decode step and a ``(max_lanes, 1)`` pure-decode step — against a
paged KV cache. One compile serves arbitrary traffic mixes; a lane's
tokens are bit-identical whatever the rest of the cohort is doing (see
forward_step), so continuous batching changes throughput, never results.

Emulation specifics:

* **Once-per-session residue streaming** — when the resolved policy
  caches weights (``+cached``), ``prepare_params`` decomposes the dense
  projections once at construction; every subsequent serve step streams
  finished int8 slices/residues.
* **Per-request guard retry** — the jitted fast path never raises:
  under jit, strict guards only *count* trips (docs/robustness.md), so
  the engine polls ``guard.stats()`` deltas per step. A tripped step is
  re-run lane-by-lane in eager mode, where the full escalation ladder
  executes: attribution lands on the offending request(s) only
  (``guard_trips`` in its result), their corrected outputs overwrite the
  fast path's, and a request that still fails strict after
  ``guard_retries`` eager attempts is failed alone — the rest of the
  cohort never replays and never pays backoff.

**Self-tracing.** Each ``step_once`` is a ``serve.step`` span
(``jax.profiler.StepTraceAnnotation``) holding ``serve.*`` spans for its
parts (plan, admit, tables, inputs, dispatch, sync, guard, replay,
commit, retire, record), on the profiler's clock; they cost about a microsecond
each when no profiler records. The jitted step's KV gather and scatter
run under the ``serve/kv_gather`` / ``serve/kv_scatter`` named scopes.
Per-execution counts (emulated sites by step shape, steps that
compiled, GC pauses, queue wait) are in :meth:`utilization` whether
telemetry is on or off (docs/observability.md).

The legacy whole-batch :class:`LockstepEngine` (prefill the full batch,
decode in lockstep) is kept for API back-compat; the continuous engine's
``wave_admission`` mode reproduces its schedule with the new step
functions and is the baseline the serve benchmark gates against.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import guard
from repro.core.precision import EmulationAccuracyError
from repro.kernels import dispatch
from repro.models import model as M
from repro.models.common import GemmPolicy
from repro.serving.kv_cache import SCRATCH_PAGE, PagedKVCache
from repro.serving.queue import Request, RequestQueue, RequestState
from repro.serving.scheduler import ScheduleConfig, Scheduler, StepPlan
from repro.telemetry import trace as _trace

_GUARD_FIELDS = ("calls", "trips", "escalations", "recoveries",
                 "native_fallbacks", "masked")


@dataclasses.dataclass
class RequestResult:
    rid: int
    status: str                    # done | failed
    tokens: list[int]
    ttft: float | None             # first token latency (s from arrival)
    tpot: float | None             # mean per-output-token latency (s)
    guard_trips: int
    evictions: int

    @classmethod
    def of(cls, s: RequestState) -> "RequestResult":
        arr = s.request.arrival
        ttft = (s.first_token_at - arr
                if s.first_token_at is not None else None)
        n = len(s.generated)
        tpot = None
        if n > 1 and s.finished_at is not None and s.first_token_at is not None:
            tpot = (s.finished_at - s.first_token_at) / (n - 1)
        return cls(rid=s.rid, status=s.status, tokens=list(s.generated),
                   ttft=ttft, tpot=tpot, guard_trips=s.guard_trips,
                   evictions=s.evictions)


class ContinuousEngine:
    def __init__(self, arch, mesh, *, max_seq: int, policy=None, params=None,
                 seed: int = 0, prepare: bool | None = None,
                 max_lanes: int = 4, chunk: int = 16, page_size: int = 16,
                 num_pages: int | None = None, queue_policy: str = "fcfs",
                 token_budget: int | None = None, guard_retries: int = 1,
                 guard_backoff: float = 0.0, wave_admission: bool = False,
                 clock=None):
        self.arch = arch
        self.mcfg = arch.model
        self.mesh = mesh
        self.policy = dispatch.resolve_policy(
            policy if policy is not None else arch.gemm_policy(), mesh)
        self.params = params if params is not None else M.init_params(
            jax.random.PRNGKey(seed), self.mcfg)
        from repro.kernels import prepared
        if prepare is None:       # auto: +cached specs stream residues
            prepare = prepared.policy_caches_weights(self.policy)
        self.prepared = bool(prepare)
        if self.prepared:
            self.params = prepared.prepare_params(self.params, self.policy)

        if num_pages is None:     # worst case: every lane at max_seq
            import math
            num_pages = 1 + max_lanes * math.ceil(max_seq / page_size)
        self.kv = PagedKVCache(self.mcfg, page_size=page_size,
                               num_pages=num_pages, max_seq=max_seq,
                               chunk=chunk)
        self.pools = self.kv.init_pools()
        cfg = ScheduleConfig(max_lanes=max_lanes, chunk=chunk,
                             token_budget=token_budget, policy=queue_policy)
        self.sched = Scheduler(cfg, self.kv, wave=wave_admission)
        self.queue: RequestQueue = self.sched.queue

        _trace.install_host_hooks()
        # Profiles of the steps are read through their scopes' op names.
        _trace.keep_op_names_in_compile_cache()
        self._host0 = _trace.HOST.snapshot()
        self._step_fns = {c: self._make_step(c) for c in {1, chunk}}
        # chunk -> {emugemm tag: sites per execution}, filled when the
        # jitted step of that shape is traced.
        self._sites: dict[int, dict[str, int]] = {}
        self._site_executions: dict[int, dict[str, int]] = {}
        self._step_compiles: dict[int, int] = {}
        # No donated buffers: a guard replay needs the pre-step pools
        # intact, and jit invalidates donated args even on failure.
        self._jit_fns = {c: jax.jit(self._collecting(c, f))
                         for c, f in self._step_fns.items()}
        self.guard_retries = guard_retries
        self.guard_backoff = guard_backoff
        self.last_guard: dict[str, int] = {}
        self._results: dict[int, RequestResult] = {}
        self._step_idx = 0
        self._calls = 0
        self._busy_steps = 0
        self._queue_nonempty_steps = 0
        self._t0 = time.monotonic()
        self._clock = clock if clock is not None else (
            lambda: time.monotonic() - self._t0)
        from repro import telemetry
        self._telemetry = telemetry
        self._tracker = telemetry.StepTracker() if telemetry.enabled() \
            else None

    # ---- step functions -------------------------------------------------

    def _make_step(self, c: int):
        kv, mcfg, policy, vocab = self.kv, self.mcfg, self.policy, \
            self.mcfg.vocab

        def step(params, pools, tables, tokens, start, n_new):
            with jax.named_scope("serve/kv_gather"):
                views = kv.gather(pools, tables)
            logits, views = M.forward_step(params, mcfg, tokens, start,
                                           n_new, views, policy)
            with jax.named_scope("serve/kv_scatter"):
                pools = kv.scatter(pools, tables, views, start, n_new, c)
            tok = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
            return tok, pools

        return step

    def _collecting(self, c: int, step):
        """``step``, noting in ``_sites[c]`` the emulated sites each
        trace of it holds (the jitted step's body runs only when traced)."""
        @functools.wraps(step)
        def traced(*args):
            with _trace.collect_sites() as sites:
                out = step(*args)
            self._sites[c] = dict(sites)
            return out

        return traced

    def step_hlo_texts(self) -> dict[int, str]:
        """chunk -> the compiled text of the jitted step of that shape,
        lowered with an empty plan's arguments."""
        b = self.sched.cfg.max_lanes
        texts = {}
        with self.mesh if self.mesh is not None else contextlib.nullcontext():
            for c in sorted(self._jit_fns):
                args = (self.params, self.pools,
                        self.kv.tables_for([None] * b),
                        jnp.zeros((b, c), jnp.int32),
                        jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b,), jnp.int32))
                texts[c] = self._jit_fns[c].lower(*args).compile().as_text()
        return texts

    # ---- request intake -------------------------------------------------

    def submit(self, request: Request) -> RequestState:
        return self.queue.submit(request)

    def reset_clock(self) -> None:
        """Re-zero the arrival/latency clock, e.g. after a jit warmup:
        request ``arrival`` offsets and TTFT/TPOT are then measured from
        serving start instead of engine construction (no-op under an
        injected ``clock``)."""
        self._t0 = time.monotonic()

    # ---- execution ------------------------------------------------------

    def _guard_delta(self, before) -> dict[str, int]:
        jax.effects_barrier()      # flush staged guard debug callbacks
        after = guard.stats()
        return {f: getattr(after, f) - getattr(before, f)
                for f in _GUARD_FIELDS}

    def _execute(self, plan: StepPlan, tables) -> np.ndarray:
        with jax.profiler.TraceAnnotation("serve.inputs"):
            args = (self.params, self.pools, tables,
                    jnp.asarray(plan.tokens), jnp.asarray(plan.start),
                    jnp.asarray(plan.n_new))
        before = guard.stats()
        compiles = _trace.HOST.compiles
        try:
            with jax.profiler.TraceAnnotation("serve.dispatch"):
                tok, pools = self._jit_fns[plan.chunk](*args)
            self._count_execution(plan.chunk,
                                  _trace.HOST.compiles != compiles)
            with jax.profiler.TraceAnnotation("serve.sync"):
                sampled = np.asarray(tok)
            self.pools = pools
            with jax.profiler.TraceAnnotation("serve.guard"):
                delta = self._guard_delta(before)
        except EmulationAccuracyError:
            # Strict trip surfaced at trace time (first call, constant
            # folding): fall straight to per-lane eager isolation.
            delta = {"trips": 1}
        self.last_guard = delta
        if delta.get("trips", 0) or delta.get("escalations", 0):
            with jax.profiler.TraceAnnotation("serve.replay"):
                sampled = self._isolation_replay(plan, tables)
        return sampled

    def _count_execution(self, chunk: int, compiled: bool) -> None:
        """One executed step of shape ``chunk``: its sites, and whether
        its dispatch compiled."""
        sites = self._sites.get(chunk, {})
        counts = self._site_executions.setdefault(chunk, {})
        for tag, n in sites.items():
            counts[tag] = counts.get(tag, 0) + n
        if compiled:
            self._step_compiles[chunk] = self._step_compiles.get(chunk, 0) + 1
        if not self._telemetry.enabled():
            return
        reg = self._telemetry.REGISTRY
        rec = self._telemetry.record
        for tag, n in sites.items():
            reg.inc(rec.SERVE_SITE_EXECUTIONS, n, {"chunk": chunk, "tag": tag})
        if compiled:
            reg.inc(rec.SERVE_STEP_COMPILES, 1, {"chunk": chunk})

    def _isolation_replay(self, plan: StepPlan, tables) -> np.ndarray:
        """Re-run the tripped step one lane at a time, eagerly.

        Eager mode runs the full guard escalation ladder, so the replay
        both *attributes* the trip to the request(s) that caused it and
        *corrects* their outputs (escalated precision / native fallback)
        instead of keeping the fast path's masked values. Only still-
        failing strict lanes are failed; innocent cohort members keep
        their (identical, row-independent) results with zero retries.
        """
        from repro.telemetry import record as _rec
        b = len(plan.rids)
        sampled = np.zeros((b,), dtype=np.int32)
        scratch_row = np.full((self.kv.view_pages,), SCRATCH_PAGE, np.int32)
        tables_np = np.asarray(tables)
        for lane in range(b):
            if plan.rids[lane] is None:
                continue
            state = self.sched.lanes[lane]
            assert state is not None and state.rid == plan.rids[lane]
            one = lambda arr, fill=0: np.full_like(arr, fill)
            t1 = np.stack([tables_np[i] if i == lane else scratch_row
                           for i in range(b)])
            toks, st, nn = (one(plan.tokens), one(plan.start),
                            one(plan.n_new))
            toks[lane], st[lane], nn[lane] = (plan.tokens[lane],
                                              plan.start[lane],
                                              plan.n_new[lane])
            attempt = 0
            while True:
                before = guard.stats()
                try:
                    tok, pools = self._step_fns[plan.chunk](
                        self.params, self.pools, jnp.asarray(t1),
                        jnp.asarray(toks), jnp.asarray(st), jnp.asarray(nn))
                    delta = self._guard_delta(before)
                    trips = delta.get("trips", 0)
                    if trips:
                        state.guard_trips += trips
                        _rec.record_event(_rec.SERVE_GUARD_TRIPS,
                                          {"rid": state.rid}, trips)
                    sampled[lane] = int(np.asarray(tok)[lane])
                    self.pools = pools
                    break
                except EmulationAccuracyError:
                    state.guard_trips += 1
                    _rec.record_event(_rec.SERVE_GUARD_TRIPS,
                                      {"rid": state.rid}, 1)
                    if attempt >= self.guard_retries:
                        self._fail_lane(lane, state)
                        plan.rids[lane] = None
                        break
                    attempt += 1
                    if self.guard_backoff:
                        time.sleep(self.guard_backoff * attempt)
        return sampled

    def _fail_lane(self, lane: int, state: RequestState) -> None:
        from repro.telemetry import record as _rec
        with jax.profiler.TraceAnnotation(
                "serve.retire", rid=state.rid, status="failed",
                tokens=len(state.generated)):
            state.status = "failed"
            state.finished_at = self._clock()
            self.kv.release(state.rid)
            self.sched.lanes[lane] = None
            self.sched.failed.append(state)
            self._results[state.rid] = RequestResult.of(state)
            _rec.record_event(_rec.SERVE_REQUESTS,
                              {"outcome": "guard_failed"})

    # ---- the serve loop -------------------------------------------------

    def step_once(self, now: float | None = None) -> StepPlan | None:
        """Plan + execute + commit one engine step. Returns the executed
        plan, or None when nothing was runnable at ``now``."""
        if now is None:
            now = self._clock()
        span = jax.profiler.StepTraceAnnotation("serve.step",
                                                step_num=self._calls)
        self._calls += 1
        with span:
            plan = self._step(now)
            if plan is None:
                span.set_metadata(chunk=0, lanes=0, tokens=0)
            else:
                span.set_metadata(chunk=plan.chunk,
                                  lanes=int((plan.n_new > 0).sum()),
                                  tokens=plan.scheduled_tokens)
        return plan

    def _step(self, now: float) -> StepPlan | None:
        evicted_before = self.sched.evictions
        with jax.profiler.TraceAnnotation("serve.plan"):
            plan = self.sched.plan(now)
        with jax.profiler.TraceAnnotation("serve.record"):
            self._record_gauges(now)
        if plan is None:
            return None
        with jax.profiler.TraceAnnotation("serve.tables"):
            tables = self.kv.tables_for(plan.rids)
        t0 = time.perf_counter()
        sampled = self._execute(plan, tables)
        dt = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("serve.commit"):
            retired = self.sched.commit(plan, sampled, self._clock())
        with jax.profiler.TraceAnnotation("serve.record"):
            self._record_step(plan, retired, dt,
                              self.sched.evictions - evicted_before)
            self._step_idx += 1
            self._busy_steps += 1
            if self.queue.depth(now) > 0:
                self._queue_nonempty_steps += 1
        return plan

    def run(self, requests=None, max_steps: int | None = None
            ) -> dict[int, RequestResult]:
        """Serve to completion (wall clock; arrivals are seconds from
        engine start). Returns {rid: RequestResult}."""
        if requests:
            for r in requests:
                self.submit(r)
        steps = 0
        while self.sched.has_work():
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"serve loop exceeded {max_steps} steps")
            now = self._clock()
            plan = self.step_once(now)
            steps += 1
            if plan is None:
                nxt = self.queue.next_arrival()
                if nxt is not None and nxt > now:
                    time.sleep(min(nxt - now, 0.05))
        return dict(self._results)

    # ---- telemetry ------------------------------------------------------

    def _record_gauges(self, now: float) -> None:
        if not self._telemetry.enabled():
            return
        reg = self._telemetry.REGISTRY
        rec = self._telemetry.record
        reg.set_gauge(rec.SERVE_QUEUE_DEPTH, self.queue.depth(now))
        reg.set_gauge(rec.SERVE_PAGE_OCCUPANCY,
                      self.kv.stats()["occupancy"])
        reg.set_gauge(rec.SERVE_LANES_ACTIVE, len(self.sched.running()))

    def _record_step(self, plan: StepPlan, retired, dt: float,
                     evicted: int) -> None:
        for s in retired:
            if s.rid not in self._results:
                self._results[s.rid] = RequestResult.of(s)
        if not self._telemetry.enabled():
            return
        reg = self._telemetry.REGISTRY
        rec = self._telemetry.record
        n_pref = int(plan.n_new[plan.prefill].sum())
        n_dec = int(plan.n_new[~plan.prefill & (plan.n_new > 0)].sum())
        if n_pref:
            reg.inc(rec.SERVE_TOKENS, n_pref, {"kind": "prefill"})
        if n_dec:
            reg.inc(rec.SERVE_TOKENS, n_dec, {"kind": "decode"})
        if evicted:
            reg.inc(rec.SERVE_EVICTIONS, evicted)
        for s in retired:
            if s.status == "done":
                reg.inc(rec.SERVE_REQUESTS, 1, {"outcome": "done"})
            r = self._results[s.rid]
            if r.ttft is not None:
                reg.observe(rec.SERVE_TTFT_SECONDS, r.ttft)
            if r.tpot is not None:
                reg.observe(rec.SERVE_TPOT_SECONDS, r.tpot)
        if self._tracker is not None:
            self._tracker.step_metrics(
                self._step_idx, dt, kind="serve_step",
                tokens=plan.scheduled_tokens,
                extra={"lanes": int((plan.n_new > 0).sum()),
                       "chunk": plan.chunk,
                       "queue_depth": self.queue.depth(),
                       "page_occupancy": self.kv.stats()["occupancy"],
                       "guard_trips": self.last_guard.get("trips", 0)})

    # ---- introspection --------------------------------------------------

    def utilization(self) -> dict:
        """Schedule-quality counters (see bench_serve), and what the
        engine's steps ran and waited on, telemetry on or off:
        ``site_executions`` (chunk -> {emugemm tag: sites executed}),
        ``step_compiles`` (chunk -> steps whose dispatch compiled),
        process compiles and GC pauses since the engine was built, and
        the queue wait of admissions."""
        host = _trace.HOST.snapshot()
        since = {k: host[k] - self._host0[k] for k in host}
        return {"steps": self._step_idx,
                "busy_steps": self._busy_steps,
                "queue_nonempty_steps": self._queue_nonempty_steps,
                "evictions": self.sched.evictions,
                "admissions": self.sched.admissions,
                "kv": self.kv.stats(),
                "site_executions": {c: dict(t) for c, t in
                                    sorted(self._site_executions.items())},
                "step_compiles": dict(sorted(self._step_compiles.items())),
                **since,
                "queue_wait_s": self.sched.queue_wait_s,
                "queue_wait_max_s": self.sched.queue_wait_max_s}


class LockstepEngine:
    """Legacy whole-batch engine: prefill the full batch once, decode all
    lanes in lockstep against a contiguous cache. Kept as the API-stable
    ``repro.launch.serve.ServeEngine``; new code and the benchmark use
    :class:`ContinuousEngine` (its ``wave_admission`` mode reproduces
    this schedule on the paged cache)."""

    def __init__(self, arch, mesh, max_seq: int, policy=None,
                 params=None, seed: int = 0, prepare: bool = False,
                 guard_retries: int = 1, guard_backoff: float = 0.25):
        self.arch = arch
        self.mcfg = arch.model
        self.mesh = mesh
        self.max_seq = max_seq
        # The one resolver decides the engine's emulation: an explicit
        # policy wins, else the arch config's gemm_sites table, else the
        # ambient repro.emulation scope / REPRO_EMULATION env configures
        # the whole serving session; resolve_policy then clamps impls to
        # what this mesh executes.
        self.policy = dispatch.resolve_policy(
            policy if policy is not None else arch.gemm_policy(), mesh)
        self.params = params if params is not None else M.init_params(
            jax.random.PRNGKey(seed), self.mcfg)
        if prepare:
            # Once-per-session weight decomposition: every prefill/decode
            # step streams the finished int8 slices instead of
            # re-splitting the projection weights (Scheme-I sites only).
            from repro.kernels import prepared
            self.params = prepared.prepare_params(self.params, self.policy)
        self._decode = jax.jit(
            lambda p, tok, pos, cache: M.forward_decode(
                p, self.mcfg, tok, pos, cache, self.policy))
        self._prefill = jax.jit(
            lambda p, inputs: M.forward_prefill(
                p, self.mcfg, inputs, self.max_seq, self.policy))
        # Guard consumption (docs/robustness.md): ``last_guard`` holds the
        # per-batch delta of the process-wide guard counters; a strict
        # accuracy trip retries the whole batch with backoff before
        # surfacing (the request-level analogue of the trainer's step
        # retry — ContinuousEngine narrows this to the offending request).
        self.guard_retries = guard_retries
        self.guard_backoff = guard_backoff
        self.last_guard: dict[str, int] = {}
        from repro import telemetry
        self._telemetry = telemetry
        self._tracker = telemetry.StepTracker() if telemetry.enabled() \
            else None
        self._batches = 0

    def _generate_once(self, prompts: np.ndarray, n_tokens: int):
        b, s = prompts.shape
        logits, cache = self._prefill(self.params,
                                      {"tokens": jnp.asarray(prompts)})
        out = []
        tok = jnp.argmax(logits[:, -1:, :self.mcfg.vocab], axis=-1)
        out.append(tok)
        for i in range(1, n_tokens):
            logits, cache = self._decode(self.params, tok, s + i - 1, cache)
            tok = jnp.argmax(logits[:, -1:, :self.mcfg.vocab], axis=-1)
            out.append(tok)
        return np.asarray(jnp.concatenate(out, axis=1))

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 greedy: bool = True):
        """prompts: (B, S) int32. Returns (B, n_tokens) generated ids."""
        before = guard.stats()
        t0 = time.time()
        attempt = 0
        while True:
            try:
                toks = self._generate_once(prompts, n_tokens)
                break
            except EmulationAccuracyError as e:
                if attempt >= self.guard_retries:
                    raise
                attempt += 1
                pause = self.guard_backoff * attempt
                print(f"[serve] guard trip (retry {attempt}/"
                      f"{self.guard_retries} after {pause:.2f}s): {e}")
                time.sleep(pause)
        dt = time.time() - t0
        after = guard.stats()
        self.last_guard = {
            f: getattr(after, f) - getattr(before, f) for f in _GUARD_FIELDS}
        self.last_guard["retries"] = attempt
        # One telemetry record per served batch (docs/observability.md):
        # kind="serve", tokens = generated ids this batch, so
        # tokens_per_s is the decode throughput the operator dashboards.
        if self._tracker is None and self._telemetry.enabled():
            self._tracker = self._telemetry.StepTracker()
        if self._tracker is not None:
            self._tracker.step_metrics(
                self._batches, dt, kind="serve",
                tokens=int(prompts.shape[0]) * int(n_tokens),
                extra={"requests": int(prompts.shape[0]),
                       "guard_retries": attempt})
        self._batches += 1
        return toks
