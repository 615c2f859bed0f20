"""What the two serving drivers share: the served model built from the
configuration file, warm-up, the per-request clock, the traced
sub-window, the end-to-end numbers and the check against the reference.

The engine is the program's ``repro.serving.ContinuousEngine``, driven
one ``step_once`` at a time from here.  Every request time is taken on
this module's clock: a token counts when ``step_once`` has returned it
to the host.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from chipbench import common, harness, work
from chipbench.reference import olmo as ref


# ---------------------------------------------------------------------------
# Traffic: sizes and arrivals from the traffic file, the same for every seed.
# ---------------------------------------------------------------------------

def length_ppf(spec: dict):
    """Quantile function of a length distribution of the traffic file."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return lambda u: lo + min(int(u * (hi - lo + 1)), hi - lo)
    if spec["dist"] == "lognormal":
        from statistics import NormalDist
        z = NormalDist()
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        return lambda u: int(min(hi, max(lo, round(
            math.exp(mu + sigma * z.inv_cdf(u))))))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def sizes(traffic: dict, n: int, max_seq: int):
    """n (prompt, output) lengths; prompt + output fits ``max_seq``."""
    p = common.fixed_quantiles(n, length_ppf(traffic["prompt_tokens"]),
                               common.SIZE_ORDER)
    o = common.fixed_quantiles(n, length_ppf(traffic["output_tokens"]),
                               common.SIZE_ORDER)
    o = np.roll(o, n // 3)        # pair lengths independently
    p = np.minimum(p, max_seq - o)
    return [(int(a), int(b)) for a, b in zip(p, o)]


def prompts(seed: int, lengths, vocab: int):
    """Token ids from the seed, one prompt per length."""
    rng = common.rng_of(seed, 2)
    return [tuple(int(t) for t in rng.integers(0, vocab, n)) for n in lengths]


# ---------------------------------------------------------------------------
# The served model.
# ---------------------------------------------------------------------------

def arch_of(config: dict):
    """The program's ArchConfig for the configuration file: the
    registered architecture with the file's sizes and GEMM sites."""
    from repro import configs
    base = configs.get_config(config["arch"])
    model = dataclasses.replace(
        base.model, n_layers=config["n_layers"], d_model=config["d_model"],
        n_heads=config["n_heads"], n_kv_heads=config["n_kv_heads"],
        d_ff=config["d_ff"], vocab=config["vocab"], dtype=config["dtype"],
        rope_theta=config["rope_theta"])
    sites = tuple(config["gemm_sites"].items())
    return dataclasses.replace(base, model=model, gemm_sites=sites)


class Clock:
    """Seconds since the window opened (since construction before)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


@dataclasses.dataclass
class Tracked:
    state: object                 # the engine's RequestState
    due: float
    times: list = dataclasses.field(default_factory=list)


class Server:
    """The engine with the benchmark's weights, warmed up."""

    def __init__(self, ctx: harness.Context):
        import jax
        import jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.serving import ContinuousEngine
        self.ctx = ctx
        cfg = self.cfg = ctx.config
        eng_cfg = cfg["engine"]
        self.lanes, self.chunk = eng_cfg["lanes"], eng_cfg["chunk"]
        self.max_seq = eng_cfg["max_seq"]
        self.mesh = make_host_mesh()
        self.clock = Clock()
        self.spans = common.Spans(False)
        dtype = jnp.dtype(cfg["dtype"])
        make = jax.jit(lambda k: ref.init_weights(k, cfg, dtype))
        params = make(common.key_of(ctx.seed, 1))
        with self.mesh:
            self.eng = ContinuousEngine(
                arch_of(cfg), self.mesh, max_seq=self.max_seq,
                params=params, max_lanes=self.lanes, chunk=self.chunk,
                page_size=eng_cfg["page_size"], clock=self.clock)
        del params
        # Host spans, written into the trace while one is recorded.  A
        # stopgap until the engine writes its own: the harness wraps the
        # engine's parts from outside, so a renamed part fails here.
        eng, sched = self.eng, self.eng.sched
        hook(sched, "plan", self.spans)
        hook(sched, "commit", self.spans)
        hook(eng, "_execute", self.spans, "execute")
        self.view = eng.kv.view_tokens
        self._warm_up()
        self.tracked: dict[int, Tracked] = {}
        self.steps = []           # (t_end, chunk, tokens emitted, seconds)
        self.traced_work = {"emugemm_least_s": 0.0, "model_flops": 0,
                            "steps": 0}
        self.traced_calls = 0     # step_once calls in the traced window

    def _warm_up(self):
        """Both compiled step shapes, twice: a prompt of chunk + 1 tokens
        runs a mixed step, then a one-token prefill and decode steps."""
        from repro.serving.queue import Request
        rng = common.rng_of(self.ctx.seed, 3)
        for _ in range(2):
            for _ in range(self.lanes):
                self.eng.submit(Request(
                    prompt=tuple(rng.integers(0, self.cfg["vocab"],
                                              self.chunk + 1)),
                    max_new_tokens=3, arrival=0.0))
            with self.mesh:
                while self.eng.sched.has_work():
                    self.eng.step_once(0.0)

    # ---- the window -----------------------------------------------------

    def submit(self, prompt, out_len: int, due: float) -> Tracked:
        from repro.serving.queue import Request
        st = self.eng.submit(Request(prompt=prompt, max_new_tokens=out_len,
                                     arrival=due))
        t = self.tracked[st.rid] = Tracked(state=st, due=due)
        return t

    def open_window(self) -> None:
        self.clock.t0 = self.ctx.start_window()
        tr = self.ctx.traffic.get("trace_seconds", 8)
        mid = self.ctx.seconds / 2
        self.trace_at = (max(0.0, mid - tr / 2), mid + tr / 2)
        self.tracing = False
        self.trace_done = not self.ctx.trace
        self._trace_win = None

    def _trace_tick(self, now: float) -> None:
        import jax
        if self.trace_done:
            return
        if not self.tracing and now >= self.trace_at[0]:
            jax.profiler.start_trace(str(self.ctx.trace_dir))
            self.spans.on = True
            self._trace_win = jax.profiler.TraceAnnotation(common.WINDOW)
            self._trace_win.__enter__()
            self.tracing = True
        elif self.tracing and now >= self.trace_at[1]:
            self.stop_trace()

    def stop_trace(self) -> None:
        import jax
        if self.tracing:
            self._trace_win.__exit__(None, None, None)
            self.spans.on = False
            jax.profiler.stop_trace()
            self.tracing = False
        self.trace_done = True

    def step(self):
        """One engine step; records the tokens each request got."""
        now = self.clock()
        self._trace_tick(now)
        with self.mesh, self.spans("step"):
            plan = self.eng.step_once(now)
        t = self.clock()
        self.traced_calls += self.tracing
        if plan is None:
            return None
        emitted = 0
        for lane, rid in enumerate(plan.rids):
            if rid is not None and plan.emit[lane] and rid in self.tracked:
                self.tracked[rid].times.append(t)
                emitted += 1
        self.steps.append((t, plan.chunk, emitted, t - now))
        if self.tracing:
            self._count_work(plan)
        return plan

    def wait(self, until: float) -> None:
        with self.spans("await_arrival"):
            time.sleep(max(0.0, until - self.clock()))

    def _count_work(self, plan) -> None:
        w = self.traced_work
        sites = work.serve_step_sites(self.cfg, self.lanes, plan.chunk,
                                      self.view)
        w["emugemm_least_s"] += work.least_s(sites, self.ctx.peaks)
        w["model_flops"] += work.step_model_flops(
            self.cfg, plan.start, plan.n_new, plan.emit)
        w["steps"] += 1

    # ---- after the window -----------------------------------------------

    def numbers(self, end: float) -> dict:
        """End-to-end numbers over the requests due in the window."""
        due = [t for t in self.tracked.values() if t.due < end]
        ttft, tpot = [], []
        for t in due:
            first = t.times[0] if t.times else end
            ttft.append(first - t.due)
            n = len(t.times)
            if t.state.status == "done" and n > 1:
                tpot.append((t.times[-1] - t.times[0]) / (n - 1))
            elif n >= 1 and t.state.status != "done":
                # the next token had not come by the window's end
                tpot.append((end - t.times[0]) / n)
        tokens = sum(e for ts, _, e, _ in self.steps if ts <= end)
        out = {"output_tokens_per_s": tokens / end,
               "requests_due": len(due),
               "requests_done": sum(t.state.status == "done" for t in due),
               "steps": len(self.steps),
               "mixed_steps": sum(c > 1 for _, c, _, _ in self.steps),
               "backlog_mid": self.backlog(end / 2),
               "backlog_end": self.backlog(end)}
        if ttft:
            out["ttft_p90_ms"] = 1e3 * common.percentile(ttft, 90)
        if tpot:
            out["tpot_p90_ms"] = 1e3 * common.percentile(tpot, 90)
        for kind, mixed in (("decode", False), ("mixed", True)):
            dt = [d for _, c, _, d in self.steps if (c > 1) == mixed]
            if dt:
                med = common.percentile(dt, 50)
                out[f"{kind}_step_ms"] = [1e3 * med, 1e3 * max(dt)]
                out[f"{kind}_steps_slow"] = sum(d > 1.5 * med for d in dt)
        return out

    def backlog(self, t: float) -> int:
        """Requests due by ``t`` and not finished by then."""
        return sum(1 for r in self.tracked.values() if r.due <= t and not (
            r.state.status == "done" and r.times and r.times[-1] <= t))

    def failed(self, end: float) -> int:
        return sum(t.state.status == "failed"
                   for t in self.tracked.values() if t.due < end)

    def traced_spans(self) -> dict:
        """The spans the traced window must hold: each ``step_once``
        plans; one that found work executes and commits."""
        ran = self.traced_work["steps"]
        return {"step": self.traced_calls, "plan": self.traced_calls,
                "execute": ran, "commit": ran}

    def hlo_texts(self) -> list:
        """The compiled text of both step programs (from the cache).

        A stopgap like the spans: it rebuilds the step's private argument
        list, and fails loudly when the engine's compiled steps change, or
        when their text names no emulated site to time."""
        import jax.numpy as jnp
        from chipbench import trace_reduce
        eng, texts = self.eng, []
        if not isinstance(getattr(eng, "_jit_fns", None), dict):
            raise RuntimeError("ContinuousEngine._jit_fns (chunk -> jitted "
                               "step) is gone: the harness reads the "
                               "compiled steps' text through it")
        for c in sorted(eng._jit_fns):
            args = (eng.params, eng.pools,
                    eng.kv.tables_for([None] * self.lanes),
                    jnp.zeros((self.lanes, c), jnp.int32),
                    jnp.zeros((self.lanes,), jnp.int32),
                    jnp.zeros((self.lanes,), jnp.int32))
            with self.mesh:
                texts.append(eng._jit_fns[c].lower(*args).compile().as_text())
        if not any(trace_reduce.names_an_emulated_site(t) for t in texts):
            raise RuntimeError("no compiled step names an emulated GEMM "
                               "site: the roofline would read nothing")
        return texts

    def finished_sample(self, end: float, count: int):
        """Finished requests drawn from the seed, the longest among them."""
        done = [t for t in self.tracked.values()
                if t.due < end and t.state.status == "done"]
        if not done:
            return []
        done.sort(key=lambda t: t.state.rid)
        longest = max(done, key=lambda t: len(t.state.request.prompt)
                      + len(t.state.generated))
        rest = [t for t in done if t is not longest]
        rng = common.rng_of(self.ctx.seed, 4)
        pick = rng.choice(len(rest), size=min(count - 1, len(rest)),
                          replace=False) if rest else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        self.eng = None
        gc.collect()


def sequences(sample, max_seq: int):
    """(tokens, targets, mask) per sampled request, padded to max_seq:
    the prompt and the served tokens but the last, and at each position
    the served token that follows it."""
    out = []
    for t in sample:
        prompt = list(t.state.request.prompt)
        served = list(t.state.generated)
        seq = prompt + served[:-1]
        tokens = np.zeros((max_seq,), np.int32)
        tokens[:len(seq)] = seq
        targets = np.zeros((max_seq,), np.int32)
        mask = np.zeros((max_seq,), bool)
        first = len(prompt) - 1
        targets[first:first + len(served)] = served
        mask[first:first + len(served)] = True
        out.append((tokens, targets, mask))
    return out


def reference_gaps(cfg: dict, seed: int, seqs):
    """Gap of each served token below the reference's best logit."""
    import jax
    import jax.numpy as jnp
    w = jax.jit(lambda k: ref.init_weights(k, cfg, jnp.dtype(cfg["dtype"])))(
        common.key_of(seed, 1))
    items = ref.cfg_items(cfg)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for tokens, targets, mask in seqs:
            g = ref.served_gaps(w, items, jnp.asarray(tokens),
                                jnp.asarray(targets))
            gaps.append(np.asarray(g)[mask])
    del w
    return gaps


def gap_stats(gaps) -> dict:
    """The numbers a check may compare, over the served tokens' gaps."""
    return {"mean_logit_gap": float(gaps.mean()),
            "mean_sq_logit_gap": float(np.square(gaps).mean()),
            "max_logit_gap": float(gaps.max())}


def check(server: Server, end: float):
    """Sampled finished requests against the plain reference.  For each
    served token, its gap is how far the reference's logit of it lies
    below the reference's best logit there; the traffic file's check
    names which statistics of the gaps are compared, with their limits.
    Returns the checks and every statistic read."""
    ctx = server.ctx
    spec = ctx.traffic["check"]
    sample = server.finished_sample(end, spec["requests"])
    seqs = sequences(sample, server.max_seq)
    server.release()
    if not seqs:
        ctx.log("no request finished in the window: nothing to compare")
        return {"served_tokens_compared": harness.Check(0.0, -1.0)}, {}
    t0 = time.perf_counter()
    gaps = np.concatenate(reference_gaps(ctx.config, ctx.seed, seqs))
    stats = dict(gap_stats(gaps), tokens=int(gaps.size),
                 requests=len(seqs))
    ctx.log(f"compared {gaps.size} served tokens of {len(seqs)} requests "
            f"in {time.perf_counter() - t0:.1f} s; "
            + " ".join(f"{k} {v:.6f}" for k, v in gap_stats(gaps).items()))
    return {k: harness.Check(stats[k], float(v)) for k, v in spec.items()
            if k != "requests"}, stats


def outcome(server: Server, end: float) -> harness.Outcome:
    """Numbers, peak memory, compiled texts, and then the check."""
    ctx = server.ctx
    server.stop_trace()
    nums = server.numbers(end)
    ctx.log(" ".join(f"{k}={v}" for k, v in nums.items()))
    mem = harness.memory_peak(ctx.devices)
    texts = server.hlo_texts() if ctx.trace else []
    spans = server.traced_spans() if ctx.trace else {}
    failed = server.failed(end)
    work_ = dict(server.traced_work)
    checks, readings = check(server, end) if ctx.check else ({}, {})
    return harness.Outcome(attempted=nums["requests_due"], failed=failed,
                           metrics=nums, checks=checks,
                           memory_peak_bytes=mem, work=work_,
                           hlo_texts=texts, readings=readings, spans=spans)


def hook(obj, name: str, spans: common.Spans, span: str | None = None):
    """Wrap the engine's ``obj.name`` in a host span; it has to exist."""
    fn = getattr(obj, name, None)
    if not callable(fn):
        raise RuntimeError(f"{type(obj).__name__}.{name} is gone: the "
                           f"harness wraps it in its {span or name!r} span")
    setattr(obj, name, spans.wrap(fn, span or name))
