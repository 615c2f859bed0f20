"""Open loop: requests arrive on a schedule, whatever the engine does.

The traffic file gives the rate and the length distributions.  Arrivals
are the quantiles of the exponential gaps of a Poisson process at that
rate, in a fixed order of their own (not that of the prompts' lengths),
so every seed offers the same load; the seed draws the prompts and the
weights.  Each request is timed from when it
was due."""

from __future__ import annotations

import math

from chipbench import common
from chipbench.drivers import _serve


def arrivals(rate: float, seconds: float):
    """Due times in [0, seconds): fixed exponential gaps at ``rate``."""
    n = max(1, math.ceil(rate * seconds))
    gaps = common.fixed_quantiles(n, lambda u: -math.log(1.0 - u) / rate,
                                  common.ARRIVAL_ORDER)
    scale = seconds * (n - 0.5) / n / gaps.sum()
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g * scale
    return due


def run(ctx):
    server = _serve.Server(ctx)
    tr = ctx.traffic
    due = arrivals(float(tr["rate_per_s"]), ctx.seconds)
    lens = _serve.sizes(tr, len(due), server.max_seq)
    texts = _serve.prompts(ctx.seed, [p for p, _ in lens], ctx.config["vocab"])
    for d, (_, out_len), prompt in zip(due, lens, texts):
        server.submit(prompt, out_len, d)
    server.open_window()
    queue = server.eng.queue
    while True:
        now = server.clock()
        if now >= ctx.seconds:
            break
        if server.step() is None:
            nxt = queue.next_arrival()
            server.wait(min(nxt if nxt is not None else ctx.seconds,
                            ctx.seconds))
    return _serve.outcome(server, server.clock())
