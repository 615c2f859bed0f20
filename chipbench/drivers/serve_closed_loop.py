"""Closed loop: a fixed number of clients, each sending its next request
as soon as its previous one is answered.

Each client works through its own list of (prompt, output) lengths from
the traffic file, the same list for every seed, and starts it again when
it is through; the seed draws the prompts and the weights."""

from __future__ import annotations

from chipbench.drivers import _serve


def run(ctx):
    server = _serve.Server(ctx)
    tr = ctx.traffic
    clients, per = int(tr["clients"]), int(tr["requests_per_client"])
    lens = _serve.sizes(tr, clients * per, server.max_seq)
    texts = _serve.prompts(ctx.seed, [p for p, _ in lens], ctx.config["vocab"])
    todo = [[(texts[c + j * clients], lens[c + j * clients][1])
             for j in range(per)] for c in range(clients)]
    server.open_window()
    sent = [0] * clients

    def send(c, t):
        sent[c] += 1
        return server.submit(*todo[c][(sent[c] - 1) % per], t)
    current = [send(c, 0.0) for c in range(clients)]
    while True:
        now = server.clock()
        if now >= ctx.seconds:
            break
        server.step()
        t = server.clock()
        for c, tracked in enumerate(current):
            if tracked.state.status in ("done", "failed"):
                current[c] = send(c, t)
    return _serve.outcome(server, server.clock())
