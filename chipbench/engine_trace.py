"""The serving engine's own spans and KV scopes in a profiler trace.

The engine writes host spans named ``serve.*`` (and ``repro.gc`` for a
garbage collection) and runs its KV gather and scatter under the
``serve/kv_gather`` and ``serve/kv_scatter`` scopes.  Inside the traced
window (the harness's ``window`` span, as in ``trace_reduce``) this
reads:

- how many engine spans there are and how long they last;
- chip 0's idle time inside ``serve.step`` spans, and chip 0's idle
  stretches placed in the innermost engine span;
- the device time of the ops under the KV scopes, found by instruction
  head in the compiled texts as ``trace_reduce.hlo_scopes`` finds the
  emulated sites, averaged over the chips.

A program that writes none of these reads as empty, and its metrics as
None.

A metric file is handed only ``trace_reduce.Reduction``, which holds
none of this and no path to the trace.  ``of_this_run`` takes the trace
directory and the compiled texts from the harness's ``traced_metrics``
call that is reading the metric, and reduces that trace once.
"""

from __future__ import annotations

import dataclasses
import sys

from chipbench import trace_reduce as tr
from chipbench.common import WINDOW

SPAN_PREFIXES = ("serve.", "repro.")
STEP = "serve.step"
DISPATCH = "serve.dispatch"
KV_SCOPES = ("serve/kv_gather", "serve/kv_scatter")


@dataclasses.dataclass
class EngineReduction:
    window_s: float = 0.0
    busy_s: float = 0.0           # chip 0's ops inside the window
    kv_copy_s: float = 0.0        # device seconds under the KV scopes
    span_count: dict = dataclasses.field(default_factory=dict)
    span_s: dict = dataclasses.field(default_factory=dict)
    host_gap_s: float = 0.0       # chip 0 idle inside serve.step spans
    idle_by_span: dict = dataclasses.field(default_factory=dict)


def kv_heads(texts) -> set:
    """Heads of the instructions whose ``op_name`` carries a KV scope."""
    out = set()
    for text in texts:
        for line in text.splitlines():
            op = tr._OPNAME.search(line)
            if op is None or not any(k in op.group(1) for k in KV_SCOPES):
                continue
            _, h = tr.head(line)
            if h is not None:
                out.add(h)
    return out


def segments(spans, w0, w1):
    """[w0, w1] cut into (start, end, innermost span) pieces, in order;
    'none' where no span is open.  Spans nest or do not overlap."""
    pts = sorted({w0, w1} | {x for _, s, e in spans for x in (s, e)
                             if w0 < x < w1})
    todo = sorted(spans, key=lambda x: x[1])
    active, j, out = [], 0, []
    for a, b in zip(pts, pts[1:]):
        active = [sp for sp in active if sp[2] > a]
        while j < len(todo) and todo[j][1] <= a:
            if todo[j][2] > a:
                active.append(todo[j])
            j += 1
        inner = min(active, key=lambda sp: sp[2] - sp[1], default=None)
        out.append((a, b, inner[0] if inner else "none"))
    return out


def overlap_by_label(gaps, pieces) -> dict:
    """Seconds of ``gaps`` (sorted (start, end)) that fall in each label
    of ``pieces`` (sorted, disjoint (start, end, label))."""
    out: dict = {}
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        a, b = gaps[i]
        s, e, label = pieces[j]
        lo, hi = max(a, s), min(b, e)
        if hi > lo:
            out[label] = out.get(label, 0.0) + (hi - lo) / 1e9
        if b <= e:
            i += 1
        else:
            j += 1
    return out


def reduce(path, hlo_texts, chips: int = 1) -> EngineReduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, windows, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == WINDOW:
                        windows.append(iv)
                    elif ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, *iv))
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
    devices = [p for _, p in sorted(devices, key=lambda x: x[0])][:chips]
    ops = []                       # per chip: [(start, end, name)]
    for plane in devices:
        ops.append([o for line in plane.lines if line.name == tr.OPS_LINE
                    for o in tr.leaves((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name) for ev in line.events)])
    if windows:
        w0, w1 = windows[0]
    elif any(ops):
        w0 = min(o[0] for chip in ops for o in chip)
        w1 = max(o[1] for chip in ops for o in chip)
    else:
        return EngineReduction()
    red = EngineReduction(window_s=(w1 - w0) / 1e9)
    spans = [(n, s, e) for n, s, e in spans if s >= w0 and e <= w1]
    for n, s, e in spans:
        red.span_count[n] = red.span_count.get(n, 0) + 1
        red.span_s[n] = red.span_s.get(n, 0.0) + (e - s) / 1e9
    heads = kv_heads(hlo_texts)
    kv = 0.0
    for i, chip_ops in enumerate(ops):
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in chip_ops
                   if e > w0 and s < w1]
        kv += sum(e - s for s, e, n in clipped
                  if tr.head(n)[1] in heads) / 1e9
        if i:
            continue
        merged = tr.union((s, e) for s, e, _ in clipped)
        red.busy_s = sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        red.idle_by_span = overlap_by_label(gaps, segments(spans, w0, w1))
        steps = sorted((s, e, n) for n, s, e in spans if n == STEP)
        red.host_gap_s = overlap_by_label(gaps, steps).get(STEP, 0.0)
    red.kv_copy_s = kv / max(1, len(ops))
    return red


def kv_copy_ms(r: EngineReduction):
    """Device time of the ops under the engine's KV gather and scatter
    scopes, per ``serve.dispatch`` span in the traced window, in ms."""
    steps = r.span_count.get(DISPATCH, 0)
    if steps <= 0 or r.kv_copy_s <= 0:
        return None
    return 1e3 * r.kv_copy_s / steps


def host_gap_ms(r: EngineReduction):
    """Chip 0's idle time inside the engine's ``serve.step`` spans, per
    ``serve.dispatch`` span in the traced window, in ms."""
    steps = r.span_count.get(DISPATCH, 0)
    if steps <= 0 or r.busy_s <= 0:
        return None
    return 1e3 * r.host_gap_s / steps


_reduced: dict = {}


def of_this_run():
    """The engine's reduction of the trace the harness is reading, or
    None outside a traced run (no trace directory, no trace in it).

    Found on the call stack: the frame of ``harness.traced_metrics``
    holds the run's ``Context`` (``trace_dir``, ``devices``) and the
    driver's ``Outcome`` (``hlo_texts``)."""
    frame = sys._getframe(1)
    while frame is not None:
        ctx, out = frame.f_locals.get("ctx"), frame.f_locals.get("out")
        if (getattr(ctx, "trace_dir", None) is not None
                and hasattr(out, "hlo_texts")):
            break
        frame = frame.f_back
    else:
        return None
    try:
        path = tr.find_xplane(ctx.trace_dir)
    except FileNotFoundError:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _reduced:
        _reduced[key] = reduce(path, out.hlo_texts, len(ctx.devices))
    return _reduced[key]


def read_kv_copy_ms(_):
    r = of_this_run()
    return None if r is None else kv_copy_ms(r)


def read_host_gap_ms(_):
    r = of_this_run()
    return None if r is None else host_gap_ms(r)
