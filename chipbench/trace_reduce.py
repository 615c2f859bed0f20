"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Read with ``jax.profiler.ProfileData``.  The device planes are
``/device:TPU:<n>``; their op line (``XLA Ops``) holds one event per
executed HLO op, with the op's HLO name and module in its stats.  The
host planes (``/host:...``) hold the harness's own spans
(``jax.profiler.TraceAnnotation``), on the same clock.

- busy: the union of the op intervals of each chip inside the traced
  window (the harness's ``window`` span), averaged over the chips;
- emulated time: the device time of every op that belongs to an
  emulated GEMM site: a kernel named ``emugemm1_*``, ``emugemm2_*`` or
  ``decompose_interleave_*``, or an op whose ``op_name`` metadata in the
  compiled program's text carries the ``emugemm/`` scope.  A trace event
  is named by its HLO instruction's text, ``%name = <shape> opcode(...)``;
  its head up to the opcode is looked up in the compiled texts, which
  tells apart two programs that reuse an instruction name.  Only leaf
  events count: a ``while`` op's event spans its body's;
- idle gaps: the stretches of the window with no op, each placed in the
  innermost harness span that holds its midpoint.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re

from chipbench.common import SPANS, WINDOW

KERNEL_PREFIXES = ("emugemm1_", "emugemm2_", "decompose_interleave_")
SCOPE = "emugemm/"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# "%name = <result shape> opcode(" -- the head of an HLO instruction, the
# same in the compiled text and in the name of the op's trace event.
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*?) ([a-z][a-z0-9\-]*)\(")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def head(text: str):
    """(instruction name, head) of an HLO line or a trace event name."""
    m = _HEAD.match(text)
    if m is None:
        return None, None
    h = f"{m.group(1)} {m.group(2)}"
    return h.split(" = ", 1)[0].lstrip("%"), h


def site_of(op_name: str):
    """The ``emugemm/<tag>`` scope of an op_name, or None."""
    i = op_name.find(SCOPE)
    if i < 0:
        return None
    parts = op_name[i:].split("/")
    return "/".join(parts[:2])


def hlo_scopes(texts) -> dict:
    """Head of each instruction -> its emulated site (``emugemm/<tag>``,
    or the kernel's name) or None, from compiled HLO texts."""
    out: dict = {}
    for text in texts:
        for line in text.splitlines():
            name, h = head(line)
            if h is None:
                continue
            op = _OPNAME.search(line)
            site = site_of(op.group(1)) if op else None
            if name.startswith(KERNEL_PREFIXES):
                site = site or name
            out[h] = out.get(h) or site
    return out


def names_an_emulated_site(text: str) -> bool:
    """Whether a compiled text holds an op of an emulated site."""
    return any(hlo_scopes([text]).values())


def classify(event_name: str, scopes: dict):
    """(display name, emulated site or None) of a device op event."""
    name, h = head(event_name)
    if name is None:
        name = event_name
    base = re.sub(r"\.\d+$", "", name) or name
    site = scopes.get(h)
    if name.startswith(KERNEL_PREFIXES):
        return base, site or base
    if site is not None:
        return f"{site}:{base}", site
    return base, None


def leaves(events):
    """The events that hold no other event of their line: a ``while``
    op spans its body's ops, which are on the same line.  Events of no
    duration are dropped."""
    evs = sorted((e for e in events if e[1] > e[0]),
                 key=lambda e: (e[0], -e[1]))
    out = []
    for i, e in enumerate(evs):
        j, inner = i + 1, False
        while j < len(evs) and evs[j][0] < e[1]:
            if evs[j][1] <= e[1]:
                inner = True
                break
            j += 1
        if not inner:
            out.append(e)
    return out


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    emulated_s: float
    op_s: dict                    # op display name -> device seconds
    idle_by_span: dict            # harness span -> idle seconds
    span_count: dict              # harness span -> spans inside the window
    span_s: dict                  # harness span -> their summed seconds
    work: dict = dataclasses.field(default_factory=dict)
    peaks: object = None

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir, hlo_texts, chips: int = 1) -> Reduction:
    return reduce(find_xplane(trace_dir), hlo_texts, chips)


def reduce(path, hlo_texts, chips: int = 1) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scopes = hlo_scopes(hlo_texts)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name == WINDOW:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
    devices = [p for _, p in sorted(devices, key=lambda x: x[0])][:chips]
    ops = []                       # per chip: [(start, end, name, emu)]
    for plane in devices:
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        chip_ops = []
        for line in lines:
            for s, e, name in leaves((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name)
                                     for ev in line.events):
                shown, site = classify(name, scopes)
                chip_ops.append((s, e, shown, site is not None))
        ops.append(chip_ops)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        w0, w1 = windows[0]
    else:
        all_ops = [o for chip in ops for o in chip]
        if not all_ops:
            return Reduction(0.0, 0.0, 0.0, {}, {}, {}, {})
        w0 = min(o[0] for o in all_ops)
        w1 = max(o[1] for o in all_ops)
    inner = [(n, s, e) for n, s, e in spans
             if n != WINDOW and s >= w0 and e <= w1]
    busy = emu = 0.0
    op_s: dict = {}
    idle: dict = {}
    for i, chip_ops in enumerate(ops):
        clipped = [(max(s, w0), min(e, w1), n, em) for s, e, n, em in chip_ops
                   if e > w0 and s < w1]
        merged = union((s, e) for s, e, _, _ in clipped)
        busy += sum(e - s for s, e in merged) / 1e9
        for s, e, n, em in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9 / len(ops)
            if em:
                emu += (e - s) / 1e9
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    label = span_at((a + b) / 2, inner)
                    idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    n_chips = max(1, len(ops))
    count: dict = {}
    total: dict = {}
    for n, s, e in inner:
        count[n] = count.get(n, 0) + 1
        total[n] = total.get(n, 0.0) + (e - s) / 1e9
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy / n_chips,
                     emulated_s=emu / n_chips, op_s=op_s, idle_by_span=idle,
                     span_count=count, span_s=total)


def span_at(t: float, spans) -> str:
    """The innermost (shortest) span holding time ``t``, or 'none'."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "none"
