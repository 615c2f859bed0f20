"""The stages of a Scheme-II GEMM in a profiler trace: encode, the
residue kernel, the CRT.

A Scheme-II lowering names its XLA stages inside its ``emugemm/...``
scope: ``encode`` (integerize and residues) and ``crt`` (canonicalize,
Garner, Horner, unscale), so each op's ``op_name`` in the compiled text
reads ``.../emugemm/<tag>/.../encode/...`` or ``.../crt/...`` (a fusion
with no ``op_name`` of its own takes its called computation's).  The
kernel between them is named ``emugemm2_p<n>``.  Inside the traced
window (the harness's ``window`` span, as in ``trace_reduce``) this sums
the device time of the leaf ops of each stage, averaged over the chips;
an op of none of them (a copy XLA inserts, an op outside the GEMM) is
``other``.

The driver reduces its own trace once it has stopped recording and
hands the sums to the metrics through ``Outcome.work``; a program whose
ops carry no stage scopes reads encode and CRT as 0, and their metrics
as None.
"""

from __future__ import annotations

import dataclasses
import re

from chipbench import trace_reduce as tr
from chipbench.common import WINDOW

STAGES = ("encode", "kernel", "crt")
KERNEL_PREFIX = "emugemm2_"
_STAGE = re.compile(r"emugemm/.*?/(encode|crt)(?:/|$)")
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


@dataclasses.dataclass
class StageReduction:
    window_s: float = 0.0
    busy_s: float = 0.0
    stage_s: dict = dataclasses.field(default_factory=dict)

    def share(self) -> float:
        """Encode, kernel and CRT over all device time in the window."""
        if self.busy_s <= 0:
            return 0.0
        return sum(self.stage_s.get(s, 0.0) for s in STAGES) / self.busy_s


def stage_of_op_name(op_name: str):
    """'encode' or 'crt' for an op_name inside an emulated GEMM's stage
    scope, else None."""
    m = _STAGE.search(op_name)
    return m.group(1) if m else None


def computation_stages(texts) -> dict:
    """Computation name -> the stages its instructions' op_names name."""
    out: dict = {}
    current = None
    for text in texts:
        for line in text.splitlines():
            m = _COMPUTATION.match(line)
            if m:
                current = out.setdefault(m.group(1), set())
                continue
            if line.strip() == "}":
                current = None
            elif current is not None:
                op = tr._OPNAME.search(line)
                stage = stage_of_op_name(op.group(1)) if op else None
                if stage is not None:
                    current.add(stage)
    return out


def stage_heads(texts) -> dict:
    """Head of each instruction -> its stage, from compiled HLO texts.

    An instruction's own ``op_name`` decides.  A fusion that carries
    none (XLA leaves it off a multi-output fusion, whose root is a
    tuple) takes the one stage that the instructions of the computation
    it calls name, if they name exactly one."""
    called = computation_stages(texts)
    out: dict = {}
    for text in texts:
        for line in text.splitlines():
            name, h = tr.head(line)
            if h is None:
                continue
            if name.startswith(KERNEL_PREFIX):
                out[h] = "kernel"
                continue
            op = tr._OPNAME.search(line)
            stage = stage_of_op_name(op.group(1)) if op else None
            if op is None:
                calls = _CALLS.search(line)
                inner = called.get(calls.group(1)) if calls else None
                if inner and len(inner) == 1:
                    stage = next(iter(inner))
            if stage is not None:
                out[h] = stage
    return out


def classify(event_name: str, heads: dict) -> str:
    name, h = tr.head(event_name)
    if (name or event_name).startswith(KERNEL_PREFIX):
        return "kernel"
    return heads.get(h, "other")


def reduce(path, hlo_texts, chips: int = 1) -> StageReduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    windows, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        windows.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
    devices = [p for _, p in sorted(devices, key=lambda x: x[0])][:chips]
    ops = [[o for line in plane.lines if line.name == tr.OPS_LINE
            for o in tr.leaves((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)]
           for plane in devices]
    if windows:
        w0, w1 = windows[0]
    elif any(ops):
        w0 = min(o[0] for chip in ops for o in chip)
        w1 = max(o[1] for chip in ops for o in chip)
    else:
        return StageReduction()
    heads = stage_heads(hlo_texts)
    n = max(1, len(ops))
    red = StageReduction(window_s=(w1 - w0) / 1e9,
                         stage_s={s: 0.0 for s in STAGES + ("other",)})
    for chip_ops in ops:
        clipped = [(max(s, w0), min(e, w1), name) for s, e, name in chip_ops
                   if e > w0 and s < w1]
        red.busy_s += sum(e - s for s, e in tr.union(
            (s, e) for s, e, _ in clipped)) / 1e9 / n
        for s, e, name in clipped:
            red.stage_s[classify(name, heads)] += (e - s) / 1e9 / n
    return red


def reduce_dir(trace_dir, hlo_texts, chips: int = 1) -> StageReduction:
    return reduce(tr.find_xplane(trace_dir), hlo_texts, chips)


# ---------------------------------------------------------------------------
# Readers: what the metric files call, on the harness's Reduction, whose
# ``work`` holds the driver's sums (``calls``, ``<stage>_s``,
# ``kernel_least_s``).
# ---------------------------------------------------------------------------

def _per_call_ms(r, stage: str):
    calls = r.work.get("calls", 0)
    s = r.work.get(f"{stage}_s", 0.0)
    if calls <= 0 or s <= 0:
        return None
    return 1e3 * s / calls


def encode_ms(r):
    """Device time under the ``encode`` scopes per traced call, ms."""
    return _per_call_ms(r, "encode")


def crt_ms(r):
    """Device time under the ``crt`` scopes per traced call, ms."""
    return _per_call_ms(r, "crt")


def kernel_roofline(r):
    """The residue kernel's least time over its device time, in %."""
    least = r.work.get("kernel_least_s", 0.0)
    s = r.work.get("kernel_s", 0.0)
    if least <= 0 or s <= 0:
        return None
    return 100.0 * least / s
