"""Per-layer readings from a traced run's reduction.

Each ``metrics/<name>.py`` file calls one of these.  A reader that finds
nothing to read returns None, and the harness leaves the metric out; a
share of a roofline or a peak is never made up as 0.
"""

from __future__ import annotations


def emugemm_roofline(r):
    """Least time of the emulated sites over the device time of every op
    that belongs to one, in %."""
    least = r.work.get("emugemm_least_s", 0.0)
    if r.emulated_s <= 0 or least <= 0:
        return None
    return 100.0 * least / r.emulated_s


def mfu_gemm(r):
    """int8 operations of the traced calls over the traced window, as a
    share of the int8 peak, in %."""
    ops = r.work.get("int8_ops", 0)
    if ops <= 0 or r.window_s <= 0:
        return None
    return 100.0 * ops / r.window_s / r.peaks.int8_ops_per_s


def mfu_serve(r):
    """Useful model FLOPs of the traced engine steps over their summed
    step time, as a share of the bf16 peak, in %."""
    flops = r.work.get("model_flops", 0)
    step_s = r.span_s.get("step", 0.0)
    if flops <= 0 or step_s <= 0:
        return None
    return 100.0 * flops / step_s / r.peaks.bf16_flops_per_s


def step_ms(r):
    """The traced window over the engine steps in it, in ms."""
    steps = r.span_count.get("step", 0)
    if steps <= 0:
        return None
    return 1e3 * r.window_s / steps


def device_idle(r):
    """Share of the traced window in which no op ran on the device, %."""
    if r.window_s <= 0 or r.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
