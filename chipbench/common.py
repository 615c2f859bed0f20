"""Helpers shared by the drivers: seeds, host spans, percentiles."""

from __future__ import annotations

import contextlib
import math

import numpy as np

# Names of the harness's own host spans.  The trace reduction places the
# device's idle gaps in them.
WINDOW = "window"
SPANS = ("call", "step", "plan", "execute", "commit", "await_arrival")


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (the driver's seeds pass
    2**31)."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def rng_of(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one named use of the seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def key_of(seed: int, stream: int = 0):
    """A JAX PRNG key for one named use of the seed."""
    import jax
    import jax.numpy as jnp
    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed), jnp.uint32))
    return jax.random.fold_in(key, stream)


class Spans:
    """Host spans written into the profiler's trace while ``on``.

    ``jax.profiler.TraceAnnotation`` puts a span on the host plane of
    the trace, on the same clock as the device's events."""

    def __init__(self, on: bool = False):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap(self, fn, name: str):
        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), nearest rank: a value that was
    measured, at least q% of the sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[rank - 1])


# The fixed orders in which ``fixed_quantiles`` hands out its values.
# Each quantity of an open loop has its own: in one shared order the
# longest prompt would always follow the longest gap between arrivals.
SIZE_ORDER = 20261017
ARRIVAL_ORDER = 20261018


def fixed_quantiles(n: int, ppf, order: int) -> np.ndarray:
    """n draws that do not depend on the seed: the distribution's values
    at the midpoints (i + 0.5) / n, shuffled in the fixed ``order``.
    Every seed then gets the same sizes and arrivals, so the seed changes
    the tokens and the weights and not the amount of work."""
    u = (np.arange(n) + 0.5) / n
    vals = np.asarray([ppf(x) for x in u])
    return vals[np.random.default_rng(order).permutation(n)]
