"""The GEMM reference: sampled rows of A @ B in float64 on the host."""

from __future__ import annotations

import numpy as np


def conditioned(key, shape, phi: float):
    """Paper Eq. 19 operands, (u - 0.5) exp(phi g) with u uniform and g
    normal, in float32 on the device."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, shape, jnp.float32)
    g = jax.random.normal(k2, shape, jnp.float32)
    return (u - 0.5) * jnp.exp(phi * g)


def operands(key, m: int, k: int, n: int, phi: float):
    """A (m, k) and B (k, n) from one key; jit it to make both in one
    call on the device."""
    import jax
    return (conditioned(jax.random.fold_in(key, 0), (m, k), phi),
            conditioned(jax.random.fold_in(key, 1), (k, n), phi))


def sample_rows(rng: np.random.Generator, m: int, count: int) -> np.ndarray:
    return np.sort(rng.choice(m, size=min(count, m), replace=False))


def rows_product(a_rows: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float64 product of the sampled rows of A with all of B."""
    return a_rows.astype(np.float64) @ b.astype(np.float64)


def normalized_error(c_rows, ref, a_rows, b) -> float:
    """max_ij |C - R|_ij / (K rowmax_i|A| colmax_j|B|) over the sampled
    rows: the error of each element against the largest it could hold,
    which is what a fixed-point slice decomposition bounds."""
    k = a_rows.shape[1]
    rmax = np.abs(a_rows).max(axis=1).astype(np.float64)
    cmax = np.abs(b).max(axis=0).astype(np.float64)
    denom = k * rmax[:, None] * cmax[None, :]
    diff = np.abs(np.asarray(c_rows, np.float64) - ref)
    return float((diff / denom).max())
