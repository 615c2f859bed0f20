"""The DGEMM reference: HPL's operand law from the seed, and sampled rows
of A @ B in ``np.longdouble`` on the host.

HPL (netlib, ``HPL_dmatgen``) fills its matrices with values uniform on
[-0.5, 0.5], the paper's Eq. 19 at phi = 0.  Here each value carries a
full 53-bit significand: a 53-bit uniform draw on a 2^-53 grid, less
one half, plus a second such draw scaled by 2^-53, which fills the bits
below that grid wherever |x| < 0.5 needs them.  (A float32 draw cast to
float64 would leave 29 of them zero, an easier problem for any scheme
that integerizes its operands.)  The operands are made on the host in
IEEE float64, so the same seed gives the same bits on any machine.

The reference product is formed in ``np.longdouble`` (x86 80-bit, 64-bit
significand): each product of two 53-bit values rounds at 2^-64, so over
K = 8192 its error stays far below the 2^-52 a float64 result holds.
"""

from __future__ import annotations

import numpy as np

ULP_GRID = 2.0 ** -53


def hpl_uniform(rng: np.random.Generator, shape) -> np.ndarray:
    """float64 values uniform on [-0.5, 0.5] with full significands."""
    x = rng.random(shape) - 0.5
    x += rng.random(shape) * ULP_GRID
    return x


def operands(rng: np.random.Generator, m: int, k: int, n: int):
    """A (m, k) and B (k, n), float64 on the host."""
    return hpl_uniform(rng, (m, k)), hpl_uniform(rng, (k, n))


def trailing_zero_bits(x: np.ndarray) -> np.ndarray:
    """Zero bits at the bottom of each nonzero value's 53-bit significand
    (52 for an exact power of two)."""
    mant = np.abs(np.asarray(x, np.float64))
    mant = mant[mant > 0]
    frac, _ = np.frexp(mant)                 # frac in [0.5, 1)
    sig = (frac * 2.0 ** 53).astype(np.uint64)
    low = sig & (~sig + np.uint64(1))        # lowest set bit
    return np.log2(low.astype(np.float64)).astype(np.int64)


def rows_product(a_rows: np.ndarray, b: np.ndarray) -> np.ndarray:
    """np.longdouble product of the sampled rows of A with all of B."""
    return a_rows.astype(np.longdouble) @ b.astype(np.longdouble)


def bits(c_rows, ref) -> float:
    """-log2 of the largest error over the sampled rows, relative to the
    largest reference value there."""
    err = np.abs(np.asarray(c_rows, np.longdouble) - ref).max()
    if err == 0:
        return float("inf")
    return float(-np.log2(err / np.abs(ref).max()))
