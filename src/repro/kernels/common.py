"""Shared utilities for the EmuGEMM Pallas TPU kernels.

Hardware model (TPU v5e target):
  * MXU systolic array: 128x128, int8 x int8 -> int32 exact.
  * VMEM ~16 MiB/core staging both operand blocks (double-buffered by the
    Pallas pipeline) and the p int32 accumulators (Scheme I).
  * int8 VMEM tiling (32, 128): block dims multiples of (32, 128)-friendly
    sizes; we keep everything 128-aligned for the MXU.

``choose_blocks`` is the TPU analogue of the paper's Eq. 12 resource budget:
  Acc^(p) = 4 p bM bN     (int32 accumulators, VMEM scratch)
  S_op    = 2 p (bM+bN) bK  (double-buffered int8 operand blocks)
  S_epi   = out_bytes bM bN
all of which must fit the per-core VMEM budget; larger tiles raise the
MXU pipeline depth (the omega of Fig. 1(c)) until the budget binds.

With the in-kernel decomposition prologue (``prologue_a`` / ``prologue_b``)
an operand side stages the *fp32* tile instead of the p int8 slices, and
the slices it carves live in VMEM alongside it:

  S_op(side) = 2 * 4 dim bK   (double-buffered fp32 block)
             + 4 dim bK       (fp32 remainder of the truncate-subtract chain)
             + p dim bK       (the carved int8 slices)

Traffic-wise this swaps the Eq. 10 operand term p*dim*K for 4*dim*K *and*
deletes the decomposition round-trips entirely (the split's (p, M, K)
write, the interleave's read+write, and the scale pass's extra fp32 read
— the decomposition-side bytes that Eqs. 9/10 never charged; see
repro.core.traffic.scheme1_decomp_*_bytes).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Blocks:
    bm: int
    bn: int
    bk: int

    def aligned(self, m: int, n: int, k: int) -> bool:
        return m % self.bm == 0 and n % self.bn == 0 and k % self.bk == 0


# Per-core VMEM we allow the kernel to claim (leave headroom of the 16 MiB).
VMEM_BUDGET = 12 * 2**20


def _operand_stage_bytes(dim: int, bk: int, p: int, prologue: bool) -> int:
    """VMEM bytes one operand side stages per K-step (see module doc)."""
    if prologue:
        # double-buffered fp32 block + fp32 remainder + carved int8 slices
        return (2 * 4 + 4 + p) * dim * bk
    return 2 * p * dim * bk  # double-buffered pre-interleaved int8 block


def wide_candidates(dim: int) -> tuple[int, ...]:
    """Block sizes above 128 for a dimension 128 divides and 256 does not.

    Such a dimension takes no block from (512, 256): with 128 alone an
    odd multiple of 128 (the 17 x 128 key view of an attention step) runs
    one 128-wide grid step per lane tile.  Its candidates are then every
    other multiple of 128 that divides it, largest first, the whole
    dimension included; a dimension 256 divides gets none.
    """
    if dim % 128 or not dim % 256:
        return ()
    return tuple(b for b in range(dim, 128, -128) if not dim % b)


def wide_dims(blocks: Blocks, m: int, n: int, k: int,
              fixed_bk: int | None = None) -> tuple[str, ...]:
    """The dims ('m', 'n', 'k') whose block came from ``wide_candidates``;
    a pinned bK came from the pin."""
    picked = [("m", blocks.bm, m), ("n", blocks.bn, n)]
    if fixed_bk is None:
        picked.append(("k", blocks.bk, k))
    return tuple(name for name, b, dim in picked if b in wide_candidates(dim))


def choose_blocks(m: int, n: int, k: int, p: int,
                  out_bytes: int = 4,
                  vmem_budget: int = VMEM_BUDGET,
                  prologue_a: bool = False,
                  prologue_b: bool = False,
                  fixed_bk: int | None = None) -> Blocks | None:
    """Largest 128-aligned blocks whose working set fits VMEM.

    Preference order: maximize bM*bN (accumulator tile = MXU work per
    operand byte), then bK (pipeline depth). Mirrors paper Eq. 12's
    alpha_max trade-off: higher p forces smaller tiles.

    ``prologue_a`` / ``prologue_b`` switch that side's operand budget to
    the fp32-staging model of the in-kernel decomposition prologue.
    ``fixed_bk`` pins the K block — required when consuming a
    PreparedOperand whose interleave granularity was already chosen.

    Mosaic takes a block dimension that is a multiple of 128 lanes or
    the whole array dimension, and bK is the lane dimension of the A
    block: a K that 128 does not divide but the int8 sublane tile (32)
    does is taken whole.

    A dimension 128 divides and 256 does not also tries its
    ``wide_candidates``: the accumulators are int32, so any block is
    bit-identical, and only the VMEM model and the preference decide.
    """
    best: tuple[tuple[int, int], Blocks] | None = None
    if fixed_bk is not None:
        bk_candidates = (fixed_bk,)
    elif k % 128:
        bk_candidates = () if k % 32 else (k,)
    else:
        bk_candidates = wide_candidates(k) + (512, 256, 128)
    bn_candidates = wide_candidates(n) + (512, 256, 128)
    for bm in wide_candidates(m) + (512, 256, 128, 64, 32):
        if m % bm:
            continue
        for bn in bn_candidates:
            if n % bn:
                continue
            for bk in bk_candidates:
                if k % bk:
                    continue
                acc = 4 * p * bm * bn
                s_op = (_operand_stage_bytes(bm, bk, p, prologue_a)
                        + _operand_stage_bytes(bn, bk, p, prologue_b))
                s_epi = out_bytes * bm * bn
                if acc + s_op + s_epi > vmem_budget:
                    continue
                key = (bm * bn, bk)
                if best is None or key > best[0]:
                    best = (key, Blocks(bm, bn, bk))
    return best[1] if best else None


def carve_slices(r: jax.Array, p: int, beta: int):
    """Yield the p signed int8 beta-bit slices of ``r`` (already divided
    by its power-of-two scale) via iterated truncate-and-subtract.

    Every step is elementwise and exact in floating point (power-of-two
    shift, trunc, exact fractional remainder), so a tile-local run inside
    a kernel is bit-identical to the full-array ``scheme1.split``
    restricted to that tile.  This is the ONE in-kernel copy of the
    recurrence — the matmul prologue (ozaki1) and the decompose kernels
    both consume it, so the bit-identity the tests and the CI traffic
    gate assert can only drift in one place.
    """
    two_beta = float(2 ** beta)
    for _ in range(p):
        shifted = r * two_beta            # exact power-of-two shift
        s = jnp.trunc(shifted)            # |s| <= 2^beta - 1
        yield s.astype(jnp.int8)
        r = shifted - s                   # exact fractional remainder


def _mod_from(x: jax.Array, m, low, inv) -> jax.Array:
    """int32 ``x`` mod ``m`` into [-low, m - 1 - low], with the quotient
    estimated as floor(r * ``inv``) in float32 (``inv`` is 1/m rounded to
    float32); see :func:`balanced_mod`."""
    r = x + low
    for _ in range(2):
        q = jnp.floor(r.astype(jnp.float32) * inv).astype(jnp.int32)
        r = r - q * m
    return jnp.where(r >= m, r - m, r) - low


def balanced_mod(x: jax.Array, m: jax.Array) -> jax.Array:
    """int32 ``x`` mod ``m`` in balanced form [-(m//2), m - 1 - m//2].

    The VPU has no integer divider: an int32 remainder lowers to a long
    sequence, which took about nine tenths of a Scheme-II residue
    GEMM's time on a TPU v5e.  The quotient is estimated in
    float32 instead, and the remainder taken in int32, which is exact.
    The first estimate errs by under 2^10 / m, so its remainder is
    below 2^10 + 2m in size.  The second quotient of that remainder is
    exact unless the remainder is a whole multiple of m, where it may
    land one low and leave r = m, which the last select takes off.  An
    int32 product that wraps wraps modulo 2^32, and the true remainder
    fits, so the wrapped difference is exact.

    Written with int32 ``lax`` ops only: ``jnp.remainder`` and ``//`` on
    a kernel scalar promote through 64-bit integers when x64 is enabled,
    which the Mosaic lowering refuses.
    """
    return _mod_from(x, m, jax.lax.div(m, jnp.int32(2)),
                     1.0 / m.astype(jnp.float32))


def garner_mod(x: jax.Array, m: int) -> jax.Array:
    """int32 ``x`` mod the constant ``m`` in Garner's balanced form
    [-(m - 1 - m//2), m//2]: :func:`balanced_mod`'s range for an odd m,
    and its mirror for an even one (m/2 stays +m/2).

    A power of two takes the low bits of ``x`` (two's complement) and
    one select; any other modulus one float32-quotient remainder, exact
    for every int32 as :func:`balanced_mod` is.  The constants are
    Python scalars, so nothing promotes to 64 bits under x64.
    """
    if not m & (m - 1):
        t = x & (m - 1)
        return jnp.where(t > m // 2, t - m, t)
    return _mod_from(x, m, m - 1 - m // 2, np.float32(1) / np.float32(m))


@functools.cache
def interpret() -> bool:
    """Whether the Mosaic TPU kernels run in Pallas interpret mode.

    Compiled on a TPU, interpreted on the CPU (the test path).  Any other
    platform is an error: the Mosaic kernels lower for neither, and
    interpreting them there would hide that the kernel never ran.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"the Mosaic TPU kernels compile for 'tpu' and "
                       f"interpret on 'cpu'; this jax platform is "
                       f"{platform!r}")


def mma_pipeline_depth(blocks: Blocks, p: int, scheme: int) -> int:
    """Effective MMA instructions per K-step (paper Eq. 13 analogue).

    On TPU the '128x128x128 MXU pass' stands in for one MMA. Scheme I's
    triangular schedule multiplies the per-K-step count by p(p+1)/2.
    """
    per_dot = (blocks.bm // 128) * (blocks.bn // 128) * max(1, blocks.bk // 128)
    tri = p * (p + 1) // 2 if scheme == 1 else 1
    return per_dot * tri
