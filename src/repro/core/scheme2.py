"""Ozaki Scheme II: CRT modular-arithmetic emulated GEMM.

Pipeline (paper Sec. II-C2):
  1. scale operands to integers A' = trunc(diag(mu) A) (power-of-two mu),
  2. residues A'_l = A' mod m_l for p pairwise-coprime moduli m_l <= 256,
  3. one exact int8 GEMM per modulus: ~C_l = A'_l B'_l (int32),
  4. modular reduction C'_l = ~C_l mod m_l  (the paper fuses this into the
     GEMM epilogue — here the XLA reference; Pallas kernel in kernels/ozaki2),
  5. CRT reconstruction of C' = A'B' and inverse scaling.

TPU adaptation (DESIGN.md Sec. 2): residues are stored in *balanced* form
r_bal = ((r + m//2) mod m) - m//2 in [-128, 127] so they fit the signed-int8
MXU path (TPU has no unsigned-int8 matmul). Congruence mod m is preserved, so
the CRT is unchanged; |r_bal| <= 128 keeps K <= (2^31 - 1) / 2^14 = 131071
exact (``check_exact_k`` enforces the bound on every pipeline).

CRT reconstruction uses Garner's mixed-radix algorithm: balanced digits
|d_i| <= m_i/2 are computed in exact int32 arithmetic, then the mixed-radix
polynomial x = d_0 + m_0 (d_1 + m_1 (...)) is evaluated in double-double
(~106 mantissa bits) — enough to round a <=120-bit integer to FP64 —
replacing the paper's multi-word-integer CRT kernel.  Garner's chain for
digit i, t <- (t - d_j) inv(m_j, m_i) mod m_i for each j < i, is folded into
one congruence d_i = r_i C[i,0] - sum_{j<i} d_j C[i,j] (mod m_i) with
constant C[i,j] < m_i (``garner_folded``): O(p^2) int32 multiply-adds whose
sum stays under 2^19 for int8 residues, and one float32-quotient remainder
per digit (``kernels.common.garner_mod``) where the chain took i.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import (EmulationAccuracyError, EmulationConfig,
                                  scheme2_budget)
from repro.core import dd
from repro.core.scheme1 import exact_pow2, frexp_exponent
from repro.telemetry import record as _rec
from repro.telemetry.trace import CRT, ENCODE, stage_scope

# Outputs reconstructed at once by ``crt_unscale``: larger products run
# their CRT over blocks of rows, so the digit planes and the
# double-double temporaries of one block, not of the whole (M, N)
# result, are live at a time.
CRT_BLOCK = 1 << 20


def _pow2_int_scale(a: jax.Array, axis: int, budget_bits: int) -> jax.Array:
    """Power-of-two mu per row/col s.t. |trunc(mu * a)| < 2^budget_bits.

    mu * amax in [2^(budget-1), 2^budget).  The exponent is built exactly
    (see :func:`repro.core.scheme1.exact_pow2` — jnp.exp2 is inexact at
    large exponents) and clamped below the dtype's overflow point:
    subnormal-only rows, whose exact mu (up to 2^(budget + 149) in fp32)
    is unrepresentable, get the largest finite power-of-two scale and
    integerize to exact zeros — a documented graceful flush, where the
    old exp2 path produced an inf scale and int-wraparound garbage.
    """
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    exp = frexp_exponent(jnp.where(amax == 0, 1.0, amax))
    info = jnp.finfo(a.dtype)
    e = jnp.minimum(budget_bits - exp, info.maxexp - 1)
    return exact_pow2(e, a.dtype)


def operand_budget(moduli, k_dim: int, dtype,
                   complex_guard: bool = False) -> int:
    """Bits each operand is integerized to: the moduli's CRT budget
    (:func:`scheme2_budget`) capped by the operand's mantissa.  Recorded
    as the ``repro_scheme2_budget_bits`` gauge (labels ``moduli``,
    ``dtype``) when telemetry is on: 52 for float64 at 16 moduli and
    K = 8192, 8 for bfloat16.  Beside it goes the
    ``repro_scheme2_crt_remainders`` gauge (label ``moduli``), the
    remainders per output the plan's CRT takes (:func:`crt_remainders`).
    Call it where the plan is made on every trace, not inside a jitted
    body whose trace may be cached."""
    bits = min(scheme2_budget(moduli, k_dim, complex_guard=complex_guard),
               jnp.finfo(dtype).nmant + 1)
    _rec.record_scheme2_budget(len(moduli), dtype, bits)
    _rec.record_scheme2_crt_remainders(len(moduli), crt_remainders(moduli))
    return bits


def integerize(a: jax.Array, axis: int, budget_bits: int):
    """A' = trunc(diag(mu) A). Returns (a_int (float, exact integer), mu)."""
    mu = _pow2_int_scale(a, axis, budget_bits)
    return jnp.trunc(a * mu), mu


def balanced_residues(a_int: jax.Array, moduli) -> jax.Array:
    """Residues of an exact-integer float array, balanced to [-m//2, ...].

    Returns (p, *a.shape) int8. Works on float inputs holding exact integers
    up to 2^52 (float64) / 2^23 (float32) by reducing via float remainder,
    which is exact for power-of-2-scaled integers within the mantissa.

    Moduli must be <= 256: the balanced form is the int8 representation
    every pipeline here (XLA reference, Mosaic and GPU kernels) carries,
    and a wider modulus would silently wrap in the cast.
    """
    oversized = [int(m) for m in moduli if int(m) > 256]
    if oversized:
        raise ValueError(
            f"moduli {oversized} exceed 256: balanced residues must fit "
            "int8 (DESIGN.md Sec. 2) — no backend lowers wider moduli")
    outs = []
    # Use the widest available int type for the exact mod.
    use_i64 = jax.config.jax_enable_x64 and a_int.dtype == jnp.float64
    int_t = jnp.int64 if use_i64 else jnp.int32
    ai = a_int.astype(int_t)
    for m in moduli:
        half = m // 2
        r = jnp.remainder(ai + half, m) - half  # balanced, in [-half, m-1-half]
        outs.append(r.astype(jnp.int8))
    res = jnp.stack(outs)
    # Lazy: the guard subsystem is optional on this hot path.
    from repro.guard.inject import maybe_corrupt_residues
    return maybe_corrupt_residues(res)


def check_exact_k(k_dim: int, moduli) -> None:
    """Refuse contraction lengths whose int32 residue accumulation could
    wrap: a K-long dot of balanced residues is bounded by
    K * (max m // 2)^2, which must stay below 2^31 (module doc: K <=
    131071 at m = 256).  Applies to every Scheme-II pipeline — the XLA
    reference, the Mosaic kernels and the fused GPU lowering share the
    same int32 accumulators."""
    half = max(int(m) for m in moduli) // 2
    if k_dim * half * half >= 2 ** 31:
        # >=: int32 tops out at 2^31 - 1, and the all-(-half)^2 worst
        # case reaches exactly K * half^2.
        k_max = (2 ** 31 - 1) // (half * half)
        raise EmulationAccuracyError(
            f"Scheme II: K={k_dim} can overflow the int32 residue "
            f"accumulators (bound K * {half}^2 < 2^31, i.e. K <= "
            f"{k_max} for these moduli). Remediation: re-plan with a "
            f"'bits=<N>:k{k_dim}' spec so plan_precision budgets the "
            "moduli for this contraction length, or shard the "
            "contraction (repro.dot_general with a K-sharded mesh "
            f"splits K across devices) so each shard stays <= {k_max}.")


def _int8_dot(a8: jax.Array, b8: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a8, b8, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def residue_gemms(a_res: jax.Array, b_res: jax.Array) -> jax.Array:
    """Paper Eq. 6: ~C_l = A'_l B'_l, one exact int8 GEMM per modulus."""
    return jax.vmap(_int8_dot)(a_res, b_res)


def modular_reduce(acc: jax.Array, moduli) -> jax.Array:
    """Paper Eq. 7: C'_l = ~C_l mod m_l, elementwise, into [0, m_l)."""
    outs = []
    for l, m in enumerate(moduli):
        outs.append(jnp.remainder(acc[l], m).astype(jnp.int32))
    return jnp.stack(outs)


@lru_cache(maxsize=None)
def garner_constants(moduli: tuple[int, ...]):
    """inv[m_j mod m_i] table for Garner's algorithm (python ints)."""
    p = len(moduli)
    inv = np.zeros((p, p), dtype=np.int32)
    for i in range(p):
        for j in range(i):
            inv[i, j] = pow(moduli[j], -1, moduli[i])
    return inv


@lru_cache(maxsize=None)
def garner_folded(moduli: tuple[int, ...]) -> tuple[tuple[int, ...], ...]:
    """Garner's chain folded per digit (python ints): row i holds
    C[i,j] = prod_{k=j}^{i-1} inv(m_k, m_i) mod m_i for j < i, so that
    d_i = r_i C[i,0] - sum_{j<i} d_j C[i,j] (mod m_i)."""
    inv = garner_constants(moduli)
    rows = []
    for i, m in enumerate(moduli):
        row, c = [], 1
        for j in range(i - 1, -1, -1):
            c = c * int(inv[i, j]) % m
            row.append(c)
        rows.append(tuple(reversed(row)))
    return tuple(rows)


def crt_remainders(moduli) -> int:
    """Remainders per output that :func:`garner_digits` takes: one per
    digit whose modulus is not a power of two (5 at the default 6
    moduli, 15 at 16), where Garner's chain took p (p - 1) / 2."""
    return sum(1 for m in moduli if int(m) & (int(m) - 1))


def garner_digits(residues: jax.Array, moduli) -> list[jax.Array]:
    """*Balanced* mixed-radix digits d_i in [-(m_i - 1 - m_i//2), m_i//2]
    with x = d_0 + m_0 (d_1 + m_1 (d_2 + ...)), all exact int32 arithmetic.
    ``residues``: (p, M, N) integers in any representative of their class
    (canonical [0, m_l), the kernels' balanced int8, or int32 sums of
    such) with |r_l| < 2^23.

    Each digit is one congruence of Garner's chain folded
    (:func:`garner_folded`): v_i = r_i C[i,0] - sum_{j<i} d_j C[i,j], with
    C[i,j] < m_i <= 256 and |d_j| <= 128, so |v_i| < 2^19 for int8
    residues (2^20 for canonical ones) and int32 holds it exactly; one
    float32-quotient remainder (``kernels.common.garner_mod``; a mask
    for a power of two) then takes v_i into Garner's balanced range.
    The digits are those of the chain, digit for digit.

    Balanced digits make the mixed-radix value itself the *centered*
    representative in (-P/2, P/2]: a small |x| has (near-)zero high digits,
    so the downstream double-double Horner evaluation never sees magnitudes
    near P and needs no final mod-P subtraction — the classic catastrophic
    cancellation of 'evaluate then subtract P' disappears. This is the TPU
    (no int128) analogue of the paper's multi-word CRT kernel.
    """
    # Lazy: repro.kernels pulls in Pallas, which importing this module
    # must not.
    from repro.kernels.common import garner_mod
    moduli = tuple(int(m) for m in moduli)
    coef = garner_folded(moduli)
    digits: list[jax.Array] = []
    for i, m in enumerate(moduli):
        v = residues[i].astype(jnp.int32)
        if i:
            v = v * coef[i][0]
        for j in range(i):
            v = v - digits[j] * coef[i][j]
        digits.append(garner_mod(v, m))
    return digits


def mixed_radix_to_dd(digits: list[jax.Array], moduli) -> tuple[jax.Array, jax.Array]:
    """Evaluate the balanced mixed-radix polynomial in double-double (Horner).

    With balanced digits the intermediate Horner values stay at the magnitude
    of the final (centered) result, so ~2x-mantissa double-double precision is
    what bounds the evaluation error — not log2(P).
    """
    p = len(digits)
    hi = digits[p - 1].astype(jnp.float64 if jax.config.jax_enable_x64
                              else jnp.float32)
    lo = jnp.zeros_like(hi)
    for i in range(p - 2, -1, -1):
        hi, lo = dd.mul_scalar(hi, lo, moduli[i])
        hi, lo = dd.add_scalar_array(hi, lo, digits[i].astype(hi.dtype))
    return hi, lo


def canonical_residues(res: jax.Array, moduli) -> jax.Array:
    """Residues (p, M, N) in any form (balanced int8, or int32 sums) ->
    canonical [0, m_l) int32, one broadcast remainder against the
    constant moduli."""
    mods = jnp.asarray(moduli, jnp.int32).reshape(-1, 1, 1)
    return jnp.remainder(res.astype(jnp.int32), mods)


def crt_block_rows(m: int, n: int) -> int:
    """Rows of C that :func:`crt_unscale` reconstructs at once: all M
    while M N <= CRT_BLOCK, else the largest divisor of M whose rows hold
    at most CRT_BLOCK outputs (one row at the least)."""
    if m * n <= CRT_BLOCK:
        return m
    cap = max(1, min(m, CRT_BLOCK // n))
    return next(r for r in range(cap, 0, -1) if m % r == 0)


def crt_unscale(res: jax.Array, moduli, out_dtype, mu: jax.Array,
                nu: jax.Array) -> jax.Array:
    """C = CRT(res) / (mu nu): the residues (p, M, N) of C' = A'B' back to
    the emulated product, as ``out_dtype``.

    The residues may be in any form :func:`garner_digits` takes (the
    Mosaic kernels' balanced int8 go in as they are).  Above
    CRT_BLOCK outputs the rows run in blocks of :func:`crt_block_rows`
    (``lax.map``), which bounds the live temporaries; each block computes
    exactly what the unblocked form computes for its rows.
    """
    moduli = tuple(int(m) for m in moduli)
    _, m, n = res.shape
    nu_t = nu.astype(out_dtype)

    def rows(r, mu_r):
        c = crt_reconstruct(r, moduli, out_dtype)
        return c / (mu_r.astype(out_dtype) * nu_t)

    step = crt_block_rows(m, n)
    if step == m:
        return rows(res, mu)

    def block(i):
        lo = i * step
        return rows(jax.lax.dynamic_slice_in_dim(res, lo, step, axis=1),
                    jax.lax.dynamic_slice_in_dim(mu, lo, step, axis=0))
    return jax.lax.map(block, jnp.arange(m // step)).reshape(m, n)


def crt_reconstruct(residues: jax.Array, moduli, out_dtype) -> jax.Array:
    """Signed CRT via balanced Garner digits: returns the centered
    representative in (-P/2, P/2] as ``out_dtype``.  ``residues`` in any
    form :func:`garner_digits` takes.

    Exact provided 2 sum_h |a'_ih||b'_hj| < P (paper Eq. 8 condition).
    """
    moduli = tuple(int(m) for m in moduli)
    digits = garner_digits(residues, moduli)
    hi, lo = mixed_radix_to_dd(digits, moduli)
    return (hi.astype(out_dtype) + lo.astype(out_dtype)).astype(out_dtype)


def matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
           out_dtype=None) -> jax.Array:
    """Emulated real GEMM via Scheme II (XLA reference path)."""
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    moduli = cfg.resolved_moduli()
    k_dim = a.shape[-1]
    check_exact_k(k_dim, moduli)
    with stage_scope(ENCODE):
        # Operand mantissa limits the useful budget (fp32 in -> 24 bits).
        budget = operand_budget(moduli, k_dim, a.dtype)
        a_int, mu = integerize(a, axis=1, budget_bits=budget)
        b_int, nu = integerize(b, axis=0, budget_bits=budget)
        a_res = balanced_residues(a_int, moduli)
        b_res = balanced_residues(b_int, moduli)
    acc = residue_gemms(a_res, b_res)          # (p, M, N) int32, balanced
    with stage_scope(CRT):
        c_res = modular_reduce(acc, moduli)    # [0, m_l)
        return crt_unscale(c_res, moduli, out_dtype, mu, nu)


def effective_bits(moduli, k_dim: int) -> int:
    return scheme2_budget(moduli, k_dim)


def fused_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                 out_dtype=None) -> jax.Array:
    """Scheme-II GEMM on the fused EmuGEMM-II kernel, via the dispatcher
    (cached block selection; non-aligned shapes are padded, not refused)."""
    import dataclasses
    from repro.kernels import dispatch  # lazy: keep the XLA path pallas-free
    if cfg.scheme != "ozaki2":
        cfg = dataclasses.replace(cfg, scheme="ozaki2")
    return dispatch.emulated_matmul(a, b, cfg=cfg, out_dtype=out_dtype)
