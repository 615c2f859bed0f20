"""Scheme-II complex GEMM via the 3M identity (paper Sec. IV-B).

T1 = Ar'Br', T2 = Ai'Bi', T3 = (Ar'+Ai')(Br'+Bi')   (all mod m_l)
C_re = T1 - T2 ; C_im = T3 - T1 - T2.

In *modular integer* arithmetic every operation is exact, so the 3M
cancellation problem of floating point does not exist — 3M is strictly
preferable, 25% fewer GEMMs than 4M at zero accuracy cost.

The sum residues (Ar'+Ai') are re-reduced (balanced) before the GEMM so the
int8 operand range is preserved.  Exactness needs the slightly tighter bound
2 * K * 2^ba * 2^bb * 2 < P (C_im sums two product matrices), handled by
``scheme2_budget(..., complex_guard=True)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import EmulationConfig
from repro.core import scheme2
from repro.telemetry.trace import CRT, ENCODE, stage_scope


def _balanced(x_int32: jax.Array, m: int) -> jax.Array:
    half = m // 2
    return (jnp.remainder(x_int32 + half, m) - half).astype(jnp.int8)


def matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
           out_dtype=None) -> jax.Array:
    """Emulated complex GEMM via Scheme II + 3M (XLA reference path)."""
    if out_dtype is None:
        out_dtype = jnp.float64 if a.dtype == jnp.complex128 else jnp.float32
    moduli = cfg.resolved_moduli()
    k_dim = a.shape[-1]
    scheme2.check_exact_k(k_dim, moduli)
    with stage_scope(ENCODE):
        budget = scheme2.operand_budget(moduli, k_dim, jnp.real(a).dtype,
                                        complex_guard=True)
        ar, ai = jnp.real(a), jnp.imag(a)
        br, bi = jnp.real(b), jnp.imag(b)
        # One power-of-two scale per row/col shared by re/im parts.
        mu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(ar), jnp.abs(ai)),
                                     axis=1, budget_bits=budget)
        nu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(br), jnp.abs(bi)),
                                     axis=0, budget_bits=budget)
        ar_i, ai_i = jnp.trunc(ar * mu), jnp.trunc(ai * mu)
        br_i, bi_i = jnp.trunc(br * nu), jnp.trunc(bi * nu)

        ar_res = scheme2.balanced_residues(ar_i, moduli)   # (p, M, K) int8
        ai_res = scheme2.balanced_residues(ai_i, moduli)
        br_res = scheme2.balanced_residues(br_i, moduli)
        bi_res = scheme2.balanced_residues(bi_i, moduli)

    c_re_res, c_im_res = [], []
    for l, m in enumerate(moduli):
        # 3M operand sums, re-balanced into int8 range after mod m.
        as_res = _balanced(ar_res[l].astype(jnp.int32)
                           + ai_res[l].astype(jnp.int32), m)
        bs_res = _balanced(br_res[l].astype(jnp.int32)
                           + bi_res[l].astype(jnp.int32), m)
        t1 = scheme2._int8_dot(ar_res[l], br_res[l])
        t2 = scheme2._int8_dot(ai_res[l], bi_res[l])
        t3 = scheme2._int8_dot(as_res, bs_res)
        # Exact modular combination (the fused kernel does this in-epilogue).
        t1m = jnp.remainder(t1, m)
        t2m = jnp.remainder(t2, m)
        t3m = jnp.remainder(t3, m)
        c_re_res.append(jnp.remainder(t1m - t2m, m).astype(jnp.int32))
        c_im_res.append(jnp.remainder(t3m - t1m - t2m, m).astype(jnp.int32))

    with stage_scope(CRT):
        c_re = scheme2.crt_reconstruct(jnp.stack(c_re_res), moduli, out_dtype)
        c_im = scheme2.crt_reconstruct(jnp.stack(c_im_res), moduli, out_dtype)
        inv = 1.0 / (mu.astype(out_dtype) * nu.astype(out_dtype))
        return jax.lax.complex(c_re * inv, c_im * inv)


def gemm_count(cfg: EmulationConfig) -> int:
    """3M: 3 GEMMs per modulus (vs 4 for 4M)."""
    return 3 * cfg.p


def fused_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                 out_dtype=None) -> jax.Array:
    """Complex Scheme-II GEMM on the fused 3M kernel, via the dispatcher
    (cached block selection; non-aligned shapes are padded, not refused)."""
    import dataclasses
    from repro.kernels import dispatch  # lazy: keep the XLA path pallas-free
    if cfg.scheme != "ozaki2":
        cfg = dataclasses.replace(cfg, scheme="ozaki2")
    return dispatch.emulated_matmul(a, b, cfg=cfg, out_dtype=out_dtype)
