"""jit'd end-to-end wrappers around the fused Pallas kernels.

The full emulated-GEMM pipelines:

  fused_scheme1_matmul : scales -> EmuGEMM-I kernel with the in-kernel
                         decomposition prologue (cfg.decomp='kernel'/'auto'
                         — the fp32 tiles slice to int8 in VMEM), or the
                         historical split -> interleave (Eq. 11) -> kernel
                         pipeline (cfg.decomp='xla')
  fused_scheme2_matmul : integerize -> residues -> EmuGEMM-II kernel -> CRT
  fused_3m_matmul      : complex residues -> fused-3M kernel -> 2x CRT

The remaining pre/post-processing (scale reductions, CRT) are XLA ops —
full-K reductions and multi-word reconstruction don't tile; everything
that *does* tile (slicing, interleaving, the INT32 accumulation, modular
reduction) now runs inside the kernels.

Routing (alignment checks, block caching, padding, batching) lives in
repro.kernels.dispatch.  ``cfg`` is optional on every wrapper here: when
omitted (or given as a spec string) it resolves through the one
documented resolver, ``repro.resolve_config`` — explicit arg > innermost
``repro.emulation`` scope > ``REPRO_EMULATION`` env > the wrapper's own
scheme default — instead of each call-site threading cfg kwargs by hand.
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import complex3m, scheme1, scheme2
from repro.core.precision import EmulationConfig
from repro.kernels import dispatch, ozaki1, ozaki2, ozaki3m
from repro.kernels.matmul_int8 import int8_matmul  # noqa: F401  (re-export)
from repro.telemetry import trace


def _resolve(cfg, scheme: str, p: int) -> EmulationConfig:
    """Resolve an optional cfg/spec for a scheme-pinned wrapper.

    Resolution happens *before* the jitted body (cfg is a static
    argument): a cached trace can never capture a stale ambient scope.
    An *explicit* cfg of the wrong scheme is a caller error; an ambient
    config of another scheme (REPRO_EMULATION=native, an ozaki2 scope
    around a scheme1 wrapper) is simply not for this wrapper — it falls
    back to the pinned default rather than break explicit kernel calls.
    """
    from repro import api
    if cfg is not None:
        cfg = api.precision(cfg)
        if cfg.scheme != scheme:
            raise ValueError(f"this wrapper is {scheme}-only; got "
                             f"scheme={cfg.scheme!r}")
        return cfg
    ambient = api.current_emulation()
    if ambient is not None and ambient.scheme == scheme:
        return ambient
    return EmulationConfig(scheme=scheme, p=p)


@partial(jax.jit, static_argnames=("cfg", "out_dtype", "blocks"))
def _fused_scheme1_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                          out_dtype=jnp.float32, blocks=None) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    p = cfg.p
    beta = cfg.resolved_beta(k)
    prologue = cfg.decomp in ("auto", "kernel")
    if blocks is None:
        blocks = dispatch.select_blocks(
            m, n, k, p, out_bytes=jnp.dtype(out_dtype).itemsize,
            backend="tpu", prologue_a=prologue, prologue_b=prologue)
    if blocks is None or not blocks.aligned(m, n, k):
        raise ValueError(f"shapes {(m, n, k)} not tile-aligned")
    if prologue:
        # Only the power-of-two scales (full-K reductions) run in XLA;
        # slicing happens in the kernel — no (M, p*K) HBM intermediate.
        # The kernel's truncate-subtract runs at >= float32, mirroring
        # split: ints/half floats widen to f32, f64 keeps its mantissa.
        def widen(x):
            if (not jnp.issubdtype(x.dtype, jnp.floating)
                    or jnp.dtype(x.dtype).itemsize < 4):
                return x.astype(jnp.float32)
            return x
        a, b = widen(a), widen(b)
        mu = scheme1._pow2_row_scale(a, axis=1)
        nu = scheme1._pow2_row_scale(b, axis=0)
        return ozaki1.fused_matmul_prologue(
            a, b, mu, nu, p, beta, blocks, out_dtype=out_dtype)
    a_sl, mu = scheme1.split(a, p, beta, axis=1)
    b_sl, nu = scheme1.split(b, p, beta, axis=0)
    a_hat = scheme1.interleave_k(a_sl, "a", blocks.bk)
    b_hat = scheme1.interleave_k(b_sl, "b", blocks.bk)
    return ozaki1.fused_matmul_interleaved(
        a_hat, b_hat, mu.astype(jnp.float32), nu.astype(jnp.float32),
        p, beta, blocks, out_dtype=out_dtype)


def fused_scheme1_matmul(a: jax.Array, b: jax.Array,
                         cfg: "EmulationConfig | str | None" = None,
                         out_dtype=jnp.float32, blocks=None) -> jax.Array:
    """End-to-end EmuGEMM-I: (M,K) x (K,N) float -> (M,N) out_dtype.

    ``cfg`` resolves through ``repro.resolve_config`` (ozaki1-p4 when
    nothing is configured); ``blocks`` (from ``dispatch.plan_emulated``)
    skips the re-search; the decomposition site follows ``cfg.decomp``.
    """
    return _fused_scheme1_matmul(a, b, _resolve(cfg, "ozaki1", 4),
                                 out_dtype=out_dtype, blocks=blocks)


@partial(jax.jit, static_argnames=("cfg", "out_dtype", "budget"))
def _fused_scheme2_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                          out_dtype, budget: int) -> jax.Array:
    moduli = cfg.resolved_moduli()
    with trace.stage_scope(trace.ENCODE):
        a_int, mu = scheme2.integerize(a, axis=1, budget_bits=budget)
        b_int, nu = scheme2.integerize(b, axis=0, budget_bits=budget)
        a_res = scheme2.balanced_residues(a_int, moduli)
        b_res = scheme2.balanced_residues(b_int, moduli)
    c_res8 = ozaki2.fused_residue_matmul(a_res, b_res, moduli)
    with trace.stage_scope(trace.CRT):
        return scheme2.crt_unscale(c_res8, moduli, jnp.dtype(out_dtype).type,
                                   mu, nu)


def fused_scheme2_matmul(a: jax.Array, b: jax.Array,
                         cfg: "EmulationConfig | str | None" = None,
                         out_dtype=jnp.float32) -> jax.Array:
    """End-to-end EmuGEMM-II real GEMM (cfg via ``repro.resolve_config``,
    ozaki2 with the default 8-modulus set when nothing is configured)."""
    cfg = _resolve(cfg, "ozaki2", 8)
    moduli = cfg.resolved_moduli()
    scheme2.check_exact_k(a.shape[1], moduli)
    # The budget is taken here, outside the jitted body, so that every
    # plan records it, a cached trace included.
    budget = scheme2.operand_budget(moduli, a.shape[1], a.dtype)
    return _fused_scheme2_matmul(a, b, cfg, out_dtype=out_dtype,
                                 budget=budget)


@partial(jax.jit, static_argnames=("cfg", "out_dtype", "budget"))
def _fused_3m_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                     out_dtype, budget: int) -> jax.Array:
    if out_dtype is None:
        out_dtype = jnp.float64 if a.dtype == jnp.complex128 else jnp.float32
    out_t = jnp.dtype(out_dtype).type
    moduli = cfg.resolved_moduli()
    with trace.stage_scope(trace.ENCODE):
        ar, ai = jnp.real(a), jnp.imag(a)
        br, bi = jnp.real(b), jnp.imag(b)
        mu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(ar), jnp.abs(ai)),
                                     axis=1, budget_bits=budget)
        nu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(br), jnp.abs(bi)),
                                     axis=0, budget_bits=budget)
        ar_res = scheme2.balanced_residues(jnp.trunc(ar * mu), moduli)
        ai_res = scheme2.balanced_residues(jnp.trunc(ai * mu), moduli)
        br_res = scheme2.balanced_residues(jnp.trunc(br * nu), moduli)
        bi_res = scheme2.balanced_residues(jnp.trunc(bi * nu), moduli)

        def sum_res(x_res, y_res, mm):
            return complex3m._balanced(
                x_res.astype(jnp.int32) + y_res.astype(jnp.int32), mm)

        a3 = jnp.stack([
            jnp.stack([ar_res[l], ai_res[l],
                       sum_res(ar_res[l], ai_res[l], int(mm))])
            for l, mm in enumerate(moduli)])          # (p, 3, M, K)
        b3 = jnp.stack([
            jnp.stack([br_res[l], bi_res[l],
                       sum_res(br_res[l], bi_res[l], int(mm))])
            for l, mm in enumerate(moduli)])          # (p, 3, K, N)

    c_re8, c_im8 = ozaki3m.fused_3m_residue_matmul(a3, b3, moduli)
    with trace.stage_scope(trace.CRT):
        c_re = scheme2.canonical_residues(c_re8, moduli)
        c_im = scheme2.canonical_residues(c_im8, moduli)
        cr = scheme2.crt_reconstruct(c_re, moduli, out_t)
        ci = scheme2.crt_reconstruct(c_im, moduli, out_t)
        inv = 1.0 / (mu.astype(out_t) * nu.astype(out_t))
        return jax.lax.complex(cr * inv, ci * inv)


def fused_3m_matmul(a: jax.Array, b: jax.Array,
                    cfg: "EmulationConfig | str | None" = None,
                    out_dtype=None) -> jax.Array:
    """End-to-end EmuGEMM-II complex GEMM via fused 3M (cfg via
    ``repro.resolve_config``)."""
    cfg = _resolve(cfg, "ozaki2", 8)
    moduli = cfg.resolved_moduli()
    k = a.shape[-1]
    scheme2.check_exact_k(k, moduli)
    budget = scheme2.operand_budget(moduli, k, jnp.real(a).dtype,
                                    complex_guard=True)
    return _fused_3m_matmul(a, b, cfg, out_dtype=out_dtype, budget=budget)


def maybe_fused_matmul(a: jax.Array, b: jax.Array, cfg: EmulationConfig):
    """Deprecated dispatch hook; use ``dispatch.auto_fused_matmul``."""
    warnings.warn(
        "ops.maybe_fused_matmul is deprecated; call "
        "dispatch.auto_fused_matmul (or repro.dot_general/einsum)",
        DeprecationWarning, stacklevel=2)
    return dispatch.auto_fused_matmul(a, b, cfg)
