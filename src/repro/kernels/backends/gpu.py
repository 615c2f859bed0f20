"""Mosaic-GPU/Triton backend: fused EmuGEMM-I and EmuGEMM-II for Hopper.

The lowering mirrors the paper's Hopper/Blackwell kernel structure
(Sec. III-B, IV) in the Triton program model rather than the TPU grid
model:

  * one program instance per (bM, bN) output tile — the grid is 2-D,
    with the K reduction as an *in-kernel* loop (``fori_loop``) instead
    of a third grid axis, matching a Triton/Mosaic-GPU persistent-tile
    kernel where accumulators live in registers (RF on Hopper, TMEM on
    Blackwell) for the whole K sweep;
  * each K step loads the fp32 operand tiles once and carves the
    on-chip int8 operands in place — Scheme I carves the p mantissa
    slices via the exact truncate-and-subtract recurrence
    (``carve_slices``), Scheme II carves the p balanced residues via the
    exact integerize + mod recurrence (``scheme2.balanced_residues``).
    The operand BlockSpecs describe the program's full K *strip*, but in
    the Triton lowering a BlockSpec is a GMEM block pointer — only the
    ``pl.ds`` slice loaded inside the K loop materializes on-chip, so
    the shared-memory working set is the per-K-step tile pair that
    ``choose_blocks_gpu`` budgets (interpret mode materializes the strip
    in host memory, which is fine);
  * Scheme I accumulates the p(p+1)/2 slice-pair products into p int32
    register accumulators; Scheme II accumulates one int32 accumulator
    per modulus (3 per modulus for complex 3M) — exact as long as
    K <= (2^31 - 1) / 2^14 (balanced residues are bounded by 128;
    ``scheme2.check_exact_k`` enforces this);
  * the epilogue runs before the single (bM, bN) output write: Scheme I
    does the shift-reduce (paper Eq. 3), Scheme II does the *entire
    residue tail* in registers — ``modular_reduce`` (paper Eq. 7),
    Garner's balanced mixed-radix digits (exact int32 with Python-int
    inverse-table constants), the double-double Horner reconstruction,
    and the inverse power-of-two scaling.  Neither the (p, M, K)
    balanced residues nor the (p, M, N) int32 accumulators of the XLA
    reference ever touch HBM — the data-movement bottleneck the paper's
    Scheme-II fusion targets (Eq. 14 vs 15, Eq. 17 vs 18).

Tiles align to the 16-lane WGMMA/MMA granularity (not the TPU's 128) and
the block search budgets shared memory per K step plus the register/TMEM
accumulator footprint, both residue-count-aware.  On CPU the kernels run
in Pallas interpret mode, which is how CI verifies bit-parity against
the ``scheme1.matmul`` / ``scheme2.matmul`` / ``complex3m.matmul``
oracles; on a real GPU the same kernel bodies lower through
Triton/Mosaic-GPU with feature-probed compiler params
(:func:`repro.kernels.compat.gpu_compiler_params`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.backends.base import (BackendCapabilities, KernelBackend,
                                         build_pallas_call)
from repro.kernels.common import Blocks, carve_slices

# WGMMA tile granularity: every GEMM dimension aligns to 16 lanes.
ALIGN = 16

# H100-class shared memory per SM is 228 KiB; leave pipeline headroom.
SMEM_BUDGET = 192 * 1024
# Register file / Blackwell TMEM available to the int32 accumulators.
ACC_BUDGET = 128 * 1024

# The fused Scheme-II kernels unroll one MMA + one epilogue chain per
# modulus and keep every balanced residue in int8: the moduli table is
# capped at the default 16 pairwise-coprime moduli <= 256.  Larger or
# wider moduli sets fall back to the 'xla' reference backend.
MAX_MODULI = 16

_CAPS = BackendCapabilities(
    align=ALIGN,
    schemes=frozenset({"ozaki1", "ozaki2"}),
    operand_dtypes=frozenset({"float32", "float64", "bfloat16", "float16"}),
    staging_budget=SMEM_BUDGET,
    accumulator_budget=ACC_BUDGET,
    peak_key="gpu",
    shardable=True,
    batched=True,
)


# Per-scheme resource model of one (bM, bN) program:
#   acc_phases — int32 accumulator sets (1 for Scheme I/II, 3 for 3M),
#   fp_sides   — fp32 operand tiles staged per side (2 for 3M: re + im),
#   res_mult   — carved int8 tiles per side per modulus/slice (3 for 3M:
#                the [re, im, re+im] residue phases),
#   n_out      — output tiles (3M writes re and im),
#   dd_bytes   — the double-double hi/lo pair the Scheme-II CRT
#                epilogue holds per output element (0 for shift-reduce).
_SCHEME_MODEL = {
    #           acc_phases, fp_sides, res_mult, n_out, dd_bytes
    "ozaki1": (1, 1, 1, 1, 0),
    "ozaki2": (1, 1, 1, 1, 8),
    "ozaki2-3m": (3, 2, 3, 2, 8),
}


def choose_blocks_gpu(m: int, n: int, k: int, p: int,
                      out_bytes: int = 4,
                      smem_budget: int = SMEM_BUDGET,
                      acc_budget: int = ACC_BUDGET,
                      fixed_bk: int | None = None,
                      scheme: str = "ozaki1") -> Blocks | None:
    """Largest 16-aligned blocks fitting the SMEM/accumulator budgets.

    The budget models the *per-K-step* working set — what a Triton
    lowering actually materializes on-chip per loop iteration (the
    BlockSpec strip itself is a GMEM block pointer, not an SMEM
    allocation; see the module doc) — and is residue-count-aware: ``p``
    is the slice count (Scheme I) or modulus count (Scheme II), and
    ``scheme`` selects the resource model.  One K step stages the fp32
    operand tiles (double-buffered by the async-copy pipeline) plus the
    carved int8 slices/residues of each:

      S_smem = (2*4 + p) * (bM + bN) * bK          (scheme1 / scheme2)
      S_smem = (2*2*4 + 3p) * (bM + bN) * bK       (complex 3M)

    while the int32 accumulators occupy 4 p bM bN (12 p bM bN for 3M)
    of RF/TMEM and the epilogue tile — output plus the Scheme-II CRT's
    double-double hi/lo pair — shares the staging space.  Preference
    mirrors the TPU search: maximize bM*bN, then bK.
    """
    try:
        acc_phases, fp_sides, res_mult, n_out, dd_bytes = \
            _SCHEME_MODEL[scheme]
    except KeyError:
        raise ValueError(f"choose_blocks_gpu: unknown scheme {scheme!r} "
                         f"(expected one of {sorted(_SCHEME_MODEL)})") \
            from None
    stage = fp_sides * 2 * 4 + res_mult * p
    epi = n_out * out_bytes + dd_bytes
    best: tuple[tuple[int, int], Blocks] | None = None
    bk_candidates = ((fixed_bk,) if fixed_bk is not None
                     else (128, 64, 32, 16))
    for bm in (128, 64, 32, 16):
        if m % bm:
            continue
        for bn in (128, 64, 32, 16):
            if n % bn:
                continue
            for bk in bk_candidates:
                if k % bk:
                    continue
                acc = 4 * acc_phases * p * bm * bn
                smem = stage * (bm + bn) * bk + epi * bm * bn
                if acc > acc_budget or smem > smem_budget:
                    continue
                key = (bm * bn, bk)
                if best is None or key > best[0]:
                    best = (key, Blocks(bm, bn, bk))
    return best[1] if best else None


# ---------------------------------------------------------------------------
# Scheme I: the fused mantissa-slice kernel (PR 3).
# ---------------------------------------------------------------------------

def _kernel(a_ref, b_ref, mu_ref, nu_ref, out_ref, *,
            p: int, beta: int, bk: int, nk: int, out_dtype):
    """One (bM, bN) output tile: in-kernel K loop, register accumulators."""
    mu = mu_ref[...]                 # (bM, 1) power-of-two row scales
    nu = nu_ref[...]                 # (1, bN) power-of-two col scales
    bm, bn = out_ref.shape

    def k_step(t, acc):
        # Stage this K step's fp32 tiles (shared memory) and carve the
        # p int8 slices in-place — elementwise, so tile-local carving is
        # bit-identical to the full-array scheme1.split.
        a_t = a_ref[:, pl.ds(t * bk, bk)] / mu       # (bM, bK)
        b_t = b_ref[pl.ds(t * bk, bk), :] / nu       # (bK, bN)
        a_slices = list(carve_slices(a_t, p, beta))
        b_slices = list(carve_slices(b_t, p, beta))
        # Triangular MMA schedule (Alg. 1 lines 6-8): C_s += A'_i B'_{s-i}.
        for s in range(p):
            partial = None
            for i in range(s + 1):
                prod = jax.lax.dot_general(
                    a_slices[i], b_slices[s - i], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                partial = prod if partial is None else partial + prod
            acc = acc.at[s].add(partial)
        return acc

    acc = jax.lax.fori_loop(0, nk, k_step,
                            jnp.zeros((p, bm, bn), jnp.int32))

    # Shift-reduce epilogue: C = diag(mu) (sum_s 2^{-beta(s+2)} C_s) diag(nu),
    # summed highest-weight-first exactly like scheme1.shift_reduce.
    c = jnp.zeros((bm, bn), dtype=out_dtype)
    for s in range(p):
        # Exact Python power of two (see scheme1.shift_reduce).
        w = jnp.asarray(2.0 ** (-beta * (s + 2)), dtype=out_dtype)
        c = c + w * acc[s].astype(out_dtype)
    out_ref[...] = c * mu.astype(out_dtype) * nu.astype(out_dtype)


def fused_matmul_scheme1(a: jax.Array, b: jax.Array,
                         mu: jax.Array, nu: jax.Array,
                         p: int, beta: int, blocks: Blocks,
                         out_dtype=jnp.float32) -> jax.Array:
    """Fused Scheme-I GEMM, GPU lowering: a (M, K) x b (K, N) fp32 with
    (M, 1)/(1, N) power-of-two scales -> (M, N) ``out_dtype``."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if not blocks.aligned(m, n, k):
        raise ValueError(f"blocks {blocks} not aligned for {(m, n, k)}")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    kernel = functools.partial(_kernel, p=p, beta=beta, bk=bk, nk=k // bk,
                               out_dtype=out_dtype)
    # Unlike the Mosaic kernels (interpreted on CPU only, see
    # common.interpret), this lowering compiles on a real GPU and
    # interprets everywhere else — including TPU hosts, which cannot run
    # a Triton/Mosaic-GPU program.
    return build_pallas_call(
        kernel,
        interpret_mode=jax.default_backend() != "gpu",
        grid=(m // bm, n // bn),
        in_specs=[
            # Each program walks its K strip tile-by-tile (pl.ds above).
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params_fn=compat.gpu_compiler_params,
        num_warps=8,
        num_stages=2,
        name=f"emugemm1_gpu_p{p}",
    )(a, b, mu, nu)


def fused_matmul_scheme1_batched(a: jax.Array, b: jax.Array,
                                 mu: jax.Array, nu: jax.Array,
                                 p: int, beta: int, blocks: Blocks,
                                 out_dtype=jnp.float32) -> jax.Array:
    """Strided-batched fused Scheme-I GEMM: (B, M, K) x (B, K, N) fp32
    with (B, M, 1)/(B, 1, N) power-of-two scales -> (B, M, N) in ONE
    ``pallas_call``.

    The grid grows a third (leading) dimension over batch and every
    BlockSpec squeezes it with a ``None`` block dim — each program
    instance therefore sees exactly the 2-D refs of the non-batched
    launch and runs the *same* kernel body (``_kernel``), which is what
    makes the batched lowering bit-identical to vmapping
    :func:`fused_matmul_scheme1` by construction.  What changes is the
    launch economics: one kernel launch instead of B, and the operand
    blocks are addressed with a batch stride (cuBLAS
    ``gemm_strided_batched`` layout) rather than re-described per
    element.
    """
    batch, m, k = a.shape
    b2, k2, n = b.shape
    assert (batch, k) == (b2, k2), (a.shape, b.shape)
    if not blocks.aligned(m, n, k):
        raise ValueError(f"blocks {blocks} not aligned for {(m, n, k)}")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    kernel = functools.partial(_kernel, p=p, beta=beta, bk=bk, nk=k // bk,
                               out_dtype=out_dtype)
    return build_pallas_call(
        kernel,
        interpret_mode=jax.default_backend() != "gpu",
        grid=(batch, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((None, bm, k), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, k, bn), lambda bb, i, j: (bb, 0, j)),
            pl.BlockSpec((None, bm, 1), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda bb, i, j: (bb, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda bb, i, j: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, m, n), out_dtype),
        compiler_params_fn=compat.gpu_compiler_params,
        num_warps=8,
        num_stages=2,
        name=f"emugemm1_gpu_p{p}_b{batch}",
    )(a, b, mu, nu)


# ---------------------------------------------------------------------------
# Scheme II: the fused residue pipeline.
# ---------------------------------------------------------------------------

def _carve_residues(x_int: jax.Array, moduli) -> jax.Array:
    """Balanced int8 residues of an exact-integer float tile.

    Defers to ``scheme2.balanced_residues`` — the elementwise integer
    recurrence is tile-local, so the in-kernel carve is bit-identical to
    the full-array encode of the XLA reference.
    """
    from repro.core import scheme2
    return scheme2.balanced_residues(x_int, moduli)


def _crt_epilogue(acc, moduli, out_dtype):
    """(p, bM, bN) int32 accumulators -> reconstructed integer tile.

    The entire residue tail of the reference pipeline — ``modular_reduce``
    (Eq. 7), balanced Garner digits, double-double mixed-radix Horner —
    runs in registers.  All moduli and inverse-table constants enter as
    exact Python ints (``garner_folded``), so there is no eager-exp2
    style constant hazard; every op is exact integer / IEEE arithmetic
    and therefore bit-identical to the full-array reference restricted
    to this tile.
    """
    from repro.core import scheme2
    c_res = scheme2.modular_reduce(acc, moduli)
    return scheme2.crt_reconstruct(c_res, moduli, out_dtype)


def _kernel2(a_ref, b_ref, mu_ref, nu_ref, out_ref, *,
             moduli, bk: int, nk: int, out_dtype, b_res: bool):
    """One (bM, bN) tile of the fused Scheme-II pipeline: integerize +
    residue-carve prologue, p modular int8 MMAs per K step into p int32
    register accumulators, modular reduction + Garner + double-double
    CRT epilogue — one store, nothing else leaves the chip.

    ``b_res`` switches the rhs to a pre-encoded residue operand (a
    :class:`repro.kernels.prepared.PreparedResidues` weight): its
    (p, K, N) int8 residues stream directly and the prologue skips the
    rhs encode.
    """
    p = len(moduli)
    mu = mu_ref[...]                 # (bM, 1) power-of-two int scales
    nu = nu_ref[...]                 # (1, bN)
    bm, bn = out_ref.shape

    def k_step(t, acc):
        # Integerize the staged fp32 tiles (trunc of the power-of-two
        # scaled operand — exact, mirrors scheme2.integerize) and carve
        # the balanced residues of all p moduli from the one staged read.
        a_t = jnp.trunc(a_ref[:, pl.ds(t * bk, bk)] * mu)     # (bM, bK)
        a_res = _carve_residues(a_t, moduli)                  # (p, bM, bK)
        if b_res:
            b_sl = [b_ref[l, pl.ds(t * bk, bk), :] for l in range(p)]
        else:
            b_t = jnp.trunc(b_ref[pl.ds(t * bk, bk), :] * nu)
            b_stack = _carve_residues(b_t, moduli)
            b_sl = [b_stack[l] for l in range(p)]
        for l in range(p):
            prod = jax.lax.dot_general(
                a_res[l], b_sl[l], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = acc.at[l].add(prod)
        return acc

    acc = jax.lax.fori_loop(0, nk, k_step,
                            jnp.zeros((p, bm, bn), jnp.int32))
    c_int = _crt_epilogue(acc, moduli, out_dtype)
    out_ref[...] = c_int / (mu.astype(out_dtype) * nu.astype(out_dtype))


def fused_matmul_scheme2(a: jax.Array, b: jax.Array,
                         mu: jax.Array, nu: jax.Array,
                         moduli, blocks: Blocks,
                         out_dtype=jnp.float32) -> jax.Array:
    """Fused Scheme-II GEMM, GPU lowering.

    a: (M, K) float; b: (K, N) float, or (p, K, N) int8 pre-encoded
    balanced residues (the PreparedResidues consumption path — the
    prologue then skips the rhs encode).  mu: (M, 1) / nu: (1, N)
    power-of-two integerization scales (full-K reductions, computed by
    the caller at the shared operand budget).
    """
    moduli = tuple(int(mm) for mm in moduli)
    p = len(moduli)
    m, k = a.shape
    b_is_res = b.ndim == 3
    if b_is_res:
        pb, k2, n = b.shape
        assert pb == p, (b.shape, p)
    else:
        k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if not blocks.aligned(m, n, k):
        raise ValueError(
            f"fused gpu ozaki2 kernel: blocks {blocks} not aligned for "
            f"{(m, n, k)}")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    kernel = functools.partial(_kernel2, moduli=moduli, bk=bk, nk=k // bk,
                               out_dtype=out_dtype, b_res=b_is_res)
    b_spec = (pl.BlockSpec((p, k, bn), lambda i, j: (0, 0, j)) if b_is_res
              else pl.BlockSpec((k, bn), lambda i, j: (0, j)))
    return build_pallas_call(
        kernel,
        interpret_mode=jax.default_backend() != "gpu",
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            b_spec,
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params_fn=compat.gpu_compiler_params,
        num_warps=8,
        num_stages=2,
        name=f"emugemm2_gpu_p{p}{'_prep' if b_is_res else ''}",
    )(a, b, mu, nu)


def fused_matmul_scheme2_batched(a: jax.Array, b: jax.Array,
                                 mu: jax.Array, nu: jax.Array,
                                 moduli, blocks: Blocks,
                                 out_dtype=jnp.float32) -> jax.Array:
    """Strided-batched fused Scheme-II GEMM: (B, M, K) x (B, K, N) float
    with (B, M, 1)/(B, 1, N) power-of-two integerization scales
    -> (B, M, N) in ONE ``pallas_call``.

    Same construction as :func:`fused_matmul_scheme1_batched`: a leading
    batch grid dimension whose BlockSpecs squeeze it away, so each
    program runs the unchanged 2-D residue pipeline (``_kernel2`` —
    integerize + balanced-residue carve, p modular int8 MMAs per K step,
    the full Garner/double-double CRT tail in the epilogue) and the
    result is bit-identical to vmapping :func:`fused_matmul_scheme2`.
    Pre-encoded (p, K, N) residue operands are per-weight, not
    per-batch-element — the prepared consumption path stays on the
    2-D kernel (one shared rhs never needs a batch stride).
    """
    moduli = tuple(int(mm) for mm in moduli)
    p = len(moduli)
    batch, m, k = a.shape
    b2, k2, n = b.shape
    assert (batch, k) == (b2, k2), (a.shape, b.shape)
    if not blocks.aligned(m, n, k):
        raise ValueError(
            f"fused gpu ozaki2 batched kernel: blocks {blocks} not aligned "
            f"for {(m, n, k)}")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    kernel = functools.partial(_kernel2, moduli=moduli, bk=bk, nk=k // bk,
                               out_dtype=out_dtype, b_res=False)
    return build_pallas_call(
        kernel,
        interpret_mode=jax.default_backend() != "gpu",
        grid=(batch, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((None, bm, k), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, k, bn), lambda bb, i, j: (bb, 0, j)),
            pl.BlockSpec((None, bm, 1), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda bb, i, j: (bb, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda bb, i, j: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, m, n), out_dtype),
        compiler_params_fn=compat.gpu_compiler_params,
        num_warps=8,
        num_stages=2,
        name=f"emugemm2_gpu_p{p}_b{batch}",
    )(a, b, mu, nu)


def _kernel2_3m(ar_ref, ai_ref, br_ref, bi_ref, mu_ref, nu_ref,
                out_re_ref, out_im_ref, *,
                moduli, bk: int, nk: int, out_dtype):
    """One (bM, bN) tile of the fused complex-3M Scheme-II pipeline.

    The three residue phases ([re, im, re+im], paper Sec. IV-B) are
    carved from *one* staged read of the re/im fp32 tile pair — the sum
    phase is re-balanced on-chip (``complex3m._balanced``) — and feed
    3p modular MMAs per K step into (3, p) int32 register accumulators.
    The epilogue forms the exact modular 3M combination

        C'_re = T1 - T2 ,  C'_im = T3 - T1 - T2    (mod m_l)

    then runs two full CRT reconstructions in registers and writes only
    the two scaled output tiles (paper Eq. 18 — the 24MN int32
    round-trip term of Eq. 17 vanishes).
    """
    from repro.core import complex3m
    p = len(moduli)
    mu = mu_ref[...]
    nu = nu_ref[...]
    bm, bn = out_re_ref.shape

    def k_step(t, acc):
        ks = pl.ds(t * bk, bk)
        ar_res = _carve_residues(jnp.trunc(ar_ref[:, ks] * mu), moduli)
        ai_res = _carve_residues(jnp.trunc(ai_ref[:, ks] * mu), moduli)
        br_res = _carve_residues(jnp.trunc(br_ref[ks, :] * nu), moduli)
        bi_res = _carve_residues(jnp.trunc(bi_ref[ks, :] * nu), moduli)
        for l, mm in enumerate(moduli):
            as_res = complex3m._balanced(
                ar_res[l].astype(jnp.int32) + ai_res[l].astype(jnp.int32),
                mm)
            bs_res = complex3m._balanced(
                br_res[l].astype(jnp.int32) + bi_res[l].astype(jnp.int32),
                mm)
            pairs = ((ar_res[l], br_res[l]), (ai_res[l], bi_res[l]),
                     (as_res, bs_res))
            for t_i, (x8, y8) in enumerate(pairs):
                prod = jax.lax.dot_general(
                    x8, y8, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc = acc.at[t_i, l].add(prod)
        return acc

    acc = jax.lax.fori_loop(0, nk, k_step,
                            jnp.zeros((3, p, bm, bn), jnp.int32))

    # Exact modular 3M combination per modulus (mirrors complex3m.matmul).
    c_re_res, c_im_res = [], []
    for l, mm in enumerate(moduli):
        t1m = jnp.remainder(acc[0, l], mm)
        t2m = jnp.remainder(acc[1, l], mm)
        t3m = jnp.remainder(acc[2, l], mm)
        c_re_res.append(jnp.remainder(t1m - t2m, mm).astype(jnp.int32))
        c_im_res.append(jnp.remainder(t3m - t1m - t2m, mm).astype(jnp.int32))
    from repro.core import scheme2
    c_re = scheme2.crt_reconstruct(jnp.stack(c_re_res), moduli, out_dtype)
    c_im = scheme2.crt_reconstruct(jnp.stack(c_im_res), moduli, out_dtype)
    inv = 1.0 / (mu.astype(out_dtype) * nu.astype(out_dtype))
    out_re_ref[...] = c_re * inv
    out_im_ref[...] = c_im * inv


def fused_matmul_3m(ar, ai, br, bi, mu, nu, moduli, blocks: Blocks,
                    out_dtype=jnp.float32):
    """Fused complex-3M Scheme-II GEMM, GPU lowering.

    ar/ai: (M, K) float real/imaginary parts; br/bi: (K, N); mu/nu the
    shared per-row/col power-of-two integerization scales.  Returns
    (c_re, c_im) real ``out_dtype`` arrays — the caller assembles the
    complex result (and divides nothing: the inverse scaling runs in
    the epilogue).
    """
    moduli = tuple(int(mm) for mm in moduli)
    p = len(moduli)
    m, k = ar.shape
    k2, n = br.shape
    assert k == k2, (ar.shape, br.shape)
    if not blocks.aligned(m, n, k):
        raise ValueError(
            f"fused gpu ozaki2 3M kernel: blocks {blocks} not aligned for "
            f"{(m, n, k)}")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    kernel = functools.partial(_kernel2_3m, moduli=moduli, bk=bk,
                               nk=k // bk, out_dtype=out_dtype)
    a_spec = pl.BlockSpec((bm, k), lambda i, j: (i, 0))
    b_spec = pl.BlockSpec((k, bn), lambda i, j: (0, j))
    return build_pallas_call(
        kernel,
        interpret_mode=jax.default_backend() != "gpu",
        grid=(m // bm, n // bn),
        in_specs=[
            a_spec, a_spec, b_spec, b_spec,
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, n), out_dtype),
                   jax.ShapeDtypeStruct((m, n), out_dtype)],
        compiler_params_fn=compat.gpu_compiler_params,
        num_warps=8,
        num_stages=2,
        name=f"emugemm2_3m_gpu_p{p}",
    )(ar, ai, br, bi, mu, nu)


def supported_moduli(moduli) -> bool:
    """Can the fused GPU Scheme-II kernels lower this moduli set?"""
    moduli = tuple(int(mm) for mm in moduli)
    return 0 < len(moduli) <= MAX_MODULI and max(moduli) <= 256


def _widen(x):
    # Match scheme1.split: ints and half floats widen to f32 before the
    # truncate-subtract recurrence; f64 keeps its mantissa.
    if (not jnp.issubdtype(x.dtype, jnp.floating)
            or jnp.dtype(x.dtype).itemsize < 4):
        return x.astype(jnp.float32)
    return x


def _float_or_f32(x):
    # Match scheme2.matmul/complex3m.matmul: floats keep their dtype
    # (the whole integerize chain runs in it), everything else -> f32.
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(jnp.float32)


class GpuBackend(KernelBackend):
    name = "gpu"

    @property
    def capabilities(self) -> BackendCapabilities:
        return _CAPS

    def choose_blocks(self, m, n, k, p, *, out_bytes=4, prologue_a=False,
                      prologue_b=False, fixed_bk=None,
                      scheme="ozaki1") -> Blocks | None:
        # The GPU kernels always decompose in the prologue (fp32 staged
        # in SMEM, slices/residues carved in place), so the prologue
        # flags are moot; ``scheme`` selects the residue-count-aware
        # resource model instead.
        del prologue_a, prologue_b
        return choose_blocks_gpu(m, n, k, p, out_bytes=out_bytes,
                                 fixed_bk=fixed_bk, scheme=scheme)

    def supports(self, cfg, a_dtype=None, b_dtype=None) -> bool:
        if not super().supports(cfg, a_dtype, b_dtype):
            return False
        if cfg.scheme == "ozaki2":
            # The fused kernels unroll per modulus and carry balanced
            # int8 residues: moduli beyond the 16-entry <=256 table have
            # no lowering here (dispatch falls back to 'xla').
            return supported_moduli(cfg.resolved_moduli())
        return True

    def matmul(self, a, b, cfg, out_dtype, blocks):
        if cfg.scheme == "ozaki1":
            return self._matmul_scheme1(a, b, cfg, out_dtype, blocks)
        if cfg.scheme == "ozaki2":
            cplx = (jnp.issubdtype(a.dtype, jnp.complexfloating)
                    or jnp.issubdtype(b.dtype, jnp.complexfloating))
            if cplx:
                return self._matmul_3m(a, b, cfg, out_dtype, blocks)
            return self._matmul_scheme2(a, b, cfg, out_dtype, blocks)
        raise ValueError(f"gpu backend has no fused kernel for scheme "
                         f"{cfg.scheme!r}")

    def matmul_batched(self, a, b, cfg, out_dtype, blocks):
        if cfg.scheme == "ozaki1":
            return self._matmul_scheme1_batched(a, b, cfg, out_dtype, blocks)
        if cfg.scheme == "ozaki2":
            if (jnp.issubdtype(a.dtype, jnp.complexfloating)
                    or jnp.issubdtype(b.dtype, jnp.complexfloating)):
                # The 3M kernel's three residue phases would triple the
                # grid bookkeeping; complex batches stay on the vmap
                # fallback until there is a workload that needs them.
                raise NotImplementedError(
                    "gpu backend: no strided-batched complex-3M lowering")
            return self._matmul_scheme2_batched(a, b, cfg, out_dtype, blocks)
        raise ValueError(f"gpu backend has no fused batched kernel for "
                         f"scheme {cfg.scheme!r}")

    def _matmul_scheme1_batched(self, a, b, cfg, out_dtype, blocks):
        from repro.core import scheme1
        batch, m, k = a.shape
        _, _, n = b.shape
        beta = cfg.resolved_beta(k)
        if blocks is None:
            blocks = self.choose_blocks(
                m, n, k, cfg.p, out_bytes=jnp.dtype(out_dtype).itemsize)
        if blocks is None or not blocks.aligned(m, n, k):
            raise ValueError(
                f"fused gpu ozaki1 batched kernel: shapes {(m, n, k)} not "
                "16-aligned (dispatch pads automatically)")
        a, b = _widen(a), _widen(b)
        # One scale pass over the whole stack: keepdims reductions give
        # (B, M, 1) / (B, 1, N), exactly the per-element scales the
        # vmapped 2-D launch computes B times.
        mu = scheme1._pow2_row_scale(a, axis=-1)
        nu = scheme1._pow2_row_scale(b, axis=1)
        return fused_matmul_scheme1_batched(a, b, mu, nu, cfg.p, beta,
                                            blocks, out_dtype=out_dtype)

    def _matmul_scheme2_batched(self, a, b, cfg, out_dtype, blocks):
        from repro.core import scheme2
        from repro.core.precision import scheme2_budget
        moduli = cfg.resolved_moduli()
        self._check_moduli(moduli)
        batch, m, k = a.shape
        _, _, n = b.shape
        scheme2.check_exact_k(k, moduli)
        if blocks is None or not blocks.aligned(m, n, k):
            blocks = self.choose_blocks(
                m, n, k, len(moduli),
                out_bytes=jnp.dtype(out_dtype).itemsize, scheme="ozaki2")
        if blocks is None or not blocks.aligned(m, n, k):
            raise ValueError(
                f"fused gpu ozaki2 batched kernel: shapes {(m, n, k)} not "
                "16-aligned (dispatch pads automatically)")
        a, b = _float_or_f32(a), _float_or_f32(b)
        budget = scheme2_budget(moduli, k)
        budget = min(budget, jnp.finfo(a.dtype).nmant + 1)
        mu = scheme2._pow2_int_scale(a, axis=-1, budget_bits=budget)
        nu = scheme2._pow2_int_scale(b, axis=1, budget_bits=budget)
        return fused_matmul_scheme2_batched(a, b, mu, nu, moduli, blocks,
                                            out_dtype=out_dtype)

    def _matmul_scheme1(self, a, b, cfg, out_dtype, blocks):
        from repro.core import scheme1  # lazy: keep import graph acyclic
        m, k = a.shape
        _, n = b.shape
        beta = cfg.resolved_beta(k)
        if blocks is None:
            blocks = self.choose_blocks(
                m, n, k, cfg.p, out_bytes=jnp.dtype(out_dtype).itemsize)
        if blocks is None or not blocks.aligned(m, n, k):
            raise ValueError(
                f"fused gpu ozaki1 kernel: shapes {(m, n, k)} not "
                "16-aligned (dispatch.emulated_matmul pads automatically)")
        a, b = _widen(a), _widen(b)
        mu = scheme1._pow2_row_scale(a, axis=1)
        nu = scheme1._pow2_row_scale(b, axis=0)
        return fused_matmul_scheme1(a, b, mu, nu, cfg.p, beta, blocks,
                                    out_dtype=out_dtype)

    def _check_moduli(self, moduli):
        if not supported_moduli(moduli):
            raise ValueError(
                f"fused gpu ozaki2 kernel supports at most {MAX_MODULI} "
                f"moduli, each <= 256 (balanced int8 residues); got "
                f"{len(moduli)} moduli, max {max(moduli)} — larger counts "
                "fall back to the 'xla' reference backend (moduli > 256 "
                "have no int8 residue representation on any backend)")

    def _matmul_scheme2(self, a, b, cfg, out_dtype, blocks):
        from repro.core import scheme2
        from repro.core.precision import scheme2_budget
        moduli = cfg.resolved_moduli()
        self._check_moduli(moduli)
        m, k = a.shape
        _, n = b.shape
        scheme2.check_exact_k(k, moduli)
        if blocks is None or not blocks.aligned(m, n, k):
            blocks = self.choose_blocks(
                m, n, k, len(moduli),
                out_bytes=jnp.dtype(out_dtype).itemsize, scheme="ozaki2")
        if blocks is None or not blocks.aligned(m, n, k):
            raise ValueError(
                f"fused gpu ozaki2 kernel: shapes {(m, n, k)} not "
                "16-aligned (dispatch.emulated_matmul pads automatically)")
        # Mirror scheme2.matmul exactly: no widening — the oracle
        # integerizes in the operand's own dtype (a bf16 exp2 scale is
        # not even an exact power of two, so a widened-f32 interior
        # would diverge bitwise) and caps the shared budget at that
        # dtype's mantissa.  Only non-float operands cast to f32.
        a, b = _float_or_f32(a), _float_or_f32(b)
        budget = scheme2_budget(moduli, k)
        budget = min(budget, jnp.finfo(a.dtype).nmant + 1)
        mu = scheme2._pow2_int_scale(a, axis=1, budget_bits=budget)
        nu = scheme2._pow2_int_scale(b, axis=0, budget_bits=budget)
        return fused_matmul_scheme2(a, b, mu, nu, moduli, blocks,
                                    out_dtype=out_dtype)

    def _matmul_3m(self, a, b, cfg, out_dtype, blocks=None):
        from repro.core import scheme2
        from repro.core.precision import scheme2_budget
        moduli = cfg.resolved_moduli()
        self._check_moduli(moduli)
        m, k = a.shape
        _, n = b.shape
        scheme2.check_exact_k(k, moduli)
        # The dispatcher's plan already selected (and cached) blocks with
        # the phase-aware 'ozaki2-3m' model; re-select only without one.
        if blocks is None or not blocks.aligned(m, n, k):
            blocks = self.choose_blocks(
                m, n, k, len(moduli),
                out_bytes=jnp.dtype(out_dtype).itemsize, scheme="ozaki2-3m")
        if blocks is None or not blocks.aligned(m, n, k):
            raise ValueError(
                f"fused gpu ozaki2 3M kernel: shapes {(m, n, k)} not "
                "16-aligned (dispatch.emulated_matmul pads automatically)")
        budget = scheme2_budget(moduli, k, complex_guard=True)
        real_t = jnp.real(a).dtype
        budget = min(budget, jnp.finfo(real_t).nmant + 1)
        ar, ai = _widen(jnp.real(a)), _widen(jnp.imag(a))
        br, bi = _widen(jnp.real(b)), _widen(jnp.imag(b))
        # One power-of-two scale per row/col shared by re/im parts
        # (mirrors complex3m.matmul).
        mu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(ar), jnp.abs(ai)),
                                     axis=1, budget_bits=budget)
        nu = scheme2._pow2_int_scale(jnp.maximum(jnp.abs(br), jnp.abs(bi)),
                                     axis=0, budget_bits=budget)
        c_re, c_im = fused_matmul_3m(ar, ai, br, bi, mu, nu, moduli,
                                     blocks, out_dtype=out_dtype)
        return jax.lax.complex(c_re, c_im)
