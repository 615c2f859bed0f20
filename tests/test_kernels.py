"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles in ref.py.

Every kernel is swept over shapes and slice/modulus counts; integer
kernels must match the oracle bit-exactly, the Scheme-I kernel (float
epilogue) to f32 summation-order tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import telemetry
from repro.core import scheme1, scheme2
from repro.core.precision import (DEFAULT_MODULI, EmulationConfig,
                                  default_moduli)
from repro.kernels import dispatch, matmul_int8, ops, ozaki1, ozaki2, ozaki3m
from repro.kernels import ref as kref
from repro.kernels.common import (Blocks, balanced_mod, choose_blocks,
                                  garner_mod)
from repro.telemetry import record as tele_rec

SHAPES = [(128, 128, 128), (256, 512, 128), (384, 256, 256)]


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_int8_matmul_exact(rng, m, n, k):
    a8 = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    out = matmul_int8.int8_matmul(a8, b8)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(kref.int8_matmul(a8, b8)))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("p", [2, 4, 8])
def test_ozaki1_kernel_vs_oracle(make_matrix, m, n, k, p):
    a = jnp.asarray(make_matrix((m, k)))
    b = jnp.asarray(make_matrix((k, n)))
    blocks = choose_blocks(m, n, k, p)
    beta = EmulationConfig(scheme="ozaki1", p=p).resolved_beta(k)
    a_sl, mu = scheme1.split(a, p, beta, axis=1)
    b_sl, nu = scheme1.split(b, p, beta, axis=0)
    a_hat = scheme1.interleave_k(a_sl, "a", blocks.bk)
    b_hat = scheme1.interleave_k(b_sl, "b", blocks.bk)
    out = ozaki1.fused_matmul_interleaved(a_hat, b_hat, mu, nu, p, beta,
                                          blocks)
    ref = kref.scheme1_interleaved(a_hat, b_hat, mu, nu, p, beta, blocks.bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(ref).max()))


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("p", [4, 9, 15])
def test_ozaki2_kernel_exact(rng, m, n, k, p):
    moduli = default_moduli(p)
    a_res = jnp.asarray(rng.integers(-127, 128, (p, m, k)), jnp.int8)
    b_res = jnp.asarray(rng.integers(-127, 128, (p, k, n)), jnp.int8)
    out = ozaki2.fused_residue_matmul(a_res, b_res, moduli)
    ref = kref.scheme2_residues(a_res, b_res, moduli)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 256, 128)])
@pytest.mark.parametrize("p", [3, 8])
def test_ozaki3m_kernel_exact(rng, m, n, k, p):
    moduli = default_moduli(p)
    a3 = jnp.asarray(rng.integers(-100, 101, (p, 3, m, k)), jnp.int8)
    b3 = jnp.asarray(rng.integers(-100, 101, (p, 3, k, n)), jnp.int8)
    c_re, c_im = ozaki3m.fused_3m_residue_matmul(a3, b3, moduli)
    r_re, r_im = kref.scheme2_3m(a3, b3, moduli)
    np.testing.assert_array_equal(np.asarray(c_re), np.asarray(r_re))
    np.testing.assert_array_equal(np.asarray(c_im), np.asarray(r_im))


@pytest.mark.parametrize("p,min_bits", [(2, 9), (4, 19)])
def test_fused_scheme1_end_to_end(make_matrix, p, min_bits):
    a = jnp.asarray(make_matrix((256, 256)))
    b = jnp.asarray(make_matrix((256, 256)))
    cfg = EmulationConfig(scheme="ozaki1", p=p)
    out = np.asarray(ops.fused_scheme1_matmul(a, b, cfg))
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert -np.log2(rel) >= min_bits  # ~beta bits per slice with margin


@pytest.mark.parametrize("p", [6, 10])
def test_fused_scheme2_end_to_end_matches_xla(make_matrix, p):
    a = jnp.asarray(make_matrix((256, 256)))
    b = jnp.asarray(make_matrix((256, 256)))
    cfg = EmulationConfig(scheme="ozaki2", p=p)
    fused = np.asarray(ops.fused_scheme2_matmul(a, b, cfg))
    xla = np.asarray(scheme2.matmul(a, b, cfg, jnp.float32))
    np.testing.assert_allclose(fused, xla, rtol=0, atol=0)  # bit-identical


def test_fused_3m_end_to_end(make_matrix):
    ar, ai = make_matrix((128, 128)), make_matrix((128, 128))
    br, bi = make_matrix((128, 128)), make_matrix((128, 128))
    a = jnp.asarray((ar + 1j * ai).astype(np.complex64))
    b = jnp.asarray((br + 1j * bi).astype(np.complex64))
    cfg = EmulationConfig(scheme="ozaki2", p=9)
    out = np.asarray(ops.fused_3m_matmul(a, b, cfg))
    ref = (ar + 1j * ai).astype(np.complex128) @ \
        (br + 1j * bi).astype(np.complex128)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert -np.log2(rel) > 12


def test_blocks_respect_vmem_budget():
    for p in (1, 4, 8):
        blocks = choose_blocks(1024, 1024, 1024, p)
        assert blocks is not None
        acc = 4 * p * blocks.bm * blocks.bn
        s_op = 2 * p * (blocks.bm + blocks.bn) * blocks.bk
        assert acc + s_op <= 12 * 2 ** 20
        # MXU alignment
        assert blocks.bm % 32 == 0 and blocks.bn % 128 == 0


def test_higher_p_forces_smaller_tiles():
    """Paper Eq. 12: the p-fold accumulator scaling shrinks alpha_max."""
    b1 = choose_blocks(2048, 2048, 2048, p=1)
    b8 = choose_blocks(2048, 2048, 2048, p=8)
    assert b1.bm * b1.bn >= b8.bm * b8.bn


@pytest.mark.parametrize("k, bk", [(1024, 512), (384, 384), (352, 352),
                                   (96, 96), (100, None)])
def test_block_k_is_lane_aligned_or_whole(k, bk):
    """bK is the lane dimension of the A block, which Mosaic takes as a
    multiple of 128 or as the whole K; K off the int8 sublane tile (32)
    has no block."""
    blocks = choose_blocks(64, 128, k, 4, prologue_a=True, prologue_b=True)
    assert (blocks.bk if blocks else None) == bk


_BOTH = dict(prologue_a=True, prologue_b=True)
_SERVE = dict(out_bytes=2, prologue_a=True, prologue_b=True)

# (m, n, k, p, choose_blocks keywords) -> the blocks chosen.
BLOCK_CASES = {
    # A dimension 128 divides and 256 does not takes a wider block.
    "attn_qk_mixed_step": ((128, 2176, 128, 1, {}), Blocks(128, 2176, 128)),
    "attn_qk_mixed_step_p6": ((128, 2176, 128, 6, dict(out_bytes=2)),
                              Blocks(128, 2176, 128)),
    "attn_av_mixed_step": ((128, 128, 2176, 4, _SERVE),
                           Blocks(128, 128, 2176)),
    "logits_prepared_50304": ((512, 50304, 2048, 4, dict(prologue_a=True)),
                              Blocks(512, 384, 512)),
    # Dimensions 256 divides keep the (512, 256, 128) candidates.
    "sgemm_square_16k": ((16384, 16384, 16384, 4, _BOTH),
                         Blocks(512, 512, 256)),
    "sgemm_skinny_m256": ((256, 19200, 7168, 4, _BOTH),
                          Blocks(256, 256, 512)),
    "olmo_qkv_prepared": ((512, 6144, 2048, 4, dict(prologue_a=True)),
                          Blocks(512, 512, 512)),
    "olmo_up_prepared": ((512, 16384, 2048, 4, dict(prologue_a=True)),
                         Blocks(512, 512, 512)),
    "olmo_down_prepared": ((512, 2048, 8192, 4, dict(prologue_a=True)),
                           Blocks(512, 512, 512)),
    "olmo_logits_prepared": ((512, 50688, 2048, 4, dict(prologue_a=True)),
                             Blocks(512, 512, 512)),
    "olmo_out_mixed_step": ((1024, 2048, 2048, 4, _SERVE),
                            Blocks(512, 512, 256)),
    "olmo_up_mixed_step": ((1024, 8192, 2048, 4, _SERVE),
                           Blocks(512, 512, 256)),
    "olmo_down_mixed_step": ((1024, 2048, 8192, 4, _SERVE),
                             Blocks(512, 512, 256)),
    # A wide block the VMEM model refuses falls back to 128.
    "vmem_refuses_257x128_p8": ((128, 257 * 128, 128, 8, {}),
                                Blocks(128, 128, 128)),
}


@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_block_choice(case):
    (m, n, k, p, kw), want = BLOCK_CASES[case]
    assert choose_blocks(m, n, k, p, **kw) == want


def test_balanced_mod_exact_over_int32(rng):
    """The float32-quotient remainder equals the exact balanced residue
    for every default modulus (and small ones) across the int32 range."""
    top = 2 ** 31 - 1 - 128  # x + m // 2 stays in int32
    x = np.concatenate([
        rng.integers(-2 ** 31, top, 200_000, endpoint=True),
        np.arange(-70_000, 70_000),
        [-2 ** 31, top, 2 ** 24 - 1, 2 ** 24, 2 ** 24 + 1, -2 ** 24 - 1],
    ]).astype(np.int32)
    bmod = jax.jit(balanced_mod)
    for m in (*DEFAULT_MODULI, 2, 3, 7):
        half = m // 2
        want = np.mod(x.astype(np.int64) + half, m) - half
        got = np.asarray(bmod(jnp.asarray(x), jnp.int32(m)))
        np.testing.assert_array_equal(got, want, err_msg=f"m={m}")


@pytest.mark.parametrize("moduli", [
    DEFAULT_MODULI[1:],          # odd: balanced_mod's range
    (256, 128, 16, 2),           # powers of two: the low bits
    (254, 250, 6),               # even, not a power of two: the mirror
])
def test_garner_mod_exact_over_int32(rng, moduli):
    """Garner's balanced residue, t > m // 2 -> t - m on the canonical
    one, for a constant modulus across the int32 range, in int32."""
    top = 2 ** 31 - 1 - 128
    x = np.concatenate([
        rng.integers(-2 ** 31 + 128, top, 200_000, endpoint=True),
        np.arange(-70_000, 70_000),
        [-2 ** 31 + 128, top, 2 ** 24 - 1, 2 ** 24, 2 ** 24 + 1],
    ]).astype(np.int32)
    for m in moduli:
        t = np.mod(x.astype(np.int64), m)
        want = np.where(t > m // 2, t - m, t)
        got = jax.jit(lambda v: garner_mod(v, m))(jnp.asarray(x))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"m={m}")


def test_ozaki2_wide_bn_bit_identical(rng):
    """attn_qk's residue GEMM at N = 17 x 128: one bn = 2,176 block gives
    the 128-wide tiles' residues exactly (int32 accumulators)."""
    moduli = default_moduli(6)
    a_res = jnp.asarray(rng.integers(-127, 128, (6, 128, 128)), jnp.int8)
    b_res = jnp.asarray(rng.integers(-127, 128, (6, 128, 2176)), jnp.int8)
    wide = choose_blocks(128, 2176, 128, 1)
    assert wide.bn == 2176
    out = ozaki2.fused_residue_matmul(a_res, b_res, moduli, wide)
    narrow = ozaki2.fused_residue_matmul(a_res, b_res, moduli,
                                         Blocks(128, 128, 128))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(narrow))


def test_ozaki1_prologue_wide_bk_bit_identical(make_matrix):
    """attn_av's Scheme-I GEMM at K = 17 x 128: bk = 2,176 in one step
    gives what seventeen 128-deep steps give, bit for bit."""
    m, k, n, p = 128, 2176, 128, 4
    a = jnp.asarray(make_matrix((m, k)))
    b = jnp.asarray(make_matrix((k, n)))
    beta = EmulationConfig(scheme="ozaki1", p=p).resolved_beta(k)
    mu = scheme1._pow2_row_scale(a, axis=1)
    nu = scheme1._pow2_row_scale(b, axis=0)
    wide = choose_blocks(m, n, k, p, prologue_a=True, prologue_b=True)
    assert wide.bk == k
    out = ozaki1.fused_matmul_prologue(a, b, mu, nu, p, beta, wide)
    narrow = ozaki1.fused_matmul_prologue(a, b, mu, nu, p, beta,
                                          Blocks(128, 128, 128))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(narrow))


@pytest.mark.parametrize("eq, spec, a_shape, b_shape", [
    # one lane, two heads, 128 queries against a 2,176-key view
    ("bqkgd,bjkd->bkgqj", "ozaki2-m6", (1, 128, 2, 1, 128), (1, 2176, 2, 128)),
    ("bkgqj,bjkd->bkgqd", "ozaki1-p4", (1, 2, 1, 128, 2176), (1, 2176, 2, 128)),
])
def test_attention_einsum_wide_blocks_match_expansion(make_matrix, eq, spec,
                                                      a_shape, b_shape):
    """The attention sites at K = N = 2,176 through the fused kernels
    (wide blocks) equal the XLA expansion of the same scheme."""
    a = jnp.asarray(make_matrix(a_shape))
    b = jnp.asarray(make_matrix(b_shape))
    dispatch.block_cache_clear()
    with telemetry.recording() as reg:
        wide0 = reg.total(tele_rec.BLOCK_WIDE)
        fused = repro.einsum(eq, a, b, precision=spec)
        assert reg.total(tele_rec.BLOCK_WIDE) > wide0
    xla = repro.einsum(eq, a, b, precision=spec + "+xla")
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(xla))
