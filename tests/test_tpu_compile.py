"""Compile the main-path Mosaic kernels for a described TPU v5e chip.

Interpret mode (the CPU test path) cannot see what only the chip's
compiler refuses: tiles not aligned to the int8 (32, 128) tiling, blocks
that overrun VMEM, kernels that cannot be partitioned.  Each test here
lowers one kernel at a real width for a ``v5e:2x2`` topology that is
described, not attached, and checks that the compiled program holds the
kernel as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside module fixtures, never at import time:
only one process at a time may load the TPU library, and every xdist
worker imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.precision import default_moduli, safe_beta
from repro.kernels import decompose, ozaki1, ozaki2, ozaki3m
from repro.kernels.backends import base
from repro.kernels.common import choose_blocks

N = 4096     # square Scheme-I / Scheme-II width
N3M = 2048   # fused 3M: three phase passes per modulus
P = 4        # Scheme-I slices
MODULI = default_moduli(8)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name, one_chip):
    """(fn, argument shapes) of one kernel at its real width."""
    f32, i8 = jnp.float32, jnp.int8
    beta = safe_beta(N)
    if name == "ozaki1_prologue":
        blocks = choose_blocks(N, N, N, P, prologue_a=True, prologue_b=True)
        fn = lambda a, b, mu, nu: ozaki1.fused_matmul_prologue(  # noqa: E731
            a, b, mu, nu, P, beta, blocks)
        args = [(N, N, f32), (N, N, f32), (N, 1, f32), (1, N, f32)]
    elif name == "ozaki1_prologue_whole_k":
        # olmo-1b-emu's attn_av site in a 64-token prefill chunk: K is the
        # paged KV view (352), which 128 does not divide.
        m, k, n = 64, 352, 128
        blocks = choose_blocks(m, n, k, P, prologue_a=True, prologue_b=True)
        fn = lambda a, b, mu, nu: ozaki1.fused_matmul_prologue(  # noqa: E731
            a, b, mu, nu, P, safe_beta(k), blocks)
        args = [(m, k, f32), (k, n, f32), (m, 1, f32), (1, n, f32)]
    elif name == "ozaki2_vmap_wide_n":
        # olmo-1b-emu's attn_qk site in a mixed step: 8 lanes x 16 heads
        # of 128 queries against the 2,176-key view, one bn = 2,176 block.
        m, k, n = 128, 128, 2176
        assert choose_blocks(m, n, k, 1).bn == n
        fn = jax.vmap(lambda a, b: ozaki2.fused_residue_matmul(  # noqa: E731
            a, b, MODULI[:6]))
        args = [(128, 6, m, k, i8), (128, 6, k, n, i8)]
    elif name == "ozaki1_prologue_wide_k":
        # olmo-1b-emu's attn_av site in a mixed step: K is the 2,176-key
        # view, taken as one bk = 2,176 block.
        m, k, n = 128, 2176, 128
        blocks = choose_blocks(m, n, k, P, prologue_a=True, prologue_b=True)
        assert blocks.bk == k
        fn = lambda a, b, mu, nu: ozaki1.fused_matmul_prologue(  # noqa: E731
            a, b, mu, nu, P, safe_beta(k), blocks)
        args = [(m, k, f32), (k, n, f32), (m, 1, f32), (1, n, f32)]
    elif name == "ozaki1_mixed_wide_n":
        # An unpadded OLMo vocabulary (50,304 = 393 x 128) as a prepared
        # logits weight at its m_hint of 512 rows: bn = 384.
        m, k, n = 512, 2048, 50304
        blocks = choose_blocks(m, n, k, P, prologue_a=True)
        assert blocks.bn == 384
        fn = lambda a, b, mu, nu: ozaki1.fused_matmul_mixed(  # noqa: E731
            a, b, mu, nu, P, safe_beta(k), blocks)
        args = [(m, k, f32), (P * k, n, i8), (m, 1, f32), (1, n, f32)]
    elif name == "ozaki1_interleaved":
        blocks = choose_blocks(N, N, N, P)
        fn = lambda a, b, mu, nu: ozaki1.fused_matmul_interleaved(  # noqa: E731
            a, b, mu, nu, P, beta, blocks)
        args = [(N, P * N, i8), (P * N, N, i8), (N, 1, f32), (1, N, f32)]
    elif name == "ozaki2":
        fn = lambda a, b: ozaki2.fused_residue_matmul(a, b, MODULI)  # noqa: E731
        args = [(len(MODULI), N, N, i8), (len(MODULI), N, N, i8)]
    elif name == "ozaki3m":
        fn = lambda a, b: ozaki3m.fused_3m_residue_matmul(  # noqa: E731
            a, b, MODULI)
        args = [(len(MODULI), 3, N3M, N3M, i8), (len(MODULI), 3, N3M, N3M, i8)]
    elif name == "decompose_interleave":
        fn = lambda a, mu: decompose.decompose_interleave(  # noqa: E731
            a, mu, P, beta)
        args = [(N, N, f32), (N, 1, f32)]
    else:
        raise ValueError(name)
    return fn, [_shape(one_chip, s[:-1], s[-1]) for s in args]


@pytest.mark.parametrize("name", ["ozaki1_prologue", "ozaki1_interleaved",
                                  "ozaki1_prologue_whole_k", "ozaki2",
                                  "ozaki3m", "decompose_interleave",
                                  "ozaki2_vmap_wide_n",
                                  "ozaki1_prologue_wide_k",
                                  "ozaki1_mixed_wide_n"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache,
                                 monkeypatch):
    # The host is a CPU, so the kernels would interpret; steer them to
    # the Mosaic lowering the chip runs.
    monkeypatch.setattr(base, "interpret", lambda: False)
    fn, shapes = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
