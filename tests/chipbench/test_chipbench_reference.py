"""The plain references, and what the seed decides."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import common  # noqa: E402
from chipbench.drivers import _serve, serve_open_loop  # noqa: E402
from chipbench.reference import gemm as gref  # noqa: E402
from chipbench.reference import olmo as oref  # noqa: E402

SMOKE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "d_ff": 128, "vocab": 500, "rope_theta": 10000.0,
         "layer_norm_eps": 1e-6}
BIG_SEED = 3_000_000_017          # beyond 32 signed bits, as the driver's


def test_olmo_reference_matches_the_program_at_smoke_size():
    """The plain forward against repro.models under NATIVE_POLICY and
    'highest', on the benchmark's weights, at olmo_1b.smoke() size."""
    from repro.configs import olmo_1b
    from repro.models import model as M
    from repro.models.common import NATIVE_POLICY
    mcfg = olmo_1b.smoke().model
    assert (mcfg.n_layers, mcfg.d_model, mcfg.n_heads, mcfg.d_ff,
            mcfg.vocab) == (2, 64, 4, 128, 500)
    w = oref.init_weights(common.key_of(5, 1), SMOKE, jnp.float32)
    tokens = jnp.asarray(common.rng_of(5, 2).integers(0, 500, 40),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = M.forward_train(w, mcfg, {"tokens": tokens[None]},
                               NATIVE_POLICY, remat=False)[0][0, :, :500]
        got = oref.forward(w, SMOKE, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_served_gaps_are_zero_for_the_references_own_argmax():
    w = oref.init_weights(common.key_of(6, 1), SMOKE, jnp.float32)
    tokens = jnp.asarray(common.rng_of(6, 2).integers(0, 500, 32),
                         jnp.int32)
    logits = oref.forward(w, SMOKE, tokens)
    best = jnp.argmax(logits, -1).astype(jnp.int32)
    items = oref.cfg_items(SMOKE)
    assert float(oref.served_gaps(w, items, tokens, best).max()) \
        == 0.0
    worst = jnp.argmin(logits, -1).astype(jnp.int32)
    assert float(oref.served_gaps(w, items, tokens, worst).min()) > 0


def test_gemm_reference_normalized_error():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((4, 16)).astype(np.float32)
    b = rng.standard_normal((16, 8)).astype(np.float32)
    r = gref.rows_product(a, b)
    assert gref.normalized_error(r, r, a, b) == 0.0
    c = r.copy()
    c[1, 2] += 16 * np.abs(a[1]).max() * np.abs(b[:, 2]).max() * 1e-3
    assert gref.normalized_error(c, r, a, b) > 0.9e-3 * \
        np.abs(b[:, 2]).max() / np.abs(b).max(0).max()


def test_a_seed_always_makes_the_same_operands_and_two_seeds_differ():
    make = jax.jit(lambda k: gref.operands(k, 8, 16, 4, 4.0))
    a1, b1 = make(common.key_of(BIG_SEED, 1))
    a2, b2 = make(common.key_of(BIG_SEED, 1))
    a3, _ = make(common.key_of(BIG_SEED + 1, 1))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    assert not np.array_equal(np.asarray(a1), np.asarray(a3))


def test_a_seed_always_makes_the_same_weights_and_two_seeds_differ():
    w1 = oref.init_weights(common.key_of(BIG_SEED, 1), SMOKE, jnp.bfloat16)
    w2 = oref.init_weights(common.key_of(BIG_SEED, 1), SMOKE, jnp.bfloat16)
    w3 = oref.init_weights(common.key_of(7, 1), SMOKE, jnp.bfloat16)
    assert w1["emb"].dtype == jnp.bfloat16
    for x, y in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(w1["emb"]), np.asarray(w3["emb"]))


def test_prompts_follow_the_seed_and_sizes_and_arrivals_do_not():
    """Every seed offers the same load, so a run's spread is not the
    seed's: the seed draws the token ids and the weights; the lengths
    and the arrivals are the traffic file's, the same for any seed."""
    traffic = {"prompt_tokens": {"dist": "lognormal", "median": 512,
                                 "sigma": 0.7, "min": 256, "max": 2048},
               "output_tokens": {"dist": "uniform", "min": 8, "max": 32}}
    lens = _serve.sizes(traffic, 90, 2048)
    assert lens == _serve.sizes(traffic, 90, 2048)
    assert all(256 <= p and 8 <= o <= 32 and p + o <= 2048 for p, o in lens)
    assert sorted(p for p, _ in lens)[45] in range(480, 545)    # median
    p1 = _serve.prompts(BIG_SEED, [p for p, _ in lens], 50304)
    assert p1 == _serve.prompts(BIG_SEED, [p for p, _ in lens], 50304)
    assert p1 != _serve.prompts(BIG_SEED + 1, [p for p, _ in lens], 50304)
    due = serve_open_loop.arrivals(2.0, 45.0)
    assert due == serve_open_loop.arrivals(2.0, 45.0)
    assert len(due) == 90 and due[0] == 0.0 and due[-1] < 45.0
    assert all(b > a for a, b in zip(due, due[1:]))


@pytest.mark.parametrize("n", [18, 21, 26, 90])
def test_arrivals_are_not_drawn_in_the_order_of_the_prompts(n):
    """The gap after a request does not follow its prompt's length: the
    two are drawn in fixed orders of their own."""
    traffic = {"prompt_tokens": {"dist": "lognormal", "median": 512,
                                 "sigma": 0.7, "min": 256, "max": 2048},
               "output_tokens": {"dist": "uniform", "min": 8, "max": 32}}
    rate = n / 51.0
    due = serve_open_loop.arrivals(rate, 51.0)
    assert len(due) == n
    gaps = np.diff(due + [51.0])
    prompts = [p for p, _ in _serve.sizes(traffic, n, 2048)]
    rank = lambda v: np.argsort(np.argsort(v, kind="stable"), kind="stable")
    assert not np.array_equal(rank(gaps), rank(prompts))
    assert abs(np.corrcoef(rank(gaps), rank(prompts))[0, 1]) < 0.5


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (100, 10), (1, 1)])
def test_percentile_is_a_measured_value(q, want):
    assert common.percentile(range(1, 11), q) == want
