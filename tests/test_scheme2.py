"""Ozaki Scheme II: moduli, residues, balanced-Garner CRT, precision."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import scheme2
from repro.core.precision import (DEFAULT_MODULI, EmulationConfig,
                                  default_moduli, scheme2_budget)


def test_moduli_pairwise_coprime():
    for i, a in enumerate(DEFAULT_MODULI):
        for b in DEFAULT_MODULI[i + 1:]:
            assert math.gcd(a, b) == 1, (a, b)
    assert all(m <= 256 for m in DEFAULT_MODULI)


def test_balanced_residues_congruent_and_int8(rng):
    x = jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (64, 64)), jnp.float32)
    moduli = default_moduli(6)
    res = scheme2.balanced_residues(x, moduli)
    xn = np.asarray(x, np.int64)
    for l, m in enumerate(moduli):
        r = np.asarray(res[l], np.int64)
        assert (np.abs(r) <= m // 2).all()
        assert ((r - xn) % m == 0).all(), f"not congruent mod {m}"


@given(st.integers(2, 15), st.data())
@settings(max_examples=40, deadline=None)
def test_crt_roundtrip_exact(p, data):
    """Property: any integer in (-P/2, P/2] reconstructs exactly through
    residues -> balanced Garner digits -> double-double assembly, up to
    the dd precision (~2^-48 relative for f32 pairs)."""
    moduli = default_moduli(p)
    p_prod = math.prod(moduli)
    lim = min(p_prod // 2 - 1, 2 ** 45)  # within f32-dd exact range
    xs = data.draw(st.lists(st.integers(-lim, lim), min_size=1, max_size=8))
    arr = np.asarray(xs, np.int64).reshape(1, -1)
    res = jnp.stack([jnp.asarray(((arr % m) + m) % m, jnp.int32)
                     for m in moduli])
    out = np.asarray(scheme2.crt_reconstruct(res, moduli, jnp.float32),
                     np.float64)
    # exact up to the float32 *output* rounding (the dd interior is wider)
    rel_err = np.abs(out - arr) / np.maximum(np.abs(arr), 1)
    assert (rel_err <= 2 ** -23).all(), (xs, out)


def test_balanced_garner_high_digits_vanish():
    """A small value's balanced mixed-radix digits are zero beyond the
    first few — the property that kills the catastrophic cancellation of
    'evaluate then subtract P'."""
    moduli = default_moduli(12)
    x = np.asarray([[12345]], np.int64)
    res = jnp.stack([jnp.asarray(x % m, jnp.int32) for m in moduli])
    digits = scheme2.garner_digits(res, moduli)
    assert all(int(d[0, 0]) == 0 for d in digits[3:])


@pytest.mark.parametrize("p,min_bits", [(6, 12), (8, 17), (12, 19)])
def test_precision_grows_with_moduli(make_matrix, p, min_bits):
    a = jnp.asarray(make_matrix((128, 128)))
    b = jnp.asarray(make_matrix((128, 128)))
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    c = np.asarray(scheme2.matmul(a, b, EmulationConfig(scheme="ozaki2", p=p),
                                  jnp.float32))
    rel = np.abs(c - ref).max() / np.abs(ref).max()
    assert -np.log2(rel) >= min_bits


def test_fp64_grade_with_x64(make_matrix):
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        a = ((rng.random((128, 128)) - 0.5)
             * np.exp(2.0 * rng.standard_normal((128, 128))))
        b = ((rng.random((128, 128)) - 0.5)
             * np.exp(2.0 * rng.standard_normal((128, 128))))
        ref = np.asarray(a, np.longdouble) @ np.asarray(b, np.longdouble)
        c = np.asarray(scheme2.matmul(
            jnp.asarray(a), jnp.asarray(b),
            EmulationConfig(scheme="ozaki2", p=15), jnp.float64))
        rel = float(np.abs(c.astype(np.longdouble) - ref).max()
                    / np.abs(ref).max())
        assert -np.log2(rel) > 40   # far beyond fp32's 24 bits


def test_fp64_scales_on_tpu_take_no_64bit_bitcast(monkeypatch):
    """XLA's TPU backend lowers no bitcast of a 64-bit type, so there the
    float64 scale is read through float32: equal to the IEEE one, or one
    power of two smaller where the float32 rounding crosses a power."""
    rng = np.random.default_rng(5)
    with jax.enable_x64(True):
        a = ((rng.random((8, 32)) - 0.5)
             * np.exp(4.0 * rng.standard_normal((8, 32))))
        a[0] = np.linspace(-0.5, 0.5, 32)
        a[0, 0] = 1.0 - 2.0 ** -40          # rounds up to 1.0 in float32
        a = jnp.asarray(a)
        want = np.asarray(scheme2._pow2_int_scale(a, 1, 52))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        hlo = jax.jit(lambda x: scheme2.integerize(x, axis=1, budget_bits=52)
                      ).lower(a).as_text()
        casts = [ln for ln in hlo.splitlines() if "bitcast_convert" in ln]
        assert casts and not [ln for ln in casts if re.search("[if]64>", ln)]
        a_int, got = scheme2.integerize(a, axis=1, budget_bits=52)
    ratio = want / np.asarray(got)
    assert set(np.unique(ratio)) <= {1.0, 2.0} and ratio[0, 0] == 2.0
    assert np.abs(np.asarray(a_int)).max() < 2.0 ** 52


def test_budget_respects_crt_bound():
    for p in (4, 8, 15):
        moduli = default_moduli(p)
        k = 4096
        bits = scheme2_budget(moduli, k)
        # 2 * K * 2^b * 2^b < P must hold
        assert 2 * k * (2 ** bits) ** 2 < math.prod(moduli)


def test_linear_gemm_count():
    assert EmulationConfig(scheme="ozaki2", p=15).gemm_count() == 15


def _chain_digits(res, moduli):
    """Garner's O(p^2) chain, as the CRT took it before the fold: canonical
    residues, then t <- (t - d_j) inv(m_j, m_i) mod m_i for each j < i,
    balanced by t > m_i // 2 -> t - m_i (numpy int64, test-only)."""
    inv = scheme2.garner_constants(tuple(moduli))
    res = [np.mod(np.asarray(r, np.int64), m) for r, m in zip(res, moduli)]
    digits = []
    for i, m in enumerate(moduli):
        t = res[i]
        for j in range(i):
            t = np.mod((t - digits[j]) * int(inv[i, j]), m)
        digits.append(np.where(t > m // 2, t - m, t))
    return digits


def _residue_forms(rng, moduli, form, shape=(48, 40)):
    """Residues of random integers in the forms the CRT is handed: the
    kernels' balanced int8 (with -128 planted at modulus 256), or int32
    sums t3 - t1 - t2 of canonical residues, as complex3m combines its
    three products."""
    if form == "int8":
        res = rng.integers(-128, 128, (len(moduli), *shape)).astype(np.int8)
        for l, m in enumerate(moduli):
            if m == 256:
                res[l, :3] = -128
        return res
    t = [rng.integers(0, m, (3, *shape)) for m in moduli]
    return np.stack([t3 - t1 - t2 for t1, t2, t3 in t]).astype(np.int32)


def _assert_digits_equal(res, moduli):
    got = scheme2.garner_digits(jnp.asarray(res), moduli)
    want = _chain_digits(res, moduli)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=f"digit {i}")


@pytest.mark.parametrize("form", ["int8", "int32-sums"])
@pytest.mark.parametrize("p", [2, 3, 6, 8, 16])
def test_folded_digits_equal_garners_chain(p, form):
    moduli = default_moduli(p)
    _assert_digits_equal(_residue_forms(np.random.default_rng(p), moduli,
                                        form), moduli)


@pytest.mark.parametrize("moduli", [
    (255, 256),          # a power of two after the first
    (255, 254, 253),     # an even modulus that is not a power of two
    (7, 16, 9),          # small moduli: int8 residues span many periods
])
def test_folded_digits_with_an_even_modulus_after_the_first(moduli):
    rng = np.random.default_rng(len(moduli))
    for form in ("int8", "int32-sums"):
        _assert_digits_equal(_residue_forms(rng, moduli, form), moduli)


def test_garner_folded_table():
    """Row i folds Garner's i steps: C[i,j] is the product of the inverses
    of m_j .. m_{i-1} modulo m_i, below m_i (246 at most by default)."""
    moduli = default_moduli(16)
    table = scheme2.garner_folded(moduli)
    for i, m in enumerate(moduli):
        assert len(table[i]) == i
        for j, c in enumerate(table[i]):
            assert 0 < c < m
            assert c * math.prod(moduli[j:i]) % m == 1
    assert max(max(row) for row in table if row) == 246


@pytest.mark.parametrize("p,dtype", [(6, "float32"), (6, "bfloat16"),
                                     (16, "float64")])
def test_crt_unscale_of_int8_residues_equals_canonical(p, dtype):
    """The kernels' int8 residues go into the CRT as they are and give the
    bits that their canonical form gives, and that Garner's chain gave."""
    moduli = default_moduli(p)
    rng = np.random.default_rng(p)
    res8 = _residue_forms(rng, moduli, "int8")
    canon = np.stack([np.mod(r.astype(np.int64), m)
                      for r, m in zip(res8, moduli)]).astype(np.int32)
    with jax.enable_x64(dtype == "float64"):
        out_t = jnp.dtype(dtype).type
        mu = jnp.asarray(2.0 ** rng.integers(-3, 4, (res8.shape[1], 1)),
                         out_t)
        nu = jnp.asarray(2.0 ** rng.integers(-3, 4, (1, res8.shape[2])),
                         out_t)
        crt = jax.jit(lambda r, u, v: scheme2.crt_unscale(r, moduli, out_t,
                                                          u, v))
        got = np.asarray(crt(jnp.asarray(res8), mu, nu))
        want = np.asarray(crt(jnp.asarray(canon), mu, nu))
        hi, lo = scheme2.mixed_radix_to_dd(
            [jnp.asarray(d, jnp.int32) for d in _chain_digits(res8, moduli)],
            moduli)
        chain = np.asarray((hi.astype(out_t) + lo.astype(out_t)).astype(out_t)
                           / (mu * nu))
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, chain)


def _count_primitives(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` in ``jaxpr``, nested jaxprs
    (jit, cond, map bodies) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_primitives(inner, name)
    return n


@pytest.mark.parametrize("p,dtype", [(6, "float32"), (16, "float64")])
def test_crt_takes_one_float32_quotient_remainder_per_digit(p, dtype):
    """The jaxpr of the CRT holds no integer remainder (digit 0 may take
    one) and two float32 quotients for each of the ``crt_remainders``
    digits; nothing in Garner's digits widens to 64 bits under x64."""
    moduli = default_moduli(p)
    res = jax.ShapeDtypeStruct((p, 8, 128), jnp.int8)
    with jax.enable_x64(True):
        crt = jax.make_jaxpr(lambda r: scheme2.crt_reconstruct(
            r, moduli, jnp.dtype(dtype).type))(res).jaxpr
        digits = jax.make_jaxpr(lambda r: scheme2.garner_digits(
            r, moduli))(res).jaxpr
    assert _count_primitives(crt, "rem") <= 1
    assert scheme2.crt_remainders(moduli) == p - 1
    assert _count_primitives(crt, "floor") == 2 * (p - 1)
    widths = {str(v.aval.dtype) for e in digits.eqns for v in e.outvars}
    assert not [w for w in widths if "64" in w], widths
