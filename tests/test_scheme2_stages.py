"""Scheme II's stage scopes, its operand-budget and CRT-remainder gauges
and its row-blocked CRT.

Every real Scheme-II lowering (the fused kernel path, the XLA expansion
and both 3M paths) names its ``encode`` and ``crt`` stages inside its
``emugemm/...`` scope, so a profiler trace can split its device time;
each plan records the operand budget it took and the remainders per
output its CRT takes; and a product of more than ``scheme2.CRT_BLOCK``
outputs reconstructs its rows in blocks, with the same bits as the
unblocked form.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import telemetry
from repro.core import scheme2
from repro.core.precision import default_moduli
from repro.telemetry import record
from repro.telemetry.trace import stage_scope

DIMS = (((1,), (0,)), ((), ()))
OPNAME = re.compile(r'op_name="([^"]*)"')


def op_names(text: str) -> list:
    return OPNAME.findall(text)


def stage_ops(text: str, tag: str, stage: str) -> list:
    """op_names of ``text`` under the ``tag`` scope and then ``stage``."""
    pat = re.compile(re.escape(tag) + r"/(?:.*/)?" + stage + r"(?:/|$)")
    return [n for n in op_names(text) if pat.search(n)]


@pytest.mark.parametrize("path,spec,dtype,tag", [
    ("fused", "ozaki2-m16", "float64", "emugemm/ozaki2-m16/tpu/pallas"),
    ("xla", "ozaki2-m16", "float64", "emugemm/ozaki2-m16/xla/xla"),
    ("fused-3m", "ozaki2-m8", "complex64", "emugemm/ozaki2-3m-m8/tpu/pallas"),
    ("xla-3m", "ozaki2-m8", "complex64", "emugemm/ozaki2-3m-m8/xla/xla"),
])
def test_compiled_scheme2_call_carries_the_stage_scopes(path, spec, dtype,
                                                        tag):
    cfg = repro.resolve_config(spec)
    if path.startswith("xla"):
        cfg = dataclasses.replace(cfg, impl="xla")
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        a = jnp.asarray(rng.random((128, 128)) - 0.5, dtype)
        b = jnp.asarray(rng.random((128, 128)) - 0.5, dtype)
        text = jax.jit(lambda x, y: repro.dot_general(
            x, y, DIMS, precision=cfg)).lower(a, b).compile().as_text()
    encode = stage_ops(text, tag, "encode")
    crt = stage_ops(text, tag, "crt")
    assert encode and crt, (path, sorted(set(op_names(text)))[:20])
    # Neither stage holds the other's ops.
    assert not any("/crt/" in n for n in encode)
    assert not any("/encode/" in n for n in crt)


def test_stage_scope_refuses_an_unknown_stage():
    with pytest.raises(ValueError, match="unknown emulated-GEMM stage"):
        with stage_scope("epilogue"):
            pass


def budget_gauges() -> dict:
    snap = telemetry.REGISTRY.snapshot()
    return {(g["labels"]["moduli"], g["labels"]["dtype"]): g["value"]
            for g in snap["gauges"]
            if g["name"] == record.SCHEME2_BUDGET_BITS}


@pytest.mark.parametrize("spec,dtype,shapes,labels,bits", [
    # The DGEMM cell: float64 at 16 moduli and K = 8192 (55 bits allowed
    # by the moduli, capped at 52 by the float64 integerize).
    ("ozaki2-m16", "float64", ((128, 8192), (8192, 128)), ("16", "float64"),
     52),
    # olmo-1b-emu's attn_qk site: bfloat16 queries against a 2,176-key
    # view, head size 128, six moduli: the bf16 mantissa caps at 8.
    ("ozaki2-m6", "bfloat16", ((128, 128), (128, 2176)), ("6", "bfloat16"),
     8),
])
def test_budget_gauge_records_the_plans_budget(spec, dtype, shapes, labels,
                                               bits):
    jax.clear_caches()             # a cached lowering is not traced again
    telemetry.REGISTRY.clear(record.SCHEME2_BUDGET_BITS)
    with jax.enable_x64(True), telemetry.recording():
        a = jax.ShapeDtypeStruct(shapes[0], jnp.dtype(dtype))
        b = jax.ShapeDtypeStruct(shapes[1], jnp.dtype(dtype))
        jax.jit(lambda x, y: repro.dot_general(
            x, y, DIMS, precision=spec)).lower(a, b)
    assert budget_gauges() == {labels: bits}
    moduli, dt = labels
    assert (f'repro_scheme2_budget_bits{{dtype="{dt}",moduli="{moduli}"}} '
            f'{bits}') in telemetry.render_prometheus()
    telemetry.REGISTRY.clear(record.SCHEME2_BUDGET_BITS)


def test_budget_gauge_is_silent_with_telemetry_off():
    jax.clear_caches()
    telemetry.REGISTRY.clear(record.SCHEME2_BUDGET_BITS)
    assert not telemetry.enabled()
    with jax.enable_x64(True):
        a = jax.ShapeDtypeStruct((128, 256), jnp.float64)
        b = jax.ShapeDtypeStruct((256, 128), jnp.float64)
        jax.jit(lambda x, y: repro.dot_general(
            x, y, DIMS, precision="ozaki2-m16")).lower(a, b)
    assert budget_gauges() == {}


def remainder_gauges() -> dict:
    snap = telemetry.REGISTRY.snapshot()
    return {g["labels"]["moduli"]: g["value"] for g in snap["gauges"]
            if g["name"] == record.SCHEME2_CRT_REMAINDERS}


@pytest.mark.parametrize("spec,dtype,shapes,count", [
    # 15 remainders per output where Garner's chain took 120, and the
    # residues' canonicalization 16 more.
    ("ozaki2-m16", "float64", ((128, 256), (256, 128)), 15),
    ("ozaki2-m6", "bfloat16", ((128, 128), (128, 2176)), 5),
])
def test_crt_remainders_gauge_records_the_plans_count(spec, dtype, shapes,
                                                      count):
    jax.clear_caches()
    telemetry.REGISTRY.clear(record.SCHEME2_CRT_REMAINDERS)
    with jax.enable_x64(True), telemetry.recording():
        a = jax.ShapeDtypeStruct(shapes[0], jnp.dtype(dtype))
        b = jax.ShapeDtypeStruct(shapes[1], jnp.dtype(dtype))
        jax.jit(lambda x, y: repro.dot_general(
            x, y, DIMS, precision=spec)).lower(a, b)
    moduli = spec.rsplit("m", 1)[1]
    assert remainder_gauges() == {moduli: count}
    assert (f'repro_scheme2_crt_remainders{{moduli="{moduli}"}} {count}'
            in telemetry.render_prometheus())
    telemetry.REGISTRY.clear(record.SCHEME2_CRT_REMAINDERS)


def test_crt_remainders_gauge_is_silent_with_telemetry_off():
    jax.clear_caches()
    telemetry.REGISTRY.clear(record.SCHEME2_CRT_REMAINDERS)
    assert not telemetry.enabled()
    with jax.enable_x64(True):
        a = jax.ShapeDtypeStruct((128, 256), jnp.float64)
        b = jax.ShapeDtypeStruct((256, 128), jnp.float64)
        jax.jit(lambda x, y: repro.dot_general(
            x, y, DIMS, precision="ozaki2-m16")).lower(a, b)
    assert remainder_gauges() == {}


@pytest.mark.parametrize("m,n,rows", [
    (8192, 8192, 128),     # the DGEMM cell: 64 blocks of 128 rows
    (1024, 1024, 1024),    # at CRT_BLOCK: one block
    (128, 2176, 128),      # olmo-1b-emu's attn_qk call: unblocked
    (3000, 1000, 1000),    # the largest divisor of M under the cap
    (2053, 1024, 1),       # a prime M: one row at a time
])
def test_crt_block_rows(m, n, rows):
    assert scheme2.crt_block_rows(m, n) == rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("canonical", [True, False])
def test_blocked_crt_is_bit_identical(monkeypatch, dtype, canonical):
    moduli = default_moduli(16 if dtype == jnp.float64 else 8)
    rng = np.random.default_rng(3)
    m, n = 96, 40
    with jax.enable_x64(True):
        x = rng.integers(-2**40, 2**40, (m, n)) if dtype == jnp.float64 \
            else rng.integers(-2**20, 2**20, (m, n))
        res = np.stack([np.mod(x, mm) for mm in moduli])
        if not canonical:        # the kernels' balanced int8 form
            res = np.stack([((r + mm // 2) % mm) - mm // 2
                            for r, mm in zip(res, moduli)]).astype(np.int8)
        res = jnp.asarray(res if not canonical else res.astype(np.int32))
        mu = jnp.asarray(2.0 ** rng.integers(-3, 4, (m, 1)), dtype)
        nu = jnp.asarray(2.0 ** rng.integers(-3, 4, (1, n)), dtype)

        def run():
            return np.asarray(jax.jit(lambda r, u, v: scheme2.crt_unscale(
                r, moduli, dtype, u, v))(res, mu, nu))

        whole = run()
        monkeypatch.setattr(scheme2, "CRT_BLOCK", 24 * n)
        assert scheme2.crt_block_rows(m, n) == 24
        blocked = run()
    np.testing.assert_array_equal(blocked, whole)
    want = x / (np.asarray(mu, np.float64) * np.asarray(nu, np.float64))
    np.testing.assert_allclose(whole, want.astype(dtype), rtol=0, atol=0)


def test_fused_dgemm_with_blocked_crt_matches_unblocked(monkeypatch):
    """The front door on float64 at 16 moduli, its CRT in four row blocks
    (the Mosaic kernel in interpret mode), bit for bit against one block."""
    rng = np.random.default_rng(5)
    with jax.enable_x64(True):
        a = jnp.asarray(rng.random((512, 256)) - 0.5)
        b = jnp.asarray(rng.random((256, 128)) - 0.5)

        def run():
            jax.clear_caches()
            return np.asarray(repro.dot_general(a, b, DIMS,
                                                precision="ozaki2-m16"))

        whole = run()
        monkeypatch.setattr(scheme2, "CRT_BLOCK", 128 * 128)
        blocked = run()
    np.testing.assert_array_equal(blocked, whole)
    ref = np.asarray(a, np.longdouble) @ np.asarray(b, np.longdouble)
    err = np.abs(whole - ref).max() / np.abs(ref).max()
    assert err < 2.0 ** -48


def _dekker_mul_scalar(hi, lo, c):
    """The double-double product by a constant through Dekker's two_prod
    (the split at half the significand), as the CRT formed it before."""
    from repro.core import dd
    c = jnp.asarray(float(c), hi.dtype)
    p1, p2 = dd.two_prod(hi, c)
    return dd.quick_two_sum(p1, p2 + lo * c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_mul_scalar_matches_dekkers_product(dtype):
    """Splitting by the constant's width gives the same bits as Dekker's
    product wherever neither overflows."""
    from repro.core import dd
    rng = np.random.default_rng(11)
    with jax.enable_x64(True):
        hi = jnp.asarray(rng.standard_normal(4096)
                         * 2.0 ** rng.integers(-20, 60, 4096), dtype)
        lo = hi * jnp.asarray(rng.uniform(-1, 1, 4096) * 2.0 ** -30, dtype)
        for c in default_moduli(16):
            got = dd.mul_scalar(hi, lo, c)
            want = _dekker_mul_scalar(hi, lo, c)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mul_scalar_stays_finite_near_the_top_of_the_range():
    """float32 stands in for float64 with float32's exponent range (a
    TPU's): a Horner value of 1.5 * 2^116 times a modulus is finite, but
    Dekker's split scales it by 2^12 + 1 past the largest float32 and
    leaves a NaN."""
    from repro.core import dd
    hi = jnp.asarray([1.5 * 2.0 ** 116, -1.25 * 2.0 ** 115], jnp.float32)
    lo = jnp.zeros_like(hi)
    got_hi, got_lo = dd.mul_scalar(hi, lo, 241)
    assert np.all(np.isfinite(np.asarray(got_hi)))
    assert np.all(np.isfinite(np.asarray(got_lo)))
    np.testing.assert_array_equal(
        np.asarray(got_hi, np.float64) + np.asarray(got_lo, np.float64),
        np.asarray(hi, np.float64) * 241)
    assert np.isnan(np.asarray(_dekker_mul_scalar(hi, lo, 241)[1])).any()
