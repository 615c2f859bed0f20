"""The benchmark's own arithmetic against hand counts."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import work  # noqa: E402

OLMO_1B = {"d_model": 2048, "n_heads": 16, "n_kv_heads": 16, "d_ff": 8192,
           "n_layers": 16, "vocab": 50304, "dtype": "bfloat16",
           "gemm_sites": {"default": "ozaki1-p4+cached",
                          "attn_qk": "ozaki2-m6", "attn_av": "ozaki1-p4"}}
PEAKS = work.Peaks("test", int8_ops_per_s=1e3, bf16_flops_per_s=1e2,
                   hbm_bytes_per_s=1e2, hbm_bytes=1e9)


@pytest.mark.parametrize("spec,products", [
    ("ozaki1-p4", 10), ("ozaki1-p4+cached", 10), ("ozaki1-p1", 1),
    ("ozaki1-p3@tpu", 6), ("ozaki2-m6", 6), ("ozaki2-m16", 16)])
def test_int8_products_follow_the_declared_precision(spec, products):
    assert work.int8_products(spec) == products


@pytest.mark.parametrize("spec", ["native", "bits=50", "ozaki3-p4"])
def test_a_spec_that_is_not_emulated_is_refused(spec):
    with pytest.raises(ValueError):
        work.int8_products(spec)


def test_site_ops_bytes_and_least_time_by_hand():
    s = work.Site("x", "ozaki1-p2", m=2, k=3, n=4, batch=5)
    # 3 products of 2*2*3*4 = 48 operations, 5 times
    assert s.int8_ops == 5 * 3 * 48 == 720
    # f32 operands read once and the output written once: 4 (6 + 12 + 8)
    assert s.bytes == 5 * 4 * 26 == 520
    assert s.least_s(PEAKS) == pytest.approx(max(720 / 1e3, 520 / 1e2))
    compute = work.Site("y", "ozaki2-m6", m=64, k=64, n=64,
                        a_bytes=1, b_bytes=1, out_bytes=1)
    assert compute.least_s(PEAKS) == pytest.approx(6 * 2 * 64**3 / 1e3)
    assert work.least_s([s, compute], PEAKS) == pytest.approx(
        s.least_s(PEAKS) + compute.least_s(PEAKS))


def test_square_16k_least_time_on_v5e():
    pk = work.peaks("TPU v5 lite")
    s = work.Site("gemm", "ozaki1-p4", 16384, 16384, 16384)
    assert s.int8_ops == 10 * 2 * 16384**3
    assert s.least_s(pk) == pytest.approx(10 * 2 * 16384**3 / 393e12)
    assert work.gemm_flops(16384, 16384, 16384) == 2 * 16384**3


def test_peaks_are_keyed_by_device_kind_and_an_unknown_chip_raises():
    pk = work.peaks("TPU v5 lite")
    assert (pk.int8_ops_per_s, pk.bf16_flops_per_s, pk.hbm_bytes_per_s) \
        == (393e12, 197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v4"):
        work.peaks("TPU v4")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_olmo_1b_layer_weights_by_hand():
    # q, k, v, o: 4 * 2048^2; SwiGLU: 3 * 2048 * 8192
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert work.layer_params(OLMO_1B) == per_layer == 67_108_864
    assert 16 * per_layer == 1_073_741_824


def test_served_token_flops_by_hand():
    L, d = 16, 2048
    assert work.served_token_flops(OLMO_1B, 0) == L * (2 * 67_108_864
                                                        + 4 * d)
    assert work.served_token_flops(OLMO_1B, 99) == L * (2 * 67_108_864
                                                         + 4 * d * 100)
    assert work.logits_flops(OLMO_1B) == 2 * d * 50304


def test_step_flops_are_the_sum_over_tokens_and_emitting_lanes():
    start, n_new, emit = [0, 300, 0, 17], [128, 1, 0, 5], [False, True,
                                                          False, True]
    want = 0
    for s, n, e in zip(start, n_new, emit):
        want += sum(work.served_token_flops(OLMO_1B, s + i)
                    for i in range(n))
        want += work.logits_flops(OLMO_1B) if e else 0
    assert work.step_model_flops(OLMO_1B, start, n_new, emit) == want


def test_serve_step_sites_count_the_compiled_shape():
    sites = {s.name: s for s in work.serve_step_sites(OLMO_1B, lanes=8,
                                                      chunk=128, view=2176)}
    ffn = sites["ffn_in"]             # gate and up, all 16 layers
    assert (ffn.m, ffn.k, ffn.n, ffn.batch) == (1024, 2048, 8192, 32)
    assert ffn.products == 10 and ffn.a_bytes == 2
    qk = sites["attn_qk"]             # per lane and head, over the view
    assert (qk.m, qk.k, qk.n, qk.batch, qk.products) == (128, 128, 2176,
                                                         8 * 16 * 16, 6)
    assert sites["logits"].m == 8 and sites["logits"].n == 50688
    decode = {s.name: s for s in work.serve_step_sites(OLMO_1B, 8, 1, 2176)}
    assert decode["attn_q"].m == 8 and decode["attn_av"].m == 1
