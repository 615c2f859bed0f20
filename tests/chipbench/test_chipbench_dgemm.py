"""The DGEMM cell's pieces: HPL's operand law, the longdouble reference,
the check at the cell's limit, the residue kernel's count, the driver on
the CPU, and the stage reader (``chipbench/scheme2_trace.py``) on a small
DGEMM trace recorded on one TPU v5e (``record_dgemm_trace.py``: M = K = N
= 2048 at ozaki2-m16, two calls inside a ``window`` span), kept gzipped
beside this file."""

import fractions
import gzip
import json
import pathlib

import numpy as np
import pytest

import chipbench_testlib as lib
from chipbench import harness, scheme2_trace, scheme2_work, work
from chipbench.drivers import dgemm_loop
from chipbench.reference import dgemm as ref

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "dgemm-ozaki2-m16.square-8k"
NEW_METRICS = ("mfu.dgemm", "emugemm2_roofline.dgemm", "encode_ms.dgemm",
               "crt_ms.dgemm", "device_idle.dgemm")
TRACE_N = 2048
TRACE_CALLS = 2


def cell_files():
    _, cell, config, traffic = harness.find_cell(lib.ROOT, CELL)
    return cell, config, traffic


# ---------------------------------------------------------------------------
# The operands and the reference.
# ---------------------------------------------------------------------------

def test_operand_law_is_hpl_uniform_with_full_significands():
    x = ref.hpl_uniform(np.random.default_rng(7), (400, 500))
    assert x.dtype == np.float64
    assert -0.5 <= x.min() and x.max() <= 0.5
    assert abs(x.mean()) < 0.01 and abs(x.std() - 12 ** -0.5) < 0.01
    tz = ref.trailing_zero_bits(x)
    # A random 53-bit significand ends in a set bit half of the time and
    # in k zero bits with odds 2^-(k+1).
    assert 0.48 < np.mean(tz == 0) < 0.52
    assert tz.max() < 30
    # The foil: a float32 draw cast to float64 leaves 29 bits at zero.
    assert ref.trailing_zero_bits(x.astype(np.float32)).min() >= 29


def test_operands_follow_the_seed():
    one = ref.operands(np.random.default_rng([11, 1]), 64, 32, 16)
    again = ref.operands(np.random.default_rng([11, 1]), 64, 32, 16)
    other = ref.operands(np.random.default_rng([12, 1]), 64, 32, 16)
    assert [x.shape for x in one] == [(64, 32), (32, 16)]
    for x, y, z in zip(one, again, other):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)


def test_longdouble_reference_against_exact_sums():
    """A few entries of the reference against their exact rational sums:
    np.longdouble (x86: a 64-bit significand) lands within a few 2^-64
    of K rowmax colmax, sixteen times or more closer than a float64
    product does."""
    assert np.finfo(np.longdouble).nmant >= 63
    rng = np.random.default_rng(3)
    k = 4096
    a = ref.hpl_uniform(rng, (3, k))
    b = ref.hpl_uniform(rng, (k, 4))
    got, f64 = ref.rows_product(a, b), a @ b
    worst_ld = worst_f64 = 0.0
    for i, j in [(0, 0), (1, 3), (2, 1)]:
        exact = sum(fractions.Fraction(x) * fractions.Fraction(y)
                    for x, y in zip(a[i], b[:, j]))
        scale = k * np.abs(a[i]).max() * np.abs(b[:, j]).max()
        ld = fractions.Fraction(*got[i, j].as_integer_ratio())
        worst_ld = max(worst_ld, abs(float(ld - exact)) / scale)
        worst_f64 = max(worst_f64, abs(float(
            fractions.Fraction(f64[i, j]) - exact)) / scale)
    assert worst_ld < 2.0 ** -60
    assert worst_ld < worst_f64 / 16


def test_bits_reads_the_largest_relative_error():
    r = np.asarray([[1.0, -4.0]], np.longdouble)
    assert ref.bits(r + np.asarray([[2.0 ** -30, 0]]), r) == pytest.approx(32)
    assert ref.bits(r, r) == float("inf")


# ---------------------------------------------------------------------------
# The check at the cell's limit, and the residue kernel's count.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision,passes", [("ozaki2-m16", True),
                                              ("ozaki2-m8", False)])
def test_the_cells_check_at_256(precision, passes):
    """At 256^3 on the CPU (IEEE float64, the kernels interpreted): the
    program's precision passes the cell's limit, its control fails it."""
    _, config, traffic = cell_files()
    got = dgemm_loop.readings(config, traffic, seed=3141592653,
                              precision=precision, size=256)
    assert got["correct"] is passes
    assert got["fallbacks"] == 0
    if passes:
        assert got["budget_bits"] == 52 and got["bits"] > 48
    else:
        assert got["norm_err"] > 100 * got["limit"]


def test_the_configuration_names_its_control():
    _, config, _ = cell_files()
    assert config["precision"] == "ozaki2-m16"
    assert config["dtype"] == "float64"
    assert config["control"]["precision"] == "ozaki2-m8"
    assert config["reduced"] == []


def test_residue_kernel_count_at_8192():
    pk = work.peaks("TPU v5 lite")
    n = 8192
    assert scheme2_work.kernel_ops(16, n, n, n) == 16 * 2 * n ** 3
    assert scheme2_work.kernel_bytes(16, n, n, n) == 16 * 3 * n * n
    least = scheme2_work.kernel_least_s(16, n, n, n, pk)
    # compute-bound: 1.76e13 int8 operations at 393 TOP/s
    assert least == pytest.approx(0.04476, rel=1e-3)
    assert scheme2_work.kernel_bytes(16, n, n, n) / pk.hbm_bytes_per_s \
        < least / 10


# ---------------------------------------------------------------------------
# The driver, end to end on the CPU.
# ---------------------------------------------------------------------------

def test_the_driver_runs_a_tiny_dgemm_cell(tmp_path, monkeypatch, capsys):
    root = lib.bench_copy(tmp_path)
    _, config, _ = cell_files()
    lib.add_config(root, dict(config, name="tiny-dgemm"))
    name = "tiny-dgemm.sq"
    (root / "chipbench" / "traffic" / f"{name}.json").write_text(json.dumps(
        {"config": "tiny-dgemm", "driver": "dgemm_loop", "m": 256,
         "k": 256, "n": 256, "trace_seconds": 0.5,
         "check": {"rows": 8, "norm_err": 1e-15}}))

    def edit(s):
        s["workloads"].append({"name": name, "config": "tiny-dgemm",
                               "traffic": "sq", "chips": 1, "why": "t"})
        for m in s["end_to_end"] + s["per_layer"]:
            if m["name"] == "gemm_tflops" or m["name"] in NEW_METRICS:
                m["workloads"].append(name)
    lib._edit(root, edit)
    rc, result, out = lib.run(root, ["--workload", name, "--seed",
                                     "3000000001", "--seconds", "1",
                                     "--trace", "0"], monkeypatch, capsys)
    assert rc == 0
    assert result["correct"] and result["attempted"] >= 1
    assert set(result["metrics"]) == {"gemm_tflops", "setup_s"}
    assert result["checks"]["fallbacks"] == {"value": 0, "limit": 0}
    line = json.loads(next(ln for ln in out.splitlines()
                           if ln.startswith('{"dgemm"')))
    assert line["budget_bits"] == 52 and line["operands_kept"]
    assert line["bits"] > 48


# ---------------------------------------------------------------------------
# The stage reader, on the recorded trace.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dgemm_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("dgemm")
    path = d / "dgemm_2048.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "dgemm_2048.xplane.pb.gz").read_bytes()))
    text = gzip.decompress(
        (DATA / "dgemm_2048_hlo.txt.gz").read_bytes()).decode()
    return d, path, text


def stage_work(red, calls=TRACE_CALLS):
    least = scheme2_work.kernel_least_s(16, TRACE_N, TRACE_N, TRACE_N,
                                        work.peaks("TPU v5 lite"))
    return {"calls": calls,
            "int8_ops": calls * 16 * work.gemm_flops(TRACE_N, TRACE_N,
                                                     TRACE_N),
            "kernel_least_s": calls * least,
            **{f"{s}_s": v for s, v in red.stage_s.items()}}


def test_the_recorded_call_splits_into_its_stages(dgemm_trace):
    _, path, text = dgemm_trace
    heads = scheme2_trace.stage_heads([text])
    assert set(heads.values()) == {"encode", "kernel", "crt"}
    red = scheme2_trace.reduce(path, [text])
    assert all(red.stage_s[s] > 0 for s in scheme2_trace.STAGES)
    assert red.share() >= 0.9
    assert 0 < red.busy_s <= red.window_s
    assert sum(red.stage_s.values()) >= red.busy_s * 0.999


def test_the_recorded_trace_through_the_metric_files(dgemm_trace):
    d, path, text = dgemm_trace
    red = scheme2_trace.reduce(path, [text])
    metrics = traced_metrics(d, [text], stage_work(red))
    assert set(metrics) == set(NEW_METRICS)
    assert 0 < metrics["emugemm2_roofline.dgemm"]["value"] <= 100
    assert 0 < metrics["mfu.dgemm"]["value"] <= 100
    assert 0 <= metrics["device_idle.dgemm"]["value"] < 100
    per_call = {s: 1e3 * v / TRACE_CALLS for s, v in red.stage_s.items()}
    assert metrics["encode_ms.dgemm"]["value"] == pytest.approx(
        per_call["encode"])
    assert metrics["crt_ms.dgemm"]["value"] == pytest.approx(per_call["crt"])


def test_a_program_without_stage_scopes_reads_no_stage_metrics(dgemm_trace):
    """The parent's program names no stages: encode and CRT read as
    None and leave the line; the kernel, mfu and idle still read."""
    d, path, text = dgemm_trace
    bare = text.replace("/encode/", "/").replace("/crt/", "/")
    red = scheme2_trace.reduce(path, [bare])
    assert red.stage_s["encode"] == red.stage_s["crt"] == 0
    metrics = traced_metrics(d, [bare], stage_work(red))
    assert set(metrics) == set(NEW_METRICS) - {"encode_ms.dgemm",
                                               "crt_ms.dgemm"}


def traced_metrics(trace_dir, texts, work_dict):
    """What the harness's traced run reads for the DGEMM cell from the
    trace in ``trace_dir``, through the benchmark's own metric files."""
    ctx = harness.Context(root=lib.ROOT, name=CELL, cell={}, config={},
                          traffic={}, seed=0, seconds=1.0, trace=True,
                          devices=lib.fake_devices(1),
                          peaks=work.peaks("TPU v5 lite"), process_start=0.0,
                          trace_dir=pathlib.Path(trace_dir))
    out = harness.Outcome(attempted=TRACE_CALLS, failed=0, metrics={},
                          checks={}, memory_peak_bytes=0, hlo_texts=texts,
                          work=work_dict, spans={"call": TRACE_CALLS})
    spec = harness.load_json(lib.ROOT / "BENCHMARK.json")
    metrics, _ = harness.traced_metrics(ctx, spec, out)
    return metrics


def test_stage_of_op_name():
    tag = "jit(f)/emugemm/ozaki2-m16/tpu/pallas/jit(_fused_scheme2_matmul)"
    assert scheme2_trace.stage_of_op_name(tag + "/encode/mul") == "encode"
    assert scheme2_trace.stage_of_op_name(
        tag + "/crt/while/body/jit(remainder)/rem") == "crt"
    assert scheme2_trace.stage_of_op_name(tag + "/crt") == "crt"
    assert scheme2_trace.stage_of_op_name("jit(f)/crt/mul") is None
    assert scheme2_trace.stage_of_op_name(tag + "/encoder/mul") is None


def test_a_fusion_without_op_name_takes_its_bodys_stage():
    """XLA leaves the op_name off a multi-output fusion (a tuple root)
    and off the update fusions it makes of a concatenate; such a fusion
    takes the one stage its called computation names."""
    enc = 'op_name="jit(f)/emugemm/ozaki2-m16/tpu/pallas/encode/rem"'
    crt = 'op_name="jit(f)/emugemm/ozaki2-m16/tpu/pallas/crt/mul"'
    text = "\n".join([
        "%fused_computation.1 (param_0: s32[8]) -> s8[8] {",
        f"  %rem.1 = s32[8]{{0}} remainder(%param_0, %c), metadata={{{enc}}}",
        "  ROOT %convert.1 = s8[8]{0} convert(%rem.1)",
        "}",
        "%fused_computation.2 (param_0: s32[8]) -> f64[8] {",
        f"  %mul.2 = s32[8]{{0}} multiply(%param_0, %param_0), "
        f"metadata={{{enc}}}",
        f"  ROOT %mul.3 = f64[8]{{0}} multiply(%x, %x), metadata={{{crt}}}",
        "}",
        "ENTRY %main (a: s32[8]) -> s8[8] {",
        "  %fusion.1 = s8[8]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %fusion.2 = f64[8]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.2",
        f"  %fusion.3 = f64[8]{{0}} fusion(%a), kind=kLoop, "
        f"calls=%fused_computation.2, metadata={{{crt}}}",
        "}"])
    heads = scheme2_trace.stage_heads([text])
    assert heads["%fusion.1 = s8[8]{0} fusion"] == "encode"
    # a body that names two stages decides nothing
    assert "%fusion.2 = f64[8]{0} fusion" not in heads
    assert heads["%fusion.3 = f64[8]{0} fusion"] == "crt"
