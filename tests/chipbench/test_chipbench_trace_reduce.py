"""The trace reduction: on hand-made pieces, and on a recorded chip trace
kept beside this file."""

import pathlib

import pytest

import chipbench_testlib as lib
from chipbench import readers, trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main.1 () -> f32[] {
  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/emugemm/ozaki1-p4/xla/xla/add"}
  %emugemm2_p6.7 = s8[8]{0} custom-call(), custom_call_target="tpu_custom_call"
  ROOT %fusion.4 = f32[] fusion(), kind=kLoop, metadata={op_name="jit(step)/gather"}
}
"""


def test_hlo_scopes_read_the_scope_and_the_kernel_names():
    scopes = tr.hlo_scopes([HLO])
    assert tr.classify("%fusion.3 = f32[8]{0} fusion()", scopes) == (
        "emugemm/ozaki1-p4:fusion", "emugemm/ozaki1-p4")
    assert tr.classify("%emugemm2_p6.7 = s8[8]{0} custom-call()",
                       scopes) == ("emugemm2_p6", "emugemm2_p6.7")
    assert tr.classify("%fusion.4 = f32[] fusion()", scopes) == (
        "fusion", None)
    # a kernel found by its name alone, without the compiled text
    assert tr.classify("%decompose_interleave_p4.2 = s8[4]{0} "
                       "custom-call(f32[4]{0} %x)", {})[1] is not None


def test_union_and_span_at():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans = [("step", 0, 100), ("execute", 10, 60), ("commit", 60, 100)]
    assert tr.span_at(30, spans) == "execute"
    assert tr.span_at(80, spans) == "commit"
    assert tr.span_at(5, spans) == "step"
    assert tr.span_at(500, spans) == "none"


def test_readers_find_nothing_and_return_nothing():
    r = tr.Reduction(window_s=1.0, busy_s=0.0, emulated_s=0.0, op_s={},
                     idle_by_span={}, span_count={}, span_s={})
    assert readers.emugemm_roofline(r) is None
    assert readers.device_idle(r) is None
    assert readers.step_ms(r) is None
    assert readers.mfu_serve(r) is None
    assert readers.mfu_gemm(r) is None


def test_heads_match_between_the_trace_and_the_compiled_text():
    line = ('  ROOT %emugemm1_p4_ff.1 = f32[2048,2048]{1,0:T(8,128)} '
            'custom-call(%custom-call, %copy-done), custom_call_target=x')
    event = ('%emugemm1_p4_ff.1 = f32[2048,2048]{1,0:T(8,128)} custom-call('
             'f32[2048,2048]{1,0:T(8,128)S(1)} %custom-call)')
    assert tr.head(line) == tr.head(event)
    assert tr.head(line)[0] == "emugemm1_p4_ff.1"
    tup = ('%while.2 = (s32[]{:T(128)}, bf16[8,1,2048]{2,0,1:T(8,128)(2,1)})'
           ' while((s32[]{:T(128)}) %tuple.9)')
    assert tr.head(tup)[1].endswith(" while")


def test_recorded_chip_trace():
    """Three ozaki1-p4 calls at 2048^3, then three XLA f32 HIGHEST dots
    at the same shape, traced on one TPU v5e; with the emulated
    program's compiled text."""
    hlo = (DATA / "sgemm_2048_hlo.txt").read_text()
    red = tr.reduce(DATA / "sgemm_2048.xplane.pb", [hlo])
    # no 'window' span in this trace: the window runs from the first op
    # to the last
    assert 0 < red.busy_s <= red.window_s
    kernel = red.op_s["emugemm1_p4_ff"]
    scoped = sum(v for k, v in red.op_s.items() if k.startswith("emugemm/"))
    assert kernel > 0 and scoped > 0
    assert red.emulated_s == pytest.approx(kernel + scoped)
    # the HIGHEST dots are not emulated, and take about as long
    assert 0.5 < red.op_s["fusion"] / kernel < 1.5
    assert red.span_count == {"execute": 1}
    red.work = {"emugemm_least_s": 3 * 10 * 2 * 2048**3 / 393e12}
    from chipbench import work
    red.peaks = work.peaks("TPU v5 lite")
    share = readers.emugemm_roofline(red)
    assert 30 < share < 100
    idle = readers.device_idle(red)
    assert 0 < idle < 100
    names = [n for n, _ in red.breakdown()["device_ops"]]
    assert names[0] == "emugemm1_p4_ff"


def test_an_instruction_name_reused_by_two_programs_is_told_apart():
    a = ('HloModule jit_step\n  %fusion.1 = f32[8]{0} fusion(%p), '
         'metadata={op_name="jit(step)/emugemm/ozaki2-m6/tpu/pallas/x"}\n')
    b = ('HloModule jit_step\n  %fusion.1 = bf16[4,4]{1,0} fusion(%q), '
         'metadata={op_name="jit(step)/gather"}\n')
    scopes = tr.hlo_scopes([a, b])
    assert tr.classify("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                       scopes) == ("emugemm/ozaki2-m6:fusion",
                                   "emugemm/ozaki2-m6")
    assert tr.classify("%fusion.1 = bf16[4,4]{1,0} fusion(bf16[4] %q)",
                       scopes) == ("fusion", None)


def test_leaves_drop_containers_and_empty_events():
    evs = [(0, 100, "while"), (0, 10, "a"), (10, 90, "b"), (90, 100, "c"),
           (100, 100, "empty"), (100, 150, "k"), (100, 100, "cc")]
    assert [n for _, _, n in tr.leaves(evs)] == ["a", "b", "c", "k"]


def test_a_traced_run_whose_spans_go_missing_fails_loudly(
        tmp_path, monkeypatch, capsys):
    """The per-layer metrics read the harness's spans: a traced window
    that lacks one the driver ran is an error, not a silent metric."""
    import contextlib

    from chipbench import common
    root = lib.bench_copy(tmp_path)
    lib.add_config(root, lib.TINY_GEMM)
    cell = lib.add_cell(root, "tiny-gemm", "sq", per_layer=["mfu.gemm"])
    monkeypatch.setattr(common.Spans, "__call__",
                        lambda self, name: contextlib.nullcontext())
    with pytest.raises(RuntimeError, match="'call' spans"):
        lib.run(root, lib.argv(cell, seconds=1.0, trace=1), monkeypatch,
                capsys)


def test_an_engine_part_the_harness_wraps_has_to_exist():
    from chipbench import common
    from chipbench.drivers import _serve

    class Engine:
        def plan(self):
            return 1
    eng = Engine()
    _serve.hook(eng, "plan", common.Spans())
    assert eng.plan() == 1
    with pytest.raises(RuntimeError, match="_execute is gone"):
        _serve.hook(eng, "_execute", common.Spans(), "execute")
