"""The control of each check, at a size a test run can hold: bf16 in
three passes for an f32 GEMM, the program's own int8 path for a bf16
model.  The control has to fail the tiny cell's check, through the
harness's own comparison, where the program passes it."""

import json

import pytest

import chipbench_testlib as lib


def readings(tmp_path, monkeypatch, capsys, config, traffic, seeds,
             seconds):
    from chipbench import compile_cache, control
    root = lib.bench_copy(tmp_path)
    lib.add_config(root, config)
    cell = lib.add_cell(root, config["name"], traffic)
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "off")
    rc = control.main(["--workload", cell, "--seconds", str(seconds),
                       "--seeds", *map(str, seeds)],
                      devices_fn=lib.fake_devices, root=root)
    assert rc == 0
    out = capsys.readouterr().out
    return [json.loads(ln) for ln in out.splitlines()
            if ln.startswith("{")]


def test_gemm_control_fails_the_limit_the_program_meets(
        tmp_path, monkeypatch, capsys):
    got = readings(tmp_path, monkeypatch, capsys, lib.TINY_GEMM, "sq",
                   [1, 2, 3], 1)
    limit = lib.TINY_TRAFFIC["sq"]["check"]["norm_err"]
    assert len(got) == 3
    for g in got:
        prog, ctrl = g["norm_err"]["program"], g["norm_err"]["control"]
        assert prog < limit < ctrl
        assert ctrl >= 3 * prog


@pytest.mark.parametrize("traffic", ["closed"])
def test_serve_control_fails_the_check_the_program_passes(
        tmp_path, monkeypatch, capsys, traffic):
    got = readings(tmp_path, monkeypatch, capsys, lib.TINY_OLMO, traffic,
                   [4], 2)
    g = got[0]
    assert set(g["gemm_sites"].values()) == {"ozaki1-p1"}
    assert g["readings"]["tokens"] > 0
    assert g["correct"] is False
    root = lib.bench_copy(tmp_path / "program")
    lib.add_config(root, lib.TINY_OLMO)
    cell = lib.add_cell(root, "tiny-olmo", traffic)
    rc, res, out = lib.run(root, lib.argv(cell, seed=4, seconds=2.0),
                           monkeypatch, capsys)
    assert rc == 0 and res["correct"] is True, out
    for name, c in g["checks"].items():
        assert c["value"] > res["checks"][name]["value"]


def test_sweep_reports_the_backlog_at_each_rate(tmp_path, monkeypatch,
                                                capsys):
    from chipbench import compile_cache, sweep
    root = lib.bench_copy(tmp_path)
    lib.add_config(root, lib.TINY_OLMO)
    cell = lib.add_cell(root, "tiny-olmo", "open")
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "off")
    assert sweep.main(["--workload", cell, "--rates", "1", "4",
                       "--seconds", "1.5"], devices_fn=lib.fake_devices,
                      root=root) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [g["rate_per_s"] for g in lines] == [1.0, 4.0]
    for g in lines:
        assert g["requests_due"] >= 1
        assert {"backlog_mid", "backlog_end", "ttft_p90_ms"} <= set(g)
