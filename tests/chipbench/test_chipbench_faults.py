"""The check fails a run whose timed path is broken underneath.

Each test skips the harness's look for a chip (a device shim) and drives
the rest of a run, with the program's timed path broken, and sees
``correct`` come out false; the same run unbroken comes out true.  The
faults are those these cells can have: an answer altered where it is
produced (a GEMM's output, a served token).  A state left unchanged and
half a batch left out are training faults, and the exchange between
chips exists only on several chips: no cell here has them."""

import pytest

import chipbench_testlib as lib


@pytest.fixture
def gemm_cell(tmp_path):
    root = lib.bench_copy(tmp_path)
    lib.add_config(root, lib.TINY_GEMM)
    return root, lib.add_cell(root, "tiny-gemm", "sq",
                              end_to_end=["gemm_tflops"])


@pytest.fixture
def serve_cell(tmp_path):
    root = lib.bench_copy(tmp_path)
    lib.add_config(root, lib.TINY_OLMO)
    return root, lib.add_cell(root, "tiny-olmo", "closed",
                              end_to_end=["output_tokens_per_s"])


@pytest.mark.parametrize("broken", [False, True])
def test_gemm_answer_altered_where_it_is_produced(gemm_cell, broken,
                                                  monkeypatch, capsys):
    import repro
    root, cell = gemm_cell
    if broken:
        sound = repro.dot_general

        def altered(*a, **kw):
            out = sound(*a, **kw)
            return out * (1 + 2.0 ** -14)       # one part in 16384
        monkeypatch.setattr(repro, "dot_general", altered)
    rc, res, out = lib.run(root, lib.argv(cell, seconds=0.5), monkeypatch,
                           capsys)
    assert rc == 0
    assert res["correct"] is (not broken), out


@pytest.mark.parametrize("broken", [False, True])
def test_served_token_altered_where_it_is_produced(serve_cell, broken,
                                                   monkeypatch, capsys):
    from repro.serving import engine
    root, cell = serve_cell
    if broken:
        sound = engine.ContinuousEngine._execute

        def altered(self, plan, tables):
            tok = sound(self, plan, tables)
            return (tok + 1) % self.mcfg.vocab
        monkeypatch.setattr(engine.ContinuousEngine, "_execute", altered)
    rc, res, out = lib.run(root, lib.argv(cell, seed=21, seconds=2.0),
                           monkeypatch, capsys)
    assert rc == 0
    assert res["attempted"] > 0
    assert res["correct"] is (not broken), out
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
