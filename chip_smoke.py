"""Chip smoke test: run the emulated-GEMM library and olmo-1b-emu serving
once on a TPU, through the entry points a user calls, and check results.

  python chip_smoke.py             # one chip: the GEMM phase, then serving
  python chip_smoke.py --chips 4   # four chips: the shard_map'ed GEMM only

Every phase prints one line (compile seconds, error against the reference
and its bound, ``tpu_custom_call`` count, fallback counters); the last line
is one JSON object, ``{"ok": true, "device": {...}}``.  A failed phase
raises, and the script exits nonzero without that line.  It refuses to run
anywhere but on a TPU: the CPU path of this repository is the test suite.
One process drives the chip; nothing here starts another.

Error bounds are normalized per element: ``e = max_ij |C - R|_ij /
(K * rowmax_i|A| * colmax_j|B|)``.  Each scheme's bound follows from its
bits (derivations at ``scheme1_bound`` / ``scheme2_bound``), so a wrong
scale, a dropped slice pair or a corrupt residue shows up as a miss.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

S1_N = 4096          # Scheme I: square f32 GEMM
S2_N = 2048          # Scheme II: square f64 DGEMM and complex 3M GEMM
S2_MODULI = 16       # 52-bit operand budget at K=2048: FP64 grade
S3M_MODULI = 8       # 24-bit budget at K=2048: all a complex64 holds
REF_ROWS = 32        # longdouble reference rows per Scheme-II result
# serve: max |emulated - native| / max |native| over the prefill logits.
# bf16 activations carry 8 bits and every layer re-rounds them, and the
# config's attn_qk site (ozaki2-m6) integerizes its bf16 operands at an
# 8-bit budget, 2^-7 of the row max.  On random weights that noise grows
# with depth: at reduced width (d=512, 16 layers, 128 tokens) the same
# policy in its XLA expansion on CPU gave 0.18, emulating the
# projections alone 0.07.  A broken emulation (a wrong scale, garbage
# residues) moves the logits by their whole range.
LOGITS_BOUND = 0.25


def require_tpu(count: int):
    """The devices of a TPU host with at least ``count`` chips, or exit."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; jax found platform "
                         f"{platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips; found "
                         f"{len(devices)}")
    return devices


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def kernel_count(compiled) -> int:
    """Mosaic kernels in a compiled program."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def fallback_counts() -> dict:
    """``repro_fallback_total`` by reason since the last reset."""
    from repro import telemetry
    from repro.telemetry import record
    out: dict = {}
    for labels, v in telemetry.REGISTRY.series(record.FALLBACK_EVENTS):
        out[labels.get("reason", "-")] = out.get(labels.get("reason", "-"),
                                                 0) + int(v)
    return out


def reset_counters() -> None:
    from repro import telemetry
    telemetry.REGISTRY.clear()


def conditioned(rng, shape, dtype=np.float32, phi=4.0):
    """Paper Eq. 19 inputs (phi=4 conditioning), as in benchmarks/common."""
    return ((rng.random(shape) - 0.5)
            * np.exp(phi * rng.standard_normal(shape))).astype(dtype)


def normalized_error(c, ref, a, b, rows=None) -> float:
    """max_ij |C - R| / (K rowmax_i|A| colmax_j|B|) over ``rows`` of C.

    Complex operands use max(|re|, |im|), the magnitude Scheme II's 3M
    path scales by."""
    def mag(x):
        return (np.maximum(np.abs(x.real), np.abs(x.imag))
                if np.iscomplexobj(x) else np.abs(x))
    rows = slice(None) if rows is None else rows
    rmax = mag(a).max(axis=1)[rows].astype(np.float64)
    cmax = mag(b).max(axis=0).astype(np.float64)
    denom = a.shape[1] * rmax[:, None] * cmax[None, :]
    diff = np.abs(np.asarray(c)[rows].astype(ref.dtype) - ref)
    return float((diff.astype(np.float64) / denom).max())


def bits(c, ref, rows=None) -> float:
    rows = slice(None) if rows is None else rows
    c = np.asarray(c)[rows].astype(ref.dtype)
    rel = float(np.abs(c - ref).max() / np.abs(ref).max())
    return -np.log2(rel) if rel > 0 else float("inf")


def scheme1_bound(p: int, beta: int) -> float:
    """Scheme I, f32 out.  Slicing keeps p beta-bit slices of a/mu with
    |a/mu| < 1 and mu <= 2 rowmax, so each operand's remainder is below
    2^-(p beta) of mu; the triangular schedule drops slice pairs with
    s + t >= p, at most (p - 1) 2^-(p beta) of mu nu per product.  Per
    product that is (p + 1) 2^-(p beta) mu nu <= 4 (p + 1) 2^-(p beta)
    rowmax colmax.  The f32 epilogue rounds each of the p int32
    accumulators and their weighted sum: (p + 1) 2^-23 on the same
    scale.  Summed over K."""
    return 4 * (p + 1) * (2.0 ** (-p * beta) + 2.0 ** -23)


def scheme2_bound(budget: int, k: int, complex_3m: bool,
                  out_dtype) -> float:
    """Scheme II.  Integerizing to ``budget`` bits leaves each element
    within 2^(1-budget) rowmax of its value, so a product is off by at
    most 2^(2-budget) rowmax colmax (3M: two products per output part,
    twice that); the CRT is exact.  Rounding the result, up to K rowmax
    colmax per part, to ``out_dtype`` twice (the double-double
    conversion, then the inverse scaling) adds 2u of it, and the
    longdouble reference itself K 2^-64 per product sum."""
    parts = 2 if complex_3m else 1
    u = 2.0 ** -(np.finfo(out_dtype).nmant + 1)
    return parts * (2.0 ** (2 - budget) + k * 2.0 ** -64 + 2 * u)


# ---------------------------------------------------------------------------
# GEMM phase: the library front door on the fused kernels.
# ---------------------------------------------------------------------------

def run_gemm(name, a, b, cfg, check):
    """One ``emulated_matmul`` on the chip: compile, run, check."""
    import jax
    from repro.kernels import dispatch
    reset_counters()
    t0 = time.perf_counter()
    fn = jax.jit(lambda x, y: dispatch.emulated_matmul(x, y, cfg=cfg))
    xa, xb = jax.device_put(a), jax.device_put(b)
    compiled = fn.lower(xa, xb).compile()
    t_compile = time.perf_counter() - t0
    out = np.asarray(jax.block_until_ready(compiled(xa, xb)))
    kernels = kernel_count(compiled)
    fallbacks = fallback_counts()
    if not np.all(np.isfinite(out)):
        raise AssertionError(f"{name}: non-finite output")
    if kernels < 1:
        raise AssertionError(f"{name}: no tpu_custom_call in the program")
    if fallbacks:
        raise AssertionError(f"{name}: fell back to XLA: {fallbacks}")
    err, bound, extra = check(out)
    say("gemm", case=name, compile_s=f"{t_compile:.2f}",
        err=f"{err:.3e}", bound=f"{bound:.3e}", kernels=kernels,
        fallbacks=fallbacks or 0, **extra)
    if not err <= bound:
        raise AssertionError(f"{name}: error {err:.3e} over bound "
                             f"{bound:.3e}")
    return out


def gemm_phase(n1: int = S1_N, seed: int = 0) -> None:
    """Scheme I at p=4 in f32, both decomposition routes."""
    from repro.core.precision import EmulationConfig, safe_beta
    from repro.telemetry import enable
    enable()
    rng = np.random.default_rng(seed)

    # Scheme I, p=4, f32: in-kernel prologue and the XLA split+interleave.
    a, b = conditioned(rng, (n1, n1)), conditioned(rng, (n1, n1))
    ref = a.astype(np.float64) @ b.astype(np.float64)
    p, beta = 4, safe_beta(n1)
    outs = {}
    for decomp in ("kernel", "xla"):
        cfg = EmulationConfig(scheme="ozaki1", p=p, impl="pallas",
                              decomp=decomp)
        outs[decomp] = run_gemm(
            f"ozaki1-p{p}-{n1}-decomp={decomp}", a, b, cfg,
            lambda c: (normalized_error(c, ref, a, b),
                       scheme1_bound(p, beta),
                       {"bits": f"{bits(c, ref):.1f}"}))
    say("gemm", case=f"ozaki1-p{p} prologue==interleaved",
        bit_identical=bool(np.array_equal(outs["kernel"], outs["xla"])))


def gemm_x64_phase(n2: int = S2_N, seed: int = 1) -> None:
    """Scheme II DGEMM in float64 and the complex 3M GEMM.  Run last: x64
    mode must not change the dtypes of any other phase.

    The 3M case runs in complex64: a TPU holds no complex128 (its
    compiler refuses or aborts on c128 values), so ZGEMM operands cannot
    reach the chip as complex arrays."""
    import jax
    from repro.core.precision import EmulationConfig, default_moduli
    from repro.core.scheme2 import operand_budget
    from repro.telemetry import enable
    enable()
    rng = np.random.default_rng(seed)
    rows = np.linspace(0, n2 - 1, REF_ROWS).astype(int)

    def case(name, a, b, n_moduli, complex_3m):
        moduli = default_moduli(n_moduli)
        cfg = EmulationConfig(scheme="ozaki2", p=len(moduli), moduli=moduli,
                              impl="pallas")
        real_t = np.real(a).dtype
        # the library's own budget: the moduli's, capped by the mantissa
        budget = operand_budget(moduli, n2, real_t, complex_guard=complex_3m)
        ref = (a[rows].astype(np.clongdouble if complex_3m else np.longdouble)
               @ b.astype(np.clongdouble if complex_3m else np.longdouble))
        run_gemm(f"ozaki2-m{len(moduli)}-{name}-{n2}", a, b, cfg,
                 lambda c: (normalized_error(c, ref, a, b, rows),
                            scheme2_bound(budget, n2, complex_3m, real_t),
                            {"budget_bits": budget,
                             "bits": f"{bits(c, ref, rows):.1f}",
                             "ref_rows": len(rows)}))

    def cplx():
        return (conditioned(rng, (n2, n2)) + 1j * conditioned(rng, (n2, n2))
                ).astype(np.complex64)

    with jax.enable_x64(True):
        case("dgemm", conditioned(rng, (n2, n2), np.float64),
             conditioned(rng, (n2, n2), np.float64), S2_MODULI, False)
        case("cgemm3m", cplx(), cplx(), S3M_MODULI, True)


# ---------------------------------------------------------------------------
# Serve phase: olmo-1b-emu through the continuous-batching engine.
# ---------------------------------------------------------------------------

def serve_phase(arch, *, requests: int = 8, prompt_len: int = 256,
                gen: int = 32, lanes: int = 4, chunk: int = 64,
                seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_trace
    from repro.models import model as M
    from repro.models.common import NATIVE_POLICY
    from repro.serving import ContinuousEngine
    from repro.telemetry import enable
    enable()
    reset_counters()
    mcfg = arch.model
    mesh = make_host_mesh()
    rng = np.random.default_rng(seed)
    trace = build_trace(rng, mcfg.vocab, requests, prompt_len, gen, 0.0)
    with mesh:
        params = M.init_params(jax.random.PRNGKey(seed), mcfg)
        t0 = time.perf_counter()
        eng = ContinuousEngine(arch, mesh, max_seq=prompt_len + gen,
                               params=params, max_lanes=lanes, chunk=chunk)
        results = eng.run(trace)
        t_serve = time.perf_counter() - t0
        fallbacks = fallback_counts()
        served = 0
        for r in trace:
            res = results[r.rid]
            toks = np.asarray(res.tokens)
            if res.status != "done" or len(toks) != gen:
                raise AssertionError(f"request {r.rid}: {res.status} with "
                                     f"{len(toks)}/{gen} tokens")
            if toks.min() < 0 or toks.max() >= mcfg.vocab:
                raise AssertionError(f"request {r.rid}: token out of range")
            served += 1
        say("serve", arch=mcfg.name, requests=served, tokens=served * gen,
            steps=eng.utilization()["steps"],
            wall_s_incl_compile=f"{t_serve:.2f}",
            fallbacks=json.dumps(fallbacks, sort_keys=True))

        # The compiled mixed prefill/decode step must hold Mosaic kernels.
        t0 = time.perf_counter()
        step_args = (eng.params, eng.pools,
                     eng.kv.tables_for([None] * lanes),
                     jnp.zeros((lanes, chunk), jnp.int32),
                     jnp.zeros((lanes,), jnp.int32),
                     jnp.zeros((lanes,), jnp.int32))
        compiled = eng._jit_fns[chunk].lower(*step_args).compile()
        kernels = kernel_count(compiled)
        say("serve", step=f"{lanes}x{chunk}", kernels=kernels,
            compile_s=f"{time.perf_counter() - t0:.2f}")
        if kernels < 1:
            raise AssertionError("serve step holds no tpu_custom_call")

        # Emulated prefill logits against the native f32-precision path.
        prompts = jnp.asarray([r.prompt for r in trace[:2]], jnp.int32)

        def prefill(p, tokens, policy):
            return M.forward_prefill(p, mcfg, {"tokens": tokens},
                                     prompt_len, policy)[0]

        emu = jax.jit(lambda p, t: prefill(p, t, eng.policy))(eng.params,
                                                              prompts)
        with jax.default_matmul_precision("highest"):
            nat = jax.jit(lambda p, t: prefill(p, t, NATIVE_POLICY))(params,
                                                                     prompts)
    emu = np.asarray(emu[..., :mcfg.vocab], np.float32)
    nat = np.asarray(nat[..., :mcfg.vocab], np.float32)
    if not np.all(np.isfinite(emu)):
        raise AssertionError("emulated prefill logits are not finite")
    rel = float(np.abs(emu - nat).max() / np.abs(nat).max())
    agree = float(np.mean(emu.argmax(-1) == nat.argmax(-1)))
    say("serve", check="prefill logits vs native highest",
        rel_err=f"{rel:.3e}", bound=LOGITS_BOUND, argmax_agree=agree)
    if not rel <= LOGITS_BOUND:
        raise AssertionError(f"prefill logits off by {rel:.3e} (bound "
                             f"{LOGITS_BOUND})")


# ---------------------------------------------------------------------------
# Four chips: the fused GEMM under shard_map on a (data=1, model=4) mesh.
# ---------------------------------------------------------------------------

def shard_phase(devices, tile: int = 2048, seed: int = 0) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.precision import EmulationConfig, safe_beta
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.parallel import shard_gemm
    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    tp = len(devices)
    p = 4
    cfg = EmulationConfig(scheme="ozaki1", p=p, impl="pallas")
    rng = np.random.default_rng(seed)
    # Each shard tile is ``tile`` wide: N/4 for the column layout, K/4
    # for the row layout (an N of tile + 2 does not divide 4, which
    # selects it).
    cases = (("column", 2 * tile, 2 * tile, tile * tp,
              (P(), P(None, "model"))),
             ("row", 2 * tile, tile * tp, tile + 2,
              (P(None, "model"), P("model", None))))
    for kind, m, k, n, specs in cases:
        part = shard_gemm.gemm_partition(m, k, n, mesh)
        if part is None or part.kind != kind:
            raise AssertionError(f"{kind}: partition chose {part}")
        a, b = conditioned(rng, (m, k)), conditioned(rng, (k, n))
        one = jax.jit(lambda x, y: dispatch.emulated_matmul(x, y, cfg=cfg))
        single = np.asarray(one(jax.device_put(a, devices[0]),
                                jax.device_put(b, devices[0])))
        xa = jax.device_put(a, NamedSharding(mesh, specs[0]))
        xb = jax.device_put(b, NamedSharding(mesh, specs[1]))
        t0 = time.perf_counter()
        compiled = jax.jit(
            lambda x, y: shard_gemm.sharded_matmul(x, y, cfg, mesh)
        ).lower(xa, xb).compile()
        t_compile = time.perf_counter() - t0
        out = jax.block_until_ready(compiled(xa, xb))
        text = compiled.as_text()
        placement = sorted((s.device.id, tuple(s.data.shape))
                           for s in out.addressable_shards)
        b_place = sorted((s.device.id, tuple(s.data.shape))
                         for s in xb.addressable_shards)
        collectives = {op: text.count(f" {op}(")
                       for op in ("all-reduce", "all-gather",
                                  "reduce-scatter", "collective-permute")}
        out = np.asarray(out)
        ref = a.astype(np.float64) @ b.astype(np.float64)
        err = normalized_error(out, ref, a, b)
        bound = scheme1_bound(p, safe_beta(k)) + 4 * 2.0 ** -24
        identical = bool(np.array_equal(out, single))
        say("shard", partition=kind, shape=f"{m}x{k}x{n}", mesh="1x4",
            compile_s=f"{t_compile:.2f}", kernels=kernel_count(compiled),
            collectives=json.dumps(collectives), b_shards=b_place,
            out_shards=placement, err=f"{err:.3e}", bound=f"{bound:.3e}",
            single_err=f"{normalized_error(single, ref, a, b):.3e}",
            bit_identical_to_single=identical)
        if len({d for d, _ in placement}) != tp:
            raise AssertionError(f"{kind}: output not on all {tp} devices")
        if kernel_count(compiled) < 1:
            raise AssertionError(f"{kind}: no tpu_custom_call")
        if kind == "column" and not identical:
            raise AssertionError("column partition is not bit-identical "
                                 "to the single-device GEMM")
        if kind == "row" and collectives["all-reduce"] < 1:
            raise AssertionError("row partition holds no all-reduce")
        if not err <= bound:
            raise AssertionError(f"{kind}: error {err:.3e} over bound "
                                 f"{bound:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map'ed GEMM on four chips")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro import configs
    from repro.launch.compile_cache import place_compile_cache
    say("device", platform=devices[0].platform,
        kind=repr(devices[0].device_kind), count=len(devices),
        compile_cache=place_compile_cache())
    if args.chips == 4:
        shard_phase(devices[:4])
    else:
        gemm_phase()
        serve_phase(configs.get_config("olmo-1b-emu"))
        gemm_x64_phase()
    from repro.core import traffic
    peak = traffic.device_peak(devices[0].device_kind)
    say("device", peaks=f"{peak.name}: {peak.int8_ops / 1e12:g} TOP/s int8, "
        f"{peak.flops / 1e12:g} TFLOP/s bf16, {peak.hbm_bw / 1e9:g} GB/s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
