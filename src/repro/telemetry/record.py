"""Instrumentation helpers: site context, labels, and recording primitives.

The label schema is fixed (docs/observability.md):

    site        logical call site ('attn', 'ffn', 'logits', 'emb', '-')
    scheme      emulation scheme ('ozaki1', 'ozaki2', 'ozaki2-3m')
    backend     kernel backend that ran ('tpu', 'gpu', 'xla')
    impl        lowering route ('pallas', 'xla', 'prepared-pallas',
                'prepared-xla')
    shape_class 'MxKxN' of the logical 2-D contraction, or 'BxMxKxN'
                when the call ran as one strided-batched launch
    mesh_shape  'axis=size,...' of the launch mesh, or '-'

Two recording moments, matching how the stack executes:

* **Trace time** (plan/route decisions, modeled bytes): recorded eagerly
  with a plain ``REGISTRY.inc`` while JAX traces — this is what compile-only
  flows (``launch.dryrun``) observe.
* **Execution time** (call counts, modeled HBM/collective bytes per run):
  staged as a ``jax.debug.callback`` with the labels captured statically in
  the closure — the same pattern ``repro.guard`` uses.  ``debug.callback``
  also runs immediately on eager calls, so one helper covers both.

Every helper is a no-op unless :func:`repro.telemetry.enabled` — checked
first, before any label work — so the disabled path stages nothing into
jaxprs and costs one global read.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Iterator, Mapping

from repro.telemetry import registry as _reg
from repro.telemetry.registry import REGISTRY

# Metric names (the catalog in docs/observability.md).
EMULATED_CALLS = "repro_emulated_calls_total"          # per execution
EMULATED_TRACES = "repro_emulated_traces_total"        # per trace/plan
MODELED_HBM_BYTES = "repro_modeled_hbm_bytes_total"    # per execution
BLOCK_CACHE = "repro_block_cache_total"                # hit/miss, per lookup
BLOCK_WIDE = "repro_block_wide_total"                  # wide block, per miss
PAD_EVENTS = "repro_pad_total"                         # per padded trace
FALLBACK_EVENTS = "repro_fallback_total"               # per fallback, w/ reason
BATCHED_LAUNCHES = "repro_emulated_batched_launches_total"  # per batched trace
PREPARED_CONSUME = "repro_prepared_consume_total"      # fused vs xla routes
PREPARED_BUILD = "repro_prepared_build_total"          # prepare/rebuild calls
PREPARED_REFUSALS = "repro_prepared_refusal_total"     # layout refusals
GUARD_EVENTS = "repro_guard_events_total"              # guard.stats() backing
SHARD_PARTITION = "repro_shard_partition_total"        # partition kind chosen
MODELED_COLLECTIVE_BYTES = "repro_modeled_collective_bytes_total"
STEP_SECONDS = "repro_step_seconds"                    # histogram
STEP_TOKENS_PER_S = "repro_step_tokens_per_s"          # gauge
COMPILES = "repro_compiles_total"                      # backend compiles
COMPILE_SECONDS = "repro_compile_seconds"              # histogram
GC_PAUSE_SECONDS = "repro_gc_pause_seconds"            # histogram, generation
SCHEME2_BUDGET_BITS = "repro_scheme2_budget_bits"      # gauge, moduli/dtype
SCHEME2_CRT_REMAINDERS = "repro_scheme2_crt_remainders"  # gauge, moduli

# Continuous-batching serve engine (repro.serving; docs/serving.md).
SERVE_QUEUE_DEPTH = "repro_serve_queue_depth"          # gauge, per step
SERVE_PAGE_OCCUPANCY = "repro_serve_page_occupancy"    # gauge, 0..1
SERVE_LANES_ACTIVE = "repro_serve_lanes_active"        # gauge, per step
SERVE_TOKENS = "repro_serve_tokens_total"              # counter, kind label
SERVE_REQUESTS = "repro_serve_requests_total"          # counter, outcome label
SERVE_EVICTIONS = "repro_serve_evictions_total"        # counter
SERVE_GUARD_TRIPS = "repro_serve_guard_trips_total"    # counter, per request
SERVE_TTFT_SECONDS = "repro_serve_ttft_seconds"        # histogram
SERVE_TPOT_SECONDS = "repro_serve_tpot_seconds"        # histogram
SERVE_QUEUE_WAIT_SECONDS = "repro_serve_queue_wait_seconds"  # histogram
SERVE_SITE_EXECUTIONS = "repro_serve_site_executions_total"  # chunk, tag
SERVE_STEP_COMPILES = "repro_serve_step_compiles_total"      # chunk

enabled = _reg.enabled

_tls = threading.local()


def current_site() -> str:
    """Innermost ambient call-site label, '-' when none is set."""
    stack = getattr(_tls, "sites", None)
    return stack[-1] if stack else "-"


@contextlib.contextmanager
def call_site(name: str) -> Iterator[None]:
    """Label emulated calls (traced or eager) inside the scope with ``site``."""
    stack = getattr(_tls, "sites", None)
    if stack is None:
        stack = _tls.sites = []
    stack.append(str(name))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def site_scope(name: str) -> Iterator[None]:
    """Re-establish a previously captured site label ('-' is a no-op).

    JAX re-traces custom-VJP rules at partial-eval/transpose time (grad,
    ``jax.checkpoint``) *after* the originating ``call_site`` block has
    exited, so the rules carry the site captured at the first, in-scope
    call as a static argument and re-enter it here on every re-trace.
    """
    if name == "-":
        yield
        return
    with call_site(name):
        yield


def shape_class(m: int, k: int, n: int, batch: int | None = None) -> str:
    """'MxKxN' of the 2-D contraction; 'BxMxKxN' for a strided-batched
    launch (``batch`` is the leading grid extent, not a vmap axis)."""
    core = f"{int(m)}x{int(k)}x{int(n)}"
    return core if batch is None else f"{int(batch)}x{core}"


def mesh_label(mesh_shape: Any = None) -> str:
    """'axis=size,...' for a ``((axis, size), ...)`` tuple / mapping, or '-'."""
    if not mesh_shape:
        return "-"
    items = mesh_shape.items() if hasattr(mesh_shape, "items") else mesh_shape
    return ",".join(f"{a}={int(s)}" for a, s in items) or "-"


def gemm_tag(scheme: str, count: int, backend: str, impl: str) -> str:
    """Profiler scope tag: ``emugemm/<scheme>-<p|m><count>/<backend>/<impl>``.

    Scheme I counts mantissa slices (``p``); Scheme II counts moduli
    (``m``).
    """
    unit = "m" if scheme.startswith("ozaki2") else "p"
    return f"emugemm/{scheme}-{unit}{int(count)}/{backend}/{impl}"


def gemm_labels(
    scheme: str,
    backend: str,
    impl: str,
    m: int,
    k: int,
    n: int,
    mesh_shape: Any = None,
    batch: int | None = None,
) -> dict[str, str]:
    return {
        "site": current_site(),
        "scheme": scheme,
        "backend": backend,
        "impl": impl,
        "shape_class": shape_class(m, k, n, batch),
        "mesh_shape": mesh_label(mesh_shape),
    }


def modeled_gemm_bytes(
    scheme: str, count: int, m: int, k: int, n: int,
    out_bytes: int = 4, complex_3m: bool = False,
) -> int:
    """Modeled fused HBM bytes of one emulated GEMM (paper Eq. 10/15/18)."""
    from repro.core import traffic

    s = traffic.GemmShape(int(m), int(n), int(k))
    if scheme.startswith("ozaki2"):
        complex_3m = complex_3m or scheme == "ozaki2-3m"
        per_mod = (
            traffic.scheme2_3m_fused_bytes_per_modulus(s)
            if complex_3m
            else traffic.scheme2_fused_bytes_per_modulus(s)
        )
        n_out = 2 if complex_3m else 1
        return int(count) * per_mod + n_out * out_bytes * s.m * s.n
    mult = 4 if scheme.endswith("-4m") else 1  # Scheme-I complex: 4 GEMMs
    return mult * traffic.scheme1_fused_bytes(s, int(count), out_bytes)


def _bump_gemm(labels: Mapping[str, str], nbytes: int) -> None:
    REGISTRY.inc(EMULATED_CALLS, 1, labels)
    if nbytes:
        REGISTRY.inc(MODELED_HBM_BYTES, nbytes, labels)


def record_gemm(
    *,
    scheme: str,
    count: int,
    backend: str,
    impl: str,
    m: int,
    k: int,
    n: int,
    mesh_shape: Any = None,
    out_bytes: int = 4,
    batch: int | None = None,
) -> None:
    """Record one emulated GEMM call site.

    Bumps trace-time counters eagerly (the call is being traced or run
    right now) and stages a per-execution callback for the call/byte
    counters.  All values — labels, modeled bytes — are static per call,
    so the callback closure carries them and the device sends nothing.
    ``batch`` marks a strided-batched launch: it enters the shape class
    ('BxMxKxN') and multiplies the modeled bytes (one launch moving the
    whole stack).
    """
    if not _reg.enabled():
        return
    labels = gemm_labels(scheme, backend, impl, m, k, n, mesh_shape, batch)
    try:
        nbytes = modeled_gemm_bytes(scheme, count, m, k, n, out_bytes)
        nbytes *= batch or 1
    except Exception:
        nbytes = 0
    REGISTRY.inc(EMULATED_TRACES, 1, labels)
    import jax

    jax.debug.callback(functools.partial(_bump_gemm, labels, nbytes))


def _bump_collective(labels: Mapping[str, str], nbytes: int) -> None:
    REGISTRY.inc(MODELED_COLLECTIVE_BYTES, nbytes, labels)


def record_collective(kind: str, mesh_shape: Any, nbytes_per_device: int) -> None:
    """Stage a per-execution modeled-collective-bytes bump.

    Called from inside a ``shard_map`` body, the callback fires once per
    shard, so the counter sums per-device bytes across the mesh.
    """
    if not _reg.enabled() or not nbytes_per_device:
        return
    labels = {
        "kind": kind,
        "mesh_shape": mesh_label(mesh_shape),
        "site": current_site(),
    }
    import jax

    jax.debug.callback(
        functools.partial(_bump_collective, labels, int(nbytes_per_device))
    )


def record_scheme2_budget(moduli: int, dtype: Any, bits: int) -> None:
    """Trace-time gauge: the operand budget (bits each operand is
    integerized to) a Scheme-II plan took, by residue count and operand
    dtype.  Gated on :func:`enabled`."""
    if not _reg.enabled():
        return
    import numpy as np

    REGISTRY.set_gauge(SCHEME2_BUDGET_BITS, int(bits),
                       {"moduli": int(moduli),
                        "dtype": np.dtype(dtype).name})


def record_scheme2_crt_remainders(moduli: int, remainders: int) -> None:
    """Trace-time gauge: the remainders per output a Scheme-II plan's
    CRT takes, by residue count.  Gated on :func:`enabled`."""
    if not _reg.enabled():
        return
    REGISTRY.set_gauge(SCHEME2_CRT_REMAINDERS, int(remainders),
                       {"moduli": int(moduli)})


def record_event(name: str, labels: Mapping[str, Any] | None = None,
                 value: float = 1) -> None:
    """Eager trace-time counter bump, gated on :func:`enabled`."""
    if not _reg.enabled():
        return
    REGISTRY.inc(name, value, labels)
