"""Profiler trace annotations, per-trace site collection and host hooks.

Every fused kernel launch and XLA-expansion site runs under a
``jax.named_scope`` named ``emugemm/<scheme>-<p|m><count>/<backend>/<impl>``.
The scope becomes part of the ``op_name`` metadata XLA attaches to every op
lowered inside it, so a profiler trace attributes device time per emulation
site: look each op event's instruction up in the compiled text and read
its ``op_name`` (docs/observability.md, "Reading a profiler trace").

Scopes are pure trace metadata: they change no numerics and cost nothing at
run time, so they are applied unconditionally (not gated on
``telemetry.enabled()``).

Scheme-II lowerings also name their stages inside that scope with
:func:`stage_scope`: ``encode`` (integerize and residues) and ``crt``
(canonicalize, Garner, Horner, unscale), so an op's ``op_name`` reads
``.../emugemm/ozaki2-m16/tpu/pallas/.../encode/...``.  The residue
kernel between them keeps its own name (``emugemm2_p<n>``).

Because the scope is entered while JAX traces, it also tells a caller which
sites a program holds: :func:`collect_sites` counts the tags entered while
it is open, and :func:`repeated` scales the sites traced once in a loop
body (``lax.scan`` over layers) by the loop's trip count.  The serve engine
keeps one such table per compiled step shape and adds it up per executed
step, which gives per-execution site counts with no callback in the step.

Two process-wide host hooks (:func:`install_host_hooks`, installed once,
by the first serve engine built) count what pauses the host: backend
compiles (``jax.monitoring``'s backend-compile duration event) and Python
garbage collections (``gc.callbacks``; each writes a ``repro.gc`` span
into a recording profiler trace).  Their plain counts are always kept;
the registry series are bumped when telemetry is on.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time
from typing import Iterator

from repro.telemetry import record as _rec
from repro.telemetry import registry as _reg
from repro.telemetry.record import gemm_tag

_tls = threading.local()


class _Collector:
    def __init__(self) -> None:
        self.sites: collections.Counter = collections.Counter()
        self.repeat = 1


@contextlib.contextmanager
def gemm_scope(scheme: str, count: int, backend: str,
               impl: str) -> Iterator[None]:
    """``jax.named_scope`` for one emulated-GEMM lowering site.

    Inside an open :func:`collect_sites`, the site counts once per
    :func:`repeated` trip.
    """
    import jax

    tag = gemm_tag(scheme, count, backend, impl)
    col = getattr(_tls, "collector", None)
    if col is not None:
        col.sites[tag] += col.repeat
    with jax.named_scope(tag):
        yield


# Stages of a Scheme-II lowering (:func:`stage_scope`).
ENCODE = "encode"
CRT = "crt"
STAGES = (ENCODE, CRT)


@contextlib.contextmanager
def stage_scope(stage: str) -> Iterator[None]:
    """``jax.named_scope`` for one stage of an emulated GEMM, entered
    inside its :func:`gemm_scope`: one of :data:`STAGES`."""
    import jax

    if stage not in STAGES:
        raise ValueError(f"unknown emulated-GEMM stage {stage!r}; "
                         f"known: {STAGES}")
    with jax.named_scope(stage):
        yield


@contextlib.contextmanager
def collect_sites() -> Iterator[collections.Counter]:
    """Count, by ``emugemm/...`` tag, the emulated sites traced inside the
    scope (this thread only).  Yields the counter it fills."""
    prev = getattr(_tls, "collector", None)
    col = _tls.collector = _Collector()
    try:
        yield col.sites
    finally:
        _tls.collector = prev


@contextlib.contextmanager
def repeated(n: int) -> Iterator[None]:
    """Sites traced inside run ``n`` times per execution (a loop body)."""
    col = getattr(_tls, "collector", None)
    if col is None:
        yield
        return
    prev = col.repeat
    col.repeat = prev * int(n)
    try:
        yield
    finally:
        col.repeat = prev


def keep_op_names_in_compile_cache() -> None:
    """Key JAX's persistent compile cache on the programs' op names.

    By default the cache key strips a program's metadata, so a cached
    executable built from code that differs only in its scopes comes back
    with that code's ``op_name``s, and a profile read through them is
    wrong.  This puts the metadata in the key, and keeps source paths out
    of the metadata so that the key does not depend on where the code is
    checked out.  Process-wide; HLO metadata then names no source lines.
    """
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


# ---------------------------------------------------------------------------
# Host hooks: compiles and garbage collections, process-wide.
# ---------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GC_SPAN = "repro.gc"


class HostCounts:
    """Plain process-wide counts, kept whether telemetry is on or off."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.gc_pauses = 0
        self.gc_pause_s = 0.0

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "gc_pauses": self.gc_pauses, "gc_pause_s": self.gc_pause_s}


HOST = HostCounts()
_hooks_lock = threading.Lock()
_hooks_installed = False
_gc_open: list = []               # (span, start) of the collection running


def _on_compile(event: str, duration_s: float, **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    HOST.compiles += 1
    HOST.compile_s += duration_s
    if _reg.enabled():
        _reg.REGISTRY.inc(_rec.COMPILES)
        _reg.REGISTRY.observe(_rec.COMPILE_SECONDS, duration_s)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        import jax

        sp = jax.profiler.TraceAnnotation(
            GC_SPAN, generation=info.get("generation", -1))
        sp.__enter__()
        _gc_open.append((sp, time.perf_counter()))
        return
    if not _gc_open:
        return
    sp, t0 = _gc_open.pop()
    sp.__exit__(None, None, None)
    dt = time.perf_counter() - t0
    HOST.gc_pauses += 1
    HOST.gc_pause_s += dt
    if _reg.enabled():
        _reg.REGISTRY.observe(_rec.GC_PAUSE_SECONDS, dt,
                              {"generation": info.get("generation", -1)})


def install_host_hooks() -> None:
    """Register the compile listener and the GC callback, once per
    process; later calls do nothing."""
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        gc.callbacks.append(_on_gc)
        _hooks_installed = True
