"""Emulated-GEMM dispatch: backend routing, block caching, padding, policy.

Every fused kernel in this package builds its ``pl.pallas_call`` through
:func:`repro.kernels.backends.base.build_pallas_call` (re-exported here
for compatibility), which resolves the JAX-version compiler-params drift
once via :mod:`repro.kernels.compat`.

On top of the call builder this module owns the *routing* policy, which
since the backend-registry subsystem landed is expressed per
:class:`repro.kernels.backends.KernelBackend`:

* :func:`select_blocks` — the selected backend's ``choose_blocks``
  memoized per (shape, p, out_bytes, prologue, fixed_bk) key in a
  *per-backend* cache, so repeated call-sites (training steps re-tracing
  the same projection shapes) never re-run the staging-budget search and
  the TPU/GPU backends keep distinct tiles for the same problem.
  ``block_cache_info()`` / ``block_cache_clear()`` report and clear
  per-backend entries.
* :func:`plan_emulated` — one (backend, dtype, blocks) resolution per
  call, shared by ``emulated_matmul`` and ``auto_fused_matmul`` and
  threaded down to the fused wrappers.  Backend selection precedence:
  explicit argument > ``REPRO_BACKEND`` env var > ``cfg.backend`` >
  platform default; a backend with no fused kernel for the requested
  (scheme, dtype) falls back to the ``xla`` reference backend.
* :func:`emulated_matmul` — the single entry point for an emulated GEMM.
  Operands not aligned to the backend's capability (128 on TPU, 16 on
  GPU) are zero-padded to the nearest aligned tile, run through the
  fused kernel, and sliced back — zero rows/columns are exact under both
  schemes, so padding changes traffic, never values.  A
  :class:`repro.kernels.prepared.PreparedOperand` rhs skips
  decomposition entirely and streams its finished int8 slices.
* :func:`emulated_matmul_batched` — leading batch dims on the activation
  flatten into M; a shared leading axis runs ONE strided-batched fused
  launch on backends advertising ``BackendCapabilities.batched`` (the
  grid grows a third dimension over batch, scales/plan computed once for
  the stack), falling back to vmapping the 2-D dispatcher elsewhere.
* :func:`resolve_policy` — clamps a model ``GemmPolicy`` to what the
  launch target supports: (scheme, backend) pairs the selected backend
  cannot lower pin ``impl='xla'``, and fused impls survive only on a
  single-device mesh whose jax platform natively compiles the selected
  backend (the interpret-mode lowering is a sequential grid loop GSPMD
  cannot partition).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import warnings

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.precision import EmulationConfig
from repro.kernels import backends
from repro.kernels.backends.base import build_pallas_call  # noqa: F401
from repro.kernels.common import Blocks, wide_dims
from repro.telemetry import record as _tele

# Historical MXU alignment; kept as the default for the padding helpers
# (the TPU backend's capability). Backend-aware callers pass
# ``backends.get_backend(name).capabilities.align`` instead.
ALIGN = 128


# ---------------------------------------------------------------------------
# Block selection: the backend's choose_blocks, cached per backend.
# ---------------------------------------------------------------------------

BLOCK_CACHE_MAXSIZE = 4096


class _BlockCache:
    """One backend's memoized block selections, with lru_cache-style stats.

    Bounded at BLOCK_CACHE_MAXSIZE entries (FIFO eviction — dict preserves
    insertion order) so shape-ragged serving loops cannot grow it forever.
    """

    __slots__ = ("data", "hits", "misses")

    def __init__(self) -> None:
        self.data: dict = {}
        self.hits = 0
        self.misses = 0

    def put(self, key, blocks) -> None:
        if len(self.data) >= BLOCK_CACHE_MAXSIZE:
            self.data.pop(next(iter(self.data)))
        self.data[key] = blocks


_BLOCK_CACHES: dict[str, _BlockCache] = {}

BlockCacheInfo = collections.namedtuple(
    "BlockCacheInfo", ["hits", "misses", "maxsize", "currsize",
                       "per_backend"])


def select_blocks(m: int, n: int, k: int, p: int, out_bytes: int = 4,
                  backend: str | None = None, prologue_a: bool = False,
                  prologue_b: bool = False,
                  fixed_bk: int | None = None,
                  scheme: str = "ozaki1",
                  mesh_shape: tuple | None = None,
                  batch: int = 1) -> Blocks | None:
    """Cached block selection through the backend registry.

    ``backend`` may be any string — platform-qualified names bucket their
    own cache entries ('tpu-v5e' and 'tpu' stay distinct) while resolving
    to the nearest registered backend for the actual tile search.
    ``scheme`` ('ozaki1' | 'ozaki2' | 'ozaki2-3m') keys the cache and
    selects the backend's residue-count-aware resource model.
    ``mesh_shape`` is the launch mesh's axis sizes when (m, n, k) are
    *shard-local* dims of a shard_map'ed GEMM: the same local shape on
    two different meshes keys distinct entries, so per-shard selections
    never collide across mesh layouts (single-device callers pass None).
    ``batch`` is the strided-batched launch's leading extent: it keys the
    cache (one selection per (B, M, K, N, scheme, p) problem) without
    entering the tile search — a batch grid dimension multiplies program
    count, not the per-program working set.
    """
    bucket = backend or backends.resolve_backend_name()
    cache = _BLOCK_CACHES.setdefault(bucket, _BlockCache())
    key = (m, n, k, p, out_bytes, prologue_a, prologue_b, fixed_bk, scheme,
           mesh_shape, batch)
    try:
        blocks = cache.data[key]
        cache.hits += 1
        telemetry.record_event(_tele.BLOCK_CACHE,
                               {"backend": bucket, "result": "hit"})
        return blocks
    except KeyError:
        cache.misses += 1
        telemetry.record_event(_tele.BLOCK_CACHE,
                               {"backend": bucket, "result": "miss"})
    bk_obj = backends.resolve_backend(bucket)
    try:
        blocks = bk_obj.choose_blocks(
            m, n, k, p, out_bytes=out_bytes, prologue_a=prologue_a,
            prologue_b=prologue_b, fixed_bk=fixed_bk, scheme=scheme)
    except TypeError:
        # Out-of-tree backends registered before the scheme kwarg grew:
        # one resource model per backend was the old contract, so the
        # argument is safely dropped.
        blocks = bk_obj.choose_blocks(
            m, n, k, p, out_bytes=out_bytes, prologue_a=prologue_a,
            prologue_b=prologue_b, fixed_bk=fixed_bk)
    if blocks is not None:
        for dim in wide_dims(blocks, m, n, k, fixed_bk):
            telemetry.record_event(_tele.BLOCK_WIDE,
                                   {"backend": bucket, "dim": dim})
    cache.put(key, blocks)
    return blocks


def block_cache_info(backend: str | None = None) -> BlockCacheInfo:
    """Cache statistics, exposed for tests and perf probes.

    Without ``backend``: aggregate hits/misses/size across every backend
    bucket, with the per-backend breakdown under ``.per_backend``.
    """
    if backend is not None:
        c = _BLOCK_CACHES.get(backend, _BlockCache())
        return BlockCacheInfo(c.hits, c.misses, BLOCK_CACHE_MAXSIZE,
                              len(c.data),
                              {backend: (c.hits, c.misses, len(c.data))})
    per = {name: (c.hits, c.misses, len(c.data))
           for name, c in sorted(_BLOCK_CACHES.items())}
    return BlockCacheInfo(sum(c.hits for c in _BLOCK_CACHES.values()),
                          sum(c.misses for c in _BLOCK_CACHES.values()),
                          BLOCK_CACHE_MAXSIZE,
                          sum(len(c.data) for c in _BLOCK_CACHES.values()),
                          per)


def block_cache_clear(backend: str | None = None) -> None:
    """Clear one backend's cached selections, or every backend's."""
    if backend is not None:
        _BLOCK_CACHES.pop(backend, None)
    else:
        _BLOCK_CACHES.clear()


# ---------------------------------------------------------------------------
# Padding: route non-aligned problems through the fused kernels.
# ---------------------------------------------------------------------------

def round_up(x: int, mult: int = ALIGN) -> int:
    return -(-x // mult) * mult


def padded_mkn(m: int, k: int, n: int,
               align: int = ALIGN) -> tuple[int, int, int]:
    return round_up(m, align), round_up(k, align), round_up(n, align)


def pad_operands(a: jax.Array, b: jax.Array, align: int = ALIGN):
    """Zero-pad (M, K) x (K, N) up to ``align`` multiples.

    Zero padding is exact for every scheme here: zero rows/cols slice to
    all-zero int8 slices (Scheme I) and integerize to all-zero residues
    (Scheme II), contributing nothing to the padded products.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp, kp, np_ = padded_mkn(m, k, n, align)
    if (mp, kp, np_) == (m, k, n):
        return a, b
    return (jnp.pad(a, ((0, mp - m), (0, kp - k))),
            jnp.pad(b, ((0, kp - k), (0, np_ - n))))


# ---------------------------------------------------------------------------
# The emulated-GEMM entry point.
# ---------------------------------------------------------------------------

def _is_complex(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.complexfloating)


# Historical no-argument behavior of emulated_matmul: Scheme I at p=4.
# Ranks below the ambient scope / env in the resolver — an explicit
# `with repro.emulation(...)` or REPRO_EMULATION spec wins.
_LEGACY_DEFAULT = EmulationConfig(scheme="ozaki1", p=4)


def _resolve_cfg(cfg, scheme, precision) -> EmulationConfig:
    """Resolve this call's config through repro.api.resolve_config.

    ``scheme=``/``precision=`` are the deprecated pre-spec kwargs; they
    keep working (with a DeprecationWarning) so old call-sites survive,
    but new code passes ``cfg=`` (an EmulationConfig or a spec string)
    or relies on the ambient scope.
    """
    from repro import api
    if scheme is not None or precision is not None:
        if cfg is not None:
            raise TypeError("pass either cfg= or the deprecated "
                            "scheme=/precision= kwargs, not both")
        warnings.warn(
            "emulated_matmul(scheme=..., precision=...) is deprecated; "
            "pass cfg=repro.precision('<scheme>-p<N>') or wrap the call "
            "in `with repro.emulation(...)`",
            DeprecationWarning, stacklevel=3)
        return EmulationConfig(
            scheme=scheme if scheme is not None else "ozaki1",
            p=precision if precision is not None else 4)
    return api.resolve_config(cfg, default=_LEGACY_DEFAULT)


def _prologue(cfg: EmulationConfig) -> bool:
    """Does this config run Scheme-I decomposition in the kernel prologue?"""
    return cfg.scheme == "ozaki1" and cfg.decomp in ("auto", "kernel")


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """One backend + block-selection + dtype resolution per GEMM.

    Built by :func:`plan_emulated`; both ``emulated_matmul`` and
    ``auto_fused_matmul`` consume the same plan, and the fused
    wrappers receive ``blocks`` instead of re-running the staging-budget
    search on the padded problem.  ``backend`` is the *resolved* name —
    after the env override and the unsupported-(scheme, dtype) fallback
    to 'xla'.
    """
    cfg: EmulationConfig
    m: int
    n: int
    k: int
    p_eff: int
    out_dtype: object
    blocks: Blocks | None
    backend: str = "tpu"
    # Block-model key: 'ozaki1' | 'ozaki2' | 'ozaki2-3m' (complex inputs
    # under Scheme II plan for the fused 3M kernel's larger footprint).
    scheme: str = "ozaki1"
    # Axis sizes of the launch mesh when (m, n, k) are shard-local dims
    # of a shard_map'ed GEMM (keys the block cache; None = unsharded).
    mesh_shape: tuple | None = None
    # Input-sentinel probe (repro.guard.sentinel.SentinelProbe) when the
    # plan was built with probe=True: NaN/Inf row/col masks + per-row
    # exponent-spread estimates, computed pre-dispatch so the guard can
    # mask special values and flag wide-dynamic-range operands without
    # touching the fused kernels.
    probe: object | None = None
    # Leading extent of a strided-batched launch (1 = plain 2-D GEMM).
    batch: int = 1

    @property
    def aligned(self) -> bool:
        return (self.blocks is not None
                and self.blocks.aligned(self.m, self.n, self.k))

    @property
    def align(self) -> int:
        return backends.get_backend(self.backend).capabilities.align


def _plan_backend(cfg: EmulationConfig, a, b,
                  backend: str | None = None) -> str:
    """Resolve the backend for one GEMM, falling back to the 'xla'
    reference when the selected backend cannot lower (scheme, dtype)."""
    name = backends.resolve_backend_name(backend, cfg)
    bk = backends.get_backend(name)
    if not bk.supports(cfg, getattr(a, "dtype", None),
                       getattr(b, "dtype", None)):
        if name != "xla":
            telemetry.record_event(_tele.FALLBACK_EVENTS, {
                "requested": name, "scheme": cfg.scheme,
                "reason": "unsupported"})
        return "xla"
    return name


def plan_emulated(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                  out_dtype=None, backend: str | None = None,
                  mesh_shape: tuple | None = None,
                  probe: bool = False) -> GemmPlan:
    """Resolve backend, output dtype and cached blocks for one 2-D GEMM.

    ``p_eff`` is the residue count the block search budgets for: the
    slice count under Scheme I, the modulus count under Scheme II
    (backends whose Scheme-II kernels run a single live accumulator —
    the TPU Mosaic lowering — re-select internally with p=1 and ignore
    the plan's blocks).

    ``probe=True`` additionally runs the guard's cheap input sentinel
    (finite masks + exponent-spread estimate, O(MK + KN) elementwise)
    and attaches it as ``GemmPlan.probe`` — the pre-dispatch leg of the
    ``+guard`` pipeline (see repro.guard).
    """
    m, k = a.shape
    _, n = b.shape
    if out_dtype is None:
        out_dtype = cfg.out_dtype
    if out_dtype is None:
        out_dtype = jnp.promote_types(jnp.real(a).dtype, jnp.real(b).dtype)
    p_eff = cfg.p
    scheme = cfg.scheme
    if scheme == "ozaki2":
        # The residue count is the moduli count — an explicit tuple may
        # disagree with cfg.p, and the kernels carve len(moduli)
        # residues/accumulators.
        p_eff = len(cfg.resolved_moduli())
        if _is_complex(a) or _is_complex(b):
            scheme = "ozaki2-3m"
    name = _plan_backend(cfg, a, b, backend)
    pro = _prologue(cfg)
    blocks = select_blocks(m, n, k, p_eff,
                           out_bytes=jnp.dtype(out_dtype).itemsize,
                           backend=name, prologue_a=pro, prologue_b=pro,
                           scheme=scheme, mesh_shape=mesh_shape)
    sentinel_probe = None
    if probe:
        from repro.guard import sentinel as _sentinel
        sentinel_probe = _sentinel.probe_operands(a, b)
    return GemmPlan(cfg, m, n, k, p_eff, out_dtype, blocks, name, scheme,
                    mesh_shape, sentinel_probe)


def plan_emulated_batched(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                          out_dtype=None,
                          backend: str | None = None) -> GemmPlan:
    """Resolve backend, dtype and blocks for one strided-batched
    (B, M, K) @ (B, K, N) GEMM.

    The tile search is the 2-D one — the batch grid dimension multiplies
    program count, not the per-program working set — but the selection
    is keyed per (B, M, K, N, scheme, p), so batched and per-element
    call-sites on the same 2-D problem keep distinct cache entries and
    ``block_cache_info`` attributes them separately.
    """
    batch, m, k = a.shape
    _, _, n = b.shape
    if out_dtype is None:
        out_dtype = cfg.out_dtype
    if out_dtype is None:
        out_dtype = jnp.promote_types(jnp.real(a).dtype, jnp.real(b).dtype)
    p_eff = cfg.p
    scheme = cfg.scheme
    if scheme == "ozaki2":
        p_eff = len(cfg.resolved_moduli())
    name = _plan_backend(cfg, a, b, backend)
    pro = _prologue(cfg)
    blocks = select_blocks(m, n, k, p_eff,
                           out_bytes=jnp.dtype(out_dtype).itemsize,
                           backend=name, prologue_a=pro, prologue_b=pro,
                           scheme=scheme, batch=batch)
    return GemmPlan(cfg, m, n, k, p_eff, out_dtype, blocks, name, scheme,
                    batch=batch)


def _replan_padded(plan: GemmPlan) -> GemmPlan:
    mp, kp, np_ = padded_mkn(plan.m, plan.k, plan.n, plan.align)
    pro = _prologue(plan.cfg)
    blocks = select_blocks(mp, np_, kp, plan.p_eff,
                           out_bytes=jnp.dtype(plan.out_dtype).itemsize,
                           backend=plan.backend, prologue_a=pro,
                           prologue_b=pro, scheme=plan.scheme,
                           mesh_shape=plan.mesh_shape)
    return dataclasses.replace(plan, m=mp, n=np_, k=kp, blocks=blocks)


def _record_plan_call(plan: GemmPlan) -> None:
    """Telemetry for one dispatched GEMM (no-op unless enabled)."""
    if not telemetry.enabled():
        return
    impl = "pallas" if plan.backend != "xla" else "xla"
    telemetry.record_gemm(
        scheme=plan.scheme, count=plan.p_eff, backend=plan.backend,
        impl=impl, m=plan.m, k=plan.k, n=plan.n,
        mesh_shape=plan.mesh_shape,
        out_bytes=jnp.dtype(plan.out_dtype).itemsize,
        batch=plan.batch if plan.batch != 1 else None)


def _scope_scheme(cfg: EmulationConfig, cplx: bool) -> tuple[str, int]:
    """(scheme tag, residue count) of one lowering for trace annotation."""
    if cfg.scheme == "ozaki2":
        return ("ozaki2-3m" if cplx else "ozaki2",
                len(cfg.resolved_moduli()))
    return ("ozaki1-4m" if cplx else cfg.scheme, cfg.p)


def _fused_2d(a: jax.Array, b: jax.Array, cfg: EmulationConfig, out_dtype,
              blocks: Blocks | None = None, backend: str | None = None):
    """Aligned 2-D problem -> the selected backend's fused lowering."""
    bk = backends.get_backend(backend) if backend \
        else backends.resolve_backend(cfg=cfg)
    cplx = _is_complex(a) or _is_complex(b)
    if cplx and jnp.issubdtype(jnp.dtype(out_dtype), jnp.complexfloating):
        # Real-valued interior: the complex result is assembled at the end.
        out_dtype = jnp.real(jnp.zeros((), out_dtype)).dtype
    scheme_tag, count = _scope_scheme(cfg, cplx)
    impl = "pallas" if bk.name != "xla" else "xla"
    with telemetry.gemm_scope(scheme_tag, count, bk.name, impl):
        if cfg.scheme == "ozaki1":
            if cplx:
                # Scheme-I complex (4M) has no fused kernel on any backend:
                # four fused real GEMMs (paper Sec. V-D runs EmuGEMM-I
                # complex exactly so).
                ar, ai = jnp.real(a), jnp.imag(a)
                br, bi = jnp.real(b), jnp.imag(b)
                rr = bk.matmul(ar, br, cfg, out_dtype, blocks)
                ii = bk.matmul(ai, bi, cfg, out_dtype, blocks)
                ri = bk.matmul(ar, bi, cfg, out_dtype, blocks)
                ir = bk.matmul(ai, br, cfg, out_dtype, blocks)
                return jax.lax.complex(rr - ii, ri + ir)
            return bk.matmul(a, b, cfg, out_dtype, blocks)
        if cfg.scheme == "ozaki2":
            return bk.matmul(a, b, cfg, out_dtype, blocks)
    raise ValueError(f"no fused kernel for scheme {cfg.scheme!r}")


def _fused_batched(a: jax.Array, b: jax.Array, cfg: EmulationConfig,
                   plan: GemmPlan) -> jax.Array:
    """One strided-batched fused launch for an eligible (B, M, K) @
    (B, K, N) problem — padding the trailing two axes when needed
    (exact: zero rows/cols carve to zero slices/residues)."""
    bk = backends.get_backend(plan.backend)
    scheme_tag, count = _scope_scheme(cfg, False)
    impl = "pallas" if bk.name != "xla" else "xla"
    telemetry.record_event(_tele.BATCHED_LAUNCHES, {
        "backend": plan.backend, "scheme": scheme_tag,
        "shape_class": _tele.shape_class(plan.m, plan.k, plan.n,
                                         batch=plan.batch)})
    with telemetry.gemm_scope(scheme_tag, count, bk.name, impl):
        if plan.aligned:
            return bk.matmul_batched(a, b, cfg, plan.out_dtype, plan.blocks)
        telemetry.record_event(_tele.PAD_EVENTS, {
            "backend": plan.backend, "scheme": plan.scheme,
            "shape_class": _tele.shape_class(plan.m, plan.k, plan.n,
                                             batch=plan.batch)})
        mp, kp, np_ = padded_mkn(plan.m, plan.k, plan.n, plan.align)
        a_p = jnp.pad(a, ((0, 0), (0, mp - plan.m), (0, kp - plan.k)))
        b_p = jnp.pad(b, ((0, 0), (0, kp - plan.k), (0, np_ - plan.n)))
        pro = _prologue(cfg)
        blocks = select_blocks(mp, np_, kp, plan.p_eff,
                               out_bytes=jnp.dtype(plan.out_dtype).itemsize,
                               backend=plan.backend, prologue_a=pro,
                               prologue_b=pro, scheme=plan.scheme,
                               batch=plan.batch)
        out = bk.matmul_batched(a_p, b_p, cfg, plan.out_dtype, blocks)
        return out[:, :plan.m, :plan.n]


def batched_fused_eligible(a, b, cfg: EmulationConfig,
                           backend: str | None = None) -> bool:
    """Would :func:`emulated_matmul_batched` take the strided-batched
    fused path for these operands under ``cfg``?

    Telemetry-free twin of the route check inside the dispatcher, for
    front doors (``repro.dot_general``) deciding between the batched
    core and their historical vmap-of-2-D lowering.
    """
    if cfg.scheme not in ("ozaki1", "ozaki2") or cfg.guard is not None:
        return False
    if _is_complex(a) or _is_complex(b):
        return False
    name = backends.resolve_backend_name(backend, cfg)
    bk = backends.get_backend(name)
    if not bk.supports(cfg, getattr(a, "dtype", None),
                       getattr(b, "dtype", None)):
        bk = backends.get_backend("xla")
    return bk.capabilities.batched


def _fused_batched_or_none(a: jax.Array, b: jax.Array, kw: dict):
    """The strided-batched fast path of :func:`emulated_matmul_batched`,
    or None when this (config, operands, backend) combination keeps the
    per-element vmap fallback.

    Eligible: real operands under a guard-free ozaki1/ozaki2 config on a
    backend whose :class:`BackendCapabilities` advertise ``batched``.
    Leading axes collapse into one batch dimension; scales and the block
    plan are computed once for the whole stack; the result is
    bit-identical to the vmapped 2-D dispatch (the batched kernels run
    the unchanged 2-D kernel body per batch grid step).
    """
    if kw.get("scheme") is not None or kw.get("precision") is not None:
        return None          # deprecated-shim callers keep the legacy path
    if kw.get("mesh_shape") is not None:
        return None          # shard-local tiles dispatch per element (2-D)
    if _is_complex(a) or _is_complex(b):
        return None          # no batched 4M/3M lowering yet
    from repro import api
    cfg = api.resolve_config(kw.get("cfg"), default=_LEGACY_DEFAULT)
    if cfg.scheme not in ("ozaki1", "ozaki2") or cfg.guard is not None:
        return None
    name = _plan_backend(cfg, a, b, kw.get("backend"))
    if not backends.get_backend(name).capabilities.batched:
        return None
    lead = a.shape[:-2]
    a3 = a.reshape((-1,) + a.shape[-2:])
    b3 = b.reshape((-1,) + b.shape[-2:])
    plan = plan_emulated_batched(a3, b3, cfg, kw.get("out_dtype"), name)
    _record_plan_call(plan)
    out = _fused_batched(a3, b3, cfg, plan)
    return out.reshape(lead + out.shape[-2:])


def _is_prepared(b) -> bool:
    from repro.kernels.prepared import PreparedOperand, PreparedResidues
    return isinstance(b, (PreparedOperand, PreparedResidues))


def _is_prepared_residues(b) -> bool:
    from repro.kernels.prepared import PreparedResidues
    return isinstance(b, PreparedResidues)


def emulated_matmul(a: jax.Array, b, *,
                    cfg: "EmulationConfig | str | None" = None,
                    out_dtype=None, backend: str | None = None,
                    scheme: str | None = None,
                    precision: int | None = None,
                    mesh_shape: tuple | None = None) -> jax.Array:
    """Emulated (M, K) @ (K, N) through the fused kernels of the selected
    backend (``backend`` arg > ``REPRO_BACKEND`` > ``cfg.backend`` >
    platform default; unsupported (scheme, dtype) pairs fall back to the
    'xla' reference backend).

    ``cfg`` is an EmulationConfig or a precision-spec string; omitted, it
    resolves through the ambient scope / ``REPRO_EMULATION`` env (see
    ``repro.resolve_config``), defaulting to the historical ozaki1-p4.
    ``scheme=``/``precision=`` are deprecated shims for pre-spec callers.

    Blocks come from the per-(shape, p, dtype, backend) cache; operands
    not aligned to the backend's capability are zero-padded to the
    nearest aligned tile, run fused, and the (M, N) result sliced back.

    ``b`` may be a :class:`repro.kernels.prepared.PreparedOperand`: its
    finished int8 slices are streamed as-is and only the lhs decomposes
    (in the kernel prologue).

    ``mesh_shape`` (the launch mesh's axis sizes) marks the operands as
    *shard-local* tiles of a shard_map'ed GEMM — it keys the block cache
    so per-shard selections never collide across mesh layouts; see
    ``repro.parallel.shard_gemm``.
    """
    cfg = _resolve_cfg(cfg, scheme, precision)
    if (cfg.guard is not None and cfg.scheme != "native"
            and a.ndim == 2 and (_is_prepared(b) or b.ndim == 2)
            and not _is_complex(a)
            and not (not _is_prepared(b) and _is_complex(b))):
        # The guard pipeline (sanitize -> run -> verify -> escalate,
        # repro.guard.ladder) wraps this entry point and re-enters it
        # with the guard stripped for every ladder rung.  Invalid shapes
        # fall through so the usual refusals fire first.
        from repro import guard
        return guard.guarded_matmul(a, b, cfg, out_dtype=out_dtype,
                                    backend=backend, mesh_shape=mesh_shape)
    if _is_prepared(b):
        from repro.kernels import prepared
        if cfg.scheme == "native":
            # Mirrors repro.dot_general: the slices/residues are emulation
            # data, so honoring a native request is impossible — refuse
            # rather than silently emulate.
            raise ValueError(
                "a prepared rhs is pre-decomposed emulation data; it "
                "cannot be consumed under a 'native' config (pass the "
                "float weight instead)")
        if _is_prepared_residues(b) and cfg.scheme != "ozaki2":
            raise ValueError(
                "a PreparedResidues rhs is Scheme-II (ozaki2) data; it "
                f"cannot be consumed under scheme={cfg.scheme!r} (pass "
                "the float weight, or prepare under the matching config)")
        if not _is_prepared_residues(b) and cfg.scheme == "ozaki2":
            raise ValueError(
                "a PreparedOperand rhs is Scheme-I (ozaki1) data; it "
                "cannot be consumed under scheme='ozaki2' (pass the "
                "float weight, or prepare under the matching config)")
        if a.ndim != 2:
            raise ValueError(
                f"emulated_matmul is strictly 2-D; got lhs {a.shape} — use "
                "repro.dot_general / repro.einsum for batched or "
                "higher-rank contractions (or emulated_matmul_batched)")
        if out_dtype is None:
            out_dtype = cfg.out_dtype
        if out_dtype is None:
            out_dtype = jnp.promote_types(a.dtype, jnp.float32)
        return prepared.matmul_prepared(a, b, out_dtype=out_dtype)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(
            f"emulated_matmul is strictly 2-D; got {a.shape} @ {b.shape} — "
            "use repro.dot_general / repro.einsum for batched or "
            "higher-rank contractions (or emulated_matmul_batched)")
    if cfg.scheme == "native":
        out_dtype = (out_dtype or cfg.out_dtype
                     or jnp.promote_types(a.dtype, b.dtype))
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=out_dtype)
    plan = plan_emulated(a, b, cfg, out_dtype, backend,
                         mesh_shape=mesh_shape)
    _record_plan_call(plan)
    if plan.aligned:
        return _fused_2d(a, b, cfg, plan.out_dtype, plan.blocks,
                         plan.backend)
    telemetry.record_event(_tele.PAD_EVENTS, {
        "backend": plan.backend, "scheme": plan.scheme,
        "shape_class": _tele.shape_class(plan.m, plan.k, plan.n)})
    a_p, b_p = pad_operands(a, b, plan.align)
    plan_p = _replan_padded(plan)
    return _fused_2d(a_p, b_p, cfg, plan.out_dtype, plan_p.blocks,
                     plan.backend)[:plan.m, :plan.n]


def emulated_matmul_batched(a: jax.Array, b, **kw) -> jax.Array:
    """Batched wrapper around :func:`emulated_matmul`.

    * ``b`` 2-D (or a PreparedOperand): leading dims of ``a`` flatten into
      M (activations @ weights) — one fused launch.
    * matching leading axes: ONE strided-batched fused launch when the
      selected backend's capabilities advertise ``batched`` (the grid
      grows a third dimension over batch; bit-identical to the vmapped
      2-D dispatch); otherwise the 2-D dispatcher is vmapped over the
      leading axes.
    """
    if _is_prepared(b):
        if a.ndim == 2:
            return emulated_matmul(a, b, **kw)
        lead = a.shape[:-1]
        out = emulated_matmul(a.reshape(-1, a.shape[-1]), b, **kw)
        return out.reshape(*lead, b.n)
    if a.ndim == 2 and b.ndim == 2:
        return emulated_matmul(a, b, **kw)
    if b.ndim == 2:
        lead = a.shape[:-1]
        out = emulated_matmul(a.reshape(-1, a.shape[-1]), b, **kw)
        return out.reshape(*lead, b.shape[-1])
    if a.ndim != b.ndim or a.shape[:-2] != b.shape[:-2]:
        raise ValueError(
            f"emulated_matmul_batched needs matching leading (batch) axes; "
            f"got lhs {a.shape} (leading {a.shape[:-2]}) @ rhs {b.shape} "
            f"(leading {b.shape[:-2]}) — repro.dot_general handles "
            "asymmetric batch/contraction layouts")
    out = _fused_batched_or_none(a, b, kw)
    if out is not None:
        return out
    fn = functools.partial(emulated_matmul_batched, **kw)
    return jax.vmap(fn)(a, b)


# Fallback RuntimeWarnings are deduped by (reason, shape-class): the
# requested backend/scheme/dtype pair that fell back plus the operand
# shape class — the (K, N) contraction geometry only, NOT the full
# operand shapes.  Batched call-sites flatten their leading axes into M
# (emulated_matmul_batched), so a full-shape key minted a fresh entry
# per batch size and the "once" warning fired once per ragged batch;
# K x N identifies the weight/call-site independent of batching.
# Scanned training steps re-trace the same call-site once per
# microbatch/layer combination; without the dedupe every re-trace
# re-warned and multi-device logs drowned in the repeat.  The one-shot
# bookkeeping lives on the telemetry registry (the process's single
# counter store; always active, independent of REPRO_TELEMETRY) under
# keys namespaced "fallback".


def fallback_warnings_clear() -> None:
    """Forget which fused-fallback warnings fired (tests/log hygiene)."""
    telemetry.REGISTRY.forget_once("fallback")


def _warn_fallback_once(reason: tuple, shape_class: tuple, message: str,
                        stacklevel: int = 3) -> None:
    if not telemetry.REGISTRY.once(("fallback", reason, shape_class)):
        return
    warnings.warn(message, RuntimeWarning, stacklevel=stacklevel)


def auto_fused_matmul(a: jax.Array, b, cfg: EmulationConfig):
    """'auto'-impl hook: the fused kernel when the 2-D problem is naturally
    tile-aligned for the selected backend, else None (caller falls back to
    the XLA expansion — padding is reserved for explicit ``impl='pallas'``
    requests, where the copy+slice overhead was asked for). A
    PreparedOperand rhs is the other exception: preparing *was* the
    commitment to the kernel path, so a non-aligned lhs is padded rather
    than refused."""
    if _is_prepared(b):
        if a.ndim != 2 or cfg.scheme == "native" or _is_complex(a):
            return None
        return emulated_matmul(a, b, cfg=cfg)
    if a.ndim != 2 or b.ndim != 2 or cfg.scheme == "native":
        return None
    if cfg.scheme == "ozaki1" and (_is_complex(a) or _is_complex(b)):
        return None  # 4x fused launches is not an 'auto' win; XLA path
    plan = plan_emulated(a, b, cfg)
    requested = backends.resolve_backend_name(None, cfg)
    if plan.backend == "xla" and requested != "xla":
        # Fell back — nothing fused to offer the 'auto' site. Name the
        # fused path being skipped (and its limits) instead of silently
        # degrading to the reference expansion.
        from repro.kernels.backends import gpu as _gpu
        detail = ""
        if requested == "gpu" and cfg.scheme == "ozaki2":
            detail = (f" (the fused gpu Scheme-II kernel takes at most "
                      f"{_gpu.MAX_MODULI} moduli, each <= 256)")
        a_name, b_name = jnp.dtype(a.dtype).name, jnp.dtype(b.dtype).name
        _warn_fallback_once(
            (requested, cfg.scheme, a_name, b_name),
            (a.shape[-1], b.shape[-1]),
            f"backend {requested!r} has no fused {cfg.scheme} lowering "
            f"for operands {a_name} @ {b_name}{detail}; this call-site "
            "expands in XLA instead")
        return None
    if not plan.aligned:
        telemetry.record_event(_tele.FALLBACK_EVENTS, {
            "requested": plan.backend, "scheme": plan.scheme,
            "reason": "unaligned-auto"})
        return None
    _record_plan_call(plan)
    return _fused_2d(a, b, cfg, plan.out_dtype, plan.blocks, plan.backend)


def maybe_emulated_matmul(a: jax.Array, b, cfg: EmulationConfig):
    """Deprecated name for :func:`auto_fused_matmul`."""
    warnings.warn(
        "maybe_emulated_matmul is deprecated; call auto_fused_matmul "
        "(or the repro.dot_general/einsum front door)",
        DeprecationWarning, stacklevel=2)
    return auto_fused_matmul(a, b, cfg)


# ---------------------------------------------------------------------------
# Launch-layer policy resolution.
# ---------------------------------------------------------------------------

def _mesh_devices(mesh) -> int:
    """Device count of a launch mesh.

    Handles every mesh flavor the launch layer produces consistently: a
    concrete ``jax.sharding.Mesh`` and a device-free ``AbstractMesh``
    both answer through ``.size`` when present; meshes exposing only a
    ``shape`` answer through it whether it is mapping-shaped
    ({axis: size}, the Mesh/AbstractMesh convention) or a plain tuple of
    axis sizes; ``None`` means the process-global device count.
    """
    if mesh is None:
        return len(jax.devices())
    size = getattr(mesh, "size", None)
    if size is not None:
        return int(size)
    shape = getattr(mesh, "shape", None)
    if hasattr(shape, "values"):             # mapping: {axis_name: size}
        return math.prod(shape.values())
    if shape is not None:                    # plain tuple of axis sizes
        try:
            return math.prod(int(s) for s in shape)
        except (TypeError, ValueError):
            pass
    return len(jax.devices())


def _mesh_shape_tuple(mesh) -> tuple | None:
    """((axis, size), ...) of a mesh, or None — the hashable mesh
    identity the block cache and prepared-operand pinning key on."""
    if mesh is None:
        return None
    shape = getattr(mesh, "shape", None)
    if hasattr(shape, "items"):
        return tuple((str(a), int(s)) for a, s in shape.items())
    if shape is not None:
        try:
            return tuple((str(i), int(s)) for i, s in enumerate(shape))
        except (TypeError, ValueError):
            return None
    return None


def _shardable_mesh(mesh) -> bool:
    """Can fused call-sites run per-shard under shard_map on this mesh?

    Requires a *concrete* multi-device Mesh: shard_map needs named axes
    backed by real devices. Device-free AbstractMeshes (dry-run
    lowering) and a bare device count (mesh=None on a multi-device
    host) keep the conservative clamp — there is nothing to map over.
    """
    from jax.sharding import Mesh
    return (isinstance(mesh, Mesh) and _mesh_devices(mesh) > 1
            and bool(getattr(mesh, "axis_names", ())))


def resolve_policy(policy, mesh=None):
    """Pin emulated call-sites to impls the launch target can execute.

    Two clamps, in order:

    1. (scheme, backend) pairs the selected kernel backend cannot lower
       (e.g. a >16-moduli Scheme-II set on the 'gpu' backend) rewrite to
       ``impl='xla'`` — the reference expansion rather than a run-time
       registry fallback buried inside a jitted step.
    2. Fused 'auto'/'pallas' impls survive in exactly two launch
       geometries:

       * a single-device mesh whose jax platform natively compiles the
         selected kernel backend (TPU host + 'tpu' backend, GPU host +
         'gpu' backend), or
       * a concrete multi-device mesh whose selected backend declares
         ``BackendCapabilities.shardable`` — the call-sites then run the
         fused kernel *per shard* under ``shard_map`` (see
         ``repro.parallel.shard_gemm``), with explicit collectives
         instead of GSPMD partitioning of the kernel body. The mesh is
         recorded on the returned policy (``GemmPolicy.mesh``) so the
         model layer knows which axes to map over.

       Everything else — device-free AbstractMeshes, a bare multi-device
       host with no mesh to map over, non-shardable out-of-tree
       backends, cross-platform single-device requests — rewrites to
       'xla' so the emulation partitions like any other dot.

    A policy whose ``default`` is None (unset) first materializes the
    ambient config through ``repro.resolve_config`` — the launch layer
    consumes the documented resolver, so ``with repro.emulation(...)``
    and ``REPRO_EMULATION`` configure whole training/serving runs and
    still pass through the clamps above.
    """
    default = policy.default
    if default is None:
        from repro import api
        default = api.resolve_config()
        if default.scheme != "native":
            # Materialize the ambient config NOW (even when no clamp will
            # fire, e.g. '+xla' specs): the step functions built from this
            # policy trace lazily, possibly after the scope has exited.
            policy = dataclasses.replace(policy, default=default)
    sites = [default] + [cfg for _, cfg in policy.overrides]
    if all(c.scheme == "native" or c.impl == "xla" for c in sites):
        return policy

    single = _mesh_devices(mesh) <= 1
    sharded = _shardable_mesh(mesh)

    def fix(cfg: EmulationConfig) -> EmulationConfig:
        if cfg.scheme == "native" or cfg.impl == "xla":
            return cfg
        bk = backends.resolve_backend(cfg=cfg)
        # supports() without dtypes: the scheme-level clamp (including
        # per-backend limits like the gpu kernels' moduli cap).
        if not bk.supports(cfg):
            return dataclasses.replace(cfg, impl="xla")
        if single and bk.name == jax.default_backend():
            return cfg  # this host compiles the selected backend natively
        if sharded and bk.capabilities.shardable:
            # GSPMD-native: the shard_map wrapper launches the fused
            # kernel on each shard's local tile and issues the
            # collectives itself, so the old multi-device clamp no
            # longer applies.
            return cfg
        return dataclasses.replace(cfg, impl="xla")

    fixed_default = fix(policy.default)
    fixed_overrides = tuple((s, fix(c)) for s, c in policy.overrides)
    fixed = dataclasses.replace(policy, default=fixed_default,
                                overrides=fixed_overrides)
    if sharded and hasattr(policy, "mesh") and any(
            c.scheme != "native" and c.impl != "xla"
            for c in [fixed_default] + [c for _, c in fixed_overrides]):
        fixed = dataclasses.replace(fixed, mesh=mesh)
    return fixed
