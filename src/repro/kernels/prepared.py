"""Pre-decomposed rhs operands, reused across GEMMs.

The emulation pipelines re-decompose the *same weight matrix* on every
emulated call: forward, the remat re-forward, and the backward
dA = dC @ B^T each pay the full scale-read + encode round-trips — 3x
per layer per step in training, and once per decode step in serving.
Two prepared artifacts hold the finished encode instead:
``PreparedOperand`` (Scheme-I int8 mantissa slices) and
``PreparedResidues`` (Scheme-II balanced int8 residues — consumed by
the fused GPU residue kernel, whose prologue then skips the rhs
encode, or expanded in XLA from the stored residue stack).

A ``PreparedOperand`` holds:

  * ``slices``  — the p int8 slices, interleaved ((p*K, N), paper Eq. 11)
                  for the fused kernels or stacked ((p, K, N)) for the XLA
                  expansion,
  * ``scale``   — the (1, N) power-of-two column scale,
  * ``beta``/``p`` and ``blocks`` (the interleave granularity lives in
    ``blocks.bk``),
  * ``twin``    — the same weight prepared in the K-transposed rhs layout
                  of B^T, consumed by the backward dA GEMM.

``prepare_rhs`` builds one with a *single fp32 read* of the weight (the
``decompose_interleave_pair`` kernel emits both layouts in one pass);
``matmul_prepared`` consumes it through the mixed fused kernel (fp32 lhs
decomposed in-VMEM, prepared int8 rhs streamed).  Traffic accounting:
``repro.core.traffic.scheme1_decomp_prepared_bytes``.

Plumbing: ``dispatch.emulated_matmul`` accepts a PreparedOperand rhs,
``core.emulated.emulated_dot`` prepares weights once per step when
``cfg.cache_weights`` is set, and ``prepare_params`` wraps a model's
projection weights for once-per-session serving reuse
(``launch/serve.py --prepare``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import scheme1
from repro.core.precision import EmulationConfig
from repro.kernels.common import Blocks
from repro.telemetry import record as _tele


def _record_consume(scheme: str, count: int, backend: str, route: str,
                    reason: str, m: int, k: int, prep) -> None:
    """One prepared-consume routing decision + the per-execution GEMM."""
    if not telemetry.enabled():
        return
    telemetry.record_event(_tele.PREPARED_CONSUME, {
        "scheme": scheme, "route": route, "reason": reason})
    telemetry.record_gemm(
        scheme=scheme, count=count, backend=backend,
        impl=("prepared-pallas" if route == "fused" else "prepared-xla"),
        m=m, k=k, n=prep.n, mesh_shape=prep.mesh_shape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PreparedOperand:
    """A pre-split, pre-interleaved Scheme-I rhs operand (see module doc).

    ``k``/``n`` are the *unpadded* logical dims; ``slices`` and ``scale``
    are 128-aligned.  ``layout`` is 'interleaved' ((p*Kp, Np) int8, fused
    kernels) or 'stacked' ((p, Kp, Np) int8, XLA expansion).
    """
    slices: jax.Array
    scale: jax.Array
    p: int
    beta: int
    blocks: Blocks | None
    layout: str
    k: int
    n: int
    twin: "PreparedOperand | None" = None
    # Launch-mesh axis sizes this operand was prepared under (the
    # consume-route pinning of the layout field, extended to GSPMD:
    # shard_gemm checks it when localizing the stack for column-parallel
    # consumption).  None = prepared for single-device launches.
    mesh_shape: tuple | None = None

    @property
    def padded_k(self) -> int:
        if self.layout == "interleaved":
            return self.slices.shape[0] // self.p
        return self.slices.shape[1]

    @property
    def padded_n(self) -> int:
        return self.slices.shape[-1]

    def stacked(self) -> jax.Array:
        """The (p, Kp, Np) slice stack, deinterleaving if needed."""
        if self.layout == "stacked":
            return self.slices
        return scheme1.deinterleave_k(self.slices, self.p, "b",
                                      self.blocks.bk)

    def reconstruct(self) -> jax.Array:
        """The dense (k, n) float32 weight the slices represent.

        Exact up to the decomposition residual (scale * 2^(-beta*p)
        elementwise) — what the guard's a posteriori verifier
        (repro.guard.verify) compares emulated results against when the
        original float weight is no longer around.
        """
        st = self.stacked().astype(jnp.float32)
        w = jnp.zeros(st.shape[1:], jnp.float32)
        for i in range(self.p):
            # Python 2.0**e is exact; see scheme1.shift_reduce.
            w = w + jnp.float32(2.0 ** (-self.beta * (i + 1))) * st[i]
        return (w * self.scale.astype(jnp.float32))[:self.k, :self.n]

    def tree_flatten(self):
        return ((self.slices, self.scale, self.twin),
                (self.p, self.beta, self.blocks, self.layout,
                 self.k, self.n, self.mesh_shape))

    @classmethod
    def tree_unflatten(cls, aux, children):
        slices, scale, twin = children
        p, beta, blocks, layout, k, n, mesh_shape = aux
        return cls(slices, scale, p, beta, blocks, layout, k, n, twin,
                   mesh_shape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PreparedResidues:
    """A pre-encoded Scheme-II rhs: balanced int8 residues of the
    integerized weight, reused across GEMMs.

    ``residues`` is the (p, Kp, Np) balanced int8 residue stack —
    16-aligned for the fused GPU residue kernel, which streams it
    directly while its prologue integerizes only the lhs; ``scale`` is
    the (1, Np) power-of-two integerization scale and ``budget_bits``
    the per-operand magnitude budget pinned at encode time (the
    consumer integerizes the lhs at the *same* budget, exactly as the
    unprepared ``scheme2.matmul`` shares one budget across operands).
    Unlike the Scheme-I interleaved layout there is no pinned K
    granularity: the residue stack is consumable at any ``bK``.

    ``layout`` mirrors the Scheme-I 'interleaved'/'stacked' split: it
    records at prepare time whether consumption may run the fused GPU
    kernel ('fused', ``cfg.impl`` auto/pallas) or must stay on the XLA
    expansion ('stacked', ``cfg.impl='xla'`` — e.g. after
    ``resolve_policy`` clamped a multi-device launch whose sequential
    interpret-mode grid GSPMD cannot partition).  The stored stack is
    identical either way; only the consumption route differs.

    ``twin`` is the same weight encoded in the K-transposed layout of
    B^T (its own scale axis and budget — the dA GEMM contracts over N),
    consumed by the backward pass under ``cfg.cache_weights``.
    """
    residues: jax.Array
    scale: jax.Array
    moduli: tuple
    budget_bits: int
    blocks: Blocks | None
    k: int
    n: int
    layout: str = "fused"
    twin: "PreparedResidues | None" = None
    # Launch-mesh axis sizes at prepare time (see PreparedOperand).
    mesh_shape: tuple | None = None

    # Spec-compat with PreparedOperand consumers (p = modulus count).
    @property
    def p(self) -> int:
        return len(self.moduli)

    @property
    def padded_k(self) -> int:
        return self.residues.shape[1]

    @property
    def padded_n(self) -> int:
        return self.residues.shape[2]

    def reconstruct(self) -> jax.Array:
        """The dense (k, n) float32 weight the residues represent.

        CRT-reconstructs the integerized weight from the balanced
        residue stack and undoes the power-of-two scale — exact up to
        the integerization truncation (1/scale elementwise), for the
        guard's a posteriori verifier (repro.guard.verify).
        """
        from repro.core import scheme2  # lazy: avoid import-order knots
        res = scheme2.modular_reduce(self.residues.astype(jnp.int32),
                                     self.moduli)
        w_int = scheme2.crt_reconstruct(res, self.moduli, jnp.float32)
        return (w_int / self.scale.astype(jnp.float32))[:self.k, :self.n]

    def tree_flatten(self):
        return ((self.residues, self.scale, self.twin),
                (self.moduli, self.budget_bits, self.blocks,
                 self.k, self.n, self.layout, self.mesh_shape))

    @classmethod
    def tree_unflatten(cls, aux, children):
        residues, scale, twin = children
        moduli, budget_bits, blocks, k, n, layout, mesh_shape = aux
        return cls(residues, scale, moduli, budget_bits, blocks, k, n,
                   layout, twin, mesh_shape)


def _pad2(x: jax.Array, align: int = 128) -> jax.Array:
    from repro.kernels.dispatch import round_up
    k, n = x.shape
    kp, np_ = round_up(k, align), round_up(n, align)
    if (kp, np_) == (k, n):
        return x
    return jnp.pad(x, ((0, kp - k), (0, np_ - n)))


def _use_kernel(cfg: EmulationConfig) -> bool:
    return cfg.impl in ("auto", "pallas") and cfg.decomp != "xla"


def prepare_rhs(b: jax.Array, cfg: EmulationConfig, *,
                with_twin: bool = False,
                m_hint: int = 512, mesh=None):
    """Decompose a (K, N) float rhs once, for reuse across GEMMs.

    Under Scheme I returns a :class:`PreparedOperand` (int8 mantissa
    slices); under Scheme II a :class:`PreparedResidues` (balanced int8
    residues — the fused GPU kernel streams them and skips the rhs
    encode).  With ``with_twin`` the K-transposed layout for the
    backward dA GEMM is produced too; when forward and backward share p,
    the Scheme-I pair comes out of one fp32 read (the pair kernel).
    ``m_hint`` sizes the lhs the block search assumes — consumers
    re-select with the granularity pinned, so only bK must be right.

    ``mesh`` records the launch mesh the operand is prepared under (the
    GSPMD leg of the consume-route pinning): its axis sizes key the
    block-granularity cache and travel on the artifact, so
    ``shard_gemm`` can refuse to localize a stack pinned for a
    different mesh layout.
    """
    if cfg.scheme == "ozaki2":
        return prepare_rhs_scheme2(b, cfg, with_twin=with_twin, mesh=mesh)
    if isinstance(b, PreparedResidues):
        raise ValueError("got a PreparedResidues (Scheme-II) operand "
                         f"under scheme={cfg.scheme!r}; pass the float "
                         "weight instead")
    if isinstance(b, PreparedOperand):
        return b
    if b.ndim != 2:
        raise ValueError(f"prepare_rhs is 2-D; got {b.shape}")
    if jnp.issubdtype(b.dtype, jnp.complexfloating):
        raise ValueError("prepare_rhs is real-valued; decompose the real "
                         "and imaginary parts separately (4M formulation)")
    from repro.kernels import decompose, dispatch

    telemetry.record_event(_tele.PREPARED_BUILD,
                           {"scheme": "ozaki1",
                            "layout": ("interleaved" if _use_kernel(cfg)
                                       else "stacked")})
    k, n = b.shape
    if not jnp.issubdtype(b.dtype, jnp.floating):
        b = b.astype(jnp.float32)
    b_pad = _pad2(b)
    kp, np_ = b_pad.shape
    p = cfg.p
    beta = cfg.resolved_beta(kp)
    nu = scheme1._pow2_row_scale(b_pad, axis=0)          # (1, Np)
    mesh_shape = dispatch._mesh_shape_tuple(mesh)

    p_bwd = cfg.bwd_p or p
    beta_bwd = cfg.resolved_beta(np_)

    if not _use_kernel(cfg):
        slices, _ = scheme1.split(b_pad, p, beta, axis=0)
        twin = None
        if with_twin:
            t_slices, tau = scheme1.split(b_pad.T, p_bwd, beta_bwd, axis=0)
            twin = PreparedOperand(t_slices, tau, p_bwd, beta_bwd, None,
                                   "stacked", n, k, mesh_shape=mesh_shape)
        return PreparedOperand(slices, nu, p, beta, None, "stacked",
                               k, n, twin, mesh_shape)

    blocks = dispatch.select_blocks(m_hint, np_, kp, p, backend="tpu",
                                    prologue_a=True, mesh_shape=mesh_shape)
    if blocks is None:
        blocks = Blocks(128, 128, 128)
    if with_twin:
        t_blocks = dispatch.select_blocks(m_hint, kp, np_, p_bwd,
                                          backend="tpu", prologue_a=True,
                                          mesh_shape=mesh_shape)
        if t_blocks is None:
            t_blocks = Blocks(128, 128, 128)
        tau = scheme1._pow2_row_scale(b_pad.T, axis=0)   # (1, Kp)
        if p_bwd == p:
            # One fp32 read of B emits both layouts.
            hat, t_hat = decompose.decompose_interleave_pair(
                b_pad, nu, tau, p, beta, beta_bwd,
                bk=blocks.bk, bt=t_blocks.bk)
        else:
            hat = decompose.decompose_interleave_rhs(b_pad, nu, p, beta,
                                                     bk=blocks.bk)
            t_hat = decompose.decompose_interleave_rhs(
                b_pad.T, tau, p_bwd, beta_bwd, bk=t_blocks.bk)
        twin = PreparedOperand(t_hat, tau, p_bwd, beta_bwd, t_blocks,
                               "interleaved", n, k, mesh_shape=mesh_shape)
        return PreparedOperand(hat, nu, p, beta, blocks, "interleaved",
                               k, n, twin, mesh_shape)
    hat = decompose.decompose_interleave_rhs(b_pad, nu, p, beta,
                                             bk=blocks.bk)
    return PreparedOperand(hat, nu, p, beta, blocks, "interleaved", k, n,
                           mesh_shape=mesh_shape)


def _encode_residues(b: jax.Array, moduli, k_dim: int):
    """One Scheme-II rhs encode: 16-aligned balanced residue stack +
    power-of-two scale + the pinned budget.  The encode mirrors
    ``scheme2.matmul`` exactly (integerize at the shared budget, then
    ``balanced_residues``), so consumption is bit-identical to the
    unprepared pipeline; zero-padded rows/cols encode to zero residues,
    which contribute nothing mod any m_l.
    """
    from repro.core import scheme2
    from repro.kernels.backends import gpu as gpu_backend

    b_pad = _pad2(b, align=gpu_backend.ALIGN)
    budget = scheme2.operand_budget(moduli, k_dim, b.dtype)
    nu = scheme2._pow2_int_scale(b_pad, axis=0, budget_bits=budget)
    res = scheme2.balanced_residues(jnp.trunc(b_pad * nu), moduli)
    return res, nu, budget


def prepare_rhs_scheme2(b: jax.Array, cfg: EmulationConfig, *,
                        with_twin: bool = False,
                        mesh=None) -> PreparedResidues:
    """Encode a (K, N) float rhs's balanced Scheme-II residues once.

    The fused GPU residue kernel streams the stack directly (its
    prologue skips the rhs encode); off-GPU consumers expand from the
    same stack in XLA.  ``with_twin`` also encodes B^T for the backward
    dA GEMM — a separate encode (the twin's scale reduces over the
    other axis and its budget is set by its own contraction length N).
    """
    if isinstance(b, PreparedResidues):
        return b
    if isinstance(b, PreparedOperand):
        raise ValueError("got a PreparedOperand (Scheme-I) operand under "
                         "scheme='ozaki2'; pass the float weight instead")
    if b.ndim != 2:
        raise ValueError(f"prepare_rhs is 2-D; got {b.shape}")
    if jnp.issubdtype(b.dtype, jnp.complexfloating):
        raise ValueError("prepare_rhs is real-valued; decompose the real "
                         "and imaginary parts separately (the complex 3M "
                         "path re-encodes per call)")
    if not jnp.issubdtype(b.dtype, jnp.floating):
        b = b.astype(jnp.float32)
    k, n = b.shape
    moduli = tuple(int(m) for m in cfg.resolved_moduli())
    # The consumption route is pinned now, like the Scheme-I
    # interleaved/stacked split: the fused GPU kernel is taken only when
    # the config would run fused AND the backend resolution lands on
    # 'gpu' — an impl='xla' config (resolve_policy's GSPMD clamp) or a
    # TPU/CPU launch without an explicit gpu request must never re-enter
    # an interpret-mode pallas_call at consume time; they expand the
    # same stack in XLA instead.
    from repro.kernels import backends, dispatch
    layout = ("fused" if _use_kernel(cfg)
              and backends.resolve_backend_name(None, cfg) == "gpu"
              else "stacked")
    mesh_shape = dispatch._mesh_shape_tuple(mesh)
    telemetry.record_event(_tele.PREPARED_BUILD,
                           {"scheme": "ozaki2", "layout": layout})
    res, nu, budget = _encode_residues(b, moduli, k_dim=k)
    twin = None
    if with_twin:
        # Mixed-precision backward: a reduced bwd_p keeps the leading
        # bwd_p moduli, mirroring _bwd_core's replace(p=bwd_p) on a
        # default-moduli config.
        t_moduli = moduli[:cfg.bwd_p] if cfg.bwd_p else moduli
        t_res, tau, t_budget = _encode_residues(b.T, t_moduli, k_dim=n)
        twin = PreparedResidues(t_res, tau, t_moduli, t_budget, None, n, k,
                                layout, mesh_shape=mesh_shape)
    return PreparedResidues(res, nu, moduli, budget, None, k, n, layout,
                            twin, mesh_shape)


def matmul_prepared_scheme2(a: jax.Array, prep: PreparedResidues,
                            out_dtype=jnp.float32) -> jax.Array:
    """(M, K) float @ prepared Scheme-II residues (K, N) -> (M, N).

    The lhs integerizes at the prep's pinned budget and carves its
    residues in the fused GPU kernel's prologue while the stored rhs
    stack streams as-is ('fused' layout); a 'stacked' prep (impl='xla'
    configs) or a missing block fit expands the same stack through the
    XLA reference ops.  Both routes are bit-identical to
    ``scheme2.matmul`` on the same operands whenever the lhs mantissa
    does not bound the shared budget below the encode-time budget (any
    same-precision pair, e.g. f32 @ f32); a lower-precision lhs stays
    exact under the CRT bound but integerizes the two operands at
    different budgets, unlike the single-budget unprepared call.
    """
    from repro.core import scheme2
    from repro.kernels import dispatch
    from repro.kernels.backends import gpu as gpu_backend

    m, k = a.shape
    if k != prep.k:
        raise ValueError(f"lhs K={k} vs prepared K={prep.k}")
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        raise ValueError("matmul_prepared is real-valued; got complex lhs "
                         f"{a.dtype}")
    moduli = prep.moduli
    scheme2.check_exact_k(k, moduli)
    if not jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(jnp.float32)
    # The lhs integerizes in its own dtype at the encode-pinned budget,
    # capped by its own mantissa (mirrors scheme2.matmul's shared cap).
    budget = min(prep.budget_bits, jnp.finfo(a.dtype).nmant + 1)
    kp, np_ = prep.padded_k, prep.padded_n
    mp = dispatch.round_up(m, gpu_backend.ALIGN)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    mu = scheme2._pow2_int_scale(a, axis=1, budget_bits=budget)

    if prep.layout == "fused":
        blocks = dispatch.select_blocks(
            mp, np_, kp, len(moduli),
            out_bytes=jnp.dtype(out_dtype).itemsize, backend="gpu",
            scheme="ozaki2")
        if blocks is not None and blocks.aligned(mp, np_, kp):
            _record_consume("ozaki2", len(moduli), "gpu", "fused", "-",
                            m, k, prep)
            with telemetry.gemm_scope("ozaki2", len(moduli), "gpu",
                                      "prepared-pallas"):
                out = gpu_backend.fused_matmul_scheme2(
                    a, prep.residues, mu, prep.scale, moduli, blocks,
                    out_dtype=out_dtype)
            return out[:m, :prep.n]
        reason = "no_block_fit"
    else:
        reason = "stacked_layout"

    # XLA expansion from the stored residue stack ('stacked' layout, or
    # no block fit at the fused tile grid).
    _record_consume("ozaki2", len(moduli), "xla", "xla", reason, m, k, prep)
    with telemetry.gemm_scope("ozaki2", len(moduli), "xla", "prepared-xla"):
        a_res = scheme2.balanced_residues(jnp.trunc(a * mu), moduli)
        acc = scheme2.residue_gemms(a_res, prep.residues)
        c_res = scheme2.modular_reduce(acc, moduli)
        c_int = scheme2.crt_reconstruct(c_res, moduli, out_dtype)
        out = c_int / (mu.astype(out_dtype) * prep.scale.astype(out_dtype))
    return out[:m, :prep.n]


def matmul_prepared(a: jax.Array, prep,
                    out_dtype=jnp.float32) -> jax.Array:
    """(M, K) float @ prepared (K, N) -> (M, N) ``out_dtype``.

    A :class:`PreparedResidues` rhs streams its Scheme-II residue stack
    (fused GPU kernel, or the XLA expansion off the tile grid).  For a
    Scheme-I :class:`PreparedOperand`, the lhs decomposes in the kernel
    prologue (interleaved layout) or via ``scheme1.split`` (stacked
    layout); the rhs slices are reused as-is.  Non-aligned lhs rows/K
    are zero-padded and the result sliced back.
    """
    from repro.kernels import dispatch, ozaki1

    if isinstance(prep, PreparedResidues):
        return matmul_prepared_scheme2(a, prep, out_dtype=out_dtype)

    m, k = a.shape
    if k != prep.k:
        raise ValueError(f"lhs K={k} vs prepared K={prep.k}")
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        # A silent float32 cast would drop the imaginary half; complex
        # problems must go through the 4M expansion on real parts.
        raise ValueError("matmul_prepared is real-valued; got complex lhs "
                         f"{a.dtype}")
    kp, np_ = prep.padded_k, prep.padded_n
    mp = dispatch.round_up(m)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if not jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(jnp.float32)

    if prep.layout == "interleaved":
        blocks = dispatch.select_blocks(
            mp, np_, kp, prep.p, out_bytes=jnp.dtype(out_dtype).itemsize,
            backend="tpu", prologue_a=True, fixed_bk=prep.blocks.bk)
        if blocks is not None:
            _record_consume("ozaki1", prep.p, "tpu", "fused", "-",
                            m, k, prep)
            with telemetry.gemm_scope("ozaki1", prep.p, "tpu",
                                      "prepared-pallas"):
                mu = scheme1._pow2_row_scale(a, axis=1)      # (Mp, 1)
                out = ozaki1.fused_matmul_mixed(
                    a, prep.slices, mu.astype(jnp.float32),
                    prep.scale.astype(jnp.float32), prep.p, prep.beta,
                    blocks, out_dtype=out_dtype)
            return out[:m, :prep.n]
        reason = "no_block_fit"
    else:
        reason = "stacked_layout"

    # XLA expansion from the stored slices (stacked layout, or no block
    # fit at the pinned granularity).
    _record_consume("ozaki1", prep.p, "xla", "xla", reason, m, k, prep)
    with telemetry.gemm_scope("ozaki1", prep.p, "xla", "prepared-xla"):
        a_sl, mu = scheme1.split(a, prep.p, prep.beta, axis=1)
        accs = scheme1.triangular_accumulators(a_sl, prep.stacked(), prep.p)
        out = scheme1.shift_reduce(accs, prep.beta, mu, prep.scale,
                                   out_dtype)
    return out[:m, :prep.n]


# ---------------------------------------------------------------------------
# Once-per-step preparation under gradient accumulation.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StepPrepared:
    """A float weight paired with its once-per-step PreparedOperand.

    Built *outside* the microbatch scan by ``build_step_preps`` and
    attached to the params tree by ``attach_step_preps``: the scan body
    then closes over the finished slices (a loop-invariant constant of
    the compiled while loop), so every microbatch streams them instead
    of re-running the prep — the decomposition executes once per
    optimizer step, not once per microbatch.  ``w`` stays the
    differentiable leaf: ``emulated_dot_prepared`` (repro.core.emulated)
    computes the forward from ``prep`` and routes dB to ``w``.
    """
    w: jax.Array
    prep: PreparedOperand

    def tree_flatten(self):
        return ((self.w, self.prep), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def _site_of(path, site_default: str = "ffn") -> str:
    keys = [getattr(kp, "key", None) for kp in path]
    if "mixer" in keys:
        return "attn"
    if "head" in keys or "emb" in keys:
        return "logits"
    return site_default


def _step_cacheable(cfg) -> bool:
    # Scheme I caches int8 slices, Scheme II balanced residues.
    return cfg.scheme in ("ozaki1", "ozaki2") and cfg.cache_weights


def policy_caches_weights(policy) -> bool:
    """Does any call-site family of this GemmPolicy cache weights?

    An unset (None) default defers to the ambient resolver, exactly as
    ``for_site`` would; launch callers run ``dispatch.resolve_policy``
    first, which materializes the ambient config into ``default``.
    """
    sites = [policy.default] + [cfg for _, cfg in policy.overrides]
    if policy.default is None:
        from repro import api
        sites[0] = api.resolve_config()
    return any(_step_cacheable(cfg) for cfg in sites)


def _path_key(path) -> str:
    return "/".join(str(getattr(kp, "key", kp)) for kp in path)


def _stack_preps(preps: list) -> PreparedOperand:
    """Stack per-layer PreparedOperands along a new leading axis.

    The static aux (p, beta, blocks, layout) is shape-derived and thus
    identical across layers; stacking only the array leaves yields a
    pytree ``jax.lax.scan`` slices back into per-layer operands."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *preps)


def build_step_preps(params, policy, *, site_default: str = "ffn",
                     names=None, mesh=None) -> dict:
    """Prepare every cacheable dense weight once, keyed by tree path.

    Returns {path: PreparedOperand (with twin)} for the float leaves in
    ``names`` whose site config caches weights.  Scan-stacked layer
    groups (3-D leaves under 'layers') are prepared per layer and
    re-stacked, so the model's layer scan slices finished slices instead
    of re-splitting each layer's weight inside the microbatch scan.
    ``mesh`` (default: the policy's recorded launch mesh, if any) pins
    each prep to the mesh layout it was built under.
    """
    if names is None:
        names = DENSE_WEIGHT_NAMES
    if mesh is None:
        mesh = getattr(policy, "mesh", None)
    preps: dict = {}

    def visit(path, leaf):
        name = getattr(path[-1], "key", None) if path else None
        keys = {getattr(kp, "key", None) for kp in path}
        ndim = getattr(leaf, "ndim", 0)
        stacked = ndim == 3 and "layers" in keys
        # MoE expert tensors reuse dense names but are consumed through
        # raw einsums (and carry an expert axis) — never prepped.
        if (name not in names or "moe" in keys or not (ndim == 2 or stacked)
                or not jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        cfg = policy.for_site(_site_of(path, site_default))
        if not _step_cacheable(cfg):
            return leaf
        if stacked:
            preps[_path_key(path)] = _stack_preps(
                [prepare_rhs(leaf[g], cfg, with_twin=True, mesh=mesh)
                 for g in range(leaf.shape[0])])
        else:
            preps[_path_key(path)] = prepare_rhs(leaf, cfg, with_twin=True,
                                                 mesh=mesh)
        return leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return preps


def attach_step_preps(params, preps: dict):
    """Swap each prepared weight leaf for a StepPrepared(w, prep) pair."""
    if not preps:
        return params

    def wrap(path, leaf):
        prep = preps.get(_path_key(path))
        return StepPrepared(leaf, prep) if prep is not None else leaf

    return jax.tree_util.tree_map_with_path(wrap, params)


# ---------------------------------------------------------------------------
# Whole-model preparation (once-per-session serving reuse).
# ---------------------------------------------------------------------------

# Projection-weight leaf names consumed via models.common.dense — the only
# places a PreparedOperand rhs is legal.  Deliberately excludes lookalikes
# used through raw einsums (w_r/w_i of RG-LRU, wkv_b of MLA, moe experts,
# frontend_proj) and the tied-embedding table.
DENSE_WEIGHT_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "wq_a", "wq_b", "wkv_a",
    "wi", "wi_gate", "wi_up", "w_y", "w_gate", "w_out", "w_in",
    "head",
})


def prepare_params(params, policy, *, site_default: str = "ffn",
                   names=DENSE_WEIGHT_NAMES, mesh=None):
    """Wrap a model's 2-D dense projection weights as PreparedOperands.

    Run once per serve session (outside jit): every subsequent prefill /
    decode step streams the finished int8 slices instead of re-splitting
    the weight.  Leaves under vmap/scan-stacked layer groups are 3-D and
    pass through untouched (their per-layer slices are decomposed by the
    per-step cache instead).  ``mesh`` (default: the policy's recorded
    launch mesh) pins each prep to the mesh it was built under.
    """
    if mesh is None:
        mesh = getattr(policy, "mesh", None)

    def wrap(path, leaf):
        name = getattr(path[-1], "key", None) if path else None
        if (name not in names or getattr(leaf, "ndim", 0) != 2
                or not jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        cfg = policy.for_site(_site_of(path, site_default))
        if cfg.scheme not in ("ozaki1", "ozaki2"):
            return leaf
        return prepare_rhs(leaf, cfg, mesh=mesh)

    return jax.tree_util.tree_map_with_path(wrap, params)


def prep_pspecs(prep, weight_spec):
    """PartitionSpec pytree for a prepared rhs, derived from the source
    weight's (K, N) spec — the slice/residue stacks are built under the
    same spec as the weight, so ``+cached`` params shard with the model
    and never gather.

    Every forward array (slices/residues/scale) carries N as its last
    dim and takes the weight's N axis there; the twin's layout is the
    K-transpose of B, so its arrays end in K and take the weight's K
    axis.  Pair with :func:`repro.parallel.sharding.shardings` to place
    a prepared params tree on a mesh.
    """
    from jax.sharding import PartitionSpec as P
    parts = tuple(weight_spec) + (None, None)
    k_part, n_part = parts[0], parts[1]

    def last_dim(part):
        return lambda leaf: P(*([None] * (leaf.ndim - 1)), part)

    specs = jax.tree.map(last_dim(n_part),
                         dataclasses.replace(prep, twin=None))
    if prep.twin is not None:
        twin_specs = jax.tree.map(
            last_dim(k_part), dataclasses.replace(prep.twin, twin=None))
        specs = dataclasses.replace(specs, twin=twin_specs)
    return specs
