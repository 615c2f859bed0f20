"""Top-level language model: embeddings -> scanned blocks -> head.

Layers are grouped by the (possibly heterogeneous) ``block_pattern`` and
executed with ``jax.lax.scan`` over stacked group parameters, so compile
time and HLO size are O(1) in depth — essential for lowering 61-layer
models against a 512-device mesh. A remainder of ``n_layers % len(pattern)``
trailing blocks runs unscanned.

Entry points:
  init_params      — (also usable under jax.eval_shape for the dry-run)
  forward_train    — (B, S) tokens -> (logits, mtp_logits|None, aux_loss)
  forward_prefill  — prompt -> (last-position logits, cache)
  forward_decode   — one token + cache -> (logits, new cache)
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.telemetry import trace as telemetry_trace
from repro.models.common import (GemmPolicy, NATIVE_POLICY, apply_norm, dense,
                                 emb_init, he_init, init_norm, pad_vocab)


def _groups(mcfg: ModelConfig):
    pat = list(mcfg.block_pattern)
    n_groups = mcfg.n_layers // len(pat)
    tail = mcfg.pattern_for_layers()[n_groups * len(pat):]
    return pat, n_groups, tail


def init_params(key, mcfg: ModelConfig):
    dtype = jnp.dtype(mcfg.dtype)
    pat, n_groups, tail = _groups(mcfg)
    keys = jax.random.split(key, 6)
    vp = pad_vocab(mcfg.vocab)
    params = {"emb": emb_init(keys[0], (vp, mcfg.d_model), dtype),
              "ln_f": init_norm(mcfg.norm, mcfg.d_model, dtype)}
    if not mcfg.tie_embeddings:
        params["head"] = he_init(keys[1], (mcfg.d_model, vp), dtype)
    if mcfg.frontend in ("audio_stub", "vision_stub"):
        params["frontend_proj"] = he_init(
            keys[2], (mcfg.frontend_dim, mcfg.d_model), dtype)

    def init_group(k):
        gk = jax.random.split(k, len(pat))
        return {f"b{j}": B.init_block(gk[j], kind, mcfg, dtype)
                for j, kind in enumerate(pat)}

    if n_groups:
        params["layers"] = jax.vmap(init_group)(
            jax.random.split(keys[3], n_groups))
    if tail:
        tk = jax.random.split(keys[4], len(tail))
        params["tail"] = [B.init_block(tk[j], kind, mcfg, dtype)
                          for j, kind in enumerate(tail)]
    if mcfg.mtp:
        mk = jax.random.split(keys[5], 3)
        params["mtp"] = {
            "proj": he_init(mk[0], (2 * mcfg.d_model, mcfg.d_model), dtype),
            "block": B.init_block(mk[1], "attn", mcfg, dtype),
            "ln": init_norm(mcfg.norm, mcfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Input embedding (token / audio-stub / vision-stub frontends).
# ---------------------------------------------------------------------------

def embed_inputs(params, mcfg: ModelConfig, inputs: dict):
    if mcfg.frontend == "audio_stub":
        x = jnp.einsum("bsf,fd->bsd", inputs["tokens"],
                       params["frontend_proj"])
        b, s = x.shape[:2]
    else:
        ids = inputs["tokens"]
        b, s = ids.shape
        x = jnp.take(params["emb"], ids, axis=0)
        if mcfg.frontend == "vision_stub" and "image_embeds" in inputs:
            img = jnp.einsum("bnf,fd->bnd", inputs["image_embeds"],
                             params["frontend_proj"]).astype(x.dtype)
            x = jax.lax.dynamic_update_slice_in_dim(x, img, 0, 1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return x, positions


def logits_from_hidden(params, mcfg: ModelConfig, x, policy: GemmPolicy):
    x = apply_norm(mcfg.norm, params["ln_f"], x)
    w = params["emb"].T if mcfg.tie_embeddings else params["head"]
    return dense(x, w, policy, "logits")


# ---------------------------------------------------------------------------
# Train forward (scanned, optionally rematerialized).
# ---------------------------------------------------------------------------

def forward_train(params, mcfg: ModelConfig, inputs: dict,
                  policy: GemmPolicy = NATIVE_POLICY, remat: bool = True):
    from repro.models.blocks import _sp_constrain
    pat, n_groups, tail = _groups(mcfg)
    x, positions = embed_inputs(params, mcfg, inputs)
    x = _sp_constrain(x, mcfg)   # sequence-parallel residual stream

    def group_fn(carry, gp):
        x, aux = carry
        for j, kind in enumerate(pat):
            x, a = B.block_train(gp[f"b{j}"], kind, mcfg, x, positions,
                                 policy)
            aux = aux + a
        return (x, aux), None

    if remat:
        group_fn = jax.checkpoint(group_fn, prevent_cse=False)

    aux = jnp.zeros((), jnp.float32)
    if n_groups:
        (x, aux), _ = jax.lax.scan(group_fn, (x, aux), params["layers"])
    for j, kind in enumerate(tail):
        x, a = B.block_train(params["tail"][j], kind, mcfg, x, positions,
                             policy)
        aux = aux + a

    mtp_logits = None
    if mcfg.mtp:
        # DeepSeek-V3 multi-token prediction: one extra block sees the
        # final hidden state fused with the embedding of the *next* token
        # and predicts token t+2 through the shared head.
        h = apply_norm(mcfg.norm, params["mtp"]["ln"], x)
        nxt = jnp.roll(inputs["tokens"], -1, axis=1)
        e = jnp.take(params["emb"], nxt, axis=0)
        fused = dense(jnp.concatenate([h, e], -1), params["mtp"]["proj"],
                      policy, "ffn")
        fused, _ = B.block_train(params["mtp"]["block"], "attn", mcfg, fused,
                                 positions, policy)
        mtp_logits = logits_from_hidden(params, mcfg, fused, policy)

    return logits_from_hidden(params, mcfg, x, policy), mtp_logits, aux


# ---------------------------------------------------------------------------
# Prefill / decode (cache threading through the scan).
# ---------------------------------------------------------------------------

def init_cache(mcfg: ModelConfig, batch: int, max_seq: int):
    dtype = jnp.dtype(mcfg.dtype)
    pat, n_groups, tail = _groups(mcfg)

    def group_cache(_):
        return {f"b{j}": B.init_block_cache(kind, mcfg, batch, max_seq, dtype)
                for j, kind in enumerate(pat)}

    cache = {}
    if n_groups:
        cache["layers"] = jax.vmap(group_cache)(jnp.arange(n_groups))
    if tail:
        cache["tail"] = [B.init_block_cache(kind, mcfg, batch, max_seq, dtype)
                         for kind in tail]
    return cache


def forward_prefill(params, mcfg: ModelConfig, inputs: dict, max_seq: int,
                    policy: GemmPolicy = NATIVE_POLICY):
    pat, n_groups, tail = _groups(mcfg)
    x, positions = embed_inputs(params, mcfg, inputs)

    def group_fn(x, gp):
        caches = {}
        for j, kind in enumerate(pat):
            x, caches[f"b{j}"] = B.block_prefill(gp[f"b{j}"], kind, mcfg, x,
                                                 positions, policy, max_seq)
        return x, caches

    cache = {}
    if n_groups:
        x, cache["layers"] = jax.lax.scan(group_fn, x, params["layers"])
    if tail:
        cache["tail"] = []
        for j, kind in enumerate(tail):
            x, c = B.block_prefill(params["tail"][j], kind, mcfg, x,
                                   positions, policy, max_seq)
            cache["tail"].append(c)
    logits = logits_from_hidden(params, mcfg, x[:, -1:], policy)
    return logits, cache


def forward_decode(params, mcfg: ModelConfig, token, pos, cache,
                   policy: GemmPolicy = NATIVE_POLICY):
    """token: (B, 1) int32 (or (B, 1, F) stub embeddings); pos scalar."""
    pat, n_groups, tail = _groups(mcfg)
    if mcfg.frontend == "audio_stub":
        x = jnp.einsum("bsf,fd->bsd", token, params["frontend_proj"])
    else:
        x = jnp.take(params["emb"], token, axis=0)

    def group_fn(x, xs):
        gp, gcache = xs
        new = {}
        for j, kind in enumerate(pat):
            x, new[f"b{j}"] = B.block_decode(gp[f"b{j}"], kind, mcfg, x, pos,
                                             gcache[f"b{j}"], policy)
        return x, new

    new_cache = {}
    if n_groups:
        x, new_cache["layers"] = jax.lax.scan(
            group_fn, x, (params["layers"], cache["layers"]))
    if tail:
        new_cache["tail"] = []
        for j, kind in enumerate(tail):
            x, c = B.block_decode(params["tail"][j], kind, mcfg, x, pos,
                                  cache["tail"][j], policy)
            new_cache["tail"].append(c)
    return logits_from_hidden(params, mcfg, x, policy), new_cache


def forward_step(params, mcfg: ModelConfig, tokens, start, n_new, cache,
                 policy: GemmPolicy = NATIVE_POLICY):
    """Ragged mixed prefill/decode step for the continuous-batching
    serving engine (repro.serving).

    tokens: (B, C) int32 — each lane's next chunk of fresh token ids,
    left-aligned and zero-padded; start: (B,) absolute position of each
    lane's first fresh token; n_new: (B,) valid counts (decode lanes 1,
    prefill chunks up to C, idle lanes 0). cache: per-lane cache views
    (same pytree as :func:`init_cache`) — the engine gathers them from
    its paged pools and scatters the fresh slots back.

    Returns (logits (B, vocab_padded) at each lane's last valid fresh
    position, updated cache views). Padding lanes/columns produce
    well-defined garbage the caller discards; per-lane rows are computed
    independently, so one lane's result is bit-identical whatever the
    rest of the cohort is doing — the invariant the serving tests pin.
    """
    pat, n_groups, tail = _groups(mcfg)
    if mcfg.frontend != "none":
        raise NotImplementedError(
            "serving steps take token ids only; stub frontends "
            f"({mcfg.frontend!r}) have no ragged chunk path")
    b, c = tokens.shape
    x = jnp.take(params["emb"], tokens, axis=0)

    def group_fn(x, xs):
        gp, gcache = xs
        new = {}
        for j, kind in enumerate(pat):
            x, new[f"b{j}"] = B.block_step(gp[f"b{j}"], kind, mcfg, x,
                                           start, n_new, gcache[f"b{j}"],
                                           policy)
        return x, new

    new_cache = {}
    if n_groups:
        # The scanned body is traced once and runs n_groups times.
        with telemetry_trace.repeated(n_groups):
            x, new_cache["layers"] = jax.lax.scan(
                group_fn, x, (params["layers"], cache["layers"]))
    if tail:
        new_cache["tail"] = []
        for j, kind in enumerate(tail):
            x, cc = B.block_step(params["tail"][j], kind, mcfg, x, start,
                                 n_new, cache["tail"][j], policy)
            new_cache["tail"].append(cc)
    idx = jnp.clip(n_new - 1, 0, c - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)   # (B, 1, D)
    logits = logits_from_hidden(params, mcfg, x_last, policy)
    return logits[:, 0], new_cache


def param_count(params) -> int:
    return sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
