"""Plain float32 forward pass of an OLMo-style decoder (arXiv:2402.00838).

Written from the published description in straightforward ``jax.numpy``:
token embedding, then per layer a non-parametric LayerNorm, multi-head
causal attention with rotary embeddings (rotate-half convention over the
whole head), a residual add, a second non-parametric LayerNorm, a SwiGLU
feed-forward and a residual add; a final non-parametric LayerNorm and
the logits through the tied embedding.  No kernels, no cache, no
batching: one sequence at a time, the whole sequence at once.

Departure from the published model, to match what the benchmark serves:
the LayerNorm epsilon is the configuration's ``layer_norm_eps`` (1e-6;
OLMo-1B publishes 1e-5).

The weights are made here, from the seed, in the dtype the program
serves; the program is handed the same pytree.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def pad_vocab(vocab: int, multiple: int = 512) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


def init_weights(key, cfg: dict, dtype):
    """Random weights: N(0, 2/fan_in) projections, N(0, 0.02^2)
    embeddings.  Laid out as the served program takes them: layers
    stacked on a leading axis under ``layers/b0``."""
    d, h, kvh, f, L = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["d_ff"], cfg["n_layers"])
    hd = d // h
    ks = jax.random.split(key, 8)

    def he(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32)
                * math.sqrt(2.0 / fan)).astype(dtype)

    layer = {"ln1": {}, "ln2": {},
             "mixer": {"wq": he(ks[1], (L, d, h * hd), d),
                       "wk": he(ks[2], (L, d, kvh * hd), d),
                       "wv": he(ks[3], (L, d, kvh * hd), d),
                       "wo": he(ks[4], (L, h * hd, d), h * hd)},
             "ffn": {"wi_gate": he(ks[5], (L, d, f), d),
                     "wi_up": he(ks[6], (L, d, f), d),
                     "wo": he(ks[7], (L, f, d), f)}}
    emb = (jax.random.normal(ks[0], (pad_vocab(cfg["vocab"]), d),
                             jnp.float32) * 0.02).astype(dtype)
    return {"emb": emb, "ln_f": {}, "layers": {"b0": layer}}


def matmul(a, b):
    """(..., m, k) @ (..., k, n) in float32."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rope(x, theta: float):
    """x: (heads, seq, hd); rotate-half rotary embedding at 0..seq-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, cfg: dict, tokens):
    """tokens: (seq,) int32 -> logits (seq, vocab) float32."""
    d, h, kvh = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, eps, theta = d // h, cfg["layer_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    f32 = lambda t: jax.tree.map(lambda v: v.astype(jnp.float32), t)
    x = f32(w["emb"])[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        p = f32(p)
        a = layer_norm(x, eps)
        q = matmul(a, p["mixer"]["wq"]).reshape(s, h, hd)
        k = matmul(a, p["mixer"]["wk"]).reshape(s, kvh, hd)
        v = matmul(a, p["mixer"]["wv"]).reshape(s, kvh, hd)
        q = rope(q.transpose(1, 0, 2), theta)
        k = rope(k.transpose(1, 0, 2), theta)
        v = v.transpose(1, 0, 2)
        rep = h // kvh
        k, v = jnp.repeat(k, rep, 0), jnp.repeat(v, rep, 0)
        scores = matmul(q, k.transpose(0, 2, 1)) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        o = matmul(jax.nn.softmax(scores, -1), v)
        x = x + matmul(o.transpose(1, 0, 2).reshape(s, h * hd),
                       p["mixer"]["wo"])
        a = layer_norm(x, eps)
        g = matmul(a, p["ffn"]["wi_gate"])
        u = matmul(a, p["ffn"]["wi_up"])
        x = x + matmul(jax.nn.silu(g) * u, p["ffn"]["wo"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"]["b0"])
    x = layer_norm(x, eps)
    emb = w["emb"][:cfg["vocab"]].astype(jnp.float32)
    return matmul(x, emb.T)


@functools.partial(jax.jit, static_argnums=(1,))
def served_gaps(w, cfg_items, tokens, targets):
    """For each position i of ``tokens``, how far the forward's logit of
    ``targets[i]`` lies below its best logit there."""
    cfg = dict(cfg_items)
    logits = forward(w, cfg, tokens)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best - got


def cfg_items(cfg: dict) -> tuple:
    """The hashable part of a configuration the forward pass reads."""
    keys = ("d_model", "n_heads", "n_kv_heads", "d_ff", "n_layers", "vocab",
            "layer_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)
