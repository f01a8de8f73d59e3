"""Plain reference of a dense decoder, the model that ``bench/reference/lm.py``
trains for architecture ``dense_decoder``.

Written from the architecture's description, in straightforward
``jax.numpy``: token embedding, per layer a pre-norm block of grouped-query
attention with rotary positions (rotate-half, sliding-window causal mask)
and a SwiGLU MLP, a final RMSNorm, and the head (its own matrix, or the
embedding where the configuration ties them).  The loss is next-token
cross-entropy.  No kernel, cache, scan or remat.

Weights follow the published initialisation rule the configuration names
(``init``): truncated normals at 1/sqrt(fan_in), the embedding at
1/sqrt(d), norm gains at zero around a unit scale, drawn key by key from one
seed in the order embedding, then per layer q, k, v, o, gate, up, down,
then the untied head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import lm


def init_params(model: dict, seed: int, precision: str):
    """One node's parameters from ``seed`` (one jitted call)."""
    d, h, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd, ff, vocab = d // h, model["intermediate_size"], model["vocab_size"]
    layers = model["num_hidden_layers"]
    untied = not model["tie_word_embeddings"]

    def dense(key, shape):
        return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                           jnp.float32) / math.sqrt(shape[0])

    def make(key):
        keys = []
        for _ in range(1 + 7 * layers + untied):
            key, sub = jax.random.split(key)
            keys.append(sub)
        p = {"embed": jax.random.normal(keys[0], (vocab, d), jnp.float32)
             / math.sqrt(d),
             "final_norm": jnp.zeros((d,), jnp.float32), "layers": []}
        for i in range(layers):
            k = keys[1 + 7 * i:8 + 7 * i]
            p["layers"].append({
                "norm1": jnp.zeros((d,), jnp.float32),
                "wq": dense(k[0], (d, h * hd)),
                "wk": dense(k[1], (d, kv * hd)),
                "wv": dense(k[2], (d, kv * hd)),
                "wo": dense(k[3], (h * hd, d)),
                "norm2": jnp.zeros((d,), jnp.float32),
                "w_gate": dense(k[4], (d, ff)),
                "w_up": dense(k[5], (d, ff)),
                "w_down": dense(k[6], (ff, d)),
            })
        if untied:
            p["lm_head"] = dense(keys[-1], (d, vocab))
        return jax.tree.map(lambda a: a.astype(lm.dtype(precision)), p)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps) * (1.0 + w.astype(jnp.float32))
    return y.astype(x.dtype)


def _rope(x, theta):
    """x (B, L, H, hd): rotate the two halves of each head by position."""
    seq, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _attention(p, model, x):
    b, seq, d = x.shape
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    q = _rope((x @ p["wq"]).reshape(b, seq, h, hd), model["rope_theta"])
    k = _rope((x @ p["wk"]).reshape(b, seq, kv, hd), model["rope_theta"])
    v = (x @ p["wv"]).reshape(b, seq, kv, hd)
    group = jnp.arange(h) // (h // kv)          # query head -> its kv head
    k, v = k[:, :, group], v[:, :, group]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    qpos, kpos = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    allowed = (kpos <= qpos) & (kpos > qpos - model["sliding_window"])
    scores = jnp.where(allowed, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, seq, h * hd)
    return out @ p["wo"]


def loss_fn(params, tokens, labels, model):
    """Mean next-token cross-entropy of one node's batch."""
    eps = model["rms_norm_eps"]
    x = params["embed"][tokens]
    for p in params["layers"]:
        x = x + _attention(p, model, _rms_norm(x, p["norm1"], eps))
        hmid = _rms_norm(x, p["norm2"], eps)
        x = x + (jax.nn.silu(hmid @ p["w_gate"]) * (hmid @ p["w_up"])) \
            @ p["w_down"]
    x = _rms_norm(x, params["final_norm"], eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
