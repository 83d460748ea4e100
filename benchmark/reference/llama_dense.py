"""Plain reference of a dense Llama-style decoder (InternLM2, Mistral,
and whatever else is pre-norm RMSNorm + GQA + rotary + SwiGLU with no
biases): the full forward pass over one sequence in `jax.numpy`,
float32, `default_matmul_precision("highest")`, no kernels, no cache,
no batching. It imports nothing of the program.

Follows the published equations:

    h  = embed[ids]
    per layer:  a = rmsnorm(h) ;  q, k, v = a Wq, a Wk, a Wv
                q, k = rope(q), rope(k)           (theta, position)
                h += softmax(causal(q k^T / sqrt(d))) v  Wo   (GQA: each
                     KV head serves num_heads / num_kv_heads Q heads)
                m = rmsnorm(h) ;  h += (silu(m Wg) * (m Wu)) Wd
    logits = rmsnorm(h) Whead

Departures, each noted: (1) rotary pairs are INTERLEAVED, (x[2i],
x[2i+1]) turning at theta^(-2i/d), as in Meta's and Mistral's reference
code; Hugging Face's half-split layout is the same arithmetic under a
fixed permutation of the q/k columns, and with seeded random weights
nothing tells them apart. (2) InternLM2's checkpoint packs `wqkv`; that
is a layout of the file, not of the arithmetic. (3) `rope_scaling`
("dynamic", InternLM2) only acts beyond `max_position_embeddings` and
is not modelled. Weights come in as stored (bf16) under the program's
parameter names, `(in, out)` matrices, and ONE layer's are upcast at a
time, so a 3.76 B model never exists in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rmsnorm(x, w, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w.astype(_F32)


def _rope(x, cos, sin):
    """x (T, H, D); cos, sin (T, D/2): interleaved pairs."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _layer(h, w, cos, sin, *, heads, kv_heads, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = h.shape[0]
    a = _rmsnorm(h, w["input_layernorm.weight"], eps)
    q = (a @ w["self_attn.q_proj.weight"]).reshape(t, heads, -1)
    k = (a @ w["self_attn.k_proj.weight"]).reshape(t, kv_heads, -1)
    v = (a @ w["self_attn.v_proj.weight"]).reshape(t, kv_heads, -1)
    d = q.shape[-1]
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)              # (T, H, D)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(t, heads * d)
    h = h + o @ w["self_attn.o_proj.weight"]
    m = _rmsnorm(h, w["post_attention_layernorm.weight"], eps)
    gate = jax.nn.silu(m @ w["mlp.gate_proj.weight"])
    return h + (gate * (m @ w["mlp.up_proj.weight"])) \
        @ w["mlp.down_proj.weight"]


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_w, head_w, *, eps):
    return _rmsnorm(h, norm_w, eps) @ head_w.astype(_F32)


def rope_tables(head_dim: int, positions: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(ang), _F32), jnp.asarray(np.sin(ang), _F32))


def forward_logits(weights: dict, model: dict, ids) -> np.ndarray:
    """(T, vocab) float32 logits of the token ids `ids` (T,).
    `weights` maps the program's parameter names to arrays; `model` is
    the configuration file's mapping of sizes."""
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    head_dim = model.get("head_dim") or model["hidden_size"] // heads
    eps = float(model["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    cos, sin = rope_tables(head_dim, int(ids.shape[0]),
                           float(model["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        h = weights["model.embed_tokens.weight"][ids].astype(_F32)
        for layer in range(model["num_hidden_layers"]):
            pre = f"model.layers.{layer}."
            w = {k[len(pre):]: v for k, v in weights.items()
                 if k.startswith(pre)}
            h = _layer(h, w, cos, sin, heads=heads, kv_heads=kv_heads,
                       eps=eps)
        head = weights["model.embed_tokens.weight"].T \
            if model.get("tie_word_embeddings") \
            else weights["lm_head.weight"]
        out = _head(h, weights["model.norm.weight"], head, eps=eps)
    return np.asarray(out, np.float32)
