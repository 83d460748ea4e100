"""Plain reference of a Mellum 2 decoder (`model_type` `mellum`): the full
forward pass over one sequence in `jax.numpy`, float32,
`default_matmul_precision("highest")`, no kernels, no cache, no
batching. It imports nothing of the program.

Rows `x` (T, hidden) of one sequence, row `p` at position `p`. Layer
`i` of the program is layer `L - n + i` of the published model (`L` =
len(`layer_types`), `n` = `num_hidden_layers`: the last stage of a
pipeline), of kind `layer_types[L - n + i]`; every RMSNorm has eps
`rms_norm_eps`:

    h  = rmsnorm(x, w_in)
    q  = h Wq -> (T, heads, d);  k = h Wk, v = h Wv -> (T, kv heads, d)
    q  = rmsnorm(q, w_qn) over d;  k = rmsnorm(k, w_kn) over d
    q, k = rope(q, k, p; the layer kind's table)
    a  = softmax(q k^T / sqrt(d) + M) v       (GQA: q head h reads kv
                                               head h // (heads / kv heads))
         M[p, j] = 0 if j <= p (full), or p - W < j <= p (sliding,
                   W = `sliding_window`); else -inf
    x  = x + concat(a) Wo
    h2 = rmsnorm(x, w_post)
    r  = softmax(h2 Wr) over the experts;  S = top-k(r)
    g_e = r_e / sum_{e' in S} r_e'               (`norm_topk_prob`)
    x  = x + sum_{e in S} g_e * Wdown_e(silu(Wgate_e h2) * Wup_e h2)

then `logits = rmsnorm(x, w_f) W_head`. No shared expert, no scaling
of the routed sum. Only the experts `[expert_offset, expert_offset +
experts_held)` are computed (all where the keys are absent).

The rotary tables, one a layer kind (`rope_parameters[kind]`), with
`inv_i = theta^(-2i/d)`, i < d/2:

    default: cos = cos(p inv), sin = sin(p inv)
    yarn:    low  = floor(d ln(C / (beta_fast 2 pi)) / (2 ln theta)),
             high = ceil(d ln(C / (beta_slow 2 pi)) / (2 ln theta)),
             C = `original_max_position_embeddings`, clipped to [0, d-1];
             ramp_i = clip((i - low) / (high - low), 0, 1);
             inv'_i = inv_i / factor * ramp_i + inv_i (1 - ramp_i);
             cos = s cos(p inv'), sin = s sin(p inv'),
             s = `attention_factor` (0.1 ln factor + 1 where absent)

(the transformers `_compute_yarn_parameters` formula with `truncate`):
the logits of a full layer are scaled by s^2.

Departures, each noted: (1) rotary pairs are INTERLEAVED, (x[2i],
x[2i+1]) turning at inv_i, as in this benchmark's other references; the
publisher's half-split layout is the same arithmetic under a fixed
permutation of the q/k columns and of the q/k norm weights, and with
seeded random weights nothing tells them apart. (2) The RMSNorm over
every q and k head is ASSUMED (the Qwen3-MoE lineage whose keys the
config carries; the config does not say). (3) Each expert's gate and up
matrices come side by side, `(experts, hidden, 2 width)`, as the program
stores them: a layout of the file. Weights come in as stored under the
program's parameter names, `(in, out)` matrices, one layer upcast at a
time.

For a chip's check at a long prompt, attention runs in blocks of
`SEQ_BLOCK` queries against every key, and the head of `vocab_size`
columns over `HEAD_BLOCK` rows at a time, of the rows asked for only.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
SEQ_BLOCK = 512
HEAD_BLOCK = 512


def _rmsnorm(x, w, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w.astype(_F32)


def _rope(x, cos, sin):
    """x (T, H, D); cos, sin (T, D/2): interleaved pairs."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def layer_kinds(model: dict) -> list:
    """The kind of each of the program's layers: the last
    `num_hidden_layers` of the published `layer_types`."""
    types = list(model["layer_types"])
    return types[len(types) - int(model["num_hidden_layers"]):]


def yarn_edges(head_dim: int, p: dict):
    """(low, high): the rotary pairs where YaRN's ramp starts and ends."""
    theta, ctx = float(p["rope_theta"]), \
        float(p["original_max_position_embeddings"])

    def dim(turns):
        return head_dim * math.log(ctx / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(dim(float(p["beta_fast"]))), 0),
            min(math.ceil(dim(float(p["beta_slow"]))), head_dim - 1))


def rope_tables(head_dim: int, positions: int, p: dict):
    """(cos, sin) (positions, head_dim / 2) of one `rope_parameters`
    entry, as the module's docstring writes them."""
    inv = 1.0 / (float(p["rope_theta"])
                 ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    s = 1.0
    if p.get("rope_type", "default") == "yarn":
        factor = float(p["factor"])
        low, high = yarn_edges(head_dim, p)
        ramp = np.clip((np.arange(head_dim // 2) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        s = float(p.get("attention_factor") or 0.1 * math.log(factor) + 1)
    elif p.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    f = np.outer(np.arange(positions, dtype=np.float64), inv)
    return jnp.asarray(s * np.cos(f), _F32), jnp.asarray(s * np.sin(f), _F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "eps"))
def _attention(h, w, cos, sin, *, heads, kv_heads, window, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = h.shape[0]
    a = _rmsnorm(h, w["input_layernorm.weight"], eps)
    q = (a @ w["self_attn.q_proj.weight"]).reshape(t, heads, -1)
    k = (a @ w["self_attn.k_proj.weight"]).reshape(t, kv_heads, -1)
    v = (a @ w["self_attn.v_proj.weight"]).reshape(t, kv_heads, -1)
    q = _rope(_rmsnorm(q, w["self_attn.q_norm.weight"], eps), cos, sin)
    k = _rope(_rmsnorm(k, w["self_attn.k_norm.weight"], eps), cos, sin)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    nb = -(-t // SEQ_BLOCK)
    qb = jnp.pad(q, ((0, nb * SEQ_BLOCK - t), (0, 0), (0, 0))).reshape(
        nb, SEQ_BLOCK, heads, -1)
    cols = jnp.arange(t)

    def block(args):
        qq, b = args
        rows = b * SEQ_BLOCK + jnp.arange(SEQ_BLOCK)
        ok = cols[None, :] <= rows[:, None]
        if window is not None:
            ok &= cols[None, :] > rows[:, None] - window
        s = jnp.einsum("qhd,khd->hqk", qq, k) / np.sqrt(q.shape[-1])
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, (qb, jnp.arange(nb))).reshape(
        nb * SEQ_BLOCK, -1)[:t]
    return h + o @ w["self_attn.o_proj.weight"]


@partial(jax.jit, static_argnames=("top_k", "offset", "renorm", "eps"))
def _experts(h, w, chosen=None, *, top_k, offset, renorm, eps):
    """(new h, chosen (T, k), gap (T,))."""
    a = _rmsnorm(h, w["post_attention_layernorm.weight"], eps)
    r = jax.nn.softmax(a @ w["mlp.gate.weight"].astype(_F32), axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(r, top_k)
    rows = jnp.arange(r.shape[0])[:, None]
    taken = jnp.zeros(r.shape, bool).at[rows, chosen].set(True)
    gap = jnp.max(jnp.where(taken, -jnp.inf, r), axis=1) \
        - jnp.min(jnp.where(taken, r, jnp.inf), axis=1)
    picked = jnp.take_along_axis(r, chosen, axis=1)
    if renorm:
        picked = picked / jnp.sum(picked, axis=1, keepdims=True)
    # (T, experts): the weight a token gives an expert, 0 if not chosen
    dense = jnp.zeros_like(r).at[rows, chosen].set(picked)
    w_gu, w_down = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]
    width = w_down.shape[1]

    def one(acc, e):
        gu = a @ w_gu[e].astype(_F32)
        y = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
            @ w_down[e].astype(_F32)
        return acc + dense[:, offset + e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(a), jnp.arange(w_gu.shape[0]))
    return h + out, chosen, gap


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_w, head_w, *, eps):
    return _rmsnorm(h, norm_w, eps) @ head_w.astype(_F32)


def forward_routed(weights, model: dict, ids, chosen=None, rows=None):
    """(logits float32, chosen, gap) of the token ids `ids` (T,), the
    logits of every row or of `rows` only. `weights` maps the program's
    parameter names to arrays; `model` is the configuration file's
    mapping of sizes. `chosen`, given or returned, is one int (T, k)
    array a layer; `gap` (layers, T) is 0 or less where a row's choice
    is its k highest scores."""
    eps = float(model["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    t = int(ids.shape[0])
    given = iter(chosen) if chosen is not None else None
    made, gaps = [], []
    kinds = layer_kinds(model)
    tables = {kind: rope_tables(int(model["head_dim"]), t,
                                model["rope_parameters"][kind])
              for kind in set(kinds)}
    with jax.default_matmul_precision("highest"):
        h = weights["model.embed_tokens.weight"][ids].astype(_F32)
        for layer, kind in enumerate(kinds):
            pre = f"model.layers.{layer}."
            w = {k[len(pre):]: weights[k] for k in weights
                 if k.startswith(pre)}
            h = _attention(
                h, {k: v for k, v in w.items() if not k.startswith("mlp.")},
                *tables[kind], heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                window=int(model["sliding_window"])
                if kind == "sliding_attention" else None, eps=eps)
            h, took, gap = _experts(
                h, {k: v for k, v in w.items()
                    if k.startswith(("mlp.", "post_"))},
                None if given is None
                else jnp.asarray(next(given), jnp.int32),
                top_k=int(model["num_experts_per_tok"]),
                offset=int(model.get("expert_offset") or 0),
                renorm=bool(model["norm_topk_prob"]), eps=eps)
            made.append(np.asarray(took))
            gaps.append(np.asarray(gap, np.float32))
        read = np.arange(t) if rows is None else np.asarray(rows, np.int64)
        out = [np.asarray(_head(
            h[jnp.asarray(read[i:i + HEAD_BLOCK], jnp.int32)],
            weights["model.norm.weight"], weights["lm_head.weight"],
            eps=eps), np.float32) for i in range(0, len(read), HEAD_BLOCK)]
    return np.concatenate(out), made, np.stack(gaps)


def forward_logits(weights, model: dict, ids) -> np.ndarray:
    """(T, vocab) float32 logits of `ids`, every expert layer making
    its own choice."""
    return forward_routed(weights, model, ids)[0]
