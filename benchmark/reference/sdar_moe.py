"""Plain reference of an SDAR-MoE decoder (`model_type` `sdar_moe`): the
full forward pass over one sequence and the generation by diffusion over
blocks, in `jax.numpy` / numpy, float32, `default_matmul_precision(
"highest")`, no kernels, no cache, no batching. It imports nothing of
the program.

Packed rows `x` of width `hidden_size`, row `t` at position `p_t`; a
layer, `num_hidden_layers` times (B = `block_length`):

    h  = rmsnorm(x, w_in)
    q  = h Wq -> (T, heads, d);  k = h Wk, v = h Wv -> (T, kv heads, d)
    q  = rmsnorm(q, w_qn) over d;  k = rmsnorm(k, w_kn) over d
    q, k = rope(q, k, p_t; theta, all d dims)
    a  = softmax(q k^T / sqrt(d) + M) v      (GQA)
         M[t, s] = 0 if p_s // B <= p_t // B else -inf
    x  = x + concat(a) Wo
    h2 = rmsnorm(x, w_post)
    s  = softmax(h2 Wr) over the experts;  E = top-k(s)
    g_e = s_e / sum_{e' in E} s_e'            (`norm_topk_prob`)
    x  = x + sum_{e in E} g_e * Wdown_e(silu(Wgate_e h2) * Wup_e h2)

then `logits = rmsnorm(x, w_f) W_head`. B = 1 is the causal model.
Only the experts `[expert_offset, expert_offset + experts_held)` are
computed (all where the keys are absent): what a choice of an expert
held elsewhere would add is left out, as in the program.

Generation (`generate_blocks`; greedy): positions are cut into blocks of
B from position 0. The `P // B` whole blocks of a prompt of P tokens are
context; the `P % B` tokens left over are the given head of the first
generated block, which holds the mask elsewhere. A PASS is a forward
over context + block; row `i` of the block predicts position `i`'s OWN
token: for every masked `i`, `x0_i = argmax logits_i` and `c_i =
softmax(logits_i)[x0_i]`. `low_confidence_static` decides the `B /
denoising_steps` masked positions of highest `c` (ties to the lower
position; a remainder goes to the first passes); `low_confidence_dynamic`
every masked position with `c_i > threshold`, and the one of highest `c`
if none. A block without a mask is committed and the next begins. (A
program with a cache runs one more pass over such a block, to leave the
keys and values later blocks read; here every pass recomputes them, and
`passes=` lists that pass too, so a program's passes can be compared one
for one.)

Departures, each noted: (1) rotary pairs are INTERLEAVED, (x[2i],
x[2i+1]) turning at theta^(-2i/d), as in this benchmark's other
references; the publisher's half-split layout is the same arithmetic
under a fixed permutation of the q/k columns and of the q/k norm
weights, and with seeded random weights nothing tells them apart.
(2) Which positions of a block are masked is a flag beside the ids, not
a test of the ids against `mask_token_id`, so a given or decided token
that equals the mask id stays a token. (3) Each expert's gate and up
matrices come side by side, `(experts, hidden, 2 width)`, as the program
stores them: a layout of the file. Weights come in as stored under the
program's parameter names, `(in, out)` matrices, one layer upcast at a
time.
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


def rope_tables(head_dim: int, positions: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    f = np.outer(np.arange(positions, dtype=np.float64), inv)
    return jnp.asarray(np.cos(f), _F32), jnp.asarray(np.sin(f), _F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "block", "eps"))
def _attention(h, w, cos, sin, *, heads, kv_heads, block, eps):
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
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    blk = jnp.arange(t) // block
    s = jnp.where((blk[None, :] <= blk[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return h + o.reshape(t, -1) @ w["self_attn.o_proj.weight"]


@partial(jax.jit, static_argnames=("top_k", "offset", "renorm", "eps"))
def _experts(h, w, chosen=None, *, top_k, offset, renorm, eps):
    """(new h, chosen (T, k), gap (T,))."""
    a = _rmsnorm(h, w["post_attention_layernorm.weight"], eps)
    s = jax.nn.softmax(a @ w["mlp.gate.weight"].astype(_F32), axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(s, top_k)
    rows = jnp.arange(s.shape[0])[:, None]
    taken = jnp.zeros(s.shape, bool).at[rows, chosen].set(True)
    gap = jnp.max(jnp.where(taken, -jnp.inf, s), axis=1) \
        - jnp.min(jnp.where(taken, s, jnp.inf), axis=1)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if renorm:
        picked = picked / jnp.sum(picked, axis=1, keepdims=True)
    # (T, experts): the weight a token gives an expert, 0 if not chosen
    dense = jnp.zeros_like(s).at[rows, chosen].set(picked)
    w_gu, w_down = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]
    width = w_down.shape[1]

    def one(acc, e):
        gu = a @ w_gu[e].astype(_F32)
        y = (jax.nn.silu(gu[:, :width]) * gu[:, width:]) \
            @ w_down[e].astype(_F32)
        return acc + dense[:, offset + e, None] * y, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(a), jnp.arange(w_gu.shape[0]))
    return h + r, chosen, gap


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_w, head_w, *, eps):
    return _rmsnorm(h, norm_w, eps) @ head_w.astype(_F32)


def forward_routed(weights, model: dict, ids, chosen=None, rows=None):
    """(logits float32, chosen, gap) of the token ids `ids` (T,), the
    logits of every row or of `rows` only (a head of 151936 columns over
    a long prompt is most of the memory). `weights` maps the program's
    parameter names to arrays; `model` is the configuration file's
    mapping of sizes. `chosen`, given or returned, is one int (T, k)
    array a layer; `gap` (layers, T) is 0 or less where a row's choice
    is its k highest scores."""
    eps = float(model["rms_norm_eps"])
    ids = jnp.asarray(ids, jnp.int32)
    given = iter(chosen) if chosen is not None else None
    made, gaps = [], []
    cos, sin = rope_tables(int(model["head_dim"]), int(ids.shape[0]),
                           float(model["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        h = weights["model.embed_tokens.weight"][ids].astype(_F32)
        for layer in range(int(model["num_hidden_layers"])):
            pre = f"model.layers.{layer}."
            w = {k[len(pre):]: weights[k] for k in weights
                 if k.startswith(pre)}
            h = _attention(
                h, {k: v for k, v in w.items() if not k.startswith("mlp.")},
                cos, sin, heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                block=int(model.get("block_length", 1)), eps=eps)
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
        if rows is not None:
            h = h[jnp.asarray(rows, jnp.int32)]
        out = _head(h, weights["model.norm.weight"],
                    weights["lm_head.weight"], eps=eps)
    return np.asarray(out, np.float32), made, np.stack(gaps)


def forward_logits(weights, model: dict, ids) -> np.ndarray:
    """(T, vocab) float32 logits of `ids`, every expert layer making
    its own choice."""
    return forward_routed(weights, model, ids)[0]


def transfer(logits, ids, masked, n_pass: int, model: dict):
    """The transfer rule on one block: `logits` (B, vocab) of a pass,
    the block's `ids` and `masked` flags as dispatched, the denoising
    passes the block has had. Returns the next (ids, masked) as lists."""
    lg = np.asarray(logits, np.float64)
    ids, masked = list(ids), list(masked)
    x0 = lg.argmax(-1)
    top = lg.max(-1)
    conf = 1.0 / np.exp(lg - top[:, None]).sum(-1)    # softmax at argmax
    open_ = [i for i in range(len(ids)) if masked[i]]
    # highest confidence first, ties to the lower position
    ranked = sorted(open_, key=lambda i: (-conf[i], i))
    if model.get("remasking", "low_confidence_static") \
            == "low_confidence_static":
        base, rem = divmod(int(model["block_length"]),
                           int(model["denoising_steps"]))
        take = ranked[:base + (n_pass < rem)]
    else:
        take = [i for i in ranked
                if conf[i] > float(model["threshold"])] or ranked[:1]
    for i in take:
        ids[i], masked[i] = int(x0[i]), False
    return ids, masked


def generate_blocks(weights, model: dict, prompt, n_tokens: int,
                    passes=None) -> list:
    """The first `n_tokens` tokens generated after `prompt` by the
    procedure of the module's docstring, a full forward every pass.
    `passes`, a list, is given one `(context tokens, block ids, masked
    flags, the block's logits)` a pass in order, as the pass was
    dispatched, the pass over a block without a mask included."""
    b, mask_id = int(model["block_length"]), int(model["mask_token_id"])
    prompt = [int(t) for t in prompt]
    c = len(prompt) // b * b
    context, given = prompt[:c], prompt[c:]
    out = []
    while len(out) < n_tokens:
        block = given + [mask_id] * (b - len(given))
        masked = [False] * len(given) + [True] * (b - len(given))
        n_pass = 0
        while any(masked) or passes is not None:
            logits = forward_routed(weights, model, context + block,
                                    rows=range(len(context),
                                               len(context) + b))[0]
            if passes is not None:
                passes.append((list(context), list(block), list(masked),
                               logits))
            if not any(masked):
                break
            block, masked = transfer(logits, block, masked, n_pass, model)
            n_pass += 1
        out += block[len(given):]
        context, given = context + block, []
    return out[:n_tokens]
