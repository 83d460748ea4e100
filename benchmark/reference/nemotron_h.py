"""Plain reference of a Nemotron-H hybrid decoder (`model_type`
`nemotron_h`: Mamba-2, latent mixture-of-experts and GQA attention
blocks in the order of `hybrid_override_pattern`): the full forward
pass over one sequence in `jax.numpy`, float32,
`default_matmul_precision("highest")`, the step-by-step recurrence, a
loop over experts, no kernels, no cache, no chunking, no batching. It
imports nothing of the program.

Every block is ONE mixer behind a pre-norm and a residual, chosen by
the block's letter (the first `num_hidden_layers` letters count):

    h = embed[ids]
    per block:   h += mixer(rmsnorm(h))
    logits = rmsnorm(h) W_head                        (untied)

`M`, Mamba-2 (H = mamba_num_heads, P = mamba_head_dim, N =
ssm_state_size, G = n_groups, d_in = H P, K = conv_kernel), `u` the
normed input:

    [z | xBC | dt] = u W_in          widths d_in, d_in + 2 G N, H; no bias
    xBC_t = silu(sum_k w_conv[:, k] xBC_{t-K+1+k} + b_conv)
                                      depthwise, causal, zeros before t=0
    [x | B | C] = xBC                 x: H x P;  B, C: G x N, group g
                                      serves heads g H/G .. (g+1) H/G - 1
    dt = softplus(dt + dt_bias);  A = -exp(A_log)       per head
    s_t = exp(dt_t A) s_{t-1} + dt_t (x_t (x) B_t)      s: H x P x N
    y_t = s_t C_t + D x_t
    y = rmsnorm_grouped(y * silu(z)) * w_norm   groups of d_in / G
    out = y W_out

`E`, latent experts (R = n_routed_experts, k = num_experts_per_tok),
`a` the normed input:

    s = sigmoid(a W_r)                              over all R experts
    chosen = the k largest of s + b_corr            (n_group = 1)
    w = s[chosen] / sum(s[chosen]) * routed_scaling_factor
                     normalised over all k, held here or not
    l = a W_down                                    hidden -> latent
    r = sum_{e chosen and held} w_e relu(l W1_e)^2 W2_e
                     held: expert_offset <= e < expert_offset +
                     experts_held; what the absent experts would add
                     is left out
    out = r W_up + relu(a W1_s)^2 W2_s              shared expert, on
                                                    the full hidden size

`*`: q, k, v = a Wq, a Wk, a Wv; softmax(causal(q k^T / sqrt(d))) v Wo
with each KV head serving num_attention_heads / num_key_value_heads
Q heads. No bias and NO rotary or other position embedding.

Departures and inferences (the configuration file lists them under
`assumed`): (1) no rotary in `*` although the config carries
`rope_theta` and `partial_rotary_factor`: the Nemotron-H description
says its attention layers use no position embeddings, the Mamba layers
carry order; (2) the router's score is computed from float32
activations and weights; (3) `dt` is not clamped (the published limit
is (0, inf)); (4) the multi-token-prediction module is a drafter beside
the forward pass and is not part of the logits. Weights come in as
stored (bf16) under the program's parameter names, `(in, out)`
matrices, and ONE layer's are upcast at a time (an expert layer's one
expert at a time), so the model never exists in float32.

`forward_routed` is the same forward pass with every expert layer's
choice GIVEN (`chosen`, one (T, k) array an expert layer): what the
choice-forced logits check (`benchmark/runners/serve_routed.py`)
compares a bf16 program with. The choice is a discontinuous function of
a score: a program in bf16 and this reference in float32 differ in the
22nd and 23rd of 512 scores by less than bf16 resolves in one row in
ten, and one exchanged expert moves that row's logits by more than any
rounding does. With the choice given, what is left is rounding; that
the given choice IS a top-k of this reference's own scores, up to such
near-ties, is returned beside the logits as each row's `gap`: the
highest score left out less the lowest score taken (0 or less where
the given choice is exactly the top k).
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@partial(jax.jit, static_argnames=("heads", "head_dim", "state", "groups",
                                   "eps"))
def _mamba(h, w, *, heads, head_dim, state, groups, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = h.shape[0]
    d_in, gn = heads * head_dim, groups * state
    u = _rmsnorm(h, w["norm.weight"], eps)
    zxd = u @ w["mixer.in_proj.weight"]
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * gn], \
        zxd[:, 2 * d_in + 2 * gn:]
    cw = w["mixer.conv1d.weight"]                       # (channels, K)
    k = cw.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), _F32), xbc])
    conv = sum(padded[i:i + t] * cw[:, i] for i in range(k))
    xbc = jax.nn.silu(conv + w["mixer.conv1d.bias"])
    x = xbc[:, :d_in].reshape(t, heads, head_dim)
    per = heads // groups
    b = jnp.repeat(xbc[:, d_in:d_in + gn].reshape(t, groups, state), per, 1)
    c = jnp.repeat(xbc[:, d_in + gn:].reshape(t, groups, state), per, 1)
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])       # (T, H)
    a = -jnp.exp(w["mixer.A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state), _F32),
                        (x, b, c, dt))
    y = (y + w["mixer.D"][:, None] * x).reshape(t, d_in)
    y = (y * jax.nn.silu(z)).reshape(t, groups, d_in // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    y = y.reshape(t, d_in) * w["mixer.norm.weight"]
    return h + y @ w["mixer.out_proj.weight"]


@partial(jax.jit, static_argnames=("top_k", "offset", "scale", "renorm",
                                   "eps"))
def _experts(h, w, chosen=None, *, top_k, offset, scale, renorm, eps):
    """(new h, chosen (T, k), gap (T,))."""
    small = {k: v.astype(_F32) for k, v in w.items()
             if not k.startswith("mixer.experts.")}
    a = _rmsnorm(h, small["norm.weight"], eps)
    s = jax.nn.sigmoid(a @ small["mixer.gate.weight"])  # (T, R)
    ranked = s + small["mixer.gate.e_score_correction_bias"]
    if chosen is None:
        _, chosen = jax.lax.top_k(ranked, top_k)
    taken = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    gap = jnp.max(jnp.where(taken, -jnp.inf, ranked), axis=1) \
        - jnp.min(jnp.where(taken, ranked, jnp.inf), axis=1)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if renorm:
        picked = picked / jnp.sum(picked, axis=1, keepdims=True)
    picked = picked * scale
    # (T, R): the weight a token gives an expert, 0 where not chosen
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(picked)
    lat = a @ small["mixer.fc1_latent_proj.weight"]
    w1, w2 = w["mixer.experts.up_proj"], w["mixer.experts.down_proj"]

    def one(acc, e):
        y = _relu2(lat @ w1[e].astype(_F32)) @ w2[e].astype(_F32)
        return acc + dense[:, offset + e, None] * y, None

    r, _ = jax.lax.scan(one, jnp.zeros_like(lat), jnp.arange(w1.shape[0]))
    shared = _relu2(a @ small["mixer.shared_experts.up_proj.weight"]) \
        @ small["mixer.shared_experts.down_proj.weight"]
    return h + r @ small["mixer.fc2_latent_proj.weight"] + shared, \
        chosen, gap


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _attention(h, w, *, heads, kv_heads, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = h.shape[0]
    a = _rmsnorm(h, w["norm.weight"], eps)
    q = (a @ w["mixer.q_proj.weight"]).reshape(t, heads, -1)
    k = (a @ w["mixer.k_proj.weight"]).reshape(t, kv_heads, -1)
    v = (a @ w["mixer.v_proj.weight"]).reshape(t, kv_heads, -1)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return h + o.reshape(t, -1) @ w["mixer.o_proj.weight"]


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_w, head_w, *, eps):
    return _rmsnorm(h, norm_w, eps) @ head_w.astype(_F32)


def block_kinds(model: dict) -> str:
    """The letters of the blocks that are run: the first
    `num_hidden_layers` of `hybrid_override_pattern`."""
    n = int(model["num_hidden_layers"])
    pattern = model["hybrid_override_pattern"][:n]
    if len(pattern) != n or set(pattern) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not "
                         f"give {n} blocks of M, E or *")
    return pattern


def forward_routed(weights, model: dict, ids, chosen=None):
    """(logits (T, vocab) float32, chosen, gap) of the token ids `ids`
    (T,). `weights` maps the program's parameter names to arrays;
    `model` is the configuration file's mapping of sizes. The expert
    layers compute the share of `experts_held` experts from
    `expert_offset` (all of them where the keys are absent), and the
    vocabulary is the slice the embedding and the head hold. `chosen`,
    given or returned, is one int (T, k) array an expert layer in layer
    order; `gap` (expert layers, T) is 0 or less where a row's choice
    is its k highest scores."""
    eps = float(model["layer_norm_epsilon"])
    ids = jnp.asarray(ids, jnp.int32)
    given = iter(chosen) if chosen is not None else None
    made, gaps = [], []
    with jax.default_matmul_precision("highest"):
        h = weights["model.embed_tokens.weight"][ids].astype(_F32)
        for layer, kind in enumerate(block_kinds(model)):
            pre = f"model.layers.{layer}."
            w = {k[len(pre):]: weights[k] for k in weights
                 if k.startswith(pre)}
            if kind == "M":
                h = _mamba(h, w, heads=model["mamba_num_heads"],
                           head_dim=model["mamba_head_dim"],
                           state=model["ssm_state_size"],
                           groups=model["n_groups"], eps=eps)
            elif kind == "E":
                h, took, gap = _experts(
                    h, w, None if given is None
                    else jnp.asarray(next(given), jnp.int32),
                    top_k=model["num_experts_per_tok"],
                    offset=int(model.get("expert_offset", 0)),
                    scale=float(model["routed_scaling_factor"]),
                    renorm=bool(model["norm_topk_prob"]), eps=eps)
                made.append(np.asarray(took))
                gaps.append(np.asarray(gap, np.float32))
            else:
                h = _attention(h, w, heads=model["num_attention_heads"],
                               kv_heads=model["num_key_value_heads"],
                               eps=eps)
        out = _head(h, weights["model.norm_f.weight"],
                    weights["lm_head.weight"], eps=eps)
    return np.asarray(out, np.float32), made, np.stack(gaps) if gaps \
        else np.zeros((0, len(ids)), np.float32)


def forward_logits(weights, model: dict, ids) -> np.ndarray:
    """(T, vocab) float32 logits of `ids`, every expert layer making
    its own choice."""
    return forward_routed(weights, model, ids)[0]
