"""Plain reference of Phi-4-mini-flash-reasoning (`model_type`
`phi4flash`, the SambaY decoder-hybrid-decoder of arXiv:2507.06607 with
Mamba, arXiv:2312.00752, and Differential Attention, arXiv:2410.05258):
the full forward pass over one sequence in `jax.numpy`, float32,
`default_matmul_precision("highest")`, the band mask written out, the
recurrence a `lax.scan` over tokens, no kernels, no cache, no batching.
It imports nothing of the program.

`x` is (tokens, hidden). Every layer `i` of 0..L-1:

    x = x + Mixer_i(LN(x));  x = x + MLP(LN'(x))
    LN: LayerNorm with weight and bias, eps layer_norm_eps
    MLP(u) = W2 (silu(G) * U),  [G | U] = W1 u          no bias

then a final LayerNorm and logits = h E^T with E the embedding (tied,
no head bias). NO position encoding anywhere. With `mem` the MEMORY
layer (L / 2; 16 of 32) the mixers are

    i even, i <= mem   Mamba-1; layer mem's scan output m (before the
                       gate) is handed to the gated memory units
    i odd,  i <  mem   differential attention, causal, over the last
                       `sliding_window` positions
    i = mem + 1        differential attention, causal, no window: its
                       keys and values are THE cache of what follows
    i even, i >  mem   GMU(u, m) = Wo (silu(Wi u) * m)
    i odd,  i >  mem+1 differential CROSS attention: its own Wq and
                       Wout over the K and V layer mem + 1 made

Mamba-1 (D = mamba_expand * hidden, N = mamba_d_state, K = mamba_d_conv,
R = mamba_dt_rank), `u` the normed input:

    [xs | z] = u Win                              no bias
    xc_t = silu(sum_k w_conv[:, k] xs_{t-K+1+k} + b_conv)
                                      depthwise, causal, zeros before t=0
    [dtr | B | C] = xc Wx                         widths R, N, N
    dt = softplus(dtr Wdt + bdt);  A = -exp(A_log)            (D x N)
    h_t[d,n] = exp(dt_t[d] A[d,n]) h_{t-1}[d,n] + dt_t[d] B_t[n] xc_t[d]
    m_t[d] = sum_n C_t[n] h_t[d,n] + D[d] xc_t[d]
    out = (m * silu(z)) Wout

Differential attention (the `flashdiff_2` form; d = hidden / heads): the
H query heads are H / 2 PAIRS (q1, q2), the HK key heads HK / 2 pairs
(k1, k2), the HK value heads HK / 2 values of 2d, v = [v1 | v2]; query
pair p reads key-value pair p // (H / HK).

    a_j = softmax(q_j k_j^T / sqrt(d) + mask) v        j = 1, 2; 2d wide
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i)                at layer i
    o_p = (1 - lambda_init) RMSNorm_2d(a_1 - lambda a_2; weight g, eps)
    out = concat_p(o_p) Wout + bout
    [q | k | v] = u Wqkv + bqkv (self);  q = u Wq + bq (cross)

Departures and inferences (the configuration file lists them under
`assumed`): (1) which columns make a pair is a fixed permutation of the
projections' columns and says nothing under seeded weights: here heads
`2p` and `2p + 1` are the pair `p` (interleaved), of queries, keys and
values alike; (2) the four lambda vectors of a layer are the rows of ONE
(4, d) matrix `lambdas` (lq1, lk1, lq2, lk2); (3) `m` is taken after the
`D` skip and before the gate; (4) where L / 2 is odd the memory layer
is the even layer below it, so that any even depth gives a model (the
published 32 is untouched by this); (5) the SSM state is float32.
Weights come in as stored (bf16) under the program's parameter names,
`(in, out)` matrices, and ONE layer's are upcast at a time, so the
model never exists in float32. Attention runs a block of query rows at
a time and the head a block of rows at a time, so a sequence of 14 k
tokens fits a chip beside the served weights. A sequence is padded to a
multiple of `SEQ_BLOCK` rows (causal: the padding changes no row that is
returned), so the layers compile few shapes, and the head runs over the
rows that are READ (`Logits`): of a 2 k prompt's forward pass a check
reads 4 rows, and a row is 0.8 MB on its way to the host.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
Q_BLOCK = 512          # query rows of attention at a time
HEAD_BLOCK = 1024      # rows of the head at a time
SEQ_BLOCK = 2048       # a sequence is padded to a multiple of this


def memory_layer(layers: int) -> int:
    return layers // 2 // 2 * 2


def kind(i: int, layers: int) -> str:
    mem = memory_layer(layers)
    if i % 2 == 0:
        return "mamba" if i <= mem else "gmu"
    return "window" if i < mem else "full" if i == mem + 1 else "cross"


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mlp(x, w, eps):
    u = _layernorm(x, w["post_attention_layernorm.weight"],
                   w["post_attention_layernorm.bias"], eps)
    gu = u @ w["mlp.gate_up_proj.weight"]
    half = gu.shape[-1] // 2
    return x + (jax.nn.silu(gu[:, :half]) * gu[:, half:]) \
        @ w["mlp.down_proj.weight"]


def _normed(x, w, eps):
    return _layernorm(x, w["input_layernorm.weight"],
                      w["input_layernorm.bias"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _mamba_layer(x, w, *, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = x.shape[0]
    u = _normed(x, w, eps)
    xz = u @ w["mixer.in_proj.weight"]
    d = xz.shape[1] // 2
    xs, z = xz[:, :d], xz[:, d:]
    cw = w["mixer.conv1d.weight"]                         # (D, K)
    k = cw.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, d), _F32), xs])
    conv = sum(padded[j:j + t] * cw[:, j] for j in range(k))
    xc = jax.nn.silu(conv + w["mixer.conv1d.bias"])
    dbc = xc @ w["mixer.x_proj.weight"]
    n = w["mixer.A_log"].shape[1]
    r = dbc.shape[1] - 2 * n
    dt = jax.nn.softplus(dbc[:, :r] @ w["mixer.dt_proj.weight"]
                         + w["mixer.dt_proj.bias"])
    a = -jnp.exp(w["mixer.A_log"])                        # (D, N)

    def step(h, row):
        dt_t, b_t, c_t, x_t = row
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((d, n), _F32),
                        (dt, dbc[:, r:r + n], dbc[:, r + n:], xc))
    m = y + w["mixer.D"] * xc
    x = x + (m * jax.nn.silu(z)) @ w["mixer.out_proj.weight"]
    return _mlp(x, w, eps), m


@partial(jax.jit, static_argnames=("eps",))
def _gmu_layer(x, m, w, *, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    u = _normed(x, w, eps)
    x = x + (jax.nn.silu(u @ w["mixer.in_proj.weight"]) * m) \
        @ w["mixer.out_proj.weight"]
    return _mlp(x, w, eps)


def _differential(q, k, v, lam, g, *, lambda_init, window, eps):
    """q (T, H, d); k, v (S, HK, d), S = T; the four softmax maps of
    every pair written out, a block of query rows at a time."""
    t, h, d = q.shape
    hk = k.shape[1]
    q = q.reshape(t, h // 2, 2, d)                # pair p: heads 2p, 2p+1
    k = k.reshape(t, hk // 2, 2, d)
    v = v.reshape(t, hk // 2, 2 * d)              # value c = [v1 | v2]
    serves = (h // 2) // (hk // 2)                # query pairs a kv pair
    k = jnp.repeat(k, serves, axis=1)             # (S, H/2, 2, d)
    v = jnp.repeat(v, serves, axis=1)
    lmb = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + lambda_init
    cols = jnp.arange(t)
    blocks = -(-t // Q_BLOCK)
    q = jnp.pad(q, ((0, blocks * Q_BLOCK - t),) + ((0, 0),) * 3)

    def block(args):
        first, qb = args
        rows = first + jnp.arange(Q_BLOCK)
        ok = cols[None, :] <= rows[:, None]
        if window is not None:
            ok = ok & (cols[None, :] > rows[:, None] - window)
        s = jnp.einsum("qpjd,kpjd->pjqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("pjqk,kpe->qpje", p, v)    # (Q, H/2, 2, 2d)
        o = a[:, :, 0] - lmb * a[:, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + eps) * g
        return ((1.0 - lambda_init) * o).reshape(Q_BLOCK, -1)

    out = jax.lax.map(block, (jnp.arange(blocks) * Q_BLOCK,
                              q.reshape((blocks, Q_BLOCK) + q.shape[1:])))
    return out.reshape(blocks * Q_BLOCK, -1)[:t]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "eps"))
def _attention_layer(x, w, lambda_init, *, heads, kv_heads, window, eps):
    w = {k: v.astype(_F32) for k, v in w.items()}
    t = x.shape[0]
    d = x.shape[1] // heads
    u = _normed(x, w, eps)
    qkv = u @ w["mixer.Wqkv.weight"] + w["mixer.Wqkv.bias"]
    q = qkv[:, :heads * d].reshape(t, heads, d)
    k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(t, kv_heads, d)
    v = qkv[:, (heads + kv_heads) * d:].reshape(t, kv_heads, d)
    o = _differential(q, k, v, w["mixer.lambdas"], w["mixer.subln.weight"],
                      lambda_init=lambda_init, window=window, eps=eps)
    x = x + o @ w["mixer.out_proj.weight"] + w["mixer.out_proj.bias"]
    return _mlp(x, w, eps), (k, v)


@partial(jax.jit, static_argnames=("heads", "eps"))
def _cross_layer(x, k, v, w, lambda_init, *, heads, eps):
    w = {k_: v_.astype(_F32) for k_, v_ in w.items()}
    t = x.shape[0]
    u = _normed(x, w, eps)
    q = (u @ w["mixer.Wq.weight"] + w["mixer.Wq.bias"]).reshape(
        t, heads, -1)
    o = _differential(q, k, v, w["mixer.lambdas"], w["mixer.subln.weight"],
                      lambda_init=lambda_init, window=None, eps=eps)
    x = x + o @ w["mixer.out_proj.weight"] + w["mixer.out_proj.bias"]
    return _mlp(x, w, eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(h, norm_w, norm_b, embed, *, eps):
    return _layernorm(h, norm_w.astype(_F32), norm_b.astype(_F32), eps) \
        @ embed.astype(_F32).T


class Logits:
    """(T, vocab) float32 logits behind the last layer's rows `h` (T
    and its padding): a slice of rows runs the head over the blocks of
    `HEAD_BLOCK` rows that hold them and brings those rows to the host;
    any other use is of the whole array."""

    def __init__(self, h, t: int, head, vocab: int):
        self._h, self._head = h, head
        self.shape, self.dtype = (t, vocab), np.dtype(np.float32)

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, key):
        if not isinstance(key, slice) or key.step not in (None, 1):
            return np.asarray(self)[key]
        lo, hi, _ = key.indices(self.shape[0])
        out = np.empty((max(hi - lo, 0), self.shape[1]), np.float32)
        for b0 in range(lo // HEAD_BLOCK * HEAD_BLOCK, hi, HEAD_BLOCK):
            r0, r1 = max(lo, b0), min(hi, b0 + HEAD_BLOCK)
            block = self._head(self._h[b0:b0 + HEAD_BLOCK])
            out[r0 - lo:r1 - lo] = np.asarray(block[r0 - b0:r1 - b0])
        return out

    def __array__(self, dtype=None, copy=None):
        return self[:].astype(dtype or np.float32, copy=False)


def forward_logits(weights: dict, model: dict, ids) -> Logits:
    """(T, vocab) float32 logits of the token ids `ids` (T,).
    `weights` maps the program's parameter names to arrays; `model` is
    the configuration file's mapping of sizes."""
    layers = int(model["num_hidden_layers"])
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    eps = float(model["layer_norm_eps"])
    t = len(ids)
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, -t % SEQ_BLOCK))
    with jax.default_matmul_precision("highest"):
        h = weights["model.embed_tokens.weight"][ids].astype(_F32)
        m = kv = None
        for i in range(layers):
            pre = f"model.layers.{i}."
            w = {k: weights[k] for k in weights if k.startswith(pre)}
            w = {k[len(pre):]: w[k] for k in w}
            what = kind(i, layers)
            # an argument, not a constant: a layer kind is ONE program
            lam0 = jnp.float32(0.8 - 0.6 * math.exp(-0.3 * i))
            if what == "mamba":
                h, mem = _mamba_layer(h, w, eps=eps)
                if i == memory_layer(layers):
                    m = mem
            elif what == "gmu":
                h = _gmu_layer(h, m, w, eps=eps)
            elif what == "cross":
                h = _cross_layer(h, kv[0], kv[1], w, lam0, heads=heads,
                                 eps=eps)
            else:
                h, made = _attention_layer(
                    h, w, lam0, heads=heads, kv_heads=kv_heads,
                    window=int(model["sliding_window"])
                    if what == "window" else None, eps=eps)
                if what == "full":
                    kv = made

    def head(rows):
        with jax.default_matmul_precision("highest"):
            return _head(rows, weights["model.final_layernorm.weight"],
                         weights["model.final_layernorm.bias"],
                         weights["model.embed_tokens.weight"], eps=eps)

    return Logits(h, t, head, int(model["vocab_size"]))
