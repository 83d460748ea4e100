"""Phi-4-mini-flash-reasoning (`model_type` `phi4flash`): the SambaY
decoder-hybrid-decoder of arXiv:2507.06607. The first half of the
layers alternates Mamba-1 mixers with differential attention over a
sliding window; layer `L/2` is a Mamba-1 mixer whose scan output is the
MEMORY of the second half, layer `L/2 + 1` differential attention over
the whole context, whose keys and values are THE cache of the second
half; behind them gated memory units (a gate on the memory, no state)
alternate with differential CROSS attention (a query of their own over
layer `L/2 + 1`'s keys and values). Every layer is one mixer and one
SwiGLU MLP, each behind a LayerNorm and a residual; there is no
position encoding. The equations are in
`benchmark/reference/phi4flash.py`'s docstring; this file computes them
for the serving engine:

* `cache_spec()` gives the engine three kinds of KV layer: window
  (`KVSpec(.., window)`), full (`KVSpec`) and sharing (`SharedKVSpec`:
  no pool, it reads the full layer's), so the engine keeps a page group
  for the window layers and one for the full layer, beside the Mamba
  layers' state a slot (`StateSpec`).
* differential attention goes through `ops/ragged_paged_attention.py`
  as it is: a stored row of `HK x 64` lanes IS `HK / 2` heads of 128
  `[k1 | k2]` and `[v1 | v2]`, and query `(p, j)` goes in with zeros in
  the half that is not `j`, so the call returns `softmax(q_j k_j^T) v`
  128 wide. Twice the QK products, on zeros; no new kernel.
* the Mamba-1 scan (a decay a channel AND a state, so no chunked matmul
  form) runs over the packed rows in plain XLA: `scan_chunk` rows a
  step of a `lax.scan` with the state carried, restarting where a
  sequence starts and continuing from the slot's stored state
  elsewhere; a decode step is the one-step recurrence.
* `rows_leave_after()`: behind the full layer nothing is kept, so an
  admission's rows that sample nothing stop there (`forward`'s
  `sample_rows`): the paper's linear-time prefill.

The forward pass without a cache (tests, the logits of a whole
sequence) runs the same mixers over a batch packed on the spot.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import initializer as I
from paddle_tpu.core.tensor import Tensor, apply as _apply
from paddle_tpu.models.cache_spec import (KVSpec, RaggedStateView,
                                          SharedKVSpec, StateSpec,
                                          conv_inputs)
from paddle_tpu.models.llama import (RaggedKVCacheView,
                                     ragged_write_attend)

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM"]

_F32 = jnp.float32


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None      # ceil(hidden / 16)
    scan_chunk: int = 16        # packed rows a step of the admission scan
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        if self.mb_per_layer != 2 or self.num_hidden_layers % 2:
            raise ValueError(
                "phi4flash: a Mamba mixer every second layer "
                "(mb_per_layer 2) over an even number of layers")
        if self.num_attention_heads % 4 or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "differential attention pairs its heads: query heads in "
                "fours (two pairs a key-value pair), key-value heads in "
                "twos")
        if not self.tie_word_embeddings:
            raise ValueError("phi4flash ties its head to the embedding")
        self.head_dim = self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw):
        """CPU test size: every kind of layer, twice in the first half."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, sliding_window=8,
                    max_position_embeddings=512, mamba_d_state=8,
                    scan_chunk=4, dtype="float32")
        base.update(kw)
        return Phi4FlashConfig(**base)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the second half reads: layer
        L / 2 (16 of 32), or the even layer below it where L / 2 is
        odd, so that any even depth gives a model. The layer behind it
        is the full attention layer."""
        return self.num_hidden_layers // 2 // 2 * 2

    def kind(self, i: int) -> str:
        """`mamba` | `window` | `full` | `gmu` | `cross` of layer `i`."""
        half = self.memory_layer
        if i % 2 == 0:
            return "mamba" if i <= half else "gmu"
        return "window" if i < half else "full" if i == half + 1 \
            else "cross"

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


# -- Mamba-1 ------------------------------------------------------------------
def _scan_one_token(xc, dt, b, c, a, d_skip, ssm, fresh):
    """Decode shape: row i is slot i's one new token. xc, dt (S, D)
    (dt zero on rows that are not live); b, c (S, N); a (N, D);
    ssm (S, N, D) float32."""
    ssm = jnp.where(fresh[:, None, None], 0.0, ssm)
    new = jnp.exp(dt[:, None, :] * a[None]) * ssm \
        + (dt * xc)[:, None, :] * b[:, :, None]
    return jnp.sum(new * c[:, :, None], axis=1) + d_skip * xc, new


def _scan_rows(xc, dt, b, c, a, d_skip, ssm, seq, idx, fresh, qlen, chunk):
    """The recurrence over a packed batch of pieces, `chunk` rows a step
    with the state carried: xc, dt (T, D) float32 (dt zero on rows that
    are not live, which then leave the state as it was); b, c (T, N);
    a (N, D); ssm (S, N, D) float32. A piece's first row starts from
    zero (a piece that starts its sequence) or from its slot's stored
    state; its last row's state is the slot's new one. The stored
    states are only READ inside the loop and the new ones only WRITTEN,
    to an array of their own, so that neither is copied. Returns
    y (T, D) float32 and the new ssm."""
    t, d = xc.shape
    slots = ssm.shape[0]
    live = seq >= 0
    seq_c = jnp.maximum(seq, 0)
    first = live & (idx == 0)
    zero = fresh[seq_c]
    # where the row's state goes: its slot at a piece's last row, a
    # spare slot behind the real ones everywhere else
    to = jnp.where(live & (idx == qlen[seq_c] - 1), seq_c, slots)

    def ch(v):
        return v.reshape((t // chunk, chunk) + v.shape[1:])

    def step(carry, rows):
        h, ends = carry
        xs, dts, bs, cs, firsts, zeros, seqs, tos = rows
        ys = []
        for r in range(chunk):
            stored = jnp.where(zeros[r], 0.0, ssm[seqs[r]])
            h = jnp.where(firsts[r], stored, h)
            h = jnp.exp(dts[r][None, :] * a) * h \
                + (dts[r] * xs[r])[None, :] * bs[r][:, None]
            ys.append(jnp.sum(h * cs[r][:, None], axis=0))
            ends = ends.at[tos[r]].set(h)
        return (h, ends), jnp.stack(ys)

    (_, ends), y = jax.lax.scan(
        step, (jnp.zeros(ssm.shape[1:], _F32),
               jnp.zeros((slots + 1,) + ssm.shape[1:], _F32)),
        (ch(xc), ch(dt), ch(b), ch(c), ch(first), ch(zero), ch(seq_c),
         ch(to)))
    ssm = jnp.where((qlen > 0)[:, None, None], ends[:slots], ssm)
    return y.reshape(t, d) + d_skip * xc, ssm


@functools.partial(jax.jit, static_argnames=("scan_chunk", "one_token"))
def mamba1_values(u, w_in, conv_w, conv_b, w_x, w_dt, b_dt, a_log, d_skip,
                  w_out, conv_state, ssm_state, seq, qstart, qlen, ctx, *,
                  scan_chunk: int, one_token: bool):
    """One Mamba-1 mixer over a packed batch. u (T, hidden) is the
    normed input. Returns (out (T, hidden), the scan's output m
    (T, d_inner) before the gate, new conv state, new ssm state).
    Under `jax.jit`: a program's mixers are traced and lowered once
    between them, not once a layer (the unrolled rows of a scan step
    are a second of Python a layer at published widths)."""
    t = u.shape[0]
    dtype = u.dtype
    d_in, n, rank = a_log.shape[0], a_log.shape[1], w_dt.shape[0]
    pad = 0 if one_token else -t % scan_chunk
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad, u.shape[1]), dtype)])
        seq = jnp.concatenate([seq, jnp.full((pad,), -1, seq.dtype)])
    seq_c = jnp.maximum(seq, 0)
    live = (seq >= 0) & (qlen[seq_c] > 0)
    seq = jnp.where(live, seq, -1)
    idx = jnp.arange(t + pad) - qstart[seq_c]              # row in its piece
    fresh = (ctx == qlen) & (qlen > 0)   # a piece that starts its sequence

    xz = u @ w_in
    xs, z = xz[:, :d_in], xz[:, d_in:]
    prev, new_tail = conv_inputs(xs, conv_state, seq, idx, fresh, qstart,
                                 qlen)
    k1 = conv_w.shape[1] - 1
    cw = conv_w.astype(_F32)
    conv = xs.astype(_F32) * cw[:, k1]
    for k, rows in enumerate(prev, start=1):
        conv = conv + rows.astype(_F32) * cw[:, k1 - k]
    xc = jax.nn.silu(conv + conv_b.astype(_F32)).astype(dtype)
    conv_state = jnp.where((qlen > 0)[:, None, None], new_tail, conv_state)

    dbc = xc @ w_x
    b, c = dbc[:, rank:rank + n].astype(_F32), dbc[:, rank + n:].astype(_F32)
    dt = jnp.where(
        live[:, None],
        jax.nn.softplus((dbc[:, :rank] @ w_dt).astype(_F32)
                        + b_dt.astype(_F32)), 0.0)
    a = -jnp.exp(a_log.astype(_F32)).T                     # (N, D)
    xf, d_skip = xc.astype(_F32), d_skip.astype(_F32)
    if one_token:
        y, ssm_state = _scan_one_token(xf, dt, b, c, a, d_skip, ssm_state,
                                       fresh)
    else:
        y, ssm_state = _scan_rows(xf, dt, b, c, a, d_skip, ssm_state, seq,
                                  idx, fresh, qlen, scan_chunk)
    m = y.astype(dtype)
    out = (y * jax.nn.silu(z.astype(_F32))).astype(dtype) @ w_out
    return out[:t], m[:t], conv_state, ssm_state


class Phi4FlashMamba(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        h, d_in, n = cfg.hidden_size, cfg.mamba_inner, cfg.mamba_d_state
        k, rank = cfg.mamba_d_conv, cfg.mamba_dt_rank
        self.in_proj = nn.Linear(h, 2 * d_in, bias_attr=False)
        self.conv1d = nn.Layer()
        self.conv1d.weight = self.create_parameter(
            (d_in, k), default_initializer=I.Uniform(
                -1 / math.sqrt(k), 1 / math.sqrt(k)))
        self.conv1d.bias = self.create_parameter((d_in,), is_bias=True)
        self.x_proj = nn.Linear(d_in, rank + 2 * n, bias_attr=False)
        self.dt_proj = nn.Linear(rank, d_in)
        self.A_log = self.create_parameter(
            (d_in, n), default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            (d_in,), default_initializer=I.Constant(1.0))
        self.out_proj = nn.Linear(d_in, h, bias_attr=False)

    def cache_spec(self) -> StateSpec:
        cfg = self.cfg
        return StateSpec(
            ((cfg.mamba_d_conv - 1, cfg.mamba_inner),
             (cfg.mamba_d_state, cfg.mamba_inner)),
            (cfg.dtype, "float32"))

    def forward(self, x, view: RaggedStateView):
        """x (1, T, hidden), packed as `view` describes. Returns (out,
        the memory m (T, d_inner), the new view)."""
        cfg = self.cfg

        def fn(u, *w):
            out, m, conv, ssm = mamba1_values(
                u[0], *w, view.token_seq, view.query_start, view.query_len,
                view.context_lens, scan_chunk=cfg.scan_chunk,
                one_token=view.one_token)
            return out[None], m, conv, ssm

        conv, ssm = view.arrays
        out, m, conv, ssm = _apply(
            "mamba1_mixer", fn,
            (x, self.in_proj.weight, self.conv1d.weight, self.conv1d.bias,
             self.x_proj.weight, self.dt_proj.weight, self.dt_proj.bias,
             self.A_log, self.D, self.out_proj.weight, Tensor(conv),
             Tensor(ssm)), multi_output=True)
        return out, m._value, view.replace((conv._value, ssm._value))


# -- gated memory unit -------------------------------------------------------
class Phi4FlashGMU(nn.Layer):
    """`Wo (silu(Wi u) * m)`: the layer's token gates the memory layer's
    scan output of the same token. Keeps nothing."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.in_proj = nn.Linear(cfg.hidden_size, cfg.mamba_inner,
                                 bias_attr=False)
        self.out_proj = nn.Linear(cfg.mamba_inner, cfg.hidden_size,
                                  bias_attr=False)

    def cache_spec(self):
        return None

    def forward(self, x, m):
        def fn(u, w_i, w_o):
            g = jax.nn.silu((u[0] @ w_i).astype(_F32))
            return ((g * m.astype(_F32)).astype(u.dtype) @ w_o)[None]
        return _apply("gated_memory_unit", fn,
                      (x, self.in_proj.weight, self.out_proj.weight))


# -- differential attention -----------------------------------------------
def pair_queries(q):
    """(.., H, d) query heads, head `2p + j - 1` the `q_j` of pair `p`,
    to (.., H, 2d): `q_1` in the first half and zeros in the second,
    `q_2` the other way round, so that against a key `[k1 | k2]` of 2d
    lanes head `(p, j)` scores `q_j . k_j`."""
    lead, (h, d) = q.shape[:-2], q.shape[-2:]
    q = q.reshape(lead + (h // 2, 2, d))
    z = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                      jnp.concatenate([z, q[..., 1, :]], -1)],
                     axis=-2).reshape(lead + (h, 2 * d))


def differential_combine(a, lam, norm_w, lambda_init, eps):
    """`(1 - lambda_init) RMSNorm(a_1 - lambda a_2)` a pair: a
    (.., H, 2d) the two softmax maps' outputs of every pair (head
    `2p + j - 1` is `a_j`), lam the (4, d) vectors `lq1, lk1, lq2, lk2`.
    Returns (.., H / 2 * 2d)."""
    lead, (h, w) = a.shape[:-2], a.shape[-2:]
    lam = lam.astype(_F32)
    lmb = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + lambda_init
    a = a.astype(_F32).reshape(lead + (h // 2, 2, w))
    o = a[..., 0, :] - lmb * a[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = o * norm_w.astype(_F32) * (1.0 - lambda_init)
    return o.reshape(lead + (h // 2 * w,))


def _dense_attend(q, k, v, scale, window):
    """Whole sequences from nothing: q (B, S, H, D), k, v (B, S, HK, D),
    causal and band-limited by `window`."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    qh = q.reshape(b, s, hk, h // hk, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh, k,
                        preferred_element_type=_F32) * scale
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ok = cols <= rows
    if window is not None:
        ok = ok & (cols > rows - window)
    p = jax.nn.softmax(jnp.where(ok, logits, -1e30), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype),
                      v).reshape(b, s, h, d)


class Phi4FlashAttention(nn.Layer):
    """Differential attention, causal: over its own keys and values
    (`window` positions of them, or all), or with `cross` a query of its
    own over the keys and values another layer stored."""

    def __init__(self, cfg: Phi4FlashConfig, layer: int, window, cross):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        self.heads, self.kv_heads = cfg.num_attention_heads, \
            cfg.num_key_value_heads
        self.head_dim, self.window, self.cross = d, window, cross
        self.lambda_init = cfg.lambda_init(layer)
        self.eps = cfg.layer_norm_eps
        if cross is None:
            self.Wqkv = nn.Linear(h, (self.heads + 2 * self.kv_heads) * d)
        else:
            self.Wq = nn.Linear(h, self.heads * d)
        self.out_proj = nn.Linear(self.heads * d, h)
        # lq1, lk1, lq2, lk2 as ONE matrix
        self.lambdas = self.create_parameter(
            (4, d), default_initializer=I.Normal(0.0, 0.1))
        self.subln = nn.Layer()
        self.subln.weight = self.create_parameter(
            (2 * d,), default_initializer=I.Constant(1.0))

    def cache_spec(self):
        if self.cross is not None:
            return SharedKVSpec(self.cross)
        return KVSpec(self.kv_heads, self.head_dim, self.window)

    def forward(self, x, view: Optional[RaggedKVCacheView]):
        """x (1, T, hidden) with a view (its own, or for a cross layer
        the one its source layer returned), else (B, S, hidden) whole
        sequences with, for a cross layer, `view` the source's (k, v).
        Returns (out, the new view | the (k, v) it made | None)."""
        b, s = x.shape[0], x.shape[1]
        h, hk, d = self.heads, self.kv_heads, self.head_dim
        scale = d ** -0.5
        if self.cross is None:
            qkv = self.Wqkv(x)
            q = qkv[:, :, :h * d]
            # a stored row of HK x d lanes is HK / 2 heads of 2d
            k = qkv[:, :, h * d:(h + hk) * d].reshape([b, s, hk // 2, 2 * d])
            v = qkv[:, :, (h + hk) * d:].reshape([b, s, hk // 2, 2 * d])
        else:
            q, k, v = self.Wq(x), None, None
        q = _apply("pair_queries", pair_queries,
                   (q.reshape([b, s, h, d]),))
        if isinstance(view, RaggedKVCacheView):
            out, new = ragged_write_attend(q, k, v, view,
                                           window=self.window, scale=scale)
        else:
            if self.cross is not None:
                k, v = view
            out = _apply("dense_attention",
                         lambda q_, k_, v_: _dense_attend(
                             q_, k_, v_, scale, self.window), (q, k, v))
            new = (k, v)
        out = _apply(
            "differential_combine",
            lambda a, lam, w: differential_combine(
                a, lam, w, self.lambda_init, self.eps).astype(a.dtype),
            (out, self.lambdas, self.subln.weight))
        return self.out_proj(out), new


# -- the model ---------------------------------------------------------------
class Phi4FlashMLP(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.width = cfg.intermediate_size
        self.gate_up_proj = nn.Linear(cfg.hidden_size, 2 * self.width,
                                      bias_attr=False)
        self.down_proj = nn.Linear(self.width, cfg.hidden_size,
                                   bias_attr=False)

    def forward(self, x):
        gu = self.gate_up_proj(x)
        act = _apply("swiglu", lambda t: (
            jax.nn.silu(t[..., :self.width].astype(_F32))
            * t[..., self.width:].astype(_F32)).astype(t.dtype), (gu,))
        return self.down_proj(act)


class Phi4FlashLayer(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig, i: int):
        super().__init__()
        self.kind = kind = cfg.kind(i)
        self.input_layernorm = nn.LayerNorm(cfg.hidden_size,
                                            cfg.layer_norm_eps)
        if kind == "mamba":
            self.mixer = Phi4FlashMamba(cfg)
        elif kind == "gmu":
            self.mixer = Phi4FlashGMU(cfg)
        else:
            self.mixer = Phi4FlashAttention(
                cfg, i, cfg.sliding_window if kind == "window" else None,
                cfg.memory_layer + 1 if kind == "cross" else None)
        self.post_attention_layernorm = nn.LayerNorm(cfg.hidden_size,
                                                     cfg.layer_norm_eps)
        self.mlp = Phi4FlashMLP(cfg)


class Phi4FlashModel(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Phi4FlashLayer(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.final_layernorm = nn.LayerNorm(cfg.hidden_size,
                                            cfg.layer_norm_eps)


class Phi4FlashForCausalLM(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig | None = None):
        super().__init__()
        cfg = cfg or Phi4FlashConfig()
        self.config = cfg
        self.model = Phi4FlashModel(cfg)

    def cache_spec(self) -> list:
        """What each layer keeps (models/cache_spec.py)."""
        return [layer.mixer.cache_spec() for layer in self.model.layers]

    def rows_leave_after(self) -> int:
        """The last layer that keeps anything: the full attention layer
        (models/cache_spec.py)."""
        return self.config.memory_layer + 1

    def forward(self, input_ids, past_key_values=None, use_cache=False,
                sample_rows=None):
        """Logits of `input_ids`. With `past_key_values` (one entry a
        layer, as `cache_spec` orders them: a view, or None) the ids are
        ONE packed ragged batch (1, T) and the result is `(logits,
        new)`, `new` holding a layer's new view or None. With
        `sample_rows` (slots,) too, only those packed rows go on past
        `rows_leave_after()` and the logits are theirs, (1, slots,
        vocab). Without views, (B, S) whole sequences from nothing."""
        cfg = self.config
        x = self.model.embed_tokens(input_ids)
        b, s = x.shape[0], x.shape[1]
        views = past_key_values
        packed = views is not None
        if not packed:
            x = x.reshape([1, b * s, -1])
        new, m, shared = [], None, None
        leave = packed and sample_rows is not None
        for i, layer in enumerate(self.model.layers):
            if leave and i == self.rows_leave_after() + 1:
                x, m, shared = self._sampled_rows(x, m, shared, sample_rows)
                leave = False
            a = layer.input_layernorm(x)
            got = None
            if layer.kind == "mamba":
                view = views[i] if packed else RaggedStateView.fresh(
                    layer.mixer.cache_spec(), b, s)
                out, mem, got = layer.mixer(a, view)
                if i == cfg.memory_layer:
                    m = mem
            elif layer.kind == "gmu":
                out = layer.mixer(a, m)
            else:
                src = shared if layer.kind == "cross" else \
                    views[i] if packed else None
                if not packed:
                    a = a.reshape([b, s, -1])
                out, got = layer.mixer(a, src)
                if not packed:
                    out = out.reshape([1, b * s, -1])
                if layer.kind == "full":
                    shared = got
                elif layer.kind == "cross":
                    got = None
            x = x + out
            x = x + layer.mlp(layer.post_attention_layernorm(x))
            new.append(got if packed else None)
        if leave:           # the full layer is the last: the head alone
            x, m, shared = self._sampled_rows(x, m, shared, sample_rows)
        if not packed:
            x = x.reshape([b, s, -1])
        logits = paddle.matmul(self.model.final_layernorm(x),
                               self.model.embed_tokens.weight,
                               transpose_y=True)
        if use_cache and packed:
            return logits, new
        return logits

    @staticmethod
    def _sampled_rows(x, m, shared: RaggedKVCacheView, sample_rows):
        """What goes on past the last keeping layer: the sampled rows of
        the residual stream and of the memory, one a slot, and the full
        layer's view with one query a sampling slot at its context's
        end (the decode shape)."""
        t = x.shape[1]
        rows = jnp.clip(sample_rows, 0, t - 1)
        slots = jnp.arange(rows.shape[0], dtype=jnp.int32)
        x = _apply("sampled_rows", lambda v: v[:, rows], (x,))
        view = RaggedKVCacheView(
            shared.k_pages, shared.v_pages, shared.block_tables, slots,
            shared.context_lens - 1, slots,
            (sample_rows < t).astype(jnp.int32), shared.context_lens,
            block_q=1, pages_bound=shared.pages_bound, tp=shared.tp)
        return x, m[rows], view
