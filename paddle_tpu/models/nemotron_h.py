"""Nemotron-H family (`model_type` `nemotron_h`): a hybrid decoder whose
blocks are, by the letter of `hybrid_override_pattern`, a Mamba-2 mixer
(`M`), a latent mixture of experts (`E`) or GQA attention without
position embedding (`*`), each ONE mixer behind a pre-norm and a
residual. The equations are in `benchmark/reference/nemotron_h.py`'s
docstring; this file computes them for the serving engine:

* every mixer works on the engine's packed ragged batch. A Mamba layer
  keeps a FIXED state a sequence (`cache_spec.StateSpec`: the
  convolution's last K-1 inputs and the SSM state, float32), indexed by
  slot; the chunked scan (`chunk_size` rows a chunk: the quadratic form
  inside, a carried state between) restarts at every sequence boundary
  of the packed axis, and a decode step is the one-step recurrence.
* the expert layer is TOLD which experts it holds (`experts_held` from
  `expert_offset`, of `n_routed_experts`): it routes over all of them,
  drops the assignments that fall on experts held elsewhere, sorts the
  rest by expert and runs them through `ops/grouped_matmul.py`. With
  `experts_held == n_routed_experts` it is the whole layer. On one chip
  there is no exchange and nothing stands in for the absent chips.
* attention goes through `ops/ragged_paged_attention.py` like Llama's.

The forward pass without a cache (tests, the logits of a whole
sequence) runs the same mixers over a batch packed on the spot.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.core.tensor import Tensor, apply as _apply
from paddle_tpu.models.cache_spec import (
    KVSpec, RaggedStateView, ReportSpec, StateSpec,
    conv_inputs as _conv_inputs)
from paddle_tpu.models.llama import (RaggedKVCacheView,
                                     ragged_write_attend)
from paddle_tpu.models.routed import (combine_rows, report_counts,
                                      report_spec, route_rows)

__all__ = ["NemotronHConfig", "NemotronHForCausalLM"]

_F32 = jnp.float32

@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    max_position_embeddings: int = 262144
    layer_norm_epsilon: float = 1e-5
    # `*`
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # `M`
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # `E`
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    # the share of an expert-parallel deployment this program holds:
    # experts [expert_offset, expert_offset + experts_held); None = all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        self.pattern = self.hybrid_override_pattern[:n]
        if len(self.pattern) != n or set(self.pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {self.pattern!r} does not give "
                f"{n} blocks of M, E or *")
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if not 0 <= self.expert_offset <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among the "
                f"{self.n_routed_experts} routed experts")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must divide by n_groups")

    @staticmethod
    def tiny(**kw):
        """CPU test size: every kind of block, `ME*EM`."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=5,
            hybrid_override_pattern="ME*EM", max_position_embeddings=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=8, n_routed_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=48,
            moe_latent_size=32, moe_shared_expert_intermediate_size=96,
            dtype="float32")
        base.update(kw)
        return NemotronHConfig(**base)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


# -- Mamba-2 ---------------------------------------------------------------
def _segment_cumsum(a, starts):
    """Inclusive cumulative sum of `a` (T, H) along rows that restarts
    at every row flagged in `starts` (T,)."""
    def combine(left, right):
        lf, lv = left
        rf, rv = right
        return lf | rf, jnp.where(rf[:, None], rv, lv + rv)
    return jax.lax.associative_scan(combine, (starts, a))[1]


def _scan_one_token(x, b, c, dt, a, d_skip, ssm, fresh):
    """Decode shape: row i is slot i's one new token. x (S, H, P);
    b, c (S, H, N); dt, a (S, H) (zero on rows that are not live);
    ssm (S, H, P, N) float32."""
    ssm = jnp.where(fresh[:, None, None, None], 0.0, ssm)
    new = jnp.exp(a)[:, :, None, None] * ssm \
        + (dt[:, :, None] * x)[..., None] * b[:, :, None, :]
    y = jnp.einsum("shpn,shn->shp", new, c) + d_skip[None, :, None] * x
    return y, new


def _scan_chunked(x, b, c, dt, a, d_skip, ssm, seq, idx, fresh, qstart,
                  qlen, chunk, dtype):
    """The chunked scan over a packed batch of pieces. x (T, H, P) and
    b, c (T, H, N) in `dtype` (the matmuls' operand type; float32
    accumulation); dt, a (T, H) float32, zero on rows that are not
    live; ssm (S, H, P, N) float32. Returns y (T, H, P) float32 and the
    new ssm. Within a chunk the quadratic form, masked to rows of one
    sequence; between chunks a carried state that restarts where the
    sequence changes; then, a slot in the batch, its final state (and
    for a continuation the stored state's part of its rows)."""
    t, h, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    g = _segment_cumsum(a, idx == 0)                      # (T, H)
    live = seq >= 0

    def ch(v):
        return v.reshape((nc, chunk) + v.shape[1:])
    xs, bs, cs, dts, gs, seqs = ch(x), ch(b), ch(c), ch(dt), ch(g), ch(seq)

    # inside a chunk: y[q] = sum_{k <= q, same sequence}
    #   exp(g_q - g_k) (c_q . b_k) dt_k x_k
    same = (seqs[:, :, None] == seqs[:, None, :]) & live.reshape(
        nc, chunk)[:, :, None] & jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        same[:, None], gs.transpose(0, 2, 1)[:, :, :, None]
        - gs.transpose(0, 2, 1)[:, :, None, :], -jnp.inf))  # (nc,H,Q,Q)
    cb = jnp.einsum("cqhn,ckhn->chqk", cs, bs,
                    preferred_element_type=_F32)
    w = (cb * decay * dts.transpose(0, 2, 1)[:, :, None, :]).astype(dtype)
    y = jnp.einsum("chqk,ckhp->cqhp", w, xs, preferred_element_type=_F32)

    # what a chunk adds to the state carried out of it: the rows of
    # the sequence that holds its LAST row
    last, g_end = seqs[:, -1], gs[:, -1]                  # (nc,), (nc, H)
    tail_w = jnp.exp(jnp.where(
        ((seqs == last[:, None]) & (last >= 0)[:, None])[..., None],
        g_end[:, None] - gs, -jnp.inf)) * dts              # (nc, Q, H)
    added = jnp.einsum("ckhp,ckhn->chpn",
                       (xs.astype(_F32) * tail_w[..., None]).astype(dtype),
                       bs, preferred_element_type=_F32)
    # between chunks: the state entering chunk i belongs to the
    # sequence of the last row of chunk i-1, and decays on through
    # chunk i only if that sequence still holds chunk i's last row
    seq_in = jnp.concatenate([jnp.full((1,), -1, last.dtype), last[:-1]])
    g_in = jnp.concatenate([jnp.zeros((1, h), _F32), g_end[:-1]])
    keep = jnp.exp(jnp.where(((last == seq_in) & (last >= 0))[:, None],
                             g_end - g_in, -jnp.inf))      # (nc, H)

    def carry_on(state, inp):
        k, add = inp
        return k[:, None, None] * state + add, state

    _, entering = jax.lax.scan(carry_on, jnp.zeros((h, p, n), _F32),
                               (keep, added))              # (nc, H, P, N)
    from_in = jnp.exp(jnp.where(
        ((seqs == seq_in[:, None]) & (seqs >= 0))[..., None],
        gs - g_in[:, None], -jnp.inf))                     # (nc, Q, H)
    y = y + from_in[..., None] * jnp.einsum(
        "cqhn,chpn->cqhp", cs, entering.astype(dtype),
        preferred_element_type=_F32)
    y = y.reshape(t, h, p) + d_skip[None, :, None] * x.astype(_F32)

    # a slot in the batch: the state at its piece's last row; a
    # continuation adds what its stored state gives its rows and its end
    present = qlen > 0
    order = jnp.argsort(~present, stable=True)
    cf = c.astype(_F32)

    def per_slot(i, carry):
        ssm, y = carry
        s = order[i]
        end = qstart[s] + qlen[s] - 1
        ci, g_e = end // chunk, g[end]
        rows = jnp.arange(chunk) + ci * chunk
        cx = jax.lax.dynamic_index_in_dim(xs, ci, keepdims=False)
        cbs = jax.lax.dynamic_index_in_dim(bs, ci, keepdims=False)
        cg = jax.lax.dynamic_index_in_dim(gs, ci, keepdims=False)
        cdt = jax.lax.dynamic_index_in_dim(dts, ci, keepdims=False)
        cseq = jax.lax.dynamic_index_in_dim(seqs, ci, keepdims=False)
        wk = jnp.exp(jnp.where(((cseq == s) & (rows <= end))[:, None],
                               g_e[None] - cg, -jnp.inf)) * cdt  # (Q, H)
        final = jnp.einsum(
            "khp,khn->hpn", (cx.astype(_F32) * wk[..., None]).astype(dtype),
            cbs, preferred_element_type=_F32)
        started_before = qstart[s] < ci * chunk
        final = final + jnp.exp(jnp.where(
            started_before, g_e - g_in[ci], -jnp.inf))[:, None, None] \
            * jax.lax.dynamic_index_in_dim(entering, ci, keepdims=False)

        def continued(args):
            final, y = args
            stored = ssm[s]
            mine = jnp.exp(jnp.where((seq == s)[:, None], g, -jnp.inf))
            y = y + mine[..., None] * jnp.einsum(
                "thn,hpn->thp", cf, stored)
            return final + jnp.exp(g_e)[:, None, None] * stored, y

        final, y = jax.lax.cond(fresh[s], lambda args: args, continued,
                                (final, y))
        return ssm.at[s].set(final), y

    ssm, y = jax.lax.fori_loop(0, jnp.sum(present), per_slot, (ssm, y))
    return y, ssm


def mamba2_values(u, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
                  w_out, conv_state, ssm_state, seq, qstart, qlen, ctx, *,
                  cfg: NemotronHConfig, one_token: bool):
    """One Mamba-2 mixer over a packed batch. u (T, hidden) is the
    normed input. Returns (out (T, hidden), new conv state, new ssm
    state)."""
    t = u.shape[0]
    dtype = u.dtype
    heads, p, n = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    d_in, gn = cfg.mamba_inner, cfg.n_groups * cfg.ssm_state_size
    per = heads // cfg.n_groups
    chunk = cfg.chunk_size
    pad = 0 if one_token else -t % chunk
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad, u.shape[1]), dtype)])
        seq = jnp.concatenate([seq, jnp.full((pad,), -1, seq.dtype)])
    seq_c = jnp.maximum(seq, 0)
    live = (seq >= 0) & (qlen[seq_c] > 0)
    seq = jnp.where(live, seq, -1)
    idx = jnp.arange(t + pad) - qstart[seq_c]              # row in its piece
    fresh = (ctx == qlen) & (qlen > 0)   # a piece that starts its sequence

    zxd = u @ w_in
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + d_in + 2 * gn], \
        zxd[:, 2 * d_in + 2 * gn:]
    prev, new_tail = _conv_inputs(xbc, conv_state, seq, idx, fresh, qstart,
                                  qlen)
    k1 = conv_w.shape[1] - 1
    cw = conv_w.astype(_F32)
    conv = xbc.astype(_F32) * cw[:, k1]
    for k, rows in enumerate(prev, start=1):
        conv = conv + rows.astype(_F32) * cw[:, k1 - k]
    xbc_a = jax.nn.silu(conv + conv_b.astype(_F32)).astype(dtype)
    conv_state = jnp.where((qlen > 0)[:, None, None], new_tail, conv_state)

    x = xbc_a[:, :d_in].reshape(-1, heads, p)
    b = jnp.repeat(xbc_a[:, d_in:d_in + gn].reshape(-1, cfg.n_groups, n),
                   per, axis=1)
    c = jnp.repeat(xbc_a[:, d_in + gn:].reshape(-1, cfg.n_groups, n),
                   per, axis=1)
    dt = jnp.where(live[:, None],
                   jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32)),
                   0.0)
    a = dt * -jnp.exp(a_log.astype(_F32))
    d_skip = d_skip.astype(_F32)
    if one_token:
        y, ssm_state = _scan_one_token(
            x.astype(_F32), b.astype(_F32), c.astype(_F32), dt, a, d_skip,
            ssm_state, fresh)
    else:
        y, ssm_state = _scan_chunked(x, b, c, dt, a, d_skip, ssm_state,
                                     seq, idx, fresh, qstart, qlen, chunk,
                                     dtype)
    y = y.reshape(-1, d_in) * jax.nn.silu(z.astype(_F32))
    y = y.reshape(-1, cfg.n_groups, d_in // cfg.n_groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    y = (y.reshape(-1, d_in) * norm_w.astype(_F32)).astype(dtype)
    return (y @ w_out)[:t], conv_state, ssm_state


class _DtBias(I.Initializer):
    """dt_bias = softplus^-1(dt), dt log-uniform in [min, max]."""

    def __init__(self, lo, hi, floor):
        self.lo, self.hi, self.floor = lo, hi, floor

    def __call__(self, shape, dtype):
        u = I.Uniform(math.log(self.lo), math.log(self.hi))(shape, "float32")
        dt = jnp.maximum(jnp.exp(u), self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _LogUniform(I.Initializer):
    def __init__(self, lo, hi):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        return jnp.log(I.Uniform(self.lo, self.hi)(shape, "float32")
                       ).astype(dtype)


class NemotronHMamba2(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        d_in, heads = cfg.mamba_inner, cfg.mamba_num_heads
        ch = cfg.conv_channels
        self.in_proj = nn.Linear(cfg.hidden_size, d_in + ch + heads,
                                 bias_attr=False)
        self.conv1d = nn.Layer()
        self.conv1d.weight = self.create_parameter(
            (ch, cfg.conv_kernel), default_initializer=I.Uniform(
                -1 / math.sqrt(cfg.conv_kernel),
                1 / math.sqrt(cfg.conv_kernel)))
        self.conv1d.bias = self.create_parameter((ch,), is_bias=True)
        self.dt_bias = self.create_parameter(
            (heads,), default_initializer=_DtBias(
                cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor))
        self.A_log = self.create_parameter(
            (heads,), default_initializer=_LogUniform(1.0, 16.0))
        self.D = self.create_parameter(
            (heads,), default_initializer=I.Constant(1.0))
        self.norm = nn.Layer()
        self.norm.weight = self.create_parameter(
            (d_in,), default_initializer=I.Constant(1.0))
        self.out_proj = nn.Linear(d_in, cfg.hidden_size, bias_attr=False)

    def cache_spec(self) -> StateSpec:
        cfg = self.cfg
        return StateSpec(
            ((cfg.conv_kernel - 1, cfg.conv_channels),
             (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)),
            (cfg.dtype, "float32"))

    def forward(self, x, view: RaggedStateView):
        """x (1, T, hidden), packed as `view` describes."""
        cfg = self.cfg

        def fn(u, w_in, cw, cb, dtb, alog, d, nw, w_out, conv, ssm):
            out, conv, ssm = mamba2_values(
                u[0], w_in, cw, cb, dtb, alog, d, nw, w_out, conv, ssm,
                view.token_seq, view.query_start, view.query_len,
                view.context_lens, cfg=cfg, one_token=view.one_token)
            return out[None], conv, ssm

        conv, ssm = view.arrays
        out, conv, ssm = _apply(
            "mamba2_mixer", fn,
            (x, self.in_proj.weight, self.conv1d.weight, self.conv1d.bias,
             self.dt_bias, self.A_log, self.D, self.norm.weight,
             self.out_proj.weight, Tensor(conv), Tensor(ssm)),
            multi_output=True)
        return out, view.replace((conv._value, ssm._value))


# -- latent experts ----------------------------------------------------------
def _relu2(x):
    return jnp.square(jax.nn.relu(x.astype(_F32)))


def latent_experts_values(a, live, w_r, b_corr, w_down, w1, w2, w_up, w1_s,
                          w2_s, *, cfg: NemotronHConfig):
    """The `E` mixer over packed rows a (T, hidden); `live` (T,) marks
    the rows that are tokens. w1 (held, latent, width) and w2 (held,
    width, latent) are the experts held here. Returns (out (T, hidden),
    counts int32 (6,) in `NemotronHExperts.cache_spec`'s order, chosen
    int32 (T, k): each row's experts)."""
    from paddle_tpu.ops.grouped_matmul import grouped_matmul_values
    dtype = a.dtype
    k, held = cfg.num_experts_per_tok, w1.shape[0]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(a.astype(_F32) @ w_r.astype(_F32))   # (T, R)
    _, chosen = jax.lax.top_k(s + b_corr.astype(_F32), k)
    wts = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.norm_topk_prob:
        wts = wts / jnp.sum(wts, axis=1, keepdims=True)
    wts = wts * cfg.routed_scaling_factor

    # the one routed dispatch (models/routed.py): assignments on experts
    # held elsewhere dropped before the sort, rows padded to the tile
    r = route_rows(chosen, live, held=held, offset=cfg.expert_offset,
                   n_experts=cfg.n_routed_experts)
    lat = a @ w_down                                            # (T, latent)
    up = grouped_matmul_values(lat[r.src], w1, r.padded, r.block_m)
    down = grouped_matmul_values(_relu2(up).astype(dtype), w2, r.padded,
                                 r.block_m)
    routed = combine_rows(down, wts, r).astype(dtype)
    shared = _relu2(a @ w1_s).astype(dtype) @ w2_s
    stats = report_counts(r, live, k)
    return routed @ w_up + shared, stats, chosen.astype(jnp.int32)


class NemotronHExperts(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        h, lat = cfg.hidden_size, cfg.moe_latent_size
        width, held = cfg.moe_intermediate_size, cfg.experts_held
        self.gate = nn.Linear(h, cfg.n_routed_experts, bias_attr=False)
        self.gate.e_score_correction_bias = self.create_parameter(
            (cfg.n_routed_experts,), is_bias=True)
        self.fc1_latent_proj = nn.Linear(h, lat, bias_attr=False)
        self.fc2_latent_proj = nn.Linear(lat, h, bias_attr=False)
        self.experts = nn.Layer()
        std = math.sqrt(2.0 / (lat + width))
        self.experts.up_proj = self.create_parameter(
            (held, lat, width), default_initializer=I.Normal(0.0, std))
        self.experts.down_proj = self.create_parameter(
            (held, width, lat), default_initializer=I.Normal(0.0, std))
        self.shared_experts = nn.Layer()
        self.shared_experts.up_proj = nn.Linear(
            h, cfg.moe_shared_expert_intermediate_size, bias_attr=False)
        self.shared_experts.down_proj = nn.Linear(
            cfg.moe_shared_expert_intermediate_size, h, bias_attr=False)

    def cache_spec(self) -> ReportSpec:
        return report_spec(self.cfg.num_experts_per_tok)

    def forward(self, x, live):
        """x (1, T, hidden); live (T,) bool. Returns (out, (counts,
        chosen)) as `cache_spec` orders and shapes them."""
        cfg = self.cfg

        def fn(a, *w):
            out, stats, chosen = latent_experts_values(a[0], live, *w,
                                                       cfg=cfg)
            return out[None], stats, chosen

        out, stats, chosen = _apply(
            "latent_experts", fn,
            (x, self.gate.weight, self.gate.e_score_correction_bias,
             self.fc1_latent_proj.weight, self.experts.up_proj,
             self.experts.down_proj, self.fc2_latent_proj.weight,
             self.shared_experts.up_proj.weight,
             self.shared_experts.down_proj.weight), multi_output=True)
        return out, (stats._value, chosen._value)


# -- attention ---------------------------------------------------------------
class NemotronHAttention(nn.Layer):
    """GQA, causal, no bias, no position embedding."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.heads, self.kv_heads = cfg.num_attention_heads, \
            cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        h = cfg.hidden_size
        self.q_proj = nn.Linear(h, self.heads * self.head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(h, self.kv_heads * self.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(h, self.kv_heads * self.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(self.heads * self.head_dim, h,
                                bias_attr=False)

    def cache_spec(self) -> KVSpec:
        return KVSpec(self.kv_heads, self.head_dim)

    def forward(self, x, view: Optional[RaggedKVCacheView]):
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.kv_heads, self.head_dim])
        if view is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            return self.o_proj(out.reshape([b, s, -1])), None
        out, view = ragged_write_attend(q, k, v, view)
        return self.o_proj(out.reshape([1, s, -1])), view


# -- the model ---------------------------------------------------------------
_MIXERS = {"M": NemotronHMamba2, "E": NemotronHExperts,
           "*": NemotronHAttention}


class NemotronHBlock(nn.Layer):
    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.mixer = _MIXERS[kind](cfg)


class NemotronHModel(nn.Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [NemotronHBlock(cfg, kind) for kind in cfg.pattern])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.layer_norm_epsilon)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, cfg: NemotronHConfig | None = None):
        super().__init__()
        cfg = cfg or NemotronHConfig()
        self.config = cfg
        self.model = NemotronHModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def cache_spec(self) -> list:
        """What each block keeps (models/cache_spec.py)."""
        return [blk.mixer.cache_spec() for blk in self.model.layers]

    def forward(self, input_ids, past_key_values=None, use_cache=False):
        """Logits of `input_ids`. With `past_key_values` (one view a
        block, as `cache_spec` orders them; `None` for an expert block)
        the ids are ONE packed ragged batch (1, T) and the result is
        `(logits, new)`, `new` holding a block's new view, or an expert
        block's report. Without, (B, S) whole sequences from nothing."""
        x = self.model.embed_tokens(input_ids)
        b, s = x.shape[0], x.shape[1]
        views = past_key_values
        if views is None:
            x = x.reshape([1, b * s, -1])
            live = jnp.ones((b * s,), bool)
        else:
            lead = next(v for v in views if v is not None)
            seq = lead.token_seq
            live = (seq >= 0) & (lead.query_len[jnp.maximum(seq, 0)] > 0)
        new = []
        for i, blk in enumerate(self.model.layers):
            a = blk.norm(x)
            if blk.kind == "E":
                out, got = blk.mixer(a, live)
            elif blk.kind == "M":
                view = views[i] if views is not None else \
                    RaggedStateView.fresh(blk.mixer.cache_spec(), b, s)
                out, got = blk.mixer(a, view)
            elif views is None:
                out, got = blk.mixer(a.reshape([b, s, -1]), None)
                out = out.reshape([1, b * s, -1])
            else:
                out, got = blk.mixer(a, views[i])
            x = x + out
            new.append(got)
        if views is None:
            x = x.reshape([b, s, -1])
        logits = self.lm_head(self.model.norm_f(x))
        if use_cache and views is not None:
            return logits, new
        return logits
