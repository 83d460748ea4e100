"""Varlen / packed flash attention — segment-ids Pallas kernel.

≙ reference `FlashAttnVarlenKernel` («paddle/phi/kernels/gpu/
flash_attn_kernel.cu» varlen variants [U], SURVEY.md §2.1 FlashAttention
row): multiple ragged sequences packed into one (B, S) buffer, attention
confined to same-segment pairs. TPU-native design: segment ids ride the
flash grid as (B, 1, S) int32 arrays blocked (1, 1, block) — the minor
block dim is the 128-multiple block size and the singleton middle axis
keeps the last-two block dims Mosaic-legal (a 2-D (1, block) spec puts
the 1 on the sublane axis, which Mosaic rejects when B % 8 != 0 —
chip-verified r5) — and the mask is segment equality fused into the
online-softmax tiles.

Causality is GLOBAL end-aligned position order, which equals per-segment
causality when q and k share the packing (the packed-pretraining case,
Sq == Sk). Zero-length tails (padding) get segment id -1 by convention:
pad queries attend nothing and output 0 with zero gradient.

Backward follows the house two-kernel scheme (dq over q-blocks, dkv over
k-blocks) with the same segment mask; lse/delta residuals stay
lane-broadcast per flash_attention.py's convention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu
from ..core.tensor import Tensor, apply
from .flash_attention import (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, LANES,
                              NEG_INF)


def _interpret() -> bool:
    return not on_tpu()


def _mask(s, seg_q, seg_k, qi, ki, block_q, block_k, causal, offset):
    """Segment-equality (+ optional global causal) mask on a logits tile.
    seg_q: (Bq,), seg_k: (Bk,)."""
    same = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] >= 0)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        same = same & (q_pos + offset >= k_pos)
    return jnp.where(same, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                num_k_blocks, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask(s, sq_ref[0, 0], sk_ref[0, 0], qi, ki, block_q, block_k,
                  causal, offset)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + mxu_dot(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + offset)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = jnp.where(m_scr[:] > NEG_INF * 0.5,
                             acc_scr[:] / l, 0.0).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                      (l.shape[0], LANES))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   sq_ref, sk_ref, dq_ref, dq_scr, *, scale, causal,
                   block_q, block_k, num_k_blocks, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask(s, sq_ref[0, 0], sk_ref[0, 0], qi, ki, block_q, block_k,
                  causal, offset)
        lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
        delta = jnp.max(delta_ref[0], axis=-1, keepdims=True)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dp = mxu_dot(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += mxu_dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + offset)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _fin():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    sq_ref, sk_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, block_q, block_k, num_q_blocks, group,
                    offset):
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % num_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask(s, sq_ref[0, 0], sk_ref[0, 0], qi, ki, block_q, block_k,
                  causal, offset)
        lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
        delta = jnp.max(delta_ref[0], axis=-1, keepdims=True)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] += mxu_dot(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = mxu_dot(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[:] += mxu_dot(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 + offset >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(t == group * num_q_blocks - 1)
    def _fin():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _varlen_fwd(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k,
                group, batch):
    """q: (B*H, Sq, D); k/v: (B*HK, Sk, D); seg: (B, S) i32."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    offset = sk - sq
    heads = bh // batch

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, offset=offset)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b // heads,
                                                           0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // heads,
                                                           0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, seg_q[:, None, :], seg_k[:, None, :])
    return o, lse


def _varlen_bwd(q, k, v, o, lse, do, seg_q, seg_k, scale, causal,
                block_q, block_k, group, batch):
    bh, sq, d = q.shape
    bhk, sk = k.shape[0], k.shape[1]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    offset = sk - sq
    heads = bh // batch
    delta = jnp.broadcast_to(
        jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1, keepdims=True), (bh, sq, LANES))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=nk, offset=offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b // heads,
                                                           0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // heads,
                                                           0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, seg_q[:, None, :], seg_k[:, None, :])

    # dk/dv: grid over kv heads; innermost axis fuses (group, q-block) so
    # one scratch accumulates over every q head sharing this kv head
    # (same scheme as flash_attention._flash_bwd)
    heads_k = bhk // batch

    def q_map(b, j, t):
        return (b * group + t // nq, t % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          num_q_blocks=nq, group=group, offset=offset),
        grid=(bhk, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, 1, block_q), lambda b, j, t: (b // heads_k,
                                                           0, t % nq)),
            pl.BlockSpec((1, 1, block_k), lambda b, j, t: (b // heads_k,
                                                           0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta, seg_q[:, None, :], seg_k[:, None, :])
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op (custom vjp; segment ids are non-differentiable residuals)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _varlen(q, k, v, seg_q, seg_k, scale, causal, block_q, block_k, group,
            batch):
    o, _ = _varlen_fwd(q, k, v, seg_q, seg_k, scale, causal, block_q,
                       block_k, group, batch)
    return o


def _varlen_fwd_rule(q, k, v, seg_q, seg_k, scale, causal, block_q,
                     block_k, group, batch):
    o, lse = _varlen_fwd(q, k, v, seg_q, seg_k, scale, causal, block_q,
                         block_k, group, batch)
    return o, (q, k, v, o, lse, seg_q, seg_k)


def _varlen_bwd_rule(scale, causal, block_q, block_k, group, batch, res,
                     do):
    q, k, v, o, lse, seg_q, seg_k = res
    dq, dk, dv = _varlen_bwd(q, k, v, o, lse, do, seg_q, seg_k, scale,
                             causal, block_q, block_k, group, batch)
    return dq, dk, dv, None, None


_varlen.defvjp(_varlen_fwd_rule, _varlen_bwd_rule)


def _varlen_xla(q, k, v, seg_q, seg_k, scale, causal):
    """Reference path for unaligned shapes / CI parity: identical
    segment-equality + end-aligned-causal semantics, fully-masked rows
    output 0."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if h != hk:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    same = (seg_q[:, None, :, None] == seg_k[:, None, None, :]) & \
        (seg_q[:, None, :, None] >= 0)
    if causal:
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        same = same & (qpos >= jnp.arange(sk)[None, :])[None, None]
    logits = jnp.where(same, logits, NEG_INF)
    any_valid = jnp.any(same, axis=-1, keepdims=True)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(any_valid, p, 0.0).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def flash_attention_varlen_values(q, k, v, seg_q, seg_k, causal=False,
                                  scale=None, block_q=None, block_k=None):
    """Packed/segment flash attention. q: (B, Sq, H, D); k/v:
    (B, Sk, HK, D); seg_q/seg_k: (B, S) int32 segment ids (-1 = padding).
    Causal = global end-aligned position order (≡ per-segment causal when
    q and k share the packing)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = block_q or min(DEFAULT_BLOCK_Q, sq)
    bk = block_k or min(DEFAULT_BLOCK_K, sk)
    aligned = (d <= 256 and sq % bq == 0 and sk % bk == 0 and h % hk == 0)
    if not aligned:
        return _varlen_xla(q, k, v, seg_q, seg_k, float(scale),
                           bool(causal))
    group = h // hk
    qb = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kb = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, d)
    vb = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, d)
    ob = _varlen(qb, kb, vb, seg_q.astype(jnp.int32),
                 seg_k.astype(jnp.int32), float(scale), bool(causal), bq,
                 bk, group, b)
    return jnp.swapaxes(ob.reshape(b, h, sq, d), 1, 2)


def flash_attention_varlen(q: Tensor, k: Tensor, v: Tensor, seg_q: Tensor,
                           seg_k: Tensor, causal: bool = False,
                           scale=None) -> Tensor:
    """Eager/tape entry point; segment ids are non-differentiable."""
    sq_v = seg_q._value if isinstance(seg_q, Tensor) else jnp.asarray(seg_q)
    sk_v = seg_k._value if isinstance(seg_k, Tensor) else jnp.asarray(seg_k)

    def fn(qq, kk, vv):
        return flash_attention_varlen_values(qq, kk, vv, sq_v, sk_v,
                                             causal=causal, scale=scale)
    return apply("flash_attention_varlen", fn, (q, k, v))


def segments_from_cu_seqlens(cu_seqlens, total_len):
    """cu_seqlens (N+1,) -> (total_len,) segment ids; positions past
    cu_seqlens[-1] get -1 (padding)."""
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    pos = jnp.arange(total_len, dtype=jnp.int32)
    seg = jnp.sum(pos[:, None] >= cu[None, 1:-1], axis=1)
    return jnp.where(pos < cu[-1], seg, -1)

