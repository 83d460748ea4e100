"""Flash attention — Pallas TPU kernel with blockwise online softmax.

≙ reference flash-attn v2 integration («paddle/phi/kernels/gpu/
flash_attn_kernel.cu» + external lib, SURVEY.md §2.1) re-designed for the
MXU: Bq×Bk logits tiles never materialize in HBM; fwd carries (m, l, acc)
across k-blocks; bwd uses the saved logsumexp + delta trick (two kernels:
dq over q-blocks, dkv over k-blocks). Layout (B, S, H, D) — paddle
convention; internally (B*H, S, D).

GQA is native: K/V stay at (B*HK, S, D) and the BlockSpec index maps fold
the q-head -> kv-head mapping (no jnp.repeat HBM expansion). The causal
mask is END-aligned (q row i attends keys <= i + Sk - Sq), matching the
XLA fallback and the KV-cache/chunked-prefill convention. A q row that
attends zero keys (causal with Sq > Sk) outputs 0 with zero gradient —
the flash-attn convention; the XLA softmax fallback returns a uniform
average there (both are mathematically undefined).

Falls back to interpreter mode off-TPU so the same code is testable on the
8-virtual-CPU-device CI mesh (SURVEY.md §4).
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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# Hypothesis, not measured on this code (docs/kernels.md): per-grid-step
# overhead — not MXU flops — dominates small blocks, so blocks are chosen
# as the largest power-of-two divisor of the sequence length up to
# MAX_BLOCK, with a VMEM guard for large head dims.
MAX_BLOCK = 1024
NEG_INF = -1e30
# Per-row scalars (lse, delta) are stored broadcast across a full 128-lane
# vector register: Mosaic requires the minor block dim to be 128-aligned, so
# a (bh, sq)-shaped residual cannot be blocked (1, block_q).
LANES = 128


def _interpret() -> bool:
    return not on_tpu()


def _aligned(sq, sk, d, block_q, block_k) -> bool:
    return (d <= 256 and sq % block_q == 0 and sk % block_k == 0
            and sq >= block_q and sk >= block_k)


def can_use_flash(q_shape, k_shape, dtype) -> bool:
    """Gate for the default nn.functional path: Pallas on real TPU only
    (interpret mode stays available for direct use + CI kernel tests)."""
    if not on_tpu() or len(q_shape) != 4:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    return _aligned(sq, sk, d, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def _auto_block(n: int, d: int, other: int = MAX_BLOCK) -> int:
    """Largest power-of-two divisor of n in [128, MAX_BLOCK], shrunk while
    the fp32 logits tile + operand blocks would overflow ~12 MB of VMEM.
    Non-128-divisible n gets min(128, n) — the shape the XLA fallback
    handles (callers gate on `_aligned`)."""
    if n % 128:
        return min(128, n)
    b = 128
    while b * 2 <= min(n, MAX_BLOCK) and n % (b * 2) == 0:
        b *= 2
    while b > 128 and b * other * 8 + (b + 2 * other) * d * 4 > 12e6:
        b //= 2
    return b


def _compiler_params(*sem):
    """Mosaic grid semantics ('parallel' dims may be reordered/partitioned;
    the accumulation dim must stay 'arbitrary'). None in interpret mode."""
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(sem))


def _causal_mask(s, qi, ki, block_q, block_k, offset, window=None):
    """End-aligned causal mask on a (Bq, Bk) logits tile: q row (absolute
    position p) sees keys <= p + offset where offset = Sk - Sq. With
    `window` (sliding-window / Mistral-style local attention) the band
    narrows to keys in [p + offset - window + 1, p + offset]."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    live = q_pos + offset >= k_pos
    if window is not None:
        live = live & (k_pos >= q_pos + offset - (window - 1))
    return jnp.where(live, s, NEG_INF)


def _tile_live(qi, ki, block_q, block_k, offset, window):
    """Predicate: does this (q-tile, k-tile) intersect the causal band?
    Used to skip fully-masked tiles in fwd and both bwd kernels."""
    upper = ki * block_k <= qi * block_q + block_q - 1 + offset
    if window is None:
        return upper
    lower = ki * block_k + block_k - 1 >= \
        qi * block_q + offset - (window - 1)
    return upper & lower


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                num_k_blocks, offset, window=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0]                       # (Bq, D)
        k = k_ref[0]                       # (Bk, D)
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset, window)
        m_prev = m_scr[:]                  # (Bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked rows leave m_new at NEG_INF; without the guard
        # exp(NEG_INF - NEG_INF) = 1 turns the mask into a uniform average
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)    # (Bq, 1)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + mxu_dot(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    if causal:
        # skip tiles outside the (end-aligned, possibly windowed) band
        @pl.when(_tile_live(qi, ki, block_q, block_k, offset, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                      (l.shape[0], LANES))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, group,
               window=None):
    """q: (B*H, Sq, D); k,v: (B*HK, Sk, D) -> (o, lse[lane-broadcast])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    offset = sk - sq

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, offset=offset, window=window)

    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0)),
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
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, num_k_blocks,
                   offset, window=None):
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
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset, window)
        # lse/delta arrive lane-broadcast; max over identical lanes restores
        # the (Bq, 1) column without an unsupported minor-dim slice.
        lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
        delta = jnp.max(delta_ref[0], axis=-1, keepdims=True)
        # masked entries must be exactly 0: for a fully-masked row lse is
        # ~NEG_INF and exp(s - lse) would blow up instead of vanishing
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dp = mxu_dot(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (Bq, Bk)
        ds = p * (dp - delta) * scale                  # (Bq, Bk)
        dq_scr[:] += mxu_dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(_tile_live(qi, ki, block_q, block_k, offset, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_k_blocks - 1)
    def _fin():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, num_q_blocks, group, offset,
                    window=None):
    ki = pl.program_id(1)
    t = pl.program_id(2)           # fused (group, q-block) index
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
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset, window)
        lse = jnp.max(lse_ref[0], axis=-1, keepdims=True)
        delta = jnp.max(delta_ref[0], axis=-1, keepdims=True)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] += mxu_dot(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bk, D)
        dp = mxu_dot(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                    # (Bq, Bk)
        dk_scr[:] += mxu_dot(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Bk, D)

    if causal:
        @pl.when(_tile_live(qi, ki, block_q, block_k, offset, window))
        def _():
            compute()
    else:
        compute()

    @pl.when(t == group * num_q_blocks - 1)
    def _fin():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k, group,
               window=None):
    bh, sq, d = q.shape
    bhk = k.shape[0]
    sk = k.shape[1]
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    offset = sk - sq
    delta = jnp.broadcast_to(
        jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1, keepdims=True),
        (bh, sq, LANES))  # (BH, S, LANES) lane-broadcast

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          offset=offset, window=window),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over kv heads; the innermost axis fuses (group, q-block)
    # so one scratch accumulates over every q head sharing this kv head.
    def q_map(b, j, t):
        return (b * group + t // nq, t % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          group=group, offset=offset, window=window),
        grid=(bhk, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
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
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op (custom vjp over (BH, S, D) + (BHK, S, D))
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, group, window):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, group,
                      window)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, group,
                    window):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, group,
                        window)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, group, window, res,
                    do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q,
                            block_k, group, window)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _attention_xla(q, k, v, scale, causal, window=None):
    """XLA-fallback attention for shapes the blocked kernel cannot tile.
    Delegates to the canonical nn.functional reference impl (end-aligned
    causal, GQA aware) so the two paths cannot drift apart. Deferred import:
    nn.functional.attention imports this module at load time. The windowed
    band is materialized as an explicit bool mask here (the fallback has
    no tile structure to exploit)."""
    from ..nn.functional.attention import _sdpa_xla
    if window is not None:
        sq, sk = q.shape[1], k.shape[1]
        offset = sk - sq
        qp = jnp.arange(sq)[:, None]
        kp = jnp.arange(sk)[None, :]
        band = (qp + offset >= kp) & (kp >= qp + offset - (window - 1))
        return _sdpa_xla(q, k, v, mask=band[None, None],
                         causal=False, scale=scale).astype(q.dtype)
    return _sdpa_xla(q, k, v, causal=causal, scale=scale).astype(q.dtype)


def flash_attention_values(q, k, v, causal=False, scale=None,
                           block_q=None, block_k=None, window_size=None):
    """jnp-level flash attention, (B, S, H, D) layout, GQA native.
    `window_size` enables sliding-window (Mistral-style local) attention:
    q at position p attends keys in [p - window_size + 1, p] (end-aligned
    under sq != sk). Requires causal=True. ≙ the reference flash-attn
    window_size=(left, 0) decode convention (SURVEY.md §2.1
    FlashAttention row)."""
    b, sq, h, d = q.shape
    hk = k.shape[2]
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if window_size is not None:
        if not causal:
            raise ValueError("window_size requires causal=True "
                             "(sliding-window attention is causal)")
        window_size = int(window_size)
        if window_size <= 0:
            raise ValueError(f"window_size must be > 0, got {window_size}")
    bq = block_q or _auto_block(sq, d)
    bk = block_k or _auto_block(sk, d)
    if not _aligned(sq, sk, d, bq, bk) or h % hk:
        # blocked kernel can't tile this shape — XLA fallback, identical math
        return _attention_xla(q, k, v, float(scale), bool(causal),
                              window_size)

    def local(q, k, v):
        # this device's (batch, heads) shard: (B, S, H, D) -> (B*H, S, D)
        b, _, h, _ = q.shape
        hk = k.shape[2]
        qb = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
        kb = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, d)
        vb = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, d)
        ob = _flash(qb, kb, vb, float(scale), bool(causal), bq, bk,
                    h // hk, window_size)
        return jnp.swapaxes(ob.reshape(b, h, sq, d), 1, 2)

    # contiguous head shards keep the q-head -> kv-head grouping
    from ..distributed.mesh import shard_kernel
    bshd = ("batch", None, "heads", None)
    return shard_kernel(local, (q, k, v), (bshd, bshd, bshd), bshd)


def flash_attention(q: Tensor, k: Tensor, v: Tensor, causal: bool = False,
                    scale=None, window_size=None) -> Tensor:
    """Eager/tape entry point, (B, S, H, D)."""
    def fn(qq, kk, vv):
        return flash_attention_values(qq, kk, vv, causal=causal,
                                      scale=scale, window_size=window_size)
    return apply("flash_attention", fn, (q, k, v))
