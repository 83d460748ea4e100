"""Context parallelism: ring flash attention + Ulysses (alltoall) attention.

≙ reference PaddleNLP `ring_flash_attention.py` (RingFlashAttention: ring
P2P of KV blocks with online-softmax merge over the `sep` group) and the
DeepSpeed-Ulysses-style alltoall head-scatter variant — SURVEY.md §2.3
"CP / ring attention" row. The reference builds these from NCCL send/recv;
here they are `shard_map` programs over a mesh axis: the KV rotation is a
`ppermute` (collective_permute riding ICI) and the schedule is a `lax.scan`,
so the whole thing jits, differentiates (scan + ppermute both have
transpose rules), and composes with every other mesh axis.

Layout convention (B, S, H, D) — paddle flash_attn convention; activations
arrive sequence-sharded over the `sep` axis.

Ring v1 computes each (q-chunk, kv-chunk) step with an XLA chunk kernel
that returns (o, lse) for the online merge; fully-masked steps contribute
lse = -inf and drop out of the merge exactly. Causal uses per-step masking
(no zigzag load-balancing yet). Ulysses runs the *local* full-sequence
attention through the Pallas flash kernel when shapes allow.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor, apply
from .mesh import ProcessMesh, get_mesh

NEG_INF = -1e30


def _chunk_attn_with_lse(q, k, v, scale, mask):
    """One (q-chunk, kv-chunk) attention step, GQA-native.

    q: (B, Sq, H, D); k, v: (B, Sk, HK, D) with H a multiple of HK — the
    kv-head group dim is folded into the einsum, so GQA never expands KV
    in memory (the ring rotates the small (B, c, HK, D) buffers).
    mask: (Sq, Sk) bool or None. Returns (o (B,Sq,H,D), lse (B,Sq,H))
    with lse = -inf for fully-masked rows (their o rows are 0).
    """
    b, sq, h, d = q.shape
    hk = k.shape[2]
    g = h // hk
    qg = q.astype(jnp.float32).reshape(b, sq, hk, g, d)
    s = jnp.einsum("bqegd,bked->begqk", qg,
                   k.astype(jnp.float32)) * scale        # (B,HK,G,Sq,Sk)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)               # (B,HK,G,Sq,1)
    masked_row = m <= NEG_INF * 0.5
    p = jnp.where(s > NEG_INF * 0.5,
                  jnp.exp(s - jnp.where(masked_row, 0.0, m)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("begqk,bked->bqegd", p,
                   v.astype(jnp.float32))                # (B,Sq,HK,G,D)
    l_q = jnp.transpose(l[..., 0], (0, 3, 1, 2))         # (B,Sq,HK,G)
    o = o / jnp.maximum(l_q[..., None], 1e-30)
    lse = jnp.where(masked_row, NEG_INF,
                    m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    lse = jnp.transpose(lse, (0, 3, 1, 2))               # (B,Sq,HK,G)
    return o.reshape(b, sq, h, d), lse.reshape(b, sq, h)


def _merge(o_a, lse_a, o_b, lse_b):
    """Associative online-softmax merge of two partial attention results."""
    lse_m = jnp.logaddexp(lse_a, lse_b)                  # (B,Sq,H)
    both_masked = lse_m <= NEG_INF * 0.5
    wa = jnp.where(both_masked, 0.0, jnp.exp(lse_a - lse_m))[..., None]
    wb = jnp.where(both_masked, 0.0, jnp.exp(lse_b - lse_m))[..., None]
    return o_a * wa + o_b * wb, lse_m


def ring_attention_values(q, k, v, mesh: Optional[ProcessMesh] = None,
                          axis: str = "sep", causal: bool = False,
                          scale: Optional[float] = None,
                          balance: Optional[str] = None):
    """jnp-level ring attention. q/k/v: GLOBAL (B, S, H, D), sequence-
    sharded over `axis`; returns the globally-sharded output.

    `balance='zigzag'` (causal only) assigns each rank the block pair
    (i, 2n-1-i) of 2n sequence blocks, so every ring step does ~the same
    work — the contiguous layout leaves rank r busy in only r+1 of n
    steps, and since the ring is tick-synchronous the idle ranks wait
    anyway (wall time = dense). Zigzag halves causal wall time."""
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.dim_names or \
            mesh.get_dim_size(axis) == 1:
        from ..ops.flash_attention import flash_attention_values
        return flash_attention_values(q, k, v, causal=causal, scale=scale)

    n = mesh.get_dim_size(axis)
    b, s_global, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    hk = k.shape[2]
    if h % hk:
        raise ValueError(f"ring attention: q heads {h} not a multiple of "
                         f"kv heads {hk}")
    if balance == "zigzag" and causal and n > 1 and \
            s_global % (2 * n) == 0:
        # (a sequence divisible by n but not 2n falls back to the
        # contiguous schedule rather than truncating blocks)
        return _ring_zigzag(q, k, v, mesh, axis, float(scale), n)
    # GQA stays compressed: the ring rotates (B, c, HK, D) KV chunks and
    # the chunk kernel folds the group dim into its einsum — no
    # jnp.repeat HBM expansion (H/HK x memory and ICI traffic saved)
    c = s_global // n  # local chunk length

    def local_fn(ql, kl, vl):
        # ql/kl/vl: (B, c, H, D) — this device's sequence chunk
        my = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]

        def step(carry, i):
            o_acc, lse_acc, k_cur, v_cur = carry
            src = (my - i) % n  # whose chunk we hold at step i
            if causal:
                # chunk-level relation: src < my full, == local causal,
                # > fully masked
                q_pos = my * c + jnp.arange(c)[:, None]
                k_pos = src * c + jnp.arange(c)[None, :]
                mask = q_pos >= k_pos
            else:
                mask = None
            o_i, lse_i = _chunk_attn_with_lse(ql, k_cur, v_cur, scale, mask)
            o_acc, lse_acc = _merge(o_acc, lse_acc, o_i, lse_i)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (o_acc, lse_acc, k_nxt, v_nxt), None

        o0 = jnp.zeros(ql.shape, jnp.float32)
        lse0 = jnp.full(ql.shape[:3], NEG_INF, jnp.float32)
        (o, lse, _, _), _ = jax.lax.scan(
            step, (o0, lse0, kl, vl), jnp.arange(n))
        return o.astype(ql.dtype)

    spec = P(None, axis, None, None)
    return _shard_map(local_fn, mesh=mesh.jax_mesh,
                      in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)(q, k, v)


def _ring_zigzag(q, k, v, mesh, axis, scale, n):
    """Zigzag-balanced causal ring (≙ the load-balanced RingFlashAttention
    variant; SURVEY.md §5 long-context row, VERDICT r2 weak 4).

    The global sequence splits into 2n blocks; rank r owns blocks
    (r, 2n-1-r). Per ring step the 4 (q-block, k-block) pairs reduce to
    exactly ~2 full-block attentions on EVERY rank (src<my: q_lo/q_hi vs
    k_lo; src==my: the two diagonal causals + one full; src>my: q_hi vs
    both), selected by `lax.switch` so masked pairs cost nothing. The
    permutation happens globally outside the shard_map; output is
    unpermuted back, so callers keep the contiguous layout contract.
    """
    b, s_global, h, d = q.shape
    bs = s_global // (2 * n)
    # global zigzag gather: rank r's rows = blocks r and 2n-1-r
    blocks = np.arange(2 * n)
    order = np.concatenate([np.stack([blocks[:n], blocks[::-1][:n]], 1)
                            .reshape(-1)])
    perm_idx = np.concatenate(
        [np.arange(bb * bs, (bb + 1) * bs) for bb in order])
    inv_idx = np.argsort(perm_idx)
    qz = jnp.take(q, jnp.asarray(perm_idx), axis=1)
    kz = jnp.take(k, jnp.asarray(perm_idx), axis=1)
    vz = jnp.take(v, jnp.asarray(perm_idx), axis=1)

    tri = jnp.tril(jnp.ones((bs, bs), bool))

    def local_fn(ql, kl, vl):
        my = jax.lax.axis_index(axis)
        ring = [(j, (j + 1) % n) for j in range(n)]
        q_lo, q_hi = ql[:, :bs], ql[:, bs:]

        def attn(qq, kk, vv, mask):
            return _chunk_attn_with_lse(qq, kk, vv, scale, mask)

        def empty(qq):
            return (jnp.zeros(qq.shape, jnp.float32),
                    jnp.full(qq.shape[:3], NEG_INF, jnp.float32))

        def step(carry, i):
            o_lo, l_lo, o_hi, l_hi, k_cur, v_cur = carry
            src = (my - i) % n
            k_s, v_s = k_cur[:, :bs], v_cur[:, :bs]
            k_S, v_S = k_cur[:, bs:], v_cur[:, bs:]

            def case_lt():   # src < my: q_lo@k_s full, q_hi@k_s full
                return (attn(q_lo, k_s, v_s, None),
                        attn(q_hi, k_s, v_s, None))

            def case_eq():   # src == my: diagonals causal + q_hi@k_s full
                lo = attn(q_lo, k_s, v_s, tri)
                hi = _merge(*attn(q_hi, k_s, v_s, None),
                            *attn(q_hi, k_S, v_S, tri))
                return (lo, hi)

            def case_gt():   # src > my: q_hi@k_s full, q_hi@k_S full
                return (empty(q_lo),
                        _merge(*attn(q_hi, k_s, v_s, None),
                               *attn(q_hi, k_S, v_S, None)))

            branch = (src >= my).astype(jnp.int32) + \
                (src > my).astype(jnp.int32)
            (lo_i, hi_i) = jax.lax.switch(
                branch, [case_lt, case_eq, case_gt])
            o_lo, l_lo = _merge(o_lo, l_lo, *lo_i)
            o_hi, l_hi = _merge(o_hi, l_hi, *hi_i)
            k_nxt = jax.lax.ppermute(k_cur, axis, ring)
            v_nxt = jax.lax.ppermute(v_cur, axis, ring)
            return (o_lo, l_lo, o_hi, l_hi, k_nxt, v_nxt), None

        z_lo = empty(q_lo)
        z_hi = empty(q_hi)
        (o_lo, _, o_hi, _, _, _), _ = jax.lax.scan(
            step, (z_lo[0], z_lo[1], z_hi[0], z_hi[1], kl, vl),
            jnp.arange(n))
        return jnp.concatenate([o_lo, o_hi], axis=1).astype(ql.dtype)

    spec = P(None, axis, None, None)
    oz = _shard_map(local_fn, mesh=mesh.jax_mesh,
                    in_specs=(spec, spec, spec), out_specs=spec,
                    check_vma=False)(qz, kz, vz)
    return jnp.take(oz, jnp.asarray(inv_idx), axis=1)


def ulysses_attention_values(q, k, v, mesh: Optional[ProcessMesh] = None,
                             axis: str = "sep", causal: bool = False,
                             scale: Optional[float] = None):
    """Ulysses sequence parallelism: alltoall scatters heads / gathers
    sequence, full-length attention runs locally per head shard (through
    the Pallas flash kernel when aligned), alltoall back."""
    mesh = mesh or get_mesh()
    from ..ops.flash_attention import flash_attention_values
    if mesh is None or axis not in mesh.dim_names or \
            mesh.get_dim_size(axis) == 1:
        return flash_attention_values(q, k, v, causal=causal, scale=scale)

    n = mesh.get_dim_size(axis)
    b, s_global, h, d = q.shape
    hk = k.shape[2]
    if h % n or (hk % n and h != hk):
        # heads must split evenly across the axis; expand GQA if the kv
        # heads alone cannot
        if h % n:
            raise ValueError(f"ulysses: num heads {h} not divisible by "
                             f"sep degree {n}")
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
        hk = h

    def local_fn(ql, kl, vl):
        # (B, c, H, D) -> tiled alltoall: scatter heads, gather sequence
        def head_scatter(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)   # (B, S, H/n, D)

        qf, kf, vf = head_scatter(ql), head_scatter(kl), head_scatter(vl)
        of = flash_attention_values(qf, kf, vf, causal=causal, scale=scale)
        # (B, S, H/n, D) -> inverse alltoall -> (B, c, H, D)
        return jax.lax.all_to_all(of, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    return _shard_map(local_fn, mesh=mesh.jax_mesh,
                      in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)(q, k, v)


def ring_flash_attention(q: Tensor, k: Tensor, v: Tensor,
                         mesh: Optional[ProcessMesh] = None,
                         axis: str = "sep", causal: bool = False,
                         scale=None, balance: Optional[str] = None) -> Tensor:
    """Eager/tape entry point. ≙ PaddleNLP RingFlashAttention [U?].
    balance='zigzag' enables the load-balanced causal schedule."""
    def fn(qq, kk, vv):
        return ring_attention_values(qq, kk, vv, mesh, axis, causal, scale,
                                     balance=balance)
    return apply("ring_flash_attention", fn, (q, k, v))


def ulysses_flash_attention(q: Tensor, k: Tensor, v: Tensor,
                            mesh: Optional[ProcessMesh] = None,
                            axis: str = "sep", causal: bool = False,
                            scale=None) -> Tensor:
    def fn(qq, kk, vv):
        return ulysses_attention_values(qq, kk, vv, mesh, axis, causal,
                                        scale)
    return apply("ulysses_flash_attention", fn, (q, k, v))
