"""Ragged paged attention — ONE fused kernel for mixed prefill+decode
over the page table.

≙ the ragged paged-attention design of the TPU serving study (PAPERS.md,
arxiv 2604.15464) and the reference engine's unified attention dispatch:
a batch that mixes decode steps (q = 1), full prefills, chunked-prefill
continuations, and prefix-cache suffix prefills runs through ONE Pallas
grid — no per-request padding to a bucket, no per-shape program family.

Layout. Queries of all sequences are PACKED along one token axis:
``q`` is (T, H, D) and sequence ``s`` owns rows
``[query_start[s], query_start[s] + query_len[s])``.  Row ``j`` of a
sequence carries the GLOBAL position ``context_len[s] - query_len[s] +
j`` — so ``query_len == context_len`` is a full prefill, ``query_len ==
1`` a decode step, and anything in between a chunk continuation or a
prefix-cache suffix prefill whose queries attend causally at
``position_offset = context_len - query_len`` into prefix-shared pages.
Rows owned by no sequence are padding: their output is zero and their
KV (see `ragged_scatter_values`) routes to the trash page.

Kernel. The grid is (q-blocks, kv-heads, pages-per-seq); the block
tables and the per-sequence descriptors are SCALAR-PREFETCHED so the
page index feeds the BlockSpec index_map and Mosaic double-buffers page
fetches (the `paged_attention.py` pattern, generalized from q = 1 to
ragged q).  Each q block belongs to exactly one sequence (the packer
aligns ``query_start`` to ``block_q``; decode batches use block_q = 1).
Dead pages — beyond a sequence's causal frontier, wholly below its
sliding window, or under a padding q block — skip both the FLOPs *and*
the DMA: their index_map routes to the RESIDENT trash page 0, and since
consecutive grid steps then fetch the same block, the Pallas pipeline
elides the copy entirely.  This fixes the "DMA still runs" cost
documented in `paged_attention.py`.

The XLA path (`_ragged_xla`) is the CI oracle: a page gather BOUNDED to
the block-table prefix actually referenced (static trim when the
context lengths are concrete) followed by the shared masked-attention
core — `paged_attention._paged_xla` is its q = 1 special case, so the
two fallbacks are one copy of the math.  Serving has no backward; no
VJP is defined.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu
from ..core.tensor import Tensor, apply

NEG_INF = -1e30
LANES = 128
DEFAULT_BLOCK_Q = 8
TRASH_PAGE = 0
KV_QMAX = 127.0     # int8 absmax lattice of a quantized KV page row


def _interpret() -> bool:
    return not on_tpu()


# ---------------------------------------------------------------------------
# packing helpers (host-side; engine + tests build batches with these)
# ---------------------------------------------------------------------------
def pack_ragged_starts(query_lens, block_q=DEFAULT_BLOCK_Q):
    """Aligned packed layout for a ragged batch: each sequence's query
    segment starts on a ``block_q`` boundary so every q block belongs to
    exactly one sequence. Returns (query_start (N,) int32, total_rows)
    where total_rows is the aligned length of the packed token axis
    (before any further bucket padding)."""
    starts, cur = [], 0
    for n in query_lens:
        starts.append(cur)
        cur += -(-int(n) // block_q) * block_q
    return np.asarray(starts, np.int32), cur


def pack_ragged_batch(pieces, n_seqs, block_q=DEFAULT_BLOCK_Q,
                      pad_to=None):
    """Pack a batch of admission/verify pieces into the descriptor +
    per-token arrays one ragged dispatch consumes. Each piece is a dict
    ``{"seq": owning sequence index, "tokens": [ids...], "offset":
    global position of the first token, "sample": bool}``; `n_seqs`
    sizes the per-sequence descriptor arrays (the engine passes its
    slot count). Segment starts are aligned to `block_q` and the token
    axis is padded to a multiple of ``pad_to`` (default `block_q`) so
    the padded length — the only program-cache key on the ragged path —
    stays coarse. Returns a dict of int32 numpy arrays: per-token
    ``ids`` / ``token_seq`` (-1 on padding rows, which trash-route) /
    ``positions``; per-sequence ``query_start`` / ``query_len`` /
    ``context_len`` / ``sample_rows`` (an out-of-range sentinel row for
    sequences that do not sample — callers clamp in-program and never
    read those back); plus ``t_pad`` and ``tokens``, the block_q-ALIGNED
    row total before the final pad (a 3-token piece at block_q=8
    contributes 8 — the historical meaning of the span ``tokens``
    attrs fed from it, NOT the raw token count).

    This is the ONE packer behind the engine's admission dispatch, the
    speculative-verify dispatch (each slot a ``query_len = k+1``
    multi-token row), and the draft-cache backfill prefills — the
    descriptor format cannot drift between them."""
    grid = int(pad_to) if pad_to else int(block_q)
    cur = 0
    row0 = []
    for p in pieces:
        row0.append(cur)
        cur += -(-len(p["tokens"]) // block_q) * block_q
    t_pad = -(-max(cur, 1) // grid) * grid
    ids = np.zeros(t_pad, np.int32)
    token_seq = np.full(t_pad, -1, np.int32)
    positions = np.zeros(t_pad, np.int32)
    query_start = np.zeros(n_seqs, np.int32)
    query_len = np.zeros(n_seqs, np.int32)
    context_len = np.zeros(n_seqs, np.int32)
    sample_rows = np.full(n_seqs, t_pad, np.int32)
    for p, r0 in zip(pieces, row0):
        s, n = int(p["seq"]), len(p["tokens"])
        ids[r0:r0 + n] = p["tokens"]
        token_seq[r0:r0 + n] = s
        positions[r0:r0 + n] = p["offset"] + np.arange(n)
        query_start[s] = r0
        query_len[s] = n
        context_len[s] = p["offset"] + n
        if p.get("sample"):
            sample_rows[s] = r0 + n - 1
    return {"ids": ids, "token_seq": token_seq, "positions": positions,
            "query_start": query_start, "query_len": query_len,
            "context_len": context_len, "sample_rows": sample_rows,
            "t_pad": t_pad, "tokens": cur}


def token_arrays(query_start, query_len, context_len, total_rows):
    """Per-token (token_seq, positions) int32 arrays for a packed ragged
    batch: ``token_seq[t]`` is the owning sequence (-1 for padding rows)
    and ``positions[t]`` the token's global position in that sequence —
    what rope rotation and the page scatter consume."""
    seq = np.full(int(total_rows), -1, np.int32)
    pos = np.zeros(int(total_rows), np.int32)
    for s, (st, ql, cl) in enumerate(zip(query_start, query_len,
                                         context_len)):
        st, ql, cl = int(st), int(ql), int(cl)
        seq[st:st + ql] = s
        pos[st:st + ql] = np.arange(cl - ql, cl, dtype=np.int32)
    return seq, pos


# ---------------------------------------------------------------------------
# shared masked-attention core (also backs paged_attention._paged_xla)
# ---------------------------------------------------------------------------
def page_gather_bound(block_tables, context_lens, pages_bound,
                      page_size) -> int:
    """STATIC column bound of a block-table gather: ``pages_bound``
    when the (traced) caller supplied one, else the concrete-context
    trim ``ceil(max(ctx) / page_size)``, else the full table. Shared
    by the page gather and the quantized-page SCALE gather so the two
    can never trim differently."""
    pps = block_tables.shape[1]
    if pages_bound is not None:
        return max(1, min(int(pages_bound), pps))
    if context_lens is not None:
        try:
            # concrete (host/eager) context lengths: trim statically;
            # traced ones raise TracerArrayConversionError and keep the
            # full table (the compiled-engine case, where the bound is
            # the slot reservation anyway)
            ctx_np = np.asarray(context_lens)
        except Exception:
            ctx_np = None
        if ctx_np is not None and ctx_np.size:
            max_ctx = int(np.max(ctx_np))
            return max(1, min(-(-max_ctx // page_size), pps))
    return pps


def gather_page_scales(scale_pool, block_tables, bound):
    """Gather a per-page scale pool (P, page_size) along the first
    `bound` block-table columns to per-sequence dense rows (N, S) —
    the XLA oracle's dequant companion of `gather_pages` (same bound,
    same row order)."""
    bt = block_tables[:, :bound]
    sg = scale_pool[bt]                       # (N, bound, page_size)
    return sg.reshape(bt.shape[0], bound * scale_pool.shape[1])


def gather_pages(k_pages, v_pages, block_tables, context_lens=None,
                 pages_bound=None):
    """Gather block-table pages to per-sequence contiguous caches
    (N, S, HK, D), bounding the gather to the block-table prefix
    actually referenced: when ``context_lens`` is CONCRETE (host-side
    numpy / eager call) the trim is static — ``S = ceil(max(ctx) /
    page_size) * page_size`` — instead of materializing the full
    ``pps * page_size`` worst case.  ``pages_bound`` overrides the trim
    explicitly (traced callers that know a static bound)."""
    page_size = k_pages.shape[2]
    pps = block_tables.shape[1]
    bound = page_gather_bound(block_tables, context_lens, pages_bound,
                              page_size)
    bt = block_tables[:, :bound]
    n = bt.shape[0]
    kg = jnp.transpose(k_pages[:, bt], (1, 2, 3, 0, 4))
    vg = jnp.transpose(v_pages[:, bt], (1, 2, 3, 0, 4))
    s_max = bound * page_size
    hk, d = k_pages.shape[0], k_pages.shape[3]
    return (kg.reshape(n, s_max, hk, d), vg.reshape(n, s_max, hk, d))


def masked_page_attention(q, kc, vc, q_positions, context_lens, scale,
                          window=None):
    """The ONE masked-attention core behind every paged XLA fallback.

    q: (T, HK, G, D) packed query tokens; kc/vc: (T, S, HK, D) — the
    gathered cache rows of each token's OWN sequence (callers gather
    per sequence and index by token); q_positions: (T,) global position
    of each query token; context_lens: (T,) context length of the
    token's sequence. Token t attends keys ``k <= q_positions[t]``
    (and ``> q_positions[t] - window``), keys past the context are
    masked, and tokens with no valid key output zero."""
    s_max = kc.shape[1]
    logits = jnp.einsum("tkgd,tskd->tkgs", q, kc,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s_max)
    valid = (kpos[None, :] <= q_positions[:, None]) \
        & (kpos[None, :] < context_lens[:, None])
    if window is not None:
        valid = valid & (kpos[None, :] > q_positions[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    any_valid = jnp.any(valid, axis=-1)[:, None, None, None]
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(any_valid, p, 0.0).astype(vc.dtype)
    return jnp.einsum("tkgs,tskd->tkgd", p, vc)


def _ragged_xla(q, k_pages, v_pages, query_start, query_len, context_len,
                block_tables, scale, window=None, pages_bound=None,
                k_scale=None, v_scale=None):
    """Reference/CI path: bounded page gather + the shared masked core.
    Semantically identical to the kernel; padding rows output zero.
    ``pages_bound`` is the TRACED caller's static trim (the engine
    passes its batch's max reserved page count — context lengths are
    tracers there, so the concrete-trim path cannot fire).
    ``k_scale``/``v_scale`` (P, page_size) dequantize int8 page pools
    per row right after the gather, so the masked core itself stays
    dtype-oblivious."""
    t, h, d = q.shape
    hk = k_pages.shape[0]
    g = h // hk
    n = block_tables.shape[0]
    kc, vc = gather_pages(k_pages, v_pages, block_tables,
                          context_lens=context_len,
                          pages_bound=pages_bound)
    if k_scale is not None:
        bound = page_gather_bound(block_tables, context_len,
                                  pages_bound, k_pages.shape[2])
        ks = gather_page_scales(k_scale, block_tables, bound)  # (N, S)
        vs = gather_page_scales(v_scale, block_tables, bound)
        kc = kc.astype(jnp.float32) * ks[:, :, None, None]
        vc = vc.astype(jnp.float32) * vs[:, :, None, None]
    # post-trim: normalize descriptors to device arrays (a numpy base
    # indexed by a traced index array would not convert)
    query_start = jnp.asarray(query_start, jnp.int32)
    query_len = jnp.asarray(query_len, jnp.int32)
    context_len = jnp.asarray(context_len, jnp.int32)
    # token -> owning sequence via segment membership (works for any
    # descriptor order; padding rows match no sequence)
    rows = jnp.arange(t)
    in_seq = (rows[:, None] >= query_start[None, :]) \
        & (rows[:, None] < (query_start + query_len)[None, :])
    tok_seq = jnp.where(jnp.any(in_seq, 1), jnp.argmax(in_seq, 1), 0)
    live = jnp.any(in_seq, 1)
    tok_pos = context_len[tok_seq] - query_len[tok_seq] \
        + (rows - query_start[tok_seq])
    tok_ctx = jnp.where(live, context_len[tok_seq], 0)
    qh = q.reshape(t, hk, g, d)
    out = masked_page_attention(qh, kc[tok_seq], vc[tok_seq],
                                jnp.where(live, tok_pos, -1), tok_ctx,
                                scale, window)
    # quantized pools dequantized kc/vc to f32 above; match the kernel
    # path's contract (output in q's dtype) on every route
    return out.reshape(t, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _ragged_kernel(qb_seq_ref, qstart_ref, qlen_ref, ctx_ref, bt_ref,
                   q_ref, k_ref, v_ref, *rest, scale, page_size,
                   block_q, group, window, quantized=False):
    # quantized page pools (int8 storage) add two (1, page_size) f32
    # per-page-row scale blocks; the dequant folds into the existing
    # multiplies — logits scale per KEY row (columns of sim), the p@v
    # weights scale per VALUE row (columns of p) — so the int8 tiles
    # feed the MXU unwidened in HBM and no transposed broadcast is
    # ever materialized
    if quantized:
        ks3_ref, vs3_ref, o_ref, acc_ref, m_ref, l_ref = rest
        # (1, 1, page_size) blocks of the (P, 1, page_size) pools —
        # the middle unit axis exists purely so the block's last two
        # dims equal the array's (the Mosaic block-shape rule); drop
        # it to the (1, page_size) row the broadcasts below want
        ks_ref = ks3_ref[0]
        vs_ref = vs3_ref[0]
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    qb = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = qb_seq_ref[qb]
    sc = jnp.maximum(s, 0)
    ctx = ctx_ref[sc]
    qlen = qlen_ref[sc]
    qb_off = qb * block_q - qstart_ref[sc]
    first_q = ctx - qlen + qb_off                  # global pos of row 0
    last_q = ctx - qlen + jnp.minimum(qb_off + block_q, qlen) - 1
    live = (s >= 0) & (qb_off < qlen) & (i * page_size <= last_q)
    if window is not None:
        live = live & ((i + 1) * page_size > first_q - window + 1)

    @pl.when(live)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q*G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)
        sim = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if quantized:
            # per-key-row dequant: sim[r, j] owes one factor ks[j]
            sim = sim * ks_ref[:]                    # (1, ps) bcast
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sim.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, sim.shape, 0) // group
        qpos = first_q + row
        valid = (kpos <= qpos) & (qb_off + row < qlen)
        if window is not None:
            valid = valid & (kpos > qpos - window)
        sim = jnp.where(valid, sim, NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(sim, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(sim > NEG_INF * 0.5, jnp.exp(sim - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, -1, keepdims=True)
        pv = p * vs_ref[:] if quantized else p       # value-row dequant
        acc_ref[:] = acc_ref[:] * alpha + mxu_dot(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = jnp.where(m_ref[:, :1] > NEG_INF * 0.5,
                                acc_ref[:] / l, 0.0).astype(o_ref.dtype)


def _page_index_map(qb, hh, ii, qb_seq, qstart, qlen, ctx, bt, *,
                    page_size, block_q, window):
    """BlockSpec index_map for k/v: live pages read their block-table
    entry; DEAD pages (causally past the frontier, below the window, or
    under a padding q block) route to the resident trash page 0 — the
    pipeline then skips the DMA because the block index is unchanged."""
    s = qb_seq[qb]
    sc = jnp.maximum(s, 0)
    c = ctx[sc]
    ql = qlen[sc]
    qb_off = qb * block_q - qstart[sc]
    first_q = c - ql + qb_off
    last_q = c - ql + jnp.minimum(qb_off + block_q, ql) - 1
    live = (s >= 0) & (qb_off < ql) & (ii * page_size <= last_q)
    if window is not None:
        live = live & ((ii + 1) * page_size > first_q - window + 1)
    return (hh, jnp.where(live, bt[sc, ii], TRASH_PAGE), 0, 0)


def _scale_index_map(qb, hh, ii, qb_seq, qstart, qlen, ctx, bt, *,
                     page_size, block_q, window):
    """Index map for the (P, 1, page_size) per-page scale pools of a
    QUANTIZED page pool: EXACTLY the page index map's live/dead
    routing (delegated, so the two can never drift — a scale routed
    to a different page than its values would be silent
    mis-dequantization), minus the head dim the scale pools do not
    have. Dead pages ride the trash page's scales; their logits are
    fully masked anyway."""
    return _page_index_map(qb, hh, ii, qb_seq, qstart, qlen, ctx, bt,
                           page_size=page_size, block_q=block_q,
                           window=window)[1:]


def _ragged_pallas(q, k_pages, v_pages, query_start, query_len,
                   context_len, block_tables, scale, window, block_q,
                   interpret, k_scale=None, v_scale=None):
    t, h, d = q.shape
    hk, _, page_size, _ = k_pages.shape
    g = h // hk
    n = block_tables.shape[0]
    pps = block_tables.shape[1]
    quantized = k_scale is not None
    nqb = t // block_q
    # q block qb -> owning sequence (padding blocks: -1); every block
    # belongs to at most one sequence because starts are block-aligned
    qb_rows = jnp.arange(nqb, dtype=jnp.int32) * block_q
    in_seq = (qb_rows[:, None] >= query_start[None, :]) \
        & (qb_rows[:, None] < (query_start + query_len)[None, :])
    qb_seq = jnp.where(jnp.any(in_seq, 1),
                       jnp.argmax(in_seq, 1), -1).astype(jnp.int32)
    # (T, H, D) -> (HK, nqb, block_q*G, D): one MXU-ready q tile per
    # (kv head, q block); all reshapes live outside the kernel
    qk = jnp.transpose(q.reshape(t, hk, g, d), (1, 0, 2, 3))
    qk = qk.reshape(hk, nqb, block_q * g, d)

    page_map = functools.partial(
        _page_index_map, page_size=page_size, block_q=block_q,
        window=window)
    in_specs = [
        pl.BlockSpec((1, 1, block_q * g, d),
                     lambda qb, hh, ii, *refs: (hh, qb, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), page_map),
        pl.BlockSpec((1, 1, page_size, d), page_map),
    ]
    inputs = [qk, k_pages, v_pages]
    if quantized:
        scale_map = functools.partial(
            _scale_index_map, page_size=page_size, block_q=block_q,
            window=window)
        # (P, ps) -> (P, 1, ps): the unit middle axis makes the block's
        # last two dims equal the array's (the Mosaic block rule — a
        # (1, ps) block of a (P, ps) array has an undividable sublane)
        in_specs += [pl.BlockSpec((1, 1, page_size), scale_map),
                     pl.BlockSpec((1, 1, page_size), scale_map)]
        inputs += [k_scale[:, None, :], v_scale[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nqb, hk, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q * g, d),
                               lambda qb, hh, ii, *refs: (hh, qb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q * g, d), jnp.float32),
            pltpu.VMEM((block_q * g, LANES), jnp.float32),
            pltpu.VMEM((block_q * g, LANES), jnp.float32),
        ],
    )
    out_dtype = q.dtype
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale,
                          page_size=page_size, block_q=block_q, group=g,
                          window=window, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hk, nqb, block_q * g, d),
                                       out_dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(qb_seq, query_start.astype(jnp.int32),
      query_len.astype(jnp.int32), context_len.astype(jnp.int32),
      block_tables.astype(jnp.int32), *inputs)
    out = out.reshape(hk, nqb, block_q, g, d)
    return jnp.transpose(out, (1, 2, 0, 3, 4)).reshape(t, h, d)


def _ragged_tp_shard_map(q, k_pages, v_pages, query_start, query_len,
                         context_len, block_tables, scale, window,
                         block_q, interpret, tp, k_scale=None,
                         v_scale=None):
    """The Pallas kernel under tensor parallelism (serving/submesh.py):
    heads are data-parallel in attention, so each TP shard runs the
    UNCHANGED kernel over its local (H/tp, HK/tp) heads via shard_map —
    q sharded on its head axis, the page pools on theirs, and the
    descriptors/block tables REPLICATED in-spec (they are host-side
    scalars describing every shard's identical page geometry: one
    logical page = tp local shards). The kernel body never learns
    about the mesh, which is what keeps its interpret-mode oracle
    parity meaningful under TP."""
    from jax import shard_map
    mesh, axis = tp
    P = jax.sharding.PartitionSpec
    quantized = k_scale is not None

    def local(qq, kp, vp, qs, ql, cl, bt, *scales):
        ks, vs = scales if quantized else (None, None)
        return _ragged_pallas(qq, kp, vp, qs, ql, cl, bt, scale,
                              window, block_q, interpret,
                              k_scale=ks, v_scale=vs)

    in_specs = (P(None, axis, None), P(axis, None, None, None),
                P(axis, None, None, None), P(), P(), P(), P())
    args = (q, k_pages, v_pages, query_start.astype(jnp.int32),
            query_len.astype(jnp.int32), context_len.astype(jnp.int32),
            block_tables.astype(jnp.int32))
    if quantized:
        # per-page scales are head-free (one scale per page row,
        # shared by every head): replicated in-spec like the
        # descriptors, so each shard dequantizes its local heads with
        # the identical factors
        in_specs = in_specs + (P(), P())
        args = args + (k_scale, v_scale)
    return shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(None, axis, None),
        # pallas_call cannot annotate varying-mesh-axes on its outputs;
        # the specs above are exact (descriptors replicated in, heads
        # sharded out), so skipping the vma check loses nothing
        check_vma=False,
    )(*args)


def ragged_paged_attention_values(q, k_pages, v_pages, query_start,
                                  query_len, context_len, block_tables,
                                  scale=None, window=None,
                                  block_q=DEFAULT_BLOCK_Q,
                                  use_kernel=None, pages_bound=None,
                                  tp=None, k_scale=None, v_scale=None):
    """q: (T, H, D) packed ragged queries; k_pages/v_pages:
    (HK, P, page_size, D); query_start/query_len/context_len: (N,)
    int32 per-sequence descriptors; block_tables: (N, pages_per_seq)
    int32.  Row j of sequence s sits at global position
    ``context_len[s] - query_len[s] + j`` and attends its sequence's
    pages causally (band-limited by ``window`` when set).  Returns
    (T, H, D); padding rows (owned by no sequence) return zero.

    ``use_kernel``: None routes by platform (Pallas on TPU, the bounded
    XLA gather oracle elsewhere); True forces the Pallas kernel — in
    interpret mode off-TPU, which is how CI proves kernel/oracle parity.
    The Pallas path requires ``query_start`` aligned to ``block_q``
    (build batches with `pack_ragged_starts`; decode batches pass
    block_q=1).  ``pages_bound``: STATIC cap on block-table columns the
    XLA fallback gathers — traced callers (context lengths are tracers,
    so the automatic concrete trim cannot fire) pass their known max
    page demand to keep the gather O(max context), not O(pps). Columns
    past every context are fully masked, so trimming them is exact.

    ``tp``: a ``(jax Mesh, axis name)`` pair (the serving engine passes
    its submesh's) making the dispatch sharding-aware — the XLA path
    needs nothing (GSPMD propagates the head sharding through the
    gather and the masked core), the kernel path runs per-shard via
    `shard_map` with replicated descriptors (`_ragged_tp_shard_map`).

    ``k_scale``/``v_scale``: (P, page_size) f32 per-page-row DEQUANT
    multipliers of QUANTIZED int8 page pools (quantized serving,
    docs/serving.md "Quantized serving"; written by
    `ragged_scatter_quantized`). The XLA oracle dequantizes right
    after the gather; the kernel dequantizes per page in flight —
    key-row scales fold into the logits, value-row scales into the
    softmax weights — so page DMA moves int8 bytes only. Trash-page
    routing and dead-page skipping are unchanged (a dead page's
    scales ride the resident trash page like its values)."""
    t, h, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    def _i32(x):
        # keep CONCRETE descriptors as host arrays: jnp.asarray inside
        # a trace would lift them to tracers and defeat the static
        # gather trim / any host-side shape decisions
        if isinstance(x, jax.core.Tracer):
            return x
        try:
            return np.asarray(x, np.int32)
        except Exception:
            return x
    query_start = _i32(query_start)
    query_len = _i32(query_len)
    context_len = _i32(context_len)
    block_tables = _i32(block_tables)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    kernel = use_kernel if use_kernel is not None else on_tpu()
    if not kernel:
        return _ragged_xla(q, k_pages, v_pages, query_start, query_len,
                           context_len, block_tables, sc, window,
                           pages_bound=pages_bound, k_scale=k_scale,
                           v_scale=v_scale)
    if t % block_q:
        raise ValueError(f"packed length {t} not a multiple of "
                         f"block_q {block_q}")
    if tp is not None:
        return _ragged_tp_shard_map(q, k_pages, v_pages, query_start,
                                    query_len, context_len,
                                    block_tables, sc, window, block_q,
                                    _interpret(), tp, k_scale=k_scale,
                                    v_scale=v_scale)
    return _ragged_pallas(q, k_pages, v_pages, query_start, query_len,
                          context_len, block_tables, sc, window,
                          block_q, _interpret(), k_scale=k_scale,
                          v_scale=v_scale)


def ragged_scatter_values(k_pages, v_pages, k_rows, v_rows, block_tables,
                          token_seq, positions):
    """Scatter packed ragged KV rows into the page pools.

    k_rows/v_rows: (T, HK, D) rows for the packed token axis;
    block_tables: (N, pps); token_seq: (T,) owning sequence per row
    (-1 = padding); positions: (T,) global position per row. Padding
    rows route to the trash page (never read). Returns the updated
    (k_pages, v_pages) — one scatter for the whole mixed batch."""
    page_size = k_pages.shape[2]
    live = token_seq >= 0
    sc = jnp.maximum(token_seq, 0)
    page_idx = jnp.where(
        live, block_tables[sc, positions // page_size], TRASH_PAGE)
    slot = jnp.where(live, positions % page_size, 0)
    kp = k_pages.at[:, page_idx, slot].set(
        jnp.swapaxes(k_rows, 0, 1).astype(k_pages.dtype))
    vp = v_pages.at[:, page_idx, slot].set(
        jnp.swapaxes(v_rows, 0, 1).astype(v_pages.dtype))
    return kp, vp


def ragged_scatter_quantized(k_pages, v_pages, k_scale, v_scale,
                             k_rows, v_rows, block_tables, token_seq,
                             positions):
    """`ragged_scatter_values` for QUANTIZED page pools: quantize on
    commit. Each packed row quantizes INDEPENDENTLY — absmax over its
    own (HK, D) values, shared across heads so the scale pools
    (P, page_size) carry no head axis and replicate under tensor
    parallelism — through the ONE shared round-clip core
    (`nn.quant.absmax_round_clip_values`). Per-ROW granularity is what
    makes the quantized bytes PATH-INVARIANT: a page written
    incrementally by decode steps holds bit-identical content to the
    same rows written at once by a preemption re-prefill (each row
    sees only its own values), which is why quantized-mode greedy
    streams stay bit-identical through the chaos drills. int8 pools
    store the lattice values; the scale pools store the DEQUANT
    multiplier absmax/127 (0 for all-zero rows — dequant returns
    exact zeros, never a division). Padding rows trash-route values
    AND scales to page 0."""
    from ..nn.quant import absmax_round_clip_values
    page_size = k_pages.shape[2]
    live = token_seq >= 0
    sc = jnp.maximum(token_seq, 0)
    page_idx = jnp.where(
        live, block_tables[sc, positions // page_size], TRASH_PAGE)
    slot = jnp.where(live, positions % page_size, 0)

    def _q(rows):
        rf = rows.astype(jnp.float32)
        amax = jnp.max(jnp.abs(rf), axis=(1, 2))            # (T,)
        qr = absmax_round_clip_values(rf, amax[:, None, None],
                                      KV_QMAX, out_dtype=jnp.int8)
        return qr, (amax / KV_QMAX).astype(jnp.float32)

    kq, ks_row = _q(k_rows)
    vq, vs_row = _q(v_rows)
    kp = k_pages.at[:, page_idx, slot].set(jnp.swapaxes(kq, 0, 1))
    vp = v_pages.at[:, page_idx, slot].set(jnp.swapaxes(vq, 0, 1))
    ks = k_scale.at[page_idx, slot].set(ks_row)
    vs = v_scale.at[page_idx, slot].set(vs_row)
    return kp, vp, ks, vs


def ragged_paged_attention(q: Tensor, k_pages: Tensor, v_pages: Tensor,
                           query_start, query_len, context_len,
                           block_tables, scale=None, window=None,
                           block_q=DEFAULT_BLOCK_Q) -> Tensor:
    """Eager/tape entry. Serving-only: no grad path."""
    qs = query_start._value if isinstance(query_start, Tensor) \
        else jnp.asarray(query_start, jnp.int32)
    ql = query_len._value if isinstance(query_len, Tensor) \
        else jnp.asarray(query_len, jnp.int32)
    cl = context_len._value if isinstance(context_len, Tensor) \
        else jnp.asarray(context_len, jnp.int32)
    bt = block_tables._value if isinstance(block_tables, Tensor) \
        else jnp.asarray(block_tables, jnp.int32)

    def fn(qq, kk, vv):
        return ragged_paged_attention_values(qq, kk, vv, qs, ql, cl, bt,
                                             scale, window, block_q)
    return apply("ragged_paged_attention", fn, (q, k_pages, v_pages))
