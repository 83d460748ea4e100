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

Stored layout. A page pool is TOKEN-MAJOR: ``(P, page_size, HK*D)``, a
token's K (or V) row over all KV heads contiguous, head ``h`` its lanes
``[h*D, (h+1)*D)``. The layout is the WRITE's: a step's rows land with
one row scatter ``pool.at[page, slot].set(rows)`` whose update window
is a whole stored row, so XLA updates the donated pool in place. (Stored
head-major, ``(HK, P, page_size, D)``, the same scatter's window was
``(HK, D)`` across the slowest axis; XLA gave it a layout of its own
and every layer of every program copied each pool whole in front of
the scatter and back behind it: PERF.md section 6, PR 28.) A page is
one contiguous block, so the kernel fetches it with one DMA descriptor,
and a head is a lane slice of the VMEM block: the same ``(keys, D)``
tile the MXU multiplies. ``HK`` is read off the shapes
(``pool.shape[2] // q.shape[2]``); `pages_to_payload` /
`payload_to_pages` are the transposes at the engine's export / import
boundary, whose payload stays ``(HK, n_pages, page_size, D)``.

Kernel. The grid is (q-blocks,): one step a q block, with every KV
head of the step in it. Each q block belongs to exactly one sequence
(the packer aligns ``query_start`` to ``block_q``; decode batches use
block_q = 1). The page pools stay in HBM; the block tables and the
per-sequence descriptors are SCALAR-PREFETCHED, and inside a step a
loop walks the q block's LIVE pages only, a KV BLOCK of several pages x
all heads a trip: from the block that holds the sliding window's lower
edge (block 0 without a window) to the block of the q block's causal
frontier. A trip starts the DMAs of the next block's pages (one
descriptor a page, a contiguous ``(page_size, HK*D)`` block with every
head in it, into the other half of a two-slot VMEM buffer) and waits
for its own — the double buffering the BlockSpec pipeline used to do a
4 KB page at a time — then multiplies every head's ``(block_q*G, D) x
(block keys, D)^T`` (the head's lane slice of the block), masks by
position, folds the block into every head's online softmax and
multiplies every head's ``p @ V``: three passes over the heads, the
products of one kind together (below). The trip count is
dynamic: a padding q block, a sequence with no query and an idle slot
do none and write zeros; no column past the frontier is visited, so the
width of the block table costs nothing and no bound on it shapes the
program.
Pages of a live block that lie outside the live range (below the
window's edge, past the frontier) copy the trash page 0 and are masked
by position; their table entries are never read. `kv_block_pages`
sizes the block from the static shapes against a fixed VMEM budget;
`live_kv_blocks` is the loop's bounds, shared with the engine's
`pdt_serving_attn_pages_total` counter (`ragged_pages_walked`).

Operand dtypes. q, K and V meet the MXU at the dtype they are stored
in (`_operand_dtype`: the wider of q's and the pools', q's for int8
pools, whose values are exact in it), the softmax weights rounded to
the same dtype beside them; the products accumulate in float32, and the
logits, the mask and the online softmax state are float32. A bf16 x
bf16 product is exact in float32, so ``q . K^T`` loses nothing to the
narrow operands; ``p`` loses what the chip always took from it: a
float32 `tpu.matmul` at DEFAULT precision is ONE pass of the v5e's MXU
with each operand rounded to bf16 on its way in, not the three or six
passes of a float32 emulation (PERF.md section 6, PR 30: the float32
kernel and this one give the same bits). What a trip's compute costs is
the ORDER of its products: ``K^T`` is a transposed weight load of the
MXU and ``V`` a plain one, and a loop that did both a head at a time
alternated the two kinds on every MXU: at 8 KV heads a trip's compute
alone took 1.41 us and the trip 1.56, where its DMAs alone take 0.81;
in three passes over the heads the compute takes 0.78 us and the trip
1.06. A float32 q (the CPU parity tests) keeps float32 operands
throughout.

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
                      pad_to=None, diffusion_block=1):
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
    descriptor format cannot drift between them.

    ``diffusion_block`` > 1 (a model that attends by blocks, below):
    every piece must start and end on a multiple of it, or the call is
    refused: a piece that ends inside a block would attend keys of its
    block that are not written yet."""
    grid = int(pad_to) if pad_to else int(block_q)
    for p in pieces if diffusion_block > 1 else ():
        if p["offset"] % diffusion_block \
                or len(p["tokens"]) % diffusion_block:
            raise ValueError(
                f"piece of {len(p['tokens'])} tokens at offset "
                f"{p['offset']} does not start and end on a multiple "
                f"of diffusion_block {diffusion_block}")
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


def pages_to_payload(pages, kv_heads):
    """Stored pages (n, page_size, HK*D) -> the export payload's
    head-major (HK, n, page_size, D). The engine's `export_pages` /
    `import_pages` boundary keeps that documented shape whatever the
    pools store; numpy or jax arrays."""
    n, page_size, row = pages.shape
    return pages.reshape(n, page_size, kv_heads,
                         row // kv_heads).transpose(2, 0, 1, 3)


def payload_to_pages(rows):
    """The inverse of `pages_to_payload`: (HK, n, page_size, D) ->
    (n, page_size, HK*D), the rows an install writes into the pool."""
    hk, n, page_size, d = rows.shape
    return rows.transpose(1, 2, 0, 3).reshape(n, page_size, hk * d)


def gather_pages(k_pages, v_pages, block_tables, kv_heads,
                 context_lens=None, pages_bound=None):
    """Gather block-table pages of the (P, page_size, HK*D) pools to
    per-sequence contiguous caches (N, S, HK, D), bounding the gather
    to the block-table prefix actually referenced: when
    ``context_lens`` is CONCRETE (host-side numpy / eager call) the
    trim is static — ``S = ceil(max(ctx) / page_size) * page_size`` —
    instead of materializing the full ``pps * page_size`` worst case.
    ``pages_bound`` overrides the trim explicitly (traced callers that
    know a static bound)."""
    page_size = k_pages.shape[1]
    bound = page_gather_bound(block_tables, context_lens, pages_bound,
                              page_size)
    bt = block_tables[:, :bound]
    shape = (bt.shape[0], bound * page_size, kv_heads, -1)
    return k_pages[bt].reshape(shape), v_pages[bt].reshape(shape)


def block_frontier(pos, diffusion_block):
    """The last key position a query at `pos` attends: itself under the
    causal mask (`diffusion_block` 1, returned untouched), the end of
    its block of `diffusion_block` positions (counted from 0) where
    attention is by blocks: ``kpos // B <= qpos // B``. Scalars or
    arrays, numpy or jax."""
    if diffusion_block == 1:
        return pos
    return (pos // diffusion_block + 1) * diffusion_block - 1


def masked_page_attention(q, kc, vc, q_positions, context_lens, scale,
                          window=None, diffusion_block=1):
    """The ONE masked-attention core behind every paged XLA fallback.

    q: (T, HK, G, D) packed query tokens; kc/vc: (T, S, HK, D) — the
    gathered cache rows of each token's OWN sequence (callers gather
    per sequence and index by token); q_positions: (T,) global position
    of each query token; context_lens: (T,) context length of the
    token's sequence. Token t attends keys ``k <= q_positions[t]``
    (and ``> q_positions[t] - window``), keys past the context are
    masked, and tokens with no valid key output zero. With
    ``diffusion_block`` B > 1 it attends every key of its own block of
    B positions too (`block_frontier`)."""
    s_max = kc.shape[1]
    logits = jnp.einsum("tkgd,tskd->tkgs", q, kc,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s_max)
    frontier = block_frontier(q_positions, diffusion_block)
    valid = (kpos[None, :] <= frontier[:, None]) \
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
                k_scale=None, v_scale=None, diffusion_block=1):
    """Reference/CI path: bounded page gather + the shared masked core.
    Semantically identical to the kernel; padding rows output zero.
    ``pages_bound`` is the TRACED caller's static trim (the engine
    passes its batch's max reserved page count — context lengths are
    tracers there, so the concrete-trim path cannot fire).
    ``k_scale``/``v_scale`` (P, page_size) dequantize int8 page pools
    per row right after the gather, so the masked core itself stays
    dtype-oblivious."""
    t, h, d = q.shape
    hk = k_pages.shape[2] // d
    g = h // hk
    kc, vc = gather_pages(k_pages, v_pages, block_tables, hk,
                          context_lens=context_len,
                          pages_bound=pages_bound)
    if k_scale is not None:
        bound = page_gather_bound(block_tables, context_len,
                                  pages_bound, k_pages.shape[1])
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
                                scale, window, diffusion_block)
    # quantized pools dequantized kc/vc to f32 above; match the kernel
    # path's contract (output in q's dtype) on every route
    return out.reshape(t, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
# VMEM the kernel gives its two-slot K + V block buffers; `kv_block_pages`
# sizes a trip's KV block against it
KV_BLOCK_VMEM_BYTES = 2 * 1024 * 1024
# keys a trip at most: one 128-lane row of logits a query row. A trip's
# time grows with its pages (about 0.13 us a page on a v5e at 8 KV
# heads, trash pages of the last block included: 0.1 us is the page's
# two DMAs). 256 keys a trip are 10 % ahead at the batch cell's decode
# shape and 26 % at the hybrid's, and 15 % behind at the batch cell's
# admission shape, where a q block's last KV block is mostly trash:
# 128 stays (PERF.md section 6, PR 30)
KV_BLOCK_MAX_KEYS = 128


def kv_block_pages(page_size, head_dim, kv_heads, itemsize,
                   table_pages) -> int:
    """Pages of one KV block: what a trip of the kernel's loop copies
    (one DMA a page, every KV head of the step in it) and multiplies at
    once. A function of the static shapes only — the largest power of
    two whose double-buffered K and V blocks fit `KV_BLOCK_VMEM_BYTES`,
    within `KV_BLOCK_MAX_KEYS` keys and no wider than the block table
    — so every caller (the kernel, the engine's page counter) computes
    the same number."""
    row = -(-kv_heads * head_dim // LANES) * LANES  # as VMEM tiles it
    page_bytes = page_size * row * itemsize
    fit = min(KV_BLOCK_VMEM_BYTES // (4 * page_bytes),  # K, V x 2 slots
              KV_BLOCK_MAX_KEYS // page_size)
    fit = 1 << (max(int(fit), 1).bit_length() - 1)
    return min(fit, 1 << (int(table_pages) - 1).bit_length())


def qblock_seq(query_start, query_len, n_qblocks, block_q, xp=jnp):
    """q block -> owning sequence (padding blocks: -1). Every block
    belongs to at most one sequence because starts are block-aligned."""
    rows = xp.arange(n_qblocks, dtype=xp.int32) * block_q
    in_seq = (rows[:, None] >= query_start[None, :]) \
        & (rows[:, None] < (query_start + query_len)[None, :])
    return xp.where(in_seq.any(1), in_seq.argmax(1), -1).astype(xp.int32)


def live_kv_blocks(seq, qb_off, qlen, ctx, *, page_size, block_q,
                   window, block_pages, xp=jnp, diffusion_block=1):
    """(first live page, last live page, first KV block, KV block count)
    of a q block whose rows start ``qb_off`` rows into sequence
    ``seq``'s query segment. Live pages run from the page that holds
    the sliding window's lower edge of the block's first row (0 without
    a window) to the page of its last row's causal frontier; KV blocks
    are ``block_pages`` table columns wide and aligned to the table, so
    the walk covers every block that holds a live page. A padding block
    (``seq < 0``), a block past its sequence's queries and a sequence
    with no queries have no block. THE bounds of the kernel's loop, in
    scalars there and over arrays on the host (`ragged_pages_walked`)."""
    first_q = ctx - qlen + qb_off                  # global pos of row 0
    last_q = ctx - qlen + xp.minimum(qb_off + block_q, qlen) - 1
    lo = 0 if window is None \
        else xp.maximum(first_q - window + 1, 0) // page_size
    hi = block_frontier(last_q, diffusion_block) // page_size
    live = (seq >= 0) & (qb_off < qlen)
    b0 = lo // block_pages
    return lo, hi, b0, xp.where(live, hi // block_pages - b0 + 1, 0)


def ragged_pages_walked(query_start, query_len, context_len, n_rows, *,
                        block_q, page_size, window, block_pages,
                        table_pages, diffusion_block=1):
    """Block-table columns the kernel's loops visit for one dispatch
    (host side, from the dispatch's own descriptors): per q block the
    columns of the KV blocks of its live page range (a last block that
    overhangs a table of ``table_pages`` columns counts its columns in
    the table)."""
    qs = np.asarray(query_start, np.int32)
    ql = np.asarray(query_len, np.int32)
    cl = np.asarray(context_len, np.int32)
    nqb = int(n_rows) // block_q
    seq = qblock_seq(qs, ql, nqb, block_q, xp=np)
    sc = np.maximum(seq, 0)
    qb_off = np.arange(nqb, dtype=np.int32) * block_q - qs[sc]
    _, _, b0, n_blocks = live_kv_blocks(
        seq, qb_off, ql[sc], cl[sc], page_size=page_size,
        block_q=block_q, window=window, block_pages=block_pages, xp=np,
        diffusion_block=diffusion_block)
    end = np.minimum((b0 + n_blocks) * block_pages, table_pages)
    return int(np.where(n_blocks > 0, end - b0 * block_pages, 0).sum())


def _operand_dtype(q_dtype, pool_dtype):
    """The dtype q, K, V and the softmax weights meet the MXU at: the
    wider of what the call was given, so nothing is widened that both
    sides store narrow. int8 pages (each an exact bf16 value) take q's."""
    if jnp.issubdtype(pool_dtype, jnp.integer):
        return jnp.dtype(q_dtype)
    return jnp.promote_types(q_dtype, pool_dtype)


def _ragged_kernel(qb_seq_ref, qstart_ref, qlen_ref, ctx_ref, bt_ref,
                   q_ref, k_hbm, v_hbm, *rest, scale, page_size,
                   block_q, group, window, block_pages, quantized=False,
                   diffusion_block=1):
    # quantized page pools (int8 storage) add the two per-page-row
    # scale pools, gathered to one (1, keys) f32 row a KV block of each
    # sequence; the dequant folds into the existing multiplies — logits
    # scale per KEY row (columns of sim), the p@v weights scale per
    # VALUE row (columns of p) — so the int8 pages feed the MXU
    # unwidened in HBM and no transposed broadcast is ever materialized
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
         ksbuf, vsbuf) = rest
    else:
        o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref = rest
    hk, d = q_ref.shape[0], q_ref.shape[3]
    operand = _operand_dtype(q_ref.dtype, kbuf.dtype)
    keys = block_pages * page_size
    pps = bt_ref.shape[1]
    qb = pl.program_id(0)

    s = qb_seq_ref[qb]
    sc = jnp.maximum(s, 0)
    ctx = ctx_ref[sc]
    qlen = qlen_ref[sc]
    qb_off = qb * block_q - qstart_ref[sc]
    first_q = ctx - qlen + qb_off                  # global pos of row 0
    lo_page, hi_page, block0, n_trips = live_kv_blocks(
        s, qb_off, qlen, ctx, page_size=page_size, block_q=block_q,
        window=window, block_pages=block_pages,
        diffusion_block=diffusion_block)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    def block_copies(trip, slot):
        """The DMAs of trip `trip`'s KV block into buffer `slot`: one
        descriptor a page and pool, a contiguous (page_size, HK*D)
        block with every KV head in it. The block's
        columns outside the live range (below the window's edge, past
        the frontier) copy the trash page: their keys are masked by
        position, and the table is never read there."""
        block = block0 + trip
        copies = []
        for j in range(block_pages):
            col = block * block_pages + j
            page = jnp.where(
                (col >= lo_page) & (col <= hi_page),
                bt_ref[sc, jnp.minimum(col, pps - 1)], TRASH_PAGE)
            copies.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, j], sem.at[0, slot]))
            copies.append(pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[slot, j], sem.at[1, slot]))
        if quantized:
            # the block's row of `_block_scale_rows`
            row = sc * -(-pps // block_pages) + block
            copies.append(pltpu.make_async_copy(
                ks_hbm.at[row], ksbuf.at[slot], sem.at[2, slot]))
            copies.append(pltpu.make_async_copy(
                vs_hbm.at[row], vsbuf.at[slot], sem.at[3, slot]))
        return copies

    @pl.when(n_trips > 0)
    def _prime():
        for c in block_copies(0, 0):
            c.start()

    def trip_body(trip, carry):
        slot = trip % 2

        @pl.when(trip + 1 < n_trips)
        def _prefetch():
            for c in block_copies(trip + 1, 1 - slot):
                c.start()

        for c in block_copies(trip, slot):
            c.wait()

        shape = (block_q * group, keys)
        kpos = (block0 + trip) * keys \
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        qpos = first_q + row
        # by blocks, a row attends to the end of its own block: keys
        # the dispatch's own scatter wrote, never past the context
        valid = (kpos <= block_frontier(qpos, diffusion_block)) \
            & (qb_off + row < qlen)
        if diffusion_block > 1:
            valid = valid & (kpos < ctx)
        if window is not None:
            valid = valid & (kpos > qpos - window)
        if quantized:
            ks = ksbuf[slot][:, :keys]               # (1, keys)
            vs = vsbuf[slot][:, :keys]
        # Three passes over the heads, not one: every head's q . K^T,
        # then every head's softmax, then every head's p @ V. K^T is a
        # TRANSPOSED weight load of the MXU and V a plain one; in one
        # pass a head the two kinds alternate on each MXU, and a trip
        # took 1.41 us of compute at 8 heads; grouped, each MXU loads
        # its K blocks, then its V blocks: 0.78 us (docs/kernels.md,
        # "Operand dtypes and the order of the products")
        sims = []
        for h in range(hk):
            q = q_ref[h, 0].astype(operand)          # (block_q*G, D)
            # head h of the block: its lanes of every stored row
            k = kbuf[slot, :, :, pl.ds(h * d, d)].astype(operand)
            sim = mxu_dot(
                q, k.reshape(keys, d), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                # per-key-row dequant: sim[r, j] owes one factor ks[j]
                sim = sim * ks                       # (1, keys) bcast
            sims.append(jnp.where(valid, sim, NEG_INF))
        weights = []
        for h, sim in enumerate(sims):
            m_prev = m_ref[h, :, :1]
            m_cur = jnp.max(sim, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(sim > NEG_INF * 0.5, jnp.exp(sim - m_new), 0.0)
            l_new = alpha * l_ref[h, :, :1] \
                + jnp.sum(p, -1, keepdims=True)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            pv = p * vs if quantized else p          # value-row dequant
            weights.append((pv.astype(operand), alpha))
        for h, (pv, alpha) in enumerate(weights):
            v = vbuf[slot, :, :, pl.ds(h * d, d)].astype(operand)
            acc_ref[h] = acc_ref[h] * alpha + mxu_dot(
                pv, v.reshape(keys, d), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_trips, trip_body, None)

    for h in range(hk):
        l = jnp.maximum(l_ref[h, :, :1], 1e-30)
        o_ref[h, 0] = jnp.where(m_ref[h, :, :1] > NEG_INF * 0.5,
                                acc_ref[h] / l, 0.0).astype(o_ref.dtype)


def _block_scale_rows(scale_pool, block_tables, block_pages):
    """A quantized pool's per-page-row scales (P, page_size), gathered
    along the block tables to one lane-dense row a KV block:
    (N * blocks-per-sequence, 1, keys rounded up to whole 128-lane
    tiles). Mosaic copies nothing narrower than a 128-lane tile out of
    HBM, so a page's 16 scales cannot ride the page's own DMA; the
    gather is XLA's (N x pps rows of 64 bytes, next to nothing) and a
    trip copies its block's row. Dead columns gather whatever page id
    they hold, clamped into the pool: masked keys, never used."""
    n, pps = block_tables.shape
    page_size = scale_pool.shape[1]
    blocks = -(-pps // block_pages)
    keys = block_pages * page_size
    rows = scale_pool[block_tables]                # (N, pps, ps)
    rows = jnp.pad(rows, ((0, 0), (0, blocks * block_pages - pps),
                          (0, 0)))
    rows = rows.reshape(n * blocks, 1, keys)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, -keys % LANES)))


# jitted so that a program's layers share ONE trace and ONE lowering of
# the kernel (same shapes, same statics -> the cached jaxpr, lowered to
# one function the layers call): traced and lowered a layer at a time
# the kernel's body cost a 24-layer program 24 times its 0.4 s
@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "block_q", "interpret", "diffusion_block"))
def _ragged_pallas(q, k_pages, v_pages, query_start, query_len,
                   context_len, block_tables, scale, window, block_q,
                   interpret, k_scale=None, v_scale=None,
                   diffusion_block=1):
    t, h, d = q.shape
    hk = k_pages.shape[2] // d
    if k_pages.shape[2] % LANES:
        # Mosaic copies whole 128-lane tiles out of HBM ("Slice shape
        # ... must be aligned to tiling (128)"), and a pool whose
        # stored row is no whole number of tiles is lane-padded there
        # anyway: pad the rows with zero lanes, which no head's slice
        # reads. A copy of both pools a call, paid only by rows
        # narrower than a tile (HK*D under 128: toy widths, or a head
        # sharded thinner than a tile) or ragged against it.
        lanes = ((0, 0), (0, 0), (0, -k_pages.shape[2] % LANES))
        k_pages, v_pages = jnp.pad(k_pages, lanes), jnp.pad(v_pages, lanes)
    _, page_size, row = k_pages.shape
    g = h // hk
    quantized = k_scale is not None
    nqb = t // block_q
    block_pages = kv_block_pages(page_size, d, hk,
                                 k_pages.dtype.itemsize,
                                 block_tables.shape[1])
    qb_seq = qblock_seq(query_start, query_len, nqb, block_q)
    # (T, H, D) -> (HK, nqb, block_q*G, D): one MXU-ready q tile per
    # (kv head, q block); all reshapes live outside the kernel
    qk = jnp.transpose(q.reshape(t, hk, g, d), (1, 0, 2, 3))
    qk = qk.reshape(hk, nqb, block_q * g, d)

    # every KV head of a q block in one grid step
    q_spec = pl.BlockSpec((hk, 1, block_q * g, d),
                          lambda qb, *refs: (0, qb, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [q_spec, hbm_spec, hbm_spec]
    inputs = [qk, k_pages, v_pages]
    kv_block = (2, block_pages, page_size, row)       # two slots
    scratch_shapes = [
        pltpu.VMEM(kv_block, k_pages.dtype),
        pltpu.VMEM(kv_block, v_pages.dtype),
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
        pltpu.VMEM((hk, block_q * g, d), jnp.float32),
        pltpu.VMEM((hk, block_q * g, LANES), jnp.float32),
        pltpu.VMEM((hk, block_q * g, LANES), jnp.float32),
    ]
    if quantized:
        ks_rows = _block_scale_rows(k_scale, block_tables, block_pages)
        in_specs += [hbm_spec, hbm_spec]
        inputs += [ks_rows,
                   _block_scale_rows(v_scale, block_tables, block_pages)]
        scratch_shapes += [
            pltpu.VMEM((2,) + ks_rows.shape[1:], jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nqb,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale,
                          page_size=page_size, block_q=block_q, group=g,
                          window=window, block_pages=block_pages,
                          quantized=quantized,
                          **({"diffusion_block": diffusion_block}
                             if diffusion_block > 1 else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hk, nqb, block_q * g, d),
                                       q.dtype),
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
                         v_scale=None, diffusion_block=1):
    """The Pallas kernel under tensor parallelism (serving/submesh.py):
    heads are data-parallel in attention, so each TP shard runs the
    UNCHANGED kernel over its local (H/tp, HK/tp) heads via shard_map —
    q sharded on its head axis, the page pools on their rows' (the last
    axis: head-major within a row, so a shard holds whole heads), and the
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
                              k_scale=ks, v_scale=vs,
                              diffusion_block=diffusion_block)

    # a shard's heads are a contiguous run of every stored row's lanes
    in_specs = (P(None, axis, None), P(None, None, axis),
                P(None, None, axis), P(), P(), P(), P())
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
                                  tp=None, k_scale=None, v_scale=None,
                                  diffusion_block=1):
    """q: (T, H, D) packed ragged queries; k_pages/v_pages:
    (P, page_size, HK*D), token-major (the module docstring says why);
    query_start/query_len/context_len: (N,) int32 per-sequence
    descriptors; block_tables: (N, pages_per_seq) int32.  Row j of sequence s sits at global position
    ``context_len[s] - query_len[s] + j`` and attends its sequence's
    pages causally (band-limited by ``window`` when set).  Returns
    (T, H, D); padding rows (owned by no sequence) return zero.

    ``diffusion_block`` B > 1 (static; a model that generates by
    diffusion over blocks, `models/cache_spec.BlockDiffusionSpec`): the
    mask is causal over BLOCKS of B positions counted from 0, a row
    attends every key of its own block, ``kpos // B <= qpos // B``, and
    a q block's walk ends at the block of its last row's frontier. Every
    segment must start and end on a multiple of B (`pack_ragged_batch`
    checks). At 1 every path is what it was, operation for operation.

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
    after the gather; the kernel dequantizes per KV block in flight —
    key-row scales fold into the logits, value-row scales into the
    softmax weights — so page DMA moves int8 bytes only. The scales
    reach the kernel gathered along the block tables, one lane-dense
    row a KV block (`_block_scale_rows`), and ride the same trips."""
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
                           v_scale=v_scale,
                           diffusion_block=diffusion_block)
    if t % block_q:
        raise ValueError(f"packed length {t} not a multiple of "
                         f"block_q {block_q}")
    if tp is not None:
        return _ragged_tp_shard_map(q, k_pages, v_pages, query_start,
                                    query_len, context_len,
                                    block_tables, sc, window, block_q,
                                    _interpret(), tp, k_scale=k_scale,
                                    v_scale=v_scale,
                                    diffusion_block=diffusion_block)
    return _ragged_pallas(q, k_pages, v_pages, query_start, query_len,
                          context_len, block_tables, sc, window,
                          block_q, _interpret(), k_scale=k_scale,
                          v_scale=v_scale,
                          diffusion_block=diffusion_block)


def _row_targets(k_pages, block_tables, token_seq, positions):
    """(page, slot in the page) each packed row is written to; padding
    rows (``token_seq`` -1) go to the trash page's slot 0."""
    page_size = k_pages.shape[1]
    live = token_seq >= 0
    sc = jnp.maximum(token_seq, 0)
    page_idx = jnp.where(
        live, block_tables[sc, positions // page_size], TRASH_PAGE)
    return page_idx, jnp.where(live, positions % page_size, 0)


def set_rows(k_pages, v_pages, page_idx, slot, k_rows, v_rows):
    """``pool[page_idx[t], slot[t]] = rows[t]`` for K and for V: THE
    write of every paged path. rows: (T, HK, D); an update is a whole
    stored row, so a donated pool is updated in place."""
    t = k_rows.shape[0]
    return (k_pages.at[page_idx, slot].set(
                k_rows.reshape(t, -1).astype(k_pages.dtype)),
            v_pages.at[page_idx, slot].set(
                v_rows.reshape(t, -1).astype(v_pages.dtype)))


def ragged_scatter_values(k_pages, v_pages, k_rows, v_rows, block_tables,
                          token_seq, positions):
    """Scatter packed ragged KV rows into the page pools.

    k_rows/v_rows: (T, HK, D) rows for the packed token axis;
    block_tables: (N, pps); token_seq: (T,) owning sequence per row
    (-1 = padding); positions: (T,) global position per row. Padding
    rows route to the trash page (never read). Returns the updated
    (k_pages, v_pages) — one row scatter (`set_rows`) for the whole
    mixed batch."""
    page_idx, slot = _row_targets(k_pages, block_tables, token_seq,
                                  positions)
    return set_rows(k_pages, v_pages, page_idx, slot, k_rows, v_rows)


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
    page_idx, slot = _row_targets(k_pages, block_tables, token_seq,
                                  positions)

    def _q(rows):
        rf = rows.astype(jnp.float32)
        amax = jnp.max(jnp.abs(rf), axis=(1, 2))            # (T,)
        qr = absmax_round_clip_values(rf, amax[:, None, None],
                                      KV_QMAX, out_dtype=jnp.int8)
        return qr, (amax / KV_QMAX).astype(jnp.float32)

    kq, ks_row = _q(k_rows)
    vq, vs_row = _q(v_rows)
    kp, vp = set_rows(k_pages, v_pages, page_idx, slot, kq, vq)
    ks = k_scale.at[page_idx, slot].set(ks_row)
    vs = v_scale.at[page_idx, slot].set(vs_row)
    return kp, vp, ks, vs


def ragged_paged_attention(q: Tensor, k_pages: Tensor, v_pages: Tensor,
                           query_start, query_len, context_len,
                           block_tables, scale=None, window=None,
                           block_q=DEFAULT_BLOCK_Q,
                           diffusion_block=1) -> Tensor:
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
        return ragged_paged_attention_values(
            qq, kk, vv, qs, ql, cl, bt, scale, window, block_q,
            diffusion_block=diffusion_block)
    return apply("ragged_paged_attention", fn, (q, k_pages, v_pages))
