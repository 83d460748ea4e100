"""Paged attention — the q = 1 decode op over a block-table KV cache.

≙ reference serving-path attention: «masked_multihead_attention» +
«fused_multi_transformer» decode kernels and the paged-KV design the
L10 inference engine needs (SURVEY.md §1 L10, §7 step 6 "paged attention
(serving)"). TPU-native design: the KV cache lives in fixed-size pages
stored token-major, (num_pages, page_size, HK*D) — the layout of the
row scatter that writes them (ragged_paged_attention.py's docstring);
each sequence owns a row of page indices (block table). The Pallas
kernel walks a sequence's pages with the block table SCALAR-PREFETCHED,
so the page index feeds the BlockSpec index_map and Mosaic
double-buffers page fetches (one contiguous block a page, every KV head
in it; a head is a lane slice) from HBM — the TPU equivalent of vLLM's
gather-free paged attention. Online softmax accumulates across
pages in VMEM scratch; pages past the sequence's context length are
masked (their DMA still runs — grid shapes are static — but a cheaper
`pl.when` skips the FLOPs).

Decode only (q = 1 token/sequence); no VJP — serving has no backward.
Forward-parity is tested against a NumPy oracle and the contiguous-cache
`masked_multihead_attention` functional.

The ragged sibling (`ragged_paged_attention.py`) generalizes this grid
to mixed prefill+decode batches AND fixes the "DMA still runs" cost
above: dead pages route their index_map to the resident trash page, so
the pipeline skips the copy, and it is what the serving engine runs.
This op remains as the public `incubate.nn.functional.paged_attention`
and as the minimal q = 1 reference the ragged kernel's tests compare
with; the XLA fallback below is the decode special case of the ragged
masked-attention core.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu
from .ragged_paged_attention import (gather_pages, masked_page_attention,
                                     set_rows)
from ..core.tensor import Tensor, apply

NEG_INF = -1e30
LANES = 128
DEFAULT_PAGE_SIZE = 16


def _interpret() -> bool:
    return not on_tpu()


def _paged_kernel(ctx_ref, bt_ref,          # scalar-prefetched
                  q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, page_size, window):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_pages = pl.num_programs(1)
    _, hk, _, d = q_ref.shape

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    ctx = ctx_ref[b]
    # sliding window: the decode query (global position ctx-1) sees keys
    # in [ctx - window, ctx); pages wholly below the window start skip
    # their FLOPs (their DMA still runs — static grid)
    live = i * page_size < ctx
    if window is not None:
        live = live & ((i + 1) * page_size > ctx - window)

    @pl.when(live)
    def _page():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32)      # (G, D)
            # head h of the page: its lanes of every stored row
            k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
            v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
            s = mxu_dot(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (G, ps)
            pos = i * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            valid = pos < ctx
            if window is not None:
                valid = valid & (pos >= ctx - window)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h, :, :1]                  # (G, 1)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                    # (G, page_size)
            l_new = alpha * l_ref[h, :, :1] + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + mxu_dot(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (G, D)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(i == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_attention_values(q, k_pages, v_pages, context_lens, block_tables,
                           scale=None, window=None, use_kernel=None):
    """q: (B, H, D); k_pages/v_pages: (P, page_size, HK*D);
    context_lens: (B,) int32; block_tables: (B, pages_per_seq) int32.
    `window`: static sliding-window size — the decode query sees only
    keys in [ctx - window, ctx). `use_kernel`: None routes by platform;
    True forces the Pallas kernel (interpret mode off-TPU — the CI
    kernel/oracle parity path). Returns (B, H, D)."""
    b, h, d = q.shape
    _, page_size, row = k_pages.shape
    hk = row // d
    g = h // hk
    pps = block_tables.shape[1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    kernel = use_kernel if use_kernel is not None else on_tpu()
    if not kernel:
        return _paged_xla(q, k_pages, v_pages, context_lens, block_tables,
                          sc, window)

    qh = q.reshape(b, hk, g, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # a step is one page of one sequence, every KV head in it
        grid=(b, pps),
        in_specs=[
            pl.BlockSpec((1, hk, g, d), lambda bb, ii, ctx, bt:
                         (bb, 0, 0, 0)),
            pl.BlockSpec((1, page_size, row), lambda bb, ii, ctx, bt:
                         (bt[bb, ii], 0, 0)),
            pl.BlockSpec((1, page_size, row), lambda bb, ii, ctx, bt:
                         (bt[bb, ii], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hk, g, d), lambda bb, ii, ctx, bt:
                               (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hk, g, d), jnp.float32),
            pltpu.VMEM((hk, g, LANES), jnp.float32),
            pltpu.VMEM((hk, g, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=sc, page_size=page_size,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
        interpret=_interpret(),
    )(context_lens, block_tables, qh, k_pages, v_pages)
    return out.reshape(b, h, d)


def _paged_xla(q, k_pages, v_pages, context_lens, block_tables, scale,
               window=None):
    """Reference/CI path: the decode (q = 1) special case of the ragged
    masked-attention core — the gather is BOUNDED to the block-table
    prefix actually referenced (static trim on pps when the context
    lengths are concrete), and the masking math is the ONE shared copy
    in `ragged_paged_attention.masked_page_attention`."""
    b, h, d = q.shape
    hk = k_pages.shape[2] // d
    g = h // hk
    kc, vc = gather_pages(k_pages, v_pages, block_tables, hk,
                          context_lens=context_lens)
    ctx = jnp.asarray(context_lens, jnp.int32)
    out = masked_page_attention(q.reshape(b, hk, g, d), kc, vc,
                                ctx - 1, ctx, scale, window)
    return out.reshape(b, h, d)


def paged_attention(q: Tensor, k_pages: Tensor, v_pages: Tensor,
                    context_lens: Tensor, block_tables: Tensor,
                    scale=None, window=None) -> Tensor:
    """Eager/tape entry. Decode-only: output has no grad path."""
    cl = context_lens._value if isinstance(context_lens, Tensor) \
        else jnp.asarray(context_lens, jnp.int32)
    bt = block_tables._value if isinstance(block_tables, Tensor) \
        else jnp.asarray(block_tables, jnp.int32)

    def fn(qq, kk, vv):
        return paged_attention_values(qq, kk, vv, cl, bt, scale, window)
    return apply("paged_attention", fn, (q, k_pages, v_pages))


def paged_append_values(k_pages, v_pages, k, v, block_tables, positions):
    """Write one token per sequence into the page pools.

    k/v: (B, HK, D); positions: (B,) global position of the new token;
    block_tables: (B, pps). Returns the updated (k_pages, v_pages):
    the ragged path's row scatter (`set_rows`)."""
    page_size = k_pages.shape[1]
    page_idx = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1)[:, 0]
    return set_rows(k_pages, v_pages, page_idx, positions % page_size,
                    k, v)


class PagedKVCache:
    """Page-pool KV cache for serving (one per layer).

    ≙ the inference engine's cache manager role (SURVEY.md §1 L10): a
    fixed pool of (page_size x HK*D) pages plus per-sequence block
    tables. `append` writes one token per sequence and returns the
    updated cache (functional — jit/donation friendly).
    """

    def __init__(self, num_kv_heads, head_dim, num_pages, page_size=16,
                 dtype=jnp.bfloat16):
        self.page_size = page_size
        self.k_pages = jnp.zeros((num_pages, page_size,
                                  num_kv_heads * head_dim), dtype)
        self.v_pages = jnp.zeros_like(self.k_pages)

    def append(self, k, v, block_tables, positions):
        """k/v: (B, HK, D) one token per sequence; positions: (B,) global
        position of the new token; block_tables: (B, pps)."""
        kp, vp = paged_append_values(self.k_pages, self.v_pages, k, v,
                                     block_tables, positions)
        new = PagedKVCache.__new__(PagedKVCache)
        new.page_size = self.page_size
        new.k_pages, new.v_pages = kp, vp
        return new
