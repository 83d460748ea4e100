"""Llama-3 family — the flagship pretraining model (north-star config #2/#3:
single-chip → DP → 4D hybrid; BASELINE.md). Mirrors the PaddleNLP llm/ recipe
shape (outside-repo zoo per SURVEY.md §1) built TPU-first:

* RMSNorm + RoPE + GQA + SwiGLU, bf16 params with fp32 norms.
* Attention via F.scaled_dot_product_attention (Pallas flash kernel when
  available, XLA fallback).
* 4D parallel named shardings (dp/sharding, mp, sep, pp) applied by
  `shard_llama` — Megatron column/row patterns expressed as placements only;
  XLA inserts the collectives (SURVEY.md §2.3).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.generation import GenerationMixin


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # activation checkpointing (≙ PaddleNLP recipe `recompute` toggle):
    # rematerialize each decoder layer in backward instead of saving
    # activations. policy: 'full' | 'dots' (save matmul outputs)
    recompute: bool = False
    recompute_policy: str = "full"
    # context parallelism over the mesh's `sep` axis (≙ PaddleNLP
    # RingFlashAttention / sep degree, SURVEY.md §2.3 CP row):
    # None | 'ring' | 'ulysses'
    sep_strategy: str | None = None
    # Mistral-style sliding-window local attention, honored on every
    # path: flash-kernel training, masked no-cache, chunked prefill with
    # cache, and single-token decode (cache positions outside the window
    # are masked out)
    sliding_window: int | None = None

    @staticmethod
    def llama3_8b():
        return LlamaConfig()

    @staticmethod
    def tiny():
        return LlamaConfig(vocab_size=512, hidden_size=128,
                           intermediate_size=256, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=256)

    @staticmethod
    def tiny_draft():
        """A draft-sized sibling of `tiny()` sharing its vocabulary
        and rope coverage — the ready-made target/draft pair for
        speculative decoding (`models.speculative`, the serving
        engine's `spec_decode=SpecConfig(...)`), so a demo or test
        does not have to hand-derive a compatible draft config."""
        return LlamaConfig(vocab_size=512, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=1,
                           num_attention_heads=2, num_key_value_heads=1,
                           max_position_embeddings=256)

    @staticmethod
    def small():
        """~110M for single-chip smoke benchmarking."""
        return LlamaConfig(vocab_size=32000, hidden_size=768,
                           intermediate_size=2048, num_hidden_layers=12,
                           num_attention_heads=12, num_key_value_heads=4,
                           max_position_embeddings=2048)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def num_params(self) -> int:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        kvh = self.num_key_value_heads * self.head_dim
        per_layer = (h * h + 2 * h * kvh + h * h) + 3 * h * i + 2 * h
        emb = v * h * (1 if self.tie_word_embeddings else 2)
        return self.num_hidden_layers * per_layer + emb + h


def precompute_rope(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    t = np.arange(max_len, dtype=np.float64)
    freqs = np.outer(t, inv)  # (S, D/2)
    return (paddle.to_tensor(np.cos(freqs).astype(np.float32)),
            paddle.to_tensor(np.sin(freqs).astype(np.float32)))


def apply_rope(x: Tensor, cos: Tensor, sin: Tensor, position_offset=0):
    """x: (B, S, H, D) — Pallas fused rope kernel (custom VJP = inverse
    rotation). ≙ fused_rotary_position_embedding
    «paddle/phi/kernels/fusion/» [U]. `position_offset` may be a traced
    scalar (decode-time position) — routed to an XLA dynamic-slice path
    — or a (B,) VECTOR of per-sequence positions with S == 1
    (continuous-batching decode: each slot rotates at its own angle)."""
    from paddle_tpu.core.tensor import apply as _apply
    from paddle_tpu.ops.rope import rope_values

    off = (position_offset._value
           if isinstance(position_offset, Tensor) else position_offset)

    if not isinstance(off, int) and jnp.ndim(off) == 1:
        from paddle_tpu.ops.rope import rope_rotate_values

        if x.shape[1] == 1:
            def fn_vec(v, c, s):
                cv = c[off].astype(jnp.float32)[:, None, None, :]
                sv = s[off].astype(jnp.float32)[:, None, None, :]
                return rope_rotate_values(v, cv, sv)  # (B,1,1,half) trig
            return _apply("rope_vec", fn_vec, (x, cos, sin))

        # (B,) offsets with S > 1 (speculative verify): row i of
        # sequence b rotates at angle position off[b] + i
        def fn_vec_s(v, c, s):
            rows = off[:, None] + jnp.arange(v.shape[1])[None, :]
            cv = c[rows].astype(jnp.float32)[:, :, None, :]  # (B,S,1,half)
            sv = s[rows].astype(jnp.float32)[:, :, None, :]
            return rope_rotate_values(v, cv, sv)
        return _apply("rope_vec_s", fn_vec_s, (x, cos, sin))

    # use_pallas=False: the XLA rotation can fuse into the surrounding
    # projections, where a standalone Pallas call is a fusion barrier
    # (a hypothesis — not measured on this code, docs/kernels.md); the
    # kernel remains for explicit use (and is required when fusing rope
    # INTO another kernel).
    def fn(v, c, s):
        return rope_values(v, c, s, off, use_pallas=False)
    return _apply("rope", fn, (x, cos, sin))


def _tp_repl(x: Tensor) -> Tensor:
    """Serving tensor parallelism's determinism fence (exact mode,
    serving/submesh.py): constrain `x` REPLICATED over the engine's
    active TP submesh so the next matmul (o_proj / down_proj / the
    sampling argmax's logits) runs without a partial-sum reduction —
    the all-gather this forces moves bits, never re-adds them, which
    is what keeps tp>=2 greedy outputs bit-identical to tp=1. Reads
    the trace-time context the engine scopes around its dispatches;
    a no-op (identity, no node) outside one."""
    from paddle_tpu.distributed.mesh import serving_tp, \
        serving_tp_replicate
    if serving_tp() is None:
        return x
    from paddle_tpu.core.tensor import apply as _apply
    return _apply("tp_replicate", serving_tp_replicate, (x,))


def _window_band(s: int, n_keys: int, offset: int,
                 window: int | None) -> np.ndarray:
    """(s, n_keys) bool: q row i (global position i + offset) may attend
    key j iff j <= i + offset (causal) and, with a sliding window,
    j > i + offset - window. The single source of truth for the band —
    every attention path derives its mask from here."""
    rows = np.arange(s)[:, None] + offset
    cols = np.arange(n_keys)[None, :]
    band = cols <= rows
    if window is not None:
        band &= cols > rows - window
    return band


def _update_kv_cache(cache: Tensor, new: Tensor, offset) -> Tensor:
    """Write `new` (B, S, HK, D) into the static cache (B, S_max, HK, D)
    at sequence position `offset` (python int, traced scalar, or a (B,)
    vector of per-sequence positions with S == 1)."""
    from paddle_tpu.core.tensor import apply as _apply
    import jax
    off = offset._value if isinstance(offset, Tensor) else offset

    if not isinstance(off, int) and jnp.ndim(off) == 1:
        s = new.shape[1]
        if s == 1:
            def fn_vec(c, n):
                b = c.shape[0]
                return c.at[jnp.arange(b), off].set(
                    n[:, 0].astype(c.dtype))
            return _apply("kv_cache_update_vec", fn_vec, (cache, new))

        # s > 1 with per-row offsets (speculative verify): row i of
        # sequence b lands at position off[b] + i
        def fn_vec_s(c, n):
            b = c.shape[0]
            rows = off[:, None] + jnp.arange(s)[None, :]      # (B, s)
            return c.at[jnp.arange(b)[:, None], rows].set(
                n.astype(c.dtype))
        return _apply("kv_cache_update_vec_s", fn_vec_s, (cache, new))

    def fn(c, n):
        return jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), off, axis=1)
    return _apply("kv_cache_update", fn, (cache, new))


class RaggedKVCacheView:
    """`past_key_value` for the RAGGED serving path (≙ the ragged
    paged-attention design, PAPERS.md arxiv 2604.15464): per-layer page
    pools (P, page_size, HK*D), the shared per-sequence block table
    (N, pps), and the descriptors of ONE packed mixed batch — decode
    steps, full prefills, chunk continuations, and prefix-cache suffix
    prefills all ride the same (1, T) token axis. `token_seq`/
    `positions` are per packed token (T,) — -1 marks padding rows,
    which scatter to the trash page; `query_start`/`query_len`/
    `context_lens` are per sequence (N,); `block_q` is the static
    q-block size the packer aligned `query_start` to (decode batches
    pass 1); `pages_bound` is the static gather trim the XLA fallback
    applies (None = full table); `diffusion_block` (static) is the
    block of a model that attends by blocks (`ragged_paged_attention`;
    1, the causal mask, for every other model).

    The speculative engine mode (`serving.SpecConfig`) rides this
    view twice over: the VERIFY pass packs each slot as a multi-token
    decode row (`query_len = k+1` at `context_len = pos+k+1` — the
    chunk-continuation descriptor shape, so no new attention math),
    and the draft scan drives the decode shape with `query_len = 0`
    rows for masked-out slots (no ownership -> zero output, KV
    trash-routed) — both exercised by tests/test_spec_decode.py."""

    def __init__(self, k_pages, v_pages, block_tables, token_seq,
                 positions, query_start, query_len, context_lens,
                 block_q=1, pages_bound=None, tp=None, k_scale=None,
                 v_scale=None, diffusion_block=1):
        self.k_pages = k_pages if isinstance(k_pages, Tensor) \
            else Tensor(k_pages)
        self.v_pages = v_pages if isinstance(v_pages, Tensor) \
            else Tensor(v_pages)
        # quantized serving (docs/serving.md "Quantized serving"):
        # int8 page pools ride with (P, page_size) f32 per-page-row
        # DEQUANT scale pools — the scatter quantizes on commit
        # (ragged_scatter_quantized), the attention dequantizes per
        # page in flight. None = full-width pools, the default.
        self.k_scale = None if k_scale is None else (
            k_scale if isinstance(k_scale, Tensor) else Tensor(k_scale))
        self.v_scale = None if v_scale is None else (
            v_scale if isinstance(v_scale, Tensor) else Tensor(v_scale))

        def _i32(x):
            return jnp.asarray(x._value if isinstance(x, Tensor) else x,
                               jnp.int32)
        self.block_tables = _i32(block_tables)
        self.token_seq = _i32(token_seq)
        self.positions = _i32(positions)
        self.query_start = _i32(query_start)
        self.query_len = _i32(query_len)
        self.context_lens = _i32(context_lens)
        self.block_q = int(block_q)
        self.diffusion_block = int(diffusion_block)
        self.pages_bound = None if pages_bound is None \
            else int(pages_bound)
        # tensor parallelism (serving/submesh.py): a (jax Mesh, axis)
        # pair routing the kernel path through its per-shard shard_map;
        # the pools arrive sharded on their KV-head axis, descriptors
        # and block tables stay replicated scalars
        self.tp = tp


def ragged_write_attend(q, k, v, view: RaggedKVCacheView, window=None,
                        scale=None):
    """The ragged path's two cache calls for full-width pools, as every
    servable attention layer makes them: ONE scatter of the packed
    batch's new K/V rows (1, T, HK, D) into the view's pages, then
    ragged paged attention of q (1, T, H, D) over them under the
    view's descriptors and mask. Returns (out (1, T, H, D), the view
    over the written pools). With `k` and `v` None it only ATTENDS: a
    layer that reads the keys and values another layer stored
    (`cache_spec.SharedKVSpec`) passes that layer's returned view, and
    gets (out, None)."""
    from paddle_tpu.core.tensor import apply as _apply
    from paddle_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention_values, ragged_scatter_values)
    bt = view.block_tables
    kp, vp = view.k_pages, view.v_pages
    if k is not None:
        def fn_scatter(kp, vp, kk, vv):
            return ragged_scatter_values(kp, vp, kk[0], vv[0], bt,
                                         view.token_seq, view.positions)
        kp, vp = _apply("ragged_kv_scatter", fn_scatter, (kp, vp, k, v),
                        multi_output=True)

    def fn_attn(qq, kp_, vp_):
        return ragged_paged_attention_values(
            qq[0], kp_, vp_, view.query_start, view.query_len,
            view.context_lens, bt, scale=scale, window=window,
            block_q=view.block_q, pages_bound=view.pages_bound,
            tp=view.tp, diffusion_block=view.diffusion_block)[None]
    out = _apply("ragged_paged_attention", fn_attn, (q, kp, vp))
    if k is None:
        return out, None
    return out, RaggedKVCacheView(
        kp, vp, bt, view.token_seq, view.positions, view.query_start,
        view.query_len, view.context_lens, view.block_q,
        view.pages_bound, tp=view.tp,
        diffusion_block=view.diffusion_block)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h = cfg.hidden_size
        hd = cfg.head_dim
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = hd
        self.sep_strategy = getattr(cfg, "sep_strategy", None)
        self.sliding_window = getattr(cfg, "sliding_window", None)
        self.q_proj = nn.Linear(h, self.num_heads * hd, bias_attr=False)
        self.k_proj = nn.Linear(h, self.num_kv_heads * hd, bias_attr=False)
        self.v_proj = nn.Linear(h, self.num_kv_heads * hd, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * hd, h, bias_attr=False)

    def forward(self, x, cos, sin, attention_mask=None,
                past_key_value=None, position_offset=0, use_cache=False):
        """`past_key_value`: (k_cache, v_cache) of static shape
        (B, S_max, HK, D); the new k/v are written at `position_offset`
        (≙ the reference decode path «masked_multihead_attention» /
        «fused_multi_transformer» KV-cache convention, SURVEY.md §2.1
        fused row). Returns out, or (out, (k_cache, v_cache)) when
        use_cache."""
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if isinstance(past_key_value, RaggedKVCacheView):
            # rope happens inside (per-token angles from the view):
            # the generic apply_rope offset conventions cannot express
            # a packed ragged batch
            return self._forward_ragged(q, k, v, cos, sin,
                                        past_key_value, use_cache, b, s)
        q = apply_rope(q, cos, sin, position_offset)
        k = apply_rope(k, cos, sin, position_offset)
        if past_key_value is not None:
            k_cache, v_cache = past_key_value
            k_cache = _update_kv_cache(k_cache, k, position_offset)
            v_cache = _update_kv_cache(v_cache, v, position_offset)
            cur_len = position_offset + s
            win = self.sliding_window
            if s == 1:
                # decode: one new token attends every cached position < len
                # inside the sliding window; attention_mask ((B, S_cache)
                # bool) excludes e.g. padding
                out = F.masked_multihead_attention(
                    q, k_cache, v_cache, seq_len=cur_len,
                    attn_mask=attention_mask, window_size=win)
            else:
                # (chunked) prefill: end-aligned causal over the filled
                # prefix — q row i attends keys <= i + offset (the flash
                # kernel's native decode convention), window-banded when
                # sliding_window is set
                if not isinstance(position_offset, int):
                    # traced scalar / (B,) vector offsets (speculative
                    # VERIFY: the target scores k drafted tokens in one
                    # forward): attention over the FULL static cache with
                    # an in-graph end-aligned causal mask — no dynamic
                    # slicing, so the offsets may differ per row
                    off = (position_offset._value
                           if isinstance(position_offset, Tensor)
                           else jnp.asarray(position_offset, jnp.int32))
                    offv = jnp.broadcast_to(jnp.atleast_1d(off), (b,))
                    s_max = k_cache.shape[1]
                    rows = offv[:, None] + jnp.arange(s)[None, :]
                    cols = jnp.arange(s_max)
                    vmask = cols[None, None, None, :] \
                        <= rows[:, None, :, None]      # (B, 1, s, S_max)
                    if win is not None:
                        vmask = vmask & (cols[None, None, None, :]
                                         > rows[:, None, :, None] - win)
                    if attention_mask is not None:
                        am = attention_mask
                        if not isinstance(am, Tensor):
                            am = paddle.to_tensor(am)
                        amv = am._value.astype(bool)
                        if amv.shape[-1] < s_max:
                            # conventional (B, prompt-width) key-validity
                            # masks cover only the prefill window; cache
                            # cells beyond it hold decode/verify tokens,
                            # which are valid keys
                            amv = jnp.pad(
                                amv,
                                ((0, 0), (0, s_max - amv.shape[-1])),
                                constant_values=True)
                        vmask = vmask & amv[:, None, None, :s_max]
                    out = F.scaled_dot_product_attention(
                        q, k_cache, v_cache, attn_mask=Tensor(vmask))
                    out = self.o_proj(out.reshape([b, s, -1]))
                    if use_cache:
                        return out, (k_cache, v_cache)
                    return out
                mask = None
                if attention_mask is not None or win is not None:
                    band = _window_band(s, cur_len, position_offset, win)
                    mask = paddle.to_tensor(band[None, None])  # (1,1,S,L)
                    if attention_mask is not None:
                        # (B, cur_len) key-validity mask -> (B,1,1,cur_len)
                        am = attention_mask
                        if not isinstance(am, Tensor):
                            am = paddle.to_tensor(am)
                        am = am[:, :cur_len].astype("bool") \
                            .unsqueeze(1).unsqueeze(1)
                        mask = paddle.logical_and(mask, am)
                out = F.scaled_dot_product_attention(
                    q, k_cache[:, :cur_len], v_cache[:, :cur_len],
                    attn_mask=mask, is_causal=mask is None)
            out = self.o_proj(out.reshape([b, s, -1]))
            if use_cache:
                return out, (k_cache, v_cache)
            return out
        if self.sep_strategy is not None:
            from paddle_tpu.distributed.mesh import get_mesh
            mesh = get_mesh()
            if (mesh is not None and "sep" in mesh.dim_names
                    and mesh.get_dim_size("sep") > 1):
                from paddle_tpu.distributed import ring_attention as ra
                attn_fn = (ra.ulysses_flash_attention
                           if self.sep_strategy == "ulysses"
                           else ra.ring_flash_attention)
                out = attn_fn(q, k, v, causal=True)
                return self.o_proj(out.reshape([b, s, -1]))
        if self.sliding_window is not None:
            if attention_mask is None:
                from paddle_tpu.ops.flash_attention import flash_attention
                out = flash_attention(q, k, v, causal=True,
                                      window_size=self.sliding_window)
                return self.o_proj(out.reshape([b, s, -1]))
            # combine the window band with the user mask (bool masks AND,
            # additive masks get -inf outside the band); is_causal still
            # applies the upper-triangular bound
            am = attention_mask
            if not isinstance(am, Tensor):
                am = paddle.to_tensor(am)
            band = _window_band(s, s, 0, self.sliding_window)
            if am.dtype == paddle.bool:
                if am.ndim == 2:          # (B, S) key-validity mask
                    am = am.unsqueeze(1).unsqueeze(1)
                am = paddle.logical_and(
                    am, paddle.to_tensor(band[None, None]))
            else:
                am = am + paddle.to_tensor(
                    np.where(band, 0.0, -1e30)[None, None]
                    .astype(np.float32)).astype(am.dtype)
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=am,
                                                 is_causal=True)
            return self.o_proj(out.reshape([b, s, -1]))
        out = F.scaled_dot_product_attention(q, k, v,
                                             attn_mask=attention_mask,
                                             is_causal=True)
        return self.o_proj(out.reshape([b, s, -1]))

    def _forward_ragged(self, q, k, v, cos, sin, view, use_cache, b, s):
        """One packed mixed batch (decode + prefills) through the page
        table: per-token rope, ONE scatter of every new KV row into the
        pages (padding rows trash-route), then ragged paged attention
        with per-sequence (query_start, query_len, context_len)
        descriptors. q/k/v arrive pre-rope as (1, T, heads, D)."""
        from paddle_tpu.core.tensor import apply as _apply
        from paddle_tpu.ops.rope import rope_rotate_values
        from paddle_tpu.ops.ragged_paged_attention import (
            ragged_paged_attention_values, ragged_scatter_quantized,
            ragged_scatter_values)
        if b != 1:
            raise ValueError(
                "ragged KV cache wants a packed (1, T, ...) batch")
        pos = view.positions
        seq = view.token_seq
        bt = view.block_tables

        def fn_rope(x, c, s_):
            cv = c[pos].astype(jnp.float32)[None, :, None, :]
            sv = s_[pos].astype(jnp.float32)[None, :, None, :]
            return rope_rotate_values(x, cv, sv)
        q = _apply("rope_ragged", fn_rope, (q, cos, sin))
        k = _apply("rope_ragged", fn_rope, (k, cos, sin))

        win = self.sliding_window
        quantized = view.k_scale is not None
        if quantized:
            # quantized pools: the scatter quantizes on commit and the
            # attention reads the POST-scatter int8 pages + scales —
            # so a prefill row attends exactly the quantized values a
            # later decode step would, the invariant the chaos drills'
            # bit-identity rests on
            def fn_scatter_q(kp, vp, ks, vs, kk, vv):
                return ragged_scatter_quantized(kp, vp, ks, vs, kk[0],
                                                vv[0], bt, seq, pos)
            kp_new, vp_new, ks_new, vs_new = _apply(
                "ragged_kv_scatter_q", fn_scatter_q,
                (view.k_pages, view.v_pages, view.k_scale,
                 view.v_scale, k, v), multi_output=True)

            def fn_attn_q(qq, kp, vp, ks, vs):
                return ragged_paged_attention_values(
                    qq[0], kp, vp, view.query_start, view.query_len,
                    view.context_lens, bt, window=win,
                    block_q=view.block_q,
                    pages_bound=view.pages_bound, tp=view.tp,
                    k_scale=ks, v_scale=vs)[None]
            out = _apply("ragged_paged_attention", fn_attn_q,
                         (q, kp_new, vp_new, ks_new, vs_new))
        else:
            def fn_scatter(kp, vp, kk, vv):
                return ragged_scatter_values(kp, vp, kk[0], vv[0], bt,
                                             seq, pos)
            kp_new, vp_new = _apply(
                "ragged_kv_scatter", fn_scatter,
                (view.k_pages, view.v_pages, k, v), multi_output=True)
            ks_new = vs_new = None

            def fn_attn(qq, kp, vp):
                return ragged_paged_attention_values(
                    qq[0], kp, vp, view.query_start, view.query_len,
                    view.context_lens, bt, window=win,
                    block_q=view.block_q,
                    pages_bound=view.pages_bound, tp=view.tp)[None]
            out = _apply("ragged_paged_attention", fn_attn,
                         (q, kp_new, vp_new))
        # TP serving: each device computed ITS heads; gather them
        # before the o_proj row matmul (exact-mode fence)
        out = self.o_proj(_tp_repl(out.reshape([1, s, -1])))
        if use_cache:
            return out, RaggedKVCacheView(
                kp_new, vp_new, bt, seq, pos, view.query_start,
                view.query_len, view.context_lens, view.block_q,
                view.pages_bound, tp=view.tp, k_scale=ks_new,
                v_scale=vs_new)
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                   bias_attr=False)

    def forward(self, x):
        h = F.silu(self.gate_proj(x)) * self.up_proj(x)
        # TP serving: gather the column-sharded activation before the
        # row matmul (exact-mode fence; no-op otherwise)
        return self.down_proj(_tp_repl(h))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cos, sin, attention_mask=None,
                past_key_value=None, position_offset=0, use_cache=False):
        attn = self.self_attn(
            self.input_layernorm(x), cos, sin, attention_mask,
            past_key_value=past_key_value,
            position_offset=position_offset,
            use_cache=use_cache)
        new_kv = None
        if use_cache and past_key_value is not None:
            attn, new_kv = attn
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        if use_cache and past_key_value is not None:
            return x, new_kv
        return x


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        cos, sin = precompute_rope(cfg.head_dim,
                                   cfg.max_position_embeddings,
                                   cfg.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def forward(self, input_ids, attention_mask=None,
                past_key_values=None, position_offset=0, use_cache=False):
        x = self.embed_tokens(input_ids)
        if past_key_values is not None:
            new_caches = []
            for layer, kv in zip(self.layers, past_key_values):
                out = layer(x, self.rope_cos, self.rope_sin, attention_mask,
                            past_key_value=kv,
                            position_offset=position_offset,
                            use_cache=use_cache)
                if use_cache:
                    x, new_kv = out
                    new_caches.append(new_kv)
                else:
                    x = out
            x = self.norm(x)
            return (x, new_caches) if use_cache else x
        if self.config.recompute and self.training:
            from paddle_tpu.distributed.fleet.utils import recompute
            for layer in self.layers:
                x = recompute(layer, x, self.rope_cos, self.rope_sin,
                              attention_mask,
                              policy=self.config.recompute_policy)
        else:
            for layer in self.layers:
                x = layer(x, self.rope_cos, self.rope_sin, attention_mask)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg: LlamaConfig | None = None):
        super().__init__()
        cfg = cfg or LlamaConfig.llama3_8b()
        self.config = cfg
        self.model = LlamaModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def cache_spec(self) -> list:
        """What each layer keeps (models/cache_spec.py): keys and
        values, in every layer, of the last `sliding_window` positions
        where the config has one."""
        from paddle_tpu.models.cache_spec import KVSpec
        cfg = self.config
        return [KVSpec(cfg.num_key_value_heads, cfg.head_dim,
                       getattr(cfg, "sliding_window", None))] \
            * cfg.num_hidden_layers

    def _logits(self, hidden):
        if self.lm_head is not None:
            # TP serving: lm_head is vocab-sharded; gather the logits
            # so the greedy argmax reduces on every device identically
            return _tp_repl(self.lm_head(hidden))
        return _tp_repl(paddle.matmul(hidden,
                                      self.model.embed_tokens.weight,
                                      transpose_y=True))

    def forward(self, input_ids, labels=None, attention_mask=None,
                past_key_values=None, position_offset=0, use_cache=False):
        out = self.model(input_ids, attention_mask,
                         past_key_values=past_key_values,
                         position_offset=position_offset,
                         use_cache=use_cache)
        caches = None
        if use_cache and past_key_values is not None:
            hidden, caches = out
        else:
            hidden = out
        logits = self._logits(hidden)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size])
                .astype("float32"),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        if caches is not None:
            return logits, caches
        return logits


# -- 4D sharding recipe ------------------------------------------------------
def shard_llama(model: LlamaForCausalLM, mesh) -> LlamaForCausalLM:
    """Apply the 4D-hybrid placements (≙ PaddleNLP Llama fleet recipe,
    SURVEY.md §3.2) to every parameter:

    * attention q/o + mlp gate/up → column pattern (out dim on 'mp')
    * attention k/v follow q;    mlp down → row pattern (in dim on 'mp')
    * embeddings/lm_head vocab dim on 'mp'
    * every 2-D weight additionally ZeRO-sharded over 'sharding' on the
      other dim when divisible; 'dp' shards only the batch; 'sep' only
      activations (sequence dim); 'pp' stages via layer index.
    """
    from paddle_tpu.distributed.mesh import (Replicate, Shard, shard_tensor)

    names = mesh.dim_names

    def put(p, **axis_dim):
        placements = [Replicate() for _ in names]
        for ax, d in axis_dim.items():
            if ax in names and mesh.get_dim_size(ax) > 1:
                if p._value.shape[d] % mesh.get_dim_size(ax) != 0:
                    continue
                placements[names.index(ax)] = Shard(d)
        sharded = shard_tensor(p, mesh, placements)
        p._value = sharded._value
        p.dist_attr = sharded.dist_attr

    for lname, p in model.named_parameters():
        nm = lname.lower()
        if "embed_tokens" in nm or "lm_head" in nm:
            put(p, mp=0 if "embed_tokens" in nm else 1, sharding=1
                if "embed_tokens" in nm else 0)
        elif any(k in nm for k in ("q_proj", "k_proj", "v_proj", "gate_proj",
                                   "up_proj")):
            put(p, mp=1, sharding=0)      # column parallel
        elif any(k in nm for k in ("o_proj", "down_proj")):
            put(p, mp=0, sharding=1)      # row parallel
        else:  # norms
            put(p)
    return model


def synthetic_lm_batch(batch_size, seq_len, vocab_size, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab_size, (batch_size, seq_len + 1),
                       dtype=np.int32)
    return (paddle.to_tensor(ids[:, :-1]),
            paddle.to_tensor(ids[:, 1:].astype(np.int32)))
