"""SDAR-MoE family (`model_type` `sdar_moe`): a Qwen3-MoE-shaped
decoder (GQA with an RMSNorm on every q and k head, rotary positions,
every layer a softmax top-k mixture of SwiGLU experts, no shared
expert) that GENERATES by diffusion over blocks: attention is causal
over blocks of `block_length` positions and full inside a block, and
the serving engine decides a block's masked positions over several
passes (`cache_spec.BlockDiffusionSpec`, which `generation_spec()`
returns). The equations are in `benchmark/reference/sdar_moe.py`'s
docstring; this file computes them for the serving engine:

* attention works on the engine's packed ragged batch through
  `ops/ragged_paged_attention.py` like Llama's, with the view's
  ``diffusion_block`` as the mask's block; a pass's rows are scattered
  into the pages before they are attended, so the block sees its own
  keys and a later pass overwrites them.
* the expert layer is TOLD which experts it holds (`experts_held` from
  `expert_offset`, of `num_experts`), as Nemotron-H's is, and runs the
  held ones through `ops/grouped_matmul.py` behind the one routed
  dispatch (`models/routed.py`); gate and up are ONE stacked operand, so
  a layer is two grouped matmuls.

`block_length` 1 is the causal model: `generation_spec()` is None and
the engine serves it a token a step. The forward pass without a cache
(tests, the logits of whole sequences) applies the same mask densely.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.core.tensor import Tensor, apply as _apply
from paddle_tpu.models.cache_spec import (BlockDiffusionSpec, KVSpec,
                                          ReportSpec)
from paddle_tpu.models.llama import (RaggedKVCacheView, apply_rope,
                                     precompute_rope, ragged_write_attend)
from paddle_tpu.models.routed import (combine_rows, report_counts,
                                      report_spec, route_rows)

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "swiglu_experts_values"]

_F32 = jnp.float32


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # the share of an expert-parallel deployment this program holds:
    # experts [expert_offset, expert_offset + experts_held); None = all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # generation by diffusion over blocks (cache_spec.BlockDiffusionSpec)
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    threshold: float = 0.9
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if not 0 <= self.expert_offset <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among the "
                f"{self.num_experts} routed experts")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is not in the "
                f"vocabulary of {self.vocab_size}")

    @staticmethod
    def tiny(**kw):
        """CPU test size."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=512, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32,
            mask_token_id=255, dtype="float32")
        base.update(kw)
        return SdarMoeConfig(**base)


def swiglu_experts_values(a, live, w_r, w_gu, w_down, *,
                          cfg: SdarMoeConfig):
    """The expert layer over packed rows a (T, hidden); `live` (T,)
    marks the rows that are tokens. w_gu (held, hidden, 2 width) holds
    each held expert's gate and up side by side, w_down (held, width,
    hidden). Returns (out (T, hidden), counts int32 (6,) in
    `SdarMoeExperts.cache_spec`'s order, chosen int32 (T, k))."""
    from paddle_tpu.ops.grouped_matmul import grouped_matmul_values
    dtype = a.dtype
    k, width = cfg.num_experts_per_tok, w_down.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(a.astype(_F32) @ w_r.astype(_F32), axis=-1)
    wts, chosen = jax.lax.top_k(s, k)
    if cfg.norm_topk_prob:
        wts = wts / jnp.sum(wts, axis=1, keepdims=True)
    r = route_rows(chosen, live, held=w_gu.shape[0],
                   offset=cfg.expert_offset, n_experts=cfg.num_experts)
    gu = grouped_matmul_values(a[r.src], w_gu, r.padded, r.block_m)
    act = (jax.nn.silu(gu[:, :width].astype(_F32))
           * gu[:, width:].astype(_F32)).astype(dtype)
    down = grouped_matmul_values(act, w_down, r.padded, r.block_m)
    return (combine_rows(down, wts, r).astype(dtype),
            report_counts(r, live, k), chosen.astype(jnp.int32))


class SdarMoeExperts(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        h, width, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        self.gate = nn.Linear(h, cfg.num_experts, bias_attr=False)
        self.experts = nn.Layer()
        std = math.sqrt(2.0 / (h + width))
        self.experts.gate_up_proj = self.create_parameter(
            (held, h, 2 * width), default_initializer=I.Normal(0.0, std))
        self.experts.down_proj = self.create_parameter(
            (held, width, h), default_initializer=I.Normal(0.0, std))

    def cache_spec(self) -> ReportSpec:
        return report_spec(self.cfg.num_experts_per_tok)

    def forward(self, x, live):
        """x (1, T, hidden); live (T,) bool. Returns (out, (counts,
        chosen)) as `cache_spec` orders and shapes them."""
        cfg = self.cfg

        def fn(a, *w):
            out, stats, chosen = swiglu_experts_values(a[0], live, *w,
                                                       cfg=cfg)
            return out[None], stats, chosen

        out, stats, chosen = _apply(
            "swiglu_experts", fn,
            (x, self.gate.weight, self.experts.gate_up_proj,
             self.experts.down_proj), multi_output=True)
        return out, (stats._value, chosen._value)


class SdarMoeAttention(nn.Layer):
    """GQA, no bias, an RMSNorm over every q and k head before the
    rotary embedding, causal over blocks."""

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.heads, self.kv_heads = cfg.num_attention_heads, \
            cfg.num_key_value_heads
        self.head_dim, self.block = cfg.head_dim, cfg.block_length
        h = cfg.hidden_size
        self.q_proj = nn.Linear(h, self.heads * self.head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(h, self.kv_heads * self.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(h, self.kv_heads * self.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(self.heads * self.head_dim, h,
                                bias_attr=False)
        self.q_norm = nn.RMSNorm(self.head_dim, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, cfg.rms_norm_eps)

    def cache_spec(self) -> KVSpec:
        return KVSpec(self.kv_heads, self.head_dim)

    def forward(self, x, cos, sin, view: Optional[RaggedKVCacheView]):
        b, s = x.shape[0], x.shape[1]
        q = self.q_norm(
            self.q_proj(x).reshape([b, s, self.heads, self.head_dim]))
        k = self.k_norm(
            self.k_proj(x).reshape([b, s, self.kv_heads, self.head_dim]))
        v = self.v_proj(x).reshape([b, s, self.kv_heads, self.head_dim])
        if view is None:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            blk = np.arange(s) // self.block
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=Tensor(jnp.asarray(
                    blk[None, :] <= blk[:, None])[None, None]))
            return self.o_proj(out.reshape([b, s, -1])), None
        from paddle_tpu.ops.rope import rope_rotate_values
        pos = view.positions

        def fn_rope(xx, c, s_):
            return rope_rotate_values(
                xx, c[pos].astype(_F32)[None, :, None, :],
                s_[pos].astype(_F32)[None, :, None, :])
        q = _apply("rope_ragged", fn_rope, (q, cos, sin))
        k = _apply("rope_ragged", fn_rope, (k, cos, sin))
        out, view = ragged_write_attend(q, k, v, view)
        return self.o_proj(out.reshape([1, s, -1])), view


class SdarMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = SdarMoeAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = SdarMoeExperts(cfg)


class SdarMoeModel(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [SdarMoeDecoderLayer(cfg)
             for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        cos, sin = precompute_rope(cfg.head_dim,
                                   cfg.max_position_embeddings,
                                   cfg.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)


class SdarMoeForCausalLM(nn.Layer):
    def __init__(self, cfg: SdarMoeConfig | None = None):
        super().__init__()
        cfg = cfg or SdarMoeConfig()
        self.config = cfg
        self.model = SdarMoeModel(cfg)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def cache_spec(self) -> list:
        """What each layer keeps, in the order the forward pass takes
        its views: a layer's keys and values, then its expert layer's
        report (models/cache_spec.py)."""
        return [spec for layer in self.model.layers
                for spec in (layer.self_attn.cache_spec(),
                             layer.mlp.cache_spec())]

    def generation_spec(self) -> Optional[BlockDiffusionSpec]:
        """How generation proceeds: by diffusion over blocks, or (block
        length 1, the causal model) a token a step."""
        cfg = self.config
        if cfg.block_length == 1:
            return None
        return BlockDiffusionSpec(cfg.block_length, cfg.mask_token_id,
                                  cfg.denoising_steps, cfg.remasking,
                                  cfg.threshold)

    def forward(self, input_ids, past_key_values=None, use_cache=False):
        """Logits of `input_ids`. With `past_key_values` (as
        `cache_spec` orders them: a layer's view, then None for its
        expert layer) the ids are ONE packed ragged batch (1, T) and the
        result is `(logits, new)`, `new` holding a layer's new view and
        its expert layer's report. Without, (B, S) whole sequences from
        nothing, every row of a block attending its whole block."""
        x = self.model.embed_tokens(input_ids)
        b, s = x.shape[0], x.shape[1]
        views = past_key_values
        cos, sin = self.model.rope_cos, self.model.rope_sin
        if views is None:
            live = jnp.ones((b * s,), bool)
        else:
            seq = views[0].token_seq
            live = (seq >= 0) & (views[0].query_len[jnp.maximum(seq, 0)] > 0)
        new = []
        for i, layer in enumerate(self.model.layers):
            out, got = layer.self_attn(
                layer.input_layernorm(x), cos, sin,
                None if views is None else views[2 * i])
            x = x + out
            out, report = layer.mlp(
                layer.post_attention_layernorm(x).reshape([1, b * s, -1]),
                live)
            x = x + out.reshape([b, s, -1])
            new += [got, report]
        logits = self.lm_head(self.model.norm(x))
        if use_cache and views is not None:
            return logits, new
        return logits
