"""Mellum 2 (`model_type` `mellum`): a Qwen3-MoE-shaped decoder whose
layers mix attention over a sliding window with attention over the
whole context (`layer_types`: three window layers to one full), each
kind with its own rotary table (`rope_parameters`: plain RoPE on the
window layers, YaRN on the full ones), and every layer a softmax top-k
mixture of SwiGLU experts with no shared expert. The equations are in
`benchmark/reference/mellum.py`'s docstring.

It is SDAR's causal model (`models/sdar.py` at `block_length` 1) with
two things chosen a layer: the attention's window and its rotary table.
So this file holds the configuration, the tables and which layer takes
which; the attention, the expert layer, the routed dispatch and the
forward pass are SDAR's. `cache_spec()` gives a window layer
`KVSpec(heads, head_dim, sliding_window)` and a full layer
`KVSpec(heads, head_dim)`, so the engine keeps a page group of each
(models/serving.py), beside the expert layers' reports.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from paddle_tpu import nn
from paddle_tpu.models.llama import precompute_rope_scaled
from paddle_tpu.models.sdar import SdarMoeDecoderLayer, SdarMoeForCausalLM

__all__ = ["MellumConfig", "MellumForCausalLM"]

_KINDS = ("sliding_attention", "full_attention")


def _published_layer_types():
    return ["sliding_attention"] * 3 + ["full_attention"]


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # the positions this program serves: its rotary tables' rows (the
    # engine refuses a longer max_seq_len). No equation reads the
    # published 131072: YaRN reads its own `factor` and
    # `original_max_position_embeddings`
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    # the published model's layers, one kind each; a program of fewer
    # layers runs the LAST `num_hidden_layers` of them (a pipeline's
    # last stage)
    layer_types: list = field(
        default_factory=lambda: _published_layer_types() * 7)
    sliding_window: int = 1024
    rope_parameters: dict = field(default_factory=lambda: {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}})
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    # the share of an expert-parallel deployment this program holds:
    # experts [expert_offset, expert_offset + experts_held); None = all
    experts_held: Optional[int] = None
    expert_offset: int = 0
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
        if not 0 < self.num_hidden_layers <= len(self.layer_types):
            raise ValueError(
                f"{self.num_hidden_layers} layers of a model of "
                f"{len(self.layer_types)} (layer_types)")
        for kind in set(self.layer_types):
            if kind not in _KINDS:
                raise ValueError(f"layer type {kind!r}: one of {_KINDS}")
            if kind not in self.rope_parameters:
                raise ValueError(f"no rope_parameters for {kind!r} layers")

    @property
    def block_length(self) -> int:
        """Causal: SDAR's attention over blocks of one position."""
        return 1

    def kind(self, i: int) -> str:
        """`sliding_attention` | `full_attention` of layer `i` of this
        program: the published model's layer `L - num_hidden_layers + i`."""
        return self.layer_types[len(self.layer_types)
                                - self.num_hidden_layers + i]

    @staticmethod
    def tiny(**kw):
        """CPU test size: a window of 8 and a YaRN table whose ramp lies
        inside the head (of its 8 rotary pairs, pair 0 keeps theta's
        frequency, 1 to 3 are blended, 4 to 7 divided by 4)."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=128, sliding_window=8,
            layer_types=_published_layer_types(),
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 10, "factor": 4,
                    "original_max_position_embeddings": 16,
                    "beta_fast": 32, "beta_slow": 1,
                    "attention_factor": 1.1386294361119891},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000}},
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            dtype="float32")
        base.update(kw)
        return MellumConfig(**base)


class MellumModel(nn.Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__()
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            SdarMoeDecoderLayer(
                cfg, cfg.sliding_window
                if cfg.kind(i) == "sliding_attention" else None)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        for kind in sorted({cfg.kind(i)
                            for i in range(cfg.num_hidden_layers)}):
            cos, sin = precompute_rope_scaled(
                cfg.head_dim, cfg.max_position_embeddings,
                cfg.rope_parameters[kind])
            self.register_buffer(f"rope_cos_{kind}", cos, persistable=False)
            self.register_buffer(f"rope_sin_{kind}", sin, persistable=False)


class MellumForCausalLM(SdarMoeForCausalLM):
    """SDAR's causal model over `MellumModel`: the forward pass, the
    cache specification and the generation specification (None: a
    token a step) are SDAR's."""
    body = MellumModel

    def __init__(self, cfg: MellumConfig | None = None):
        super().__init__(cfg or MellumConfig())

    def layer_rope(self, i: int):
        kind = self.config.kind(i)
        return (getattr(self.model, f"rope_cos_{kind}"),
                getattr(self.model, f"rope_sin_{kind}"))
