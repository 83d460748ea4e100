"""Pipeline-ready Llama: decoder weights stacked along a leading layer dim.

≙ reference `LlamaForCausalLMPipe` (PaddleNLP) built on PipelineLayer/
LayerDesc («.../fleet/meta_parallel/parallel_layers/pp_layers.py», SURVEY.md
§2.3 PP row) — re-designed for TPU:

* Every decoder weight is ONE stacked parameter (L, ...). Without pp the
  stack runs under `lax.scan` (O(1) compile time for deep models — the
  idiomatic XLA form). With a 'pp' mesh axis the stack reshapes to
  (S, L/S, ...), stage-sharded, and runs the circular pipelined scan of
  distributed.fleet.pipeline (ppermute activation hops, remat per tick).
* Inside the pipeline the tensor-parallel ('mp') dims are composed
  Megatron-style BY HAND: the stage body sees local head/feature shards
  and issues the two psums per layer (after the attention out-proj and the
  ffn down-proj) — the manual-SPMD counterpart of Column/RowParallelLinear.
* Embedding / final norm / lm head live outside the pipeline (GSPMD
  placements); batch stays dp-sharded through the pipeline via x_spec.
* Decoder math is the values-level kernel path (fused rms_norm, fused
  rope, Pallas flash attention) — the same kernels the eager Llama uses.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.core.tensor import Tensor, apply

from .llama import LlamaConfig, precompute_rope, synthetic_lm_batch

__all__ = ["LlamaForCausalLMPipe", "shard_llama_pipe", "synthetic_lm_batch"]

_STACK_NAMES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "wgate", "wup",
                "wdown")


def _layer_values(lp, x, cos, sin, cfg, n_heads, n_kv_heads, psum_axis):
    """One decoder layer on (possibly mp-local) weight shards.
    lp: dict of one layer's weights; n_heads/n_kv_heads: LOCAL head counts;
    psum_axis: mesh axis name to reduce partial matmul products over, or
    None when weights are full."""
    from paddle_tpu.ops.norm_kernels import rms_norm_values
    from paddle_tpu.ops.rope import rope_values
    from paddle_tpu.ops.flash_attention import flash_attention_values

    b, s, h = x.shape
    dt = x.dtype
    hd = cfg.head_dim
    xn = rms_norm_values(x, lp["ln1"], cfg.rms_norm_eps)
    q = (xn @ lp["wq"].astype(dt)).reshape(b, s, n_heads, hd)
    k = (xn @ lp["wk"].astype(dt)).reshape(b, s, n_kv_heads, hd)
    v = (xn @ lp["wv"].astype(dt)).reshape(b, s, n_kv_heads, hd)
    # XLA rope (use_pallas=False) can fuse into the projections (see
    # models/llama.py apply_rope; not measured on this code)
    q = rope_values(q, cos, sin, use_pallas=False)
    k = rope_values(k, cos, sin, use_pallas=False)
    attn = flash_attention_values(q, k, v, causal=True)
    o = attn.reshape(b, s, -1) @ lp["wo"].astype(dt)   # partial over mp
    if psum_axis is not None:
        o = jax.lax.psum(o, psum_axis)
    x = x + o
    xn = rms_norm_values(x, lp["ln2"], cfg.rms_norm_eps)
    up = xn @ lp["wup"].astype(dt)
    gate = xn @ lp["wgate"].astype(dt)
    ffn = (jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up) \
        @ lp["wdown"].astype(dt)                        # partial over mp
    if psum_axis is not None:
        ffn = jax.lax.psum(ffn, psum_axis)
    return x + ffn


class LlamaForCausalLMPipe(nn.Layer):
    """Stacked-weight Llama causal LM with optional pipeline execution.

    Same forward contract as LlamaForCausalLM. When the active mesh has a
    'pp' axis of size > 1, the decoder stack runs as the SPMD pipeline
    (composing 'mp' tensor parallelism inside); otherwise it runs as one
    lax.scan over layers.
    """

    def __init__(self, cfg: LlamaConfig | None = None,
                 num_microbatches: int = 1,
                 virtual_pipeline_degree: int = 1,
                 pipeline_schedule: str = "1f1b"):
        super().__init__()
        cfg = cfg or LlamaConfig.llama3_8b()
        self.config = cfg
        self.num_microbatches = num_microbatches
        self.virtual_pipeline_degree = virtual_pipeline_degree
        # '1f1b' (default; ≙ reference PipelineParallel.train_batch,
        # S-bounded activation residency) or 'gpipe' (grad-of-scan).
        # Both compose with the interleaved virtual pipeline (V > 1);
        # 1f1b × V>1 runs the table-driven interleaved 1F1B schedule
        # (≙ PipelineParallelWithInterleave).
        if pipeline_schedule not in ("1f1b", "gpipe"):
            raise ValueError(f"unknown pipeline_schedule "
                             f"{pipeline_schedule!r}")
        self.pipeline_schedule = pipeline_schedule
        h = cfg.hidden_size
        hd = cfg.head_dim
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        i = cfg.intermediate_size
        L = cfg.num_hidden_layers
        self.embed_tokens = nn.Embedding(cfg.vocab_size, h)
        mk = self.create_parameter
        self.ln1 = mk((L, h), default_initializer=I.Constant(1.0))
        self.ln2 = mk((L, h), default_initializer=I.Constant(1.0))
        self.wq = mk((L, h, nh * hd), default_initializer=I.XavierNormal(
            fan_in=h, fan_out=nh * hd))
        self.wk = mk((L, h, nkv * hd), default_initializer=I.XavierNormal(
            fan_in=h, fan_out=nkv * hd))
        self.wv = mk((L, h, nkv * hd), default_initializer=I.XavierNormal(
            fan_in=h, fan_out=nkv * hd))
        self.wo = mk((L, nh * hd, h), default_initializer=I.XavierNormal(
            fan_in=nh * hd, fan_out=h))
        self.wgate = mk((L, h, i), default_initializer=I.XavierNormal(
            fan_in=h, fan_out=i))
        self.wup = mk((L, h, i), default_initializer=I.XavierNormal(
            fan_in=h, fan_out=i))
        self.wdown = mk((L, i, h), default_initializer=I.XavierNormal(
            fan_in=i, fan_out=h))
        self.norm = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.lm_head = nn.Linear(h, cfg.vocab_size, bias_attr=False)
        cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                   cfg.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def _decoder_params(self):
        return [getattr(self, n) for n in _STACK_NAMES]

    def forward(self, input_ids, labels=None, attention_mask=None):
        from paddle_tpu.distributed.mesh import get_mesh
        cfg = self.config
        mesh = get_mesh()
        use_pp = (mesh is not None and "pp" in mesh.dim_names
                  and mesh.get_dim_size("pp") > 1)
        mp_n = (mesh.get_dim_size("mp")
                if mesh is not None and "mp" in mesh.dim_names else 1)
        m = self.num_microbatches
        # training with pp: fuse norm+head+CE into the pipeline's last
        # stage (reduce_fn) — the (M, mb, S, H) output buffer and its
        # cross-stage broadcast collapse to (M,) scalars; logits are never
        # materialized (returned as None)
        fused = use_pp and labels is not None
        if labels is not None and not isinstance(labels, Tensor):
            labels = paddle.to_tensor(labels)

        def fn(ids, cos, sin, emb, *rest):
            if fused:
                norm_w, head_w, lab = rest[0], rest[1], rest[2]
                dec = rest[3:]
            else:
                dec = rest
            x = jnp.take(emb, ids, axis=0)
            cs = cos[:ids.shape[1]]
            sn = sin[:ids.shape[1]]
            params = dict(zip(_STACK_NAMES, dec))
            if use_pp:
                from paddle_tpu.distributed.fleet.pipeline import (
                    pipeline_1f1b, pipeline_forward)
                s_count = mesh.get_dim_size("pp")
                L = cfg.num_hidden_layers
                vp = self.virtual_pipeline_degree
                assert L % (s_count * vp) == 0, (L, s_count, vp)
                per = L // (s_count * vp)
                if vp > 1:
                    # interleaved: staged[s, v] = layers of global chunk
                    # v*S + s -> reshape (V, S, per, ...) then swap to
                    # (S, V, per, ...)
                    staged = {k: v.reshape(vp, s_count, per, *v.shape[1:])
                              .swapaxes(0, 1)
                              for k, v in params.items()}
                else:
                    staged = {k: v.reshape(s_count, per, *v.shape[1:])
                              for k, v in params.items()}
                mp = "mp" if mp_n > 1 else None
                pad = (None,) * (1 if vp > 1 else 0)
                specs = {
                    "ln1": P("pp", *pad, None, None),
                    "ln2": P("pp", *pad, None, None),
                    "wq": P("pp", *pad, None, None, mp),
                    "wk": P("pp", *pad, None, None, mp),
                    "wv": P("pp", *pad, None, None, mp),
                    "wo": P("pp", *pad, None, mp, None),
                    "wgate": P("pp", *pad, None, None, mp),
                    "wup": P("pp", *pad, None, None, mp),
                    "wdown": P("pp", *pad, None, mp, None),
                }
                dp = ("dp" if "dp" in mesh.dim_names
                      and mesh.get_dim_size("dp") > 1 else None)

                def stage_fn(sp, act, cs_, sn_):
                    # works for both fat stages (per = L/S layers) and
                    # interleaved chunks (per = L/(S*V)): the pipeline
                    # hands this fn exactly one stage's/chunk's layers
                    for li in range(sp["ln1"].shape[0]):
                        lp = {k: v[li] for k, v in sp.items()}
                        act = _layer_values(
                            lp, act, cs_, sn_, cfg,
                            cfg.num_attention_heads // mp_n,
                            cfg.num_key_value_heads // mp_n,
                            "mp" if mp_n > 1 else None)
                    return act

                if fused:
                    b = ids.shape[0]
                    lab_r = lab.reshape(m, b // m, lab.shape[1])
                    v_glob = cfg.vocab_size

                    def reduce_fn(y, idx, nw, hw, lr):
                        # per-microbatch (loss_sum, valid_count): the
                        # caller computes the GLOBAL token mean, so
                        # ignore_index imbalance across microbatches / dp
                        # shards cannot skew the weighting. The lm head is
                        # mp-sharded (hw: (H, V/mp) local shard); the
                        # logsumexp and the picked logit are assembled
                        # with pmax/psum over 'mp'.
                        from paddle_tpu.ops.norm_kernels import \
                            rms_norm_values
                        yn = rms_norm_values(y, nw, cfg.rms_norm_eps)
                        lg = (yn @ hw.astype(yn.dtype)).astype(
                            jnp.float32)            # (mb, S, V_local)
                        lg = lg.reshape(-1, lg.shape[-1])
                        lmb = jax.lax.dynamic_index_in_dim(
                            lr, idx, 0, keepdims=False).reshape(-1)
                        valid = lmb != -100
                        v_loc = lg.shape[-1]
                        # max-shift is gradient-neutral; stop_gradient
                        # keeps pmax (no differentiation rule) out of the
                        # autodiff graph without changing the lse grad
                        m_loc = jax.lax.stop_gradient(
                            jnp.max(lg, axis=-1))
                        if mp_n > 1:
                            m_glob = jax.lax.pmax(m_loc, "mp")
                        else:
                            m_glob = m_loc
                        z = jnp.sum(jnp.exp(lg - m_glob[:, None]), -1)
                        if mp_n > 1:
                            z = jax.lax.psum(z, "mp")
                            off = jax.lax.axis_index("mp") * v_loc
                        else:
                            off = 0
                        lse = m_glob + jnp.log(z)
                        li = jnp.maximum(lmb, 0) - off
                        in_rng = (li >= 0) & (li < v_loc)
                        picked = jnp.take_along_axis(
                            lg, jnp.clip(li, 0, v_loc - 1)[:, None],
                            -1)[:, 0] * in_rng
                        if mp_n > 1:
                            picked = jax.lax.psum(picked, "mp")
                        per_tok = jnp.where(valid, lse - picked, 0.0)
                        return jnp.stack([jnp.sum(per_tok),
                                          valid.sum().astype(jnp.float32)])

                    use_1f1b = self.pipeline_schedule == "1f1b"
                    pipe_call = (pipeline_1f1b if use_1f1b
                                 else pipeline_forward)
                    kw = {"virtual_chunks": vp}
                    stats = pipe_call(
                        stage_fn, staged, x, mesh, m, axis="pp",
                        extra_args=(cs, sn), param_specs=specs,
                        x_spec=P(dp, None, None),
                        reduce_fn=reduce_fn,
                        reduce_args=(norm_w, head_w, lab_r),
                        reduce_arg_specs=(P(None), P(None, mp),
                                          P(None, dp, None)),
                        reduce_mean_axes=("dp",) if dp else (),
                        reduce_shape=(2,), **kw)
                    # (M, 2) per-microbatch (sum, count) — dp-pmean'd,
                    # which preserves the sum/count ratio
                    return jnp.sum(stats[:, 0]) / jnp.maximum(
                        jnp.sum(stats[:, 1]), 1.0)

                x = pipeline_forward(
                    stage_fn, staged, x, mesh, m, axis="pp",
                    extra_args=(cs, sn), param_specs=specs,
                    x_spec=P(dp, None, None), virtual_chunks=vp)
            else:
                def body(act, lp):
                    return _layer_values(
                        lp, act, cs, sn, cfg, cfg.num_attention_heads,
                        cfg.num_key_value_heads, None), None
                if cfg.recompute and self.training:
                    # scan-form remat: residuals shrink from every wide
                    # per-layer intermediate to just the (L, B, S, H)
                    # layer inputs — structural in the jaxpr, so it
                    # holds on every backend (unlike loop-form remat,
                    # which XLA:CPU CSE can undo)
                    body = jax.checkpoint(body)
                x, _ = jax.lax.scan(body, x, params)
            return x

        args = [a if isinstance(a, Tensor) else paddle.to_tensor(a)
                for a in [input_ids, self.rope_cos, self.rope_sin]]
        if fused:
            loss = apply("llama_pipe_fused", fn,
                         tuple(args) + (self.embed_tokens.weight,
                                        self.norm.weight,
                                        self.lm_head.weight, labels)
                         + tuple(self._decoder_params()))
            return loss, None
        hidden = apply("llama_pipe_stack", fn,
                       tuple(args) + (self.embed_tokens.weight,)
                       + tuple(self._decoder_params()))
        hidden = self.norm(hidden)
        logits = self.lm_head(hidden)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]).astype("float32"),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def load_from_unstacked(self, model):
        """Copy weights from a LlamaForCausalLM (same config) for parity
        tests and checkpoint interop."""
        g = lambda t: t._value

        def setp(param, arr):
            param._value = jnp.asarray(arr).astype(param._value.dtype)

        setp(self.embed_tokens.weight, g(model.model.embed_tokens.weight))
        setp(self.norm.weight, g(model.model.norm.weight))
        setp(self.lm_head.weight, g(model.lm_head.weight))
        stacks = {k: [] for k in _STACK_NAMES}
        for lyr in model.model.layers:
            stacks["ln1"].append(g(lyr.input_layernorm.weight))
            stacks["ln2"].append(g(lyr.post_attention_layernorm.weight))
            stacks["wq"].append(g(lyr.self_attn.q_proj.weight))
            stacks["wk"].append(g(lyr.self_attn.k_proj.weight))
            stacks["wv"].append(g(lyr.self_attn.v_proj.weight))
            stacks["wo"].append(g(lyr.self_attn.o_proj.weight))
            stacks["wgate"].append(g(lyr.mlp.gate_proj.weight))
            stacks["wup"].append(g(lyr.mlp.up_proj.weight))
            stacks["wdown"].append(g(lyr.mlp.down_proj.weight))
        for k, v in stacks.items():
            setp(getattr(self, k), jnp.stack(v, 0))
        return self


def shard_llama_pipe(model: LlamaForCausalLMPipe, mesh):
    """GSPMD placements for the NON-pipelined tensors (embedding, head,
    final norm) and the stacked decoder weights' storage layout: layer dim
    over 'pp', feature dims over 'mp', ZeRO over 'sharding' where divisible.
    (The pipeline shard_map re-specs the decoder weights identically, so
    storage placement and program specs agree — no resharding at entry.)"""
    from paddle_tpu.distributed.mesh import Replicate, Shard, shard_tensor

    names = mesh.dim_names

    def put(p, **axis_dim):
        placements = [Replicate() for _ in names]
        for ax, d in axis_dim.items():
            if ax in names and mesh.get_dim_size(ax) > 1 and \
                    p._value.shape[d] % mesh.get_dim_size(ax) == 0:
                placements[names.index(ax)] = Shard(d)
        s = shard_tensor(p, mesh, placements)
        p._value = s._value
        p.dist_attr = s.dist_attr

    put(model.ln1, pp=0)
    put(model.ln2, pp=0)
    for nm in ("wq", "wk", "wv", "wgate", "wup"):
        put(getattr(model, nm), pp=0, mp=2, sharding=1)  # column pattern
    for nm in ("wo", "wdown"):
        put(getattr(model, nm), pp=0, mp=1, sharding=2)  # row pattern
    put(model.embed_tokens.weight, mp=0, sharding=1)
    put(model.lm_head.weight, mp=1, sharding=0)
    put(model.norm.weight)
    return model
