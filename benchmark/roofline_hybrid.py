"""Bytes a decode step of a Nemotron-H hybrid needs, from the
configuration's sizes alone (`benchmark/reference/nemotron_h.py` has the
equations). Kept with the benchmark so that a PR that claims a gain
cannot change the yardstick. Every count is a LOWER bound of what the
step moves (activations, the router's float32 copy, the sort's indices
and every re-read are left out), so a share of this floor cannot pass
100 %."""
from __future__ import annotations

from benchmark.roofline import dtype_bytes


def kinds(m: dict) -> str:
    return m["hybrid_override_pattern"][:int(m["num_hidden_layers"])]


def mamba_params(m: dict) -> int:
    h, heads = m["hidden_size"], m["mamba_num_heads"]
    d_in = heads * m["mamba_head_dim"]
    ch = d_in + 2 * m["n_groups"] * m["ssm_state_size"]
    return (h * (d_in + ch + heads) + d_in * h          # in_proj, out_proj
            + ch * m["conv_kernel"] + ch                # conv weight, bias
            + 3 * heads + d_in + h)     # dt_bias, A_log, D; norms


def attention_params(m: dict) -> int:
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return h * q + 2 * h * kv + q * h + h


def expert_params(m: dict) -> int:
    """One routed expert: latent -> width -> latent, no gate."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def expert_layer_rest_params(m: dict) -> int:
    """An expert layer outside its routed experts: router and its
    correction, the two latent projections, the shared expert, the norm."""
    h = m["hidden_size"]
    return (h * m["n_routed_experts"] + m["n_routed_experts"]
            + 2 * h * m["moe_latent_size"]
            + 2 * h * m["moe_shared_expert_intermediate_size"] + h)


def state_bytes_per_slot(m: dict) -> int:
    """What one sequence keeps in the Mamba layers: the SSM state in
    float32 and the convolution's last K-1 inputs in the model's type."""
    heads = m["mamba_num_heads"]
    d_in = heads * m["mamba_head_dim"]
    ch = d_in + 2 * m["n_groups"] * m["ssm_state_size"]
    per_layer = heads * m["mamba_head_dim"] * m["ssm_state_size"] * 4 \
        + (m["conv_kernel"] - 1) * ch * dtype_bytes(m["torch_dtype"])
    return kinds(m).count("M") * per_layer


def kv_bytes_per_token(m: dict) -> int:
    return (2 * kinds(m).count("*") * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes(m["torch_dtype"]))


def expert_bytes(m: dict) -> int:
    return expert_params(m) * dtype_bytes(m["torch_dtype"])


def decode_step_bytes(m: dict, live_context_tokens: float,
                      live_slots: float, experts_hit: float) -> float:
    """The least a decode step must move through HBM: every weight
    outside the routed experts once (of the embedding one row a live
    slot), the routed experts that were HIT (`experts_hit`, summed over
    the expert layers, from the program's counter), the state of the
    live slots read and written, and the K and V rows of every live
    context token."""
    k = kinds(m)
    wb = dtype_bytes(m["torch_dtype"])
    h = m["hidden_size"]
    fixed = (k.count("M") * mamba_params(m)
             + k.count("*") * attention_params(m)
             + k.count("E") * expert_layer_rest_params(m)
             + m["vocab_size"] * h + h) * wb            # head, final norm
    return (fixed + live_slots * h * wb
            + experts_hit * expert_bytes(m)
            + 2 * live_slots * state_bytes_per_slot(m)
            + live_context_tokens * kv_bytes_per_token(m))
