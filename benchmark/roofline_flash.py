"""Parameters and bytes of a Phi-4-mini-flash (SambaY) decode step, from
the configuration's sizes alone (`benchmark/reference/phi4flash.py` has
the equations). Kept with the benchmark so that a PR that claims a gain
cannot change the yardstick. Every count is a LOWER bound of what the
step moves (activations, the float32 copies of the scan's inputs and
every re-read are left out), so a share of this floor cannot pass
100 %."""
from __future__ import annotations

from benchmark.roofline import dtype_bytes
from benchmark.reference.phi4flash import kind


def kinds(m: dict) -> list:
    n = int(m["num_hidden_layers"])
    return [kind(i, n) for i in range(n)]


def params_by_layer(m: dict) -> dict:
    """Parameters of one layer of each kind (mixer, MLP and the two
    LayerNorms), of the embedding and of the final norm."""
    h, i = m["hidden_size"], m["intermediate_size"]
    d = h // m["num_attention_heads"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    d_in = m["mamba_expand"] * h
    n, k, r = m["mamba_d_state"], m["mamba_d_conv"], m["mamba_dt_rank"]
    rest = 3 * h * i + 4 * h            # W1 (h, 2i), W2; two LayerNorms
    lam = 4 * d + 2 * d                 # the lambda vectors, the sub-norm
    mamba = (h * 2 * d_in + d_in * k + d_in + d_in * (r + 2 * n)
             + r * d_in + d_in + d_in * n + d_in + d_in * h)
    return {
        "mamba": mamba + rest,
        "window": h * (q + 2 * kv) + (q + 2 * kv) + q * h + h + lam + rest,
        "gmu": 2 * h * d_in + rest,
        "cross": h * q + q + q * h + h + lam + rest,
        "embedding": m["vocab_size"] * h,
        "final_norm": 2 * h,
    }


def params_total(m: dict) -> int:
    p = params_by_layer(m)
    p["full"] = p["window"]
    return sum(p[k] for k in kinds(m)) + p["embedding"] + p["final_norm"]


def kv_row_bytes(m: dict) -> int:
    """One token's K and V row in ONE layer's pools."""
    d = m["hidden_size"] // m["num_attention_heads"]
    return 2 * m["num_key_value_heads"] * d * dtype_bytes(m["torch_dtype"])


def state_bytes_per_slot(m: dict) -> int:
    """What one sequence keeps in the Mamba layers: the SSM state in
    float32 and the convolution's last K-1 inputs in the model's type."""
    d_in = m["mamba_expand"] * m["hidden_size"]
    per_layer = d_in * m["mamba_d_state"] * 4 \
        + (m["mamba_d_conv"] - 1) * d_in * dtype_bytes(m["torch_dtype"])
    return kinds(m).count("mamba") * per_layer


def decode_step_bytes(m: dict, kv_rows_read: float,
                      live_slots: float) -> float:
    """The least a decode step must move through HBM: every weight once
    (the tied embedding is the head: read whole), the K and V rows its
    attention calls read (`kv_rows_read`: rows x layers, from the
    program's counter: a window layer reads a window's, the full layer
    and each layer that shares it the whole context's), and the live
    slots' state read and written."""
    return (params_total(m) * dtype_bytes(m["torch_dtype"])
            + kv_rows_read * kv_row_bytes(m)
            + 2 * live_slots * state_bytes_per_slot(m))
