"""Bytes a decode step of a model of window and full attention layers
over routed SwiGLU experts (`mellum2-12b-a2_5b-instruct`) needs, from
the configuration's sizes alone (`benchmark/reference/mellum.py` has the
equations). The parameter counts are `roofline_blocks`' (the same
attention and expert layer); what is this file's is the K and V row of
the model's own `head_dim` (`roofline_flash.kv_row_bytes` takes the
head as `hidden_size // num_attention_heads`: 72 here, not 128) and the
rows each attending layer reads, which the program counts. Kept with
the benchmark so that a PR that claims a gain cannot change the
yardstick. Every count is a LOWER bound of what the step moves
(activations, the router's float32 copy, the sort's indices, the
logits and every re-read are left out), so a share of this floor
cannot pass 100 %."""
from __future__ import annotations

from benchmark.roofline import dtype_bytes
from benchmark.roofline_blocks import expert_bytes, params_by_part

__all__ = ["params_by_part", "kv_row_bytes", "decode_step_bytes"]


def kv_row_bytes(m: dict) -> int:
    """One token's K and V row in ONE layer's pools."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] \
        * dtype_bytes(m["torch_dtype"])


def decode_step_bytes(m: dict, kv_rows_read: float, live_slots: float,
                      experts_hit: float) -> float:
    """The least a decode step must move through HBM: every weight
    outside the experts once (of the embedding one row a live slot),
    the experts that were HIT (`experts_hit`, summed over the layers,
    from the program's counter), the head, the K and V rows its
    attention calls read (`kv_rows_read`: rows x layers, from the
    program's counter: a window layer reads at most a window's, a full
    layer the whole context) and the row each live slot writes in every
    layer."""
    p = params_by_part(m)
    wb = dtype_bytes(m["torch_dtype"])
    n = m["num_hidden_layers"]
    fixed = (n * (p["attention"] + p["router"] + p["norms"])
             + p["head"] + p["final_norm"]) * wb
    return (fixed + live_slots * m["hidden_size"] * wb
            + experts_hit * expert_bytes(m)
            + (kv_rows_read + live_slots * n) * kv_row_bytes(m))
