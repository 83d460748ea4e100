"""Bytes a PASS of an SDAR-MoE block model needs, from the
configuration's sizes alone (`benchmark/reference/sdar_moe.py` has the
equations): a pass runs `block_length` rows of every live slot through
every layer. Kept with the benchmark so that a PR that claims a gain
cannot change the yardstick. Every count is a LOWER bound of what the
pass moves (activations, the router's float32 copy, the sort's indices,
the logits and every re-read are left out), so a share of this floor
cannot pass 100 %."""
from __future__ import annotations

from benchmark.roofline import dtype_bytes


def params_by_part(m: dict) -> dict:
    """Parameter counts: of one layer by part, of the embedding, the
    head and the final norm, and of the whole model as configured."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    held = m.get("experts_held") or m["num_experts"]
    part = {
        "attention": h * q + 2 * h * kv + q * h,
        "router": h * m["num_experts"],
        # two norms over the hidden width, one over a q and a k head
        "norms": 2 * h + 2 * d,
        "expert": 3 * h * m["moe_intermediate_size"],
    }
    part["experts"] = held * part["expert"]
    part["layer"] = part["attention"] + part["router"] + part["norms"] \
        + part["experts"]
    part["embedding"] = part["head"] = m["vocab_size"] * h
    part["final_norm"] = h
    part["total"] = m["num_hidden_layers"] * part["layer"] \
        + part["embedding"] + part["head"] + part["final_norm"]
    return part


def kv_bytes_per_token(m: dict) -> int:
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes(m["torch_dtype"]))


def expert_bytes(m: dict) -> int:
    return params_by_part(m)["expert"] * dtype_bytes(m["torch_dtype"])


def experts_a_dispatch(m: dict) -> int:
    """Held experts a dispatch, over the layers: what the program's
    counter adds to hit + idle every dispatch."""
    return m["num_hidden_layers"] * (m.get("experts_held")
                                     or m["num_experts"])


def pass_bytes(m: dict, live_context_tokens: float, live_slots: float,
               experts_hit: float) -> float:
    """The least a pass must move through HBM: every weight outside the
    experts once (of the embedding one row a dispatched row), the
    experts that were HIT (`experts_hit`, summed over the layers, from
    the program's counter), the head, the K and V rows of every live
    context token read, and `block_length` rows a live slot written."""
    p = params_by_part(m)
    wb = dtype_bytes(m["torch_dtype"])
    rows = live_slots * m["block_length"]
    fixed = (m["num_hidden_layers"]
             * (p["attention"] + p["router"] + p["norms"])
             + p["head"] + p["final_norm"]) * wb
    return (fixed + rows * m["hidden_size"] * wb
            + experts_hit * expert_bytes(m)
            + (live_context_tokens + rows) * kv_bytes_per_token(m))


def hits_a_pass(m: dict, hit: float, idle: float, passes: float):
    """Experts hit a pass, from the window's growth of the program's
    counter (`hit`, `idle`) and its count of passes. Every dispatch adds
    `experts_a_dispatch` to hit + idle, so the counter gives the
    dispatches; those that were no pass were admissions, taken to have
    hit EVERY held expert, which leaves the passes the fewest hits they
    can have had. None where there is nothing to read."""
    each = experts_a_dispatch(m)
    if hit + idle <= 0 or not each or not passes:
        return None
    admissions = (hit + idle) / each - passes
    return max(hit - admissions * each, 0.0) / passes
