"""Bytes and operations a step needs, from shapes alone. Kept with the
benchmark so that a PR that claims a gain cannot change the yardstick.

Only the decode PROGRAM's HBM floor is here today: the trace names no
kernel of the program's yet (PERF.md, Open questions), so a per-kernel
roofline share cannot be taken."""
from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def dtype_bytes(name: str) -> int:
    return _DTYPE_BYTES[name]


def dense_decoder_params(m: dict) -> dict:
    """Parameter counts of a Llama-style dense decoder (GQA + SwiGLU,
    two RMSNorms a layer, no biases) from its config's sizes."""
    h, i, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = m.get("head_dim") or h // m["num_attention_heads"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    layer = h * q + 2 * h * kv + q * h + 3 * h * i + 2 * h
    embed = v * h
    head = 0 if m.get("tie_word_embeddings") else v * h
    return {"layer": layer, "layers": layer * m["num_hidden_layers"],
            "embed": embed, "head": head, "final_norm": h,
            "total": layer * m["num_hidden_layers"] + embed + head + h}


def kv_bytes_per_token(m: dict) -> int:
    """Bytes one context token holds in the KV cache, all layers."""
    hd = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"] * hd
            * dtype_bytes(m["torch_dtype"]))


def decode_step_bytes(m: dict, live_context_tokens: float,
                      live_slots: float) -> float:
    """The least a decode step must read from HBM: every layer's
    weights and the output head once (the embedding table is gathered,
    one row a live slot), and the K and V rows of every live context
    token once. Activations are left out: they are some KB a slot."""
    p = dense_decoder_params(m)
    wb = dtype_bytes(m["torch_dtype"])
    head = p["head"] or p["embed"]        # a tied head reads the table
    weights = (p["layers"] + head + p["final_norm"]) * wb
    gathered = live_slots * m["hidden_size"] * wb
    return weights + gathered + live_context_tokens * kv_bytes_per_token(m)


def decode_floor_s(m: dict, live_context_tokens: float, live_slots: float,
                   peaks: dict) -> float:
    """Seconds: `decode_step_bytes` at the chip's published HBM rate.
    At decode the step is bound by bytes, not by operations (2 FLOPs a
    weight a slot against 2 bytes a weight: far under the ridge for any
    batch the chip can hold), so the floor is the byte floor."""
    return decode_step_bytes(m, live_context_tokens, live_slots) \
        / peaks["hbm_bytes_per_s"]
