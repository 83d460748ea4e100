"""paddle_tpu.nn.quant — weight-only quantization + int8 execution.

≙ reference `paddle.nn.quant.weight_quantize` / `weight_only_linear` /
`llm_int8_linear` (the cuBLASLt int8 serving path, SURVEY.md §2.1 fused
rows + «python/paddle/nn/quant/») — TPU-native:

* W8A8 executes on the MXU's native int8 systolic path: int8×int8 →
  int32 via `lax.dot_general(..., preferred_element_type=int32)`, then
  one fp rescale. This is the int8 MXU mode (datasheet 2x-peak;
  its speed against bf16 is not measured on this code).
* weight-only int8/int4 targets decode (HBM-bandwidth-bound): weights
  live in HBM at 1/2 or 1/4 the bytes and dequantize on the fly into
  the bf16 matmul (XLA fuses the dequant into the dot's operand read).
  int4 packs two nibbles per int8 along the in-feature dim; scales are
  group-wise (`group_size` input rows share one scale per out-channel).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, apply, to_tensor

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear",
           "llm_int8_linear", "int8_dot", "quantize_activation_dynamic",
           "absmax_round_clip_values"]

_Q8 = 127.0
_Q4 = 7.0


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def absmax_round_clip_values(v, absmax, qmax, out_dtype=None,
                             round_fn=jnp.round):
    """THE absmax round-clip quantization core:
    ``q = clip(round(v / max(absmax, 1e-9) * qmax), -qmax-1, qmax)``.

    Every quantizer in the repo — `weight_quantize_values`,
    `quantize_activation_dynamic_values`, `quantization.quantize_linear`,
    `quantization.fake_quant`, the serving engine's weight and KV-page
    quantization (`ops/quant_matmul.py`,
    `ops/ragged_paged_attention.ragged_scatter_quantized`) — routes
    through this one function, so the rounding mode, the tiny-scale
    guard, and the asymmetric clip (``-qmax-1`` keeps int8's -128
    reachable) cannot drift between paths. ``absmax`` broadcasts
    against ``v``; ``round_fn`` lets QAT substitute the
    straight-through-estimator round without forking the core;
    ``out_dtype=None`` returns the float lattice values (fake-quant
    callers re-scale them)."""
    s = jnp.maximum(absmax, 1e-9)
    q = jnp.clip(round_fn(v / s * qmax), -qmax - 1, qmax)
    return q if out_dtype is None else q.astype(out_dtype)


# -- value-level kernels (usable inside shard_map / models) ------------
def weight_quantize_values(w, algo: str = "weight_only_int8",
                           group_size: int = -1):
    """w: (K, N) float -> (quantized storage, scales).

    int8: storage (K, N) int8; int4: storage (K//2, N) int8, two
    nibbles per byte (row 2i in low nibble, 2i+1 in high). scales:
    (N,) for group_size=-1 (per-channel) else (K//group_size, N).
    """
    k, n = w.shape
    bits = 4 if "int4" in algo else 8
    qmax = _Q4 if bits == 4 else _Q8
    g = k if group_size in (-1, None) else int(group_size)
    if k % g:
        raise ValueError(f"group_size {g} must divide in-features {k}")
    wg = w.reshape(k // g, g, n).astype(jnp.float32)
    scales = jnp.max(jnp.abs(wg), axis=1)                 # (K/g, N)
    scales = jnp.maximum(scales, 1e-9)
    q = absmax_round_clip_values(wg, scales[:, None, :], qmax,
                                 out_dtype=jnp.int8).reshape(k, n)
    if bits == 4:
        if k % 2:
            raise ValueError("int4 packing needs even in-features")
        lo = q[0::2].astype(jnp.uint8) & 0xF
        hi = (q[1::2].astype(jnp.uint8) & 0xF) << 4
        q = (lo | hi).astype(jnp.int8)                    # (K/2, N)
    return q, (scales[0] if group_size in (-1, None)
               else scales)


def weight_dequantize_values(qw, scales, algo: str = "weight_only_int8",
                             group_size: int = -1,
                             out_dtype=jnp.float32):
    bits = 4 if "int4" in algo else 8
    qmax = _Q4 if bits == 4 else _Q8
    if bits == 4:
        u = qw.astype(jnp.uint8)
        lo = (u & 0xF).astype(jnp.int8)
        hi = ((u >> 4) & 0xF).astype(jnp.int8)
        # sign-extend the nibbles: values were stored as 4-bit two's
        # complement
        lo = jnp.where(lo > 7, lo - 16, lo)
        hi = jnp.where(hi > 7, hi - 16, hi)
        k2, n = qw.shape
        q = jnp.stack([lo, hi], axis=1).reshape(2 * k2, n)
    else:
        q = qw
    k, n = q.shape
    g = k if group_size in (-1, None) else int(group_size)
    sc = scales if scales.ndim == 2 else scales[None, :]
    w = (q.reshape(k // g, g, n).astype(jnp.float32)
         * sc[:, None, :] / qmax)
    return w.reshape(k, n).astype(out_dtype)


def weight_only_linear_values(x, qw, scales, bias=None,
                              algo: str = "weight_only_int8",
                              group_size: int = -1):
    w = weight_dequantize_values(qw, scales, algo, group_size,
                                 out_dtype=x.dtype)
    out = x @ w
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def int8_dot_values(xq, wq, x_scale, w_scale):
    """MXU-native W8A8: int8 (..., K) × int8 (K, N) -> int32 accumulate,
    one fp32 rescale. x_scale: scalar or (..., 1); w_scale: (N,) or
    scalar (absmax scales; values were quantized as v/scale*127)."""
    acc = jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32)
            * (x_scale / _Q8) * (w_scale / _Q8))


def quantize_activation_dynamic_values(x):
    """Per-tensor dynamic activation quantization (inference): live
    abs-max scale, int8 values. Returns (xq int8, scale fp32)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-9)
    xq = absmax_round_clip_values(x.astype(jnp.float32), scale, _Q8,
                                  out_dtype=jnp.int8)
    return xq, scale


# -- Tensor-level API (reference signatures) ---------------------------
def weight_quantize(x, algo: str = "weight_only_int8", arch=None,
                    group_size: int = -1):
    """≙ paddle.nn.quant.weight_quantize: returns (quantized weight,
    scales)."""
    xt = _t(x)

    def fn(v):
        return weight_quantize_values(v, algo, group_size)
    return apply("weight_quantize", fn, (xt,), multi_output=True)


def weight_dequantize(x, scale, algo: str = "weight_only_int8",
                      out_dtype="float32", group_size: int = -1):
    xt, st = _t(x), _t(scale)
    from ..core import dtype as dtypes
    dt = dtypes.convert_dtype(out_dtype)

    def fn(v, s):
        return weight_dequantize_values(v, s, algo, group_size, dt)
    return apply("weight_dequantize", fn, (xt, st))


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype: str = "int8", arch=None,
                       group_size: int = -1):
    """≙ paddle.nn.quant.weight_only_linear."""
    algo = f"weight_only_{weight_dtype}"
    xt, wt = _t(x), _t(weight)
    st = _t(weight_scale) if weight_scale is not None else None
    bt = _t(bias) if bias is not None else None
    args = [xt, wt] + ([st] if st is not None else []) \
        + ([bt] if bt is not None else [])

    def fn(xv, wv, *rest):
        i = 0
        sv = rest[i] if st is not None else jnp.ones(
            (wv.shape[-1],), jnp.float32)
        i += 1 if st is not None else 0
        bv = rest[i] if bt is not None else None
        return weight_only_linear_values(xv, wv, sv, bv, algo,
                                         group_size)
    return apply("weight_only_linear", fn, tuple(args))


def llm_int8_linear(x, weight, bias=None, weight_scale=None,
                    threshold: float = 6.0):
    """≙ paddle.nn.quant.llm_int8_linear — dynamic-activation W8A8 on
    the MXU int8 path (the outlier-threshold decomposition of the CUDA
    implementation is unnecessary on TPU: the int32 accumulator does
    not saturate)."""
    xt, wt = _t(x), _t(weight)
    st = _t(weight_scale) if weight_scale is not None else None
    bt = _t(bias) if bias is not None else None
    args = [xt, wt] + ([st] if st is not None else []) \
        + ([bt] if bt is not None else [])

    def fn(xv, wv, *rest):
        i = 0
        sv = rest[i] if st is not None else jnp.ones(
            (wv.shape[-1],), jnp.float32)
        i += 1 if st is not None else 0
        bv = rest[i] if bt is not None else None
        xq, xs = quantize_activation_dynamic_values(xv)
        out = int8_dot_values(xq, wv, xs, sv)
        if bv is not None:
            out = out + bv.astype(out.dtype)
        return out.astype(xv.dtype)
    return apply("llm_int8_linear", fn, tuple(args))


def int8_dot(xq, wq, x_scale, w_scale):
    """Raw MXU int8 matmul (Tensor-level)."""
    return apply("int8_dot",
                 lambda a, b, sa, sb: int8_dot_values(a, b, sa, sb),
                 (_t(xq), _t(wq), _t(x_scale), _t(w_scale)))


def quantize_activation_dynamic(x):
    return apply("quantize_activation_dynamic",
                 quantize_activation_dynamic_values, (_t(x),),
                 multi_output=True)
