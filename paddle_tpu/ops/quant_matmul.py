"""Fused dequant-matmul — int8/fp8 weights dequantized in the matmul
epilogue (quantized serving, ISSUE 15).

≙ the Liger-style fused dequant-matmul epilogues (PAPERS.md arxiv
2410.10989) and the reference weight-only serving path
(`paddle.nn.quant.weight_only_linear`): weights live in HBM at 1/4
(int8/fp8 vs f32) or 1/2 (vs bf16) the bytes with one f32 scale per
OUTPUT channel, and the dequantization never materializes a full-width
weight copy — the scale is applied to the matmul ACCUMULATOR, which is
exact because a per-out-channel scale is constant along the
contraction:

    y[m, n] = sum_k x[m, k] * (qw[k, n] * s[n])
            = (sum_k x[m, k] * qw[k, n]) * s[n]

Kernel. The Pallas path tiles (M, K) x (K, N) on the MXU with an f32
VMEM accumulator; each int8 weight tile is widened in VMEM
(HBM->VMEM moved 1 byte/element — the bandwidth win decode serving is
bound by) and the per-column scale block multiplies the accumulator
once, on the last K step (the epilogue). fp8 (float8_e4m3fn) storage
routes through the XLA path: Mosaic's f8 tile support is not part of
this repo's offline lowering gate, and XLA already fuses the widening
convert into the dot's operand read.

The XLA fallback (`use_kernel=False`/non-TPU) computes the identical
epilogue form; `use_kernel=True` forces the Pallas kernel in interpret
mode — the CI parity path (tests/test_quant_serving.py holds it
against an independent NumPy oracle). Serving-only: no VJP.

`QuantizedWeight` is the registered-pytree value the serving engine
binds in place of a quantized parameter's array (`bind_state` installs
it; `nn.functional.linear` detects it and dispatches here), so the
model code never forks on quantization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu

WEIGHT_QMAX = 127.0          # int8 absmax lattice
FP8_MAX = 448.0              # float8_e4m3fn finite max


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """One quantized matmul weight as a jit-traversable value:
    ``qw`` (K, N) int8 or float8_e4m3fn storage, ``scale`` (N,) f32
    DEQUANT multiplier per output channel (``w ~= qw * scale``).
    Registered as a pytree so it rides a compiled program's argument
    list like any array — `bind_state` installs it as a Parameter's
    ``_value`` and `nn.functional.linear` routes it to
    `dequant_matmul_values`."""

    def __init__(self, qw, scale):
        self.qw = qw
        self.scale = scale

    @property
    def shape(self):
        return self.qw.shape

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.qw.shape)) * self.qw.dtype.itemsize \
            + int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize

    def tree_flatten(self):
        return (self.qw, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return (f"QuantizedWeight(shape={tuple(self.qw.shape)}, "
                f"dtype={self.qw.dtype})")


def quantize_weight_values(w, mode: str = "int8"):
    """Per-OUT-CHANNEL weight quantization for the serving engine:
    ``w`` (K, N) float -> (storage, dequant scale (N,) f32).

    * ``int8``: absmax lattice via the ONE shared round-clip core
      (`nn.quant.absmax_round_clip_values`), scale = absmax/127.
    * ``fp8``: float8_e4m3fn storage scaled so each channel's absmax
      lands on the format's finite max (448) — the e4m3 mantissa then
      spends its bits on the channel's actual range.
    """
    from ..nn.quant import absmax_round_clip_values
    if w.ndim != 2:
        raise ValueError(f"quantize_weight_values wants (K, N), got "
                         f"shape {tuple(w.shape)}")
    absmax = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)),
                                 axis=0), 1e-9)            # (N,)
    if mode == "int8":
        qw = absmax_round_clip_values(w.astype(jnp.float32),
                                      absmax[None, :], WEIGHT_QMAX,
                                      out_dtype=jnp.int8)
        return qw, (absmax / WEIGHT_QMAX).astype(jnp.float32)
    if mode == "fp8":
        scale = (absmax / FP8_MAX).astype(jnp.float32)
        qw = (w.astype(jnp.float32) / scale[None, :]).astype(
            jnp.float8_e4m3fn)
        return qw, scale
    raise ValueError(f"quantize mode {mode!r}: int8|fp8")


def _dequant_matmul_xla(x, qw, scale):
    """The epilogue form in XLA: widen the quantized operand in the dot
    (XLA fuses the convert into the operand read), scale the
    accumulator per column."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), qw.astype(jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)


def _dequant_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                           n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += mxu_dot(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        # the fused dequant: one per-column multiply of the f32
        # accumulator — exact for per-out-channel scales
        o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


def _block(dim: int, pref: int, step: int) -> int:
    """Largest tile <= pref that divides `dim` stepping down by
    `step`-multiples; falls back to `dim` itself (one block)."""
    b = min(pref, dim)
    b -= b % step
    while b >= step:
        if dim % b == 0:
            return b
        b -= step
    return dim


def _dequant_matmul_pallas(x2, qw, scale, out_dtype, interpret):
    m, k = x2.shape
    _, n = qw.shape
    bm = _block(m, 128, 8)
    bk = _block(k, 512, 32)       # int8 sublane tile is 32
    bn = _block(n, 128, 128)
    n_k = k // bk
    out = pl.pallas_call(
        functools.partial(_dequant_matmul_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(x2, qw, scale[None, :])
    return out


def dequant_matmul_values(x, qw, scale, use_kernel=None):
    """``x`` (..., K) float; ``qw`` (K, N) int8 or float8_e4m3fn;
    ``scale`` (N,) f32 dequant multiplier (``w ~= qw * scale``).
    Returns ``x @ (qw * scale)`` in x's dtype, computed as the fused
    epilogue (module docstring) — the quantized weight is never
    widened in HBM.

    ``use_kernel``: None routes by platform (Pallas on TPU, XLA
    elsewhere); True forces the Pallas kernel — interpret mode off-TPU,
    the CI parity path. fp8 storage always takes the XLA path (module
    docstring); so do shapes off the MXU tile grid (m % 8 / k % 32 /
    n % 128 nonzero — a whole-dim block would be legal Mosaic but an
    unbounded VMEM accumulator tile)."""
    kernel = use_kernel if use_kernel is not None else on_tpu()
    if not kernel or qw.dtype != jnp.int8:
        return _dequant_matmul_xla(x, qw, scale)
    k, n = qw.shape
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    if m % 8 or k % 32 or n % 128:
        return _dequant_matmul_xla(x, qw, scale)
    x2 = x.reshape(m, k)
    out = _dequant_matmul_pallas(x2, qw, scale, x.dtype,
                                 interpret=not on_tpu())
    return out.reshape(*lead, n)
