"""paddle_tpu.quantization — QAT / PTQ.
≙ reference «python/paddle/quantization/» [U]: QuantConfig, QAT (fake-quant
training), PTQ (observer calibration + convert), quanters/observers.

TPU-native: fake-quant is a pure elementwise round-through-STE op that XLA
fuses into the surrounding matmul; int8 inference on TPU lowers through
XLA's int8 dot support (quantized Linear converts to int8 weights +
fp scale)."""
from __future__ import annotations

from typing import Dict, Optional, Type

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from ..core.tensor import Tensor, apply, to_tensor
from ..nn.layer.layers import Layer
from ..nn.quant import absmax_round_clip_values

__all__ = ["fake_quant", "quantize_linear", "dequantize_linear",
           "AbsmaxObserver", "EMAObserver", "FakeQuanterWithAbsMax",
           "QuantConfig", "QAT", "PTQ", "QuantedLinear",
           "WeightOnlyLinear", "quantize_model_weight_only",
           "absmax_round_clip_values", "QuantServingConfig"]


def __getattr__(name):
    # QuantServingConfig (the serving engine's quant=... mode) lives in
    # models/serving.py next to SpecConfig; re-exported here lazily so
    # `from paddle_tpu.quantization import QuantServingConfig` works
    # without importing the serving stack at package-import time
    if name == "QuantServingConfig":
        from ..models.serving import QuantServingConfig
        return QuantServingConfig
    raise AttributeError(name)


def _ste_round(x):
    """Round with straight-through-estimator gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def fake_quant(x: Tensor, scale, bit_length=8, channel_axis=None) -> Tensor:
    """Quantize-dequantize with STE. ≙ fake_quantize_dequantize ops [U]."""
    qmax = float(2 ** (bit_length - 1) - 1)

    def fn(v, s):
        if channel_axis is not None:
            shape = [1] * v.ndim
            shape[channel_axis] = -1
            s = s.reshape(shape)
        q = absmax_round_clip_values(v, s, qmax, round_fn=_ste_round)
        return q * jnp.maximum(s, 1e-9) / qmax
    s_t = scale if isinstance(scale, Tensor) else to_tensor(scale)
    return apply("fake_quant", fn, (x, s_t))


def quantize_linear(x: Tensor, scale, zero_point=0, bit_length=8,
                    axis=None) -> Tensor:
    qmax = float(2 ** (bit_length - 1) - 1)

    def fn(v, s):
        if axis is not None:
            shape = [1] * v.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        return absmax_round_clip_values(v, s, qmax, out_dtype=jnp.int8)
    s_t = scale if isinstance(scale, Tensor) else to_tensor(scale)
    return apply("quantize_linear", fn, (x, s_t))


def dequantize_linear(x: Tensor, scale, zero_point=0, bit_length=8,
                      axis=None) -> Tensor:
    qmax = float(2 ** (bit_length - 1) - 1)

    def fn(v, s):
        if axis is not None:
            shape = [1] * v.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        return v.astype(jnp.float32) * s / qmax
    s_t = scale if isinstance(scale, Tensor) else to_tensor(scale)
    return apply("dequantize_linear", fn, (x, s_t))


class AbsmaxObserver:
    """PTQ calibration observer: running abs-max. ≙ quantization
    observers [U]."""

    def __init__(self, quant_bits=8, channel_axis=None):
        self.quant_bits = quant_bits
        self.channel_axis = channel_axis
        self._scale = None

    def observe(self, x):
        v = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        if self.channel_axis is not None:
            axes = tuple(i for i in range(v.ndim)
                         if i != self.channel_axis)
            m = jnp.max(jnp.abs(v), axis=axes)
        else:
            m = jnp.max(jnp.abs(v))
        self._scale = m if self._scale is None else jnp.maximum(
            self._scale, m)

    def scale(self):
        return self._scale if self._scale is not None else jnp.float32(1.0)


class EMAObserver(AbsmaxObserver):
    """Exponential-moving-average abs-max (activation observer)."""

    def __init__(self, quant_bits=8, decay=0.9):
        super().__init__(quant_bits)
        self.decay = decay

    def observe(self, x):
        v = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        m = jnp.max(jnp.abs(v))
        self._scale = m if self._scale is None else \
            self.decay * self._scale + (1 - self.decay) * m


class FakeQuanterWithAbsMax(Layer):
    """QAT quanter: fake-quant with live abs-max scale (weight) or EMA
    (activation). ≙ FakeQuanterWithAbsMaxObserver [U]."""

    def __init__(self, quant_bits=8, dtype="float32", name=None,
                 moving_rate=0.9, is_weight=False, channel_axis=None):
        super().__init__()
        self.quant_bits = quant_bits
        self.channel_axis = channel_axis
        self.is_weight = is_weight
        self.moving_rate = moving_rate
        self._ema = None

    def forward(self, x):
        v = x._value
        if self.channel_axis is not None:
            axes = tuple(i for i in range(v.ndim)
                         if i != self.channel_axis)
            scale = jnp.max(jnp.abs(jax.lax.stop_gradient(v)), axis=axes)
        else:
            scale = jnp.max(jnp.abs(jax.lax.stop_gradient(v)))
            if not self.is_weight:
                self._ema = scale if self._ema is None else (
                    self.moving_rate * self._ema
                    + (1 - self.moving_rate) * scale)
                scale = self._ema
        return fake_quant(x, Tensor(scale), self.quant_bits,
                          self.channel_axis)


class QuantedLinear(Layer):
    """Linear with weight+activation fake-quant (QAT) or int8 weights
    (converted). ≙ quantized nn.QuantedLinear [U]."""

    def __init__(self, linear, q_config=None):
        super().__init__()
        self.linear = linear
        cfg = q_config or QuantConfig()
        self.weight_quanter = FakeQuanterWithAbsMax(
            cfg.weight_bits, is_weight=True, channel_axis=1)
        self.act_quanter = FakeQuanterWithAbsMax(
            cfg.activation_bits, is_weight=False)
        self._converted = False

    def forward(self, x):
        from ..nn import functional as F
        if self._converted == "w8a8":
            # MXU-native int8 execution: dynamic per-tensor activation
            # scale, per-channel weight scale, int8xint8->int32 dot
            from ..nn.quant import (int8_dot_values,
                                    quantize_activation_dynamic_values)
            iw, ws = self._int_weight, self._w_scale
            bias = self.linear.bias

            def fn(xv, wv, sv, *b):
                xq, xs = quantize_activation_dynamic_values(xv)
                out = int8_dot_values(xq, wv, xs, sv)
                if b:
                    out = out + b[0].astype(out.dtype)
                return out.astype(xv.dtype)
            args = (x, iw, ws) + ((bias,) if bias is not None else ())
            return apply("quanted_linear_w8a8", fn, args)
        if self._converted:
            wq = dequantize_linear(self._int_weight, self._w_scale,
                                   axis=1)
            return F.linear(x, wq, self.linear.bias)
        xq = self.act_quanter(x)
        wq = self.weight_quanter(self.linear.weight)
        return F.linear(xq, wq, self.linear.bias)

    def convert(self, mode: str = "dequant"):
        """Freeze: int8 weights + per-channel scales.

        mode='dequant' — weights stored int8, dequantized into the fp
        matmul (weight-only memory win). mode='w8a8' — activations
        dynamically quantized per call and the matmul runs on the MXU's
        int8 path (datasheet 2x-peak, not measured on this code;
        ≙ the cuBLASLt int8 fused linear)."""
        if mode not in ("dequant", "w8a8"):
            raise ValueError(f"unknown convert mode {mode!r}")
        w = self.linear.weight
        scale = jnp.max(jnp.abs(w._value), axis=0)
        self._w_scale = Tensor(scale.astype(jnp.float32))
        self._int_weight = quantize_linear(w, self._w_scale, axis=1)
        self._converted = mode
        return self


class WeightOnlyLinear(Layer):
    """Serving-path Linear with int8/int4 weights in HBM, dequantized on
    the fly into the bf16 matmul (≙ paddle.nn.quant weight-only path for
    LLM decode — HBM-bandwidth-bound, so 1/2 or 1/4 the weight bytes is
    a direct decode speedup)."""

    def __init__(self, linear, weight_dtype: str = "int8",
                 group_size: int = -1):
        super().__init__()
        from ..nn.quant import weight_quantize_values
        self.weight_dtype = weight_dtype
        self.group_size = group_size
        self._algo = f"weight_only_{weight_dtype}"
        qw, sc = weight_quantize_values(
            linear.weight._value, self._algo, group_size)
        self.register_buffer("quant_weight", Tensor(qw))
        self.register_buffer("weight_scale", Tensor(sc))
        self.bias = linear.bias
        self.in_features = linear.weight.shape[0]
        self.out_features = linear.weight.shape[1]

    def forward(self, x):
        from ..nn.quant import weight_only_linear
        return weight_only_linear(
            x, self.quant_weight, bias=self.bias,
            weight_scale=self.weight_scale,
            weight_dtype=self.weight_dtype, group_size=self.group_size)


def quantize_model_weight_only(model, weight_dtype: str = "int8",
                               group_size: int = -1, exclude=()):
    """Swap every nn.Linear in `model` for a WeightOnlyLinear (the LLM
    serving conversion; pass e.g. exclude=('lm_head',) to keep the
    output head in full precision). Returns the model, modified in
    place. Layers that cannot be quantized (odd in-features for int4,
    in-features not divisible by group_size) are left in fp, collected
    on `model._weight_only_skipped`, and warned about — never a
    mid-walk crash with a half-converted model."""
    import warnings

    from ..nn import Linear
    skipped = []
    for parent in model.sublayers(include_self=True):
        for name, sub in list(parent._sub_layers.items()):
            if not isinstance(sub, Linear) or name in exclude:
                continue
            k = sub.weight.shape[0]
            if weight_dtype == "int4" and k % 2:
                skipped.append((name, tuple(sub.weight.shape),
                                "odd in-features for int4 packing"))
                continue
            if group_size not in (-1, None) and k % int(group_size):
                skipped.append((name, tuple(sub.weight.shape),
                                f"in-features not divisible by "
                                f"group_size={group_size}"))
                continue
            # setattr, not _sub_layers[name]=...: sublayers also live in
            # the instance __dict__, and attribute-access forwards
            # (self.q_proj(x)) would otherwise keep the stale fp layer
            setattr(parent, name, WeightOnlyLinear(sub, weight_dtype,
                                                   group_size))
    model._weight_only_skipped = skipped
    if skipped:
        warnings.warn(
            f"quantize_model_weight_only: {len(skipped)} layer(s) left "
            "in fp (see model._weight_only_skipped): "
            + "; ".join(f"{nm} {sh}: {why}"
                        for nm, sh, why in skipped[:3])
            + ("..." if len(skipped) > 3 else ""))
    return model


class QuantConfig:
    """≙ paddle.quantization.QuantConfig."""

    def __init__(self, activation=None, weight=None, weight_bits=8,
                 activation_bits=8):
        self.activation = activation
        self.weight = weight
        self.weight_bits = weight_bits
        self.activation_bits = activation_bits
        self._layer_types: Dict[Type, Type] = {}

    def add_type_config(self, layer_type, activation=None, weight=None):
        self._layer_types[layer_type] = (activation, weight)


def _swap_linears(model, fn):
    from ..nn import Linear
    for parent in model.sublayers(include_self=True):
        for name, sub in list(parent._sub_layers.items()):
            if isinstance(sub, Linear):
                # setattr keeps _sub_layers and the instance __dict__ in
                # sync (attribute-access forwards see the new layer)
                setattr(parent, name, fn(sub))
    return model


class QAT:
    """Quantization-aware training driver. ≙ paddle.quantization.QAT."""

    def __init__(self, q_config=None):
        self.q_config = q_config or QuantConfig()

    def quantize(self, model, inplace=False):
        return _swap_linears(model,
                             lambda lin: QuantedLinear(lin, self.q_config))

    def convert(self, model, inplace=False):
        for sub in model.sublayers(include_self=True):
            if isinstance(sub, QuantedLinear) and not sub._converted:
                sub.convert()
        return model


class PTQ:
    """Post-training quantization: observe activations on calibration
    data, then convert. ≙ paddle.quantization.PTQ."""

    def __init__(self, q_config=None):
        self.q_config = q_config or QuantConfig()
        self._observers = []

    def quantize(self, model, inplace=False):
        ptq = self

        class _ObservedLinear(Layer):
            def __init__(self, lin):
                super().__init__()
                self.linear = lin
                self.obs = EMAObserver(ptq.q_config.activation_bits)
                ptq._observers.append(self.obs)

            def forward(self, x):
                self.obs.observe(x)
                return self.linear(x)

        return _swap_linears(model, _ObservedLinear)

    def convert(self, model, inplace=False):
        def conv(sub):
            ql = QuantedLinear(sub.linear
                               if hasattr(sub, "linear") else sub,
                               self.q_config)
            ql.convert()
            return ql

        for parent in model.sublayers(include_self=True):
            for name, sub in list(parent._sub_layers.items()):
                if sub.__class__.__name__ == "_ObservedLinear":
                    setattr(parent, name, conv(sub))
        return model
