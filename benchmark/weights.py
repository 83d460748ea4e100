"""Seeded weights, made on the device in the type they are served in.

The program's models create float32 parameters leaf by leaf on the
default device and cast afterwards (`LlamaConfig.dtype` is not read at
construction; PERF.md, Open questions): 3.76 B parameters would be
15 GB. So the model is CONSTRUCTED under a trace, through the program's
own constructor and `.to(dtype=...)`, which costs no memory and no
time and leaves a skeleton whose parameters have shapes and types but
no values; then one small jitted program, called once a layer, makes
the layer's parameters from the seed in their final type, and the
values are set into the skeleton. The seed is an argument of that
program, not a constant in it, so every seed runs the same cached one.

Scales follow the program's initializers (Xavier-normal matrices, a
unit-normal embedding, norm weights of 1), so activations and logits
have the magnitudes the smoke measured. The reference gets the same
arrays, so nothing about correctness rests on the scales.
"""
from __future__ import annotations

import importlib
import math
import re
from functools import partial

import jax
import jax.numpy as jnp


def model_config(spec: dict, sizes: dict):
    """The program's config object from a configuration file: `spec`
    is its `program` block (module, classes, which file key feeds which
    field), `sizes` the file's top level."""
    mod = importlib.import_module(spec["module"])
    fields = {field: sizes[key] for field, key in spec["fields"].items()
              if sizes.get(key) is not None}
    return getattr(mod, spec["config_class"])(**fields), \
        getattr(mod, spec["model_class"])


def _std(name: str, shape) -> float | None:
    if len(shape) == 1:
        return None                       # a norm weight: ones
    if name.endswith("embed_tokens.weight"):
        return 1.0
    return math.sqrt(2.0 / (shape[0] + shape[1]))


def _groups(avals) -> dict:
    """Parameter indices by the first whole-number part of their name
    (`model.layers.7.mlp...` -> 7; none -> -1). Layers of one shape then
    share ONE small program, called once a layer: unrolled over every
    leaf of a 24-layer model the same arithmetic took 48 s to compile."""
    out = {}
    for i, (name, _, _) in enumerate(avals):
        m = re.search(r"\.(\d+)\.", name)
        out.setdefault(int(m.group(1)) if m else -1, []).append(i)
    return out


@partial(jax.jit, static_argnames=("specs",))
def _fill_group(seed32, group, specs):
    key = jax.random.fold_in(jax.random.key(seed32, impl="rbg"), group)
    out = []
    for i, (std, shape, dtype) in enumerate(specs):
        if std is None:
            out.append(jnp.ones(shape, dtype))
        else:
            k = jax.random.fold_in(key, i)
            out.append((std * jax.random.normal(k, shape, jnp.float32)
                        ).astype(dtype))
    return out


def build(spec: dict, sizes: dict, seed: int):
    """The program's model, in eval mode, holding seeded weights."""
    import paddle_tpu as paddle
    cfg, model_class = model_config(spec, sizes)
    box = {}

    def skeleton():
        model = model_class(cfg)
        if cfg.dtype != "float32":
            model.to(dtype=cfg.dtype)
        box["model"] = model
        box["avals"] = [(n, tuple(p._value.shape), p._value.dtype)
                        for n, p in model.named_parameters()]
        # the buffers (rope tables) come from numpy, not from the
        # random stream: they are this call's only real outputs
        return [b._value for b in model.buffers()]

    buffers = jax.jit(skeleton)()
    model, avals = box["model"], box["avals"]

    # --seed may pass 2**31: fold it to the 32 bits a key takes
    seed32 = jnp.uint32((int(seed) ^ (int(seed) >> 32)) & 0xFFFFFFFF)
    values = [None] * len(avals)
    for group, members in _groups(avals).items():
        specs = tuple((_std(avals[i][0], avals[i][1]), avals[i][1],
                       jnp.dtype(avals[i][2]).name) for i in members)
        made = _fill_group(seed32, jnp.uint32(group + 1), specs)
        for i, v in zip(members, made):
            values[i] = v
    for p, v in zip(model.parameters(), values):
        p._value = v
    for b, v in zip(model.buffers(), buffers):
        b._value = v
    # the trace left a tracer in the framework's global random stream
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model.eval()
    return model, cfg


def named_values(model) -> dict:
    """{parameter name: array}, as the plain reference takes them."""
    return {n: p._value for n, p in model.named_parameters()}
