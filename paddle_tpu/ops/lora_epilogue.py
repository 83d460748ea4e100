"""Batched multi-LoRA matmul epilogue — per-token low-rank adapter
gathers over one shared base matmul (multi-model serving, ISSUE 17).

≙ the BGMV/SGMV kernels of multi-LoRA serving stacks (Punica, S-LoRA;
PAPERS.md arxiv 2605.25645 serves fine-tune fleets this way) and the
fused-epilogue discipline of `ops/quant_matmul.py` (Liger, arxiv
2410.10989): requests for DIFFERENT fine-tunes share one ragged
dispatch because the expensive matmul is the shared base weight —
optionally `QuantizedWeight` int8/fp8 storage — and each token then
adds its own adapter's low-rank delta, gathered by a per-token adapter
row id:

    y[t] = x[t] @ W_base  +  (x[t] @ A[ids[t]]) @ B[ids[t]] * s[ids[t]]

Row 0 of every stack is ZEROS (the no-adapter row): base-model tokens
ride the same program and their delta is an exact ``+0.0``, so a mixed
batch's greedy stream is bit-identical to serving each adapter alone —
the per-token delta has no cross-token reduction, the same
batching-invariance the canary machinery already relies on
(serving/sentry.py). Adapter ranks are padded to one fixed ``r`` at
registration (`serving.model_store.FleetModelStore.max_rank`): padded
rank columns contribute exact zeros, so fleets hosting different
adapter subsets still produce bit-identical per-model streams.

Kernel. The Pallas path is BGMV-shaped: grid (T,) with the adapter id
vector scalar-prefetched (`PrefetchScalarGridSpec`), so each token's
program DMAs exactly its adapter's (K, r) / (r, N) blocks — the gather
never materializes a (T, K, r) operand in HBM. The XLA fallback
(`use_kernel=False` / non-TPU) computes the identical per-token
einsum form; `use_kernel=True` forces the kernel in interpret mode —
the CI parity path (tests/test_multimodel.py holds it against an
independent NumPy oracle). Serving-only: no VJP.

`LoraWeight` is the registered-pytree value the serving engine binds
in place of an adapted matmul parameter's array (`bind_state` installs
it per dispatch with that dispatch's token->adapter-row vector;
`nn.functional.linear` detects it and dispatches here), so the model
code never forks on multi-LoRA — exactly the `QuantizedWeight` seam,
one epilogue further.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu

__all__ = ["LoraWeight", "lora_epilogue_values", "lora_matmul_values"]


@jax.tree_util.register_pytree_node_class
class LoraWeight:
    """One multi-LoRA matmul weight as a jit-traversable value:
    ``base`` (K, N) array or `ops.quant_matmul.QuantizedWeight`,
    stacked adapters ``a`` (R, K, r) / ``b`` (R, r, N) with per-row
    dequant-style multiplier ``scale`` (R,) f32 (row 0 all-zeros = no
    adapter), and ``ids`` — this DISPATCH's per-token adapter row
    vector (T,) int32. Registered as a pytree so every piece rides a
    compiled program's argument list; the engine rebuilds the wrapper
    per dispatch (host-cheap) with that batch's ``ids``."""

    def __init__(self, base, a, b, scale, ids):
        self.base = base
        self.a = a
        self.b = b
        self.scale = scale
        self.ids = ids

    @property
    def shape(self):
        return self.base.shape

    @property
    def nbytes(self) -> int:
        n = int(np.prod(self.a.shape)) * self.a.dtype.itemsize \
            + int(np.prod(self.b.shape)) * self.b.dtype.itemsize \
            + int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize
        return n + int(getattr(self.base, "nbytes", 0))

    def tree_flatten(self):
        return (self.base, self.a, self.b, self.scale, self.ids), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return (f"LoraWeight(shape={tuple(self.base.shape)}, "
                f"adapters={int(self.a.shape[0]) - 1}, "
                f"rank={int(self.a.shape[2])})")


def _lora_epilogue_xla(x2, a, b, scale, ids):
    """The per-token gather epilogue in XLA: both einsums keep the
    token axis elementwise (no cross-token reduction — the
    bit-identity argument in the module docstring), reduce in f32."""
    av = a[ids].astype(jnp.float32)                    # (T, K, r)
    bv = b[ids].astype(jnp.float32)                    # (T, r, N)
    h = jnp.einsum("tk,tkr->tr", x2.astype(jnp.float32), av)
    d = jnp.einsum("tr,trn->tn", h, bv)
    return (d * scale[ids][:, None]).astype(x2.dtype)


def _lora_epilogue_kernel(ids_ref, x_ref, a_ref, b_ref, s_ref, o_ref):
    # one token per program: (1, K) x (K, r) -> (1, r) x (r, N); the
    # scalar-prefetched ids drove the BlockSpec index maps, so a_ref /
    # b_ref already hold THIS token's adapter row
    h = mxu_dot(x_ref[0].astype(jnp.float32),
                a_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    d = mxu_dot(h, b_ref[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = (d * s_ref[0]).astype(o_ref.dtype)      # (1, 1) bcast


def _lora_epilogue_pallas(x2, a, b, scale, ids, interpret):
    t, k = x2.shape
    r_stack, _, r = a.shape
    n = b.shape[2]
    # one-row blocks ride a unit middle axis — (T, K) -> (T, 1, K) and
    # so on — so that every block's last two dims EQUAL the array's
    # (the Mosaic block rule: a (1, K) block of a (T, K) array has an
    # undividable sublane; the TPU lowering refuses it)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, 1, k), lambda tt, ids_: (tt, 0, 0)),
            pl.BlockSpec((1, k, r), lambda tt, ids_: (ids_[tt], 0, 0)),
            pl.BlockSpec((1, r, n), lambda tt, ids_: (ids_[tt], 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda tt, ids_: (ids_[tt], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, n), lambda tt, ids_: (tt, 0, 0)),
    )
    return pl.pallas_call(
        _lora_epilogue_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, n), x2.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), x2[:, None, :], a, b,
      scale[:, None, None])[:, 0, :]


def lora_epilogue_values(x, a, b, scale, ids, use_kernel=None):
    """The per-token adapter DELTA: ``x`` (..., K) float with T total
    tokens; stacked ``a`` (R, K, r) / ``b`` (R, r, N) / ``scale``
    (R,); ``ids`` (T,) int32 adapter row per token (0 = none). Returns
    the (..., N) delta in x's dtype — the caller adds it to the shared
    base matmul.

    ``use_kernel``: None routes by platform (Pallas BGMV on TPU, XLA
    gather-einsum elsewhere); True forces the Pallas kernel —
    interpret mode off-TPU, the CI parity path. Shapes off the MXU
    lane grid (K or N % 128, rank % 8) take the XLA path."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    t = int(np.prod(lead)) if lead else 1
    x2 = x.reshape(t, k)
    kernel = use_kernel if use_kernel is not None else on_tpu()
    n = b.shape[2]
    if not kernel or k % 128 or n % 128 or a.shape[2] % 8:
        return _lora_epilogue_xla(x2, a, b, scale,
                                  ids).reshape(*lead, n)
    out = _lora_epilogue_pallas(x2, a, b, scale, ids,
                                interpret=not on_tpu())
    return out.reshape(*lead, n)


def lora_matmul_values(x, w: "LoraWeight", use_kernel=None):
    """``x @ base + per-token delta`` for one bound `LoraWeight`. The
    base matmul is EXACTLY the unadapted path's computation —
    `jnp.matmul` for an array base, the fused dequant epilogue for a
    `QuantizedWeight` base — so a row-0 (no-adapter) token's result
    differs from a plain engine's by one exact ``+0.0``."""
    base = w.base
    if type(base).__name__ == "QuantizedWeight":
        from .quant_matmul import dequant_matmul_values
        y = dequant_matmul_values(x, base.qw, base.scale,
                                  use_kernel=use_kernel)
    else:
        y = jnp.matmul(x, base)
    return y + lora_epilogue_values(x, w.a, w.b, w.scale, w.ids,
                                    use_kernel=use_kernel).astype(
                                        y.dtype)
