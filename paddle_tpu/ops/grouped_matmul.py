"""Grouped (ragged) matmul — the MoE expert-compute primitive.

≙ reference MoE expert FFN loops + fused grouped GEMMs
(«python/paddle/incubate/distributed/models/moe/» experts executed per
group, SURVEY.md §2.3 EP row; §7 step-6 'grouped matmul (megablox-style)')
— re-designed for the MXU:

    out[r] = lhs[r] @ rhs[g(r)]        g(r) = expert owning row r

where rows are pre-sorted by expert and `group_sizes[e]` rows belong to
expert e. Two paths with identical semantics:

* Pallas kernel (TPU): classic blocked matmul over a (m_tile, n_tile,
  k_tile) grid whose rhs block index is looked up per m-tile from a
  scalar-prefetched tile→expert map. Requires every group size to be a
  multiple of block_m (the MoE dispatch pads each expert's rows to the
  block boundary — a bounded O(E·block_m) cost), so no tile straddles a
  group boundary.
* `jax.lax.ragged_dot` (XLA) everywhere else — also the transpose rule
  used for d(rhs) in the custom vjp.

Rows beyond sum(group_sizes) produce zeros on both paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu

DEFAULT_BLOCK = 128

__all__ = ["grouped_matmul_values", "gmm_pallas"]


def _gmm_kernel(te_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(
        lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def gmm_pallas(lhs, rhs, group_sizes, block_m=DEFAULT_BLOCK,
               block_n=DEFAULT_BLOCK, block_k=DEFAULT_BLOCK,
               interpret=False):
    """lhs (M, K) @ rhs (E, K, N) with rows grouped by expert -> (M, N).

    PRECONDITION: every group_sizes[e] is a multiple of block_m (so each
    m-tile belongs to exactly one expert). M/K/N must divide by their
    block sizes.
    """
    m, k = lhs.shape
    e, _, n = rhs.shape
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n, block_m, block_k, block_n))
    nmt, nnt, nkt = m // block_m, n // block_n, k // block_k

    # tile -> expert map (scalar-prefetched). Pad tiles past the last
    # group clamp to e-1; their lhs rows are zero so the result is zero.
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tile_start = jnp.arange(nmt, dtype=jnp.int32) * block_m
    te = jnp.searchsorted(ends, tile_start, side="right").astype(jnp.int32)
    te = jnp.minimum(te, e - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nmt, nnt, nkt),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda i, j, kk, te_: (i, kk)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda i, j, kk, te_: (te_[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, te_: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    out_dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nkt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(te, lhs, rhs)


def _gmm_xla(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                              group_sizes.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul_values(lhs, rhs, group_sizes, block_aligned=False):
    """Grouped matmul with autodiff. `block_aligned=True` asserts every
    group size is a multiple of DEFAULT_BLOCK, enabling the Pallas TPU
    kernel; otherwise XLA's ragged_dot runs."""
    return _gmm_fwd(lhs, rhs, group_sizes, block_aligned)[0]


def _use_pallas(lhs, rhs, block_aligned):
    m, k = lhs.shape
    n = rhs.shape[2]
    return (block_aligned and on_tpu()
            and m % DEFAULT_BLOCK == 0 and k % DEFAULT_BLOCK == 0
            and n % DEFAULT_BLOCK == 0)


def _gmm_fwd(lhs, rhs, group_sizes, block_aligned):
    if _use_pallas(lhs, rhs, block_aligned):
        out = gmm_pallas(lhs, rhs.astype(lhs.dtype), group_sizes)
    else:
        out = _gmm_xla(lhs, rhs, group_sizes)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(block_aligned, res, dout):
    lhs, rhs, group_sizes = res
    rhs_t = jnp.swapaxes(rhs, 1, 2)               # (E, N, K)
    if _use_pallas(dout, rhs_t, block_aligned):
        dlhs = gmm_pallas(dout, rhs_t.astype(dout.dtype), group_sizes)
    else:
        dlhs = _gmm_xla(dout, rhs_t, group_sizes)
    # d(rhs)[e] = lhs_e^T @ dout_e — XLA's ragged_dot transpose rule
    _, pull = jax.vjp(lambda r: _gmm_xla(lhs, r, group_sizes), rhs)
    drhs, = pull(dout.astype(jnp.result_type(lhs.dtype, rhs.dtype)))
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype),
            jnp.zeros_like(group_sizes))


grouped_matmul_values.defvjp(_gmm_fwd, _gmm_bwd)
