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
  group boundary. The caller picks block_m with `row_block` from the
  rows it expects in a group: 128 where a group holds hundreds of rows
  (training), 16 where it holds two or three (a decode step's share of
  an expert-parallel layer), so that the padding does not outgrow the
  rows. At 128 rows a tile the n and k blocks are 128, as the training
  path was measured; at fewer rows they are the largest 128-multiples
  up to 1024 that divide the dimension: the kernel then streams each
  hit expert's weights once, in a few large blocks, and is bound by
  those bytes. Tiles past the last group do no work and fetch nothing.
* everywhere else (off the TPU, groups of any size, odd dimensions)
  the same walk in plain XLA (`_gmm_xla`): the rows are cut at every
  tile's and every group's end, and each piece is one small product.
  Its cost follows the live rows; XLA's `ragged_dot` off the TPU costs
  every row every expert (128 experts: 4 s for a decode step's matmul
  on the CPU against 0.04 s), so it is kept only as the transpose rule
  for d(rhs) in the custom vjp.

Rows beyond sum(group_sizes) produce zeros on both paths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu_dot, on_tpu

DEFAULT_BLOCK = 128
MIN_BLOCK = 16          # a bf16 tile's sublanes: the least rows a block holds
MAX_TILE = 1024

__all__ = ["grouped_matmul_values", "gmm_pallas", "row_block"]


def row_block(rows_per_group: float) -> int:
    """The row alignment (block_m) for groups expected to hold about
    `rows_per_group` rows: the power of two at or above it, between
    MIN_BLOCK and DEFAULT_BLOCK. The caller pads each group to it and
    passes it as `block_m`."""
    want = max(math.ceil(rows_per_group), 1)
    return min(DEFAULT_BLOCK, max(MIN_BLOCK, 1 << (want - 1).bit_length()))


def _tile(dim: int) -> int:
    """The largest multiple of 128, at most MAX_TILE, that divides
    `dim` (a multiple of 128)."""
    return max(t for t in range(DEFAULT_BLOCK, min(dim, MAX_TILE) + 1,
                                DEFAULT_BLOCK) if dim % t == 0)


def _tile_map(group_sizes, tiles: int, block_m: int):
    """(tile -> expert (tiles,), live tiles (1,)) for rows grouped in
    multiples of block_m. Tiles past the last group clamp to the last
    expert."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * block_m
    te = jnp.searchsorted(ends, tile_start, side="right").astype(jnp.int32)
    return jnp.minimum(te, group_sizes.shape[0] - 1), \
        (ends[-1:] // block_m).astype(jnp.int32)


def _gmm_kernel(te_ref, live_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                nk):
    kk = pl.program_id(2)
    live = pl.program_id(0) < live_ref[0]

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _dot():
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

    # tile -> expert map (scalar-prefetched). Tiles past the last group
    # are not live: they skip the dot (zeros come out) and keep ONE rhs
    # block index, so nothing is fetched for them.
    te, live = _tile_map(group_sizes, nmt, block_m)

    def rhs_block(i, j, kk, te_, live_):
        on = (i < live_[0]).astype(jnp.int32)
        return te_[i], kk * on, j * on

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nmt, nnt, nkt),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda i, j, kk, te_, live_: (i, kk)),
            pl.BlockSpec((1, block_k, block_n), rhs_block),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, te_, live_: (i, j)),
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
        name="grouped_matmul",
    )(te, live, lhs, rhs)


def _gmm_xla(lhs, rhs, group_sizes, block_m):
    """The kernel's walk in plain XLA for groups of any size. The row
    axis is cut at every multiple of `block_m` (MIN_BLOCK where it is
    0) and at every group's end; a piece between two cuts lies in ONE
    row tile and ONE group and is one (tile, K) @ (K, N) product of the
    tile's rows, the rows outside the piece zeroed, added into the
    tile's output. Only the pieces that hold rows are walked."""
    bm = block_m or MIN_BLOCK
    m, n = lhs.shape[0], rhs.shape[2]
    tiles = -(-m // bm)
    lhs = jnp.pad(lhs, ((0, tiles * bm - m), (0, 0)))
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    cuts = jnp.minimum(jnp.sort(jnp.concatenate(
        [jnp.arange(1, tiles + 1, dtype=jnp.int32) * bm, ends])), ends[-1])
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), cuts[:-1]])
    group = jnp.minimum(jnp.searchsorted(ends, starts, side="right"),
                        rhs.shape[0] - 1)
    at = jnp.arange(bm, dtype=jnp.int32)
    # the pieces that hold rows, in row order
    walk = jnp.argsort(cuts <= starts, stable=True)

    def piece(i, out):
        p = walk[i]
        lo, hi = starts[p], cuts[p]
        first = lo // bm * bm
        rows = jax.lax.dynamic_slice_in_dim(lhs, first, bm)
        mine = (first + at >= lo) & (first + at < hi)
        y = jnp.dot(jnp.where(mine[:, None], rows, 0),
                    rhs[group[p]].astype(lhs.dtype),
                    preferred_element_type=jnp.float32)
        old = jax.lax.dynamic_slice_in_dim(out, first, bm)
        return jax.lax.dynamic_update_slice_in_dim(
            out, old + y.astype(out.dtype), first, 0)

    out = jnp.zeros((tiles * bm, n), lhs.dtype)
    # inside a shard_map the loop's carry varies over the axes its
    # inputs vary over, from the start
    vma = set().union(*(jax.typeof(x).vma
                        for x in (lhs, rhs, group_sizes)))
    if vma:
        out = jax.lax.pcast(out, tuple(vma), to="varying")
    return jax.lax.fori_loop(0, jnp.sum(cuts > starts), piece, out)[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul_values(lhs, rhs, group_sizes, block_m=0):
    """Grouped matmul with autodiff. `block_m` > 0 asserts that every
    group size is a multiple of it (the caller padded each group:
    DEFAULT_BLOCK, or a `row_block`) and runs the kernel at that row
    tile on the TPU; 0 takes groups of any size through plain XLA."""
    return _gmm_fwd(lhs, rhs, group_sizes, block_m)[0]


def _gmm(lhs, rhs, group_sizes, block_m):
    m, k = lhs.shape
    n = rhs.shape[2]
    if not (block_m and on_tpu() and m % block_m == 0
            and k % DEFAULT_BLOCK == 0 and n % DEFAULT_BLOCK == 0):
        return _gmm_xla(lhs, rhs, group_sizes, block_m)
    wide = {} if block_m >= DEFAULT_BLOCK else \
        {"block_n": _tile(n), "block_k": _tile(k)}
    return gmm_pallas(lhs, rhs.astype(lhs.dtype), group_sizes,
                      block_m=block_m, **wide)


def _gmm_fwd(lhs, rhs, group_sizes, block_m):
    return _gmm(lhs, rhs, group_sizes, block_m), (lhs, rhs, group_sizes)


def _gmm_bwd(block_m, res, dout):
    lhs, rhs, group_sizes = res
    rhs_t = jnp.swapaxes(rhs, 1, 2)               # (E, N, K)
    dlhs = _gmm(dout, rhs_t, group_sizes, block_m)
    # d(rhs)[e] = lhs_e^T @ dout_e — XLA's ragged_dot transpose rule
    _, pull = jax.vjp(lambda r: jax.lax.ragged_dot(
        lhs, r.astype(lhs.dtype), group_sizes.astype(jnp.int32)), rhs)
    drhs, = pull(dout.astype(jnp.result_type(lhs.dtype, rhs.dtype)))
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype),
            jnp.zeros_like(group_sizes))


grouped_matmul_values.defvjp(_gmm_fwd, _gmm_bwd)
