"""Grouped (ragged) matmul — the MoE expert-compute primitive.

≙ reference MoE expert FFN loops + fused grouped GEMMs
(«python/paddle/incubate/distributed/models/moe/» experts executed per
group, SURVEY.md §2.3 EP row; §7 step-6 'grouped matmul (megablox-style)')
— re-designed for the MXU:

    out[r] = lhs[r] @ rhs[g(r)]        g(r) = expert owning row r

where rows are pre-sorted by expert and `group_sizes[e]` rows belong to
expert e. Two paths with identical semantics:

* Pallas kernels (TPU), one `pallas_call` named `grouped_matmul` a
  call, the rhs block index looked up per row tile from a
  scalar-prefetched tile→expert map. Every group size must be a
  multiple of block_m (the MoE dispatch pads each expert's rows to the
  block boundary — a bounded O(E·block_m) cost), so no tile straddles a
  group boundary. The caller picks block_m with `row_block` from the
  rows it expects in a group: 128 where a group holds hundreds of rows
  (training), 16 where it holds two or three (a decode step's share of
  an expert-parallel layer), so that the padding does not outgrow the
  rows. Two grids:
  - at 128 rows a tile (`gmm_pallas`), the classic blocked matmul over
    (m_tile, n_tile, k_tile) with blocks of 128, as the training path
    was measured;
  - at fewer rows (`gmm_stationary`: serving) the call is bound by the
    bytes of the experts' weights, so the grid is (n_block, m_tile)
    with the row tile INNERMOST, the whole of K in one block, and the
    weights copied by the kernel itself: they stay in HBM, and a tile
    that opens an expert's run of row tiles waits for that expert's
    block (K, block_n) and starts the copy of the NEXT hit expert's
    into the other of two VMEM buffers (`_weight_copies`). A call
    reads each hit expert's weights ONCE an n block, however its rows
    fall into tiles, and the copy flies while all the row tiles of the
    expert before it compute. With the row tile outermost every tile
    swept the n and k blocks from (0, 0) again: 1.43 reads an expert
    at 16 rows an expert on average. A BlockSpec on the weights reads
    them once too, but the pipeline looks ONE GRID STEP ahead: the next
    block's copy starts at an expert's last tile, and every tile
    before it (1.2-1.7 us each) runs with no copy in flight (PERF.md
    section 6, PR 32: 84 % of the HBM rate against 90 %). The n block
    is the largest 128-multiple dividing N whose two buffers fit
    `WEIGHT_VMEM_BUDGET`, from K, N and the dtype the call is given
    (all of N at the served widths). The lhs tile (block_m, K) is
    read once an n block: a few per cent of the weights' bytes.
  Tiles past the last group do no work and copy no weights.
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
# what `gmm_stationary`'s two buffers of a weight block (K, block_n)
# may take of VMEM; the lhs, the output and the float32 product of a
# row tile come on top, with VMEM_HEADROOM for what Mosaic keeps itself
WEIGHT_VMEM_BUDGET = 12 << 20
VMEM_HEADROOM = 2 << 20
# what Mosaic gives a kernel that asks for nothing (v5e)
DEFAULT_VMEM_LIMIT = 16 << 20

__all__ = ["grouped_matmul_values", "gmm_pallas", "gmm_stationary",
           "row_block"]


def row_block(rows_per_group: float) -> int:
    """The row alignment (block_m) for groups expected to hold about
    `rows_per_group` rows: the power of two at or above it, between
    MIN_BLOCK and DEFAULT_BLOCK. The caller pads each group to it and
    passes it as `block_m`."""
    want = max(math.ceil(rows_per_group), 1)
    return min(DEFAULT_BLOCK, max(MIN_BLOCK, 1 << (want - 1).bit_length()))


def _weight_block_n(k: int, n: int, itemsize: int) -> int:
    """The n block of `gmm_stationary`: the largest multiple of 128
    dividing `n` (a multiple of 128) such that two buffers of
    (k, block_n) fit WEIGHT_VMEM_BUDGET; 128 where none does."""
    fits = [t for t in range(DEFAULT_BLOCK, n + 1, DEFAULT_BLOCK)
            if n % t == 0 and 2 * k * t * itemsize <= WEIGHT_VMEM_BUDGET]
    return max(fits, default=DEFAULT_BLOCK)


def _tile_map(group_sizes, tiles: int, block_m: int):
    """(tile -> expert (tiles,), live tiles (1,)) for rows grouped in
    multiples of block_m. Tiles past the last group clamp to the last
    expert."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * block_m
    te = jnp.searchsorted(ends, tile_start, side="right").astype(jnp.int32)
    return jnp.minimum(te, group_sizes.shape[0] - 1), \
        (ends[-1:] // block_m).astype(jnp.int32)


def _run_map(group_sizes, tiles: int, block_m: int):
    """`_tile_map` and, a tile, what `gmm_stationary` needs to know of
    the RUN it lies in (the consecutive live tiles of one expert):
    first (tiles,) 1 where the tile opens its run, slot (tiles,) the
    weight buffer the run computes from (runs alternate), ahead
    (tiles,) the expert of the next run, -1 behind the last."""
    te, live = _tile_map(group_sizes, tiles, block_m)
    first = (jnp.arange(tiles) < live) & (te != jnp.roll(te, 1).at[0].set(-1))
    run = jnp.cumsum(first) - 1
    # the hit experts in order, then the rest
    order = jnp.argsort(group_sizes == 0, stable=True)
    ahead = jnp.where(run + 1 < jnp.sum(first),
                      order[jnp.minimum(run + 1, order.shape[0] - 1)], -1)
    return (te, first.astype(jnp.int32), (run & 1).astype(jnp.int32),
            ahead.astype(jnp.int32), live)


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


def _weight_copies(i, te, first, slot, ahead, live):
    """What row tile `i` of an n sweep does about weight blocks, as
    ((condition, expert, buffer) of each copy it STARTS, (condition,
    expert, buffer) of the copy it WAITS for). The sweep's first tile
    starts its own run's copy (nothing to hide that one behind); a tile
    that opens a run starts the NEXT run's copy into the other buffer,
    whose run is over, and waits for its own, which the run before
    started: an expert's block is copied once a sweep and flies while
    the row tiles of the expert before it compute. On scalars read
    from SMEM in the kernel; on numpy arrays in the tests."""
    opens = (i < live[0]) & (first[i] == 1)
    return (((i < live[0]) & (i == 0), te[0], 0),
            (opens & (ahead[i] >= 0), ahead[i], 1 - slot[i])), \
        (opens, te[i], slot[i])


def _gmm_stationary_kernel(te_ref, first_ref, slot_ref, ahead_ref, live_ref,
                           lhs_ref, rhs_hbm, out_ref, wbuf, sem, *, block_n):
    j, i = pl.program_id(0), pl.program_id(1)

    def copy(expert, buffer):
        return pltpu.make_async_copy(
            rhs_hbm.at[expert, :, pl.ds(j * block_n, block_n)],
            wbuf.at[buffer], sem.at[buffer])

    starts, (waits, expert, buffer) = _weight_copies(
        i, te_ref, first_ref, slot_ref, ahead_ref, live_ref)
    for on, ahead_expert, other in starts:
        pl.when(on)(copy(ahead_expert, other).start)
    pl.when(waits)(copy(expert, buffer).wait)
    live = i < live_ref[0]

    @pl.when(live)
    def _dot():
        out_ref[...] = mxu_dot(
            lhs_ref[...], wbuf[slot_ref[i]], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def gmm_stationary(lhs, rhs, group_sizes, block_m=MIN_BLOCK, block_n=None,
                   interpret=False):
    """`gmm_pallas`'s product and precondition on the grid (n block,
    row tile) with K whole and the weights copied by the kernel, one
    expert ahead (module docstring). `block_n` None takes
    `_weight_block_n`. Jitted, so that a program's layers, and a
    process's programs, trace the run map and the kernel once a shape
    and not once a call (40 ms each: seconds of an engine's set-up)."""
    (m, k), n = lhs.shape, rhs.shape[2]
    itemsize = jnp.dtype(rhs.dtype).itemsize
    if block_n is None:
        block_n = _weight_block_n(k, n, itemsize)
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    out_dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    # the two weight buffers, two buffers of the lhs and of the output
    # block, and the float32 product of a row tile
    vmem = 2 * (k * block_n * itemsize
                + block_m * k * jnp.dtype(lhs.dtype).itemsize
                + block_m * block_n * jnp.dtype(out_dtype).itemsize) \
        + block_m * block_n * 4 + VMEM_HEADROOM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda j, i, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((2, k, block_n), rhs.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(_gmm_stationary_kernel, block_n=block_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            # a sweep waits for every copy it starts: sweeps are
            # independent, row tiles are walked in order
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > DEFAULT_VMEM_LIMIT else None),
        interpret=interpret,
        name="grouped_matmul",
    )(*_run_map(group_sizes, m // block_m, block_m), lhs, rhs)


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
    kernel = gmm_pallas if block_m >= DEFAULT_BLOCK else gmm_stationary
    return kernel(lhs, rhs.astype(lhs.dtype), group_sizes, block_m=block_m)


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
