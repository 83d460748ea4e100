"""The routed dispatch of a served expert layer: ONE copy, called by
every expert layer that runs over `ops/grouped_matmul.py`
(`nemotron_h.latent_experts_values`, `sdar.swiglu_experts_values`).

A layer is TOLD which experts it holds (`held` of them, from `offset`).
Its router has chosen `k` experts a packed row among all of them;
`route_rows` drops the assignments that fall on experts held elsewhere
BEFORE the sort (they take the group `held`, which sorts last and gets
no rows), sorts the rest by expert and pads every expert's rows to the
kernel's row tile; `combine_rows` gathers the experts' outputs back to
their rows and adds them under the router's weights; `report_spec` /
`report_counts` are the `cache_spec.ReportSpec` and the vector of counts
both layers hand the engine, over the three counters defined here.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from paddle_tpu import observability as telemetry
from paddle_tpu.models.cache_spec import ReportSpec

__all__ = ["Routed", "route_rows", "combine_rows", "report_spec",
           "report_counts"]

# what an expert layer counts a dispatch (cache_spec.ReportSpec)
_M_MOE_ASSIGNMENTS = telemetry.counter(
    "pdt_serving_moe_assignments_total",
    "Token-to-expert assignments of the dispatched live rows, summed "
    "over the expert layers, by kind: local = on an expert this "
    "program holds (computed), remote = on an expert held elsewhere "
    "(dropped before the sort).", ("kind",))
_M_MOE_EXPERTS = telemetry.counter(
    "pdt_serving_moe_experts_total",
    "Held experts a dispatch, summed over the expert layers, by kind: "
    "hit = got at least one row (its weights were read), idle = got "
    "none.", ("kind",))
_M_MOE_ROW_TILES = telemetry.counter(
    "pdt_serving_moe_row_tiles_total",
    "Live row tiles of a dispatch's grouped matmuls (an expert's rows "
    "padded to the kernel's row tile), summed over the expert layers, "
    "by kind: first = the first tile of its expert (the tile that "
    "fetches the expert's weights; equal to the experts hit), further "
    "= a tile behind another of the same expert, which runs on the "
    "weight block already fetched.", ("kind",))


def report_spec(k: int) -> ReportSpec:
    """What a routed expert layer of `k` choices a row reports: the
    six counts of `report_counts`, and each row's chosen experts."""
    return ReportSpec(
        ((_M_MOE_ASSIGNMENTS, "local"), (_M_MOE_ASSIGNMENTS, "remote"),
         (_M_MOE_EXPERTS, "hit"), (_M_MOE_EXPERTS, "idle"),
         (_M_MOE_ROW_TILES, "first"), (_M_MOE_ROW_TILES, "further")), (k,))


class Routed(NamedTuple):
    mine: jnp.ndarray      # (T, k) bool: the assignment is computed here
    counts: jnp.ndarray    # (held,) int32 rows an expert got
    padded: jnp.ndarray    # (held,) `counts` rounded up to `block_m`
    block_m: int           # the grouped matmul's row tile (static)
    src: jnp.ndarray       # (m_pad,) the packed row each sorted row reads
    dest: jnp.ndarray      # (T k,) the sorted row of each assignment
    m_pad: int             # sorted rows, padding included (static)


def route_rows(chosen, live, *, held: int, offset: int,
               n_experts: int) -> Routed:
    """`chosen` (T, k) int: each packed row's experts among all
    `n_experts`; `live` (T,) bool marks the rows that are tokens. The
    grouped matmuls then run as `grouped_matmul_values(x[r.src], w,
    r.padded, r.block_m)`."""
    from paddle_tpu.ops.grouped_matmul import row_block
    t, k = chosen.shape
    local = chosen - offset
    mine = (local >= 0) & (local < held) & live[:, None]
    gid = jnp.where(mine, local, held).reshape(-1)              # (T k,)
    counts = jnp.zeros(held + 1, jnp.int32).at[gid].add(1)[:held]
    bm = row_block(t * k / n_experts)
    padded = -(-counts // bm) * bm
    start_p = jnp.cumsum(padded) - padded
    start_u = jnp.cumsum(counts) - counts
    order = jnp.argsort(gid, stable=True)
    sgid = gid[order]
    sg = jnp.minimum(sgid, held - 1)
    m_pad = -(-(t * k + held * (bm - 1)) // bm) * bm            # static
    row = jnp.where(sgid < held,
                    start_p[sg] + jnp.arange(t * k) - start_u[sg], m_pad)
    src = jnp.zeros(m_pad, jnp.int32).at[row].set(order // k, mode="drop")
    dest = jnp.zeros(t * k, jnp.int32).at[order].set(row)
    return Routed(mine, counts, padded, bm, src, dest, m_pad)


def combine_rows(out_rows, wts, r: Routed):
    """The experts' sorted output rows (m_pad, width) back at their
    packed rows, summed under the router's weights `wts` (T, k) float32
    (an assignment held elsewhere adds nothing). Float32 (T, width)."""
    t, k = wts.shape
    rows = out_rows[jnp.minimum(r.dest, r.m_pad - 1)].reshape(t, k, -1)
    return jnp.einsum("tkl,tk->tl", rows.astype(jnp.float32),
                      jnp.where(r.mine, wts, 0.0))


def report_counts(r: Routed, live, k: int):
    """int32 (6,): assignments local and remote, held experts hit and
    idle, live row tiles that are the first of their expert and those
    behind another of the same expert, in `report_spec`'s order."""
    held = r.counts.shape[0]
    n_local, n_hit = jnp.sum(r.counts), jnp.sum(r.counts > 0)
    return jnp.stack([n_local, jnp.sum(live) * k - n_local,
                      n_hit, held - n_hit, n_hit,
                      jnp.sum(r.padded // r.block_m) - n_hit]
                     ).astype(jnp.int32)
