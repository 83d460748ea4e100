"""Mixture-of-Experts with expert parallelism — TPU-native.

≙ reference «python/paddle/incubate/distributed/models/moe/» (MoELayer,
GShard/Switch gates) + the `global_scatter`/`global_gather` alltoall
dispatch ops («paddle/fluid/operators/collective/global_scatter_op*» [U?],
SURVEY.md §2.3 EP row).

TPU-native design — two dispatch strategies behind one MoELayer API:

* capacity (dense) path: dispatch/combine are one-hot einsums (GShard
  style, static shapes); experts are ONE stacked parameter (E, ...)
  sharded over the `ep` mesh axis, and the alltoall the reference
  hand-codes is inserted by XLA from the sharding of the dispatched
  (E, C, d) tensor. O(T·E·C) dispatch memory — fine at small E, used
  for expert-parallel execution.
* dropless (ragged, megablox-style) path: tokens sort by expert
  (O(T·k) memory, no capacity hyperparameter) and the expert FFN runs
  as grouped matmuls — the Pallas kernel in ops/grouped_matmul.py on
  TPU (block-padded groups), ragged_dot elsewhere. This is the
  DeepSeekMoE-scale path (E=64+), where the dense (T, E, C) tensors
  are catastrophic. Under expert parallelism, TWO dispatch modes:

  - exact mode (default, `ep_pair_capacity_factor=None`): ZERO drops
    under any routing skew. On TPU this is a TWO-PHASE exchange —
    per-pair counts are all-gathered, then `lax.ragged_all_to_all`
    moves ONLY the real rows, so just the ragged payload rides the ICI
    (the TPU-native equivalent of the reference's
    `global_scatter`/`global_gather` exactness); the receive buffer is
    still sized to the static ep·T_local·k worst case, the price of
    exactness under XLA's static shapes. On backends where XLA has no
    `ragged-all-to-all` (CPU — the 8-virtual-device test mesh), the
    same exactness is kept by a dense `lax.all_to_all` of worst-case
    per-pair buffers (ep× the bandwidth of the actual load); the two
    paths are numerically identical.
  - capacity mode (`ep_pair_capacity_factor=f`): static per-pair
    budget buffers (cheapest memory, bounded bandwidth); tokens beyond
    a pair's budget are DROPPED, and the layer surfaces a hard
    per-step drop counter (`MoELayer.last_drop_count`) so silent
    degradation is impossible.

Both use the standard load-balancing auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, apply
from ...nn import initializer as I
from ...nn.layer.layers import Layer

__all__ = ["moe_gating_values", "moe_ffn_values",
           "moe_ffn_dropless_values", "moe_ffn_dropless_ep_values",
           "MoELayer", "shard_moe"]


def moe_gating_values(logits, top_k: int, capacity: int):
    """GShard-style top-k capacity gating (all static shapes).

    logits: (T, E) router scores.
    Returns (dispatch (T, E, C) float {0,1}, combine (T, E, C) float,
    aux_loss scalar). Priority is choice-major: every token's 1st choice
    is placed before any 2nd choice, matching the reference gate.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)             # (T, K)

    # one-hot per choice: (K, T, E), then position of each (choice, token)
    # inside its expert's queue by cumulative count in priority order
    oh = jax.nn.one_hot(gate_idx.T, e, dtype=jnp.float32)         # (K, T, E)
    flat = oh.reshape(top_k * t, e)
    pos = jnp.cumsum(flat, axis=0) - flat                         # (K*T, E)
    pos = jnp.sum(pos * flat, axis=-1).astype(jnp.int32)          # (K*T,)
    keep = (pos < capacity) & (jnp.sum(flat, -1) > 0)
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) \
        * keep[:, None]                                           # (K*T, C)
    # (K, T, E, C): expert one-hot x capacity one-hot
    disp = (flat.reshape(top_k, t, e)[..., None]
            * pos_oh.reshape(top_k, t, 1, capacity))
    dispatch = jnp.sum(disp, axis=0)                              # (T, E, C)
    combine = jnp.sum(disp * gate_vals.T[..., None, None], axis=0)

    return dispatch, combine, _aux_loss(probs, gate_idx)


def moe_ffn_values(x2, gate_w, w_gate, w_up, w_down, top_k: int,
                   capacity_factor: float, ep_axis: Optional[str] = None,
                   mesh=None):
    """Dense-dispatch MoE SwiGLU FFN. x2: (T, H); gate_w: (H, E);
    stacked experts w_gate/w_up: (E, H, I), w_down: (E, I, H).
    Returns (out, aux, drops) — drops = routed slots beyond expert
    capacity (int32 scalar)."""
    t, h = x2.shape
    e = gate_w.shape[1]
    capacity = max(int(math.ceil(top_k * t / e * capacity_factor)), 1)
    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine, aux = moe_gating_values(logits, top_k, capacity)

    # capacity drops: routed slots that found no queue position
    drops = (jnp.float32(t * top_k)
             - jnp.sum(dispatch)).astype(jnp.int32)

    xe = jnp.einsum("tec,th->ech", dispatch.astype(x2.dtype), x2)  # (E,C,H)
    if ep_axis is not None and mesh is not None and \
            ep_axis in mesh.dim_names:
        from ...distributed.mesh import shard_constraint
        xe = shard_constraint(xe, ep_axis, None, None, mesh=mesh)
    hgate = jnp.einsum("ech,ehi->eci", xe, w_gate.astype(xe.dtype))
    hup = jnp.einsum("ech,ehi->eci", xe, w_up.astype(xe.dtype))
    ho = jax.nn.silu(hgate.astype(jnp.float32)).astype(xe.dtype) * hup
    oe = jnp.einsum("eci,eih->ech", ho, w_down.astype(xe.dtype))  # (E,C,H)
    if ep_axis is not None and mesh is not None and \
            ep_axis in mesh.dim_names:
        from ...distributed.mesh import shard_constraint
        oe = shard_constraint(oe, ep_axis, None, None, mesh=mesh)
    out = jnp.einsum("tec,ech->th", combine.astype(oe.dtype), oe)
    return out.astype(x2.dtype), aux, drops


def _aux_loss(probs, gate_idx):
    """Switch/GShard load-balance loss: E * sum_e f_e * p_e over choice 0."""
    e = probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                 axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)


def _expert_ffn_rows(xs_in, eid, w_gate, w_up, w_down, e: int):
    """Grouped SwiGLU FFN over rows with per-row expert ids.

    xs_in: (N, H); eid: (N,) int32 in [0, e) — rows that should not
    contribute must be ZERO rows (SwiGLU with no bias maps 0 -> 0).
    Returns (N, H) outputs in the caller's row order. Sorts by expert,
    runs the grouped matmul (Pallas kernel when block-aligned), unsorts.
    """
    from ...ops import on_tpu
    from ...ops.grouped_matmul import (DEFAULT_BLOCK,
                                       grouped_matmul_values)
    n, h = xs_in.shape
    i_size = w_gate.shape[2]

    order = jnp.argsort(eid, stable=True)         # expert-sorted row index
    es = eid[order]                               # (N,) sorted expert ids
    counts = jnp.bincount(eid, length=e)          # (E,)

    # the kernel's row tile, or 0: groups as they come, through XLA
    block_m = DEFAULT_BLOCK if (on_tpu() and h % DEFAULT_BLOCK == 0
                                and i_size % DEFAULT_BLOCK == 0) else 0
    if block_m:
        # pad each expert's group to a block_m multiple so no m-tile of
        # the Pallas kernel straddles a group boundary
        co = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])        # excl. offs
        padded = ((counts + block_m - 1) // block_m) * block_m
        po = jnp.concatenate([jnp.zeros(1, padded.dtype),
                              jnp.cumsum(padded)[:-1]])
        rank = jnp.arange(n) - co[es]
        pos = po[es] + rank                                    # padded row
        m_pad = ((n + e * block_m) // block_m + 1) * block_m   # static
        xs = jnp.zeros((m_pad, h), xs_in.dtype).at[pos].set(xs_in[order])
        gs = padded
    else:
        pos = None
        xs = xs_in[order]
        gs = counts

    hg = grouped_matmul_values(xs, w_gate.astype(xs.dtype), gs,
                               block_m)
    hu = grouped_matmul_values(xs, w_up.astype(xs.dtype), gs,
                               block_m)
    act = jax.nn.silu(hg.astype(jnp.float32)).astype(xs.dtype) * hu
    rows = grouped_matmul_values(act, w_down.astype(xs.dtype), gs,
                                 block_m)                # (M, H)
    if pos is not None:
        rows = rows[pos]                                       # (N, H)
    # unsort back to the caller's order
    return jnp.zeros_like(rows).at[order].set(rows)


def moe_ffn_dropless_values(x2, gate_w, w_gate, w_up, w_down, top_k: int):
    """Dropless sort-based MoE SwiGLU FFN (megablox-style).

    x2: (T, H); gate_w: (H, E); w_gate/w_up: (E, H, I); w_down: (E, I, H).
    Dispatch memory is O(T·k·H): tokens are gathered into expert-sorted
    order and the expert matmuls run grouped. No capacity, no drops.
    """
    t, h = x2.shape
    e = gate_w.shape[1]
    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)             # (T, K)

    flat = gate_idx.reshape(-1)                   # slot f=t*K+k -> expert
    tok = jnp.arange(t * top_k) // top_k          # source token per slot
    rows = _expert_ffn_rows(x2[tok], flat, w_gate, w_up, w_down, e)
    wv = gate_vals.reshape(-1).astype(jnp.float32)
    out = jnp.zeros((t, h), jnp.float32).at[tok].add(
        rows.astype(jnp.float32) * wv[:, None])
    return out.astype(x2.dtype), _aux_loss(probs, gate_idx)


def _ragged_ep_offsets(counts, me):
    """Offset bookkeeping for the two-phase ragged exchange.

    counts: (ep, ep) int32, counts[s, j] = rows shard s sends to shard
    j (the all-gathered per-pair counts). Receivers lay incoming rows
    out in sender order. For shard `me` returns, all (ep,) int32:
      out_off[j]      where my rows land in receiver j's buffer
      recv_sizes[s]   rows I receive from sender s
      recv_off[s]     where sender s's rows sit in my receive buffer
      back_out_off[s] where my returned rows land in sender s's
                      dst-sorted send layout (= s's own send offsets
                      toward me, recomputed here from the shared counts)
    """
    out_off = (jnp.cumsum(counts, axis=0) - counts)[me]
    recv_sizes = counts[:, me]
    recv_off = jnp.cumsum(recv_sizes) - recv_sizes
    back_out_off = (jnp.cumsum(counts, axis=1) - counts)[:, me]
    return out_off, recv_sizes, recv_off, back_out_off


def moe_ffn_dropless_ep_values(x2, gate_w, w_gate_l, w_up_l, w_down_l,
                               top_k: int, ep_size: int, axis_name: str,
                               token_axes, pair_capacity: int,
                               ragged: bool = False):
    """Per-shard body of the dropless × expert-parallel path. Runs INSIDE
    shard_map: x2 is this program's (T_local, H) token shard; w_*_l are
    the E/ep experts this shard owns.

    ≙ the reference's `global_scatter`/`global_gather` alltoall dispatch
    (SURVEY.md §2.3 EP row). Two exchange strategies:

    * ragged=False (every backend): each (src, dst) shard pair exchanges
      a fixed `pair_capacity`-row buffer via `lax.all_to_all` over the
      `ep` ICI axis. With pair_capacity = T_local·k (the static worst
      case — MoELayer's 'exact' mode, the default) NO routing skew can
      overflow a pair's buffer, so the exchange is EXACT like the
      reference's; with a smaller budget ('capacity' mode) overflow
      tokens are dropped and the returned drop counter (globally
      psum-reduced) surfaces exactly how many.
    * ragged=True (TPU only — XLA:CPU has no ragged-all-to-all thunk;
      always exact, `pair_capacity` is ignored): per-pair counts are
      all-gathered, then THREE `lax.ragged_all_to_all`s move only the
      real rows (tokens out, expert ids out, FFN rows home), so just
      the ragged payload rides the ICI. The receive buffer stays at the
      static ep·T_local·k worst case — static shapes — but bandwidth is
      proportional to the actual routed load, like `global_scatter`.

    Expert compute is the same grouped-matmul FFN either way.

    Returns (out (T_local, H), aux scalar, drops scalar int32 —
    replicated global count of dropped token-choices this step; always
    0 when ragged).
    """
    t_l, h = x2.shape
    e = gate_w.shape[1]
    e_l = e // ep_size
    cap = pair_capacity
    n = t_l * top_k

    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)             # (T_l, K)

    flat = gate_idx.reshape(-1)                   # (N,) global expert id
    tok = jnp.arange(n) // top_k
    dst = flat // e_l                             # target ep shard

    if ragged:
        out, aux = _moe_ep_ragged(x2, tok, flat, dst, gate_vals, probs,
                                  gate_idx, w_gate_l, w_up_l, w_down_l,
                                  e, e_l, ep_size, axis_name, token_axes)
        return out, aux, jnp.zeros((), jnp.int32)
    # rank of each slot within its destination's buffer (priority = slot
    # order, i.e. token-major / choice-minor)
    oh = jax.nn.one_hot(dst, ep_size, dtype=jnp.int32)
    rank = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(n), dst]
    keep = rank < cap
    idx = jnp.where(keep, dst * cap + rank, ep_size * cap)  # overflow slot

    send_x = jnp.zeros((ep_size * cap + 1, h), x2.dtype) \
        .at[idx].set(jnp.where(keep[:, None], x2[tok], 0))[:-1]
    send_e = jnp.zeros((ep_size * cap + 1,), jnp.int32) \
        .at[idx].set(flat % e_l)[:-1]

    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, axis_name, 0, 0, tiled=True)

    rows = _expert_ffn_rows(recv_x, jnp.clip(recv_e, 0, e_l - 1),
                            w_gate_l, w_up_l, w_down_l, e_l)

    back = jax.lax.all_to_all(rows.astype(x2.dtype), axis_name, 0, 0,
                              tiled=True)         # (ep*cap, H)
    slot_rows = jnp.where(keep[:, None],
                          back[jnp.minimum(idx, ep_size * cap - 1)], 0)
    wv = gate_vals.reshape(-1).astype(jnp.float32)
    out = jnp.zeros((t_l, h), jnp.float32).at[tok].add(
        slot_rows.astype(jnp.float32) * wv[:, None])
    # hard drop counter: every shard counts its overflowed slots; psum
    # over every token-sharding axis gives the replicated global count
    drops = jnp.sum(~keep).astype(jnp.int32)
    for ax in token_axes:
        drops = jax.lax.psum(drops, ax)
    # aux loss: pmean the FACTORS (routed fraction f, mean prob p) across
    # token shards before multiplying, so the scalar equals the
    # single-shard global aux exactly (mean of per-shard products would
    # be a biased estimator) and is replicated (out_spec P())
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                 axis=0)
    p = jnp.mean(probs, axis=0)
    for ax in token_axes:
        f = jax.lax.pmean(f, ax)
        p = jax.lax.pmean(p, ax)
    aux = e * jnp.sum(f * p)
    return out.astype(x2.dtype), aux, drops


def _moe_ep_ragged(x2, tok, flat, dst, gate_vals, probs, gate_idx,
                   w_gate_l, w_up_l, w_down_l, e, e_l, ep_size,
                   axis_name, token_axes):
    """Two-phase exact exchange: count all-gather + ragged_all_to_all.
    See moe_ffn_dropless_ep_values (ragged=True). TPU-only at runtime."""
    t_l, h = x2.shape
    n = t_l * gate_vals.shape[1]

    # dst-sorted send layout: receiver j's rows are contiguous
    order = jnp.argsort(dst, stable=True)                     # (N,)
    send_x = x2[tok[order]]
    send_e = (flat % e_l)[order].astype(jnp.int32)
    send_sizes = jnp.bincount(dst, length=ep_size).astype(jnp.int32)
    in_off = (jnp.cumsum(send_sizes) - send_sizes).astype(jnp.int32)

    # phase 1: per-pair counts ride a (tiny) all_gather
    counts = jax.lax.all_gather(send_sizes, axis_name)        # (ep, ep)
    me = jax.lax.axis_index(axis_name)
    out_off, recv_sizes, recv_off, back_out_off = \
        _ragged_ep_offsets(counts, me)

    # phase 2: only the real rows move; the receive buffer keeps the
    # static worst-case size (zeros beyond the received region — zero
    # rows contribute zero through the bias-free SwiGLU)
    r_buf = ep_size * n
    recv_x = jax.lax.ragged_all_to_all(
        send_x, jnp.zeros((r_buf, h), send_x.dtype), in_off,
        send_sizes, out_off, recv_sizes, axis_name=axis_name)
    recv_e = jax.lax.ragged_all_to_all(
        send_e, jnp.zeros((r_buf,), jnp.int32), in_off,
        send_sizes, out_off, recv_sizes, axis_name=axis_name)

    rows = _expert_ffn_rows(recv_x, recv_e, w_gate_l, w_up_l, w_down_l,
                            e_l)

    # route rows home into the sender's dst-sorted layout, then unsort
    back = jax.lax.ragged_all_to_all(
        rows.astype(x2.dtype), jnp.zeros((n, h), x2.dtype), recv_off,
        recv_sizes, back_out_off, send_sizes, axis_name=axis_name)
    slot_rows = jnp.zeros_like(back).at[order].set(back)

    wv = gate_vals.reshape(-1).astype(jnp.float32)
    out = jnp.zeros((t_l, h), jnp.float32).at[tok].add(
        slot_rows.astype(jnp.float32) * wv[:, None])
    # aux loss: pmean the factors (see the dense path's comment)
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                 axis=0)
    p = jnp.mean(probs, axis=0)
    for ax in token_axes:
        f = jax.lax.pmean(f, ax)
        p = jax.lax.pmean(p, ax)
    aux = e * jnp.sum(f * p)
    return out.astype(x2.dtype), aux


def _ragged_ep_supported() -> bool:
    """Gate for the ragged exact-EP exchange: XLA has a
    ragged-all-to-all thunk on TPU but not on CPU (verified UNIMPLEMENTED
    on jax 0.9.0 XLA:CPU). PDT_MOE_RAGGED=1/0 overrides for tests."""
    import os
    ov = os.environ.get("PDT_MOE_RAGGED")
    if ov is not None:
        return ov == "1"
    from ...ops import on_tpu
    return on_tpu()


class MoELayer(Layer):
    """Sparse SwiGLU MoE block (+ optional dense shared experts).
    ≙ paddle.incubate MoELayer / Qwen2-MoE & DeepSeekMoE sparse MLP [U?].

    forward(x) -> (out, aux_loss); x: (..., H).
    """

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25,
                 shared_intermediate_size: int = 0,
                 ep_axis: str = "ep", dropless: bool = False,
                 ep_pair_capacity_factor: Optional[float] = None,
                 name=None):
        """ep_pair_capacity_factor: None (default) = EXACT dropless-EP
        dispatch — per-pair buffers sized to the T_local·k worst case so
        no routing skew can drop a token (≙ reference global_scatter
        exactness; costs ep× the bandwidth of the uniform load). A float
        f bounds each pair's buffer at ≈ f·uniform-load instead; skewed
        routing beyond it drops tokens, and the global count lands in
        `self.last_drop_count` after every eager forward."""
        super().__init__()
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.dropless = dropless
        self.ep_pair_capacity_factor = ep_pair_capacity_factor
        self.last_drop_count: Optional[int] = None
        e, h, i = num_experts, hidden_size, intermediate_size
        self.gate_weight = self.create_parameter(
            (h, e), default_initializer=I.Normal(0.0, 0.02))
        self.w_gate = self.create_parameter(
            (e, h, i), default_initializer=I.XavierNormal(fan_in=h,
                                                          fan_out=i))
        self.w_up = self.create_parameter(
            (e, h, i), default_initializer=I.XavierNormal(fan_in=h,
                                                          fan_out=i))
        self.w_down = self.create_parameter(
            (e, i, h), default_initializer=I.XavierNormal(fan_in=i,
                                                          fan_out=h))
        if shared_intermediate_size:
            from ...nn import Linear
            self.shared_gate = Linear(h, shared_intermediate_size,
                                      bias_attr=False)
            self.shared_up = Linear(h, shared_intermediate_size,
                                    bias_attr=False)
            self.shared_down = Linear(shared_intermediate_size, h,
                                      bias_attr=False)
        else:
            self.shared_gate = None

    def forward(self, x):
        from ...distributed.mesh import get_mesh
        shape = x.shape
        h = shape[-1]
        e = self.num_experts
        mesh = get_mesh()
        top_k, cf, ep = self.top_k, self.capacity_factor, self.ep_axis
        ep_active = (mesh is not None and ep in mesh.dim_names
                     and mesh.get_dim_size(ep) > 1)
        pcf = self.ep_pair_capacity_factor

        def fn(xv, gw, wg, wu, wd):
            x2 = xv.reshape(-1, h)
            t = x2.shape[0]
            if self.dropless and ep_active:
                # dropless × EP: shard_map ragged-alltoall dispatch
                # (static per-pair buffers), ≙ global_scatter/gather
                ep_size = mesh.get_dim_size(ep)
                tok_axes = tuple(
                    a for a in ("dp", ep)
                    if a in mesh.dim_names and mesh.get_dim_size(a) > 1)
                n_shards = int(np.prod(
                    [mesh.get_dim_size(a) for a in tok_axes]))
                if t % n_shards == 0 and e % ep_size == 0:
                    from jax import shard_map as _shard_map
                    from jax.sharding import PartitionSpec as P
                    t_l = t // n_shards
                    use_ragged = False
                    if pcf is None:
                        # exact mode: zero drops under ANY routing
                        # (≙ global_scatter exactness). On TPU the
                        # two-phase ragged exchange moves only real
                        # rows; elsewhere the dense worst-case buffer
                        # (one shard can never send more than its own
                        # T_local*k slots to one destination) keeps
                        # the same exactness at ep× the bandwidth.
                        cap = t_l * top_k
                        use_ragged = _ragged_ep_supported()
                    else:
                        cap = max(1, min(
                            int(math.ceil(top_k * t_l / ep_size * pcf)),
                            t_l * top_k))

                    def body(x_l, gw_, wg_l, wu_l, wd_l):
                        return moe_ffn_dropless_ep_values(
                            x_l, gw_, wg_l, wu_l, wd_l, top_k, ep_size,
                            ep, list(tok_axes), cap, ragged=use_ragged)
                    # check_vma off: the grouped-matmul pallas_call in
                    # _expert_ffn_rows can't annotate vma on its outputs
                    mapped = _shard_map(
                        body, mesh=mesh.jax_mesh,
                        in_specs=(P(tok_axes, None), P(None, None),
                                  P(ep, None, None), P(ep, None, None),
                                  P(ep, None, None)),
                        out_specs=(P(tok_axes, None), P(), P()),
                        check_vma=False)
                    out, aux, drops = mapped(x2, gw, wg, wu, wd)
                    return out.reshape(xv.shape), aux, drops
                # fall through to capacity path on indivisible shapes
            elif self.dropless:
                out, aux = moe_ffn_dropless_values(x2, gw, wg, wu, wd,
                                                   top_k)
                return (out.reshape(xv.shape), aux,
                        jnp.zeros((), jnp.int32))
            out, aux, drops = moe_ffn_values(x2, gw, wg, wu, wd, top_k,
                                             cf, ep, mesh)
            return out.reshape(xv.shape), aux, drops

        out, aux, drops = apply("moe_ffn", fn,
                                (x, self.gate_weight, self.w_gate,
                                 self.w_up, self.w_down),
                                multi_output=True)
        # surface the hard drop counter when running eagerly (a traced
        # value would leak a tracer — skip inside jit)
        try:
            self.last_drop_count = int(drops._value)
        except Exception:
            self.last_drop_count = None
        if self.shared_gate is not None:
            from ...nn import functional as F
            out = out + self.shared_down(
                F.silu(self.shared_gate(x)) * self.shared_up(x))
        return out, aux


def shard_moe(layer, mesh, ep_axis: str = "ep"):
    """Place stacked expert params Shard(0) over the `ep` axis (the
    reference's expert-parallel group); gate + shared experts replicate."""
    from ...distributed.mesh import Replicate, Shard, shard_tensor
    if ep_axis not in mesh.dim_names:
        return layer
    for sub in layer.sublayers(include_self=True):
        if isinstance(sub, MoELayer):
            for pname in ("w_gate", "w_up", "w_down"):
                p = getattr(sub, pname)
                if p._value.shape[0] % mesh.get_dim_size(ep_axis):
                    import warnings
                    warnings.warn(
                        f"shard_moe: {pname} has {p._value.shape[0]} "
                        f"experts, not divisible by ep="
                        f"{mesh.get_dim_size(ep_axis)}; leaving it "
                        "replicated")
                    continue
                placements = [Replicate() for _ in mesh.dim_names]
                placements[mesh.dim_names.index(ep_axis)] = Shard(0)
                s = shard_tensor(p, mesh, placements)
                p._value = s._value
                p.dist_attr = s.dist_attr
    return layer
