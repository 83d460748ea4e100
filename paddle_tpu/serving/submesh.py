"""Tensor-parallel serving submeshes: one replica = one GSPMD submesh.

Training already dry-runs 4D dp/mp/pp/ep meshes (distributed/mesh.py —
"this IS GSPMD", PAPERS.md arxiv 2105.04663); this module gives the
SERVING fleet the same footing. A `SubMesh` is a disjoint slice of the
global device set wrapped in a one-axis `jax.sharding.Mesh` (axis
`"tp"`), and a replica engine built over it shards its model math and
its paged KV cache across that slice:

* **Weights** — Megatron column/row placements expressed as
  NamedShardings (the `shard_llama` patterns, serving-side): q/k/v,
  gate/up and lm_head shard their OUTPUT dim over `tp`; embeddings
  shard the vocab dim. In the default **exact** mode o_proj/down_proj
  stay replicated and the engine fences their inputs with a
  replicate constraint (`distributed.mesh.serving_tp_replicate`), so
  the forward pass contains NO cross-device reduction — float
  accumulation order never changes and greedy outputs are
  BIT-IDENTICAL to tp=1 by construction. `TpConfig(mode="fast")`
  row-shards o_proj/down_proj instead (input dim over `tp`,
  partial-sum all-reduce), trading the determinism guarantee for the
  full Megatron compute split — bench-only until a tolerance-graded
  quality gate exists.
* **KV pages** — the page pools (P, page_size, HK*D) shard their
  stored ROW over `tp`; a row's lanes are head-major, so this is the
  KV-HEAD split: one LOGICAL page = `tp` local shards, each holding
  HK/tp contiguous heads of every resident token. The page allocator,
  block tables, and ragged descriptors stay host-side REPLICATED
  scalars — sharding never touches the accounting, so
  `check_invariants()` is unchanged and migration/export walk the
  same block-table windows.
* **Activations** — GSPMD propagation carries the head/feature
  sharding through rope, the ragged scatter, and attention (each
  device computes ITS heads' attention exactly as tp=1 does for those
  heads); the exact-mode fences above are the only explicit
  constraints.

`carve_submeshes(n, TpConfig(tp=k))` partitions `jax.devices()` into n
DISJOINT k-device slices — 8 devices serve 4 replicas x tp=2 or
2 x tp=4 — and `ServingRouter(tp=...)` hands one slice to each
`ReplicaHandle`, which keeps it across restarts: replica identity is
(submesh, generation). Failover needs no page movement (the router
re-prefills from its token mirror onto the survivor's own submesh);
migration serializes one payload FRAGMENT per shard
(`kv_fragments`, engine `export_pages`) so transfer bytes stay local
to each device's host link.

Telemetry (`pdt_tp_*`, docs/observability.md): carved-submesh gauge +
`tp.carve` event, sharded-dispatch counter, per-shard migration bytes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import observability as telemetry
from ..distributed import mesh as mesh_mod

__all__ = ["TP_AXIS", "TpConfig", "SubMesh", "carve_submeshes",
           "kv_fragments", "record_shard_bytes"]

# The ONE mesh-axis name serving shardings use. docs/serving.md
# "Tensor parallelism" documents it in the axis table, and a drift
# guard (tests/test_tp_serving.py) asserts the two stay equal — axis
# names are stringly-typed, and a silent rename would turn every
# NamedSharding below into a KeyError at first dispatch.
TP_AXIS = "tp"

_M_SUBMESHES = telemetry.gauge(
    "pdt_tp_submeshes",
    "Tensor-parallel submeshes carved by the most recent "
    "carve_submeshes call.")
_M_SHARDS = telemetry.gauge(
    "pdt_tp_shards",
    "Shards per replica (tp degree) of the most recently built "
    "TP engine.")
_M_DISPATCHES = telemetry.counter(
    "pdt_tp_dispatches_total",
    "Engine dispatches compiled/ran over a TP submesh (admission, "
    "decode, spec draft/verify, migration installs).")
_M_SHARD_BYTES = telemetry.counter(
    "pdt_tp_migration_shard_bytes_total",
    "Migration payload bytes serialized per TP shard (each fragment "
    "stays local to its device's host link).", ("shard",))


@dataclass
class TpConfig:
    """Tensor-parallel degree + determinism mode for serving replicas.

    `tp` devices per replica; `mode="exact"` (default) guarantees
    greedy outputs bit-identical to tp=1 (no cross-device reductions —
    module docstring), `mode="fast"` row-shards o_proj/down_proj for
    the full Megatron split (partial-sum all-reduce; NOT bit-exact)."""

    tp: int = 1
    mode: str = "exact"

    def __post_init__(self):
        if int(self.tp) < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.mode not in ("exact", "fast"):
            raise ValueError(f"mode {self.mode!r}: exact|fast")
        self.tp = int(self.tp)


class SubMesh:
    """One replica's device slice as a one-axis GSPMD mesh.

    Carries everything the engine needs to shard itself: the jax Mesh
    (axis `tp`), cached NamedShardings, the weight-spec table, and the
    `replicate_rows` flag `distributed.mesh.serving_tp_replicate`
    reads at trace time (True in exact mode — the determinism fence)."""

    def __init__(self, devices: Sequence, config: TpConfig):
        devices = list(devices)
        if len(devices) != config.tp:
            raise ValueError(f"submesh needs exactly tp={config.tp} "
                             f"devices, got {len(devices)}")
        self.config = config
        self.tp = config.tp
        self.devices = tuple(devices)
        self.device_ids = tuple(int(d.id) for d in devices)
        self.jax_mesh = Mesh(np.asarray(devices), (TP_AXIS,))
        self.replicate_rows = config.mode == "exact"
        self._repl = NamedSharding(self.jax_mesh, PartitionSpec())

    # -- shardings -------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return self._repl

    def sharding(self, *axes) -> NamedSharding:
        """NamedSharding with `tp` on the named tensor dims (None =
        unsharded dim), e.g. ``sharding(TP_AXIS, None)``."""
        return NamedSharding(self.jax_mesh, PartitionSpec(*axes))

    def kv_sharding(self, num_kv_heads: int) -> NamedSharding:
        """Page pools (P, page_size, HK*D): shard the stored row, whose
        lanes are head-major, so a shard holds HK/tp whole contiguous
        heads of every token (one logical page = tp local shards),
        when `tp` divides HK; replicate otherwise (draft pools with
        hk < tp)."""
        if num_kv_heads % self.tp == 0 and self.tp > 1:
            return self.sharding(None, None, TP_AXIS)
        return self._repl

    def validate_model(self, cfg) -> None:
        """A TARGET model must split cleanly: the whole TP story rests
        on per-head attention over head-sharded pages, so the head
        counts must divide (a replicated-page 'TP' engine would just
        be tp copies of the same work)."""
        if self.tp == 1:
            return
        bad = []
        if cfg.num_attention_heads % self.tp:
            bad.append(f"num_attention_heads {cfg.num_attention_heads}")
        if cfg.num_key_value_heads % self.tp:
            bad.append(f"num_key_value_heads {cfg.num_key_value_heads}")
        if bad:
            raise ValueError(
                f"model does not split over tp={self.tp}: "
                + ", ".join(bad) + " must be divisible by tp")

    def _param_spec(self, name: str, shape) -> PartitionSpec:
        """The serving-side Megatron placement table (mirrors
        `models.llama.shard_llama`'s mp patterns; weight layout is
        (in, out) — nn.Linear). Falls back to replicated whenever the
        would-be sharded dim does not divide."""
        nm = name.lower()
        spec = PartitionSpec()
        if "embed_tokens" in nm:
            spec = PartitionSpec(TP_AXIS)          # vocab rows; the
            # gather's cross-shard combine only ever adds exact zeros
        elif any(k in nm for k in ("q_proj", "k_proj", "v_proj",
                                   "gate_proj", "up_proj", "lm_head")):
            spec = PartitionSpec(None, TP_AXIS)    # column parallel
        elif any(k in nm for k in ("o_proj", "down_proj")):
            if self.replicate_rows:
                spec = PartitionSpec()             # exact mode: the
                # row matmul runs replicated behind the activation
                # all-gather fence — no partial-sum reduction, ever
            else:
                spec = PartitionSpec(TP_AXIS, None)  # fast: row split
        for tdim, ax in enumerate(spec):
            if ax is not None and shape[tdim] % self.tp:
                return PartitionSpec()             # does not divide
        return spec

    def shard_model_values(self, model):
        """device_put every parameter/buffer VALUE onto this submesh
        per the placement table; returns (param_values, buffer_values)
        aligned with `model.parameters()` / `model.buffers()`. The
        model OBJECT is untouched — replicas on different submeshes
        share it, each engine holding its own placed copies."""
        specs: Dict[int, PartitionSpec] = {}
        for name, p in model.named_parameters():
            specs[id(p)] = self._param_spec(name, p._value.shape)
        pv = [jax.device_put(
            p._value, NamedSharding(self.jax_mesh,
                                    specs.get(id(p), PartitionSpec())))
            for p in model.parameters()]
        bv = [jax.device_put(b._value, self._repl)
              for b in model.buffers()]
        _M_SHARDS.set(self.tp)
        return pv, bv

    def replicate_values(self, model):
        """Fully-replicated placement on this submesh (the draft model
        of a spec-decode TP engine: small by design, and its scan must
        live on the same devices as the verify pass)."""
        pv = [jax.device_put(p._value, self._repl)
              for p in model.parameters()]
        bv = [jax.device_put(b._value, self._repl)
              for b in model.buffers()]
        return pv, bv

    # -- trace scope -----------------------------------------------------
    def scope(self):
        """Context manager the engine wraps around jit dispatch calls:
        trace-time reads (`serving_tp_replicate` in llama.py) then see
        THIS submesh. Counting dispatches here keeps the metric at the
        one choke point every TP program passes through."""
        _M_DISPATCHES.inc()
        return mesh_mod.serving_tp_scope(self)

    def describe(self) -> Dict[str, object]:
        """Operator-facing placement summary (fleet_info/status.py)."""
        return {"tp": self.tp, "mode": self.config.mode,
                "devices": list(self.device_ids)}

    def __repr__(self):
        return (f"SubMesh(tp={self.tp}, mode={self.config.mode}, "
                f"devices={list(self.device_ids)})")


def carve_submeshes(num_replicas: int, config: TpConfig,
                    devices: Optional[Sequence] = None) -> List[SubMesh]:
    """Partition the device set into `num_replicas` DISJOINT contiguous
    tp-sized slices (contiguity keeps each replica's shards
    ICI-adjacent on real topologies — jax.devices() order is the
    platform's physical order). Raises when the fleet does not fit:
    submeshes never overlap, so a dead replica's compute cannot take a
    survivor down with it."""
    devs = list(devices if devices is not None else jax.devices())
    need = num_replicas * config.tp
    if need > len(devs):
        raise ValueError(
            f"{num_replicas} replicas x tp={config.tp} needs {need} "
            f"devices, have {len(devs)}")
    meshes = [SubMesh(devs[i * config.tp:(i + 1) * config.tp], config)
              for i in range(num_replicas)]
    _M_SUBMESHES.set(len(meshes))
    telemetry.event("tp.carve", replicas=num_replicas, tp=config.tp,
                    mode=config.mode,
                    devices=[m.device_ids for m in meshes])
    return meshes


def kv_fragments(arr, pages: np.ndarray,
                 head_dim: int) -> List[np.ndarray]:
    """Per-shard host gathers of one page pool's selected pages: one
    (hk_local, n_pages, page_size, hd) numpy fragment per TP shard (the
    payload's head-major shape, transposed on the host from the stored
    token-major rows), ordered by head offset. The gather
    `shard.data[pages]` executes ON that shard's device and only its
    result crosses to the host — migration bytes stay local to each
    device's host link (the serialize half of per-shard transfer;
    `export_pages`). Replicated arrays yield one fragment (every shard
    holds the whole pool)."""
    from ..ops.ragged_paged_attention import pages_to_payload
    by_off: Dict[int, object] = {}
    for s in arr.addressable_shards:
        off = s.index[2].start or 0
        if off not in by_off:               # replicated: keep one copy
            by_off[off] = s.data
    return [np.ascontiguousarray(pages_to_payload(
        np.asarray(by_off[off][pages]), by_off[off].shape[2] // head_dim))
        for off in sorted(by_off)]


def record_shard_bytes(nbytes_per_shard: Sequence[int]) -> None:
    """Count one migration's serialized payload bytes per shard index
    (`export_pages` passes each shard's total across layers)."""
    for i, nb in enumerate(nbytes_per_shard):
        _M_SHARD_BYTES.inc(int(nb), shard=str(i))
