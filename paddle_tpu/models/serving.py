"""Continuous-batching serving loop over a PAGED KV cache.

≙ the reference inference engine's in-flight batching
(«paddle/fluid/inference/» serving stack + fused_multi_transformer /
masked_multihead_attention decode kernels, SURVEY.md §1 L10 / §2.1 fused
rows) — TPU-native:

* ONE compiled decode-step program serves the whole slot batch forever:
  (page pools, last tokens, per-slot positions, block tables) ->
  (next tokens, page pools), with per-slot positions flowing as a VECTOR
  through rope, the paged KV append, and the paged-attention context
  lengths. Slots at different sequence positions decode together — no
  recompilation, ever.
* The KV cache is a fixed pool of (page_size x D) pages per layer shared
  by all slots (vLLM-style). A host-side allocator hands pages out
  lazily as sequences grow and reclaims them when requests finish, so
  HBM-in-use is proportional to the tokens actually resident, not to
  B x S_max. Page 0 is a permanently reserved trash page: writes from
  inactive slots and padded prefill rows land there and are never read.
* Admission happens BETWEEN steps on the host: a request is admitted
  only when its WORST-CASE page demand fits the pool net of other
  slots' outstanding reservations — growth can then never strand a
  mid-flight request.
* EVERY admission goes through one ragged paged-attention dispatch
  (`ops/ragged_paged_attention.py`): the admitted prompts — full
  prefills, prefix-cache suffix prefills, and chunk continuations —
  are PACKED along one token axis with per-sequence (query_start,
  query_len, context_len) descriptors, so admitting N ragged prompts
  costs ONE dispatch instead of N, and the program key is the padded
  token count (packed lengths are rounded up to a padding grid so
  programs are reused, LRU-capped) and a power-of-two page bound.
  Decode is the same builder (`_build_ragged_step`) at block_q=1;
  speculation's draft backfill and verify pass are two more variants
  of it.
* Greedy decoding by default; temperature / top-k / top-p sampling rides
  the same compiled step via `_sample_token` (seeded, reproducible).
* `enable_prefix_caching=True` turns on vLLM-style AUTOMATIC PREFIX
  CACHING: a finished request's full-page prompt KV is retained
  (per-page refcounts, LRU eviction under pool pressure) and a later
  request with the same token prefix attaches those pages read-only —
  safe because full pages are immutable, decode only appends past them
  — and prefills just the suffix, whose rows attend the attached pages
  through the page table at their own positions.
* Sliding-window models: the ragged kernel applies the window band, and
  pages that slide wholly below the window are RECLAIMED between steps
  (their block-table entries trash-route), so resident KV is bounded by
  the window, not the sequence.
* The parity oracle is outside the engine: `model.generate()` through
  the dense-tuple cache (`models/generation.py`) — greedy streams are
  bit-identical to it per request, through preemption and failover.
* REQUEST LIFECYCLE HARDENING (≙ production TPU serving stacks, which
  treat KV-pool exhaustion and preemption as first-class events): a
  monotonic-clock tick per step expires requests past their deadline /
  max_queue_time (status `timeout`); `max_waiting` bounds the admission
  queue with explicit backpressure (`EngineOverloaded`) plus an
  `admission_policy` hook; a failed prefill finalizes only THAT request
  (status `failed`) and the engine keeps serving; decode-time page
  exhaustion preempts the youngest running request — its pages are
  released and it re-enters the queue head with generated tokens folded
  into the re-prefill prompt (prefix caching makes that cheap), with a
  starvation guard after `max_preemptions` evictions. `fault_point()`
  sites (`serving.alloc_page` / `serving.prefill` / `serving.decode`)
  make every failure branch forcible by deterministic chaos tests on the
  CPU mesh, and `check_invariants()` (every step under
  `PDT_CHECK_INVARIANTS=1`) proves page accounting stays consistent.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import time
import traceback
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..distributed import mesh as mesh_mod
from ..ops.ragged_paged_attention import pages_to_payload, payload_to_pages
from ..autograd import no_grad
from ..utils.faults import (FaultError, fault_point, fault_value,
                            value_armed)
from .. import observability as telemetry
from ..observability import profile as _profile
from .cache_spec import (KVSpec, RaggedStateView, ReportSpec, SharedKVSpec,
                         StateSpec)
from .generation import RequestStatus

_NULL_SCOPE = contextlib.nullcontext()

__all__ = ["ContinuousBatchingEngine", "Request", "RequestStatus",
           "SpecConfig", "QuantServingConfig", "EngineOverloaded",
           "PoolExhausted", "EngineInvariantError", "PayloadCorruption",
           "QuantMismatch", "assemble_payload_kv", "payload_checksums",
           "payload_scale_checksums", "verify_payload"]


def assemble_payload_kv(payload: dict):
    """Logical per-layer (k, v) page rows of a transfer payload.

    A single-chip source exports them directly (``payload["kv"]``); a
    tensor-parallel source exports one FRAGMENT per shard
    (``payload["kv_shards"]``: outer list = shard in head order, inner
    = layer) so serialize bytes stay local per device — this helper is
    the consumer-side view that reassembles the logical rows by
    concatenating fragments on the KV-head axis (`import_pages`, the
    prefix store's spill). The wire format stays the fragments."""
    if payload.get("kv") is not None:
        return payload["kv"]
    shards = payload["kv_shards"]
    layers = len(shards[0])
    if len(shards) == 1:
        return list(shards[0])
    return [(np.concatenate([s[li][0] for s in shards], axis=0),
             np.concatenate([s[li][1] for s in shards], axis=0))
            for li in range(layers)]


def payload_checksums(payload: dict):
    """Content checksums of a transfer payload's KV page bytes: one
    ``"sha256:<hex>"`` per key and value array of every SHARD FRAGMENT
    (the wire unit — `export_pages`), per layer, in wire order. The
    manifest.py hashing discipline applied to the transfer plane:
    hashes cover exactly the bytes that cross the device->host link,
    so a flipped byte anywhere in the payload is detectable before it
    installs into a target engine's pool."""
    shards = [payload["kv"]] if payload.get("kv") is not None \
        else payload["kv_shards"]
    return [[["sha256:" + hashlib.sha256(
                  np.ascontiguousarray(k).tobytes()).hexdigest(),
              "sha256:" + hashlib.sha256(
                  np.ascontiguousarray(v).tobytes()).hexdigest()]
             for k, v in shard] for shard in shards]


def payload_scale_checksums(payload: dict):
    """Content checksums of a QUANTIZED payload's per-page scale rows
    (`payload["kv_scales"]`, one (k_scale, v_scale) pair per layer —
    replicated across TP shards, so there is exactly one copy): a
    flipped scale byte corrupts every row of a page at dequant, so the
    scales are manifested exactly like the int8 page bytes. None for
    full-width payloads."""
    scales = payload.get("kv_scales")
    if scales is None:
        return None
    return [["sha256:" + hashlib.sha256(
                 np.ascontiguousarray(ks).tobytes()).hexdigest(),
             "sha256:" + hashlib.sha256(
                 np.ascontiguousarray(vs).tobytes()).hexdigest()]
            for ks, vs in scales]


def verify_payload(payload: dict) -> None:
    """Verify a payload's `kv_sha256` manifest against its actual KV
    bytes; raises :class:`PayloadCorruption` on any mismatch. A
    payload without a manifest (a pre-integrity producer) passes —
    `export_pages` always attaches one, so that case is foreign
    payloads only. Called by `import_pages` BEFORE any target
    mutation, so a corrupt payload leaves both engines consistent and
    the transfer plane counts it as a failure at stage ``verify``."""
    want = payload.get("kv_sha256")
    if want is None:
        return
    got = payload_checksums(payload)
    if got != [[list(pair) for pair in shard] for shard in want]:
        for s, (gs, ws) in enumerate(zip(got, want)):
            for layer, (gp, wp) in enumerate(zip(gs, ws)):
                if gp != list(wp):
                    raise PayloadCorruption(
                        f"KV payload checksum mismatch for request "
                        f"{payload.get('request_id')!r} at shard {s} "
                        f"layer {layer} — the payload was corrupted "
                        "in flight; refusing to install")
        raise PayloadCorruption(
            f"KV payload checksum manifest shape mismatch for request "
            f"{payload.get('request_id')!r} (manifest "
            f"{len(want)} shards vs payload {len(got)})")
    want_sc = payload.get("scales_sha256")
    if want_sc is not None:
        got_sc = payload_scale_checksums(payload)
        if got_sc != [list(pair) for pair in want_sc]:
            raise PayloadCorruption(
                f"KV payload SCALE checksum mismatch for request "
                f"{payload.get('request_id')!r} — the per-page dequant "
                "scales were corrupted in flight; refusing to install")


# -- telemetry (docs/serving.md "Observability" metric catalog) --------
# Instruments are process-global (all engines in a process aggregate)
# and created unconditionally — recording is a no-op unless telemetry
# is enabled (PDT_TELEMETRY=1 / telemetry.enable()).
_M_QUEUE_DEPTH = telemetry.gauge(
    "pdt_serving_queue_depth", "Requests waiting for a slot.")
_M_RUNNING = telemetry.gauge(
    "pdt_serving_running_slots", "Slots with an in-flight request.")
_M_ADMISSIONS = telemetry.counter(
    "pdt_serving_admissions_total",
    "Requests admitted into a slot (prefill dispatched successfully).")
_M_QUEUE_WAIT = telemetry.histogram(
    "pdt_serving_queue_wait_seconds",
    "Wait for a slot: enqueue (or requeue after a preemption) to the "
    "claim of a slot, engine clock. One observation a claim.")
_M_PREFILL_ROWS = telemetry.counter(
    "pdt_serving_prefill_rows_total",
    "Rows the ragged admission programs were dispatched with, by kind: "
    "token = a prompt's real tokens, pad = the rest of the padded "
    "token axis (block_q alignment and the padding grid).", ("kind",))
_M_ATTN_PAGES = telemetry.counter(
    "pdt_serving_attn_pages_total",
    "Block-table columns of one ragged attention call of a dispatch "
    "(admission or decode), by kind: walked = pages the kernel's loops "
    "visit (per q block the KV blocks of its live page range), skipped "
    "= q blocks x table columns less that, which a grid over the whole "
    "table would have stepped over.", ("kind",))
_M_REJECTIONS = telemetry.counter(
    "pdt_serving_rejections_total",
    "add_request refusals by reason.", ("reason",))
_M_TERMINAL = telemetry.counter(
    "pdt_serving_requests_terminal_total",
    "Requests reaching a terminal state, by final status.", ("status",))
_M_TTFT = telemetry.histogram(
    "pdt_serving_ttft_seconds",
    "Time to first token: enqueue to first prefill token, engine clock.")
_M_TPOT = telemetry.histogram(
    "pdt_serving_tpot_seconds",
    "Time per output token after the first, finished requests.",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5))
_M_DECODE_STEP = telemetry.histogram(
    "pdt_serving_decode_step_seconds",
    "Wall time of one batched decode dispatch incl. its D2H sync "
    "(the synchronous harvest_every=1 path).")
_M_DECODE_TOKENS = telemetry.counter(
    "pdt_serving_decode_tokens_total",
    "Tokens emitted by decode steps (excludes prefill first tokens).")
_M_TOKENS_PER_SEC = telemetry.gauge(
    "pdt_serving_tokens_per_sec",
    "Decode throughput of the most recent step (active slots / wall).")
_M_PREEMPTIONS = telemetry.counter(
    "pdt_serving_preemptions_total",
    "Preemption events (requeues and starvation finalizations).")
_M_DECODE_RETRIES = telemetry.counter(
    "pdt_serving_decode_retries_total",
    "Transient decode-dispatch faults retried.")
_M_PAGES_IN_USE = telemetry.gauge(
    "pdt_serving_pages_in_use", "Allocated KV pages.")
_M_PAGE_OCCUPANCY = telemetry.gauge(
    "pdt_serving_page_occupancy",
    "Fraction of usable KV pages allocated.")
_M_GROUP_OCCUPANCY = telemetry.gauge(
    "pdt_serving_group_page_occupancy",
    "Fraction of a page group's usable KV pages allocated, a series a "
    "group of a model with several (full, w<window>); "
    "pdt_serving_page_occupancy stays the first group's.", ("group",))
_M_KV_PAGES = telemetry.counter(
    "pdt_serving_kv_pages_total",
    "KV pages of a page group, by kind: allocated = taken from the "
    "group's free list for a slot, reclaimed = given back because they "
    "slid wholly below the group's window (a slot's release is not "
    "counted).", ("group", "kind"))
_M_ATTN_KV_ROWS = telemetry.counter(
    "pdt_serving_attn_kv_rows_total",
    "Stored K and V rows the attention calls of a dispatch must read, "
    "each row once a layer that reads it (a sequence's rows from its "
    "first query row's window edge to its context's end, times the "
    "layers that read the group's pools), by page group and by phase "
    "(decode or admit).", ("group", "phase"))
_M_PREFILL_LAYER_ROWS = telemetry.counter(
    "pdt_serving_prefill_layer_rows_total",
    "Rows x layers of the admission dispatches, by kind: run = what a "
    "dispatch ran, skipped = what it did not because the rows that "
    "sample nothing left after the model's last keeping layer "
    "(cache_spec: rows_leave_after).", ("kind",))
_M_INVARIANT_SECONDS = telemetry.histogram(
    "pdt_serving_invariant_check_seconds",
    "Duration of check_invariants() page-accounting sweeps.")
# -- layers that keep a state (models/cache_spec.py) -------------------
_M_STATE_BYTES = telemetry.gauge(
    "pdt_serving_state_bytes",
    "Bytes of the per-slot state arrays the engine allocated for the "
    "model's state layers (all slots, all layers).")
_M_STATE_SLOTS = telemetry.gauge(
    "pdt_serving_state_slots_live",
    "Slots whose state arrays hold a running sequence's state.")
# -- generation by diffusion over blocks (cache_spec.BlockDiffusionSpec)
_M_BLOCK_PASSES = telemetry.counter(
    "pdt_serving_block_passes_total",
    "Passes over a live slot's block, one count a live slot a pass, by "
    "kind: denoise = the dispatched block it samples held a mask (the "
    "pass decides positions; a finished block committed beside it counts "
    "here), commit = it held none (a block that ends its request, "
    "committed alone: the pass only leaves its keys and values).",
    ("kind",))
_M_BLOCK_COMMITS = telemetry.counter(
    "pdt_serving_block_commits_total",
    "Finished blocks whose keys and values a pass wrote, by kind: fused "
    "= in the first pass of the request's next block, alone = in a pass "
    "of its own (the block ends its request).", ("kind",))
_M_BLOCK_TOKENS = telemetry.counter(
    "pdt_serving_block_tokens_total",
    "Tokens of committed blocks handed to requests (a first block's "
    "given prompt tokens and what lies past a budget are not).")
_M_BLOCK_SECONDS = telemetry.histogram(
    "pdt_serving_block_seconds",
    "A slot's block from the start of its first pass to its tokens, "
    "engine clock.",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5))
# -- speculative decoding (spec_decode=SpecConfig(...), ISSUE 10) ------
_M_SPEC_ROUNDS = telemetry.counter(
    "pdt_spec_rounds_total",
    "Completed speculative decode rounds (draft + verify + commit).")
_M_SPEC_PROPOSED = telemetry.counter(
    "pdt_spec_proposed_total",
    "Draft tokens submitted to a verify pass.")
_M_SPEC_ACCEPTED = telemetry.counter(
    "pdt_spec_accepted_total",
    "Draft tokens the target's greedy verify accepted.")
_M_SPEC_ACCEPT_RATE = telemetry.gauge(
    "pdt_spec_acceptance_rate",
    "Running accepted/proposed fraction across all spec rounds.")
_M_SPEC_DEGRADED = telemetry.counter(
    "pdt_spec_degraded_total",
    "Spec rounds degraded to plain decode, by failing site.", ("site",))
_M_SPEC_DRAFT_SECONDS = telemetry.histogram(
    "pdt_spec_draft_seconds",
    "Wall time of one round's draft pass (backfill prefills + the "
    "k-step draft scan), incl. the D2H sync.")
_M_SPEC_VERIFY_SECONDS = telemetry.histogram(
    "pdt_spec_verify_seconds",
    "Wall time of one batched verify dispatch incl. the D2H sync.")
# -- quantized serving (quant=QuantServingConfig(...), ISSUE 15) -------
_M_QUANT_WEIGHT_LAYERS = telemetry.gauge(
    "pdt_quant_weight_layers",
    "Matmul weights held quantized (int8/fp8 + per-channel scale) by "
    "the most recently built quantized engine.")
_M_QUANT_WEIGHT_BYTES = telemetry.gauge(
    "pdt_quant_weight_bytes",
    "Bytes of the most recently built engine's quantized weights, "
    "storage plus scales (the HBM the full-width copies would have "
    "multiplied).")
_M_QUANT_PAGE_BYTES = telemetry.gauge(
    "pdt_quant_page_bytes",
    "Bytes of ONE quantized KV page across layers, int8 storage plus "
    "per-page-row scales (cache_memory_info page_bytes of the most "
    "recently built quantized engine).")
_M_QUANT_MISMATCH = telemetry.counter(
    "pdt_quant_mode_mismatch_total",
    "Cross-quant-mode installs refused with QuantMismatch, by entry "
    "path (import = migration payload, prefix = spill-chain restore).",
    ("kind",))
# -- multi-model serving (ISSUE 17, serving/model_store.py) ------------
_M_MODEL_MISMATCH = telemetry.counter(
    "pdt_model_mismatch_total",
    "Cross-model installs refused with ModelMismatch, by entry path "
    "(import = migration payload, adapter = unknown/non-resident "
    "adapter id at add_request or import).", ("kind",))
_M_LORA_RESIDENT = telemetry.gauge(
    "pdt_lora_adapters_resident",
    "LoRA adapter rows resident in the most recently mutated engine's "
    "stacked A/B tensors (row 0 — the all-zeros no-adapter row — "
    "excluded).")
_M_LORA_BYTES = telemetry.gauge(
    "pdt_lora_adapter_bytes",
    "Bytes held by the resident LoRA adapter stacks (A + B + per-row "
    "scales) across all adapted matmuls of the most recently mutated "
    "engine.")
_M_LORA_INSTALLS = telemetry.counter(
    "pdt_lora_installs_total",
    "Adapter rows installed into an engine's stacks (install_adapter "
    "commits).")
_M_LORA_EVICTIONS = telemetry.counter(
    "pdt_lora_evictions_total",
    "Adapter rows evicted from an engine's stacks (evict_adapter "
    "commits; refusals for in-flight use do not count).")


# what each part of a keyed family's program key is, as one letter of
# the program's name: `pdt_ragged_t512` is the admission program of 512
# padded tokens. The (t_pad, bound) families leave the page bound out:
# it trims the XLA fallback's gather and does not shape the program on
# the kernel path, and equal programs under one name are ONE entry of
# the persistent compile cache (with the bound in the name a cold
# set-up compiled every bound apart: +40 s, PERF.md PR 25).
_PROGRAM_KEY_LETTERS = {
    "ragged": "t", "draft": "t", "verify": "t",  # (t_pad, bound)
    "install": "n"}                              # pages


def _name_program(jitted, family: str, key=None):
    """Give a freshly built, never-called program a stable name from
    its family and key: `pdt_decode`, `pdt_ragged_t512`. JAX reads
    the wrapped function's `__name__` when it first traces it, and the
    HLO module is `jit_<name>` — what the profiler's `XLA Modules` line
    shows, so device time can be split by program (every builder's
    local function is called `run` otherwise). The seam is the one
    place that knows family and key; no builder names itself. The
    name is part of the persistent compile cache's key."""
    fn = getattr(jitted, "__wrapped__", None)
    if fn is None:
        return jitted
    name = f"pdt_{family}"
    if key is not None:
        parts = key if isinstance(key, tuple) else (key,)
        letters = _PROGRAM_KEY_LETTERS.get(family, "k" * len(parts))
        for letter, part in zip(letters, parts):
            name += f"_{letter}" + "".join(
                c if c.isalnum() else "_" for c in str(part))
    fn.__name__ = fn.__qualname__ = name
    return jitted


def _cache_spec(model) -> list:
    """What each layer of `model` keeps (models/cache_spec.py). A model
    that does not say keeps keys and values in every layer, of the
    last `sliding_window` positions where its config has one."""
    if hasattr(model, "cache_spec"):
        return list(model.cache_spec())
    cfg = model.config
    return [KVSpec(cfg.num_key_value_heads, cfg.head_dim,
                   getattr(cfg, "sliding_window", None))] \
        * cfg.num_hidden_layers


class _PageGroup:
    """The allocator's state for the KV layers of ONE `KVSpec`: layers
    that keep the same heads for the same window share a block table, a
    free list, reference counts and a reservation, and their pools have
    `num_pages` pages. `pools` are the indices (into the engine's pool
    list) of the layers that own a pool here; `readers_before` /
    `readers_after` count the layers whose attention call reads these
    pools (the owners and the `SharedKVSpec` layers on them), split at
    the model's `rows_leave_after()`. A `derived` group (a window group
    of a model with several groups) allocates a dispatch at a time and
    its pool follows from slots, window and chunk; every other group is
    sized by `num_pages` and reserves a sequence's worst case."""

    def __init__(self, spec: KVSpec, num_pages: int, slots: int, pps: int,
                 derived: bool = False, steady: int = 0):
        self.spec = spec
        self.window = spec.window
        self.name = "full" if spec.window is None else f"w{spec.window}"
        self.num_pages = int(num_pages)
        self.derived = derived
        self.steady = int(steady)     # a derived group's pages a slot
        self.pools: List[int] = []
        self.readers_before = self.readers_after = 0
        self.bt = np.zeros((slots, pps), np.int32)
        self.free: List[int] = list(range(1, self.num_pages))
        self.page_rc = np.zeros(self.num_pages, np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self.slot_reserved = np.zeros(slots, np.int64)
        # pages ever attached (shared + allocated) — the next block-
        # table index to fill; stays monotonic even after window
        # reclamation frees leading pages
        self.slot_next_idx = np.zeros(slots, np.int64)
        self.slot_freed = np.zeros(slots, np.int64)
        # pages taken from the free list / slid out below the window,
        # and what of each the telemetry counter has been told
        self.allocated = self.reclaimed = 0
        self.told = [0, 0]


class EngineOverloaded(RuntimeError):
    """add_request refused: the bounded admission queue is full or the
    admission policy rejected the request. Callers shed load or retry
    later (≙ a serving front end's 429)."""


class PoolExhausted(RuntimeError):
    """A KV page allocation could not be satisfied even after prefix-
    cache eviction. Admission reservation makes this unreachable on the
    healthy path; decode-time growth converts it into preemption."""


class EngineInvariantError(AssertionError):
    """check_invariants() found inconsistent page accounting."""


class PayloadCorruption(ValueError):
    """A transfer payload's KV bytes do not match its `kv_sha256`
    manifest (`verify_payload`). Raised by `import_pages` BEFORE any
    target mutation: both engines stay consistent, the transfer plane
    counts ``pdt_transfer_failures_total{stage="verify"}``, and the
    router keeps the request decoding on its source (falling back to
    folded-token failover re-prefill if that source later dies)."""


class QuantMismatch(ValueError):
    """A KV install crossed quantization modes: a quantized engine's
    payload (int8 pages + per-page scales) offered to a full-width
    engine, or vice versa — the page bytes are not interpretable on
    the other side, so installing them would be silent corruption,
    not a conversion. Raised by `import_pages` / `import_prefix`
    BEFORE any target mutation and counted
    ``pdt_quant_mode_mismatch_total{kind=}``; fleets must be
    quant-homogeneous (docs/serving.md "Quantized serving")."""


class ModelMismatch(ValueError):
    """A request or KV install crossed MODEL identity (ISSUE 17): a
    migration payload produced under one hosted model (``model_tag``
    and adapter) offered to an engine serving another — its pages
    encode a different function of the weights, so installing them
    would be silent cross-model corruption — or a request names a LoRA
    adapter that is not resident in this engine's stacks. Raised
    BEFORE any target mutation and counted
    ``pdt_model_mismatch_total{kind=}``; the fleet store
    (`serving/model_store.py`) installs the right artifact before
    dispatch, so a counted refusal here means routing skipped the
    store (docs/serving.md "Multi-model serving")."""


@dataclass
class SpecConfig:
    """Speculative decoding as an ENGINE mode (ISSUE 10 / ROADMAP 4):
    every decode round drafts `k` greedy tokens per active slot with
    `draft_model` over its own paged KV cache (one fused k-step scan —
    ONE dispatch, no host round-trips between draft steps), then
    verifies every slot in ONE batched target pass through the ragged
    dispatch (each slot a (query_start, query_len=k+1, context_len)
    descriptor), accepts the longest matching prefix plus the bonus
    token (`speculative.spec_accept_greedy` — the same acceptance core
    as `speculative_generate`), and rewinds per-slot context lengths
    past the rejected positions (stale K/V in rewound cells is sound:
    the next round's scatter overwrites them before any query's causal
    mask can admit them — `speculative.py`'s trash-routing argument).
    Greedy outputs are BIT-IDENTICAL to the non-speculative engine.

    `draft_model` must share the target's vocabulary and cover
    `max_seq_len` with its rope table; `num_pages` sizes the draft
    page pool (default: the full `B x pages_per_seq` worst case —
    the draft cache has no prefix sharing, so unlike the target pool
    it cannot lean on attached pages). Greedy engines only
    (`do_sample=False`); sampling callers use the standalone
    `speculative_generate`, whose rejection-sampling path needs its
    own key discipline."""

    draft_model: object
    k: int = 4
    num_pages: Optional[int] = None


# the Megatron-placed matmuls a quantized engine converts — exactly the
# weights serving/submesh.py's placement table shards (embeddings stay
# full-width: the embed lookup is a gather, not a matmul, and a tied
# lm_head reuses the embedding so it is excluded with it)
QUANT_MATMULS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
                 "up_proj", "down_proj", "lm_head")


@dataclass
class QuantServingConfig:
    """Quantized serving as an ENGINE mode (ISSUE 15 / ROADMAP 2):
    ``ContinuousBatchingEngine(quant=QuantServingConfig(...))``.

    ``weights``: ``"int8"`` | ``"fp8"`` | None — the Megatron-placed
    matmul weights (`QUANT_MATMULS`) are converted at engine build to
    quantized storage + one f32 scale per OUTPUT channel
    (`ops.quant_matmul.quantize_weight_values`) and consumed by the
    fused dequant-matmul epilogue (`dequant_matmul_values`; the
    per-channel scale multiplies the f32 accumulator, exact). Under
    tensor parallelism the scales shard with their out dim. The model
    OBJECT is untouched — the engine binds `QuantizedWeight` values
    per dispatch, so replicas sharing one model compose.

    ``kv``: ``"int8"`` | None — the KV page pools store int8 with
    (P, page_size) f32 per-page-row DEQUANT scales
    (`ragged_scatter_quantized` quantizes on commit, the ragged
    kernel dequantizes per page in flight). Half-width pages double
    concurrent residency and prefix-store warmth per byte and halve
    migration payloads; per-ROW quantization keeps the bytes
    path-invariant, so quantized-mode greedy streams stay
    BIT-IDENTICAL through preemption / failover / migration /
    quarantine re-serve (values differ from bf16 within a test-pinned
    logit-error budget). Spec-decode draft pools quantize alongside.

    Fleets must be quant-homogeneous: cross-mode migration or spill
    restore is refused with :class:`QuantMismatch`."""

    weights: Optional[str] = None
    kv: Optional[str] = None

    def __post_init__(self):
        if self.weights not in (None, "int8", "fp8"):
            raise ValueError(
                f"quant weights {self.weights!r}: int8|fp8|None")
        if self.kv not in (None, "int8"):
            raise ValueError(f"quant kv {self.kv!r}: int8|None")
        if self.weights is None and self.kv is None:
            raise ValueError(
                "QuantServingConfig with neither weights nor kv set — "
                "drop the quant= argument instead")


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    output: List[int] = field(default_factory=list)
    done: bool = False
    status: str = RequestStatus.QUEUED
    deadline: Optional[float] = None     # absolute engine-clock time
    max_queue_time: Optional[float] = None
    enqueue_time: float = 0.0
    preemptions: int = 0
    error: Optional[str] = None
    # engine clock, stamped whether or not telemetry is on: the claim
    # of a slot (the latest one, after a preemption) and the first
    # token. TTFT = first_token_time - arrival_time; its queueing part
    # is the `serving.queue_wait` record of each claim.
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    arrival_time: float = 0.0      # original add_request tick: TTFT base
    # (enqueue_time restarts on requeue — it feeds max_queue_time)
    # stable caller-scoped identity: `rid` is engine-local and restarts
    # from 0 in every engine, so a fleet router re-dispatching a request
    # onto a survivor replica needs an id that follows the request
    # across engines. Surfaced in telemetry events and failover logs;
    # defaults to str(rid) for single-engine callers.
    request_id: str = ""
    # QoS lane ordering (serving/admission.py Lane.PRIORITY): lower
    # admits first; FIFO within a priority class. 0 = interactive,
    # 1 = batch for router-submitted work
    priority: int = 0
    # multi-model serving (ISSUE 17): the resident LoRA adapter this
    # request decodes under (None = the bare hosted base). Validated
    # against the engine's stacks at add_request / import_pages and
    # threaded into every ragged dispatch as the slot's adapter row.
    adapter: Optional[str] = None
    # pipelined decode staleness contract (harvest_every=k, ISSUE 18):
    # tokens the DEVICE has produced, counting deferred dispatches the
    # host has not harvested yet — always >= len(output), resynced to
    # it at every harvest (an EOS inside the window clamps the
    # overshoot away). The synchronous k=1 path leaves it at 0; read
    # it as max(device_len, len(output)) like FleetRequest.device_len
    # does.
    device_len: int = 0


class ContinuousBatchingEngine:
    """In-flight batched serving for cache-capable causal LMs
    (LlamaForCausalLM-family: forward(ids, past_key_values,
    position_offset, use_cache))."""

    def __init__(self, model, max_batch_size: int = 8,
                 max_seq_len: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 prompt_pad: int = 16,
                 kv_layout: str = "paged",
                 attention_impl: str = "ragged",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 do_sample: bool = False,
                 temperature: float = 1.0,
                 top_k: int = 0,
                 top_p: float = 1.0,
                 seed: int = 0,
                 max_prefill_programs: int = 8,
                 enable_prefix_caching: bool = False,
                 max_prefix_entries: int = 32,
                 prefill_chunk: Optional[int] = None,
                 max_waiting: Optional[int] = None,
                 request_timeout: Optional[float] = None,
                 max_queue_time: Optional[float] = None,
                 max_preemptions: int = 3,
                 max_decode_retries: int = 3,
                 admission_policy: Optional[
                     Callable[["ContinuousBatchingEngine", Request],
                              bool]] = None,
                 clock: Optional[Callable[[], float]] = None,
                 spec_decode: Optional[SpecConfig] = None,
                 submesh=None,
                 quant: Optional[QuantServingConfig] = None,
                 harvest_every: int = 1):
        # the engine keeps its cache one way and runs one attention path;
        # the two keywords stay only as checked input while
        # benchmark/configs/*.json pass them (ROADMAP D1a)
        if kv_layout != "paged" or attention_impl != "ragged":
            raise ValueError(
                f"kv_layout={kv_layout!r}, attention_impl="
                f"{attention_impl!r}: the dense layout and the legacy "
                "attention path were removed in PR 29 — the engine "
                "serves kv_layout='paged' with attention_impl='ragged' "
                "only")
        cfg = model.config
        self.model = model
        # -- what each layer keeps (models/cache_spec.py): pools for
        # the KV layers only, a (slots, ...) array per state array of a
        # state layer, nothing for the rest (a reporting layer's counts
        # and records come back beside the tokens). Every feature that
        # takes "a sequence's cache is its pages" for granted refuses a
        # model with state layers, by name.
        self._layer_spec = _cache_spec(model)
        self._state_spec = [s for s in self._layer_spec
                            if isinstance(s, StateSpec)]
        self._report_spec = [s for s in self._layer_spec
                             if isinstance(s, ReportSpec)]
        if self._state_spec:
            for feature, asked in (
                    ("enable_prefix_caching", enable_prefix_caching),
                    ("spec_decode", spec_decode is not None),
                    ("quant.kv", quant is not None and quant.kv),
                    ("quant.weights", quant is not None and quant.weights),
                    ("submesh tp > 1", submesh is not None
                     and int(submesh.tp) > 1),
                    ("harvest_every > 1", int(harvest_every) > 1)):
                if asked:
                    self._refuse_state(feature)
        # -- how generation proceeds (models/cache_spec.py): a token a
        # sequence a step, or by diffusion over blocks. Read once, here;
        # what takes a token to be a step refuses a block model by name.
        gen = getattr(model, "generation_spec", None)
        self._gen = gen() if gen is not None else None
        self._dblock = 1 if self._gen is None else \
            int(self._gen.block_length)
        if self._gen is not None:
            self._gen.check()
            for feature, asked in (
                    ("spec_decode", spec_decode is not None),
                    ("harvest_every > 1", int(harvest_every) > 1),
                    ("do_sample", do_sample),
                    ("quant.kv", quant is not None and quant.kv),
                    ("quant.weights", quant is not None and quant.weights),
                    ("submesh tp > 1", submesh is not None
                     and int(submesh.tp) > 1),
                    ("a state layer", bool(self._state_spec))):
                if asked:
                    self._refuse_blocks(feature)
        # -- pipelined decode (ISSUE 18, docs/serving.md "Pipelined
        # decode"): harvest_every=k defers the D2H token sync — the
        # greedy-sampled token stays ON DEVICE and feeds step N+1's
        # dispatch, with one batched harvest (sync + commits + sentry
        # checks) every k dispatches. k=1 IS today's synchronous loop.
        self.harvest_every = int(harvest_every)
        if self.harvest_every < 1:
            raise ValueError(
                f"harvest_every must be >= 1, got {harvest_every}")
        if self.harvest_every > 1:
            if do_sample:
                raise ValueError(
                    "harvest_every > 1 is greedy-only: a window "
                    "dispatched past another slot's EOS consumes PRNG "
                    "keys the synchronous loop never drew, desyncing "
                    "the sampling stream from the k=1 oracle")
            if spec_decode is not None:
                raise ValueError(
                    "harvest_every > 1 does not compose with "
                    "spec_decode — a speculative round's verify pass "
                    "IS its synchronous harvest")
        # -- quantized serving (QuantServingConfig docstring) ----------
        self._quant = quant
        self._qw_mode = quant.weights if quant is not None else None
        self._qkv = quant.kv if quant is not None else None
        # -- tensor parallelism (serving/submesh.py, docs/serving.md
        # "Tensor parallelism"): one engine = one GSPMD submesh -------
        # Param/buffer values are device_put onto the submesh per the
        # column/row placement table and the KV page pools shard their
        # KV-head axis (one logical page = tp local shards); ALL host-
        # side accounting (allocator, block tables, descriptors) stays
        # replicated scalars, untouched by sharding.
        self._tp = submesh
        if submesh is not None:
            submesh.validate_model(cfg)
        self.B = int(max_batch_size)
        self.S = int(max_seq_len or cfg.max_position_embeddings)
        if self.S > cfg.max_position_embeddings:
            # past the precomputed rope table the traced gather would
            # silently clamp to the last row — wrong angles forever
            raise ValueError(
                f"max_seq_len {self.S} exceeds the model's rope table "
                f"(max_position_embeddings="
                f"{cfg.max_position_embeddings})")
        # -- page groups (models/cache_spec.py): the KV layers of one
        # (heads, head size, window) share an allocator. The engine
        # learns windows and sharing from the specification and from
        # nowhere else. The window-less group comes first: `num_pages`
        # sizes it, and `_bt`, `_free`, ... below are ITS arrays.
        kv_spec = [s for s in self._layer_spec if isinstance(s, KVSpec)]
        # a model without a KV layer keeps the page bookkeeping (block
        # tables, reservations) over zero pools
        group_specs = sorted(dict.fromkeys(kv_spec or [KVSpec(1, 1)]),
                             key=lambda g: g.window is not None)
        shares = any(isinstance(s, SharedKVSpec) for s in self._layer_spec)
        self._grouped = len(group_specs) > 1 or shares
        if self._grouped:
            for feature, asked in (
                    ("enable_prefix_caching", enable_prefix_caching),
                    ("spec_decode", spec_decode is not None),
                    ("harvest_every > 1", int(harvest_every) > 1),
                    ("quant.kv", quant is not None and quant.kv),
                    ("submesh tp > 1", submesh is not None
                     and int(submesh.tp) > 1)):
                if asked:
                    self._refuse_groups(feature)
        windowed = any(g.window is not None for g in group_specs)
        if windowed and enable_prefix_caching:
            # slid-out pages are reclaimed and their block-table entries
            # trash-routed, so a window model's prompt pages are not
            # stable shareable KV
            import warnings
            warnings.warn(
                "sliding_window model: prefix caching is DISABLED "
                "(window reclamation invalidates cached prompt pages)")
            enable_prefix_caching = False
        self.eos = eos_token_id
        self.pad = int(prompt_pad)
        self.strategy = "sampling" if do_sample else "greedy_search"
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self._key = jax.random.PRNGKey(int(seed))
        self._max_prefill = int(max_prefill_programs)
        self._params = list(model.parameters())
        self._buffers = list(model.buffers())
        if self._tp is not None:
            # the engine holds its OWN placed copies — replicas on
            # different submeshes share one model object
            self._tp_pv, self._tp_bv = \
                self._tp.shard_model_values(model)
        dt = self._params[0]._value.dtype
        self._state = [
            tuple(jnp.zeros((int(max_batch_size),) + tuple(shape), d)
                  for shape, d in zip(s.shapes, s.dtypes))
            for s in self._state_spec]
        # a slot's state is live iff the slot holds a dispatched sequence
        self._state_live = np.zeros(int(max_batch_size), bool)
        self.page_size = int(page_size)
        self.pps = -(-self.S // self.page_size)
        # +1: page 0 is the reserved trash page
        self.num_pages = int(num_pages or self.B * self.pps + 1)
        if self.page_size % self._dblock or self.S % self._dblock:
            # a page then holds whole blocks (so a cached prompt page
            # depends on no later token) and the last block fits
            raise ValueError(
                f"page_size {self.page_size} and max_seq_len {self.S} "
                f"must be multiples of the model's block_length "
                f"{self._dblock}")
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is "
                             "reserved)")
        self._groups: List[_PageGroup] = []
        for g in group_specs:
            derived = g.window is not None and len(group_specs) > 1
            pages, steady = self.num_pages, 0
            if derived:
                # a slot between dispatches holds the window's pages,
                # the one that straddles its edge and the one being
                # written; a dispatch adds its rows' pages, and slots x
                # a sequence's pages is the most there can ever be
                steady = -(-g.window // self.page_size) + 2
                rows = int(prefill_chunk) if prefill_chunk else self.S
                pages = 1 + min(self.B * self.pps, self.B * steady
                                + -(-(g.window + rows) // self.page_size))
            self._groups.append(_PageGroup(g, pages, self.B, self.pps,
                                           derived, steady))
        self._window_groups = [g for g in self._groups
                               if g.window is not None]
        # which layers read which group, and which group a pool is in
        leave = getattr(model, "rows_leave_after", None)
        self._leave_after = None if leave is None else int(leave())
        self._pool_group: List[int] = []
        layer_group = {}
        for i, s in enumerate(self._layer_spec):
            if isinstance(s, KVSpec):
                layer_group[i] = group_specs.index(s)
                self._groups[layer_group[i]].pools.append(
                    len(self._pool_group))
                self._pool_group.append(layer_group[i])
            elif isinstance(s, SharedKVSpec):
                if s.source_layer not in layer_group \
                        or s.source_layer >= i:
                    raise ValueError(
                        f"layer {i} shares the keys and values of layer "
                        f"{s.source_layer}, which is not an earlier "
                        "KVSpec layer")
                layer_group[i] = layer_group[s.source_layer]
            else:
                continue
            g = self._groups[layer_group[i]]
            if self._leave_after is not None and i > self._leave_after:
                g.readers_after += 1
            else:
                g.readers_before += 1
        primary = self._groups[0]
        L = len(primary.pools)
        hk, hd = primary.spec.num_kv_heads, primary.spec.head_dim
        self._kv_shape = (L, hk, hd, dt)

        def _pool(g: _PageGroup):
            # stored token-major, the layout the row scatter writes
            # (ops/ragged_paged_attention.py): a token's row over all
            # KV heads is contiguous, so the write updates the donated
            # pool in place
            pool_dt = jnp.int8 if self._qkv else dt
            z = jnp.zeros((g.num_pages, self.page_size,
                           g.spec.num_kv_heads * g.spec.head_dim), pool_dt)
            if self._tp is None:
                return z
            # sharded allocator contract: the pool splits its rows
            # by KV head, so every page id names tp local shards
            return jax.device_put(
                z, self._tp.kv_sharding(g.spec.num_kv_heads))

        def _spool():
            # per-page-row dequant scales of a QUANTIZED pool:
            # head-free (one scale per row, shared by every head),
            # so they REPLICATE over a TP submesh like the
            # descriptors
            z = jnp.zeros((self.num_pages, self.page_size),
                          jnp.float32)
            if self._tp is None:
                return z
            return jax.device_put(z, self._tp.replicated())

        pool_groups = [self._groups[i] for i in self._pool_group]
        if self._qkv:
            self._kv = [(_pool(g), _pool(g), _spool(), _spool())
                        for g in pool_groups]
        else:
            self._kv = [(_pool(g), _pool(g)) for g in pool_groups]
        # the first group's arrays under the names the engine has always
        # had for them: one and the same objects, never rebound
        self._bt = primary.bt
        self._free = primary.free
        self._slot_pages = primary.slot_pages
        self._slot_reserved = primary.slot_reserved
        self._slot_next_idx = primary.slot_next_idx
        self._slot_freed = primary.slot_freed
        # -- automatic prefix caching (vLLM-style, opt-in) ---------
        # Full pages are immutable once written (decode only appends
        # past them), so a finished request's full-page prompt KV can
        # be SHARED read-only by later requests with the same token
        # prefix: the new request attaches the cached pages to its
        # block table and prefills only the suffix (its rows attend
        # the attached pages through the page table). The cache is a
        # PAGE TRIE (≙ vLLM hash-chain / SGLang radix): one node per
        # (parent, page-of-tokens), so match/registration are O(p_len)
        # and key memory is linear, with exact-token keys (no hash-
        # collision risk). Per-page refcounts arbitrate slots + trie
        # nodes; childless LRU nodes are evicted under pool pressure.
        self._prefix_enabled = bool(enable_prefix_caching)
        self._max_prefix_entries = int(max_prefix_entries)
        self._page_rc = primary.page_rc
        # node key -> {"page": id, "parent": key|None, "children": n}
        self._prefix_nodes: "OrderedDict[tuple, dict]" = OrderedDict()
        self._slot_shared_pages: List[List[int]] = \
            [[] for _ in range(self.B)]
        # migration/prefix-store page-content installs, by count
        self._install_jits: "OrderedDict[int, object]" = OrderedDict()
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # chunked prefill (vLLM-style): an admission dispatch packs at
        # most `prefill_chunk` tokens; a longer prompt continues in the
        # next dispatch, attending its earlier rows through its pages
        # (_ragged_batches)
        self._chunk = int(prefill_chunk) if prefill_chunk else None
        if self._chunk is not None:
            if self._chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk {self._chunk} must be a multiple "
                    f"of page_size {self.page_size}")
            if self.S % self._chunk:
                raise ValueError(
                    f"max_seq_len {self.S} must be a multiple of "
                    f"prefill_chunk {self._chunk}")
        # host-side slot state
        self._pos = np.zeros(self.B, np.int32)        # next write position
        self._tok = np.zeros(self.B, np.int32)        # last emitted token
        # a block model's slot holds a BLOCK in flight at positions
        # [_pos, _pos + block): its ids, which of them are still masked
        # (a flag, so a token that equals the mask id stays a token),
        # how many lead tokens were given by the prompt, the denoising
        # passes it has had and when its first pass began
        self._blk_ids = np.zeros((self.B, self._dblock), np.int32)
        self._blk_masked = np.zeros((self.B, self._dblock), bool)
        self._blk_given = np.zeros(self.B, np.int32)
        self._blk_passes = np.zeros(self.B, np.int32)
        self._blk_t0 = np.full(self.B, np.nan)
        self._slot_req: List[Optional[Request]] = [None] * self.B
        self._queue: List[Request] = []
        self._next_rid = 0
        # -- request-lifecycle robustness (deadlines / backpressure /
        # preemption — module docstring, last bullet) ------------------
        self.max_waiting = None if max_waiting is None else int(max_waiting)
        self.request_timeout = request_timeout
        self.max_queue_time = max_queue_time
        self.max_preemptions = int(max_preemptions)
        self.max_decode_retries = int(max_decode_retries)
        self.admission_policy = admission_policy
        self._clock = clock if clock is not None else time.monotonic
        self.num_timeouts = 0
        self.num_failures = 0
        # formatted traceback of the latest failure the engine healed
        # (an isolated admission, a retried decode dispatch): the
        # one-line `Request.error` names it, this says where it rose
        self.last_failure: Optional[str] = None
        self.num_preemptions = 0
        self.num_decode_retries = 0
        self._consec_decode_faults = 0
        self._finished_backlog: List[Request] = []
        self._admit_seq = 0                 # global admission order
        self._slot_seq = np.zeros(self.B, np.int64)
        self._decode_jit = None
        # deferred-harvest window (harvest_every > 1): one entry per
        # un-harvested dispatch {nxt (device), lg (device|None), scan,
        # act (active slots — constant within a window), pos (host
        # position snapshot AFTER the dispatch)}; _tok_dev is the last
        # dispatch's on-device token vector, the ring that feeds the
        # next dispatch without a host round-trip
        self._pending: List[dict] = []
        self._tok_dev = None
        self._window_wall = 0.0             # dispatch walls this window
        # gray-failure defense (ISSUE 14, serving/sentry.py): an
        # attached numeric sentry observes every token harvest (and,
        # every Nth step, the ragged decode program's sampled-row
        # logits); fault_tag pins corrupt-mode VALUE faults
        # (serving.kv_page / serving.logits) to THIS engine — a fleet
        # replica sets it to its index, so one sick chip is drillable
        # inside a healthy fleet
        self._sentry = None
        self._decode_logits = False
        self.fault_tag: Optional[str] = None
        # -- multi-model serving (ISSUE 17, serving/model_store.py) ----
        # hosted-model identity: model_tag is None for the build-time
        # weights; install_weights() swaps the whole dispatch value
        # list (same pytree structure — no retrace) and stamps the tag
        # migration payloads are matched on (ModelMismatch otherwise).
        self.model_tag: Optional[str] = None
        self._mpv = None                 # install_weights override
        self._mpv_nbytes = 0
        # batched multi-LoRA decode (ops/lora_epilogue.py): per adapted
        # matmul a stacked (R, K, r)/(R, r, N) pair whose row 0 is the
        # all-zeros no-adapter row; _slot_adapter maps each slot to its
        # request's row and rides every ragged dispatch as the
        # per-token gather vector
        self._lora = None
        self._adapter_rows: Dict[str, int] = {}
        self._lora_free_rows: List[int] = []
        self._slot_adapter = np.zeros(self.B, np.int32)
        # ragged path: ONE program family keyed only on the padded
        # token count of the admission batch (the decode program lives
        # in _decode_jit at block_q=1)
        self._ragged_jits: "OrderedDict[int, object]" = OrderedDict()
        self._ragged_block_q = 8
        # -- speculative decoding (SpecConfig docstring) ---------------
        self._spec = spec_decode
        self.num_spec_rounds = 0
        self.num_spec_proposed = 0
        self.num_spec_accepted = 0
        self.num_spec_degraded = 0
        if spec_decode is not None:
            if do_sample:
                raise ValueError(
                    "spec_decode is greedy-only (bit-identical to the "
                    "plain engine); for sampling use "
                    "models.speculative.speculative_generate")
            if windowed:
                raise ValueError(
                    "spec_decode does not compose with sliding_window "
                    "models (window page reclamation would race the "
                    "draft cache's rewind bookkeeping)")
            if int(spec_decode.k) < 1:
                raise ValueError(
                    f"spec_decode.k must be >= 1, got {spec_decode.k}")
            draft = spec_decode.draft_model
            d_cfg = draft.config
            if d_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {d_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            if d_cfg.max_position_embeddings < self.S:
                raise ValueError(
                    f"draft rope table ({d_cfg.max_position_embeddings}"
                    f" positions) does not cover max_seq_len {self.S}")
            self._spec_k = int(spec_decode.k)
            self._d_params = list(draft.parameters())
            self._d_buffers = list(draft.buffers())
            if self._tp is not None:
                # the draft must live on the SAME submesh as the
                # verify pass; it is small by design, so replicate
                # (its pages shard only when its own hk divides tp —
                # kv_sharding falls back to replicated otherwise)
                self._tp_d_pv, self._tp_d_bv = \
                    self._tp.replicate_values(draft)
            d_hk = d_cfg.num_key_value_heads
            d_hd = d_cfg.head_dim
            d_dt = self._d_params[0]._value.dtype
            # full worst case by default: every slot may hold its whole
            # context in the draft cache with nothing shared (page 0 is
            # the draft pool's trash page, mirroring the target pool)
            self._d_num_pages = int(spec_decode.num_pages
                                    or self.B * self.pps + 1)
            def _d_pool():
                z = jnp.zeros((self._d_num_pages, self.page_size,
                               d_hk * d_hd),
                              jnp.int8 if self._qkv else d_dt)
                if self._tp is None:
                    return z
                return jax.device_put(z, self._tp.kv_sharding(d_hk))

            def _d_spool():
                z = jnp.zeros((self._d_num_pages, self.page_size),
                              jnp.float32)
                if self._tp is None:
                    return z
                return jax.device_put(z, self._tp.replicated())

            if self._qkv:
                # the draft cache rides the same quantized page layout
                # — draft pools are the other half of the KV byte bill
                self._d_kv = [(_d_pool(), _d_pool(), _d_spool(),
                               _d_spool())
                              for _ in range(d_cfg.num_hidden_layers)]
            else:
                self._d_kv = [(_d_pool(), _d_pool())
                              for _ in range(d_cfg.num_hidden_layers)]
            self._d_bt = np.zeros((self.B, self.pps), np.int32)
            self._d_free: List[int] = list(range(1, self._d_num_pages))
            self._d_slot_pages: List[List[int]] = \
                [[] for _ in range(self.B)]
            self._d_next_idx = np.zeros(self.B, np.int64)
            # draft-cache validity: rows [0, _pos) of the slot's stream
            # are resident iff _d_valid — cleared on release/degrade so
            # fresh admissions, preemption re-prefills, and migration
            # imports rebuild (or keep dropping) the draft cache lazily
            self._d_valid = np.zeros(self.B, bool)
            self._d_scan_jit = None
            self._d_prefill_jits: "OrderedDict[tuple, object]" = \
                OrderedDict()
            self._verify_jits: "OrderedDict[tuple, object]" = \
                OrderedDict()
            # greedy ignores sampling keys — one constant key serves
            # every spec dispatch without perturbing the engine stream
            self._spec_key = jax.random.PRNGKey(0)
            # verify packing: k+1 live rows per slot. On the XLA
            # oracle path any alignment is legal, so pack EXACTLY
            # (zero padding rows — at k=4 a block_q=8 pack would
            # compute 8 rows per slot for 5 live, a 60% attention
            # tax); the Pallas kernel keeps the MXU-friendly 8-row
            # q blocks
            from ..ops import on_tpu
            self._verify_block_q = self._ragged_block_q if on_tpu() \
                else self._spec_k + 1
        # -- quantized weights (QuantServingConfig docstring) ----------
        self._qpv = None
        if self._qw_mode is not None:
            self._qpv = self._build_quant_weights()
        if self._qkv:
            L_, hk_, hd_, dt_ = self._kv_shape
            _M_QUANT_PAGE_BYTES.set(
                self.page_size * hk_ * hd_ * 2 * L_      # int8 storage
                + self.page_size * 4 * 2 * L_)           # f32 scales

    def _refuse_state(self, feature: str):
        raise ValueError(
            f"{feature} is not supported for a model with state layers "
            f"({type(self.model).__name__}): it takes a sequence's cache "
            "to be its pages, and this model also keeps a recurrent "
            "state per slot")

    def _refuse_groups(self, feature: str):
        raise ValueError(
            f"{feature} is not supported for a model whose layers keep "
            f"their keys and values differently "
            f"({type(self.model).__name__}: window, full or shared "
            "layers in page groups): it takes a sequence's cache to be "
            "one list of pages that every layer fills (ROADMAP M2)")

    def _refuse_blocks(self, feature: str):
        raise ValueError(
            f"{feature} is not supported for a model that generates by "
            f"diffusion over blocks ({type(self.model).__name__}): it "
            "takes a decode step to give one token a sequence, and this "
            f"model's step is a pass over a block of {self._dblock} "
            "positions that may decide any of them")

    def _state_nbytes(self) -> int:
        return self.B * sum(s.nbytes() for s in self._state_spec)

    def _cache(self):
        """What a step program takes, donated, as its cache: the page
        pools, and beside them the state arrays where the model has
        state layers."""
        return (self._kv, self._state) if self._state_spec else self._kv

    def _take_step(self, out, logits: bool = False):
        """Unpack a ragged step program's outputs, keep the new cache,
        and return (tokens, logit rows or None, the reporting layers'
        (counts, records) or None)."""
        nxt, rest = out[0], list(out[1:])
        rows = rest.pop(0) if logits else None
        cache = rest.pop(0)
        if self._state_spec:
            self._kv, self._state = cache
        else:
            self._kv = cache
        return nxt, rows, (rest[0] if rest else None)

    def _harvest_reports(self, reports, rows, slots, positions):
        """What the reporting layers (cache_spec.ReportSpec) handed back
        from one dispatch. Their counts go into the counters the
        specification names, with telemetry on only; the records of the
        dispatch's live packed rows `rows` (of `slots`, at `positions`)
        go to a sentry that takes them. Each is one more D2H pull, so
        neither happens unasked."""
        self._count_reports(reports[0])
        self._give_layer_rows(reports[1], rows, slots, positions)

    def _count_reports(self, counts):
        """The reporting layers' counts of one dispatch into the
        counters the specification names, with telemetry on only."""
        if telemetry.enabled():
            names = [c for s in self._report_spec for c in s.counters]
            for (counter, kind), n in zip(names, np.asarray(counts)):
                counter.inc(int(n), kind=kind)

    def _give_layer_rows(self, records, rows, slots, positions):
        """The reporting layers' records of packed rows `rows` (of
        `slots`, at `positions`), to a sentry that takes them."""
        take = getattr(self._sentry, "observe_layer_rows", None)
        if take is not None:
            take(np.asarray(slots), np.asarray(positions),
                 [np.asarray(r)[rows] for r in records])

    def _build_quant_weights(self):
        """Quantize the Megatron-placed matmul weights once at engine
        build: the dispatch param list swaps each converted weight's
        value for a `QuantizedWeight` (int8/fp8 storage + per-OUT-
        channel f32 scale) that `nn.functional.linear` routes through
        the fused dequant-matmul epilogue. The model object is never
        mutated. Under TP the storage takes the weight's own placement
        and the scale shards WITH ITS OUT DIM (a column-sharded weight
        owns a slice of output channels; each shard dequantizes with
        exactly its channels' scales)."""
        from ..ops.quant_matmul import (QuantizedWeight,
                                        quantize_weight_values)
        names = {id(p): nm for nm, p in self.model.named_parameters()}
        base = self._tp_pv if self._tp is not None \
            else [p._value for p in self._params]
        out, n_q, n_bytes = [], 0, 0
        for p, bv in zip(self._params, base):
            nm = names.get(id(p), "").lower()
            if p._value.ndim != 2 \
                    or not any(k in nm for k in QUANT_MATMULS):
                out.append(bv)
                continue
            qw, sc = quantize_weight_values(p._value, self._qw_mode)
            if self._tp is not None:
                spec = self._tp._param_spec(nm, p._value.shape)
                qw = jax.device_put(qw, self._tp.sharding(*spec))
                out_ax = spec[1] if len(spec) > 1 else None
                sc = jax.device_put(sc, self._tp.sharding(out_ax))
            w = QuantizedWeight(qw, sc)
            n_q += 1
            n_bytes += w.nbytes
            out.append(w)
        _M_QUANT_WEIGHT_LAYERS.set(n_q)
        _M_QUANT_WEIGHT_BYTES.set(n_bytes)
        return out

    # -- multi-model serving (ISSUE 17, serving/model_store.py) --------
    def _place_replicated(self, arr):
        if self._tp is None:
            return arr
        return jax.device_put(arr, self._tp.replicated())

    def install_adapter(self, adapter_id: str, deltas: dict,
                        scale: float = 1.0) -> None:
        """Install one LoRA adapter into the engine's stacked adapter
        tensors (batched multi-LoRA decode, ops/lora_epilogue.py).
        ``deltas`` maps adapted parameter names (named_parameters keys
        of 2D matmul weights) to ``(A, B)`` pairs — A (K, r), B (r, N)
        over the (K, N) base — applied as ``x @ W + scale·(x@A)@B``.

        Safe MID-FLIGHT: appending a stack row never changes existing
        rows, and a live token's per-row gather reads only its own row
        — running streams stay bit-identical through a neighbour's
        cold install (the router's cold-install fallback leans on
        this). Every adapter in an engine must adapt the SAME
        parameter set at the SAME rank (the fleet store pads ranks to
        its ``max_rank`` constant at registration, which is also what
        keeps streams bit-identical across fleets hosting different
        adapter subsets). Transactional: all stacks are rebuilt before
        any engine state changes. Requires the ragged paged dispatch
        family; refuses to compose with prefix caching (cached KV is a
        function of the weights — a shared trie would silently alias
        KV across adapters), spec decode, and chunked prefill."""
        if self._state_spec:
            self._refuse_state("install_adapter")
        if self._prefix_enabled:
            raise ValueError(
                "install_adapter refuses to compose with prefix "
                "caching: cached KV pages are a function of the "
                "weights, so a shared trie would alias KV across "
                "adapters — build the engine with "
                "enable_prefix_caching=False to serve multi-LoRA")
        if self._spec is not None:
            raise ValueError(
                "install_adapter does not compose with spec_decode "
                "(the draft cache's rewind bookkeeping has no "
                "per-adapter dimension)")
        if self._chunk is not None:
            raise ValueError(
                "install_adapter does not compose with prefill_chunk "
                "(the chunk program does not thread the per-token "
                "adapter-row vector)")
        if adapter_id in self._adapter_rows:
            raise ValueError(f"adapter {adapter_id!r} already resident")
        if not deltas:
            raise ValueError("install_adapter with empty deltas")
        names = {nm: p for nm, p in self.model.named_parameters()}
        idx = {nm: i for i, (nm, _) in
               enumerate(self.model.named_parameters())}
        rank = None
        prepared = {}
        for nm, (a, b) in sorted(deltas.items()):
            p = names.get(nm)
            if p is None:
                raise ValueError(f"adapter {adapter_id!r} targets "
                                 f"unknown parameter {nm!r}")
            if p._value.ndim != 2:
                raise ValueError(
                    f"adapter {adapter_id!r} targets non-matmul "
                    f"parameter {nm!r} (ndim {p._value.ndim})")
            a = np.asarray(a)
            b = np.asarray(b)
            k, n = p._value.shape
            if a.ndim != 2 or b.ndim != 2 or a.shape[0] != k \
                    or b.shape[1] != n or a.shape[1] != b.shape[0]:
                raise ValueError(
                    f"adapter {adapter_id!r} delta for {nm!r}: A "
                    f"{a.shape} / B {b.shape} do not factor the "
                    f"({k}, {n}) base")
            if rank is None:
                rank = int(a.shape[1])
            elif int(a.shape[1]) != rank:
                raise ValueError(
                    f"adapter {adapter_id!r} mixes ranks "
                    f"({rank} vs {a.shape[1]} at {nm!r}) — one rank "
                    "per adapter (the store pads to max_rank)")
            prepared[nm] = (a, b)
        lo = self._lora
        if lo is not None:
            if tuple(sorted(prepared)) != lo["names"]:
                raise ValueError(
                    f"adapter {adapter_id!r} adapts "
                    f"{sorted(prepared)} but resident adapters adapt "
                    f"{list(lo['names'])} — every adapter in an "
                    "engine must adapt the same parameter set (pad "
                    "missing targets with zero deltas)")
            if rank != lo["rank"]:
                raise ValueError(
                    f"adapter {adapter_id!r} rank {rank} != resident "
                    f"rank {lo['rank']} — the store pads every "
                    "adapter to one fixed max_rank")
        dt = names[next(iter(prepared))]._value.dtype
        # build the new stacks FULLY before committing any state
        if lo is None:
            row = 1
            new_a, new_b = {}, {}
            for nm, (a, b) in prepared.items():
                za = np.zeros((2,) + a.shape, np.float32)
                zb = np.zeros((2,) + b.shape, np.float32)
                za[1], zb[1] = a, b
                new_a[nm] = self._place_replicated(jnp.asarray(za, dt))
                new_b[nm] = self._place_replicated(jnp.asarray(zb, dt))
            sc = np.zeros(2, np.float32)
            sc[1] = float(scale)
            new_scale = self._place_replicated(jnp.asarray(sc))
            committed = {"rank": rank,
                         "names": tuple(sorted(prepared)),
                         "param_idx": {nm: idx[nm] for nm in prepared},
                         "a": new_a, "b": new_b, "scale": new_scale}
        else:
            grow = not self._lora_free_rows
            row = int(lo["scale"].shape[0]) if grow \
                else self._lora_free_rows[-1]
            new_a, new_b = {}, {}
            for nm in lo["names"]:
                a, b = prepared[nm]
                sa, sb = lo["a"][nm], lo["b"][nm]
                if grow:
                    sa = jnp.concatenate(
                        [sa, jnp.asarray(a, sa.dtype)[None]], 0)
                    sb = jnp.concatenate(
                        [sb, jnp.asarray(b, sb.dtype)[None]], 0)
                else:
                    sa = sa.at[row].set(jnp.asarray(a, sa.dtype))
                    sb = sb.at[row].set(jnp.asarray(b, sb.dtype))
                new_a[nm] = self._place_replicated(sa)
                new_b[nm] = self._place_replicated(sb)
            ssc = lo["scale"]
            if grow:
                ssc = jnp.concatenate(
                    [ssc, jnp.full((1,), float(scale), ssc.dtype)])
            else:
                ssc = ssc.at[row].set(float(scale))
            new_scale = self._place_replicated(ssc)
            committed = dict(lo, a=new_a, b=new_b, scale=new_scale)
        # commit
        if lo is not None and self._lora_free_rows:
            self._lora_free_rows.pop()
        self._lora = committed
        self._adapter_rows[adapter_id] = row
        _M_LORA_INSTALLS.inc()
        _M_LORA_RESIDENT.set(len(self._adapter_rows))
        _M_LORA_BYTES.set(self._lora_nbytes())
        if self._invariants_enabled():
            self.check_invariants()

    def evict_adapter(self, adapter_id: str) -> None:
        """Evict a resident adapter: its stack row zeroes and returns
        to the free-row list (stacks never shrink — shrinking would
        retrace every ragged program; the zeroed row is inert by the
        row-0 argument). REFUSES while any queued or in-flight request
        decodes under the adapter — evictions never strand a request —
        so the store evicts only unpinned entries. Dropping the last
        adapter drops the stacks entirely (dispatches return to the
        unwrapped value list)."""
        row = self._adapter_rows.get(adapter_id)
        if row is None:
            raise ValueError(f"adapter {adapter_id!r} is not resident")
        live = [r.request_id for r in
                list(self._queue) + [q for q in self._slot_req
                                     if q is not None]
                if r.adapter == adapter_id]
        if live:
            raise ValueError(
                f"adapter {adapter_id!r} is in flight (requests "
                f"{live}) — evicting it would strand them; drain or "
                "migrate first")
        del self._adapter_rows[adapter_id]
        if not self._adapter_rows:
            self._lora = None
            self._lora_free_rows = []
        else:
            lo = self._lora
            new_a = {nm: self._place_replicated(
                         lo["a"][nm].at[row].set(0.0))
                     for nm in lo["names"]}
            new_b = {nm: self._place_replicated(
                         lo["b"][nm].at[row].set(0.0))
                     for nm in lo["names"]}
            new_scale = self._place_replicated(
                lo["scale"].at[row].set(0.0))
            self._lora = dict(lo, a=new_a, b=new_b, scale=new_scale)
            self._lora_free_rows.append(row)
        _M_LORA_EVICTIONS.inc()
        _M_LORA_RESIDENT.set(len(self._adapter_rows))
        _M_LORA_BYTES.set(self._lora_nbytes())
        if self._invariants_enabled():
            self.check_invariants()

    def _lora_nbytes(self) -> int:
        lo = self._lora
        if lo is None:
            return 0
        n = int(lo["scale"].nbytes)
        for nm in lo["names"]:
            n += int(lo["a"][nm].nbytes) + int(lo["b"][nm].nbytes)
        return n

    def install_weights(self, values: dict, tag: str) -> None:
        """Hot-swap the engine's FULL dispatch weights to another
        registered checkpoint (fleet store cold install): ``values``
        maps every named parameter to its new value — a plain array
        (cast to the build dtype; quantized on the fly when the engine
        runs quantized weights) or a pre-quantized
        `ops.quant_matmul.QuantizedWeight` (the store's halved-
        footprint storage). The swap replaces the dispatch VALUE list
        only — same pytree structure, so every compiled program is
        reused without retrace — and stamps ``model_tag``, the
        identity migration payloads are matched on. IDLE-ONLY: every
        resident KV page is a function of the weights, so swapping
        under in-flight or queued requests would corrupt their
        streams; refuses to compose with prefix caching for the same
        reason (the trie outlives requests). Resident adapters drop
        with the base they adapted."""
        if self._queue or any(r is not None for r in self._slot_req):
            raise ValueError(
                "install_weights on a busy engine: resident KV pages "
                "are a function of the weights — drain or migrate "
                "in-flight requests first")
        if self._prefix_enabled:
            raise ValueError(
                "install_weights refuses to compose with prefix "
                "caching: the trie's cached KV pages were produced "
                "under the OLD weights and would silently poison "
                "future prefills")
        from ..ops.quant_matmul import (QuantizedWeight,
                                        quantize_weight_values)
        named = list(self.model.named_parameters())
        missing = [nm for nm, _ in named if nm not in values]
        if missing:
            raise ValueError(
                f"install_weights({tag!r}): checkpoint is missing "
                f"{len(missing)} parameters (first: {missing[:3]}) — "
                "full checkpoints only; use install_adapter for "
                "deltas")
        out, n_bytes = [], 0
        for nm, p in named:
            v = values[nm]
            if isinstance(v, QuantizedWeight):
                if tuple(v.qw.shape) != tuple(p._value.shape):
                    raise ValueError(
                        f"install_weights({tag!r}): {nm!r} shape "
                        f"{tuple(v.qw.shape)} != engine "
                        f"{tuple(p._value.shape)}")
                qw, sc = jnp.asarray(v.qw), jnp.asarray(v.scale)
            else:
                v = jnp.asarray(v)
                if tuple(v.shape) != tuple(p._value.shape):
                    raise ValueError(
                        f"install_weights({tag!r}): {nm!r} shape "
                        f"{tuple(v.shape)} != engine "
                        f"{tuple(p._value.shape)}")
                lnm = nm.lower()
                if self._qw_mode is not None and v.ndim == 2 \
                        and any(k in lnm for k in QUANT_MATMULS):
                    qw, sc = quantize_weight_values(
                        v.astype(p._value.dtype), self._qw_mode)
                else:
                    w = v.astype(p._value.dtype)
                    if self._tp is not None:
                        spec = self._tp._param_spec(nm, w.shape)
                        w = jax.device_put(w, self._tp.sharding(*spec))
                    n_bytes += int(w.nbytes)
                    out.append(w)
                    continue
            if self._tp is not None:
                spec = self._tp._param_spec(nm, p._value.shape)
                qw = jax.device_put(qw, self._tp.sharding(*spec))
                out_ax = spec[1] if len(spec) > 1 else None
                sc = jax.device_put(sc, self._tp.sharding(out_ax))
            w = QuantizedWeight(qw, sc)
            n_bytes += int(w.nbytes)
            out.append(w)
        # commit: the value list swaps atomically; adapters over the
        # old base die with it
        self._mpv = out
        self._mpv_nbytes = n_bytes
        self.model_tag = str(tag)
        self._lora = None
        self._adapter_rows = {}
        self._lora_free_rows = []
        self._slot_adapter[:] = 0
        _M_LORA_RESIDENT.set(0)
        _M_LORA_BYTES.set(0)

    def reset_weights(self) -> None:
        """Drop an install_weights override: dispatches return to the
        build-time weights (`model_tag` None). Idle-only, like
        install_weights, and for the same KV-coupling reason."""
        if self._queue or any(r is not None for r in self._slot_req):
            raise ValueError(
                "reset_weights on a busy engine: drain or migrate "
                "in-flight requests first")
        self._mpv = None
        self._mpv_nbytes = 0
        self.model_tag = None
        self._lora = None
        self._adapter_rows = {}
        self._lora_free_rows = []
        self._slot_adapter[:] = 0
        _M_LORA_RESIDENT.set(0)
        _M_LORA_BYTES.set(0)

    def _adapter_row(self, req: "Request") -> int:
        if req.adapter is None:
            return 0
        row = self._adapter_rows.get(req.adapter)
        if row is None:       # evict_adapter refuses while referenced
            raise ModelMismatch(
                f"request {req.request_id!r} decodes under adapter "
                f"{req.adapter!r} which is no longer resident")
        return row

    def _lora_pv(self, pv, ids):
        """Wrap each adapted matmul's dispatch value in a `LoraWeight`
        carrying THIS dispatch's per-token adapter-row vector (`ids`,
        one int32 row per packed token; rows of inactive/padding
        tokens may be anything — the epilogue has no cross-token
        reduction, so garbage rows never touch live rows). Identity
        when no adapter is resident."""
        if self._lora is None:
            return pv
        from ..ops.lora_epilogue import LoraWeight
        lo = self._lora
        idv = jnp.asarray(np.asarray(ids, np.int32))
        out = list(pv)
        for nm in lo["names"]:
            i = lo["param_idx"][nm]
            out[i] = LoraWeight(out[i], lo["a"][nm], lo["b"][nm],
                                lo["scale"], idv)
        return out

    # -- public API ----------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int = 32,
                    deadline: Optional[float] = None,
                    max_queue_time: Optional[float] = None,
                    request_id: Optional[str] = None,
                    priority: int = 0,
                    adapter: Optional[str] = None) -> int:
        """Queue a request. `deadline` is a completion budget in seconds
        from now on the engine's monotonic clock (overrides the engine
        `request_timeout` default); `max_queue_time` bounds time spent
        WAITING for a slot. `request_id` is a stable caller-scoped
        identity carried through telemetry and failover logs (defaults
        to the engine-local rid) — a fleet router passes the same id on
        every re-dispatch so the request stays traceable across
        replicas. `priority` is the QoS lane's queue class (lower
        admits first, FIFO within a class — serving/admission.py maps
        interactive=0, batch=1), so queued batch work can never starve
        interactive admissions. `adapter` decodes the request under a
        resident LoRA adapter (install_adapter) — the batched
        multi-LoRA path; an unknown/non-resident adapter is refused
        with ModelMismatch BEFORE enqueue, so the queue never holds a
        request no dispatch could serve. Expired requests finalize
        with status `timeout` at the next step tick. Raises
        EngineOverloaded when the bounded queue is full (`max_waiting`)
        or the admission policy rejects the request."""
        toks = [int(t) for t in np.asarray(prompt).ravel()]
        if not toks:
            raise ValueError("empty prompt")
        if adapter is not None and adapter not in self._adapter_rows:
            _M_MODEL_MISMATCH.inc(kind="adapter")
            raise ModelMismatch(
                f"adapter {adapter!r} is not resident in this engine "
                f"(resident: {sorted(self._adapter_rows)}) — "
                "install_adapter it first (the fleet model store does "
                "this before dispatch)")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(toks) >= self.S:
            raise ValueError(
                f"prompt length {len(toks)} does not fit max_seq_len "
                f"{self.S} (need at least one decode position)")
        if self.max_waiting is not None \
                and len(self._queue) >= self.max_waiting:
            _M_REJECTIONS.inc(reason="queue_full")
            raise EngineOverloaded(
                f"admission queue full ({self.max_waiting} waiting) — "
                "shed load or retry after in-flight requests drain")
        now = self._clock()
        budget = deadline if deadline is not None else self.request_timeout
        r = Request(self._next_rid, toks, int(max_new_tokens),
                    enqueue_time=now, arrival_time=now,
                    deadline=None if budget is None else now + budget,
                    max_queue_time=max_queue_time
                    if max_queue_time is not None else self.max_queue_time,
                    request_id=request_id if request_id is not None
                    else str(self._next_rid),
                    priority=int(priority), adapter=adapter)
        usable = self.num_pages - 1
        need = self._worst_pages(r)
        if need > usable:
            raise ValueError(
                f"request needs up to {need} KV pages (prompt "
                f"{len(toks)} + max_new_tokens {max_new_tokens} at "
                f"page_size {self.page_size}) but the pool has only "
                f"{usable} usable pages — it could never be "
                f"admitted; raise num_pages")
        if self.admission_policy is not None \
                and not self.admission_policy(self, r):
            _M_REJECTIONS.inc(reason="policy")
            raise EngineOverloaded(
                f"admission policy rejected request (prompt {len(toks)} "
                f"tokens, max_new_tokens {max_new_tokens})")
        self._next_rid += 1
        # lane-aware ordering: insert behind every request of the same
        # or more urgent class (stable — FIFO within a class). The
        # admit loop still only ever peeks the HEAD, so the priority
        # discipline composes with the page-reservation wait unchanged
        idx = len(self._queue)
        while idx > 0 and self._queue[idx - 1].priority > r.priority:
            idx -= 1
        self._queue.insert(idx, r)
        _M_QUEUE_DEPTH.set(len(self._queue))
        return r.rid

    def run(self) -> Dict[int, List[int]]:
        """Drive until every queued request completes; returns
        {request id: generated tokens}."""
        results: Dict[int, List[int]] = {}
        while self._queue or any(r is not None for r in self._slot_req):
            for r in self.step():
                results[r.rid] = r.output
        return results

    def step(self) -> List[Request]:
        """Admit waiting requests into free slots, decode ONE token for
        every active slot, release finished slots. Returns the requests
        that reached a TERMINAL state this step (finished / timeout /
        failed / preempted-out — check `.status`). One monotonic-clock
        tick per step drives deadline and queue-time expiry. For a model
        that generates by diffusion over blocks the decode is one PASS
        over every active slot's block (`_decode_blocks`), and a slot
        gives its block's tokens when the block holds no mask.

        Pipelined mode (harvest_every=k > 1): a due deferred window is
        harvested FIRST — before expiry, admission, and the next
        dispatch — so every host-visible transition (deadline
        finalization, slot release, re-admission) acts on committed
        token state exactly like the synchronous loop would."""
        # the root of an engine step's span tree (docs/observability.md):
        # its self time is expiry, the gauges and the glue between
        # `serving.admit`, `serving.decode` and `serving.commit`
        with telemetry.span("serving.step"):
            finished = self._finished_backlog
            self._finished_backlog = []
            try:
                if self._pending and self._harvest_due():
                    self._harvest_pending(finished)
                finished += self._expire()
                with telemetry.span("serving.admit"):
                    finished += self._admit_ragged()
                active = [i for i, r in enumerate(self._slot_req)
                          if r is not None]
                if active:
                    try:
                        # _decode appends starvation-guard finalizations
                        # into `finished` BEFORE its dispatch, so they
                        # survive an injected dispatch fault below.
                        # handled=True: a speculative round already
                        # committed tokens and finalizations itself
                        with telemetry.span("serving.decode"):
                            handled = self._decode(finished)
                    except FaultError as e:
                        # transient dispatch fault: it fires BEFORE the
                        # compiled step runs, so slot/page state is
                        # consistent and the next step() simply retries —
                        # bounded so an always-on fault cannot livelock
                        # run()
                        self.num_decode_retries += 1
                        self.last_failure = "".join(
                            traceback.format_exception(e))
                        _M_DECODE_RETRIES.inc()
                        self._consec_decode_faults += 1
                        if self._consec_decode_faults \
                                > self.max_decode_retries:
                            raise
                        if self._invariants_enabled():
                            self.check_invariants()
                        self._update_telemetry_gauges()
                        return finished
                    self._consec_decode_faults = 0
                    if not handled:
                        with telemetry.span("serving.commit"):
                            self._commit(active, finished)
            except BaseException:
                # ANY escaping error: requests already finalized this step
                # must not be lost in the raise — the next step() (if the
                # caller keeps going) delivers them
                self._finished_backlog = finished
                raise
            if self._invariants_enabled():
                self.check_invariants()
            self._update_telemetry_gauges()
            return finished

    def _commit(self, active, finished: List[Request]):
        """The synchronous step's commit loop: append each active
        slot's decoded token, finalize and release what ended."""
        for i in active:
            r = self._slot_req[i]
            if r is None:
                continue    # preempted/finalized during decode
            tok = int(self._tok[i])
            r.output.append(tok)
            hit_eos = self.eos is not None and tok == self.eos
            if hit_eos or len(r.output) >= r.max_new_tokens \
                    or int(self._pos[i]) >= self.S - 1:
                self._finalize(r, RequestStatus.FINISHED, None,
                               finished)
                self._release_slot(i)

    def _update_telemetry_gauges(self):
        """Refresh the point-in-time gauges once per step tick (queue
        depth, running slots, page occupancy)."""
        if not telemetry.enabled():
            return
        _M_QUEUE_DEPTH.set(len(self._queue))
        _M_RUNNING.set(sum(r is not None for r in self._slot_req))
        usable = self.num_pages - 1
        in_use = usable - len(self._free)
        _M_PAGES_IN_USE.set(in_use)
        _M_PAGE_OCCUPANCY.set(in_use / max(usable, 1))
        for g in self._groups if len(self._groups) > 1 \
                else self._window_groups:
            _M_GROUP_OCCUPANCY.set(
                1.0 - len(g.free) / max(g.num_pages - 1, 1), group=g.name)
            for i, (kind, n) in enumerate((("allocated", g.allocated),
                                           ("reclaimed", g.reclaimed))):
                _M_KV_PAGES.inc(n - g.told[i], group=g.name, kind=kind)
                g.told[i] = n
        if self._state_spec:
            _M_STATE_BYTES.set(self._state_nbytes())
            _M_STATE_SLOTS.set(int(self._state_live.sum()))

    def lifecycle_info(self) -> Dict[str, int]:
        """Robustness counters + queue depth (≙ serving-stack SLO
        telemetry)."""
        return {"waiting": len(self._queue),
                "running": sum(r is not None for r in self._slot_req),
                "timeouts": self.num_timeouts,
                "failures": self.num_failures,
                "preemptions": self.num_preemptions,
                "decode_retries": self.num_decode_retries}

    def get_request(self, rid: int) -> Optional[Request]:
        """The live (queued or running) Request with engine-local id
        `rid`, or None once it reached a terminal state. A fleet router
        holds this reference to mirror the token stream a replica has
        produced so far — the basis of zero-loss failover re-prefill."""
        for req in self._queue:
            if req.rid == rid:
                return req
        for req in self._slot_req:
            if req is not None and req.rid == rid:
                return req
        return None

    # -- gray-failure sentries (ISSUE 14, serving/sentry.py) ------------
    def attach_sentry(self, sentry) -> None:
        """Attach a `serving.sentry.NumericSentry`: token in-vocab
        checks ride every harvest (decode, ragged admission, spec
        verify), and when the sentry scans logits the decode program
        is rebuilt to return its sampled-row logits for the
        every-Nth-step scan.
        One sentry per engine incarnation; a fleet's ReplicaHandle
        attaches a fresh one on every (re)build. A sentry trip never
        raises — the step completes and the router reads
        ``sentry.trips`` to drive SUSPECT -> canary -> quarantine.
        A sentry with an ``observe_layer_rows(slots, positions,
        records)`` method is also handed, after every synchronous
        dispatch, the per-row records of the model's reporting layers
        (models/cache_spec.py ``ReportSpec``) for the live rows."""
        self.quiesce()    # pending logit rows belong to the OLD sentry
        self._sentry = sentry
        self._decode_jit = None       # rebuild with/without logits out

    def _corrupt_kv_site(self):
        """The ``serving.kv_page`` VALUE fault site (utils/faults.py
        CORRUPT mode), visited once per KV commit of a BUSY
        engine — decode step, ragged admission, spec verify — so
        ``nth=`` visit counting targets one replica like
        ``router.step`` (or arm with ``tag=``). The mutation gathers
        the slot-owned live pages of the layer-0 KEY pool to host,
        lets the armed rule damage them, and scatters the result back:
        seeded-deterministic, and guaranteed to land in pages a live
        request (or an in-flight canary) will actually read — damage
        in free/trash pages would drill nothing."""
        if not value_armed("serving.kv_page", self.fault_tag):
            return
        if not self._kv:
            return
        group = self._groups[self._pool_group[0]]
        live = sorted({p for pages in group.slot_pages for p in pages})
        if not live:
            return
        entry = self._kv[0]
        kp = entry[0]
        idx = np.asarray(live, np.int32)
        sub = np.asarray(kp[idx])
        mut = fault_value("serving.kv_page", sub, tag=self.fault_tag)
        if mut is sub:
            return
        new_kp = kp.at[jnp.asarray(idx)].set(
            jnp.asarray(np.asarray(mut), kp.dtype))
        if self._tp is not None:
            # keep the pool on its declared submesh sharding — the
            # eager scatter above may have resolved to replicated
            new_kp = jax.device_put(
                new_kp, self._tp.kv_sharding(self._kv_shape[1]))
        # quantized engines keep their scale pools untouched: the
        # damage lands in the int8 lattice bytes (a flipped high bit
        # is a sign/magnitude flip after dequant — same loudness)
        self._kv[0] = (new_kp,) + tuple(entry[1:])

    # -- migration hooks (serving/transfer.py, disaggregated fleets) ----
    def _resident_slot(self, rid: int) -> int:
        for i, r in enumerate(self._slot_req):
            if r is not None and r.rid == rid:
                return i
        raise ValueError(f"no resident request with rid {rid} (queued "
                         "or terminal requests hold no pages)")

    def export_pages(self, rid: int) -> dict:
        """Serialize a RUNNING request's resident KV pages + request
        state for migration into another engine (the disaggregated
        prefill/decode transfer plane, serving/transfer.py).
        READ-ONLY: the request keeps running here until
        `evict_request`, so a failure anywhere downstream leaves this
        engine untouched. The payload's `kv` entries are host numpy,
        per layer, shaped (hk, n_pages, page_size, hd) over the slot's
        live block-table window — the D2H gather is the transfer
        plane's serialize cost."""
        if self._state_spec:
            self._refuse_state("export_pages")
        if self._grouped:
            self._refuse_groups("export_pages")
        # pipelined decode: the payload serializes host slot state
        # (ctx/last_token/output) — drain the deferred window first so
        # it reflects every token the device produced (quiesce seam,
        # docs/serving.md "Pipelined decode")
        self.quiesce()
        slot = self._resident_slot(rid)
        req = self._slot_req[slot]
        freed = int(self._slot_freed[slot])
        n_idx = int(self._slot_next_idx[slot])
        pages = np.asarray(self._bt[slot, freed:n_idx], np.int32)
        L, hk, hd, dt = self._kv_shape
        pool_dt = jnp.int8 if self._qkv else dt
        now = self._clock()
        kv, kv_shards, n_tp = None, None, 1
        kv_scales = None
        if self._qkv:
            # per-page scale rows ride the payload once (head-free, so
            # replicated across TP shards — no fragments to assemble)
            kv_scales = [(np.asarray(e[2][pages]),
                          np.asarray(e[3][pages])) for e in self._kv]
        if self._tp is not None and self._tp.tp > 1:
            # tensor-parallel source: serialize one payload FRAGMENT
            # per shard — each `shard.data[pages]` gather runs on
            # its own device and only its result crosses to the host,
            # so migration bytes stay local per shard (the wire format
            # is the fragments; `assemble_payload_kv` is the
            # consumer-side logical view)
            from ..serving import submesh as tp_mod
            per_layer = [(tp_mod.kv_fragments(e[0], pages, hd),
                          tp_mod.kv_fragments(e[1], pages, hd))
                         for e in self._kv]
            n_tp = len(per_layer[0][0])
            kv_shards = [[(kf[s], vf[s]) for kf, vf in per_layer]
                         for s in range(n_tp)]
            tp_mod.record_shard_bytes(
                [sum(k.nbytes + v.nbytes for k, v in shard)
                 for shard in kv_shards])
        else:
            # the payload keeps its head-major shape: the few exported
            # pages are transposed here, off the step
            kv = [tuple(np.ascontiguousarray(pages_to_payload(
                np.asarray(pool[pages]), hk)) for pool in e[:2])
                for e in self._kv]
        payload_kv = {"kv": kv, "kv_shards": kv_shards,
                      "kv_scales": kv_scales}
        return {
            "request_id": req.request_id,
            "prompt": list(req.prompt),
            "output": list(req.output),
            "max_new_tokens": req.max_new_tokens,
            # multi-model serving: the hosted-model identity these KV
            # bytes are a function of — import_pages refuses a
            # cross-model install with ModelMismatch
            "model_tag": self.model_tag,
            "adapter": req.adapter,
            "deadline_remaining": None if req.deadline is None
            else req.deadline - now,
            # ages, not absolutes: the target rebases them on ITS clock
            # so TPOT keeps dividing by the full first-token-to-finish
            # interval across the move
            "first_token_age": None if req.first_token_time is None
            else now - req.first_token_time,
            "preemptions": req.preemptions,
            "priority": req.priority,
            "ctx": int(self._pos[slot]),
            "last_token": int(self._tok[slot]),
            "freed": freed,
            "n_pages": int(n_idx - freed),
            "page_size": self.page_size,
            "max_seq_len": self.S,
            "kv_spec": (L, hk, hd, str(jnp.dtype(pool_dt))),
            "kv": kv,
            "kv_shards": kv_shards,
            # quantized serving: int8 page bytes + per-page scale rows
            # + the mode tag import_pages refuses cross-mode on
            "kv_scales": kv_scales,
            "kv_quant": self._qkv,
            # integrity manifest (ISSUE 13): sha256 per shard fragment
            # — import_pages verifies BEFORE install, so in-flight
            # corruption is a counted refusal, not silent garbage KV.
            # Quantized payloads manifest their scale rows too: the
            # hashes cover exactly the bytes that cross the wire.
            "kv_sha256": payload_checksums(payload_kv),
            "scales_sha256": payload_scale_checksums(payload_kv),
            "tp": n_tp,
        }

    def import_pages(self, payload: dict,
                     deadline: Optional[float] = None) -> Request:
        """Install a serialized request (`export_pages` payload) into
        this engine: claim a free slot, attach any prompt prefix this
        engine's own trie already holds READ-ONLY (a migrated system
        prompt costs no page copies the second time), allocate the
        remaining pages and write their contents in one donated
        program, then re-register the installed chain in the prefix
        structures so it is warm for the NEXT migration. `deadline`
        (seconds from now on this engine's clock) overrides the
        payload's remaining budget. Transactional: any failure backs
        the slot out, so `check_invariants()` holds on both sides of
        every outcome. Raises EngineOverloaded (no free slot) /
        PoolExhausted (no pages) when the engine cannot take it NOW —
        capacity deferrals, distinct from transfer failures."""
        if self._state_spec:
            self._refuse_state("import_pages")
        if self._grouped:
            self._refuse_groups("import_pages")
        # pipelined decode: the active set must be CONSTANT within a
        # deferred window (the device token ring carries no entry for
        # a slot installed mid-window) — drain the window before the
        # install changes slot occupancy
        self.quiesce()
        pq = payload.get("kv_quant")
        if pq != self._qkv:
            # cross-mode pages are not interpretable on the other
            # side; refusing here (typed, counted) is what keeps a
            # mixed fleet from silently corrupting a pool
            _M_QUANT_MISMATCH.inc(kind="import")
            raise QuantMismatch(
                f"cross-quant-mode migration refused: payload KV is "
                f"{pq or 'full-width'}, this engine serves "
                f"{self._qkv or 'full-width'} pages — fleets must be "
                "quant-homogeneous")
        # cross-MODEL install refusal (ISSUE 17): the payload's pages
        # are a function of its source's hosted weights — a different
        # model_tag (or a non-resident adapter) here would be silent
        # corruption, not a migration. BEFORE any target mutation.
        ptag = payload.get("model_tag")
        if ptag != self.model_tag:
            _M_MODEL_MISMATCH.inc(kind="import")
            raise ModelMismatch(
                f"cross-model migration refused: payload KV was "
                f"produced under model {ptag or 'base'!r}, this "
                f"engine hosts {self.model_tag or 'base'!r} — the "
                "fleet store installs the model before routing here")
        pad = payload.get("adapter")
        if pad is not None and pad not in self._adapter_rows:
            _M_MODEL_MISMATCH.inc(kind="adapter")
            raise ModelMismatch(
                f"migration payload decodes under adapter {pad!r} "
                "which is not resident in this engine — the fleet "
                "store installs adapters before routing here")
        L, hk, hd, dt = self._kv_shape
        pool_dt = jnp.int8 if self._qkv else dt
        spec = tuple(payload["kv_spec"])
        mine = (L, hk, hd, str(jnp.dtype(pool_dt)))
        if spec != mine:
            raise ValueError(f"kv geometry mismatch: payload {spec} vs "
                             f"engine {mine}")
        if payload["page_size"] != self.page_size:
            raise ValueError(
                f"page_size mismatch: payload {payload['page_size']} "
                f"vs engine {self.page_size}")
        ctx = int(payload["ctx"])
        if ctx >= self.S:
            raise ValueError(f"context {ctx} does not fit max_seq_len "
                             f"{self.S}")
        free = [i for i, r in enumerate(self._slot_req) if r is None]
        if not free:
            raise EngineOverloaded("no free slot for a migration "
                                   "import — retry after a step")
        # integrity gate (ISSUE 13): reject corrupt payloads BEFORE any
        # target mutation — both engines stay consistent and the
        # transfer plane books stage="verify". Deliberately AFTER the
        # free-slot check: a capacity-deferred migration retries every
        # router tick, and hashing the full KV payload per deferral
        # would be pure wasted step-path work
        verify_payload(payload)
        now = self._clock()
        budget = payload["deadline_remaining"] if deadline is None \
            else deadline
        req = Request(self._next_rid, list(payload["prompt"]),
                      int(payload["max_new_tokens"]),
                      output=list(payload["output"]),
                      status=RequestStatus.RUNNING,
                      deadline=None if budget is None else now + budget,
                      enqueue_time=now, arrival_time=now, admit_time=now,
                      preemptions=int(payload.get("preemptions", 0)),
                      first_token_time=None
                      if payload.get("first_token_age") is None
                      else now - payload["first_token_age"],
                      request_id=payload["request_id"],
                      priority=int(payload.get("priority", 0)),
                      adapter=payload.get("adapter"))
        freed = int(payload["freed"])
        shared = None
        if self._prefix_enabled and not freed:
            shared = self._match_prefix(req.prompt)
            if shared is not None:
                shared = list(shared)
                for p in shared:
                    self._incref(p)        # pin across _reserve_ok
        # the pin is held across the reservation; any exit without a
        # reservation — refusal OR raise — must unpin (PDT005 found
        # the raise path unguarded)
        try:
            ok = self._reserve_ok(req, len(shared) if shared else 0)
        except BaseException:
            ok = False
            raise
        finally:
            if not ok and shared:
                for p in shared:
                    self._decref(p)
        if not ok:
            raise PoolExhausted(
                "migration import cannot reserve worst-case pages — "
                "retry after running requests release")
        slot = free[0]
        self._slot_req[slot] = req
        self._slot_adapter[slot] = self._adapter_row(req)
        self._slot_seq[slot] = self._admit_seq
        self._admit_seq += 1
        self._next_rid += 1
        try:
            m = 0
            try:
                if shared:
                    self._attach_shared(slot, shared)
                    m = len(shared)
            finally:
                if shared:
                    for p in shared:
                        self._decref(p)    # unpin: the slot holds refs
            if freed:
                # window engines: the slid-out leading pages stay
                # trash-routed on the target too
                self._slot_next_idx[slot] = freed
                self._slot_freed[slot] = freed
            self._slot_reserved[slot] = self._worst_pages(req)
            n_total = freed + int(payload["n_pages"])
            while int(self._slot_next_idx[slot]) < n_total:
                self._alloc_page(slot)
            start = m if m else freed
            ids = [int(self._bt[slot, j]) for j in range(start, n_total)]
            off = start - freed
            # a TP source's per-shard fragments reassemble to the
            # logical rows here; a TP TARGET re-splits them across its
            # own shards inside _install_kv — which is what makes
            # cross-tp migration (tp=2 source -> tp=4 target) legal:
            # the LOGICAL kv geometry is what the spec check compares
            scale_rows = None
            if self._qkv:
                scale_rows = [(ks[off:], vs[off:])
                              for ks, vs in payload["kv_scales"]]
            self._install_kv(ids, [(kp[:, off:], vp[:, off:])
                                   for kp, vp in
                                   assemble_payload_kv(payload)],
                             scale_rows)
            if self._prefix_enabled and not freed:
                self._register_prefix(slot, req)
            if shared:
                self.prefix_hits += 1
                self.prefix_tokens_reused += m * self.page_size
        except BaseException:
            self._release_slot(slot, register=False)
            raise
        self._pos[slot] = ctx
        self._tok[slot] = int(payload["last_token"])
        if self._gen is not None:
            # a block model's payload holds its COMMITTED blocks (the
            # block in flight on the source is dropped, as a preemption
            # drops it): generation goes on from there, the tokens past
            # the committed context the first block's given head
            self._start_block(slot,
                              self._effective_prompt(req)[ctx:])
        if self._invariants_enabled():
            self.check_invariants()
        return req

    def evict_request(self, rid: int) -> Request:
        """Detach a live request WITHOUT a terminal transition — the
        migration hand-off (its pages now live in another engine). A
        running slot is released exactly like a finished request's
        (prompt full pages register into the prefix trie, so the chain
        stays warm HERE for future prefills); a queued request just
        leaves the queue. Terminal counters are untouched: the request
        finishes, exactly once, wherever it lands."""
        self.quiesce()          # hand off COMMITTED state only
        for i, r in enumerate(self._slot_req):
            if r is not None and r.rid == rid:
                self._release_slot(i)
                return r
        for i, r in enumerate(self._queue):
            if r.rid == rid:               # pre-admission hand-off
                self._queue.pop(i)
                return r
        raise ValueError(f"no live request with rid {rid}")

    def import_prefix(self, pages_tokens: List[List[int]],
                      kv_rows, kv_scales=None) -> int:
        """Install an externally-held prefix chain (the fleet prefix
        store's host-RAM spill, serving/prefix_store.py) into this
        engine's prefix cache: `pages_tokens` is a list of FULL-page
        token lists forming one chain from position 0, `kv_rows` the
        per-layer (k, v) page contents shaped (hk, n, page_size, hd).
        Pages already in the trie are skipped (trie keys are exact
        tokens, so contents are identical by construction); missing
        ones — always a chain SUFFIX, existence is prefix-closed —
        allocate, install, and register with their refcount held by
        the trie node, evictable under pressure like any cached chain.
        Installs draw ONLY on genuinely free pages — restoring a cold
        chain never evicts resident (warmer-by-definition) cached
        chains, and, critically, never mutates the trie mid-build
        (an eviction between registrations could delete a node the
        chain under construction already linked through). Returns the
        pages newly installed (0 when prefix caching is off, the
        chain is already resident, or the pool has nothing free).
        Quantized engines require `kv_scales` (per-layer (k_scale,
        v_scale) rows of the quantized chain, shaped (n, page_size));
        a cross-mode chain is refused with :class:`QuantMismatch` —
        the spilled bytes are only interpretable in their own mode."""
        if self._state_spec:
            self._refuse_state("import_prefix")
        if self._grouped:
            self._refuse_groups("import_prefix")
        if not self._prefix_enabled:
            return 0
        if (kv_scales is None) == bool(self._qkv):
            _M_QUANT_MISMATCH.inc(kind="prefix")
            raise QuantMismatch(
                f"cross-quant-mode prefix install refused: chain is "
                f"{'quantized' if kv_scales is not None else 'full-width'}"
                f", this engine serves "
                f"{self._qkv or 'full-width'} pages")
        parent, missing_from = None, None
        for f, ptoks in enumerate(pages_tokens):
            if len(ptoks) != self.page_size:
                raise ValueError("import_prefix needs FULL pages "
                                 f"(page {f} has {len(ptoks)} tokens)")
            key = (parent, tuple(int(t) for t in ptoks))
            if missing_from is None and key not in self._prefix_nodes:
                missing_from = f
            parent = key
        if missing_from is None:
            return 0                       # chain already resident
        page_ids, parent = [], None
        for f, ptoks in enumerate(pages_tokens):
            key = (parent, tuple(int(t) for t in ptoks))
            if f < missing_from:
                self._prefix_nodes.move_to_end(key)
                parent = key
                continue
            if not self._free:
                break                      # install what fits for free
            page = self._free.pop()
            self._page_rc[page] = 1        # held by the trie node
            self._prefix_nodes[key] = {"page": page, "parent": parent,
                                       "children": 0}
            if parent is not None:
                self._prefix_nodes[parent]["children"] += 1
            page_ids.append(page)
            parent = key
        if page_ids:
            end = missing_from + len(page_ids)
            self._install_kv(
                page_ids, [(kp[:, missing_from:end],
                            vp[:, missing_from:end])
                           for kp, vp in kv_rows],
                None if kv_scales is None else
                [(ks[missing_from:end], vs[missing_from:end])
                 for ks, vs in kv_scales])
        # entry-budget cap AFTER content lands: an eviction here can
        # only take a fully-installed, consistent node
        while len(self._prefix_nodes) > self._max_prefix_entries:
            if not self._evict_one():
                break
        return len(page_ids)

    def _install_kv(self, page_ids: List[int], rows, scale_rows=None):
        """Write transferred page contents into the pool — one donated
        program per page count, LRU-capped
        (migration imports + prefix-store spill restores land here).
        Quantized engines additionally install each page's per-row
        dequant scales (`scale_rows`: one (k_scale, v_scale) pair of
        (n_pages, page_size) arrays per layer) — the quantized BYTES
        move verbatim, never re-quantized, which is what keeps
        migrated streams bit-identical. `rows` arrive in the payload's
        head-major shape (hk, n_pages, page_size, hd) and are
        transposed here, on the host, to the pools' token-major rows."""
        n = len(page_ids)
        rows = [(payload_to_pages(np.asarray(rk)),
                 payload_to_pages(np.asarray(rv))) for rk, rv in rows]
        jit = self._jit_lru(self._install_jits, n,
                            self._build_install, family="install")
        if self._tp is not None:
            # place the incoming rows with the pools' head sharding so
            # each device receives only ITS fragment of the transfer
            hk = self.model.config.num_key_value_heads
            sh = self._tp.kv_sharding(hk)
            rows_dev = [(jax.device_put(rk, sh), jax.device_put(rv, sh))
                        for rk, rv in rows]
            srows_dev = None if scale_rows is None else [
                (jax.device_put(np.asarray(sk), self._tp.replicated()),
                 jax.device_put(np.asarray(sv), self._tp.replicated()))
                for sk, sv in scale_rows]
        else:
            rows_dev = [(jnp.asarray(rk), jnp.asarray(rv))
                        for rk, rv in rows]
            srows_dev = None if scale_rows is None else [
                (jnp.asarray(sk), jnp.asarray(sv))
                for sk, sv in scale_rows]
        with self._tp_scope():
            self._kv = jit(self._kv,
                           jnp.asarray(np.asarray(page_ids, np.int32)),
                           rows_dev, srows_dev)

    def _build_install(self):
        quant = bool(self._qkv)

        def _ins(kv, ids_, rows_, srows_):
            if quant:
                return [
                    (kp.at[ids_].set(rk.astype(kp.dtype)),
                     vp.at[ids_].set(rv.astype(vp.dtype)),
                     ks.at[ids_].set(sk.astype(ks.dtype)),
                     vs.at[ids_].set(sv.astype(vs.dtype)))
                    for (kp, vp, ks, vs), (rk, rv), (sk, sv)
                    in zip(kv, rows_, srows_)]
            return [(kp.at[ids_].set(rk.astype(kp.dtype)),
                     vp.at[ids_].set(rv.astype(vp.dtype)))
                    for (kp, vp), (rk, rv) in zip(kv, rows_)]
        return jax.jit(_ins, donate_argnums=(0,))

    def _expire(self) -> List[Request]:
        """Monotonic-clock tick: finalize queued/running requests whose
        deadline (or queue-time budget) has passed. Granularity is one
        engine step — a request never decodes past the step in which
        its deadline elapsed."""
        now = self._clock()
        finished: List[Request] = []
        keep: List[Request] = []
        for req in self._queue:
            if (req.deadline is not None and now >= req.deadline) \
                    or (req.max_queue_time is not None
                        and now - req.enqueue_time >= req.max_queue_time):
                self.num_timeouts += 1
                self._finalize(req, RequestStatus.TIMEOUT,
                               "expired while waiting for a slot",
                               finished)
            else:
                keep.append(req)
        self._queue = keep
        for i, req in enumerate(self._slot_req):
            if req is not None and req.deadline is not None \
                    and now >= req.deadline:
                self.num_timeouts += 1
                self._finalize(req, RequestStatus.TIMEOUT,
                               "deadline expired mid-decode", finished)
                self._release_slot(i)
        return finished

    def _invariants_enabled(self) -> bool:
        # read dynamically so test fixtures can flip it per-module
        return os.environ.get("PDT_CHECK_INVARIANTS") == "1"

    def cache_memory_info(self) -> Dict[str, float]:
        """KV-cache HBM accounting: `bytes_in_use` is proportional to
        pages actually allocated (≙ the inference engine's memory-optim
        story, SURVEY.md §1 L10)."""
        L, hk, hd, dt = self._kv_shape
        itemsize = jnp.dtype(dt).itemsize
        if self._qkv:
            # int8 storage + (page_size,) f32 scale rows per page per
            # pool — the HONEST per-page bill the residency A/B in
            # bench.py divides fixed pool bytes by
            itemsize = 1
            page_bytes = self.page_size * hk * hd * itemsize * 2 * L \
                + self.page_size * 4 * 2 * L
        else:
            page_bytes = self.page_size * hk * hd * itemsize * 2 * L
        usable = self.num_pages - 1
        in_use = usable - len(self._free)
        info = {"layout": "paged", "page_bytes": page_bytes,
                "state_bytes": self._state_nbytes(),
                "kv_quant": self._qkv,
                "total_pages": usable, "pages_in_use": in_use,
                "bytes_pool": self.num_pages * page_bytes,
                "bytes_in_use": in_use * page_bytes,
                "utilization": in_use / max(usable, 1)}
        if len(self._groups) > 1:
            # the numbers above are the first group's; every group's here
            info["groups"] = {g.name: {
                "page_bytes": 2 * len(g.pools) * self.page_size * itemsize
                * g.spec.num_kv_heads * g.spec.head_dim,
                "total_pages": g.num_pages - 1,
                "pages_in_use": g.num_pages - 1 - len(g.free)}
                for g in self._groups}
        if self._prefix_enabled:
            cached = {n["page"] for n in self._prefix_nodes.values()}
            info.update(prefix_entries=len(self._prefix_nodes),
                        prefix_pages=len(cached),
                        prefix_hits=self.prefix_hits,
                        prefix_tokens_reused=self.prefix_tokens_reused)
        return info

    def check_invariants(self):
        """Page-accounting invariant checker (runs after every step
        under `PDT_CHECK_INVARIANTS=1`): every page's refcount equals
        its holder count (slot-owned + slot-attached + prefix-trie
        nodes), the free list is duplicate-free and is EXACTLY the
        rc==0 pages (no leaks after `_release_slot`, no premature
        frees), released slots hold nothing, and each active slot's
        live block-table window points only at allocated pages while
        everything outside it trash-routes to page 0. Raises
        EngineInvariantError listing every violation."""
        with telemetry.span("serving.invariants"), \
                _M_INVARIANT_SECONDS.time():
            self._check_invariants_paged()

    def _check_invariants_group(self, g: _PageGroup, errs: List[str]):
        """One page group's accounting (`check_invariants`); the shared
        prefix pages and the trie's nodes are the first group's."""
        first = g is self._groups[0]
        tag = f"group {g.name}: " if len(self._groups) > 1 else ""
        free = list(g.free)
        free_set = set(free)
        if len(free_set) != len(free):
            errs.append(f"{tag}free list has duplicates: {sorted(free)}")
        if 0 in free_set:
            errs.append(f"{tag}reserved trash page 0 is on the free list")
        expected = np.zeros(g.num_pages, np.int64)
        for i, r in enumerate(self._slot_req):
            shared = self._slot_shared_pages[i] if first else []
            if r is None and (g.slot_pages[i] or shared
                              or np.any(g.bt[i] != 0)):
                errs.append(
                    f"{tag}released slot {i} still holds pages "
                    f"{g.slot_pages[i]} shared {shared} or a nonzero "
                    "block-table row")
            for p in g.slot_pages[i]:
                expected[p] += 1
            for p in shared:
                expected[p] += 1
        for node in self._prefix_nodes.values() if first else ():
            expected[node["page"]] += 1
        for p in range(1, g.num_pages):
            rc = int(g.page_rc[p])
            if rc != int(expected[p]):
                errs.append(f"{tag}page {p}: refcount {rc} != "
                            f"{int(expected[p])} holders "
                            "(slots + prefix nodes)")
            if rc == 0 and p not in free_set:
                errs.append(f"{tag}page {p} LEAKED: refcount 0 but "
                            "absent from the free list")
            if rc > 0 and p in free_set:
                errs.append(f"{tag}page {p} on the free list with "
                            f"refcount {rc}")
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            lo = int(g.slot_freed[i])
            hi = int(g.slot_next_idx[i])
            for j in range(self.pps):
                p = int(g.bt[i, j])
                if lo <= j < hi:
                    if p == 0 or int(g.page_rc[p]) < 1:
                        errs.append(
                            f"{tag}slot {i} block-table[{j}] -> page {p} "
                            "is not an allocated page")
                elif p != 0:
                    errs.append(
                        f"{tag}slot {i} block-table[{j}] = {p} outside "
                        f"the live window [{lo}, {hi}) must trash-route "
                        "to 0")
            # a step ends with every page that lies wholly below the
            # window of the slot's LAST dispatched position given back
            if g.window is not None and lo < hi and \
                    (lo + 1) * self.page_size <= int(self._pos[i]) - g.window:
                errs.append(
                    f"{tag}slot {i} block-table[{lo}] is still allocated "
                    f"wholly below the window of {g.window} at position "
                    f"{int(self._pos[i])}")

    def _check_invariants_paged(self):
        errs: List[str] = []
        for g in self._groups:
            self._check_invariants_group(g, errs)
        # multi-model (ISSUE 17): the slot -> adapter-row map must
        # mirror slot ownership exactly — a stale row would gather
        # ANOTHER adapter's delta into this slot's stream, silent
        # cross-model corruption
        for i, r in enumerate(self._slot_req):
            want = 0
            if r is not None and r.adapter is not None:
                want = self._adapter_rows.get(r.adapter, -1)
            if int(self._slot_adapter[i]) != want:
                errs.append(
                    f"slot {i} adapter row "
                    f"{int(self._slot_adapter[i])} != expected {want} "
                    f"(request "
                    f"{r.request_id if r is not None else None!r})")
        rows = list(self._adapter_rows.values())
        if len(set(rows)) != len(rows) or 0 in rows:
            errs.append(
                f"adapter row map corrupt (duplicate or reserved row "
                f"0): {self._adapter_rows}")
        if self._lora is not None:
            cap = int(self._lora["scale"].shape[0])
            for aid, row in self._adapter_rows.items():
                if not 1 <= row < cap:
                    errs.append(f"adapter {aid!r} row {row} outside "
                                f"the stacks [1, {cap})")
            taken = set(rows) & set(self._lora_free_rows)
            if taken:
                errs.append(f"adapter rows {sorted(taken)} both "
                            "assigned and on the free-row list")
        elif self._adapter_rows:
            errs.append(f"adapter rows {self._adapter_rows} registered "
                        "but no stacks resident")
        # state layers: a slot's state is live iff the slot holds a
        # sequence (a step ends with every claimed slot dispatched), and
        # the arrays are still what the specification says
        for i, r in enumerate(self._slot_req if self._state_spec else ()):
            if bool(self._state_live[i]) != (r is not None):
                errs.append(
                    f"slot {i}: state live={bool(self._state_live[i])} "
                    f"but the slot is "
                    f"{'running' if r is not None else 'free'}")
        for layer, (s, arrays) in enumerate(zip(self._state_spec,
                                                self._state)):
            got = tuple((tuple(a.shape[1:]), jnp.dtype(a.dtype).name)
                        for a in arrays)
            want = tuple((tuple(sh), jnp.dtype(d).name)
                         for sh, d in zip(s.shapes, s.dtypes))
            if got != want or any(a.shape[0] != self.B for a in arrays):
                errs.append(f"state layer {layer}: arrays {got} are not "
                            f"({self.B} slots of) {want}")
        # a block model: a running slot's block lies on a block boundary
        # inside the sequence, its given tokens are the request's own and
        # never masked, a masked position holds the mask id; a free slot
        # has no block in flight
        for i, r in enumerate(self._slot_req if self._gen is not None
                              else ()):
            pos, given = int(self._pos[i]), int(self._blk_given[i])
            if r is None:
                if self._blk_masked[i].any() or given \
                        or self._blk_passes[i]:
                    errs.append(f"free slot {i} holds a block in flight")
                continue
            if pos % self._dblock or pos + self._dblock > self.S:
                errs.append(f"slot {i}: block at {pos} is not a whole "
                            f"block of {self._dblock} inside {self.S}")
            if self._blk_masked[i, :given].any() or list(
                    self._blk_ids[i, :given]) \
                    != self._effective_prompt(r)[pos:pos + given]:
                errs.append(f"slot {i}: the block's {given} given tokens "
                            "are masked or not the request's")
            if np.any(self._blk_ids[i][self._blk_masked[i]]
                      != self._gen.mask_token_id):
                errs.append(f"slot {i}: a masked position does not hold "
                            f"the mask id {self._gen.mask_token_id}")
        if self._spec is not None:
            self._check_invariants_draft(errs)
        if self._tp is not None:
            self._check_invariants_tp(errs)
        if errs:
            raise EngineInvariantError(
                "engine invariant violations:\n  " + "\n  ".join(errs))

    def _check_invariants_tp(self, errs: List[str]):
        """Sharded-allocator invariants (tensor parallelism): the page
        pools must still live EXACTLY on the engine's submesh with the
        declared head sharding — a stray dispatch that resharded or
        relocated a pool would silently turn every 'local shard' claim
        (per-shard export, the kernel shard_map) into fiction."""
        def _norm(spec):
            # PartitionSpec('tp') == PartitionSpec(('tp',), None, ...):
            # normalize entries to tuples and strip trailing Nones so
            # propagation's spelling differences don't read as drift
            out = []
            for e in spec:
                out.append(None if e is None
                           else tuple(e) if isinstance(e, (list, tuple))
                           else (e,))
            while out and out[-1] is None:
                out.pop()
            return tuple(out)

        want = set(self._tp.devices)

        def _check_pools(pools, hk, label):
            want_spec = _norm(self._tp.kv_sharding(hk).spec)
            for li, e in enumerate(pools):
                pairs = [("k", e[0], want_spec), ("v", e[1], want_spec)]
                if len(e) == 4:
                    # quantized pools: the scale pools are declared
                    # REPLICATED (head-free) — a sharded scale pool
                    # would dequantize different heads with different
                    # factors, silent corruption by construction
                    pairs += [("k-scale", e[2], ()),
                              ("v-scale", e[3], ())]
                for nm, arr, wspec in pairs:
                    got = set(arr.sharding.device_set)
                    if got != want:
                        errs.append(
                            f"layer {li} {label}{nm}-pool left its "
                            f"submesh: on "
                            f"{sorted(d.id for d in got)}, expected "
                            f"{sorted(d.id for d in want)}")
                    spec = getattr(arr.sharding, "spec", None)
                    if spec is not None and _norm(spec) != wspec:
                        errs.append(
                            f"layer {li} {label}{nm}-pool resharded: "
                            f"spec {spec} != declared {wspec}")

        _check_pools(self._kv, self.model.config.num_key_value_heads,
                     "")
        if self._spec is not None:
            # the draft pools feed the same per-shard shard_map path
            # (placed with kv_sharding(draft hk), replicated-fallback
            # and all) — a relocated draft pool is the same fiction
            _check_pools(
                self._d_kv,
                self._spec.draft_model.config.num_key_value_heads,
                "draft-")

    def _check_invariants_draft(self, errs: List[str]):
        """Draft-cache page accounting (spec_decode engines): draft
        pages are EXCLUSIVELY owned — no refcounts, no sharing — so
        the free list and the per-slot page lists must partition
        {1..N-1} exactly, released slots must hold nothing, and each
        live slot's draft block-table window must point only at its
        own pages (everything past it trash-routes to page 0)."""
        free = list(self._d_free)
        free_set = set(free)
        if len(free_set) != len(free):
            errs.append(f"draft free list has duplicates: {sorted(free)}")
        if 0 in free_set:
            errs.append("draft trash page 0 is on the free list")
        owner: Dict[int, int] = {}
        for i, r in enumerate(self._slot_req):
            if r is None and (self._d_slot_pages[i]
                              or np.any(self._d_bt[i] != 0)
                              or self._d_valid[i]):
                errs.append(
                    f"released slot {i} still holds draft pages "
                    f"{self._d_slot_pages[i]} / a nonzero draft "
                    "block-table row / a validity flag")
            for p in self._d_slot_pages[i]:
                if p in owner:
                    errs.append(f"draft page {p} owned by slots "
                                f"{owner[p]} and {i}")
                owner[p] = i
        for p in range(1, self._d_num_pages):
            if (p in owner) == (p in free_set):
                errs.append(
                    f"draft page {p} must be exactly one of "
                    f"owned/free (owned={p in owner}, "
                    f"free={p in free_set})")
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            hi = int(self._d_next_idx[i])
            for j in range(self.pps):
                p = int(self._d_bt[i, j])
                if j < hi:
                    if p == 0 or owner.get(p) != i:
                        errs.append(
                            f"slot {i} draft block-table[{j}] -> page "
                            f"{p} is not a page the slot owns")
                elif p != 0:
                    errs.append(
                        f"slot {i} draft block-table[{j}] = {p} past "
                        f"the frontier {hi} must trash-route to 0")

    # -- internals -----------------------------------------------------
    def _finalize(self, req: Request, status: str, error: Optional[str],
                  finished: List[Request]):
        """The one place a request enters a terminal state — so the
        per-status terminal counters reconcile EXACTLY with the request
        objects handed back by step()."""
        req.done = True
        req.status = status
        req.error = error
        finished.append(req)
        _M_TERMINAL.inc(status=status)
        if telemetry.enabled():
            n = len(req.output)
            if status == RequestStatus.FINISHED and n >= 2 \
                    and req.first_token_time is not None:
                _M_TPOT.observe((self._clock() - req.first_token_time)
                                / (n - 1))
            telemetry.event("serving.terminal", rid=req.rid,
                            request_id=req.request_id,
                            status=status, tokens=n,
                            preemptions=req.preemptions)

    def _effective_prompt(self, req: Request) -> List[int]:
        """What admission prefills: the original prompt plus everything
        already generated — a preempted request resumes by re-prefilling
        its full context (cheap when the prefix cache retained it)."""
        return req.prompt + req.output if req.output else req.prompt

    def _release_slot(self, slot: int, register: bool = True):
        # register=False skips prefix registration — a failed prefill
        # leaves garbage KV in the slot's pages, which must never enter
        # the shared cache
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        self._slot_adapter[slot] = 0
        # the arrays keep the old state; the slot's next sequence
        # starts from zero by its descriptors (cache_spec.py)
        self._state_live[slot] = False
        # a block in flight is dropped: a preempted request resumes
        # from its last committed block
        self._blk_masked[slot] = False
        self._blk_given[slot] = self._blk_passes[slot] = 0
        self._blk_t0[slot] = np.nan
        if self._prefix_enabled and req is not None and register:
            # register BEFORE the decrefs so the prompt pages never
            # transit through the free list
            self._register_prefix(slot, req)
        for p in self._slot_shared_pages[slot]:
            self._decref(p)
        self._slot_shared_pages[slot] = []
        for g in self._groups:
            for p in g.slot_pages[slot]:
                self._decref(p, g)
            g.slot_pages[slot] = []
            g.slot_reserved[slot] = 0
            g.slot_next_idx[slot] = 0
            g.slot_freed[slot] = 0
            # inactive slots keep decoding garbage; their block-table
            # row must point at the trash page, not at reclaimed pages
            g.bt[slot] = 0
        if self._spec is not None:
            # the draft cache dies with the slot: preemption
            # re-prefills, failover re-dispatch, and migration all
            # DROP draft state — the next spec round rebuilds it
            # from the folded stream (never torn, by construction)
            self._d_release(slot)

    def _next_keys(self, n: int = 1):
        keys = jax.random.split(self._key, n + 1)
        self._key = keys[0]
        return keys[1:] if n > 1 else keys[1]

    def _claim_candidate(self, free):
        """The admission preamble: peek the FIFO head, match + PIN any cached prefix pages
        (pin BEFORE reservation — under pool pressure _reserve_ok may
        evict the matched entry itself, and unpinned pages would land
        on the free list while still referenced), check the worst-case
        page reservation, then claim a slot. Returns (slot, req,
        prompt, shared) with the prefix pages still pinned, or None
        when the head request must wait for pages (FIFO: stop
        admitting)."""
        req = self._queue[0]
        prompt = self._effective_prompt(req)
        shared = None
        if self._prefix_enabled:
            shared = self._match_prefix(prompt)
            if shared is not None:
                shared = list(shared)
                for p in shared:
                    self._incref(p)
        # the pin is held ACROSS the reservation (it may evict the
        # matched chain), so the reservation's own error path must
        # unpin — an unguarded raise here would leak the refcounts
        # and fail a later check_invariants() far from the cause
        # (PDT005 found this unguarded)
        try:
            ok = self._reserve_ok(req, len(shared) if shared else 0)
        except BaseException:
            if shared:
                for p in shared:
                    self._decref(p)
            raise
        if not ok:
            if shared:
                for p in shared:
                    self._decref(p)    # unpin before waiting
            return None
        slot = free.pop(0)
        self._queue.pop(0)
        # slot ownership is recorded BEFORE any dispatch so a failed
        # prefill can release partially-built slot state uniformly
        self._slot_req[slot] = req
        req.status = RequestStatus.RUNNING
        self._slot_adapter[slot] = self._adapter_row(req)
        self._slot_seq[slot] = self._admit_seq
        self._admit_seq += 1
        # where the request stops waiting: one record a claim (a
        # preempted request that queued again gets another), so TTFT
        # splits into queue wait and prefill
        req.admit_time = self._clock()
        if telemetry.enabled():
            wait = req.admit_time - req.enqueue_time
            _M_QUEUE_WAIT.observe(wait)
            telemetry.interval("serving.queue_wait", wait, rid=req.rid,
                               request_id=req.request_id,
                               preemptions=req.preemptions)
        return slot, req, prompt, shared

    def _admission_pool_exhausted(self, slot, req, free, finished):
        """Back out a claimed slot after an admission-time allocation
        failure and requeue (or starve out) the request. Returns True
        when the caller should try the NEXT queued request (the victim
        starved out), False to stop admitting this step."""
        self._release_slot(slot, register=False)
        free.insert(0, slot)
        self._requeue_or_starve(req, finished)
        return req.done

    def _admission_failed(self, slot, req, exc, free, finished):
        """Isolate a failed prefill: finalize THIS request, free the
        slot's partial state, keep admitting everything else."""
        self.num_failures += 1
        self.last_failure = "".join(traceback.format_exception(exc))
        self._finalize(req, RequestStatus.FAILED,
                       f"{type(exc).__name__}: {exc}", finished)
        self._release_slot(slot, register=False)
        free.insert(0, slot)

    def _attach_shared(self, slot: int, shared: List[int]) -> int:
        """Attach pinned prefix-cache pages read-only to `slot`'s block
        table; returns the shared token length."""
        self._slot_shared_pages[slot] = list(shared)
        for j, p in enumerate(shared):
            self._bt[slot, j] = p
            self._incref(p)
        self._slot_next_idx[slot] = len(shared)
        return len(shared) * self.page_size

    def _note_admitted(self, req: Request):
        """An admission's prefill gave the request a token."""
        _M_ADMISSIONS.inc()
        self._note_first_token(req)

    def _note_first_token(self, req: Request):
        """The first token is stamped once per request (a preempted
        request's re-admission must not re-observe TTFT), telemetry on
        or off."""
        if req.first_token_time is not None:
            return
        req.first_token_time = self._clock()
        if telemetry.enabled():
            ttft = req.first_token_time - req.arrival_time
            _M_TTFT.observe(ttft, exemplar=req.request_id)
            telemetry.event("serving.first_token", rid=req.rid,
                            request_id=req.request_id, ttft_s=ttft)

    # -- admission -------------------------------------------------------
    def _admit_ragged(self):
        """Batched admission through the ragged paged-attention path:
        collect every admittable request (FIFO + worst-case page
        reservation), then prefill them ALL in one packed dispatch —
        full prefills, prefix-cache suffix prefills, and (when
        `prefill_chunk` bounds the dispatch) chunk continuations ride
        one token axis. Loops while instant-finish admissions free
        slots."""
        finished: List[Request] = []
        while True:
            entries = self._collect_ragged_entries(finished)
            if not entries:
                break
            freed = False
            for batch in self._ragged_batches(entries):
                freed |= self._dispatch_ragged(batch, finished)
            if not (freed and self._queue):
                break
        return finished

    def _collect_ragged_entries(self, finished):
        """The host-side half of admission: reservation, slot and page
        allocation, prefix-cache attach — everything EXCEPT the model
        dispatch, per request, so `serving.prefill` faults still
        isolate a single request. Returns the admission entries to
        pack."""
        entries = []
        free = [i for i, r in enumerate(self._slot_req) if r is None]
        while free and self._queue:
            claim = self._claim_candidate(free)
            if claim is None:
                break                  # FIFO: wait for pages to free
            slot, req, prompt, shared = claim
            p_len = len(prompt)
            shared_len = 0
            try:
                with telemetry.span("serving.prefill", rid=req.rid,
                                    request_id=req.request_id,
                                    prompt_len=p_len,
                                    shared_pages=len(shared)
                                    if shared else 0):
                    try:
                        fault_point("serving.prefill")
                        if shared:
                            shared_len = self._attach_shared(slot,
                                                             shared)
                        self._reserve_and_alloc(slot, req, p_len)
                    finally:
                        if shared:
                            for p in shared:
                                self._decref(p)    # unpin: slot holds refs
                if shared:
                    self.prefix_hits += 1
                    self.prefix_tokens_reused += shared_len
                # a block model prefills the prompt's whole blocks and
                # samples nothing: what is left over is the given head
                # of the first block it generates
                whole = p_len // self._dblock * self._dblock \
                    if self._gen is not None else p_len
                if whole == shared_len:
                    self._start_generation(slot, whole, prompt[whole:])
                    continue
                entries.append({"slot": slot, "req": req,
                                "tokens": prompt[shared_len:whole],
                                "offset": shared_len,
                                "tail": prompt[whole:]})
            except PoolExhausted:
                if self._admission_pool_exhausted(slot, req, free,
                                                  finished):
                    continue       # starved out: try the next request
                break              # pool exhausted: stop admitting
            except Exception as e:
                # no dispatch happened yet, so the shared KV is intact:
                # isolate the failure and keep admitting
                self._admission_failed(slot, req, e, free, finished)
                continue
        return entries

    def _ragged_batches(self, entries):
        """Split admission entries into dispatch batches bounded by
        `prefill_chunk` tokens (unbounded without it). A long prompt
        spills into CHUNK CONTINUATION pieces in later batches — their
        earlier rows are already scattered into the slot's pages, so
        the continuation attends them through the page table at its
        position offset. Only a request's final piece samples."""
        budget = self._chunk
        batches, cur, cur_tok = [], [], 0
        for e in entries:
            toks, off = e["tokens"], e["offset"]
            while toks:
                if budget is not None and cur_tok >= budget:
                    batches.append(cur)
                    cur, cur_tok = [], 0
                take = len(toks) if budget is None \
                    else min(len(toks), budget - cur_tok)
                cur.append({"slot": e["slot"], "req": e["req"],
                            "tokens": toks[:take], "offset": off,
                            "sample": take == len(toks),
                            "tail": e.get("tail")})
                toks = toks[take:]
                off += take
                cur_tok += take
        if cur:
            batches.append(cur)
        return batches

    def _dispatch_ragged(self, batch, finished):
        """Pack one batch of admission pieces (each sequence's query
        segment aligned to block_q) and run the ONE ragged program —
        scatter + attention + sampling for every piece in a single
        dispatch. Returns True when an instant-finish freed a slot."""
        from ..ops.ragged_paged_attention import pack_ragged_batch
        bq = self._ragged_block_q
        grid = -(-self.pad // bq) * bq
        blocks = self._gen is not None      # its prefill samples nothing
        for g in self._window_groups:
            if not g.derived:
                continue        # its prompt's pages were taken at the claim
            for p in batch:
                end = p["offset"] + len(p["tokens"])
                while g.slot_next_idx[p["slot"]] * self.page_size < end:
                    self._alloc_page(p["slot"], g)
        pk = pack_ragged_batch(
            [{"seq": p["slot"], "tokens": p["tokens"],
              "offset": p["offset"], "sample": p["sample"] and not blocks}
             for p in batch],
            self.B, block_q=bq, pad_to=grid,
            diffusion_block=self._dblock)
        t_pad = pk["t_pad"]
        # static gather trim for the XLA fallback: the batch's max page
        # demand, power-of-two bucketed so the (t_pad, bound) program
        # family stays log-bounded. Exact — trimmed columns lie past
        # every context in this dispatch.
        bound = self._pages_bound(
            int(pk["context_len"][p["slot"]]) for p in batch)
        rids = ([p["req"].request_id for p in batch]
                if telemetry.enabled() else ())
        # useful work against rows dispatched (`prefill_pad_share`)
        tokens = int(pk["tokens"])
        _M_PREFILL_ROWS.inc(tokens, kind="token")
        _M_PREFILL_ROWS.inc(int(t_pad) - tokens, kind="pad")
        if telemetry.enabled():
            self._count_attn_pages(pk["query_start"], pk["query_len"],
                                   pk["context_len"], t_pad, bq)
            sampled = (pk["sample_rows"] < t_pad).astype(np.int32)
            self._count_attn_kv_rows(
                "admit", pk["query_len"], pk["context_len"],
                sampled if self._leave_after is not None
                else pk["query_len"])
            layers = len(self._layer_spec)
            behind = 0 if self._leave_after is None or blocks \
                else layers - 1 - self._leave_after
            gone = max(int(t_pad) - self.B, 0) * behind
            _M_PREFILL_LAYER_ROWS.inc(int(t_pad) * layers - gone,
                                      kind="run")
            _M_PREFILL_LAYER_ROWS.inc(gone, kind="skipped")
        with telemetry.span("serving.ragged_prefill", tokens=tokens,
                            t_pad=int(t_pad), rids=rids,
                            rows_sampled=sum(
                                p["sample"] and not blocks
                                for p in batch)), \
                self._tp_scope():
            # the dispatch's three phases: its operands (inputs), the
            # call until it returns (launch), and in the span's own time
            # the wait for its result
            with telemetry.span("serving.inputs"):
                jit = self._get_ragged_prefill(t_pad, bound)
                # multi-LoRA: each packed row gathers its OWNING slot's
                # adapter row (padding rows gather slot 0's — inert,
                # their outputs are never read and the epilogue has no
                # cross-token reduction)
                pv = self._lora_pv(
                    self._pv(),
                    self._slot_adapter[np.asarray(pk["token_seq"],
                                                  np.int32)])
                args = (pv, self._bv(),
                        self._cache(), jnp.asarray(pk["ids"]),
                        jnp.asarray(pk["token_seq"]),
                        jnp.asarray(pk["positions"]),
                        jnp.asarray(pk["query_start"]),
                        jnp.asarray(pk["query_len"]),
                        jnp.asarray(pk["context_len"]),
                        self._tables(), jnp.asarray(pk["sample_rows"]),
                        self._next_keys())
            with telemetry.span("serving.launch"):
                nxt, _, reports = self._take_step(jit(*args))
            nxt = np.asarray(nxt)
        for p in batch if self._window_groups else ():
            self._reclaim_below_window(
                p["slot"], p["offset"] + len(p["tokens"]))
        if reports is not None:
            seq = np.asarray(pk["token_seq"])
            live = np.flatnonzero(seq >= 0)
            self._harvest_reports(reports, live, seq[live],
                                  np.asarray(pk["positions"])[live])
        if self._state_spec:
            for p in batch:
                self._state_live[p["slot"]] = True
        self._corrupt_kv_site()
        if self._sentry is not None and not blocks:
            rows = [p["slot"] for p in batch if p["sample"]]
            if rows:
                self._sentry.observe_tokens(nxt[rows])
        freed = False
        for piece in batch:
            if not piece["sample"]:
                continue
            req, s = piece["req"], piece["slot"]
            if blocks:
                self._start_generation(
                    s, piece["offset"] + len(piece["tokens"]),
                    piece["tail"])
                continue
            self._pos[s] = piece["offset"] + len(piece["tokens"])
            tok = int(nxt[s])
            self._tok[s] = tok
            req.output.append(tok)
            self._note_admitted(req)
            if (self.eos is not None and tok == self.eos) \
                    or len(req.output) >= req.max_new_tokens:
                self._finalize(req, RequestStatus.FINISHED, None,
                               finished)
                self._release_slot(s)
                freed = True
        return freed

    # -- tensor parallelism plumbing (serving/submesh.py) --------------
    def _pv(self):
        """Target param VALUES for a dispatch: the install_weights
        override when another checkpoint is hosted (already placed and
        quantized — `model_tag` names it), else the quantized list
        when the engine runs quantized weights (converted matmuls
        carry `QuantizedWeight` values the model's linears dequantize
        in the matmul epilogue), else the submesh-placed copies under
        TP, else the live model values."""
        if self._mpv is not None:
            return self._mpv
        if self._qpv is not None:
            return self._qpv
        if self._tp is not None:
            return self._tp_pv
        return [p._value for p in self._params]

    def _bv(self):
        if self._tp is not None:
            return self._tp_bv
        return [b._value for b in self._buffers]

    def _d_pv(self):
        if self._tp is not None:
            return self._tp_d_pv
        return [p._value for p in self._d_params]

    def _d_bv(self):
        if self._tp is not None:
            return self._tp_d_bv
        return [b._value for b in self._d_buffers]

    def _tp_scope(self):
        """Scope every jit DISPATCH in: trace-time reads inside model
        code (`llama._tp_repl`'s determinism fences, the kernels'
        `mesh.shard_kernel`) then see this replica's submesh. Without
        TP the scope hides any global TRAINING mesh (`fleet.init`
        leaves one set): a one-device engine's kernels must not be
        split over it."""
        if self._tp is None:
            # the common case — no training mesh set — stays the shared
            # stateless nullcontext (this sits on the per-step hot path)
            return _NULL_SCOPE if mesh_mod.get_mesh() is None \
                else mesh_mod.use_mesh(None)
        return self._tp.scope()

    def _view_tp(self, draft: bool = False):
        """The (mesh, axis) pair `RaggedKVCacheView` routes the kernel
        path's shard_map through — only when the respective pool is
        actually head-sharded (a replicated draft pool must run the
        plain kernel)."""
        if self._tp is None or self._tp.tp <= 1:
            return None
        from ..serving.submesh import TP_AXIS
        hk = (self._spec.draft_model.config.num_key_value_heads
              if draft else self.model.config.num_key_value_heads)
        if hk % self._tp.tp:
            return None
        return (self._tp.jax_mesh, TP_AXIS)

    def _jit_lru(self, cache: "OrderedDict", key, build, cap=None,
                 family: str = "misc"):
        """The one keyed-LRU program-cache discipline (build on miss,
        evict oldest past the cap, MRU-bump on hit) behind every keyed
        program family (ragged, install, draft, verify). Every miss routes through
        `profile.compile_timed`, so the program's first invocation is
        metered as `pdt_jit_compiles_total{family}` + compile-seconds
        + the retrace-storm window, and cache footprint/evictions ride
        `pdt_jit_cache_entries`/`pdt_jit_cache_evictions_total` —
        pdt-lint PDT012 pins all compile seams to this method (or
        `_jit_singleton`), so compile observability cannot be
        bypassed."""
        jit = cache.get(key)
        if jit is None:
            jit = _profile.compile_timed(
                _name_program(build(), family, key), family, key)
            cache[key] = jit
            evicted = 0
            while len(cache) > (cap or self._max_prefill):
                cache.popitem(last=False)                  # LRU
                evicted += 1
            _profile.note_cache(family, len(cache), evicted)
        else:
            cache.move_to_end(key)
        return jit

    def _jit_singleton(self, family: str, build):
        """The singleton-program arm of the compile-metering seam:
        built once per engine lifetime (decode, draft_scan), no key space, no cache — but the same
        `compile_timed` first-call metering as `_jit_lru` misses."""
        return _profile.compile_timed(
            _name_program(build(), family), family)

    def _count_attn_pages(self, query_start, query_len, context_len,
                          n_rows, block_q):
        """`pdt_serving_attn_pages_total` for one ragged dispatch, from
        the descriptors it is dispatched with: the kernel's own loop
        bounds (`ragged_pages_walked`), evaluated on the host. Called
        with telemetry on only."""
        from ..ops.ragged_paged_attention import (kv_block_pages,
                                                  ragged_pages_walked)
        dt = self._kv_shape[3]
        for g in self._groups:
            if not g.pools:
                continue                # no KV layer: no attention call
            hk, hd = g.spec.num_kv_heads, g.spec.head_dim
            if self._view_tp() is not None:
                hk //= self._tp.tp              # a shard's local heads
            walked = ragged_pages_walked(
                query_start, query_len, context_len, n_rows,
                block_q=block_q, page_size=self.page_size,
                window=g.window, table_pages=self.pps,
                diffusion_block=self._dblock,
                block_pages=kv_block_pages(
                    self.page_size, hd, hk,
                    1 if self._qkv else jnp.dtype(dt).itemsize, self.pps))
            _M_ATTN_PAGES.inc(walked, kind="walked")
            _M_ATTN_PAGES.inc(int(n_rows) // block_q * self.pps - walked,
                              kind="skipped")

    def _count_attn_kv_rows(self, phase, query_len, context_len,
                            sampled_len):
        """`pdt_serving_attn_kv_rows_total` for one dispatch, from its
        descriptors: a group's layers each read a sequence's stored rows
        from the window edge of its first query row (row 0 without a
        window) to its context's end, the bounds of the kernel's walk
        (`live_kv_blocks`) in rows. The layers behind the model's
        `rows_leave_after()` run on the sampled rows alone, one query a
        slot at its context's end (`sampled_len`: 1 where the slot
        samples). Called with telemetry on only."""
        ctx = np.asarray(context_len)

        def rows(n, window):
            lo = 0 if window is None else np.maximum(ctx - n - window + 1, 0)
            return int(np.where(n > 0, ctx - lo, 0).sum())

        ql, sl = np.asarray(query_len), np.asarray(sampled_len)
        for g in self._groups:
            n = g.readers_before * rows(ql, g.window) \
                + g.readers_after * rows(sl, g.window)
            if n:
                _M_ATTN_KV_ROWS.inc(n, group=g.name, phase=phase)

    def _tables(self):
        """The block tables a step program takes: the one table, or one
        a page group in the groups' order."""
        if len(self._groups) == 1:
            return jnp.asarray(self._bt)
        return tuple(jnp.asarray(g.bt) for g in self._groups)

    def _pages_bound(self, contexts) -> int:
        """Power-of-two-bucketed static gather trim for a dispatch
        whose max context length is ``max(contexts)`` — the shared
        bound formula of the admission, verify, and draft-backfill
        program families."""
        need = max(-(-int(c) // self.page_size) for c in contexts)
        return min(1 << max(need - 1, 0).bit_length(), self.pps)

    def _get_ragged_prefill(self, t_pad: int, pages_bound: int):
        """One jit object per (padded token count, pow2 gather bound) —
        the whole program key space of admission."""
        return self._jit_lru(
            self._ragged_jits, (t_pad, pages_bound),
            lambda: self._build_ragged_step(self._ragged_block_q,
                                            pages_bound),
            family="ragged")

    def _build_ragged_step(self, block_q: int, pages_bound=None,
                           draft: bool = False,
                           select_rows: bool = True,
                           return_logits: bool = False,
                           rule=None):
        """The one ragged program: packed ids -> per-token rope ->
        ONE KV scatter into the pages -> ragged paged attention with
        per-sequence descriptors -> sample each slot's designated row.
        Serves admission batches (block_q=8) and, at block_q=1 with
        t_pad == B, the decode step. `draft=True` builds the same
        program over the DRAFT model/pools — the spec mode's
        draft-cache backfill prefill (its sampled rows are never read
        back). `select_rows=False` drops the per-slot row select and
        returns EVERY packed row's pick (`sample_rows` is ignored) —
        the speculative VERIFY pass, whose acceptance needs the
        target's choice at all k+1 positions. `return_logits=True`
        additionally returns the (selected) logit rows — the decode
        program's sentry variant, so the every-Nth-step numeric scan
        (serving/sentry.py) can pull them to host without a second
        dispatch. `rule` (a block model's pass, `_block_rule`) stands in
        for the row select and the sampler: the caller passes `(read,
        *aux)` in `sample_rows`' place, the head runs over the packed
        rows `read` alone (over every row where `return_logits` asks
        for them), the rule is given those rows' logits, their ids and
        `aux`, and what it returns comes back where the tokens do."""
        model = self._spec.draft_model if draft else self.model
        params = self._d_params if draft else self._params
        buffers = self._d_buffers if draft else self._buffers
        strat, temp = self.strategy, self.temperature
        tk, tp = self.top_k, self.top_p
        view_tp = self._view_tp(draft=draft)
        qkv = bool(self._qkv)
        spec = _cache_spec(model) if draft else self._layer_spec
        stateful = bool(self._state_spec) and not draft
        dblock = 1 if draft else self._dblock
        pool_group = None if draft or len(self._groups) == 1 \
            else self._pool_group
        # an admission program of a model whose last layers keep
        # nothing carries only the sampled rows through them
        leave = self._leave_after is not None and not draft \
            and rule is None and select_rows and block_q != 1

        def run(pv, bv, kv, ids, tok_seq, qpos, qstart, qlen, ctx, bt,
                sample_rows, key):
            from .generation import bind_state, _sample_token
            from .llama import RaggedKVCacheView
            state = ()
            if stateful:
                kv, state = kv
            pools, states = enumerate(kv), iter(state)
            with bind_state(params, buffers, pv, bv), no_grad():
                views = []
                for s in spec:
                    if isinstance(s, KVSpec):
                        n, e = next(pools)
                        # its group's table, where there are several
                        table = bt if pool_group is None \
                            else bt[pool_group[n]]
                        views.append(RaggedKVCacheView(
                            e[0], e[1], table, tok_seq, qpos, qstart, qlen,
                            ctx, block_q, pages_bound, tp=view_tp,
                            k_scale=e[2] if qkv else None,
                            v_scale=e[3] if qkv else None,
                            diffusion_block=dblock))
                    elif isinstance(s, StateSpec):
                        views.append(RaggedStateView(
                            next(states), tok_seq, qstart, qlen, ctx,
                            one_token=block_q == 1))
                    else:
                        views.append(None)      # ReportSpec or nothing
                head = {}
                if leave:
                    head = {"sample_rows": sample_rows}
                elif rule is not None and not return_logits:
                    head = {"sample_rows": sample_rows[0]}
                logits, new = model.forward(
                    Tensor(ids[None]), past_key_values=views,
                    use_cache=True, **head)
                rows = logits._value[0]
                if rule is not None:
                    read = sample_rows[0]
                    nxt = rule(rows[read] if return_logits else rows,
                               ids[read], sample_rows[1:])
                else:
                    if select_rows and not leave:
                        rows = rows[jnp.clip(sample_rows, 0,
                                             rows.shape[0] - 1)]
                    nxt, _ = _sample_token(rows, key, strat, temp, tk, tp)
                cache = [
                    (v.k_pages._value, v.v_pages._value,
                     v.k_scale._value, v.v_scale._value) if qkv
                    else (v.k_pages._value, v.v_pages._value)
                    for v in new if isinstance(v, RaggedKVCacheView)]
                if stateful:
                    cache = (cache, [v.arrays for v in new
                                     if isinstance(v, RaggedStateView)])
                out = (nxt,) + ((rows,) if return_logits else ()) \
                    + (cache,)
                reports = [v for s, v in zip(spec, new)
                           if isinstance(s, ReportSpec)]
                if reports:
                    out += ((jnp.concatenate([c for c, _ in reports]),
                             tuple(r for _, r in reports)),)
                return out

        return jax.jit(run, donate_argnums=(2,))

    # -- page accounting ------------------------------------------------
    def _worst_pages(self, req: Request,
                     g: Optional[_PageGroup] = None) -> int:
        """Pages a request reserves in a group: its longest possible
        sequence's, or in a derived window group what a slot holds
        between dispatches (a dispatch's own rows come out of the slack
        the group's pool was sized with, never out of a reservation)."""
        worst_len = min(len(req.prompt) + req.max_new_tokens, self.S)
        worst = -(-worst_len // self.page_size)
        return min(worst, g.steady) if g is not None and g.derived \
            else worst

    def _reserve_ok(self, req: Request, shared_pages: int = 0) -> bool:
        """Admit only if the request's worst-case page demand (net of any
        shared prefix pages it attaches) fits the pool net of other
        slots' outstanding (reserved-but-unallocated) pages — lazy
        growth can then never fail mid-flight — in every page group.
        Evicts LRU prefix-cache entries when that frees enough (the
        first group's: a model with several has no prefix cache)."""
        for g in self._groups:
            held = g.slot_next_idx - g.slot_freed if g.derived \
                else g.slot_next_idx
            outstanding = int(sum(
                g.slot_reserved[i] - held[i]
                for i, r in enumerate(self._slot_req) if r is not None))
            need = self._worst_pages(req, g) + outstanding
            if g is self._groups[0]:
                need -= shared_pages
                if len(g.free) < need and not self._ensure_free(need):
                    return False
            elif len(g.free) < need:
                return False
        return True

    # -- prefix cache ---------------------------------------------------
    def _incref(self, page: int):
        self._page_rc[page] += 1

    def _decref(self, page: int, g: Optional[_PageGroup] = None):
        g = g or self._groups[0]
        g.page_rc[page] -= 1
        if g.page_rc[page] == 0:
            g.free.append(page)

    def _evict_one(self) -> bool:
        """Evict the least-recently-used CHILDLESS trie node (leaves
        first — an inner node's page must outlive its descendants'
        block-table references into the shared chain)."""
        for key, node in self._prefix_nodes.items():   # LRU order
            if node["children"] == 0:
                del self._prefix_nodes[key]
                if node["parent"] is not None:
                    self._prefix_nodes[node["parent"]]["children"] -= 1
                self._decref(node["page"])
                return True
        return False

    def _cache_only_pages(self) -> int:
        """Pages whose every reference comes from trie nodes — the upper
        bound on what eviction can return to the free list."""
        holds: Dict[int, int] = {}
        for node in self._prefix_nodes.values():
            holds[node["page"]] = holds.get(node["page"], 0) + 1
        return sum(1 for p, n in holds.items() if self._page_rc[p] == n)

    def _ensure_free(self, n: int) -> bool:
        if len(self._free) >= n:
            return True
        # feasibility first: draining the whole cache for a request that
        # still cannot fit would destroy every shared prefix for nothing
        if len(self._free) + self._cache_only_pages() < n:
            return False
        while len(self._free) < n and self._evict_one():
            pass
        return len(self._free) >= n

    def _match_prefix(self, toks: List[int]):
        """Longest cached full-page prefix of `toks` via the page trie —
        O(p_len) total key work — capped so at least one prompt token
        remains to prefill (its logits seed decoding)."""
        max_pages = (len(toks) - 1) // self.page_size
        pages, parent = [], None
        for f in range(max_pages):
            key = (parent, tuple(toks[f * self.page_size:
                                      (f + 1) * self.page_size]))
            node = self._prefix_nodes.get(key)
            if node is None:
                break
            self._prefix_nodes.move_to_end(key)     # MRU
            pages.append(node["page"])
            parent = key
        if not pages:
            return None
        # attach a POWER-OF-TWO page count. The ragged program's key has
        # no shared_len in it, so nothing needs this; it stays because
        # lifting it changes what a hit reuses (ROADMAP D1c)
        return pages[:1 << (len(pages).bit_length() - 1)]

    def _register_prefix(self, slot: int, req: Request):
        # walk/extend the page trie; registration depth is capped at the
        # entry budget — registering more nodes than the cache can hold
        # would only churn the LRU
        full = min(len(req.prompt) // self.page_size,
                   self._max_prefix_entries)
        parent = None
        for f in range(full):
            key = (parent, tuple(req.prompt[f * self.page_size:
                                            (f + 1) * self.page_size]))
            node = self._prefix_nodes.get(key)
            if node is None:
                page = int(self._bt[slot, f])
                self._incref(page)
                self._prefix_nodes[key] = {"page": page, "parent": parent,
                                           "children": 0}
                if parent is not None:
                    self._prefix_nodes[parent]["children"] += 1
            else:
                self._prefix_nodes.move_to_end(key)
            parent = key
        while len(self._prefix_nodes) > self._max_prefix_entries:
            if not self._evict_one():
                break

    def _alloc_page(self, slot: int,
                    g: Optional[_PageGroup] = None) -> int:
        # chaos tests arm this site (exc=PoolExhausted) to force the
        # preemption path that reservation accounting makes unreachable
        fault_point("serving.alloc_page")
        g = g or self._groups[0]
        if not g.free and g is self._groups[0]:
            self._ensure_free(1)
        if not g.free:
            raise PoolExhausted(
                f"KV page pool exhausted ({g.num_pages - 1} usable "
                "pages, none free after prefix-cache eviction)")
        page = g.free.pop()
        g.page_rc[page] = 1
        g.slot_pages[slot].append(page)
        g.bt[slot, g.slot_next_idx[slot]] = page
        g.slot_next_idx[slot] += 1
        g.allocated += 1
        return page

    def _reserve_and_alloc(self, slot: int, req: Request, p_len: int):
        """Record the slot's worst-case reservation and allocate pages
        covering the prompt — the common preamble of every paged
        admission path. A derived window group allocates a dispatch at
        a time instead (`_dispatch_ragged`): a long prompt never holds
        more of its pages than a dispatch's rows and the window."""
        for g in self._groups:
            g.slot_reserved[slot] = self._worst_pages(req, g)
            while not g.derived \
                    and g.slot_next_idx[slot] * self.page_size < p_len:
                self._alloc_page(slot, g)

    def _reclaim_below_window(self, slot: int, pos: int):
        """Give back `slot`'s pages that slid wholly below a group's
        attention window [pos + 1 - w, pos] of a query at `pos`, the
        slot's next position: no later call reads them."""
        for g in self._window_groups:
            ws = pos + 1 - g.window
            while (g.slot_freed[slot] + 1) * self.page_size <= ws:
                j = int(g.slot_freed[slot])
                page = int(g.bt[slot, j])
                if page != 0:
                    g.slot_pages[slot].remove(page)
                    self._decref(page, g)
                    g.bt[slot, j] = 0      # trash-route
                    g.reclaimed += 1
                g.slot_freed[slot] += 1

    # -- decode --------------------------------------------------------
    def _decode_query_lens(self):
        """One query a slot. A state layer must leave an idle slot's
        state alone, so for a model with state layers an idle slot's
        row is dispatched with NO query (the KV layers' idle rows
        trash-route either way)."""
        if not self._state_spec:
            return self._decode_ones
        return jnp.asarray(np.fromiter(
            (r is not None for r in self._slot_req), np.int32, self.B))

    def _requeue_or_starve(self, req: Request,
                           finished: List[Request]):
        """Shared tail of both preemption paths (decode-time eviction,
        admission-time allocation failure): bump the counters, then
        requeue at the queue HEAD — or finalize PREEMPTED past
        `max_preemptions` (starvation guard). `enqueue_time` restarts:
        `max_queue_time` bounds each contiguous wait for a slot (time
        spent RUNNING before a preemption must not count as waiting);
        end-to-end budgets belong to `deadline`, and repeated bouncing
        is bounded by the starvation guard."""
        self.num_preemptions += 1
        _M_PREEMPTIONS.inc()
        telemetry.event("serving.preempt", rid=req.rid,
                        request_id=req.request_id,
                        preemptions=req.preemptions + 1,
                        tokens=len(req.output))
        req.preemptions += 1
        if req.preemptions > self.max_preemptions:
            self._finalize(req, RequestStatus.PREEMPTED,
                           f"preempted {req.preemptions}x under pool "
                           "pressure (starvation guard)", finished)
        else:
            req.status = RequestStatus.QUEUED
            req.enqueue_time = self._clock()
            # head of its own PRIORITY CLASS: a preempted batch
            # request resumes ahead of other batch work but never
            # jumps queued interactive admissions
            idx = 0
            while idx < len(self._queue) \
                    and self._queue[idx].priority < req.priority:
                idx += 1
            self._queue.insert(idx, req)

    def _preempt_youngest(self,
                          finished: List[Request]) -> Optional[int]:
        """Release the most-recently-admitted running slot to free its
        pages. The victim re-enters the queue HEAD with its generated
        tokens folded into the re-prefill prompt (the prefix cache, when
        enabled, keeps its prompt pages so re-prefill is cheap); past
        `max_preemptions` evictions the starvation guard finalizes it
        PREEMPTED instead of bouncing forever. Returns the released
        slot, or None if nothing is running."""
        running = [i for i, r in enumerate(self._slot_req)
                   if r is not None]
        if not running:
            return None
        slot = max(running, key=lambda i: int(self._slot_seq[i]))
        req = self._slot_req[slot]
        # prompt full pages hold valid prefilled KV, so registration is
        # safe — and cache-only pages remain evictable under pressure
        self._release_slot(slot)
        self._requeue_or_starve(req, finished)
        return slot

    def _grow_slot(self, slot: int, finished: List[Request],
                   extra: int = 0) -> bool:
        """Lazy page growth for `slot`'s next decode write — `extra`
        further positions when a speculative round will scatter
        ``k+1`` rows at ``pos..pos+k`` (still within the admission
        reservation: the verify budget is capped at the remaining
        token budget). On pool exhaustion (reachable only via fault
        injection or an accounting bug — admission reserves worst-case
        demand) preempt the youngest running request and retry.
        Returns False if `slot` itself was preempted away."""
        while True:
            g = next((g for g in self._groups
                      if g.slot_next_idx[slot] * self.page_size
                      <= int(self._pos[slot]) + extra), None)
            if g is None:
                return True
            try:
                self._alloc_page(slot, g)
            except PoolExhausted:
                if self._pending:
                    # pipelined window: commit the in-flight dispatches
                    # FIRST so the preemption victim keeps every token
                    # the device actually produced (zero loss under
                    # pressure at k>1) — and an EOS hiding in the
                    # window may free the pages without any victim
                    self._harvest_pending(finished)
                    if self._slot_req[slot] is None:
                        return False    # slot finalized at harvest
                    continue
                victim = self._preempt_youngest(finished)
                if victim is None:
                    raise
                if victim == slot:
                    return False

    def _decode(self, finished: List[Request]) -> bool:
        """One batched decode step for every active slot. Starvation-
        guard finalizations are appended to the CALLER's `finished`
        before the dispatch, so they survive an injected dispatch
        fault. Returns True when a SPECULATIVE round fully handled the
        step (tokens appended and finalizations done inside the
        round); False when the plain path ran and the caller commits
        one token per slot from `self._tok`. A spec round that
        degrades (an armed `speculative.draft`/`speculative.verify`
        site fired) falls straight through to the plain path — the
        round still makes progress, the REQUEST never fails."""
        if self._gen is not None:
            return self._decode_blocks(finished)
        if self._spec is not None and self._spec_decode(finished):
            return True
        if self._decode_jit is None:
            # decode is the SAME ragged program at block_q=1 — B
            # sequences of one query token each. The constant
            # descriptor arrays (slot indices, unit query lens) are
            # built once: B never changes for the engine's lifetime and
            # re-uploading them every step would tax the hot loop.
            # Sentry variant: the program also returns its sampled-row
            # logits, so the every-Nth scan is a host pull, not a
            # second dispatch (attach_sentry resets _decode_jit so this
            # rebuild happens)
            self._decode_logits = (self._sentry is not None
                                   and self._sentry.wants_logits)
            self._decode_jit = self._jit_singleton(
                "decode", lambda: self._build_ragged_step(
                    1, return_logits=self._decode_logits))
            self._decode_idx = jnp.arange(self.B, dtype=jnp.int32)
            self._decode_ones = jnp.ones(self.B, jnp.int32)
        # inactive slots decode garbage at a clamped position; their
        # outputs are never read, and their block-table rows are all
        # trash-page, so their KV writes land in page 0 (never read)
        pos = np.clip(self._pos, 0, self.S - 1)
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            if not self._grow_slot(i, finished):
                continue          # slot i itself was preempted
            if self._window_groups:
                # reclaim pages that slid wholly below the attention
                # window [ctx - w, ctx): the kernel never reads them
                self._reclaim_below_window(i, int(self._pos[i]))
        if not any(r is not None for r in self._slot_req):
            return False          # every slot preempted away
        kv = self._cache()
        bt = self._tables()
        # fault BEFORE the dispatch (and before the PRNG key advances):
        # a retried step replays an identical sampling stream
        fault_point("serving.decode")
        n_active = sum(r is not None for r in self._slot_req)
        # rids: the request_ids this batched step decodes for — the
        # Chrome exporter fans the span out into each request's
        # timeline row, and request_tree() fans it into each tree
        rids = ([r.request_id for r in self._slot_req if r is not None]
                if telemetry.enabled() else ())
        if telemetry.enabled():
            # one query a slot at its position: the decode dispatch's
            # descriptors, as built below
            self._count_attn_pages(
                np.arange(self.B), np.ones(self.B, np.int32), pos + 1,
                self.B, 1)
            live = np.fromiter((r is not None for r in self._slot_req),
                               np.int32, self.B)
            self._count_attn_kv_rows("decode", live, pos + 1, live)
        with telemetry.span("serving.decode_step", slots=n_active,
                            rids=rids):
            # pdt-lint: disable=PDT001 decode_step_seconds measures the
            # REAL wall time of one device dispatch incl. its D2H sync
            # (tokens/sec derives from it) — a fake clock here would
            # fabricate hardware throughput, not make tests exact
            t0 = time.perf_counter()
            # inputs and launch as in _dispatch_ragged; the block
            # tables were uploaded above, in serving.decode's own time
            with self._tp_scope():
                with telemetry.span("serving.inputs"):
                    bidx = self._decode_idx
                    qlen = self._decode_query_lens()
                    # pipelined mode: mid-window the token input is the
                    # PREVIOUS dispatch's on-device output — the greedy
                    # feedback needs no host round-trip (the whole point)
                    tok_in = (self._tok_dev if self._tok_dev is not None
                              else jnp.asarray(self._tok))
                    # multi-LoRA: decode packs one row per slot in slot
                    # order, so the gather vector IS the slot-adapter map
                    args = (self._lora_pv(self._pv(), self._slot_adapter),
                            self._bv(),
                            kv, tok_in, bidx,
                            jnp.asarray(pos.astype(np.int32)), bidx,
                            qlen,
                            jnp.asarray((pos + 1).astype(np.int32)), bt,
                            bidx, self._next_keys())
                with telemetry.span("serving.launch"):
                    nxt, lg_rows, reports = self._take_step(
                        self._decode_jit(*args), self._decode_logits)
            # pdt-lint: disable=PDT001 same real-wall measurement as t0
            t1 = time.perf_counter()
            if self.harvest_every > 1:
                # deferred-harvest path: the token vector stays on
                # device; defer the sync, commits, and sentry checks to
                # the window's one batched harvest. The stride tick
                # happens NOW (per dispatch) so the scan schedule
                # matches the synchronous loop step for step.
                scan = False
                if self._sentry is not None:
                    with telemetry.span("serving.sentry"):
                        # pdt-lint: disable=PDT001 sentry cost is REAL
                        # wall (the bench bar divides it by step time)
                        s0 = time.perf_counter()
                        scan = self._sentry.step_tick()
                        # pdt-lint: disable=PDT001 same measurement
                        self._sentry.note_cost(time.perf_counter() - s0)
                self._corrupt_kv_site()
                act = tuple(i for i, r in enumerate(self._slot_req)
                            if r is not None)
                for i in act:
                    r = self._slot_req[i]
                    r.device_len = max(r.device_len,
                                       len(r.output)) + 1
                    self._pos[i] += 1
                self._pending.append({
                    "nxt": nxt,
                    "lg": lg_rows if scan else None,
                    "scan": scan, "act": act,
                    "pos": self._pos.copy()})
                self._tok_dev = nxt
                self._window_wall += t1 - t0
                return True
            # synchronous path (harvest_every=1, today's loop): the
            # D2H copy is the step's sync point — dispatch alone
            # returns before the device finishes, so time through it
            nxt = self._harvest_sync(nxt)
            # pdt-lint: disable=PDT001 same real-wall measurement
            dt = time.perf_counter() - t0
        if telemetry.enabled():
            _M_DECODE_STEP.observe(dt)
            _M_DECODE_TOKENS.inc(n_active)
            if dt > 0:
                _M_TOKENS_PER_SEC.set(n_active / dt)
        if reports is not None:
            act = [i for i, r in enumerate(self._slot_req)
                   if r is not None]
            self._harvest_reports(reports, act, act, pos[act])
        # gray-failure corrupt site + sentry checks, AFTER the timed
        # window so decode_step_seconds stays comparable across
        # sentry-on/off engines (the sentry's own cost rides
        # sentry.spent — the bench's in-situ overhead numerator)
        self._corrupt_kv_site()
        if self._sentry is not None:
            with telemetry.span("serving.sentry"):
                # pdt-lint: disable=PDT001 sentry cost is a REAL-wall
                # hardware-honesty number (the <=3% bench bar divides
                # it by real step time) — a fake clock would fabricate
                # it
                s0 = time.perf_counter()
                scan = self._sentry.step_tick()
                act = [i for i, r in enumerate(self._slot_req)
                       if r is not None]
                # pdt-lint: disable=PDT001 same real-wall measurement
                self._sentry.note_cost(time.perf_counter() - s0)
                self._harvest_sentry(nxt, lg_rows if scan else None,
                                     act, lag=0)
        for i, r in enumerate(self._slot_req):
            if r is not None:
                self._tok[i] = nxt[i]
                self._pos[i] += 1
        return False

    # -- generation by diffusion over blocks ----------------------------
    # (cache_spec.BlockDiffusionSpec says what a block and a pass are)
    def _start_generation(self, slot: int, context: int, given):
        """A block model's slot whose whole prompt blocks are in its
        pages: it generates from position `context`, and its first block
        starts with the prompt's left-over tokens `given`."""
        self._pos[slot] = context
        self._start_block(slot, given)
        _M_ADMISSIONS.inc()

    def _start_block(self, slot: int, given=()):
        """A fresh block at the slot's position: `given` tokens, then
        the mask."""
        n = len(given)
        self._blk_ids[slot] = self._gen.mask_token_id
        self._blk_ids[slot, :n] = given
        self._blk_masked[slot] = np.arange(self._dblock) >= n
        self._blk_given[slot] = n
        self._blk_passes[slot] = 0
        self._blk_t0[slot] = np.nan

    def _block_rule(self):
        """The transfer rule as the step program runs it: every live
        slot's next block, (slots, block + 1) int32, the ids and then
        the masked flags as one bit a position: the pass's whole answer
        to the host, not its (block, vocabulary) logits."""
        gen, b = self._gen, self._dblock

        def rule(rows, ids, aux):
            masked, passes = aux
            new_ids, new_masked = gen.transfer(
                rows.reshape(-1, b, rows.shape[-1]), ids.reshape(-1, b),
                masked.reshape(-1, b), passes)
            bits = jnp.sum(new_masked.astype(jnp.int32)
                           << jnp.arange(b, dtype=jnp.int32), axis=1)
            return jnp.concatenate(
                [new_ids.astype(jnp.int32), bits[:, None]], axis=1)

        return rule

    def _block_end(self, slot: int):
        """What slot `slot`'s finished block (one that holds no mask)
        hands its request: (tokens past the given head, within the
        budget and up to EOS; whether the request ends with them, by
        EOS, budget or a next block past `max_seq_len`)."""
        r, b = self._slot_req[slot], self._dblock
        toks = [int(t) for t in
                self._blk_ids[slot, int(self._blk_given[slot]):]]
        toks = toks[:r.max_new_tokens - len(r.output)]
        if self.eos is not None and self.eos in toks:
            toks = toks[:toks.index(self.eos) + 1]
        ends = (self.eos is not None and toks and toks[-1] == self.eos) \
            or len(r.output) + len(toks) >= r.max_new_tokens \
            or int(self._pos[slot]) + 2 * b > self.S
        return toks, bool(ends)

    def _decode_blocks(self, finished: List[Request]) -> bool:
        """One PASS over the block of every live slot: ONE dispatch of
        the ragged program at `slots x 2 block` rows, slot `i`'s segment
        in rows `[2 i block, 2 (i + 1) block)`, one q block of the
        attention kernel. The block the pass samples is at positions
        `[p, p + block)` with `context_len = p + block`; the transfer
        rule (`_block_rule`) reads its rows alone. A block that holds no
        mask is finished and its keys and values (the last pass wrote
        them from ids that were not final yet) must be written once
        more. Where its request goes on, the finished block fills the
        segment's first half at its own positions and the next block's
        first pass the second (`p` a block further on): the K/V scatter
        in front of attention writes both halves and the mask by blocks
        (`ragged_paged_attention.block_frontier`) shows each half what a
        pass of its own would have shown it, so the same numbers come
        out of one pass fewer, and the q block walks the slot's pages
        once for both. Any other slot's block fills the first half and
        the second is padding; a block that ends its request has a pass
        of its own there (its keys may still feed the prefix cache).
        After the dispatch the host hands finished blocks' tokens to
        their requests and advances the slots by what the rule decided.
        Slots in any pass of their block share the dispatch. Always
        handles the step itself (returns True)."""
        b, seg = self._dblock, 2 * self._dblock
        if self._decode_jit is None:
            self._decode_logits = (self._sentry is not None
                                   and self._sentry.wants_logits)
            self._decode_jit = self._jit_singleton(
                "decode", lambda: self._build_ragged_step(
                    seg, return_logits=self._decode_logits,
                    rule=self._block_rule()))
            self._decode_idx = jnp.arange(self.B, dtype=jnp.int32) * seg
        done = {i: self._block_end(i)
                for i, r in enumerate(self._slot_req)
                if r is not None and not self._blk_masked[i].any()}
        for i, r in enumerate(self._slot_req):
            if r is not None:
                # pages for what the pass writes, before it (within the
                # admission reservation: a next block is begun only for
                # a request that goes on; a preempted slot just leaves)
                fused = i in done and not done[i][1]
                self._grow_slot(i, finished,
                                extra=(seg if fused else b) - 1)
        live = [i for i, r in enumerate(self._slot_req) if r is not None]
        if not live:
            return True           # every slot preempted away
        fault_point("serving.decode")
        on = np.zeros(self.B, bool)
        on[live] = True
        fuse = np.zeros(self.B, bool)
        fuse[[i for i in live if i in done and not done[i][1]]] = True
        n_fuse = int(fuse.sum())
        n_alone = sum(1 for i in live if i in done) - n_fuse
        pos = np.where(on, self._pos, 0).astype(np.int32)
        # the block a segment's second half holds: a fused slot's next
        # block, all masks, in its first pass (padding elsewhere, at the
        # first half's positions)
        at = pos + np.where(fuse, b, 0)
        masked = np.where(fuse[:, None], True,
                          self._blk_masked) & on[:, None]
        passes = np.where(fuse, 0, self._blk_passes).astype(np.int32)
        now = self._clock()
        self._blk_t0[on & np.isnan(self._blk_t0)] = now
        rids = ([self._slot_req[i].request_id for i in live]
                if telemetry.enabled() else ())
        qlen = (np.where(on, b, 0) + np.where(fuse, b, 0)).astype(np.int32)
        ctx = np.where(on, pos + qlen, 0).astype(np.int32)
        if telemetry.enabled():
            self._count_attn_pages(np.arange(self.B) * seg, qlen, ctx,
                                   self.B * seg, seg)
        with telemetry.span("serving.decode_step", slots=len(live),
                            rows=int(qlen.sum()),
                            commit_slots=n_fuse + n_alone,
                            fused_slots=n_fuse, rids=rids):
            # pdt-lint: disable=PDT001 decode_step_seconds measures the
            # REAL wall time of one device dispatch incl. its D2H sync
            t0 = time.perf_counter()
            # inputs and launch as in _dispatch_ragged
            with self._tp_scope():
                with telemetry.span("serving.inputs"):
                    slot = np.arange(self.B)[:, None]
                    tok_seq = np.concatenate(
                        [np.where(on[:, None], slot, -1).repeat(b, 1),
                         np.where(fuse[:, None], slot, -1).repeat(b, 1)],
                        axis=1).reshape(-1)
                    qpos = np.concatenate([pos[:, None] + np.arange(b),
                                           at[:, None] + np.arange(b)],
                                          axis=1).reshape(-1)
                    ids = np.concatenate(
                        [self._blk_ids,
                         np.full_like(self._blk_ids,
                                      self._gen.mask_token_id)],
                        axis=1).reshape(-1)
                    # the rows the rule reads: a fused segment's second
                    # half, any other's first
                    read = (slot * seg + np.where(fuse, b, 0)[:, None]
                            + np.arange(b)).reshape(-1)
                    args = (self._lora_pv(self._pv(),
                                          np.repeat(self._slot_adapter,
                                                    seg)),
                            self._bv(), self._cache(),
                            jnp.asarray(ids.astype(np.int32)),
                            jnp.asarray(tok_seq.astype(np.int32)),
                            jnp.asarray(qpos.astype(np.int32)),
                            self._decode_idx, jnp.asarray(qlen),
                            jnp.asarray(ctx), jnp.asarray(self._bt),
                            (jnp.asarray(read.astype(np.int32)),
                             jnp.asarray(masked.reshape(-1)),
                             jnp.asarray(passes)), self._next_keys())
                with telemetry.span("serving.launch"):
                    nxt, lg_rows, reports = self._take_step(
                        self._decode_jit(*args), self._decode_logits)
            nxt = self._harvest_sync(nxt)
            # pdt-lint: disable=PDT001 same real-wall measurement
            dt = time.perf_counter() - t0
        # the finished blocks' tokens, then what the pass decided
        handed = []
        for i in live:
            r = self._slot_req[i]
            if i in done:
                toks, ends = done[i]
                r.output.extend(toks)
                handed.extend(toks)
                self._note_first_token(r)
                self._pos[i] += b
                if telemetry.enabled():
                    _M_BLOCK_SECONDS.observe(self._clock()
                                             - self._blk_t0[i])
                if ends:
                    self._finalize(r, RequestStatus.FINISHED, None,
                                   finished)
                    self._release_slot(i)
                    continue
                self._start_block(i)
                self._blk_t0[i] = now
            self._blk_ids[i] = nxt[i, :b]
            self._blk_masked[i] = (int(nxt[i, b]) >> np.arange(b)) & 1
            self._blk_passes[i] += 1
        # counted after the step's timed part, telemetry on only
        if telemetry.enabled():
            _M_DECODE_STEP.observe(dt)
            _M_DECODE_TOKENS.inc(len(handed))
            _M_BLOCK_TOKENS.inc(len(handed))
            _M_BLOCK_PASSES.inc(len(live) - n_alone, kind="denoise")
            _M_BLOCK_PASSES.inc(n_alone, kind="commit")
            _M_BLOCK_COMMITS.inc(n_fuse, kind="fused")
            _M_BLOCK_COMMITS.inc(n_alone, kind="alone")
            if dt > 0:
                _M_TOKENS_PER_SEC.set(len(handed) / dt)
        if reports is not None:
            self._count_reports(reports[0])
        self._corrupt_kv_site()
        if self._sentry is not None:
            with telemetry.span("serving.sentry"):
                # pdt-lint: disable=PDT001 sentry cost is REAL wall
                s0 = time.perf_counter()
                scan = self._sentry.step_tick()
                # pdt-lint: disable=PDT001 same real-wall measurement
                self._sentry.note_cost(time.perf_counter() - s0)
                self._harvest_block_sentry(
                    handed, lg_rows if scan else None,
                    None if reports is None else reports[1], tok_seq,
                    qpos, np.repeat(fuse, seg) & (np.arange(self.B * seg)
                                                  % seg < b))
        return True

    def _harvest_block_sentry(self, handed, lg_rows, records, tok_seq,
                              qpos, committed):
        """Sentry checks over one pass: the tokens it handed out, then
        the reporting layers' records and, on a scan, the logits of its
        live rows. A pass that commits finished blocks beside their next
        blocks (packed rows `committed`) is given as the two passes it
        stands for: those blocks' rows, then the sampled blocks' rows,
        each in slot order."""
        if handed:
            self._sentry.observe_tokens(np.asarray(handed, np.int32))
        rows = np.flatnonzero(tok_seq >= 0)
        if lg_rows is not None:
            lg_rows = fault_value("serving.logits",
                                  np.asarray(lg_rows)[rows],
                                  tag=self.fault_tag)
        first = committed[rows]
        for part in (first, ~first):
            if not part.any():
                continue
            if records is not None:
                self._give_layer_rows(records, rows[part],
                                      tok_seq[rows[part]], qpos[rows[part]])
            if lg_rows is not None:
                self._sentry.observe_logits(lg_rows[part])

    # -- pipelined harvest seam (harvest_every=k, ISSUE 18) -------------
    # The _harvest_* family are the DESIGNATED host-sync functions of
    # the decode path: pdt-lint PDT011 bans D2H syncs (np.asarray,
    # .item(), jax.device_get, float()-of-operand) in step()/_decode()
    # outside them, so the overlap window cannot silently regrow a
    # per-step sync.
    def _harvest_sync(self, nxt):
        """The k=1 synchronous harvest: ONE dispatch's D2H token sync."""
        return np.asarray(nxt)

    def _harvest_sentry(self, nxt, lg_rows, act, lag: int):
        """Sentry checks over one harvested dispatch: the in-vocab
        token check, the every-Nth logit scan (pulled HERE — at k>1
        the pull rides the harvest, bounding detection latency at k
        steps, which `note_lag` meters), and the `serving.logits`
        VALUE fault site over the ACTIVE rows the scan inspects (the
        NaN-poisoned-logits drill; an inactive slot's garbage row is
        not a harvest). Callers wrap it in a `serving.sentry` span."""
        # pdt-lint: disable=PDT001 sentry cost is REAL wall (bench bar)
        s0 = time.perf_counter()
        lg_np = None
        if lg_rows is not None:
            lg_np = fault_value("serving.logits",
                                np.asarray(lg_rows)[act],
                                tag=self.fault_tag)
        # pdt-lint: disable=PDT001 same real-wall measurement
        sc = time.perf_counter() - s0
        self._sentry.note_cost(sc)
        self._sentry.observe_tokens(nxt[act])
        # lag metering is optional on the sentry protocol — custom
        # sentries (test recorders, canary probes) predate it
        note_lag = getattr(self._sentry, "note_lag", None)
        if note_lag is not None:
            note_lag(lag)
        if lg_np is not None:
            self._sentry.observe_logits(lg_np)

    def _harvest_due(self) -> bool:
        """Must the deferred window be harvested BEFORE this step's
        expiry/admission/dispatch? True when the window is full, when
        host work needs committed token state (waiting admissions, a
        running deadline that has passed), or when the NEXT dispatch
        could overrun a request's token budget or the sequence cap —
        the synchronous loop would have finalized the slot by now."""
        if len(self._pending) >= self.harvest_every:
            return True
        if self._queue:
            # admission needs free slots + host _tok; harvesting on a
            # non-empty queue keeps admission timing aligned with the
            # synchronous loop (pipelining pays off on settled batches)
            return True
        now = self._clock()
        depth = len(self._pending)
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            if r.deadline is not None and now >= r.deadline:
                return True         # _expire must see committed tokens
            if len(r.output) + depth >= r.max_new_tokens:
                return True         # the window holds the final token
            if int(self._pos[i]) >= self.S - 1:
                return True         # sequence cap: slot must finalize
        return False

    def _harvest_pending(self, finished: List[Request]):
        """Drain the deferred-harvest window: ONE batched D2H sync
        over every pending dispatch, then per-dispatch (in dispatch
        order) sentry checks and token commits — exactly the commits
        the synchronous loop would have made, including EOS/budget/
        cap finalization at the dispatch where it fired (later
        in-window tokens for a finalized slot are DISCARDED: the
        device over-ran the EOS it could not see, by construction at
        most k-1 tokens)."""
        entries, self._pending = self._pending, []
        self._tok_dev = None
        if not entries:
            self._window_wall = 0.0
            return
        with telemetry.span("serving.harvest",
                            window=len(entries)):
            # pdt-lint: disable=PDT001 the window's sync is REAL wall,
            # like decode_step_seconds (hardware-honesty throughput)
            t0 = time.perf_counter()
            stacked = np.asarray(jnp.stack([e["nxt"] for e in entries]))
            # pdt-lint: disable=PDT001 same real-wall measurement
            harvest_dt = time.perf_counter() - t0
        with telemetry.span("serving.commit", window=len(entries)):
            n_committed = self._commit_window(entries, stacked,
                                              finished)
        if telemetry.enabled():
            _M_DECODE_TOKENS.inc(n_committed)
            wall = self._window_wall + harvest_dt
            if wall > 0:
                _M_TOKENS_PER_SEC.set(n_committed / wall)
        self._window_wall = 0.0

    def _commit_window(self, entries, stacked, finished) -> int:
        """The deferred window's commits, per dispatch in dispatch
        order; returns the tokens committed."""
        n = len(entries)
        n_committed = 0
        done_slots: set = set()
        live_last: Dict[int, int] = {}
        for j, e in enumerate(entries):
            nxt = stacked[j]
            if self._sentry is not None:
                act = [i for i in e["act"] if i not in done_slots]
                with telemetry.span("serving.sentry"):
                    self._harvest_sentry(
                        nxt, e["lg"] if e["scan"] else None,
                        act, lag=n - 1 - j)
            for i in e["act"]:
                if i in done_slots:
                    continue        # finalized earlier in this window
                r = self._slot_req[i]
                if r is None:
                    continue
                tok = int(nxt[i])
                r.output.append(tok)
                n_committed += 1
                live_last[i] = tok
                hit_eos = self.eos is not None and tok == self.eos
                if hit_eos or len(r.output) >= r.max_new_tokens \
                        or int(e["pos"][i]) >= self.S - 1:
                    r.device_len = len(r.output)
                    self._finalize(r, RequestStatus.FINISHED, None,
                                   finished)
                    self._release_slot(i)
                    done_slots.add(i)
                    live_last.pop(i, None)
        for i, tok in live_last.items():
            self._tok[i] = tok
        for r in self._slot_req:
            if r is not None:
                r.device_len = len(r.output)    # staleness resync
        return n_committed

    def quiesce(self) -> int:
        """Drain the pipelined-decode window NOW: harvest every
        deferred dispatch so host-visible request state (`output`,
        `_tok`, `_pos`) is committed and consistent. The quiesce seam
        every state-export path crosses first — migration
        (`export_pages`), eviction, page install, sentry attach, and
        mid-decode preemption all call this before touching slot
        state. A no-op (returns 0) when the window is empty, including
        always at harvest_every=1. Finalizations land in the finished
        backlog the next step() delivers."""
        n = len(self._pending)
        if n:
            self._harvest_pending(self._finished_backlog)
        return n

    # -- speculative decoding (spec_decode=SpecConfig, ISSUE 10) -------
    def _spec_decode(self, finished: List[Request]) -> bool:
        """One speculative round: draft k tokens per slot (one fused
        scan dispatch over the draft's own paged cache, plus backfill
        prefills for slots whose draft cache was dropped), verify
        every slot in ONE batched ragged target dispatch, commit the
        longest matching prefix + bonus token, rewind the rest.
        Returns True when the round committed (the step is handled);
        False to degrade THIS round to plain decode (an armed
        `speculative.draft` / `speculative.verify` site fired)."""
        K = self._spec_k
        rids = ([r.request_id for r in self._slot_req if r is not None]
                if telemetry.enabled() else ())
        # pdt-lint: disable=PDT001 spec-round wall time feeds the same
        # REAL decode-throughput metrics as the plain decode step — a
        # fake clock would fabricate hardware tokens/sec
        t0 = time.perf_counter()
        try:
            with telemetry.span("serving.draft", k=K, rids=rids):
                fault_point("speculative.draft")
                props, kuse = self._spec_draft(finished)
        except FaultError as e:
            # only THIS site's faults degrade; a foreign FaultError
            # (serving.alloc_page armed with the default exc fires
            # inside the growth phase here) keeps its own semantics —
            # step()'s bounded decode-retry — instead of being
            # miscounted as a draft degradation
            if getattr(e, "site", "") != "speculative.draft":
                raise
            self._spec_degrade("draft", e)
            return False
        # pdt-lint: disable=PDT001 same real-wall measurement as t0
        draft_dt = time.perf_counter() - t0
        active = [i for i, r in enumerate(self._slot_req)
                  if r is not None]
        if not active:
            return True               # growth preempted everything
        try:
            emitted, proposed, accepted = self._spec_verify(
                active, props, kuse, finished)
        except FaultError as e:
            if getattr(e, "site", "") != "speculative.verify":
                raise
            self._spec_degrade("verify", e)
            return False
        # pdt-lint: disable=PDT001 same real-wall measurement as t0
        dt = time.perf_counter() - t0
        self.num_spec_rounds += 1
        self.num_spec_proposed += proposed
        self.num_spec_accepted += accepted
        _M_SPEC_ROUNDS.inc()
        _M_SPEC_PROPOSED.inc(proposed)
        _M_SPEC_ACCEPTED.inc(accepted)
        if telemetry.enabled():
            _M_SPEC_DRAFT_SECONDS.observe(draft_dt)
            # the round IS this step's decode dispatch: the effective-
            # throughput gauges stay meaningful under speculation
            _M_DECODE_STEP.observe(dt)
            _M_DECODE_TOKENS.inc(emitted)
            if dt > 0:
                _M_TOKENS_PER_SEC.set(emitted / dt)
            if self.num_spec_proposed:
                _M_SPEC_ACCEPT_RATE.set(self.num_spec_accepted
                                        / self.num_spec_proposed)
        return True

    def _spec_degrade(self, site: str, err: BaseException):
        """An armed spec fault site fired: count it, drop draft-cache
        validity (whatever the draft pass wrote is unverified garbage
        relative to the stream plain decode will now extend), and let
        the caller fall through to plain decode for THIS round — the
        request itself never fails."""
        self.num_spec_degraded += 1
        _M_SPEC_DEGRADED.inc(site=site)
        telemetry.event("serving.spec_degraded", site=site,
                        error=f"{type(err).__name__}: {err}")
        self._d_valid[:] = False

    def _spec_draft(self, finished: List[Request]):
        """The draft half of a round: size each slot's verify budget
        ``k_i = min(k, remaining_budget - 1, cache_room)`` (so a round
        can never emit past `max_new_tokens` or the cache end), grow
        TARGET pages to cover the verify scatter at ``pos..pos+k_i``
        (within the admission reservation — preempting only under
        injected pressure), grow + backfill the draft cache for slots
        whose draft state was dropped (fresh admissions, preemption
        re-prefills, migration imports, degraded rounds), then draft
        K greedy tokens per live slot in ONE fused scan dispatch.
        Returns (proposals (B, K), per-slot verify budgets (B,))."""
        K = self._spec_k
        kuse = np.zeros(self.B, np.int32)
        for i, r in enumerate(self._slot_req):
            if r is None:
                continue
            ki = min(K, r.max_new_tokens - len(r.output) - 1,
                     self.S - 1 - int(self._pos[i]))
            ki = max(int(ki), 0)
            if not self._grow_slot(i, finished, extra=ki):
                continue              # preempted away mid-growth
            kuse[i] = ki
        backfill = []
        for i, r in enumerate(self._slot_req):
            if r is None or kuse[i] < 1:
                continue
            try:
                # through pos+k_i: the scan's CATCH-UP step writes the
                # last proposal's row there (see _build_draft_scan)
                self._d_grow(i, int(self._pos[i]) + int(kuse[i]))
            except PoolExhausted:
                # draft-pool pressure (reachable only with an
                # undersized explicit SpecConfig.num_pages): this slot
                # rides the round as a plain qlen=1 row
                self._d_release(i)
                kuse[i] = 0
                continue
            if not self._d_valid[i]:
                backfill.append(i)
        if backfill:
            self._spec_backfill(backfill)
        return self._spec_scan(kuse), kuse

    def _d_grow(self, slot: int, last_pos: int):
        """Allocate draft pages until the slot's draft block table
        covers writes through position `last_pos`."""
        while self._d_next_idx[slot] * self.page_size <= last_pos:
            if not self._d_free:
                raise PoolExhausted(
                    f"draft page pool exhausted "
                    f"({self._d_num_pages - 1} usable pages)")
            page = self._d_free.pop()
            self._d_slot_pages[slot].append(page)
            self._d_bt[slot, self._d_next_idx[slot]] = page
            self._d_next_idx[slot] += 1

    def _d_release(self, slot: int):
        """Return a slot's draft pages and trash-route its draft block
        table — draft pages are exclusively owned, so release is a
        plain free (no refcounts to settle)."""
        self._d_free.extend(self._d_slot_pages[slot])
        self._d_slot_pages[slot] = []
        self._d_bt[slot] = 0
        self._d_next_idx[slot] = 0
        self._d_valid[slot] = False

    def _spec_backfill(self, slots: List[int]):
        """Rebuild dropped draft caches: prefill each slot's current
        stream minus its pending last token (exactly the rows the
        next draft scan will attend) through the DRAFT-model ragged
        program, packed like any admission batch and chunked by
        `prefill_chunk` when set. This is the 'draft cache rebuilt on
        the target replica' half of the migration contract — the
        other half being `_release_slot`'s drop."""
        entries = []
        for i in slots:
            r = self._slot_req[i]
            stream = self._effective_prompt(r)
            entries.append({"slot": i, "req": r,
                            "tokens": stream[:-1], "offset": 0})
        for batch in self._ragged_batches(entries):
            self._dispatch_draft_prefill(batch)
        for i in slots:
            self._d_valid[i] = True

    def _dispatch_draft_prefill(self, batch):
        from ..ops.ragged_paged_attention import pack_ragged_batch
        bq = self._ragged_block_q
        grid = -(-self.pad // bq) * bq
        pk = pack_ragged_batch(
            [{"seq": p["slot"], "tokens": p["tokens"],
              "offset": p["offset"]} for p in batch],
            self.B, block_q=bq, pad_to=grid)
        bound = self._pages_bound(
            int(pk["context_len"][p["slot"]]) for p in batch)
        jit = self._get_draft_prefill(pk["t_pad"], bound)
        with self._tp_scope():
            _, self._d_kv = jit(
                self._d_pv(), self._d_bv(),
                self._d_kv, jnp.asarray(pk["ids"]),
                jnp.asarray(pk["token_seq"]),
                jnp.asarray(pk["positions"]),
                jnp.asarray(pk["query_start"]),
                jnp.asarray(pk["query_len"]),
                jnp.asarray(pk["context_len"]),
                jnp.asarray(self._d_bt),
                jnp.asarray(pk["sample_rows"]),
                self._spec_key)

    def _get_draft_prefill(self, t_pad: int, pages_bound: int):
        return self._jit_lru(
            self._d_prefill_jits, (t_pad, pages_bound),
            lambda: self._build_ragged_step(self._ragged_block_q,
                                            pages_bound, draft=True),
            family="draft")

    def _spec_scan(self, kuse) -> np.ndarray:
        """K greedy draft tokens for every live slot in ONE dispatch:
        a `lax.scan` of (single-token draft forward -> argmax -> feed
        forward) over the draft's paged cache — no host round trips
        between draft steps, which is where the speculative win over
        k+1 plain decode dispatches comes from."""
        if self._d_scan_jit is None:
            self._d_scan_jit = self._jit_singleton(
                "draft_scan", self._build_draft_scan)
        live = np.array([r is not None and kuse[i] >= 1
                         and bool(self._d_valid[i])
                         for i, r in enumerate(self._slot_req)])
        if not live.any():
            return np.zeros((self.B, self._spec_k), np.int32)
        with self._tp_scope():
            props, self._d_kv = self._d_scan_jit(
                self._d_pv(), self._d_bv(),
                self._d_kv, jnp.asarray(self._tok),
                jnp.asarray(self._pos.astype(np.int32)),
                jnp.asarray(live), jnp.asarray(self._d_bt))
        return np.asarray(props)

    def _build_draft_scan(self):
        """The fused draft loop: K+1 single-token draft steps as one
        compiled scan. Each step feeds the previous argmax at the
        next position through the draft's ragged view (block_q=1, the
        decode shape); dead rows (inactive slots, positions past the
        cache) carry qlen=0 — attention returns zero and their KV
        scatter trash-routes. The K+1-th step is the DRAFT CATCH-UP
        from `speculative.py`'s loop: K steps alone never feed the
        last proposal d_K, so a full-accept round would leave a HOLE
        at pos+K that the next round's draft attends as garbage
        (observed there as self-draft acceptance 0.67 instead of 1.0;
        reproduced here the same way before this step existed). Its
        sampled token is discarded — only the KV row matters."""
        model = self._spec.draft_model
        params, buffers = self._d_params, self._d_buffers
        K, B, S = self._spec_k, self.B, self.S

        view_tp = self._view_tp(draft=True)
        qkv = bool(self._qkv)

        def run(pv, bv, kv, tok, pos0, live, bt):
            from .generation import bind_state
            from .llama import RaggedKVCacheView
            with bind_state(params, buffers, pv, bv), no_grad():
                bidx = jnp.arange(B, dtype=jnp.int32)

                def body(carry, step):
                    kv, tok = carry
                    ok = live & (pos0 + step <= S - 1)
                    posv = jnp.minimum(pos0 + step, S - 1)
                    seq = jnp.where(ok, bidx, -1)
                    qlen = ok.astype(jnp.int32)
                    views = [RaggedKVCacheView(
                        e[0], e[1], bt, seq, posv, bidx, qlen,
                        posv + 1, 1, tp=view_tp,
                        k_scale=e[2] if qkv else None,
                        v_scale=e[3] if qkv else None) for e in kv]
                    logits, new = model.forward(
                        Tensor(tok[None]), past_key_values=views,
                        use_cache=True)
                    # greedy proposals: argmax over f32 logits, the
                    # same reduction _sample_token's greedy arm runs
                    nxt = jnp.argmax(
                        logits._value[0].astype(jnp.float32),
                        -1).astype(jnp.int32)
                    new_kv = [
                        (v.k_pages._value, v.v_pages._value,
                         v.k_scale._value, v.v_scale._value) if qkv
                        else (v.k_pages._value, v.v_pages._value)
                        for v in new]
                    return (new_kv, nxt), nxt

                (kv, _), props = jax.lax.scan(
                    body, (kv, tok), jnp.arange(K + 1, dtype=jnp.int32))
                return jnp.transpose(props[:K]), kv   # (B, K)

        return jax.jit(run, donate_argnums=(2,))

    def _spec_verify(self, active, props, kuse, finished):
        """The verify half: ONE batched target dispatch over packed
        per-slot rows ``[last_token, d_1..d_{k_i}]`` at positions
        ``pos..pos+k_i`` (context_len = pos+k_i+1 — exactly the
        chunk-continuation descriptor shape), greedy acceptance via
        the shared `spec_accept_greedy` core, commit + rewind. The
        emitted tokens are the TARGET's greedy choices at every
        position, so the stream is bit-identical to plain decode for
        any draft. Returns (emitted, proposed, accepted) counts."""
        from ..ops.ragged_paged_attention import pack_ragged_batch
        from .speculative import spec_accept_greedy
        K = self._spec_k
        pieces = []
        for i in active:
            ki = int(kuse[i])
            toks = [int(self._tok[i])] + [int(t) for t in
                                          props[i, :ki]]
            pieces.append({"seq": i, "tokens": toks,
                           "offset": int(self._pos[i])})
        bq = self._verify_block_q
        pk = pack_ragged_batch(pieces, self.B, block_q=bq, pad_to=bq)
        bound = self._pages_bound(
            int(pk["context_len"][i]) for i in active)
        rids = ([self._slot_req[i].request_id for i in active]
                if telemetry.enabled() else ())
        with telemetry.span("serving.verify", slots=len(active),
                            tokens=int(pk["tokens"]), rids=rids):
            fault_point("speculative.verify")
            # pdt-lint: disable=PDT001 real dispatch+D2H wall time
            # (pdt_spec_verify_seconds) — same contract as decode_step
            t0 = time.perf_counter()
            jit = self._get_spec_verify(pk["t_pad"], bound)
            with self._tp_scope():
                g_all, self._kv = jit(
                    self._pv(), self._bv(),
                    self._kv, jnp.asarray(pk["ids"]),
                    jnp.asarray(pk["token_seq"]),
                    jnp.asarray(pk["positions"]),
                    jnp.asarray(pk["query_start"]),
                    jnp.asarray(pk["query_len"]),
                    jnp.asarray(pk["context_len"]),
                    jnp.asarray(self._bt),
                    jnp.asarray(pk["sample_rows"]),
                    self._spec_key)
            g_all = np.asarray(g_all)
            # pdt-lint: disable=PDT001 same real-wall measurement
            vdt = time.perf_counter() - t0
        if telemetry.enabled():
            _M_SPEC_VERIFY_SECONDS.observe(vdt)
        # ragged acceptance through the ONE shared core: pad each
        # slot's row with sentinels that can never match, so `j` caps
        # at the slot's real proposal count
        n = len(active)
        gm = np.full((n, K + 1), -2, np.int32)
        pm = np.full((n, K), -1, np.int32)
        for idx, i in enumerate(active):
            r0, ki = int(pk["query_start"][i]), int(kuse[i])
            gm[idx, :ki + 1] = g_all[r0:r0 + ki + 1]
            pm[idx, :ki] = props[i, :ki]
        j_arr = np.asarray(spec_accept_greedy(gm, pm)[0])
        self._corrupt_kv_site()
        emitted = proposed = accepted = 0
        committed: List[int] = []
        for idx, i in enumerate(active):
            r = self._slot_req[i]
            ki, j = int(kuse[i]), int(j_arr[idx])
            toks = [int(t) for t in gm[idx, :j + 1]]
            if self.eos is not None and self.eos in toks:
                toks = toks[:toks.index(self.eos) + 1]
            r.output.extend(toks)
            # the rewind: context advances by what was COMMITTED; the
            # scattered rows past it are stale garbage no causal mask
            # can admit before the next round's scatter overwrites
            # them (page frontiers stay — the pages are owned and the
            # very next round writes into them)
            self._pos[i] += len(toks)
            self._tok[i] = toks[-1]
            proposed += ki
            accepted += j
            emitted += len(toks)
            committed.extend(toks)
            if (self.eos is not None and toks[-1] == self.eos) \
                    or len(r.output) >= r.max_new_tokens \
                    or int(self._pos[i]) >= self.S - 1:
                self._finalize(r, RequestStatus.FINISHED, None,
                               finished)
                self._release_slot(i)
        if self._sentry is not None and committed:
            self._sentry.observe_tokens(np.asarray(committed, np.int32))
        return emitted, proposed, accepted

    def _get_spec_verify(self, t_pad: int, pages_bound: int):
        return self._jit_lru(
            self._verify_jits, (t_pad, pages_bound),
            lambda: self._build_ragged_step(self._verify_block_q,
                                            pages_bound,
                                            select_rows=False),
            family="verify")

    @property
    def spec_enabled(self) -> bool:
        return self._spec is not None

    def spec_info(self) -> Dict[str, float]:
        """Speculation counters (zeros on non-spec engines) — the
        fleet router aggregates these across replicas, folding in
        counters from engines a replica has already discarded."""
        return {"rounds": self.num_spec_rounds,
                "proposed": self.num_spec_proposed,
                "accepted": self.num_spec_accepted,
                "degraded": self.num_spec_degraded,
                "acceptance_rate": self.num_spec_accepted
                / max(self.num_spec_proposed, 1)}
