"""What a servable model tells the engine about its layers (ROADMAP D3).

`model.cache_spec()` returns one entry a layer, in layer order: what the
layer keeps between the tokens of a sequence.

* `KVSpec(num_kv_heads, head_dim)`: keys and values of every context
  token. The engine gives the layer a pair of page pools and hands it a
  `llama.RaggedKVCacheView` over them and the block table.
* `StateSpec(shapes, dtypes)`: arrays of FIXED size a sequence, however
  long it is (a recurrent state). The engine allocates one
  `(slots, *shape)` array per entry, indexed by SLOT, donates them
  through the step like the pools, and hands the layer a
  `RaggedStateView`.
* `ReportSpec(counters, row_record)`: the layer keeps nothing and is
  handed nothing, and it REPORTS on every dispatch: an int32 vector of
  counts, entry `i` to be added to the telemetry counter
  `counters[i] = (counter, kind)`, and one int32 record a packed row
  of shape `row_record` (an expert layer's chosen experts). The step
  program returns both beside the tokens; the engine knows neither's
  meaning. It pulls the counts with telemetry on and adds them; it
  pulls the records of a dispatch's live rows only for a sentry that
  takes them (`observe_layer_rows(slots, positions, records)`: the
  benchmark's choice-forced logits check).
* `None`: the layer keeps nothing, is handed nothing, reports nothing.

The forward pass takes the views as `past_key_values` (one a layer) and
returns, in the same order, a layer's new view, a reporting layer's
`(counts, records)`, or None.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax.numpy as jnp

__all__ = ["KVSpec", "StateSpec", "ReportSpec", "RaggedStateView"]


class KVSpec(NamedTuple):
    num_kv_heads: int
    head_dim: int


class StateSpec(NamedTuple):
    shapes: Tuple[Tuple[int, ...], ...]       # one a state array, per slot
    dtypes: Tuple[str, ...]

    def nbytes(self) -> int:
        """Bytes one slot's state takes in this layer."""
        return sum(math.prod(shape) * jnp.dtype(dt).itemsize
                   for shape, dt in zip(self.shapes, self.dtypes))


class ReportSpec(NamedTuple):
    counters: Tuple[Tuple[object, str], ...]  # (telemetry counter, kind)
    row_record: Tuple[int, ...]               # int32, one a packed row


class RaggedStateView:
    """`past_key_value` of a state layer on the ragged serving path: the
    layer's `(slots, ...)` state arrays and the descriptors of ONE
    packed batch, as `llama.RaggedKVCacheView` carries them. A sequence
    IS its slot: `token_seq[t]` is the slot of packed row `t` (-1 on
    padding rows), `query_start` / `query_len` / `context_lens` are per
    slot. A slot with `query_len` 0 is not in the batch and its state
    must come back unchanged; a piece with `context_lens == query_len`
    starts its sequence and starts from ZERO state whatever the slot
    held (so a recycled slot needs no reset); any other piece continues
    from the slot's stored state. `one_token` (static) says the batch
    has the decode shape: row `i` is slot `i`'s single new token."""

    def __init__(self, arrays, token_seq, query_start, query_len,
                 context_lens, one_token: bool = False):
        self.arrays = tuple(arrays)
        self.token_seq = jnp.asarray(token_seq, jnp.int32)
        self.query_start = jnp.asarray(query_start, jnp.int32)
        self.query_len = jnp.asarray(query_len, jnp.int32)
        self.context_lens = jnp.asarray(context_lens, jnp.int32)
        self.one_token = bool(one_token)

    def replace(self, arrays) -> "RaggedStateView":
        return RaggedStateView(arrays, self.token_seq, self.query_start,
                               self.query_len, self.context_lens,
                               self.one_token)

    @staticmethod
    def fresh(spec: StateSpec, batch: int, seq_len: int):
        """The view of `batch` whole sequences of `seq_len` tokens packed
        one after another with nothing stored: the forward pass without
        a cache."""
        arrays = [jnp.zeros((batch,) + tuple(s), d)
                  for s, d in zip(spec.shapes, spec.dtypes)]
        lens = jnp.full((batch,), seq_len, jnp.int32)
        return RaggedStateView(
            arrays, jnp.repeat(jnp.arange(batch, dtype=jnp.int32), seq_len),
            jnp.arange(batch, dtype=jnp.int32) * seq_len, lens, lens)
