"""What a servable model tells the engine about its layers (ROADMAP D3).

`model.cache_spec()` returns one entry a layer, in layer order: what the
layer keeps between the tokens of a sequence.

* `KVSpec(num_kv_heads, head_dim, window=None)`: keys and values of
  every context token, or with `window` of the last `window` positions
  only (a query at position p attends keys p - window < k <= p). The
  engine gives the layer a pair of page pools and hands it a
  `llama.RaggedKVCacheView` over them and a block table. Layers of one
  `KVSpec` form a PAGE GROUP: one block table, one free list, one
  reservation, and in a window group the pages that slide below the
  window go back to the free list after every dispatch.
* `SharedKVSpec(source_layer)`: the layer keeps nothing and READS the
  keys and values layer `source_layer` (an earlier `KVSpec` layer)
  stored. It owns no pool and is handed None: the forward pass gives
  it the view `source_layer` returned in this same dispatch, and it
  attends without writing (`llama.ragged_write_attend` with no k, v).
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

`model.rows_leave_after()` (optional) names the last layer that KEEPS
anything. Behind it a packed row that samples nothing is work thrown
away, so an admission program hands the forward pass `sample_rows`
(one packed row a slot, a row count or more where the slot samples
nothing) and the forward pass carries only those rows past that layer:
it returns logits `(1, slots, vocab)`, a sampled row's equal to what
the whole batch would have given it. A decode step samples every row
and passes no `sample_rows`.

`model.generation_spec()` says how generation PROCEEDS, the way
`cache_spec()` says what a layer keeps. Absent or None: one token a
sequence a step, each from the logits of the token before it. A
`BlockDiffusionSpec`: by diffusion over blocks (its docstring). The
engine reads it once, at construction.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["KVSpec", "SharedKVSpec", "StateSpec", "ReportSpec",
           "RaggedStateView", "BlockDiffusionSpec", "conv_inputs"]


class KVSpec(NamedTuple):
    num_kv_heads: int
    head_dim: int
    window: Optional[int] = None


class SharedKVSpec(NamedTuple):
    source_layer: int


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


class BlockDiffusionSpec(NamedTuple):
    """Generation by diffusion over blocks. Positions are cut into
    blocks of `block_length` from position 0; attention is causal over
    blocks and full inside one (`ragged_paged_attention`'s
    ``diffusion_block``). A block in flight holds its given tokens and
    `mask_token_id` elsewhere; a PASS runs the model over the block's
    rows against the stored context (row `i` predicts position `i`'s own
    token) and `transfer` decides some of the masked positions. A block
    that holds no mask takes one more pass, which leaves the keys and
    values later blocks read, and the next block starts.

    `remasking`: ``low_confidence_static`` decides the `block_length /
    denoising_steps` masked positions of highest confidence a pass (the
    remainder of the division goes to the first passes);
    ``low_confidence_dynamic`` decides every masked position whose
    confidence passes `threshold`, and the most confident one if none
    does."""
    block_length: int
    mask_token_id: int
    denoising_steps: int
    remasking: str = "low_confidence_static"
    threshold: float = 0.9

    def check(self) -> None:
        if self.remasking not in ("low_confidence_static",
                                  "low_confidence_dynamic"):
            raise ValueError(f"remasking {self.remasking!r}: "
                             "low_confidence_static|low_confidence_dynamic")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} outside "
                f"[1, block_length {self.block_length}]")

    def transfer(self, logits, ids, masked, passes):
        """The transfer rule, inside the step program: a pure function
        of a pass's logits (slots, block, vocab), the dispatched blocks'
        `ids` and `masked` flags (slots, block) and the denoising passes
        each slot's block has had (slots,). Greedy: a masked position's
        candidate is its argmax, its confidence the candidate's softmax
        probability; ties go to the lower position. Returns the next
        blocks' (ids, masked). A block without a mask comes back as it
        was."""
        b = self.block_length
        lg = logits.astype(jnp.float32)
        x0 = jnp.argmax(lg, axis=-1).astype(ids.dtype)
        conf = jnp.exp(jnp.max(lg, axis=-1)
                       - jax.nn.logsumexp(lg, axis=-1))
        conf = jnp.where(masked, conf, -jnp.inf)
        # rank 0 = the most confident masked position of the block
        order = jnp.argsort(-conf, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        if self.remasking == "low_confidence_static":
            base, rem = divmod(b, self.denoising_steps)
            n = base + (passes < rem).astype(jnp.int32)
            take = masked & (rank < n[:, None])
        else:
            take = masked & ((conf > self.threshold) | (rank == 0))
        return jnp.where(take, x0, ids), masked & ~take


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


def conv_inputs(xbc, tail, seq, idx, fresh, qstart, qlen):
    """A causal depthwise convolution's inputs over a packed batch of
    pieces (what every state layer with a convolution in front of its
    scan needs): the K-1 rows before each packed row in ITS sequence's
    stream (`prev[k-1]` is the row k back): earlier rows of the piece,
    then the slot's stored tail, zeros for a piece that starts its
    sequence. And the tails to store: the last K-1 rows of every slot's
    stream."""
    t = xbc.shape[0]
    k1 = tail.shape[1]                                    # K - 1
    seq_c = jnp.maximum(seq, 0)
    tail = jnp.where(fresh[:, None, None], 0, tail)
    prev = []
    for k in range(1, k1 + 1):
        stored = tail[seq_c, jnp.clip(k1 - k + idx, 0, k1 - 1)]
        prev.append(jnp.where((idx >= k)[:, None],
                              jnp.roll(xbc, k, axis=0), stored))
    # stream = [tail ; piece]; the new tail is stream[qlen : qlen + K-1]
    j = qlen[:, None] + jnp.arange(k1)[None, :]           # (S, K-1)
    piece = xbc[jnp.clip(qstart[:, None] + j - k1, 0, t - 1)]
    old = jnp.take_along_axis(tail, jnp.clip(j, 0, k1 - 1)[..., None], 1)
    return prev, jnp.where((j >= k1)[..., None], piece, old)
