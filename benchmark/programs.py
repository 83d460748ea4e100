"""Which admission programs a cell's traffic can reach, and requests
that reach each of them once.

The engine keys its admission programs on `(t_pad, bound)`: the packed
row count rounded up to `prompt_pad`, and the power-of-two bucket of the
dispatch's largest context in pages (`ContinuousBatchingEngine.
_dispatch_ragged`, `_pages_bound`; `ops.ragged_paged_attention.
pack_ragged_batch`). A key met for the first time inside the measured
window would trace, lower and load a program there. So set-up drives
every reachable key once. `dispatch_keys` repeats the engine's
arithmetic for a set of prompts submitted together to an idle engine
(`benchmark/tests/test_programs.py` holds it to the engine's own
functions); `warmup_sets` searches small sets of prompt lengths inside
the traffic's clamps for one that reaches each key.
"""
from __future__ import annotations

BLOCK_Q = 8          # the engine's `_ragged_block_q`


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def pages_bound(contexts, page_size: int, pps: int) -> int:
    need = max(-(-int(c) // page_size) for c in contexts)
    return min(1 << max(need - 1, 0).bit_length(), pps)


def dispatch_keys(prompt_lens, *, prefill_chunk, prompt_pad: int,
                  page_size: int, max_seq_len: int) -> list:
    """`[(t_pad, bound), ...]`, one per dispatch, for prompts admitted
    together with nothing cached: the split of `_ragged_batches`, the
    row count of `pack_ragged_batch`, the bound of `_pages_bound`."""
    pps = -(-max_seq_len // page_size)
    grid = _ceil_to(prompt_pad, BLOCK_Q)
    batches, cur, cur_tok = [], [], 0
    for n in prompt_lens:
        left, off = int(n), 0
        while left:
            if prefill_chunk is not None and cur_tok >= prefill_chunk:
                batches.append(cur)
                cur, cur_tok = [], 0
            take = left if prefill_chunk is None \
                else min(left, prefill_chunk - cur_tok)
            cur.append((take, off + take))        # (rows, context)
            left -= take
            off += take
            cur_tok += take
    if cur:
        batches.append(cur)
    keys = []
    for b in batches:
        rows = sum(_ceil_to(take, BLOCK_Q) for take, _ in b)
        keys.append((_ceil_to(max(rows, 1), grid),
                     pages_bound((ctx for _, ctx in b), page_size, pps)))
    return keys


def _candidates(lo: int, hi: int, chunk):
    """Small sets of prompt lengths inside [lo, hi]: one prompt; some
    equal prompts that fill a chunk with ragged ends; a prompt whose
    last piece shares a dispatch with the head of a long one."""
    span = sorted({lo, hi, *range(lo, hi + 1, 8), *range(lo + 1, hi + 1, 16),
                   *range(lo + 3, hi + 1, 32)})
    for n in span:
        yield (n,)
    if chunk is None:
        return
    for k in range(2, chunk // lo + 1):
        top = chunk // k              # k prompts of `top` fill the chunk
        for n in range(top, max(lo - 1, top - BLOCK_Q), -1):
            yield (n,) * k
    filler = min(hi, max(lo, chunk))
    for n in span:
        yield (n, filler)


def warmup_sets(*, prompt_min: int, prompt_max: int, prefill_chunk,
                prompt_pad: int, page_size: int, max_seq_len: int) -> dict:
    """`{(t_pad, bound): (prompt lengths...)}`: for every key that some
    candidate set reaches, the first (smallest) set whose dispatches
    include it. Keys no candidate reaches are taken as unreachable; the
    window's `compiles_in_window` says if one was met after all."""
    kw = dict(prefill_chunk=prefill_chunk, prompt_pad=prompt_pad,
              page_size=page_size, max_seq_len=max_seq_len)
    found = {}
    for lens in _candidates(prompt_min, prompt_max, prefill_chunk):
        for key in dispatch_keys(lens, **kw):
            found.setdefault(key, lens)
    return found


def minimal_cover(sets: dict, **kw) -> list:
    """A few of the found sets, chosen greedily, that between them
    reach every key (a long prompt's chunks reach several). `kw` are
    `dispatch_keys`' settings."""
    todo, chosen = set(sets), []
    pool = sorted(set(sets.values()), key=lambda s: (-sum(s), s))
    reach = {s: set(dispatch_keys(s, **kw)) & todo for s in pool}
    while todo:
        best = max(pool, key=lambda s: (len(reach[s] & todo), -sum(s)))
        gain = reach[best] & todo
        if not gain:
            break
        chosen.append(best)
        todo -= gain
    return chosen
