"""`benchmark/programs.py` repeats the engine's admission arithmetic;
hold it to the engine's own functions on the two cells' settings."""
import json
import os

import numpy as np
import pytest

from benchmark import programs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["internlm2-1_8b.batch", "mistral-7b-v0_3.chat"]


def _cell(name):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           name + ".json")) as f:
        return json.load(f)


def _kw(cell):
    e = cell["engine"]
    return dict(prefill_chunk=e["prefill_chunk"], prompt_pad=e["prompt_pad"],
                page_size=16, max_seq_len=e["max_seq_len"])


@pytest.fixture(scope="module")
def tiny_model():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=2048)
    return LlamaForCausalLM(cfg)


def _engine_keys(eng, lens):
    """The keys the engine itself would build for prompts admitted
    together: its own split, its own packer, its own bound."""
    from paddle_tpu.ops.ragged_paged_attention import pack_ragged_batch
    entries = [{"slot": i, "req": None, "tokens": [1] * n, "offset": 0}
               for i, n in enumerate(lens)]
    bq = eng._ragged_block_q
    grid = -(-eng.pad // bq) * bq
    keys = []
    for batch in eng._ragged_batches(entries):
        pk = pack_ragged_batch(
            [{"seq": p["slot"], "tokens": p["tokens"],
              "offset": p["offset"], "sample": p["sample"]} for p in batch],
            eng.B, block_q=bq, pad_to=grid)
        keys.append((int(pk["t_pad"]), eng._pages_bound(
            int(pk["context_len"][p["slot"]]) for p in batch)))
    return keys


@pytest.mark.parametrize("name", CELLS)
def test_dispatch_keys_are_the_engines(name, tiny_model):
    from paddle_tpu.models.serving import ContinuousBatchingEngine
    cell = _cell(name)
    kw = _kw(cell)
    eng = ContinuousBatchingEngine(
        tiny_model, max_batch_size=8, max_seq_len=kw["max_seq_len"],
        prefill_chunk=kw["prefill_chunk"], prompt_pad=kw["prompt_pad"])
    assert programs.BLOCK_Q == eng._ragged_block_q
    t = cell["traffic"]["prompt_tokens"]
    rng = np.random.default_rng(0)
    sets = list(programs.warmup_sets(prompt_min=t["min"],
                                     prompt_max=t["max"], **kw).values())
    sets += [tuple(int(x) for x in rng.integers(t["min"], t["max"] + 1, k))
             for k in (1, 2, 3, 5, 8) for _ in range(20)]
    for lens in sets:
        if len(lens) > eng.B:
            continue
        assert programs.dispatch_keys(lens, **kw) == _engine_keys(eng, lens)


@pytest.mark.parametrize("name,n_keys", [(CELLS[0], 9), (CELLS[1], 11)])
def test_every_found_key_is_reached_by_its_set(name, n_keys):
    cell = _cell(name)
    kw = _kw(cell)
    t = cell["traffic"]["prompt_tokens"]
    sets = programs.warmup_sets(prompt_min=t["min"], prompt_max=t["max"],
                                **kw)
    assert len(sets) == n_keys
    for key, lens in sets.items():
        assert all(t["min"] <= n <= t["max"] for n in lens)
        assert key in programs.dispatch_keys(lens, **kw)
    chosen = programs.minimal_cover(sets, **kw)
    reached = {k for lens in chosen
               for k in programs.dispatch_keys(lens, **kw)}
    assert set(sets) <= reached
    # what random traffic inside the clamps reaches is inside the found
    # set: the window then meets no new key
    rng = np.random.default_rng(1)
    for _ in range(300):
        lens = [int(x) for x in rng.integers(t["min"], t["max"] + 1,
                                             int(rng.integers(1, 9)))]
        assert set(programs.dispatch_keys(lens, **kw)) <= set(sets)
