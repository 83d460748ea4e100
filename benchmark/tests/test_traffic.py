import json
import os
from collections import Counter

import pytest

from benchmark.traffic import Traffic, lognormal_block, scaled

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["internlm2-1_8b.batch", "mistral-7b-v0_3.chat"]


def _spec(name, schedule_seed=None):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           name + ".json")) as f:
        spec = json.load(f)["traffic"]
    # the cells fix their schedule; most tests here want the seed's
    spec["schedule_seed"] = schedule_seed
    return spec


def _take(spec, seed, n, vocab=1000):
    t = Traffic(spec, seed, vocab)
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("cell", CELLS)
def test_replays_bit_for_bit(cell):
    spec = _spec(cell)
    assert _take(spec, 2**31 + 77, 150) == _take(spec, 2**31 + 77, 150)
    assert _take(spec, 1, 150) != _take(spec, 2, 150)


@pytest.mark.parametrize("cell", CELLS)
def test_clamps_and_vocab(cell):
    spec = _spec(cell)
    p, o = spec["prompt_tokens"], spec["output_tokens"]
    for gap, prompt, new in _take(spec, 5, 200, vocab=50):
        assert p["min"] <= len(prompt) <= p["max"]
        assert o["min"] <= new <= o["max"]
        assert all(1 <= t < 50 for t in prompt)
        assert (gap is None) == (spec["arrivals"]["process"]
                                 == "closed_loop")


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_gets_the_same_sizes(cell):
    """A block holds the same multiset of lengths (and gaps) whatever
    the seed: seeds change the order and the content, not the work."""
    spec = _spec(cell)
    n = spec["block"]

    def sizes(seed):
        got = _take(spec, seed, 2 * n)
        return (Counter(len(p) for _, p, _ in got[:n]),
                Counter(o for _, _, o in got[:n]),
                sorted(round(g, 9) for g, _, _ in got[:n]
                       if g is not None))

    assert sizes(3) == sizes(2**31 + 5)
    assert Counter(len(p) for _, p, _ in _take(spec, 3, n)) \
        == Counter(lognormal_block(spec["prompt_tokens"], n))


def test_poisson_rate_is_the_files():
    spec = _spec("mistral-7b-v0_3.chat")
    n = spec["block"] * 4
    total = sum(g for g, _, _ in _take(spec, 9, n))
    assert n / total == pytest.approx(spec["arrivals"]["rate_per_s"],
                                      rel=0.02)


def test_bursts_and_shared_prefixes_are_data():
    spec = dict(_spec("mistral-7b-v0_3.chat"))
    spec["arrivals"] = dict(spec["arrivals"], burst={
        "start_prob": 0.2, "mean_s": 3.0, "multiplier": 4.0})
    spec["shared_prefix"] = {"pool": 2, "tokens": 48, "share_prob": 0.5}
    got = _take(spec, 4, 256)
    assert got == _take(spec, 4, 256)
    plain = sum(g for g, _, _ in _take(_spec("mistral-7b-v0_3.chat"), 4,
                                       256))
    assert sum(g for g, _, _ in got) < plain        # bursts are faster
    heads = Counter(tuple(p[:48]) for _, p, _ in got)
    shared = [h for h, c in heads.items() if c > 10]
    assert len(shared) == 2
    # the lengths and the gaps of the plain mix did not move: the extra
    # draws come from a generator of their own
    assert [o for _, _, o in got] \
        == [o for _, _, o in _take(_spec("mistral-7b-v0_3.chat"), 4, 256)]


def test_scaled_keeps_shape():
    spec = _spec("internlm2-1_8b.batch")
    small = scaled(spec, 8)
    assert small["prompt_tokens"]["max"] == spec["prompt_tokens"]["max"] // 8
    assert small["arrivals"] == spec["arrivals"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_fixed_schedule_leaves_the_seed_the_content(cell):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           cell + ".json")) as f:
        spec = json.load(f)["traffic"]
    assert spec["schedule_seed"] is not None      # both cells fix it
    a, b = _take(spec, 1, 100), _take(spec, 2**31 + 2, 100)
    assert [(g, len(p), o) for g, p, o in a] \
        == [(g, len(p), o) for g, p, o in b]
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    other = _take(dict(spec, schedule_seed=spec["schedule_seed"] + 1), 1,
                  100)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in other]
