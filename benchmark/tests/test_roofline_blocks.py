"""`roofline_blocks.py` against ISSUE 31's arithmetic for
`sdar-30b-a3b-chat`, and the reference's transfer rule by hand. (The
cell's `--dry` run with `compiles_in_window` 0 is tier-1's:
`tests/test_benchmark_dry.py` runs every cell of `BENCHMARK.json`.)"""
import json
import os

import numpy as np

from benchmark import roofline_blocks
from benchmark.reference import sdar_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def test_parameters_by_part():
    m = _sizes()
    p = roofline_blocks.params_by_part(m)
    assert p["attention"] == 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert p["router"] == 2048 * 128 and p["norms"] == 2 * 2048 + 2 * 128
    assert p["experts"] == 128 * 3 * 2048 * 768
    assert round(2 * p["layer"] / 1e9, 3) == 1.246
    assert round(2 * (p["embedding"] + p["head"]) / 1e9, 3) == 1.245
    assert round(2 * p["total"] / 1e9, 2) == 8.72
    # the published depth: 30.5 B parameters
    whole = roofline_blocks.params_by_part(dict(m, num_hidden_layers=48))
    assert round(whole["total"] / 1e9, 1) == 30.5


def test_a_pass_reads_its_experts_mostly():
    m = _sizes()
    total = roofline_blocks.pass_bytes(m, 64 * 1000, 64, 6 * 128)
    experts = 6 * 128 * roofline_blocks.expert_bytes(m)
    assert round(experts / 1e9, 2) == 7.25 and round(total / 1e9, 1) == 8.9
    # K and V: 12 KiB a token; 64 slots x 4096 tokens are 3.22 GB
    assert round(64 * 4096 * roofline_blocks.kv_bytes_per_token(m)
                 / 1e9, 2) == 3.22
    # fewer experts hit, fewer bytes; nothing to read, no number
    assert roofline_blocks.pass_bytes(m, 64000, 64, 700) < total
    assert roofline_blocks.hits_a_pass(m, 0.0, 0.0, 10) is None


def test_transfer_rule_by_hand():
    m = {"block_length": 4, "denoising_steps": 2,
         "remasking": "low_confidence_static", "threshold": 0.5}
    lg = np.full((4, 8), -4.0)
    lg[0, 3], lg[1, 5], lg[2, 6], lg[3, 2] = 2.0, 5.0, 3.0, 5.0
    ids, masked = sdar_moe.transfer(lg, [7, 9, 9, 9],
                                    [False, True, True, True], 0, m)
    # two a pass, highest confidence first, the tie to the lower position
    assert ids == [7, 5, 9, 2] and masked == [False, False, True, False]
    dyn = dict(m, remasking="low_confidence_dynamic", threshold=0.995)
    ids, masked = sdar_moe.transfer(lg, [9, 9, 9, 9], [True] * 4, 0, dyn)
    assert ids == [9, 5, 9, 2] and masked == [True, False, True, False]
    none = dict(dyn, threshold=0.9999)       # none passes: the best one
    ids, masked = sdar_moe.transfer(lg, [9, 9, 9, 9], [True] * 4, 0, none)
    assert ids == [9, 5, 9, 9] and masked == [True, False, True, True]
