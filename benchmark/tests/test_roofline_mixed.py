"""`roofline_mixed.py` and the plain reference's rotary tables for
`mellum2-12b-a2_5b-instruct` against the cut's arithmetic. (The
parameter counts of the cut and the readers on a hand-made window are
tier-1's: `tests/test_mellum.py`; the cell's `--dry` run is
`tests/test_benchmark_dry.py`'s.)"""
import json
import math
import os

import numpy as np

from benchmark import roofline_flash, roofline_mixed
from benchmark.reference import mellum

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2_5b-instruct.json")) as f:
        return json.load(f)


def test_a_kv_row_is_of_the_models_own_head():
    """128-wide heads: 2 x 4 x 128 x 2 B a token a layer; the head
    taken as hidden / heads (72) would count 44 % too few bytes."""
    m = _sizes()
    assert roofline_mixed.kv_row_bytes(m) == 2048
    assert roofline_flash.kv_row_bytes(m) == 1152
    # the full group (2 layers) is 4096 B a token: 24576 pages of 16
    # tokens are 1.61 GB
    assert round(24576 * 16 * 2 * roofline_mixed.kv_row_bytes(m) / 1e9,
                 2) == 1.61


def test_a_decode_step_reads_its_experts_mostly():
    m = _sizes()
    rows = 64 * (6 * 1024 + 2 * 3300)
    every = roofline_mixed.decode_step_bytes(m, rows, 64, 8 * 64)
    fewer = roofline_mixed.decode_step_bytes(m, rows, 64, 400)
    assert fewer < every and round(every / 1e9, 1) == 8.8
    # with no expert hit and no row read: the weights outside the
    # experts, the head among them (1.2 GB)
    bare = roofline_mixed.decode_step_bytes(m, 0, 0, 0)
    assert round(bare / 1e9, 2) == 0.80


def test_the_references_yarn_by_the_formula():
    """Edges 18.08 -> 18 and 34.98 -> 35 at the published numbers; the
    table at position 0 is the attention factor, and at position p a
    pair past the ramp turns at theta^(-2i/d) / 16."""
    full = _sizes()["rope_parameters"]["full_attention"]

    def dim(turns):
        return 128 * math.log(8192 / (turns * 2 * math.pi)) \
            / (2 * math.log(500000))
    assert 18 < dim(32) < 18.1 and 34.9 < dim(1) < 35
    assert mellum.yarn_edges(128, full) == (18, 35)
    cos, sin = mellum.rope_tables(128, 3000, full)
    assert np.allclose(np.asarray(cos)[0], 0.1 * math.log(16) + 1)
    inv = 500000 ** (-2 * 50 / 128) / 16
    assert abs(float(cos[2999, 50]) - 1.2772588722239782
               * math.cos(2999 * inv)) < 1e-5
    plain = _sizes()["rope_parameters"]["sliding_attention"]
    cos, _ = mellum.rope_tables(128, 3000, plain)
    assert abs(float(cos[2999, 3]) - math.cos(
        2999 * 500000 ** (-6 / 128))) < 1e-5
