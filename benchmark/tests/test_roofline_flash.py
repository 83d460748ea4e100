"""`roofline_flash.py` against ISSUE 35's arithmetic for
`phi-4-mini-flash-reasoning` and against the shapes of the program's
own skeleton model, and the two new readers on hand-made windows. (The
cell's `--dry` run is tier-1's: `tests/test_benchmark_dry.py` runs
every cell of `BENCHMARK.json`.)"""
import json
import os

import jax

from benchmark import roofline_flash, weights
from benchmark.readers import attn_kv_roofline, flash_decode_floor_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def test_parameters_by_layer_and_in_all():
    m = _sizes()
    p = roofline_flash.params_by_layer(m)
    assert p["mamba"] == 119_895_040 and p["window"] == 98_322_304
    assert p["gmu"] == 104_867_840 and p["cross"] == 91_766_144
    assert p["embedding"] == 512_163_840
    kinds = roofline_flash.kinds(m)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    total = roofline_flash.params_total(m)
    assert round(total / 1e9, 3) == 3.853
    assert round(2 * total / 1e9, 3) == 7.705


def test_the_counts_are_the_skeleton_models_own():
    """The program's model built under a trace at the published sizes
    (shapes, no values): its parameters a layer and in all are what the
    roofline reckons."""
    m = _sizes()
    cfg, model_class = weights.model_config(m["program"], m)
    box = {}

    def skeleton():
        box["shapes"] = [(n, tuple(p._value.shape)) for n, p in
                         model_class(cfg).named_parameters()]
        return 0

    jax.eval_shape(skeleton)
    by_layer, rest = {}, 0
    for name, shape in box["shapes"]:
        n = 1
        for s in shape:
            n *= s
        parts = name.split(".")
        if parts[1] == "layers":
            by_layer[int(parts[2])] = by_layer.get(int(parts[2]), 0) + n
        else:
            rest += n
    p = roofline_flash.params_by_layer(m)
    p["full"] = p["window"]
    assert [by_layer[i] for i in range(32)] \
        == [p[k] for k in roofline_flash.kinds(m)]
    assert rest == p["embedding"] + p["final_norm"]
    assert sum(by_layer.values()) + rest == roofline_flash.params_total(m)


def test_bytes_of_a_row_a_slot_and_a_step():
    m = _sizes()
    assert roofline_flash.kv_row_bytes(m) == 5120
    assert roofline_flash.state_bytes_per_slot(m) == 9 * 358_400
    # 64 slots at 3.6 k tokens: the full layer and the 7 that share it
    # read every row, the 8 window layers 512 a slot
    rows = 64 * (8 * 3600 + 8 * 512)
    total = roofline_flash.decode_step_bytes(m, rows, 64)
    assert round(64 * 8 * 3600 * 5120 / 1e9, 1) == 9.4
    assert round(64 * 8 * 512 * 5120 / 1e9, 1) == 1.3
    assert round(2 * 64 * 9 * 358_400 / 1e9, 2) == 0.41
    assert round(total / 1e9, 1) == 18.9


def _obs(m):
    def snap(steps, decode, admit, seconds):
        return {"histograms": {"h": {"": {"sum": seconds, "count": steps}}},
                "counters": {"rows": {
                    'group="full",phase="decode"': decode,
                    'group="w512",phase="decode"': decode / 4,
                    'group="full",phase="admit"': admit}}}
    return {"model": m, "peaks": {"hbm_bytes_per_s": 819e9},
            "telemetry": {"before": snap(10, 1e6, 1e5, 1.0),
                          "after": snap(110, 1e6 + 2.5e8, 1e5 + 4e7, 6.0)},
            "steps": [{"running_slots": 64}] * 3, "t_open": 0.0,
            "t_close": 10.0,
            "spans": [{"name": "serving.decode_step", "ts_mono": t,
                       "dur_s": 0.05} for t in (1.0, 8.5, 9.0, 9.5)]
            + [{"name": "serving.ragged_prefill", "ts_mono": t,
                "dur_s": 0.05} for t in (2.0, 8.7)],
            "trace": {"window_s": 2.0, "ops_s": {
                "ragged_paged_attention.3": 0.05, "fusion": 1.0}}}


def test_decode_floor_share_reads_the_programs_rows():
    m = _sizes()
    obs = _obs(m)
    rows = (2.5e8 + 2.5e8 / 4) / 100          # a decode step
    floor = roofline_flash.decode_step_bytes(m, rows, 64) / 819e9
    got = flash_decode_floor_share.read(obs, histogram="h", rows="rows")
    assert abs(got - 100 * floor / 0.05) < 1e-9
    obs["telemetry"]["after"]["counters"] = {}
    assert flash_decode_floor_share.read(obs, histogram="h",
                                         rows="rows") is None


def test_attn_kv_roofline_counts_the_traced_spans_dispatches():
    m = _sizes()
    obs = _obs(m)
    # the last 2 s hold 3 decode steps and 1 of the window's 2 admissions
    rows = 3 * (2.5e8 * 1.25 / 100) + 1 * (4e7 / 2)
    got = attn_kv_roofline.read(
        obs, pattern="^ragged_paged_attention", rows="rows", histogram="h",
        decode_span="serving.decode_step",
        admit_span="serving.ragged_prefill")
    assert abs(got - 100 * rows * 5120 / 819e9 / 0.05) < 1e-9
    obs["trace"]["ops_s"] = {"fusion": 1.0}
    assert attn_kv_roofline.read(
        obs, pattern="^ragged_paged_attention", rows="rows", histogram="h",
        decode_span="serving.decode_step",
        admit_span="serving.ragged_prefill") is None
