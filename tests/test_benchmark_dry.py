"""The benchmark harness against the program, on the CPU (tier-1):
`benchmark/run.py --dry --trace 1` end to end for every cell, every
per-layer metric's file and reader, and the readers of the program's
spans and counters on hand-made observations. `pytest tests/` does not
collect `benchmark/tests/`, so a PR which breaks what the harness calls
(an engine argument, a span's or a counter's name, a reader) fails here
and not on the chip."""
import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader, spec.get("args", {})


@pytest.mark.parametrize("cell", CELLS)
def test_dry_traced_run_is_correct(cell):
    """A reader that raises, a span the runner cannot find or an engine
    argument that went away fails the run. The window is 12 s so that
    a request ends in it under `-n 6` too: the hybrid cell's shortest
    staggered answer is 16 tokens at 0.07-0.25 s a CPU decode step (at
    8 s, PR 31 saw one run of the hybrid cell under six busy workers
    end none)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("PDT_TELEMETRY", None)     # the runner switches it itself
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 25), "--seconds", "12",
         "--trace", "1", "--dry"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_its_file_and_reader(metric):
    reader, args = _metric(metric)
    assert callable(reader.read)
    # nothing to read (a parent without the span or the counter): the
    # metric is left out of the line, the reader does not raise
    empty = {"telemetry": {"before": {}, "after": {}}, "spans": [],
             "requests": [], "steps": [], "t_open": 0.0, "t_close": 1.0,
             "window_s": 1.0}
    value = reader.read(empty, **args)
    assert value is None or isinstance(value, (int, float))


def _snap(hist=None, counters=None):
    return {"histograms": hist or {}, "counters": counters or {}}


def _self(**sums):
    return {"pdt_span_self_seconds": {
        f'name="{n}"': {"sum": s, "count": c}
        for n, (s, c) in sums.items()}}


OBS = {"window_s": 10.0, "telemetry": {
    "before": _snap(_self(**{"router.step": (1.0, 10),
                             "serving.admit": (2.0, 10),
                             "serving.ragged_prefill": (4.0, 4),
                             "serving.decode_step": (8.0, 10)}),
                    {"pdt_serving_prefill_rows_total": {
                        'kind="token"': 1000.0, 'kind="pad"': 1000.0}}),
    "after": _snap(_self(**{"router.step": (1.5, 20),
                            "serving.admit": (2.25, 20),
                            "serving.commit": (0.25, 10),
                            "serving.ragged_prefill": (6.0, 8),
                            "serving.decode_step": (14.0, 20),
                            "jit.compile": (3.0, 1)}),
                   {"pdt_serving_prefill_rows_total": {
                       'kind="token"': 1600.0, 'kind="pad"': 1200.0}})}}


def test_span_self_reads_window_deltas():
    reader, args = _metric("host_self_time_share")
    # (0.5 + 0.25 + 0.25) s of host self time in a 10 s window
    assert reader.read(OBS, **args) == pytest.approx(10.0)
    reader, args = _metric("admit_dispatch_ms")
    assert reader.read(OBS, **args) == pytest.approx(500.0)
    assert reader.read(OBS, names=["serving.harvest"], per="count") is None
    assert reader.read({"telemetry": None}) is None
    assert reader.read({"window_s": 1.0, "telemetry": {
        "before": _snap(), "after": _snap()}}) is None


def test_dispatch_phases_split_the_window():
    """`dispatch_host_share` reads the two phases' self time, and
    `device_wait_share` the dispatch spans' own. `host_self_time_share`
    holds the phases (they are host time), so it and the waits (and
    `jit.compile`) are the whole of the window's self time, and the
    phases are a part of it. A parent without the phases reads 0.0."""
    phases = {"serving.inputs": (0.5, 10), "serving.launch": (1.0, 10)}
    after = _self(**{"router.step": (1.5, 20), "serving.admit": (2.25, 20),
                     "serving.commit": (0.25, 10),
                     "serving.ragged_prefill": (6.0, 8),
                     "serving.decode_step": (14.0, 20),
                     "jit.compile": (3.0, 1), **phases})
    before = _self(**{"router.step": (1.0, 10), "serving.admit": (2.0, 10),
                      "serving.ragged_prefill": (4.0, 4),
                      "serving.decode_step": (8.0, 10)})
    obs = {"window_s": 10.0, "telemetry": {"before": _snap(before),
                                           "after": _snap(after)}}
    shares = {}
    for name in ("dispatch_host_share", "device_wait_share",
                 "host_self_time_share"):
        reader, args = _metric(name)
        shares[name] = reader.read(obs, **args)
    # (0.5 + 1.0) of phases; (2.0 + 6.0) of waits; 1.0 of host before
    # and 1.5 more now that the phases are spans
    assert shares == pytest.approx({"dispatch_host_share": 15.0,
                                    "device_wait_share": 80.0,
                                    "host_self_time_share": 25.0})
    total = sum(s["sum"] for s in after["pdt_span_self_seconds"].values()) \
        - sum(s["sum"] for s in before["pdt_span_self_seconds"].values())
    jit = 3.0 / 10.0 * 100.0
    assert shares["host_self_time_share"] + shares["device_wait_share"] \
        + jit == pytest.approx(total / 10.0 * 100.0)
    reader, args = _metric("dispatch_host_share")
    assert reader.read(OBS, **args) == 0.0


def test_gc_time_share_reads_the_histograms_window_delta():
    reader, args = _metric("gc_time_share")

    def gc_hist(**sums):
        return {"pdt_host_gc_seconds": {f'generation="{g}"': {
            "sum": s, "count": 1} for g, s in sums.items()}}

    def obs(before, after):
        return {"window_s": 2.0, "telemetry": {"before": _snap(before),
                                               "after": _snap(after)}}
    # (0.04 - 0.01) + (0.06 - 0.0) s of collections in a 2 s window
    assert reader.read(obs(gc_hist(**{"0": 0.01}),
                           gc_hist(**{"0": 0.04, "2": 0.06})),
                       **args) == pytest.approx(4.5)
    assert reader.read(obs(gc_hist(**{"0": 0.01}), gc_hist(**{"0": 0.01})),
                       **args) == 0.0
    # a program without the histogram (the parent): nothing to read
    assert reader.read(obs({}, _self(**{"router.step": (1.0, 1)})),
                       **args) is None
    assert reader.read({"telemetry": None}, **args) is None
    from paddle_tpu.observability import trace
    assert trace._M_GC.name == args["histogram"]


def test_counter_ratio_reads_window_deltas():
    reader, args = _metric("prefill_pad_share")
    assert reader.read(OBS, **args) == pytest.approx(25.0)
    assert reader.read({"telemetry": None}, **args) is None
    assert reader.read({"telemetry": {"before": _snap(),
                                      "after": _snap()}}, **args) is None


def test_queue_wait_mean_reads_the_engines_histogram():
    reader, args = _metric("queue_wait_mean_s")
    obs = {"telemetry": {
        "before": _snap({"pdt_serving_queue_wait_seconds": {
            "": {"sum": 1.0, "count": 10}}}),
        "after": _snap({"pdt_serving_queue_wait_seconds": {
            "": {"sum": 1.5, "count": 60}}})}}
    assert reader.read(obs, **args) == pytest.approx(0.01)
    from paddle_tpu.models import serving
    assert serving._M_QUEUE_WAIT.name == args["histogram"]
