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
