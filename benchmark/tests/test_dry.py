"""`run.py --dry` end to end on the CPU, for both cells, and for a
cell, a configuration, a per-layer metric and a runner ADDED AS FILES in
a copy of the benchmark, with no file that exists edited other than by
new entries in `BENCHMARK.json`."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _run(root, *args, check=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    if check:
        assert p.returncode == 0, p.stderr[-3000:]
    return p


def _last(p):
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,trace", [
    ("internlm2-1_8b.batch", 0), ("internlm2-1_8b.batch", 1),
    ("mistral-7b-v0_3.chat", 0), ("mistral-7b-v0_3.chat", 1)])
def test_dry_line_holds_the_contracts_keys(cell, trace):
    line = _last(_run(ROOT, "--workload", cell, "--seed", str(2**31 + 9),
                      "--seconds", "2", "--trace", str(trace), "--dry"))
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    # a CPU run gives counts, never a time, a rate or a share
    assert all(m["unit"] == "count" for m in line["metrics"].values())
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_without_a_tpu_the_measurement_path_fails():
    p = _run(ROOT, "--workload", "internlm2-1_8b.batch", "--seed", "1",
             "--seconds", "1", "--trace", "0", check=False)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_cell_config_metric_and_runner_are_added_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmark")
    before = {os.path.join(b, f): os.path.getmtime(os.path.join(b, f))
              for b, _, fs in os.walk(bdir) for f in fs}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # a configuration: a file of sizes
    with open(os.path.join(bdir, "configs", "internlm2-1_8b.json")) as f:
        sizes = json.load(f)
    sizes["num_hidden_layers"] = 12
    sizes["reduced"] = ["num_hidden_layers"]
    sizes["source"] = "https://example.org/half-depth"
    with open(os.path.join(bdir, "configs", "half.json"), "w") as f:
        json.dump(sizes, f)
    bench["configs"].append({
        "name": "half", "source": sizes["source"],
        "file": "benchmark/configs/half.json",
        "reduced": ["num_hidden_layers"], "why": "a test's configuration"})
    # a runner: a file with run(ctx); this one wraps `serve`
    with open(os.path.join(bdir, "runners", "serve_twice.py"), "w") as f:
        f.write("from benchmark.runners import serve\n\n\n"
                "def run(ctx):\n"
                "    res = serve.run(ctx)\n"
                "    res['obs']['answer'] = 42\n"
                "    return res\n")
    # a cell: a file of parameters, with bursts and a shared prefix,
    # which the generator already reads
    with open(os.path.join(bdir, "workloads",
                           "mistral-7b-v0_3.chat.json")) as f:
        cell = json.load(f)
    cell["runner"] = "serve_twice"
    cell["why"] = "a test's cell"
    cell["traffic"]["arrivals"]["burst"] = {
        "start_prob": 0.1, "mean_s": 1.0, "multiplier": 3.0}
    cell["traffic"]["shared_prefix"] = {"pool": 2, "tokens": 256,
                                        "share_prob": 0.5}
    with open(os.path.join(bdir, "workloads", "half.bursty.json"),
              "w") as f:
        json.dump(cell, f)
    bench["workloads"].append({
        "name": "half.bursty", "config": "half", "traffic": "bursty",
        "chips": 1, "why": cell["why"]})
    # a per-layer metric: a JSON naming a reader, and the reader
    with open(os.path.join(bdir, "readers", "answer.py"), "w") as f:
        f.write("def read(obs, plus=0):\n"
                "    return obs.get('answer', None) and "
                "obs['answer'] + plus\n")
    with open(os.path.join(bdir, "metrics", "the_answer.json"), "w") as f:
        json.dump({"reader": "answer", "args": {"plus": 1}}, f)
    bench["per_layer"].append({
        "name": "the_answer", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry / fleet",
        "moves": "itl_p95_s", "workloads": ["half.bursty"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line = _last(_run(root, "--workload", "half.bursty", "--seed", "3",
                      "--seconds", "2", "--trace", "1", "--dry"))
    assert set(line) == KEYS and line["correct"] is True
    assert line["metrics"]["the_answer"] == {"value": 43, "unit": "count"}
    # the old cells still run from the same tree, and do not report it
    old = _last(_run(root, "--workload", "mistral-7b-v0_3.chat", "--seed",
                     "3", "--seconds", "1", "--trace", "1", "--dry"))
    assert "the_answer" not in old["metrics"]
    # nothing that was there was edited
    assert all(os.path.getmtime(p) == t for p, t in before.items())
