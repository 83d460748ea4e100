"""`BENCHMARK.json` against the contract's limits on names, units and
lengths, and against the files it points at."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what `reduced` may never name: a hidden, intermediate, latent, state or
# projection size, a head size, an expansion factor, experts per token
WIDTH = re.compile(
    r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head)_size$"
    r"|head_dim|expan|per_tok|top_k")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert bench["paths"] == ["benchmark"]
    assert len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            sizes = json.load(f)
        assert sizes["source"] == c["source"]
        assert sizes["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            # never a width; a count (layers, experts held, vocabulary
            # rows a chip's share keeps) may be cut
            assert not WIDTH.search(key), key


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        with open(os.path.join(ROOT, "benchmark", "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["why"] == w["why"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "runners", cell["runner"] + ".py"))
        # an explicit pool holds a slot-full of the traffic's median
        # sequences (page 0 is never handed out), or admission waits
        # for pages and the slots the cell names stay empty
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            eng = {**json.load(f)["engine"], **cell["engine"]}
        t = cell["traffic"]
        if eng.get("num_pages"):
            median = t["prompt_tokens"]["median"] \
                + t["output_tokens"]["median"]
            pages = -(-median // eng["page_size"])
            assert eng["num_pages"] - 1 >= eng["max_batch_size"] * pages


@pytest.mark.parametrize("key,width", [
    ("num_hidden_layers", False), ("experts_held", False),
    ("vocab_size", False), ("num_experts", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_latent_size", True), ("ssm_state_size", True),
    ("head_dim", True), ("kv_lora_rank", True), ("mamba_head_dim", True),
    ("num_experts_per_tok", True), ("expand", True),
    ("moe_shared_expert_intermediate_size", True)])
def test_reduced_never_names_a_width(key, width):
    assert bool(WIDTH.search(key)) is width


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = list(e2e) + [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert _line(m["layer"]) and m["source"] in SOURCES
        # the metric it moves is reported wherever this one is
        here = set(m.get("workloads", cells))
        there = set(e2e[m["moves"]].get("workloads", cells))
        assert here <= there, m["name"]
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_files_are_named_from_permitted_characters():
    for base, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", rel), rel
