#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It knows no model and no traffic mix by name. `--workload X` is looked
up in `BENCHMARK.json` (its configuration, its chips, its metrics) and
in `benchmark/workloads/X.json` (its runner, its engine settings, its
traffic); the configuration's file holds the sizes; each per-layer
metric is `benchmark/metrics/<name>.json`, which names its reader under
`benchmark/readers/`. README.md says how a later PR adds any of these
as files of its own.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1`
`breakdown`, and last `compared`: each number that decided `correct`
beside its limit. The line before it, `[summary] {...}`, holds whatever else
the run learned. Without a TPU the measurement path exits non-zero and
prints no result; `--dry` rehearses a cell on the CPU at the tiny sizes
of `benchmark/tests/dry.json` and prints counts, never a time or a rate.
`--sweep r1,r2,...` runs an open-loop cell at each rate after one
set-up and prints a table (how a cell's rate is found, once).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _mine(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def _apply_dry(cell: dict, sizes: dict) -> None:
    """Cut a cell to the rehearsal's sizes: tiny widths, short contexts,
    the same code path."""
    from benchmark import traffic
    dry = _load("benchmark", "tests", "dry.json")
    sizes.update(dry["sizes"])
    cut = dry["lengths_divided_by"]
    cell["traffic"] = traffic.scaled(cell["traffic"], cut)
    eng = cell["engine"]
    page = sizes["engine"]["page_size"]

    def default_pool():
        return eng["max_batch_size"] * -(-eng["max_seq_len"] // page)

    full = default_pool()
    for key in ("max_seq_len", "prefill_chunk", "prompt_pad"):
        if eng.get(key):
            eng[key] = max(16, int(eng[key] / cut))
    eng["max_batch_size"] = min(eng["max_batch_size"], dry["max_slots"])
    if eng.get("num_pages"):
        # an explicit pool is cut with the slots and the contexts: the
        # same share of slots x pages a sequence (page 0 is spare)
        eng["num_pages"] = 1 + -(-(eng["num_pages"] - 1) * default_pool()
                                 // full)
    arr = cell["traffic"]["arrivals"]
    if "clients" in arr:
        arr["clients"] = min(arr["clients"], dry["max_slots"] * 3 // 2)
    cell["warm"] = {k: min(v, dry["max_warm_s"]) if k != "until" else v
                    for k, v in cell["warm"].items()}
    cell["drain_s"] = min(cell.get("drain_s", 0), dry["max_drain_s"])
    lc = cell["logits_check"]
    lc["prompt_tokens"] = max(8, int(lc["prompt_tokens"] / cut))


def load_cell(workload: str):
    """`(bench, entry, conf, cell, sizes)` of a workload's name: its
    entries in `BENCHMARK.json`, its file and its configuration's."""
    bench = _load("BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return (bench, entry, conf,
            _load("benchmark", "workloads", entry["name"] + ".json"),
            _load(conf["file"]))


def _device(dry: bool, chips: int, peak=None) -> dict:
    import jax
    if dry:
        from paddle_tpu.device import describe_devices
        info = describe_devices()
    else:
        from paddle_tpu.device import require_tpu
        info = require_tpu()               # raises without a TPU
        if info["count"] < chips:
            raise RuntimeError(f"the cell asks for {chips} chips, JAX "
                               f"found {info['count']}")
    if peak is None:
        peak = 0
        for d in jax.devices():
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": info["platform"], "kind": info["kind"],
            "count": info["count"], "memory_peak_bytes": peak}


def _per_layer(declared: list, obs: dict) -> dict:
    out = {}
    for m in declared:
        spec = _load("benchmark", "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:      # nothing to read: leave it out
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates per second")
    ap.add_argument("--out", default=None,
                    help="also write the run's details to this JSON file")
    args = ap.parse_args()

    bench, entry, _, cell, sizes = load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    # the spans ring must hold a whole window (read at import)
    os.environ.setdefault("PDT_TELEMETRY_TRACE_CAP", "1000000")
    if args.dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        _apply_dry(cell, sizes)
    import jax
    from paddle_tpu.device import enable_compile_cache
    # JAX_COMPILATION_CACHE_DIR where it is set, else one fixed
    # directory inside the checkout; every program goes in, however
    # quick its compile, so that a second run compiles nothing
    # (a rehearsal keeps none: CPU entries would sit beside the chip's)
    cache_dir = None
    if not args.dry:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = _device(args.dry, entry["chips"])

    runner = importlib.import_module(f"benchmark.runners.{cell['runner']}")
    ctx = {"cell": cell, "sizes": sizes, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace), "dry": args.dry,
           "t_start": T_START, "chips": entry["chips"]}
    if args.sweep:
        rows = runner.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        if args.dry:     # counts only: a CPU's times are not the chip's
            rows = [{k: v for k, v in r.items() if not k.endswith("_s")}
                    for r in rows]
        print("[sweep-table] " + json.dumps(rows), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
        return 0
    res = runner.run(ctx)
    # the peak: as the runner read it when its window had closed, before
    # a reference ran; else as it is now
    device = _device(args.dry, entry["chips"], res.get("memory_peak_bytes"))
    obs = res.pop("obs")

    e2e = {m["name"]: m for m in _mine(bench["end_to_end"], entry["name"])}
    if args.trace:
        from benchmark.peaks import peaks_for
        obs["peaks"] = None if args.dry else peaks_for(device["kind"])
        metrics = _per_layer(_mine(bench["per_layer"], entry["name"]), obs)
        tr = obs.get("trace")
        if tr:
            device["busy_s"], device["window_s"] = tr["busy_s"], \
                tr["window_s"]
    else:
        missing = [n for n in e2e if res["end_to_end"].get(n) is None]
        if missing:
            raise SystemExit(f"the runner gave no value for {missing}")
        metrics = {n: {"value": res["end_to_end"][n], "unit": m["unit"]}
                   for n, m in e2e.items()}
    if args.dry:
        # a CPU run gives counts and correctness, never a time or rate
        metrics = {n: v for n, v in metrics.items() if v["unit"] == "count"}

    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and obs.get("trace"):
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                             "idle_gaps": obs["trace"]["idle_gaps"]}
    # each number `correct` compared beside its limit: last in the line,
    # and (below) the last lines of standard error
    line["compared"] = res.get("compared", {})
    summary = {"workload": entry["name"], "seed": args.seed,
               "seconds": seconds, "trace": args.trace, "dry": args.dry,
               "compile_cache": cache_dir,
               "wall_s": time.perf_counter() - T_START,
               "notes": res["notes"]}
    if args.dry:     # no CPU time under a device metric's name
        summary = {k: v for k, v in summary.items() if k != "wall_s"}
        summary["notes"] = {k: v for k, v in res["notes"].items()
                            if not k.endswith("_s") and k != "samples"}
    else:
        summary["all_end_to_end"] = res["end_to_end"]
    print("[summary] " + json.dumps(summary, default=str), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"line": line, "summary": summary,
                       "trace_planes": obs.get("trace_planes"),
                       "steps": obs.get("steps")}, f, default=str)
    for name, c in line["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
