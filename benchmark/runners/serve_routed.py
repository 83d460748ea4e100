"""The serving runner (`runners/serve.py`: the same set-up, window,
drain and checks) for a model whose expert layers report each row's
chosen experts (`models/cache_spec.py` `ReportSpec`), with the logits
check CHOICE-FORCED.

Why. The check of `serve.py` compares the program's logits with the
reference's own full forward. Top-k routing is a discontinuous function
of scores that a bf16 program and a float32 reference compute from
slightly different activations: with 512 experts the 22nd and the 23rd
score lie closer than bf16 resolves in about one row in ten a layer, the
two sides then take different experts, and one exchanged expert moves
that row's logits by 0.5-3.6 of their standard deviation (PERF.md,
PR 27), as far as a whole model rounded to fp8 moves them. No tolerance
on that comparison separates bf16 from fp8. Here the reference is given
the program's choices (the engine hands them to a sentry that takes
them) and two limits, both in the cell's `logits_check`, decide:

* `tolerance`: the largest |program - reference| over the standard
  deviation of the reference's logits, as in `serve.py`, on the decode
  rows, with every expert layer of the reference taking the experts the
  program took in that row. What is left is rounding.
* `route_margin`: the program's choice must BE a top-k of the
  reference's own scores up to near-ties: in every row of every expert
  layer, the highest score the program left out less the lowest score it
  took (`gap`, 0 or less for the exact top k) is at most this. A program
  that routes wrongly, which a forced reference would follow, fails
  here.

`"control": <dtype>` in the check's block puts the reference with every
matrix rounded to that type in the program's place, through the same
two limits: how the limits were set (PERF.md) and how a test shows that
a lower precision than the configuration states is not correct.
"""
from __future__ import annotations

import importlib
import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np

if __name__ == "__main__":      # run as a script: the repo on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import weights                               # noqa: E402

# this runner's own instance of the serving runner: its check is swapped
# below, and the module the other cells use is left as it is
_spec = importlib.util.find_spec("benchmark.runners.serve")
serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve)


class _RouteRecorder(serve._LogitRecorder):
    """Also takes the reporting layers' per-row records: the expert
    layers' choices, by position of the one sequence the check runs."""

    def __init__(self):
        super().__init__()
        self.routes = {}

    def observe_layer_rows(self, slots, positions, records):
        if len(set(np.asarray(slots).tolist())) > 1:
            raise serve.Unsound("logits check: rows of two sequences")
        for j, pos in enumerate(np.asarray(positions).tolist()):
            self.routes[pos] = [r[j] for r in records]


class _Rounded(dict):
    """The same weights with every matrix rounded to `dtype` and back,
    one at a time as it is read."""

    def __init__(self, values, dtype):
        super().__init__(values)
        self.dtype = jnp.dtype(dtype)

    def __getitem__(self, name):
        v = super().__getitem__(name)
        return v.astype(self.dtype).astype(v.dtype) if v.ndim >= 2 else v


def _through_the_engine(model, engine_kw, prompt, steps):
    """(tokens, decode logit rows, choices by expert layer) of one
    prompt prefilled and decoded through the engine's caches."""
    eng = serve.ContinuousBatchingEngine(
        model, **{**engine_kw, "max_batch_size": 2})
    rec = _RouteRecorder()
    eng.attach_sentry(rec)
    rid = eng.add_request(prompt, max_new_tokens=steps + 1)
    tokens = eng.run()[rid]
    if eng.num_failures or eng.num_decode_retries:
        raise serve.Unsound(f"logits check: the engine healed a failure:\n"
                            f"{eng.last_failure}")
    total = len(prompt) + steps
    missing = [p for p in range(total) if p not in rec.routes]
    if missing:
        raise serve.Unsound(f"logits check: no expert choices reported "
                            f"for positions {missing[:5]}")
    layers = len(rec.routes[0])
    chosen = [np.stack([rec.routes[p][i] for p in range(total)])
              for i in range(layers)]
    return tokens[:steps], np.stack([r[0] for r in rec.rows[:steps]]), \
        chosen


def logits_check(model, sizes: dict, engine_kw: dict, spec: dict,
                 seed: int) -> dict:
    """One seeded prompt prefilled and decoded `steps` tokens through
    the engine (ragged prefill in chunks, state and pages, ragged
    decode); each decode step's logits against the plain reference's
    full forward over prompt + generated tokens, the reference's expert
    layers taking the program's choices (the module's docstring)."""
    ref = importlib.import_module(f"benchmark.reference.{sizes['reference']}")
    n, steps = int(spec["prompt_tokens"]), int(spec["steps"])
    rng = np.random.default_rng(int(seed) + 1)
    prompt = rng.integers(1, sizes["vocab_size"], n).tolist()
    values = weights.named_values(model)
    if spec.get("control"):
        ids = prompt + rng.integers(1, sizes["vocab_size"], steps).tolist()
        got, chosen, _ = ref.forward_routed(
            _Rounded(values, spec["control"]), sizes, ids)
        got = got[n:n + steps]
    else:
        tokens, got, chosen = _through_the_engine(model, engine_kw, prompt,
                                                  steps)
        ids = prompt + tokens
    # decode step j consumed generated token j at position n + j: row
    # n + j of the full forward
    want, _, gap = ref.forward_routed(values, sizes, ids, chosen)
    want = want[n:n + steps]
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise serve.Unsound("logits check: non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.std(want))
    out = {"prompt_tokens": n, "steps": steps, "max_err_over_ref_std": err,
           "mean_err_over_ref_std":
               float(np.mean(np.abs(got - want)) / np.std(want)),
           "ref_std": float(np.std(want)),
           "argmax_agree": int(np.sum(got.argmax(-1) == want.argmax(-1))),
           "tolerance": float(spec["tolerance"]),
           "route_gap_max": float(gap.max()) if gap.size else 0.0,
           "route_gap_by_layer": [float(g) for g in gap.max(axis=1)],
           "rows_not_the_reference_top_k":
               [int(c) for c in (gap > 0).sum(axis=1)],
           "route_margin": float(spec["route_margin"]),
           "control": spec.get("control")}
    out["ok"] = err <= out["tolerance"] \
        and out["route_gap_max"] <= out["route_margin"]
    return out


serve.logits_check = logits_check
run, sweep = serve.run, serve.sweep


def main() -> int:
    """`python3 benchmark/runners/serve_routed.py --workload <cell>
    --seeds a,b [--control float8_e4m3fn]`: the cell's logits check
    alone, at the cell's sizes, one line a seed; with `--control` the
    reference in that precision in the program's place (exit code 1
    unless every seed comes out NOT correct)."""
    import argparse
    import json

    from benchmark import run as harness
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args()
    bench = harness._load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = harness._load("benchmark", "workloads", entry["name"] + ".json")
    sizes = harness._load(conf["file"])
    from paddle_tpu.device import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    spec = dict(cell["logits_check"])
    if args.control:
        spec["control"] = args.control
    wrong = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        model, _ = weights.build(sizes["program"], sizes, seed)
        check = logits_check(model, sizes,
                             {**sizes["engine"], **cell["engine"]}, spec, seed)
        print(json.dumps({"seed": seed, **check}), flush=True)
        wrong += check["ok"] == (args.control is None)
        del model
    return 0 if wrong == len(args.seeds.split(",")) else 1


if __name__ == "__main__":
    raise SystemExit(main())
