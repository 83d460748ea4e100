"""The logits check of a cell whose model keeps window pages, shared
pages and a recurrent state (`phi-4-mini-flash-reasoning.longgen`),
alone and with its controls, a seed at a time in one process:

    python3 benchmark/runners/serve_flash_check.py --workload <cell>
        --seeds a,b,c [--controls none,float8_e4m3fn,zero_state,
                                  lost_window_page]

`--controls` lists what to run on each seed's weights (built once a
seed); `none`, the default, is the check itself. Without a control it is
`serve.logits_check`: a seeded prompt through a
two-slot engine of the cell's settings (chunks, window reclamation, the
restart of the scan, the shared cache), then `steps` decode steps,
against the plain reference's full forward pass. The controls say what
the tolerance can tell apart, and each must come out NOT correct:

* `float8_e4m3fn`: the reference with every matrix rounded to 8 bits in
  the program's place (arithmetic one precision lower);
* `zero_state`: the slot's recurrent state zeroed before the first
  decode step (what the state layers carry into a logit);
* `lost_window_page`: the oldest live page of the slot in the window
  group trash-routed before the first decode step (what one page of the
  window layers carries).

One line a seed and control; exit code 0 iff every one came out as
expected (the check correct, each control not)."""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

if __name__ == "__main__":      # run as a script: the repo on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.serving import ContinuousBatchingEngine

from benchmark import weights
from benchmark.runners.serve import Unsound, _LogitRecorder, _Rounded

ENGINE_CONTROLS = ("zero_state", "lost_window_page")


def _tamper(eng, control: str) -> None:
    if control == "zero_state":
        eng._state = [tuple(jnp.zeros_like(a) for a in st)
                      for st in eng._state]
        return
    group = next((g for g in eng._groups if g.window is not None), None)
    if group is None:
        raise Unsound("the model has no window group to lose a page of")
    slot = next(i for i, r in enumerate(eng._slot_req) if r is not None)
    group.bt[slot, int(group.slot_freed[slot])] = 0


def check(model, sizes: dict, engine_kw: dict, spec: dict, seed: int,
          control=None) -> dict:
    ref = importlib.import_module(f"benchmark.reference.{sizes['reference']}")
    n, steps = int(spec["prompt_tokens"]), int(spec["steps"])
    rng = np.random.default_rng(int(seed) + 1)
    prompt = rng.integers(1, sizes["vocab_size"], n).tolist()
    eng = ContinuousBatchingEngine(model, **{**engine_kw,
                                             "max_batch_size": 2,
                                             "num_pages": None})
    rec = _LogitRecorder()
    eng.attach_sentry(rec)
    if control in ENGINE_CONTROLS:
        decode = eng._decode

        def tampered(finished):
            if not rec.rows:             # before the FIRST decode step
                _tamper(eng, control)
            return decode(finished)

        eng._decode = tampered
    rid = eng.add_request(prompt, max_new_tokens=steps + 1)
    tokens = eng.run()[rid]
    if eng.num_failures or eng.num_decode_retries:
        raise Unsound(f"the engine healed a failure:\n{eng.last_failure}")
    got = np.stack([r[0] for r in rec.rows[:steps]])
    values = weights.named_values(model)
    if control is not None and control not in ENGINE_CONTROLS:
        values = _Rounded(values, control)
        # the control stands in the program's place: its logits against
        # the full-precision reference's
        got = ref.forward_logits(values, sizes,
                                 prompt + tokens[:steps])[n:n + steps]
        values = weights.named_values(model)
    want = ref.forward_logits(values, sizes,
                              prompt + tokens[:steps])[n:n + steps]
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise Unsound("non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.std(want))
    return {"seed": seed, "control": control, "prompt_tokens": n,
            "max_err_over_ref_std": err,
            "mean_err_over_ref_std":
                float(np.mean(np.abs(got - want)) / np.std(want)),
            "ref_std": float(np.std(want)),
            "argmax_agree": int(np.sum(got.argmax(-1) == want.argmax(-1))),
            "tolerance": float(spec["tolerance"]),
            "ok": err <= float(spec["tolerance"])}


def main() -> int:
    from benchmark import run as harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="none")
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    if not args.dry:
        from paddle_tpu.device import enable_compile_cache, require_tpu
        require_tpu()
        enable_compile_cache()
    controls = [None if c == "none" else c
                for c in args.controls.split(",")]
    expected = total = 0
    for seed in (int(x) for x in args.seeds.split(",")):
        _, _, _, cell, sizes = harness.load_cell(args.workload)
        if args.dry:
            harness._apply_dry(cell, sizes)
        model, _ = weights.build(sizes["program"], sizes, seed)
        for control in controls:
            res = check(model, sizes, {**sizes["engine"], **cell["engine"]},
                        cell["logits_check"], seed, control)
            print(json.dumps(res), flush=True)
            expected += res["ok"] == (control is None)
            total += 1
        del model
        gc.collect()
    return 0 if expected == total else 1


if __name__ == "__main__":
    raise SystemExit(main())
