"""The serving runner (`runners/serve.py`: the same set-up, window,
drain and checks) for a model that generates by DIFFUSION OVER BLOCKS
(`models/cache_spec.py` `BlockDiffusionSpec`) and whose expert layers
report each row's chosen experts, with the logits check comparing a PASS
and not a token.

Why. `serve.py`'s check follows one prompt through its prefill and a few
decode steps and compares each step's ONE sampled row. Here a decode
step is a pass over a block of `block_length` rows that attend each
other, the same positions are dispatched several passes in a row with
other ids, and what the program hands back is the block the transfer
rule made of the pass's logits. So the check runs one seeded prompt
(`P % block_length != 0`: the first block has a given head) through the
engine at the timed sizes for `blocks` blocks, and for EVERY pass:

* the block as dispatched is rebuilt on this side, from the pass before
  it: the reference's transfer rule (`reference.transfer`) applied to
  the PROGRAM's own logits. The tokens the engine hands out must equal
  the blocks so built: the program's rule is the reference's.
* the pass's `block_length` rows of logits are compared with the
  reference's forward over prompt + committed blocks + that block,
  CHOICE-FORCED as `serve_routed.py` does and for its reason (top-k
  routing is discontinuous; bf16 and float32 take other experts in a
  share of the rows): the reference's expert layers take the experts the
  program took in that row (a context row: in the last pass that wrote
  its keys), and two limits of the cell's `logits_check` decide,
  `tolerance` on the largest |program - reference| over the standard
  deviation of the reference's logits, and `route_margin` on how far the
  program's choice is from the reference's own top k.

The tolerance, 0.15, and its reason are `serve.py`'s: bf16 keeps 8
significant bits; a logit is a dot product of `hidden_size` terms behind
every layer's bf16 activations and is itself stored in bf16. PR 21
measured 0.043 at 16 dense layers of width 2048; here 6 layers of the
same width, each with 8 experts' outputs summed under float32 weights,
and PERF.md gives what this cell reads. A wrong mask (a row that does
not see its block's later keys, or sees the next block), a wrong page, a
pass that did not overwrite its rows, or 4-bit arithmetic costs a
multiple of 1.0; fp8 weights cost several times bf16's error
(`"control": <dtype>`, as in `serve_routed.py`).
"""
from __future__ import annotations

import importlib
import importlib.util
import os
import sys

import numpy as np

if __name__ == "__main__":      # run as a script: the repo on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import weights                               # noqa: E402
from benchmark.runners import serve_routed                  # noqa: E402

# this runner's own instance of the serving runner: its check is swapped
# below, and the module the other cells use is left as it is
_spec = importlib.util.find_spec("benchmark.runners.serve")
serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve)


class _PassRecorder(serve._LogitRecorder):
    """Takes every pass's live rows: their logits, and the reporting
    layers' records (the chosen experts) by position. A prefill's rows
    come without logits and only fill `routes`."""

    def __init__(self):
        super().__init__()
        self.routes, self.passes, self._last = {}, [], None

    def observe_layer_rows(self, slots, positions, records):
        if len(set(np.asarray(slots).tolist())) > 1:
            raise serve.Unsound("logits check: rows of two sequences")
        self._last = {pos: [r[j] for r in records] for j, pos in
                      enumerate(np.asarray(positions).tolist())}
        self.routes.update(self._last)

    def observe_logits(self, lg):
        # (first position, logits (block, vocab), the rows' choices)
        self.passes.append((min(self._last), np.asarray(lg, np.float32),
                            self._last))


def _through_the_engine(model, engine_kw, prompt, budget):
    eng = serve.ContinuousBatchingEngine(
        model, **{**engine_kw, "max_batch_size": 2})
    rec = _PassRecorder()
    eng.attach_sentry(rec)
    rid = eng.add_request(prompt, max_new_tokens=budget)
    tokens = eng.run()[rid]
    if eng.num_failures or eng.num_decode_retries:
        raise serve.Unsound(f"logits check: the engine healed a failure:\n"
                            f"{eng.last_failure}")
    return tokens, rec


def _compare(got, want, gap, spec, extra):
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise serve.Unsound("logits check: non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.std(want))
    out = {"max_err_over_ref_std": err,
           "mean_err_over_ref_std":
               float(np.mean(np.abs(got - want)) / np.std(want)),
           "ref_std": float(np.std(want)),
           "argmax_agree": int(np.sum(got.argmax(-1) == want.argmax(-1))),
           "tolerance": float(spec["tolerance"]),
           "route_gap_max": float(gap.max()) if gap.size else 0.0,
           "rows_not_the_reference_top_k":
               [int(c) for c in (gap > 0).sum(axis=1)],
           "route_margin": float(spec["route_margin"]),
           "control": spec.get("control"), **extra}
    out["ok"] = err <= out["tolerance"] \
        and out["route_gap_max"] <= out["route_margin"]
    return out


def logits_check(model, sizes: dict, engine_kw: dict, spec: dict,
                 seed: int) -> dict:
    """One seeded prompt through the engine for `blocks` blocks, every
    pass against the plain reference (the module's docstring)."""
    ref = importlib.import_module(f"benchmark.reference.{sizes['reference']}")
    b, n = int(sizes["block_length"]), int(spec["prompt_tokens"])
    if n % b == 0:
        n += 1                    # the first block has a given head
    budget = int(spec["blocks"]) * b - n % b
    rng = np.random.default_rng(int(seed) + 1)
    prompt = rng.integers(1, sizes["vocab_size"], n).tolist()
    values = weights.named_values(model)
    c0 = n // b * b
    layers = int(sizes["num_hidden_layers"])
    if spec.get("control"):
        # the reference in a lower precision in the program's place, on
        # given ids: through the same two limits
        ids = prompt + rng.integers(1, sizes["vocab_size"], budget).tolist()
        rows = range(c0, len(ids))
        got, chosen, _ = ref.forward_routed(
            serve_routed._Rounded(values, spec["control"]), sizes, ids,
            rows=rows)
        want, _, gap = ref.forward_routed(values, sizes, ids, chosen,
                                          rows=rows)
        return _compare(got, want, gap, spec, {"prompt_tokens": n,
                                               "passes": 0})
    tokens, rec = _through_the_engine(model, engine_kw, prompt, budget)
    context, given = prompt[:c0], prompt[c0:]
    block = given + [int(sizes["mask_token_id"])] * (b - len(given))
    masked = [False] * len(given) + [True] * (b - len(given))
    n_pass, built, got, want, gaps = 0, [], [], [], []
    for pos, lg, routes in rec.passes:
        if pos != len(context) or len(lg) != b:
            raise serve.Unsound(f"logits check: a pass at {pos}, expected "
                                f"{len(context)}")
        ids = context + block
        missing = [p for p in range(len(ids))
                   if p not in routes and p not in rec.routes]
        if missing:
            raise serve.Unsound(f"logits check: no expert choices reported "
                                f"for positions {missing[:5]}")
        # a context row's choice: the last pass that wrote its keys; the
        # block's rows: this pass's
        chosen = [np.stack([(routes.get(p) or rec.routes[p])[i]
                            for p in range(len(ids))])
                  for i in range(layers)]
        w, _, gap = ref.forward_routed(
            values, sizes, ids, chosen, rows=range(pos, pos + b))
        got.append(lg)
        want.append(w)
        gaps.append(gap)
        if any(masked):
            block, masked = ref.transfer(lg, block, masked, n_pass, sizes)
            n_pass += 1
        else:                      # the commit pass: the next block
            built += block[len(given):]
            context, given, n_pass = context + block, [], 0
            block, masked = [int(sizes["mask_token_id"])] * b, [True] * b
    out = _compare(np.concatenate(got), np.concatenate(want),
                   np.concatenate(gaps, axis=1), spec,
                   {"prompt_tokens": n, "passes": len(rec.passes),
                    "tokens_are_the_rules": built[:budget] == tokens})
    out["ok"] = out["ok"] and out["tokens_are_the_rules"] \
        and len(tokens) == budget
    return out


serve.logits_check = logits_check
sweep = serve.sweep


def run(ctx: dict) -> dict:
    """`serve.run` with this runner's check. A rehearsal's vocabulary
    (512) does not hold the published mask id: it takes the last id."""
    sizes = ctx["sizes"]
    if ctx["dry"]:
        sizes["mask_token_id"] = int(sizes["vocab_size"]) - 1
    res = serve.run(ctx)
    res["notes"]["mask_token_id"] = sizes["mask_token_id"]
    return res


def main() -> int:
    """`python3 benchmark/runners/serve_blocks.py --workload <cell>
    --seeds a,b [--control float8_e4m3fn]`: the cell's logits check
    alone, at the cell's sizes, one line a seed: `serve_routed.py`'s
    command line around this runner's check."""
    serve_routed.logits_check = logits_check
    return serve_routed.main()


if __name__ == "__main__":
    raise SystemExit(main())
