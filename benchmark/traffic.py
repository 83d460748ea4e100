"""The one traffic generator. A mix is a block of parameters in a
workload file; nothing here knows a mix by name.

```
"traffic": {
  "arrivals": {"process": "poisson", "rate_per_s": 2.4,
               "burst": {"start_prob": 0.05, "mean_s": 3, "multiplier": 4}}
            | {"process": "closed_loop", "clients": 48},
  "prompt_tokens": {"median": 512, "sigma": 0.5, "min": 128, "max": 1280},
  "output_tokens": {"median": 128, "sigma": 0.4, "min": 32, "max": 384},
  "shared_prefix": {"pool": 4, "tokens": 1024, "share_prob": 0.7},
  "block": 64
}
```

Lengths are lognormal, clamped. **Every seed gets the same sizes in
another order**: a block of `block` requests holds the lognormal's
`block` mid-quantiles, each once, and the seed only shuffles them (and
draws the token ids). The exponential gaps of a Poisson process are
drawn the same way. So two seeds differ in order and in content, never
in the amount of work, and a difference between seeds is the system's,
not the sample's. Bursts (a Markov on/off state that multiplies the
rate, as `paddle_tpu/loadgen/trace.py` has it) and the choice of a
shared prefix are plain seeded draws; with `start_prob` 0 and `pool` 0
they draw nothing.

With `"schedule_seed": n` the whole schedule (the order of sizes and of
gaps, bursts, which request takes which prefix) is drawn from `n`, the
same in every run, and `--seed` draws the token ids (and, in the
runner, the weights) only. A window of 45 s holds some thirty to a
hundred requests, fewer than two blocks, so with the order left to the
seed two seeds still put different work inside the window: measured on
the chip, 6 % of spread in a closed loop's tokens/s and 17 % in an open
loop's 90th-percentile TTFT (PERF.md, PR 24). Both cells fix it.

Times are seconds from the start of the traffic. Stdlib + numpy only.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def _mid_quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def lognormal_block(spec: dict, n: int) -> list:
    """The `n` mid-quantiles of a clamped lognormal, as whole numbers,
    in rising order."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = []
    for u in _mid_quantiles(n):
        x = math.exp(mu + sigma * _NORMAL.inv_cdf(u))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def exponential_block(n: int) -> list:
    """The `n` mid-quantiles of the unit exponential."""
    return [-math.log(1.0 - u) for u in _mid_quantiles(n)]


class _Shuffled:
    """An endless stream over a fixed block of values: each pass
    through the block is a fresh seeded permutation of it."""

    def __init__(self, block: list, rng: np.random.Generator):
        self._block, self._rng, self._left = list(block), rng, []

    def next(self):
        if not self._left:
            self._left = [self._block[i] for i in
                          self._rng.permutation(len(self._block))]
        return self._left.pop()


class Traffic:
    """Requests in order. `next()` gives `(gap_s, prompt, new_tokens)`:
    `gap_s` is the time since the previous arrival (None in a closed
    loop, where a client sends when its last request ends), `prompt` a
    list of token ids, `new_tokens` the request's budget."""

    def __init__(self, spec: dict, seed: int, vocab_size: int):
        self.spec = spec
        self.vocab = int(vocab_size)
        n = int(spec.get("block", 64))
        # one generator per quantity, so that adding a draw to one
        # (bursts, sharing) never moves the others
        ss = np.random.SeedSequence(int(seed)).spawn(5)
        self._rt = np.random.default_rng(ss[3])        # token ids
        if spec.get("schedule_seed") is not None:
            # a fixed schedule: sizes, gaps, bursts and the choice of a
            # prefix are the same in every run; --seed draws the ids
            ss = np.random.SeedSequence(
                int(spec["schedule_seed"])).spawn(5)
        rp, ro, rg, _, self._rx = (np.random.default_rng(s) for s in ss)
        self._prompt = _Shuffled(lognormal_block(spec["prompt_tokens"], n),
                                 rp)
        self._output = _Shuffled(lognormal_block(spec["output_tokens"], n),
                                 ro)
        arr = spec["arrivals"]
        self.closed_loop = arr["process"] == "closed_loop"
        if self.closed_loop:
            self.clients = int(arr["clients"])
        elif arr["process"] == "poisson":
            self.rate = float(arr["rate_per_s"])
            self._gap = _Shuffled(exponential_block(n), rg)
            self._burst = arr.get("burst") or {}
            self._burst_left = 0.0
        else:
            raise ValueError(f"arrival process {arr['process']!r}: "
                             "poisson | closed_loop")
        sp = spec.get("shared_prefix") or {}
        self._share_prob = float(sp.get("share_prob", 0.0))
        self._pool = [self._ids(int(sp["tokens"]))
                      for _ in range(int(sp.get("pool", 0)))]

    def _ids(self, n: int) -> list:
        # token 0 is never drawn (padding in some tokenizers)
        return self._rt.integers(1, self.vocab, n).tolist()

    def _next_gap(self):
        if self.closed_loop:
            return None
        mult = 1.0
        b = self._burst
        if b and b.get("start_prob", 0) > 0:
            if self._burst_left <= 0 \
                    and self._rx.random() < b["start_prob"]:
                self._burst_left = self._rx.exponential(b["mean_s"])
            if self._burst_left > 0:
                mult = float(b["multiplier"])
        gap = self._gap.next() / (self.rate * mult)
        if mult != 1.0:
            self._burst_left -= gap
        return gap

    def next(self):
        gap = self._next_gap()
        prompt = self._ids(self._prompt.next())
        if self._pool and self._rx.random() < self._share_prob:
            prompt = self._pool[int(self._rx.integers(len(self._pool)))] \
                + prompt
        return gap, prompt, self._output.next()


def scaled(spec: dict, lengths: float) -> dict:
    """The same mix with every length divided by `lengths` (the `--dry`
    rehearsal's cut); rates and client counts stay."""
    out = dict(spec)
    for key in ("prompt_tokens", "output_tokens"):
        d = dict(spec[key])
        for k in ("median", "min", "max"):
            d[k] = max(2 if key == "prompt_tokens" else 1,
                       int(round(d[k] / lengths)))
        out[key] = d
    sp = spec.get("shared_prefix")
    if sp and sp.get("pool"):
        out["shared_prefix"] = dict(
            sp, tokens=max(1, int(sp["tokens"] / lengths)))
    return out
