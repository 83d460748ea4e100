"""Arithmetic on request timelines: percentiles, token gaps, TTFT.
Plain Python on plain lists, so that it can be checked by hand."""
from __future__ import annotations

import bisect
import math

# what a request that never produced its first token counts as: a TTFT
# beyond every other. JSON has no infinity, so a metric that lands on
# a miss is printed as this many seconds
MISS_S = 1e9


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between
    order statistics (numpy's default). Misses (inf) sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def finite(x: float) -> float:
    return MISS_S if math.isinf(x) else x


def ttfts(requests) -> list:
    """Seconds from the moment each counted request was DUE to the
    moment its first token was seen. A request that failed, or never
    showed a token, is a miss: infinity."""
    out = []
    for r in requests:
        if not r["counted"]:
            continue
        if r["failed"] or not r["token_times"]:
            out.append(math.inf)
        else:
            out.append(r["token_times"][0] - r["due"])
    return out


def token_gaps(requests, t_open: float, t_close: float) -> list:
    """Gaps between consecutive tokens of one request, pooled over all
    requests, for every gap that ENDS inside (t_open, t_close]."""
    out = []
    for r in requests:
        ts = r["token_times"]
        out.extend(b - a for a, b in zip(ts, ts[1:])
                   if t_open < b <= t_close)
    return out


def tokens_in_window(requests, t_open: float, t_close: float) -> int:
    return sum(1 for r in requests for t in r["token_times"]
               if t_open < t <= t_close)


def rate_by_thirds(requests, t_open: float, t_close: float) -> list:
    """Tokens per second in each third of the window: whether a run
    that reads low was low throughout, or for a stretch."""
    edges = [t_open + (t_close - t_open) * i / 3 for i in range(3)] \
        + [t_close]
    return [tokens_in_window(requests, a, b) / (b - a)
            for a, b in zip(edges, edges[1:])]


def admission_times(requests) -> list:
    """The moments at which a request's first token was seen, in rising
    order: a fleet step that held an admission ends at one (an
    admission's every chunk is dispatched in the step that claims the
    slot, and its first token shows with it)."""
    return sorted({r["token_times"][0] for r in requests
                   if r["token_times"]})


def gaps_with_admission_share(requests, t_open: float,
                              t_close: float):
    """Of the token gaps that end inside the window (`token_gaps`), the
    share in percent during which some OTHER request was admitted: the
    gaps that read a decode step plus an admission. Where it lies near
    5 %, the 95th-percentile gap sits on the edge between the two
    kinds. None without a gap."""
    admits = admission_times(requests)
    held = total = 0
    for r in requests:
        ts = r["token_times"]
        for a, b in zip(ts, ts[1:]):
            if t_open < b <= t_close:
                total += 1
                i = bisect.bisect_right(admits, a)
                held += i < len(admits) and admits[i] <= b
    return 100.0 * held / total if total else None


def step_durations(steps, requests) -> dict:
    """Mean duration of the window's fleet steps that held an admission
    and of those that did not, with their counts."""
    admits = set(admission_times(requests))
    out = {}
    for kind, durs in (
            ("admission", [s["dur_s"] for s in steps if s["t"] in admits]),
            ("decode_only", [s["dur_s"] for s in steps
                             if s["t"] not in admits])):
        out[kind + "_steps"] = len(durs)
        out[kind + "_mean_s"] = sum(durs) / len(durs) if durs else None
    return out


def lateness(requests) -> list:
    """How late the generator ran: actual submit minus due, for the
    counted requests."""
    return [r["submit"] - r["due"] for r in requests if r["counted"]]
