"""Arithmetic on request timelines: percentiles, token gaps, TTFT.
Plain Python on plain lists, so that it can be checked by hand."""
from __future__ import annotations

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


def lateness(requests) -> list:
    """How late the generator ran: actual submit minus due, for the
    counted requests."""
    return [r["submit"] - r["due"] for r in requests if r["counted"]]
