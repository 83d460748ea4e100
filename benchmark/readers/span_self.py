"""Self time of the program's spans over the window: the window's delta
of `pdt_span_self_seconds{name}` sums (a span's duration less its
children's, so the names' sums are disjoint), over every name in `names`
(all of them when it is not given) that is not in `exclude`. `per` =
"window" gives seconds of self time a second of window; "count" gives
seconds a span. None where the program has no such histogram."""

HISTOGRAM = "pdt_span_self_seconds"


def read(obs, names=None, exclude=(), per="window", scale=1.0):
    tel = obs.get("telemetry")
    if not tel or HISTOGRAM not in tel["after"].get("histograms", {}):
        return None
    total, count = 0.0, 0
    for sign, snap in ((-1, tel["before"]), (1, tel["after"])):
        for labels, s in snap.get("histograms", {}).get(HISTOGRAM,
                                                        {}).items():
            name = labels.split('"')[1]
            if (names is None or name in names) and name not in exclude:
                total += sign * s["sum"]
                count += sign * s["count"]
    if per == "count":
        return total / count * scale if count > 0 else None
    return total / obs["window_s"] * scale
