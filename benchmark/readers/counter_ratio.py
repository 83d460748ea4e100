"""A share of a labelled telemetry counter over the window: the delta
of the series whose label string is in `numerator`, over the delta of
all of the counter's series. None where there is nothing to read."""


def read(obs, counter, numerator, scale=1.0):
    tel = obs.get("telemetry")
    if not tel:
        return None
    part = whole = 0.0
    for sign, snap in ((-1, tel["before"]), (1, tel["after"])):
        for labels, v in snap.get("counters", {}).get(counter,
                                                      {}).items():
            whole += sign * v
            if labels in numerator:
                part += sign * v
    return part / whole * scale if whole > 0 else None
