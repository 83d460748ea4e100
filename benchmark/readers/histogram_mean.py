"""Mean of a telemetry histogram over the window: (sum after - sum
before) / (count after - count before), summed over its label sets."""


def delta(obs, histogram):
    tel = obs.get("telemetry")
    if not tel:
        return None
    tot = [0.0, 0]
    for sign, snap in ((-1, tel["before"]), (1, tel["after"])):
        for s in snap.get("histograms", {}).get(histogram, {}).values():
            tot[0] += sign * s["sum"]
            tot[1] += sign * s["count"]
    return tot if tot[1] > 0 else None


def read(obs, histogram, scale=1.0):
    d = delta(obs, histogram)
    return None if d is None else d[0] / d[1] * scale
