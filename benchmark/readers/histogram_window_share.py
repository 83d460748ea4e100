"""The window's delta of a telemetry histogram's `sum`, over all its
label sets, over the window's seconds, times `scale`: the share of the
window the histogram's events took. None where the program has no such
histogram (absent from the after-snapshot); 0.0 where none fell in the
window."""


def read(obs, histogram, scale=1.0):
    tel = obs.get("telemetry")
    if not tel or histogram not in tel["after"].get("histograms", {}):
        return None
    total = 0.0
    for sign, snap in ((-1, tel["before"]), (1, tel["after"])):
        for s in snap.get("histograms", {}).get(histogram, {}).values():
            total += sign * s["sum"]
    return total / obs["window_s"] * scale
