"""Summed durations of one of the program's spans that ended inside the
window, over the window, in percent."""


def read(obs, span):
    if obs.get("spans") is None:
        return None
    t0, t1 = obs["t_open"], obs["t_close"]
    busy = sum(e["dur_s"] for e in obs["spans"] if e["name"] == span
               and t0 < e["ts_mono"] + e["dur_s"] <= t1)
    return 100.0 * busy / (t1 - t0)
