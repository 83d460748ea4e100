"""The decode PROGRAM's share of its HBM roofline: the least time a
decode step could take on this chip (benchmark/roofline.py: weights
once + the K and V rows of the live context, at the published HBM rate)
over the mean time a decode step took (the engine's histogram of one
decode dispatch and its D2H sync). The live context is the harness's
own count, sampled after every step of the window."""
from benchmark import roofline
from benchmark.readers import histogram_mean


def read(obs, histogram):
    d = histogram_mean.delta(obs, histogram)
    live = [s for s in obs["steps"] if s.get("running_slots")]
    if d is None or not live or obs.get("peaks") is None:
        return None
    ctx = sum(s["live_context_tokens"] for s in live) / len(live)
    slots = sum(s["running_slots"] for s in live) / len(live)
    floor = roofline.decode_floor_s(obs["model"], ctx, slots, obs["peaks"])
    return 100.0 * floor / (d[0] / d[1])
