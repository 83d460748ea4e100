"""A hybrid model's decode PROGRAM's share of its HBM floor: the least
time a decode step could take on this chip (benchmark/roofline_hybrid.py:
the weights outside the routed experts, the routed experts the step HIT,
the live slots' state read and written, the live K and V rows, at the
published HBM rate) over the mean time a decode step took. The experts
hit come from the program's counter `experts` (kind hit / idle): every
dispatch adds its layers' held experts to hit + idle, so the counter
gives the dispatches, the decode histogram gives how many were decode
steps, and the admission dispatches are taken to have hit EVERY held
expert, which leaves the decode steps the fewest hits they can have had.
None where the program has no such counter."""
from benchmark import roofline_hybrid
from benchmark.readers import histogram_mean


def counter_deltas(obs, counter) -> dict:
    """{label string: the series' growth over the window}."""
    tel = obs.get("telemetry") or {"before": {}, "after": {}}
    d = {}
    for sign, snap in ((-1, tel["before"]), (1, tel["after"])):
        for labels, v in snap.get("counters", {}).get(counter, {}).items():
            d[labels] = d.get(labels, 0.0) + sign * v
    return d


def hits_a_decode_step(obs, experts, decode_steps):
    d = counter_deltas(obs, experts)
    hit, idle = d.get('kind="hit"', 0.0), d.get('kind="idle"', 0.0)
    m = obs["model"]
    a_dispatch = roofline_hybrid.kinds(m).count("E") * m["experts_held"]
    if hit + idle <= 0 or not a_dispatch:
        return None
    admissions = (hit + idle) / a_dispatch - decode_steps
    return max(hit - admissions * a_dispatch, 0.0) / decode_steps


def read(obs, histogram, experts):
    d = histogram_mean.delta(obs, histogram)
    live = [s for s in obs["steps"] if s.get("running_slots")]
    if d is None or not live or obs.get("peaks") is None:
        return None
    hits = hits_a_decode_step(obs, experts, d[1])
    if hits is None:
        return None
    ctx = sum(s["live_context_tokens"] for s in live) / len(live)
    slots = sum(s["running_slots"] for s in live) / len(live)
    floor = roofline_hybrid.decode_step_bytes(obs["model"], ctx, slots, hits) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (d[0] / d[1])
