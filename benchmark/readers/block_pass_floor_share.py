"""A block model's PASS's share of its HBM floor: the least time a pass
could take on this chip (benchmark/roofline_blocks.py: the weights
outside the experts, the experts the pass HIT, the head, the live K and
V rows read and a block of rows a live slot written, at the published
HBM rate) over the mean time a pass took (the decode histogram: a pass
is the program's decode step). The experts hit come from the program's
counter `experts` (`roofline_blocks.hits_a_pass`). None where the
program has no such counter or histogram."""
from benchmark import roofline_blocks
from benchmark.readers import histogram_mean
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def read(obs, histogram, experts):
    d = histogram_mean.delta(obs, histogram)
    live = [s for s in obs["steps"] if s.get("running_slots")]
    if d is None or not live or obs.get("peaks") is None:
        return None
    c = counter_deltas(obs, experts)
    hits = roofline_blocks.hits_a_pass(
        obs["model"], c.get('kind="hit"', 0.0), c.get('kind="idle"', 0.0),
        d[1])
    if hits is None:
        return None
    ctx = sum(s["live_context_tokens"] for s in live) / len(live)
    slots = sum(s["running_slots"] for s in live) / len(live)
    floor = roofline_blocks.pass_bytes(obs["model"], ctx, slots, hits) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (d[0] / d[1])
