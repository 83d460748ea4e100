"""The decode PROGRAM's share of its HBM floor for a model of window and
full attention layers over routed experts: the least time a decode step
could take on this chip (benchmark/roofline_mixed.py: the weights
outside the experts, the experts the step HIT, the head, the K and V
rows its attention calls read and the rows it writes, at the published
HBM rate) over the mean time a decode step took. The experts hit come
from the program's counter `experts` as `roofline_blocks.hits_a_pass`
reads it (an admission taken to hit every held expert, which leaves the
decode steps the fewest hits they can have had); the rows read from the
counter `rows` (phase decode, summed over the page groups). None where
the program has no such counter."""
from benchmark import roofline_blocks, roofline_mixed
from benchmark.readers import histogram_mean
from benchmark.readers.flash_decode_floor_share import rows_by_phase
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def read(obs, histogram, experts, rows):
    d = histogram_mean.delta(obs, histogram)
    live = [s for s in obs["steps"] if s.get("running_slots")]
    if d is None or not live or obs.get("peaks") is None:
        return None
    m = obs["model"]
    c = counter_deltas(obs, experts)
    hits = roofline_blocks.hits_a_pass(
        m, c.get('kind="hit"', 0.0), c.get('kind="idle"', 0.0), d[1])
    kv_rows = rows_by_phase(obs, rows)["decode"] / d[1]
    if hits is None or kv_rows <= 0:
        return None
    slots = sum(s["running_slots"] for s in live) / len(live)
    floor = roofline_mixed.decode_step_bytes(m, kv_rows, slots, hits) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (d[0] / d[1])
