"""A Phi-4-mini-flash decode PROGRAM's share of its HBM floor: the least
time a decode step could take on this chip (benchmark/roofline_flash.py:
every weight once, the K and V rows each attending layer reads, the
live slots' state read and written, at the published HBM rate) over the
mean time a decode step took. The rows come from the program's counter
`rows` (phase decode: a sequence's rows from its window's edge to its
context's end, times the layers that read them), so a window layer is
charged a window and each of the layers that share the full layer's
cache the whole context. None where the program has no such counter."""
from benchmark import roofline_flash
from benchmark.readers import histogram_mean
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def rows_by_phase(obs, rows) -> dict:
    """{"decode": n, "admit": n}: the counter's growth over the window,
    summed over the page groups."""
    d = counter_deltas(obs, rows)
    return {p: sum(v for k, v in d.items() if f'phase="{p}"' in k)
            for p in ("decode", "admit")}


def read(obs, histogram, rows):
    d = histogram_mean.delta(obs, histogram)
    live = [s for s in obs["steps"] if s.get("running_slots")]
    if d is None or not live or obs.get("peaks") is None:
        return None
    a_step = rows_by_phase(obs, rows)["decode"] / d[1]
    if a_step <= 0:
        return None
    slots = sum(s["running_slots"] for s in live) / len(live)
    floor = roofline_flash.decode_step_bytes(obs["model"], a_step, slots) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (d[0] / d[1])
