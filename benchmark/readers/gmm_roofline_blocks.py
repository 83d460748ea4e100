"""The grouped matmul's share of its HBM roofline over the traced span
for a block model, as `gmm_roofline` reckons it but from
`roofline_blocks`: the bytes of the experts' weights that the span's
dispatches had to read, at the published HBM rate, over the device
seconds of the operations whose name matches `pattern`. The program's
spans say how many passes (`decode_span`) and admissions (`admit_span`)
fell in the traced span, the last `trace.window_s` seconds of the
window; an admission is taken to hit EVERY held expert once, a pass what
the window's counter leaves it (`roofline_blocks.hits_a_pass`). Rows,
outputs and a second read of an expert with more rows than a tile are
left out, so the bytes are a lower bound. None where the trace has no
such operation or the program no such counter or spans."""
import re

from benchmark import roofline_blocks
from benchmark.readers import histogram_mean
from benchmark.readers.hybrid_decode_floor_share import counter_deltas


def read(obs, pattern, experts, histogram, decode_span, admit_span):
    tr = obs.get("trace")
    steps = histogram_mean.delta(obs, histogram)
    if not tr or not tr["window_s"] or not steps \
            or obs.get("peaks") is None:
        return None
    spent = sum(s for name, s in tr["ops_s"].items()
                if re.search(pattern, name))
    m = obs["model"]
    c = counter_deltas(obs, experts)
    a_pass = roofline_blocks.hits_a_pass(
        m, c.get('kind="hit"', 0.0), c.get('kind="idle"', 0.0), steps[1])
    if not spent or a_pass is None:
        return None
    lo = obs["t_close"] - tr["window_s"]
    traced = [s["name"] for s in obs.get("spans", ())
              if lo <= s["ts_mono"] + s["dur_s"] / 2 <= obs["t_close"]]
    hits = traced.count(decode_span) * a_pass \
        + traced.count(admit_span) * roofline_blocks.experts_a_dispatch(m)
    if not hits:
        return None
    floor = hits * roofline_blocks.expert_bytes(m) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / spent
