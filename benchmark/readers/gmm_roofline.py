"""The grouped matmul's share of its HBM roofline over the traced span:
the bytes of the routed experts' weights that the span's dispatches had
to read, at the published HBM rate, over the device seconds of the
operations whose name matches `pattern`. Numerator and denominator
cover the same dispatches: the program's spans say how many decode
steps (`decode_span`) and admissions (`admit_span`) fell in the traced
span, the last `trace.window_s` seconds of the window; an admission is
taken to hit EVERY held expert once, which leaves a decode step the
fewest hits the window's counter allows
(`hybrid_decode_floor_share.hits_a_decode_step`). Rows, outputs and an
admission's second read of an expert with more rows than a tile are
left out, so the bytes are a lower bound. None where the trace has no
such operation or the program no such counter or spans."""
import re

from benchmark import roofline_hybrid
from benchmark.readers import histogram_mean
from benchmark.readers.hybrid_decode_floor_share import hits_a_decode_step


def read(obs, pattern, experts, histogram, decode_span, admit_span):
    tr = obs.get("trace")
    steps = histogram_mean.delta(obs, histogram)
    if not tr or not tr["window_s"] or not steps \
            or obs.get("peaks") is None:
        return None
    spent = sum(s for name, s in tr["ops_s"].items()
                if re.search(pattern, name))
    a_decode_step = hits_a_decode_step(obs, experts, steps[1])
    if not spent or a_decode_step is None:
        return None
    lo = obs["t_close"] - tr["window_s"]
    traced = [s["name"] for s in obs.get("spans", ())
              if lo <= s["ts_mono"] + s["dur_s"] / 2 <= obs["t_close"]]
    m = obs["model"]
    hits = traced.count(decode_span) * a_decode_step \
        + traced.count(admit_span) \
        * roofline_hybrid.kinds(m).count("E") * m["experts_held"]
    if not hits:
        return None
    floor = hits * roofline_hybrid.expert_bytes(m) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / spent
