"""The attention kernel's share of its HBM roofline over the traced
span: the bytes of the stored K and V rows that the span's attention
calls had to read (each row once a layer that reads it), at the
published HBM rate, over the device seconds of the operations whose
name matches `pattern`. Numerator and denominator cover the same
dispatches: the program's spans say how many decode steps
(`decode_span`) and admissions (`admit_span`) fell in the traced span,
the last `trace.window_s` seconds of the window, and the program's
counter `rows` gives the rows a decode step and an admission read on
the window's average. An admission's q blocks each walk their
sequence's pages again and the queries, the outputs and the pages
copied beyond a sequence's end are left out, so the bytes are a lower
bound. None where the trace has no such operation or the program no
such counter or spans."""
import re

from benchmark import roofline_flash
from benchmark.readers import histogram_mean
from benchmark.readers.flash_decode_floor_share import rows_by_phase


def read(obs, pattern, rows, histogram, decode_span, admit_span):
    tr = obs.get("trace")
    steps = histogram_mean.delta(obs, histogram)
    if not tr or not tr["window_s"] or not steps \
            or obs.get("peaks") is None:
        return None
    spent = sum(s for name, s in tr["ops_s"].items()
                if re.search(pattern, name))
    by_phase = rows_by_phase(obs, rows)
    if not spent or by_phase["decode"] <= 0:
        return None
    lo = obs["t_close"] - tr["window_s"]
    spans = [s for s in obs.get("spans", ())
             if s["ts_mono"] + s["dur_s"] / 2 <= obs["t_close"]]
    traced = [s["name"] for s in spans if lo <= s["ts_mono"] + s["dur_s"] / 2]
    admissions = sum(s["name"] == admit_span for s in spans)
    read_rows = traced.count(decode_span) * by_phase["decode"] / steps[1]
    if admissions:
        read_rows += traced.count(admit_span) * by_phase["admit"] \
            / admissions
    if not read_rows:
        return None
    floor = read_rows * roofline_flash.kv_row_bytes(obs["model"]) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / spent
