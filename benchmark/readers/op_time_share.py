"""Seconds of the device operations whose name (benchmark/trace_reduce.py
`stem`) matches `pattern`, over the traced window, in percent. The
pattern sits in the metric's own file."""
import re


def read(obs, pattern):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    hit = [s for name, s in tr["ops_s"].items() if re.search(pattern, name)]
    if not hit:
        return None            # no such operation in the trace
    return 100.0 * sum(hit) / tr["window_s"]
