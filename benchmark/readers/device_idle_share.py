"""1 - (union of the intervals in which an operation ran on the device)
/ (traced span), from the profiler's trace of a few seconds inside the
window (benchmark/trace_reduce.py), in percent."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
