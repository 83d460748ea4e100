"""From a profiler trace to numbers: device busy time, the operations
that took most of it, and the longest idle gaps with what the host was
doing in each.

`load` turns an `.xplane.pb` into plain data (`jax.profiler.ProfileData`,
nothing but JAX): `[{"name": plane, "lines": [{"name": line, "events":
[(name, start_ns, duration_ns), ...]}]}]`. `reduce` works on that plain
form, so `benchmark/tests/test_trace_reduce.py` checks it on a plane
made by hand.

What the trace of this program looks like (looked at by hand, PERF.md
section 5): one plane per chip, `/device:TPU:<n>`, whose line `XLA Ops`
holds one event per device operation (fusions, custom calls, copies),
whose line `Async XLA Ops` holds the DMAs that run beside them and
whose line `XLA Modules` holds one event per program run; the host
plane `/host:CPU` holds one line per thread, with the harness's own
`jax.profiler.TraceAnnotation`s (`bench.*`) on the main thread's. All
planes share one clock. An operation's name is its whole HLO line
(`%ragged_paged_attention.24 = bf16[...] custom-call(...)`): `stem`
keeps what is before ` = ` and drops the instance number, so that the
24 layers' calls of one kernel add up under one name.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_MARK = "bench."


def load(trace_dir: str) -> list:
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def describe(planes: list, per_line: int = 3) -> list:
    """Plane and line names with event counts and a few event names:
    what to look at by hand before trusting a pattern."""
    out = []
    for p in planes:
        for ln in p["lines"]:
            names = []
            for name, _, _ in ln["events"]:
                if name not in names:
                    names.append(name)
                if len(names) >= per_line:
                    break
            out.append({"plane": p["name"], "line": ln["name"],
                        "events": len(ln["events"]), "first_names": names})
    return out


def stem(name: str) -> str:
    """`%fusion.123 = f32[...] fusion(...)` -> `fusion`."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def union(intervals) -> list:
    """Merge [start, end) intervals; overlapping and nested ones count
    once."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _host_marks(planes) -> list:
    return [(n, s, s + d) for p in planes
            if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith(HOST_MARK)]


def _gap_owner(marks, lo, hi) -> str:
    """The harness annotation that covers most of [lo, hi)."""
    best, best_cov = "unmarked", 0
    for name, s, e in marks:
        cov = min(e, hi) - max(s, lo)
        if cov > best_cov:
            best, best_cov = name, cov
    return best


def reduce(planes: list, top: int = 10, gaps: int = 5) -> dict | None:
    """`{"busy_s", "window_s", "chips", "ops_s", "device_ops",
    "idle_gaps"}`: `ops_s` is every operation's seconds by `stem`,
    `device_ops` the `top` largest of them.
    The window is the span of the harness's own annotations where the
    trace holds any (the loop is tiled by them), else the span of the
    device events. Busy is the union of the device operations' intervals
    inside it, averaged over the chips that ran any. Returns None when
    no operation ran on a device."""
    devices = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE] \
            or p["lines"]
        ev = [e for ln in ops for e in ln["events"]]
        if ev:
            devices.append(ev)
    if not devices:
        return None
    marks = _host_marks(planes)
    if marks:
        lo = min(s for _, s, _ in marks)
        hi = max(e for _, _, e in marks)
    else:
        lo = min(s for ev in devices for _, s, _ in ev)
        hi = max(s + d for ev in devices for _, s, d in ev)
    busy_ns, by_name, idle = 0, {}, []
    for ev in devices:
        merged = union(_clip([(s, s + d) for _, s, d in ev], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for name, s, d in ev:
            got = _clip([(s, s + d)], lo, hi)
            if got:
                key = stem(name)
                by_name[key] = by_name.get(key, 0) \
                    + got[0][1] - got[0][0]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "chips": n,
        "ops_s": {name: ns / n / 1e9 for name, ns in by_name.items()},
        "device_ops": [[name, ns / n / 1e9] for name, ns in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_gap_owner(marks, s, e), (e - s) / 1e9]
                      for s, e in idle[:gaps]],
    }
