"""Profiler. ≙ reference «python/paddle/profiler/» (Profiler + make_scheduler
state machine, RecordEvent spans, chrome trace export, summary tables) and the
C++ host/CUPTI tracers «paddle/fluid/platform/profiler/» (SURVEY.md §5) [U].

TPU-native: device tracing is XLA's XPlane via jax.profiler (TensorBoard /
Perfetto); RecordEvent forwards to jax.profiler.TraceAnnotation so host spans
land in the same timeline. `summary()` renders host-side op statistics
collected by the eager dispatch layer."""
from __future__ import annotations

import enum
import os
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Callable, Iterable, Optional

import jax


class ProfilerState(enum.IntEnum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.IntEnum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """≙ paddle.profiler.make_scheduler: CLOSED(closed)→READY(ready)→
    RECORD(record-1)→RECORD_AND_RETURN, repeating."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos < period - 1:
            return ProfilerState.RECORD
        return ProfilerState.RECORD_AND_RETURN
    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    """on_trace_ready callback: the jax trace directory already contains
    perfetto/chrome-compatible output; this records where it went."""
    os.makedirs(dir_name, exist_ok=True)

    def handle(prof):
        prof._last_export_dir = dir_name
    return handle


def export_protobuf(dir_name: str, worker_name: str | None = None):
    return export_chrome_tracing(dir_name, worker_name)


class RecordEvent:
    """Host span; shows up in the XLA timeline via TraceAnnotation.
    ≙ paddle.profiler.RecordEvent."""

    # name -> [calls, total seconds]: one pair a name, so a serving
    # process that spans for ever holds O(names) memory here
    _host_stats: dict[str, list] = defaultdict(lambda: [0, 0.0])

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self._t0 = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        if self._ann is not None:
            stat = RecordEvent._host_stats[self.name]
            stat[0] += 1
            stat[1] += time.perf_counter() - self._t0
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()
        return False


def load_profiler_result(filename: str):
    raise NotImplementedError(
        "load_profiler_result: inspect the exported TensorBoard/perfetto "
        "trace directory instead (xplane format).")


class Profiler:
    """≙ paddle.profiler.Profiler."""

    def __init__(self, *, targets: Iterable = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, emit_nvtx=False, custom_device_types=None,
                 with_flops=False):
        if scheduler is None:
            self._scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi
                else ProfilerState.CLOSED)
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._trace_dir = None
        self._last_export_dir = None
        self._step_times: list[float] = []
        self._t_last = None
        # per-step HBM accounting (≙ StatAllocator / max_memory_allocated,
        # SURVEY.md §5): sample the live allocator counters at every
        # step() boundary; empty on backends without memory_stats (CPU)
        self._profile_memory = profile_memory
        self._mem_samples: list[dict] = []

    def _sample_memory(self):
        if not self._profile_memory:
            return
        try:
            st = jax.devices()[0].memory_stats() or {}
        except Exception:
            st = {}
        self._mem_samples.append({
            "step": self.step_num,
            "bytes_in_use": st.get("bytes_in_use", 0),
            "peak_bytes_in_use": st.get("peak_bytes_in_use", 0),
        })

    def start(self):
        self._t_last = time.perf_counter()
        self._transition(self._scheduler(self.step_num))

    def stop(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self._on_trace_ready:
                self._on_trace_ready(self)

    def step(self, num_samples: int | None = None):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        self._sample_memory()
        self.step_num += 1
        self._transition(self._scheduler(self.step_num))

    def _transition(self, new_state: ProfilerState):
        if self._timer_only:
            self._state = new_state
            return
        want_trace = new_state in (ProfilerState.RECORD,
                                   ProfilerState.RECORD_AND_RETURN)
        if want_trace and not self._tracing:
            self._trace_dir = self._trace_dir or os.path.join(
                os.getcwd(), "profiler_log")
            os.makedirs(self._trace_dir, exist_ok=True)
            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
        elif not want_trace and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            if self._on_trace_ready:
                self._on_trace_ready(self)
        self._state = new_state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False

    def export(self, path: str, format: str = "json"):
        # jax writes traces at stop_trace time into the trace dir
        pass

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        lines = ["-" * 72,
                 f"{'Host span':40s}{'calls':>8s}{'total(ms)':>12s}"
                 f"{'avg(ms)':>10s}",
                 "-" * 72]
        for name, (calls, total_s) in sorted(
                RecordEvent._host_stats.items(),
                key=lambda kv: -kv[1][1]):
            tot = total_s * 1e3
            lines.append(f"{name[:40]:40s}{calls:8d}{tot:12.3f}"
                         f"{tot / calls:10.3f}")
        if self._step_times:
            st = self._step_times
            lines.append("-" * 72)
            lines.append(
                f"steps: {len(st)}  avg step: {1e3 * sum(st) / len(st):.3f} "
                f"ms  min: {1e3 * min(st):.3f}  max: {1e3 * max(st):.3f}")
        if self._mem_samples and any(
                s["peak_bytes_in_use"] for s in self._mem_samples):
            peak = max(s["peak_bytes_in_use"] for s in self._mem_samples)
            last = self._mem_samples[-1]["bytes_in_use"]
            lines.append(
                f"device memory: peak {peak / 2**20:.1f} MiB, "
                f"in-use (last step) {last / 2**20:.1f} MiB "
                f"({len(self._mem_samples)} samples)")
        elif self._profile_memory:
            lines.append("device memory: allocator stats unavailable on "
                         "this backend (use utils.memory."
                         "compiled_memory_stats for AOT numbers)")
        if self._trace_dir:
            lines.append(f"device trace (XPlane): {self._trace_dir} — view "
                         f"with TensorBoard or Perfetto")
        out = "\n".join(lines)
        print(out)
        return out


@contextmanager
def profile_span(name: str):
    with RecordEvent(name):
        yield
