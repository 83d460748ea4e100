"""Performance attribution: the fleet step's self-time table,
compile-cache observability, and the memory ledger.

Three surfaces, all in the PR-2 tradition (stdlib+jax only, guaranteed
no-op unless telemetry is enabled):

* **Self-time table** — the fleet step is ONE span tree
  (`router.step` -> `router.replica_step` -> `serving.step` ->
  `serving.admit` / `serving.decode` / `serving.commit` / ..., drawn in
  docs/observability.md) and `trace.span` observes each span's self
  time into `pdt_span_self_seconds{name}`. Self times are disjoint and
  add up to the root's duration, so `span_summary()` of a snapshot IS
  the step's decomposition: no second set of clock reads, and the time
  a dispatch waits for the device sits in the span round the dispatch
  (`serving.ragged_prefill`, `serving.decode_step`,
  `serving.harvest`), never in its host parent.
* **Compile-cache observability** — `compile_timed()` wraps every
  program the engine's `_jit_lru`/`_jit_singleton` seam builds: the
  first invocation (the one that traces and compiles) is metered as
  `pdt_jit_compiles_total{family}` + `pdt_jit_compile_seconds` under a
  `jit.compile` span, cache footprints ride
  `pdt_jit_cache_entries{family}` / `pdt_jit_cache_evictions_total`,
  and a sliding-window retrace-storm detector emits the
  `profile.retrace_storm` event (+ `pdt_jit_retrace_storms_total`)
  when program-key churn drives compiles past a threshold — the
  failure mode the pow2 bucketing exists to prevent, now detectable.
* **Memory ledger** — `memory_ledger()` folds `cache_memory_info`,
  draft pools, prefix-store spill bytes, and model-store residency
  into the one `pdt_mem_bytes{pool}` family, surfaced by
  `fleet_info()["perf"]` and `render_fleet_status`.

`render_profile_report(snapshot)` renders all three surfaces from any
saved snapshot — the `paddle-tpu-obs profile` CLI, the post-kill-drill
report in `recipes/llama_serve.py`, and failing-test attachments in
`tests/conftest.py` all print the same text.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List, Optional

from . import registry as _registry
from . import trace as _trace
from .registry import counter, gauge, histogram

__all__ = ["compile_timed", "note_cache",
           "configure_retrace", "retrace_window",
           "memory_ledger", "perf_section",
           "span_summary", "compile_summary", "mem_summary",
           "render_profile_report", "snapshot_report"]

_M_JIT_COMPILES = counter(
    "pdt_jit_compiles_total",
    "Programs compiled through the _jit_lru/_jit_singleton seam "
    "(first invocation of a freshly built jit), by program family.",
    ("family",))
_M_JIT_COMPILE_SECONDS = histogram(
    "pdt_jit_compile_seconds",
    "Wall seconds of a program's first invocation — trace + compile + "
    "first execute, the honest cold-start bill.", ("family",))
_M_JIT_KERNELS = counter(
    "pdt_jit_mosaic_kernels_total",
    "Mosaic (Pallas TPU) kernel custom calls in the programs compiled "
    "through the metered seam, read from each program's lowered text — "
    "zero for a family means its dispatchers took the XLA reference or "
    "interpret path.", ("family", "kernel"))
_M_JIT_CACHE = gauge(
    "pdt_jit_cache_entries",
    "Programs resident in a keyed-LRU jit cache, by family.",
    ("family",))
_M_JIT_EVICTIONS = counter(
    "pdt_jit_cache_evictions_total",
    "Programs evicted from a keyed-LRU jit cache past its cap, by "
    "family.", ("family",))
_M_RETRACE_STORMS = counter(
    "pdt_jit_retrace_storms_total",
    "Retrace-storm detections: sliding-window compile count exceeded "
    "the storm threshold (program-key churn).")
_M_MEM = gauge(
    "pdt_mem_bytes",
    "Memory ledger: bytes held per accounting pool (KV pools, draft "
    "pools, prefix-store spill, model-store residency).", ("pool",))


# -- compile-cache observability --------------------------------------

class _RetraceWindow:
    """Sliding-window compile counter: a storm is >= `threshold`
    compiles inside `window_s` seconds. The clock is injectable for
    tests; detection is re-armed only after the window drains below
    half the threshold, so one sustained churn episode fires once per
    window rather than once per compile."""

    def __init__(self, window_s: float = 30.0, threshold: int = 10,
                 clock: Callable[[], float] = time.monotonic):
        self.window_s = float(window_s)
        self.threshold = int(threshold)
        self.clock = clock
        self._times: deque = deque()
        self._families: deque = deque()
        self._armed = True

    def note(self, family: str) -> bool:
        """Record one compile; True when this compile tripped a storm."""
        now = self.clock()
        self._times.append(now)
        self._families.append(family)
        while self._times and now - self._times[0] > self.window_s:
            self._times.popleft()
            self._families.popleft()
        n = len(self._times)
        if n < self.threshold:
            if n <= self.threshold // 2:
                self._armed = True
            return False
        if not self._armed:
            return False
        self._armed = False
        fams: Dict[str, int] = {}
        for f in self._families:
            fams[f] = fams.get(f, 0) + 1
        _M_RETRACE_STORMS.inc()
        _trace.event("profile.retrace_storm", compiles=n,
                     window_s=self.window_s,
                     threshold=self.threshold,
                     families=",".join(f"{k}={v}"
                                       for k, v in sorted(fams.items())))
        return True

    def count(self) -> int:
        now = self.clock()
        while self._times and now - self._times[0] > self.window_s:
            self._times.popleft()
            self._families.popleft()
        return len(self._times)


_RETRACE = _RetraceWindow()


def retrace_window() -> _RetraceWindow:
    return _RETRACE


def configure_retrace(window_s: Optional[float] = None,
                      threshold: Optional[int] = None,
                      clock: Optional[Callable[[], float]] = None) \
        -> _RetraceWindow:
    """Replace the process-wide retrace-storm detector (tests inject a
    fake clock / low threshold; returns the new window)."""
    global _RETRACE
    cur = _RETRACE
    _RETRACE = _RetraceWindow(
        window_s=cur.window_s if window_s is None else window_s,
        threshold=cur.threshold if threshold is None else threshold,
        clock=cur.clock if clock is None else clock)
    return _RETRACE


def compile_timed(fn, family: str, key=None):
    """Wrap a freshly built (never-invoked) ``jax.jit`` callable so its
    FIRST invocation — the one that traces and compiles — is metered:
    `pdt_jit_compiles_total{family}` / `pdt_jit_compile_seconds` under
    a `jit.compile` span, feeding the retrace-storm window. The same
    call lowers the program once more (the trace is shared with the
    invocation, so it costs the text dump only) and records the HLO
    module's name (the span's `module` attr) and which Mosaic kernels
    it contains: `pdt_jit_mosaic_kernels_total{family,
    kernel}` and the span's `mosaic_kernels` attr — what says
    afterwards whether a dispatcher ran its kernel or gave way to its
    reference. Later invocations pay one boolean check. The engine's
    `_jit_lru` / `_jit_singleton` seam routes every cached program
    through here (pdt-lint PDT012 pins that), so compile observability
    cannot be bypassed."""
    state = [True]

    def _first_call_timed(*args, **kwargs):
        if not state[0]:
            return fn(*args, **kwargs)
        state[0] = False
        if not _registry.enabled():
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        with _trace.span("jit.compile", family=family,
                         key="" if key is None else str(key)) as sp:
            if hasattr(fn, "lower"):
                from ..ops import mosaic_kernels
                text = fn.lower(*args, **kwargs).as_text()
                # `jit_pdt_decode`: the name the seam gave the program,
                # as the profiler's `XLA Modules` line will show it
                sp.attrs["module"] = text[8:text.find(" ", 8)] \
                    if text.startswith("module @") else ""
                kernels = mosaic_kernels(text)
                sp.attrs["mosaic_kernels"] = kernels
                for name, n in kernels.items():
                    _M_JIT_KERNELS.inc(n, family=family, kernel=name)
            out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        _M_JIT_COMPILES.inc(family=family)
        _M_JIT_COMPILE_SECONDS.observe(dt, family=family)
        _RETRACE.note(family)
        return out

    return _first_call_timed


def note_cache(family: str, entries: int, evicted: int = 0) -> None:
    """Record a keyed-LRU cache's footprint after a miss/evict pass."""
    if not _registry.enabled():
        return
    _M_JIT_CACHE.set(entries, family=family)
    if evicted:
        _M_JIT_EVICTIONS.inc(evicted, family=family)


# -- memory ledger -----------------------------------------------------

def _engine_pools(engine) -> Dict[str, float]:
    pools = {"kv_pool": 0.0, "kv_in_use": 0.0}
    info = engine.cache_memory_info()
    pools["kv_pool"] += float(info.get("bytes_pool", 0))
    pools["kv_in_use"] += float(info.get("bytes_in_use", 0))
    d_kv = getattr(engine, "_d_kv", None)
    if d_kv:
        pools["draft_pool"] = float(sum(
            sum(int(arr.nbytes) for arr in entry) for entry in d_kv))
    return pools


def memory_ledger(engines=(), prefix_store=None,
                  model_store=None) -> Dict[str, float]:
    """Fold the fleet's memory accounting into the one
    `pdt_mem_bytes{pool}` family (gauges set as a side effect when
    telemetry is on) and return the pool -> bytes dict."""
    pools: Dict[str, float] = {}
    for eng in engines:
        if eng is None:
            continue
        for name, v in _engine_pools(eng).items():
            pools[name] = pools.get(name, 0.0) + v
    if prefix_store is not None:
        pools["prefix_spill"] = float(
            prefix_store.stats().get("spilled_bytes", 0))
    if model_store is not None:
        resident = model_store.stats().get("resident_bytes", {})
        pools["model_store"] = float(sum(resident.values()))
    for name, v in pools.items():
        _M_MEM.set(v, pool=name)
    return pools


def perf_section(engines=(), prefix_store=None,
                 model_store=None) -> Dict[str, object]:
    """The `fleet_info()["perf"]` section: the memory ledger plus the
    compile-cache counters, read from the live registry (zeros when
    telemetry is off — the ledger itself is computed either way)."""
    mem = memory_ledger(engines, prefix_store=prefix_store,
                        model_store=model_store)
    jit: Dict[str, Dict[str, float]] = {}
    for fam_series, key in ((_M_JIT_COMPILES, "compiles"),
                            (_M_JIT_CACHE, "entries"),
                            (_M_JIT_EVICTIONS, "evictions")):
        for labels, v in fam_series._series.items():
            fam = labels[0] if labels else ""
            jit.setdefault(fam, {})[key] = float(v)
    return {"mem_bytes": mem, "jit": jit,
            "retrace_storms": _M_RETRACE_STORMS.get()}


# -- snapshot report rendering ----------------------------------------

def _label_value(labels: str) -> str:
    return labels.split('"')[1] if '"' in labels else labels


def span_summary(snapshot: Dict[str, object]) -> Dict[str, dict]:
    """span name -> {count, total_s} of self time, from a snapshot's
    `pdt_span_self_seconds` series."""
    series = snapshot.get("histograms", {}).get(
        "pdt_span_self_seconds", {})
    return {_label_value(labels): {"count": int(s["count"]),
                                   "total_s": float(s["sum"])}
            for labels, s in series.items() if s.get("count")}


def compile_summary(snapshot: Dict[str, object]) -> Dict[str, dict]:
    """family -> {compiles, compile_s, entries, evictions}."""
    out: Dict[str, dict] = {}
    for labels, v in snapshot.get("counters", {}).get(
            "pdt_jit_compiles_total", {}).items():
        out.setdefault(_label_value(labels), {})["compiles"] = int(v)
    for labels, s in snapshot.get("histograms", {}).get(
            "pdt_jit_compile_seconds", {}).items():
        out.setdefault(_label_value(labels), {})["compile_s"] = \
            float(s.get("sum", 0.0))
    for labels, v in snapshot.get("gauges", {}).get(
            "pdt_jit_cache_entries", {}).items():
        out.setdefault(_label_value(labels), {})["entries"] = int(v)
    for labels, v in snapshot.get("counters", {}).get(
            "pdt_jit_cache_evictions_total", {}).items():
        out.setdefault(_label_value(labels), {})["evictions"] = int(v)
    return out


def mem_summary(snapshot: Dict[str, object]) -> Dict[str, float]:
    return {_label_value(labels): float(v)
            for labels, v in snapshot.get("gauges", {}).get(
                "pdt_mem_bytes", {}).items()}


def _fmt_s(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v >= 0.1:
        return f"{v:.3f}s"
    return f"{v * 1e3:.3f}ms"


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024 or unit == "GiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024.0
    return f"{v:.1f}GiB"


def render_profile_report(snapshot: Dict[str, object]) -> str:
    """The one profile report (self-time waterfall + compile table +
    memory ledger) from any saved snapshot — shared by
    the `paddle-tpu-obs profile` CLI, the recipes, and failing-test
    attachments. The waterfall is one row a span name: self seconds,
    count, and the share of the fleet step — the self times of the
    step's spans (`router.*`, `serving.*`, `jit.compile`) add up to
    the duration of their root, `router.step` (`serving.step` where no
    router ran). Sections with no data are omitted; an entirely empty
    report renders a one-line notice."""
    lines: List[str] = []
    spans = span_summary(snapshot)
    if spans:
        root = next((r for r in ("router.step", "serving.step")
                     if r in spans), None)
        in_step = [n for n in spans if root and n.startswith(
            ("router.", "serving.", "jit.compile"))]
        total = sum(spans[n]["total_s"] for n in in_step)
        lines.append("span self time" + (f" (share of {root})"
                                         if root else ""))
        for name in sorted(spans, key=lambda n: -spans[n]["total_s"]):
            r = spans[name]
            tail = ""
            if name in in_step and total > 0:
                share = 100.0 * r["total_s"] / total
                tail = f" ({share:5.1f}%) " \
                    + "#" * max(int(round(share / 4)), 1)
            lines.append(f"  {name:<24} {_fmt_s(r['total_s']):>10} "
                         f"{r['count']:>8}x{tail}")
    compiles = compile_summary(snapshot)
    if compiles:
        lines.append("compile cache")
        lines.append(f"  {'family':<14} {'compiles':>8} "
                     f"{'compile_s':>10} {'entries':>8} {'evicted':>8}")
        for fam in sorted(compiles):
            c = compiles[fam]
            lines.append(
                f"  {fam:<14} {c.get('compiles', 0):>8} "
                f"{c.get('compile_s', 0.0):>10.3f} "
                f"{c.get('entries', 0):>8} {c.get('evictions', 0):>8}")
        storms = snapshot.get("counters", {}).get(
            "pdt_jit_retrace_storms_total", {}).get("")
        if storms:
            lines.append(f"  retrace storms: {int(storms)}")
    mem = mem_summary(snapshot)
    if mem:
        lines.append("memory ledger")
        for pool in sorted(mem):
            lines.append(f"  {pool:<14} {_fmt_bytes(mem[pool]):>12}")
    if not lines:
        return ("no profile data in snapshot (pdt_span_self_seconds/"
                "pdt_jit_*/pdt_mem_* series absent)")
    return "\n".join(lines)


def snapshot_report() -> str:
    """`render_profile_report` of the LIVE registry."""
    return render_profile_report(_registry.snapshot())
