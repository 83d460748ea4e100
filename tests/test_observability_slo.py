"""Observability PR 5 unit tests (fast tier, `telemetry` marker):
quantile math (reservoir path golden-checked against numpy.percentile,
le-bucket interpolation golden-checked by hand), rolling-window expiry,
SLO burn-rate grading (pass/warn/breach ladder), the one-clock trace
model + request-tree reconstruction + Chrome export schema, the
operator CLI round trips, the bench regression gate, and two
lint-style drift guards: fault sites documented in `utils/faults.py`
must equal the `fault_point()` call sites in the source, and the
metric catalog in docs/observability.md must equal the instruments
actually registered. conftest enables PDT_TELEMETRY=1 and zeroes the
registry/ring for every test in this file."""
import json
import math
import os
import re

import numpy as np
import pytest

import paddle_tpu.observability as telemetry
from paddle_tpu.observability import slo as slo_mod
from paddle_tpu.observability import trace as trace_mod
from paddle_tpu.observability.__main__ import main as cli_main
from paddle_tpu.observability.slo import (Reservoir, SloMonitor,
                                          SloObjective,
                                          fraction_over_threshold,
                                          objectives_from_spec,
                                          quantile_from_buckets)

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# -- quantile math -----------------------------------------------------
class TestQuantileMath:
    def test_reservoir_quantile_matches_numpy_percentile(self):
        """Golden contract of the exact path: linear interpolation,
        bit-for-bit numpy.percentile."""
        rng = np.random.default_rng(0)
        vals = rng.uniform(0.0, 2.0, 37).tolist()
        r = Reservoir(window_s=1e9, clock=FakeClock())
        for v in vals:
            r.observe(v)
        for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
            want = float(np.percentile(vals, q * 100))
            assert r.quantile(q) == pytest.approx(want, abs=1e-12), q

    def test_bucket_interpolation_golden_values(self):
        buckets = {"0.1": 5, "1": 10, "+Inf": 10}
        assert quantile_from_buckets(buckets, 0.5) \
            == pytest.approx(0.1)           # rank 5 = first boundary
        assert quantile_from_buckets(buckets, 0.75) \
            == pytest.approx(0.55)          # halfway into (0.1, 1]
        assert quantile_from_buckets(buckets, 1.0) == pytest.approx(1.0)
        assert quantile_from_buckets(buckets, 0.25) \
            == pytest.approx(0.05)          # halfway into [0, 0.1]

    def test_quantile_in_inf_bucket_clamps_to_highest_finite(self):
        buckets = {"0.1": 5, "+Inf": 10}
        assert quantile_from_buckets(buckets, 0.9) == pytest.approx(0.1)

    def test_empty_and_invalid(self):
        assert quantile_from_buckets({}, 0.5) is None
        assert quantile_from_buckets({"+Inf": 0}, 0.5) is None
        with pytest.raises(ValueError):
            quantile_from_buckets({"+Inf": 1}, 1.5)
        assert Reservoir(clock=FakeClock()).quantile(0.5) is None

    def test_fraction_over_threshold_interpolates(self):
        buckets = {"0.1": 9, "1": 10, "+Inf": 10}
        # cumulative at 0.5 = 9 + (0.5-0.1)/0.9 -> over = (10-at)/10
        want = (10 - (9 + (0.5 - 0.1) / 0.9)) / 10
        assert fraction_over_threshold(buckets, 0.5) \
            == pytest.approx(want)
        assert fraction_over_threshold(buckets, 2.0) == 0.0
        assert fraction_over_threshold({}, 0.5) is None

    def test_unresolvable_threshold_counts_inf_mass_as_over(self):
        """A threshold beyond the highest finite boundary cannot be
        placed against the +Inf mass — that mass must count as OVER
        (conservative), never as a confident pass."""
        buckets = {"0.1": 9, "+Inf": 10}       # 1 sample is ">0.1s"
        assert fraction_over_threshold(buckets, 5.0) \
            == pytest.approx(0.1)
        # and through the monitor's histogram path: every sample in
        # +Inf with a threshold twice the top boundary -> breach
        h = telemetry.histogram("t_slo_inf_seconds", buckets=(0.1,))
        for _ in range(10):
            h.observe(300.0)
        mon = SloMonitor(
            [SloObjective("p95", "lat", "latency", 0.2, quantile=0.95,
                          metric="t_slo_inf_seconds")],
            clock=FakeClock())
        st = mon.evaluate()["p95"]
        assert st.source == "histogram" and st.state == "breach"
        assert st.burn_rate == pytest.approx(20.0)


class TestReservoirWindow:
    def test_window_expiry_drops_old_samples(self):
        clk = FakeClock()
        r = Reservoir(window_s=10.0, clock=clk)
        for v in (1.0, 2.0, 3.0):
            r.observe(v)
        clk.advance(5.0)
        r.observe(100.0)
        assert sorted(r.values()) == [1.0, 2.0, 3.0, 100.0]
        clk.advance(6.0)                     # t=11: the t=0 batch ages out
        assert r.values() == [100.0]
        assert r.quantile(0.5) == 100.0
        clk.advance(10.0)                    # t=21: everything gone
        assert r.quantile(0.5) is None

    def test_sample_cap_bounds_memory(self):
        r = Reservoir(window_s=1e9, max_samples=3, clock=FakeClock())
        for v in range(10):
            r.observe(float(v))
        assert r.values() == [7.0, 8.0, 9.0]


# -- SLO grading -------------------------------------------------------
def _latency_obj(**kw):
    kw.setdefault("window_s", 60.0)
    return SloObjective("lat_p90", "lat", "latency", 0.1,
                        quantile=0.9, **kw)


class TestSloMonitor:
    def test_burn_rate_ladder_pass_warn_breach(self):
        clk = FakeClock()
        for n_over, want_state, want_burn in ((0, "pass", 0.0),
                                              (1, "warn", 0.5),
                                              (4, "breach", 2.0)):
            mon = SloMonitor([_latency_obj()], clock=clk, warn_burn=0.5)
            for i in range(20):
                mon.observe("lat", 0.5 if i < n_over else 0.01)
            st = mon.evaluate()["lat_p90"]
            # budget = 1 - 0.9 = 10% of samples allowed past 0.1s
            assert st.state == want_state, (n_over, st)
            assert st.burn_rate == pytest.approx(want_burn)
            assert st.source == "reservoir" and st.samples == 20
            assert st.value == pytest.approx(float(np.percentile(
                [0.5 if i < n_over else 0.01 for i in range(20)], 90)))

    def test_window_expiry_clears_breach(self):
        clk = FakeClock()
        mon = SloMonitor([_latency_obj()], clock=clk)
        for _ in range(10):
            mon.observe("lat", 1.0)
        assert mon.evaluate()["lat_p90"].state == "breach"
        clk.advance(61.0)
        mon.observe("lat", 0.01)
        st = mon.evaluate()["lat_p90"]
        assert st.state == "pass" and st.samples == 1

    def test_ratio_objectives_error_rate_and_availability(self):
        clk = FakeClock()
        mon = SloMonitor(
            [SloObjective("err", "outcome", "error_rate", 0.2),
             SloObjective("avail", "outcome", "availability", 0.95)],
            clock=clk, warn_burn=0.5)
        for i in range(10):
            mon.observe_outcome("outcome", ok=i != 0)
        rep = mon.evaluate()
        # 1 bad / 10: error budget 0.2 -> burn 0.5 (warn);
        # availability budget 1-0.95 -> burn 2.0 (breach)
        assert rep["err"].state == "warn"
        assert rep["err"].value == pytest.approx(0.1)
        assert rep["err"].burn_rate == pytest.approx(0.5)
        assert rep["avail"].state == "breach"
        assert rep["avail"].value == pytest.approx(0.9)
        assert rep["avail"].burn_rate == pytest.approx(2.0)

    def test_no_data_grades_pass(self):
        mon = SloMonitor([_latency_obj()], clock=FakeClock())
        st = mon.evaluate()["lat_p90"]
        assert st.state == "pass" and st.value is None \
            and st.source == "none"

    def test_histogram_fallback_when_reservoir_empty(self):
        h = telemetry.histogram("t_slo_fb_seconds", buckets=(0.1, 1.0))
        for _ in range(9):
            h.observe(0.05)
        h.observe(0.9)
        mon = SloMonitor(
            [SloObjective("p90", "lat", "latency", 0.5, quantile=0.9,
                          metric="t_slo_fb_seconds")],
            clock=FakeClock(), warn_burn=0.5)
        st = mon.evaluate()["p90"]
        assert st.source == "histogram" and st.samples == 10
        # ~0.056 of mass interpolates past 0.5 on a 0.1 budget -> warn
        assert st.state == "warn"
        assert st.burn_rate == pytest.approx(0.5556, abs=1e-3)

    def test_gauges_exported(self):
        mon = SloMonitor([_latency_obj()], clock=FakeClock())
        for _ in range(10):
            mon.observe("lat", 1.0)
        mon.evaluate()
        assert telemetry.value("pdt_slo_state", objective="lat_p90") \
            == slo_mod.STATE_CODE["breach"]
        assert telemetry.value("pdt_slo_burn_rate",
                               objective="lat_p90") \
            == pytest.approx(10.0)
        assert telemetry.value("pdt_slo_value",
                               objective="lat_p90") == 1.0

    def test_zero_budget_burn_exports_finite_cap(self):
        """An infinite burn (zero-tolerance objective violated) must
        export as a huge FINITE gauge value: a `burn > 1` alert rule
        has to fire, and the text exposition must stay renderable."""
        mon = SloMonitor(
            [SloObjective("zero_err", "outcome", "error_rate", 0.0)],
            clock=FakeClock())
        mon.observe_outcome("outcome", ok=False)
        st = mon.evaluate()["zero_err"]
        assert st.state == "breach" and math.isinf(st.burn_rate)
        assert telemetry.value("pdt_slo_burn_rate",
                               objective="zero_err") == 1e9
        assert "inf" in mon.report()
        telemetry.parse_prometheus(telemetry.to_prometheus())

    def test_replica_state_grades_each_slice(self):
        clk = FakeClock()
        mon = SloMonitor([_latency_obj()], clock=clk)
        for _ in range(5):
            mon.observe("lat", 0.01, replica="0")
            mon.observe("lat", 1.0, replica="1")
        assert mon.replica_state("0") == "pass"
        assert mon.replica_state("1") == "breach"
        assert mon.replica_state("2") is None    # never contributed

    def test_spec_round_trip_and_validation(self, tmp_path):
        spec = [{"name": "a", "signal": "ttft", "kind": "latency",
                 "threshold": 0.25, "quantile": 0.5, "window_s": 30.0}]
        objs = objectives_from_spec(spec)
        assert objs[0] == SloObjective("a", "ttft", "latency", 0.25,
                                       quantile=0.5, window_s=30.0)
        p = tmp_path / "spec.json"
        p.write_text(json.dumps(spec))
        assert objectives_from_spec(str(p)) == objs
        with pytest.raises(ValueError, match="unknown keys"):
            objectives_from_spec([{"name": "x", "signal": "s",
                                   "kind": "latency", "threshold": 1,
                                   "typo": 1}])
        with pytest.raises(ValueError, match="unknown kind"):
            SloObjective("x", "s", "meanness", 1.0)
        with pytest.raises(ValueError, match="already added"):
            SloMonitor([_latency_obj(), _latency_obj()])


# -- trace model -------------------------------------------------------
class TestTraceClock:
    def test_events_share_one_monotonic_base(self):
        """The satellite fix: a child event's timestamps must be
        directly comparable with its parent span's — same clock, same
        base — so durations reconstruct from the JSONL alone."""
        with telemetry.span("outer"):
            telemetry.event("mid")
        mid, outer = telemetry.events()
        assert outer["name"] == "outer" and mid["name"] == "mid"
        assert outer["ts_mono"] <= mid["ts_mono"] \
            <= outer["ts_mono"] + outer["dur_s"]
        # wall ts is DERIVED from ts_mono via one base pair: deltas agree
        assert (mid["ts"] - outer["ts"]) == pytest.approx(
            mid["ts_mono"] - outer["ts_mono"], abs=1e-6)

    def test_file_sink_carries_ts_mono(self, tmp_path):
        sink = tmp_path / "t.jsonl"
        telemetry.set_trace_file(str(sink))
        try:
            with telemetry.span("sunk"):
                pass
        finally:
            telemetry.set_trace_file(None)
        line = json.loads(sink.read_text().strip())
        assert {"ts", "ts_mono", "dur_s", "seq", "parent",
                "trace"} <= set(line)


class TestRequestTrace:
    def test_request_id_attr_joins_trace_automatically(self):
        tid = telemetry.start_trace("r-1", name="router.submit")
        with telemetry.span("router.dispatch", request_id="r-1",
                            replica=0):
            pass
        with telemetry.span("router.replica_step", replica=0):
            with telemetry.span("serving.prefill", request_id="r-1"):
                pass
        telemetry.event("router.terminal", request_id="r-1",
                        status="finished")
        evs = {e["name"]: e for e in telemetry.events()}
        root = evs["router.submit"]
        assert root["trace"] == tid and root["parent"] is None
        assert evs["router.dispatch"]["trace"] == tid
        assert evs["router.dispatch"]["parent"] == root["seq"]
        # nested under the replica span: LOCAL parent wins, trace joins
        prefill = evs["serving.prefill"]
        assert prefill["trace"] == tid
        assert prefill["parent"] == evs["router.replica_step"]["seq"]
        assert evs["router.replica_step"]["trace"] is None
        assert evs["router.terminal"]["parent"] == root["seq"]

    def test_attach_and_end_trace(self):
        telemetry.start_trace("r-2")
        with telemetry.trace_attach("r-2"):
            with telemetry.span("inner"):
                pass
        telemetry.end_trace("r-2")
        with telemetry.span("after", request_id="r-2"):
            pass
        evs = {e["name"]: e for e in telemetry.events()}
        assert evs["inner"]["trace"] == telemetry.events()[0]["trace"]
        assert evs["inner"]["parent"] == telemetry.events()[0]["seq"]
        assert evs["after"]["trace"] is None   # carrier dropped

    def test_tree_reconstruction_with_decode_fanin(self):
        telemetry.start_trace("r-3", name="router.submit")
        with telemetry.span("router.dispatch", request_id="r-3",
                            replica=1):
            pass
        with telemetry.span("serving.decode_step", slots=2,
                            rids=["r-3", "r-other"]):
            pass
        tree = telemetry.request_tree("r-3")
        assert tree["event"]["name"] == "router.submit"
        kids = [c["event"]["name"] for c in tree["children"]]
        assert kids == ["router.dispatch", "serving.decode_step"]
        assert telemetry.request_tree("nobody") is None
        text = trace_mod.format_tree(tree)
        assert "router.submit" in text and "replica=1" in text

    def test_retried_submit_reconstructs_the_newest_trace(self):
        """A refused submit leaves its root event behind; the retry
        that actually served must win request_tree reconstruction."""
        telemetry.start_trace("r-4", name="router.submit")  # refused
        telemetry.end_trace("r-4")
        tid = telemetry.start_trace("r-4", name="router.submit")
        with telemetry.span("router.dispatch", request_id="r-4",
                            replica=0):
            pass
        tree = telemetry.request_tree("r-4")
        assert tree["event"]["trace"] == tid
        assert [c["event"]["name"] for c in tree["children"]] \
            == ["router.dispatch"]

    def test_disabled_mode_true_noop(self, monkeypatch):
        monkeypatch.setenv("PDT_TELEMETRY", "0")
        assert telemetry.start_trace("r-x") is None
        with telemetry.trace_attach("r-x"):
            with telemetry.span("s", request_id="r-x"):
                telemetry.event("e", request_id="r-x")
        assert telemetry.events() == []
        assert telemetry.trace_of("r-x") is None


class TestChromeExport:
    def _validate(self, doc):
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        for e in doc["traceEvents"]:
            assert isinstance(e["name"], str)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            assert e["ph"] in ("X", "i", "M"), e
            if e["ph"] == "M":
                assert e["name"] in ("process_name", "thread_name")
                assert isinstance(e["args"]["name"], str)
            else:
                assert isinstance(e["ts"], float) and e["ts"] >= 0.0
            if e["ph"] == "X":
                assert isinstance(e["dur"], float) and e["dur"] >= 0.0
            if e["ph"] == "i":
                assert e["s"] in ("t", "p", "g")
        json.dumps(doc)                      # must be JSON-serializable

    def test_schema_pid_replica_tid_request(self, tmp_path):
        telemetry.start_trace("req-a", name="router.submit")
        with telemetry.span("router.dispatch", request_id="req-a",
                            replica=2):
            pass
        with telemetry.span("router.replica_step", replica=2):
            with telemetry.span("serving.prefill", request_id="req-a"):
                pass
        with telemetry.span("serving.decode_step", slots=2,
                            rids=["req-a", "req-b"]):
            pass
        out = tmp_path / "chrome.json"
        doc = telemetry.export_chrome_trace(path=str(out))
        self._validate(doc)
        assert json.loads(out.read_text()) == doc
        procs = {e["args"]["name"]: e["pid"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        threads = {e["args"]["name"] for e in doc["traceEvents"]
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "replica 2" in procs
        assert {"req-a", "req-b"} <= threads
        # pid=replica INHERITS down the span tree: the engine prefill
        # has no replica attr but sits under the replica_step span
        prefill = [e for e in doc["traceEvents"]
                   if e["name"] == "serving.prefill"]
        assert prefill and prefill[0]["pid"] == procs["replica 2"]
        # the batched decode step fans out into BOTH request rows
        decode = [e for e in doc["traceEvents"]
                  if e["name"] == "serving.decode_step"]
        assert len(decode) == 2
        assert {d["tid"] for d in decode} == {
            e["tid"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
            and e["args"]["name"] in ("req-a", "req-b")
            and e["pid"] == decode[0]["pid"]}


# -- operator CLI ------------------------------------------------------
class TestCLI:
    def _populate(self):
        telemetry.counter("t_cli_total", "", ("k",)).inc(2, k="x")
        telemetry.gauge("t_cli_depth").set(3)
        telemetry.histogram("t_cli_seconds",
                            buckets=(0.5, 2.5)).observe(0.25)
        return telemetry.snapshot()

    def test_snapshot_json_prom_round_trip(self, tmp_path):
        snap = self._populate()
        src = tmp_path / "snap.json"
        telemetry.write_json(str(src))
        prom = tmp_path / "snap.prom"
        assert cli_main(["snapshot", "--from", str(src),
                         "--out", str(prom)]) == 0
        parsed = telemetry.parse_prometheus(prom.read_text())
        want = {k: snap[k] for k in ("counters", "gauges",
                                     "histograms")}
        assert parsed == want
        # and back: prom text -> JSON snapshot
        back = tmp_path / "back.json"
        assert cli_main(["snapshot", "--from", str(prom), "--format",
                         "json", "--out", str(back)]) == 0
        got = json.loads(back.read_text())
        assert {k: got[k] for k in want} == want

    def _slo_snap(self, breach: bool):
        ttft = {"count": 10, "sum": 1.0,
                "buckets": ({"0.1": 9, "1": 10, "+Inf": 10} if breach
                            else {"0.1": 10, "1": 10, "+Inf": 10})}
        term = {'status="finished"': 9.0, 'status="failed"': 1.0} \
            if breach else {'status="finished"': 10.0}
        return {"counters":
                {"pdt_serving_requests_terminal_total": term},
                "gauges": {},
                "histograms": {"pdt_serving_ttft_seconds": {"": ttft}}}

    def test_slo_command_exit_codes_and_report(self, tmp_path, capsys):
        good = tmp_path / "good.json"
        good.write_text(json.dumps(self._slo_snap(breach=False)))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(self._slo_snap(breach=True)))
        assert cli_main(["slo", "--from", str(good)]) == 0
        out = capsys.readouterr().out
        assert "PASS" in out and "BREACH" not in out
        assert cli_main(["slo", "--from", str(bad)]) == 1
        out = capsys.readouterr().out
        assert "BREACH" in out          # error_rate 0.1 > 0.01
        assert "ttft_p95" in out and "availability" in out

    def test_slo_command_custom_spec(self, tmp_path):
        snap = tmp_path / "s.json"
        snap.write_text(json.dumps(self._slo_snap(breach=True)))
        spec = tmp_path / "spec.json"
        # generous objectives: the same snapshot passes under them
        spec.write_text(json.dumps(
            [{"name": "ttft_p50", "signal": "ttft", "kind": "latency",
              "threshold": 5.0, "quantile": 0.5,
              "metric": "pdt_serving_ttft_seconds"},
             {"name": "err", "signal": "outcome", "kind": "error_rate",
              "threshold": 0.5,
              "metric": "pdt_serving_requests_terminal_total"}]))
        assert cli_main(["slo", "--from", str(snap), "--spec",
                         str(spec)]) == 0

    def test_trace_export_and_tree_round_trip(self, tmp_path, capsys):
        sink = tmp_path / "trace.jsonl"
        telemetry.set_trace_file(str(sink))
        try:
            telemetry.start_trace("cli-req", name="router.submit")
            with telemetry.span("router.dispatch",
                                request_id="cli-req", replica=0):
                pass
        finally:
            telemetry.set_trace_file(None)
        chrome = tmp_path / "chrome.json"
        assert cli_main(["trace", "export", str(sink), "--chrome",
                         str(chrome)]) == 0
        doc = json.loads(chrome.read_text())
        names = {e["name"] for e in doc["traceEvents"]}
        assert "router.dispatch" in names and "router.submit" in names
        assert cli_main(["trace", "tree", str(sink), "--request",
                         "cli-req"]) == 0
        out = capsys.readouterr().out
        assert "router.submit" in out and "router.dispatch" in out
        assert cli_main(["trace", "tree", str(sink), "--request",
                         "absent"]) == 1


# -- drift guards ------------------------------------------------------
# Since ISSUE 9 these are thin wrappers over the pdt-lint checkers
# (paddle_tpu.analysis, PDT003/PDT004) — ONE source of truth for what
# counts as drift; the word-boundary regex scans that used to live
# here are now AST passes shared with the `paddle-tpu-lint` CLI. The
# wrappers run with suppressions ignored: catalog drift cannot be
# opted out of inline.
class TestDocsAndSiteConsistency:
    def _project(self):
        from paddle_tpu.analysis import Project
        return Project(REPO, [os.path.join(REPO, "paddle_tpu")])

    def _documented_sites(self):
        from paddle_tpu.analysis.checkers.faultsites import (
            FaultSiteDriftChecker, collect_doc_sites)
        return collect_doc_sites(
            self._project(), FaultSiteDriftChecker.DEFAULT_FAULTS_FILE)

    def test_fault_site_docstring_matches_source(self):
        """Every site in the faults.py docstring exists as a
        fault_point() call in the source, and vice versa — the PDT003
        checker, which also rejects non-literal fault_point() sites
        the old regex could not see."""
        from paddle_tpu.analysis import run_checkers
        from paddle_tpu.analysis.checkers import FaultSiteDriftChecker
        res = run_checkers(self._project(), [FaultSiteDriftChecker()],
                           respect_suppressions=False)
        assert res.new == [], ("fault-site drift: "
                               + "; ".join(f.render() for f in res.new))

    def test_every_documented_site_fires_with_site_label(self):
        """Arming + visiting each documented site must produce the
        `pdt_faults_fired_total{site=...}` series chaos tests assert
        on — the docstring and the counter labels cannot drift."""
        from paddle_tpu.utils.faults import (FaultError, FaultInjector,
                                             fault_point)
        sites = self._documented_sites()
        assert sites                          # the regex found the list
        for site in sites:
            with FaultInjector() as fi:
                fi.arm(site, always=True)
                with pytest.raises(FaultError):
                    fault_point(site)
        snap = telemetry.snapshot()
        labels = set(snap["counters"]["pdt_faults_fired_total"])
        assert labels == {f'site="{s}"' for s in sites}

    def test_metric_catalog_matches_registered_instruments(self):
        """docs/observability.md's catalog rows must equal the pdt_*
        instruments the code registers — drift fails in BOTH
        directions (the PDT004 checker; being AST-based it needs no
        import list, so modules the old test forgot to import are
        covered too, and span/event names are checked alongside the
        metric table)."""
        from paddle_tpu.analysis import run_checkers
        from paddle_tpu.analysis.checkers import CatalogDriftChecker
        res = run_checkers(self._project(), [CatalogDriftChecker()],
                           respect_suppressions=False)
        assert res.new == [], ("catalog drift: "
                               + "; ".join(f.render() for f in res.new))
        # the static view must agree with the live registry: every
        # dynamically registered pdt_* instrument is one the AST
        # collector sees (guards against registration forms the
        # checker cannot parse creeping in)
        import paddle_tpu.distributed.checkpoint      # noqa: F401
        import paddle_tpu.distributed.fleet.elastic   # noqa: F401
        import paddle_tpu.distributed.launch          # noqa: F401
        import paddle_tpu.loadgen                     # noqa: F401
        import paddle_tpu.models.nemotron_h           # noqa: F401
        import paddle_tpu.models.serving              # noqa: F401
        import paddle_tpu.observability.slo           # noqa: F401
        import paddle_tpu.serving                     # noqa: F401
        import paddle_tpu.utils.faults                # noqa: F401
        from paddle_tpu.analysis.checkers.catalog import (
            collect_instruments)
        static = set(collect_instruments(
            self._project(), CatalogDriftChecker.DEFAULT_SCOPE,
            CatalogDriftChecker.DEFAULT_EXCLUDE))
        registered = {n for n in telemetry.REGISTRY.instruments()
                      if n.startswith("pdt_")}
        assert registered == static, (
            "static/live registry drift: AST-collector-only (a "
            "registration the runtime never executes) "
            f"{sorted(static - registered)}, live-only (a form the "
            f"collector cannot parse) {sorted(registered - static)}")

    def test_every_pallas_kernel_has_interpret_oracle_test(self):
        """Every `ops/` module containing a Pallas kernel must be
        referenced by a test file that also names an oracle. CI runs on
        the CPU mesh, so those references exercise the interpret /
        XLA-oracle paths — a kernel module without one ships unproven
        math (ISSUE 6 drift guard)."""
        ops_dir = os.path.join(REPO, "paddle_tpu", "ops")
        kernels = []
        for fn in sorted(os.listdir(ops_dir)):
            if not fn.endswith(".py") or fn == "__init__.py":
                continue
            with open(os.path.join(ops_dir, fn)) as f:
                if "pallas_call(" in f.read():
                    kernels.append(fn[:-3])
        assert kernels                       # the scan found the set
        tests_dir = os.path.join(REPO, "tests")
        srcs = []
        for fn in sorted(os.listdir(tests_dir)):
            if fn.startswith("test_") and fn.endswith(".py"):
                with open(os.path.join(tests_dir, fn)) as f:
                    srcs.append(f.read())
        missing = []
        for mod in kernels:
            # word-ish boundary: "paged_attention" must not take credit
            # from "ragged_paged_attention" references
            pat = re.compile(rf"(?<![a-z_]){mod}")
            if not any(pat.search(src) and re.search("oracle", src, re.I)
                       for src in srcs):
                missing.append(mod)
        assert not missing, ("Pallas kernel modules without an "
                             f"interpret-mode oracle test: {missing}")


class TestBenchRegressionGate:
    def _bench(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_bench_under_test", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_check_regression_detects_drop(self):
        bench = self._bench()
        prev = {"detail": {"tokens_per_sec_per_chip": 100.0,
                           "decode_tokens_per_sec": 50.0}}
        ok = {"detail": {"tokens_per_sec_per_chip": 95.0,
                         "decode_tokens_per_sec": 49.0}}
        bad = {"detail": {"tokens_per_sec_per_chip": 80.0,
                          "decode_tokens_per_sec": 50.0}}
        regs, n = bench.check_regression(prev, ok, 10.0)
        assert regs == [] and n == 2
        regs, n = bench.check_regression(prev, bad, 10.0)
        assert n == 2 and len(regs) == 1 \
            and "tokens_per_sec_per_chip" in regs[0]
        # a tighter threshold flags the small drop too
        regs, _ = bench.check_regression(prev, ok, 1.0)
        assert len(regs) == 2
        # nothing comparable is reported, not silently passed
        assert bench.check_regression({}, {}, 10.0) == ([], 0)

    def test_hist_diff_removes_warm_phase_from_quantiles(self):
        """Steady-state quantiles must exclude warm-up (compile)
        observations — count, sum, AND the cumulative buckets diff."""
        bench = self._bench()
        warm = {"count": 2, "sum": 8.0,
                "buckets": {"0.01": 0, "10": 2, "+Inf": 2}}
        final = {"count": 12, "sum": 8.05,
                 "buckets": {"0.01": 10, "10": 12, "+Inf": 12}}
        steady = bench._hist_diff(final, warm)
        assert steady == {"count": 10, "sum": pytest.approx(0.05),
                          "buckets": {"0.01": 10, "10": 10,
                                      "+Inf": 10}}
        # raw p99 sits in the compile bucket; steady-state does not
        raw_p99 = bench._hist_quantiles(final)["p99"]
        steady_p99 = bench._hist_quantiles(steady)["p99"]
        assert raw_p99 > 1.0 and steady_p99 <= 0.01
        assert bench._hist_diff({}, warm) == {}
        assert bench._hist_diff(None, None) is None

    def test_cli_compare_mode_exit_codes(self, tmp_path):
        bench = self._bench()
        prev = tmp_path / "prev.json"
        prev.write_text(json.dumps(
            {"detail": {"tokens_per_sec_per_chip": 100.0}}))
        good = tmp_path / "good.json"
        good.write_text(json.dumps(
            {"detail": {"tokens_per_sec_per_chip": 99.0}}))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(
            {"detail": {"tokens_per_sec_per_chip": 50.0}}))
        base = ["--check-regression", str(prev), "--current"]
        assert bench.main(base + [str(good)]) == 0
        assert bench.main(base + [str(bad)]) == 1
        empty = tmp_path / "empty.json"
        empty.write_text("{}")
        assert bench.main(base + [str(empty)]) == 2
        assert bench.main(["--current", str(good)]) == 2
