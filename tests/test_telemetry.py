"""Telemetry subsystem tests (fast tier, `telemetry` marker):
instrument semantics (counter/gauge/histogram, labels, disabled-mode
true no-op), span tracing (nesting, ring bound, JSONL sink), the
Prometheus text-exposition golden format + parse-back round trip, and
the integration contract from ISSUE 2's acceptance criteria — a
chaos-injected serving run whose terminal-status counters reconcile
EXACTLY with per-request statuses and whose text export parses back to
the same values. conftest enables PDT_TELEMETRY=1 and zeroes the
registry/ring for every test in this file."""
import json
import random
import types
from collections import deque

import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.observability import trace as _trace

pytestmark = pytest.mark.telemetry


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


class TestCounter:
    def test_inc_labels_and_value(self):
        c = telemetry.counter("t_reqs_total", "requests", ("kind",))
        c.inc(kind="a")
        c.inc(2.5, kind="a")
        c.inc(kind="b")
        assert c.get(kind="a") == 3.5
        assert telemetry.value("t_reqs_total", kind="b") == 1.0
        assert telemetry.value("t_reqs_total", kind="absent") == 0.0

    def test_negative_inc_rejected(self):
        c = telemetry.counter("t_mono_total")
        with pytest.raises(ValueError, match="< 0"):
            c.inc(-1)

    def test_label_mismatch_rejected(self):
        c = telemetry.counter("t_lab_total", "", ("site",))
        with pytest.raises(ValueError, match="expected labels"):
            c.inc()
        with pytest.raises(ValueError, match="expected labels"):
            c.inc(site="x", extra="y")

    def test_redeclare_idempotent_conflict_raises(self):
        a = telemetry.counter("t_same_total", "h", ("x",))
        assert telemetry.counter("t_same_total", "h", ("x",)) is a
        with pytest.raises(ValueError, match="already registered"):
            telemetry.gauge("t_same_total")
        with pytest.raises(ValueError, match="labels"):
            telemetry.counter("t_same_total", "h", ("y",))


class TestGauge:
    def test_set_inc_dec(self):
        g = telemetry.gauge("t_depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.get() == 6.0


class TestHistogram:
    def test_bucket_boundaries_cumulative(self):
        h = telemetry.histogram("t_lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = telemetry.snapshot()["histograms"]["t_lat_seconds"][""]
        # le-boundaries are INCLUSIVE and counts cumulative
        assert snap["buckets"] == {"0.1": 2, "1": 3, "10": 4, "+Inf": 5}
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(55.65)

    def test_timer_monotonic(self):
        h = telemetry.histogram("t_timer_seconds")
        with h.time():
            pass
        got = h.get()
        assert got["count"] == 1 and got["sum"] >= 0.0

    def test_value_rejects_histogram(self):
        telemetry.histogram("t_hist_seconds").observe(1.0)
        with pytest.raises(TypeError, match="histogram"):
            telemetry.value("t_hist_seconds")


class TestDisabledMode:
    def test_true_noop_when_disabled(self, monkeypatch):
        monkeypatch.setenv("PDT_TELEMETRY", "0")
        assert not telemetry.enabled()
        c = telemetry.counter("t_off_total", "", ("k",))
        g = telemetry.gauge("t_off_gauge")
        h = telemetry.histogram("t_off_seconds")
        c.inc(k="x")
        g.set(3)
        h.observe(1.0)
        with telemetry.span("t.off", a=1):
            telemetry.event("t.off.point")
        snap = telemetry.snapshot()
        assert snap["enabled"] is False
        assert snap["counters"] == snap["gauges"] \
            == snap["histograms"] == {}
        assert telemetry.events() == []
        assert telemetry.to_prometheus() == ""

    def test_enable_overrides_env(self, monkeypatch):
        monkeypatch.setenv("PDT_TELEMETRY", "0")
        telemetry.enable()
        try:
            assert telemetry.enabled()
            telemetry.counter("t_ovr_total").inc()
            assert telemetry.value("t_ovr_total") == 1.0
            telemetry.disable()
            assert not telemetry.enabled()
        finally:
            telemetry.disable(clear_override=True)  # back to env-driven

    def test_reset_keeps_instruments_clears_series(self):
        c = telemetry.counter("t_reset_total")
        c.inc()
        telemetry.reset()
        assert telemetry.counter("t_reset_total") is c
        assert c.get() == 0.0
        assert "t_reset_total" not in telemetry.snapshot()["counters"]


class TestTrace:
    def test_nesting_depth_parent_and_attrs(self):
        with telemetry.span("outer", rid=1):
            with telemetry.span("inner"):
                pass
            telemetry.event("point", site="s")
        evs = telemetry.events()
        names = [e["name"] for e in evs]
        assert names == ["inner", "point", "outer"]  # completion order
        inner, point, outer = evs
        assert inner["depth"] == 1 and inner["parent"] == outer["seq"]
        assert point["depth"] == 1 and point["parent"] == outer["seq"]
        assert outer["depth"] == 0 and outer["parent"] is None
        assert outer["attrs"] == {"rid": 1}
        assert outer["dur_s"] >= inner["dur_s"] >= 0.0
        assert inner["seq"] > outer["seq"]  # outer entered first

    def test_exception_lands_in_attrs(self):
        with pytest.raises(RuntimeError):
            with telemetry.span("boom", rid=2):
                raise RuntimeError("kaput")
        ev = telemetry.events()[-1]
        assert ev["attrs"]["rid"] == 2
        assert "RuntimeError: kaput" in ev["attrs"]["error"]

    def test_ring_buffer_is_bounded(self, monkeypatch):
        monkeypatch.setattr(_trace, "_RING", deque(maxlen=8))
        for i in range(20):
            telemetry.event("e", i=i)
        evs = telemetry.events()
        assert len(evs) == 8
        assert [e["attrs"]["i"] for e in evs] == list(range(12, 20))

    def test_file_sink_writes_jsonl(self, tmp_path):
        sink = tmp_path / "trace.jsonl"
        telemetry.set_trace_file(str(sink))
        try:
            with telemetry.span("sunk", k="v"):
                pass
            telemetry.event("pt")
        finally:
            telemetry.set_trace_file(None)
        lines = [json.loads(ln) for ln in
                 sink.read_text().strip().splitlines()]
        assert [ln["name"] for ln in lines] == ["sunk", "pt"]
        assert lines[0]["attrs"] == {"k": "v"}

    def test_self_times_add_up_to_the_root(self):
        """A span's `self_s` is its duration less its direct
        children's: the self times of a tree are disjoint and add up
        to the root's `dur_s`, and `pdt_span_self_seconds{name}`
        carries the same sums."""
        with telemetry.span("t.root"):
            for _ in range(3):
                with telemetry.span("t.mid"):
                    with telemetry.span("t.leaf"):
                        sum(range(2000))
                    with telemetry.span("t.leaf"):
                        pass
            with _trace.attach("no-such-carrier"):
                with telemetry.span("t.side"):
                    sum(range(2000))
        evs = [e for e in telemetry.events() if "self_s" in e]
        root = evs[-1]
        assert root["name"] == "t.root" and len(evs) == 11
        assert all(0.0 <= e["self_s"] <= e["dur_s"] for e in evs)
        assert sum(e["self_s"] for e in evs) \
            == pytest.approx(root["dur_s"], rel=1e-9, abs=1e-12)
        by_name = {}
        for e in evs:
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["self_s"]
        hist = telemetry.snapshot()["histograms"]["pdt_span_self_seconds"]
        assert {k: v["count"] for k, v in hist.items()} == {
            'name="t.root"': 1, 'name="t.mid"': 3, 'name="t.leaf"': 6,
            'name="t.side"': 1}
        for name, total in by_name.items():
            assert hist[f'name="{name}"']["sum"] == pytest.approx(total)

    def test_attached_span_still_counts_in_its_lexical_parent(self):
        telemetry.start_trace("req-7")
        with telemetry.span("t.outer"):
            with _trace.attach("req-7"):
                with telemetry.span("t.inner"):
                    sum(range(2000))
        inner, outer = [e for e in telemetry.events() if "self_s" in e]
        assert inner["trace"] is not None and inner["self_s"] > 0.0
        assert outer["self_s"] == pytest.approx(
            outer["dur_s"] - inner["dur_s"], abs=1e-12)

    def test_interval_is_one_record_outside_the_self_time_tree(self):
        """An interval that is not lexically scoped: `dur_s` is what
        the caller measured, `ts_mono` is its start, the request's
        trace is joined through `request_id`, and the enclosing span's
        self time does not lose it."""
        tid = telemetry.start_trace("req-9")
        with telemetry.span("t.claim"):
            telemetry.interval("t.wait", 2.5, request_id="req-9", rid=3)
        wait, claim = telemetry.events()[-2:]
        assert wait["name"] == "t.wait" and wait["dur_s"] == 2.5
        assert "self_s" not in wait
        assert wait["trace"] == tid and wait["parent"] == claim["seq"]
        assert wait["ts_mono"] + 2.5 == pytest.approx(
            claim["ts_mono"] + claim["dur_s"], abs=0.05)
        assert claim["self_s"] == claim["dur_s"]
        tree = telemetry.request_tree("req-9")
        assert [c["event"]["name"] for c in tree["children"]] \
            == ["t.wait"]
        assert list(telemetry.snapshot()["histograms"][
            "pdt_span_self_seconds"]) == ['name="t.claim"']

    def test_record_event_host_stats_do_not_grow(self):
        """Each span enters `profiler.RecordEvent`; with no `Profiler`
        collecting, a thousand spans must leave its class-level table
        the size one span made it (one pair a name)."""
        from paddle_tpu.profiler import Profiler, RecordEvent

        def size():
            return sum(len(v) for v in RecordEvent._host_stats.values())

        with telemetry.span("t.grow"):
            pass
        before = size()
        calls = RecordEvent._host_stats["t.grow"][0]
        for _ in range(1000):
            with telemetry.span("t.grow"):
                pass
        assert size() == before
        assert RecordEvent._host_stats["t.grow"][0] == calls + 1000
        assert "t.grow" in Profiler(timer_only=True).summary()

    def test_set_trace_file_none_sticks_over_env(self, tmp_path,
                                                 monkeypatch):
        """set_trace_file(None) must close the sink FOR GOOD — the env
        var is not re-consulted on the next emit."""
        sink = tmp_path / "env_trace.jsonl"
        monkeypatch.setenv("PDT_TELEMETRY_TRACE_FILE", str(sink))
        monkeypatch.setattr(_trace, "_SINK_RESOLVED", False)
        monkeypatch.setattr(_trace, "_SINK_PATH", None)
        telemetry.event("before")
        telemetry.set_trace_file(None)
        telemetry.event("after")
        names = [json.loads(ln)["name"]
                 for ln in sink.read_text().strip().splitlines()]
        assert names == ["before"]


class TestPrometheusExport:
    def test_golden_text_format(self):
        reg = telemetry.Registry()
        c = reg.counter("g_req_total", "Requests served.", ("code",))
        c.inc(3, code="200")
        c.inc(code="500")
        reg.gauge("g_depth", "Queue depth.").set(2)
        h = reg.histogram("g_lat_seconds", "Latency.",
                          buckets=(0.5, 2.5))
        h.observe(0.25)
        h.observe(1.0)
        h.observe(9.0)
        assert telemetry.to_prometheus(reg) == """\
# HELP g_req_total Requests served.
# TYPE g_req_total counter
g_req_total{code="200"} 3
g_req_total{code="500"} 1
# HELP g_depth Queue depth.
# TYPE g_depth gauge
g_depth 2
# HELP g_lat_seconds Latency.
# TYPE g_lat_seconds histogram
g_lat_seconds_bucket{le="0.5"} 1
g_lat_seconds_bucket{le="2.5"} 2
g_lat_seconds_bucket{le="+Inf"} 3
g_lat_seconds_sum 10.25
g_lat_seconds_count 3
"""

    def test_parse_roundtrip_matches_snapshot(self):
        telemetry.counter("r_a_total", "", ("x", "y")).inc(
            2, x="1", y="two words")
        telemetry.gauge("r_g").set(0.125)
        telemetry.histogram("r_h_seconds", "", ("op",),
                            buckets=(0.01, 0.1)).observe(0.05, op="save")
        snap = telemetry.snapshot()
        parsed = telemetry.parse_prometheus(telemetry.to_prometheus())
        assert parsed == {k: snap[k]
                          for k in ("counters", "gauges", "histograms")}

    def test_label_values_escaped_and_roundtrip(self):
        """Quotes/backslashes/newlines in label values (e.g. a hostile
        --job_id) must not corrupt the exposition or the round trip."""
        c = telemetry.counter("r_esc_total", "", ("job",))
        c.inc(job='a"b')
        c.inc(2, job="back\\slash")
        c.inc(3, job="new\nline")
        txt = telemetry.to_prometheus()
        assert r'job="a\"b"' in txt
        assert r'job="back\\slash"' in txt
        assert r'job="new\nline"' in txt and "new\nline" not in txt
        snap = telemetry.snapshot()
        parsed = telemetry.parse_prometheus(txt)
        assert parsed["counters"]["r_esc_total"] \
            == snap["counters"]["r_esc_total"]
        assert c.get(job='a"b') == 1.0    # raw value still the key


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=64)
    paddle.seed(7)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _drain(eng):
    reqs = {}
    while eng._queue or any(r is not None for r in eng._slot_req):
        for r in eng.step():
            reqs[r.rid] = r
    return reqs


class TestEngineIntegration:
    """ISSUE 2 acceptance: under fault injection, telemetry counters
    reconcile exactly with request terminal statuses, and the Prometheus
    export round-trips; with telemetry disabled the engine records
    nothing and still serves."""

    def _chaos_run(self, model, clock=None):
        from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                               PoolExhausted)
        from paddle_tpu.utils.faults import FaultInjector
        eng = ContinuousBatchingEngine(
            model, max_batch_size=2, max_seq_len=64, page_size=4,
            max_preemptions=0, clock=clock)
        # one request per fate: the injected decode-time exhaustion
        # preempts the youngest (starved terminal at max_preemptions=0),
        # the 3rd prefill (the waiting request's admission into the
        # freed slot) faults -> failed, the first finishes; with a fake
        # clock a 4th expires -> timeout
        eng.add_request([5, 4, 3, 2, 6, 7], 8)
        eng.add_request([9, 1, 2], 6)
        eng.add_request([1, 2, 3], 4)
        with FaultInjector() as fi:
            # prompts of 6+3 tokens at page_size 4 = alloc visits 1-3;
            # visit 4 is the first decode-time growth
            fi.arm("serving.alloc_page", nth=4, exc=PoolExhausted)
            fi.arm("serving.prefill", nth=3)
            reqs = _drain(eng)
        if clock is not None:
            eng.add_request([7, 7, 7], 30, deadline=5.0)
            eng.step()
            clock.advance(6.0)
            reqs.update(_drain(eng))
        return eng, reqs

    def test_terminal_counters_reconcile_and_roundtrip(self, model):
        clk = FakeClock()
        eng, reqs = self._chaos_run(model, clock=clk)
        statuses = [r.status for r in reqs.values()]
        snap = telemetry.snapshot()
        term = snap["counters"]["pdt_serving_requests_terminal_total"]
        # every terminal status the run produced is counted EXACTLY
        for status in ("finished", "timeout", "failed", "preempted"):
            want = statuses.count(status)
            got = term.get(f'status="{status}"', 0)
            assert got == want, (status, got, want, statuses)
        assert sum(term.values()) == len(reqs)
        assert {"finished", "failed", "preempted", "timeout"} \
            <= set(statuses)          # the run exercised all four fates
        # engine's own counters agree with telemetry
        li = eng.lifecycle_info()
        assert telemetry.value("pdt_serving_preemptions_total") \
            == li["preemptions"]
        assert telemetry.value("pdt_serving_requests_terminal_total",
                               status="timeout") == li["timeouts"]
        assert telemetry.value("pdt_serving_requests_terminal_total",
                               status="failed") == li["failures"]
        # fault fires carry the site label
        faults = snap["counters"]["pdt_faults_fired_total"]
        assert faults['site="serving.alloc_page"'] == 1
        assert faults['site="serving.prefill"'] == 1
        # TTFT observed once per request that produced a first token
        first_tok = sum(1 for r in reqs.values() if r.output)
        assert snap["histograms"]["pdt_serving_ttft_seconds"][""][
            "count"] == first_tok
        # Prometheus text export parses back to the same values
        parsed = telemetry.parse_prometheus(telemetry.to_prometheus())
        assert parsed == {k: snap[k]
                          for k in ("counters", "gauges", "histograms")}

    def test_spans_cover_prefill_and_decode(self, model):
        self._chaos_run(model)
        names = [e["name"] for e in telemetry.events()]
        for expected in ("serving.prefill", "serving.decode_step",
                         "serving.terminal", "serving.preempt",
                         "fault.fire"):
            assert expected in names, (expected, set(names))

    def test_disabled_engine_records_nothing(self, model, monkeypatch):
        monkeypatch.setenv("PDT_TELEMETRY", "0")
        eng, reqs = self._chaos_run(model)
        assert all(r.done for r in reqs.values())
        snap = telemetry.snapshot()
        assert snap["counters"] == snap["gauges"] \
            == snap["histograms"] == {}
        assert telemetry.events() == []


class TestInfraIntegration:
    def test_launch_restart_counter_and_backoff(self, tmp_path):
        from paddle_tpu.distributed.launch import launch
        script = tmp_path / "fail.py"
        script.write_text("import sys; sys.exit(3)\n")
        args = types.SimpleNamespace(
            master=None, nnodes=1, rank=0, job_id="tm", log_dir=None,
            elastic_level=1, max_restart=1, restart_backoff=2.0,
            restart_backoff_max=5.0, script=str(script), script_args=[])
        rc = launch(args, sleep=lambda _: None, rng=random.Random(0))
        assert rc == 3
        assert telemetry.value("pdt_launch_restarts_total", job="tm") == 1
        bo = telemetry.histogram(
            "pdt_launch_restart_backoff_seconds").get()
        assert bo["count"] == 1 and 1.0 <= bo["sum"] <= 5.0
        assert any(e["name"] == "launch.restart"
                   for e in telemetry.events())

    def test_heartbeat_staleness_and_membership_events(self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import \
            HeartbeatMembership
        import os
        clk = {"t": 1000.0}
        watch = HeartbeatMembership(str(tmp_path), timeout=5.0,
                                    clock=lambda: clk["t"])

        def beat(rank, age=0.0):
            HeartbeatMembership(str(tmp_path), rank=rank).heartbeat()
            path = os.path.join(str(tmp_path), f"worker_{rank}.hb")
            os.utime(path, (clk["t"] - age, clk["t"] - age))

        beat(0)
        beat(1, age=2.0)
        watch.poll()
        assert telemetry.value("pdt_elastic_heartbeat_staleness_seconds",
                               rank="0") == pytest.approx(0.0)
        assert telemetry.value("pdt_elastic_heartbeat_staleness_seconds",
                               rank="1") == pytest.approx(2.0)
        beat(0, age=10.0)                    # silent past the timeout
        d = watch.poll()
        assert d["event"] == "scale_down"
        assert telemetry.value("pdt_elastic_membership_events_total",
                               event="scale_down") == 1
        # a departed worker (beat file gone, as stop() leaves it) must
        # not keep exporting a frozen staleness value
        os.remove(os.path.join(str(tmp_path), "worker_1.hb"))
        watch.poll()
        series = telemetry.snapshot()["gauges"].get(
            "pdt_elastic_heartbeat_staleness_seconds", {})
        assert 'rank="1"' not in series and 'rank="0"' in series

    def test_checkpoint_save_load_bytes_and_spans(self, tmp_path):
        from paddle_tpu import nn
        from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                       save_state_dict)
        paddle.seed(0)
        net = nn.Linear(4, 4)
        nbytes = sum(p._value.nbytes for p in net.parameters())
        save_state_dict(net.state_dict(), str(tmp_path / "ck"))
        load_state_dict(net.state_dict(), str(tmp_path / "ck"))
        assert telemetry.value("pdt_checkpoint_ops_total", op="save") == 1
        assert telemetry.value("pdt_checkpoint_ops_total", op="load") == 1
        assert telemetry.value("pdt_checkpoint_bytes_total",
                               op="save") == nbytes
        assert telemetry.value("pdt_checkpoint_bytes_total",
                               op="load") == nbytes
        names = [e["name"] for e in telemetry.events()]
        assert "checkpoint.save" in names and "checkpoint.load" in names

    def test_async_checkpoint_counts_on_completion(self, tmp_path):
        """An async save is only DISPATCHED by save_state_dict — the op
        must not count as completed until wait_until_finished()."""
        from paddle_tpu import nn
        from paddle_tpu.distributed.checkpoint import save_state_dict
        paddle.seed(0)
        net = nn.Linear(4, 4)
        nbytes = sum(p._value.nbytes for p in net.parameters())
        ckptr = save_state_dict(net.state_dict(), str(tmp_path / "ck"),
                                async_save=True)
        assert telemetry.value("pdt_checkpoint_ops_total",
                               op="save") == 0
        ckptr.wait_until_finished()
        ckptr.wait_until_finished()          # idempotent: counts once
        assert telemetry.value("pdt_checkpoint_ops_total",
                               op="save") == 1
        assert telemetry.value("pdt_checkpoint_bytes_total",
                               op="save") == nbytes
