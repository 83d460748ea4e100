"""Performance attribution plane (ISSUE 20,
paddle_tpu/observability/profile.py): the fleet step's span tree and
its self times (ISSUE 25), compile-cache
observability behind the `_jit_lru`/`_jit_singleton` seam, the memory
ledger, histogram exemplars, and the `paddle-tpu-obs profile` CLI.

The two acceptance gates pinned here:

* the span self times of the fleet step add up to the `router.step`
  durations exactly, and those to the wall round them (the attribution
  is honest — nothing is missing and nothing is double-counted);
* 50 warm pipelined rounds record ZERO compiles (the steady-state
  claim every bench number rests on, finally verified).

conftest runs this file with PDT_TELEMETRY=1 and
PDT_CHECK_INVARIANTS=1 and attaches the profile report to failing
reports."""
import json
import re
import time

import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.observability import profile
from paddle_tpu.observability.__main__ import main as obs_main

pytestmark = pytest.mark.telemetry


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=64)
    paddle.seed(7)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, k=1, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    return ContinuousBatchingEngine(model, harvest_every=k, **kw)


JOBS = [([1, 2, 3], 40), ([4, 5], 38), ([6, 7, 8, 9], 36)]


def _warm_engine(model, k=1, jobs=JOBS):
    eng = _engine(model, k)
    for p, n in jobs:
        eng.add_request(list(p), n)
    for _ in range(4):
        eng.step()
    return eng


def _compile_total(snap):
    return sum(snap.get("counters", {}).get(
        "pdt_jit_compiles_total", {}).values())


# -- no-op unless enabled ----------------------------------------------
class TestDisabledNoOp:
    def test_disabled_records_nothing(self, model, monkeypatch):
        monkeypatch.delenv("PDT_TELEMETRY", raising=False)
        telemetry.disable()
        telemetry.reset()
        try:
            with telemetry.span("serving.step"):
                telemetry.interval("serving.queue_wait", 0.01)
            jit = profile.compile_timed(lambda: 7, "decode")
            assert jit() == 7
            profile.note_cache("prefill", 3, evicted=1)
            eng = _warm_engine(model)
            eng.step()
            snap = telemetry.snapshot()
        finally:
            telemetry.disable(clear_override=True)  # back to env-driven
        for section in ("counters", "gauges", "histograms"):
            assert not any(
                n.startswith(("pdt_span_", "pdt_jit_", "pdt_mem_"))
                for n in snap.get(section, {})), snap[section]
        assert telemetry.events() == []


# -- the fleet step's span tree ----------------------------------------
# spans whose self time is a wait for the device (dispatch + D2H sync)
DEVICE_WAIT = ("serving.ragged_prefill", "serving.decode_step",
               "serving.harvest", "jit.compile")


def _router(model, k=1, **kw):
    from paddle_tpu.serving.router import ServingRouter

    def factory(index, submesh=None):
        return _engine(model, k, submesh=submesh, **kw)

    return ServingRouter(factory, num_replicas=1)


def _spans():
    return [e for e in telemetry.events() if "self_s" in e]


def _tree_of(root, spans):
    """`root` and every span below it."""
    kids = {}
    for e in spans:
        kids.setdefault(e["parent"], []).append(e)
    out, todo = [], [root]
    while todo:
        e = todo.pop()
        out.append(e)
        todo += kids.get(e["seq"], [])
    return out


class TestStepTree:
    def test_ring_holds_the_fleet_step_tree(self, model, tmp_path):
        """After a few steps through a one-replica router the ring
        holds the tree of docs/observability.md, each span under its
        stated parent."""
        from paddle_tpu.serving.journal import RouterJournal
        router = _router(model)
        router.journal = RouterJournal(str(tmp_path / "j"))
        for i, (p, n) in enumerate(JOBS):
            router.submit(list(p), max_new_tokens=4, request_id=f"q{i}")
        for _ in range(3):
            router.step()
        spans = _spans()
        by_seq = {e["seq"]: e for e in spans}
        edges = {(e["name"], by_seq[e["parent"]]["name"])
                 for e in spans if e["parent"] in by_seq}
        assert {("router.replica_step", "router.step"),
                ("router.journal_mirror", "router.step"),
                ("serving.step", "router.replica_step"),
                ("serving.admit", "serving.step"),
                ("serving.prefill", "serving.admit"),
                ("serving.ragged_prefill", "serving.admit"),
                ("jit.compile", "serving.ragged_prefill"),
                ("serving.decode", "serving.step"),
                ("serving.decode_step", "serving.decode"),
                ("serving.commit", "serving.step"),
                ("serving.invariants", "serving.step")} <= edges, edges
        roots = {e["name"] for e in spans if e["parent"] is None}
        assert roots == {"router.step"}, roots

    @pytest.mark.parametrize("k", [1, 4])
    def test_self_times_are_the_whole_of_the_steps(self, model, k):
        """The honesty gate, now exact: the self times in
        `pdt_span_self_seconds` over 20 warm steps add up to the
        `router.step` durations of those steps, and those to the wall
        round them less the loop's own overhead."""
        router = _router(model, k)
        for i, (p, n) in enumerate(JOBS):
            router.submit(list(p), max_new_tokens=n, request_id=f"q{i}")
        for _ in range(4):
            router.step()
        telemetry.reset()
        telemetry.clear_events()
        t0 = time.perf_counter()
        for _ in range(20):
            router.step()
        wall = time.perf_counter() - t0
        roots = [e for e in _spans() if e["name"] == "router.step"]
        assert len(roots) == 20
        steps_s = sum(e["dur_s"] for e in roots)
        series = telemetry.snapshot()["histograms"][
            "pdt_span_self_seconds"]
        assert sum(v["sum"] for v in series.values()) \
            == pytest.approx(steps_s, rel=1e-9)
        assert 0.90 * wall <= steps_s <= wall
        names = {lbl.split('"')[1] for lbl in series}
        assert {"router.step", "serving.step", "serving.admit",
                "serving.decode", "serving.decode_step",
                "serving.commit"} <= names
        if k > 1:
            assert "serving.harvest" in names

    def test_host_time_no_longer_holds_the_admission_dispatch(self, model):
        """What was wrong with the round components: the "host"
        interval held `_admit()`, and under ragged admission that is a
        dispatch and its D2H sync. Host time is now the self time of
        every span but the device waits, so the admitting step's host
        time is its duration less ALL of `serving.ragged_prefill`."""
        eng = _engine(model)
        eng.add_request([1, 2, 3], 4)
        eng.step()
        spans = _spans()
        step = next(e for e in spans if e["name"] == "serving.step")
        tree = _tree_of(step, spans)
        waits = [e for e in tree if e["name"] in DEVICE_WAIT]
        assert {"serving.ragged_prefill", "serving.decode_step"} \
            <= {e["name"] for e in waits}
        host = sum(e["self_s"] for e in tree if e not in waits)
        device = sum(e["self_s"] for e in waits)
        assert host + device == pytest.approx(step["dur_s"], rel=1e-9)
        admit = next(e for e in tree if e["name"] == "serving.admit")
        ragged = next(e for e in tree
                      if e["name"] == "serving.ragged_prefill")
        assert admit["self_s"] <= admit["dur_s"] - ragged["dur_s"] + 1e-9

    def test_queue_wait_once_a_claim_on_the_engine_clock(self, model):
        """`serving.queue_wait` at each claim of a slot, `dur_s` = the
        engine-clock wait since `enqueue_time`; a preempted request
        that queued again gets a second one."""
        from paddle_tpu.models.serving import PoolExhausted
        from paddle_tpu.utils.faults import FaultInjector
        clk = FakeClock()
        eng = _engine(model, max_batch_size=2, clock=clk)
        eng.add_request([5, 4, 3, 2, 6, 7], 8)
        clk.advance(1.5)
        rid = eng.add_request([9, 1, 2], 6)
        clk.advance(2.0)
        with FaultInjector() as fi:
            # pages of 4: allocations 1-3 are the two prompts, the 4th
            # is the first decode-time growth -> preempt the youngest
            fi.arm("serving.alloc_page", nth=4, exc=PoolExhausted)
            eng.step()
            clk.advance(0.25)
            eng.step()              # the growth: rid goes back to wait
        clk.advance(0.75)
        eng.step()
        req = eng.get_request(rid)
        assert req.preemptions == 1
        waits = [(e["attrs"]["rid"], e["dur_s"], e["attrs"]["preemptions"])
                 for e in telemetry.events()
                 if e["name"] == "serving.queue_wait"]
        assert waits == [(0, 3.5, 0), (rid, 2.0, 0), (rid, 0.75, 1)]
        assert req.admit_time == clk.t
        hist = telemetry.snapshot()["histograms"][
            "pdt_serving_queue_wait_seconds"][""]
        assert hist["count"] == 3 and hist["sum"] == 6.25

    def test_prefill_rows_are_tokens_and_padding(self, model):
        eng = _engine(model, prefill_chunk=8)
        for p, n in JOBS + [(list(range(1, 14)), 3)]:
            eng.add_request(list(p), 3)
        eng.run()
        packs = [e["attrs"] for e in telemetry.events()
                 if e["name"] == "serving.ragged_prefill"]
        assert len(packs) >= 2
        rows = telemetry.snapshot()["counters"][
            "pdt_serving_prefill_rows_total"]
        assert rows['kind="token"'] == sum(a["tokens"] for a in packs)
        assert rows['kind="token"'] + rows['kind="pad"'] \
            == sum(a["t_pad"] for a in packs)
        assert rows['kind="pad"'] > 0

    def test_request_stamps_do_not_need_telemetry(self, model,
                                                  monkeypatch):
        monkeypatch.delenv("PDT_TELEMETRY", raising=False)
        telemetry.disable()
        try:
            clk = FakeClock()
            eng = _engine(model, clock=clk)
            rid = eng.add_request([1, 2, 3], 3)
            clk.advance(0.5)
            eng.step()
            req = eng.get_request(rid)
            assert req.admit_time == 0.5
            assert req.first_token_time == 0.5
            assert telemetry.events() == []
            assert telemetry.snapshot()["histograms"] == {}
        finally:
            telemetry.disable(clear_override=True)

    def test_programs_are_named_at_the_seam(self, model):
        """`XLA Modules` can tell admission from decode: the lowered
        programs are `jit_pdt_ragged_t<rows>` and `jit_pdt_decode`,
        not `jit_run` twice. (The page bound is in the key and not in
        the name: on the kernel path it does not shape the program,
        and equal programs under one name share a compile-cache
        entry.)"""
        _warm_engine(model)
        modules = {e["attrs"]["family"]: e["attrs"]["module"]
                   for e in telemetry.events()
                   if e["name"] == "jit.compile"}
        assert modules["decode"] == "jit_pdt_decode"
        assert re.fullmatch(r"jit_pdt_ragged_t\d+",
                            modules["ragged"]), modules


# -- compile-cache observability ---------------------------------------
class TestCompileObservability:
    def test_fifty_warm_pipelined_rounds_zero_compiles(self, model):
        """THE steady-state gate (ISSUE 20 acceptance): 50 warm
        pipelined rounds on a shape-stable batch mint zero programs."""
        eng = _warm_engine(model, k=4,
                           jobs=[([1, 2, 3], 60), ([4, 5], 58),
                                 ([6, 7, 8, 9], 56)])
        telemetry.reset()
        for _ in range(50):
            eng.step()
        snap = telemetry.snapshot()
        assert _compile_total(snap) == 0, snap["counters"][
            "pdt_jit_compiles_total"]

    def test_compiles_metered_per_family(self, model):
        telemetry.reset()
        eng = _warm_engine(model)
        snap = telemetry.snapshot()
        compiles = snap["counters"]["pdt_jit_compiles_total"]
        fams = {lbl.split('"')[1] for lbl in compiles}
        # the paged+ragged admission/decode path mints exactly these:
        # one keyed ragged-prefill program + the decode singleton
        assert {"decode", "ragged"} <= fams
        hist = snap["histograms"]["pdt_jit_compile_seconds"]
        for lbl, n in compiles.items():
            assert hist[lbl]["count"] == n
        # the jit.compile span joined the trace ring
        assert any(e.get("name") == "jit.compile"
                   for e in telemetry.events())

    def test_lru_eviction_metered(self, model):
        telemetry.reset()
        from collections import OrderedDict
        eng = _engine(model)
        cache = OrderedDict()
        for key in ("a", "b", "c"):
            eng._jit_lru(cache, key, lambda: (lambda: None), cap=2,
                         family="suffix")
        snap = telemetry.snapshot()
        assert snap["counters"]["pdt_jit_cache_evictions_total"][
            'family="suffix"'] == 1.0
        assert snap["gauges"]["pdt_jit_cache_entries"][
            'family="suffix"'] == 2.0
        assert len(cache) == 2

    def test_retrace_storm_fires_on_churn_not_on_warm(self):
        clock = FakeClock()
        win = profile.configure_retrace(window_s=30.0, threshold=4,
                                        clock=clock)
        try:
            telemetry.reset()
            telemetry.clear_events()
            # warm path: ONE program invoked many times — no storm
            jit = profile.compile_timed(lambda: 0, "decode")
            for _ in range(20):
                jit()
                clock.advance(0.1)
            assert not any(e.get("name") == "profile.retrace_storm"
                           for e in telemetry.events())
            # churn: a fresh program every call (the program-key-churn
            # failure mode pow2 bucketing exists to prevent)
            for _ in range(4):
                profile.compile_timed(lambda: 0, "ragged")()
                clock.advance(0.1)
            evts = [e for e in telemetry.events()
                    if e.get("name") == "profile.retrace_storm"]
            assert len(evts) == 1
            assert telemetry.snapshot()["counters"][
                "pdt_jit_retrace_storms_total"][""] == 1.0
            # still inside the same saturated window: no re-fire
            profile.compile_timed(lambda: 0, "ragged")()
            assert sum(1 for e in telemetry.events()
                       if e.get("name") == "profile.retrace_storm") == 1
        finally:
            profile.configure_retrace(window_s=30.0, threshold=10,
                                      clock=time.monotonic)


# -- memory ledger ------------------------------------------------------
class TestMemoryLedger:
    def test_ledger_pools_from_live_engine(self, model):
        eng = _warm_engine(model)
        mem = profile.memory_ledger([eng])
        assert mem["kv_pool"] > 0
        assert 0 < mem["kv_in_use"] <= mem["kv_pool"]
        gs = telemetry.snapshot()["gauges"]["pdt_mem_bytes"]
        assert gs['pool="kv_pool"'] == mem["kv_pool"]

    def test_fleet_info_perf_section(self, model):
        from paddle_tpu.serving import ServingRouter
        router = ServingRouter(
            lambda i: _engine(model), num_replicas=1)
        router.submit([1, 2, 3], max_new_tokens=6)
        for _ in range(4):
            router.step()
        perf = router.fleet_info()["perf"]
        assert perf["mem_bytes"]["kv_pool"] > 0
        assert perf["jit"]["decode"]["compiles"] >= 1
        # and status.py renders it
        text = telemetry.render_fleet_status(router.fleet_info())
        assert "memory: " in text and "jit compiles: " in text


# -- exemplars ----------------------------------------------------------
class TestExemplars:
    def test_observe_exemplar_snapshot_and_roundtrip(self):
        telemetry.reset()
        h = telemetry.histogram("pdt_test_exemplar_seconds", "t",
                                buckets=(0.1, 1.0))
        h.observe(0.05, exemplar="req-1")
        h.observe(0.5, exemplar='we"ird\\id')
        h.observe(0.07)          # no exemplar: keeps req-1's bucket
        snap = telemetry.snapshot()
        ex = snap["histograms"]["pdt_test_exemplar_seconds"][""][
            "exemplars"]
        assert ex["0.1"] == {"trace_id": "req-1", "value": 0.05}
        assert ex["1"]["trace_id"] == 'we"ird\\id'
        text = telemetry.to_prometheus()
        assert '# {trace_id="req-1"} 0.05' in text
        parsed = telemetry.parse_prometheus(text)
        snap.pop("enabled", None)
        assert parsed == snap

    def test_ttft_exemplar_links_request(self, model):
        telemetry.reset()
        eng = _engine(model)
        rid = eng.add_request([1, 2, 3], 4)
        for _ in range(3):
            eng.step()
        ex = telemetry.snapshot()["histograms"][
            "pdt_serving_ttft_seconds"][""]["exemplars"]
        assert any(e["trace_id"] == str(rid) for e in ex.values())


# -- report + CLI -------------------------------------------------------
class TestReportAndCli:
    def _fleet_snapshot(self, model, tmp_path):
        telemetry.reset()
        eng = _warm_engine(model)
        for _ in range(4):
            eng.step()
        profile.memory_ledger([eng])
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(telemetry.snapshot()))
        return path

    def test_cli_renders_ranked_report(self, model, tmp_path, capsys):
        path = self._fleet_snapshot(model, tmp_path)
        assert obs_main(["profile", "--from", str(path)]) == 0
        out = capsys.readouterr().out
        assert "span self time (share of serving.step)" in out
        rows = [ln.split()[0] for ln in out.splitlines()
                if ln.startswith("  serving.")]
        assert {"serving.step", "serving.admit", "serving.decode",
                "serving.decode_step", "serving.commit"} <= set(rows)
        assert "compile cache" in out
        assert "memory ledger" in out

    def test_cli_prom_text_input(self, model, tmp_path, capsys):
        json_path = self._fleet_snapshot(model, tmp_path)
        prom = tmp_path / "snap.prom"
        prom.write_text(telemetry.render_prometheus(
            json.loads(json_path.read_text())))
        assert obs_main(["profile", "--from", str(prom)]) == 0

    def test_cli_exit_one_on_empty_snapshot(self, tmp_path, capsys):
        p = tmp_path / "empty.json"
        p.write_text(json.dumps(
            {"counters": {}, "gauges": {}, "histograms": {}}))
        assert obs_main(["profile", "--from", str(p)]) == 1
        assert "no profile data" in capsys.readouterr().out
