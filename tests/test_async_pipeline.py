"""Pipelined decode with a bounded-staleness durability window
(ISSUE 18): `ContinuousBatchingEngine(harvest_every=k)` keeps the
greedy-sampled token vector ON DEVICE between dispatches and batches
the D2H harvest every k steps.

The acceptance property threaded through this file: greedy streams are
BIT-IDENTICAL to the k=1 (synchronous) oracle through every drill —
plain runs, EOS overshoot, deadline expiry mid-window, quiesce seams,
replica SIGKILL at every intra-window offset, router SIGKILL at every
intra-window offset followed by `recover()`, and sentry quarantine —
while the staleness contract `durable_len <= len(tokens) <=
device_len` holds at every observable instant and the sentry's
detection latency stays bounded at k steps. conftest runs this file
with PDT_TELEMETRY=1 and PDT_CHECK_INVARIANTS=1."""
import json
import os
import shutil

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       RequestStatus, SpecConfig)
from paddle_tpu.serving import (CanaryConfig, ReplicaState,
                                RouterJournal, SentryConfig,
                                ServingRouter)
from paddle_tpu.serving.journal import _HEADER
from paddle_tpu.serving.sentry import NumericSentry
from paddle_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.chaos


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


# more jobs than slots, staggered budgets: queue pressure forces
# early harvests (admission trigger) AND full windows coexist
JOBS = [([1, 2, 3], 9), ([4, 5], 7), ([6, 7, 8, 9], 5),
        ([2, 2], 12), ([9, 1], 3)]


def _run_engine(model, k, jobs=JOBS, **kw):
    eng = _engine(model, k, **kw)
    rids = [eng.add_request(p, n) for p, n in jobs]
    res = eng.run()
    return [res[r] for r in rids]


def _segment_files(path):
    return sorted(fn for fn in os.listdir(path)
                  if fn.startswith("seg-") and fn.endswith(".wal"))


def _record_spans(blob):
    spans, off = [], 0
    while off < len(blob):
        length, _ = _HEADER.unpack_from(blob, off)
        end = off + _HEADER.size + length
        spans.append((off, end))
        off = end
    return spans


def _journal_records(path):
    out = []
    for seg in _segment_files(path):
        blob = open(os.path.join(path, seg), "rb").read()
        for start, end in _record_spans(blob):
            out.append(json.loads(
                blob[start + _HEADER.size:end].decode()))
    return out


# ---------------------------------------------------------------------
class TestEnginePipeline:
    """Engine-level k-identity + the staleness contract's seams."""

    @pytest.mark.parametrize("k", [2, 4, 8])
    def test_bit_identical_streams(self, model, k):
        ref = _run_engine(model, 1)
        assert _run_engine(model, k) == ref

    def test_eos_overshoot_discarded(self, model):
        """The device can't see EOS mid-window, so a pipelined engine
        dispatches up to k-1 steps past it — the harvest must discard
        the overshoot, leaving the stream identical to the
        synchronous engine's EOS cut."""
        plain = _run_engine(model, 1)
        # an eos that fires mid-stream for at least one request
        eos = plain[3][4]
        ref = _run_engine(model, 1, eos_token_id=eos)
        assert any(len(r) < n for r, (_, n) in zip(ref, JOBS))
        for k in (4, 8):
            assert _run_engine(model, k, eos_token_id=eos) == ref

    def test_k1_keeps_the_synchronous_loop(self, model):
        eng = _engine(model, 1)
        rids = [eng.add_request(p, n) for p, n in JOBS[:2]]
        eng.step()
        eng.step()
        assert eng._pending == [] and eng._tok_dev is None
        assert eng.quiesce() == 0          # no-op on the sync loop
        out = eng.run()
        assert [out[r] for r in rids] == _run_engine(model, 1, JOBS[:2])

    def test_constructor_validation(self, model):
        with pytest.raises(ValueError, match="harvest_every"):
            _engine(model, 0)
        with pytest.raises(ValueError, match="greedy-only"):
            _engine(model, 4, do_sample=True)
        with pytest.raises(ValueError, match="spec_decode"):
            _engine(model, 4,
                    spec_decode=SpecConfig(draft_model=model, k=2))

    def test_quiesce_drains_the_window(self, model):
        eng = _engine(model, 4)
        rids = [eng.add_request(p, n) for p, n in JOBS[:2]]
        eng.step()                          # prefill + first dispatch
        eng.step()                          # deferred dispatch
        assert len(eng._pending) >= 1
        drained = eng.quiesce()
        assert drained >= 1
        assert eng._pending == [] and eng._tok_dev is None
        out = eng.run()
        assert [out[r] for r in rids] == _run_engine(model, 1, JOBS[:2])

    def test_device_len_runs_ahead_then_resyncs(self, model):
        eng = _engine(model, 4)
        rid = eng.add_request([1, 2, 3], 9)
        eng.step()                          # prefill (+1 output token)
        eng.step()                          # deferred dispatch
        eng.step()                          # deferred dispatch
        req = eng.get_request(rid)
        depth = len(eng._pending)
        assert depth >= 1
        assert req.device_len == len(req.output) + depth
        eng.quiesce()
        assert req.device_len == len(req.output)

    def test_export_pages_quiesces_first(self, model):
        """Migration's export must hand off COMMITTED state only: a
        mid-window export sees every deferred token harvested."""
        eng = _engine(model, 4)
        rid = eng.add_request([1, 2, 3], 9)
        eng.step()
        eng.step()
        assert len(eng._pending) >= 1
        payload = eng.export_pages(rid)
        assert eng._pending == []
        req = eng.get_request(rid)
        assert len(payload["output"]) == len(req.output)

    def test_deadline_expiry_mid_window(self, model):
        """A deadline elapsing inside the deferred window finalizes at
        the same token count as the synchronous engine: the running-
        deadline harvest trigger closes the window before expiry
        acts."""
        def script(k):
            clock = FakeClock()
            eng = _engine(model, k, clock=clock)
            doomed = eng.add_request([1, 2, 3], 30, deadline=4.0)
            safe = eng.add_request([4, 5], 6)
            outs = {}
            for i in range(40):
                for r in eng.step():
                    outs[r.rid] = (r.status, list(r.output))
                clock.advance(1.0)
                if doomed in outs and safe in outs:
                    break
            return outs[doomed], outs[safe]

        ref = script(1)
        assert ref[0][0] == RequestStatus.TIMEOUT
        for k in (4, 8):
            assert script(k) == ref

    def test_sentry_stream_identical_and_lag_bounded(self, model):
        """The sentry on the pipelined loop: checks defer to harvest
        (lag metered, bounded at k-1) but the stream never moves."""
        ref = _run_engine(model, 1)
        k = 4
        eng = _engine(model, k)
        s = NumericSentry(SentryConfig(scan_every=1), vocab_size=64)
        eng.attach_sentry(s)
        rids = [eng.add_request(p, n) for p, n in JOBS]
        out = eng.run()
        assert [out[r] for r in rids] == ref
        assert s.scans >= 2 and s.trips == 0
        from paddle_tpu.serving.sentry import _M_DETECTION_LAG
        lag = _M_DETECTION_LAG.get()
        assert lag["count"] > 0
        assert lag["sum"] <= (k - 1) * lag["count"]

    def test_nan_poison_detected_within_k_steps(self, model):
        """Detection latency bound: with the scan every step, a NaN
        poisoning armed before the run trips at the FIRST harvest —
        within k dispatches of the poisoned one."""
        k = 4
        eng = _engine(model, k)
        s = NumericSentry(SentryConfig(scan_every=1), vocab_size=64)
        eng.attach_sentry(s)
        eng.add_request([1, 2, 3], 9)
        with FaultInjector(seed=0) as fi:
            fi.arm_corrupt("serving.logits", mode="nan", always=True)
            for _ in range(k + 1):          # prefill + one full window
                eng.step()
            assert s.trips >= 1
        assert s.last_trip["kind"] == "logit_nonfinite"


# ---------------------------------------------------------------------
class TestRouterPipelineChaos:
    """Fleet drills with pipelined engines: the kill offset sweeps
    EVERY position inside a k=4 window, so a dropped in-flight window
    of every depth 0..k-1 is re-generated bit-identically."""

    def _fleet(self, model, k, n=2, clock=None, **kw):
        clock = clock if clock is not None else FakeClock()
        kw.setdefault("page_size", 4)
        kw.setdefault("sleep", clock.advance)
        router = ServingRouter(
            lambda i: ContinuousBatchingEngine(
                model, clock=clock, max_batch_size=3, max_seq_len=64,
                page_size=4, harvest_every=k),
            num_replicas=n, policy="round_robin", clock=clock, **kw)
        return router, clock

    def _ref(self, model, jobs):
        eng = _engine(model, 1)
        rids = [eng.add_request(p, m) for p, m in jobs]
        res = eng.run()
        return [res[r] for r in rids]

    @pytest.mark.parametrize("offset", [0, 1, 2, 3])
    def test_replica_kill_every_window_offset(self, model, offset):
        """SIGKILL replica 0 at every intra-window offset: the unseen
        window dies with the engine, the mirrored prefix folds into a
        survivor's re-prefill, and the stream re-generates
        bit-identically (zero loss, up to k-1 tokens re-decoded)."""
        ref = self._ref(model, JOBS)
        router, clock = self._fleet(model, k=4, n=2,
                                    restart_backoff_base=3.0,
                                    restart_backoff_max=3.0)
        ids = [router.submit(p, m) for p, m in JOBS]
        for _ in range(2 + offset):
            router.step()
        assert any(not router.requests[i].done for i in ids)
        router.kill_replica(0)
        clock.advance(4.0)
        out = router.run()
        assert [out[i] for i in ids] == ref

    def test_quarantine_reserve_with_pipelined_engines(self, model):
        """Gray-failure response at k=4: persistent NaN poisoning of
        one replica's logit harvest quarantines it via dirty canaries
        and every stream re-serves bit-identically — canary verdicts
        quantize to harvest boundaries without weakening the drill."""
        ref = self._ref(model, JOBS)
        router, clock = self._fleet(
            model, k=4, n=2, restart_backoff_base=3.0,
            restart_backoff_max=3.0,
            sentry=SentryConfig(scan_every=1),
            canary=CanaryConfig(interval=1000.0, max_new_tokens=6))
        ids = [router.submit(p, m) for p, m in JOBS]
        with FaultInjector(seed=0) as fi:
            fi.arm_corrupt("serving.logits", mode="nan", always=True,
                           tag="1")
            for _ in range(120):
                router.step()
                if router.replicas[1].state \
                        == ReplicaState.QUARANTINED:
                    break
            assert router.replicas[1].state \
                == ReplicaState.QUARANTINED
            clock.advance(4.0)
            out = router.run()
        assert [out[i] for i in ids] == ref

    def test_fleet_info_reports_pending_harvest(self, model):
        router, _ = self._fleet(model, k=4, n=1)
        router.submit([1, 2, 3], 9)
        router.step()
        router.step()
        info = router.fleet_info()
        assert info["replicas"][0]["pending_harvest"] >= 1
        router.run()
        info = router.fleet_info()
        assert info["replicas"][0]["pending_harvest"] == 0


# ---------------------------------------------------------------------
class TestJournalWindow:
    """Group-commit + crash durability of the deferred window."""

    def _journaled(self, model, tmp_path, k, clock=None, name="wal",
                   fsync="off"):
        clock = clock if clock is not None else FakeClock()
        jr = RouterJournal(os.path.join(str(tmp_path), name),
                           fsync=fsync, clock=clock)
        router = ServingRouter(
            lambda i: ContinuousBatchingEngine(
                model, clock=clock, max_batch_size=3, max_seq_len=64,
                page_size=4, harvest_every=k),
            num_replicas=2, policy="round_robin", clock=clock,
            sleep=clock.advance, journal=jr, page_size=4)
        return router, jr, clock

    def test_group_commit_one_progress_record_per_window(
            self, model, tmp_path):
        """Mirrors only move at harvest ticks, so the journal writes
        ONE batched progress record per window — the record count
        shrinks ~k-fold vs the synchronous loop while the journaled
        token payload stays identical."""
        counts, tokens = {}, {}
        for k in (1, 4, 8):
            router, jr, _ = self._journaled(model, tmp_path, k,
                                            name=f"wal{k}")
            ids = [router.submit(p, m) for p, m in JOBS]
            out = router.run()
            tokens[k] = [out[i] for i in ids]
            jr.close()
            recs = _journal_records(jr.path)
            counts[k] = sum(1 for r in recs if r["kind"] == "progress")
        assert tokens[4] == tokens[1] and tokens[8] == tokens[1]
        assert counts[4] * 2 <= counts[1]
        assert counts[8] <= counts[4]

    @pytest.mark.parametrize("offset", [0, 1, 2, 3])
    def test_router_sigkill_every_window_offset(self, model, tmp_path,
                                                offset):
        """SIGKILL the ROUTER at every intra-window offset, then
        recover(): durable_len is monotone while alive, at most k
        undurable suffix tokens die with the process, replay
        re-generates them bit-identically, and no token is ever
        duplicated (the streams equal the oracle EXACTLY)."""
        ref = TestRouterPipelineChaos()._ref(model, JOBS)
        # fsync="step" — one fsync per GROUP-COMMIT record, i.e. per
        # harvest window: the policy whose cost this PR amortizes
        # k-fold, and the one under which durable_len means DISK
        router, jr, clock = self._journaled(model, tmp_path, 4,
                                            name=f"wal{offset}",
                                            fsync="step")
        ids = [router.submit(p, m) for p, m in JOBS]
        floor = {i: 0 for i in ids}
        for _ in range(2 + offset):
            router.step()
            for i in ids:
                rec = router.requests[i]
                # the staleness contract, at every observable instant
                assert rec.durable_len >= floor[i]       # monotone
                assert rec.durable_len <= len(rec.tokens)
                assert len(rec.tokens) <= rec.device_len
                floor[i] = rec.durable_len
        assert any(not router.requests[i].done for i in ids)
        del router                                   # SIGKILL-shaped
        jr2 = RouterJournal(os.path.join(str(tmp_path),
                                         f"wal{offset}"),
                            fsync="off", clock=clock)
        recovered = ServingRouter.recover(
            jr2, lambda i: ContinuousBatchingEngine(
                model, clock=clock, max_batch_size=3, max_seq_len=64,
                page_size=4, harvest_every=4),
            num_replicas=2, policy="round_robin", clock=clock,
            sleep=clock.advance, page_size=4)
        for i in ids:
            rec = recovered.requests[i]
            assert rec.durable_len == len(rec.tokens)
            assert rec.durable_len >= floor[i]
        out = recovered.run()
        assert [out[i] for i in ids] == ref   # bit-identical, no dups

    def test_torn_window_tail_fuzz_every_offset(self, tmp_path):
        """Truncate the journal at EVERY byte offset inside a final
        WINDOW-SIZED progress record (the group-commit shape): replay
        never raises, recovers the committed prefix, and counts
        exactly one corrupt-tail drop — a torn window is
        indistinguishable from a window that never committed."""
        src = os.path.join(str(tmp_path), "wal")
        with RouterJournal(src, fsync="off") as jr:
            jr.append_submit(request_id="a", prompt=[1, 2],
                             max_new_tokens=16)
            jr.step_mirror({"a": [5, 6, 7, 8]})      # window 1 commits
            jr.step_mirror({"a": [5, 6, 7, 8, 9, 10, 11, 12]})  # torn
        seg = _segment_files(src)[-1]
        blob = open(os.path.join(src, seg), "rb").read()
        last_start, last_end = _record_spans(blob)[-1]
        assert last_end == len(blob)
        for cut in range(last_start + 1, last_end):
            trial = os.path.join(str(tmp_path), f"trial-{cut}")
            shutil.copytree(src, trial)
            with open(os.path.join(trial, seg), "r+b") as f:
                f.truncate(cut)
            rep = RouterJournal(trial, fsync="off").replay()
            assert rep.corrupt_dropped == 1, cut
            # the committed window survives whole; the torn one is
            # dropped whole — never a partial window
            assert rep.live["a"].tokens == [5, 6, 7, 8], cut
