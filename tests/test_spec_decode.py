"""Speculative decoding as a first-class ENGINE mode (ISSUE 10):
`ContinuousBatchingEngine(spec_decode=SpecConfig(draft, k))` drafts k
greedy tokens per slot over the draft's own paged cache (one fused
scan dispatch), verifies every slot in ONE batched ragged target pass,
and commits the longest matching prefix + bonus token.

The contract under test is LOSSLESSNESS: engine-speculative greedy
streams are BIT-IDENTICAL to the engine-plain streams — in the clean
run, at tiny token budgets, through eos, through a forced preemption
(token-folding re-prefill drops draft state), through a SIGKILL router
failover, and through a prefill→decode migration under `roles=` (the
draft cache is dropped at the source and rebuilt on the target —
never torn). conftest runs this file with PDT_TELEMETRY=1 and
PDT_CHECK_INVARIANTS=1, so the DRAFT pool's page accounting
(`_check_invariants_draft`) is re-proved after every engine step of
every test here."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       PoolExhausted, RequestStatus,
                                       SpecConfig)
from paddle_tpu.serving import ServingRouter
from paddle_tpu.utils.faults import FaultInjector


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


@pytest.fixture(scope="module")
def draft():
    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=64)
    paddle.seed(8)
    d = LlamaForCausalLM(cfg)
    d.eval()
    return d


JOBS = [([5, 4, 3, 2, 6, 7], 8), ([9, 1, 2], 6), ([7, 7, 1, 2], 5)]


def _engine(model, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    return ContinuousBatchingEngine(model, **kw)


def _drain(eng):
    reqs = {}
    while eng._queue or any(r is not None for r in eng._slot_req):
        for r in eng.step():
            reqs[r.rid] = r
    return reqs


def _run(model, jobs=JOBS, fault=None, **kw):
    eng = _engine(model, **kw)
    rids = [eng.add_request(p, n) for p, n in jobs]
    if fault is None:
        reqs = _drain(eng)
    else:
        with FaultInjector() as fi:
            fi.arm(fault[0], **fault[1])
            reqs = _drain(eng)
    return eng, [reqs[r].output for r in rids], \
        [reqs[r].status for r in rids]


@pytest.fixture(scope="module")
def plain(model):
    """The engine-plain greedy reference streams for JOBS — computed
    once; every lossless assertion in this module compares to it."""
    _, outs, statuses = _run(model)
    assert all(s == RequestStatus.FINISHED for s in statuses)
    return outs


class TestSpecConfigValidation:
    def test_greedy_only(self, model, draft):
        with pytest.raises(ValueError, match="greedy"):
            _engine(model, do_sample=True, temperature=0.8,
                    spec_decode=SpecConfig(draft))

    def test_vocab_and_rope_coverage(self, model, draft):
        bad = LlamaForCausalLM(LlamaConfig(
            vocab_size=32, hidden_size=16, intermediate_size=32,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, max_position_embeddings=64))
        with pytest.raises(ValueError, match="vocab"):
            _engine(model, spec_decode=SpecConfig(bad))
        short = LlamaForCausalLM(LlamaConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, max_position_embeddings=16))
        with pytest.raises(ValueError, match="rope"):
            _engine(model, spec_decode=SpecConfig(short))

    def test_k_validation(self, model, draft):
        with pytest.raises(ValueError, match="k must be"):
            _engine(model, spec_decode=SpecConfig(draft, k=0))

    def test_tiny_draft_pairs_with_tiny(self):
        """The ready-made tiny()/tiny_draft() pair passes every
        spec_decode compatibility check (shared vocab, rope coverage)
        — the config a demo reaches for first must actually work."""
        t_cfg, d_cfg = LlamaConfig.tiny(), LlamaConfig.tiny_draft()
        assert d_cfg.vocab_size == t_cfg.vocab_size
        assert d_cfg.max_position_embeddings \
            == t_cfg.max_position_embeddings
        paddle.seed(0)
        target = LlamaForCausalLM(t_cfg)
        d = LlamaForCausalLM(d_cfg)
        eng = ContinuousBatchingEngine(target, max_batch_size=1,
                                       max_seq_len=64,
                                       spec_decode=SpecConfig(d, k=4))
        assert eng.spec_enabled

    def test_sliding_window_rejected(self, draft):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=64, sliding_window=8)
        paddle.seed(9)
        win = LlamaForCausalLM(cfg)
        win.eval()
        with pytest.raises(ValueError, match="sliding_window"):
            _engine(win, spec_decode=SpecConfig(draft))


class TestAcceptanceCore:
    """`spec_accept_greedy` is the ONE copy of the acceptance math —
    shared by `speculative_generate`'s compiled loop and the engine's
    verify commit (sentinel-padded for ragged per-slot budgets)."""

    def test_prefix_match_and_bonus(self):
        from paddle_tpu.models.speculative import spec_accept_greedy
        g = np.array([[1, 2, 3], [4, 9, 9], [5, 6, 7]], np.int32)
        p = np.array([[1, 2], [4, 5], [9, 9]], np.int32)
        j, bonus = (np.asarray(x) for x in spec_accept_greedy(g, p))
        # full accept -> bonus is the free extra token
        # partial -> bonus corrects the first mismatch
        # zero accept -> bonus is the plain greedy token
        np.testing.assert_array_equal(j, [2, 1, 0])
        np.testing.assert_array_equal(bonus, [3, 9, 5])

    def test_sentinel_padding_caps_accept_count(self):
        from paddle_tpu.models.speculative import spec_accept_greedy
        # row budget k_i=1 padded with -1 proposals / -2 greedy: j can
        # never run past the real proposal count
        g = np.array([[1, 2, -2, -2]], np.int32)
        p = np.array([[1, -1, -1]], np.int32)
        j, bonus = (np.asarray(x) for x in spec_accept_greedy(g, p))
        assert int(j[0]) == 1 and int(bonus[0]) == 2


class TestSpecEngineLossless:
    def test_streams_identical_clean(self, model, draft, plain):
        for k in (2, 4):
            eng, outs, statuses = _run(
                model, spec_decode=SpecConfig(draft, k=k))
            assert outs == plain, f"k={k}"
            assert all(s == RequestStatus.FINISHED for s in statuses)
            assert eng.num_spec_rounds > 0

    def test_self_draft_accepts_everything(self, model, plain):
        """target==draft: the draft's greedy picks ARE the target's,
        so every proposal is accepted and each round commits k+1
        tokens — the multiplicative-throughput configuration bench.py
        measures."""
        eng, outs, _ = _run(model, spec_decode=SpecConfig(model, k=4))
        assert outs == plain
        info = eng.spec_info()
        assert info["proposed"] > 0
        assert info["accepted"] == info["proposed"]
        assert info["acceptance_rate"] == 1.0

    def test_eos_stops_identically(self, model, draft, plain):
        eos = plain[0][3]            # a token plain emits mid-stream
        _, want, p_st = _run(model, eos_token_id=eos)
        eng, got, s_st = _run(model, eos_token_id=eos,
                              spec_decode=SpecConfig(draft, k=4))
        assert got == want and p_st == s_st
        assert got[0][-1] == eos and len(got[0]) == 4

    def test_tiny_budgets_never_overshoot(self, model, draft):
        """k > remaining budget: the verify budget caps at
        remaining-1, so a round can never emit past max_new_tokens —
        incl. the k_i=0 degenerate where the slot rides the round as
        a plain qlen=1 row."""
        jobs = [([5, 4, 3], 1), ([9, 1, 2], 2), ([8, 8], 3)]
        _, want, _ = _run(model, jobs=jobs)
        _, got, statuses = _run(model, jobs=jobs,
                                spec_decode=SpecConfig(draft, k=8))
        assert got == want
        assert [len(o) for o in got] == [1, 2, 3]
        assert all(s == RequestStatus.FINISHED for s in statuses)

    def test_streams_identical_through_preemption(self, model, draft,
                                                  plain):
        """Forced pool exhaustion mid-round: the victim's slot release
        DROPS its draft cache with it; the token-folding re-prefill
        readmits, and the next spec round backfills the draft from the
        folded stream — the final streams still equal plain greedy."""
        eng, outs, statuses = _run(
            model, jobs=JOBS[:2],
            fault=("serving.alloc_page", dict(nth=4, exc=PoolExhausted)),
            spec_decode=SpecConfig(draft, k=4))
        assert eng.num_preemptions >= 1
        assert outs == plain[:2]
        assert all(s == RequestStatus.FINISHED for s in statuses)

    def test_draft_pool_exhaustion_degrades_that_slot(self, model,
                                                      draft, plain):
        """An undersized draft pool (explicit SpecConfig.num_pages)
        starves the draft cache: affected slots ride rounds as plain
        qlen=1 rows — streams stay bit-identical, nothing fails."""
        eng, outs, statuses = _run(
            model, spec_decode=SpecConfig(draft, k=4, num_pages=3))
        assert outs == plain
        assert all(s == RequestStatus.FINISHED for s in statuses)


class TestSpecTelemetry:
    def test_spans_metrics_and_acceptance_gauge(self, model, draft,
                                                plain):
        telemetry.reset()
        telemetry.clear_events()
        eng, outs, _ = _run(model, spec_decode=SpecConfig(draft, k=4))
        assert outs == plain
        names = [e["name"] for e in telemetry.events()]
        drafts = [e for e in telemetry.events()
                  if e["name"] == "serving.draft"]
        verifies = [e for e in telemetry.events()
                    if e["name"] == "serving.verify"]
        assert len(drafts) == eng.num_spec_rounds == len(verifies)
        assert drafts[0]["attrs"]["k"] == 4
        assert verifies[0]["attrs"]["rids"]      # trace fan-out handle
        assert "serving.decode_step" not in names   # no plain rounds
        snap = telemetry.snapshot()["counters"]
        assert snap["pdt_spec_rounds_total"][""] == eng.num_spec_rounds
        assert snap["pdt_spec_proposed_total"][""] \
            == eng.num_spec_proposed
        assert snap["pdt_spec_accepted_total"][""] \
            == eng.num_spec_accepted
        rate = telemetry.value("pdt_spec_acceptance_rate")
        assert rate == pytest.approx(eng.spec_info()["acceptance_rate"])
        # emitted spec tokens ride the decode-token counter: effective
        # decode throughput stays one metric, speculative or not
        emitted = sum(len(o) for o in outs)
        first_tokens = len(outs)
        assert telemetry.value("pdt_serving_decode_tokens_total") \
            == emitted - first_tokens
        hists = telemetry.snapshot()["histograms"]
        assert hists["pdt_spec_draft_seconds"][""]["count"] \
            == eng.num_spec_rounds
        assert hists["pdt_spec_verify_seconds"][""]["count"] \
            == eng.num_spec_rounds


class TestSpecFleet:
    def _factory(self, model, draft, k):
        def f(i):
            return _engine(model, enable_prefix_caching=True,
                           spec_decode=None if k is None
                           else SpecConfig(draft, k=k))
        return f

    def test_streams_identical_through_sigkill_failover(self, model,
                                                        draft):
        """SIGKILL a spec replica mid-decode: failover re-prefills on
        a survivor from the router's token mirror (draft cache died
        with the engine — rebuilt lazily on the survivor), and fleet
        outputs equal an UNKILLED PLAIN fleet's."""
        clock = FakeClock()
        ref = ServingRouter(self._factory(model, draft, None),
                            num_replicas=3, policy="round_robin",
                            clock=clock, sleep=clock.advance,
                            page_size=4)
        ids0 = [ref.submit(p, n) for p, n in JOBS]
        want = ref.run()

        clock = FakeClock()
        router = ServingRouter(self._factory(model, draft, 4),
                               num_replicas=3, policy="round_robin",
                               clock=clock, sleep=clock.advance,
                               page_size=4)
        ids = [router.submit(p, n) for p, n in JOBS]
        router.step()
        router.step()                            # mid-decode
        router.kill_replica(1)
        got = router.run()
        assert router.num_failovers >= 1
        assert [got[i] for i in ids] == [want[i] for i in ids0]
        info = router.fleet_info()
        assert info["speculation"]["rounds"] > 0
        # the killed replica's acceptance history survived the discard
        assert info["speculation"]["proposed"] >= \
            sum(h.spec_info()["proposed"] for h in router.replicas
                if h.engine is not None)

    def test_migration_rebuilds_draft_on_decode_replica(self, model,
                                                        draft):
        """Disaggregated roles with speculation: prefill→decode
        migration moves TARGET pages only; the decode replica rebuilds
        the draft cache from the migrated stream on its first spec
        round. Outputs equal a plain colocated fleet's, and the
        invariant checker (draft section included) holds on both
        engines through every transfer."""
        clock = FakeClock()
        ref = ServingRouter(self._factory(model, draft, None),
                            num_replicas=2, policy="round_robin",
                            clock=clock, sleep=clock.advance,
                            page_size=4)
        ids0 = [ref.submit(p, n) for p, n in JOBS]
        want = ref.run()

        clock = FakeClock()
        router = ServingRouter(self._factory(model, draft, 4),
                               policy="prefix_affinity",
                               roles="prefill:1,decode:1",
                               clock=clock, sleep=clock.advance,
                               page_size=4)
        ids = [router.submit(p, n) for p, n in JOBS]
        got = router.run()
        info = router.fleet_info()
        assert info["migrations"] >= 1
        assert [got[i] for i in ids] == [want[i] for i in ids0]
        decode_replica = router.replicas[1]
        assert decode_replica.role == "decode"
        assert decode_replica.spec_info()["rounds"] > 0

    def test_fleet_info_omits_speculation_when_off(self, model, draft):
        clock = FakeClock()
        router = ServingRouter(self._factory(model, draft, None),
                               num_replicas=1, clock=clock,
                               sleep=clock.advance, page_size=4)
        assert "speculation" not in router.fleet_info()
