"""Disaggregated prefill/decode serving (ISSUE 8): role-aware routing,
the KV page transfer plane (`serving/transfer.py` over the engine's
`export_pages` / `import_pages` / `evict_request` hooks), and the
fleet-wide prefix store with host-RAM spill (`serving/prefix_store.py`).

The acceptance property threaded through this file: greedy outputs are
BIT-IDENTICAL between a colocated fleet (== a single engine, pinned by
tests/test_router.py) and a role-split fleet, including through
mid-transfer faults and a SIGKILL of either transfer endpoint. conftest
runs this file with PDT_TELEMETRY=1 and PDT_CHECK_INVARIANTS=1, so
every engine step of every migration re-proves page accounting."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       EngineOverloaded, RequestStatus)
from paddle_tpu.serving import (FleetPrefixStore, PrefixAffinityPolicy,
                                ReplicaRole, ReplicaState, ServingRouter,
                                chain_hashes, parse_roles)
from paddle_tpu.serving import transfer
from paddle_tpu.utils.faults import FaultError, FaultInjector

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


def _engine(model, clock=None, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("enable_prefix_caching", True)
    return ContinuousBatchingEngine(model, clock=clock, **kw)


def _fleet(model, roles, policy="prefix_affinity", clock=None,
           engine_kw=None, **kw):
    clock = clock if clock is not None else FakeClock()
    kw.setdefault("page_size", 4)
    kw.setdefault("sleep", clock.advance)
    ekw = dict(engine_kw or {})
    router = ServingRouter(
        lambda i: _engine(model, clock=clock, **ekw),
        roles=roles, policy=policy, clock=clock, **kw)
    return router, clock


def _reference(model, jobs, **kw):
    """Single-engine greedy outputs — the colocated oracle (a colocated
    fleet equals one engine, pinned by tests/test_router.py)."""
    eng = _engine(model, **kw)
    rids = [eng.add_request(p, n) for p, n in jobs]
    res = eng.run()
    return [res[r] for r in rids]


# two full 4-token pages of shared system prompt + distinct tails: the
# workload disaggregation + the prefix store exist for
SYS = [11, 7, 23, 42, 9, 30, 5, 17]
JOBS = [(SYS + [3, 1, 4], 6), (SYS + [55, 2], 5), (SYS + [8, 8, 61], 6),
        (SYS + [19, 44], 5), (SYS + [31, 6, 12], 6), (SYS + [27], 5)]


@pytest.fixture(scope="module")
def oracle(model):
    """Greedy outputs for every JOB from ONE engine run — per-request
    outputs are independent of co-batching (the engine's bit-identity
    guarantee), so each test slices what it needs."""
    return _reference(model, JOBS)


class TestRoleSpec:
    def test_parse_roles_forms(self):
        assert parse_roles("prefill:2,decode:1") \
            == ["prefill", "prefill", "decode"]
        assert parse_roles({"decode": 1, "prefill": 1}) \
            == ["prefill", "decode"]
        assert parse_roles(["decode", "colocated"]) \
            == ["decode", "colocated"]
        assert parse_roles(None) is None
        with pytest.raises(ValueError, match="unknown replica role"):
            parse_roles("turbo:2")
        with pytest.raises(ValueError, match="count"):
            parse_roles("prefill:0,decode:2")

    def test_decode_only_fleet_rejected(self, model):
        with pytest.raises(ValueError, match="prefill-capable"):
            _fleet(model, roles="decode:2")

    def test_fresh_submits_avoid_decode_replicas(self, model):
        router, _ = _fleet(model, roles="prefill:1,decode:2")
        assert [h.role for h in router.replicas] \
            == [ReplicaRole.PREFILL, ReplicaRole.DECODE,
                ReplicaRole.DECODE]
        ids = [router.submit(p, n) for p, n in JOBS[:3]]
        assert all(router.requests[i].replica == 0 for i in ids)
        snap = telemetry.snapshot()["counters"]
        dispatched_to = {lbl for lbl in
                         snap.get("pdt_router_dispatch_total", {})}
        assert not any('replica="1"' in s or 'replica="2"' in s
                       for s in dispatched_to)


class TestTransferPlane:
    def test_migrate_mid_stream_bit_identical(self, model, oracle):
        ref = [oracle[0]]
        src, dst = _engine(model), _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        src.step()                              # mid-decode: 3 tokens
        req, payload = transfer.migrate_request(src, dst, rid)
        src.check_invariants()
        dst.check_invariants()
        assert src.get_request(rid) is None     # evicted, not terminal
        assert src.lifecycle_info()["running"] == 0
        assert req.output == ref[0][:len(req.output)]
        done = {}
        while src._queue or dst._queue \
                or any(r is not None for r in dst._slot_req):
            for r in dst.step():
                done[r.request_id] = r
            src.step()                          # source keeps serving
        assert done[req.request_id].status == RequestStatus.FINISHED
        assert done[req.request_id].output == ref[0]
        assert telemetry.value("pdt_transfer_migrations_total") == 1
        assert telemetry.value("pdt_transfer_bytes_total") > 0
        assert payload["request_id"] == req.request_id

    def test_export_validations(self, model):
        src = _engine(model)
        with pytest.raises(ValueError, match="no resident request"):
            src.export_pages(99)
        # a queued (never admitted) request has no pages to export
        src2 = _engine(model, max_batch_size=1)
        src2.add_request(*JOBS[0])
        waiting = src2.add_request(*JOBS[1])
        src2.step()
        with pytest.raises(ValueError, match="no resident request"):
            src2.export_pages(waiting)

    def test_import_validations_and_capacity(self, model):
        src = _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        payload = transfer.serialize_request(src, rid)
        geom = _engine(model, page_size=8)
        with pytest.raises(ValueError, match="page_size"):
            geom.import_pages(payload)
        full = _engine(model, max_batch_size=1)
        full.add_request(*JOBS[1])
        full.step()
        with pytest.raises(EngineOverloaded, match="no free slot"):
            full.import_pages(payload)
        # source was never touched: the request is still live there
        assert src.get_request(rid) is not None
        src.check_invariants()

    def test_import_attaches_target_warm_prefix(self, model, oracle):
        # warm the target's trie with the shared system prompt first
        dst = _engine(model)
        warm_rid = dst.add_request(SYS + [50, 12], 4)
        dst.run()
        assert dst._prefix_nodes                 # SYS pages registered
        src = _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        req, _ = transfer.migrate_request(src, dst, rid)
        dst.check_invariants()
        slot = dst._slot_req.index(req)
        # the two full SYS pages attached read-only instead of copying
        assert len(dst._slot_shared_pages[slot]) == 2
        res = dst.run()
        assert res[req.rid] == oracle[0]
        assert warm_rid is not None

    def test_evict_keeps_source_chain_warm(self, model):
        src, dst = _engine(model), _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        transfer.migrate_request(src, dst, rid)
        assert src._prefix_nodes                # chain registered at evict
        rid2 = src.add_request(*JOBS[1])        # same SYS prefix
        src.run()
        assert src.prefix_hits == 1 and src.prefix_tokens_reused == 8
        assert rid2 is not None

    def test_transfer_fault_sites_fire_and_isolate(self, model, oracle):
        ref = [oracle[0]]
        src, dst = _engine(model), _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        with FaultInjector() as fi:
            fi.arm("transfer.serialize", nth=1)
            with pytest.raises(FaultError):
                transfer.migrate_request(src, dst, rid)
        with FaultInjector() as fi:
            fi.arm("transfer.install", nth=1)
            with pytest.raises(FaultError):
                transfer.migrate_request(src, dst, rid)
        src.check_invariants()
        dst.check_invariants()
        assert dst.lifecycle_info()["running"] == 0     # backed out
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="serialize") == 1
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="install") == 1
        # both engines stayed consistent: the migration then succeeds
        req, _ = transfer.migrate_request(src, dst, rid)
        res = dst.run()
        assert res[req.rid] == ref[0]


class TestPrefixStore:
    def test_chain_hash_shared_with_policy(self):
        pol = PrefixAffinityPolicy(page_size=4)
        prompt = SYS + [3, 1, 4]
        assert pol._chain_hashes(prompt) == chain_hashes(prompt, 4)

    def test_record_lookup_forget(self):
        store = FleetPrefixStore(page_size=4)
        store.record(0, SYS + [1])
        hashes = chain_hashes(SYS + [9, 9], 4)
        assert store.longest_warm(0, hashes) == 2
        assert store.longest_warm(1, hashes) == 0
        store.forget_replica(0)
        assert store.longest_warm(0, hashes) == 0
        assert store.stats()["chains"] == 2

    def test_spill_fetch_import_prefix_roundtrip(self, model, oracle):
        src = _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        payload = transfer.serialize_request(src, rid)
        store = FleetPrefixStore(page_size=4)
        assert store.spill_payload(payload) == 2        # both SYS pages
        entry = store.fetch(SYS + [77, 78])
        assert entry is not None
        tokens, kv_rows = entry
        assert [len(t) for t in tokens] == [4, 4]
        fresh = _engine(model)
        assert fresh.import_prefix(tokens, kv_rows) == 2
        fresh.check_invariants()
        rid2 = fresh.add_request(*JOBS[1])
        res = fresh.run()
        assert fresh.prefix_hits == 1                   # spill revived
        assert res[rid2] == oracle[1]
        assert store.fetch([1, 2, 3, 4, 5]) is None

    def test_import_prefix_respects_free_pool(self, model):
        """Restoring a spilled chain draws only on genuinely FREE
        pages — it must not evict resident chains, and (the review
        repro) a mid-build eviction must never corrupt the trie: a
        3-page chain into a 2-usable-page pool installs exactly what
        fits and the engine keeps serving."""
        src = _engine(model)
        long_prompt = SYS + [3, 1, 4, 1, 5]     # 3 full chain pages
        rid = src.add_request(long_prompt, 4)
        src.step()
        payload = transfer.serialize_request(src, rid)
        store = FleetPrefixStore(page_size=4)
        assert store.spill_payload(payload) == 3
        tokens, kv_rows = store.fetch(long_prompt)
        tiny = _engine(model, max_batch_size=1, num_pages=3)
        assert tiny.import_prefix(tokens, kv_rows) == 2
        tiny.check_invariants()
        # the partially-restored chain is ordinary cache content:
        # admission can evict it under pressure and serve normally
        r2 = tiny.add_request([1, 2, 3, 4], 4)
        res = tiny.run()
        assert len(res[r2]) == 4
        tiny.check_invariants()

    def test_spill_budget_evicts_lru_content(self, model):
        src = _engine(model)
        rid = src.add_request(*JOBS[0])
        src.step()
        payload = transfer.serialize_request(src, rid)
        page_bytes = sum(k[:, 0].nbytes + v[:, 0].nbytes
                         for k, v in payload["kv"])
        store = FleetPrefixStore(page_size=4,
                                 spill_budget_bytes=page_bytes)
        store.spill_payload(payload)            # 2 pages > 1-page budget
        assert store.spilled_bytes <= page_bytes
        assert store.evictions >= 1
        stats = store.stats()
        assert stats["spilled_chains"] < 2
        assert stats["chains"] == 2             # warmth records survive


class TestDisaggFleet:
    def test_disagg_fleet_matches_colocated_engine(self, model, oracle):
        """The acceptance drill: a prefill:2,decode:2 fleet on the
        shared-prefix workload produces greedy outputs bit-identical to
        a colocated run, every request migrates exactly once, decode
        replicas take no fresh submits, and fleet-vs-engine terminal
        counters reconcile exactly under roles."""
        ref = oracle
        # an earlier test's engines ticked the global pdt_serving_* counters;
        # baseline them so reconciliation measures the fleet run alone
        eng_base = telemetry.value("pdt_serving_requests_terminal_total",
                                   status="finished")
        router, _ = _fleet(model, roles="prefill:2,decode:2")
        ids = [router.submit(p, n) for p, n in JOBS]
        out = router.run()
        assert [out[i] for i in ids] == ref
        assert router.num_migrations == len(JOBS)
        assert telemetry.value("pdt_transfer_migrations_total") \
            == len(JOBS)
        # terminal counters reconcile exactly under roles
        assert telemetry.value("pdt_router_requests_terminal_total",
                               status="finished") == len(JOBS)
        assert telemetry.value("pdt_serving_requests_terminal_total",
                               status="finished") - eng_base \
            == len(JOBS)
        # decode replicas never saw a fresh dispatch, only migrations
        snap = telemetry.snapshot()["counters"]
        for lbl in snap.get("pdt_router_dispatch_total", {}):
            assert 'replica="2"' not in lbl and 'replica="3"' not in lbl
        # decode dispatch balanced outstanding slots across both
        info = router.fleet_info()
        roles = info["roles"]
        assert roles["prefill"]["replicas"] == 2
        assert roles["decode"]["replicas"] == 2
        assert roles["prefill"]["migrations"] == len(JOBS)
        assert roles["decode"]["migrations"] == len(JOBS)
        assert min(h.migrations_in for h in router.replicas[2:]) >= 1
        assert info["migrations"] == len(JOBS)
        assert info["prefix_store"]["chains"] >= 2
        rendered = telemetry.render_fleet_status(info)
        assert "prefill" in rendered and "roles" in rendered

    def test_no_decode_capacity_serves_colocated_style(self, model,
                                                       oracle):
        """Liveness: with every decode replica permanently dead, prefill
        replicas keep decoding their own work — migration is an
        optimization, never a dependency."""
        ref = oracle[:2]
        router, _ = _fleet(model, roles="prefill:1,decode:1",
                           max_restarts=0)
        router.kill_replica(1)
        ids = [router.submit(p, n) for p, n in JOBS[:2]]
        out = router.run()
        assert [out[i] for i in ids] == ref
        assert router.num_migrations == 0

    def test_kill_prefill_endpoint_mid_migration_zero_loss(self, model,
                                                           oracle):
        """SIGKILL of the SOURCE endpoint mid-transfer: the serialize
        fault marks the transfer dead, the replica is killed, and the
        failover machinery re-prefills on a survivor with streamed
        tokens folded in — greedy outputs bit-identical."""
        ref = oracle[:3]
        router, clock = _fleet(model, roles="prefill:1,decode:1",
                               restart_backoff_base=2.0,
                               restart_backoff_max=2.0)
        ids = [router.submit(p, n) for p, n in JOBS[:3]]
        with FaultInjector() as fi:
            fi.arm("transfer.serialize", always=True)
            router.step()               # prefills land; migrations die
            assert fi.trips("transfer.serialize") >= 1
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="serialize") >= 1
        router.kill_replica(0)          # SIGKILL the source endpoint
        clock.advance(2.5)
        out = router.run()
        assert [out[i] for i in ids] == ref
        assert router.num_failovers >= 1

    def test_kill_decode_endpoint_after_install_zero_loss(self, model,
                                                          oracle):
        """SIGKILL of the TARGET endpoint just after pages installed:
        the migrated request dies with the decode replica and fails
        over (re-prefill, tokens folded) — still bit-identical."""
        ref = oracle[:2]
        router, clock = _fleet(model, roles="prefill:1,decode:1",
                               restart_backoff_base=2.0,
                               restart_backoff_max=2.0)
        ids = [router.submit(p, n) for p, n in JOBS[:2]]
        router.step()                   # prefill + migrate to replica 1
        migrated = [i for i in ids
                    if router.requests[i].replica == 1]
        assert migrated                 # at least one landed on decode
        router.kill_replica(1)
        clock.advance(2.5)
        out = router.run()
        assert [out[i] for i in ids] == ref
        assert router.num_failovers >= 1

    def test_migration_respects_replica_outstanding_bound(self, model,
                                                          oracle):
        """The bounded per-replica queue holds for MIGRATED work too
        (review repro): one tick finishing more prefills than the
        decode tier has headroom must not pile them past
        max_replica_outstanding — the surplus keeps decoding on its
        prefill replica until slots free."""
        router, _ = _fleet(model, roles="prefill:4,decode:1",
                           max_replica_outstanding=1)
        ids = [router.submit(p, n) for p, n in JOBS[:4]]
        router.step()               # up to 4 prefills finish this tick
        assert router.replicas[4].outstanding() <= 1
        out = router.run()
        assert [out[i] for i in ids] == oracle[:4]

    def test_install_fault_defers_and_retries(self, model, oracle):
        ref = oracle[:1]
        router, _ = _fleet(model, roles="prefill:1,decode:1")
        rid = router.submit(*JOBS[0])
        with FaultInjector() as fi:
            fi.arm("transfer.install", nth=1)
            router.step()               # first migration attempt fails
        assert router.requests[rid].replica == 0    # still on source
        out = router.run()              # next step retries and succeeds
        assert out[rid] == ref[0]
        assert router.num_migrations == 1
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="install") == 1

    def test_spill_revives_prefix_after_replica_death(self, model,
                                                      oracle):
        """The fleet-wide story: a chain warm only on a dead replica is
        re-installed from the host-RAM spill into the next prefill
        replica — the prefix outlives every engine that computed it."""
        router, clock = _fleet(model, roles="prefill:2,decode:1",
                               restart_backoff_base=2.0,
                               restart_backoff_max=2.0)
        a = router.submit(*JOBS[0])
        router.run()                    # migrated: prompt chain spilled
        assert router.prefix_store.stats()["spilled_chains"] == 2
        victim = 0 if telemetry.value(
            "pdt_router_dispatch_total", policy="prefix_affinity",
            replica="0") else 1
        router.kill_replica(victim)     # the only warm replica dies
        b = router.submit(*JOBS[1])     # same SYS prefix, cold fleet
        out = router.run()
        assert out[b] == oracle[1]
        stats = router.prefix_store.stats()
        assert stats["spill_hits"] >= 1
        assert router.fleet_info()["prefix_hits"] >= 1  # engine-level hit
        assert telemetry.value("pdt_prefix_store_hits_total",
                               source="spill") >= 1
        assert a is not None

    def test_obs_cli_status_renders_roles(self, model, tmp_path,
                                          capsys):
        from paddle_tpu.observability.__main__ import main as obs_main
        router, _ = _fleet(model, roles="prefill:1,decode:1")
        path = tmp_path / "fleet.json"
        path.write_text(json.dumps(router.fleet_info()))
        assert obs_main(["status", "--from", str(path)]) == 0
        rendered = capsys.readouterr().out
        assert "roles" in rendered and "prefill" in rendered \
            and "decode" in rendered
        assert "prefix store" in rendered


class TestMigrationTiming:
    """ISSUE 9 (pdt-lint PDT001, the live hit that motivated the
    rule): `migrate_request` timed migrations on
    `time.perf_counter()`, so the `pdt_transfer_seconds` quantiles the
    bench reports could never be driven by the tests' fake clocks.
    The clock is now injectable and the router threads ITS clock
    through every hand-off."""

    def test_fake_clock_drives_transfer_histogram(self, model):
        src, dst = _engine(model), _engine(model)
        rid = src.add_request([5, 4, 3, 2, 6, 7], 6)
        src.step()                      # prefill -> RUNNING w/ output
        ticks = iter([10.0, 11.5])
        transfer.migrate_request(src, dst, rid,
                                 clock=lambda: next(ticks))
        h = telemetry.snapshot()["histograms"]["pdt_transfer_seconds"]
        assert h[""]["count"] == 1
        assert h[""]["sum"] == pytest.approx(1.5)

    def test_router_migrations_run_on_the_router_clock(self, model):
        router, clock = _fleet(model, "prefill:1,decode:1")
        rids = [router.submit(p, n) for p, n in
                [([5, 4, 3, 2, 6, 7], 8), ([9, 1, 2], 6)]]
        out = router.run()
        assert router.num_migrations >= 1
        assert all(len(out[r]) > 0 for r in rids)
        h = telemetry.snapshot()["histograms"]["pdt_transfer_seconds"]
        # the fake clock does not advance inside one step tick, so a
        # migration timed on the ROUTER clock observes exactly 0.0 —
        # any perf_counter leak would observe real (nonzero) wall time
        assert h[""]["count"] == router.num_migrations
        assert h[""]["sum"] == 0.0


class TestPayloadIntegrity:
    """Migration payload integrity (ISSUE 13): `export_pages` attaches
    a sha256 per KV shard fragment (the manifest.py hashing
    discipline) and `import_pages` verifies BEFORE install — a flipped
    byte in flight is a counted `stage="verify"` transfer failure that
    leaves both engines consistent."""

    @staticmethod
    def _flip(payload, which=0):
        """Corrupt one byte of a KV fragment (the exported arrays are
        read-only views of device memory — corrupting a copy is
        exactly what in-flight damage looks like)."""
        pair = list(payload["kv"][0])
        arr = pair[which].copy()
        arr.flat[arr.size // 2] += 1.0
        pair[which] = arr
        payload["kv"][0] = tuple(pair)

    def _running_payload(self, model):
        src = _engine(model)
        rid = src.add_request([5, 4, 3, 2, 6, 7], 6)
        src.step()
        src.step()
        return src, rid, src.export_pages(rid)

    def test_export_attaches_sha256_manifest(self, model):
        src, rid, payload = self._running_payload(model)
        want_layers = len(payload["kv"])
        assert len(payload["kv_sha256"]) == 1          # one shard
        assert len(payload["kv_sha256"][0]) == want_layers
        for k_sha, v_sha in payload["kv_sha256"][0]:
            assert k_sha.startswith("sha256:")
            assert v_sha.startswith("sha256:")
        # the manifest covers the actual bytes: recompute == attached
        from paddle_tpu.models.serving import payload_checksums
        assert payload_checksums(payload) == payload["kv_sha256"]

    def test_corrupt_payload_refused_before_any_mutation(self, model):
        from paddle_tpu.models.serving import PayloadCorruption
        src, rid, payload = self._running_payload(model)
        dst = _engine(model)
        self._flip(payload)
        before = dst.cache_memory_info()["pages_in_use"]
        with pytest.raises(PayloadCorruption):
            transfer.install_request(dst, payload)
        src.check_invariants()
        dst.check_invariants()
        assert dst.cache_memory_info()["pages_in_use"] == before
        assert src.get_request(rid) is not None   # source still owns it
        # a clean payload still installs afterwards: the refusal left
        # the target fully serviceable
        req = transfer.install_request(dst, src.export_pages(rid))
        assert req.request_id == payload["request_id"]

    def test_migrate_books_stage_verify(self, model):
        from paddle_tpu.models.serving import PayloadCorruption
        src, rid, _ = self._running_payload(model)
        dst = _engine(model)
        flip = self._flip

        class CorruptingWire:
            """A source whose exported payloads are damaged in flight."""

            def get_request(self, r):
                return src.get_request(r)

            def export_pages(self, r):
                p = src.export_pages(r)
                flip(p, which=1)
                return p

        with pytest.raises(PayloadCorruption):
            transfer.migrate_request(CorruptingWire(), dst, rid)
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="verify") == 1
        events = [e for e in telemetry.events()
                  if e["name"] == "transfer.failed"]
        assert events and events[-1]["attrs"]["stage"] == "verify"
        src.check_invariants()
        dst.check_invariants()
        assert src.get_request(rid) is not None   # never evicted

    def test_router_falls_back_to_source_on_corrupt_wire(
            self, model, oracle, monkeypatch):
        """A corrupt payload at the router's migration pass: the
        request keeps decoding on its consistent source and the
        outputs stay bit-identical to the colocated oracle."""
        flip = self._flip
        real_serialize = transfer.serialize_request
        corrupted = {"n": 0}

        def bad_serialize(engine, rid):
            p = real_serialize(engine, rid)
            if corrupted["n"] == 0:
                corrupted["n"] += 1
                flip(p)
            return p

        monkeypatch.setattr(transfer, "serialize_request",
                            bad_serialize)
        router, clock = _fleet(model, "prefill:1,decode:1")
        rids = [router.submit(p, n) for p, n in JOBS[:2]]
        out = router.run()
        assert corrupted["n"] == 1
        assert [out[r] for r in rids] == oracle[:2]
        assert router.fleet_info()["pending"] == 0
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="verify") == 1
