"""Tensor-parallel serving replicas (ISSUE 12, serving/submesh.py).

One replica = one GSPMD submesh on the 8-simulated-device harness:
submesh carving, sharded-allocator invariants, per-shard migration
payload round-trips, tp=1-vs-tp=2 BIT-IDENTICAL greedy outputs through
SIGKILL failover and prefill->decode migration, spec-decode-on-TP, the
sharded kernel's shard_map parity, and the mesh-axis drift guard
(docs/serving.md "Tensor parallelism" axis table == the specs
serving/submesh.py actually builds).
"""
import ast
import os
import re

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       SpecConfig, assemble_payload_kv)
from paddle_tpu.serving import (ServingRouter, TP_AXIS, TpConfig,
                                carve_submeshes, transfer)
from paddle_tpu.serving.submesh import SubMesh

pytestmark = pytest.mark.chaos  # fast tier, runs in tier-1

NEW_TOKENS = 10
MAX_SEQ = 96


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def jobs(model):
    rng = np.random.default_rng(7)
    v = model.config.vocab_size
    return [rng.integers(1, v, int(rng.integers(6, 18))).tolist()
            for _ in range(6)]


@pytest.fixture(scope="module")
def oracle(model, jobs):
    """Greedy outputs of a plain single-chip engine — the tp=1 truth
    every TP drill below must reproduce bit-identically."""
    eng = ContinuousBatchingEngine(model, max_batch_size=3,
                                   max_seq_len=MAX_SEQ,
                                   enable_prefix_caching=True)
    rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
    out = eng.run()
    return [out[r] for r in rids]


def _tp_engine(model, sm, **kw):
    return ContinuousBatchingEngine(model, max_batch_size=3,
                                    max_seq_len=MAX_SEQ, submesh=sm,
                                    **kw)


# -- carving + validation ----------------------------------------------
class TestCarving:
    def test_disjoint_slices(self):
        meshes = carve_submeshes(4, TpConfig(tp=2))
        ids = [m.device_ids for m in meshes]
        flat = [d for t in ids for d in t]
        assert len(flat) == len(set(flat)) == 8
        assert all(len(t) == 2 for t in ids)
        d = meshes[1].describe()
        assert d["tp"] == 2 and d["mode"] == "exact" \
            and len(d["devices"]) == 2

    def test_fleet_must_fit(self):
        with pytest.raises(ValueError, match="needs 16 devices"):
            carve_submeshes(4, TpConfig(tp=4))

    def test_tpconfig_validation(self):
        with pytest.raises(ValueError, match="tp must be >= 1"):
            TpConfig(tp=0)
        with pytest.raises(ValueError, match="exact|fast"):
            TpConfig(tp=2, mode="turbo")

    def test_model_must_split(self, model):
        # tiny(): 4 q heads / 2 kv heads — tp=4 cannot shard the pages
        sm = SubMesh(jax.devices()[:4], TpConfig(tp=4))
        with pytest.raises(ValueError, match="num_key_value_heads"):
            _tp_engine(model, sm)


# -- engine-level parity + sharded allocator ---------------------------
class TestTpEngine:
    def test_bit_identical_greedy(self, model, jobs, oracle):
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        eng = _tp_engine(model, sm, enable_prefix_caching=True)
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
        out = eng.run()
        assert [out[r] for r in rids] == oracle
        assert telemetry.value("pdt_tp_dispatches_total") >= 1
        assert telemetry.value("pdt_tp_shards") == 2

    def test_sharded_allocator_invariants(self, model, jobs):
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        eng = _tp_engine(model, sm)
        eng.add_request(jobs[0], NEW_TOKENS)
        eng.step()
        eng.check_invariants()       # pools on-submesh, spec declared
        hk = model.config.num_key_value_heads
        kp = eng._kv[0][0]
        assert set(kp.sharding.device_set) == set(sm.devices)
        # one logical page = tp local shards: each shard holds hk/tp
        # heads (a contiguous run of every stored row) of the WHOLE
        # pool
        hd = model.config.head_dim
        assert kp.shape[2] == hk * hd
        shard_shapes = {s.data.shape for s in kp.addressable_shards}
        assert shard_shapes == {kp.shape[:2] + (hk // 2 * hd,)}
        # a resharded pool must be caught by the invariant checker
        from paddle_tpu.models.serving import EngineInvariantError
        good = eng._kv[0]
        eng._kv[0] = (jax.device_put(np.asarray(kp), jax.devices()[7]),
                      good[1])
        with pytest.raises(EngineInvariantError, match="submesh"):
            eng.check_invariants()
        eng._kv[0] = good
        eng.check_invariants()

    def test_exact_mode_fences_are_scoped(self, model, jobs, oracle):
        # a plain engine built AFTER a TP engine must stay unaffected
        # (the trace context is scoped to TP dispatches only)
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        _tp_engine(model, sm).add_request(jobs[0], 2)
        from paddle_tpu.distributed.mesh import serving_tp
        assert serving_tp() is None
        eng = ContinuousBatchingEngine(model, max_batch_size=3,
                                       max_seq_len=MAX_SEQ,
                                       enable_prefix_caching=True)
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
        out = eng.run()
        assert [out[r] for r in rids] == oracle


# -- per-shard migration payloads --------------------------------------
class TestPerShardTransfer:
    def test_export_import_roundtrip(self, model, jobs, oracle):
        sms = carve_submeshes(2, TpConfig(tp=2))
        src = _tp_engine(model, sms[0])
        dst = _tp_engine(model, sms[1])
        rid = src.add_request(jobs[0], NEW_TOKENS)
        for _ in range(3):
            src.step()
        payload = transfer.serialize_request(src, rid)
        # the wire format is one fragment per shard; nbytes counts the
        # fragments (sum == the logical bytes, no double count)
        assert payload["kv"] is None and payload["tp"] == 2
        assert len(payload["kv_shards"]) == 2
        frag_bytes = sum(k.nbytes + v.nbytes
                         for sh in payload["kv_shards"] for k, v in sh)
        assert transfer.payload_nbytes(payload) == frag_bytes
        logical = assemble_payload_kv(payload)
        hk = model.config.num_key_value_heads
        assert logical[0][0].shape[0] == hk
        assert frag_bytes == sum(k.nbytes + v.nbytes
                                 for k, v in logical)
        # shard-bytes metering: one series per shard, equal halves
        b0 = telemetry.value("pdt_tp_migration_shard_bytes_total",
                             shard="0")
        b1 = telemetry.value("pdt_tp_migration_shard_bytes_total",
                             shard="1")
        assert b0 == b1 and b0 > 0
        new_req, _ = transfer.migrate_request(src, dst, rid)
        while not new_req.done:
            dst.step()
        assert new_req.output == oracle[0]
        src.check_invariants()
        dst.check_invariants()

    def test_spill_store_handles_fragment_payloads(self, model, jobs):
        from paddle_tpu.serving.prefix_store import FleetPrefixStore
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        eng = _tp_engine(model, sm, page_size=8,
                         enable_prefix_caching=True)
        rid = eng.add_request(jobs[1][:3] * 8, NEW_TOKENS)
        eng.step()
        payload = transfer.serialize_request(eng, rid)
        store = FleetPrefixStore(page_size=8)
        spilled = store.spill_payload(payload)
        assert spilled >= 1
        entry = store.fetch(payload["prompt"])
        assert entry is not None
        hk = model.config.num_key_value_heads
        assert entry[1][0][0].shape[0] == hk     # logical rows stored


# -- fleet drills -------------------------------------------------------
class TestTpFleet:
    def _factory(self, model):
        def make(i, sm):
            return _tp_engine(model, sm, enable_prefix_caching=True)
        return make

    def test_kill_a_submesh_bit_identical(self, model, jobs, oracle):
        router = ServingRouter(self._factory(model), num_replicas=2,
                               tp=2)
        ids = [router.submit(p, NEW_TOKENS) for p in jobs]
        router.step()
        router.step()                       # mid-decode
        victim = router.requests[ids[0]].replica
        router.kill_replica(victim)         # SIGKILL one whole submesh
        out = router.run()
        assert [out[i] for i in ids] == oracle
        info = router.fleet_info()
        assert info["failovers"] >= 1
        assert info["tp"]["tp"] == 2
        subs = [r["submesh"] for r in info["replicas"]]
        assert all(s and len(s["devices"]) == 2 for s in subs)
        assert len({tuple(s["devices"]) for s in subs}) == 2
        # replica identity is (submesh, generation): the restarted
        # victim reports the SAME device slice
        assert router.replicas[victim].submesh.device_ids \
            == tuple(subs[victim]["devices"])
        from paddle_tpu.observability.status import render_fleet_status
        text = render_fleet_status(info)
        assert "submesh" in text and "tp=2@[" in text

    def test_roles_migration_bit_identical(self, model, jobs, oracle):
        router = ServingRouter(self._factory(model),
                               roles="prefill:1,decode:1", tp=2,
                               policy="prefix_affinity", page_size=16)
        ids = [router.submit(p, NEW_TOKENS) for p in jobs]
        out = router.run()
        assert [out[i] for i in ids] == oracle
        info = router.fleet_info()
        assert info["migrations"] >= 1
        assert telemetry.value("pdt_tp_migration_shard_bytes_total",
                               shard="0") > 0


# -- speculative decoding on TP ----------------------------------------
class TestSpecOnTp:
    def test_self_draft_smoke(self, model, jobs, oracle):
        # target == draft: acceptance must be total and the stream
        # bit-identical to the plain tp=1 engine — the draft scan,
        # backfill, and verify all ran on the submesh
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        eng = _tp_engine(model, sm,
                         spec_decode=SpecConfig(model, k=3))
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs[:3]]
        out = eng.run()
        assert [out[r] for r in rids] == oracle[:3]
        assert eng.num_spec_rounds >= 1
        assert eng.num_spec_accepted == eng.num_spec_proposed > 0

    def test_draft_pool_invariants(self, model, jobs):
        # the draft pools feed the same per-shard kernel path as the
        # target pools — a relocated draft pool must be caught by the
        # same invariant checker, not surface later as wrong proposals
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        eng = _tp_engine(model, sm, spec_decode=SpecConfig(model, k=3))
        eng.add_request(jobs[0], NEW_TOKENS)
        eng.step()
        eng.check_invariants()
        from paddle_tpu.models.serving import EngineInvariantError
        good = eng._d_kv[0]
        eng._d_kv[0] = (jax.device_put(np.asarray(good[0]),
                                       jax.devices()[7]), good[1])
        with pytest.raises(EngineInvariantError, match="draft-k-pool"):
            eng.check_invariants()
        eng._d_kv[0] = good
        eng.check_invariants()


# -- sharded kernel path ------------------------------------------------
class TestShardMapKernel:
    def test_interpret_parity_under_tp(self):
        """The Pallas kernel via shard_map over `tp` (the on-TPU path,
        forced in interpret mode) == the XLA oracle on head-sharded
        pools with replicated descriptors."""
        from jax.sharding import NamedSharding, PartitionSpec
        from paddle_tpu.ops.ragged_paged_attention import (
            pack_ragged_starts, ragged_paged_attention_values,
            token_arrays)
        rng = np.random.default_rng(3)
        hk, g, d, ps, pps, n = 2, 2, 8, 4, 4, 3
        h = hk * g
        sm = carve_submeshes(1, TpConfig(tp=2))[0]
        qlens = [3, 1, 5]
        ctx = np.asarray([7, 9, 5], np.int32)
        qstart, t = pack_ragged_starts(qlens, block_q=4)
        q = rng.standard_normal((t, h, d)).astype(np.float32)
        kp = rng.standard_normal((16, ps, hk * d)).astype(np.float32)
        vp = rng.standard_normal((16, ps, hk * d)).astype(np.float32)
        bt = rng.integers(1, 16, (n, pps)).astype(np.int32)
        qlen = np.asarray(qlens, np.int32)
        want = np.asarray(ragged_paged_attention_values(
            q, kp, vp, qstart, qlen, ctx, bt, use_kernel=False))
        assert sm.kv_sharding(hk).spec == PartitionSpec(None, None,
                                                         TP_AXIS)
        shard = sm.kv_sharding(hk)
        got = np.asarray(ragged_paged_attention_values(
            jax.device_put(q, NamedSharding(
                sm.jax_mesh, PartitionSpec(None, TP_AXIS, None))),
            jax.device_put(kp, shard), jax.device_put(vp, shard),
            qstart, qlen, ctx, bt, use_kernel=True, block_q=4,
            tp=(sm.jax_mesh, TP_AXIS)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- drift guard: mesh-axis names vs the documented axis table ----------
class TestAxisTableDrift:
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _doc_axes(self):
        doc = open(os.path.join(self.ROOT, "docs/serving.md")).read()
        section = doc.split("### Tensor parallelism", 1)[1]
        table = section.split("| axis | meaning |", 1)[1]
        axes = set()
        for line in table.splitlines():
            m = re.match(r"\|\s*`(\w+)`\s*\|", line)
            if m:
                axes.add(m.group(1))
            elif axes and not line.startswith("|"):
                break                        # table ended
        return axes

    def _spec_axes(self):
        """Every string literal an explicit PartitionSpec(...) in
        serving/submesh.py names, plus the TP_AXIS constant — the
        axes serving shardings can possibly use."""
        src = open(os.path.join(
            self.ROOT, "paddle_tpu/serving/submesh.py")).read()
        tree = ast.parse(src)
        axes, consts = set(), {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and node.targets \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                consts[node.targets[0].id] = node.value.value
            if isinstance(node, ast.Call) \
                    and getattr(node.func, "id",
                                getattr(node.func, "attr", "")) \
                    == "PartitionSpec":
                for a in ast.walk(node):
                    if isinstance(a, ast.Constant) \
                            and isinstance(a.value, str):
                        axes.add(a.value)
                    if isinstance(a, ast.Name) and a.id in consts:
                        axes.add(consts[a.id])
        axes.add(consts["TP_AXIS"])
        return axes

    def test_axes_match_doc_table(self):
        doc, spec = self._doc_axes(), self._spec_axes()
        assert doc == spec == {TP_AXIS}, (
            f"mesh-axis drift: docs/serving.md table {sorted(doc)} vs "
            f"serving/submesh.py specs {sorted(spec)} — axis names are "
            "stringly-typed; update both sides together")
