"""Quantized serving end to end (ISSUE 15): int8/fp8 weight matmuls
through the fused dequant-matmul epilogue and int8 KV pages with
per-page scales through the ragged kernel.

Layers covered, bottom up: the ONE shared absmax round-clip core every
quantizer routes through; `ops/quant_matmul.py` interpret-mode kernel
parity against an independent NumPy oracle; quantized-page scatter +
attention (`ragged_scatter_quantized`) against a NumPy oracle, incl.
the PATH-INVARIANCE property (incremental vs bulk commits produce
bit-identical int8 pools) the chaos bit-identity rests on; the engine
mode (`quant=QuantServingConfig(...)`) — determinism, preemption
bit-identity, the logit-error budget vs the full-width engine on fixed
prompts; migration byte honesty (~payload bytes quartered vs the f32
CPU pools, scales counted) and cross-mode refusals (QuantMismatch,
both directions, import + prefix-spill paths); sentry/canary
compatibility (the golden is factory-derived, so a quantized fleet
canaries against a QUANTIZED golden — satellite 1's
false-quarantine regression); and tp=2 on the 8-simulated-device
harness (bit-identical to quantized tp=1 through SIGKILL failover).
conftest enables PDT_TELEMETRY=1 + PDT_CHECK_INVARIANTS=1 here."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       QuantMismatch,
                                       QuantServingConfig, SpecConfig,
                                       verify_payload)
from paddle_tpu.serving import ServingRouter, TpConfig, transfer
from paddle_tpu.serving.prefix_store import FleetPrefixStore
from paddle_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.chaos          # fast tier, runs in tier-1

Q8 = QuantServingConfig(weights="int8", kv="int8")
NEW_TOKENS = 10
MAX_SEQ = 96


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


class RecorderSentry:
    """Minimal attach_sentry-shaped logit recorder: pulls every decode
    step's sampled-row logits to host (the logit-budget probe)."""
    wants_logits = True

    def __init__(self):
        self.logits = []
        self.trips = 0

    def step_tick(self):
        return True

    def observe_tokens(self, toks):
        pass

    def observe_logits(self, lg):
        self.logits.append(np.asarray(lg, np.float32))

    def note_cost(self, s):
        pass


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def jobs(model):
    rng = np.random.default_rng(11)
    v = model.config.vocab_size
    return [rng.integers(1, v, int(rng.integers(6, 18))).tolist()
            for _ in range(4)]


def _engine(model, quant=Q8, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_seq_len", MAX_SEQ)
    return ContinuousBatchingEngine(model, quant=quant, **kw)


@pytest.fixture(scope="module")
def quant_oracle(model, jobs):
    """Greedy outputs of an uninterrupted quantized engine — the truth
    every quantized chaos/migration drill must reproduce
    bit-identically (bit-identity is WITHIN quantized mode; values
    legitimately differ from bf16)."""
    eng = _engine(model)
    rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
    out = eng.run()
    return [out[r] for r in rids]


# -- the shared round-clip core ----------------------------------------
class TestRoundClipCore:
    def test_matches_numpy_reference(self):
        from paddle_tpu.nn.quant import absmax_round_clip_values
        rng = np.random.default_rng(0)
        v = rng.normal(size=(64,)).astype(np.float32) * 3
        s = np.float32(np.abs(v).max())
        got = np.asarray(absmax_round_clip_values(
            jnp.asarray(v), s, 127.0, out_dtype=jnp.int8))
        want = np.clip(np.round(v / s * 127.0), -128, 127).astype(np.int8)
        np.testing.assert_array_equal(got, want)

    def test_negative_extreme_reaches_minus_128(self):
        from paddle_tpu.nn.quant import absmax_round_clip_values
        # the asymmetric clip keeps int8's full range: -absmax rounds
        # to -127, but a value past -absmax (stale scale) saturates
        # at -128, not wraps
        got = np.asarray(absmax_round_clip_values(
            jnp.asarray([-2.0, -1.0, 1.0]), 1.0, 127.0,
            out_dtype=jnp.int8))
        np.testing.assert_array_equal(got, [-128, -127, 127])

    def test_zero_scale_guard(self):
        from paddle_tpu.nn.quant import absmax_round_clip_values
        got = np.asarray(absmax_round_clip_values(
            jnp.zeros(4), 0.0, 127.0, out_dtype=jnp.int8))
        np.testing.assert_array_equal(got, np.zeros(4, np.int8))

    def test_quantize_linear_rides_the_core(self):
        # satellite 6: the quantization/ entry points are thin wrappers
        # over the ONE core — same lattice, bit for bit
        from paddle_tpu import quantization as q
        from paddle_tpu.nn.quant import absmax_round_clip_values
        rng = np.random.default_rng(1)
        w = rng.normal(size=(8, 8)).astype(np.float32)
        s = np.abs(w).max()
        got = q.quantize_linear(paddle.to_tensor(w), float(s))
        want = np.asarray(absmax_round_clip_values(
            jnp.asarray(w), jnp.float32(s), 127.0, out_dtype=jnp.int8))
        np.testing.assert_array_equal(np.asarray(got._value), want)


# -- fused dequant-matmul kernel (ops/quant_matmul.py) -----------------
class TestDequantMatmulOracle:
    """Interpret-mode kernel parity for quant_matmul against an
    independent NumPy oracle (the lint-enforced ops/ discipline)."""

    @pytest.mark.parametrize("m,k,n", [(8, 128, 256), (32, 64, 128),
                                       (5, 96, 512)])
    def test_int8_kernel_matches_numpy_oracle(self, m, k, n):
        from paddle_tpu.ops.quant_matmul import (dequant_matmul_values,
                                                 quantize_weight_values)
        rng = np.random.default_rng(m + k + n)
        w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        qw, sc = quantize_weight_values(w, "int8")
        oracle = np.asarray(x) @ (np.asarray(qw, np.float32)
                                  * np.asarray(sc))
        for use_kernel in (False, True):
            got = np.asarray(dequant_matmul_values(
                x, qw, sc, use_kernel=use_kernel))
            np.testing.assert_allclose(got, oracle, rtol=2e-5,
                                       atol=2e-4)

    def test_fp8_path_matches_numpy_oracle(self):
        from paddle_tpu.ops.quant_matmul import (dequant_matmul_values,
                                                 quantize_weight_values)
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
        qw, sc = quantize_weight_values(w, "fp8")
        assert qw.dtype == jnp.float8_e4m3fn
        oracle = np.asarray(x) @ (np.asarray(qw, np.float32)
                                  * np.asarray(sc))
        # fp8 storage routes through the XLA path even when the kernel
        # is forced (module docstring)
        for use_kernel in (False, True):
            got = np.asarray(dequant_matmul_values(
                x, qw, sc, use_kernel=use_kernel))
            np.testing.assert_allclose(got, oracle, rtol=2e-5,
                                       atol=2e-4)

    def test_dequant_error_bounded_by_lattice(self):
        from paddle_tpu.ops.quant_matmul import quantize_weight_values
        rng = np.random.default_rng(4)
        w = rng.normal(size=(64, 32)).astype(np.float32)
        qw, sc = quantize_weight_values(jnp.asarray(w), "int8")
        deq = np.asarray(qw, np.float32) * np.asarray(sc)
        # per-channel absmax lattice: error <= scale/2 per element
        assert np.all(np.abs(deq - w) <= np.asarray(sc)[None, :] * 0.5
                      + 1e-7)

    def test_quantized_weight_is_a_pytree(self):
        import jax
        from paddle_tpu.ops.quant_matmul import (QuantizedWeight,
                                                 quantize_weight_values)
        qw, sc = quantize_weight_values(jnp.ones((8, 8)), "int8")
        w = QuantizedWeight(qw, sc)
        leaves = jax.tree_util.tree_leaves(w)
        assert len(leaves) == 2
        back = jax.tree_util.tree_map(lambda a: a, w)
        assert isinstance(back, QuantizedWeight)
        assert back.nbytes == 8 * 8 + 8 * 4

    def test_mode_validation(self):
        from paddle_tpu.ops.quant_matmul import quantize_weight_values
        with pytest.raises(ValueError, match="int8|fp8"):
            quantize_weight_values(jnp.ones((4, 4)), "int4")
        with pytest.raises(ValueError, match="wants"):
            quantize_weight_values(jnp.ones((4,)), "int8")


# -- quantized KV pages through the ragged kernel ----------------------
def _quant_pools(hk, pages, ps, d):
    # token-major, as the engine stores them: a row holds every head
    return (jnp.zeros((pages, ps, hk * d), jnp.int8),
            jnp.zeros((pages, ps, hk * d), jnp.int8),
            jnp.zeros((pages, ps), jnp.float32),
            jnp.zeros((pages, ps), jnp.float32))


class TestQuantizedPagesOracle:
    """ragged_scatter_quantized + per-page dequant in
    ragged_paged_attention against an independent NumPy oracle, on
    both the XLA fallback and the interpret-mode Pallas kernel."""

    def _mixed_case(self):
        rng = np.random.default_rng(0)
        from paddle_tpu.ops.ragged_paged_attention import (
            pack_ragged_starts, ragged_scatter_quantized, token_arrays)
        hk, d, g = 2, 16, 2
        pages, ps, pps = 16, 4, 8
        ql = np.array([5, 1, 3], np.int32)
        cl = np.array([5, 9, 7], np.int32)
        qs, total = pack_ragged_starts(ql, block_q=4)
        seq, pos = token_arrays(qs, ql, cl, total)
        bt = np.zeros((3, pps), np.int32)
        nxt = 1
        for i in range(3):
            for j in range(-(-int(cl[i]) // ps)):
                bt[i, j] = nxt
                nxt += 1
        kp, vp, ks, vs = _quant_pools(hk, pages, ps, d)
        hist = [(i, p) for i in range(3)
                for p in range(int(cl[i]) - int(ql[i]))]
        if hist:
            kp, vp, ks, vs = ragged_scatter_quantized(
                kp, vp, ks, vs,
                jnp.asarray(rng.normal(
                    size=(len(hist), hk, d)).astype(np.float32)),
                jnp.asarray(rng.normal(
                    size=(len(hist), hk, d)).astype(np.float32)),
                jnp.asarray(bt),
                jnp.asarray([h[0] for h in hist], jnp.int32),
                jnp.asarray([h[1] for h in hist], jnp.int32))
        kp, vp, ks, vs = ragged_scatter_quantized(
            kp, vp, ks, vs,
            jnp.asarray(rng.normal(
                size=(total, hk, d)).astype(np.float32)),
            jnp.asarray(rng.normal(
                size=(total, hk, d)).astype(np.float32)),
            jnp.asarray(bt), jnp.asarray(seq), jnp.asarray(pos))
        q = rng.normal(size=(total, hk * g, d)).astype(np.float32)
        return (q, kp, vp, ks, vs, qs, ql, cl, bt, seq, pos,
                (hk, g, d, ps))

    def _numpy_oracle(self, case):
        q, kp, vp, ks, vs, qs, ql, cl, bt, seq, pos, geo = case
        hk, g, d, ps = geo
        kp_n = np.asarray(kp, np.float32).reshape(kp.shape[:2] + (hk, d))
        vp_n = np.asarray(vp, np.float32).reshape(vp.shape[:2] + (hk, d))
        ks_n, vs_n = np.asarray(ks), np.asarray(vs)
        total = q.shape[0]
        ref = np.zeros((total, hk * g, d), np.float32)
        sc_at = 1.0 / np.sqrt(d)
        for t in range(total):
            if seq[t] < 0:
                continue
            i = int(seq[t])
            S = int(cl[i])
            kd = np.zeros((S, hk, d), np.float32)
            vd = np.zeros((S, hk, d), np.float32)
            for p_ in range(S):
                pg, sl = bt[i, p_ // ps], p_ % ps
                kd[p_] = kp_n[pg, sl] * ks_n[pg, sl]
                vd[p_] = vp_n[pg, sl] * vs_n[pg, sl]
            qt = q[t].reshape(hk, g, d)
            for hh in range(hk):
                for gg in range(g):
                    lg = (kd[:, hh] @ qt[hh, gg]) * sc_at
                    lg[np.arange(S) > pos[t]] = -1e30
                    w = np.exp(lg - lg.max())
                    w /= w.sum()
                    ref[t, hh * g + gg] = w @ vd[:, hh]
        return ref

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_kernel_and_xla_match_numpy_oracle(self, use_kernel):
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_paged_attention_values
        case = self._mixed_case()
        q, kp, vp, ks, vs, qs, ql, cl, bt, seq, pos, _ = case
        ref = self._numpy_oracle(case)
        got = np.asarray(ragged_paged_attention_values(
            jnp.asarray(q), kp, vp, qs, ql, cl, jnp.asarray(bt),
            use_kernel=use_kernel, block_q=4, k_scale=ks, v_scale=vs))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
        assert np.all(got[np.asarray(seq) < 0] == 0)   # padding rows

    def test_commit_order_path_invariance(self):
        """The property the chaos drills' bit-identity rests on: a
        page written row by row (decode) holds BIT-IDENTICAL int8
        content and scales to the same rows written in one commit
        (preemption re-prefill) — per-row quantization sees only its
        own values."""
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_scatter_quantized
        rng = np.random.default_rng(5)
        hk, d, ps, pages = 2, 8, 4, 4
        bt = np.asarray([[1, 2]], np.int32)
        rows_k = rng.normal(size=(6, hk, d)).astype(np.float32)
        rows_v = rng.normal(size=(6, hk, d)).astype(np.float32)
        bulk = _quant_pools(hk, pages, ps, d)
        bulk = ragged_scatter_quantized(
            *bulk, jnp.asarray(rows_k), jnp.asarray(rows_v),
            jnp.asarray(bt), jnp.zeros(6, jnp.int32),
            jnp.arange(6, dtype=jnp.int32))
        inc = _quant_pools(hk, pages, ps, d)
        for t in range(6):
            inc = ragged_scatter_quantized(
                *inc, jnp.asarray(rows_k[t:t + 1]),
                jnp.asarray(rows_v[t:t + 1]), jnp.asarray(bt),
                jnp.zeros(1, jnp.int32),
                jnp.asarray([t], jnp.int32))
        for a, b in zip(bulk, inc):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_zero_rows_dequantize_to_exact_zero(self):
        from paddle_tpu.ops.ragged_paged_attention import \
            ragged_scatter_quantized
        hk, d, ps, pages = 1, 8, 4, 2
        out = ragged_scatter_quantized(
            *_quant_pools(hk, pages, ps, d),
            jnp.zeros((1, hk, d)), jnp.zeros((1, hk, d)),
            jnp.asarray([[1]], jnp.int32), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32))
        kp, vp, ks, vs = out
        assert float(np.abs(np.asarray(ks)).max()) == 0.0
        assert int(np.abs(np.asarray(kp)).max()) == 0


# -- engine mode -------------------------------------------------------
class TestQuantConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="int8|fp8"):
            QuantServingConfig(weights="int4")
        with pytest.raises(ValueError, match="int8"):
            QuantServingConfig(kv="fp8")
        with pytest.raises(ValueError, match="neither"):
            QuantServingConfig()


class TestQuantEngine:
    def test_deterministic_and_all_modes_serve(self, model, jobs,
                                               quant_oracle):
        # the same quantized engine built twice produces identical
        # greedy streams; weights-only / kv-only / fp8 modes all serve
        eng = _engine(model)
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
        out = eng.run()
        assert [out[r] for r in rids] == quant_oracle
        for q in (QuantServingConfig(weights="int8"),
                  QuantServingConfig(kv="int8"),
                  QuantServingConfig(weights="fp8", kv="int8")):
            e2 = _engine(model, quant=q)
            r = e2.add_request(jobs[0], 4)
            assert len(e2.run()[r]) == 4

    def test_weight_bytes_and_page_bytes_metered(self, model):
        eng = _engine(model)
        # every Megatron-placed matmul converted: 2 layers x 7 + lm_head
        assert telemetry.value("pdt_quant_weight_layers") == 15
        wb = telemetry.value("pdt_quant_weight_bytes")
        fp_bytes = sum(int(np.prod(p._value.shape)) * 4
                       for nm, p in model.named_parameters()
                       if any(k in nm for k in
                              ("proj", "lm_head")))
        assert 0 < wb < fp_bytes / 3        # ~1/4 of f32 + scales
        info = eng.cache_memory_info()
        assert info["kv_quant"] == "int8"
        assert telemetry.value("pdt_quant_page_bytes") \
            == info["page_bytes"]
        # honest bill: int8 storage + f32 scale rows, well under half
        # of the full-width f32 page
        fp_info = _engine(model, quant=None).cache_memory_info()
        assert info["page_bytes"] / fp_info["page_bytes"] < 0.5

    def test_preemption_bit_identity(self, model, jobs):
        """Forced preemption (injected pool exhaustion) folds tokens
        into a re-prefill whose pages are re-QUANTIZED from scratch —
        per-row path invariance makes the resumed stream bit-identical
        to the uninterrupted quantized engine."""
        from paddle_tpu.models.serving import PoolExhausted
        ref_eng = _engine(model, page_size=4)
        ref_rids = [ref_eng.add_request(p, NEW_TOKENS) for p in jobs]
        ref_out = ref_eng.run()
        ref = [ref_out[r] for r in ref_rids]
        eng = _engine(model, page_size=4)
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
        with FaultInjector() as fi:
            fi.arm("serving.alloc_page", nth=10, exc=PoolExhausted)
            out = eng.run()
            assert fi.trips("serving.alloc_page") == 1
        assert eng.num_preemptions >= 1
        assert [out[r] for r in rids] == ref

    def test_prefix_cache_hit_stays_bit_identical(self, model):
        sys_p = list(range(1, 40))          # two+ full pages at ps=16
        tails = [[41, 42, 43], [44, 45]]
        cold = _engine(model, enable_prefix_caching=True)
        rids = [cold.add_request(sys_p + t, 8) for t in tails]
        ref = cold.run()
        warm = _engine(model, enable_prefix_caching=True)
        r1 = warm.add_request(sys_p + tails[0], 8)
        warm.run()
        r2 = warm.add_request(sys_p + tails[1], 8)
        out2 = warm.run()
        assert warm.prefix_hits >= 1        # the attach actually fired
        assert out2[r2] == ref[rids[1]]

    def test_logit_error_budget_vs_full_width(self, model, jobs):
        """The acceptance quality gate: per-decode-step sampled-row
        logits of the quantized engine stay within a pinned budget of
        the full-width engine's on fixed prompts (compared while the
        two streams agree — after a divergence the rows stop being
        comparable)."""
        recs, streams = {}, {}
        for name, q in (("fp", None), ("quant", Q8)):
            rec = RecorderSentry()
            eng = _engine(model, quant=q)
            eng.attach_sentry(rec)
            rids = [eng.add_request(list(p), NEW_TOKENS)
                    for p in jobs]
            out = eng.run()
            recs[name] = rec
            streams[name] = [out[r] for r in rids]
        err, agree = 0.0, 0
        for a, b in zip(recs["fp"].logits, recs["quant"].logits):
            if a.shape != b.shape:
                break
            err = max(err, float(np.max(np.abs(a - b))))
            agree += 1
            if [s[:agree] for s in streams["fp"]] \
                    != [s[:agree] for s in streams["quant"]]:
                break                      # streams diverged: stop
        assert agree >= 3                  # the comparison is real
        assert err < 0.25                  # test-pinned budget

    def test_spec_decode_quant_bit_identical(self, model, jobs,
                                             quant_oracle):
        paddle.seed(1)
        draft = LlamaForCausalLM(LlamaConfig.tiny_draft())
        draft.eval()
        eng = _engine(model, spec_decode=SpecConfig(draft, k=3))
        rids = [eng.add_request(p, NEW_TOKENS) for p in jobs]
        out = eng.run()
        assert [out[r] for r in rids] == quant_oracle
        assert eng.num_spec_rounds > 0


# -- migration / byte honesty / cross-mode refusals --------------------
class TestQuantMigration:
    def _run_to_mid_decode(self, model, quant, prompt, steps=3):
        eng = _engine(model, quant=quant)
        rid = eng.add_request(list(prompt), NEW_TOKENS)
        for _ in range(steps):
            eng.step()
        return eng, rid

    def test_migrated_stream_bit_identical(self, model, jobs,
                                           quant_oracle):
        src, rid = self._run_to_mid_decode(model, Q8, jobs[0])
        dst = _engine(model)
        req, payload = transfer.migrate_request(src, dst, rid)
        while not req.done:
            dst.step()
        assert req.output == quant_oracle[0]
        assert payload["kv_quant"] == "int8"

    def test_payload_bytes_honestly_reduced(self, model, jobs):
        """Satellite 2: payload_nbytes (scales INCLUDED) and the
        transfer byte counter report the reduction — ~4x vs the f32
        CPU pools, i.e. comfortably past the ~2x-vs-bf16 claim."""
        base = telemetry.value("pdt_transfer_bytes_total")
        sizes = {}
        for name, q in (("fp", None), ("quant", Q8)):
            src, rid = self._run_to_mid_decode(model, q, jobs[0])
            dst = _engine(model, quant=q)
            _, payload = transfer.migrate_request(src, dst, rid)
            sizes[name] = transfer.payload_nbytes(payload)
        assert sizes["quant"] / sizes["fp"] < 0.55
        # the counter books exactly what payload_nbytes reports
        assert telemetry.value("pdt_transfer_bytes_total") - base \
            == sizes["fp"] + sizes["quant"]
        # and the scales genuinely ride the count: int8 page bytes
        # alone would be exactly a quarter of the f32 bytes
        assert sizes["quant"] > sizes["fp"] / 4

    @pytest.mark.parametrize("direction", ["quant_to_fp", "fp_to_quant"])
    def test_cross_mode_migration_refused(self, model, jobs, direction):
        src_q, dst_q = (Q8, None) if direction == "quant_to_fp" \
            else (None, Q8)
        src, rid = self._run_to_mid_decode(model, src_q, jobs[0])
        dst = _engine(model, quant=dst_q)
        base = telemetry.value("pdt_quant_mode_mismatch_total",
                               kind="import")
        fail_base = telemetry.value("pdt_transfer_failures_total",
                                    stage="install")
        with pytest.raises(QuantMismatch, match="cross-quant-mode"):
            transfer.migrate_request(src, dst, rid)
        assert telemetry.value("pdt_quant_mode_mismatch_total",
                               kind="import") - base == 1
        assert telemetry.value("pdt_transfer_failures_total",
                               stage="install") - fail_base == 1
        # the refusal left both engines consistent: the source still
        # owns the request and finishes it
        req = src.get_request(rid)
        while not req.done:
            src.step()
        src.check_invariants()
        dst.check_invariants()

    def test_corrupt_scale_refused_by_verify(self, model, jobs):
        src, rid = self._run_to_mid_decode(model, Q8, jobs[0])
        payload = src.export_pages(rid)
        ks, vs = payload["kv_scales"][0]
        ks = ks.copy()
        ks.flat[0] += 0.5
        payload["kv_scales"][0] = (ks, vs)
        with pytest.raises(Exception, match="SCALE"):
            verify_payload(payload)

    def test_spill_roundtrip_and_cross_mode_prefix_refusal(
            self, model):
        """Quantized chains spill HALF-WIDTH into the fleet prefix
        store and restore bit-identically; a cross-mode restore is a
        typed refusal, not silent garbage KV."""
        sys_p = list(range(1, 50))          # 3 full pages at ps=16
        src = _engine(model, enable_prefix_caching=True)
        rid = src.add_request(sys_p + [55, 56], 6)
        src.step()
        payload = src.export_pages(rid)
        store = FleetPrefixStore(page_size=16)
        spilled = store.spill_payload(payload)
        assert spilled == 3
        entry = store.fetch(sys_p + [60])
        assert entry is not None and len(entry) == 3   # scales ride
        # byte honesty: the spilled bytes are the quantized bill
        fp_src = _engine(model, quant=None,
                         enable_prefix_caching=True)
        fp_rid = fp_src.add_request(sys_p + [55, 56], 6)
        fp_src.step()
        fp_store = FleetPrefixStore(page_size=16)
        fp_store.spill_payload(fp_src.export_pages(fp_rid))
        assert store.spilled_bytes / fp_store.spilled_bytes < 0.55
        # restore into a fresh QUANTIZED engine: the chain attaches
        # and the prefilled stream matches an engine that computed the
        # prefix itself
        fresh = _engine(model, enable_prefix_caching=True)
        assert fresh.import_prefix(*entry) == 3
        r2 = fresh.add_request(sys_p + [55, 56], 6)
        out = fresh.run()[r2]
        ref_eng = _engine(model, enable_prefix_caching=True)
        r3 = ref_eng.add_request(sys_p + [55, 56], 6)
        assert ref_eng.run()[r3] == out
        assert fresh.prefix_hits >= 1
        # cross-mode: a full-width engine must refuse the quant chain
        base = telemetry.value("pdt_quant_mode_mismatch_total",
                               kind="prefix")
        fp_eng = _engine(model, quant=None,
                         enable_prefix_caching=True)
        with pytest.raises(QuantMismatch, match="prefix"):
            fp_eng.import_prefix(*entry)
        assert telemetry.value("pdt_quant_mode_mismatch_total",
                               kind="prefix") - base == 1
        # ... and a quant engine refuses a full-width chain
        fp_entry = fp_store.fetch(sys_p + [60])
        assert fp_entry is not None and len(fp_entry) == 2
        with pytest.raises(QuantMismatch, match="prefix"):
            fresh.import_prefix(*fp_entry)


# -- sentry / canary compatibility (satellite 1) -----------------------
class TestQuantSentryCompat:
    def test_quant_fleet_canaries_against_quant_golden(self, model):
        """Satellite 1's false-quarantine regression: the canary
        golden is computed from the fleet's OWN factory, so a
        quantized fleet replays a QUANTIZED golden — healthy quantized
        replicas pass their canaries and nothing quarantines, even
        where the bf16 golden differs."""
        from paddle_tpu.serving import CanaryConfig, SentryConfig
        clock = FakeClock()
        canary = CanaryConfig(prompt=(3, 1, 4, 1, 5, 9),
                              max_new_tokens=8, interval=5.0)

        def factory(i):
            return ContinuousBatchingEngine(
                model, max_batch_size=3, max_seq_len=MAX_SEQ,
                clock=clock, quant=Q8)

        router = ServingRouter(
            factory, num_replicas=2, clock=clock, sleep=clock.advance,
            sentry=SentryConfig(scan_every=1), canary=canary)
        # the golden IS the quantized engine's stream
        probe = _engine(model, clock=clock)
        prid = probe.add_request(list(canary.prompt),
                                 canary.max_new_tokens)
        assert router._canary_golden == probe.run()[prid]
        ids = [router.submit([7, 8, 9, 10], 6) for _ in range(3)]
        clock.advance(6.0)                  # canaries come due
        out = router.run()
        for _ in range(30):                 # let canaries conclude
            if all(h.canary is None and h.canary_runs >= 1
                   for h in router.replicas):
                break
            router.step()
        assert all(len(out[i]) == 6 for i in ids)
        assert router.num_quarantines == 0
        passes = telemetry.value("pdt_sentry_canary_runs_total",
                                 result="pass")
        assert passes >= 1
        # the regression's teeth: had the golden come from a
        # FULL-WIDTH engine, the very first canary would have
        # mismatched (quarantine) whenever the two modes' streams
        # differ on the canary prompt
        fp_probe = _engine(model, quant=None, clock=clock)
        fprid = fp_probe.add_request(list(canary.prompt),
                                     canary.max_new_tokens)
        fp_golden = fp_probe.run()[fprid]
        if fp_golden != router._canary_golden:
            # modes genuinely diverge on this prompt — the factory-
            # derived golden is what kept the fleet clean above
            assert router.num_quarantines == 0

    def test_corrupt_scale_pool_is_caught_by_canary(self, model):
        """docs/serving.md failure-matrix row: corrupted PER-PAGE
        SCALES silently rescale every row of their pages at dequant —
        a sick chip's systematic damage, simulated by re-poisoning
        replica 0's layer-0 k-scale pool before every step so the
        canary's own pages are hit too. The canary replay then
        mismatches its quantized golden (proof of corruption), the
        replica quarantines, and the tainted streams re-serve
        bit-identically on the healthy replica."""
        from paddle_tpu.serving import CanaryConfig, SentryConfig
        clock = FakeClock()

        def factory(i):
            return ContinuousBatchingEngine(
                model, max_batch_size=3, max_seq_len=MAX_SEQ,
                clock=clock, quant=Q8)

        jobs2 = [[5, 4, 3, 2, 6, 7], [9, 1, 2]]
        ref_eng = _engine(model, clock=FakeClock())
        rr = [ref_eng.add_request(p, NEW_TOKENS) for p in jobs2]
        ref_out = ref_eng.run()
        ref = [ref_out[r] for r in rr]
        router = ServingRouter(
            factory, num_replicas=2, clock=clock, sleep=clock.advance,
            sentry=SentryConfig(scan_every=1),
            canary=CanaryConfig(interval=1.0, max_new_tokens=6),
            restart_backoff_base=1.0, restart_backoff_max=1.0)
        ids = [router.submit(p, NEW_TOKENS) for p in jobs2]
        h0 = router.replicas[0]
        gen0 = h0.generation
        for _ in range(200):
            if all(router.requests[i].done for i in ids):
                break
            if h0.engine is not None and h0.generation == gen0:
                # the sick chip: every step re-poisons the scale pool
                # (stops once the incarnation is discarded)
                e0 = h0.engine._kv[0]
                h0.engine._kv[0] = (e0[0], e0[1],
                                    e0[2] * 1e3 + 1.0, e0[3])
            clock.advance(1.1)
            router.step()
        out = {i: router.requests[i].tokens for i in ids}
        assert router.num_quarantines >= 1
        assert [out[i] for i in ids] == ref


# -- tensor parallelism ------------------------------------------------
class TestQuantTP:
    def test_tp2_bit_identical_and_survives_kill(self, model, jobs,
                                                 quant_oracle):
        """Quantized tp=2 greedy streams equal quantized tp=1
        BIT-IDENTICALLY (scale pools replicate; the per-row absmax is
        a max-reduction, exact under sharding), and a SIGKILLed TP
        replica's work re-serves identically on the survivor."""
        clock = FakeClock()

        def factory(i, sm):
            return ContinuousBatchingEngine(
                model, max_batch_size=3, max_seq_len=MAX_SEQ,
                clock=clock, submesh=sm, quant=Q8)

        router = ServingRouter(
            factory, num_replicas=2, tp=TpConfig(tp=2), clock=clock,
            sleep=clock.advance, restart_backoff_base=1.0,
            restart_backoff_max=1.0)
        ids = [router.submit(p, NEW_TOKENS) for p in jobs]
        router.step()
        router.step()
        victim = router.requests[ids[0]].replica
        router.kill_replica(victim)
        clock.advance(2.0)
        out = router.run()
        assert [out[i] for i in ids] == quant_oracle
        assert router.num_failovers >= 1

    def test_tp2_migration_carries_quantized_fragments(self, model,
                                                       jobs,
                                                       quant_oracle):
        """Per-shard int8 fragments + replicated scale rows round-trip
        a tp=2 -> tp=2 migration; the migrated stream stays
        bit-identical to quantized tp=1."""
        from paddle_tpu.serving import carve_submeshes
        meshes = carve_submeshes(2, TpConfig(tp=2))
        src = _engine(model, submesh=meshes[0])
        dst = _engine(model, submesh=meshes[1])
        rid = src.add_request(list(jobs[0]), NEW_TOKENS)
        for _ in range(3):
            src.step()
        req, payload = transfer.migrate_request(src, dst, rid)
        assert payload["tp"] == 2
        assert payload["kv_shards"] is not None
        assert payload["kv_quant"] == "int8"
        assert all(f[0][0].dtype == np.int8
                   for f in payload["kv_shards"])
        while not req.done:
            dst.step()
        assert req.output == quant_oracle[0]
        src.check_invariants()
        dst.check_invariants()
