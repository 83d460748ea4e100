"""Serving path (L10): KV-cache generation, masked_multihead_attention,
paged attention. ≙ SURVEY.md §1 L10 + §7 step 6; VERDICT r2 item 3."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.paged_attention import (PagedKVCache,
                                            paged_attention_values)
from paddle_tpu.ops.ragged_paged_attention import payload_to_pages


def _pool(pages):
    """A head-major (HK, P, page, D) array, as the oracles here index
    it, as the token-major pool (P, page, HK*D) the ops store."""
    return jnp.asarray(payload_to_pages(np.asarray(pages)))


def _mha_oracle(q, k, v, seq_len):
    """NumPy decode attention oracle: q (B,1,H,D), cache (B,T,HK,D)."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    g = h // hk
    q = q.astype(np.float32).reshape(b, s, hk, g, d)
    k = k.astype(np.float32)
    v = v.astype(np.float32)
    logits = np.einsum("bskgd,btkd->bkgst", q, k) / np.sqrt(d)
    t = k.shape[1]
    mask = np.arange(t)[None, :] <= (seq_len - s + np.arange(s))[:, None]
    logits = np.where(mask[None, None, None], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bkgst,btkd->bskgd", p, v).reshape(b, s, h, d)


class TestMaskedMHA:
    @pytest.mark.parametrize("hk", [4, 2])
    def test_matches_oracle(self, hk):
        rng = np.random.default_rng(0)
        b, t, h, d = 2, 32, 4, 16
        q = rng.standard_normal((b, 1, h, d)).astype(np.float32)
        k = rng.standard_normal((b, t, hk, d)).astype(np.float32)
        v = rng.standard_normal((b, t, hk, d)).astype(np.float32)
        seq_len = 20
        out = F.masked_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            seq_len=seq_len)
        ref = _mha_oracle(q, k, v, seq_len)
        np.testing.assert_allclose(np.asarray(out._value), ref,
                                   rtol=1e-4, atol=1e-5)

    def test_traced_seq_len(self):
        rng = np.random.default_rng(1)
        b, t, h, d = 1, 16, 2, 8
        q = rng.standard_normal((b, 1, h, d)).astype(np.float32)
        k = rng.standard_normal((b, t, h, d)).astype(np.float32)
        v = rng.standard_normal((b, t, h, d)).astype(np.float32)

        def fn(sl):
            return F.masked_multihead_attention(
                paddle.to_tensor(q), paddle.to_tensor(k),
                paddle.to_tensor(v), seq_len=paddle.Tensor(sl))._value
        out = jax.jit(fn)(jnp.int32(10))
        ref = _mha_oracle(q, k, v, 10)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)


class TestPagedAttention:
    def _setup(self, b=3, h=4, hk=2, d=16, page=8, pps=4, seed=0):
        rng = np.random.default_rng(seed)
        n_pages = b * pps + 2
        q = rng.standard_normal((b, h, d)).astype(np.float32)
        k_pages = rng.standard_normal((hk, n_pages, page, d)).astype(
            np.float32)
        v_pages = rng.standard_normal((hk, n_pages, page, d)).astype(
            np.float32)
        # distinct non-contiguous pages per sequence
        perm = rng.permutation(n_pages)[:b * pps]
        block_tables = perm.reshape(b, pps).astype(np.int32)
        context_lens = rng.integers(1, page * pps + 1, (b,)).astype(
            np.int32)
        return q, k_pages, v_pages, context_lens, block_tables

    def _oracle(self, q, k_pages, v_pages, context_lens, block_tables):
        b, h, d = q.shape
        hk, _, page, _ = k_pages.shape
        pps = block_tables.shape[1]
        outs = []
        for i in range(b):
            kc = k_pages[:, block_tables[i]].reshape(hk, pps * page, d)
            vc = v_pages[:, block_tables[i]].reshape(hk, pps * page, d)
            kc = np.swapaxes(kc, 0, 1)[None]   # (1, T, HK, D)
            vc = np.swapaxes(vc, 0, 1)[None]
            o = _mha_oracle(q[i][None, None], kc, vc,
                            int(context_lens[i]))
            outs.append(o[0, 0])
        return np.stack(outs)

    def test_matches_oracle(self):
        args = self._setup()
        q, kp, vp, cl, bt = args
        out = paged_attention_values(jnp.asarray(q), _pool(kp), _pool(vp),
                                     jnp.asarray(cl), jnp.asarray(bt))
        ref = self._oracle(*args)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)

    def test_gqa_and_min_context(self):
        args = self._setup(b=2, h=8, hk=2, d=32, page=16, pps=2, seed=3)
        q, kp, vp, cl, bt = args
        cl = np.array([1, 32], np.int32)  # one-token and full contexts
        out = paged_attention_values(jnp.asarray(q), _pool(kp),
                                     _pool(vp), jnp.asarray(cl),
                                     jnp.asarray(bt))
        ref = self._oracle(q, kp, vp, cl, bt)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)

    def test_sliding_window_matches_truncated_oracle(self):
        """window=w must equal full attention over only the last w keys
        (band semantics of the kernel / XLA gather path)."""
        args = self._setup(b=3, h=4, hk=2, d=16, page=8, pps=4, seed=6)
        q, kp, vp, cl, bt = args
        cl = np.array([5, 20, 32], np.int32)
        w = 12
        out = paged_attention_values(jnp.asarray(q), _pool(kp),
                                     _pool(vp), jnp.asarray(cl),
                                     jnp.asarray(bt), window=w)
        # oracle: re-gather each sequence keeping only [ctx-w, ctx)
        b_, h, d = q.shape
        hk, _, page, _ = kp.shape
        pps = bt.shape[1]
        outs = []
        for i in range(b_):
            kc = kp[:, bt[i]].reshape(hk, pps * page, d)
            vc = vp[:, bt[i]].reshape(hk, pps * page, d)
            lo = max(0, int(cl[i]) - w)
            kc = np.swapaxes(kc[:, lo:cl[i]], 0, 1)[None]
            vc = np.swapaxes(vc[:, lo:cl[i]], 0, 1)[None]
            o = _mha_oracle(q[i][None, None], kc, vc, int(cl[i]) - lo)
            outs.append(o[0, 0])
        np.testing.assert_allclose(np.asarray(out), np.stack(outs),
                                   rtol=1e-4, atol=1e-5)

    def test_cache_append(self):
        b, hk, d, page = 2, 2, 8, 4
        cache = PagedKVCache(hk, d, num_pages=8, page_size=page,
                             dtype=jnp.float32)
        bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        k = jnp.ones((b, hk, d))
        v = jnp.full((b, hk, d), 2.0)
        cache = cache.append(k, v, bt, jnp.asarray([0, 5], jnp.int32))
        # seq 0 pos 0 -> page 0 slot 0; seq 1 pos 5 -> page 3 slot 1;
        # a stored row holds every head: (num_pages, page, hk * d)
        assert cache.k_pages.shape == (8, page, hk * d)
        assert np.all(np.asarray(cache.k_pages[0, 0]) == 1.0)
        assert np.all(np.asarray(cache.v_pages[3, 1]) == 2.0)
        assert np.all(np.asarray(cache.k_pages[0, 1]) == 0.0)


class TestGenerate:
    def _model(self, seed=0):
        cfg = LlamaConfig.tiny()
        paddle.seed(seed)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    @pytest.mark.slow
    def test_greedy_matches_eager_refeed(self):
        """Greedy KV-cache decode == argmax over full re-forward each
        step (the VERDICT 'greedy-decode parity test vs eager forward')."""
        cfg, model = self._model()
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 12)).astype(np.int32)
        toks, scores = model.generate(paddle.to_tensor(ids),
                                      max_new_tokens=6)
        cur = ids.copy()
        for _ in range(6):
            logits = model(paddle.to_tensor(cur))
            nxt = np.asarray(jnp.argmax(logits._value[:, -1], -1),
                             np.int32)
            cur = np.concatenate([cur, nxt[:, None]], 1)
        np.testing.assert_array_equal(np.asarray(toks._value),
                                      cur[:, 12:])
        assert scores.shape == [2, 6]

    def test_eos_padding(self):
        cfg, model = self._model()
        ids = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (1, 8)).astype(np.int32)
        # find the first greedy token, use it as eos => all later = eos
        toks, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
        first = int(np.asarray(toks._value)[0, 0])
        toks2, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                                  eos_token_id=first)
        got = np.asarray(toks2._value)[0]
        assert got[0] == first
        assert all(t == first for t in got[1:])

    def test_sampling_reproducible_with_seed(self):
        cfg, model = self._model()
        ids = np.random.default_rng(2).integers(
            0, cfg.vocab_size, (2, 8)).astype(np.int32)
        paddle.seed(42)
        a, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                              decode_strategy="sampling", top_k=20,
                              temperature=0.9)
        paddle.seed(42)
        b, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                              decode_strategy="sampling", top_k=20,
                              temperature=0.9)
        np.testing.assert_array_equal(np.asarray(a._value),
                                      np.asarray(b._value))

    def test_top_p_keeps_top_token(self):
        cfg, model = self._model()
        ids = np.random.default_rng(3).integers(
            0, cfg.vocab_size, (1, 8)).astype(np.int32)
        # top_p -> 0 degenerates to greedy
        greedy, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4)
        samp, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                                 decode_strategy="sampling", top_p=1e-9)
        np.testing.assert_array_equal(np.asarray(greedy._value),
                                      np.asarray(samp._value))

    def test_cache_overflow_raises(self):
        cfg, model = self._model()
        ids = np.zeros((1, 8), np.int32)
        with pytest.raises(ValueError):
            model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                           max_cache_len=10)

    def test_chunked_prefill_matches_full(self):
        """Two-chunk prefill through the cache == one-shot prefill
        (exercises the end-aligned causal convention with offset > 0)."""
        cfg, model = self._model()
        rng = np.random.default_rng(4)
        ids = rng.integers(0, cfg.vocab_size, (1, 16)).astype(np.int32)
        hk, hd = cfg.num_key_value_heads, cfg.head_dim
        n_l = cfg.num_hidden_layers
        caches = [(paddle.to_tensor(np.zeros((1, 32, hk, hd), np.float32)),
                   paddle.to_tensor(np.zeros((1, 32, hk, hd), np.float32)))
                  for _ in range(n_l)]
        with paddle.no_grad():
            l1, caches = model(paddle.to_tensor(ids[:, :8]),
                               past_key_values=caches, position_offset=0,
                               use_cache=True)
            l2, caches = model(paddle.to_tensor(ids[:, 8:]),
                               past_key_values=caches, position_offset=8,
                               use_cache=True)
            full = model(paddle.to_tensor(ids))
        np.testing.assert_allclose(
            np.asarray(l2._value[:, -1]),
            np.asarray(full._value[:, -1]), rtol=2e-3, atol=2e-3)


class TestAttentionMaskWithCache:
    def test_padding_mask_excludes_cached_positions(self):
        """Left-padding written into the cache must get zero weight."""
        rng = np.random.default_rng(9)
        b, t, h, d = 2, 16, 2, 8
        q = rng.standard_normal((b, 1, h, d)).astype(np.float32)
        k = rng.standard_normal((b, t, h, d)).astype(np.float32)
        v = rng.standard_normal((b, t, h, d)).astype(np.float32)
        pad = np.ones((b, t), bool)
        pad[0, :4] = False                       # seq 0: first 4 are pad
        out_m = F.masked_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            seq_len=12, attn_mask=paddle.to_tensor(pad))
        # reference: zero out padded keys by giving them -inf manually
        k2 = k.copy()
        ref = _mha_oracle(q, np.where(pad[:, :, None, None], k, -1e4),
                          v, 12)
        # cheaper check: masked positions have no influence — perturb them
        k_pert = k.copy()
        k_pert[0, :4] += 100.0
        v_pert = v.copy()
        v_pert[0, :4] += 100.0
        out_p = F.masked_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k_pert),
            paddle.to_tensor(v_pert), seq_len=12,
            attn_mask=paddle.to_tensor(pad))
        np.testing.assert_allclose(np.asarray(out_m._value),
                                   np.asarray(out_p._value), atol=1e-6)
        # and unmasked output differs from masked (mask has an effect)
        out_nomask = F.masked_multihead_attention(
            paddle.to_tensor(q), paddle.to_tensor(k_pert),
            paddle.to_tensor(v_pert), seq_len=12)
        assert not np.allclose(np.asarray(out_m._value),
                               np.asarray(out_nomask._value))


class TestGPTGenerate:
    @pytest.mark.slow
    def test_greedy_matches_eager_refeed(self):
        """GPT decode with learned position embeddings + KV cache matches
        argmax over full re-forward each step."""
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        cfg = GPTConfig.tiny() if hasattr(GPTConfig, "tiny") else GPTConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=64)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.eval()
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 10)).astype(np.int32)
        toks, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5)
        cur = ids.copy()
        for _ in range(5):
            logits = model(paddle.to_tensor(cur))
            nxt = np.asarray(jnp.argmax(logits._value[:, -1], -1),
                             np.int32)
            cur = np.concatenate([cur, nxt[:, None]], 1)
        np.testing.assert_array_equal(np.asarray(toks._value), cur[:, 10:])

    def test_beam_search_runs_on_gpt(self):
        """GenerationMixin strategies are model-family-generic: beam
        search drives GPT (learned position embeddings) unchanged."""
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        num_hidden_layers=1, num_attention_heads=2,
                        intermediate_size=64, max_position_embeddings=32)
        paddle.seed(4)
        model = GPTForCausalLM(cfg)
        model.eval()
        ids = np.array([[3, 5, 7]], np.int32)
        toks, score = model.generate(paddle.to_tensor(ids),
                                     max_new_tokens=6,
                                     decode_strategy="beam_search",
                                     num_beams=3)
        assert np.asarray(toks._value).shape == (1, 6)
        assert np.isfinite(float(score[0]))


_SYS = list(range(3, 19))                 # two full pages of 8

# The engine's modes, each held to the same oracle. A mode changes how
# the cache is filled and walked, never the stream. Per mode: engine
# keywords, (prompt, max_new_tokens) jobs and, where the engine shows
# it, that the mode was taken ("holds"); "window" builds a
# sliding-window model, "eos_at" declares the first request's token at
# that index as EOS.
_ENGINE_MODES = {
    "small_pages": dict(
        engine=dict(max_batch_size=2, page_size=4),
        jobs=[(list(range(5, 5 + n)), k)
              for n, k in ((3, 6), (13, 8), (7, 5), (10, 7))]),
    "sliding_window": dict(
        window=16,
        engine=dict(max_batch_size=2, page_size=8),
        jobs=[(list(range(2, 11 + 4 * j)), 30) for j in range(3)]),
    "sliding_window_chunked": dict(
        window=16,
        engine=dict(max_batch_size=1, page_size=8, prompt_pad=8,
                    prefill_chunk=8),
        jobs=[(list(range(2, 29)), 12)],
        holds=lambda eng: all(t <= 8 for t, _ in eng._ragged_jits)),
    "prefix_hit": dict(
        engine=dict(max_batch_size=1, page_size=8,
                    enable_prefix_caching=True),
        jobs=[(_SYS + t, 6) for t in ([70, 80, 90], [100, 101])],
        holds=lambda eng: (eng.prefix_hits, eng.prefix_tokens_reused)
        == (1, 16)),
    "prefix_hit_chunked": dict(
        engine=dict(max_batch_size=1, page_size=8, prompt_pad=8,
                    prefill_chunk=8, enable_prefix_caching=True),
        jobs=[(_SYS + t, 5) for t in ([70], list(range(100, 112)))],
        holds=lambda eng: eng.prefix_hits == 1
        and all(t <= 8 for t, _ in eng._ragged_jits)),
    "long_prompt_chunked": dict(
        engine=dict(max_batch_size=2, page_size=8, prompt_pad=8,
                    prefill_chunk=16),
        jobs=[(list(range(1, 1 + n)), 6) for n in (5, 16, 23, 40)],
        # + 8: a second piece of a batch starts on the next row block
        holds=lambda eng: all(t <= 24 for t, _ in eng._ragged_jits)),
    "harvest_every_4": dict(
        engine=dict(max_batch_size=2, harvest_every=4),
        jobs=[([5, 42, 7], 6), ([9, 1, 2, 3, 4], 9), ([11, 13], 5)],
        holds=lambda eng: not eng._pending),
    "harvest_every_4_eos": dict(
        eos_at=2,
        engine=dict(max_batch_size=2, harvest_every=4),
        jobs=[([5, 42, 7], 9), ([9, 1, 2, 3, 4], 9)],
        holds=lambda eng: not eng._pending),
    "one_admission_program": dict(
        engine=dict(max_batch_size=1, prompt_pad=4,
                    max_prefill_programs=1),
        jobs=[(list(range(1, 1 + n)), 3) for n in (3, 7, 3, 11)],
        holds=lambda eng: len(eng._ragged_jits) == 1),
}


class TestContinuousBatching:
    """In-flight batching (VERDICT r3 next #3): slots at different
    positions decode in ONE compiled step; admission reuses freed slots.
    Oracle: per-request generate() greedy outputs — the engine's parity
    oracle, so tier-1 runs this class."""

    def _model(self, window=None):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(7)
        cfg = LlamaConfig.tiny()
        cfg.sliding_window = window
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m, cfg

    def _ref_greedy(self, m, prompt, n):
        out = m.generate(paddle.to_tensor(
            np.asarray(prompt, np.int32)[None]), max_new_tokens=n,
            decode_strategy="greedy_search")
        t = out[0] if isinstance(out, (tuple, list)) else out
        return [int(x) for x in np.asarray(t._value).ravel()[:n]]

    def test_matches_per_request_greedy(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        rng_ = np.random.default_rng(3)
        prompts = [list(rng_.integers(1, cfg.vocab_size,
                                      rng_.integers(3, 12)))
                   for _ in range(5)]
        lens = [6, 9, 4, 7, 5]
        # max_batch_size 2 < 5 requests: slots MUST be reused in flight
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, lens)]
        results = eng.run()
        assert set(results) == set(rids)
        for rid, p, n in zip(rids, prompts, lens):
            ref = self._ref_greedy(m, p, n)
            assert results[rid] == ref, (rid, results[rid], ref)

    @pytest.mark.parametrize("mode", list(_ENGINE_MODES))
    def test_mode_matches_per_request_greedy(self, mode):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        spec = _ENGINE_MODES[mode]
        m, cfg = self._model(window=spec.get("window"))
        refs = [self._ref_greedy(m, p, n) for p, n in spec["jobs"]]
        eos = None
        if "eos_at" in spec:
            eos = refs[0][spec["eos_at"]]
            refs = [r[:r.index(eos) + 1] if eos in r else r
                    for r in refs]
            assert len(refs[0]) < spec["jobs"][0][1]    # it does cut
        eng = ContinuousBatchingEngine(m, max_seq_len=64,
                                       eos_token_id=eos, **spec["engine"])
        rids = [eng.add_request(p, n) for p, n in spec["jobs"]]
        results = eng.run()
        assert [results[r] for r in rids] == refs
        assert spec.get("holds", bool)(eng)

    def test_mid_flight_admission(self):
        """A request added while others are mid-decode joins without
        disturbing them."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_batch_size=4,
                                       max_seq_len=64)
        a = eng.add_request([5, 42, 7], 8)
        b = eng.add_request([9, 1, 2, 3, 4], 8)
        done = {}
        for _ in range(3):
            for r in eng.step():
                done[r.rid] = r.output
        c = eng.add_request([11, 13], 6)     # mid-flight
        while len(done) < 3:
            for r in eng.step():
                done[r.rid] = r.output
        assert done[a] == self._ref_greedy(m, [5, 42, 7], 8)
        assert done[b] == self._ref_greedy(m, [9, 1, 2, 3, 4], 8)
        assert done[c] == self._ref_greedy(m, [11, 13], 6)

    def test_eos_frees_slot(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        # discover the greedy continuation, then declare its 2nd token
        # as EOS: the engine must stop that request early
        ref = self._ref_greedy(m, [5, 42, 7], 6)
        eos = ref[1]
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, eos_token_id=eos)
        rid = eng.add_request([5, 42, 7], 6)
        out = eng.run()[rid]
        assert out == ref[:2], (out, ref)

    def test_single_compiled_decode_program(self):
        """The decode step compiles once regardless of slot positions."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_batch_size=3,
                                       max_seq_len=64)
        for p, n in [([5, 4], 4), ([1, 2, 3, 4, 5, 6, 7], 6),
                     ([9], 5)]:
            eng.add_request(p, n)
        eng.run()
        assert eng._decode_jit is not None
        # jax caches by signature; the step signature never changed
        if not hasattr(eng._decode_jit, "_cache_size"):
            pytest.skip("jax private _cache_size API unavailable — "
                        "single-compilation guarantee unverifiable here")
        assert eng._decode_jit._cache_size() == 1

    def test_prompt_length_validation(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=32)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(list(range(40)), 4)
        # near-limit prompt: bucket must clamp to the cache, not crash
        rid = eng.add_request(list(np.arange(1, 30) % cfg.vocab_size), 2)
        out = eng.run()[rid]
        assert len(out) == 2


@pytest.mark.slow
class TestPagedEngine:
    """Paged-KV serving engine (VERDICT r4 item 2): block-table cache
    wired into the decode step, occupancy-proportional HBM accounting,
    sampling exposure, page-pool admission control."""

    def _model(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(7)
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m, cfg

    def test_memory_occupancy_proportional(self):
        """bytes_in_use tracks pages actually allocated, not B*S_max;
        finished requests return their pages."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_batch_size=4,
                                       max_seq_len=64, kv_layout="paged",
                                       page_size=16)
        info0 = eng.cache_memory_info()
        assert info0["pages_in_use"] == 0 and info0["bytes_in_use"] == 0
        rid = eng.add_request([3, 5, 7], 4)       # 3 tokens -> 1 page
        eng.step()
        info1 = eng.cache_memory_info()
        assert info1["pages_in_use"] >= 1
        assert info1["bytes_in_use"] < info1["bytes_pool"] / 2
        eng.run()
        info2 = eng.cache_memory_info()
        assert info2["pages_in_use"] == 0         # pages reclaimed

    def test_pool_exhaustion_defers_admission(self):
        """A pool too small for two concurrent requests serves them
        SEQUENTIALLY (FIFO), not incorrectly."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        # each request worst-cases at ceil((3+6)/16)=1 page; pool of 1
        # usable page forces one-at-a-time admission
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, kv_layout="paged",
                                       page_size=16, num_pages=2)
        a = eng.add_request([5, 42, 7], 6)
        b = eng.add_request([9, 1, 2], 6)
        # after the first step only one request may hold pages
        eng.step()
        active = [r for r in eng._slot_req if r is not None]
        assert len(active) == 1
        res = eng.run()
        ref_a = self._ref(m, [5, 42, 7], 6)
        ref_b = self._ref(m, [9, 1, 2], 6)
        assert res[a] == ref_a and res[b] == ref_b

    def _ref(self, m, prompt, n):
        out = m.generate(paddle.to_tensor(
            np.asarray(prompt, np.int32)[None]), max_new_tokens=n)
        t = out[0] if isinstance(out, (tuple, list)) else out
        return [int(x) for x in np.asarray(t._value).ravel()[:n]]

    def test_sampling_seeded_reproducible(self):
        """do_sample engines with the same seed emit identical streams;
        top_p -> 0 degenerates to greedy."""
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        p = [5, 42, 7, 11]

        def run_once(seed, **kw):
            eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                           max_seq_len=64, seed=seed,
                                           **kw)
            rid = eng.add_request(p, 8)
            return eng.run()[rid]

        s1 = run_once(3, do_sample=True, temperature=0.8, top_k=20)
        s2 = run_once(3, do_sample=True, temperature=0.8, top_k=20)
        s3 = run_once(4, do_sample=True, temperature=0.8, top_k=20)
        assert s1 == s2
        greedy = run_once(0)
        tiny_p = run_once(9, do_sample=True, top_p=1e-9)
        assert tiny_p == greedy
        assert len(s3) == 8

    def test_sliding_window_reclaims_pages(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny()
        cfg.sliding_window = 16
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=64, page_size=8)
        eng.add_request(list(range(1, 10)), 40)   # runs to position ~49
        max_in_use = 0
        while eng._queue or any(r is not None for r in eng._slot_req):
            eng.step()
            max_in_use = max(max_in_use,
                             eng.cache_memory_info()["pages_in_use"])
        # window 16 at page 8 -> at most ceil(16/8)+1 = 3 live pages
        # (+1 partial write page) ever resident after reclamation
        assert max_in_use <= 4, max_in_use
        assert all(eng._page_rc[1:] == 0)         # all reclaimed at end

    def test_prefill_program_cache_capped(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m, cfg = self._model()
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=64, prompt_pad=4,
                                       max_prefill_programs=2)
        for n_len in (3, 7, 11, 15):
            eng.add_request(list(range(1, n_len + 1)), 2)
        eng.run()
        assert len(eng._ragged_jits) <= 2


class TestPrefixCaching:
    """Automatic prefix caching (VERDICT r4 weak #4: no cross-request
    prefix sharing): a finished request's full-page prompt KV is reused
    read-only by later requests with the same token prefix; only the
    suffix is prefilled. Oracle: an identical engine with caching off."""

    def _model(self):
        paddle.seed(11)
        cfg = LlamaConfig(vocab_size=512, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m, cfg

    def _run(self, m, prompts, lens, **kw):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        # batch 1: sequential admission, so earlier requests register
        # their prefixes before later ones are admitted
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=96, page_size=8,
                                       prompt_pad=8, **kw)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, lens)]
        res = eng.run()
        return [res[r] for r in rids], eng

    @pytest.mark.slow
    def test_hit_outputs_match_uncached(self):
        m, cfg = self._model()
        rng = np.random.default_rng(5)
        base = list(rng.integers(1, cfg.vocab_size, 24))
        prompts = [base + [7, 8, 9],        # registers prefix
                   base + [100, 101],       # hits it (24 = 3 pages)
                   base[:16] + [55, 56, 57, 58],  # shorter-prefix hit
                   list(rng.integers(1, cfg.vocab_size, 10))]  # miss
        lens = [6, 6, 5, 4]
        out_ref, _ = self._run(m, prompts, lens)
        out_cached, eng = self._run(m, prompts, lens,
                                    enable_prefix_caching=True)
        assert out_cached == out_ref
        assert eng.prefix_hits >= 2
        # shared length is power-of-two-page quantized: the 3-page (24
        # token) match attaches 2 pages, the 2-page match attaches both
        assert eng.prefix_tokens_reused >= 16 + 16
        info = eng.cache_memory_info()
        assert info["prefix_entries"] >= 2 and info["prefix_pages"] >= 2

    @pytest.mark.slow
    def test_whole_prompt_cached_still_decodes(self):
        """Prompt == cached prefix: sharing must cap at one page less so
        the suffix prefill still produces first-token logits."""
        m, cfg = self._model()
        base = list(range(1, 17))           # exactly 2 pages of 8
        out_ref, _ = self._run(m, [base, base], [5, 5])
        out_cached, eng = self._run(m, [base, base], [5, 5],
                                    enable_prefix_caching=True)
        assert out_cached == out_ref
        assert eng.prefix_hits == 1
        assert eng.prefix_tokens_reused == 8   # capped below p_len

    @pytest.mark.slow
    def test_eviction_under_pool_pressure(self):
        """Tiny pool: cached pages must be reclaimed (LRU) so new
        requests still admit; outputs stay correct."""
        m, cfg = self._model()
        rng = np.random.default_rng(9)
        prompts = [list(rng.integers(1, cfg.vocab_size, 16))
                   for _ in range(4)]
        lens = [6] * 4
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=96, page_size=8,
                                       prompt_pad=8, num_pages=8,
                                       enable_prefix_caching=True)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, lens)]
        res = eng.run()
        ref, _ = self._run(m, prompts, lens)
        assert [res[r] for r in rids] == ref
        # pool accounting sane: every page is free, cached, or trash
        rc = eng._page_rc
        cached = {n["page"] for n in eng._prefix_nodes.values()}
        assert set(eng._free).isdisjoint(cached)
        assert all(rc[p] >= 1 for p in cached)
        assert all(rc[p] == 0 for p in eng._free)

    @pytest.mark.slow
    def test_refcounts_zero_after_cache_clear(self):
        m, cfg = self._model()
        base = list(range(1, 25))
        _, eng = self._run(m, [base, base + [3]], [4, 4],
                           enable_prefix_caching=True)
        while eng._evict_one():
            pass
        assert all(eng._page_rc[1:] == 0)
        assert sorted(eng._free) == list(range(1, eng.num_pages))

    @pytest.mark.slow
    def test_eviction_cannot_reclaim_matched_pages(self):
        """r5 review: _reserve_ok may evict the just-matched entry under
        pool pressure; the matched pages must be pinned so they never
        transit the free list while a slot attaches them."""
        m, cfg = self._model()
        rng = np.random.default_rng(13)
        base = list(rng.integers(1, cfg.vocab_size, 16))  # 2 pages
        others = [list(rng.integers(1, cfg.vocab_size, 16))
                  for _ in range(3)]
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        # pool of 9 usable pages: each 16+6-token request needs 3; the
        # cache fills fast and hit-admissions must evict under pressure
        eng = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=96, page_size=8,
                                       prompt_pad=8, num_pages=10,
                                       enable_prefix_caching=True)
        prompts = [base, others[0], base + [3], others[1],
                   base + [4], others[2], base + [5]]
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run()
        ref, _ = self._run(m, prompts, [6] * len(prompts))
        assert [res[r] for r in rids] == ref
        rc = eng._page_rc
        assert all(rc[p] == 0 for p in eng._free)
        assert len(set(eng._free)) == len(eng._free)   # no double-free


class TestBeamSearch:
    """Scan-native beam search (≙ PaddleNLP decode_strategy='beam_search').
    Exactness oracle: with K >= V^(n_new-1) beams the search is
    exhaustive, so its best score must equal the brute-force maximum
    total log-prob over ALL V^n_new continuations computed by eager full
    re-forwards — this exercises the cache reorder/gather machinery
    end-to-end."""

    def _model(self, vocab=8):
        cfg = LlamaConfig(vocab_size=vocab, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=32)
        paddle.seed(23)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    @pytest.mark.slow
    def test_full_width_beam_finds_global_max(self):
        import itertools
        cfg, m = self._model(vocab=8)
        v, n_new = cfg.vocab_size, 3
        ids = np.array([[3, 1, 4, 1, 5]], np.int32)
        toks, score = m.generate(paddle.to_tensor(ids),
                                 max_new_tokens=n_new,
                                 decode_strategy="beam_search",
                                 num_beams=v * v)    # >= V^(n-1): exhaustive
        # brute force: total logprob of every continuation by re-forward
        best = -np.inf
        best_seq = None
        for seq in itertools.product(range(v), repeat=n_new):
            total, cur = 0.0, ids[0].tolist()
            for tk in seq:
                logits = m(paddle.to_tensor(
                    np.asarray(cur, np.int32)[None]))
                lgp = jax.nn.log_softmax(
                    logits._value[0, -1].astype(jnp.float32))
                total += float(lgp[tk])
                cur.append(tk)
            if total > best:
                best, best_seq = total, seq
        assert abs(float(score[0]) - best) < 1e-3, (float(score[0]), best)
        assert tuple(int(t) for t in np.asarray(toks._value)[0]) == best_seq

    def test_eos_freezes_beams(self):
        cfg, m = self._model(vocab=16)
        eos = 5
        ids = np.array([[2, 7, 9]], np.int32)
        toks, score = m.generate(paddle.to_tensor(ids), max_new_tokens=10,
                                 decode_strategy="beam_search",
                                 num_beams=4, eos_token_id=eos,
                                 length_penalty=0.6)
        seq = [int(t) for t in np.asarray(toks._value)[0]]
        if eos in seq:
            i = seq.index(eos)
            assert all(t == eos for t in seq[i:])
        assert np.isfinite(float(score[0]))

    @pytest.mark.slow
    def test_reported_score_matches_eager_recompute(self):
        """Self-consistency: the returned score (length_penalty=0) must
        equal the returned sequence's actual total log-prob, recomputed
        by eager full re-forwards — catches any cache-reorder or score-
        bookkeeping drift. (Beam width monotonicity is NOT asserted:
        greedy pruning does not guarantee it.)"""
        cfg, m = self._model(vocab=12)
        ids = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
        toks, scores = m.generate(paddle.to_tensor(ids), max_new_tokens=5,
                                  decode_strategy="beam_search",
                                  num_beams=4)
        toks = np.asarray(toks._value)
        for i in range(ids.shape[0]):
            total, cur = 0.0, ids[i].tolist()
            for tk in toks[i]:
                lg = m(paddle.to_tensor(np.asarray(cur, np.int32)[None]))
                total += float(jax.nn.log_softmax(
                    lg._value[0, -1].astype(jnp.float32))[int(tk)])
                cur.append(int(tk))
            assert abs(float(scores._value[i]) - total) < 1e-3, \
                (i, float(scores._value[i]), total)

    def test_rejects_single_beam(self):
        cfg, m = self._model()
        with pytest.raises(ValueError, match="num_beams"):
            m.generate(paddle.to_tensor(np.array([[1]], np.int32)),
                       decode_strategy="beam_search", num_beams=1)


class TestLogitsProcessors:
    """repetition_penalty + min_new_tokens (≙ the reference's
    LogitsProcessor stack in generate). Oracle: an eager re-forward loop
    applying the identical rule."""

    def _model(self):
        cfg = LlamaConfig(vocab_size=32, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=32)
        paddle.seed(31)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    @pytest.mark.slow
    def test_repetition_penalty_matches_eager_rule(self):
        cfg, m = self._model()
        rp, n = 1.8, 6
        ids = np.array([[3, 9, 3]], np.int32)
        toks, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=n,
                             decode_strategy="greedy_search",
                             repetition_penalty=rp)
        got = [int(t) for t in np.asarray(toks._value)[0]]
        # eager oracle
        seen = set(ids[0].tolist())
        cur, want = ids[0].tolist(), []
        for _ in range(n):
            lg = np.array(m(paddle.to_tensor(
                np.asarray(cur, np.int32)[None]))._value[0, -1],
                np.float32)
            for tk in seen:
                lg[tk] = lg[tk] / rp if lg[tk] > 0 else lg[tk] * rp
            nxt = int(np.argmax(lg))
            want.append(nxt)
            seen.add(nxt)
            cur.append(nxt)
        assert got == want, (got, want)
        # and the penalty actually changes the output for this model
        plain, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=n)
        assert got != [int(t) for t in np.asarray(plain._value)[0]]

    def test_min_new_tokens_suppresses_eos(self):
        cfg, m = self._model()
        ids = np.array([[5, 6]], np.int32)
        # pick eos = the unconstrained first greedy token, so generation
        # would otherwise stop immediately
        t0, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=1)
        eos = int(np.asarray(t0._value)[0, 0])
        toks, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=8,
                             eos_token_id=eos, min_new_tokens=4)
        seq = [int(t) for t in np.asarray(toks._value)[0]]
        assert all(t != eos for t in seq[:4]), seq

    def test_beam_repetition_penalty_runs(self):
        cfg, m = self._model()
        ids = np.array([[1, 2]], np.int32)
        toks, score = m.generate(paddle.to_tensor(ids), max_new_tokens=5,
                                 decode_strategy="beam_search",
                                 num_beams=3, repetition_penalty=1.5,
                                 min_new_tokens=2, eos_token_id=7)
        seq = [int(t) for t in np.asarray(toks._value)[0]]
        assert len(seq) == 5 and np.isfinite(float(score[0]))
        assert all(t != 7 for t in seq[:2])


class TestSpeculativeDecoding:
    """Greedy speculative decoding is LOSSLESS: the emitted stream must
    equal target-only greedy exactly, for ANY draft — a random unrelated
    draft (worst case, low acceptance) and the target itself (best case,
    full acceptance)."""

    def _models(self):
        cfg_t = LlamaConfig(vocab_size=64, hidden_size=64,
                            intermediate_size=128, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            max_position_embeddings=64)
        cfg_d = LlamaConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_hidden_layers=1,
                            num_attention_heads=2, num_key_value_heads=1,
                            max_position_embeddings=64)
        paddle.seed(17)
        t = LlamaForCausalLM(cfg_t)
        paddle.seed(18)
        d = LlamaForCausalLM(cfg_d)
        t.eval(); d.eval()
        return t, d

    @pytest.mark.slow
    def test_lossless_vs_target_greedy_random_draft(self):
        from paddle_tpu.models.speculative import speculative_generate
        t, d = self._models()
        rng = np.random.default_rng(2)
        ids = rng.integers(1, 64, (2, 7)).astype(np.int32)
        n = 12
        want, _ = t.generate(paddle.to_tensor(ids), max_new_tokens=n)
        got, acc = speculative_generate(t, d, paddle.to_tensor(ids),
                                        max_new_tokens=n,
                                        num_draft_tokens=3)
        np.testing.assert_array_equal(np.asarray(got._value),
                                      np.asarray(want._value))
        assert 0.0 <= float(acc) <= 1.0

    @pytest.mark.slow
    def test_self_draft_full_acceptance(self):
        from paddle_tpu.models.speculative import speculative_generate
        t, _ = self._models()
        ids = np.array([[5, 9, 13]], np.int32)
        n = 10
        want, _ = t.generate(paddle.to_tensor(ids), max_new_tokens=n)
        got, acc = speculative_generate(t, t, paddle.to_tensor(ids),
                                        max_new_tokens=n,
                                        num_draft_tokens=4)
        np.testing.assert_array_equal(np.asarray(got._value),
                                      np.asarray(want._value))
        assert float(acc) > 0.95, float(acc)   # target drafts for itself

    def test_eos_stops_early(self):
        from paddle_tpu.models.speculative import speculative_generate
        t, d = self._models()
        ids = np.array([[3, 4]], np.int32)
        w, _ = t.generate(paddle.to_tensor(ids), max_new_tokens=1)
        eos = int(np.asarray(w._value)[0, 0])
        got, _ = speculative_generate(t, d, paddle.to_tensor(ids),
                                      max_new_tokens=8,
                                      num_draft_tokens=3,
                                      eos_token_id=eos)
        seq = [int(x) for x in np.asarray(got._value)[0]]
        assert seq[0] == eos
        assert all(x == 0 for x in seq[1:]), seq   # PAD after EOS

    def test_rejection_sampling_first_token_distribution(self):
        """The Leviathan guarantee, tested directly on _spec_accept:
        whatever the draft q, the first emitted token's marginal must
        equal the target p. 200k vectorized draws vs closed form."""
        from paddle_tpu.models.speculative import _spec_accept
        rng = np.random.default_rng(0)
        V, K, N = 8, 2, 200_000
        p = rng.dirichlet(np.ones(V), size=K + 1)    # target rows
        q = rng.dirichlet(np.ones(V) * 0.4, size=K)  # skewed draft rows
        p_logp = jnp.log(jnp.asarray(p, jnp.float32))[None]
        q_logp = jnp.log(jnp.asarray(q, jnp.float32))[None]

        def one(key):
            kq, ka = jax.random.split(key)
            props = jax.random.categorical(
                kq, q_logp[0], axis=-1).astype(jnp.int32)[None]  # (1, K)
            j, repl = _spec_accept(p_logp, q_logp, props, ka)
            return jnp.where(j[0] >= 1, props[0, 0], repl[0])

        keys = jax.random.split(jax.random.PRNGKey(7), N)
        toks = np.asarray(jax.jit(jax.vmap(one))(keys))
        freq = np.bincount(toks, minlength=V) / N
        np.testing.assert_allclose(freq, p[0], atol=0.006)

    def test_sampling_near_zero_temperature_equals_greedy(self):
        from paddle_tpu.models.speculative import speculative_generate
        t, d = self._models()
        ids = np.array([[4, 8, 15]], np.int32)
        n = 10
        want, _ = t.generate(paddle.to_tensor(ids), max_new_tokens=n)
        got, _ = speculative_generate(t, d, paddle.to_tensor(ids),
                                      max_new_tokens=n,
                                      num_draft_tokens=3, do_sample=True,
                                      temperature=1e-4)
        np.testing.assert_array_equal(np.asarray(got._value),
                                      np.asarray(want._value))

    def test_vocab_mismatch_raises(self):
        from paddle_tpu.models.speculative import speculative_generate
        t, _ = self._models()
        cfg_bad = LlamaConfig(vocab_size=32, hidden_size=32,
                              intermediate_size=64, num_hidden_layers=1,
                              num_attention_heads=2,
                              num_key_value_heads=1,
                              max_position_embeddings=64)
        bad = LlamaForCausalLM(cfg_bad)
        with pytest.raises(ValueError, match="vocab"):
            speculative_generate(t, bad, paddle.to_tensor(
                np.array([[1]], np.int32)))


class TestNoRepeatNgram:
    def _model(self):
        cfg = LlamaConfig(vocab_size=32, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=32)
        paddle.seed(31)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    def test_no_repeat_ngram_matches_eager_rule(self):
        cfg, m = self._model()
        n_gram, n = 2, 8
        ids = np.array([[3, 9, 3]], np.int32)
        toks, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=n,
                             no_repeat_ngram_size=n_gram)
        got = [int(t) for t in np.asarray(toks._value)[0]]
        cur, want = ids[0].tolist(), []
        for _ in range(n):
            lg = np.array(m(paddle.to_tensor(
                np.asarray(cur, np.int32)[None]))._value[0, -1],
                np.float32)
            suffix = tuple(cur[-(n_gram - 1):])
            for i in range(len(cur) - n_gram + 1):
                if tuple(cur[i:i + n_gram - 1]) == suffix:
                    lg[cur[i + n_gram - 1]] = -1e30
            nxt = int(np.argmax(lg))
            want.append(nxt)
            cur.append(nxt)
        assert got == want, (got, want)
        # the constraint binds: no repeated bigram in prompt+output
        grams = set()
        for a, bb in zip(cur, cur[1:]):
            assert (a, bb) not in grams, (a, bb, cur)
            grams.add((a, bb))

    def test_no_repeat_ngram_beam_runs(self):
        cfg, m = self._model()
        ids = np.array([[1, 2, 1]], np.int32)
        toks, score = m.generate(paddle.to_tensor(ids), max_new_tokens=6,
                                 decode_strategy="beam_search",
                                 num_beams=3, no_repeat_ngram_size=2)
        seq = ids[0].tolist() + [int(t) for t in
                                 np.asarray(toks._value)[0]]
        grams = list(zip(seq, seq[1:]))
        assert len(grams) == len(set(grams)), seq
        assert np.isfinite(float(score[0]))


class TestChunkedPrefill:
    """Chunked prefill (≙ vLLM chunked prefill): an admission dispatch
    packs at most `prefill_chunk` tokens, a longer prompt continues in
    the next. Oracle: the same engine without a chunk bound."""

    def _model(self):
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        paddle.seed(13)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return cfg, m

    def test_matches_unchunked_engine(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        cfg, m = self._model()
        rng = np.random.default_rng(7)
        # short, exact multiple, ragged, long
        prompts = [list(rng.integers(1, cfg.vocab_size, p))
                   for p in (5, 16, 23, 40)]
        outs = {}
        for chunk in (None, 16):
            eng = ContinuousBatchingEngine(
                m, max_batch_size=2, max_seq_len=96, page_size=8,
                prompt_pad=8, prefill_chunk=chunk)
            rids = [eng.add_request(p, 6) for p in prompts]
            res = eng.run()
            outs[chunk] = [res[r] for r in rids]
            if chunk:
                # no admission program is as wide as the long prompts
                # (+ 8: a second piece starts on the next row block)
                assert all(t <= chunk + 8 for t, _ in eng._ragged_jits)
        assert outs[16] == outs[None]

    def test_chunk_must_align_to_pages(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        cfg, m = self._model()
        with pytest.raises(ValueError, match="multiple of page_size"):
            ContinuousBatchingEngine(m, page_size=8, prefill_chunk=12)


class TestPageAccounting:
    """Robustness PR satellite: after ANY engine.run() — plain,
    prefix-cache-sharing, sliding-window-reclamation — every page is
    back on the free list, all refcounts are zero, and
    `cache_memory_info()` matches the fresh-engine baseline. conftest
    enables PDT_CHECK_INVARIANTS=1 for this file, so every intermediate
    step is also re-proved by `check_invariants()`."""

    def _tiny(self, **cfg_kw):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=64, **cfg_kw)
        paddle.seed(3)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    @staticmethod
    def _occupancy(info):
        # occupancy-only view: hit counters legitimately differ after
        # a run, occupancy must not
        return {k: v for k, v in info.items()
                if k in ("pages_in_use", "bytes_in_use", "utilization",
                         "prefix_entries", "prefix_pages")}

    def _assert_pool_restored(self, eng, baseline):
        assert self._occupancy(eng.cache_memory_info()) == baseline
        assert all(eng._page_rc[1:] == 0)
        assert sorted(eng._free) == list(range(1, eng.num_pages))
        eng.check_invariants()

    def test_plain_run_returns_every_page(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m = self._tiny()
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, page_size=4)
        baseline = self._occupancy(eng.cache_memory_info())
        rids = [eng.add_request([5, 4, 3, 2, 6, 7], 8),
                eng.add_request([9, 1, 2], 6)]
        res = eng.run()
        assert [len(res[r]) for r in rids] == [8, 6]
        self._assert_pool_restored(eng, baseline)

    def test_prefix_sharing_run_returns_every_page(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m = self._tiny()
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, page_size=4,
                                       enable_prefix_caching=True)
        baseline = self._occupancy(eng.cache_memory_info())
        base = list(range(1, 13))
        rids = [eng.add_request(base + [t], 5) for t in (20, 21, 22)]
        res = eng.run()
        assert all(len(res[r]) == 5 for r in rids)
        assert eng.prefix_hits >= 1
        # cached pages are retained BY DESIGN; after draining the cache
        # the pool must be byte-identical to the fresh-engine baseline
        while eng._evict_one():
            pass
        self._assert_pool_restored(eng, baseline)

    def test_sliding_window_run_returns_every_page(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=64)
        cfg.sliding_window = 8
        paddle.seed(3)
        m = LlamaForCausalLM(cfg)
        m.eval()
        eng = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, page_size=4)
        baseline = self._occupancy(eng.cache_memory_info())
        rids = [eng.add_request(list(range(1, 10)), 16),
                eng.add_request(list(range(3, 9)), 12)]
        res = eng.run()
        assert [len(res[r]) for r in rids] == [16, 12]
        self._assert_pool_restored(eng, baseline)


class TestPinSafety:
    """ISSUE 9 (pdt-lint PDT005): admission pins matched prefix pages
    BEFORE the worst-case reservation — so the reservation's ERROR
    path must unpin, or the refcounts leak and a later
    `check_invariants()` dies far from the cause. Both pin-across-
    reserve sites (`_claim_candidate`, `import_pages`) were unguarded
    until the checker flagged them; these tests pin the guard."""

    def _tiny(self):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=64)
        paddle.seed(3)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    @staticmethod
    def _raising_reserve():
        def boom(req, shared_pages=0):
            raise RuntimeError("reservation accounting exploded")
        return boom

    def test_claim_candidate_unpins_when_reserve_raises(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(self._tiny(), max_batch_size=1,
                                       max_seq_len=64, page_size=4,
                                       enable_prefix_caching=True)
        base = list(range(1, 13))
        rid0 = eng.add_request(base, 4)
        res = eng.run()
        assert len(res[rid0]) == 4          # chain now registered
        rc_before = eng._page_rc.copy()
        orig = eng._reserve_ok
        eng._reserve_ok = self._raising_reserve()
        try:
            eng.add_request(base + [40, 41], 4)   # prefix match pins
            with pytest.raises(RuntimeError, match="accounting"):
                eng.step()
        finally:
            eng._reserve_ok = orig
        # the pins taken for the matched prefix were released on the
        # error path: refcounts identical, invariants hold
        assert (eng._page_rc == rc_before).all()
        eng.check_invariants()
        res = eng.run()                     # and the engine still serves
        assert len(res[rid0 + 1]) == 4

    def test_import_pages_unpins_when_reserve_raises(self):
        from paddle_tpu.models.serving import ContinuousBatchingEngine
        m = self._tiny()
        prompt = list(range(1, 11))
        src = ContinuousBatchingEngine(m, max_batch_size=1,
                                       max_seq_len=64, page_size=4)
        rid = src.add_request(prompt, 6)
        src.step()                          # prefilled + first token
        payload = src.export_pages(rid)
        dst = ContinuousBatchingEngine(m, max_batch_size=2,
                                       max_seq_len=64, page_size=4,
                                       enable_prefix_caching=True)
        warm = dst.add_request(prompt, 3)
        dst.run()                           # dst trie holds the chain
        rc_before = dst._page_rc.copy()
        orig = dst._reserve_ok
        dst._reserve_ok = self._raising_reserve()
        try:
            with pytest.raises(RuntimeError, match="accounting"):
                dst.import_pages(payload)
        finally:
            dst._reserve_ok = orig
        assert (dst._page_rc == rc_before).all()
        dst.check_invariants()
        req = dst.import_pages(payload)     # and the import still works
        assert req.request_id == payload["request_id"]
        dst.check_invariants()
        assert warm is not None
