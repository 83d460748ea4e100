"""The stored layout of a page pool (ISSUE 28): token-major,
``(P, page_size, HK*D)`` — a token's K (or V) row over all KV heads is
the unit stored contiguously, so the row scatter that writes a step's
K and V updates the donated pool in place (the static proof is
tests/test_mosaic_lowering.py::TestPoolWriteInPlace).

Here, on the CPU: the write against a plain per-row reference, the
kernel in interpret mode against the XLA oracle at the widths that
serve (HK in {2, 8}, D in {64, 128}), and the engine's export / import
boundary, whose payload keeps its documented head-major shape
``(HK, n_pages, page_size, D)`` whatever the pools store."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       QuantServingConfig)
from paddle_tpu.ops.paged_attention import paged_append_values
from paddle_tpu.ops.ragged_paged_attention import (
    KV_QMAX, pack_ragged_starts, pages_to_payload, payload_to_pages,
    ragged_paged_attention_values, ragged_scatter_quantized,
    ragged_scatter_values, token_arrays)

HK, D, PS, PAGES = 2, 8, 4, 8
BT = np.array([[1, 2, 3], [4, 5, 0]], np.int32)

# (owning sequence, position) of each packed row; -1 = padding
WRITES = {
    # a prefill of 5 rows, three padding rows, one decode row
    "padding_rows_to_trash": [(0, 0), (0, 1), (0, 2), (0, 3), (0, 4),
                              (-1, 0), (-1, 0), (-1, 0), (1, 6)],
    # both rows land in page 1 (slots 1 and 2) in one call
    "two_rows_of_one_page": [(0, 1), (0, 2)],
    # a chunk continuation: rows 6..9 of a sequence whose first six
    # are cached, starting mid-page and crossing into the next
    "continuation_at_offset": [(0, 6), (0, 7), (0, 8), (0, 9)],
}


def _rows(rng, n):
    return rng.standard_normal((n, HK, D)).astype(np.float32)


def _targets(writes):
    """(page, slot) of each live row, by the definition: the page the
    sequence's table names for the position, the position's slot."""
    return [None if s < 0 else (int(BT[s, p // PS]), p % PS)
            for s, p in writes]


class TestRowWrite:
    @pytest.mark.parametrize("case", sorted(WRITES))
    def test_plain_write_matches_per_row_reference(self, case):
        rng = np.random.default_rng(len(case))
        writes = WRITES[case]
        seq = np.array([s for s, _ in writes], np.int32)
        pos = np.array([p for _, p in writes], np.int32)
        k_rows, v_rows = _rows(rng, len(writes)), _rows(rng, len(writes))
        # pools start full of other values: what is not written stays
        kp0 = rng.standard_normal((PAGES, PS, HK * D)).astype(np.float32)
        vp0 = rng.standard_normal((PAGES, PS, HK * D)).astype(np.float32)
        kp, vp = ragged_scatter_values(
            jnp.asarray(kp0), jnp.asarray(vp0), jnp.asarray(k_rows),
            jnp.asarray(v_rows), jnp.asarray(BT), jnp.asarray(seq),
            jnp.asarray(pos))
        want_k, want_v = kp0.copy(), vp0.copy()
        for t, tgt in enumerate(_targets(writes)):
            if tgt is not None:
                # head h of the row is its lanes [h*D, (h+1)*D)
                want_k[tgt] = np.concatenate(list(k_rows[t]))
                want_v[tgt] = np.concatenate(list(v_rows[t]))
        kp, vp = np.asarray(kp), np.asarray(vp)
        np.testing.assert_array_equal(kp[1:], want_k[1:])
        np.testing.assert_array_equal(vp[1:], want_v[1:])
        # padding rows went to the trash page's slot 0 and nowhere else
        np.testing.assert_array_equal(kp[0, 1:], kp0[0, 1:])
        if (seq < 0).any():
            assert any(np.array_equal(kp[0, 0], k_rows[t].reshape(-1))
                       for t in np.flatnonzero(seq < 0))

    @pytest.mark.parametrize("case", sorted(WRITES))
    def test_quantized_write_matches_per_row_reference(self, case):
        """int8 pools: each row quantized on its own absmax over all
        heads, bytes and scales at the row's (page, slot) — and the
        same bytes whether the rows are written in one call or one
        call a row (the path invariance migration and preemption
        rest on)."""
        rng = np.random.default_rng(7 + len(case))
        writes = [w for w in WRITES[case] if w[0] >= 0]
        seq = jnp.asarray([s for s, _ in writes], jnp.int32)
        pos = jnp.asarray([p for _, p in writes], jnp.int32)
        k_rows, v_rows = _rows(rng, len(writes)), _rows(rng, len(writes))

        def empty():
            return (jnp.zeros((PAGES, PS, HK * D), jnp.int8),
                    jnp.zeros((PAGES, PS, HK * D), jnp.int8),
                    jnp.zeros((PAGES, PS), jnp.float32),
                    jnp.zeros((PAGES, PS), jnp.float32))

        bulk = ragged_scatter_quantized(
            *empty(), jnp.asarray(k_rows), jnp.asarray(v_rows),
            jnp.asarray(BT), seq, pos)
        inc = empty()
        for t in range(len(writes)):
            inc = ragged_scatter_quantized(
                *inc, jnp.asarray(k_rows[t:t + 1]),
                jnp.asarray(v_rows[t:t + 1]), jnp.asarray(BT),
                seq[t:t + 1], pos[t:t + 1])
        for a, b in zip(bulk, inc):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        kp, _, ks, _ = (np.asarray(a) for a in bulk)
        for t, tgt in enumerate(_targets(writes)):
            amax = np.abs(k_rows[t]).max()
            want = np.clip(np.round(k_rows[t].reshape(-1) / amax * KV_QMAX),
                           -KV_QMAX - 1, KV_QMAX).astype(np.int8)
            np.testing.assert_array_equal(kp[tgt], want)
            np.testing.assert_allclose(ks[tgt], amax / KV_QMAX, rtol=1e-6)

    def test_decode_append_writes_whole_rows(self):
        """The q = 1 op's write (`paged_append_values`) is the same row
        scatter."""
        rng = np.random.default_rng(3)
        kp0 = jnp.zeros((PAGES, PS, HK * D), jnp.float32)
        k, v = _rows(rng, 2), _rows(rng, 2)
        kp, vp = paged_append_values(
            kp0, kp0, jnp.asarray(k), jnp.asarray(v), jnp.asarray(BT),
            jnp.asarray([5, 2], jnp.int32))
        np.testing.assert_array_equal(np.asarray(kp)[2, 1], k[0].reshape(-1))
        np.testing.assert_array_equal(np.asarray(vp)[4, 2], v[1].reshape(-1))
        assert np.count_nonzero(np.asarray(kp)) == 2 * HK * D


class TestKernelAtServingWidths:
    """The kernel (interpret mode) against `_ragged_xla` at the widths
    that serve: the Llama-path cells (8, 128), the hybrid's attention
    block (2, 128), the smoke's Llama-3.2-1B head_dim (8, 64) — the
    last without padding its pools to 128 lanes a head, which the
    kernel did a call before the rows were stored lane-dense."""

    @pytest.mark.parametrize("block_q", [1, 8])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("hk", [2, 8])
    def test_kernel_matches_oracle(self, hk, d, block_q):
        rng = np.random.default_rng(hk * d + block_q)
        ps, pps, g = 16, 4, 2
        if block_q == 1:
            ql, cl = np.ones(3, np.int32), np.array([40, 1, 17], np.int32)
        else:
            ql = np.array([1, 11, 5], np.int32)
            cl = np.array([33, 11, 23], np.int32)    # 5 at offset 18
        qs, total = pack_ragged_starts(ql, block_q=block_q)
        pages = len(ql) * pps + 1
        q = jnp.asarray(rng.standard_normal((total, hk * g, d)),
                        jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pages, ps, hk * d)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pages, ps, hk * d)),
                         jnp.float32)
        bt = (1 + rng.permutation(pages - 1)).reshape(
            len(ql), pps).astype(np.int32)
        kern, xla = (np.asarray(ragged_paged_attention_values(
            q, kp, vp, qs, ql, cl, bt, block_q=block_q, use_kernel=uk))
            for uk in (True, False))
        np.testing.assert_allclose(kern, xla, atol=2e-5)
        seq_t, _ = token_arrays(qs, ql, cl, total)
        assert np.all(kern[seq_t < 0] == 0)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64)
    paddle.seed(11)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    return ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                    page_size=4, **kw)


def _finish(eng, req):
    while not req.done:
        eng.step()
    return list(req.output)


class TestExportImportBoundary:
    def test_pools_are_token_major(self, model):
        cfg = model.config
        eng = _engine(model)
        kp, vp = eng._kv[0]
        assert kp.shape == vp.shape == (
            eng.num_pages, 4, cfg.num_key_value_heads * cfg.head_dim)

    def test_layout_transposes_are_inverses(self):
        rng = np.random.default_rng(0)
        pages = rng.standard_normal((5, PS, HK * D)).astype(np.float32)
        payload = pages_to_payload(pages, HK)
        assert payload.shape == (HK, 5, PS, D)
        # head h of token (page, slot) is lanes [h*D, (h+1)*D) of its row
        np.testing.assert_array_equal(payload[1, 3, 2],
                                      pages[3, 2, D:2 * D])
        np.testing.assert_array_equal(payload_to_pages(payload), pages)

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_export_import_round_trip_is_byte_equal(self, model, quant):
        """`export_pages` keeps its documented payload — per layer
        (hk, n_pages, page_size, hd), `kv_spec` (L, hk, hd, dtype) —
        and what `import_pages` installs exports again byte for byte;
        the migrated stream ends as the stay-at-home one does."""
        kw = dict(quant=QuantServingConfig(kv="int8")) if quant \
            else {}
        cfg = model.config
        hk, hd = cfg.num_key_value_heads, cfg.head_dim
        prompt, new = [5, 4, 3, 2, 6, 7, 1, 9, 8], 8
        home = _engine(model, **kw)
        want = _finish(home, home.get_request(
            home.add_request(prompt, new)))

        src, dst = _engine(model, **kw), _engine(model, **kw)
        rid = src.add_request(prompt, new)
        for _ in range(3):
            src.step()
        payload = src.export_pages(rid)
        n_pages = payload["n_pages"]
        assert n_pages == -(-payload["ctx"] // 4) >= 3
        assert tuple(payload["kv_spec"]) == (
            cfg.num_hidden_layers, hk, hd, "int8" if quant else "float32")
        assert len(payload["kv"]) == cfg.num_hidden_layers
        for k, v in payload["kv"]:
            assert k.shape == v.shape == (hk, n_pages, 4, hd)
            assert k.flags["C_CONTIGUOUS"] and k.any()
        # the payload's rows are the pool's rows: head h of a token is
        # lanes [h*hd, (h+1)*hd) of its stored row
        slot = src._resident_slot(rid)
        pool = np.asarray(src._kv[0][0])
        for j in range(n_pages):
            page = int(src._bt[slot, j])
            for h in range(hk):
                np.testing.assert_array_equal(
                    payload["kv"][0][0][h, j],
                    pool[page, :, h * hd:(h + 1) * hd])

        req = dst.import_pages(payload)
        again = dst.export_pages(req.rid)
        for (k0, v0), (k1, v1) in zip(payload["kv"], again["kv"]):
            assert k0.tobytes() == k1.tobytes()
            assert v0.tobytes() == v1.tobytes()
        if quant:
            for (a0, b0), (a1, b1) in zip(payload["kv_scales"],
                                          again["kv_scales"]):
                assert a0.tobytes() == a1.tobytes()
                assert b0.tobytes() == b1.tobytes()
        assert payload["kv_sha256"] == again["kv_sha256"]
        src.evict_request(rid)
        assert _finish(dst, req) == want
        src.check_invariants()
        dst.check_invariants()
