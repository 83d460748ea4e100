"""Ragged paged attention (ISSUE 6): the fused mixed prefill+decode
kernel (`ops/ragged_paged_attention.py`) proved in INTERPRET mode
against an independent NumPy oracle — mixed batches, prefix-shared
pages at nonzero position offsets, sliding windows, GQA group sizes,
and empty/degenerate sequences — plus the scatter/packing helpers, the
bounded-gather static trim, and the ENGINE-level contract: greedy
streams bit-identical to `model.generate()`'s, request by request,
through a forced preemption and a SIGKILL replica failover.

conftest runs this file with PDT_TELEMETRY=1 and
PDT_CHECK_INVARIANTS=1, so every engine step here re-proves page
accounting on the ragged path."""
import contextlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.observability as telemetry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving import (ContinuousBatchingEngine,
                                       PoolExhausted, RequestStatus)
from paddle_tpu.ops.paged_attention import paged_attention_values
from paddle_tpu.ops import ragged_paged_attention as rpa_mod
from paddle_tpu.ops.ragged_paged_attention import (
    gather_pages, kv_block_pages, pack_ragged_starts, pages_to_payload,
    payload_to_pages, ragged_pages_walked, ragged_paged_attention_values,
    ragged_scatter_values, token_arrays)
from paddle_tpu.utils.faults import FaultInjector


def _pool(pages, dtype=None):
    """A head-major (HK, P, page_size, D) array, the shape the NumPy
    oracle indexes and the export payload keeps, as the pool the ops
    store: token-major (P, page_size, HK*D)."""
    return jnp.asarray(payload_to_pages(np.asarray(pages)), dtype)


def np_ragged_oracle(q, kp, vp, qs, ql, cl, bt, window=None):
    """Independent NumPy reference: per-token loop over the page table
    (pools head-major, (HK, P, page_size, D)), full-precision softmax.
    Padding rows output zero."""
    hk, _, ps, d = kp.shape
    h = q.shape[1]
    g = h // hk
    out = np.zeros_like(q, dtype=np.float32)
    scale = 1.0 / np.sqrt(d)
    for s in range(len(ql)):
        for j in range(int(ql[s])):
            row = int(qs[s]) + j
            pos = int(cl[s]) - int(ql[s]) + j
            lo = 0 if window is None else max(0, pos - window + 1)
            keys, vals = [], []
            for kpos in range(lo, pos + 1):
                pg = bt[s, kpos // ps]
                keys.append(kp[:, pg, kpos % ps])
                vals.append(vp[:, pg, kpos % ps])
            if not keys:
                continue
            K = np.stack(keys, 0)                    # (L, HK, D)
            V = np.stack(vals, 0)
            for head in range(h):
                kh = head // g
                logits = (K[:, kh] @ q[row, head]) * scale
                p = np.exp(logits - logits.max())
                p /= p.sum()
                out[row, head] = p @ V[:, kh]
    return out


def _case(rng, hk=2, g=2, d=16, ps=4, n_pages=12, pps=4,
          ql=(1, 7, 5), cl=(9, 7, 13), block_q=4, tail_pad=4,
          bt=None):
    """Build one ragged batch: packed q, page pools (head-major, as
    the oracle reads them: `_pool` stores them), block tables,
    descriptors. Defaults mix a decode step, a full prefill, and a
    suffix continuation (context > query: nonzero position offset)."""
    h = hk * g
    ql = np.asarray(ql, np.int32)
    cl = np.asarray(cl, np.int32)
    qs, total = pack_ragged_starts(ql, block_q=block_q)
    t = total + tail_pad
    q = rng.standard_normal((t, h, d)).astype(np.float32)
    kp = rng.standard_normal((hk, n_pages, ps, d)).astype(np.float32)
    vp = rng.standard_normal((hk, n_pages, ps, d)).astype(np.float32)
    if bt is None:
        bt = np.zeros((len(ql), pps), np.int32)
        nxt = 1
        for s in range(len(ql)):
            need = -(-int(cl[s]) // ps) if cl[s] else 0
            for j in range(need):
                bt[s, j] = nxt
                nxt += 1
            assert nxt <= n_pages
    return q, kp, vp, qs, ql, cl, np.asarray(bt, np.int32)


def _both_paths(q, kp, vp, qs, ql, cl, bt, window=None, block_q=4):
    """(interpret-mode Pallas kernel, XLA gather oracle) outputs."""
    args = (jnp.asarray(q), _pool(kp), _pool(vp),
            qs, ql, cl, bt)
    kern = np.asarray(ragged_paged_attention_values(
        *args, window=window, block_q=block_q, use_kernel=True))
    xla = np.asarray(ragged_paged_attention_values(
        *args, window=window, block_q=block_q, use_kernel=False))
    return kern, xla


class TestRaggedKernelParity:
    """Interpret-mode kernel AND the XLA oracle vs NumPy — the parity
    ladder every ops/ kernel carries."""

    def test_mixed_decode_prefill_batch(self):
        rng = np.random.default_rng(0)
        q, kp, vp, qs, ql, cl, bt = _case(rng)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt)
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)
        # padding rows (owned by no sequence) are exactly zero
        seq_t, _ = token_arrays(qs, ql, cl, q.shape[0])
        assert np.all(kern[seq_t < 0] == 0)
        assert np.all(xla[seq_t < 0] == 0)

    def test_prefix_shared_pages_nonzero_offset(self):
        """Two sequences attach the SAME physical pages for their first
        two page slots (a prefix-cache hit); the second prefills only a
        suffix at position_offset = 8."""
        rng = np.random.default_rng(1)
        bt = np.zeros((2, 4), np.int32)
        bt[0] = [1, 2, 3, 0]       # full owner: ctx 12, decode q=1
        bt[1] = [1, 2, 4, 5]       # shares pages 1-2, suffix q=5 @ off 8
        q, kp, vp, qs, ql, cl, bt = _case(
            rng, ql=(1, 5), cl=(12, 13), bt=bt)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt)
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)

    @pytest.mark.parametrize("window", [3, 6, 64])
    def test_sliding_window(self, window):
        rng = np.random.default_rng(2)
        q, kp, vp, qs, ql, cl, bt = _case(rng)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt, window=window)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt, window=window)
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)

    @pytest.mark.parametrize("g", [1, 2, 4])
    def test_gqa_group_sizes(self, g):
        rng = np.random.default_rng(3)
        q, kp, vp, qs, ql, cl, bt = _case(rng, g=g)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt)
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)

    def test_empty_and_degenerate_sequences(self):
        """query_len 0 (nothing to do) and context_len == query_len == 1
        (a sequence's very first token) are both well-defined; outputs
        stay finite and match NumPy."""
        rng = np.random.default_rng(4)
        q, kp, vp, qs, ql, cl, bt = _case(
            rng, ql=(0, 1, 3), cl=(0, 1, 3), block_q=1, tail_pad=0)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt, block_q=1)
        assert np.isfinite(kern).all() and np.isfinite(xla).all()
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)

    def test_decode_batch_matches_legacy_kernel(self):
        """A pure decode batch (block_q=1, one query per sequence) is
        exactly the legacy kernel's domain: both kernels, both in
        interpret mode, must agree — the ragged kernel subsumes the
        q=1 one."""
        rng = np.random.default_rng(5)
        b = 3
        q, kp, vp, qs, ql, cl, bt = _case(
            rng, ql=(1,) * b, cl=(9, 6, 2), block_q=1, tail_pad=0)
        ragged = np.asarray(ragged_paged_attention_values(
            jnp.asarray(q), _pool(kp), _pool(vp),
            qs, ql, cl, bt, block_q=1, use_kernel=True))
        legacy = np.asarray(paged_attention_values(
            jnp.asarray(q), _pool(kp), _pool(vp),
            jnp.asarray(cl), jnp.asarray(bt), use_kernel=True))
        np.testing.assert_allclose(ragged, legacy, atol=2e-5)
        # and the legacy interpret kernel agrees with ITS oracle
        legacy_xla = np.asarray(paged_attention_values(
            jnp.asarray(q), _pool(kp), _pool(vp),
            jnp.asarray(cl), jnp.asarray(bt)))
        np.testing.assert_allclose(legacy, legacy_xla, atol=2e-5)

    def test_unaligned_packed_length_rejected(self):
        rng = np.random.default_rng(6)
        q, kp, vp, qs, ql, cl, bt = _case(rng, tail_pad=3)  # t % 4 != 0
        with pytest.raises(ValueError, match="block_q"):
            ragged_paged_attention_values(
                jnp.asarray(q), _pool(kp), _pool(vp),
                qs, ql, cl, bt, block_q=4, use_kernel=True)


def _small_blocks(monkeypatch, pages, ps=4):
    """KV blocks of `pages` pages: the tests' contexts are tens of
    tokens, so the default block (256 keys) would hold any of them in
    one trip and the loop's edges would go unwalked."""
    monkeypatch.setattr(rpa_mod, "KV_BLOCK_MAX_KEYS", pages * ps)


# each: (_case overrides, window, block_q, explicit query_start).
# Blocks are 2 pages of 4 tokens, so a trip holds 8 keys.
LOOP_EDGES = {
    # 9 tokens = pages 0-2: the second block holds one live page and
    # the context ends in the middle of it
    "ends_mid_block": (dict(ql=(1, 9), cl=(9, 9), pps=4), None, 4, None),
    # 8 and 16 tokens: the frontier's page is the last of its block
    "ends_on_block_edge": (dict(ql=(1, 16, 1), cl=(8, 16, 16), pps=4,
                                n_pages=16), None, 4, None),
    "one_token": (dict(ql=(1, 1), cl=(1, 5), pps=4, block_q=1,
                       tail_pad=0), None, 1, None),
    # a decode batch with an idle slot: row 1 is owned by a sequence
    # with no query -> no trip, zero output
    "inactive_slot": (dict(ql=(1, 0, 1), cl=(11, 0, 6), pps=4, block_q=1,
                           tail_pad=1), None, 1, (0, 1, 2)),
    # rows 8-11 (q block 2) lie between the two segments, owned by none
    "padding_qblock_between": (dict(ql=(5, 3), cl=(14, 3), pps=4,
                                    tail_pad=4), None, 4, (0, 12)),
    # window 6 at context 21: the lower edge (key 15) is in page 3, the
    # second page of block 1; pages 0-1 are never visited
    "window_edge_mid_block": (dict(ql=(1, 6), cl=(21, 23), pps=8,
                                   n_pages=16), 6, 4, None),
    # the cells' shape in small: 128 columns, a third of them live
    "wide_table": (dict(ql=(1, 9, 1), cl=(170, 90, 140), pps=128,
                        n_pages=110), None, 4, None),
}


# `_case` overrides of the decode form (block_q 1: three decode rows)
# and of the admission form (block_q 8: a decode row, a prefill, a
# continuation)
BLOCK_Q_CASES = {
    1: dict(ql=(1, 1, 1), cl=(9, 16, 3), tail_pad=0),
    8: dict(ql=(1, 11, 5), cl=(9, 11, 13), tail_pad=8),
}


class TestKernelLoopEdges:
    """The in-kernel loop over KV blocks (ISSUE 26): the edges a
    dynamic trip count brings, kernel (interpret) against the XLA
    oracle and NumPy."""

    @pytest.mark.parametrize("edge", sorted(LOOP_EDGES))
    def test_loop_edge(self, edge, monkeypatch):
        _small_blocks(monkeypatch, 2)
        kw, window, block_q, qs_override = LOOP_EDGES[edge]
        rng = np.random.default_rng(len(edge))
        q, kp, vp, qs, ql, cl, bt = _case(rng, **dict(kw, block_q=block_q))
        if qs_override is not None:
            qs = np.asarray(qs_override, np.int32)
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt, window=window)
        kern, xla = _both_paths(q, kp, vp, qs, ql, cl, bt, window=window,
                                block_q=block_q)
        np.testing.assert_allclose(kern, ref, atol=2e-5)
        np.testing.assert_allclose(xla, ref, atol=2e-5)
        seq_t, _ = token_arrays(qs, ql, cl, q.shape[0])
        assert np.all(kern[seq_t < 0] == 0)

    def test_dead_columns_are_never_read(self, monkeypatch):
        """Columns past a context's frontier hold garbage: an id far
        outside the pool, or the id of a page of NaNs (0 x NaN would
        reach the output if the kernel read it and only masked it).
        The kernel must not notice either."""
        _small_blocks(monkeypatch, 2)
        rng = np.random.default_rng(11)
        q, kp, vp, qs, ql, cl, bt = _case(
            rng, ql=(1, 7, 5), cl=(9, 7, 13), pps=8, n_pages=13)
        poison = 12
        kp[:, poison] = np.nan
        vp[:, poison] = np.nan
        for s in range(len(cl)):
            live = -(-int(cl[s]) // 4)
            bt[s, live:] = [poison if j % 2 else 2 ** 30
                            for j in range(8 - live)]
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        kern = np.asarray(ragged_paged_attention_values(
            jnp.asarray(q), _pool(kp), _pool(vp), qs, ql, cl,
            bt, block_q=4, use_kernel=True))
        np.testing.assert_allclose(kern, ref, atol=2e-5)

    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    @pytest.mark.parametrize("block_q", [1, 8])
    @pytest.mark.parametrize("g", [1, 2, 4])
    def test_group_blockq_pool_dtype(self, g, block_q, pool, monkeypatch):
        """GQA group x q block x pool dtype: the decode form (block_q
        1) and the admission form (block_q 8) of the cells' groups, on
        bf16 pools and on int8 pools with their per-row scales."""
        _small_blocks(monkeypatch, 2)
        rng = np.random.default_rng(100 + 10 * g + block_q)
        kw = BLOCK_Q_CASES[block_q]
        q, kp, vp, qs, ql, cl, bt = _case(rng, g=g, pps=4, n_pages=12,
                                          block_q=block_q, **kw)
        scales = {}
        if pool == "bf16":
            kp_d, vp_d = _pool(kp, jnp.bfloat16), _pool(vp, jnp.bfloat16)
            kp_f, vp_f = (np.asarray(jnp.asarray(x, jnp.bfloat16),
                                     np.float32) for x in (kp, vp))
        else:
            kp_i = rng.integers(-127, 128, kp.shape).astype(np.int8)
            vp_i = rng.integers(-127, 128, vp.shape).astype(np.int8)
            kp_d, vp_d = _pool(kp_i), _pool(vp_i)
            ks = rng.uniform(0.002, 0.02, kp.shape[1:3]).astype(np.float32)
            vs = rng.uniform(0.002, 0.02, kp.shape[1:3]).astype(np.float32)
            scales = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
            kp_f = kp_i.astype(np.float32) * ks[None, :, :, None]
            vp_f = vp_i.astype(np.float32) * vs[None, :, :, None]
        ref = np_ragged_oracle(q, kp_f, vp_f, qs, ql, cl, bt)
        args = (jnp.asarray(q), kp_d, vp_d, qs, ql, cl, bt)
        kern = np.asarray(ragged_paged_attention_values(
            *args, block_q=block_q, use_kernel=True, **scales))
        xla = np.asarray(ragged_paged_attention_values(
            *args, block_q=block_q, use_kernel=False, **scales))
        np.testing.assert_allclose(kern, ref, atol=5e-5)
        # the oracle rounds the softmax weights to the pool's dtype
        # before p @ v (the kernel keeps them in float32): bf16 pools
        # differ by that rounding, int8 pools (dequantized to float32)
        # do not
        np.testing.assert_allclose(kern, xla,
                                   atol=2e-2 if pool == "bf16" else 5e-5)


def _bf16(x):
    """x rounded to bfloat16, as the float32 array the oracle reads."""
    return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)


def _all_eqns(jaxpr):
    """A jaxpr's equations, those of its sub-jaxprs (a pallas_call's
    body, its loop, its `pl.when` branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


class TestOperandDtypes:
    """ISSUE 30: q, K and V go to the MXU at the dtype they are stored
    in, the softmax weights beside them (what the chip's MXU made of a
    float32 operand anyway: one pass, the operand rounded to bf16); the
    accumulator, the logits and the softmax state are float32. The
    parity cases above pass a float32 q; these pass the cells' own
    pair, bf16 q on bf16 (or int8) pools."""

    @staticmethod
    def _close(kern, q, kp, vp, *desc, window=None):
        """The float32 cases' atol, widened only by bf16's roundings:
        of the output (half a unit of its 8 significant bits, 2^-8 of a
        value at most) and of each softmax weight in front of p @ V
        (2^-8 of sum_j p_j |v_j| at most)."""
        ref = np_ragged_oracle(q, kp, vp, *desc, window=window)
        mass = np_ragged_oracle(q, kp, np.abs(vp), *desc, window=window)
        err = np.abs(np.asarray(kern, np.float32) - ref)
        bound = 5e-5 + 2.0 ** -8 * (np.abs(ref) + mass)
        assert (err <= bound).all(), (err.max(), (err - bound).max())

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("block_q", [1, 8])
    @pytest.mark.parametrize("g", [1, 2, 4, 16])
    def test_all_bf16_call(self, g, block_q, window, monkeypatch):
        _small_blocks(monkeypatch, 2)
        rng = np.random.default_rng(300 + 10 * g + block_q)
        kw = BLOCK_Q_CASES[block_q]
        q, kp, vp, qs, ql, cl, bt = _case(rng, g=g, pps=4, n_pages=12,
                                          block_q=block_q, **kw)
        kern = ragged_paged_attention_values(
            jnp.asarray(q, jnp.bfloat16), _pool(kp, jnp.bfloat16),
            _pool(vp, jnp.bfloat16), qs, ql, cl, bt, window=window,
            block_q=block_q, use_kernel=True)
        assert kern.dtype == jnp.bfloat16
        self._close(kern, _bf16(q), _bf16(kp), _bf16(vp), qs, ql, cl, bt,
                    window=window)

    @pytest.mark.parametrize("block_q", [1, 8])
    def test_bf16_q_on_int8_pools(self, block_q, monkeypatch):
        """int8 pages widen to q's dtype (exact) and no further."""
        _small_blocks(monkeypatch, 2)
        rng = np.random.default_rng(400 + block_q)
        kw = BLOCK_Q_CASES[block_q]
        q, kp, vp, qs, ql, cl, bt = _case(rng, g=2, pps=4, n_pages=12,
                                          block_q=block_q, **kw)
        kp_i = rng.integers(-127, 128, kp.shape).astype(np.int8)
        vp_i = rng.integers(-127, 128, vp.shape).astype(np.int8)
        ks = rng.uniform(0.002, 0.02, kp.shape[1:3]).astype(np.float32)
        vs = rng.uniform(0.002, 0.02, kp.shape[1:3]).astype(np.float32)
        kern = ragged_paged_attention_values(
            jnp.asarray(q, jnp.bfloat16), _pool(kp_i), _pool(vp_i), qs, ql,
            cl, bt, block_q=block_q, use_kernel=True,
            k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
        self._close(kern, _bf16(q),
                    kp_i.astype(np.float32) * ks[None, :, :, None],
                    vp_i.astype(np.float32) * vs[None, :, :, None],
                    qs, ql, cl, bt)

    @pytest.mark.parametrize("q_dtype,pool_dtype,operand", [
        ("bfloat16", "bfloat16", "bfloat16"),
        ("bfloat16", "int8", "bfloat16"),
        ("float32", "bfloat16", "float32"),
        ("float32", "int8", "float32"),
        ("float32", "float32", "float32"),
    ])
    def test_dots_take_the_stored_dtype(self, q_dtype, pool_dtype, operand):
        """The guard that the widening does not come back: every
        `dot_general` inside the pallas_call takes both operands at the
        wider of q's and the pools' dtype (q's for int8 pools), the
        softmax weights among them, with a float32
        `preferred_element_type`."""
        rng = np.random.default_rng(7)
        q, kp, vp, qs, ql, cl, bt = _case(rng)
        quantized = pool_dtype == "int8"
        scales = (jnp.ones(kp.shape[1:3], jnp.float32),) * 2 \
            if quantized else (None, None)
        jaxpr = jax.make_jaxpr(
            lambda q, kp, vp, ks, vs: rpa_mod._ragged_pallas(
                q, kp, vp, jnp.asarray(qs), jnp.asarray(ql),
                jnp.asarray(cl), jnp.asarray(bt), 0.25, None, 4, True,
                k_scale=ks, v_scale=vs))(
            jnp.asarray(q, q_dtype), _pool(kp, pool_dtype),
            _pool(vp, pool_dtype), *scales)
        calls = [e for e in _all_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        dots = [e for e in _all_eqns(calls[0].params["jaxpr"])
                if e.primitive.name == "dot_general"]
        hk = kp.shape[0]
        assert len(dots) == 2 * hk           # a head: q . K^T and p @ V
        for eqn in dots:
            assert [v.aval.dtype.name for v in eqn.invars] == [operand] * 2
            assert eqn.params["preferred_element_type"] == jnp.float32
            assert eqn.outvars[0].aval.dtype == jnp.float32


def _brute_walked(qs, ql, cl, n_rows, block_q, ps, window, block_pages,
                  pps):
    """Table columns the kernel has to visit, from the attention's
    definition: per q block, the table-aligned KV blocks from the one
    holding its lowest attended key to the one holding its highest."""
    walked = 0
    for qb in range(n_rows // block_q):
        pages = set()
        for s in range(len(ql)):
            for j in range(int(ql[s])):
                if (int(qs[s]) + j) // block_q != qb:
                    continue
                pos = int(cl[s]) - int(ql[s]) + j
                lo = 0 if window is None else max(0, pos - window + 1)
                pages.update((lo // ps, pos // ps))
        if pages:
            first = min(pages) // block_pages * block_pages
            end = (max(pages) // block_pages + 1) * block_pages
            walked += min(end, pps) - first
    return walked


class TestLivePageRange:
    """`ragged_pages_walked`: the kernel's loop bounds on the host,
    which `pdt_serving_attn_pages_total` counts with."""

    @pytest.mark.parametrize("seed", range(6))
    def test_walked_is_the_kernels_range(self, seed):
        rng = np.random.default_rng(seed)
        block_q = int(rng.choice([1, 4, 8]))
        ps = int(rng.choice([4, 16]))
        block_pages = int(rng.choice([1, 2, 8]))
        window = [None, 5, 40][seed % 3]
        n = int(rng.integers(2, 7))
        ql = rng.integers(0, 30, n) if block_q > 1 else rng.integers(0, 2, n)
        cl = np.where(ql > 0, ql + rng.integers(0, 200, n), 0)
        if block_q > 1:
            qs, total = pack_ragged_starts(ql, block_q=block_q)
            total += 2 * block_q                       # padding q blocks
        else:
            qs, total = np.arange(n, dtype=np.int32), n
        pps = -(-int(cl.max() + 1) // ps) + int(rng.integers(0, 3))
        got = ragged_pages_walked(qs, ql, cl, total, block_q=block_q,
                                  page_size=ps, window=window,
                                  block_pages=block_pages,
                                  table_pages=pps)
        assert got == _brute_walked(qs, ql, cl, total, block_q, ps,
                                    window, block_pages, pps)
        assert 0 <= got <= total // block_q * pps

    def test_block_pages_follow_the_shapes(self):
        # the cells: 16-token pages, 8 KV heads of 128, bf16 -> 8 pages,
        # 128 keys a trip
        assert kv_block_pages(16, 128, 8, 2, 128) == 8
        assert kv_block_pages(16, 128, 8, 2, 64) == 8
        # a head under 128 lanes is padded to them; a table narrower
        # than a block bounds it; many heads shrink it to the budget
        assert kv_block_pages(16, 64, 8, 2, 128) == 8
        assert kv_block_pages(4, 16, 2, 4, 4) == 4
        assert kv_block_pages(16, 128, 64, 2, 128) == 2

    def test_engine_counts_walked_and_skipped(self, model):
        """A short engine run with telemetry on: every ragged dispatch
        adds q blocks x table columns, split into walked and skipped."""
        eng = _engine(model)
        for p, n in JOBS:
            eng.add_request(p, n)
        steps = 0
        while eng._queue or any(r is not None for r in eng._slot_req):
            eng.step()
            steps += 1
        pages = telemetry.snapshot()["counters"][
            "pdt_serving_attn_pages_total"]
        packs = [e["attrs"] for e in telemetry.events()
                 if e["name"] == "serving.ragged_prefill"]
        decodes = [e for e in telemetry.events()
                   if e["name"] == "serving.decode_step"]
        qblocks = sum(a["t_pad"] // eng._ragged_block_q for a in packs) \
            + len(decodes) * eng.B
        assert pages['kind="walked"'] > 0 and pages['kind="skipped"'] > 0
        assert pages['kind="walked"'] + pages['kind="skipped"'] \
            == qblocks * eng.pps
        # whole KV blocks only
        assert pages['kind="walked"'] % kv_block_pages(
            4, 16, 1, 4, eng.pps) == 0


class TestScatterAndPacking:
    def test_scatter_roundtrip_and_trash_routing(self):
        rng = np.random.default_rng(7)
        hk, d, ps, n_pages = 2, 8, 4, 6
        ql = np.array([3, 2], np.int32)
        cl = np.array([7, 2], np.int32)
        qs, total = pack_ragged_starts(ql, block_q=4)
        t = total
        seq_t, pos_t = token_arrays(qs, ql, cl, t)
        k_rows = rng.standard_normal((t, hk, d)).astype(np.float32)
        v_rows = rng.standard_normal((t, hk, d)).astype(np.float32)
        bt = np.array([[1, 2, 0], [3, 0, 0]], np.int32)
        kp0 = np.zeros((n_pages, ps, hk * d), np.float32)
        kp, vp = ragged_scatter_values(
            jnp.asarray(kp0), jnp.asarray(kp0.copy()),
            jnp.asarray(k_rows), jnp.asarray(v_rows),
            jnp.asarray(bt), jnp.asarray(seq_t), jnp.asarray(pos_t))
        kp = pages_to_payload(np.asarray(kp), hk)   # (hk, P, ps, d)
        for row in range(t):
            s, pos = int(seq_t[row]), int(pos_t[row])
            if s < 0:
                continue
            pg = bt[s, pos // ps]
            np.testing.assert_array_equal(kp[:, pg, pos % ps],
                                          k_rows[row])
        # live pages hold ONLY live rows; everything else (incl. every
        # padding row) landed in trash page 0
        live = {(int(bt[int(seq_t[r])][int(pos_t[r]) // ps]),
                 int(pos_t[r]) % ps)
                for r in range(t) if seq_t[r] >= 0}
        for pg in range(1, n_pages):
            for sl in range(ps):
                if (pg, sl) not in live:
                    assert np.all(kp[:, pg, sl] == 0), (pg, sl)

    def test_pack_starts_aligned_and_token_arrays(self):
        ql = [1, 7, 5, 0]
        qs, total = pack_ragged_starts(ql, block_q=8)
        assert list(qs) == [0, 8, 16, 24]
        assert total == 24
        seq_t, pos_t = token_arrays(qs, np.asarray(ql),
                                    np.asarray([4, 7, 9, 0]), 24)
        assert seq_t[0] == 0 and pos_t[0] == 3          # decode @ ctx-1
        assert list(seq_t[8:15]) == [1] * 7
        assert list(pos_t[16:21]) == [4, 5, 6, 7, 8]    # offset 4 suffix
        assert np.all(seq_t[np.r_[1:8, 15:16, 21:24]] == -1)


class TestGatherTrim:
    """The `_paged_xla` satellite: the gather is bounded to the
    block-table prefix actually referenced when context lengths are
    concrete, and the trim never changes results."""

    def test_gather_bounded_to_referenced_prefix(self):
        kp = jnp.zeros((33, 4, 2 * 8))
        bt = jnp.asarray(np.zeros((3, 8), np.int32))
        ctx = np.array([5, 9, 2], np.int32)               # 3 pages max
        kc, _ = gather_pages(kp, kp, bt, 2, context_lens=ctx)
        assert kc.shape == (3, 3 * 4, 2, 8)               # trimmed
        kc_full, _ = gather_pages(kp, kp, bt, 2, pages_bound=8)
        assert kc_full.shape[1] == 8 * 4                  # full on demand
        # traced context lengths cannot trim (shape must be static)
        shape = jax.eval_shape(
            lambda c: gather_pages(kp, kp, bt, 2, context_lens=c)[0],
            jax.ShapeDtypeStruct((3,), jnp.int32)).shape
        assert shape[1] == 8 * 4

    def test_trim_matches_full_gather_attention(self):
        rng = np.random.default_rng(8)
        q, kp, vp, qs, ql, cl, bt = _case(rng, pps=8, n_pages=40)
        trimmed = np.asarray(ragged_paged_attention_values(
            jnp.asarray(q), _pool(kp), _pool(vp),
            qs, ql, cl, bt, use_kernel=False))
        ref = np_ragged_oracle(q, kp, vp, qs, ql, cl, bt)
        np.testing.assert_allclose(trimmed, ref, atol=2e-5)


# -- engine integration ------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=64)
    paddle.seed(7)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    return ContinuousBatchingEngine(model, **kw)


JOBS = [([5, 4, 3, 2, 6, 7], 8), ([9, 1, 2], 6), ([7, 7, 1, 2], 5)]


def _drain(eng):
    reqs = {}
    while eng._queue or any(r is not None for r in eng._slot_req):
        for r in eng.step():
            reqs[r.rid] = r
    return reqs


def _generate(model, jobs=JOBS):
    """The oracle outside the engine: each request alone through
    `generate()`'s dense-tuple cache (models/generation.py)."""
    outs = []
    for prompt, n in jobs:
        toks, _ = model.generate(
            paddle.to_tensor(np.asarray(prompt, np.int32)[None]),
            max_new_tokens=n, decode_strategy="greedy_search")
        outs.append([int(x) for x in np.asarray(toks._value).ravel()[:n]])
    return outs


class TestRaggedEngineParity:
    """The ISSUE 6 acceptance contract: the engine's greedy streams
    are IDENTICAL to `generate()`'s per request — in the clean run,
    through a forced preemption, and through a SIGKILL replica
    failover (the PR-4/5 chaos drills as the kernel's regression
    harness)."""

    def _run(self, model, jobs=JOBS, fault=None, **kw):
        eng = _engine(model, **kw)
        rids = [eng.add_request(p, n) for p, n in jobs]
        if fault is None:
            reqs = _drain(eng)
        else:
            with FaultInjector() as fi:
                fi.arm(*fault[:1], **fault[1])
                reqs = _drain(eng)
        return eng, rids, reqs

    def test_streams_identical_clean(self, model):
        _, rids, reqs = self._run(model)
        assert all(reqs[r].status == RequestStatus.FINISHED
                   for r in rids)
        assert [reqs[r].output for r in rids] == _generate(model)

    def test_streams_identical_through_preemption(self, model):
        """Forced pool exhaustion mid-decode: the victim requeues and
        re-prefills its prompt and what it had generated — final
        streams equal the oracle's, which saw no fault."""
        eng, rids, reqs = self._run(
            model, jobs=JOBS[:2],
            fault=("serving.alloc_page",
                   dict(nth=4, exc=PoolExhausted)))
        assert eng.num_preemptions == 1
        assert all(reqs[r].status == RequestStatus.FINISHED
                   for r in rids)
        assert [reqs[r].output for r in rids] == _generate(model, JOBS[:2])

    def test_streams_identical_through_sigkill_failover(self, model):
        """A replica SIGKILL mid-decode with zero-loss failover: fleet
        outputs equal the oracle's."""
        from paddle_tpu.serving import ServingRouter

        class Clock:
            def __init__(self):
                self.t = 0.0

            def advance(self, dt):
                self.t += dt

            def __call__(self):
                return self.t

        clock = Clock()
        router = ServingRouter(
            lambda i: _engine(model, clock=clock),
            num_replicas=3, policy="round_robin", clock=clock,
            sleep=clock.advance, page_size=4)
        ids = [router.submit(p, n) for p, n in JOBS]
        router.step()
        router.step()                            # mid-decode
        router.kill_replica(1)
        out = router.run()
        assert router.num_failovers == 1
        assert [out[i] for i in ids] == _generate(model)

    def test_one_dispatch_per_admission_round(self, model):
        """Admitting N ragged prompts costs ONE dispatch: the first
        step's admission produces a single serving.ragged_prefill span
        carrying every admitted request_id, and the only programs
        compiled are that admission's and the decode step."""
        eng = _engine(model, max_batch_size=3)
        rids = [eng.add_request(p, n) for p, n in JOBS]
        eng.step()
        spans = [e for e in telemetry.events()
                 if e["name"] == "serving.ragged_prefill"]
        assert len(spans) == 1            # N admissions, ONE dispatch
        batch_rids = set(spans[0]["attrs"]["rids"])
        assert batch_rids == {str(r) for r in rids}
        eng.run()
        assert len(eng._ragged_jits) == 1
        compiled = telemetry.snapshot()["counters"][
            "pdt_jit_compiles_total"]
        assert set(compiled) == {'family="ragged"', 'family="decode"'}

    def test_prefix_cache_rides_ragged_admission(self, model):
        """A prefix-cache hit admits through the packed suffix path:
        hits are counted and outputs equal the cache-off engine."""
        sys_prompt = [3, 9, 2, 7, 5, 1, 4, 8]          # 2 full pages
        jobs = [(sys_prompt + [11], 6), (sys_prompt + [13, 14], 6)]
        outs = {}
        for caching in (False, True):
            eng = _engine(model, max_batch_size=1,
                          enable_prefix_caching=caching)
            rids = [eng.add_request(p, n) for p, n in jobs]
            reqs = _drain(eng)
            outs[caching] = [reqs[r].output for r in rids]
        assert outs[True] == outs[False]
        assert eng.prefix_hits >= 1
        assert eng.prefix_tokens_reused >= 4

    def test_chunked_prefill_spills_across_dispatches(self, model):
        """prefill_chunk bounds the ragged dispatch: a long prompt
        spills into chunk-continuation pieces, and the stream equals
        the unchunked engine's."""
        prompt = list(np.arange(1, 30) % 60 + 1)
        ref_eng, _, ref_reqs = self._run(model, jobs=[(prompt, 6)])
        eng = _engine(model, prefill_chunk=8)
        rid = eng.add_request(prompt, 6)
        reqs = _drain(eng)
        assert reqs[rid].output == list(ref_reqs.values())[0].output
        # the admission really split: > 1 ragged_prefill span for one
        # admitted request
        spans = {e["seq"] for e in telemetry.events()
                 if e["name"] == "serving.ragged_prefill"}
        assert len(spans) >= 2

    def test_admission_program_gather_is_bounded(self, model):
        """The traced admission program cannot use the concrete trim
        (context lengths are tracers), so the engine threads a STATIC
        pages_bound — short prompts must compile a program whose
        gather is O(their pages), not O(pps)."""
        eng = _engine(model, max_batch_size=2)      # pps = 64/4 = 16
        eng.add_request([1, 2, 3], 2)
        eng.step()
        keys = list(eng._ragged_jits)
        assert keys, "no ragged admission program was built"
        t_pad, bound = keys[0]
        assert bound == 1                            # ceil(3/4) -> pow2
        assert bound < eng.pps

    @pytest.mark.parametrize("kw,takes", [
        (dict(kv_layout="dense"), False),
        (dict(attention_impl="legacy"), False),
        (dict(attention_impl="fused"), False),
        (dict(kv_layout="paged", attention_impl="ragged"), True)],
        ids=["dense", "legacy", "fused", "paged+ragged"])
    def test_layout_and_attention_keywords_are_checked(self, model, kw,
                                                       takes):
        """The two keywords the benchmark's configurations still pass
        take one value each; anything else is refused by name."""
        with (contextlib.nullcontext() if takes else
              pytest.raises(ValueError, match="removed in PR 29")):
            _engine(model, **kw)

    def test_sampling_seeded_reproducible_on_ragged(self, model):
        def run(seed, **kw):
            eng = _engine(model, seed=seed, **kw)
            rid = eng.add_request([5, 42, 7, 11], 8)
            return _drain(eng)[rid].output

        s1 = run(3, do_sample=True, temperature=0.8, top_k=20)
        s2 = run(3, do_sample=True, temperature=0.8, top_k=20)
        assert s1 == s2 and len(s1) == 8
        tiny_p = run(9, do_sample=True, top_p=1e-9)
        greedy = run(0)
        assert tiny_p == greedy
