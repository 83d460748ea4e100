"""Phi-4-mini-flash (models/phi4flash.py) against its plain reference
(benchmark/reference/phi4flash.py), and the engine's page GROUPS
(models/serving.py, models/cache_spec.py): window layers, a full layer
and layers that share the full layer's keys and values, beside Mamba-1
state, in one cache manager. Tiny widths, CPU, float32 unless a case
says otherwise: a window (8) shorter than the prompts, a page (4)
shorter than the window, chunks (8) shorter than the prompts.
Parameters come from the model's own initialiser but for the biases,
`A_log` and the step's bias, which are drawn so that a state lives for
tens of tokens: a lost state or a lost page is then far outside every
tolerance here."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu.models import phi4flash as pf                 # noqa: E402
from paddle_tpu.models.cache_spec import (KVSpec, SharedKVSpec,  # noqa: E402
                                          StateSpec)
from paddle_tpu.models.llama import RaggedKVCacheView          # noqa: E402
from paddle_tpu.models.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.ops import ragged_paged_attention as rpa       # noqa: E402
from benchmark.reference import phi4flash as ref               # noqa: E402
from benchmark.runners.serve import _LogitRecorder             # noqa: E402

# float32 against float32: what is left is the order of the sums
TIGHT = 2e-4        # of the reference logits' standard deviation


def _model(seed=0, **kw):
    paddle.seed(seed)
    cfg = pf.Phi4FlashConfig.tiny(**kw)
    model = pf.Phi4FlashForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("dt_proj.bias"):       # dt about 0.05
            p._value = jnp.asarray(rng.normal(-3, 0.5, p._value.shape),
                                   p._value.dtype)
        elif name.endswith(".bias") or name.endswith("A_log"):
            p._value = jnp.asarray(rng.normal(0, 0.3, p._value.shape),
                                   p._value.dtype)
    if cfg.dtype != "float32":
        model.to(dtype=cfg.dtype)
    model.eval()
    return model, cfg


def _weights(model):
    return {n: p._value for n, p in model.named_parameters()}


def _ref_logits(model, cfg, ids):
    return ref.forward_logits(_weights(model), dict(vars(cfg)), ids)


def _err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _engine(model, **kw):
    base = dict(max_batch_size=3, max_seq_len=64, page_size=4,
                prefill_chunk=8, prompt_pad=8)
    base.update(kw)
    eng = ContinuousBatchingEngine(model, **base)
    rec = _LogitRecorder()       # every decode step's sampled-row logits
    eng.attach_sentry(rec)
    return eng, rec


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _decode_errs(model, cfg, rec, prompts, rids, out, steps, first=0):
    errs = []
    for slot, (rid, p) in enumerate(zip(rids, prompts)):
        toks = out[rid]
        want = _ref_logits(model, cfg, p + toks[:steps])
        got = np.stack([r[slot] for r in rec.rows[first:first + steps]])
        errs.append(_err(got, want[len(p):len(p) + steps]))
        assert toks[0] == int(want[len(p) - 1].argmax())
    return errs


# -- the model against the reference -----------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", TIGHT),
                                       ("bfloat16", 0.1)])
def test_forward_matches_the_reference(dtype, tol):
    """Whole sequences from nothing: two of them packed on the spot, 21
    tokens each at a scan chunk of 4 (padding rows inside the scan),
    longer than the window."""
    model, cfg = _model(dtype=dtype)
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 21))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value, np.float32)
    for b in range(2):
        assert _err(got[b], _ref_logits(model, cfg, ids[b])) < tol


@pytest.mark.parametrize("rows", [slice(3, 7), slice(0, None),
                                  slice(30, 40), 5])
def test_the_reference_reads_the_rows_asked_for(rows, monkeypatch):
    """The reference pads a sequence to `SEQ_BLOCK` and runs its head
    over the blocks that hold the rows read: a slice of rows, in one
    block of the head or over several, is those rows of the whole
    array, and the padding is no row of it."""
    monkeypatch.setattr(ref, "HEAD_BLOCK", 16)
    model, cfg = _model()
    ids = np.random.default_rng(2).integers(1, cfg.vocab_size, 37)
    logits = _ref_logits(model, cfg, ids)
    assert logits.shape == (37, cfg.vocab_size) and len(logits) == 37
    np.testing.assert_array_equal(logits[rows], np.asarray(logits)[rows])


def test_layers_and_what_each_keeps_at_the_published_depth():
    """32 layers: 9 state layers, 8 window layers, the full layer and 7
    that read it; the 16 attention layers hold 9 pools in two groups."""
    model, cfg = _model(num_hidden_layers=32)
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds == [ref.kind(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    spec = model.cache_spec()
    assert sum(isinstance(s, StateSpec) for s in spec) == 9
    assert spec[1] == KVSpec(2, 16, window=8) and spec[17] == KVSpec(2, 16)
    assert [i for i, s in enumerate(spec) if s == SharedKVSpec(17)] \
        == list(range(19, 32, 2))
    assert [i for i, s in enumerate(spec) if s is None] \
        == list(range(18, 32, 2))
    assert model.rows_leave_after() == 17
    eng = ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                   page_size=4, prefill_chunk=8)
    full, win = eng._groups
    assert (full.name, win.name) == ("full", "w8")
    assert len(eng._kv) == 9 and len(full.pools) == 1 \
        and len(win.pools) == 8
    assert (full.readers_before, full.readers_after) == (1, 7)
    assert (win.readers_before, win.readers_after) == (8, 0)
    # `num_pages` sizes the full group; the window group's pool follows
    # from slots, window, page and chunk: 2 x (2 + 2) + (8 + 8) / 4 + 1
    assert full.num_pages == 2 * 16 + 1 and win.num_pages == 13
    assert [e[0].shape[0] for e in eng._kv].count(13) == 8
    # any even depth gives a model: the rehearsal's two layers are the
    # memory layer and the full layer
    assert [ref.kind(i, 2) for i in range(2)] == ["mamba", "full"]
    assert [pf.Phi4FlashConfig.tiny(num_hidden_layers=6).kind(i)
            for i in range(6)] == ["mamba", "window", "mamba", "full",
                                   "gmu", "cross"]


# -- (b) differential attention through the kernel the repo has ----------
@pytest.mark.parametrize("window", [None, 5])
def test_pair_packed_call_equals_the_four_softmax_definition(window):
    """Queries zero in the half that is not theirs against stored rows
    read as heads of twice the size: the call returns each of a pair's
    two softmax maps applied to the whole value."""
    t, h, hk, d, ps = 11, 4, 2, 8, 4
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(t, n, d)), jnp.float32)
               for n in (h, hk, hk))
    bt = jnp.arange(1, 5, dtype=jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)
    kp, vp = rpa.ragged_scatter_values(
        jnp.zeros((5, ps, hk * d)), jnp.zeros((5, ps, hk * d)),
        k.reshape(t, hk // 2, 2 * d), v.reshape(t, hk // 2, 2 * d), bt,
        jnp.zeros(t, jnp.int32), pos)
    for use_kernel in (False, True):
        got = rpa.ragged_paged_attention_values(
            pf.pair_queries(q), kp, vp, jnp.zeros(1, jnp.int32),
            jnp.full(1, t, jnp.int32), jnp.full(1, t, jnp.int32), bt,
            scale=d ** -0.5, window=window, block_q=1,
            use_kernel=use_kernel, pages_bound=4)
        ok = pos[None, :] <= pos[:, None]
        if window is not None:
            ok &= pos[None, :] > pos[:, None] - window
        for head in range(h):           # head 2p + j - 1 is (p, j)
            pair, j = head // 2, head % 2
            c = pair // ((h // 2) // (hk // 2))
            s = q[:, head] @ k[:, 2 * c + j].T / np.sqrt(d)
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            want = p @ jnp.concatenate([v[:, 2 * c], v[:, 2 * c + 1]], -1)
            np.testing.assert_allclose(got[:, head], want, atol=2e-5)


# -- (a) the engine against the reference -------------------------------
def test_engine_prefill_in_chunks_then_decode_equals_the_reference():
    """Prompts of 27, 9 and 30 through chunks of 8 (continuation
    pieces, pieces of two sequences in one dispatch, padding rows), a
    window of 8 over pages of 4, then 8 decode steps through state, the
    window group, the full group and the layers that share it: the
    logits are the reference's full forward's."""
    model, cfg = _model()
    eng, rec = _engine(model)
    prompts = _prompts(cfg, (27, 9, 30))
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    out = eng.run()
    assert not eng.num_failures, eng.last_failure
    errs = _decode_errs(model, cfg, rec, prompts, rids, out, 8)
    assert max(errs) < TIGHT, errs
    eng.check_invariants()
    assert all(sorted(g.free) == list(range(1, g.num_pages))
               for g in eng._groups)


# -- (e) rows that sample nothing leave after the full layer ------------
def test_sampled_rows_logits_are_the_same_with_and_without_the_gather():
    """An admission program hands the forward pass `sample_rows`: the
    rows go on alone past layer `rows_leave_after()` and their logits
    are what the whole batch gave them; the engine's admission program
    asks for it and its decode program does not."""
    model, cfg = _model()
    eng, _ = _engine(model)
    prompts = _prompts(cfg, (11, 6), seed=3)
    pk = rpa.pack_ragged_batch(
        [{"seq": s, "tokens": p, "offset": 0, "sample": True}
         for s, p in enumerate(prompts)], eng.B, block_q=8, pad_to=8)
    for g in eng._groups:
        for s, p in enumerate(prompts):
            for j in range(-(-len(p) // 4)):
                g.bt[s, j] = 1 + 3 * s + j

    def views():
        out, pools, states = [], iter(eng._kv), iter(eng._state)
        for spec in eng._layer_spec:
            if isinstance(spec, KVSpec):
                k, v = next(pools)
                g = eng._groups[spec.window is not None]
                out.append(RaggedKVCacheView(
                    k, v, g.bt, pk["token_seq"], pk["positions"],
                    pk["query_start"], pk["query_len"], pk["context_len"],
                    8, 4))
            elif isinstance(spec, StateSpec):
                from paddle_tpu.models.cache_spec import RaggedStateView
                out.append(RaggedStateView(
                    next(states), pk["token_seq"], pk["query_start"],
                    pk["query_len"], pk["context_len"]))
            else:
                out.append(None)
        return out

    ids = paddle.to_tensor(pk["ids"][None])
    with paddle.no_grad():
        whole, _ = model(ids, past_key_values=views(), use_cache=True)
        some, _ = model(ids, past_key_values=views(), use_cache=True,
                        sample_rows=jnp.asarray(pk["sample_rows"]))
    assert whole.shape[1] == pk["t_pad"] and some.shape[1] == eng.B
    rows = pk["sample_rows"][:2]
    np.testing.assert_allclose(some._value[0, :2], whole._value[0, rows],
                               atol=1e-5)
    for p, row in zip(prompts, rows):
        assert _err(whole._value[0, row],
                    _ref_logits(model, cfg, p)[-1:]) < TIGHT
    # the step programs: admission gathers, decode does not
    text = {bq: str(jax.make_jaxpr(eng._build_ragged_step(bq, 4))(
        *_step_args(eng, 8 if bq == 8 else eng.B))) for bq in (8, 1)}
    assert "sampled_rows" not in text[1]
    assert model.rows_leave_after() == 5 and eng._leave_after == 5


def _step_args(eng, t):
    def i32(*s):
        return jnp.zeros(s, jnp.int32)
    return (eng._pv(), eng._bv(), eng._cache(), i32(t), i32(t), i32(t),
            i32(eng.B), i32(eng.B), i32(eng.B), eng._tables(), i32(eng.B),
            jax.random.PRNGKey(0))


# -- (d) preemption, re-admission, a recycled slot -----------------------
def test_preemption_and_a_recycled_slot_give_the_undisturbed_tokens():
    model, cfg = _model()
    prompts = _prompts(cfg, (21, 13), seed=9)

    def ref_tokens(p, toks):
        want = _ref_logits(model, cfg, p + toks[:-1])
        return [int(t) for t in want[len(p) - 1:].argmax(-1)]

    eng, rec = _engine(model, max_batch_size=2)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    for _ in range(3):
        eng.step()
    eng._preempt_youngest([])
    assert eng.num_preemptions == 1
    eng.check_invariants()
    done = {}
    while len(done) < 2:
        for r in eng.step():
            done[r.rid] = list(r.output)
    for rid, p in zip(rids, prompts):
        assert len(done[rid]) == 8 and done[rid] == ref_tokens(p, done[rid])
    eng.check_invariants()
    # the slots are recycled: the next request finds the first ones'
    # state and pages' leftovers in slot 0 and must not see them
    n0 = len(rec.rows)
    assert float(jnp.abs(eng._state[0][1][0]).max()) > 0    # left behind
    second = _prompts(cfg, (13,), seed=7)[0]
    rid = eng.add_request(second, max_new_tokens=6)
    out = eng.run()
    want = _ref_logits(model, cfg, second + out[rid][:5])
    got = np.stack([r[0] for r in rec.rows[n0:n0 + 5]])
    assert _err(got, want[13:18]) < TIGHT


# -- the controls: what a lost state and a lost window page cost ---------
@pytest.mark.parametrize("lose", ["state", "window_page", "full_page"])
def test_a_lost_state_or_page_fails_the_comparison(lose):
    """The comparison FAILS when, before the first decode step, the
    slot's state is zeroed, the window group's oldest live page is
    trash-routed, or a page of the shared full layer is."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=1)
    prompt = _prompts(cfg, (27,))[0]
    rid = eng.add_request(prompt, max_new_tokens=5)
    decode = eng._decode

    def lossy(finished):
        if not rec.rows:
            full, win = eng._groups
            if lose == "state":
                eng._state = [tuple(jnp.zeros_like(a) for a in st)
                              for st in eng._state]
            elif lose == "window_page":
                win.bt[0, int(win.slot_freed[0])] = 0
            else:
                full.bt[0, 2] = 0
        return decode(finished)

    eng._decode = lossy
    out = eng.run()
    want = _ref_logits(model, cfg, prompt + out[rid][:4])
    got = np.stack([r[0] for r in rec.rows[:4]])
    assert _err(got, want[27:31]) > 100 * TIGHT


