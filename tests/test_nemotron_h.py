"""Nemotron-H (models/nemotron_h.py) against its plain reference
(benchmark/reference/nemotron_h.py), and the engine's contract with a
model whose cache is mostly not pages (models/cache_spec.py): state
beside paged KV, an expert layer told which experts it holds. Tiny
widths, CPU, float32 unless a case says otherwise; parameters come from
the model's OWN initialiser (A in 1-16, dt in 0.001-0.1), under which a
lost or stale state is far outside every tolerance here."""
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
import paddle_tpu.observability as telemetry                  # noqa: E402
from paddle_tpu.models import nemotron_h as nh                # noqa: E402
from paddle_tpu.models.cache_spec import (KVSpec, RaggedStateView,  # noqa: E402
                                          ReportSpec, StateSpec)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.serving import (ContinuousBatchingEngine,  # noqa: E402
                                       EngineInvariantError,
                                       QuantServingConfig, SpecConfig)
from benchmark.reference import nemotron_h as ref              # noqa: E402
from benchmark.runners.serve import _LogitRecorder             # noqa: E402

# float32 against float32: what is left is the order of the sums
TIGHT = 2e-4        # of the reference logits' standard deviation


def _model(seed=0, **kw):
    paddle.seed(seed)
    cfg = nh.NemotronHConfig.tiny(**kw)
    model = nh.NemotronHForCausalLM(cfg)
    if cfg.dtype != "float32":
        model.to(dtype=cfg.dtype)
    model.eval()
    return model, cfg


def _sizes(cfg):
    return dict(vars(cfg))


def _weights(model):
    return {n: p._value for n, p in model.named_parameters()}


def _ref_logits(model, cfg, ids):
    return ref.forward_logits(_weights(model), _sizes(cfg), ids)


def _err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _engine(model, **kw):
    base = dict(max_batch_size=3, max_seq_len=128, page_size=8,
                prefill_chunk=16, prompt_pad=16)
    base.update(kw)
    eng = ContinuousBatchingEngine(model, **base)
    rec = _LogitRecorder()       # every decode step's sampled-row logits
    eng.attach_sentry(rec)
    return eng, rec


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


def _decode_errs(model, cfg, eng, rec, prompts, rids, out, steps):
    """Per request: the engine's decode logits (step j consumed
    generated token j) against the reference's full forward."""
    errs = []
    for slot, (rid, p) in enumerate(zip(rids, prompts)):
        toks = out[rid]
        want = _ref_logits(model, cfg, p + toks[:steps])
        got = np.stack([r[slot] for r in rec.rows[:steps]])
        errs.append(_err(got, want[len(p):len(p) + steps]))
        # the prefill's sampled token is the reference's too
        assert toks[0] == int(want[len(p) - 1].argmax())
    return errs


# -- the model against the reference -----------------------------------
@pytest.mark.parametrize("dtype,kw,tol", [
    ("float32", {}, TIGHT),
    # bf16 keeps 8 bits: 0.04 measured. Every expert is chosen, so no
    # choice can flip between program and reference: at these widths
    # one flipped expert of 4 moves a token's logits by its whole spread
    ("bfloat16", {"num_experts_per_tok": 16}, 0.1)])
def test_forward_matches_the_reference(dtype, kw, tol):
    """Whole sequences from nothing: two of them packed on the spot,
    21 tokens each at a scan chunk of 8 (padding rows inside the
    scan)."""
    model, cfg = _model(dtype=dtype, **kw)
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 21))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value, np.float32)
    for b in range(2):
        assert _err(got[b], _ref_logits(model, cfg, ids[b])) < tol


def test_pattern_is_cut_with_the_depth():
    cfg = nh.NemotronHConfig.tiny(num_hidden_layers=2)
    assert cfg.pattern == "ME"
    assert ref.block_kinds(_sizes(cfg)) == "ME"
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.NemotronHConfig.tiny(num_hidden_layers=6)
    with pytest.raises(ValueError, match="routed experts"):
        nh.NemotronHConfig.tiny(experts_held=8, expert_offset=12)


def test_cache_spec_states_what_each_block_keeps():
    model, cfg = _model()
    spec = model.cache_spec()
    assert [type(s) for s in spec] == [StateSpec, ReportSpec, KVSpec,
                                       ReportSpec, StateSpec]
    assert spec[1].row_record == (cfg.num_experts_per_tok,)
    assert [kind for _, kind in spec[1].counters] == [
        "local", "remote", "hit", "idle", "first", "further"]
    assert spec[2] == KVSpec(2, 16)
    assert spec[0].shapes == ((3, 32 + 2 * 2 * 16), (4, 8, 16))
    assert spec[0].dtypes == ("float32", "float32")
    assert spec[0].nbytes() == 4 * (3 * 96 + 4 * 8 * 16)


def test_chunked_scan_equals_the_recurrence():
    """One Mamba mixer: 37 tokens through the chunked scan (chunk 8)
    in one piece, against the same tokens one at a time through the
    one-step recurrence, on the outputs and on the final state."""
    model, cfg = _model()
    mixer = model.model.layers[0].mixer
    x = jax.random.normal(jax.random.key(3), (1, 37, cfg.hidden_size))
    spec = mixer.cache_spec()
    with paddle.no_grad():
        whole, view = mixer(paddle.to_tensor(x),
                            RaggedStateView.fresh(spec, 1, 37))
        arrays = [jnp.zeros((1,) + s, d)
                  for s, d in zip(spec.shapes, spec.dtypes)]
        steps = []
        for t in range(37):
            one = RaggedStateView(arrays, [0], [0], [1], [t + 1],
                                  one_token=True)
            y, one = mixer(paddle.to_tensor(x[:, t:t + 1]), one)
            arrays = one.arrays
            steps.append(np.asarray(y._value[0, 0]))
    np.testing.assert_allclose(np.asarray(whole._value[0]),
                               np.stack(steps), rtol=2e-4, atol=2e-5)
    for a, b in zip(view.arrays, arrays):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_scan_takes_pieces_in_any_packing():
    """Three pieces in one packed batch: a continuation that starts
    mid-chunk, a fresh sequence, and a slot that is not in the batch
    (its state comes back untouched); padding rows between them."""
    model, cfg = _model()
    mixer = model.model.layers[0].mixer
    spec = mixer.cache_spec()
    x = jax.random.normal(jax.random.key(4), (1, 40, cfg.hidden_size))
    with paddle.no_grad():
        # slot 1's whole sequence is rows 0..19; its first 9 come first
        _, head = mixer(paddle.to_tensor(x[:, :9]),
                        RaggedStateView.fresh(spec, 1, 9))
        want1, end1 = mixer(paddle.to_tensor(x[:, :20]),
                            RaggedStateView.fresh(spec, 1, 20))
        want2, end2 = mixer(paddle.to_tensor(x[:, 20:33]),
                            RaggedStateView.fresh(spec, 1, 13))
        stored = [jnp.concatenate([jnp.full_like(a, 7.0), a,
                                   jnp.full_like(a, 5.0)])
                  for a in head.arrays]
        # packed: rows 0..12 slot 2 (fresh, 13 tokens), 13..15 padding,
        # rows 16..26 slot 1 (continuation of 11 from context 9)
        packed = jnp.concatenate([x[:, 20:33], jnp.zeros((1, 3, x.shape[2])),
                                  x[:, 9:20], jnp.zeros((1, 5, x.shape[2]))],
                                 axis=1)
        seq = [2] * 13 + [-1] * 3 + [1] * 11 + [-1] * 5
        got, new = mixer(paddle.to_tensor(packed), RaggedStateView(
            stored, seq, [0, 16, 0], [0, 11, 13], [0, 20, 13]))
    got = np.asarray(got._value[0])
    np.testing.assert_allclose(got[:13], np.asarray(want2._value[0]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[16:27], np.asarray(want1._value[0, 9:]),
                               rtol=2e-4, atol=2e-5)
    for a, e1, e2 in zip(new.arrays, end1.arrays, end2.arrays):
        assert np.all(np.asarray(a[0]) == 7.0)      # not in the batch
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(e1[0]),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(a[2]), np.asarray(e2[0]),
                                   rtol=2e-4, atol=2e-5)


# -- the shares of an expert layer add up --------------------------------
def test_four_shares_add_up_to_the_uncut_layer():
    """Four programs that each hold 4 of a layer's 16 experts: what
    they compute for their own experts, with the shared expert (which
    every chip computes alike) counted once, is the uncut reference's
    layer."""
    model, cfg = _model()
    layer = model.model.layers[1]
    h = jax.random.normal(jax.random.key(5), (23, cfg.hidden_size))
    w = {n: p._value for n, p in layer.named_parameters()}
    whole = np.asarray(ref._experts(
        h, w, top_k=cfg.num_experts_per_tok, offset=0,
        scale=cfg.routed_scaling_factor, renorm=True,
        eps=cfg.layer_norm_epsilon)[0]) - np.asarray(h)
    with paddle.no_grad():
        a = layer.norm(paddle.to_tensor(h[None]))
        av = np.asarray(a._value[0])
        shared = np.square(np.maximum(
            av @ np.asarray(w["mixer.shared_experts.up_proj.weight"]), 0)) \
            @ np.asarray(w["mixer.shared_experts.down_proj.weight"])
        total = np.zeros_like(whole)
        counters = []
        for share in range(4):
            part_cfg = nh.NemotronHConfig.tiny(experts_held=4,
                                               expert_offset=4 * share)
            part = nh.NemotronHExperts(part_cfg)
            for (name, p), (_, q) in zip(part.named_parameters(),
                                         layer.mixer.named_parameters()):
                p._value = q._value[4 * share:4 * share + 4] \
                    if name.startswith("experts.") else q._value
            out, (c, chosen) = part(a, jnp.ones((23,), bool))
            total += np.asarray(out._value[0]) - shared
            counters.append(np.asarray(c))
            assert chosen.shape == (23, cfg.num_experts_per_tok)
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4,
                               atol=2e-5 * float(np.abs(whole).max()))
    # every assignment is local to exactly one share
    counters = np.stack(counters)
    assert counters[:, 0].sum() == 23 * cfg.num_experts_per_tok
    assert (counters[:, :2].sum(1) == 23 * cfg.num_experts_per_tok).all()
    assert (counters[:, 2:4].sum(1) == 4).all()
    assert (counters[:, 4] == counters[:, 2]).all()


# -- through the engine ----------------------------------------------------
def test_engine_prefill_in_chunks_then_decode_equals_the_reference():
    """A prompt of 27 through chunks of 16 (a continuation piece), one
    of 9 packed with it (padding rows), then 8 decode steps through
    state and pages: the logits are the reference's full forward's."""
    model, cfg = _model(experts_held=8, expert_offset=4)
    eng, rec = _engine(model)
    prompts = _prompts(cfg, (27, 9))
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    out = eng.run()
    assert not eng.num_failures, eng.last_failure
    errs = _decode_errs(model, cfg, eng, rec, prompts, rids, out, 8)
    assert max(errs) < TIGHT, errs
    eng.check_invariants()
    assert not eng._state_live.any()


def test_zeroed_state_fails_the_comparison():
    """The same comparison FAILS when the state a continuation chunk
    starts from is lost: the tolerance is not so wide that a stale or
    zero state sits inside it."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=1)
    dispatch, calls = eng._dispatch_ragged, []

    def lossy(batch, finished):
        freed = dispatch(batch, finished)
        if not calls:                       # after the FIRST chunk only
            eng._state = [tuple(jnp.zeros_like(a) for a in st)
                          for st in eng._state]
        calls.append(len(batch))
        return freed

    eng._dispatch_ragged = lossy
    prompts = _prompts(cfg, (27,))
    rid = eng.add_request(prompts[0], max_new_tokens=5)
    out = eng.run()
    assert len(calls) == 2
    want = _ref_logits(model, cfg, prompts[0] + out[rid][:4])
    got = np.stack([r[0] for r in rec.rows[:4]])
    assert _err(got, want[27:31]) > 100 * TIGHT


def test_recycled_slot_starts_from_zero_state():
    """One slot, two requests one after the other: the second finds
    the first's state in the arrays and must not see it."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=1)
    first, second = _prompts(cfg, (19, 11), seed=7)
    eng.add_request(first, max_new_tokens=6)
    eng.run()
    n0 = len(rec.rows)
    assert float(jnp.abs(eng._state[0][1]).max()) > 0   # left behind
    rid = eng.add_request(second, max_new_tokens=6)
    out = eng.run()
    want = _ref_logits(model, cfg, second + out[rid][:5])
    got = np.stack([r[0] for r in rec.rows[n0:n0 + 5]])
    assert _err(got, want[11:16]) < TIGHT


def test_preemption_and_reprefill_give_the_same_logits():
    """A preempted request's state is recomputed by its re-prefill
    (prompt + what it generated): decoding goes on as if nothing
    had happened."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=2)
    prompts = _prompts(cfg, (21, 13), seed=9)
    rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    for _ in range(4):
        eng.step()
    eng._preempt_youngest([])
    assert eng.num_preemptions == 1
    done = {}
    while len(done) < 2:
        for r in eng.step():
            done[r.rid] = list(r.output)
    for rid, p in zip(rids, prompts):
        toks = done[rid]
        assert len(toks) == 12
        want = _ref_logits(model, cfg, p + toks[:-1])
        assert toks == [int(t) for t in
                        want[len(p) - 1:].argmax(-1)]
    eng.check_invariants()


def test_state_accounting_is_an_invariant():
    model, cfg = _model()
    eng, _ = _engine(model)
    eng.add_request(_prompts(cfg, (9,))[0], max_new_tokens=4)
    eng.step()
    eng.check_invariants()
    info = eng.cache_memory_info()
    assert info["state_bytes"] == 3 * sum(s.nbytes()
                                          for s in eng._state_spec)
    eng._state_live[0] = False
    with pytest.raises(EngineInvariantError, match="state live"):
        eng.check_invariants()
    eng._state_live[0] = True
    eng._state[0] = tuple(a.astype(jnp.bfloat16) for a in eng._state[0])
    with pytest.raises(EngineInvariantError, match="state layer 0"):
        eng.check_invariants()


def test_expert_counters_and_state_gauges(monkeypatch):
    model, cfg = _model(experts_held=8, expert_offset=4)
    monkeypatch.setenv("PDT_TELEMETRY", "1")     # as conftest's fixture
    telemetry.reset()
    eng, _ = _engine(model, max_batch_size=2)
    for p in _prompts(cfg, (10, 6)):
        eng.add_request(p, max_new_tokens=4)
    eng.run()
    snap = telemetry.snapshot()
    a = snap["counters"]["pdt_serving_moe_assignments_total"]
    e = snap["counters"]["pdt_serving_moe_experts_total"]
    # 16 prompt tokens and 2 x 3 decode tokens, 4 choices, 2 layers
    assert a['kind="local"'] + a['kind="remote"'] == (16 + 6) * 4 * 2
    assert 0 < a['kind="local"'] < a['kind="remote"']
    # one admission and three decode dispatches of 8 held x 2 layers
    assert e['kind="hit"'] + e['kind="idle"'] == 4 * 8 * 2
    # the engine adds the specification's every entry: a hit expert's
    # first row tile, and none further (a handful of rows on 16-row tiles)
    tiles = snap["counters"]["pdt_serving_moe_row_tiles_total"]
    assert tiles['kind="first"'] == e['kind="hit"']
    assert tiles.get('kind="further"', 0) == 0
    assert snap["gauges"]["pdt_serving_state_bytes"][""] == \
        2 * sum(s.nbytes() for s in eng._state_spec)
    assert snap["gauges"]["pdt_serving_state_slots_live"][""] == 0


# -- what a model with state layers refuses ---------------------------------
def _refused(**kw):
    model, _ = _model()
    return ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                    page_size=8, **kw)


@pytest.mark.parametrize("name,kw", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("spec_decode", dict(spec_decode=SpecConfig(draft_model=None, k=2))),
    ("quant.kv", dict(quant=QuantServingConfig(kv="int8"))),
    ("quant.weights", dict(quant=QuantServingConfig(weights="int8"))),
    ("harvest_every > 1", dict(harvest_every=2)),
    ("submesh tp > 1", dict(submesh=types.SimpleNamespace(tp=2))),
])
def test_unsupported_features_refuse_by_name(name, kw):
    with pytest.raises(ValueError) as e:
        _refused(**kw)
    assert name in str(e.value) and "state layers" in str(e.value)


@pytest.mark.parametrize("call", [
    lambda e: e.export_pages(0),
    lambda e: e.import_pages({}),
    lambda e: e.import_prefix([[1] * 8], [], []),
    lambda e: e.install_adapter("a", {}),
])
def test_page_only_methods_refuse_by_name(call):
    eng = _refused()
    with pytest.raises(ValueError, match="state layers"):
        call(eng)


# -- the Llama path keeps what it had ----------------------------------------
def test_llama_states_kv_for_every_layer_and_keeps_its_pools():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    assert model.cache_spec() == [KVSpec(2, 32)] * 2
    eng = ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                   page_size=8)
    assert eng._kv_shape[:3] == (2, 2, 32)
    # token-major pools: (pages, page_size, KV heads x head_dim)
    assert [tuple(a.shape for a in e) for e in eng._kv] == \
        [((17, 8, 2 * 32),) * 2] * 2
    assert eng._state == [] and eng._cache() is eng._kv
    rid = eng.add_request(list(range(1, 12)), max_new_tokens=3)
    assert len(eng.run()[rid]) == 3
    # the step program's outputs are (tokens, pools): no counters
    out = eng._decode_jit(
        eng._pv(), eng._bv(), eng._kv, jnp.zeros(2, jnp.int32),
        eng._decode_idx, jnp.zeros(2, jnp.int32), eng._decode_idx,
        eng._decode_ones, jnp.ones(2, jnp.int32), jnp.asarray(eng._bt),
        eng._decode_idx, eng._next_keys())
    assert len(out) == 2 and len(out[1]) == 2
    eng._kv = out[1]


# -- the grouped matmul at two or three rows an expert ---------------------
def test_row_block_follows_the_rows_a_group_holds():
    from paddle_tpu.ops.grouped_matmul import row_block
    assert [row_block(r) for r in (0.3, 2.75, 16, 17, 44, 500)] == \
        [16, 16, 16, 32, 64, 128]


# groups padded to 16 rows: tiles a group, empty groups, dead tiles
_PADDED = [16, 0, 32, 0, 0, 16, 48, 0]
_GMM_CASES = {
    # case: (path, group sizes, rows, K, N, n block)
    "aligned": ("aligned", _PADDED, 160, 128, 256, None),
    "kernel": ("kernel", _PADDED, 160, 128, 256, None),
    "any_size": ("any_size", [5, 0, 37, 0, 0, 16, 51, 3], 160, 128, 256,
                 None),
    # the stationary grid: more than one n block, K as the served
    # widths have it (768 and 2688, an eighth of each), groups of one,
    # two and three tiles with empty groups between them, no dead tile,
    # nothing but dead tiles, and the 128-row grid at 16 rows for the
    # same answer
    "kernel_two_n_blocks": ("kernel", _PADDED, 160, 128, 256, 128),
    "kernel_k_768": ("kernel", _PADDED, 160, 96, 256, 128),
    "kernel_k_2688": ("kernel", _PADDED, 160, 336, 384, 128),
    "kernel_last_group_live": ("kernel", [0, 48, 0, 16, 32], 96, 128, 256,
                               128),
    "kernel_one_group": ("kernel", [0, 0, 32, 0], 64, 128, 128, None),
    "kernel_all_dead": ("kernel", [0, 0, 0], 32, 128, 256, 128),
    "kernel_row_tile_outermost": ("outer", _PADDED, 160, 128, 256, None),
}


@pytest.mark.parametrize("case", sorted(_GMM_CASES))
def test_grouped_matmul_at_sixteen_rows_a_tile(case):
    """Against a per-group oracle: groups padded to 16 rows, some
    empty, dead tiles at the end, through the XLA walk and through the
    kernels (interpret mode); and groups of any size, through the walk
    at block_m 0."""
    from paddle_tpu.ops import grouped_matmul as gm
    path, sizes, m, k, n, block_n = _GMM_CASES[case]
    rng = np.random.default_rng(0)
    sizes = np.array(sizes, np.int32)
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((len(sizes), k, n)).astype(np.float32)
    want, row = np.zeros((m, n), np.float32), 0
    for g, size in enumerate(sizes):
        want[row:row + size] = lhs[row:row + size] @ rhs[g]
        row += size
    args = jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes)
    if path == "kernel":
        got = gm.gmm_stationary(*args, block_m=16, block_n=block_n,
                                interpret=True)
    elif path == "outer":
        got = gm.gmm_pallas(*args, block_m=16, interpret=True)
    else:
        got = gm.grouped_matmul_values(*args, 16 if path == "aligned" else 0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-3)
    assert not np.asarray(got[row:]).any()


def test_grouped_matmul_input_gradient_at_sixteen_rows_a_tile(monkeypatch):
    """The custom vjp's d(lhs) goes through the kernel at the tile it
    was given: `_gmm` on the transposed weights. The kernel path (what
    a TPU takes, here in interpret mode) against the XLA walk's."""
    import functools
    from paddle_tpu.ops import grouped_matmul as gm
    rng = np.random.default_rng(1)
    sizes = jnp.asarray(_PADDED, jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((160, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((8, 128, 256)), jnp.float32)

    def dlhs():
        return jax.grad(lambda x: jnp.sum(
            gm.grouped_matmul_values(x, rhs, sizes, 16) ** 2))(lhs)

    want = dlhs()
    calls = []

    def kernel(*args, **kw):
        calls.append(args[1].shape)
        return gm_stationary(*args, interpret=True, **kw)

    gm_stationary = gm.gmm_stationary
    monkeypatch.setattr(gm, "on_tpu", lambda: True)
    monkeypatch.setattr(gm, "gmm_stationary", kernel)
    got = dlhs()
    # forward on (E, K, N), then d(lhs) on (E, N, K)
    assert calls == [(8, 128, 256), (8, 256, 128)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-2)
    assert not np.asarray(got[112:]).any()


def _weight_fetches(counts, block_m, n_blocks, tiles):
    """Walk `gmm_stationary`'s grid in order through the kernel's own
    `_weight_copies` and return the experts whose block a sweep
    copies, in order. Checked on the way: a tile computes from a buffer
    that holds its expert's block, waited for, and no copy lands in a
    buffer whose run is not over."""
    from paddle_tpu.ops import grouped_matmul as gm
    padded = -(-np.asarray(counts) // block_m) * block_m
    te, first, slot, ahead, live = (np.asarray(a) for a in gm._run_map(
        jnp.asarray(padded, jnp.int32), tiles, block_m))
    assert live[0] == padded.sum() // block_m <= tiles
    assert first.sum() == (padded > 0).sum()
    sweeps = []
    for _ in range(n_blocks):
        copied, holds, landed = [], [None, None], [False, False]
        for i in range(tiles):
            starts, (waits, expert, buffer) = gm._weight_copies(
                i, te, first, slot, ahead, live)
            for on, e, b in starts:
                if on:
                    # the run that computed from this buffer is over
                    assert i == 0 or slot[i] != b
                    copied.append(int(e))
                    holds[b], landed[b] = int(e), False
            if waits:
                assert holds[buffer] == expert and not landed[buffer]
                landed[buffer] = True
            if i < live[0]:
                assert holds[slot[i]] == te[i] and landed[slot[i]]
        assert landed == [h is not None for h in holds]   # none in flight
        sweeps.append(copied)
    return sweeps


@pytest.mark.parametrize("draw", ["even", "skewed", "last_expert_idle",
                                  "one_expert", "none"])
def test_an_experts_weights_are_copied_once_an_n_block(draw):
    """The property the stationary grid is for: over a sweep of the
    row tiles each HIT expert's weight block is copied exactly once,
    in order, however many row tiles its rows take; dead tiles and
    idle experts copy nothing."""
    rng = np.random.default_rng(3)
    experts, block_m = 128, 16
    p = {"even": np.full(experts, 1 / experts),
         "skewed": (lambda z: z / z.sum())(
             1 / np.arange(1, experts + 1) ** 1.2)}.get(draw)
    if p is not None:
        counts = rng.multinomial(2048, p)
    elif draw == "last_expert_idle":
        counts = np.r_[rng.integers(1, 60, experts - 3), 0, 0, 0]
    elif draw == "one_expert":
        counts = np.r_[np.zeros(40, int), 100, np.zeros(experts - 41, int)]
    else:
        counts = np.zeros(experts, int)
    tiles = (int(counts.sum()) + experts * (block_m - 1)) // block_m + 1
    hit = np.nonzero(counts)[0].tolist()
    further = int((-(-counts // block_m)).sum()) - len(hit)
    assert further > 0 or draw == "none"
    for n_blocks in (1, 2, 3):
        assert _weight_fetches(counts, block_m, n_blocks, tiles) \
            == [hit] * n_blocks


# -- the benchmark's arithmetic for this model ---------------------------------
def _config_file():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


def test_configuration_file_feeds_the_programs_config():
    from benchmark import roofline_hybrid, weights
    sizes = _config_file()
    cfg, cls = weights.model_config(sizes["program"], sizes)
    assert cls is nh.NemotronHForCausalLM
    assert cfg.pattern == "MEMEMEM*EME" and cfg.experts_held == 128
    assert sizes["reduced"] == ["num_hidden_layers", "experts_held",
                                "vocab_size"]
    # the cut's arithmetic (ISSUE 27): 4.65 B parameters held, 21 MB of
    # state a slot, 1 KiB of K and V a token
    held = (5 * roofline_hybrid.mamba_params(sizes)
            + 5 * (roofline_hybrid.expert_layer_rest_params(sizes)
                   + 128 * roofline_hybrid.expert_params(sizes))
            + roofline_hybrid.attention_params(sizes)
            + 2 * sizes["vocab_size"] * sizes["hidden_size"]
            + sizes["hidden_size"])
    assert abs(held - 4.648e9) < 2e6
    assert roofline_hybrid.state_bytes_per_slot(sizes) == 21278720
    assert roofline_hybrid.kv_bytes_per_token(sizes) == 1024


def test_hybrid_floor_readers_on_a_hand_made_window():
    """100 decode steps and 4 admissions of 640 held experts each; the
    admissions are taken to hit all, so the decode steps hit
    (58000 - 2560) / 100 = 554.4 a step."""
    from benchmark import roofline_hybrid
    from benchmark.readers import gmm_roofline, hybrid_decode_floor_share
    sizes = _config_file()
    hist = "pdt_serving_decode_step_seconds"
    ctr = "pdt_serving_moe_experts_total"

    def snap(steps, seconds, hit, idle):
        return {"histograms": {hist: {"": {"sum": seconds,
                                           "count": steps}}},
                "counters": {ctr: {'kind="hit"': hit,
                                   'kind="idle"': idle}}}
    obs = {"telemetry": {"before": snap(10, 0.3, 1000.0, 280.0),
                         "after": snap(110, 3.3, 59000.0, 8840.0)},
           "steps": [{"running_slots": 64, "live_context_tokens": 128000}],
           "model": sizes, "peaks": {"hbm_bytes_per_s": 819e9},
           "window_s": 40.0, "t_open": 100.0, "t_close": 140.0,
           # the last 4 s were traced: 9 decode steps and an admission
           "spans": [{"name": "serving.decode_step", "ts_mono": t,
                      "dur_s": 0.03} for t in np.arange(100.2, 139.9, 0.4)]
           + [{"name": "serving.ragged_prefill", "ts_mono": t,
               "dur_s": 0.06} for t in (110.0, 120.0, 130.0, 138.0)],
           "trace": {"window_s": 4.0, "ops_s": {"grouped_matmul": 0.5,
                                                 "fusion": 1.0}}}
    assert hybrid_decode_floor_share.hits_a_decode_step(
        obs, ctr, 100) == pytest.approx(554.4)
    floor = roofline_hybrid.decode_step_bytes(sizes, 128000, 64, 554.4) \
        / 819e9
    assert hybrid_decode_floor_share.read(
        obs, histogram=hist, experts=ctr) == pytest.approx(
            100 * floor / 0.03)
    gmm = dict(pattern="^grouped_matmul", experts=ctr, histogram=hist,
               decode_span="serving.decode_step",
               admit_span="serving.ragged_prefill")
    traced = sum(1 for t in np.arange(100.2, 139.9, 0.4) if t + 0.015 >= 136)
    assert gmm_roofline.read(obs, **gmm) == pytest.approx(
        100 * (traced * 554.4 + 640) * 11010048 / 819e9 / 0.5)
    empty = {"telemetry": {"before": {}, "after": {}}, "steps": [],
             "window_s": 1.0}
    assert hybrid_decode_floor_share.read(
        empty, histogram=hist, experts=ctr) is None
    assert gmm_roofline.read(empty, **gmm) is None


# -- the choice-forced logits check (benchmark/runners/serve_routed.py) ----
def _routed_check(dtype, seed=2, **spec):
    from benchmark.runners import serve_routed
    model, cfg = _model(seed, dtype=dtype, experts_held=8, expert_offset=4)
    sizes = dict(_sizes(cfg), reference="nemotron_h")
    engine_kw = dict(max_seq_len=128, page_size=8, prefill_chunk=16,
                     prompt_pad=16)
    spec = dict(dict(prompt_tokens=43, steps=4, tolerance=0.15,
                     route_margin=0.02), **spec)
    return serve_routed.logits_check(model, sizes, engine_kw, spec, seed), \
        (model, sizes, engine_kw, spec)


def test_engine_hands_a_sentry_every_rows_expert_choices():
    """Prefill in three chunks and four decode steps: every position's
    choice in every expert layer reaches the sentry, and in float32 it
    is the reference's own."""
    from benchmark.runners import serve_routed
    model, cfg = _model(experts_held=8, expert_offset=4)
    prompt = _prompts(cfg, (43,))[0]
    tokens, _, chosen = serve_routed._through_the_engine(
        model, dict(max_seq_len=128, page_size=8, prefill_chunk=16,
                    prompt_pad=16), prompt, 4)
    _, want, gap = ref.forward_routed(_weights(model), _sizes(cfg),
                                      prompt + tokens)
    assert len(chosen) == len(want) == 2
    for got, own in zip(chosen, want):
        assert got.shape == (47, cfg.num_experts_per_tok)
        assert (np.sort(got, 1) == np.sort(own, 1)).all()
    assert (gap <= 0).all()


@pytest.mark.parametrize("control,ok", [(None, True),
                                        ("float8_e4m3fn", False)])
def test_choice_forced_check_tells_bf16_from_fp8(control, ok):
    """Seed 2's bf16 program exchanges an expert in a decode row: the
    harness's own check reads 2.1 there, as far as fp8 reads. With the
    choices given the program reads 0.03 and a gap of 0.003, the
    reference in fp8 0.36 and 0.13: each limit lies between."""
    from benchmark.runners import serve
    check, (model, sizes, engine_kw, spec) = _routed_check(
        "bfloat16", **({"control": control} if control else {}))
    assert check["ok"] is ok, check
    if ok:
        assert check["max_err_over_ref_std"] < 0.05
        assert 0 < check["route_gap_max"] < 0.01
        assert sum(check["rows_not_the_reference_top_k"]) > 0
        plain = serve.logits_check(model, sizes, engine_kw, spec, 2)
        assert plain["max_err_over_ref_std"] > 1.0
    else:
        assert check["max_err_over_ref_std"] > 0.3
        assert check["route_gap_max"] > 0.05


def test_choice_forced_check_fails_a_program_that_routes_wrongly(
        monkeypatch):
    """A forced reference follows whatever the program chose, so the
    choice itself is held to the reference's scores: a program whose
    every row takes its lowest-ranked experts reads a gap far over the
    margin (and logits that still agree)."""
    real = jax.lax.top_k
    monkeypatch.setattr(
        nh.jax.lax, "top_k", lambda x, k: real(-x, k)
        if x.ndim == 2 and x.shape[1] == 16 else real(x, k))
    check, _ = _routed_check("float32", seed=0)
    assert not check["ok"]
    assert check["route_gap_max"] > 0.1
    assert check["max_err_over_ref_std"] < TIGHT
