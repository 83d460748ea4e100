"""SDAR-MoE (models/sdar.py) against its plain reference
(benchmark/reference/sdar_moe.py), and the engine's contract with a
model that generates by diffusion over blocks (models/cache_spec.py
`BlockDiffusionSpec`): a decode step is a PASS over a block of 4 rows a
slot, a slot advances when its block holds no mask. Tiny widths, CPU,
float32. conftest runs the engine cases with PDT_CHECK_INVARIANTS=1."""
import hashlib
import json
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
from paddle_tpu.models import sdar                            # noqa: E402
from paddle_tpu.models.cache_spec import (BlockDiffusionSpec,  # noqa: E402
                                          KVSpec, ReportSpec)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.serving import (ContinuousBatchingEngine,  # noqa: E402
                                       EngineInvariantError,
                                       QuantServingConfig, SpecConfig)
from paddle_tpu.ops import ragged_paged_attention as rpa      # noqa: E402
from benchmark.reference import sdar_moe as ref                # noqa: E402

# float32 against float32: what is left is the order of the sums
TIGHT = 2e-4        # of the reference logits' standard deviation


def _model(seed=0, **kw):
    paddle.seed(seed)
    cfg = sdar.SdarMoeConfig.tiny(**kw)
    model = sdar.SdarMoeForCausalLM(cfg)
    model.eval()
    return model, cfg


def _weights(model):
    return {n: p._value for n, p in model.named_parameters()}


def _err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.std(want))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size - 1, n).tolist() for n in lens]


class _PassRecorder:
    """Sentry-shaped (`attach_sentry`'s contract): every pass's live
    rows (slots, positions, chosen experts) and their logits."""
    wants_logits = True
    trips = 0

    def __init__(self):
        self.passes, self.tokens, self._rows = [], [], None

    def step_tick(self):
        return True

    def note_cost(self, seconds):
        pass

    def observe_tokens(self, toks):
        self.tokens += np.asarray(toks).tolist()

    def observe_layer_rows(self, slots, positions, records):
        self._rows = (np.asarray(slots), np.asarray(positions), records)

    def observe_logits(self, lg):
        self.passes.append(self._rows + (np.asarray(lg, np.float32),))


def _engine(model, **kw):
    base = dict(max_batch_size=3, max_seq_len=128, page_size=8,
                prefill_chunk=16, prompt_pad=8)
    base.update(kw)
    eng = ContinuousBatchingEngine(model, **base)
    rec = _PassRecorder()
    eng.attach_sentry(rec)
    return eng, rec


def _run(eng, rec, block):
    """Step until idle. ({rid: tokens}, {rid: [(position, logits (block,
    vocab)) a pass of the request's slot, in order]})."""
    done, by_rid = {}, {}
    while eng._queue or any(r is not None for r in eng._slot_req):
        before = {s: r.rid for s, r in enumerate(eng._slot_req)
                  if r is not None}
        n0 = len(rec.passes)
        for r in eng.step():
            done[r.rid] = list(r.output)
        owner = {**before, **{s: r.rid for s, r in enumerate(eng._slot_req)
                              if r is not None}}
        for slots, positions, _, lg in rec.passes[n0:]:
            for j in range(0, len(slots), block):
                by_rid.setdefault(owner[int(slots[j])], []).append(
                    (int(positions[j]), lg[j:j + block]))
    assert not eng.num_failures, eng.last_failure
    return done, by_rid


def _check_against_the_reference(model, cfg, prompts, budgets, done,
                                 by_rid, rids):
    """Tokens equal `generate_blocks`'; every pass's block logits agree
    with the reference's forward over prompt + committed blocks + the
    block as the reference would dispatch it."""
    w, sizes = _weights(model), dict(vars(cfg))
    for rid, p, n in zip(rids, prompts, budgets):
        passes = []
        want = ref.generate_blocks(w, sizes, p, n, passes=passes)
        assert done[rid] == want, (len(p), n)
        assert len(by_rid[rid]) == len(passes)
        for (pos, got), (context, _, _, lg) in zip(by_rid[rid], passes):
            assert pos == len(context)
            assert _err(got, lg) < TIGHT


# -- (a) the model against the reference ---------------------------------
@pytest.mark.parametrize("block", [1, 4])
def test_forward_matches_the_reference(block):
    model, cfg = _model(block_length=block)
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 22))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value, np.float32)
    for row in range(2):
        want = ref.forward_logits(_weights(model), dict(vars(cfg)),
                                  ids[row])
        assert _err(got[row], want) < TIGHT


def test_block_mask_changes_the_logits():
    """The comparison above sees the mask: under the causal mask the
    same weights give other logits in every block's first rows (and,
    with one layer, the same in its last, which sees the same keys)."""
    model, cfg = _model(num_hidden_layers=1)
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, 16)
    sizes = dict(vars(cfg))
    by_blocks = ref.forward_logits(_weights(model), sizes, ids)
    causal = ref.forward_logits(_weights(model),
                                dict(sizes, block_length=1), ids)
    assert _err(by_blocks[3::4], causal[3::4]) < TIGHT   # a block's end
    assert _err(by_blocks[0::4], causal[0::4]) > 0.05


def test_specifications():
    model, cfg = _model()
    spec = model.cache_spec()
    assert spec[0::2] == [KVSpec(2, 16)] * 2
    assert all(isinstance(s, ReportSpec) and s.row_record == (4,)
               for s in spec[1::2])
    assert model.generation_spec() == BlockDiffusionSpec(
        4, 255, 4, "low_confidence_static", 0.9)
    assert _model(block_length=1)[0].generation_spec() is None
    with pytest.raises(ValueError, match="routed experts"):
        sdar.SdarMoeConfig.tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="mask_token_id"):
        sdar.SdarMoeConfig.tiny(mask_token_id=256)
    with pytest.raises(ValueError, match="remasking"):
        BlockDiffusionSpec(4, 0, 4, "random").check()
    with pytest.raises(ValueError, match="denoising_steps"):
        BlockDiffusionSpec(4, 0, 5).check()


# -- (b) the engine against generate_blocks --------------------------------
RULES = {
    "static_4_steps": dict(denoising_steps=4),
    "static_2_steps": dict(denoising_steps=2),
    # fires on some rows and not on others at these weights (asserted)
    "dynamic": dict(remasking="low_confidence_dynamic", threshold=0.016),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_engine_generates_the_references_blocks(rule):
    """Five requests on three slots, so slots run out of phase and a
    slot is recycled: prompts with P % 4 in {0, 1, 3}, one shorter than
    a block, one prefilled in three chunks; budgets that are no
    multiples of 4."""
    model, cfg = _model(**RULES[rule])
    eng, rec = _engine(model)
    lens, budgets = (13, 16, 35, 3, 21), (7, 9, 6, 10, 5)
    prompts = _prompts(cfg, lens, seed=3)
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    done, by_rid = _run(eng, rec, cfg.block_length)
    _check_against_the_reference(model, cfg, prompts, budgets, done,
                                 by_rid, rids)
    live = {len(s) // 4 for s, _, _, _ in rec.passes}
    assert live >= {2, 3}          # passes shared by slots out of phase
    n_passes = [len(by_rid[r]) for r in rids]
    blocks = [-(-(p % 4 + n) // 4) for p, n in zip(lens, budgets)]
    if rule == "static_4_steps":
        # a block of 4 masks costs 4 + 1 passes, one of m masks m + 1
        assert n_passes[1] == 5 * blocks[1]
        assert n_passes[0] == 4 + 5 * (blocks[0] - 1)
    elif rule == "static_2_steps":
        assert n_passes[1] == 3 * blocks[1]
    else:
        per_block = n_passes[1] / blocks[1]
        assert 2 < per_block < 5, per_block   # the threshold fires, not always
    assert rec.tokens and len(rec.tokens) == sum(budgets)
    eng.check_invariants()


def test_a_token_equal_to_the_mask_id_stays_a_token():
    """Which positions are masked is a flag beside the ids: a prompt
    whose left-over token IS the mask id keeps it as given."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=1)
    prompt = _prompts(cfg, (9,), seed=5)[0]
    prompt[8] = cfg.mask_token_id
    rid = eng.add_request(prompt, max_new_tokens=5)
    done, by_rid = _run(eng, rec, 4)
    _check_against_the_reference(model, cfg, [prompt], [5], done, by_rid,
                                 [rid])
    assert len(by_rid[rid]) == 4 + 5     # 3 masks + commit, then 4 + 1


def test_prefix_cache_is_sound_for_blocks():
    """A page holds whole blocks and a block's keys depend on no later
    token: a second request with the same first page attaches it and
    generates what the reference does."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=1,
                       enable_prefix_caching=True)
    head = _prompts(cfg, (16,), seed=6)[0]
    prompts = [head + t for t in _prompts(cfg, (5, 2), seed=7)]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    done, by_rid = _run(eng, rec, 4)
    assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 16
    _check_against_the_reference(model, cfg, prompts, [6, 6], done,
                                 by_rid, rids)


def test_a_model_without_the_specification_runs_as_before():
    """The causal model (block length 1) has no generation spec and is
    served a token a step, from the logits of the token before."""
    model, cfg = _model(block_length=1)
    eng = ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                   page_size=8, prefill_chunk=16)
    assert eng._gen is None and eng._dblock == 1
    prompt = _prompts(cfg, (11,), seed=8)[0]
    rid = eng.add_request(prompt, max_new_tokens=5)
    toks = eng.run()[rid]
    want = ref.forward_logits(_weights(model), dict(vars(cfg)),
                              prompt + toks[:-1])
    assert toks == [int(t) for t in want[len(prompt) - 1:].argmax(-1)]
    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    assert ContinuousBatchingEngine(llama, max_seq_len=64)._gen is None


# -- (c) the mask in the kernel and in the XLA path ----------------------
def _dense_oracle(q, kp, vp, qs, ql, cl, bt, block):
    """Per-row NumPy attention over the page table under the block
    mask; pools token-major (P, page_size, HK*D)."""
    t, h, d = q.shape
    ps, hk = kp.shape[1], kp.shape[2] // d
    out = np.zeros((t, h, d), np.float32)
    for s in range(len(ql)):
        ctx = int(cl[s])
        keys = np.stack([kp[bt[s, p // ps], p % ps] for p in range(ctx)]
                        or [np.zeros(hk * d)]).reshape(-1, hk, d)
        vals = np.stack([vp[bt[s, p // ps], p % ps] for p in range(ctx)]
                        or [np.zeros(hk * d)]).reshape(-1, hk, d)
        for j in range(int(ql[s])):
            pos = ctx - int(ql[s]) + j
            seen = np.arange(ctx) // block <= pos // block
            for head in range(h):
                kh = head // (h // hk)
                lg = keys[seen, kh] @ q[qs[s] + j, head] / np.sqrt(d)
                p = np.exp(lg - lg.max())
                out[qs[s] + j, head] = (p / p.sum()) @ vals[seen, kh]
    return out


@pytest.mark.parametrize("block", [1, 4, 8])
def test_kernel_xla_and_a_dense_mask_agree(block, monkeypatch):
    """A mixed batch (a block pass at a long context, a whole prefill, a
    chunk continuation, an idle sequence, padding rows), KV blocks of 2
    pages so the in-kernel loop takes several trips."""
    monkeypatch.setattr(rpa, "KV_BLOCK_MAX_KEYS", 8)
    rng = np.random.default_rng(block)
    hk, g, d, ps, pps = 2, 2, 16, 4, 12
    ql = np.asarray([8, 24, 16, 0], np.int32)
    cl = np.asarray([40, 24, 48, 0], np.int32)
    qs, total = rpa.pack_ragged_starts(ql, block_q=8)
    t = total + 8
    q = rng.standard_normal((t, hk * g, d)).astype(np.float32)
    kp = rng.standard_normal((40, ps, hk * d)).astype(np.float32)
    vp = rng.standard_normal((40, ps, hk * d)).astype(np.float32)
    bt = np.zeros((4, pps), np.int32)
    bt[:3] = 1 + rng.permutation(36).reshape(3, 12)
    want = _dense_oracle(q, kp, vp, qs, ql, cl, bt, block)
    for use_kernel in (True, False):
        got = np.asarray(rpa.ragged_paged_attention_values(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), qs, ql, cl,
            bt, block_q=8, use_kernel=use_kernel, diffusion_block=block))
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert np.all(got[total:] == 0)
    walked = rpa.ragged_pages_walked(
        qs, ql, cl, t, block_q=8, page_size=ps, window=None,
        block_pages=2, table_pages=pps, diffusion_block=block)
    # per q block: KV blocks up to the one of its last row's frontier
    frontier = [(cl[s] - ql[s] + r + 7) for s in range(3)
                for r in range(0, ql[s], 8)]
    assert walked == sum((f // ps // 2 + 1) * 2 for f in frontier)


def test_rows_inside_a_block_see_its_later_keys():
    """What the mask is for: at block 4 a block's first row attends
    the three keys after it, and the causal kernel does not."""
    rng = np.random.default_rng(0)
    q = rng.standard_normal((8, 2, 16)).astype(np.float32)
    kp = rng.standard_normal((4, 4, 16)).astype(np.float32)
    bt = np.asarray([[1, 2, 3]], np.int32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(kp),
            np.asarray([0], np.int32), np.asarray([8], np.int32),
            np.asarray([8], np.int32), bt)
    causal = np.asarray(rpa.ragged_paged_attention_values(
        *args, block_q=8, use_kernel=True))
    blocks = np.asarray(rpa.ragged_paged_attention_values(
        *args, block_q=8, use_kernel=True, diffusion_block=4))
    np.testing.assert_allclose(blocks[3::4], causal[3::4], atol=2e-6)
    assert np.abs(blocks[0::4] - causal[0::4]).max() > 0.05


# sha256 of the jaxpr text at the parent of the PR that brought
# `diffusion_block` (PR 31), for the call below under conftest's
# matmul precision: (kernel, XLA path)
JAXPR_AT_BLOCK_1 = (
    "90acc2a80d36b66089cd79a8d14f1c555e60f48b4d0d3e6b3e5fc8bbf2d0d557",
    "9d3e553f69433684e95cb419551ea2125ec4820ba223609527000e5be710e42e")


@pytest.mark.parametrize("path", [0, 1])
def test_at_block_1_the_call_traces_as_it_did(path):
    """`diffusion_block` is static: at 1 the kernel call and the XLA
    path trace to the jaxpr they had before the parameter existed,
    operation for operation (no divide, no extra compare), and at 4 to
    another."""
    t, h, hk, d, ps, pps, n = 16, 4, 2, 16, 4, 8, 3
    q = jnp.zeros((t, h, d), jnp.float32)
    kp = jnp.zeros((32, ps, hk * d), jnp.float32)
    qs = jnp.asarray([0, 8, 0], jnp.int32)
    ql = jnp.asarray([8, 8, 0], jnp.int32)
    cl = jnp.asarray([8, 20, 0], jnp.int32)
    bt = jnp.zeros((n, pps), jnp.int32)

    def text(**kw):
        kw.update(dict(use_kernel=False, pages_bound=8) if path
                  else dict(use_kernel=True))
        return str(jax.make_jaxpr(
            lambda *a: rpa.ragged_paged_attention_values(
                *a, block_q=8, **kw))(q, kp, kp, qs, ql, cl, bt))

    for kw in ({}, {"diffusion_block": 1}):
        assert hashlib.sha256(text(**kw).encode()).hexdigest() \
            == JAXPR_AT_BLOCK_1[path]
    assert text(diffusion_block=4) != text()


def test_the_packer_refuses_a_piece_that_ends_inside_a_block():
    piece = {"seq": 0, "tokens": [1] * 8, "offset": 4}
    rpa.pack_ragged_batch([piece], 1, diffusion_block=4)
    for bad in (dict(piece, tokens=[1] * 6), dict(piece, offset=2)):
        with pytest.raises(ValueError, match="diffusion_block 4"):
            rpa.pack_ragged_batch([bad], 1, diffusion_block=4)
        rpa.pack_ragged_batch([bad], 1)            # causal: any piece


# -- (d) the expert layer and its shares -----------------------------------
def test_four_shares_add_up_to_the_uncut_layer():
    """Four programs that each hold 4 of a layer's 16 experts: what
    they compute adds up to the uncut reference's expert layer."""
    model, cfg = _model()
    layer = model.model.layers[1]
    h = jax.random.normal(jax.random.key(5), (23, cfg.hidden_size))
    w = {n: p._value for n, p in layer.named_parameters()
         if n.startswith(("mlp.", "post_"))}
    whole = np.asarray(ref._experts(
        h, w, top_k=cfg.num_experts_per_tok, offset=0, renorm=True,
        eps=cfg.rms_norm_eps)[0]) - np.asarray(h)
    total, counters = np.zeros_like(whole), []
    with paddle.no_grad():
        a = layer.post_attention_layernorm(paddle.to_tensor(h[None]))
        for share in range(4):
            part = sdar.SdarMoeExperts(sdar.SdarMoeConfig.tiny(
                experts_held=4, expert_offset=4 * share))
            for (name, p), (_, q) in zip(part.named_parameters(),
                                         layer.mlp.named_parameters()):
                p._value = q._value[4 * share:4 * share + 4] \
                    if name.startswith("experts.") else q._value
            out, (c, chosen) = part(a, jnp.ones((23,), bool))
            total += np.asarray(out._value[0])
            counters.append(np.asarray(c))
            assert chosen.shape == (23, cfg.num_experts_per_tok)
    np.testing.assert_allclose(total, whole, rtol=2e-4,
                               atol=2e-5 * float(np.abs(whole).max()))
    counters = np.stack(counters)
    assert counters[:, 0].sum() == 23 * cfg.num_experts_per_tok
    assert (counters[:, :2].sum(1) == 23 * cfg.num_experts_per_tok).all()
    assert (counters[:, 2:4].sum(1) == 4).all()
    assert (counters[:, 4] == counters[:, 2]).all()


def test_both_expert_layers_share_one_routed_dispatch():
    """`models/routed.py` is the one copy: the two layers' traces hold
    the same sort, scatter and gather (same primitives, same count)
    around their own matmuls."""
    from paddle_tpu.models import routed
    live = jnp.arange(24) < 20
    chosen = jax.random.randint(jax.random.key(0), (24, 4), 0, 16)
    r = routed.route_rows(chosen, live, held=8, offset=4, n_experts=16)
    counts = np.asarray(r.counts)
    mine = np.asarray(r.mine)
    assert counts.sum() == mine.sum()
    assert (np.asarray(r.padded) % r.block_m == 0).all()
    # every computed assignment reads its own row back
    src, dest = np.asarray(r.src), np.asarray(r.dest).reshape(24, 4)
    rows, ks = np.nonzero(mine)
    assert (src[dest[rows, ks]] == rows).all()
    stats = np.asarray(routed.report_counts(r, live, 4))
    assert stats.tolist() == [counts.sum(), 20 * 4 - counts.sum(),
                              (counts > 0).sum(), 8 - (counts > 0).sum(),
                              (counts > 0).sum(),
                              (-(-counts // r.block_m)).sum()
                              - (counts > 0).sum()]
    for fn in (nh.latent_experts_values, sdar.swiglu_experts_values):
        assert "route_rows" in fn.__code__.co_names


@pytest.mark.parametrize("draw", ["even", "skewed"])
def test_row_tile_counts_against_numpy(draw):
    """`report_counts`' row tiles: `first` = live row tiles that are
    the first of their expert (the experts hit), `further` = live row
    tiles behind another of the same expert; rows held elsewhere and
    dead rows take no tile."""
    from paddle_tpu.models import routed
    rng = np.random.default_rng(5)
    t, k, n_experts, held, offset = 256, 8, 128, 96, 16
    p = np.full(n_experts, 1 / n_experts) if draw == "even" else \
        (lambda z: z / z.sum())(1 / np.arange(1, n_experts + 1.0))
    chosen = np.stack([rng.choice(n_experts, k, replace=False, p=p)
                       for _ in range(t)])
    live = np.arange(t) < 230
    r = routed.route_rows(jnp.asarray(chosen), jnp.asarray(live),
                          held=held, offset=offset, n_experts=n_experts)
    assert r.block_m == 16
    local = chosen[live] - offset
    counts = np.bincount(local[(local >= 0) & (local < held)],
                         minlength=held)
    tiles = -(-counts // 16)
    stats = np.asarray(routed.report_counts(r, jnp.asarray(live), k))
    assert stats[4] == (counts > 0).sum() == stats[2]
    assert stats[5] == tiles.sum() - (counts > 0).sum()
    assert stats[5] > 0 and stats[4] + stats[5] == np.asarray(
        r.padded).sum() // r.block_m
    spec = routed.report_spec(k)
    assert [(c.name, kind) for c, kind in spec.counters[4:]] == [
        ("pdt_serving_moe_row_tiles_total", "first"),
        ("pdt_serving_moe_row_tiles_total", "further")]
    assert len(spec.counters) == stats.shape[0]


# -- (e) preemption and migration mid-block --------------------------------
def test_preempt_mid_block_and_resume():
    """A preempted request resumes from its last committed block: the
    block in flight is dropped, the tokens are the reference's."""
    model, cfg = _model()
    eng, rec = _engine(model, max_batch_size=2)
    prompts = _prompts(cfg, (21, 12), seed=9)
    rids = [eng.add_request(p, max_new_tokens=11) for p in prompts]
    for _ in range(8):
        eng.step()
    victim = eng._slot_req[1]
    assert eng._blk_passes[1] == 3 and len(victim.output) == 4
    eng._preempt_youngest([])
    assert eng.num_preemptions == 1 and not eng._blk_masked[1].any()
    done, _ = _run(eng, rec, 4)
    w, sizes = _weights(model), dict(vars(cfg))
    for rid, p in zip(rids, prompts):
        assert done[rid] == ref.generate_blocks(w, sizes, p, 11)
    eng.check_invariants()


def test_export_mid_block_and_import():
    """`export_pages` hands over the committed blocks; the target starts
    the block in flight anew and generates the reference's tokens."""
    model, cfg = _model()
    src, _ = _engine(model, max_batch_size=1)
    dst, rec = _engine(model, max_batch_size=1)
    prompt = _prompts(cfg, (18,), seed=11)[0]
    rid = src.add_request(prompt, max_new_tokens=10)
    for _ in range(6):
        src.step()
    assert src._blk_passes[0] > 0
    payload = src.export_pages(rid)
    assert payload["ctx"] % 4 == 0
    src.evict_request(rid)
    req = dst.import_pages(payload)
    done, _ = _run(dst, rec, 4)
    assert done[req.rid] == ref.generate_blocks(
        _weights(model), dict(vars(cfg)), prompt, 10)
    src.check_invariants()
    dst.check_invariants()


def test_a_slots_block_is_an_invariant():
    model, cfg = _model()
    eng, _ = _engine(model)
    eng.add_request(_prompts(cfg, (9,))[0], max_new_tokens=8)
    eng.step()
    eng.check_invariants()
    eng._blk_ids[0, 3] = 7
    with pytest.raises(EngineInvariantError, match="mask id"):
        eng.check_invariants()
    eng._blk_ids[0, 3] = cfg.mask_token_id
    eng._blk_ids[0, 0] += 1
    with pytest.raises(EngineInvariantError, match="given tokens"):
        eng.check_invariants()
    eng._blk_ids[0, 0] -= 1
    eng._pos[0] += 1
    with pytest.raises(EngineInvariantError, match="whole block"):
        eng.check_invariants()
    eng._pos[0] -= 1
    eng._blk_masked[2, 1] = True
    with pytest.raises(EngineInvariantError, match="free slot 2"):
        eng.check_invariants()


# -- (f) what a block model refuses ------------------------------------------
@pytest.mark.parametrize("name,kw", [
    ("spec_decode", dict(spec_decode=SpecConfig(draft_model=None, k=2))),
    ("harvest_every > 1", dict(harvest_every=2)),
    ("do_sample", dict(do_sample=True)),
    ("quant.kv", dict(quant=QuantServingConfig(kv="int8"))),
    ("quant.weights", dict(quant=QuantServingConfig(weights="int8"))),
    ("submesh tp > 1", dict(submesh=types.SimpleNamespace(tp=2))),
])
def test_unsupported_features_refuse_by_name(name, kw):
    model, _ = _model()
    with pytest.raises(ValueError) as e:
        ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                 page_size=8, **kw)
    assert name in str(e.value) and "diffusion over blocks" in str(e.value)


@pytest.mark.parametrize("kw", [dict(page_size=6), dict(max_seq_len=66)])
def test_pages_and_length_hold_whole_blocks(kw):
    model, _ = _model()
    with pytest.raises(ValueError, match="block_length 4"):
        ContinuousBatchingEngine(model, **{"max_batch_size": 2,
                                           "max_seq_len": 64,
                                           "page_size": 8, **kw})


# -- (g) the counters --------------------------------------------------------
def test_block_counters(monkeypatch):
    model, cfg = _model()
    monkeypatch.setenv("PDT_TELEMETRY", "1")     # as conftest's fixture
    telemetry.reset()
    telemetry.clear_events()     # an earlier file's spans, same worker
    eng, _ = _engine(model, max_batch_size=2)
    for p in _prompts(cfg, (12, 8)):
        eng.add_request(p, max_new_tokens=8)
    eng.run()
    snap = telemetry.snapshot()
    passes = snap["counters"]["pdt_serving_block_passes_total"]
    # 2 requests x 2 blocks of 4 masks: 4 denoising passes and 1 commit
    assert passes['kind="denoise"'] == 16 and passes['kind="commit"'] == 4
    assert snap["counters"]["pdt_serving_block_tokens_total"][""] == 16
    assert snap["counters"]["pdt_serving_decode_tokens_total"][""] == 16
    assert snap["histograms"]["pdt_serving_block_seconds"][""]["count"] \
        == 4
    steps = [e for e in telemetry.events()
             if e.get("name") == "serving.decode_step"]
    assert len(steps) == 10
    assert {e["attrs"]["rows"] for e in steps} == {8}
    assert sum(e["attrs"]["commit_slots"] for e in steps) == 4
    # the expert layer reports through the counters Nemotron's does:
    # 20 prompt rows and 10 passes x 8 rows, 4 choices, 2 layers
    a = snap["counters"]["pdt_serving_moe_assignments_total"]
    assert a['kind="local"'] == (20 + 80) * 4 * 2 and \
        a.get('kind="remote"', 0) == 0
    e = snap["counters"]["pdt_serving_moe_experts_total"]
    # (two admission dispatches: 20 prompt rows at a chunk of 16)
    assert e['kind="hit"'] + e['kind="idle"'] == (2 + 10) * 16 * 2
    tiles = snap["counters"]["pdt_serving_moe_row_tiles_total"]
    assert tiles['kind="first"'] == e['kind="hit"']


# -- (h) the configuration, the pass's bytes, the readers, the check --------
def _config_file():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def test_configuration_file_feeds_the_programs_config():
    import json
    from benchmark import roofline_blocks, weights
    sizes = _config_file()
    cfg, cls = weights.model_config(sizes["program"], sizes)
    assert cls is sdar.SdarMoeForCausalLM
    assert (cfg.num_hidden_layers, cfg.experts_held, cfg.block_length,
            cfg.mask_token_id) == (6, 128, 4, 151669)
    assert sizes["reduced"] == ["num_hidden_layers"]
    # every key of the catalog's row at its published value but the depth
    catalog = os.path.join(os.sep, "opt", "skills", "guides",
                           "model-configs", "architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == sizes["source"]
        differ = {k for k, v in row["config"].items() if sizes[k] != v}
        assert differ == {"num_hidden_layers"}
    # the cut's arithmetic (ISSUE 31)
    p = roofline_blocks.params_by_part(sizes)
    assert p["attention"] == 18874368 and p["router"] == 262144
    assert p["norms"] == 4352 and p["expert"] == 4718592
    assert p["layer"] == 623120640                  # 1.246 GB in bf16
    assert p["embedding"] + p["head"] == 622329856  # 1.245 GB
    assert abs(2 * p["total"] - 8.722e9) < 1e6
    assert roofline_blocks.kv_bytes_per_token(sizes) == 12 * 1024
    assert roofline_blocks.experts_a_dispatch(sizes) == 768
    # a pass of 64 slots at 1000 tokens each, every expert hit: the
    # experts are 7.25 of its 8.9 GB
    total = roofline_blocks.pass_bytes(sizes, 64000, 64, 768)
    assert abs(768 * roofline_blocks.expert_bytes(sizes) - 7.248e9) < 1e6
    assert abs(total - 8.89e9) < 2e7


def test_block_readers_on_a_hand_made_window():
    """100 passes and 4 admissions of 768 held experts each; the
    admissions are taken to hit all, so a pass hit (76000 - 3072) / 100."""
    from benchmark import roofline_blocks
    from benchmark.readers import (block_pass_floor_share,
                                   counter_over_counter, counter_ratio,
                                   gmm_roofline_blocks)
    sizes = _config_file()
    hist = "pdt_serving_decode_step_seconds"
    ctr = "pdt_serving_moe_experts_total"

    def snap(steps, seconds, hit, idle, denoise, commit, tokens):
        return {"histograms": {hist: {"": {"sum": seconds,
                                           "count": steps}}},
                "counters": {
                    ctr: {'kind="hit"': hit, 'kind="idle"': idle},
                    "pdt_serving_block_passes_total": {
                        'kind="denoise"': denoise, 'kind="commit"': commit},
                    "pdt_serving_block_tokens_total": {"": tokens}}}
    obs = {"telemetry": {
        "before": snap(10, 0.2, 7000.0, 680.0, 512, 128, 500),
        "after": snap(110, 2.2, 83000.0, 4552.0, 5632, 1408, 5620)},
        "steps": [{"running_slots": 64, "live_context_tokens": 64000}],
        "model": sizes, "peaks": {"hbm_bytes_per_s": 819e9},
        "window_s": 40.0, "t_open": 100.0, "t_close": 140.0,
        "spans": [{"name": "serving.decode_step", "ts_mono": t,
                   "dur_s": 0.02} for t in np.arange(100.2, 139.9, 0.4)]
        + [{"name": "serving.ragged_prefill", "ts_mono": t, "dur_s": 0.03}
           for t in (110.0, 120.0, 130.0, 138.0)],
        "trace": {"window_s": 4.0, "ops_s": {"grouped_matmul": 0.2,
                                              "fusion": 1.0}}}
    hits = (76000 - 4 * 768) / 100
    assert roofline_blocks.hits_a_pass(sizes, 76000.0, 3872.0, 100) \
        == pytest.approx(hits)
    floor = roofline_blocks.pass_bytes(sizes, 64000, 64, hits) / 819e9
    assert block_pass_floor_share.read(
        obs, histogram=hist, experts=ctr) == pytest.approx(
            100 * floor / 0.02)
    gmm = dict(pattern="^grouped_matmul", experts=ctr, histogram=hist,
               decode_span="serving.decode_step",
               admit_span="serving.ragged_prefill")
    traced = sum(1 for t in np.arange(100.2, 139.9, 0.4) if t + 0.01 >= 136)
    assert gmm_roofline_blocks.read(obs, **gmm) == pytest.approx(
        100 * (traced * hits + 768) * 9437184 / 819e9 / 0.2)
    assert counter_over_counter.read(
        obs, numerator="pdt_serving_block_passes_total",
        denominator="pdt_serving_block_tokens_total") == pytest.approx(1.25)
    assert counter_ratio.read(
        obs, counter="pdt_serving_block_passes_total",
        numerator=['kind="commit"'], scale=100.0) == pytest.approx(20.0)
    # moe_tile_reuse_share, through its own file: the row tiles that
    # ran on a weight block already fetched, of all live row tiles
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "moe_tile_reuse_share.json")) as f:
        reuse = json.load(f)
    assert reuse["reader"] == "counter_ratio"
    for when, first, further in (("before", 7000.0, 3000.0),
                                 ("after", 83000.0, 36000.0)):
        obs["telemetry"][when]["counters"][
            "pdt_serving_moe_row_tiles_total"] = {
                'kind="first"': first, 'kind="further"': further}
    assert counter_ratio.read(obs, **reuse["args"]) == pytest.approx(
        100 * 33000 / (76000 + 33000))
    # a program without the counters (the parent): nothing to read
    empty = {"telemetry": {"before": {}, "after": {}}, "steps": [],
             "window_s": 1.0}
    assert block_pass_floor_share.read(empty, histogram=hist,
                                       experts=ctr) is None
    assert gmm_roofline_blocks.read(empty, **gmm) is None
    assert counter_over_counter.read(empty, numerator="a",
                                     denominator="b") is None
    assert counter_ratio.read(empty, **reuse["args"]) is None


def _blocks_check(seed=2, model_kw=None, **spec):
    from benchmark.runners import serve_blocks
    model, cfg = _model(seed, **(model_kw or {}))
    sizes = dict(vars(cfg), reference="sdar_moe")
    engine_kw = dict(max_seq_len=128, page_size=8, prefill_chunk=16,
                     prompt_pad=16)
    spec = dict(dict(prompt_tokens=43, blocks=2, tolerance=0.15,
                     route_margin=0.02), **spec)
    return serve_blocks.logits_check(model, sizes, engine_kw, spec, seed), \
        (model, sizes, engine_kw, spec)


def test_pass_by_pass_check_passes_the_program():
    out, _ = _blocks_check()
    # 43 % 4 = 3 given: 1 + 1 passes, then 4 + 1
    assert out["ok"] and out["passes"] == 7 and out["tokens_are_the_rules"]
    assert out["max_err_over_ref_std"] < TIGHT
    assert out["route_gap_max"] <= 0


def test_pass_by_pass_check_fails_fp8_and_a_wrong_mask(monkeypatch):
    """The control (the reference with fp8 matrices in the program's
    place) fails, and so does a program whose rows do not see their
    block's later keys: the causal kernel under the block model."""
    out, (model, sizes, engine_kw, spec) = _blocks_check(
        control="float8_e4m3fn")
    assert not out["ok"] and out["control"] == "float8_e4m3fn"
    from benchmark.runners import serve_blocks
    real = rpa.ragged_paged_attention_values
    monkeypatch.setattr(
        rpa, "ragged_paged_attention_values",
        lambda *a, diffusion_block=1, **kw: real(*a, **kw))
    wrong = serve_blocks.logits_check(model, sizes, engine_kw,
                                      dict(spec, control=None), 2)
    assert not wrong["ok"] and wrong["max_err_over_ref_std"] > 0.15
