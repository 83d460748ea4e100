"""Mellum 2 (models/mellum.py) against its plain reference
(benchmark/reference/mellum.py): window and full attention layers, each
kind with its own rotary table (YaRN on the full ones), over SwiGLU
experts, served through the engine's two page groups beside the expert
layers' reports. Tiny widths, CPU, float32: layers S S S F, a window (8)
shorter than the prompts, a page (4) shorter than the window, chunks
(8) shorter than the prompts, 8 experts top-2, and a YaRN table whose
ramp lies inside the head (`MellumConfig.tiny`)."""
import json
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
import paddle_tpu.observability as telemetry                  # noqa: E402
from paddle_tpu.models import mellum                          # noqa: E402
from paddle_tpu.models.cache_spec import KVSpec, ReportSpec   # noqa: E402
from paddle_tpu.models.llama import (precompute_rope_scaled,  # noqa: E402
                                     yarn_ramp)
from paddle_tpu.models.serving import (ContinuousBatchingEngine,  # noqa: E402
                                       QuantServingConfig, SpecConfig)
from benchmark.reference import mellum as ref                 # noqa: E402
from benchmark.runners.serve import _LogitRecorder             # noqa: E402

# float32 against float32: what is left is the order of the sums
TIGHT = 2e-4        # of the reference logits' standard deviation
CELL = "mellum2-12b-a2_5b-instruct"
S, F = "sliding_attention", "full_attention"


def _model(seed=0, **kw):
    paddle.seed(seed)
    cfg = mellum.MellumConfig.tiny(**kw)
    model = mellum.MellumForCausalLM(cfg)
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


def _sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CELL + ".json")) as f:
        return json.load(f)


# -- the model against the reference -----------------------------------
def test_forward_matches_the_reference():
    """Whole sequences from nothing, 21 tokens each: longer than the
    window, past YaRN's original context of 16."""
    model, cfg = _model()
    ids = np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 21))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value, np.float32)
    for b in range(2):
        assert _err(got[b], _ref_logits(model, cfg, ids[b])) < TIGHT


def test_engine_prefill_in_chunks_then_decode_equals_the_reference():
    """Prompts of 27, 5 and 30 in one queue through chunks of 8: a
    short one inside one window beside two that cross it three times,
    continuation pieces, pieces of two sequences in one dispatch,
    padding rows; then 8 decode steps through the window group (which
    reclaims pages behind every dispatch), the full group and the
    expert layers: the logits are the reference's full forward's."""
    model, cfg = _model()
    eng, rec = _engine(model)
    assert [g.name for g in eng._groups] == ["full", "w8"]
    prompts = _prompts(cfg, (27, 5, 30))
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    out = eng.run()
    assert not eng.num_failures, eng.last_failure
    for slot, (rid, p) in enumerate(zip(rids, prompts)):
        toks = out[rid]
        want = _ref_logits(model, cfg, p + toks[:8])
        got = np.stack([r[slot] for r in rec.rows[:8]])
        assert _err(got, want[len(p):len(p) + 8]) < TIGHT
        assert toks[0] == int(want[len(p) - 1].argmax())
    eng.check_invariants()
    assert all(sorted(g.free) == list(range(1, g.num_pages))
               for g in eng._groups)


def test_preemption_and_a_recycled_slot_give_the_undisturbed_tokens():
    model, cfg = _model()
    prompts = _prompts(cfg, (21, 13), seed=9)
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
        want = _ref_logits(model, cfg, p + done[rid][:-1])
        assert done[rid] == [int(t) for t in
                             want[len(p) - 1:].argmax(-1)]
    n0 = len(rec.rows)
    second = _prompts(cfg, (17,), seed=7)[0]
    rid = eng.add_request(second, max_new_tokens=6)
    out = eng.run()
    want = _ref_logits(model, cfg, second + out[rid][:5])
    got = np.stack([r[0] for r in rec.rows[n0:n0 + 5]])
    assert _err(got, want[17:22]) < TIGHT


# -- the controls ------------------------------------------------------
@pytest.mark.parametrize("lose", ["window_page", "wrong_table",
                                  "no_attention_factor"])
def test_a_lost_page_or_a_wrong_table_fails_the_comparison(lose):
    """The comparison FAILS, by far more than its tolerance, when before
    the first decode step the window group's oldest live page is
    trash-routed, when the full layer rotates by the window layers'
    table, or when the full layers' table lacks YaRN's attention
    factor."""
    model, cfg = _model()
    if lose == "wrong_table":
        model.layer_rope = lambda i: (model.model.rope_cos_sliding_attention,
                                      model.model.rope_sin_sliding_attention)
    elif lose == "no_attention_factor":
        af = cfg.rope_parameters[F]["attention_factor"]
        for b in (model.model.rope_cos_full_attention,
                  model.model.rope_sin_full_attention):
            b._value = b._value / af
    eng, rec = _engine(model, max_batch_size=1)
    prompt = _prompts(cfg, (27,))[0]
    rid = eng.add_request(prompt, max_new_tokens=5)
    decode = eng._decode

    def lossy(finished):
        if not rec.rows and lose == "window_page":
            win = eng._groups[1]
            win.bt[0, int(win.slot_freed[0])] = 0
        return decode(finished)

    eng._decode = lossy
    out = eng.run()
    want = _ref_logits(model, cfg, prompt + out[rid][:4])
    got = np.stack([r[0] for r in rec.rows[:4]])
    assert _err(got, want[27:31]) > 100 * TIGHT


def test_yarn_table_at_the_published_numbers():
    """Ramp edges at rotary pairs 18 and 35; the first 19 pairs keep
    theta's frequency, those from 35 turn 16 times slower; cos and sin
    carry the attention factor 1.27726 = 0.1 ln 16 + 1; the program's
    tables are the reference's; a rope_type not implemented is refused
    by name."""
    full = mellum.MellumConfig().rope_parameters[F]
    ramp = yarn_ramp(128, full)
    assert ref.yarn_edges(128, full) == (18, 35)
    assert np.all(ramp[:19] == 0) and np.all(ramp[35:] == 1)
    assert np.all((ramp[19:35] > 0) & (ramp[19:35] < 1))
    assert abs(full["attention_factor"] - (0.1 * np.log(16) + 1)) < 1e-12
    cos, sin = precompute_rope_scaled(128, 9000, full)
    assert np.allclose(np.asarray(cos._value)[0], 1.2772588722239782)
    want = ref.rope_tables(128, 9000, full)
    np.testing.assert_allclose(cos._value, want[0], atol=1e-6)
    np.testing.assert_allclose(sin._value, want[1], atol=1e-6)
    inv = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(np.asarray(cos._value)[1, :19],
                               1.2772588722239782 * np.cos(inv[:19]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(cos._value)[1, 35:],
                               1.2772588722239782 * np.cos(inv[35:] / 16),
                               atol=1e-6)
    plain, _ = precompute_rope_scaled(128, 4, {"rope_type": "default",
                                               "rope_theta": 500000})
    np.testing.assert_allclose(np.asarray(plain._value)[1],
                               np.cos(inv), atol=1e-6)
    with pytest.raises(ValueError, match="'linear'"):
        precompute_rope_scaled(128, 4, {"rope_type": "linear",
                                        "rope_theta": 1e4, "factor": 2})


# -- what each layer keeps, at the cell's depth ------------------------
def test_layers_groups_and_pools_at_the_cells_sizes():
    """The configuration file through `benchmark/weights.py`, as
    shapes only: the last 8 of 28 layers, S S S F S S S F; a window
    group of 6 layers whose pool follows from slots, window and chunk
    (64 x (64 + 2) + (1024 + 1024) / 16 + 1 pages), a full group of 2
    layers of `num_pages`; the rehearsal's 2 layers keep both kinds."""
    from benchmark import weights
    sizes = _sizes()
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".codemix.json")) as f:
        cell = json.load(f)
    box = {}

    def build():
        cfg, cls = weights.model_config(sizes["program"], sizes)
        box["cfg"], box["model"] = cfg, cls(cfg)
        box["model"].eval()
        box["eng"] = ContinuousBatchingEngine(
            box["model"], **{**sizes["engine"], **cell["engine"]})
        return 0

    jax.eval_shape(build)
    cfg, model, eng = box["cfg"], box["model"], box["eng"]
    kinds = [cfg.kind(i) for i in range(8)]
    assert kinds == [S, S, S, F] * 2 == ref.layer_kinds(sizes)
    assert cfg.max_position_embeddings == 16384 and cfg.experts_held == 64
    spec = model.cache_spec()
    assert spec[0::2] == [KVSpec(4, 128, 1024)] * 3 + [KVSpec(4, 128)] \
        + [KVSpec(4, 128, 1024)] * 3 + [KVSpec(4, 128)]
    assert all(isinstance(s, ReportSpec) and s.row_record == (8,)
               for s in spec[1::2])
    full, win = eng._groups
    assert (full.name, win.name) == ("full", "w1024")
    assert (len(full.pools), len(win.pools)) == (2, 6)
    assert full.num_pages == 24576 and not full.derived
    assert win.derived and win.num_pages == 1 + 64 * 66 + 128
    assert model.model.rope_cos_full_attention.shape == [16384, 64]
    rehearsal = dict(sizes, num_hidden_layers=2)
    assert ref.layer_kinds(rehearsal) == [S, F]
    with pytest.raises(ValueError, match="29 layers"):
        mellum.MellumConfig(num_hidden_layers=29)
    with pytest.raises(ValueError, match="no rope_parameters"):
        mellum.MellumConfig(rope_parameters={S: {"rope_theta": 1e4}})


def test_configuration_file_and_the_cuts_arithmetic():
    """Every published size the program's defaults carry is the file's
    but the depth; 417.7 M parameters a layer, 3.795 B at the cut (7.59
    GB in bf16), 12.15 B whole; the helpers of `gmm_roofline.swiglu`
    read the expert's width and the experts held from this file."""
    from benchmark import roofline_blocks, roofline_mixed
    sizes = _sizes()
    assert sizes["reduced"] == ["num_hidden_layers"]
    assert sizes["reduced_from"] == {"num_hidden_layers": 28}
    published = vars(mellum.MellumConfig())
    for key in ("vocab_size", "hidden_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "max_position_embeddings",
                "rms_norm_eps", "layer_types", "sliding_window",
                "rope_parameters", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "norm_topk_prob"):
        assert sizes[key] == published[key], key
    assert (sizes["num_hidden_layers"], published["num_hidden_layers"]) \
        == (8, 28)
    p = roofline_mixed.params_by_part(sizes)
    assert p["attention"] == 21233664 and p["router"] == 147456
    assert p["expert"] == 6193152 and p["experts"] == 64 * 6193152
    assert round(p["layer"] / 1e6, 1) == 417.7
    assert round((p["embedding"] + p["head"]) / 1e6, 1) == 453.0
    assert round(p["total"] / 1e9, 3) == 3.795
    assert round(2 * p["total"] / 1e9, 2) == 7.59
    whole = roofline_mixed.params_by_part(dict(sizes, num_hidden_layers=28))
    assert round(whole["total"] / 1e9, 2) == 12.15
    assert roofline_mixed.kv_row_bytes(sizes) == 2048
    assert roofline_blocks.expert_bytes(sizes) == 2 * 3 * 2304 * 896
    assert roofline_blocks.experts_a_dispatch(sizes) == 8 * 64
    # 10 dispatches of 512 held experts, 8 of them decode steps: the two
    # admissions are taken to hit all, the steps what is left
    assert roofline_blocks.hits_a_pass(sizes, 4600.0, 520.0, 8) \
        == (4600 - 2 * 512) / 8
    # a decode step of 64 slots hitting every expert, 6 windows of 1024
    # and 2 contexts of 3300 rows: 8.8 GB, 6.34 of them the experts
    step = roofline_mixed.decode_step_bytes(
        sizes, 64 * (6 * 1024 + 2 * 3300), 64, 8 * 64)
    assert round(step / 1e9, 1) == 8.8
    assert round(8 * 64 * roofline_blocks.expert_bytes(sizes) / 1e9, 2) \
        == 6.34


# -- the counters reach this model -------------------------------------
def test_counters_of_the_groups_and_the_expert_layers(monkeypatch):
    """One admission of a prompt longer than the window (30 rows in
    chunks of 8) and one decode step: the window group reclaims, both
    groups count the rows their layers read in both phases, the expert
    layers count their experts."""
    monkeypatch.setenv("PDT_TELEMETRY", "1")     # as conftest's fixture
    telemetry.reset()
    telemetry.clear_events()
    model, cfg = _model()
    eng, _ = _engine(model, max_batch_size=1)
    eng.add_request(_prompts(cfg, (30,))[0], max_new_tokens=2)
    eng.run()
    full, win = eng._groups
    val = telemetry.value
    assert val("pdt_serving_kv_pages_total", group="w8",
               kind="reclaimed") == win.reclaimed > 0
    rows = {(g, ph): val("pdt_serving_attn_kv_rows_total", group=g,
                         phase=ph)
            for g in ("full", "w8") for ph in ("admit", "decode")}
    # pieces of 8, 8, 8 and 6 rows: the full layer reads each piece's
    # context, a window layer (3 of them) a piece's rows and the 7 before
    assert rows["full", "admit"] == 8 + 16 + 24 + 30
    assert rows["w8", "admit"] == 3 * (8 + 15 + 15 + 13)
    assert rows["full", "decode"] == 31 and rows["w8", "decode"] == 3 * 8
    snap = telemetry.snapshot()["counters"]
    e = snap["pdt_serving_moe_experts_total"]
    # 4 admission dispatches and a decode step, 4 layers of 8 experts
    assert e['kind="hit"'] + e['kind="idle"'] == 5 * 4 * 8
    a = snap["pdt_serving_moe_assignments_total"]
    assert a['kind="local"'] == (30 + 1) * 2 * 4


@pytest.mark.parametrize("name,kw", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("spec_decode", dict(spec_decode=SpecConfig(draft_model=None, k=2))),
    ("harvest_every > 1", dict(harvest_every=2)),
    ("quant.kv", dict(quant=QuantServingConfig(kv="int8"))),
])
def test_unsupported_features_refuse_by_name(name, kw):
    model, _ = _model()
    with pytest.raises(ValueError) as e:
        ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                 page_size=4, **kw)
    assert name in str(e.value) and "page groups" in str(e.value)


# -- the cell's new per-layer metrics on a hand-made window ----------------
def _metric(name):
    import importlib
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader, spec["args"]


def test_mixed_readers_on_a_hand_made_window():
    """100 decode steps and 4 admissions of 512 held experts each (the
    admissions taken to hit all); decode read 64 x (6 x 1024 + 2 x
    3300) K and V rows a step, the admissions 40000 rows each; 6 steps
    and 1 admission fall in the last 4 s, which the trace covers."""
    from benchmark import roofline_mixed
    sizes = _sizes()
    hist = "pdt_serving_decode_step_seconds"
    a_step = 64 * (6 * 1024 + 2 * 3300)

    def snap(steps, seconds, hit, idle, rows_d, rows_a, alloc, back):
        return {"histograms": {hist: {"": {"sum": seconds,
                                           "count": steps}}},
                "counters": {
                    "pdt_serving_moe_experts_total": {
                        'kind="hit"': hit, 'kind="idle"': idle},
                    "pdt_serving_attn_kv_rows_total": {
                        'group="full",phase="decode"': rows_d * 2 / 8,
                        'group="w1024",phase="decode"': rows_d * 6 / 8,
                        'group="full",phase="admit"': rows_a / 2,
                        'group="w1024",phase="admit"': rows_a / 2},
                    "pdt_serving_kv_pages_total": {
                        'group="w1024",kind="allocated"': alloc,
                        'group="w1024",kind="reclaimed"': back,
                        'group="full",kind="allocated"': 5 * alloc}}}
    obs = {"telemetry": {
        "before": snap(10, 0.2, 5000.0, 120.0, 10 * a_step, 0.0, 100, 40),
        "after": snap(110, 2.2, 50000.0, 8368.0, 110 * a_step,
                      4 * 40000.0, 1100, 940)},
        "steps": [{"running_slots": 64, "live_context_tokens": 211200}],
        "model": sizes, "peaks": {"hbm_bytes_per_s": 819e9},
        "window_s": 40.0, "t_open": 100.0, "t_close": 140.0,
        "spans": [{"name": "serving.decode_step", "ts_mono": t,
                   "dur_s": 0.02}
                  for t in (136.2, 136.6, 137.0, 137.4, 137.8, 138.2)]
        + [{"name": "serving.ragged_prefill", "ts_mono": t, "dur_s": 0.03}
           for t in (110.0, 120.0, 130.0, 138.0)],
        "trace": {"window_s": 4.0, "ops_s": {
            "ragged_paged_attention": 0.05, "grouped_matmul": 0.2}}}
    hits = (45000 - 4 * 512) / 100
    reader, args = _metric("mixed_decode_floor_share")
    floor = roofline_mixed.decode_step_bytes(sizes, a_step, 64, hits)
    assert reader.read(obs, **args) == pytest.approx(
        100 * floor / 819e9 / 0.02)
    reader, args = _metric("attn_kv_roofline.head_dim")
    read_rows = 6 * a_step + 1 * 40000
    assert reader.read(obs, **args) == pytest.approx(
        100 * read_rows * 2048 / 819e9 / 0.05)
    reader, args = _metric("window_kv_reclaimed_share.w1024")
    assert reader.read(obs, **args) == pytest.approx(90.0)
    # a program without the counters (the parent): nothing to read
    empty = {"telemetry": {"before": {}, "after": {}}, "steps": [],
             "spans": [], "window_s": 1.0, "t_close": 1.0,
             "model": sizes, "peaks": {"hbm_bytes_per_s": 819e9},
             "trace": {"window_s": 1.0, "ops_s": {}}}
    for name in ("mixed_decode_floor_share", "attn_kv_roofline.head_dim",
                 "window_kv_reclaimed_share.w1024"):
        reader, args = _metric(name)
        assert reader.read(empty, **args) is None
