"""The engine's page GROUPS (models/serving.py, models/cache_spec.py)
under Phi-4-mini-flash (models/phi4flash.py): window layers, a full
layer and layers that share the full layer's keys and values, beside
Mamba-1 state, in one cache manager: what the groups hold, the
invariants over them, what is refused by name, the counters, and that a
model with one group runs the programs it ran (tests/test_phi4flash.py
has the model against its reference, and the helpers used here). Tiny
widths, CPU, float32: a window (8) shorter than the prompts, a page (4)
shorter than the window, chunks (8) shorter than the prompts."""
import hashlib
import os
import sys
import types

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
import paddle_tpu.observability as telemetry                  # noqa: E402
from paddle_tpu.models.cache_spec import KVSpec, SharedKVSpec  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.serving import (ContinuousBatchingEngine,  # noqa: E402
                                       EngineInvariantError,
                                       QuantServingConfig, SpecConfig)
from test_phi4flash import _engine, _model, _prompts            # noqa: E402


def _step_args(eng, t):
    def i32(*s):
        return jnp.zeros(s, jnp.int32)
    return (eng._pv(), eng._bv(), eng._cache(), i32(t), i32(t), i32(t),
            i32(eng.B), i32(eng.B), i32(eng.B), eng._tables(), i32(eng.B),
            jax.random.PRNGKey(0))


# -- (c) what the groups hold --------------------------------------------
def test_window_group_holds_a_window_and_a_dispatch_the_full_group_grows():
    """A 50-token prompt in chunks of 8, then decode: a slot never holds
    more window pages than the window's, the straddling one, the one
    being written and a dispatch's rows' (2 + 2 + 2 at a page of 4),
    whatever its length, and between dispatches two fewer; the full
    group holds every page of the sequence."""
    model, cfg = _model()
    eng, _ = _engine(model, max_batch_size=2)
    full, win = eng._groups
    assert win.derived and win.steady == 4 and not full.derived
    seen = {"win": 0, "full": 0, "between": 0}
    dispatch = eng._dispatch_ragged

    def watched(batch, finished):
        for g in (full, win):     # a derived group allocates here
            pass
        freed = dispatch(batch, finished)
        seen["between"] = max(seen["between"], max(
            len(p) for p in win.slot_pages))
        return freed

    alloc = eng._alloc_page

    def counted(slot, g=None):
        page = alloc(slot, g)
        seen["win"] = max(seen["win"], max(len(p) for p in win.slot_pages))
        seen["full"] = max(seen["full"],
                           max(len(p) for p in full.slot_pages))
        return page

    eng._dispatch_ragged, eng._alloc_page = watched, counted
    rid = eng.add_request(_prompts(cfg, (50,))[0], max_new_tokens=10)
    other = eng.add_request(_prompts(cfg, (5,), seed=1)[0],
                            max_new_tokens=4)
    out = eng.run()
    assert len(out[rid]) == 10 and len(out[other]) == 4
    assert seen["win"] <= win.steady + 8 // 4
    assert seen["between"] <= win.steady - 1
    assert seen["full"] == -(-(50 + 10) // 4)
    assert win.reclaimed > 0 and full.reclaimed == 0
    assert win.allocated == win.reclaimed + 5     # released, not slid out
    eng.check_invariants()


# -- (h) the invariants run over the groups --------------------------------
def test_invariants_catch_a_window_page_left_below_the_window():
    model, cfg = _model()
    eng, _ = _engine(model, max_batch_size=2)
    eng.add_request(_prompts(cfg, (30,))[0], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    eng.check_invariants()
    full, win = eng._groups
    assert win.slot_freed[0] > 0 and full.slot_freed[0] == 0
    # a reclaimed page put back as if reclamation had skipped it
    j = int(win.slot_freed[0]) - 1
    page = win.free.pop()
    win.page_rc[page] = 1
    win.slot_pages[0].append(page)
    win.bt[0, j] = page
    win.slot_freed[0] = j
    with pytest.raises(EngineInvariantError,
                       match=r"group w8: slot 0 block-table\[\d+\] is "
                             "still allocated wholly below the window"):
        eng.check_invariants()
    win.slot_freed[0] = j + 1
    with pytest.raises(EngineInvariantError,
                       match="group w8: .*outside the live window"):
        eng.check_invariants()
    win.bt[0, j] = 0
    win.slot_pages[0].remove(page)
    win.page_rc[page] = 0
    win.free.append(page)
    eng.check_invariants()
    # a page of the FULL group lost from its free list is a leak there
    leaked = full.free.pop()
    with pytest.raises(EngineInvariantError,
                       match=f"group full: page {leaked} LEAKED"):
        eng.check_invariants()
    full.free.append(leaked)
    info = eng.cache_memory_info()["groups"]
    assert set(info) == {"full", "w8"} and info["w8"]["pages_in_use"] > 0


# -- (g) what is refused for a model with page groups, by name ------------
def _refused(**kw):
    model, _ = _model()
    return ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=64,
                                    page_size=4, **kw)


@pytest.mark.parametrize("name,kw", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("spec_decode", dict(spec_decode=SpecConfig(draft_model=None, k=2))),
    ("quant.kv", dict(quant=QuantServingConfig(kv="int8"))),
    ("harvest_every > 1", dict(harvest_every=2)),
    ("submesh tp > 1", dict(submesh=types.SimpleNamespace(tp=2))),
])
def test_unsupported_features_refuse_by_name(name, kw):
    with pytest.raises(ValueError) as e:
        _refused(**kw)
    assert name in str(e.value)


@pytest.mark.parametrize("call", [
    lambda e: e.export_pages(0),
    lambda e: e.import_pages({}),
    lambda e: e.import_prefix([[1] * 8], [], []),
])
def test_page_only_methods_refuse_by_name(call):
    with pytest.raises(ValueError, match="state layers|page groups"):
        call(_refused())


class _TwoGeometries:
    """Window and full layers WITHOUT a state layer: the refusals are
    the page groups' own."""

    def __init__(self, spec):
        paddle.seed(0)
        self._m = LlamaForCausalLM(LlamaConfig.tiny())
        self.config, self._spec = self._m.config, spec

    def cache_spec(self):
        return self._spec

    def __getattr__(self, name):
        return getattr(self._m, name)


@pytest.mark.parametrize("name,kw", [
    ("enable_prefix_caching", dict(enable_prefix_caching=True)),
    ("harvest_every > 1", dict(harvest_every=2)),
    ("quant.kv", dict(quant=QuantServingConfig(kv="int8"))),
])
def test_page_groups_refuse_without_a_state_layer(name, kw):
    spec = [KVSpec(2, 32, window=8), KVSpec(2, 32)]
    with pytest.raises(ValueError, match="page groups") as e:
        ContinuousBatchingEngine(_TwoGeometries(spec), max_seq_len=64, **kw)
    assert name in str(e.value)
    with pytest.raises(ValueError, match="not an earlier KVSpec layer"):
        ContinuousBatchingEngine(
            _TwoGeometries([SharedKVSpec(1), KVSpec(2, 32)]),
            max_seq_len=64)
    # two geometries are two groups now, not an error
    eng = ContinuousBatchingEngine(
        _TwoGeometries([KVSpec(2, 32), KVSpec(1, 64)]), max_seq_len=64)
    assert [g.name for g in eng._groups] == ["full", "full"]
    with pytest.raises(ValueError, match="page groups"):
        eng.export_pages(0)


# -- the counters -----------------------------------------------------------
def test_group_counters_and_the_sampled_rows_attribute(monkeypatch):
    monkeypatch.setenv("PDT_TELEMETRY", "1")     # as conftest's fixture
    telemetry.reset()
    telemetry.clear_events()
    model, cfg = _model()
    eng, _ = _engine(model, max_batch_size=2)
    eng.add_request(_prompts(cfg, (30,))[0], max_new_tokens=6)
    eng.add_request(_prompts(cfg, (5,), seed=1)[0], max_new_tokens=6)
    eng.run()
    full, win = eng._groups
    val = telemetry.value
    for g in (full, win):
        assert val("pdt_serving_kv_pages_total", group=g.name,
                   kind="allocated") == g.allocated > 0
        assert val("pdt_serving_kv_pages_total", group=g.name,
                   kind="reclaimed") == g.reclaimed
    assert win.reclaimed > 0 and full.reclaimed == 0
    assert val("pdt_serving_group_page_occupancy", group="w8") == 0.0
    assert val("pdt_serving_page_occupancy") == 0.0
    # admission in chunks of 8: the long prompt's 8, 8, 8 and 6 rows,
    # the short one's first 2 beside the 6, its last 3 alone. The
    # full layer reads each piece's whole context, the layer that
    # shares it the contexts of the slots that sample (30, then 5);
    # a window layer a piece's rows and the 7 before them
    admit = {g: val("pdt_serving_attn_kv_rows_total", group=g,
                    phase="admit") for g in ("full", "w8")}
    assert admit["full"] == (8 + 16 + 24 + 30 + 2 + 5) + (30 + 5)
    assert admit["w8"] == 2 * (8 + 15 + 15 + 13 + 2 + 5)
    # decode, 5 steps: contexts 31..35 and 6..10, a window of 8
    decode = {g: val("pdt_serving_attn_kv_rows_total", group=g,
                     phase="decode") for g in ("full", "w8")}
    assert decode["full"] == 2 * sum(range(31, 36)) \
        + 2 * sum(range(6, 11))
    assert decode["w8"] == 2 * (5 * 8 + 6 + 7 + 8 + 8 + 8)
    # 8 layers; behind layer 5 an admission runs slots, not rows
    run = val("pdt_serving_prefill_layer_rows_total", kind="run")
    skipped = val("pdt_serving_prefill_layer_rows_total",
                  kind="skipped")
    rows = 8 + 8 + 8 + 16 + 8
    assert skipped == (rows - 5 * 2) * 2 and run + skipped == rows * 8
    spans = [e for e in telemetry.events()
             if e.get("name") == "serving.ragged_prefill"]
    assert [e["attrs"]["rows_sampled"] for e in spans] \
        == [0, 0, 0, 1, 1]


# -- (f) a model with one group runs the programs it ran -----------------
def _one_group_models():
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)
    from paddle_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
    paddle.seed(0)
    yield "llama", LlamaForCausalLM(LlamaConfig.tiny())
    cfg = LlamaConfig.tiny()
    cfg.sliding_window = 16
    yield "llama_window", LlamaForCausalLM(cfg)
    yield "nemotron_h", NemotronHForCausalLM(NemotronHConfig.tiny())
    yield "sdar", SdarMoeForCausalLM(SdarMoeConfig.tiny())


# sha256 of the step programs' jaxprs, taken on the parent tree (PR 34)
# under `default_matmul_precision("highest")`, as tests/conftest.py sets
# it (the precision is part of a jaxpr's text)
JAXPR_OF_THE_PARENT = {
    ("llama", "decode"):
    "a329855bdd4f8ba2a4122d16112556596951d3224b6a0bbc0cd1bf1ba81d5760",
    ("llama", "admit"):
    "6327ace17e06b3d3ff351d554e1acc5d91637a24a69a3533cd8f28d664973771",
    ("llama_window", "decode"):
    "3f9f68d9c1e818d887debcd0accfbe0d038f4f4f706cc3e480f57c1495a5e878",
    ("llama_window", "admit"):
    "fc66b8fde88c6eddba5b0e239eb8d1543212f7f9510ba48be4b02b59061f82af",
    ("nemotron_h", "decode"):
    "bd1a06c1052e013594d231795dfa383365efd01721450841fd164dc4f2b01b46",
    ("nemotron_h", "admit"):
    "c505b02b667d63dde4871a0a25339d332285f69a4eb5b581232a18f3607194fc",
    ("sdar", "decode"):
    "c62ca46338479f5563ddb78fcddeb961a78df4bca874930e38e3b0d499e9d0f9",
    ("sdar", "admit"):
    "20fb16ec1ccd687b52cad130cc8ef14ff95a5fb5a7104c5edf0127c5cdc80557",
}


@pytest.mark.parametrize("which", ["llama", "llama_window", "nemotron_h",
                                   "sdar"])
def test_one_group_models_trace_to_the_parents_programs(which):
    """The decode and admission programs of every model the benchmark
    already runs (and of a one-window model) trace to the jaxpr they had
    before page groups, operation for operation; the engine's own names
    for its one group's arrays are the group's arrays."""
    model = dict(_one_group_models())[which]
    model.eval()
    eng = ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=32,
                                   page_size=8, prompt_pad=8)
    (group,) = eng._groups
    assert eng._bt is group.bt and eng._free is group.free \
        and eng._page_rc is group.page_rc and not group.derived
    assert isinstance(eng._tables(), jax.Array)
    for name, bq, t, bound in (("decode", 1, eng.B, None),
                               ("admit", 8, 16, 2)):
        with jax.default_matmul_precision("highest"):
            text = str(jax.make_jaxpr(eng._build_ragged_step(bq, bound))(
                *_step_args(eng, t)))
        assert hashlib.sha256(text.encode()).hexdigest() \
            == JAXPR_OF_THE_PARENT[which, name], (which, name)
