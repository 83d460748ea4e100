#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the published widths of one model the repo supports (Llama-3.2-1B;
depth and batch are the only cuts, each printed with its reason; the
weights are random, made from a seed):

1. gate   — `paddle_tpu.device.require_tpu()`: a TPU or exit non-zero;
2. train  — `LlamaForCausalLM` -> `AdamW(multi_precision=True)` ->
            `paddle.jit.TrainStep` at sequence 2048, a few steps;
3. serve  — `ServingRouter(num_replicas=1)` over
            `ContinuousBatchingEngine` (paged + ragged, prefix caching
            on) at full depth, a dozen greedy requests, twice; then one
            logits check against the model's own float32 forward with
            kernels off;
4. four chips (when `jax.device_count() >= 4`) — the train step under
            `create_mesh(sharding=2, mp=2)` + `shard_llama`, then
            `ServingRouter(num_replicas=4, tp=1)` and
            `ServingRouter(num_replicas=2, tp=2)`.

ONE process: a chip belongs to one process, so nothing here starts a
child. Any phase that fails raises and the run exits non-zero; no
phase's exception is caught and carried past. **Anything the serving
plane healed is a failure of the smoke** (`healed_failures`). The last
line of stdout is one JSON object, `{"ok": true, "device": {...}}`, the
device as JAX reports it and nothing else; the line before it,
`[summary] {...}`, carries what the phases measured.

The phases are functions with size arguments so that
tests/test_chip_smoke.py can drive each at `LlamaConfig.tiny()` on the
CPU. A number this script prints is a smoke reading (one run, compile
included where it says so), never a benchmark figure.
"""
from __future__ import annotations

import gc
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.observability as telemetry
from paddle_tpu.autograd import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.device import enable_compile_cache, require_tpu
from paddle_tpu.models.generation import RequestStatus, bind_state
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     shard_llama, synthetic_lm_batch)
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.observability.profile import memory_ledger
from paddle_tpu.ops import mosaic_kernels, xla_reference
from paddle_tpu.optimizer import AdamW
from paddle_tpu.serving.replica import ReplicaState
from paddle_tpu.serving.router import ServingRouter

TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "rms_norm_fwd", "rms_norm_bwd")
SERVE_KERNEL = "ragged_paged_attention"

# The prefix cache holds `max_prefix_entries` = 32 pages of `page_size`
# = 16 tokens (the engine's defaults), and registers at most that many
# leading pages of a finished prompt. A shared prefix of exactly that
# size makes the cache state a FIXED POINT of a pass (see `phase_serve`).
SHARED_PREFIX_TOKENS = 32 * 16

# (shares the prefix, prompt tokens, new tokens) — the serve traffic at
# max_seq_len 2048 with 8 slots. Prompt lengths of 41-1499, none a
# multiple of 16 or 128; 64-128 new tokens; 12 requests for 8 slots, so
# four admissions land in the middle of decoding. Request 0 has the
# smallest budget (it frees the first slot) and shares the 512-token
# prefix with the LAST request, which has the largest budget and so
# finishes last.
REQUESTS_2048 = (
    (True, 535, 64), (False, 41, 96), (False, 1499, 72),
    (False, 203, 120), (False, 777, 80), (False, 1203, 88),
    (False, 350, 104), (False, 97, 112),
    (False, 613, 100), (False, 59, 90), (False, 1001, 70),
    (True, 813, 128),
)


class SmokeFailure(RuntimeError):
    """A phase ran to an end and its result is wrong."""


# JAX reports every program it lowers (a compile, or a persistent-cache
# hit, starts with one); a warm train step must cause none
_LOWERED = [0]


def _count_lowering(event: str, duration_secs: float, **_) -> None:
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _LOWERED[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_lowering)


def llama_3_2_1b(num_layers: int = 16) -> LlamaConfig:
    """Llama-3.2-1B at its published widths (the model's public
    config.json: 16 layers, hidden 2048, intermediate 8192, 32 Q / 8 KV
    heads of 64, vocab 128256, tied embeddings, rope theta 500000, rms
    eps 1e-5, bf16). Its `rope_scaling` is not modelled: the smoke
    checks the system against its own float32 oracle, not against the
    public checkpoint. `num_layers` is the depth cut."""
    return LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=num_layers, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=131072,
        rms_norm_eps=1e-5, rope_theta=500000.0,
        tie_word_embeddings=True, dtype="bfloat16")


def build_model(cfg: LlamaConfig, seed: int) -> LlamaForCausalLM:
    """Seeded random weights in the configuration's dtype."""
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype != "float32":
        model.to(dtype=cfg.dtype)
    return model


def device_memory(where: str, devices) -> list:
    """Per device `memory_stats()` bytes in use and peak (the peak is
    since process start; the backend keeps no per-phase peak), printed
    and returned. A device that reports stats and holds nothing fails:
    it was given work and did none. (The CPU backend reports none.)"""
    out = []
    for d in devices:
        st = d.memory_stats()
        if st is None:
            continue
        m = {"id": int(d.id), "bytes_in_use": int(st["bytes_in_use"]),
             "peak_bytes_in_use": int(st["peak_bytes_in_use"])}
        print(f"  {where}: device {m['id']} holds "
              f"{_gb(m['bytes_in_use'])}, peak since start "
              f"{_gb(m['peak_bytes_in_use'])}", flush=True)
        if not m["bytes_in_use"]:
            raise SmokeFailure(f"{where}: nothing in use on device "
                               f"{m['id']}")
        out.append(m)
    return out


def _gb(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def _bytes_in_use() -> int:
    """Bytes in use summed over every device that reports stats."""
    return sum(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices())


def _require_kernels(where: str, found: dict, expected) -> None:
    missing = [k for k in expected if not found.get(k)]
    if missing:
        raise SmokeFailure(
            f"{where}: Mosaic kernels {missing} are missing from the "
            f"compiled program (found {found}) — a dispatcher gave way "
            "to its reference on the chip")


def _require_on(where: str, array, devices) -> None:
    want = {int(d.id) for d in devices}
    got = {int(d.id) for d in array.sharding.device_set}
    if got != want:
        raise SmokeFailure(
            f"{where}: lives on devices {sorted(got)}, was given "
            f"{sorted(want)}")


# ---------------------------------------------------------------------------
# phase 2 / 4(a): the train step
# ---------------------------------------------------------------------------
def _drive_train_step(step, ids, labels, steps: int, mesh_devices,
                      expect_kernels: bool) -> dict:
    """Lower the step (which kernels does it hold?), run it `steps`
    times synced, then twice queued; fail on a loss that is not finite
    or did not fall, on a second compile, and on a `block_until_ready`
    that did not wait."""
    kernels = mosaic_kernels(step.lower(ids, labels).as_text())
    if expect_kernels:
        _require_kernels("train step", kernels, TRAIN_KERNELS)
    if mesh_devices is not None:
        # lowering created the optimizer state: it must be spread like
        # its parameters BEFORE the first step loads, not parked whole
        # on the first device
        opt = step.optimizer
        for arr in [*opt._master_weights.values(),
                    *(a for store in opt._accumulators.values()
                      for a in store.values())]:
            _require_on("optimizer state", arr, mesh_devices)
    t0 = time.perf_counter()
    first = step(ids, labels)
    jax.block_until_ready(first._value)
    first_step_s = time.perf_counter() - t0
    losses = [float(first)]
    lowered = _LOWERED[0]
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        loss = step(ids, labels)
        jax.block_until_ready(loss._value)
        losses.append(float(loss))
    warm_step_s = (time.perf_counter() - t0) / max(steps - 1, 1)
    # does block_until_ready wait on this backend? Queue two steps,
    # block on the last, then fetch: the fetch of a value that is ready
    # costs a transfer, not a step
    step(ids, labels)
    loss = step(ids, labels)
    t0 = time.perf_counter()
    jax.block_until_ready(loss._value)
    blocked_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses.append(float(loss))
    fetch_s = time.perf_counter() - t0
    if _LOWERED[0] != lowered:
        raise SmokeFailure(
            f"train: {_LOWERED[0] - lowered} program(s) were lowered "
            "after the first step — the step compiled again (its state "
            "came back with other shardings than it went in?)")
    if not all(np.isfinite(losses)):
        raise SmokeFailure(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(
            f"train: loss did not fall over {len(losses)} steps: "
            f"{losses}")
    if fetch_s > max(0.05, 0.25 * blocked_s):
        raise SmokeFailure(
            f"train: block_until_ready returned after {blocked_s:.3f}s "
            f"but the fetch behind it took {fetch_s:.3f}s — it did not "
            "wait for the device")
    return {"steps": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1],
            "first_step_s": round(first_step_s, 3),
            "warm_step_s": round(warm_step_s, 4),
            "block_until_ready_s": round(blocked_s, 4),
            "fetch_after_block_s": round(fetch_s, 5),
            "mosaic_kernels": kernels}


def phase_train(cfg: LlamaConfig, *, batch: int, seq: int, steps: int,
                mesh_axes: dict | None = None, seed: int = 0,
                expect_kernels: bool = True) -> dict:
    """`LlamaForCausalLM` -> `AdamW(multi_precision=True)` ->
    `paddle.jit.TrainStep`, as recipes/llama_pretrain.py does it; with
    `mesh_axes` the same step under `dist.create_mesh(**mesh_axes)` +
    `shard_llama`. Every step is synced with `jax.block_until_ready`;
    the loss must be finite and lower after the steps than before."""
    t_phase = time.perf_counter()
    model = build_model(cfg, seed)
    ids, labels = synthetic_lm_batch(batch, seq, cfg.vocab_size,
                                     seed=seed)

    def build_step():
        opt = AdamW(learning_rate=3e-4, parameters=model.parameters(),
                    weight_decay=0.01, multi_precision=True)
        return paddle.jit.TrainStep(
            model, opt, loss_fn=lambda m, x, y: m(x, labels=y)[0])

    if mesh_axes:
        mesh = dist.create_mesh(**mesh_axes)
        devices = list(mesh.jax_mesh.devices.flat)
        with dist.use_mesh(mesh):
            shard_llama(model, mesh)
            pl = [dist.Shard(0)] + [dist.Replicate()] * (
                len(mesh.dim_names) - 1)
            res = _drive_train_step(
                build_step(), dist.shard_tensor(ids, mesh, pl),
                dist.shard_tensor(labels, mesh, pl), steps, devices,
                expect_kernels)
        for name, p in model.named_parameters():
            _require_on(f"sharded parameter {name}", p._value, devices)
    else:
        devices = jax.devices()[:1]
        res = _drive_train_step(build_step(), ids, labels, steps, None,
                                expect_kernels)
    out = {"layers": cfg.num_hidden_layers, "batch": batch, "seq": seq,
           "mesh": mesh_axes, "params": cfg.num_params(), **res}
    print(f"  train: {cfg.num_hidden_layers} layers, batch {batch} x seq "
          f"{seq}, mesh {mesh_axes}: loss {out['loss_first']:.4f} -> "
          f"{out['loss_last']:.4f} over {out['steps']} steps", flush=True)
    print(f"  train: first step (trace + compile + run) "
          f"{out['first_step_s']:.1f}s, warm step "
          f"{out['warm_step_s'] * 1e3:.1f} ms with 0 programs lowered "
          f"after the first; block_until_ready waited "
          f"{out['block_until_ready_s'] * 1e3:.1f} ms, the fetch behind "
          f"it {out['fetch_after_block_s'] * 1e3:.2f} ms", flush=True)
    print(f"  train: Mosaic kernels in the step: "
          f"{out['mosaic_kernels']}", flush=True)
    out["memory"] = device_memory("train", devices)
    out["wall_s"] = round(time.perf_counter() - t_phase, 2)
    return out


# ---------------------------------------------------------------------------
# phase 3 / 4(b) / 4(c): the serving fleet
# ---------------------------------------------------------------------------
def make_requests(table, vocab_size: int, seed: int):
    """[(prompt ids, new tokens)] for a request table, plus the primer
    prompt: the shared prefix followed by a tail of its own. Token 0 is
    never drawn."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab_size, SHARED_PREFIX_TOKENS).tolist()
    reqs = []
    for shares, p_len, new in table:
        if shares:
            prompt = shared + rng.integers(
                1, vocab_size, p_len - len(shared)).tolist()
        else:
            prompt = rng.integers(1, vocab_size, p_len).tolist()
        reqs.append((prompt, new))
    primer = shared + rng.integers(1, vocab_size, 37).tolist()
    return reqs, primer


def healed_failures(router: ServingRouter) -> list:
    """Everything the serving plane is built to survive and a smoke
    must not: an exception in a replica's step, dispatch or health
    probe (degraded -> dead -> restart), an admission the engine
    isolated into one FAILED request, a retried decode dispatch, a
    preemption, a timeout, a failover, a quarantine. Each entry carries
    the first traceback the plane kept, not its summary."""
    found = []
    for h in router.replicas:
        if h.last_traceback:
            found.append(f"replica {h.index} raised (state {h.state}, "
                         f"{h.consecutive_failures} consecutive "
                         f"failures):\n{h.last_traceback}")
        elif h.state != ReplicaState.HEALTHY or h.restarts:
            found.append(f"replica {h.index} is {h.state} after "
                         f"{h.restarts} restarts ({h.death_reason})")
        eng = h.engine
        if eng is None:
            continue
        counts = {n: getattr(eng, n) for n in
                  ("num_failures", "num_decode_retries",
                   "num_preemptions", "num_timeouts")}
        if any(counts.values()):
            found.append(f"replica {h.index} engine healed {counts}:\n"
                         f"{eng.last_failure}")
    counts = {n: getattr(router, n) for n in
              ("num_failovers", "num_restarts", "num_quarantines")}
    if any(counts.values()):
        found.append(f"router healed {counts}")
    return found


def run_pass(router: ServingRouter, reqs, tag: str) -> dict:
    """Submit every request, step the fleet until all are terminal,
    and fail on the first thing the plane healed. Returns
    {request_id: tokens}."""
    budget = {}
    for i, (prompt, new) in enumerate(reqs):
        budget[router.submit(prompt, max_new_tokens=new,
                             request_id=f"{tag}-{i}")] = new
    done = {}
    # every step emits at least one token while anything is live
    for _ in range(sum(budget.values()) + 16):
        for rec in router.step():
            done[rec.request_id] = rec
        healed = healed_failures(router)
        if healed:
            raise SmokeFailure(f"serve {tag}: the plane healed a "
                               "failure:\n" + "\n".join(healed))
        if len(done) == len(budget):
            break
    else:
        raise SmokeFailure(f"serve {tag}: {len(budget) - len(done)} "
                           "requests still live at the step bound")
    for rid, new in budget.items():
        rec = done[rid]
        if rec.status != RequestStatus.FINISHED or rec.failovers \
                or len(rec.tokens) != new:
            raise SmokeFailure(
                f"serve {tag}: request {rid} ended {rec.status} with "
                f"{len(rec.tokens)}/{new} tokens, {rec.failovers} "
                f"failovers, error {rec.error!r}")
    return {rid: list(done[rid].tokens) for rid in budget}


def _counter_by_labels(snap: dict, name: str) -> dict:
    """{(label values...): value} of one counter family."""
    return {tuple(re.findall(r'"([^"]*)"', labels)): v
            for labels, v in snap.get("counters", {}).get(name,
                                                          {}).items()}


class _LogitRecorder:
    """Sentry-shaped recorder (`attach_sentry` contract): the decode
    program then returns its sampled-row logits and every step's rows
    are pulled to the host."""
    wants_logits = True
    trips = 0

    def __init__(self):
        self.rows = []

    def step_tick(self):
        return True

    def observe_tokens(self, toks):
        pass

    def observe_logits(self, lg):
        self.rows.append(np.asarray(lg, np.float32))

    def note_cost(self, seconds):
        pass


def reference_logits(model, ids) -> np.ndarray:
    """The float32 oracle: the model's plain full forward (no cache, no
    batching) over its own weights upcast to float32, kernels off
    (`ops.xla_reference`) and `default_matmul_precision("highest")` —
    on a TPU a float32 matmul otherwise runs in bf16 passes."""
    params, buffers = list(model.parameters()), list(model.buffers())

    def f32(v):
        return v.astype(jnp.float32) \
            if jnp.issubdtype(v.dtype, jnp.floating) else v

    def run(pv, bv, tokens):
        with bind_state(params, buffers, pv, bv), no_grad():
            return model.forward(Tensor(tokens))._value

    with xla_reference(), jax.default_matmul_precision("highest"):
        out = jax.jit(run)([f32(p._value) for p in params],
                           [f32(b._value) for b in buffers],
                           jnp.asarray(ids, jnp.int32)[None])
    return np.asarray(out[0], np.float32)


def logits_check(model, factory, *, prompt_len: int, steps: int,
                 seed: int, tolerance: float) -> dict:
    """One prompt prefilled and decoded `steps` tokens through the
    paged cache (ragged prefill at block_q=8, scatter, ragged decode at
    block_q=1), each decode step's logits against `reference_logits`
    over prompt + generated tokens. The error is the largest absolute
    difference divided by the standard deviation of the reference's
    logits, so the tolerance does not depend on the weights' scale."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, model.config.vocab_size,
                          prompt_len).tolist()
    eng = factory(0)
    rec = _LogitRecorder()
    eng.attach_sentry(rec)
    rid = eng.add_request(prompt, max_new_tokens=steps + 1)
    tokens = eng.run()[rid]
    if eng.num_failures or eng.num_decode_retries:
        raise SmokeFailure(f"logits check: the engine healed a "
                           f"failure:\n{eng.last_failure}")
    got = np.stack([r[0] for r in rec.rows[:steps]])
    # decode step j consumed generated token j at position
    # prompt_len + j: row prompt_len + j of the full forward
    ref = reference_logits(model, prompt + tokens[:steps])
    ref = ref[prompt_len:prompt_len + steps]
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise SmokeFailure("logits check: non-finite logits")
    err = float(np.max(np.abs(got - ref)) / np.std(ref))
    out = {"prompt_len": prompt_len, "steps": steps,
           "max_abs_err_over_ref_std": round(err, 5),
           "mean_abs_err_over_ref_std": round(
               float(np.mean(np.abs(got - ref)) / np.std(ref)), 6),
           "ref_std": round(float(np.std(ref)), 4),
           "argmax_agree": int(np.sum(got.argmax(-1) == ref.argmax(-1))),
           "tolerance": tolerance}
    print(f"  serve: logits check {out}", flush=True)
    if err > tolerance:
        raise SmokeFailure(f"logits check outside its tolerance: {out}")
    return out


def phase_serve(cfg: LlamaConfig, *, table, max_seq_len: int,
                num_replicas: int = 1, tp: int | None = None,
                slots: int = 8, second_pass: bool = True,
                logits_tolerance: float | None = None,
                logits_prompt_len: int = 299, logits_steps: int = 6,
                seed: int = 0, expect_kernels: bool = True) -> dict:
    """`ServingRouter` over `ContinuousBatchingEngine` with its
    defaults (paged + ragged), prefix caching on. With `second_pass`
    the fleet first serves a primer that leaves the shared prefix in
    the prefix cache; every pass then starts AND ends with exactly that
    prefix cached (the last request to finish re-registers it and its
    32 pages evict everything else), so the second pass packs the same
    batches as the first and must compile nothing."""
    t_phase = time.perf_counter()
    model = build_model(cfg, seed)
    model.eval()

    def factory(index, submesh=None):
        return ContinuousBatchingEngine(
            model, max_batch_size=slots, max_seq_len=max_seq_len,
            enable_prefix_caching=True, submesh=submesh)

    reqs, primer = make_requests(table, cfg.vocab_size, seed)
    telemetry.enable()
    telemetry.reset()
    try:
        held = _bytes_in_use()
        router = ServingRouter(factory, num_replicas=num_replicas, tp=tp)
        # what building the fleet took on the devices: the page pools,
        # plus each TP/placed replica's own copy of the weights
        fleet_bytes = _bytes_in_use() - held
        engines = [h.engine for h in router.replicas]
        for h in router.replicas:
            devices = h.submesh.devices if h.submesh is not None \
                else jax.devices()[:1]
            for layer in h.engine._kv:
                for pool in layer:
                    _require_on(f"replica {h.index} page pool", pool,
                                devices)
        t0 = time.perf_counter()
        if second_pass:
            run_pass(router, [(primer, 8)], "primer")
        first = run_pass(router, reqs, "pass1")
        cold_s = time.perf_counter() - t0
        snap1 = telemetry.snapshot()
        compiles = _counter_by_labels(snap1, "pdt_jit_compiles_total")
        compile_s = sum(
            s["sum"] for s in snap1.get("histograms", {}).get(
                "pdt_jit_compile_seconds", {}).values())
        kernels = {}
        for (family, kernel), n in _counter_by_labels(
                snap1, "pdt_jit_mosaic_kernels_total").items():
            kernels.setdefault(family, {})[kernel] = int(n)
        if expect_kernels:
            for family in ("ragged", "decode"):
                _require_kernels(f"engine {family} programs",
                                 kernels.get(family, {}),
                                 (SERVE_KERNEL,))
        out = {
            "layers": cfg.num_hidden_layers, "replicas": num_replicas,
            "tp": tp, "slots": slots, "max_seq_len": max_seq_len,
            "requests": len(reqs),
            "prompt_tokens": sum(len(p) for p, _ in reqs),
            "new_tokens": sum(n for _, n in reqs),
            "first_pass_s": round(cold_s, 2),
            "first_pass_compile_s": round(compile_s, 2),
            "compiles": {k[0]: int(v) for k, v in compiles.items()},
            "mosaic_kernels": kernels,
            "prefix_hits": sum(e.prefix_hits for e in engines),
        }
        print(f"  serve: {num_replicas} replica(s), tp {tp}, "
              f"{cfg.num_hidden_layers} layers, {len(reqs)} requests "
              f"({out['prompt_tokens']} prompt + {out['new_tokens']} "
              f"new tokens) all FINISHED; 0 failed, 0 retries, 0 "
              f"restarts, 0 preemptions", flush=True)
        print(f"  serve: first pass {cold_s:.1f}s of which "
              f"{compile_s:.1f}s in {out['compiles']} first calls "
              f"(trace + compile + run); prefix hits "
              f"{out['prefix_hits']}", flush=True)
        print(f"  serve: Mosaic kernels by program family: {kernels}",
              flush=True)
        if second_pass:
            t0 = time.perf_counter()
            second = run_pass(router, reqs, "pass2")
            warm_s = time.perf_counter() - t0
            snap2 = telemetry.snapshot()
            after = _counter_by_labels(snap2, "pdt_jit_compiles_total")
            delta = {k[0]: int(v - compiles.get(k, 0))
                     for k, v in after.items()
                     if v != compiles.get(k, 0)}
            if delta:
                raise SmokeFailure(
                    f"serve: the second pass compiled {delta}")
            same = sum(first[f"pass1-{i}"] == second[f"pass2-{i}"]
                       for i in range(len(reqs)))
            out.update(second_pass_s=round(warm_s, 2),
                       second_pass_compiles=0,
                       streams_equal_across_passes=same)
            print(f"  serve: second pass {warm_s:.1f}s, 0 compiles; "
                  f"{same}/{len(reqs)} token streams equal to the "
                  f"first pass", flush=True)
        ledger = memory_ledger(engines)
        out["memory"] = device_memory(
            "serve", [d for h in router.replicas for d in (
                h.submesh.devices if h.submesh is not None
                else jax.devices()[:1])])
        out["ledger"] = {k: int(v) for k, v in ledger.items()}
        out["fleet_bytes_on_devices"] = fleet_bytes
        print(f"  serve: pdt_mem_bytes ledger "
              f"{ {k: _gb(v) for k, v in ledger.items()} }; building "
              f"the fleet took {_gb(fleet_bytes)} on the devices",
              flush=True)
        if logits_tolerance is not None:
            out["logits"] = logits_check(
                model, factory, prompt_len=logits_prompt_len,
                steps=logits_steps, seed=seed + 1,
                tolerance=logits_tolerance)
    finally:
        telemetry.disable(clear_override=True)
    out["wall_s"] = round(time.perf_counter() - t_phase, 2)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
# bf16 keeps 8 significant bits, so one rounding is 2**-9 of a value; a
# logit here is a 2048-term dot product behind 16 layers of bf16
# activations, finally stored in bf16 itself (a logit of 128-256 is
# stored to the nearest 1.0, and the reference's logits have a standard
# deviation of 45). Measured on the v5e at these widths (my chip run,
# PR 21): over 6 decode steps the largest difference was 0.043 of the
# reference's standard deviation and the mean 0.0066, the argmax equal
# in all 6. The bound is 3.5x the measured maximum: a wrong page, a
# wrong position or a 4-bit cache costs a multiple of 1.0. It does NOT
# separate an int8 cache from bf16 — both round at about 2**-8.
LOGITS_TOLERANCE = 0.15


def _phase(name: str, why: str = "") -> None:
    """Announce a phase, with what the devices still hold from before
    it (a phase that leaked would starve the next)."""
    gc.collect()
    print(f"[{name}]" + (f" {why}" if why else ""), flush=True)
    print(f"  devices hold {_gb(_bytes_in_use())} at phase start",
          flush=True)


def result_line(device: dict) -> str:
    """The LAST line of stdout, read by the driver: exactly the keys
    `ok` and `device` (`platform`, `kind`, `count` as JAX reports them).
    Everything else the run learned is on the `[summary]` line before
    it."""
    return json.dumps({"ok": True, "device": device})


def main() -> int:
    t_start = time.perf_counter()
    _phase("gate")
    cache_dir = enable_compile_cache()
    info = require_tpu()
    print(f"  platform={info['platform']} device_kind={info['kind']!r} "
          f"device_count={info['count']} jax={info['jax']} "
          f"jaxlib={info['jaxlib']} libtpu={info['libtpu']}", flush=True)
    print(f"  compile cache: {cache_dir}", flush=True)
    phases = {}

    _phase("train, one chip",
           "depth cut 16 -> 6 layers, batch 2 x 2048: bf16 weights with "
           "AdamW's float32 masters and moments cost 14 bytes a "
           "parameter (the tied 263M-parameter embedding alone 3.7 GB, "
           "each layer 0.85 GB) and the float32 logits 1 GB a sequence; "
           "XLA's buffer assignment puts this step at 13.3 GiB of the "
           "chip's 15.75")
    phases["train"] = phase_train(llama_3_2_1b(6), batch=2, seq=2048,
                                  steps=4)

    _phase("serve, one chip", "full depth, 2.5 GB of weights")
    phases["serve"] = phase_serve(
        llama_3_2_1b(16), table=REQUESTS_2048, max_seq_len=2048,
        logits_tolerance=LOGITS_TOLERANCE)

    if info["count"] >= 4:
        _phase("four chips (a): train, mesh sharding=2 x mp=2",
               "full depth, batch 4 x 2048: 10.5 GiB a chip by XLA's "
               "buffer assignment")
        phases["train_4"] = phase_train(
            llama_3_2_1b(16), batch=4, seq=2048, steps=3,
            mesh_axes={"sharding": 2, "mp": 2})
        _phase("four chips (b): 4 replicas, tp=1, one per chip")
        phases["serve_4x1"] = phase_serve(
            llama_3_2_1b(16), table=REQUESTS_2048, max_seq_len=2048,
            num_replicas=4, tp=1, second_pass=False)
        _phase("four chips (c): 2 replicas, tp=2")
        phases["serve_2x2"] = phase_serve(
            llama_3_2_1b(16), table=REQUESTS_2048, max_seq_len=2048,
            num_replicas=2, tp=2, second_pass=False)
    else:
        print(f"[four chips] NOT RUN: phases train_4, serve_4x1 and "
              f"serve_2x2 need jax.device_count() >= 4, found "
              f"{info['count']}", flush=True)

    total = time.perf_counter() - t_start
    print(f"[done] {total:.0f}s", flush=True)
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"]}
    print("[summary] " + json.dumps({
        "device": device,
        "versions": {k: info[k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache": cache_dir,
        "wall_s": round(total, 1),
        "phases": phases,
        "claim": None,
    }), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
