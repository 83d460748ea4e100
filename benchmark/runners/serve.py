"""The serving runner: one replica behind `ServingRouter`, driven from
the client's side by one thread.

    set-up   build the model (benchmark/weights.py), the fleet, warm
             every admission program the traffic can reach
             (benchmark/programs.py) and the decode program, then run
             the cell's own traffic until the system is in its working
             state (`warm` in the workload file)
    window   `--seconds` of the same traffic; the harness stamps every
             token the moment a `router.step()` shows it
    drain    follow what the window started, up to `drain_s`
    check    every request ended FINISHED on its budget, nothing was
             healed, the expected kernels are in the programs, one
             prompt's logits through the paged cache agree with the
             plain reference, and (a cell with a `served_check` block)
             the tokens the window SERVED are the plain reference's
             best, to a gap the cell's file bounds

The loop is synchronous, as the router is: submit what is due, step,
look. An open loop's request is timed from the moment it was DUE.
"""
from __future__ import annotations

import gc
import importlib
import os
import re
import shutil
import sys
import tempfile
import time

if __name__ == "__main__":      # run as a script: the repo on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

import jax
import numpy as np

import paddle_tpu.observability as telemetry
from paddle_tpu.models.generation import RequestStatus
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.serving.replica import ReplicaState
from paddle_tpu.serving.router import ServingRouter

from benchmark import programs, stats, trace_reduce, weights
from benchmark.traffic import Traffic

CLOCK = time.perf_counter
_LOWERED = [0, False]          # [programs lowered, listener registered]


def _watch_lowering() -> None:
    """Count every program JAX lowers from now on (a compile, or a hit
    in the persistent cache, starts with one). JAX has no way to take a
    listener away, so it is registered once a process."""
    def count(event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            _LOWERED[0] += 1

    if not _LOWERED[1]:
        jax.monitoring.register_event_duration_secs_listener(count)
        _LOWERED[1] = True


class Unsound(RuntimeError):
    """The run reached its end and its result is wrong."""


def healed_failures(router) -> list:
    """Everything the serving plane is built to survive and a
    measurement must not: an exception in a replica's step or health
    probe, an admission isolated into one FAILED request, a retried
    decode dispatch, a timeout, a failover, a restart, a quarantine.
    (Preemptions are counted apart: they are a policy, not a fault.)"""
    found = []
    for h in router.replicas:
        if h.last_traceback:
            found.append(f"replica {h.index} raised (state {h.state}):\n"
                         f"{h.last_traceback}")
        elif h.state != ReplicaState.HEALTHY or h.restarts:
            found.append(f"replica {h.index} is {h.state} after "
                         f"{h.restarts} restarts ({h.death_reason})")
        eng = h.engine
        if eng is None:
            continue
        counts = {n: getattr(eng, n) for n in
                  ("num_failures", "num_decode_retries", "num_timeouts")}
        if any(counts.values()):
            found.append(f"replica {h.index} engine healed {counts}:\n"
                         f"{eng.last_failure}")
    counts = {n: getattr(router, n) for n in
              ("num_failovers", "num_restarts", "num_quarantines")}
    if any(counts.values()):
        found.append(f"router healed {counts}")
    return found


def _preemptions(router) -> int:
    return sum(h.engine.num_preemptions for h in router.replicas
               if h.engine is not None)


class _LogitRecorder:
    """Sentry-shaped recorder (`attach_sentry`'s contract): the decode
    program then returns its sampled rows' logits and every step's rows
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


def logits_check(model, sizes: dict, engine_kw: dict, spec: dict,
                 seed: int) -> dict:
    """One seeded prompt prefilled and decoded `steps` tokens through
    the engine's paged cache (ragged prefill, scatter, ragged decode),
    each decode step's logits against the plain reference's full
    forward over prompt + generated tokens. The error is the largest
    absolute difference over the standard deviation of the reference's
    logits, so the tolerance does not depend on the weights' scale.

    The tolerance (in the workload file) and its reason: bf16 keeps 8
    significant bits; a logit is a dot product of `hidden_size` terms
    behind every layer's bf16 activations and is itself stored in bf16.
    PR 21 measured 0.043 at 16 layers of width 2048 against a float32
    oracle; 0.15 is some three times that. A wrong page, a wrong
    position, a dropped layer or 4-bit arithmetic costs a multiple of
    1.0; fp8/int8 weights or cache cost several times bf16's error."""
    ref = importlib.import_module(f"benchmark.reference.{sizes['reference']}")
    n, steps = int(spec["prompt_tokens"]), int(spec["steps"])
    rng = np.random.default_rng(int(seed) + 1)
    prompt = rng.integers(1, sizes["vocab_size"], n).tolist()
    # two slots and their default pool, whatever pool the cell names
    eng = ContinuousBatchingEngine(model, **{**engine_kw,
                                             "max_batch_size": 2,
                                             "num_pages": None})
    rec = _LogitRecorder()
    eng.attach_sentry(rec)
    rid = eng.add_request(prompt, max_new_tokens=steps + 1)
    tokens = eng.run()[rid]
    if eng.num_failures or eng.num_decode_retries:
        raise Unsound(f"logits check: the engine healed a failure:\n"
                      f"{eng.last_failure}")
    got = np.stack([r[0] for r in rec.rows[:steps]])
    # decode step j consumed generated token j at position n + j: row
    # n + j of the full forward
    want = ref.forward_logits(weights.named_values(model), sizes,
                              prompt + tokens[:steps])[n:n + steps]
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise Unsound("logits check: non-finite logits")
    err = float(np.max(np.abs(got - want)) / np.std(want))
    out = {"prompt_tokens": n, "steps": steps, "max_err_over_ref_std": err,
           "mean_err_over_ref_std":
               float(np.mean(np.abs(got - want)) / np.std(want)),
           "ref_std": float(np.std(want)),
           "argmax_agree": int(np.sum(got.argmax(-1) == want.argmax(-1))),
           "tolerance": float(spec["tolerance"])}
    out["ok"] = err <= out["tolerance"]
    return out


class _Rounded(dict):
    """The same weights with every matrix rounded to `dtype` and back,
    one at a time as it is read (the control's reference)."""

    def __init__(self, values, dtype):
        super().__init__(values)
        self.dtype = jax.numpy.dtype(dtype)

    def __getitem__(self, name):
        v = super().__getitem__(name)
        return v.astype(self.dtype).astype(v.dtype) if v.ndim >= 2 else v


def served_sample(ended, k: int, seed: int) -> list:
    """`k` of the requests the run finished: the longest, and the rest
    drawn from the seed."""
    done = [r for r in ended if not r["failed"] and r.get("tokens")]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick.tolist())]


def served_check(values, sizes: dict, sample: list, spec: dict,
                 control=None) -> dict:
    """What the timed path produced, against the plain reference: the
    reference runs once over each sampled request's prompt with the
    tokens it was SERVED (by the window's own programs, in its own
    batches), and for every served token reads how far its logit lies
    below the reference's best at that position, over the standard
    deviation of the reference's logits. A greedy token of a sound bf16
    program is the reference's best or a near tie (the gap is bounded
    by twice the logit error); a wrong page, position or slot, a token
    altered on its way out, or arithmetic a precision lower serves
    tokens the reference holds well below its best. Two numbers, each
    with a limit in the cell's file: the widest gap, and the mean gap
    over all compared tokens, which grows with the SQUARE of the logit
    error (how often a tie flips times how far) and swings far less
    from seed to seed.

    With `control` (a dtype) the reference itself, its matrices rounded
    to that precision, stands in the program's place: at each position
    of the same prompts and tokens, the gap of the token IT puts first.
    Sequences are padded to a multiple of `pad_to` (causal: the padding
    changes no compared row), so the reference compiles few shapes."""
    ref = importlib.import_module(f"benchmark.reference.{sizes['reference']}")
    pad = int(spec.get("pad_to", 256))
    served, low = [], []
    for r in sample:
        n, toks = len(r["prompt"]), np.asarray(r["tokens"])
        ids = list(r["prompt"]) + list(r["tokens"])
        ids += [1] * (-len(ids) % pad)
        rows = slice(n - 1, n - 1 + len(toks))
        want = ref.forward_logits(values, sizes, ids)[rows]
        if not np.isfinite(want).all():
            raise Unsound("served check: non-finite reference logits")
        best, scale, at = want.max(-1), np.std(want), np.arange(len(toks))
        served.append((best - want[at, toks]) / scale)
        if control:
            first = ref.forward_logits(_Rounded(values, control), sizes,
                                       ids)[rows].argmax(-1)
            low.append((best - want[at, first]) / scale)
    if not served:
        return {"requests": 0, "ok": False}

    def numbers(gaps):
        gap = np.concatenate(gaps)
        return {"served_gap_max": float(gap.max()),
                "served_gap_mean": float(gap.mean()),
                "not_the_reference_best": int((gap > 0).sum())}

    out = {"requests": len(sample), "tokens": sum(len(g) for g in served),
           "longest": len(sample[0]["prompt"]) + len(sample[0]["tokens"]),
           **numbers(low if control else served),
           "gap_max_limit": float(spec["gap_max_limit"]),
           "gap_mean_limit": float(spec["gap_mean_limit"]),
           "control": control}
    if control:       # the program's own reading, beside the control's
        out["program"] = numbers(served)
    out["ok"] = out["served_gap_max"] <= out["gap_max_limit"] \
        and out["served_gap_mean"] <= out["gap_mean_limit"]
    return out


def memory_peak_bytes() -> int:
    """The peak on the fullest chip so far (0 where the backend does
    not say)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def _kernels_by_family(snap: dict) -> dict:
    out = {}
    for labels, n in snap.get("counters", {}).get(
            "pdt_jit_mosaic_kernels_total", {}).items():
        family, kernel = re.findall(r'"([^"]*)"', labels)
        out.setdefault(family, {})[kernel] = int(n)
    return out


class Fleet:
    """The fleet under test and the one loop that drives it."""

    def __init__(self, model, engine_kw: dict, vocab_size: int):
        self.vocab = vocab_size

        def factory(index, submesh=None):
            return ContinuousBatchingEngine(model, submesh=submesh,
                                            **engine_kw)

        self.router = ServingRouter(factory, num_replicas=1)
        self.slots = int(engine_kw["max_batch_size"])
        self._n = 0

    # -- one request's record ------------------------------------------
    def _submit(self, live, prompt, budget, due, counted):
        self._n += 1
        rid = f"r{self._n}"
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.router.submit(prompt, max_new_tokens=budget,
                               request_id=rid)
        live[rid] = {"id": rid, "due": due, "submit": CLOCK(),
                     "prompt_tokens": len(prompt), "budget": budget,
                     "prompt": prompt, "tokens": None,
                     "token_times": [], "counted": counted,
                     "failed": False, "end": None, "status": None}
        return live[rid]

    def _look(self, live, ended, finished, now):
        """Stamp the tokens this step showed; retire what ended."""
        reqs = self.router.requests
        for rid, r in live.items():
            seen = len(reqs[rid].tokens)
            if seen > len(r["token_times"]):
                r["token_times"] += [now] * (seen - len(r["token_times"]))
        for rec in finished:
            r = live.pop(rec.request_id, None)
            if r is None:
                continue
            n = len(rec.tokens)
            r["token_times"] += [now] * (n - len(r["token_times"]))
            r["end"], r["status"] = now, rec.status
            r["tokens"] = list(rec.tokens)
            r["failed"] = (rec.status != RequestStatus.FINISHED
                           or bool(rec.failovers) or n != r["budget"])
            if r["failed"]:
                r["error"] = (f"{rec.status}, {n}/{r['budget']} tokens, "
                              f"{rec.failovers} failovers, {rec.error!r}")
            ended.append(r)
            # the router keeps every record for ever; the client has
            # its answer
            self.router.release_request(rec.request_id)

    def drive_all(self, prompts, budget: int = 2) -> None:
        """Submit a set of prompts together and step until all end
        (set-up's crafted requests)."""
        live, ended = {}, []
        for p in prompts:
            self._submit(live, p, budget, CLOCK(), False)
        for _ in range(10_000):
            if not live:
                break
            self._look(live, ended, self.router.step(), CLOCK())
        bad = [r for r in ended if r["failed"]] + list(live.values())
        healed = healed_failures(self.router)
        if bad or healed:
            raise Unsound(f"warm-up: {bad[:3]} {healed}")

    # -- the measured loop ----------------------------------------------
    def drive(self, traffic: Traffic, *, seconds: float, warm: dict,
              drain_s: float, on_open=None, on_close=None,
              trace_seconds: float = 0.0, trace_dir=None) -> dict:
        """Run `traffic` through warm-up, a window of `seconds` and the
        drain. Returns the window's observations."""
        router, live, ended, steps = self.router, {}, [], []
        closed_loop = traffic.closed_loop
        t_begin, n0 = CLOCK(), self._n
        t_ref = None if closed_loop else t_begin + float(warm["seconds"])
        t_open = t_close = None
        tracing = traced = False
        pending = None                        # (due, prompt, budget)
        next_due = t_begin
        full_steps = 0
        while True:
            now = CLOCK()
            if t_open is None:
                if closed_loop:
                    age = now - t_begin
                    ready = age >= warm.get("min_s", 0) and (
                        full_steps >= 2 or age >= warm.get("max_s", 60))
                else:
                    ready = now >= t_ref
                if ready:
                    gc.collect()
                    if on_open:
                        on_open()
                    t_open = now = CLOCK()
                    lowered0 = _LOWERED[0]
                    preempt0 = _preemptions(router)
            elif t_close is None and now >= t_open + seconds:
                t_close = now
                lowered1, preempt1 = _LOWERED[0], _preemptions(router)
                if tracing:
                    jax.profiler.stop_trace()
                    tracing = False
                if on_close:
                    on_close()
            if t_close is not None and (
                    not live or drain_s <= 0
                    or CLOCK() > t_close + drain_s):
                break
            if trace_dir and t_open is not None and not traced \
                    and now >= t_open + seconds - trace_seconds:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = traced = True
            # -- submissions ---------------------------------------
            if t_close is None:
                if closed_loop:
                    while len(live) < traffic.clients:
                        _, prompt, budget = traffic.next()
                        if warm.get("stagger") and self._n - n0 < self.slots:
                            # the first slot-full starts as if part-way
                            # through its answers, so that requests end
                            # (and admissions come) at the steady rate
                            # from the start, not in one late wave
                            budget = max(1, round(
                                budget * (self._n - n0 + 0.5) / self.slots))
                        self._submit(live, prompt, budget, CLOCK(), True)
                else:
                    while True:
                        if pending is None:
                            gap, prompt, budget = traffic.next()
                            next_due += gap
                            pending = (next_due, prompt, budget)
                        if pending[0] > now:
                            break
                        due = pending[0]
                        self._submit(live, pending[1], pending[2], due,
                                     t_open is not None
                                     and due < t_open + seconds)
                        pending = None
            if not live:
                # an open loop with nothing in flight: wait for the next
                # arrival (never past the window's end)
                until = pending[0] if pending else now
                if t_open is not None and t_close is None:
                    until = min(until, t_open + seconds)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(min(max(until - CLOCK(), 0.0), 0.05))
                continue
            with jax.profiler.TraceAnnotation("bench.step"):
                finished = router.step()
            t1 = CLOCK()
            with jax.profiler.TraceAnnotation("bench.look"):
                self._look(live, ended, finished, t1)
                running = sum(1 for r in live.values() if r["token_times"])
                full_steps = full_steps + 1 \
                    if running >= self.slots or (
                        closed_loop and running >= len(live)) else 0
                if t_open is not None and t_close is None:
                    steps.append({
                        "t": t1, "dur_s": t1 - now,
                        "running_slots": telemetry.value(
                            "pdt_serving_running_slots")
                        if telemetry.enabled() else running,
                        "page_occupancy": telemetry.value(
                            "pdt_serving_page_occupancy")
                        if telemetry.enabled() else None,
                        "live_context_tokens": sum(
                            r["prompt_tokens"] + len(r["token_times"])
                            for r in live.values() if r["token_times"]),
                        "waiting_first_token": sum(
                            1 for r in live.values()
                            if not r["token_times"]),
                    })
        # what the drain did not finish: given time and still open is a
        # failure; cut off without a drain is not counted at all
        cut = list(live.values())
        for r in cut:
            if len(r["token_times"]) > r["budget"]:
                raise Unsound(f"request {r['id']} passed its budget")
        if drain_s > 0:
            for r in cut:
                r["failed"], r["error"] = True, "unfinished after the drain"
            ended, cut = ended + cut, []
        return {"t_open": t_open, "t_close": t_close,
                "window_s": t_close - t_open, "requests": ended + cut,
                "ended": ended, "in_flight_at_close": len(cut),
                "steps": steps,
                "lowered_in_window": lowered1 - lowered0,
                "preemptions_in_window": preempt1 - preempt0}


def _crafted(fleet: Fleet, cell: dict, engine_kw: dict, seed: int,
             check_kernels: bool) -> dict:
    """Drive every reachable admission program once, the decode program
    with them. With `check_kernels`, telemetry is on for the first set
    only: the program's kernel counter counts at a program's first call
    and only while telemetry is on, and with it on every first call
    lowers its program twice."""
    t = cell["traffic"]["prompt_tokens"]
    kw = dict(prefill_chunk=engine_kw.get("prefill_chunk"),
              prompt_pad=engine_kw.get("prompt_pad", 16),
              page_size=engine_kw.get("page_size", 16),
              max_seq_len=engine_kw["max_seq_len"])
    sets = programs.warmup_sets(prompt_min=t["min"], prompt_max=t["max"],
                                **kw)
    rng = np.random.default_rng([int(seed), 7])
    kernels = None
    lowered = _LOWERED[0]
    for i, lens in enumerate(programs.minimal_cover(sets, **kw)):
        fleet.drive_all([rng.integers(1, fleet.vocab, n).tolist()
                         for n in lens])
        if i == 0 and check_kernels:
            kernels = _kernels_by_family(telemetry.snapshot())
            telemetry.disable()
    return {"keys": sorted(sets), "kernels": kernels,
            "lowered": _LOWERED[0] - lowered}


def _kernels_ok(found: dict, expected: dict) -> list:
    return [f"{family}: {k}" for family, ks in expected.items()
            for k in ks if not (found or {}).get(family, {}).get(k)]


def _spans_in(t0: float) -> list:
    return [{"name": e["name"], "ts_mono": e["ts_mono"],
             "dur_s": e["dur_s"]}
            for e in telemetry.events() if "dur_s" in e
            and e["ts_mono"] >= t0]


def _set_up(ctx: dict, telemetry_stays_on: bool):
    """Model, fleet and warmed programs. Telemetry is left on if asked,
    else off from the end of the first crafted set."""
    cell, sizes, seed = ctx["cell"], ctx["sizes"], ctx["seed"]
    engine_kw = {**sizes["engine"], **cell["engine"]}
    if ctx.get("quant"):
        # the control that is the program's own path: weights and pages
        # held in 8 bits (`main` below; never in a benchmark run)
        from paddle_tpu.models.serving import QuantServingConfig
        engine_kw["quant"] = QuantServingConfig(weights=ctx["quant"],
                                                kv="int8")
    notes = {}
    _watch_lowering()
    t0 = CLOCK()
    model, _ = weights.build(sizes["program"], sizes, seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    notes["build_s"] = CLOCK() - t0
    telemetry.enable()
    telemetry.reset()
    t0 = CLOCK()
    fleet = Fleet(model, engine_kw, sizes["vocab_size"])
    warmed = _crafted(fleet, cell, engine_kw, seed,
                      check_kernels=not telemetry_stays_on)
    if telemetry_stays_on:
        warmed["kernels"] = _kernels_by_family(telemetry.snapshot())
    notes["crafted_warmup_s"] = CLOCK() - t0
    notes["admission_keys"] = [list(k) for k in warmed["keys"]]
    notes["lowered_in_crafted_warmup"] = warmed["lowered"]
    notes["kernels"] = warmed["kernels"]
    return model, fleet, engine_kw, warmed, notes


def run(ctx: dict) -> dict:
    """`ctx`: cell (workload file), sizes (configuration file), seed,
    seconds, trace, dry, t_start. Returns `{"correct", "attempted",
    "failed", "end_to_end": {name: value}, "obs": {...}, "notes"}`."""
    cell, sizes, seed = ctx["cell"], ctx["sizes"], ctx["seed"]
    trace, dry = ctx["trace"], ctx["dry"]
    model, fleet, engine_kw, warmed, notes = _set_up(
        ctx, telemetry_stays_on=trace)
    snaps = {}

    def on_open():
        if trace:
            snaps["before"] = telemetry.snapshot()
        notes["setup_s"] = CLOCK() - ctx["t_start"]

    def on_close():
        if trace:
            snaps["after"] = telemetry.snapshot()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        obs = fleet.drive(
            Traffic(cell["traffic"], seed, sizes["vocab_size"]),
            seconds=ctx["seconds"], warm=cell["warm"],
            drain_s=float(cell.get("drain_s", 0)),
            on_open=on_open, on_close=on_close,
            trace_seconds=min(float(cell.get("trace_seconds", 5)),
                              ctx["seconds"]),
            trace_dir=trace_dir)
        if trace:
            planes = trace_reduce.load(trace_dir)
            obs["trace"] = trace_reduce.reduce(planes)
            obs["trace_planes"] = trace_reduce.describe(planes)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if trace:
        obs["telemetry"] = snaps
        obs["spans"] = _spans_in(obs["t_open"])
    telemetry.disable()

    # -- correctness, outside the window ----------------------------
    # the peak is the fleet's: read before any reference runs, and the
    # fleet's pools are freed before one does
    peak = memory_peak_bytes()
    healed = healed_failures(fleet.router)
    slots = fleet.slots
    del fleet
    gc.collect()
    failed = [r for r in obs["ended"] if r["failed"]]
    problems = healed \
        + [f"request {r['id']}: {r.get('error')}" for r in failed[:5]]
    missing = _kernels_ok(warmed["kernels"], cell.get("expect_kernels", {})) \
        if not dry else []
    if missing:
        problems.append(f"Mosaic kernels missing from the programs: "
                        f"{missing} (found {warmed['kernels']})")
    compared = {"failed_requests": (len(failed), 0),
                "healed_failures": (len(healed), 0)}
    t0 = CLOCK()
    check = logits_check(model, sizes, engine_kw, cell["logits_check"], seed)
    notes["logits_check_s"] = CLOCK() - t0
    notes["logits_check"] = check
    compared["logits_err"] = (check["max_err_over_ref_std"],
                              check["tolerance"])
    if "route_gap_max" in check:
        compared["route_gap"] = (check["route_gap_max"],
                                 check["route_margin"])
    if not check["ok"]:
        problems.append(f"logits outside the tolerance: {check}")
    if cell.get("served_check"):
        spec = cell["served_check"]
        t0 = CLOCK()
        served = served_check(
            weights.named_values(model), sizes,
            served_sample(obs["ended"], int(spec["requests"]), seed),
            spec, control=ctx.get("control"))
        notes["served_check_s"] = CLOCK() - t0
        notes["served_check"] = served
        if served["requests"]:
            compared["served_gap_max"] = (served["served_gap_max"],
                                          served["gap_max_limit"])
            compared["served_gap_mean"] = (served["served_gap_mean"],
                                           served["gap_mean_limit"])
        if not served["ok"]:
            problems.append(f"served tokens below the reference's best: "
                            f"{served}")
    notes["problems"] = problems
    notes["in_flight_at_close"] = obs["in_flight_at_close"]
    notes["lowered_in_window"] = obs["lowered_in_window"]
    notes["ttft_p50_s"] = None if dry or not obs["ended"] else stats.finite(
        stats.percentile(stats.ttfts(obs["requests"]) or [0.0], 50))

    t_open, t_close = obs["t_open"], obs["t_close"]
    reqs = obs["requests"]
    gaps = stats.token_gaps(reqs, t_open, t_close)
    first = stats.ttfts(reqs)
    e2e = {
        "setup_s": notes["setup_s"],
        "tokens_per_s": stats.tokens_in_window(reqs, t_open, t_close)
        / obs["window_s"],
        "itl_p95_s": stats.percentile(gaps, 95) if gaps else None,
        "ttft_p90_s": stats.finite(stats.percentile(first, 90))
        if first else None,
    }
    notes["samples"] = {"token_gaps": len(gaps), "ttft": len(first),
                        "steps": len(obs["steps"]),
                        "window_s": obs["window_s"]}
    if not dry:
        # what a later reader of one run's line needs to tell a run
        # that was slow throughout from one that stalled, and a 95th
        # percentile that reads a plain decode step from one that reads
        # a step plus an admission (none of it is an end-to-end value)
        live = [s["running_slots"] for s in obs["steps"]]
        notes["tokens_per_s_thirds"] = stats.rate_by_thirds(
            reqs, t_open, t_close)
        notes["gaps_with_admission_share"] = \
            stats.gaps_with_admission_share(reqs, t_open, t_close)
        notes["running_slots_mean"] = sum(live) / max(len(live), 1)
        notes["itl_p92_p98_s"] = [stats.percentile(gaps, q) if gaps
                                  else None for q in (92, 98)]
        # how far the 95th percentile sits from the next kind of gap on
        # either side: the shares of gaps over 0.8 and over 1.25 times
        # it. Where one of them lies near 5 %, it is on an edge
        p95 = e2e["itl_p95_s"]
        notes["gaps_over_p95_bracket_share"] = [
            100.0 * sum(g > f * p95 for g in gaps) / len(gaps)
            for f in (0.8, 1.25)] if gaps else None
        notes["step_durations"] = stats.step_durations(obs["steps"], reqs)
    obs["engine"] = {"slots": slots, **engine_kw}
    obs["model"] = sizes
    counted = [r for r in obs["ended"] if r["counted"]]
    return {"correct": not problems,
            "attempted": len(counted),
            "failed": sum(1 for r in counted if r["failed"]),
            "end_to_end": e2e, "obs": obs, "notes": notes,
            "memory_peak_bytes": peak,
            "compared": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in compared.items()}}


def sweep(ctx: dict, rates) -> list:
    """One set-up, then a window at each rate (open loop only), the
    system drained in between. A row per rate; the knee is the highest
    rate at which the count of requests still waiting for a first token
    does not grow over the second half of the window."""
    cell, sizes, seed = ctx["cell"], ctx["sizes"], ctx["seed"]
    _, fleet, _, _, _ = _set_up(ctx, telemetry_stays_on=False)
    rows = []
    for rate in rates:
        spec = dict(cell["traffic"])
        spec["arrivals"] = dict(spec["arrivals"], rate_per_s=float(rate))
        obs = fleet.drive(Traffic(spec, seed, sizes["vocab_size"]),
                          seconds=ctx["seconds"], warm=cell["warm"],
                          drain_s=float(cell.get("drain_s", 60)))
        t_open, t_close = obs["t_open"], obs["t_close"]
        mid = t_open + obs["window_s"] / 2
        wait = [(s["t"], s["waiting_first_token"]) for s in obs["steps"]]
        q3 = [w for t, w in wait if mid <= t < mid + obs["window_s"] / 4]
        q4 = [w for t, w in wait if t >= mid + obs["window_s"] / 4]
        first = stats.ttfts(obs["requests"])
        gaps = stats.token_gaps(obs["requests"], t_open, t_close)
        live = [s["running_slots"] for s in obs["steps"]]
        rows.append({
            "rate_per_s": float(rate),
            "counted": len(first),
            "failed": sum(1 for r in obs["ended"] if r["failed"]),
            "waiting_mean_3rd_quarter": sum(q3) / max(len(q3), 1),
            "waiting_mean_4th_quarter": sum(q4) / max(len(q4), 1),
            "waiting_at_close": wait[-1][1] if wait else 0,
            "running_slots_mean": sum(live) / max(len(live), 1),
            "ttft_p50_s": stats.finite(stats.percentile(first, 50)),
            "ttft_p90_s": stats.finite(stats.percentile(first, 90)),
            "itl_p95_s": stats.percentile(gaps, 95) if gaps else None,
            # a 95th percentile over "a step plus k admission dispatches"
            # is steady where its neighbours read the same k
            "itl_p92_s": stats.percentile(gaps, 92) if gaps else None,
            "itl_p98_s": stats.percentile(gaps, 98) if gaps else None,
            "gaps_with_admission_share": stats.gaps_with_admission_share(
                obs["requests"], t_open, t_close),
            "tokens_per_s": stats.tokens_in_window(
                obs["requests"], t_open, t_close) / obs["window_s"],
            "lowered_in_window": obs["lowered_in_window"],
            "healed": healed_failures(fleet.router),
        })
        if not ctx["dry"]:
            print("[sweep] " + repr(rows[-1]), flush=True)
    return rows


def main() -> int:
    """`python3 benchmark/runners/serve.py --workload <cell> --seeds a,b,c
    --seconds 15 [--control float8_e4m3fn | --quant int8]`: a short
    window at the cell's own load a seed, all in one process, and the
    numbers `correct` compares, one line a seed. `--control`: the plain
    reference rounded to that precision in the program's place.
    `--quant`: the program with its own 8-bit path switched on. Exit
    code 1 unless every seed comes out correct (without a control) or
    NOT correct (with one)."""
    import argparse
    import json

    from benchmark import run as harness
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--quant", default=None)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    if not args.dry:
        from paddle_tpu.device import enable_compile_cache, require_tpu
        require_tpu()
        enable_compile_cache()
    seeds = [int(x) for x in args.seeds.split(",")]
    as_expected = 0
    for seed in seeds:
        _, entry, _, cell, sizes = harness.load_cell(args.workload)
        if args.dry:
            harness._apply_dry(cell, sizes)
        res = run({"cell": cell, "sizes": sizes, "seed": seed,
                   "seconds": args.seconds, "trace": False, "dry": args.dry,
                   "t_start": CLOCK(), "chips": entry["chips"],
                   "control": args.control, "quant": args.quant})
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "compared": res["compared"],
                          "served_check": res["notes"].get("served_check"),
                          "logits_check": res["notes"]["logits_check"]}),
              flush=True)
        as_expected += res["correct"] == (
            args.control is None and args.quant is None)
        del res
        gc.collect()
    return 0 if as_expected == len(seeds) else 1


if __name__ == "__main__":
    raise SystemExit(main())
