#!/usr/bin/env python
"""Trace-driven fleet soak + QoS drill (ISSUE 11) — the first direct
evidence for the million-user north star.

Two phases against a real `ServingRouter` fleet of tiny-model engines
on ONE shared virtual clock (`paddle_tpu.loadgen`):

1. **Capacity.** Binary-search the open-loop arrival rate for the
   fleet's max sustainable QPS: the highest rate at which nothing is
   refused and the interactive lane's p95 TTFT meets the stated
   objective, on a seeded replayable trace (diurnal + burst arrivals,
   heavy-tailed lengths, tenant/lane mix).
2. **Overload.** Soak at `--overload` x that rate with the QoS
   admission controller ON (`serving/admission.py`): interactive vs
   batch priority lanes, sliding-window tenant budgets (the `free`
   tenant gets a deliberately tight one), and SLO-arbitrated shedding
   — the burn-rate engine decides WHEN to shed, lane/tenant ordering
   decides WHO.

The drill then GRADES the run (non-zero exit on failure):

* interactive p95 TTFT stays under the objective at overload,
* sheds are confined to the batch lane / over-budget tenants — an
  in-budget interactive session is never QoS-shed,
* `pdt_admission_*` counters reconcile EXACTLY with the router's
  terminal counters (committed admissions == terminal requests, with
  backpressure refusals booked separately; sheds == qos_shed
  rejections),
* the trace replays: the same seed regenerates the identical arrival
  sequence.

A third leg then kills the CONTROL PLANE (docs/serving.md
"Durability"): a write-ahead-journaled fleet takes the front half of
a sustainable-rate trace, the router dies mid-decode (SIGKILL-shaped
teardown), `ServingRouter.recover()` rehydrates a fresh incarnation
from the journal, the remaining arrivals land on it, and the drill
grades ZERO lost soak sessions + outputs identical to an unkilled
fleet, printing the `pdt_journal_*` Prometheus dump.

`--autoscale` adds a fourth leg (ISSUE 16, docs/serving.md
"Autoscaling"): the same diurnal trace replays twice — once against a
static peak-provisioned fleet, once against a journaled fleet scaled
from a 1-replica floor by `FleetAutoscaler` — and the drill grades
zero lost sessions, autoscaled p95 TTFT within the objective,
replica-step (chip-time) savings > 0, at least one grow AND one
shrink, and burst reaction time <= 2 virtual seconds.

`--multimodel` adds the consolidation leg (ISSUE 17, docs/serving.md
"Multi-model serving"): a per-tenant model mix (two LoRA fine-tunes
over the shared base) soaks ONE `model_affinity` fleet behind a
`FleetModelStore`, then each model's arrivals replay against a
DEDICATED single-model fleet of the same size. The drill grades zero
ADMITTED sessions lost (backpressure refusals are visible and
reconciled — the mix rides a different arrival realization than the
one phase 1 certified), mixed-fleet interactive p95 TTFT meeting the
same objective the dedicated baselines meet (latency parity at 1/N
the chips), and EXACT per-model terminal-counter reconciliation
(driver-side per-model outcomes == `fleet_info()["models"]` ==
`num_terminal_by_model`).

    python recipes/fleet_soak.py                   # search + 2x soak
    python recipes/fleet_soak.py --qps 6 --overload 3
    python recipes/fleet_soak.py --duration 120 --replicas 4  # heavier
`--profile` prints the performance-attribution report after the soak
(ISSUE 20, docs/observability.md "Performance attribution"):
span self-time waterfall (the router.step tree), compile-cache table,
memory ledger.

    python recipes/fleet_soak.py                   # search + 2x soak
    python recipes/fleet_soak.py --qps 6 --overload 3
    python recipes/fleet_soak.py --duration 120 --replicas 4  # heavier
    python recipes/fleet_soak.py --autoscale       # + the elastic leg
    python recipes/fleet_soak.py --multimodel      # + the model-mix leg
    python recipes/fleet_soak.py --profile         # + attribution
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Open-loop fleet soak + QoS admission drill")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--duration", type=float, default=30.0,
                   help="virtual seconds of trace per soak run")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--slots", type=int, default=2,
                   help="engine max_batch_size per replica")
    p.add_argument("--step-dt", type=float, default=0.05,
                   help="virtual wall seconds charged per fleet step")
    p.add_argument("--qps", type=float, default=0.0,
                   help="sustainable QPS to assume (0 = binary search)")
    p.add_argument("--overload", type=float, default=2.0,
                   help="overload factor over max sustainable QPS")
    p.add_argument("--ttft-objective", type=float, default=0.5,
                   help="interactive p95 TTFT objective, virtual s")
    p.add_argument("--free-budget", type=int, default=400,
                   help="sliding-window token budget for the 'free' "
                        "tenant (deliberately tight)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the elastic-fleet leg: the same diurnal "
                        "trace against a STATIC peak-size fleet and an "
                        "AUTOSCALED one (journal-attached, min 1 .. max "
                        "--replicas), grading p95 TTFT parity, "
                        "replica-step savings, burst reaction time, "
                        "and zero lost sessions")
    p.add_argument("--multimodel", action="store_true",
                   help="run the multi-model leg: a per-tenant LoRA "
                        "model mix against ONE model_affinity fleet vs "
                        "per-model DEDICATED fleets, grading TTFT "
                        "parity and exact per-model terminal-counter "
                        "reconciliation")
    p.add_argument("--quant", action="store_true",
                   help="serve the whole fleet quantized (int8 weights"
                        " + int8 KV pages, QuantServingConfig) — the "
                        "soak grades the same objectives against the "
                        "half-width-page engine")
    p.add_argument("--harvest-every", type=int, default=1,
                   help="pipelined decode: every engine defers its "
                        "D2H token harvest to one batched pull per K "
                        "steps (docs/serving.md 'Pipelined decode'); "
                        "1 = the synchronous loop. The soak grades "
                        "the SAME objectives — chaos, recovery, and "
                        "SLOs must hold at any window size")
    p.add_argument("--profile", action="store_true",
                   help="print the performance-attribution report "
                        "(span self-time waterfall, compile-cache table, "
                        "memory ledger — docs/observability.md "
                        "'Performance attribution') after the soak")
    args = p.parse_args(argv)

    import paddle_tpu as paddle
    import paddle_tpu.observability as telemetry
    from paddle_tpu.loadgen import (SoakDriver, TraceConfig,
                                    VirtualClock, binary_search_qps,
                                    generate_trace)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.serving import ContinuousBatchingEngine
    from paddle_tpu.observability import render_fleet_status
    from paddle_tpu.observability.slo import (SloMonitor, SloObjective,
                                              format_slo_report)
    from paddle_tpu.serving import QosAdmission, ServingRouter

    telemetry.enable()
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()

    page = 16
    out_max, prompt_max = 12, 32
    objective = args.ttft_objective

    def trace_cfg(qps):
        return TraceConfig(
            seed=args.seed, duration_s=args.duration, base_qps=qps,
            diurnal_amplitude=0.3, diurnal_period_s=args.duration,
            burst_start_prob=0.02, burst_mean_s=1.5,
            burst_multiplier=2.5,
            prompt_len_median=10.0, prompt_len_max=prompt_max,
            output_len_median=6.0, output_len_max=out_max,
            tenants=(("acme", 3.0), ("bidco", 2.0), ("free", 1.0)),
            # the drill must be physically winnable: shedding batch
            # frees capacity for interactive only if the interactive
            # slice alone fits the fleet — keep
            # interactive_fraction * overload < 1
            interactive_fraction=min(0.4, 0.8 / args.overload),
            num_system_prompts=4,
            system_prompt_len=page, shared_prefix_prob=0.4,
            vocab_size=cfg.vocab_size)

    def build_fleet(with_qos, journal=None, recover_from=None):
        clock = VirtualClock()
        # a SHORT window makes the burn responsive: shedding starts
        # within seconds of the first breach-shaped samples and backs
        # off as soon as the recent window recovers
        window = min(10.0, args.duration / 3)
        mon = SloMonitor(
            [SloObjective("interactive_ttft_p95", "ttft.interactive",
                          "latency", objective, quantile=0.95,
                          window_s=window),
             SloObjective("ttft_p95", "ttft", "latency", objective,
                          quantile=0.95, window_s=window)],
            clock=clock)
        qos = None
        if with_qos:
            qos = QosAdmission(
                slo_monitor=mon,
                shed_objective="interactive_ttft_p95", shed_burn=0.5,
                budgets={"free": args.free_budget},
                tenant_window_s=max(10.0, args.duration / 3),
                clock=clock)
        # --quant: every replica serves int8 weights + int8 KV pages
        # (fleets must be quant-homogeneous — cross-mode migration is
        # a typed refusal); the soak's grading is unchanged, which is
        # the point: the quantized fleet must hold the same objectives
        quant_cfg = None
        if args.quant:
            from paddle_tpu.models.serving import QuantServingConfig
            quant_cfg = QuantServingConfig(weights="int8", kv="int8")

        def engine(i):
            return ContinuousBatchingEngine(
                model, max_batch_size=args.slots, page_size=page,
                max_seq_len=prompt_max + page + out_max + 2 * page,
                clock=clock, quant=quant_cfg,
                harvest_every=args.harvest_every)

        kw = dict(
            num_replicas=args.replicas, policy="least_outstanding",
            page_size=page, max_replica_outstanding=4 * args.slots,
            clock=clock, sleep=clock.advance, slo_monitor=mon,
            admission=qos)
        if recover_from is not None:
            # a fresh incarnation rehydrated from a dead router's
            # write-ahead journal (docs/serving.md "Durability")
            router = ServingRouter.recover(recover_from, engine, **kw)
        else:
            router = ServingRouter(engine, journal=journal, **kw)
        return router, clock, mon

    def soak(qps, with_qos):
        telemetry.reset()
        router, clock, mon = build_fleet(with_qos)
        driver = SoakDriver(router, generate_trace(trace_cfg(qps)),
                            clock=clock, step_dt=args.step_dt,
                            max_wall_s=1800)
        result = driver.run()
        return result, router, mon

    if args.quant:
        print("mode: QUANTIZED fleet (weights=int8, kv=int8 — "
              "half-width KV pages, fused dequant matmuls)")
    if args.harvest_every > 1:
        print(f"mode: PIPELINED decode (harvest_every="
              f"{args.harvest_every} — one batched D2H harvest per "
              f"window, bounded-staleness durability)")

    # -- phase 1: capacity ---------------------------------------------
    if args.qps > 0:
        max_qps = args.qps
        print(f"capacity: assuming max sustainable QPS {max_qps:g} "
              "(--qps)")
    else:
        def sustainable(qps):
            s = soak(qps, with_qos=False)[0].summary()
            # sustainable = every session FINISHED (refusals and
            # admitted-then-lost preemptions/timeouts both disqualify
            # — a lost session leaves no TTFT sample to grade) under
            # the interactive p95 objective
            lost = s["sessions"] - s["outcomes"].get("finished", 0)
            p95 = s["lanes"].get("interactive", {}).get("ttft_p95_s")
            ok = lost == 0 and (p95 is None or p95 <= objective)
            print(f"  probe {qps:6.2f} qps: lost={lost} "
                  f"interactive p95 TTFT="
                  f"{'-' if p95 is None else f'{p95:.3f}'}s -> "
                  f"{'sustainable' if ok else 'UNSUSTAINABLE'}")
            return ok

        print("capacity: binary search for max sustainable QPS "
              f"(objective: interactive p95 TTFT <= {objective:g}s)")
        max_qps = binary_search_qps(sustainable, 0.5, 4.0, iters=5)
        print(f"capacity: max sustainable ~{max_qps:.2f} qps")

    # -- phase 2: overload with QoS -------------------------------------
    rate = max_qps * args.overload
    print(f"\noverload: soaking at {rate:.2f} qps "
          f"({args.overload:g}x) with QoS admission ON")
    result, router, mon = soak(rate, with_qos=True)
    summary = result.summary()
    print(json.dumps(summary, indent=1))
    print()
    print(render_fleet_status(router.fleet_info()))
    print()
    print(format_slo_report(mon.evaluate(export=False)))

    # -- grading --------------------------------------------------------
    failures = []
    inter = summary["lanes"].get("interactive", {})
    p95 = inter.get("ttft_p95_s")
    if p95 is None:
        failures.append("no interactive TTFT samples at overload")
    elif p95 > objective:
        failures.append(
            f"interactive p95 TTFT {p95:.3f}s exceeds the "
            f"{objective:g}s objective at {args.overload:g}x overload")

    # sheds confined to the batch lane / over-budget tenants
    stray = [s for s in result.sessions
             if s.outcome == "shed" and s.lane == "interactive"
             and s.shed_reason != "tenant_budget"]
    if stray:
        failures.append(
            f"{len(stray)} in-budget interactive sessions were shed "
            f"(e.g. {stray[0].request_id})")
    sheds = sum(1 for s in result.sessions if s.outcome == "shed")
    if sheds == 0:
        failures.append(
            f"no sheds at {args.overload:g}x overload — the drill "
            "proved nothing; raise --overload")

    # exact counter reconciliation (one telemetry snapshot)
    snap = telemetry.snapshot()["counters"]

    def total(name, **labels):
        series = snap.get(name, {})
        want = [f'{k}="{v}"' for k, v in labels.items()]
        return int(sum(v for key, v in series.items()
                       if all(w in key for w in want)))

    admits = total("pdt_admission_decisions_total", decision="admit")
    terminals = total("pdt_router_requests_terminal_total")
    fleet_full = total("pdt_router_rejections_total",
                       reason="fleet_full")
    # admissions are counted at COMMIT (after the fleet accepted), so
    # the identity is exact: every committed admission reaches exactly
    # one terminal state once the fleet drains
    if admits != terminals:
        failures.append(
            f"admission/terminal mismatch: {admits} committed "
            f"admissions != {terminals} terminals "
            f"({fleet_full} fleet_full refusals booked separately)")
    shed_counter = total("pdt_admission_shed_total")
    qos_rejects = total("pdt_router_rejections_total",
                        reason="qos_shed")
    if not (shed_counter == qos_rejects == sheds):
        failures.append(
            f"shed reconciliation failed: pdt_admission_shed_total="
            f"{shed_counter}, qos_shed rejections={qos_rejects}, "
            f"driver-side sheds={sheds}")

    # replayability: the same seed regenerates the same arrivals
    replay = generate_trace(trace_cfg(rate))
    original = generate_trace(trace_cfg(rate))
    if replay != original:
        failures.append("trace replay diverged for the same seed")

    # -- phase 3: kill the control plane mid-run ------------------------
    # everything the soak graded above survives REPLICA death; this leg
    # kills the ROUTER. A journaled fleet takes the front half of a
    # sustainable-rate trace, dies mid-decode (SIGKILL-shaped teardown:
    # nothing of the incarnation survives but its write-ahead journal),
    # `ServingRouter.recover()` rehydrates a fresh incarnation, the
    # remaining arrivals land on IT, and the drill grades zero lost
    # sessions + outputs identical to an unkilled fleet on the same
    # submissions (docs/serving.md "Durability").
    print(f"\nrestart: kill-the-router drill at {max_qps:.2f} qps")
    import shutil
    import tempfile
    from paddle_tpu.serving import RouterJournal

    # enough sessions to straddle the kill, few enough that open-loop
    # submission stays inside the fleet's backpressure bound
    drill_events = generate_trace(trace_cfg(max_qps))[
        :3 * args.replicas * args.slots]

    def drill_submit(router, events):
        return [router.submit(list(ev.prompt), ev.max_new_tokens,
                              request_id=ev.request_id, lane=ev.lane,
                              tenant=ev.tenant) for ev in events]

    ref_router, _, _ = build_fleet(with_qos=False)
    ref_ids = drill_submit(ref_router, drill_events)
    ref_out = ref_router.run()                   # the unkilled oracle

    wal_root = tempfile.mkdtemp(prefix="fleet_soak_wal_")
    try:
        wal = os.path.join(wal_root, "wal")
        router, _, _ = build_fleet(
            with_qos=False,
            journal=RouterJournal(wal, fsync="terminal"))
        half = len(drill_events) // 2
        drill_submit(router, drill_events[:half])
        finished_before = []
        while not finished_before:               # kill mid-decode,
            finished_before += router.step()     # some work finished
        del router                               # SIGKILL-shaped
        recovered, _, _ = build_fleet(
            with_qos=False,
            recover_from=RouterJournal(wal, fsync="terminal"))
        drill_submit(recovered, drill_events[half:])
        got_out = recovered.run()
        n_rec = int(telemetry.value(
            "pdt_journal_replay_recovered_total"))
        n_dedup = int(telemetry.value(
            "pdt_journal_replay_deduped_total"))
        lost = [i for i in ref_ids if i not in got_out]
        if lost:
            failures.append(
                f"router restart lost {len(lost)} soak session(s) "
                f"(e.g. {lost[0]})")
        mismatched = [i for i in ref_ids
                      if got_out.get(i) != ref_out[i]]
        if mismatched:
            failures.append(
                f"router restart changed {len(mismatched)} output "
                f"stream(s) (e.g. {mismatched[0]})")
        print(f"restart: killed the router with {half} sessions in "
              f"flight ({len(finished_before)} already finished) -> "
              f"recover() rehydrated {n_rec} live, restored {n_dedup} "
              f"finished without re-execution; "
              f"{len(drill_events) - half} post-restart arrivals "
              "served by the recovered incarnation; "
              f"{len(drill_events) - len(lost)}/{len(drill_events)} "
              "sessions finished")
        print("--- journal telemetry (Prometheus text exposition) ---")
        print("\n".join(line for line in telemetry.to_prometheus()
                        .splitlines() if "pdt_journal" in line))
        print("--- end journal telemetry ---")
    finally:
        shutil.rmtree(wal_root, ignore_errors=True)

    # -- phase 4 (--autoscale): the elastic fleet ------------------------
    # the same pronounced-diurnal trace twice: a STATIC fleet pinned at
    # peak size, then an AUTOSCALED one (journal-attached so every
    # resize is a two-phase INTENT/COMMIT transaction) starting at one
    # replica under a FleetAutoscaler. Grades: zero lost sessions,
    # interactive p95 TTFT holds the objective, measurably fewer
    # replica-steps (the chip-time proxy), bounded burst reaction, and
    # at least one journaled grow + shrink (docs/serving.md
    # "Autoscaling").
    if args.autoscale:
        from paddle_tpu.loadgen import TraceConfig as _TC
        from paddle_tpu.serving import (AutoscalePolicy, FleetAutoscaler,
                                        RouterJournal)

        def diurnal_cfg():
            base = max_qps * 0.6
            return _TC(
                seed=args.seed + 1, duration_s=2 * args.duration,
                base_qps=base,
                # one pronounced cycle: the trough needs ~a third of
                # the peak's capacity — exactly the gap elasticity
                # harvests
                diurnal_amplitude=0.6,
                diurnal_period_s=2 * args.duration,
                burst_start_prob=0.0, burst_mean_s=1.0,
                burst_multiplier=1.0,
                prompt_len_median=10.0, prompt_len_max=prompt_max,
                output_len_median=6.0, output_len_max=out_max,
                tenants=(("acme", 3.0), ("bidco", 2.0), ("free", 1.0)),
                interactive_fraction=0.4, num_system_prompts=4,
                system_prompt_len=page, shared_prefix_prob=0.4,
                vocab_size=cfg.vocab_size)

        def elastic_soak(autoscaled, journal=None):
            telemetry.reset()
            router, clock, mon = build_fleet(with_qos=False,
                                             journal=journal)
            scaler = None
            if autoscaled:
                # shrink to one replica first — the drill starts at
                # the trough-shaped fleet the policy would converge to
                while len(router.replicas) > 1:
                    router.resize(
                        num_replicas=len(router.replicas) - 1,
                        reason="autoscale-drill-floor")
                scaler = FleetAutoscaler(
                    router,
                    AutoscalePolicy(
                        min_replicas=1, max_replicas=args.replicas,
                        scale_up_depth=2.0 * args.slots,
                        scale_down_depth=0.75,
                        # the capacity model: phase 1 measured the
                        # peak fleet's sustainable rate, so one
                        # replica's share is the per-replica capacity
                        replica_qps=max_qps / args.replicas,
                        up_ticks=2, down_ticks=6,
                        cooldown_s=2.0, max_step=1),
                    interval_s=1.0, clock=clock)
            driver = SoakDriver(router, generate_trace(diurnal_cfg()),
                                clock=clock, step_dt=args.step_dt,
                                max_wall_s=1800, autoscaler=scaler)
            return driver.run(), router, scaler

        print(f"\nautoscale: diurnal drill at {max_qps * 0.6:.2f} qps "
              f"base (static peak fleet = {args.replicas} replicas "
              "vs autoscaled 1.." f"{args.replicas})")
        static_res, _, _ = elastic_soak(autoscaled=False)
        static_sum = static_res.summary()
        wal_root2 = tempfile.mkdtemp(prefix="fleet_soak_autoscale_")
        try:
            auto_res, auto_router, scaler = elastic_soak(
                autoscaled=True,
                journal=RouterJournal(os.path.join(wal_root2, "wal"),
                                      fsync="off"))
            auto_sum = auto_res.summary()
            journaled_resizes = auto_router.fleet_info()["resizes"]
        finally:
            shutil.rmtree(wal_root2, ignore_errors=True)

        lost_auto = auto_sum["sessions"] \
            - auto_sum["outcomes"].get("finished", 0)
        p95_static = static_sum["lanes"].get(
            "interactive", {}).get("ttft_p95_s")
        p95_auto = auto_sum["lanes"].get(
            "interactive", {}).get("ttft_p95_s")
        savings_pct = 100.0 * (1.0 - auto_res.replica_steps
                               / max(1, static_res.replica_steps))
        grows = sum(1 for a in scaler.actions if a["action"] == "grow")
        shrinks = sum(1 for a in scaler.actions
                      if a["action"] == "shrink")
        reaction = max(scaler.reactions, default=None)
        autoscale_metrics = {
            "ttft_p95_static_s": p95_static,
            "ttft_p95_autoscaled_s": p95_auto,
            "replica_steps_static": static_res.replica_steps,
            "replica_steps_autoscaled": auto_res.replica_steps,
            "replica_step_savings_pct": round(savings_pct, 2),
            "burst_reaction_s": reaction,
            "grows": grows, "shrinks": shrinks,
            "journaled_resizes": journaled_resizes,
            "lost_sessions": lost_auto,
        }
        print(json.dumps({"autoscale": autoscale_metrics}, indent=1))
        if lost_auto:
            failures.append(
                f"autoscaled soak lost {lost_auto} session(s) — "
                "elasticity must never cost work")
        if p95_auto is None:
            failures.append("autoscaled soak produced no interactive "
                            "TTFT samples")
        elif p95_auto > objective:
            failures.append(
                f"autoscaled interactive p95 TTFT {p95_auto:.3f}s "
                f"exceeds the {objective:g}s objective (static peak "
                f"fleet held {p95_static})")
        if savings_pct <= 0:
            failures.append(
                f"autoscaling saved no replica-steps "
                f"({auto_res.replica_steps} vs "
                f"{static_res.replica_steps} static)")
        if grows < 1 or shrinks < 1:
            failures.append(
                f"diurnal cycle should force both directions: "
                f"{grows} grows, {shrinks} shrinks")
        if reaction is not None and reaction > 2.0:
            failures.append(
                f"burst reaction {reaction:.2f}s exceeds the 2.0s "
                "bound (hysteresis + cooldown mistuned)")

    # -- phase 5 (--multimodel): the consolidation leg --------------------
    # a per-tenant model mix (two LoRA fine-tunes over the shared base)
    # soaks ONE model_affinity fleet behind a FleetModelStore, then
    # each model's arrivals replay against a DEDICATED single-model
    # fleet of the SAME size — the baseline a consolidation must match
    # while using 1/N the chips (docs/serving.md "Multi-model serving").
    if args.multimodel:
        import dataclasses

        import numpy as np
        from paddle_tpu.serving import FleetModelStore

        # small LoRA deltas over two of the tiny model's matmuls; the
        # shapes come from the live state dict so the recipe tracks
        # the config
        sd = {k: v for k, v in model.state_dict().items()}
        targets = ("model.layers.0.self_attn.q_proj.weight",
                   "model.layers.1.mlp.gate_proj.weight")
        drng = np.random.default_rng(args.seed)

        def lora_deltas():
            out = {}
            for nm in targets:
                K, N = sd[nm].shape
                out[nm] = (
                    drng.normal(size=(K, 4)).astype(np.float32) * 0.05,
                    drng.normal(size=(4, N)).astype(np.float32) * 0.05)
            return out

        def fresh_store():
            # fresh per fleet (resident sets are per-router state);
            # re-seeding regenerates identical deltas, so every fleet
            # hosts the same artifacts
            nonlocal drng
            drng = np.random.default_rng(args.seed)
            store = FleetModelStore(base_model="base", max_rank=8)
            mids = [store.register_adapter("tuna", lora_deltas()),
                    store.register_adapter("salmon", lora_deltas())]
            return store, mids

        def build_mm_fleet(store):
            clock = VirtualClock()
            mon = SloMonitor(
                [SloObjective("interactive_ttft_p95",
                              "ttft.interactive", "latency", objective,
                              quantile=0.95,
                              window_s=min(10.0, args.duration / 3))],
                clock=clock)

            def engine(i):
                return ContinuousBatchingEngine(
                    model, max_batch_size=args.slots, page_size=page,
                    max_seq_len=prompt_max + page + out_max + 2 * page,
                    clock=clock)

            router = ServingRouter(
                engine, num_replicas=args.replicas,
                policy="model_affinity", page_size=page,
                max_replica_outstanding=4 * args.slots,
                clock=clock, sleep=clock.advance, slo_monitor=mon,
                model_store=store)
            return router, clock

        store, (m_tuna, m_salmon) = fresh_store()
        mm_rate = max_qps
        mm_cfg = dataclasses.replace(
            trace_cfg(mm_rate),
            seed=args.seed + 2,
            request_id_prefix="mm",
            model_mix=(("acme", ((m_tuna, 3.0), ("base", 1.0))),
                       ("bidco", ((m_salmon, 1.0),)),
                       ("free", (("base", 1.0),))))
        mm_events = generate_trace(mm_cfg)
        mix_counts = {}
        for ev in mm_events:
            mix_counts[ev.model] = mix_counts.get(ev.model, 0) + 1
        print(f"\nmultimodel: {len(mm_events)} arrivals at "
              f"{mm_rate:.2f} qps, mix {mix_counts} -> one "
              f"{args.replicas}-replica model_affinity fleet vs "
              "dedicated per-model fleets")

        telemetry.reset()
        mm_router, mm_clock = build_mm_fleet(store)
        mm_res = SoakDriver(mm_router, mm_events, clock=mm_clock,
                            step_dt=args.step_dt, max_wall_s=1800).run()
        mm_sum = mm_res.summary()
        mm_info = mm_router.fleet_info()
        # snapshot NOW: the dedicated baselines below tick the same
        # process-wide counters
        mm_terminals_total = int(sum(
            telemetry.snapshot()["counters"]
            .get("pdt_router_requests_terminal_total", {}).values()))
        # phase 1 certified max_qps on a DIFFERENT arrival realization
        # (the model draws shift the trace RNG stream), so backpressure
        # refusals are legitimate here — visible and reconciled below.
        # What may NEVER happen is an ADMITTED session going missing.
        refused_mm = sum(mm_sum["outcomes"].get(o, 0)
                         for o in ("shed", "overloaded", "invalid"))
        lost_mm = mm_sum["sessions"] - refused_mm \
            - mm_sum["outcomes"].get("finished", 0)
        p95_mm = mm_sum["lanes"].get("interactive", {}) \
            .get("ttft_p95_s")

        # the dedicated baseline: each model's arrivals alone against a
        # fleet of the same size hosting only that model
        dedicated_p95 = {}
        for mid in sorted(mix_counts):
            d_store, _ = fresh_store()
            d_router, d_clock = build_mm_fleet(d_store)
            d_events = [ev for ev in mm_events if ev.model == mid]
            d_res = SoakDriver(d_router, d_events, clock=d_clock,
                               step_dt=args.step_dt,
                               max_wall_s=1800).run()
            d_sum = d_res.summary()
            d_lost = d_sum["sessions"] \
                - sum(d_sum["outcomes"].get(o, 0)
                      for o in ("shed", "overloaded", "invalid")) \
                - d_sum["outcomes"].get("finished", 0)
            if d_lost:
                failures.append(f"dedicated {mid} fleet lost "
                                f"{d_lost} admitted session(s)")
            dedicated_p95[mid] = d_sum["lanes"].get(
                "interactive", {}).get("ttft_p95_s")

        # exact per-model terminal reconciliation, three ways: the
        # driver's per-session ledger, the router's python-side
        # num_terminal_by_model, and fleet_info()["models"]
        driver_by_model = {}
        for s in mm_res.sessions:
            if s.outcome in ("shed", "overloaded", "invalid"):
                continue
            mid = s.model if s.model is not None else "base"
            d = driver_by_model.setdefault(mid, {})
            d[s.outcome] = d.get(s.outcome, 0) + 1
        router_by_model = {
            mid: dict(c)
            for mid, c in mm_router.num_terminal_by_model.items()}
        info_by_model = {
            mid: dict(rec["terminal"])
            for mid, rec in mm_info["models"].items()
            if rec["terminal"]}
        if not (driver_by_model == router_by_model == info_by_model):
            failures.append(
                "per-model terminal reconciliation failed: "
                f"driver={driver_by_model} "
                f"router={router_by_model} fleet_info={info_by_model}")
        by_model_sum = sum(sum(c.values())
                           for c in router_by_model.values())
        if mm_terminals_total != by_model_sum:
            failures.append(
                f"per-model terminals {by_model_sum} != fleet total "
                f"{mm_terminals_total}")

        mm_metrics = {
            "arrivals": len(mm_events), "mix": mix_counts,
            "ttft_p95_mixed_s": p95_mm,
            "ttft_p95_dedicated_s": dedicated_p95,
            "cold_installs": dict(mm_router.num_cold_installs_by_model),
            "model_store": mm_info["model_store"],
            "refusals": refused_mm,
            "lost_admitted_sessions": lost_mm,
            "replicas_mixed": args.replicas,
            "replicas_dedicated_total":
                args.replicas * len(mix_counts),
        }
        print(json.dumps({"multimodel": mm_metrics}, indent=1))
        if lost_mm:
            failures.append(f"multi-model soak lost {lost_mm} "
                            "admitted session(s)")
        if p95_mm is None:
            failures.append("multi-model soak produced no interactive "
                            "TTFT samples")
        elif p95_mm > objective:
            failures.append(
                f"mixed-model interactive p95 TTFT {p95_mm:.3f}s "
                f"exceeds the {objective:g}s objective "
                f"(dedicated baselines: {dedicated_p95}) — "
                "consolidation broke latency parity")
        for mid, p in dedicated_p95.items():
            if p is not None and p > objective:
                failures.append(
                    f"dedicated {mid} baseline p95 TTFT {p:.3f}s "
                    f"missed the {objective:g}s objective — the "
                    "parity grade has no valid baseline")

    if args.profile:
        # where the soak's decode rounds went + what compiled; the
        # fleet_info/render_fleet_status calls above already refreshed
        # the pdt_mem_bytes ledger from the live fleet
        from paddle_tpu.observability import profile as _profile
        print()
        print(_profile.snapshot_report())

    print()
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print(f"PASS: interactive p95 TTFT {p95:.3f}s <= {objective:g}s "
          f"at {args.overload:g}x overload; {sheds} sheds, all "
          "batch-lane or over-budget; admission counters reconcile "
          f"exactly ({admits} committed admissions = {terminals} "
          f"terminals; {fleet_full} backpressure refusals booked "
          "separately); trace replays bit-identically")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
