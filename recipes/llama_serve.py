#!/usr/bin/env python
"""Llama serving demo — the full L10 inference stack in one script.

≙ the reference's serving deployment recipe (PaddleNLP llm serving /
`AnalysisPredictor` flows, SURVEY.md §1 L10): load or build a model,
then drive every decode surface the framework ships —

  * `generate()` greedy / sampling / beam search (+ repetition penalty),
  * the continuous-batching engine on the paged KV cache,
  * automatic prefix caching across requests sharing a system prompt,
  * resilient serving: bounded-queue backpressure, per-request
    deadlines, and a chaos drill (injected prefill fault + forced
    pool exhaustion -> preemption) proving failure isolation,
  * the multi-replica fleet (`--replicas N`): prefix-affinity dispatch
    over N engines plus a kill-a-replica failover drill — SIGKILL one
    replica mid-decode, prove zero loss (outputs identical to an
    unkilled fleet), and print the `pdt_router_*` Prometheus dump,
  * disaggregated prefill/decode (`--roles prefill:N,decode:M`): the
    role-split fleet vs a colocated oracle on the same jobs, with a
    kill-a-prefill-replica-mid-migration drill — the transfer dies at
    the `transfer.serialize` fault site, the source is SIGKILLed, and
    outputs are still identical (KV page transfer plane + fleet-wide
    prefix store stats printed),
  * the crash-durable control plane (docs/serving.md "Durability"):
    a write-ahead-journaled fleet loses its ROUTER mid-decode
    (SIGKILL-shaped teardown), `ServingRouter.recover()` rehydrates a
    fresh incarnation from the journal — finished requests restored
    without re-execution, live ones re-prefilled with folded tokens —
    outputs identical to an unkilled fleet, `pdt_journal_*` dump
    printed,
  * the operator surface (docs/observability.md): an `SloMonitor`
    grades the drill's TTFT/availability objectives (SLO report +
    fleet status printed), and the failover timeline is written as a
    Perfetto/Chrome trace (`--trace-out`) for visual inspection,
  * speculative decoding with a draft model (lossless vs greedy),

and print per-path outputs + engine cache/occupancy stats.

    python recipes/llama_serve.py                    # tiny synthetic model
    python recipes/llama_serve.py --hf path/to/llama # converted HF weights
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser(description="Llama serving demo")
    p.add_argument("--hf", default=None,
                   help="path to a HuggingFace Llama checkpoint "
                        "(default: tiny synthetic model)")
    p.add_argument("--max-new-tokens", type=int, default=24)
    p.add_argument("--num-beams", type=int, default=4)
    p.add_argument("--draft-layers", type=int, default=1)
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="run the kill-a-replica fleet drill with "
                        "ENGINE speculative decoding (spec_decode="
                        "SpecConfig(draft, K)): the killed fleet "
                        "drafts K tokens per slot per round while the "
                        "unkilled reference fleet stays plain, so the "
                        "outputs-identical assert proves losslessness "
                        "through SIGKILL failover; prints acceptance "
                        "rate + effective tokens/sec (0 = off)")
    p.add_argument("--replicas", type=int, default=3,
                   help="fleet size for the router failover drill")
    p.add_argument("--roles", default="prefill:2,decode:2",
                   help="role split for the disaggregation drill "
                        "(prefill:N,decode:M[,colocated:K]); the "
                        "drill proves outputs identical to a "
                        "colocated fleet through a SIGKILL of a "
                        "prefill replica mid-migration")
    p.add_argument("--tp", type=int, default=0, metavar="N",
                   help="run the kill-a-SUBMESH drill: a fleet of "
                        "tensor-parallel replicas (one replica = one "
                        "N-device GSPMD submesh, serving/submesh.py), "
                        "SIGKILL one TP replica mid-decode, assert "
                        "outputs identical to an unkilled tp=1 fleet, "
                        "and print the pdt_tp/transfer Prometheus "
                        "dump (0 = off)")
    p.add_argument("--corrupt-drill", action="store_true",
                   help="run the GRAY-FAILURE drill (docs/serving.md "
                        "\"Gray failures\"): arm a seeded KV bit-flip "
                        "corrupt-mode fault on one replica of a "
                        "sentried fleet — the replica keeps answering "
                        "but answers WRONG — prove the canary probe "
                        "quarantines it and every stream re-serves "
                        "bit-identical to a clean fleet, then print "
                        "the pdt_sentry quarantine/canary Prometheus "
                        "dump")
    p.add_argument("--trace-out", default=None,
                   help="write the failover drill's Perfetto/Chrome "
                        "trace here (default: a temp file)")
    p.add_argument("--lint-gate", action="store_true",
                   help="run paddle-tpu-lint against the committed "
                        "baseline FIRST and refuse to serve a dirty "
                        "tree (the serving invariants the lint "
                        "encodes are the ones this recipe's drills "
                        "rely on — docs/static_analysis.md)")
    args = p.parse_args(argv)

    if args.lint_gate:
        # fail fast, before any model build: a tree that violates the
        # serving invariants (or drifted from the baseline) must not
        # demo green
        from paddle_tpu.analysis.__main__ import main as lint_main
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        rc = lint_main([os.path.join(root, "paddle_tpu"),
                        "--root", root])
        if rc != 0:
            print("lint gate: tree is dirty vs the pdt-lint baseline "
                  "— fix or suppress (with a reason) before serving")
            return rc
        print("lint gate: clean vs baseline")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.observability as telemetry
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.serving import ContinuousBatchingEngine
    from paddle_tpu.models.speculative import speculative_generate

    # live demo of the metric catalog: every path below records, and the
    # recipe ends with the Prometheus dump a scraper would see
    telemetry.enable()

    if args.hf:
        # transformers loads the checkpoint; the converter copies weights
        # into our model (q/k rope-permutation handled inside)
        from transformers import AutoConfig, AutoModelForCausalLM
        from paddle_tpu.models.hf_convert import load_llama_from_hf
        hc = AutoConfig.from_pretrained(args.hf)
        cfg = LlamaConfig(
            vocab_size=hc.vocab_size, hidden_size=hc.hidden_size,
            intermediate_size=hc.intermediate_size,
            num_hidden_layers=hc.num_hidden_layers,
            num_attention_heads=hc.num_attention_heads,
            num_key_value_heads=hc.num_key_value_heads,
            max_position_embeddings=hc.max_position_embeddings,
            rope_theta=getattr(hc, "rope_theta", 10000.0),
            rms_norm_eps=hc.rms_norm_eps)
        model = LlamaForCausalLM(cfg)
        # torch_dtype="auto": load at the checkpoint's stored dtype (bf16
        # for modern Llamas) instead of materializing fp32 host copies
        load_llama_from_hf(
            model, AutoModelForCausalLM.from_pretrained(
                args.hf, torch_dtype="auto").state_dict())
    else:
        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
    model.eval()
    n = args.max_new_tokens
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)

    # 1) generate(): one compiled program per strategy
    ids = paddle.to_tensor(prompt[None])
    for strat, kw in (("greedy_search", {}),
                      ("sampling", dict(temperature=0.8, top_p=0.95)),
                      ("beam_search", dict(num_beams=args.num_beams,
                                           length_penalty=0.6))):
        t0 = time.perf_counter()
        toks, score = model.generate(ids, max_new_tokens=n,
                                     decode_strategy=strat,
                                     repetition_penalty=1.1, **kw)
        dt = time.perf_counter() - t0
        print(f"{strat:>14}: {np.asarray(toks._value)[0, :8].tolist()}... "
              f"({dt:.2f}s incl. compile)")

    # 2) continuous batching on the paged cache + prefix caching
    system = rng.integers(1, cfg.vocab_size, 32).tolist()
    eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                   max_seq_len=min(
                                       256, cfg.max_position_embeddings),
                                   enable_prefix_caching=True)
    rids = [eng.add_request(
        system + rng.integers(1, cfg.vocab_size,
                              int(rng.integers(4, 10))).tolist(), n)
        for _ in range(6)]
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0
    info = eng.cache_memory_info()
    print(f"engine: {len(results)} requests, "
          f"{sum(len(v) for v in results.values())} tokens in {dt:.2f}s; "
          f"prefix hits {eng.prefix_hits} "
          f"({eng.prefix_tokens_reused} tokens reused), "
          f"pages in use {info['pages_in_use']}/{info['total_pages']}")
    assert sorted(results) == sorted(rids)

    # 3) resilient serving: backpressure + deadlines + chaos drill
    from paddle_tpu.models.serving import EngineOverloaded, RequestStatus
    from paddle_tpu.utils.faults import FaultInjector
    eng = ContinuousBatchingEngine(
        model, max_batch_size=2,
        max_seq_len=min(256, cfg.max_position_embeddings),
        max_waiting=3)
    for _ in range(3):
        eng.add_request(rng.integers(1, cfg.vocab_size, 6).tolist(), 8)
    try:
        eng.add_request([1, 2, 3], 8)
        raise AssertionError("queue bound not enforced")
    except EngineOverloaded:
        shed = True                      # ≙ a front end's 429
    reqs = {}
    with FaultInjector(seed=0) as fi:
        fi.arm("serving.prefill", nth=1)  # first prefill dies
        while True:
            for r in eng.step():
                reqs[r.rid] = r
            li = eng.lifecycle_info()
            if not li["waiting"] and not li["running"]:
                break
    statuses = sorted(r.status for r in reqs.values())
    assert statuses.count(RequestStatus.FAILED) == 1     # isolated
    assert statuses.count(RequestStatus.FINISHED) == 2   # others fine
    li = eng.lifecycle_info()
    print(f"robustness: shed_on_overload={shed}, "
          f"failures={li['failures']} (isolated), "
          f"finished={statuses.count(RequestStatus.FINISHED)}, "
          f"pages_in_use="
          f"{eng.cache_memory_info()['pages_in_use']}")

    # 3b) telemetry: the serving + chaos drill above populated the
    # metric catalog — dump the text exposition a Prometheus scraper
    # would collect, and prove it reconciles with what we observed
    snap = telemetry.snapshot()
    term = snap["counters"]["pdt_serving_requests_terminal_total"]
    assert term['status="failed"'] == statuses.count(RequestStatus.FAILED)
    assert telemetry.value("pdt_faults_fired_total",
                           site="serving.prefill") == 1
    print("--- telemetry (Prometheus text exposition) ---")
    print(telemetry.to_prometheus(), end="")
    print("--- end telemetry ---")

    # 3c) the serving fleet: prefix-affinity dispatch over --replicas
    # engines, then the failover drill — SIGKILL a replica mid-decode
    # and prove zero loss against an unkilled fleet's outputs
    from paddle_tpu.serving import ServingRouter
    from paddle_tpu.observability.slo import (SloMonitor,
                                              default_serving_objectives)

    # the draft model (shared by the --speculate fleet drill and the
    # standalone speculative_generate demo below)
    from paddle_tpu.models.serving import SpecConfig
    d_cfg = LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size // 2,
        intermediate_size=cfg.intermediate_size // 2,
        num_hidden_layers=args.draft_layers,
        num_attention_heads=max(1, cfg.num_attention_heads // 2),
        num_key_value_heads=max(1, cfg.num_key_value_heads // 2),
        max_position_embeddings=cfg.max_position_embeddings)
    paddle.seed(1)
    draft = LlamaForCausalLM(d_cfg)
    draft.eval()

    def fleet(mon=None, speculate=0):
        return ServingRouter(
            lambda i: ContinuousBatchingEngine(
                model, max_batch_size=2,
                max_seq_len=min(256, cfg.max_position_embeddings),
                enable_prefix_caching=True,
                spec_decode=SpecConfig(draft, k=speculate)
                if speculate else None),
            num_replicas=args.replicas, policy="prefix_affinity",
            page_size=16, slo_monitor=mon)

    fleet_jobs = [system + rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 10))).tolist()
        for _ in range(2 * args.replicas)]
    ref_router = fleet()
    ref_ids = [ref_router.submit(pr, n) for pr in fleet_jobs]
    want_out = ref_router.run()                  # the unkilled oracle

    # the killed fleet runs with the operator surface attached: an SLO
    # monitor grading the drill (generous bounds — tiny-model CPU
    # prefills span compiles) and a cleared trace ring so the exported
    # Perfetto timeline shows exactly the failover drill
    telemetry.clear_events()
    slo_mon = SloMonitor(default_serving_objectives(
        ttft_p95=120.0, tpot_p95=30.0, max_error_rate=0.01,
        min_availability=0.99, window_s=3600.0))
    router = fleet(mon=slo_mon, speculate=args.speculate)
    ids_f = [router.submit(pr, n) for pr in fleet_jobs]
    router.step()
    router.step()                                # mid-decode everywhere
    victim = router.requests[ids_f[0]].replica
    router.kill_replica(victim)                  # SIGKILL-shaped
    t0 = time.perf_counter()
    got_out = router.run()
    drill_wall = time.perf_counter() - t0
    assert [got_out[i] for i in ids_f] \
        == [want_out[i] for i in ref_ids], "failover changed outputs"
    info = router.fleet_info()
    print(f"fleet: {args.replicas} replicas, killed replica {victim} "
          f"mid-decode -> {info['failovers']} failover(s), "
          f"{info['pending']} lost, outputs identical; "
          f"prefix hits {info['prefix_hits']} "
          f"({info['prefix_tokens_reused']} tokens reused), "
          f"affinity hit rate "
          f"{telemetry.value('pdt_router_affinity_hit_rate'):.2f}")
    assert info["failovers"] >= 1 and info["pending"] == 0
    if args.speculate:
        # the killed fleet ran ENGINE speculation against a PLAIN
        # reference fleet — the assert above just proved losslessness
        # through the SIGKILL (the survivor's rebuilt draft cache
        # included)
        sp = info["speculation"]
        toks = sum(len(v) for v in got_out.values())
        print(f"speculation: k={args.speculate}, acceptance "
              f"{sp['acceptance_rate']:.2f} ({sp['accepted']}/"
              f"{sp['proposed']} over {sp['rounds']} rounds, "
              f"{sp['degraded']} degraded), effective "
              f"{toks / drill_wall:.0f} tok/s through the kill drill")
        assert sp["rounds"] >= 1
    print("--- router telemetry (Prometheus text exposition) ---")
    print("\n".join(line for line in telemetry.to_prometheus()
                    .splitlines() if "pdt_router" in line))
    print("--- end router telemetry ---")

    # 3d) operator surface: SLO verdicts, the fleet status report, and
    # the drill's failover timeline as a Perfetto/Chrome trace
    slo_report = slo_mon.evaluate()
    assert all(st.ok for st in slo_report.values()), slo_report
    print(slo_mon.report())
    print(telemetry.render_fleet_status(info))
    killed_rid = ids_f[0]
    tree = telemetry.request_tree(killed_rid)
    assert tree is not None and tree["children"], \
        "killed request left no span tree"
    import tempfile
    trace_out = args.trace_out or os.path.join(
        tempfile.gettempdir(), "llama_serve_failover_trace.json")
    telemetry.export_chrome_trace(path=trace_out)
    print(f"failover drill trace -> {trace_out} "
          "(load in chrome://tracing or https://ui.perfetto.dev; "
          "pid=replica, tid=request)")

    # 3f) performance attribution (docs/observability.md "Performance
    # attribution"): where did the drill's fleet steps go, and what
    # compiled — the span self-time waterfall (one row a span of the
    # router.step tree) + compile-cache table from the live
    # registry, same report `paddle-tpu-obs profile` renders offline
    # (fleet_info above already refreshed the pdt_mem_bytes ledger)
    from paddle_tpu.observability import profile as _profile
    print(_profile.snapshot_report())

    # 3e) disaggregated prefill/decode (docs/serving.md
    # "Disaggregation"): the same jobs through a colocated fleet (the
    # oracle) and a role-split fleet, with a kill-a-prefill-replica-
    # mid-migration drill — the first migration attempt dies at the
    # transfer.serialize fault site, the source replica is SIGKILLed
    # with the transfer un-done, and failover re-prefills on survivors:
    # outputs must still be identical to the unkilled colocated fleet
    from paddle_tpu.serving import parse_roles
    role_list = parse_roles(args.roles)
    n_roles = len(role_list)
    disagg_jobs = [system + rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 10))).tolist()
        for _ in range(2 * n_roles)]

    def role_fleet(roles):
        return ServingRouter(
            lambda i: ContinuousBatchingEngine(
                model, max_batch_size=2,
                max_seq_len=min(256, cfg.max_position_embeddings),
                enable_prefix_caching=True),
            num_replicas=n_roles, policy="prefix_affinity",
            page_size=16, roles=roles)

    colo = role_fleet(None)
    colo_ids = [colo.submit(pr, n) for pr in disagg_jobs]
    colo_out = colo.run()                        # the colocated oracle

    disagg = role_fleet(args.roles)
    d_ids = [disagg.submit(pr, n) for pr in disagg_jobs]
    victim = next(i for i, h in enumerate(disagg.replicas)
                  if h.role == "prefill")
    with FaultInjector(seed=0) as fi:
        fi.arm("transfer.serialize", nth=1)      # first migration dies
        disagg.step()                            # ... mid-transfer
    disagg.kill_replica(victim)                  # SIGKILL the source
    d_out = disagg.run()
    assert [d_out[i] for i in d_ids] == [colo_out[i] for i in colo_ids], \
        "disaggregation changed outputs"
    info = disagg.fleet_info()
    assert info["migrations"] >= 1 and info["pending"] == 0
    store = info["prefix_store"]
    print(f"disaggregation: roles {args.roles}, killed prefill replica "
          f"{victim} mid-migration -> {info['failovers']} failover(s), "
          f"{info['migrations']} migration(s), outputs identical to the "
          f"colocated fleet; prefix store {store['chains']} chains "
          f"({store['spilled_chains']} spilled), hit rate "
          f"{store['hit_rate']}")
    print(telemetry.render_fleet_status(info))
    print("--- transfer telemetry (Prometheus text exposition) ---")
    print("\n".join(line for line in telemetry.to_prometheus()
                    .splitlines()
                    if "pdt_transfer" in line or "pdt_prefix_store"
                    in line))
    print("--- end transfer telemetry ---")

    # 3f) tensor parallelism (docs/serving.md "Tensor parallelism"):
    # the kill-a-submesh drill — a fleet where each replica is one
    # --tp-device GSPMD submesh (weights column/row-sharded, KV pages
    # sharded on the head axis), SIGKILL one TP replica mid-decode,
    # and prove outputs identical to an unkilled tp=1 fleet; then one
    # roles migration so the per-shard transfer fragments are
    # exercised and metered
    if args.tp:
        import jax as _jax
        from paddle_tpu.serving import TpConfig
        n_dev = len(_jax.devices())
        tp_replicas = min(2, n_dev // args.tp)
        if tp_replicas < 2:
            raise SystemExit(
                f"--tp {args.tp} needs >= {2 * args.tp} devices for a "
                f"2-replica drill, have {n_dev}")
        tp_jobs = [system + rng.integers(
            1, cfg.vocab_size, int(rng.integers(4, 10))).tolist()
            for _ in range(2 * tp_replicas)]

        def tp_fleet(tp):
            if tp is None:
                return ServingRouter(
                    lambda i: ContinuousBatchingEngine(
                        model, max_batch_size=2,
                        max_seq_len=min(256,
                                        cfg.max_position_embeddings),
                        enable_prefix_caching=True),
                    num_replicas=tp_replicas)
            return ServingRouter(
                lambda i, sm: ContinuousBatchingEngine(
                    model, max_batch_size=2,
                    max_seq_len=min(256, cfg.max_position_embeddings),
                    enable_prefix_caching=True, submesh=sm),
                num_replicas=tp_replicas, tp=TpConfig(tp=tp))

        ref = tp_fleet(None)                     # the tp=1 oracle
        ref_ids = [ref.submit(pr, n) for pr in tp_jobs]
        tp_want = ref.run()
        fleet_tp = tp_fleet(args.tp)
        tp_ids = [fleet_tp.submit(pr, n) for pr in tp_jobs]
        fleet_tp.step()
        fleet_tp.step()                          # mid-decode
        victim = fleet_tp.requests[tp_ids[0]].replica
        fleet_tp.kill_replica(victim)            # SIGKILL the submesh
        tp_got = fleet_tp.run()
        assert [tp_got[i] for i in tp_ids] \
            == [tp_want[i] for i in ref_ids], \
            "tensor parallelism changed outputs"
        info = fleet_tp.fleet_info()
        print(f"tensor parallelism: {tp_replicas} replicas x "
              f"tp={args.tp}, killed replica {victim} (submesh "
              f"{info['replicas'][victim]['submesh']['devices']}) "
              f"mid-decode -> {info['failovers']} failover(s), "
              "outputs identical to the tp=1 fleet")
        assert info["failovers"] >= 1 and info["pending"] == 0
        # one migration between TP replicas: per-shard payload bytes
        disagg_tp = ServingRouter(
            lambda i, sm: ContinuousBatchingEngine(
                model, max_batch_size=2,
                max_seq_len=min(256, cfg.max_position_embeddings),
                enable_prefix_caching=True, submesh=sm),
            roles="prefill:1,decode:1", tp=args.tp, page_size=16)
        d_ids = [disagg_tp.submit(pr, n) for pr in tp_jobs]
        d_got = disagg_tp.run()
        assert [d_got[i] for i in d_ids] \
            == [tp_want[i] for i in ref_ids], \
            "TP migration changed outputs"
        assert disagg_tp.fleet_info()["migrations"] >= 1
        print(telemetry.render_fleet_status(info))
        print("--- tp telemetry (Prometheus text exposition) ---")
        print("\n".join(line for line in telemetry.to_prometheus()
                        .splitlines()
                        if "pdt_tp" in line or "pdt_transfer" in line))
        print("--- end tp telemetry ---")

    # 3g) crash-durable control plane (docs/serving.md "Durability"):
    # every drill above killed things BELOW the router; this one kills
    # the ROUTER. A journaled fleet dies mid-decode (abandoned,
    # SIGKILL-shaped — nothing of the incarnation survives but the
    # write-ahead journal directory), `ServingRouter.recover()`
    # rehydrates a fresh incarnation: requests that finished before
    # the kill restore WITHOUT re-execution (idempotent per
    # request_id), live ones re-prefill with their journaled tokens
    # folded in, and outputs must be identical to an unkilled fleet
    import shutil
    import tempfile
    from paddle_tpu.serving import RouterJournal

    def dur_engine(i):
        return ContinuousBatchingEngine(
            model, max_batch_size=2,
            max_seq_len=min(256, cfg.max_position_embeddings),
            enable_prefix_caching=True)

    dur_kwargs = dict(num_replicas=args.replicas,
                      policy="prefix_affinity", page_size=16)

    dur_jobs = [system + rng.integers(
        1, cfg.vocab_size, int(rng.integers(4, 10))).tolist()
        for _ in range(2 * args.replicas)]
    # staggered budgets: some requests must FINISH before the kill
    # (exercising the restore-without-re-execution path) while others
    # are still mid-decode (the folded re-prefill path)
    dur_budgets = [n if i % 2 == 0 else max(2, n // 4)
                   for i in range(len(dur_jobs))]
    dur_ref = ServingRouter(dur_engine, **dur_kwargs)
    dur_ref_ids = [dur_ref.submit(pr, b)
                   for pr, b in zip(dur_jobs, dur_budgets)]
    dur_want = dur_ref.run()                     # the unkilled oracle

    wal_root = tempfile.mkdtemp(prefix="llama_serve_wal_")
    try:
        wal = os.path.join(wal_root, "wal")
        router = ServingRouter(
            dur_engine, journal=RouterJournal(wal, fsync="terminal"),
            **dur_kwargs)
        dur_ids = [router.submit(pr, b)
                   for pr, b in zip(dur_jobs, dur_budgets)]
        finished_before = []
        while not finished_before:               # someone must finish
            finished_before += [r.request_id for r in router.step()]
        assert any(not router.requests[i].done for i in dur_ids)
        del router                               # SIGKILL-shaped
        recovered = ServingRouter.recover(
            RouterJournal(wal, fsync="terminal"), dur_engine,
            **dur_kwargs)
        for rid in finished_before:              # restored, not re-run
            assert recovered.requests[rid].done
            assert recovered.requests[rid].dispatches == 0
        dur_out = recovered.run()
        assert [dur_out[i] for i in dur_ids] \
            == [dur_want[i] for i in dur_ref_ids], \
            "router restart changed outputs"
        n_rec = telemetry.value("pdt_journal_replay_recovered_total")
        n_dedup = telemetry.value("pdt_journal_replay_deduped_total")
        print(f"durability: killed the ROUTER mid-decode -> recover() "
              f"rehydrated {n_rec:.0f} live request(s) and restored "
              f"{n_dedup:.0f} finished one(s) without re-execution; "
              "outputs identical to the unkilled fleet")
        assert n_rec >= 1 and n_dedup >= len(finished_before)
        print("--- journal telemetry (Prometheus text exposition) ---")
        print("\n".join(line for line in telemetry.to_prometheus()
                        .splitlines() if "pdt_journal" in line))
        print("--- end journal telemetry ---")
    finally:
        shutil.rmtree(wal_root, ignore_errors=True)

    # 3h) gray-failure drill (docs/serving.md "Gray failures"): every
    # drill above is FAIL-STOP — this one is fail-WRONG. One replica
    # of a sentried fleet gets a seeded always-firing KV bit-flip
    # (corrupt-mode fault, pinned by tag= like one sick chip); its
    # streams go silently wrong, the scheduled canary replays the
    # golden prompt THROUGH the corrupt engine and mismatches, the
    # replica quarantines, tainted token suffixes are dropped, and
    # every request re-serves bit-identically to a clean fleet
    if args.corrupt_drill:
        from paddle_tpu.serving import CanaryConfig, SentryConfig

        def gray_fleet(sentried):
            return ServingRouter(
                lambda i: ContinuousBatchingEngine(
                    model, max_batch_size=3,
                    max_seq_len=min(256, cfg.max_position_embeddings)),
                num_replicas=args.replicas, policy="round_robin",
                page_size=16,
                sentry=SentryConfig(scan_every=8) if sentried
                else None,
                canary=CanaryConfig(interval=0.05, max_new_tokens=8)
                if sentried else None,
                restart_backoff_base=0.2, restart_backoff_max=0.5)

        gray_jobs = [rng.integers(
            1, cfg.vocab_size, int(rng.integers(5, 11))).tolist()
            for _ in range(2 * args.replicas)]
        clean = gray_fleet(False)
        clean_ids = [clean.submit(pr, n) for pr in gray_jobs]
        clean_out = clean.run()                  # the uncorrupted oracle

        gray = gray_fleet(True)
        g_ids = [gray.submit(pr, n) for pr in gray_jobs]
        gray.step()
        victim = gray.requests[g_ids[0]].replica
        with FaultInjector(seed=0) as fi:
            # the sick chip: every KV commit on the victim flips one
            # seeded byte of a LIVE page — requests AND the canary
            # replay decode through the damage
            fi.arm_corrupt("serving.kv_page", mode="bitflip",
                           always=True, tag=str(victim))
            g_out = gray.run()
        assert [g_out[i] for i in g_ids] \
            == [clean_out[i] for i in clean_ids], \
            "gray failure leaked tainted tokens into a finished stream"
        info = gray.fleet_info()
        sn = info["sentry"]
        assert sn["quarantines"] >= 1, "corrupt replica never caught"
        print(f"gray failure: replica {victim} served a seeded KV "
              f"bit-flip -> canary caught it ({sn['canary_runs']} "
              f"probe(s), {sn['canary_failures']} failure(s)), "
              f"{sn['quarantines']} quarantine(s), "
              f"{sn['tainted_tokens_dropped']} tainted token(s) "
              "dropped and re-served; outputs identical to a clean "
              "fleet")
        print("--- sentry telemetry (Prometheus text exposition) ---")
        print("\n".join(line for line in telemetry.to_prometheus()
                        .splitlines() if "pdt_sentry" in line))
        print("--- end sentry telemetry ---")

    # 4) standalone speculative decoding (same draft as the fleet
    # drill's engine-mode speculation)
    want, _ = model.generate(ids, max_new_tokens=n)
    got, acc = speculative_generate(model, draft, ids, max_new_tokens=n,
                                    num_draft_tokens=4)
    ok = np.array_equal(np.asarray(got._value), np.asarray(want._value))
    print(f"speculative: lossless={ok}, draft acceptance "
          f"{float(acc):.2f}")
    assert ok
    print("SERVING DEMO OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
