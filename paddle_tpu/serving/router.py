"""Health-aware fleet router over replica engines: prefix-affinity
dispatch, replica supervision, zero-loss failover.

The layer above `models/serving.py`: one `ServingRouter` fronts N
`ReplicaHandle`s (each wrapping a `ContinuousBatchingEngine`), the way
a TPU serving deployment fronts a replica fleet with a request router —
dispatch policy decides KV prefix-cache hit rate and tail latency, the
supervisor decides whether a replica kill is an outage or a blip.

Design (everything is step-driven and clock-injectable — deterministic
on the CPU test mesh, no threads, no sleeps inside `step()`):

* **Admission** — `submit()` routes through the pluggable policy
  (`policy.py`) over replicas that `can_accept()` (healthy/degraded
  with room in their bounded queue). When no replica can take the
  request the router sheds load FLEET-WIDE: `FleetOverloaded`
  (a subclass of the engine's `EngineOverloaded`, so front ends treat
  both as a 429) carrying a `retry_after` hint — queue-depth-derived
  when replicas are merely full, next-restart-derived when the whole
  fleet is down, and burn-boosted when a `QosAdmission` controller is
  attached (`admission.derive_retry_after` is the ONE retry_after
  semantics for every refusal surface).
* **QoS** — with `admission=QosAdmission(...)` (serving/admission.py,
  docs/serving.md "Admission & QoS") every submit carries a `lane`
  (interactive | batch) and optional `tenant`: the controller
  arbitrates by SLO burn rate + tenant budgets BEFORE dispatch and a
  shed surfaces as `QosShed` (a FleetOverloaded) with a burn-derived
  `retry_after`; admitted requests dispatch with their lane's engine
  queue priority, so interactive work admits into slots ahead of
  queued batch work. A controller failure (the `admission.decide`
  fault site) fails OPEN to plain FIFO admission — QoS never wedges
  submits.
* **Mirroring** — the router keeps a `FleetRequest` per submission and,
  after every replica step, copies the tokens each live engine Request
  has produced (`folded + output`). This is exactly the information a
  real router already holds — the tokens it streamed to the client —
  and it is what makes failover zero-loss without reading a dead
  engine.
* **Supervision** — each step tick: restart-due replicas come back
  (exponential backoff with jitter, the launcher's `restart_backoff`
  shape), health probes run (`router.health` fault site + wedge
  detection on the injectable clock), every live replica steps
  (`router.step` fault site), and step/dispatch/health failures drive
  the HEALTHY -> DEGRADED -> DEAD machine in `replica.py`.
* **Failover** — when a replica dies (consecutive failures, wedge,
  or `kill_replica`), its engine is already gone (SIGKILL semantics).
  Every non-terminal mirrored request assigned to it is re-dispatched
  to a survivor with its streamed tokens FOLDED INTO the re-prefill
  prompt and its token budget reduced by what was already produced —
  the same recovery shape as the engine's own preemption (PR 1), so
  greedy outputs are bit-identical to an unfaulted run. Re-dispatch is
  idempotent per `request_id`; with no survivor the request parks
  orphaned and retries after the next restart.

* **Disaggregation** — with `roles="prefill:N,decode:M"` the fleet
  splits the engine's two phases (docs/serving.md "Disaggregation"):
  fresh submits land only on PREFILL-CAPABLE replicas (prefix-affine
  dispatch as before), and every finished prefill migrates — KV pages
  + request state through the transfer plane (`transfer.py`,
  `router.migrate` span, `pdt_transfer_*`) — to the decode replica
  with the fewest outstanding slots. The fleet-wide prefix store
  (`prefix_store.py`) replaces per-replica warmth sets and spills cold
  chains to host RAM, so a prefix outlives the replicas that computed
  it. A SIGKILL of either transfer endpoint degrades to the ordinary
  failover path: re-prefill on a survivor, greedy outputs
  bit-identical to a colocated fleet.

* **Gray failures** — with `sentry=SentryConfig(...)` and
  `canary=CanaryConfig(...)` (serving/sentry.py, docs/serving.md
  "Gray failures") the fleet defends the CORRECTNESS of its outputs,
  not just the liveness of its processes: every replica incarnation
  carries a numeric sentry (token in-vocab every step, every-Nth-step
  logit scan), a trip marks the replica SUSPECT (no new traffic,
  terminals PARK), and a canary probe — a fixed prompt whose golden
  greedy stream was computed once at fleet build — replays through
  the replica's ordinary step path immediately on suspicion and on a
  clock-driven schedule. A token mismatch is proof of corruption
  (greedy decode is batching-invariant): the replica QUARANTINES
  (engine discarded, backoff restart into canary-gated PROBATION),
  its in-flight work re-dispatches zero-loss, and tokens streamed
  since its last clean canary are TAINTED — dropped from the mirror
  and re-generated on a healthy replica, so users get correct
  streams, not fast wrong ones. A clean canary restores a SUSPECT
  replica with zero failovers and advances every resident request's
  verified-prefix frontier.

* **Durability** — with `journal=RouterJournal(...)` (serving/
  journal.py, docs/serving.md "Durability") the router write-ahead
  journals the state it already mirrors: every submit BEFORE dispatch
  (the durability point), one batched token-progress record per step
  tick, and every terminal with its final stream. A SIGKILL of the
  ROUTER process is then zero-loss: `ServingRouter.recover(journal,
  factory, ...)` builds a fresh incarnation that rehydrates every
  un-finalized request onto fresh replicas (journaled tokens folded
  into re-prefill — the PR-4 failover shape), restores finished
  requests WITHOUT re-execution (idempotent per request_id), restores
  QoS lane/tenant/budget context, and finalizes honest timeouts for
  deadlines that died with the old incarnation. Greedy outputs stay
  bit-identical to an uninterrupted fleet.

Telemetry (`pdt_router_*`, docs/serving.md "Fleet"): dispatch counters
by {policy, replica}, failover/restart counters, per-replica state and
queue-depth gauges, affinity hit-rate, fleet terminal counters that
reconcile exactly with the engines' `pdt_serving_*` counters.

Observability (docs/observability.md): `submit()` opens a REQUEST-
SCOPED TRACE keyed by the stable request_id (`trace.start_trace`);
every dispatch attempt runs under a `router.dispatch` span, and the
engine's prefill/decode spans + terminal/failover events join the same
trace automatically via their `request_id` attrs — so one request's
dispatch, queue wait, prefill, decode steps, and failover re-dispatch
form a single causal tree across replicas, exportable as a Perfetto
trace. An optional read-only `slo_monitor=` (observability.slo) is fed
each terminal outcome + the fleet-level TTFT (submit to first mirrored
token on the router clock — robust across failover), and
`fleet_info()` then reports fleet and per-replica SLO state alongside
health.
"""
from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from .. import observability as telemetry
from ..observability import profile as _profile
from ..observability import trace as tracing
from ..models.serving import (ContinuousBatchingEngine, EngineOverloaded,
                              PoolExhausted, Request, RequestStatus)
from ..utils.faults import fault_point
from . import transfer
from . import journal as journal_mod
from .admission import (Lane, QosAdmission, budget_key,
                        derive_retry_after, note_failopen)
from .journal import RouterJournal
from .model_store import FleetModelStore, split_model_id
from .policy import (DispatchPolicy, ModelAffinityPolicy,
                     PrefixAffinityPolicy, make_policy)
from .prefix_store import FleetPrefixStore
from .replica import ReplicaHandle, ReplicaRole, ReplicaState
from . import sentry as sentry_mod
from .sentry import CanaryConfig, SentryConfig

__all__ = ["ServingRouter", "FleetRequest", "FleetOverloaded",
           "QosShed", "parse_roles"]


def parse_roles(roles):
    """Normalize a role spec into a per-replica role list: None (all
    colocated), a ``"prefill:2,decode:1"`` string, a ``{role: count}``
    dict, or an explicit per-index list. String/dict forms order
    replicas prefill, then decode, then colocated — so
    ``"prefill:2,decode:2"`` puts prefill on indices 0-1."""
    if roles is None:
        return None
    if isinstance(roles, str):
        spec = {}
        for part in roles.split(","):
            if not part.strip():
                continue
            name, _, count = part.partition(":")
            spec[name.strip()] = int(count) if count.strip() else 1
        roles = spec
    if isinstance(roles, dict):
        out = []
        for name, count in roles.items():
            if name not in ReplicaRole.ALL:
                raise ValueError(f"unknown replica role {name!r}: "
                                 f"{sorted(ReplicaRole.ALL)}")
            if int(count) < 1:
                raise ValueError(
                    f"role count must be >= 1, got {name}:{count}")
        for name in (ReplicaRole.PREFILL, ReplicaRole.DECODE,
                     ReplicaRole.COLOCATED):
            out.extend([name] * int(roles.get(name, 0)))
        return out
    out = [str(r) for r in roles]
    for name in out:
        if name not in ReplicaRole.ALL:
            raise ValueError(f"unknown replica role {name!r}: "
                             f"{sorted(ReplicaRole.ALL)}")
    return out


_M_DISPATCH = telemetry.counter(
    "pdt_router_dispatch_total",
    "Requests dispatched to a replica, by policy and replica "
    "(failover re-dispatches included).", ("policy", "replica"))
_M_REJECTIONS = telemetry.counter(
    "pdt_router_rejections_total",
    "Fleet-level submit refusals by reason.", ("reason",))
_M_FAILOVERS = telemetry.counter(
    "pdt_router_failovers_total",
    "In-flight requests re-routed off a dead replica.")
_M_TERMINAL = telemetry.counter(
    "pdt_router_requests_terminal_total",
    "Fleet requests reaching a terminal state, by final status.",
    ("status",))
_M_AFF_LOOKUPS = telemetry.counter(
    "pdt_router_affinity_lookups_total",
    "Prefix-affinity placement decisions.")
_M_AFF_HITS = telemetry.counter(
    "pdt_router_affinity_hits_total",
    "Placements that found a warm prefix chain on some replica.")
_M_AFF_RATE = telemetry.gauge(
    "pdt_router_affinity_hit_rate",
    "Warm-placement fraction of prefix-affinity decisions so far.")
_M_STEPS = telemetry.counter(
    "pdt_router_steps_total", "Router step ticks.")
_M_MODEL_COLD = telemetry.counter(
    "pdt_router_model_cold_installs_total",
    "Placements that had to cold-install the request's model on the "
    "chosen replica through the fleet model store (the model-affinity "
    "miss path), by canonical model id.", ("model",))
_M_RESIZES = telemetry.counter(
    "pdt_router_resizes_total",
    "Completed fleet resizes by kind (grow | shrink | recarve | "
    "roles), each a two-phase INTENT/COMMIT journal transaction on "
    "journal-attached fleets.", ("kind",))


class FleetOverloaded(EngineOverloaded):
    """Fleet-wide admission refusal. `retry_after` hints (seconds) when
    capacity is likely back: queue-drain-derived when replicas are
    full, restart-backoff-derived when the whole fleet is down."""

    def __init__(self, message: str, retry_after: float):
        super().__init__(f"{message} (retry after ~{retry_after:.2f}s)")
        self.retry_after = retry_after


class QosShed(FleetOverloaded):
    """A QoS admission shed (serving/admission.py): the fleet COULD
    take the request but the SLO burn / tenant-budget arbitration
    refused it. Same 429 surface as FleetOverloaded; `retry_after` is
    burn-derived through the shared `derive_retry_after` semantics."""

    def __init__(self, message: str, retry_after: float, *,
                 lane: str, tenant: str, reason: str,
                 burn_rate: float):
        super().__init__(message, retry_after)
        self.lane = lane
        self.tenant = tenant
        self.reason = reason
        self.burn_rate = burn_rate


@dataclass
class FleetRequest:
    """Router-side mirror of one submitted request (module docstring:
    the basis of zero-loss failover). `tokens` is the full stream the
    fleet has produced; `folded` is the part baked into the CURRENT
    replica's re-prefill prompt after failovers."""

    request_id: str
    prompt: List[int]
    max_new_tokens: int
    deadline_abs: Optional[float] = None    # router-clock absolute
    max_queue_time: Optional[float] = None
    # QoS (serving/admission.py): the lane rides into the engine as a
    # queue priority; the tenant is admission-side bookkeeping only
    lane: str = Lane.INTERACTIVE
    tenant: Optional[str] = None
    priority: int = 0
    # canonical model id (serving/model_store.py) on multi-model
    # fleets; None on fleets without a model store. Durable at submit,
    # re-ensured on every (re-)dispatch — failover, recovery, and
    # quarantine re-serve all land the request back on ITS weights
    model: Optional[str] = None
    # gray-failure taint frontier (docs/serving.md "Gray failures"):
    # tokens[:verified_len] are trusted — folded at dispatch onto the
    # current replica, or mirrored before that replica's last CLEAN
    # canary. On quarantine the suffix past it is dropped and
    # re-generated on a healthy replica
    verified_len: int = 0
    # bounded-staleness durability frontier (ISSUE 18): tokens
    # [:durable_len] are journaled (group-commit at harvest ticks) —
    # a router SIGKILL loses at most the suffix past it, and replay
    # re-generates that suffix bit-identically. Monotone except at
    # quarantine, which clamps it to verified_len with the taint
    # rewind. Always <= len(tokens) <= device_len: the engine may be
    # up to harvest_every-1 dispatches ahead of everything mirrored
    durable_len: int = 0
    # router-clock request timeline: TTFT for SLO purposes is measured
    # HERE (first mirrored token minus submit), not on any one engine's
    # clock — an engine's arrival_time resets on every failover
    # re-dispatch, which would under-report exactly when failover
    # added the latency
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    status: str = RequestStatus.QUEUED
    tokens: List[int] = field(default_factory=list)
    folded: List[int] = field(default_factory=list)
    replica: Optional[int] = None
    generation: int = -1       # replica incarnation it was dispatched to
    engine_req: Optional[Request] = None
    dispatches: int = 0
    failovers: int = 0
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.status in RequestStatus.TERMINAL

    @property
    def device_len(self) -> int:
        """Tokens the serving engine has COMMITTED ON DEVICE for this
        request — the top of the staleness contract
        ``durable_len <= verified_len/len(tokens) <= device_len``.
        On the pipelined loop (harvest_every>1) this runs up to k-1
        ahead of ``tokens``; those tokens are discardable (a crash
        mid-window re-generates them bit-identically from the
        harvested prefix)."""
        if self.engine_req is None:
            return len(self.tokens)
        return len(self.folded) + max(self.engine_req.device_len,
                                      len(self.engine_req.output))


class ServingRouter:
    """Deterministic, step-driven router over a replica fleet.

    `engine_factory(index)` builds one replica's engine; it is called
    N times up front and again on every restart. With `tp=` set the
    router carves one submesh per replica and calls the factory as
    `engine_factory(index, submesh)` — pass the submesh through to
    `ContinuousBatchingEngine(submesh=...)`. Pass the router's
    `clock` into the engines it builds when per-request deadlines must
    stay exact across failover (the router re-derives the remaining
    budget on the same clock).

    Drive it like the engine: `submit()` then `run()`, or `step()`
    yourself. `sleep` is only used by `run()` while the whole fleet
    waits on a restart backoff (tests pass the fake clock's `advance`).
    """

    def __init__(self, engine_factory:
                 Callable[..., ContinuousBatchingEngine],
                 num_replicas: int = 2,
                 policy="least_outstanding",
                 *, page_size: int = 16,
                 roles=None,
                 tp=None,
                 prefix_store: Optional[FleetPrefixStore] = None,
                 model_store: Optional[FleetModelStore] = None,
                 max_replica_outstanding: Optional[int] = None,
                 degraded_after: int = 1,
                 dead_after: int = 3,
                 wedge_timeout: Optional[float] = None,
                 restart_backoff_base: float = 1.0,
                 restart_backoff_max: float = 60.0,
                 max_restarts: Optional[int] = 5,
                 retry_after_per_request: float = 0.05,
                 clock: Optional[Callable[[], float]] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 slo_monitor=None,
                 admission: Optional[QosAdmission] = None,
                 journal: Optional[RouterJournal] = None,
                 sentry: Optional[SentryConfig] = None,
                 canary: Optional[CanaryConfig] = None,
                 transfer_stage_deadline: Optional[float] = None,
                 seed: int = 0):
        # roles (disaggregated prefill/decode, docs/serving.md
        # "Disaggregation"): a spec — see `parse_roles` — defines both
        # the fleet SIZE and each replica's role; without one every
        # replica is colocated and num_replicas rules
        role_list = parse_roles(roles)
        if role_list is not None:
            num_replicas = len(role_list)
        else:
            role_list = [ReplicaRole.COLOCATED] * num_replicas
        if num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, got "
                             f"{num_replicas}")
        if not any(r in ReplicaRole.PREFILL_CAPABLE for r in role_list):
            raise ValueError(
                "a fleet needs at least one prefill-capable replica "
                "(prefill or colocated) — decode-only fleets can "
                "never admit")
        self.roles_enabled = any(r != ReplicaRole.COLOCATED
                                 for r in role_list)
        self._clock = clock if clock is not None else time.monotonic
        self._sleep = sleep
        # read-only observability hook (observability.slo.SloMonitor):
        # fed terminal outcomes + TTFT; never consulted for routing
        self.slo_monitor = slo_monitor
        # QoS admission brain (serving/admission.py) — consulted by
        # submit() BEFORE dispatch; unlike slo_monitor it DOES shape
        # traffic. Build it over the same monitor/clock for
        # burn-arbitrated shedding
        self.admission = admission
        # crash durability (serving/journal.py): submits journal BEFORE
        # dispatch, token mirrors once per step, terminals with their
        # final stream — ServingRouter.recover() is the read side
        self.journal = journal
        # the fleet-wide prefix store rides along whenever roles are on
        # (its spill is what makes a prefix outlive its replica); pass
        # `prefix_store=` to share one across routers or tune bounds
        if prefix_store is None and self.roles_enabled:
            prefix_store = FleetPrefixStore(page_size=page_size)
        self.prefix_store = prefix_store
        # the fleet model store (serving/model_store.py, ISSUE 17):
        # model identity becomes a routing dimension — submit(model=)
        # validates against it, _dispatch ensures residency through
        # it, and the model_affinity policy reads its resident sets
        self.model_store = model_store
        self.policy: DispatchPolicy = make_policy(
            policy, page_size=page_size, store=prefix_store,
            model_store=model_store)
        self._retry_cost = float(retry_after_per_request)
        # tensor parallelism (serving/submesh.py, docs/serving.md
        # "Tensor parallelism"): `tp=` (an int or a TpConfig) carves
        # `num_replicas` DISJOINT tp-device submeshes from the global
        # device set at construction — one per replica slot, kept
        # across restarts — and the factory must take (index, submesh)
        self.submeshes = None
        self._tp_cfg = None
        if tp is not None:
            from .submesh import TpConfig, carve_submeshes
            self._tp_cfg = tp if isinstance(tp, TpConfig) \
                else TpConfig(tp=int(tp))
            self.submeshes = carve_submeshes(num_replicas, self._tp_cfg)
        # gray-failure defense (serving/sentry.py, docs/serving.md
        # "Gray failures"): sentry trips need a canary to clear or
        # condemn them — a SUSPECT replica with no probe would park
        # forever, so the pairing is mandatory
        if sentry is not None and canary is None:
            raise ValueError(
                "sentry= requires canary= — a SUSPECT replica can "
                "only be cleared or condemned by a canary probe")
        self.sentry_cfg = sentry
        self.canary_cfg = canary
        # per-stage migration deadline (serving/transfer.py): a slow
        # serialize/install is counted, deferred, and charged to the
        # slow endpoint's health instead of silently eaten
        self.transfer_stage_deadline = transfer_stage_deadline
        self._canary_golden: Optional[List[int]] = None
        # per-hosted-BASE canary goldens on multi-model fleets: a
        # replica whose base was swapped is graded against ITS model's
        # golden stream, lazily computed per base (`_golden_for`)
        self._canary_goldens: Dict[str, List[int]] = {}
        if canary is not None:
            self._canary_golden = self._compute_canary_golden(
                engine_factory)
            if model_store is not None:
                self._canary_goldens[model_store.base_model] = \
                    self._canary_golden
        # everything _make_handle needs to build a replica slot again
        # later: the resize API (ISSUE 16) grows/shrinks/recarves the
        # fleet after construction with handles identical to these
        self._engine_factory = engine_factory
        self._page_size = page_size
        self._fleet_rng = random.Random(seed)
        self._handle_kw = dict(
            degraded_after=degraded_after, dead_after=dead_after,
            wedge_timeout=wedge_timeout,
            max_outstanding=max_replica_outstanding,
            restart_backoff_base=restart_backoff_base,
            restart_backoff_max=restart_backoff_max,
            max_restarts=max_restarts)
        self.replicas: List[ReplicaHandle] = [
            self._make_handle(i, role_list[i],
                              None if self.submeshes is None
                              else self.submeshes[i])
            for i in range(num_replicas)]
        self.num_quarantines = 0
        self.num_tainted_tokens = 0
        self.num_migrations = 0
        self.requests: Dict[str, FleetRequest] = {}
        # non-terminal requests only: the per-step harvest/failover
        # scans iterate THIS index, not every request ever submitted
        self._live: Dict[str, FleetRequest] = {}
        self._next_id = 0
        self.num_failovers = 0
        self.num_restarts = 0
        self.num_resizes = 0
        # monotone two-phase resize sequence (recovery resumes it past
        # the highest journaled seq)
        self._resize_seq = 0
        # observation counters for the autoscaler (serving/
        # autoscaler.py): submit ATTEMPTS (refusals included — arrival
        # rate must see the load the fleet is shedding) and survived
        # journal append failures (degraded mode refuses scale-up
        # while the journal is failing)
        self.num_submit_attempts = 0
        self.journal_append_failures = 0
        # per-model accounting (multi-model fleets, fleet_info
        # "models"/"autoscale"): submit attempts and cold installs by
        # canonical model id, terminals by (model id, final status) —
        # the exact-reconciliation ledger the soak recipe checks
        self.num_submit_attempts_by_model: Dict[str, int] = {}
        self.num_cold_installs_by_model: Dict[str, int] = {}
        self.num_terminal_by_model: Dict[str, Dict[str, int]] = {}
        # requests finalized OUTSIDE the step tick (e.g. a deadline that
        # expires during a submit-time failover) are delivered by the
        # next step() — same never-lose-a-terminal shape as the engine's
        # _finished_backlog
        self._terminal_backlog: List[FleetRequest] = []

    def _make_handle(self, index: int, role: str, submesh,
                     generation: int = 0) -> ReplicaHandle:
        """Build one replica slot (construction and every resize use
        the same recipe). A non-zero `generation` seeds a REPLACEMENT
        slot (tp recarve) past its predecessor's, so requests
        dispatched to the old incarnation read as stranded and fail
        over — the fresh engine never heard of them."""
        h = ReplicaHandle(index, self._engine_factory,
                          clock=self._clock, submesh=submesh,
                          rng=random.Random(self._fleet_rng.random()),
                          role=role, sentry_config=self.sentry_cfg,
                          probation_gate=self.canary_cfg is not None,
                          **self._handle_kw)
        if generation:
            h.generation = generation
        return h

    def _note_append_failure(self, error: BaseException,
                             where: str) -> None:
        """Counted-but-survived journal append failure — the shared
        module counter/event plus a router-local tally the autoscaler
        reads: a journal that is failing fsync puts the fleet in
        degraded mode (scale-up refused, serving/autoscaler.py)."""
        self.journal_append_failures += 1
        journal_mod.note_append_failure(error, where=where)

    # -- admission -------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32,
               request_id: Optional[str] = None,
               deadline: Optional[float] = None,
               max_queue_time: Optional[float] = None,
               lane: str = Lane.INTERACTIVE,
               tenant: Optional[str] = None,
               model: Optional[str] = None) -> str:
        """Admit one request into the fleet; returns its stable
        request_id. Re-submitting an id already known to the router is
        a no-op returning the same id (idempotent retries: a client
        that lost the response resubmits without double-generating).
        `lane`/`tenant` feed the QoS controller when one is attached
        (`admission=`): a QoS refusal raises `QosShed`, hard
        backpressure raises `FleetOverloaded` — both 429-shaped with
        one `retry_after` semantics. Raises FleetOverloaded when no
        replica can accept.

        `model` (multi-model fleets, `model_store=`) is the canonical
        model id the request must decode under — a registered full
        checkpoint or ``base+adapter`` LoRA fine-tune. Unregistered
        ids refuse HERE (typed, before any journal/dispatch work);
        omitting it on a multi-model fleet pins the store's builtin
        base, so a replica whose base was swapped away still serves
        the base-model stream."""
        if request_id is not None and request_id in self.requests:
            return request_id
        if lane not in Lane.ALL:
            raise ValueError(f"unknown lane {lane!r}: "
                             f"{sorted(Lane.ALL)}")
        if model is not None:
            if self.model_store is None:
                raise ValueError(
                    "submit(model=) needs a model_store= attached to "
                    "the router (serving.model_store.FleetModelStore)")
            if not self.model_store.known(model):
                _M_REJECTIONS.inc(reason="unknown_model")
                raise ValueError(
                    f"unknown model {model!r}: the fleet store hosts "
                    f"{self.model_store.models()} — register_model/"
                    "register_adapter it first")
        elif self.model_store is not None:
            model = self.model_store.base_model
        # arrival-rate observation (refusals INCLUDED: the autoscaler
        # must see the demand the fleet is shedding, not just what it
        # admitted)
        self.num_submit_attempts += 1
        if model is not None:
            self.num_submit_attempts_by_model[model] = \
                self.num_submit_attempts_by_model.get(model, 0) + 1
        toks = [int(t) for t in prompt]
        decision = None
        if self.admission is not None:
            try:
                decision = self.admission.decide(
                    prompt_tokens=len(toks),
                    max_new_tokens=int(max_new_tokens),
                    lane=lane, tenant=tenant, model=model,
                    queue_depth=min(
                        (h.outstanding() for h in self.replicas
                         if h.alive()), default=0))
            except Exception as e:
                # fail OPEN: a broken/faulted admission brain degrades
                # to plain FIFO admission — never wedge submits
                note_failopen(e, where="router.submit")
                decision = None
            if decision is not None and not decision.admit:
                _M_REJECTIONS.inc(reason="qos_shed")
                raise QosShed(
                    f"QoS shed ({decision.reason}): lane "
                    f"{decision.lane!r}, tenant {decision.tenant!r}, "
                    f"burn {decision.burn_rate:.2f}",
                    decision.retry_after, lane=decision.lane,
                    tenant=decision.tenant, reason=decision.reason,
                    burn_rate=decision.burn_rate)
        if request_id is None:
            # skip ids the caller already used — colliding would
            # silently overwrite an in-flight record
            while f"fleet-{self._next_id}" in self.requests:
                self._next_id += 1
            request_id = f"fleet-{self._next_id}"
            self._next_id += 1
        now = self._clock()
        rec = FleetRequest(
            request_id, toks, int(max_new_tokens),
            deadline_abs=None if deadline is None else now + deadline,
            max_queue_time=max_queue_time, submit_time=now,
            lane=lane, tenant=tenant, priority=Lane.PRIORITY[lane],
            model=model)
        if self.journal is not None:
            # the DURABILITY point (docs/serving.md "Durability"): the
            # submit record lands BEFORE any dispatch, so a router
            # SIGKILL at any later instant is recoverable. An append
            # failure here refuses the submit — work the journal
            # cannot record must not be accepted
            self.journal.append_submit(
                request_id=request_id, prompt=toks,
                max_new_tokens=int(max_new_tokens), lane=lane,
                tenant=tenant, priority=rec.priority, model=model,
                deadline_abs=rec.deadline_abs,
                max_queue_time=max_queue_time)
        # one distributed trace per request, keyed by the stable id:
        # every span/event below that carries this request_id (dispatch
        # attempts, engine prefill/first-token/terminal, failovers)
        # joins it, across replicas and restarts
        tracing.start_trace(request_id, name="router.submit",
                            request_id=request_id,
                            prompt_tokens=len(toks),
                            max_new_tokens=int(max_new_tokens))
        try:
            self._dispatch(rec, forced=False)
        except BaseException:
            if self.journal is not None:
                # the journaled submit must not be resurrected by
                # recover(): the client saw this refusal
                try:
                    self.journal.append_rejected(request_id)
                except Exception as e:
                    self._note_append_failure(
                        e, where="router.submit_rejected")
            tracing.end_trace(request_id)   # refused: nothing to trace
            raise
        # budget charge only AFTER the fleet actually accepted — a
        # fleet_full refusal must not bill the tenant for nothing.
        # Fail OPEN like decide(): the request is ALREADY dispatched,
        # so a broken commit must lose the bookkeeping, never the
        # request
        if decision is not None:
            try:
                self.admission.commit(decision)
            except Exception as e:
                note_failopen(e, where="router.commit")
        self.requests[request_id] = rec
        self._live[request_id] = rec
        return request_id

    def _accepting(self) -> List[ReplicaHandle]:
        """Replicas eligible for new work, HEALTHY before DEGRADED (a
        degraded replica takes traffic only when no healthy one can).
        Fresh submits are PREFILL-CAPABLE only: decode-role replicas
        receive work exclusively through the transfer plane."""
        capable = [h for h in self.replicas
                   if h.role in ReplicaRole.PREFILL_CAPABLE
                   and h.can_accept()]
        healthy = [h for h in capable
                   if h.state == ReplicaState.HEALTHY]
        if healthy:
            return healthy
        return [h for h in capable
                if h.state == ReplicaState.DEGRADED]

    def _burn_hint(self) -> float:
        """The QoS controller's cached burn rate for retry_after
        derivation (0 without a controller — and 0 when the controller
        is broken: the hint is best-effort, fail open)."""
        if self.admission is None:
            return 0.0
        try:
            return self.admission.current_burn()
        except Exception as e:
            # same fail-open surface as a decide() fault: degraded,
            # never silent (PDT006)
            note_failopen(e, where="router.retry_after")
            return 0.0

    def _overloaded(self) -> FleetOverloaded:
        # both refusal reasons derive retry_after through the SAME
        # semantics as a QoS shed (admission.derive_retry_after):
        # queue drain vs burn backoff vs restart wait, whichever is
        # strongest
        now = self._clock()
        # DRAINING replicas are alive but their capacity is never
        # coming back for NEW work — they must not feed a
        # queue-will-drain retry hint
        alive = [h for h in self.replicas
                 if h.state in (ReplicaState.HEALTHY,
                                ReplicaState.DEGRADED)
                 and h.engine is not None
                 and h.role in ReplicaRole.PREFILL_CAPABLE]
        if alive:
            _M_REJECTIONS.inc(reason="fleet_full")
            depth = min(h.outstanding() for h in alive)
            return FleetOverloaded(
                f"every replica queue is full "
                f"({len(alive)} alive, min depth {depth})",
                retry_after=derive_retry_after(
                    self._retry_cost, queue_depth=depth,
                    burn_rate=self._burn_hint()))
        _M_REJECTIONS.inc(reason="no_replicas")
        pending = [h.next_restart_time - now for h in self.replicas
                   if h.next_restart_time is not None]
        return FleetOverloaded(
            "no live replicas",
            retry_after=derive_retry_after(
                0.001, burn_rate=self._burn_hint(),
                restart_wait=max(0.001, min(pending))
                if pending else 1.0))

    def _dispatch(self, rec: FleetRequest, forced: bool):
        """Place `rec` on a replica. `forced` (failover) ignores the
        bounded-queue cap — zero-loss beats backpressure for work the
        fleet already accepted — but still respects health states.
        A dispatch failure counts against that replica's health and the
        next candidate is tried (each replica at most once per call);
        with none left: FleetOverloaded (fresh submits) or an orphaned
        park (failovers, retried next step)."""
        tried = set()
        while True:
            if forced:
                # zero-loss beats role purity: stranded work prefers
                # prefill-capable survivors but re-prefills on a decode
                # replica when nothing else is left standing
                tiers = (
                    [h for h in self.replicas
                     if h.state == ReplicaState.HEALTHY
                     and h.role in ReplicaRole.PREFILL_CAPABLE],
                    [h for h in self.replicas
                     if h.state == ReplicaState.DEGRADED
                     and h.role in ReplicaRole.PREFILL_CAPABLE],
                    [h for h in self.replicas
                     if h.state == ReplicaState.HEALTHY],
                    [h for h in self.replicas
                     if h.state == ReplicaState.DEGRADED],
                )
                cands = next((t for t in tiers if t), [])
            else:
                cands = self._accepting()
            cands = [h for h in cands if h.index not in tried]
            if not cands:
                if forced:
                    rec.replica, rec.engine_req = None, None
                    rec.status = RequestStatus.QUEUED
                    return
                raise self._overloaded()
            if rec.model is not None \
                    or isinstance(self.policy, ModelAffinityPolicy):
                h = self.policy.select(cands,
                                       self._effective_prompt(rec),
                                       model=rec.model)
            else:
                # legacy two-arg call: user-supplied policies predating
                # the model dimension keep working on model-less fleets
                h = self.policy.select(cands, self._effective_prompt(rec))
            if isinstance(self.policy, PrefixAffinityPolicy):
                _M_AFF_LOOKUPS.inc()
                if self.policy.last_match_pages > 0:
                    _M_AFF_HITS.inc()
                if telemetry.enabled():
                    lookups = telemetry.value(
                        "pdt_router_affinity_lookups_total")
                    if lookups:
                        _M_AFF_RATE.set(telemetry.value(
                            "pdt_router_affinity_hits_total") / lookups)
            if not tried:
                # once per PLACEMENT, not per retried candidate: the
                # store's hit/miss accounting describes routing
                # decisions, and the spill restore warms the
                # first-choice replica only (a retry's replica gets
                # warmed by its own next placement)
                spilled = self._restore_spill(
                    h, self._effective_prompt(rec))
                if self.prefix_store is not None \
                        and isinstance(self.policy,
                                       PrefixAffinityPolicy):
                    self.prefix_store.note_lookup(
                        "replica" if self.policy.last_match_pages > 0
                        else "spill" if spilled else "miss")
            tried.add(h.index)
            if self.model_store is not None and rec.model is not None:
                # make the request's model resident BEFORE the engine
                # sees the request: warm replicas are a move-to-end,
                # cold ones install through the store's byte-budgeted
                # LRU (full-checkpoint swaps need an idle engine — a
                # busy replica's refusal is a capacity event, not a
                # health event: try the next candidate, shed if none)
                try:
                    with telemetry.span("router.model_install",
                                        request_id=rec.request_id,
                                        replica=h.index,
                                        model=rec.model):
                        cold = self.model_store.ensure(
                            h.index, h.engine, rec.model)
                except Exception as e:
                    telemetry.event("router.model_install_failed",
                                    request_id=rec.request_id,
                                    replica=h.index, model=rec.model,
                                    error=f"{type(e).__name__}: {e}")
                    continue
                if cold:
                    _M_MODEL_COLD.inc(model=rec.model)
                    self.num_cold_installs_by_model[rec.model] = \
                        self.num_cold_installs_by_model.get(
                            rec.model, 0) + 1
            try:
                # one span per ATTEMPT: failed candidates stay in the
                # trace with their error, so a failover's path across
                # replicas reads straight off the request tree
                # candidate = how many replicas THIS placement pass has
                # tried (incl. this one) — truthful per-call ordering;
                # use `seq` to order across passes
                with telemetry.span("router.dispatch",
                                    request_id=rec.request_id,
                                    replica=h.index,
                                    policy=self.policy.name,
                                    forced=forced,
                                    candidate=len(tried)):
                    rec.engine_req = h.dispatch(
                        self._effective_prompt(rec),
                        self._remaining_budget(rec), rec.request_id,
                        deadline=self._remaining_deadline(rec),
                        max_queue_time=rec.max_queue_time,
                        priority=rec.priority,
                        adapter=self._adapter_of(rec))
            except EngineOverloaded:
                # the engine's OWN admission bound refused (a factory
                # that set max_waiting): not a health event — try the
                # next replica
                continue
            except ValueError as e:
                # request-shaped refusal (empty prompt, zero budget,
                # a prompt that could never fit the pool): the
                # CALLER's fault, not the replica's — charging it to
                # health would let one malformed submit degrade the
                # whole fleet
                if not forced:
                    raise
                rec.status = RequestStatus.FAILED
                rec.error = f"failover re-dispatch rejected: {e}"
                rec.engine_req = None
                self._terminal_backlog.append(rec)
                self._live.pop(rec.request_id, None)
                self._journal_terminal(rec)
                _M_TERMINAL.inc(status=rec.status)
                self._count_model_terminal(rec)
                telemetry.event("router.terminal",
                                request_id=rec.request_id,
                                status=rec.status, replica=None,
                                tokens=len(rec.tokens),
                                failovers=rec.failovers)
                self._slo_feed(rec)
                tracing.end_trace(rec.request_id)
                return
            except Exception as e:          # router.dispatch fault etc.
                if h.note_failure(self._clock(), e):
                    self._failover_replica(h)
                continue
            rec.replica = h.index
            rec.generation = h.generation
            rec.folded = list(rec.tokens)
            # the folded prefix is the trusted baseline on the new
            # replica: whatever it streams past this point is inside
            # ITS taint window until a clean canary advances the
            # frontier (quarantine truncates back to here)
            rec.verified_len = len(rec.tokens)
            rec.status = RequestStatus.QUEUED
            rec.dispatches += 1
            if self.model_store is not None and rec.model is not None:
                # in-flight pin: the store's LRU may not evict this
                # model off this replica until the matching unpin
                # (_finalize / migration hand-off; replica death
                # clears pins wholesale via forget_replica)
                self.model_store.pin(h.index, rec.model)
            self.policy.on_dispatch(h, self._effective_prompt(rec))
            _M_DISPATCH.inc(policy=self.policy.name,
                            replica=str(h.index))
            return

    def _effective_prompt(self, rec: FleetRequest) -> List[int]:
        """What the next replica must prefill: the original prompt plus
        every token the fleet already streamed (the engine-preemption
        fold-in shape, one level up)."""
        return rec.prompt + rec.tokens if rec.tokens else rec.prompt

    def _adapter_of(self, rec: FleetRequest) -> Optional[str]:
        """The engine-side adapter name for this request's model id
        (None for a bare checkpoint or a model-less fleet)."""
        if rec.model is None:
            return None
        return split_model_id(rec.model)[1]

    def _unpin_model(self, rec: FleetRequest):
        """Release the in-flight residency pin taken at dispatch (a
        dead replica's pins were already cleared wholesale by
        `forget_replica`, where unpin is a no-op)."""
        if self.model_store is not None and rec.model is not None \
                and rec.replica is not None:
            self.model_store.unpin(rec.replica, rec.model)

    def _count_model_terminal(self, rec: FleetRequest):
        """Per-(model, status) terminal ledger — reconciles EXACTLY
        with per-model submits once the fleet drains (the multimodel
        soak's check), alongside `pdt_router_requests_terminal_total`."""
        if rec.model is None:
            return
        row = self.num_terminal_by_model.setdefault(rec.model, {})
        row[rec.status] = row.get(rec.status, 0) + 1

    def _remaining_budget(self, rec: FleetRequest) -> int:
        return rec.max_new_tokens - len(rec.tokens)

    def _remaining_deadline(self, rec: FleetRequest) -> Optional[float]:
        if rec.deadline_abs is None:
            return None
        return rec.deadline_abs - self._clock()

    # -- the step tick ---------------------------------------------------
    def step(self) -> List[FleetRequest]:
        """One fleet tick: restarts due -> health probes -> step every
        live replica (harvesting token streams and terminal requests)
        -> fail over work stranded on replicas that died this tick.
        Returns the fleet requests that reached a terminal state."""
        # the root of a fleet step's span tree (docs/observability.md);
        # its self time is the router's own work between replica steps
        with telemetry.span("router.step"):
            _M_STEPS.inc()
            now = self._clock()
            finished = self._terminal_backlog
            self._terminal_backlog = []
            for h in self.replicas:
                if h.maybe_restart(now):
                    self.num_restarts += 1
            unhealthy = set()
            for h in self.replicas:
                try:
                    h.check_health(now)     # may kill a wedged replica
                except Exception as e:      # router.health fault fired
                    h.note_failure(now, e)
                    # a replica that just failed its probe sits this tick
                    # out — otherwise an immediately-successful step would
                    # erase the probe failure and the probe would mean
                    # nothing
                    unhealthy.add(h.index)
            # canary probes launch where due (suspect/probation replicas
            # immediately, healthy ones on the schedule) so this same
            # tick's replica steps start serving them
            self._launch_canaries(now)
            for h in self.replicas:
                if not h.alive() or h.index in unhealthy:
                    continue
                # canary probes are infra, not traffic: they neither make
                # a step "busy" for the restart-budget ledger nor count as
                # served work — only a canary PASS proves anything
                busy = h.real_outstanding() > 0
                try:
                    done = h.step()
                except Exception as e:
                    h.note_failure(self._clock(), e)
                    continue
                canary_id = (h.canary["request_id"]
                             if h.canary is not None else None)
                # an idle tick is not evidence of stability: only steps that
                # served real work reset the restart-backoff budget
                h.note_success(self._clock(),
                               did_work=busy or any(
                                   r.request_id != canary_id for r in done))
                # poll sentry trips BEFORE delivering this step's
                # terminals: a trip raised inside h.step() must park the
                # very terminals it casts doubt on
                if h.sentry is not None and h.sentry.trips > h.sentry_seen:
                    h.sentry_seen = h.sentry.trips
                    h.mark_suspect("sentry_trip")
                canary_done = None
                for req in done:
                    if canary_id is not None \
                            and req.request_id == canary_id:
                        canary_done = req
                        continue
                    rec = self.requests.get(req.request_id)
                    if rec is None:
                        continue
                    if h.state == ReplicaState.SUSPECT:
                        # a terminal from a replica under suspicion must
                        # not finalize until the canary rules — its stream
                        # may be tainted (docs/serving.md "Gray failures")
                        h.parked.append((rec, req))
                    else:
                        self._finalize(rec, req, finished)
                self._harvest(h)
                if canary_done is not None:
                    self._canary_verdict(h, canary_done, finished,
                                         self._clock())
                h.finish_drain_if_empty(self._clock())
            # disaggregation hand-off: finished prefills on prefill-role
            # replicas migrate to decode replicas through the transfer
            # plane, BEFORE the failover scan (a migrated request must not
            # read as stranded on its source)
            if self.roles_enabled:
                self._migrate_ready()
            # suspicion that resolved WITHOUT a canary verdict (the
            # replica died, was killed, or drained mid-suspicion): deliver
            # the parked terminals as the engine reported them — the taint
            # window closes unproven, a documented detection-latency hole
            # (docs/serving.md failure matrix), not silent data loss
            for h in self.replicas:
                if h.parked and h.state != ReplicaState.SUSPECT:
                    for rec, req in h.parked:
                        if not rec.done:
                            self._finalize(rec, req, finished)
                    h.parked = []
            # failover pass: anything mirrored onto a replica that is no
            # longer alive (died in the health or step pass, or was killed
            # between ticks), plus orphans parked by an earlier all-dead tick
            for h in self.replicas:
                if not h.alive():
                    self._forget_caches(h.index)   # its warm cache is gone
            for rec in list(self._live.values()):
                if rec.done:
                    continue
                h = (self.replicas[rec.replica]
                     if rec.replica is not None else None)
                if h is None or not h.alive() \
                        or rec.generation != h.generation:
                    # a generation mismatch means the replica died AND
                    # restarted since this request was dispatched — the
                    # fresh engine never heard of it, however alive the
                    # handle looks now
                    self._failover_one(rec)
            finished += self._terminal_backlog
            self._terminal_backlog = []
            # durability: mirror this tick's new tokens into the journal
            # AFTER harvests and failovers, so one batched progress record
            # reflects exactly what the router would have streamed
            if self.journal is not None:
                with telemetry.span("router.journal_mirror"):
                    self._journal_mirror()
            for h in self.replicas:
                h.update_gauges()
            return finished

    def _forget_caches(self, index: int):
        """A replica's warm state died with it: the dispatch policy
        AND the fleet prefix store both forget (the store's host-RAM
        spill survives — that is the point of it)."""
        self.policy.forget(index)
        if self.prefix_store is not None:
            self.prefix_store.forget_replica(index)
        if self.model_store is not None:
            # residency (and every in-flight pin) was device state —
            # it died with the engine; artifacts are host state and
            # survive for the next cold install
            self.model_store.forget_replica(index)

    def _restore_spill(self, h: ReplicaHandle, prompt) -> int:
        """Re-install a host-RAM-spilled prefix chain into the chosen
        replica BEFORE dispatch, so a chain that outlived every warm
        replica (prefix_store.py) still saves the prefill — admission
        then matches the engine's trie as if the chain had always
        lived there. Best-effort: cache warming never fails a
        dispatch. Returns the pages installed."""
        store = self.prefix_store
        if store is None or h.engine is None:
            return 0
        if isinstance(self.policy, PrefixAffinityPolicy) \
                and self.policy.last_match_pages > 0:
            return 0               # a warm replica was found: no need
        entry = store.fetch(prompt)
        if entry is None:
            return 0
        try:
            installed = h.engine.import_prefix(*entry)
        except Exception as e:
            # best-effort still means VISIBLE: a failing spill restore
            # must not read as an ordinary cold miss (PDT006 — this
            # handler swallowed errors silently before pdt-lint)
            telemetry.event("router.prefix_restore_failed",
                            replica=h.index,
                            error=f"{type(e).__name__}: {e}")
            return 0
        if installed:
            telemetry.event("router.prefix_restore", replica=h.index,
                            pages=installed)
        return installed

    def _migrate_ready(self):
        """The disaggregation hand-off (one pass per step tick): every
        request whose PREFILL has finished on a prefill-role replica
        migrates — pages + state, serving/transfer.py — to the decode
        replica with the fewest outstanding slots (decode dispatch
        balances decode slots, where prefill dispatch stays
        prefix-affine). Capacity refusals defer to the next tick with
        the request decoding where it is: migration is an
        optimization, never a dependency. Transfer FAILURES leave both
        engines consistent (serialize is read-only, install backs its
        slot out), so the request simply stays on its source — if the
        source then dies mid-transfer, the ordinary failover pass
        re-prefills it on a survivor with its streamed tokens folded
        in, bit-identical to a colocated fleet."""
        targets = [h for h in self.replicas
                   if h.role == ReplicaRole.DECODE and h.alive()]
        for rec in list(self._live.values()):
            if rec.done or rec.replica is None \
                    or rec.engine_req is None:
                continue
            src = self.replicas[rec.replica]
            if src.role != ReplicaRole.PREFILL or not src.alive() \
                    or rec.generation != src.generation \
                    or src.state == ReplicaState.SUSPECT:
                # a SUSPECT source neither donates nor receives
                # migrations: its pages are in question, and moving
                # them would carry the taint outside the quarantine
                # machinery's reach
                continue
            req = rec.engine_req
            if req.status != RequestStatus.RUNNING or not req.output:
                continue           # not prefilled yet (or requeued)
            # re-check can_accept PER migration: each install raises a
            # target's outstanding count, and the bounded per-replica
            # queue (max_replica_outstanding) must hold for migrated
            # work exactly as it does for fresh dispatches
            avail = [t for t in targets if t.can_accept()]
            if not avail:
                return             # no decode capacity this tick
            dst = min(avail, key=lambda t: (t.outstanding(), t.index))
            if self.model_store is not None and rec.model is not None:
                # the target must host this request's model BEFORE the
                # pages move — `import_pages` refuses a cross-model
                # import with a typed ModelMismatch (pages are a
                # function of the weights), so a target the store
                # cannot prepare right now (busy base swap) simply
                # defers the migration to a later tick
                try:
                    self.model_store.ensure(dst.index, dst.engine,
                                            rec.model)
                except Exception as e:
                    telemetry.event("router.model_install_failed",
                                    request_id=rec.request_id,
                                    replica=dst.index, model=rec.model,
                                    error=f"{type(e).__name__}: {e}")
                    continue
            try:
                # the span joins the request's distributed trace via
                # request_id — migration shows up between the source's
                # prefill and the target's decode steps
                with telemetry.span("router.migrate",
                                    request_id=rec.request_id,
                                    from_replica=src.index,
                                    to_replica=dst.index,
                                    tokens=len(rec.tokens)):
                    new_req, payload = transfer.migrate_request(
                        src.engine, dst.engine, req.rid,
                        deadline=self._remaining_deadline(rec),
                        clock=self._clock,
                        stage_deadline=self.transfer_stage_deadline)
            except (EngineOverloaded, PoolExhausted):
                # target full RIGHT NOW: try other targets for later
                # requests, retry this one next tick
                targets = [t for t in targets if t is not dst]
                continue
            except transfer.TransferStageTimeout as e:
                # a stage that RETURNED but overran its deadline: the
                # migration is deferred (both engines are consistent —
                # a late install was backed out) and the SLOW endpoint
                # is charged a health failure, so a persistently slow
                # replica degrades instead of wedging every tick's
                # migration pass (transfer.py already counted
                # stage="timeout" + the transfer.failed event)
                slow = src if e.stage == "serialize" else dst
                if slow.note_failure(self._clock(), e):
                    self._failover_replica(slow)
                continue
            # pdt-lint: disable=PDT006 transfer.migrate_request already
            # counted pdt_transfer_failures_total{stage=} and emitted
            # transfer.failed before re-raising — a second count here
            # would double-book the same fault
            except Exception:
                # both engines are consistent and a dead endpoint is
                # the health/failover machinery's job — leave the
                # request where it is
                continue
            if self.model_store is not None and rec.model is not None:
                # the residency pin follows the request across the
                # hand-off
                self.model_store.unpin(src.index, rec.model)
                self.model_store.pin(dst.index, rec.model)
            rec.replica, rec.generation = dst.index, dst.generation
            rec.engine_req = new_req    # rec.folded is unchanged: the
            #                             target holds the same output
            #                             stream the source did
            # hand-off closes the source's taint window (same scope
            # rule as a dispatch fold-in): the target's window opens
            # at the full mirrored stream
            rec.verified_len = len(rec.tokens)
            rec.dispatches += 1
            self.num_migrations += 1
            src.migrations_out += 1
            dst.migrations_in += 1
            if self.prefix_store is not None:
                # the serialized prompt pages are host-side already —
                # spilling them is free, and makes the chain outlive
                # every replica that ever computed it
                self.prefix_store.spill_payload(payload)
                self.prefix_store.record(dst.index, payload["prompt"])

    def _harvest(self, h: ReplicaHandle):
        """Mirror the token streams of this replica's live requests —
        the 'already streamed to the client' state failover folds in.
        The first harvest that sees tokens stamps the request's
        fleet-level first-token time (router clock)."""
        for rec in self._live.values():
            if rec.replica == h.index and not rec.done \
                    and rec.generation == h.generation \
                    and rec.engine_req is not None:
                rec.tokens = rec.folded + list(rec.engine_req.output)
                if rec.tokens and rec.first_token_time is None:
                    rec.first_token_time = self._clock()

    def _journal_terminal(self, rec: FleetRequest):
        """Append one terminal record (final status + the complete
        stream). Counted-but-survived on failure: the request IS
        terminal regardless, and a greedy recovery re-derives a lost
        terminal by re-execution, bit-identically."""
        if self.journal is None:
            return
        try:
            self.journal.append_terminal(rec.request_id, rec.status,
                                         rec.tokens, rec.error)
            rec.durable_len = len(rec.tokens)
        except Exception as e:
            self._note_append_failure(e, where="router.terminal")

    def _journal_mirror(self):
        """One batched progress record per step tick: the journal
        diffs the full mirrors against its own table and records only
        new suffixes. Counted-but-survived on failure (a lost suffix
        re-generates bit-identically from the folded re-prefill)."""
        if self.journal is None:
            return
        try:
            self.journal.step_mirror(
                {rec.request_id: rec.tokens
                 for rec in self._live.values() if rec.tokens})
            # the whole mirrored prefix is now journaled: advance each
            # live request's durability frontier to it. On pipelined
            # replicas mirrors only change at harvest ticks, so this
            # is naturally one group-commit per window
            for rec in self._live.values():
                if rec.tokens:
                    rec.durable_len = len(rec.tokens)
        except Exception as e:
            self._note_append_failure(e, where="router.step")

    def _finalize(self, rec: FleetRequest, req: Request,
                  finished: List[FleetRequest]):
        rec.tokens = rec.folded + list(req.output)
        if rec.tokens and rec.first_token_time is None:
            rec.first_token_time = self._clock()
        rec.status = req.status
        rec.error = req.error
        rec.engine_req = None
        self._unpin_model(rec)
        self._live.pop(rec.request_id, None)
        finished.append(rec)
        self._journal_terminal(rec)
        _M_TERMINAL.inc(status=rec.status)
        self._count_model_terminal(rec)
        telemetry.event("router.terminal", request_id=rec.request_id,
                        status=rec.status, replica=rec.replica,
                        tokens=len(rec.tokens),
                        failovers=rec.failovers)
        self._slo_feed(rec)
        tracing.end_trace(rec.request_id)

    def _failover_replica(self, h: ReplicaHandle):
        """Re-route everything mirrored onto `h` (which just died)."""
        self._forget_caches(h.index)
        for rec in list(self._live.values()):
            if rec.replica == h.index and not rec.done:
                self._failover_one(rec)

    def _failover_one(self, rec: FleetRequest):
        """Zero-loss re-dispatch of one stranded request: streamed
        tokens fold into the survivor's re-prefill, budget shrinks by
        what was already produced, the id stays stable (idempotent)."""
        from_replica = rec.replica
        if rec.deadline_abs is not None \
                and self._clock() >= rec.deadline_abs:
            # its budget elapsed while its replica was dead: finalize
            # honestly instead of re-prefilling doomed work
            rec.status = RequestStatus.TIMEOUT
            rec.error = "deadline expired during failover"
            rec.engine_req = None
            self._live.pop(rec.request_id, None)
            self._terminal_backlog.append(rec)
            self._journal_terminal(rec)
            _M_TERMINAL.inc(status=rec.status)
            self._count_model_terminal(rec)
            telemetry.event("router.terminal",
                            request_id=rec.request_id,
                            status=rec.status, replica=from_replica,
                            tokens=len(rec.tokens),
                            failovers=rec.failovers)
            self._slo_feed(rec)
            tracing.end_trace(rec.request_id)
            return
        if from_replica is not None:
            # an orphan being retried (replica=None) already counted
            # when it left its dead replica — don't inflate per retry
            rec.failovers += 1
            self.num_failovers += 1
            _M_FAILOVERS.inc()
            telemetry.event("router.failover",
                            request_id=rec.request_id,
                            from_replica=from_replica,
                            tokens_folded=len(rec.tokens),
                            budget_left=self._remaining_budget(rec))
        self._dispatch(rec, forced=True)
        if rec.replica is None:
            telemetry.event("router.orphaned",
                            request_id=rec.request_id,
                            tokens_folded=len(rec.tokens))

    def _slo_feed(self, rec: FleetRequest):
        """Read-only SLO hook: one terminal outcome (+ the fleet-level
        TTFT when a first token was streamed) per request, tagged with
        the replica that held it last. TTFT is submit-to-first-
        mirrored-token on the ROUTER clock, so time a request spent on
        a replica that died before producing anything counts — the
        client waited through it. Nothing here influences routing."""
        mon = self.slo_monitor
        if mon is None:
            return
        replica = None if rec.replica is None else str(rec.replica)
        mon.observe_outcome("outcome",
                            rec.status == RequestStatus.FINISHED,
                            replica=replica)
        if rec.first_token_time is not None:
            ttft = rec.first_token_time - rec.submit_time
            mon.observe("ttft", ttft, replica=replica)
            # lane-scoped signal (`ttft.interactive` / `ttft.batch`)
            # so QoS arbitration can burn on the PROTECTED lane's
            # objective alone — docs/serving.md "Admission & QoS"
            mon.observe(f"ttft.{rec.lane}", ttft, replica=replica)

    # -- gray-failure defense (serving/sentry.py, ISSUE 14) --------------
    def _compute_canary_golden(self, engine_factory,
                               base_mid: Optional[str] = None
                               ) -> List[int]:
        """The canary's golden greedy stream, computed ONCE per
        (model, tp, quant) at fleet build on a SCRATCH engine from the
        same factory (replica-0 signature, same submesh under TP, same
        `quant=` mode — a QUANTIZED replica's correct stream differs
        from bf16's, so a golden from any other configuration would
        false-quarantine healthy replicas; deriving it from the fleet's
        own factory is what keeps the golden in the replicas' numeric
        regime by construction) — a live replica's engine would be
        left warm and its counters skewed. Greedy decoding is
        batching-invariant (test-pinned since PR 1), so any healthy
        replica must reproduce this stream exactly, whatever traffic
        it is serving alongside."""
        cfg = self.canary_cfg
        if self.submeshes is not None:
            eng = engine_factory(0, self.submeshes[0])
        else:
            eng = engine_factory(0)
        if base_mid is not None and self.model_store is not None \
                and base_mid != self.model_store.base_model:
            # per-hosted-model goldens (multi-model fleets): host the
            # checkpoint on the scratch engine through the store's own
            # install path, then drop the scratch replica's residency
            # accounting — the golden must come from the SAME install
            # seam the fleet's replicas use
            self.model_store.ensure("__golden__", eng, base_mid)
            self.model_store.forget_replica("__golden__")
        rid = eng.add_request(list(cfg.prompt),
                              int(cfg.max_new_tokens))
        out = eng.run()[rid]
        return [int(t) for t in out]

    def _golden_for(self, h: ReplicaHandle) -> Optional[List[int]]:
        """The canary golden for the base checkpoint `h` currently
        HOSTS: on multi-model fleets a swapped replica is graded
        against ITS model's stream (grading it against any other
        base's golden would false-quarantine a healthy replica — the
        PR-14 arm must fire on the right stream), lazily computed per
        base on a scratch engine. The canary probe itself carries no
        adapter, so its stream is a pure function of the base."""
        if self.model_store is None:
            return self._canary_golden
        base = self.model_store.replica_base(h.index)
        g = self._canary_goldens.get(base)
        if g is None:
            g = self._compute_canary_golden(self._engine_factory, base)
            self._canary_goldens[base] = g
        return g

    def _launch_canaries(self, now: float):
        """Start canary probes where due: immediately on SUSPECT and
        PROBATION replicas, on the clock-driven schedule for healthy
        ones. The probe is an ordinary engine request (reserved
        ``__canary_*`` id, never a FleetRequest) riding the replica's
        normal step path — corruption in that engine corrupts the
        canary too, which is the point. An overloaded engine defers
        the launch to the next tick."""
        if self.canary_cfg is None:
            return
        for h in self.replicas:
            if not h.alive() or h.engine is None \
                    or h.canary is not None:
                continue
            if h.state in (ReplicaState.SUSPECT,
                           ReplicaState.PROBATION):
                due = True
            elif h.state in (ReplicaState.HEALTHY,
                             ReplicaState.DEGRADED):
                itv = self.canary_cfg.interval
                due = itv is not None \
                    and now - h.last_canary_start >= itv
            else:
                due = False            # draining: on its way out
            if not due:
                continue
            cid = f"__canary_{h.index}_{h.canary_seq}"
            try:
                rid = h.engine.add_request(
                    list(self.canary_cfg.prompt),
                    int(self.canary_cfg.max_new_tokens),
                    request_id=cid)
            except EngineOverloaded:
                continue               # full queue: retry next tick
            h.canary_seq += 1
            h.last_canary_start = now
            h.canary = {"request_id": cid, "rid": rid,
                        "generation": h.generation, "started": now,
                        "trips0": h.sentry.trips
                        if h.sentry is not None else 0}

    def _canary_verdict(self, h: ReplicaHandle, req: Request,
                        finished: List[FleetRequest], now: float):
        """One canary completed on `h`: grade it and act.

        * **pass** — tokens == golden AND no sentry trips in the
          run's window: suspicion lifts / probation ends (restart
          budget resets), parked terminals deliver with ZERO
          failovers, and every resident request's verified-prefix
          frontier advances to its full mirror.
        * **dirty** — tokens match but the sentry tripped during the
          run: inconclusive. Stay SUSPECT and probe again; after
          `max_suspect_rounds` consecutive dirty passes the replica
          is quarantined as persistently sick.
        * **fail** — token mismatch: PROOF of corruption (greedy is
          batching-invariant) — quarantine.
        * **aborted** — the probe finalized without finishing
          (starved/timed out): no verdict; relaunch next tick.
        """
        state = h.canary
        h.canary = None
        trips = (h.sentry.trips - state["trips0"]) \
            if h.sentry is not None else 0
        if req.status != RequestStatus.FINISHED:
            result = "aborted"
        elif [int(t) for t in req.output] != self._golden_for(h):
            result = "fail"
        elif trips > 0:
            result = "dirty"
        else:
            result = "pass"
        h.canary_runs += 1
        sentry_mod.note_canary(result, now - state["started"])
        telemetry.event("sentry.canary", replica=h.index,
                        result=result, tokens=len(req.output),
                        trips=trips, probe=state["request_id"])
        if result == "pass":
            for rec in self._live.values():
                if rec.replica == h.index \
                        and rec.generation == h.generation \
                        and not rec.done:
                    rec.verified_len = len(rec.tokens)
            for prec, preq in h.parked:
                if not prec.done:      # delivered: zero failovers
                    self._finalize(prec, preq, finished)
            h.parked = []
            h.note_canary_pass(now)
        elif result == "dirty":
            h.canary_failures += 1
            h.suspect_rounds += 1
            if h.suspect_rounds >= self.canary_cfg.max_suspect_rounds:
                self._quarantine(h, "sentry_dirty")
        elif result == "fail":
            h.canary_failures += 1
            self._quarantine(h, "canary_mismatch")

    def _quarantine(self, h: ReplicaHandle, reason: str):
        """Canary evidence condemned `h`: drop every resident
        request's TAINTED suffix (tokens mirrored since the replica's
        last clean canary — `verified_len` is the frontier), then
        kill the replica into QUARANTINED. The same step's failover
        scan re-dispatches the stranded work from the truncated
        mirrors — greedy re-generates the dropped suffix
        bit-identically on a healthy replica, so zero tainted tokens
        can reach a finished stream. Parked terminals re-serve the
        same way (their recs never left `_live`)."""
        now = self._clock()
        h.parked = []
        for rec in list(self._live.values()):
            if rec.replica != h.index \
                    or rec.generation != h.generation or rec.done:
                continue
            dropped = len(rec.tokens) - rec.verified_len
            if dropped > 0:
                self.num_tainted_tokens += dropped
                sentry_mod.note_tainted(dropped)
                telemetry.event("sentry.tainted",
                                request_id=rec.request_id,
                                replica=h.index, dropped=dropped,
                                kept=rec.verified_len)
                rec.tokens = rec.tokens[:rec.verified_len]
                # the taint rewind is the ONE sanctioned retreat of
                # the durability frontier: journaled-but-tainted
                # tokens are no longer durable once the rewind record
                # supersedes them
                rec.durable_len = min(rec.durable_len,
                                      rec.verified_len)
                if self.journal is not None:
                    # the journal mirrored the tainted suffix as
                    # progress records — it must forget it too, or a
                    # recovery landing before this request's terminal
                    # would fold tainted tokens back in as a trusted
                    # prefix (and later suffixes would journal at
                    # misaligned offsets). Counted-but-survived like
                    # a terminal append; the double-fault window
                    # (rewind append lost AND router killed pre-
                    # terminal) is in the failure matrix
                    try:
                        self.journal.rewind(rec.request_id,
                                            rec.verified_len)
                    except Exception as e:
                        self._note_append_failure(
                            e, where="router.quarantine")
            rec.engine_req = None
        self.num_quarantines += 1
        sentry_mod.note_quarantine(h.index)
        telemetry.event("replica.quarantine", replica=h.index,
                        reason=reason,
                        suspect_rounds=h.suspect_rounds)
        h.die(reason, now, to_state=ReplicaState.QUARANTINED)
        self._forget_caches(h.index)   # its warm pages are condemned

    # -- operator surface ------------------------------------------------
    def _replica_at(self, index: int) -> ReplicaHandle:
        """Typed index validation for the manual scaling primitives:
        an out-of-range index is an operator error, reported as such —
        never a bare IndexError from fleet internals (and after a
        scale-down, yesterday's valid index may be gone)."""
        if not 0 <= int(index) < len(self.replicas):
            raise ValueError(
                f"no replica {index}: fleet has "
                f"{len(self.replicas)} replicas (0.."
                f"{len(self.replicas) - 1})")
        return self.replicas[int(index)]

    def kill_replica(self, index: int, reason: str = "killed"):
        """SIGKILL-style drill switch: the replica dies NOW (engine
        discarded), restart is scheduled with backoff, and the next
        step() re-routes its in-flight work. `tests/test_chaos.py` and
        the llama_serve drill use this for deterministic mid-decode
        kills."""
        h = self._replica_at(index)
        h.die(reason, self._clock())
        self._forget_caches(h.index)

    def drain_replica(self, index: int) -> bool:
        """Graceful decommission: no new traffic, in-flight completes,
        then the replica parks dead until `restore_replica`. Repeats
        are idempotent no-ops and conflicting states raise
        `ReplicaOpRefused` — `ReplicaHandle.drain` has the contract."""
        return self._replica_at(index).drain()

    def restore_replica(self, index: int) -> bool:
        """Bring a drained/dead replica back (fresh engine, no
        backoff). Restoring a live replica is an idempotent no-op;
        restoring one still draining raises `ReplicaOpRefused` —
        `ReplicaHandle.restore` has the contract."""
        return self._replica_at(index).restore(self._clock())

    def release_request(self, request_id: str):
        """Drop a TERMINAL request's record once its result has been
        delivered — a long-running fleet must evict, or `requests`
        grows without bound. Releasing a live request is refused."""
        rec = self.requests.get(request_id)
        if rec is None:
            return
        if not rec.done:
            raise ValueError(f"request {request_id!r} is still "
                             f"{rec.status}; only terminal requests "
                             "can be released")
        del self.requests[request_id]
        if self.journal is not None:
            # the client acknowledged delivery: compaction may drop
            # the request's journal history entirely
            try:
                self.journal.append_release(request_id)
            except Exception as e:
                self._note_append_failure(e,
                                          where="router.release")

    # -- elastic resize (ISSUE 16) ---------------------------------------
    def _current_topology(self) -> dict:
        return {"num_replicas": len(self.replicas),
                "roles": [h.role for h in self.replicas],
                "tp": None if self._tp_cfg is None
                else self._tp_cfg.tp}

    def resize(self, num_replicas: Optional[int] = None,
               roles=None, tp=None, *,
               reason: str = "operator") -> dict:
        """Change the fleet's topology — replica count, roles mix,
        and/or tp carve — as ONE crash-durable transaction
        (docs/serving.md "Autoscaling"). On journal-attached fleets
        the full target topology is journaled as a ``resize_intent``
        BEFORE any fleet mutation and a ``resize_commit`` lands after
        the last one, so a router SIGKILL at any instant recovers via
        `recover()` into exactly the old topology (killed before the
        intent reached disk) or the new one (any later instant) with
        zero lost tokens.

        * **grow** — new replica slots append at the top indices; on
          canary fleets they land in PROBATION and take no real
          traffic until their canary passes.
        * **shrink** — the top slots drain via MIGRATION: running
          work moves to survivors through the transfer plane (prefix
          payloads spill warm), anything unmovable re-prefills on a
          survivor with its mirrored stream folded in (zero loss,
          greedy bit-identical either way).
        * **tp change** — a full recarve: every slot gets a fresh
          engine on the new submesh carve and every live request
          re-enters through the ordinary failover fold-in.

        An impossible target (no prefill-capable replica, a carve
        that does not fit the device mesh) refuses BEFORE the intent
        is journaled. Returns a summary dict; ``changed=False`` means
        the target equals the current topology and nothing was done.

        The ``autoscale.resize`` fault site fires at every journal
        record boundary (before/after INTENT, mid-mutation,
        before/after COMMIT) so chaos drills can kill the router at
        each of them."""
        from .submesh import TpConfig, carve_submeshes
        role_list = parse_roles(roles)
        if role_list is not None:
            num_replicas = len(role_list)
        n_new = len(self.replicas) if num_replicas is None \
            else int(num_replicas)
        if n_new < 1:
            raise ValueError(f"num_replicas must be >= 1, got {n_new}")
        if role_list is None:
            # surviving slots keep their roles; added slots colocate
            cur = [h.role for h in self.replicas]
            role_list = (cur + [ReplicaRole.COLOCATED]
                         * max(0, n_new - len(cur)))[:n_new]
        if not any(r in ReplicaRole.PREFILL_CAPABLE
                   for r in role_list):
            raise ValueError(
                "a fleet needs at least one prefill-capable replica "
                "(prefill or colocated) — decode-only fleets can "
                "never admit")
        if tp is None:
            tp_cfg = self._tp_cfg
        else:
            tp_cfg = tp if isinstance(tp, TpConfig) \
                else TpConfig(tp=int(tp))
        tp_changed = ((None if tp_cfg is None else tp_cfg.tp)
                      != (None if self._tp_cfg is None
                          else self._tp_cfg.tp))
        if tp_cfg is not None:
            # validate the carve BEFORE journaling: an intent the
            # mutation could never honor must not reach the journal
            carve_submeshes(n_new, tp_cfg)
        target = {"num_replicas": n_new, "roles": list(role_list),
                  "tp": None if tp_cfg is None else tp_cfg.tp}
        if target == self._current_topology():
            return {"changed": False, "topology": target}
        n_old = len(self.replicas)
        kind = ("recarve" if tp_changed
                else "grow" if n_new > n_old
                else "shrink" if n_new < n_old else "roles")
        seq = self._resize_seq + 1
        fault_point("autoscale.resize")   # kill: before the INTENT
        if self.journal is not None:
            # raises on failure: a resize the journal cannot record
            # must not start (the submit-append rule, one level up)
            self.journal.append_resize_intent(seq, target)
        self._resize_seq = seq
        telemetry.event("router.resize", phase="intent", seq=seq,
                        kind=kind, reason=reason,
                        num_replicas=n_new, tp=target["tp"])
        fault_point("autoscale.resize")   # kill: INTENT durable,
        #                                   fleet untouched
        self._apply_topology(n_new, role_list, tp_cfg, tp_changed)
        fault_point("autoscale.resize")   # kill: mutated, no COMMIT
        if self.journal is not None:
            try:
                self.journal.append_resize_commit(seq)
            except Exception as e:
                # counted-but-survived: recovery rolls the open
                # intent forward into the SAME topology the live
                # fleet is already running
                self._note_append_failure(
                    e, where="router.resize_commit")
        self.num_resizes += 1
        _M_RESIZES.inc(kind=kind)
        telemetry.event("router.resize", phase="commit", seq=seq,
                        kind=kind, reason=reason,
                        num_replicas=n_new, tp=target["tp"])
        fault_point("autoscale.resize")   # kill: after the COMMIT
        return {"changed": True, "seq": seq, "kind": kind,
                "topology": target}

    def _apply_topology(self, n_new: int, role_list: List[str],
                        tp_cfg, tp_changed: bool) -> None:
        """The mutation half of a resize — only ever reached through
        an intent: `resize()` journals the ``resize_intent`` first,
        and `_topology_recover` replays one (pdt-lint PDT009 pins
        this dominance for every topology-mutation call site)."""
        if tp_changed:
            self._topology_recarve(n_new, role_list, tp_cfg)
        else:
            if n_new < len(self.replicas):
                self._topology_shrink(n_new)
            elif n_new > len(self.replicas):
                self._topology_grow(n_new, role_list)
            self._topology_set_roles(role_list)
        fault_point("autoscale.resize")   # kill: fleet mutated,
        #                                   stranded work not re-routed
        self._reroute_stranded()

    def _topology_shrink(self, n_new: int) -> None:
        """Retire the top `len - n_new` slots: drain-via-migration
        (running work moves warm through the transfer plane), then
        the slot dies decommissioned and its handle is removed. Work
        that could not migrate is re-routed by `_reroute_stranded`
        through the zero-loss failover fold-in."""
        now = self._clock()
        survivors = self.replicas[:n_new]
        victims = self.replicas[n_new:]
        for v in victims:
            self._evacuate(v, survivors)
        for v in victims:
            v.auto_restart = False     # removed slots must stay gone
            if v.state not in ReplicaState.DOWN:
                v.die("scale_down", now)
            self._forget_caches(v.index)
        del self.replicas[n_new:]
        if self.submeshes is not None:
            # the carve is deterministic contiguous slices, so the
            # surviving prefix is exactly the old slots' submeshes
            self.submeshes = self.submeshes[:n_new]

    def _evacuate(self, victim: ReplicaHandle,
                  survivors: List[ReplicaHandle]) -> None:
        """Scale-down drain: move the victim's RUNNING requests to
        survivors through the transfer plane — pages + state, no
        recompute — spilling each prefix payload warm into the fleet
        store. Best-effort: a refusal (capacity, transfer fault,
        not-yet-prefilled) leaves the request for the failover
        fold-in, which re-prefills it bit-identically."""
        if victim.engine is None \
                or victim.state == ReplicaState.SUSPECT:
            return      # nothing to donate / taint must not spread
        for rec in list(self._live.values()):
            if rec.done or rec.replica != victim.index \
                    or rec.generation != victim.generation \
                    or rec.engine_req is None:
                continue
            req = rec.engine_req
            if req.status != RequestStatus.RUNNING or not req.output:
                continue   # not prefilled: re-dispatch costs nothing
            avail = [t for t in survivors
                     if t.alive() and t.can_accept()
                     and t.state != ReplicaState.SUSPECT]
            if not avail:
                return     # no survivor capacity: failover handles it
            dst = min(avail, key=lambda t: (t.outstanding(), t.index))
            if self.model_store is not None and rec.model is not None:
                # same discipline as the disagg hand-off: the survivor
                # must host this request's model BEFORE the pages move
                # (`import_pages` refuses cross-model payloads typed);
                # a survivor the store cannot prepare leaves the
                # request for the failover fold-in
                try:
                    self.model_store.ensure(dst.index, dst.engine,
                                            rec.model)
                except Exception as e:
                    telemetry.event("router.model_install_failed",
                                    request_id=rec.request_id,
                                    replica=dst.index, model=rec.model,
                                    error=f"{type(e).__name__}: {e}")
                    continue
            try:
                with telemetry.span("router.migrate",
                                    request_id=rec.request_id,
                                    from_replica=victim.index,
                                    to_replica=dst.index,
                                    tokens=len(rec.tokens)):
                    new_req, payload = transfer.migrate_request(
                        victim.engine, dst.engine, req.rid,
                        deadline=self._remaining_deadline(rec),
                        clock=self._clock,
                        stage_deadline=self.transfer_stage_deadline)
            # pdt-lint: disable=PDT006 transfer.migrate_request already
            # counted pdt_transfer_failures_total{stage=} and emitted
            # transfer.failed before re-raising — a second count here
            # would double-book the same fault
            except Exception:
                # both engines stay consistent on any refusal/fault;
                # the stranded request re-prefills on a survivor
                continue
            if self.model_store is not None and rec.model is not None:
                # the residency pin follows the request across the
                # hand-off
                self.model_store.unpin(victim.index, rec.model)
                self.model_store.pin(dst.index, rec.model)
            rec.replica, rec.generation = dst.index, dst.generation
            rec.engine_req = new_req
            rec.verified_len = len(rec.tokens)
            rec.dispatches += 1
            self.num_migrations += 1
            victim.migrations_out += 1
            dst.migrations_in += 1
            if self.prefix_store is not None:
                self.prefix_store.spill_payload(payload)
                self.prefix_store.record(dst.index, payload["prompt"])

    def _topology_grow(self, n_new: int,
                       role_list: List[str]) -> None:
        """Append fresh slots at the top indices. Under tp the carve
        re-derives for the larger fleet — deterministic contiguous
        slices, so existing slots keep their exact device sets. On
        canary fleets every added slot lands in PROBATION."""
        n_old = len(self.replicas)
        if self._tp_cfg is not None:
            from .submesh import carve_submeshes
            self.submeshes = carve_submeshes(n_new, self._tp_cfg)
        for i in range(n_old, n_new):
            h = self._make_handle(i, role_list[i],
                                  None if self.submeshes is None
                                  else self.submeshes[i])
            h.start_in_probation("scale_up")
            self.replicas.append(h)

    def _topology_recarve(self, n_new: int, role_list: List[str],
                          tp_cfg) -> None:
        """Change the tp width: every engine's sharding changes, so
        every slot is rebuilt on the new carve (the GSPMD
        re-partitioning shape). Replacement slots seed their
        generation PAST the old one, so every live request reads as
        stranded and re-enters through the failover fold-in — greedy
        keeps the streams bit-identical. The canary golden recomputes
        for the new carve (a different sharding is a different
        numeric regime)."""
        from .submesh import carve_submeshes
        now = self._clock()
        self._tp_cfg = tp_cfg
        self.submeshes = None if tp_cfg is None \
            else carve_submeshes(n_new, tp_cfg)
        old = self.replicas
        fresh: List[ReplicaHandle] = []
        for i in range(n_new):
            gen = old[i].generation + 1 if i < len(old) else 0
            fresh.append(self._make_handle(
                i, role_list[i],
                None if self.submeshes is None else self.submeshes[i],
                generation=gen))
        for h in old:
            h.auto_restart = False
            if h.state not in ReplicaState.DOWN:
                h.die("recarve", now)
            self._forget_caches(h.index)
        self.replicas = fresh
        if self.canary_cfg is not None:
            self._canary_golden = self._compute_canary_golden(
                self._engine_factory)

    def _topology_set_roles(self, role_list: List[str]) -> None:
        """Re-role the (already right-sized) fleet: roles steer
        scheduling only, so this is pure relabeling — plus the
        fleet-wide prefix store coming up if roles just turned on."""
        for h, role in zip(self.replicas, role_list):
            if role not in ReplicaRole.ALL:
                raise ValueError(f"unknown replica role {role!r}: "
                                 f"{sorted(ReplicaRole.ALL)}")
            h.role = role
        self.roles_enabled = any(r != ReplicaRole.COLOCATED
                                 for r in role_list)
        if self.roles_enabled and self.prefix_store is None:
            self.prefix_store = FleetPrefixStore(
                page_size=self._page_size)
            if isinstance(self.policy, PrefixAffinityPolicy) \
                    and getattr(self.policy, "store", None) is None:
                self.policy.store = self.prefix_store

    def _reroute_stranded(self) -> None:
        """Post-mutation failover pass: anything mirrored onto a slot
        that no longer exists, died, or changed generation re-enters
        NOW through the zero-loss fold-in — a resize is
        zero-downtime, not wait-for-the-next-tick."""
        n = len(self.replicas)
        for rec in list(self._live.values()):
            if rec.done:
                continue
            h = (self.replicas[rec.replica]
                 if rec.replica is not None and rec.replica < n
                 else None)
            if h is None or not h.alive() \
                    or rec.generation != h.generation:
                self._failover_one(rec)

    def _topology_recover(self, target: dict) -> None:
        """Rebuild this (fresh, empty) incarnation onto a
        journal-resolved topology during `recover()` — the replayed
        ``resize_intent``/``resize_commit`` records are the
        dominating intent here (`journal.replay()` precedes this on
        every path, which is how PDT009 reads it)."""
        from .submesh import TpConfig
        n_new = int(target["num_replicas"])
        roles = list(target.get("roles")
                     or [ReplicaRole.COLOCATED] * n_new)
        tp = target.get("tp")
        if tp is None:
            tp_cfg = None
        elif self._tp_cfg is not None and self._tp_cfg.tp == int(tp):
            tp_cfg = self._tp_cfg    # keep the constructor's config
        else:
            tp_cfg = TpConfig(tp=int(tp))
        tp_changed = ((None if tp_cfg is None else tp_cfg.tp)
                      != (None if self._tp_cfg is None
                          else self._tp_cfg.tp))
        self._apply_topology(n_new, roles, tp_cfg, tp_changed)

    # -- crash recovery (serving/journal.py) -----------------------------
    @classmethod
    def recover(cls, journal: RouterJournal, engine_factory,
                **router_kwargs) -> "ServingRouter":
        """Build a fresh router incarnation from a write-ahead journal
        after the previous incarnation died (SIGKILL-shaped — nothing
        of the old process survives but the journal). Every
        un-finalized journaled request rehydrates onto the fresh
        replicas with its journaled tokens FOLDED into re-prefill and
        its budget shrunk (the PR-4 failover shape, so greedy outputs
        are bit-identical to an uninterrupted fleet); already-finished
        request_ids restore WITHOUT re-execution (idempotent per
        request_id — their final streams stay redeliverable and a
        client's re-submit of the same id is a no-op); deadlines that
        expired while the router was dead finalize as honest timeouts;
        QoS lane/tenant budgets re-charge for the live work
        (`admission=` in `router_kwargs`). Replay is torn-tail
        tolerant but an unreadable journal (the `journal.replay` fault
        site) RAISES — recovery must not silently pretend the journal
        was empty. `router_kwargs` are the ordinary constructor
        arguments (replicas, policy, clocks, admission, ...); the
        journal is re-attached, so the new incarnation keeps
        journaling where the old one stopped."""
        router = cls(engine_factory, journal=journal, **router_kwargs)
        router._rehydrate()
        return router

    def _rehydrate(self):
        """Replay the attached journal into this (fresh) router — see
        `recover()`. Runs under the `journal.replay` span; counts
        recovered/deduped and the recovery-seconds histogram."""
        assert self.journal is not None, "recovery needs a journal"
        t0 = self._clock()
        with telemetry.span("journal.replay", path=self.journal.path):
            replay = self.journal.replay()
        now = self._clock()
        # journaled topology rules over the constructor's: rebuild the
        # fleet BEFORE rehydrating work so live requests land on the
        # resolved shape. An intent without its commit rolls FORWARD —
        # the closing commit is appended here, so the transaction is
        # settled for every later recovery (counted-but-survived on
        # failure: the next recovery simply rolls forward again)
        self._resize_seq = max(self._resize_seq, replay.resize_seq)
        if replay.topology is not None \
                and replay.topology != self._current_topology():
            self._topology_recover(replay.topology)
        if replay.resize_rolled_forward:
            telemetry.event("router.resize", phase="rollforward",
                            seq=replay.resize_seq,
                            num_replicas=len(self.replicas))
            try:
                self.journal.append_resize_commit(replay.resize_seq)
            except Exception as e:
                self._note_append_failure(
                    e, where="router.resize_commit")
        for st in replay.finished.values():
            if st.request_id in self.requests:
                continue
            # finished before the crash: restore the terminal record
            # (status + final stream) and NEVER re-execute — the
            # dedupe half of the idempotent-per-request_id contract
            rec = FleetRequest(st.request_id, list(st.prompt),
                               st.max_new_tokens, lane=st.lane,
                               tenant=st.tenant, priority=st.priority,
                               model=st.model, submit_time=now)
            rec.status = st.status
            rec.tokens = list(st.tokens)
            rec.durable_len = len(rec.tokens)  # it CAME from the journal
            rec.error = st.error
            self.requests[st.request_id] = rec
            # the restored terminal re-enters the per-model ledger:
            # num_terminal_by_model must reconcile EXACTLY with
            # per-model submits ACROSS incarnations (the multimodel
            # soak's check), and the old incarnation's ledger died
            # with its process
            self._count_model_terminal(rec)
        journal_mod.note_deduped(len(replay.finished))
        for st in replay.live.values():           # journal/submit order
            if st.request_id in self.requests:
                continue
            rec = FleetRequest(st.request_id, list(st.prompt),
                               st.max_new_tokens,
                               deadline_abs=st.deadline_abs,
                               max_queue_time=st.max_queue_time,
                               lane=st.lane, tenant=st.tenant,
                               priority=st.priority, model=st.model,
                               submit_time=now)
            rec.tokens = list(st.tokens)
            rec.durable_len = len(rec.tokens)  # replayed = durable
            self.requests[st.request_id] = rec
            self._live[st.request_id] = rec
            if self.admission is not None:
                # restore the tenant BUDGET charge (reservation
                # currency, same as submit-time commit) — but NOT the
                # admit ledger: the OLD incarnation already counted
                # this admission, so the cross-incarnation identity is
                # terminals == committed admits + replay-recovered
                # (docs/serving.md "Durability"). Fail OPEN like every
                # admission surface — recovery never wedges on
                # bookkeeping
                try:
                    budget = self.admission.budget_for(budget_key(
                        st.tenant if st.tenant is not None
                        else self.admission.default_tenant, st.model))
                    if budget is not None:
                        budget.charge(len(st.prompt)
                                      + st.max_new_tokens)
                except Exception as e:
                    note_failopen(e, where="router.recover")
            # a fresh trace root: the old incarnation's carrier died
            # with it, and the recovered request's re-prefill/decode
            # spans should join ONE reconstructable tree
            tracing.start_trace(st.request_id, name="router.recover",
                                request_id=st.request_id,
                                tokens_folded=len(rec.tokens),
                                budget_left=self._remaining_budget(rec))
            # the failover shape, one incarnation up: expired
            # deadlines finalize honestly, everything else re-prefills
            # with the journaled stream folded in (replica=None, so no
            # failover counters inflate)
            self._failover_one(rec)
        journal_mod.note_recovered(len(replay.live))
        journal_mod.observe_recovery_seconds(self._clock() - t0)
        telemetry.event("journal.recovered",
                        live=len(replay.live),
                        deduped=len(replay.finished),
                        corrupt_dropped=replay.corrupt_dropped,
                        records=replay.records,
                        segments=replay.segments)

    # -- drive-to-completion --------------------------------------------
    def run(self) -> Dict[str, List[int]]:
        """Step until every submitted request is terminal; returns
        {request_id: tokens}. While the WHOLE fleet is down awaiting a
        restart backoff, waits via the injectable `sleep` (pass the
        fake clock's `advance` in tests). Raises RuntimeError if work
        remains but every replica is permanently dead."""
        while True:
            pending = [r for r in self._live.values() if not r.done]
            if not pending:
                return {rid: rec.tokens
                        for rid, rec in self.requests.items()}
            if not any(h.alive() for h in self.replicas):
                now = self._clock()
                waits = [h.next_restart_time - now
                         for h in self.replicas
                         if h.next_restart_time is not None]
                if not waits:
                    raise RuntimeError(
                        f"{len(pending)} requests pending but every "
                        "replica is permanently dead (restart budget "
                        "exhausted or drained)")
                if max(0.0, min(waits)) > 0:
                    self._sleep(min(waits))
            self.step()

    # -- introspection ---------------------------------------------------
    def fleet_info(self) -> Dict[str, object]:
        """Operator snapshot: per-replica state/queue/restarts plus
        fleet counters and the prefix-cache aggregate (hits survive
        replica death — the handles fold in retired engine counters).
        With an `slo_monitor` attached, each replica row also carries
        its worst SLO state over its own traffic, and a fleet-level
        `slo` section holds every objective's verdict — render with
        `observability.render_fleet_status`."""
        pending = len(self._live)
        info = {
            "replicas": [
                {"index": h.index, "role": h.role, "state": h.state,
                 "outstanding": h.outstanding(),
                 "pending_harvest": h.pending_harvest(),
                 "consecutive_failures": h.consecutive_failures,
                 "restarts": h.restarts,
                 "migrations_in": h.migrations_in,
                 "migrations_out": h.migrations_out,
                 "death_reason": h.death_reason,
                 # operator visibility of PLACEMENT: which devices
                 # this replica's engine (every incarnation) lives on
                 "submesh": None if h.submesh is None
                 else h.submesh.describe()}
                for h in self.replicas],
            "pending": pending,
            "submitted": len(self.requests),
            "failovers": self.num_failovers,
            "restarts": self.num_restarts,
            "resizes": self.num_resizes,
            "resize_seq": self._resize_seq,
            "migrations": self.num_migrations,
            "prefix_hits": sum(h.prefix_hits() for h in self.replicas),
            "prefix_tokens_reused": sum(h.prefix_tokens_reused()
                                        for h in self.replicas),
        }
        if self.submeshes is not None:
            info["tp"] = {"tp": self.submeshes[0].tp,
                          "mode": self.submeshes[0].config.mode,
                          "submeshes": [m.describe()
                                        for m in self.submeshes]}
        if self.roles_enabled:
            # per-role aggregates: migrations count OUT of prefill and
            # INTO decode (the same transfers seen from each end)
            agg: Dict[str, dict] = {}
            for h in self.replicas:
                row = agg.setdefault(h.role, {"replicas": 0,
                                              "queue_depth": 0,
                                              "migrations": 0})
                row["replicas"] += 1
                row["queue_depth"] += h.outstanding()
                row["migrations"] += (h.migrations_out
                                      if h.role == ReplicaRole.PREFILL
                                      else h.migrations_in)
            info["roles"] = agg
        if self.prefix_store is not None:
            info["prefix_store"] = self.prefix_store.stats()
        if self.model_store is not None:
            # multi-model surface: store accounting, per-model
            # request ledgers (submits/pending/cold installs/terminal
            # by status — the exact-reconciliation set), and per-model
            # autoscaling pressure (pending work per serving replica
            # — what a per-model FleetAutoscaler votes on)
            serving = sum(1 for h in self.replicas
                          if h.state in (ReplicaState.HEALTHY,
                                         ReplicaState.DEGRADED))
            per_model: Dict[str, dict] = {}
            for mid in self.model_store.models():
                per_model[mid] = {
                    "submitted":
                        self.num_submit_attempts_by_model.get(mid, 0),
                    "pending": 0,
                    "cold_installs":
                        self.num_cold_installs_by_model.get(mid, 0),
                    "resident_replicas": sum(
                        1 for h in self.replicas
                        if self.model_store.is_resident(h.index, mid)),
                    "terminal": dict(
                        self.num_terminal_by_model.get(mid, {})),
                }
            for rec in self._live.values():
                if rec.model in per_model:
                    per_model[rec.model]["pending"] += 1
            info["model_store"] = self.model_store.stats()
            info["models"] = per_model
            info["autoscale"] = {
                "per_model": {
                    mid: {"pending": row["pending"],
                          "submitted": row["submitted"],
                          "pressure": row["pending"] / max(1, serving)}
                    for mid, row in per_model.items()}}
        # performance attribution surface (observability/profile.py):
        # the pdt_mem_bytes{pool} memory ledger over every live
        # engine + the compile-cache counters — render with
        # render_fleet_status, drill down with `paddle-tpu-obs
        # profile`
        info["perf"] = _profile.perf_section(
            (h.engine for h in self.replicas),
            prefix_store=self.prefix_store,
            model_store=self.model_store)
        if self.journal is not None:
            # durability surface: segment/byte footprint + how much
            # request state the journal is currently carrying
            info["journal"] = self.journal.stats()
        if self.canary_cfg is not None:
            # gray-failure surface: canary verdicts, quarantines, and
            # the tainted tokens that were dropped instead of served
            trips = sum(h.sentry_trips() for h in self.replicas)
            info["sentry"] = {
                "canary_runs": sum(h.canary_runs
                                   for h in self.replicas),
                "canary_failures": sum(h.canary_failures
                                       for h in self.replicas),
                "quarantines": self.num_quarantines,
                "tainted_tokens_dropped": self.num_tainted_tokens,
                "sentry_trips": trips,
                "golden_tokens": len(self._canary_golden or ()),
            }
            for row, h in zip(info["replicas"], self.replicas):
                row["canary_runs"] = h.canary_runs
                row["last_canary_pass"] = h.last_canary_pass
        # speculative decoding (engine spec_decode=): fleet-wide
        # acceptance aggregate, retired incarnations folded in by the
        # handles — the operator's one look at whether speculation is
        # actually paying (a sagging acceptance rate means the draft
        # has drifted from the traffic)
        spec_rows = [h.spec_info() for h in self.replicas]
        if any(r["rounds"] or r["degraded"] for r in spec_rows) \
                or any(h.engine is not None and h.engine.spec_enabled
                       for h in self.replicas):
            agg = {k: sum(r[k] for r in spec_rows)
                   for k in ("rounds", "proposed", "accepted",
                             "degraded")}
            agg["acceptance_rate"] = (agg["accepted"]
                                      / max(agg["proposed"], 1))
            info["speculation"] = agg
        if self.admission is not None:
            # lane admit/shed counts, tenant budget occupancy, and the
            # arbitration burn — render with render_fleet_status
            info["admission"] = self.admission.stats()
        if self.slo_monitor is not None:
            statuses = self.slo_monitor.evaluate()
            info["slo"] = {
                name: {"state": st.state, "value": st.value,
                       "burn_rate": st.burn_rate,
                       "samples": st.samples}
                for name, st in statuses.items()}
            for row in info["replicas"]:
                row["slo"] = self.slo_monitor.replica_state(
                    str(row["index"]))
        return info
