"""Replica supervision: one engine behind a health state machine.

A `ReplicaHandle` wraps one :class:`ContinuousBatchingEngine` the way a
fleet supervisor wraps a serving process: the engine object stands in
for a whole replica (its HBM-resident KV pool included), and the handle
tracks whether that replica should receive traffic at all.

Health state machine (driven by the router's injectable clock — no
wall-clock reads, so every transition is forcible in tests)::

    HEALTHY --consecutive failures >= degraded_after--> DEGRADED
    DEGRADED --one successful step--> HEALTHY
    DEGRADED --consecutive failures >= dead_after--> DEAD
    HEALTHY|DEGRADED --no step progress for wedge_timeout s
                       while work is outstanding--> DEAD   ("wedged")
    any live state --drain()--> DRAINING
    DRAINING --in-flight work reaches zero--> DEAD         ("drained")
    DEAD --router restart after exponential backoff--> HEALTHY

Gray-failure arm (ISSUE 14, docs/serving.md "Gray failures") — the
states above all describe LIVENESS; these describe CORRECTNESS, and
only exist on fleets with a canary configured (`ServingRouter(
sentry=, canary=)`)::

    HEALTHY|DEGRADED --numeric sentry trip--> SUSPECT
    SUSPECT --canary passes with a clean sentry window--> HEALTHY
    SUSPECT --canary token mismatch, or max_suspect_rounds
              dirty passes--> QUARANTINED
    QUARANTINED --backoff restart--> PROBATION
    DEAD --backoff restart (canary-gated fleets)--> PROBATION
    PROBATION --canary passes--> HEALTHY       (restart budget resets)
    PROBATION --canary token mismatch--> QUARANTINED

SUSPECT replicas keep stepping their in-flight work (the streams are
re-verified if quarantine lands) but accept nothing new, donate no
migrations, and their terminals PARK until the canary's verdict — a
tainted stream must not finalize. QUARANTINED is DEAD-shaped (the
engine is discarded: a corrupt chip's state is untrustworthy) but
distinct, so operators can tell corruption from crash; it restarts on
the SAME backoff ladder and re-enters through PROBATION, where it must
reproduce the canary's golden stream before taking real traffic — and
ONLY a passed canary (or real served work) resets the restart budget,
closing the PR-4 hole where an idle restarted replica sat HEALTHY
without ever proving it works.

Death is SIGKILL-shaped: the engine object is DISCARDED the moment the
replica dies (``self.engine = None``) — its queues, slots, and KV pages
are unrecoverable, exactly as if the serving process had been killed.
Zero-loss failover therefore lives one layer up: the router mirrors
every replica's token stream as it is produced (the tokens a real
router would have streamed to clients already) and re-prefills
survivors from that mirror (`router.py`).

Fault sites (`utils.faults`): ``router.dispatch`` fires before a
request is handed to the engine; ``router.step`` fires before a step of
a replica that has outstanding work (so `nth=`/`times=` arming can
target one replica of a fleet deterministically — idle replicas do not
consume visits); ``router.health`` fires inside every health probe.
"""
from __future__ import annotations

import random
import traceback
from typing import Callable, List, Optional

from .. import observability as telemetry
from ..distributed.launch import restart_backoff
from ..models.serving import ContinuousBatchingEngine, Request
from ..utils.faults import fault_point

__all__ = ["ReplicaHandle", "ReplicaState", "ReplicaRole",
           "ReplicaOpRefused"]


class ReplicaOpRefused(RuntimeError):
    """A manual scaling primitive (`drain`/`restore`) was refused
    because the replica's current state makes the operation ambiguous
    — e.g. restoring a replica that is still draining, or draining one
    whose canary verdict is unresolved. Typed so operators (and the
    autoscaler, which drives these primitives in a loop) can tell a
    refusal from a crash; plain repeats of an already-applied
    operation are idempotent no-ops instead (ISSUE 16)."""


class ReplicaRole:
    """Disaggregated serving roles (ISSUE 8, router.py `roles=`):
    `prefill` replicas take fresh admissions and hand finished
    prefills to the KV transfer plane, `decode` replicas receive
    migrated pages and run the decode loop, `colocated` does both (the
    PR-4 default). Roles steer SCHEDULING only — every engine keeps
    both capabilities, which is what lets failover re-prefill stranded
    work on ANY survivor, role notwithstanding."""

    PREFILL = "prefill"
    DECODE = "decode"
    COLOCATED = "colocated"
    ALL = frozenset({PREFILL, DECODE, COLOCATED})
    # fresh submits may land here; decode replicas only take migrations
    PREFILL_CAPABLE = frozenset({PREFILL, COLOCATED})


class ReplicaState:
    """Replica health states + the numeric encoding exported on the
    `pdt_router_replica_state` gauge (0-3: the liveness ladder,
    higher = less healthy; 4-6: the gray-failure arm, appended so the
    PR-4 encodings stay stable)."""

    HEALTHY = "healthy"
    DEGRADED = "degraded"
    DRAINING = "draining"
    DEAD = "dead"
    # gray-failure arm (module docstring): correctness, not liveness
    SUSPECT = "suspect"
    QUARANTINED = "quarantined"
    PROBATION = "probation"
    LIVE = frozenset({HEALTHY, DEGRADED, DRAINING, SUSPECT, PROBATION})
    # engine discarded, restart pending on the backoff ladder
    DOWN = frozenset({DEAD, QUARANTINED})
    # gauge encoding: docs/serving.md "Fleet" metric catalog
    CODE = {HEALTHY: 0, DEGRADED: 1, DRAINING: 2, DEAD: 3,
            SUSPECT: 4, QUARANTINED: 5, PROBATION: 6}


_M_STATE = telemetry.gauge(
    "pdt_router_replica_state",
    "Replica health state (0=healthy 1=degraded 2=draining 3=dead "
    "4=suspect 5=quarantined 6=probation).",
    ("replica",))
_M_QDEPTH = telemetry.gauge(
    "pdt_router_replica_queue_depth",
    "Outstanding (waiting + running) requests per replica.",
    ("replica",))
_M_RESTARTS = telemetry.counter(
    "pdt_router_replica_restarts_total",
    "Replica restarts after death, by replica.", ("replica",))


class ReplicaHandle:
    """One engine + its health state (see module docstring).

    `engine_factory(index)` builds a fresh engine — called at
    construction and again on every restart, so a restarted replica
    comes back with empty queues and a cold KV pool, like a respawned
    process. When a `submesh` is attached (TP fleets) the factory is
    called as `engine_factory(index, submesh)` instead, so every
    incarnation is built on the SAME device slice. Restart pacing reuses the elastic launcher's
    `restart_backoff` shape (exponential, jittered via the injectable
    `rng`, capped) expressed as a *next-restart deadline* on the
    injectable clock rather than a sleep — the router is step-driven.
    """

    def __init__(self, index: int,
                 engine_factory: Callable[..., ContinuousBatchingEngine],
                 *, clock: Callable[[], float],
                 degraded_after: int = 1,
                 dead_after: int = 3,
                 wedge_timeout: Optional[float] = None,
                 max_outstanding: Optional[int] = None,
                 restart_backoff_base: float = 1.0,
                 restart_backoff_max: float = 60.0,
                 max_restarts: Optional[int] = 5,
                 rng: Optional[random.Random] = None,
                 role: str = ReplicaRole.COLOCATED,
                 submesh=None,
                 sentry_config=None,
                 probation_gate: bool = False):
        if role not in ReplicaRole.ALL:
            raise ValueError(f"unknown replica role {role!r}: "
                             f"{sorted(ReplicaRole.ALL)}")
        self.role = role
        # tensor parallelism (serving/submesh.py): the replica's device
        # slice. It belongs to the SLOT, not the engine incarnation —
        # a restarted replica comes back on the SAME submesh, so
        # replica identity is (submesh, generation)
        self.submesh = submesh
        # transfer-plane traffic (survives restarts — the counters
        # describe the SLOT in the fleet, not one engine incarnation)
        self.migrations_in = 0
        self.migrations_out = 0
        self.index = int(index)
        self._factory = engine_factory
        self._clock = clock
        self.degraded_after = int(degraded_after)
        self.dead_after = int(dead_after)
        self.wedge_timeout = wedge_timeout
        self.max_outstanding = max_outstanding
        self._backoff_base = float(restart_backoff_base)
        self._backoff_cap = float(restart_backoff_max)
        self.max_restarts = max_restarts
        self._rng = rng if rng is not None else random.Random(index)
        # -- gray-failure defense (ISSUE 14, serving/sentry.py) --------
        # sentry_config builds one NumericSentry per engine INCARNATION
        # (attached in _build_engine); probation_gate=True (set by a
        # router with a canary) makes every restart land in PROBATION —
        # canary-gated readmission — instead of HEALTHY
        self.sentry_config = sentry_config
        self.probation_gate = bool(probation_gate)
        self.sentry = None
        self.sentry_seen = 0          # trips the router has acted on
        self.canary = None            # in-flight canary probe state
        self.canary_seq = 0
        self.last_canary_start: Optional[float] = clock()
        self.last_canary_pass: Optional[float] = None
        self.suspect_rounds = 0       # consecutive dirty canary passes
        self.canary_runs = 0
        self.canary_failures = 0
        # terminals harvested while SUSPECT: (FleetRequest, Request)
        # pairs the router parks until the canary's verdict
        self.parked: List[tuple] = []
        self.engine: Optional[ContinuousBatchingEngine] = \
            self._build_engine()
        # bumped on every restart: a request dispatched to generation g
        # is STRANDED once the handle runs generation g+1 — the fresh
        # engine never heard of it, however alive the replica looks
        self.generation = 0
        self.state = ReplicaState.HEALTHY
        self.consecutive_failures = 0
        self.last_error: Optional[str] = None
        self.last_traceback: Optional[str] = None
        self.death_reason: Optional[str] = None
        self.restarts = 0                  # completed restarts
        self.restart_attempt = 0           # backoff exponent (resets on
        self._stabilizing = False          # first post-restart success)
        self.next_restart_time: Optional[float] = None
        self.auto_restart = True           # False for drained replicas
        self.last_progress = clock()
        # prefix-cache + speculation counters folded in from engines
        # this handle has already discarded, so fleet aggregates
        # survive replica death
        self.retired_prefix_hits = 0
        self.retired_prefix_tokens_reused = 0
        self.retired_sentry_trips = 0
        self.retired_spec = {"rounds": 0, "proposed": 0, "accepted": 0,
                             "degraded": 0}
        _M_STATE.set(ReplicaState.CODE[self.state], replica=str(index))

    def _build_engine(self) -> ContinuousBatchingEngine:
        """Factory invocation, submesh-aware: a TP fleet's factory
        takes (index, submesh) — the router carved the slice and every
        incarnation of this replica lives on it. Every incarnation
        gets its replica index as the engine `fault_tag` (corrupt-mode
        drills pin a sick chip to one replica, utils/faults.py) and,
        on sentried fleets, a FRESH NumericSentry — a restarted
        replica's trip history must not follow it."""
        if self.submesh is not None:
            eng = self._factory(self.index, self.submesh)
        else:
            eng = self._factory(self.index)
        eng.fault_tag = str(self.index)
        self.sentry = None
        self.sentry_seen = 0
        if self.sentry_config is not None:
            from .sentry import NumericSentry
            self.sentry = NumericSentry(
                self.sentry_config,
                vocab_size=eng.model.config.vocab_size,
                replica=self.index)
            eng.attach_sentry(self.sentry)
        return eng

    # -- introspection ---------------------------------------------------
    def outstanding(self) -> int:
        """Waiting + running requests on this replica (0 when dead)."""
        if self.engine is None:
            return 0
        info = self.engine.lifecycle_info()
        return info["waiting"] + info["running"]

    def pending_harvest(self) -> int:
        """Dispatches in the engine's deferred-harvest window that no
        host state has seen yet (0 when dead or on the synchronous
        harvest_every=1 loop) — the operator-visible depth of the
        bounded-staleness window (ISSUE 18)."""
        if self.engine is None:
            return 0
        return len(getattr(self.engine, "_pending", ()))

    def real_outstanding(self) -> int:
        """`outstanding()` minus an in-flight canary probe: the
        did-work ledger (restart-budget resets, busy-step accounting)
        must not count infra probes as served traffic — a canary
        RUNNING proves nothing, only its PASS does."""
        n = self.outstanding()
        if n and self.canary is not None and self.engine is not None \
                and self.canary["generation"] == self.generation \
                and self.engine.get_request(self.canary["rid"]) \
                is not None:
            n -= 1
        return n

    def can_accept(self) -> bool:
        """Eligible for NEW dispatches: healthy/degraded with room in
        the bounded per-replica queue. Draining and dead replicas never
        accept (failover force-dispatch uses `alive()` instead)."""
        if self.state not in (ReplicaState.HEALTHY, ReplicaState.DEGRADED):
            return False
        return (self.max_outstanding is None
                or self.outstanding() < self.max_outstanding)

    def alive(self) -> bool:
        return self.state in ReplicaState.LIVE and self.engine is not None

    def prefix_hits(self) -> int:
        live = self.engine.prefix_hits if self.engine is not None else 0
        return self.retired_prefix_hits + live

    def prefix_tokens_reused(self) -> int:
        live = (self.engine.prefix_tokens_reused
                if self.engine is not None else 0)
        return self.retired_prefix_tokens_reused + live

    def sentry_trips(self) -> int:
        """Numeric-sentry trips for this replica SLOT (live sentry +
        retired incarnations) — the fleet aggregate must keep the
        evidence that explained a quarantine after the engine (and
        its sentry) were discarded by it."""
        live = self.sentry.trips if self.sentry is not None else 0
        return self.retired_sentry_trips + live

    def spec_info(self) -> dict:
        """Speculative-decoding counters for this replica SLOT (live
        engine + retired incarnations): a killed spec replica's
        acceptance history must survive into the fleet aggregate."""
        out = dict(self.retired_spec)
        if self.engine is not None:
            live = self.engine.spec_info()
            for k in out:
                out[k] += live[k]
        out["acceptance_rate"] = out["accepted"] / max(out["proposed"],
                                                       1)
        return out

    # -- traffic ---------------------------------------------------------
    def dispatch(self, prompt: List[int], max_new_tokens: int,
                 request_id: str,
                 deadline: Optional[float] = None,
                 max_queue_time: Optional[float] = None,
                 priority: int = 0,
                 adapter: Optional[str] = None) -> Request:
        """Hand one request to this replica's engine; returns the live
        engine Request so the router can mirror its token stream.
        `priority` is the QoS lane's engine queue priority (lane-aware
        ordering, models/serving.py); `adapter` is the LoRA adapter
        the request decodes under (multi-model fleets — the router
        made it resident via the model store before dispatching)."""
        fault_point("router.dispatch")
        assert self.engine is not None, f"dispatch to dead replica " \
                                        f"{self.index}"
        rid = self.engine.add_request(prompt, max_new_tokens,
                                      deadline=deadline,
                                      max_queue_time=max_queue_time,
                                      request_id=request_id,
                                      priority=priority,
                                      adapter=adapter)
        req = self.engine.get_request(rid)
        assert req is not None
        return req

    def step(self) -> List[Request]:
        """One engine step. The `router.step` fault site fires only when
        this replica has outstanding work, so chaos tests can target a
        specific busy replica with visit counting. Busy steps run under
        a `router.replica_step` span carrying the replica index, so
        every engine span inside (prefill, decode) has a replica
        ancestor — that is how the Chrome-trace exporter assigns
        pid=replica to engine-side work."""
        if not self.outstanding():
            return self.engine.step()
        fault_point("router.step")
        with telemetry.span("router.replica_step", replica=self.index,
                            generation=self.generation):
            return self.engine.step()

    # -- health state machine --------------------------------------------
    def _transition(self, state: str, reason: str):
        if state == self.state:
            return
        prev, self.state = self.state, state
        _M_STATE.set(ReplicaState.CODE[state], replica=str(self.index))
        telemetry.event("router.replica_state", replica=self.index,
                        prev=prev, state=state, reason=reason)

    def note_success(self, now: float, did_work: bool = True):
        """A step completed: progress happened, failures stop counting,
        a DEGRADED replica recovers. The restart-backoff budget resets
        only when the step served REAL work (`did_work`) — an idle tick
        after a restart proves nothing, and resetting on it would let a
        dies-under-load replica restart forever. SUSPECT and PROBATION
        never clear here: a step that merely COMPLETED is liveness
        evidence, and those states question correctness — only a
        canary verdict moves them (`note_canary_pass`)."""
        self.consecutive_failures = 0
        self.last_progress = now
        if self._stabilizing and did_work:
            self._stabilizing = False
            self.restart_attempt = 0       # backoff resets once stable
        if self.state == ReplicaState.DEGRADED:
            self._transition(ReplicaState.HEALTHY, "recovered")

    def note_failure(self, now: float, error: BaseException) -> bool:
        """A step / dispatch / health probe failed. Returns True when
        the failure killed the replica (caller must fail over)."""
        self.consecutive_failures += 1
        self.last_error = f"{type(error).__name__}: {error}"
        # the formatted text, not the exception: its frames would pin
        # the dead engine (and its page pools) past the discard
        self.last_traceback = "".join(
            traceback.format_exception(error))
        if self.state in ReplicaState.DOWN:
            return False
        if self.consecutive_failures >= self.dead_after:
            self.die("failures", now)
            return True
        if self.state == ReplicaState.HEALTHY \
                and self.consecutive_failures >= self.degraded_after:
            self._transition(ReplicaState.DEGRADED, self.last_error)
        return False

    # -- gray-failure arm (module docstring; ISSUE 14) -------------------
    def mark_suspect(self, reason: str):
        """A numeric sentry tripped on this replica's data: stop
        taking new work, keep stepping what is in flight (its stream
        is re-verified if quarantine lands), and let the router run a
        canary immediately. Only HEALTHY/DEGRADED replicas move —
        draining or down replicas are already on their way out."""
        if self.state in (ReplicaState.HEALTHY, ReplicaState.DEGRADED):
            self._transition(ReplicaState.SUSPECT, reason)

    def note_canary_pass(self, now: float):
        """A canary reproduced the golden stream with a clean sentry
        window: suspicion lifts, probation ends, and — the ISSUE-14
        restart-budget rule — a restarted replica's backoff budget
        resets HERE (proof of correct work), not on an idle tick."""
        self.last_canary_pass = now
        self.suspect_rounds = 0
        if self.state == ReplicaState.SUSPECT:
            self._transition(ReplicaState.HEALTHY, "canary_pass")
        elif self.state == ReplicaState.PROBATION:
            self._transition(ReplicaState.HEALTHY, "probation_pass")
            self._stabilizing = False
            self.restart_attempt = 0

    def check_health(self, now: float):
        """Health probe, run by the router once per step tick. Raises
        (counted as a failure by the caller) when the armed
        `router.health` fault site fires; kills the replica directly
        when it is WEDGED — outstanding work but no step progress for
        `wedge_timeout` seconds on the injectable clock."""
        if not self.alive():
            return
        fault_point("router.health")
        if self.wedge_timeout is not None and self.outstanding() > 0 \
                and now - self.last_progress > self.wedge_timeout:
            self.die("wedged", now)

    def drain(self) -> bool:
        """Stop dispatching to this replica; in-flight work completes,
        then the replica parks DEAD (reason `drained`) without
        auto-restart — `ServingRouter.restore_replica` brings it back.
        auto_restart drops immediately: a replica that dies MID-drain
        (wedge, failure storm) must stay decommissioned too, not
        restart itself back into traffic.

        Idempotence contract (ISSUE 16): draining a DRAINING replica
        is a no-op (returns False); draining a DOWN replica cancels
        any pending auto-restart — "drained" means "stay out" — and
        returns False; draining a SUSPECT/PROBATION replica raises
        :class:`ReplicaOpRefused` (the canary must rule first: a
        drain would let a possibly-tainted stream finalize as a
        normal drain-out). Returns True only when this call started
        the drain."""
        if self.state in (ReplicaState.HEALTHY, ReplicaState.DEGRADED):
            self.auto_restart = False
            self._transition(ReplicaState.DRAINING, "drain requested")
            return True
        if self.state == ReplicaState.DRAINING:
            return False                       # idempotent repeat
        if self.state in ReplicaState.DOWN:
            # decommission: a dead replica told to drain must not
            # restart itself back into traffic
            self.auto_restart = False
            self.next_restart_time = None
            return False
        raise ReplicaOpRefused(
            f"replica {self.index} is {self.state}: the canary must "
            "rule before it can drain (quarantine or restore it "
            "instead)")

    def finish_drain_if_empty(self, now: float):
        if self.state == ReplicaState.DRAINING and self.outstanding() == 0:
            self.auto_restart = False
            self.die("drained", now)

    def die(self, reason: str, now: float,
            to_state: str = ReplicaState.DEAD):
        """SIGKILL-shaped death: the engine object (queues, slots, KV
        pool) is discarded outright. The router re-routes this
        replica's in-flight requests from its own mirror.
        ``to_state=QUARANTINED`` is the gray-failure flavor — same
        discard and same backoff ladder (a corrupt chip's engine
        state is untrustworthy, exactly like a killed process's), but
        a distinct state so corruption reads differently from crash."""
        if self.state in ReplicaState.DOWN:
            return
        if self.engine is not None:        # fold counters before discard
            self.retired_prefix_hits += self.engine.prefix_hits
            self.retired_prefix_tokens_reused += \
                self.engine.prefix_tokens_reused
            live_spec = self.engine.spec_info()
            for k in self.retired_spec:
                self.retired_spec[k] += live_spec[k]
        self.engine = None
        if self.sentry is not None:
            # fold trips like the prefix/spec counters above: the
            # evidence trail that EXPLAINS a quarantine must survive
            # the engine discard it causes
            self.retired_sentry_trips += self.sentry.trips
        self.sentry = None                 # died with its incarnation
        self.canary = None
        self.suspect_rounds = 0
        self.death_reason = reason
        self._transition(to_state, reason)
        _M_QDEPTH.set(0, replica=str(self.index))
        if self.auto_restart and (self.max_restarts is None
                                  or self.restart_attempt
                                  < self.max_restarts):
            self.restart_attempt += 1
            delay = restart_backoff(self.restart_attempt,
                                    self._backoff_base,
                                    self._backoff_cap, self._rng)
            self.next_restart_time = now + delay
            telemetry.event("router.replica_death", replica=self.index,
                            reason=reason, restart_in_s=delay,
                            attempt=self.restart_attempt)
        else:
            self.next_restart_time = None  # permanently out
            telemetry.event("router.replica_death", replica=self.index,
                            reason=reason, restart_in_s=None,
                            attempt=self.restart_attempt)

    def maybe_restart(self, now: float) -> bool:
        """Restart a dead/quarantined replica once its backoff
        deadline passes: fresh engine from the factory, cold caches.
        Canary-gated fleets (`probation_gate`) land EVERY restart in
        PROBATION — no real traffic, and no restart-budget reset,
        until a canary passes (the ISSUE-14 readmission rule; without
        a canary there is nothing to gate with, so plain fleets keep
        the PR-4 HEALTHY + real-work-resets semantics). Returns True
        when a restart happened this tick."""
        if self.state not in ReplicaState.DOWN \
                or self.next_restart_time is None \
                or now < self.next_restart_time:
            return False
        self.engine = self._build_engine()
        self.generation += 1
        self.consecutive_failures = 0
        self.death_reason = None
        self.next_restart_time = None
        self.last_progress = now
        self.restarts += 1
        self._stabilizing = True
        if self.probation_gate:
            self._transition(ReplicaState.PROBATION, "restarted")
        else:
            self._transition(ReplicaState.HEALTHY, "restarted")
        _M_RESTARTS.inc(replica=str(self.index))
        telemetry.event("router.replica_restart", replica=self.index,
                        restarts=self.restarts)
        return True

    def restore(self, now: float) -> bool:
        """Manually bring back a drained (or permanently dead) replica:
        immediate fresh engine, no backoff — an operator action, not a
        crash recovery. Canary-gated fleets still route the fresh
        engine through PROBATION — operators cannot waive the proof.

        Idempotence contract (ISSUE 16): restoring a replica that is
        already live is a no-op (returns False); restoring one that is
        still DRAINING raises :class:`ReplicaOpRefused` — the two
        intents conflict, and silently un-draining would race the
        drain's completion. Wait for the drain to park it DEAD, or
        kill it, then restore. Returns True when a fresh engine came
        up."""
        if self.state == ReplicaState.DRAINING:
            raise ReplicaOpRefused(
                f"replica {self.index} is still draining: wait for "
                "the drain to finish (or kill it) before restoring")
        if self.state not in ReplicaState.DOWN:
            return False                       # already live: no-op
        self.auto_restart = True
        self.restart_attempt = 0
        self.next_restart_time = now
        self.maybe_restart(now)
        return True

    def start_in_probation(self, reason: str = "scale_up"):
        """Canary-gated fleets route a freshly ADDED replica (scale-up,
        ISSUE 16) through PROBATION exactly like a restarted one: no
        real traffic until its canary reproduces the golden stream.
        No-op on fleets without a canary (nothing to gate with)."""
        if self.probation_gate and self.state == ReplicaState.HEALTHY:
            self._stabilizing = True
            self._transition(ReplicaState.PROBATION, reason)

    def update_gauges(self):
        _M_QDEPTH.set(self.outstanding(), replica=str(self.index))
