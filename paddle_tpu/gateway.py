"""Serving gateway: the front door for a fleet of serving-engine replicas.

PRs 1-7 built everything *behind* the socket — ragged/paged/speculative
engines, AOT-warmed compile caches, telemetry, a live ops endpoint — but
``add_request`` has no deadline, no cancel, no backpressure, and nothing
routes across more than one engine.  :class:`ServingGateway` is that
missing subsystem: it fronts N engine replicas (any mix of the three engine
classes in ``paddle_tpu.serving``) and turns a fast engine into a service
that stays fast under overload, replica stalls, and rolling restarts.

Four disciplines, each host-side only (no compiled program changes):

**Admission control & load shedding.**  Requests wait in bounded
per-priority queues (priority 0 is served first).  Each priority bounds
both queue DEPTH (``max_queue_depth``) and queued TOKEN budget
(``max_queued_tokens`` — prompt + ``max_new_tokens`` per request, the
token-budget-aware limit: a queue of 8 huge prompts is as overloaded as a
queue of 800 small ones).  Past either limit ``submit()`` rejects
IMMEDIATELY with a structured :class:`Overloaded` result — the client gets
a retryable signal in O(1) instead of a admission that silently grows
everyone's tail latency.

**Deadlines & cancellation.**  ``submit(..., ttft_deadline_s=,
deadline_s=)`` bounds time-to-first-token and total latency.  The dispatch
loop expires overdue QUEUED requests before they ever touch an engine, and
cancels overdue IN-FLIGHT ones through the ``Engine.cancel(rid)``
primitive (slots / KV blocks / prefix pins / sampling rows all released;
serving.py).  Expired requests carry a structured
:class:`DeadlineExceeded`; streaming consumers get the terminal
``on_token(gid, None, True)`` end-of-stream either way.
``gateway.cancel(gid)`` is the client-initiated form of the same path.

**Replica routing.**  Default policy is least-outstanding-tokens (the
replica with the smallest Σ of prompt + remaining-budget tokens in
flight).  Replicas with a warm prefix cache get an AFFINITY override:
requests whose prompt chain-digest prefix matches cached blocks route to
that replica (deepest match wins; ties fall back to least-outstanding) —
shared system prompts keep hitting the replica that already holds their
k/v.  Health is watched per the PR 7 ``/healthz`` stall logic: a replica
whose tracer's newest event is older than ``stall_threshold_s`` while it
holds in-flight work is QUARANTINED — its completed requests are
harvested, and every other in-flight request is re-admitted elsewhere
after the documented replay signal ``on_token(gid, None, False)``
(discard the streamed prefix; the rerun re-delivers from token one).

**Graceful drain.**  ``drain(name)`` stops admission to a replica while
its in-flight requests run to completion (zero drops); optionally a
``replacement`` engine is AOT-``warmup()``-ed against a ``cache_dir``
(PR 6) while the old replica drains, and takes traffic the moment the
drain completes — the rolling-restart primitive.

**Resilience** (opt-in via ``resilience=ResiliencePolicy(...)``) — the
failure-response layer above quarantine (docs/RESILIENCE.md): per-replica
CIRCUIT BREAKERS (closed → open on consecutive dispatch failures /
stall-timeouts, half-open probe after ``breaker_open_s``, operator-visible
state), bounded RETRY of :class:`~paddle_tpu.faults
.TransientDispatchError` dispatches with exponential backoff + seeded
jitter and a per-request retry budget (exhaustion is a structured
:class:`RetriesExhausted`, never a silent drop), HEDGED dispatch for
requests whose TTFT deadline is at risk (a second attempt races on
another replica; the first token decides the winner and the loser is
``Engine.cancel``-ed — the consumer stream is single-sourced by
construction), and a BROWNOUT degradation ladder driven by
occupancy/SLO burn (``normal`` → clamp ``max_new_tokens`` →
priority-0-only admission → shed-all; every rung a structured,
observable state with dwell hysteresis, docs/RESILIENCE.md runbook).
With ``resilience=None`` (default) none of these paths run — engine
lowerings and program-cache keys are identical either way (host-side
control flow only).

**Disaggregated prefill/decode + tiered KV migration** (docs/
KV_TIERING.md) — replicas register with a ``role``: ``prefill``
replicas only run gateway-internal prompt prefills whose KV pages are
exported (``engine.export_prefix_pages``) and migrated under a
``migration_bytes_per_tick`` budget into a ``decode`` replica's
:class:`~paddle_tpu.kv_store.TieredKVStore`; the request then
dispatches there and admission restores the pages device-side.  The
prefix-affinity router reads the engines' PUBLIC tier-aware
``prefix_match`` API (a deep DRAM hit outranks a shallow HBM hit), and
``gateway.prefix_index()`` aggregates the fleet-wide index.  Every
pipeline failure — quarantine, stall, meta mismatch, lost destination —
falls back to plain recompute dispatch: slower, never wrong, zero
drops.

The gateway is COOPERATIVE and single-threaded, like the engines it
fronts: ``step()`` runs one round (health → brownout → expiry → drains →
dispatch → hedging → replica steps → harvest → in-flight deadlines), and
``run_to_completion`` drives it.  A replica whose ``step()`` RAISES
mid-tick is quarantined and its in-flight work replayed — one broken
engine never poisons the whole gateway tick.  With a ``tracer=`` it emits ``gateway`` events
(shed/expired/dispatch/reroute/quarantine/drain) through the PR 2 Tracer
— ring buffer, ``summary()``, Prometheus, and chrome exports included —
and ``ops_server.OpsServer.attach(gateway)`` serves the live
``/gateway`` view.

Typical use::

    gw = ServingGateway(tracer=Tracer())
    gw.add_replica(engine_a, "a")
    gw.add_replica(engine_b, "b")
    req = gw.submit([12, 71, 9], max_new_tokens=32, ttft_deadline_s=0.5)
    if req.status == "shed":
        ...                         # req.error is a structured Overloaded
    while gw.pending():
        gw.step()
    assert req.status == "finished" and req.tokens

No reference counterpart: the reference snapshot serves static batches
with no service layer at all (SURVEY §2.3); this is the serving-system
capstone over the beyond-reference engines.
"""

from __future__ import annotations

import collections
import itertools
import logging
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .faults import TransientDispatchError
from .utils.stats import (DEFAULT_TIME_BUCKETS, StatRegistry,
                          prometheus_text as _prometheus_text)

__all__ = ["ServingGateway", "GatewayRequest", "Replica", "Overloaded",
           "DeadlineExceeded", "ResiliencePolicy", "CircuitBreaker",
           "RetriesExhausted", "Brownout", "BROWNOUT_LEVELS", "ROLES"]

#: replica lifecycle states
ACTIVE = "active"
DRAINING = "draining"
QUARANTINED = "quarantined"
STOPPED = "stopped"

#: replica roles (disaggregated prefill/decode serving — docs/KV_TIERING.md).
#: ``unified`` replicas serve whole requests (the pre-disaggregation
#: behaviour); ``prefill`` replicas ONLY run gateway-internal prompt
#: prefills whose KV pages are then migrated out; ``decode`` replicas
#: serve requests and receive migrated pages through their kv_store.
ROLES = ("unified", "prefill", "decode")

#: gateway-request terminal states (plus the live "queued"/"dispatched")
_TERMINAL = frozenset({"finished", "shed", "expired", "cancelled",
                       "failed"})


class Overloaded:
    """Structured shed rejection: the queue the request would have joined
    was over its depth or token budget.  Returned on ``GatewayRequest
    .error`` with ``status == "shed"`` — never an exception, never a
    silent drop: the client sees exactly which limit fired and how deep
    the queue was, the retryable-backpressure contract."""

    __slots__ = ("priority", "queue_depth", "queued_tokens", "est_tokens",
                 "max_queue_depth", "max_queued_tokens")

    def __init__(self, priority, queue_depth, queued_tokens, est_tokens,
                 max_queue_depth, max_queued_tokens):
        self.priority = priority
        self.queue_depth = queue_depth
        self.queued_tokens = queued_tokens
        self.est_tokens = est_tokens
        self.max_queue_depth = max_queue_depth
        self.max_queued_tokens = max_queued_tokens

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        return (f"Overloaded(priority={self.priority}, "
                f"queue_depth={self.queue_depth}/{self.max_queue_depth}, "
                f"queued_tokens={self.queued_tokens}"
                f"{'' if self.max_queued_tokens is None else '/' + str(self.max_queued_tokens)})")


class DeadlineExceeded:
    """Structured deadline expiry: ``kind`` is ``"ttft"`` (no first token
    by ``ttft_deadline_s``) or ``"total"`` (``deadline_s`` elapsed).
    ``tokens_delivered`` counts what the consumer already streamed —
    a mid-decode total-deadline cancel keeps the partial output on
    ``GatewayRequest.tokens``."""

    __slots__ = ("kind", "deadline_s", "waited_s", "tokens_delivered")

    def __init__(self, kind, deadline_s, waited_s, tokens_delivered):
        self.kind = kind
        self.deadline_s = deadline_s
        self.waited_s = waited_s
        self.tokens_delivered = tokens_delivered

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        return (f"DeadlineExceeded(kind={self.kind!r}, "
                f"deadline_s={self.deadline_s}, "
                f"waited_s={round(self.waited_s, 4)}, "
                f"tokens_delivered={self.tokens_delivered})")


class RetriesExhausted:
    """Structured terminal failure: every retry of a transiently failing
    dispatch was spent.  ``attempts`` counts dispatch attempts made (the
    first try plus ``budget`` retries), ``last_error`` is the repr of
    the final :class:`~paddle_tpu.faults.TransientDispatchError`.  Lands
    on ``GatewayRequest.error`` with ``status == "failed"`` — bounded
    retry never becomes an unbounded silent loop."""

    __slots__ = ("attempts", "budget", "last_error")

    def __init__(self, attempts, budget, last_error):
        self.attempts = attempts
        self.budget = budget
        self.last_error = last_error

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        return (f"RetriesExhausted(attempts={self.attempts}, "
                f"budget={self.budget}, last_error={self.last_error!r})")


class Brownout:
    """Structured brownout rejection: the degradation ladder is at a
    rung that does not admit this request (``priority_only`` admits only
    priority 0; ``shed_all`` admits nothing).  Like :class:`Overloaded`
    it is a retryable-backpressure signal, but it names the LADDER state
    — the client can distinguish "queue full" from "service degraded"."""

    __slots__ = ("level", "label", "priority")

    def __init__(self, level, label, priority):
        self.level = level
        self.label = label
        self.priority = priority

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        return (f"Brownout(level={self.level}, label={self.label!r}, "
                f"priority={self.priority})")


#: brownout ladder rungs, lowest (healthy) first — the gauge encoding
BROWNOUT_LEVELS = ("normal", "clamp", "priority_only", "shed_all")


class CircuitBreaker:
    """Per-replica dispatch circuit breaker (docs/RESILIENCE.md state
    machine).  CLOSED counts consecutive failures; ``failures_to_open``
    of them OPEN the breaker — the replica leaves the routing set.
    After ``open_s`` the next routing inquiry moves it to HALF_OPEN,
    which admits exactly ONE probe dispatch: a success CLOSES the
    breaker, a failure re-OPENS it (and re-arms the window).  Pure host
    state on the gateway's injectable clock — deterministic under the
    simulation harness."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    __slots__ = ("failures_to_open", "open_s", "state",
                 "consecutive_failures", "opened_at", "_probe_inflight",
                 "probe_gid")

    def __init__(self, failures_to_open: int = 3, open_s: float = 5.0):
        if int(failures_to_open) < 1:
            raise ValueError("failures_to_open must be >= 1")
        if float(open_s) <= 0:
            raise ValueError("open_s must be > 0")
        self.failures_to_open = int(failures_to_open)
        self.open_s = float(open_s)
        self.state = self.CLOSED
        self.consecutive_failures = 0
        self.opened_at: Optional[float] = None
        self._probe_inflight = False
        #: gid of the request holding the HALF_OPEN probe claim — the
        #: probe's verdict (success/failure/release) is keyed to THIS
        #: request, so an unrelated pre-open in-flight request
        #: terminating cannot free or fail a probe it never held
        self.probe_gid: Optional[int] = None

    def allow(self, now: float) -> bool:
        """May a dispatch be routed here at ``now``?  Advances OPEN →
        HALF_OPEN once the window has elapsed; HALF_OPEN admits one
        probe at a time (``note_dispatch`` claims it)."""
        if self.state == self.CLOSED:
            return True
        if self.state == self.OPEN:
            if now - (self.opened_at or 0.0) < self.open_s:
                return False
            self.state = self.HALF_OPEN
            self._probe_inflight = False
        return not self._probe_inflight

    def note_dispatch(self, now: float, gid: Optional[int] = None):
        """A dispatch was actually sent (the HALF_OPEN probe claim)."""
        if self.state == self.HALF_OPEN:
            self._probe_inflight = True
            self.probe_gid = gid

    def effectively_open(self, now: float) -> bool:
        """OPEN *and* still inside the window at ``now`` — the
        non-mutating form of what ``allow`` would answer.  An OPEN
        breaker whose window has elapsed is one routing inquiry away
        from HALF_OPEN, so it is not missing capacity: consumers that
        never route (an idle fleet, the autoscaler's signal scan) must
        not treat it as open forever."""
        return (self.state == self.OPEN
                and now - (self.opened_at or 0.0) < self.open_s)

    def record_failure(self, now: float) -> bool:
        """One dispatch failure / stall-timeout; True when this one
        OPENED (or re-opened) the breaker."""
        self.consecutive_failures += 1
        if self.state == self.HALF_OPEN or (
                self.state == self.CLOSED
                and self.consecutive_failures >= self.failures_to_open):
            self.state = self.OPEN
            self.opened_at = now
            self._probe_inflight = False
            self.probe_gid = None
            return True
        if self.state == self.OPEN:
            self.opened_at = now          # still failing: re-arm window
        return False

    def release_probe(self):
        """The HALF_OPEN probe ended without a verdict (client cancel
        before any token): free the claim so the next dispatch can
        probe — neither a success nor a failure."""
        self._probe_inflight = False
        self.probe_gid = None

    def record_success(self) -> bool:
        """A dispatch delivered (first token or finish); True when this
        CLOSED a non-closed breaker."""
        self.consecutive_failures = 0
        self._probe_inflight = False
        self.probe_gid = None
        if self.state != self.CLOSED:
            self.state = self.CLOSED
            self.opened_at = None
            return True
        return False

    def to_dict(self) -> Dict[str, Any]:
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "opened_at": self.opened_at,
                "failures_to_open": self.failures_to_open,
                "open_s": self.open_s}

    def __repr__(self):
        return (f"CircuitBreaker({self.state}, "
                f"failures={self.consecutive_failures}/"
                f"{self.failures_to_open})")


class ResiliencePolicy:
    """Every resilience knob, explicit (docs/RESILIENCE.md semantics):

    - **retry**: ``retry_budget`` retries per request beyond the first
      attempt; backoff ``min(retry_backoff_max_s, retry_backoff_s *
      2**(attempt-1))`` scaled by a seeded jitter in ``[1 - retry_jitter,
      1 + retry_jitter]`` — the EQuARX discipline applied to retries: the
      added load is BOUNDED and documented, never an open loop.
    - **breaker**: ``breaker_failures`` consecutive failures open a
      replica's breaker for ``breaker_open_s`` (half-open probe after).
    - **hedge**: with ``hedge=True``, a dispatched request that has no
      first token by ``hedge_ttft_frac`` of its ``ttft_deadline_s`` gets
      ONE hedged attempt on another replica, bounded fleet-wide by
      ``max_hedges`` concurrent hedges (the hedge budget: worst-case
      extra work is ``max_hedges`` duplicate decodes, never 2× traffic).
    - **brownout**: occupancy ((in-flight + queued) / active slots)
      above ``brownout_high`` — or, with ``brownout_use_slo``, any
      firing SLO — climbs the ladder one rung per ``brownout_up_dwell_s``
      of sustained pressure; occupancy below ``brownout_low`` descends
      one rung per ``brownout_down_dwell_s``.  The band between the two
      thresholds holds the current rung (no flapping).  Rung 1+ clamps
      dispatched ``max_new_tokens`` to ``brownout_clamp``."""

    __slots__ = ("retry_budget", "retry_backoff_s", "retry_backoff_max_s",
                 "retry_jitter", "seed", "breaker_failures",
                 "breaker_open_s", "hedge", "hedge_ttft_frac",
                 "max_hedges", "brownout", "brownout_high", "brownout_low",
                 "brownout_up_dwell_s", "brownout_down_dwell_s",
                 "brownout_clamp", "brownout_use_slo")

    def __init__(self, *, retry_budget: int = 2,
                 retry_backoff_s: float = 0.05,
                 retry_backoff_max_s: float = 2.0,
                 retry_jitter: float = 0.5, seed: int = 0,
                 breaker_failures: int = 3, breaker_open_s: float = 5.0,
                 hedge: bool = True, hedge_ttft_frac: float = 0.5,
                 max_hedges: int = 4, brownout: bool = True,
                 brownout_high: float = 2.0, brownout_low: float = 0.75,
                 brownout_up_dwell_s: float = 0.0,
                 brownout_down_dwell_s: float = 5.0,
                 brownout_clamp: int = 16,
                 brownout_use_slo: bool = True):
        if int(retry_budget) < 0:
            raise ValueError("retry_budget must be >= 0")
        if float(retry_backoff_s) < 0 or float(retry_backoff_max_s) < 0:
            raise ValueError("backoff times must be >= 0")
        if not 0.0 <= float(retry_jitter) < 1.0:
            raise ValueError("retry_jitter must be in [0, 1)")
        if not 0.0 < float(hedge_ttft_frac) <= 1.0:
            raise ValueError("hedge_ttft_frac must be in (0, 1]")
        if int(max_hedges) < 0:
            raise ValueError("max_hedges must be >= 0")
        if float(brownout_low) >= float(brownout_high):
            raise ValueError("need brownout_low < brownout_high (the "
                             "hysteresis band)")
        if int(brownout_clamp) < 1:
            raise ValueError("brownout_clamp must be >= 1")
        self.retry_budget = int(retry_budget)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_max_s = float(retry_backoff_max_s)
        self.retry_jitter = float(retry_jitter)
        self.seed = int(seed)
        self.breaker_failures = int(breaker_failures)
        self.breaker_open_s = float(breaker_open_s)
        self.hedge = bool(hedge)
        self.hedge_ttft_frac = float(hedge_ttft_frac)
        self.max_hedges = int(max_hedges)
        self.brownout = bool(brownout)
        self.brownout_high = float(brownout_high)
        self.brownout_low = float(brownout_low)
        self.brownout_up_dwell_s = float(brownout_up_dwell_s)
        self.brownout_down_dwell_s = float(brownout_down_dwell_s)
        self.brownout_clamp = int(brownout_clamp)
        self.brownout_use_slo = bool(brownout_use_slo)

    def backoff_s(self, attempt: int, rng: random.Random) -> float:
        """Backoff before retry ``attempt`` (1-based): exponential,
        capped, jittered from the gateway's seeded RNG."""
        base = min(self.retry_backoff_max_s,
                   self.retry_backoff_s * (2.0 ** max(attempt - 1, 0)))
        if self.retry_jitter == 0.0:
            return base
        return base * (1.0 + self.retry_jitter * (2.0 * rng.random() - 1.0))

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    def __repr__(self):
        return (f"ResiliencePolicy(retries={self.retry_budget}, "
                f"breaker={self.breaker_failures}/{self.breaker_open_s}s, "
                f"hedge={self.hedge}, brownout={self.brownout})")


class _BrownoutLadder:
    """The brownout state machine: one rung at a time, dwell-gated both
    ways, with the ``[low, high]`` hysteresis band holding the current
    rung (the telemetry_slo resolve-band discipline — pressure hovering
    at a threshold cannot flap the ladder)."""

    def __init__(self, policy: ResiliencePolicy):
        self.policy = policy
        self.level = 0
        self.changed_at: Optional[float] = None
        self._above_since: Optional[float] = None
        self._below_since: Optional[float] = None

    def evaluate(self, now: float, pressure: float,
                 slo_firing: bool) -> int:
        """Advance the ladder; returns +1 / -1 on a rung change this
        round, else 0."""
        p = self.policy
        if pressure >= p.brownout_high or slo_firing:
            self._below_since = None
            if self._above_since is None:
                self._above_since = now
            if self.level < len(BROWNOUT_LEVELS) - 1 \
                    and now - self._above_since >= p.brownout_up_dwell_s:
                self.level += 1
                self.changed_at = now
                self._above_since = now      # next rung needs its own dwell
                return +1
        elif pressure <= p.brownout_low:
            self._above_since = None
            if self._below_since is None:
                self._below_since = now
            if self.level > 0 \
                    and now - self._below_since >= p.brownout_down_dwell_s:
                self.level -= 1
                self.changed_at = now
                self._below_since = now
                return -1
        else:
            # inside the hysteresis band: hold the rung, reset dwells
            self._above_since = None
            self._below_since = None
        return 0

    def to_dict(self) -> Dict[str, Any]:
        return {"level": self.level, "label": BROWNOUT_LEVELS[self.level],
                "changed_at": self.changed_at}


class GatewayRequest:
    """One gateway-tracked request (host-side handle).  ``status`` walks
    ``queued`` → ``dispatched`` → ``finished``, or terminates early as
    ``shed`` / ``expired`` / ``cancelled`` / ``failed`` with the
    structured reason on ``error``.  Timestamps are the gateway's clock
    (injectable for tests)."""

    __slots__ = ("gid", "prompt", "max_new_tokens", "priority",
                 "ttft_deadline_s", "deadline_s", "sampling", "on_token",
                 "status", "tokens", "error", "replica", "engine_rid",
                 "submitted_at", "dispatched_at", "first_token_at",
                 "finished_at", "replays", "trace", "_rerouting",
                 "_pending_expiry", "retries", "not_before", "hedged",
                 "hedge_replica", "hedge_rid", "dispatch_max_new",
                 "no_disagg")

    def __init__(self, gid, prompt, max_new_tokens, priority,
                 ttft_deadline_s, deadline_s, sampling, on_token,
                 submitted_at):
        self.gid = gid
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.priority = int(priority)
        self.ttft_deadline_s = ttft_deadline_s
        self.deadline_s = deadline_s
        self.sampling = dict(sampling)
        self.on_token = on_token
        self.status = "queued"
        self.tokens: List[int] = []
        self.error = None
        self.replica: Optional[str] = None
        self.engine_rid: Optional[int] = None
        self.submitted_at = submitted_at
        self.dispatched_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.replays = 0
        # end-to-end trace identity (telemetry.TraceContext): the ROOT
        # span, minted at submit when the gateway traces; each dispatch
        # mints a child for that engine attempt
        self.trace = None
        self._rerouting = False
        self._pending_expiry: Optional[DeadlineExceeded] = None
        # resilience bookkeeping (all inert when resilience is off):
        # dispatch retries spent, earliest next dispatch (backoff),
        # hedge-attempt identity (replica name + engine rid of the
        # SECOND in-flight attempt, None once resolved), and the
        # possibly-brownout-clamped budget the live attempt was
        # dispatched with
        self.retries = 0
        self.not_before: Optional[float] = None
        self.hedged = False
        self.hedge_replica: Optional[str] = None
        self.hedge_rid: Optional[int] = None
        self.dispatch_max_new: Optional[int] = None
        # a disaggregated-pipeline fallback sets this: the request is
        # served the normal recompute way and never re-enters the
        # pipeline (one fallback would otherwise loop forever)
        self.no_disagg = False

    @property
    def done(self) -> bool:
        return self.status in _TERMINAL

    @property
    def est_tokens(self) -> int:
        """Queue-budget estimate: prompt plus full generation budget."""
        return len(self.prompt) + self.max_new_tokens

    def remaining_tokens(self) -> int:
        """Outstanding-work estimate for routing: whatever of the
        prompt+budget has not been delivered yet."""
        return max(self.est_tokens - len(self.tokens), 0)

    def to_dict(self) -> Dict[str, Any]:
        err = self.error
        return {"gid": self.gid, "status": self.status,
                "priority": self.priority, "replica": self.replica,
                "prompt_len": len(self.prompt),
                "max_new_tokens": self.max_new_tokens,
                "tokens": len(self.tokens), "replays": self.replays,
                "retries": self.retries, "hedged": self.hedged,
                "trace_id": (None if self.trace is None
                             else self.trace.trace_id),
                "error": (err.to_dict() if hasattr(err, "to_dict")
                          else err)}

    def __repr__(self):
        return (f"GatewayRequest(gid={self.gid}, status={self.status!r}, "
                f"replica={self.replica!r}, tokens={len(self.tokens)})")


def _engine_slots(engine) -> int:
    """Slot capacity of one engine — the serving engines expose ``S``
    (max_slots); anything else counts as one slot.  Shared with the
    autoscaler's occupancy signal (one definition of "a slot")."""
    for attr in ("S", "max_slots"):
        v = getattr(engine, attr, None)
        if isinstance(v, int) and v > 0:
            return v
    return 1


class Replica:
    """One engine replica under gateway management: lifecycle state plus
    the gateway's view of its in-flight work (engine rid → request)."""

    def __init__(self, name: str, engine, role: str = "unified"):
        self.name = name
        self.engine = engine
        self.role = role
        self.state = ACTIVE
        self.inflight: Dict[int, GatewayRequest] = {}
        self.reason: Optional[str] = None          # quarantine reason
        self.replacement = None                    # (engine, name) draining
        self.warm_report = None

    def outstanding_tokens(self) -> int:
        return sum(r.remaining_tokens() for r in self.inflight.values())

    def slots_available(self) -> int:
        """Admission headroom: free engine slots not already spoken for by
        the engine's own internal queue (the gateway keeps waiting
        requests in ITS queues, where deadlines and shedding apply)."""
        eng = self.engine
        return len(eng._free_slots()) - len(eng._queue)

    def idle(self) -> bool:
        return not self.inflight and not self.engine.pending()

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "state": self.state,
                "role": self.role,
                "inflight": len(self.inflight),
                "outstanding_tokens": self.outstanding_tokens(),
                "engine": type(self.engine).__name__,
                "reason": self.reason}


class _DisaggJob:
    """One request's disaggregated prefill→decode pipeline state
    (docs/KV_TIERING.md): the prompt runs on a ``prefill``-role replica
    (``max_new_tokens=1`` — the ragged pack's admission prefill IS the
    work; the sampled token is discarded, the decode replica re-derives
    it from the migrated pages), its KV pages are exported and migrated
    under a byte budget into a decode replica's
    :class:`~paddle_tpu.kv_store.TieredKVStore`, and the request is then
    dispatched there — admission restores the pages device-side, so the
    decode replica computes only the bucket's last block.  Every failure
    along the way (quarantine, stall, meta mismatch, dry destination)
    FALLS BACK to plain recompute dispatch: slower, never wrong, zero
    drops."""

    __slots__ = ("req", "src", "prefill_rid", "phase", "phase_at",
                 "prefill_done", "prefill_failed", "migration", "dest",
                 "pages")

    def __init__(self, req: GatewayRequest, src: str, now: float):
        self.req = req
        self.src = src                     # prefill replica name
        self.prefill_rid: Optional[int] = None
        self.phase = "prefill"             # -> migrate -> handoff
        self.phase_at = now
        self.prefill_done = False
        self.prefill_failed = False
        self.migration = None              # kv_store.PageMigration
        self.dest: Optional[str] = None    # decode replica name
        self.pages = None

    def to_dict(self) -> Dict[str, Any]:
        return {"gid": self.req.gid, "phase": self.phase,
                "src": self.src, "dest": self.dest,
                "migration": (None if self.migration is None
                              else self.migration.to_dict())}


class ServingGateway:
    """Multi-replica serving front door (module docstring).

    ``max_queue_depth`` / ``max_queued_tokens``: per-priority admission
    bounds (None disables the token budget).  ``priorities``: number of
    priority classes (0 = highest, dispatched first).
    ``stall_threshold_s``: the PR 7 ``/healthz`` dial — a replica whose
    tracer shows no event for this long while holding in-flight work is
    quarantined.  ``tracer``: optional ``telemetry.Tracer`` for structured
    ``gateway`` events (None keeps every emit behind one attribute
    check).  ``clock``: monotonic-seconds callable — injectable so tests
    drive deadlines deterministically."""

    def __init__(self, replicas=None, *, max_queue_depth: int = 64,
                 max_queued_tokens: Optional[int] = None,
                 priorities: int = 2, stall_threshold_s: float = 30.0,
                 tracer=None, clock: Callable[[], float] = time.monotonic,
                 request_history: int = 4096,
                 resilience: Optional[ResiliencePolicy] = None,
                 migration_bytes_per_tick: Optional[int] = 8 << 20,
                 logger: Optional[logging.Logger] = None):
        if int(priorities) < 1:
            raise ValueError("priorities must be >= 1")
        if int(max_queue_depth) < 1:
            raise ValueError("max_queue_depth must be >= 1")
        self.max_queue_depth = int(max_queue_depth)
        self.max_queued_tokens = (None if max_queued_tokens is None
                                  else int(max_queued_tokens))
        self.priorities = int(priorities)
        self.stall_threshold_s = float(stall_threshold_s)
        self.tracer = tracer
        self._clock = clock
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        self._queues: List[collections.deque] = [
            collections.deque() for _ in range(self.priorities)]
        self._queued_tokens = [0] * self.priorities
        self._replicas: Dict[str, Replica] = {}
        # gid → handle while live, plus a BOUNDED tail of terminal
        # handles for late cancel()/request() lookups — a long-lived
        # gateway must not grow host memory per request served (the
        # caller's own handle from submit() stays valid regardless)
        self.request_history = int(request_history)
        # optional SLO monitor (telemetry_slo.SLOMonitor): gateway-level
        # TTFT samples and terminal counts forward into its windowed
        # stores behind one attribute check
        self._slo = None
        # optional engine factory (autoscaler scale-out spawns from it);
        # registered via register_replica_factory
        self._replica_factory: Optional[Callable[[], Any]] = None
        self._requests: Dict[int, GatewayRequest] = {}
        self._terminal_order: collections.deque = collections.deque()
        self._finished: Dict[int, List[int]] = {}
        self._gids = itertools.count()
        # disaggregated prefill/decode pipeline (docs/KV_TIERING.md):
        # gid -> _DisaggJob while a request's pages are being produced /
        # migrated; the byte budget paces each migration per step()
        if migration_bytes_per_tick is not None \
                and int(migration_bytes_per_tick) < 1:
            raise ValueError("migration_bytes_per_tick must be >= 1 "
                             "(or None for unbounded)")
        self.migration_bytes_per_tick = (
            None if migration_bytes_per_tick is None
            else int(migration_bytes_per_tick))
        self._disagg: Dict[int, _DisaggJob] = {}
        # _disagg is read by ops-server scrape threads (GET /kvstore /
        # /gateway) while step() inserts/pops jobs — every mutation and
        # every iteration-snapshot goes through this lock (the PR 12
        # SLOMonitor._firing discipline)
        self._disagg_lock = threading.Lock()
        # per-tick prefix-match memo (gid, replica) -> match: the
        # disagg coverage gate and _route's affinity scoring both walk
        # the chain digests for the same request in the same tick —
        # ONE walk per (request, replica) per step(), cleared each round
        self._match_memo: Dict[Tuple[int, str], Dict[str, Any]] = {}
        self._kvstats = StatRegistry()
        self._stats = StatRegistry()
        self._stats.histogram("queue_seconds", DEFAULT_TIME_BUCKETS)
        self._stats.histogram("ttft_seconds", DEFAULT_TIME_BUCKETS)
        # resilience layer (None = every resilience path is one attribute
        # check and the pre-resilience control flow byte-for-byte)
        self.resilience = resilience
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._brownout: Optional[_BrownoutLadder] = None
        self._hedges_live = 0
        self._rstats = StatRegistry()
        self._rrng = random.Random(0 if resilience is None
                                   else resilience.seed)
        if resilience is not None and resilience.brownout:
            self._brownout = _BrownoutLadder(resilience)
        for engine in (replicas or []):
            self.add_replica(engine)

    # ------------------------------------------------------------ fleet --

    def add_replica(self, engine, name: Optional[str] = None,
                    role: str = "unified") -> str:
        """Register an engine replica (any of the three serving classes —
        it only needs the shared scheduling surface: ``add_request`` /
        ``step`` / ``pop_finished`` / ``cancel`` / ``pending``).

        ``role`` (docs/KV_TIERING.md): ``"unified"`` (default) serves
        whole requests; ``"prefill"`` only runs gateway-internal prompt
        prefills whose KV pages migrate out (it is excluded from request
        routing); ``"decode"`` serves requests and receives migrated
        pages — it needs a :class:`~paddle_tpu.kv_store.TieredKVStore`
        (one is auto-attached when the engine supports
        ``attach_kv_store`` and has none).  Both disaggregated roles
        need a prefix-caching engine: pages are addressed by its chain
        digests."""
        if not hasattr(engine, "cancel"):
            raise TypeError(
                f"{type(engine).__name__} has no cancel(rid) — the gateway "
                f"needs the serving-engine cancellation primitive")
        if role not in ROLES:
            raise ValueError(f"unknown replica role {role!r}; want one of "
                             f"{ROLES}")
        if role != "unified" and not getattr(engine, "prefix_caching",
                                             False):
            raise ValueError(
                f"role {role!r} needs a prefix-caching engine "
                f"(enable_prefix_cache=True): KV pages are addressed by "
                f"prefix-cache chain digests")
        if role == "decode" and getattr(engine, "kv_store", None) is None:
            attach = getattr(engine, "attach_kv_store", None)
            if attach is None:
                raise ValueError(
                    f"role 'decode' needs an engine with a kv_store "
                    f"(TieredKVStore) to receive migrated pages; "
                    f"{type(engine).__name__} supports neither")
            from .kv_store import TieredKVStore
            attach(TieredKVStore(tracer=self.tracer))
        if name is None:
            i = len(self._replicas)
            while f"r{i}" in self._replicas:     # auto-names never collide
                i += 1
            name = f"r{i}"
        if name in self._replicas and \
                self._replicas[name].state != STOPPED:
            raise ValueError(f"replica {name!r} already registered")
        self._replicas[name] = Replica(name, engine, role=role)
        if self.resilience is not None:
            self._breakers[name] = CircuitBreaker(
                self.resilience.breaker_failures,
                self.resilience.breaker_open_s)
        self._stats.add("replicas_added")
        return name

    def remove_replica(self, name: str) -> Replica:
        """Deregister a STOPPED replica — the final step of an elastic
        scale-down (``drain`` without replacement leaves the stopped
        shell registered so ``is_drained`` stays answerable; a long-lived
        elastic fleet must not accumulate one dead entry per drain).
        Only stopped replicas may be removed: draining ones still hold
        work, and removing an active one would drop its in-flight
        bookkeeping."""
        rep = self.replica(name)
        if rep.state != STOPPED:
            raise ValueError(f"replica {name!r} is {rep.state}; only "
                             f"stopped replicas can be removed (drain it "
                             f"first)")
        del self._replicas[name]
        self._breakers.pop(name, None)
        self._stats.add("replicas_removed")
        self._emit("removed", replica=name)
        return rep

    def register_replica_factory(self, factory: Optional[Callable[[], Any]]
                                 ) -> Optional[Callable[[], Any]]:
        """Register (or with None clear) the engine factory that elastic
        scale-out spawns replicas from — a zero-arg callable returning a
        FRESH engine (any of the three serving classes).  The gateway never
        calls it itself; ``autoscaler.ElasticAutoscaler`` does, then warms
        and ``add_replica``s the result."""
        if factory is not None and not callable(factory):
            raise TypeError(f"replica factory must be callable, got "
                            f"{factory!r}")
        self._replica_factory = factory
        return factory

    @property
    def replica_factory(self) -> Optional[Callable[[], Any]]:
        return self._replica_factory

    def replica(self, name: str) -> Replica:
        rep = self._replicas.get(name)
        if rep is None:
            raise KeyError(f"unknown replica {name!r}")
        return rep

    def replicas(self) -> List[Replica]:
        """Every registered replica (all lifecycle states) — the public
        fleet enumeration the autoscaler and ops views read."""
        return list(self._replicas.values())

    def replica_tracers(self) -> List[Tuple[str, Any]]:
        """(name, tracer) for every CURRENT replica engine that has one —
        the public enumeration ``ops_server`` pulls per ``/requests`` /
        ``/request/<id>`` query, so drain-swapped replacements feed the
        trace stitcher without re-attaching anything."""
        out = []
        for name, rep in list(self._replicas.items()):
            tr = getattr(rep.engine, "tracer", None)
            if tr is not None:
                out.append((name, tr))
        return out

    def quarantine(self, name: str, reason: str = "manual"):
        """Pull a replica out of rotation: completed requests are
        harvested, every other in-flight request is cancelled on the
        replica (host-side bookkeeping — safe even when the device is
        wedged) and re-admitted at the FRONT of its priority queue after
        the documented replay signal ``on_token(gid, None, False)``."""
        rep = self.replica(name)
        if rep.state in (QUARANTINED, STOPPED):
            return rep
        was_draining = rep.state == DRAINING
        rep.state = QUARANTINED
        rep.reason = reason
        # a quarantine is the stall/timeout form of a dispatch failure:
        # the breaker opens too, so an operator reinstate() is probed
        # (half-open) instead of trusted blindly
        self._breaker_failure(name, self._clock(), reason)
        self._stats.add("quarantines")
        self._emit("quarantine", replica=name, reason=reason,
                   inflight=len(rep.inflight))
        self._log.warning("gateway: quarantined replica %s (%s), "
                          "re-admitting %d in-flight request(s)",
                          name, reason, len(rep.inflight))
        self._reroute_inflight(rep)
        if was_draining:
            # a drain interrupted by quarantine still COMPLETES: the
            # rerouted work finishes elsewhere, and the (possibly already
            # warmed) replacement must not be silently dropped —
            # is_drained() stays answerable and drains_started/_completed
            # stay symmetric
            self._complete_drain(rep)
        return rep

    def reinstate(self, name: str):
        """Return a quarantined replica to rotation (operator decision —
        the gateway never auto-reinstates a replica it benched)."""
        rep = self.replica(name)
        if rep.state == QUARANTINED:
            rep.state = ACTIVE
            rep.reason = None
        return rep

    def drain(self, name: str, replacement=None,
              cache_dir: Optional[str] = None, warm: bool = True,
              replacement_name: Optional[str] = None):
        """Gracefully drain a replica: admission stops NOW, in-flight work
        runs to completion under ``step()``, and once idle the replica is
        STOPPED.  ``replacement``: an engine to take its place — with
        ``warm=True`` it is AOT-``warmup()``-ed immediately (optionally
        against ``cache_dir``, the PR 6 persistent compile cache) so it
        joins the fleet already compiled.  Returns the warmup report (or
        None)."""
        rep = self.replica(name)
        if rep.state == STOPPED:
            return rep.warm_report
        # validate the hand-over NOW, not rounds later inside step() when
        # the drain completes (by then the replacement reference would be
        # cleared and the fleet left a replica short)
        if replacement is not None:
            if not hasattr(replacement, "cancel"):
                raise TypeError(
                    f"{type(replacement).__name__} has no cancel(rid) — "
                    f"the gateway needs the serving-engine cancellation "
                    f"primitive")
            other = self._replicas.get(replacement_name)
            if other is not None and other is not rep \
                    and other.state != STOPPED:
                raise ValueError(
                    f"replacement name {replacement_name!r} is a live "
                    f"replica")
        rep.state = DRAINING
        rep.replacement = (replacement, replacement_name)
        self._stats.add("drains_started")
        self._emit("drain_start", replica=name,
                   inflight=len(rep.inflight),
                   replacement=replacement is not None)
        if replacement is not None and warm:
            try:
                rep.warm_report = replacement.warmup(cache_dir=cache_dir)
            except NotImplementedError as e:
                # TP/mesh engines compile on first dispatch (serving.py);
                # the swap still proceeds, just unwarmed
                self._log.debug("gateway: replacement warmup skipped: %r",
                                e)
        self._advance_drains()
        return rep.warm_report

    def is_drained(self, name: str) -> bool:
        return self.replica(name).state == STOPPED

    # --------------------------------------------------------- admission --

    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None, on_token=None,
               **sampling) -> GatewayRequest:
        """Admit (or shed) one request; always returns the
        :class:`GatewayRequest` handle.  A shed request is terminal on
        return: ``status == "shed"`` with a structured
        :class:`Overloaded` on ``error`` — and a streaming consumer gets
        the terminal ``on_token(gid, None, True)`` immediately, so no
        rejection is ever silent."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 0 <= int(priority) < self.priorities:
            raise ValueError(f"priority must be in [0, {self.priorities})")
        now = self._clock()
        req = GatewayRequest(next(self._gids), prompt, max_new_tokens,
                             priority, ttft_deadline_s, deadline_s,
                             sampling, on_token, now)
        if self.tracer is not None:
            # mint the request's end-to-end trace: this root context is
            # THE trace_id every gateway event and (via per-dispatch
            # child spans) every engine-timeline event will carry
            from .telemetry import TraceContext
            req.trace = TraceContext.root()
        self._requests[req.gid] = req
        self._stats.add("submitted")
        if self._slo is not None:
            self._slo.count("submitted")
        self._emit("submit", gid=req.gid, priority=req.priority,
                   prompt_len=len(prompt),
                   max_new_tokens=req.max_new_tokens,
                   **self._trace_fields(req))
        if self._brownout is not None and self._brownout.level >= 2:
            lvl = self._brownout.level
            if lvl >= 3 or req.priority > 0:
                # the ladder's admission rungs: priority_only admits only
                # priority 0, shed_all admits nothing — structured, never
                # silent (same contract as Overloaded)
                req.error = Brownout(lvl, BROWNOUT_LEVELS[lvl],
                                     req.priority)
                self._rstats.add("brownout_sheds")
                self._finalize(req, "shed", now)
                self._emit("shed", gid=req.gid, priority=req.priority,
                           over="brownout", level=lvl,
                           **self._trace_fields(req))
                return req
        q = self._queues[req.priority]
        qtok = self._queued_tokens[req.priority]
        over_depth = len(q) >= self.max_queue_depth
        over_tokens = (self.max_queued_tokens is not None
                       and qtok + req.est_tokens > self.max_queued_tokens)
        if over_depth or over_tokens:
            req.error = Overloaded(req.priority, len(q), qtok,
                                   req.est_tokens, self.max_queue_depth,
                                   self.max_queued_tokens)
            self._finalize(req, "shed", now)
            self._emit("shed", gid=req.gid, priority=req.priority,
                       queue_depth=len(q), queued_tokens=qtok,
                       over=("depth" if over_depth else "tokens"),
                       **self._trace_fields(req))
            return req
        q.append(req)
        self._queued_tokens[req.priority] += req.est_tokens
        return req

    def set_slo(self, slo):
        """Attach (or with None detach) a ``telemetry_slo.SLOMonitor``:
        submitted/terminal counts and gateway-level TTFT samples
        (submit → first surviving token) forward into its windowed
        stores — the inputs of the shed-rate and TTFT objectives."""
        self._slo = slo
        return slo

    @staticmethod
    def _trace_fields(req: GatewayRequest, ctx=None) -> Dict[str, Any]:
        """trace_id/span_id/parent_span_id fields for a request-scoped
        gateway event: the dispatch-attempt child when ``ctx`` is given,
        else the request's root span; {} for untraced requests."""
        if ctx is not None:
            return ctx.to_dict()
        if req.trace is None:
            return {}
        return req.trace.to_dict()

    def cancel(self, gid: int) -> bool:
        """Client-initiated cancellation: a queued request is removed and
        finalized here; a dispatched one rides ``Engine.cancel`` (exact
        resource release, terminal stream signal).  False: unknown or
        already terminal."""
        req = self._requests.get(gid)
        if req is None or req.done:
            return False
        job = self._disagg.get(gid)
        if job is not None:
            # mid-pipeline (prefill/migrate/handoff): tear the job down
            # — the prefill attempt is cancelled, host-side pages are
            # dropped with the plan — and finalize here
            self._drop_job(job)
            self._finalize(req, "cancelled", self._clock())
            self._emit("cancel", gid=gid, where="migration",
                       **self._trace_fields(req))
            return True
        if req.status == "queued":
            self._unqueue(req)
            self._finalize(req, "cancelled", self._clock())
            self._emit("cancel", gid=gid, where="queued",
                       **self._trace_fields(req))
            return True
        rep = self._replicas.get(req.replica)
        if rep is None or req.engine_rid is None:
            return False
        if rep.engine.cancel(req.engine_rid):
            # the engine's terminal on_token already finalized the handle
            self._emit("cancel", gid=gid, where="inflight",
                       replica=rep.name, **self._trace_fields(req))
            return True
        return False

    # -------------------------------------------------------- scheduling --

    def step(self):
        """One gateway round: health-check replicas, advance the brownout
        ladder, expire overdue queued requests, advance drains, dispatch
        to replicas, hedge TTFT-at-risk requests, step every replica with
        work, harvest completions, enforce in-flight deadlines.  A
        replica whose ``step()`` raises is quarantined and replayed —
        the exception never escapes the gateway tick."""
        self._check_health()
        self._match_memo.clear()       # affinity walks memoized per round
        now = self._clock()
        if self._brownout is not None:
            self._evaluate_brownout(now)
        self._expire_queued(now)
        self._advance_drains()
        self._dispatch(now)
        if self.resilience is not None and self.resilience.hedge:
            self._maybe_hedge(self._clock())
        for rep in list(self._replicas.values()):
            if rep.state in (ACTIVE, DRAINING) and rep.engine.pending():
                try:
                    rep.engine.step()
                except Exception as e:  # noqa: BLE001 — isolation: one
                    # raising engine must never poison the whole tick
                    self._on_step_error(rep, e)
        self._harvest()
        if self._disagg:
            # after harvest: a prefill that completed THIS tick exports
            # and starts migrating immediately (overlap with serving)
            self._advance_disagg(self._clock())
        self._enforce_inflight_deadlines(self._clock())
        self._advance_drains()

    def _on_step_error(self, rep: Replica, exc: BaseException):
        """A replica engine raised mid-tick: surface it, open its
        breaker, quarantine it (in-flight work replays elsewhere after
        the documented replay signal) — the other replicas' work in this
        very tick proceeds untouched."""
        self._stats.add("step_errors")
        self._log.warning("gateway: replica %s step() raised: %r — "
                          "quarantining and replaying its in-flight work",
                          rep.name, exc)
        self._emit("replica_step_error", replica=rep.name,
                   error=repr(exc))
        # quarantine() records the breaker failure (the stall/timeout
        # form); no separate count here or one event would tick twice
        self.quarantine(rep.name, reason=f"step raised: {exc!r}")

    def pending(self) -> bool:
        if any(self._queues) or self._disagg:
            return True
        return any(rep.inflight or (rep.state in (ACTIVE, DRAINING)
                                    and rep.engine.pending())
                   for rep in self._replicas.values())

    def run_to_completion(self, max_ticks: Optional[int] = None
                          ) -> Dict[int, List[int]]:
        """Drive ``step()`` until nothing is queued or in flight; returns
        ``pop_finished()``."""
        ticks = 0
        while self.pending():
            self.step()
            ticks += 1
            if max_ticks is not None and ticks > max_ticks:
                raise RuntimeError(f"not done after {max_ticks} ticks")
        return self.pop_finished()

    def pop_finished(self) -> Dict[int, List[int]]:
        """Completed generations since the last pop: {gid: tokens}.  Only
        natural completions land here — shed/expired/cancelled requests
        terminate on their handle (``status`` + ``error``)."""
        out, self._finished = self._finished, {}
        return out

    def request(self, gid: int) -> GatewayRequest:
        req = self._requests.get(gid)
        if req is None:
            raise KeyError(f"unknown gateway request {gid}")
        return req

    # ----------------------------------------------------- step internals --

    def _check_health(self):
        """PR 7 ``/healthz`` stall logic applied per replica: in-flight
        work + a tracer whose newest event is older than the threshold =
        a stalled tick → quarantine.  An idle replica is never flagged
        (no work → no events is healthy), and a replica without a tracer
        is trusted (nothing to judge by)."""
        for rep in list(self._replicas.values()):
            if rep.state not in (ACTIVE, DRAINING) or not rep.inflight:
                continue
            tracer = getattr(rep.engine, "tracer", None)
            if tracer is None:
                continue
            try:
                age = tracer.last_event_age_s()
            except Exception as e:  # noqa: BLE001 — a broken tracer must
                # not take the dispatch loop down with it
                self._log.debug("gateway: health scan failed on %s: %r",
                                rep.name, e)
                continue
            if age is not None and age > self.stall_threshold_s:
                self.quarantine(rep.name,
                                reason=f"stalled tick ({age:.1f}s > "
                                       f"{self.stall_threshold_s:.1f}s)")

    def _expire_queued(self, now: float):
        for pri, q in enumerate(self._queues):
            if not q:
                continue
            keep = collections.deque()
            for req in q:
                waited = now - req.submitted_at
                kind = None
                if req.deadline_s is not None and waited > req.deadline_s:
                    kind = "total"
                elif (req.ttft_deadline_s is not None
                        and waited > req.ttft_deadline_s):
                    kind = "ttft"
                if kind is None:
                    keep.append(req)
                    continue
                self._queued_tokens[pri] -= req.est_tokens
                req.error = DeadlineExceeded(kind, req.deadline_s
                                             if kind == "total"
                                             else req.ttft_deadline_s,
                                             waited, 0)
                self._finalize(req, "expired", now)
                self._stats.add(f"expired_{kind}")
                # field name "deadline", not "kind": "kind" is the ring
                # event's reserved key (Tracer.emit's positional)
                self._emit("expired", gid=req.gid, deadline=kind,
                           waited_s=waited, where="queued",
                           **self._trace_fields(req))
            self._queues[pri] = keep

    def _enforce_inflight_deadlines(self, now: float):
        for rep in self._replicas.values():
            for rid, req in list(rep.inflight.items()):
                if req.done:
                    continue    # hedged twin already finalized this round
                if not (rep.name == req.replica
                        and rid == req.engine_rid):
                    continue    # hedge-attempt entry: enforced via its
                    #             primary (both attempts are cancelled)
                waited = now - req.submitted_at
                kind = None
                if req.deadline_s is not None and waited > req.deadline_s:
                    kind = "total"
                elif (req.first_token_at is None
                        and req.ttft_deadline_s is not None
                        and waited > req.ttft_deadline_s):
                    kind = "ttft"
                if kind is None:
                    continue
                req._pending_expiry = DeadlineExceeded(
                    kind, req.deadline_s if kind == "total"
                    else req.ttft_deadline_s, waited, len(req.tokens))
                self._stats.add(f"expired_{kind}")
                self._emit("expired", gid=req.gid, deadline=kind,
                           waited_s=waited, where="inflight",
                           replica=rep.name,
                           tokens_delivered=len(req.tokens),
                           **self._trace_fields(req))
                self._abort_hedge(req)    # no-op when not hedging
                if not rep.engine.cancel(rid):
                    # lost the race with retirement: the engine finished
                    # it this very round — harvest delivers it, the
                    # deadline miss stays recorded as an event only
                    req._pending_expiry = None

    def _advance_drains(self):
        for rep in list(self._replicas.values()):
            if rep.state == DRAINING and rep.idle():
                self._complete_drain(rep)

    def _complete_drain(self, rep: Replica):
        rep.state = STOPPED
        self._stats.add("drains_completed")
        self._emit("drain_done", replica=rep.name)
        replacement, new_name = rep.replacement or (None, None)
        rep.replacement = None
        if replacement is not None:
            name = self.add_replica(replacement, name=new_name)
            self._emit("replaced", replica=rep.name, by=name)

    def _dispatch(self, now: float):
        """Move queued requests onto replicas, highest priority first,
        FIFO within a priority, while any replica has admission headroom.
        With resilience on, requests inside their retry backoff window
        (``not_before``) are stepped over — they keep their queue
        position but never block the requests behind them."""
        if self.resilience is None:
            for pri, q in enumerate(self._queues):
                while q:
                    req = q[0]
                    prep = self._disagg_route(req, now)
                    if prep is not None \
                            and self._begin_prefill(prep, req, now):
                        q.popleft()
                        self._queued_tokens[pri] -= req.est_tokens
                        continue
                    target = self._route(req, now)
                    if target is None:
                        return          # fleet-wide: no headroom anywhere
                    q.popleft()
                    self._queued_tokens[pri] -= req.est_tokens
                    self._dispatch_to(target, req, now)
            return
        fleet_full = False
        for pri in range(self.priorities):
            if fleet_full:
                break          # _route candidacy is request-independent:
                #                no headroom for one request this tick
                #                means none for any (same early exit as
                #                the non-resilience loop)
            q = self._queues[pri]
            deferred: collections.deque = collections.deque()
            while q:
                req = q.popleft()
                if req.not_before is not None and now < req.not_before:
                    deferred.append(req)      # backing off: hold in place
                    continue
                prep = self._disagg_route(req, now)
                if prep is not None \
                        and self._begin_prefill(prep, req, now):
                    self._queued_tokens[pri] -= req.est_tokens
                    continue
                target = self._route(req, now)
                if target is None:
                    # no headroom anywhere: put everything back, done
                    deferred.append(req)
                    deferred.extend(q)
                    q.clear()
                    fleet_full = True
                    break
                self._queued_tokens[pri] -= req.est_tokens
                if self._dispatch_to(target, req, now) is not None:
                    # transient dispatch failure: the request is backing
                    # off for a retry — hold it in this queue
                    self._queued_tokens[pri] += req.est_tokens
                    deferred.append(req)
            self._queues[pri] = deferred

    def _route(self, req: GatewayRequest, now: float,
               exclude: Optional[str] = None) -> Optional[Replica]:
        """Pick the target replica: among ACTIVE non-``prefill`` replicas
        with admission headroom (and, with resilience on, a breaker that
        allows dispatch), the deepest TIER-AWARE prefix match wins: a
        deep lower-tier hit (restorable from DRAM/disk, no recompute)
        outranks a shallow HBM hit; equal total depth prefers the warmer
        (HBM-deeper) replica; ties — including the common no-match case
        — go to the least outstanding tokens.  ``exclude`` drops one
        name (the hedge path never hedges onto the primary's
        replica)."""
        cands = [rep for rep in self._replicas.values()
                 if rep.state == ACTIVE and rep.role != "prefill"
                 and rep.slots_available() > 0
                 and rep.name != exclude
                 and self._breaker_allows(rep.name, now)]
        if not cands:
            return None
        scored = []
        for i, rep in enumerate(cands):
            m = self._match_of(rep, req)
            scored.append((-m["total"], -m["hbm"],
                           rep.outstanding_tokens(), i))
        return cands[min(scored)[3]]

    def _match_of(self, rep: Replica, req: GatewayRequest
                  ) -> Dict[str, Any]:
        """Memoized tier-aware affinity read for this round (the memo
        clears at every ``step()``): the disagg coverage gate and the
        router score the SAME (request, replica) pairs back to back —
        one chain-digest walk serves both."""
        key = (req.gid, rep.name)
        m = self._match_memo.get(key)
        if m is None:
            m = self._prefix_match(rep.engine, req.prompt)
            self._match_memo[key] = m
        return m

    @staticmethod
    def _prefix_match(engine, prompt: List[int]) -> Dict[str, Any]:
        """Tier-aware affinity read through the engines' PUBLIC
        ``prefix_match`` API (serving.py contract — the router no longer
        reaches into ``engine._prefix_cache``): a pure read, no LRU
        touch, no pinning.  Engines without the API (or with a broken
        one) score zero rather than breaking routing."""
        fn = getattr(engine, "prefix_match", None)
        if fn is None:
            return {"hbm": 0, "total": 0, "tiers": []}
        try:
            return fn(prompt)
        except Exception as e:  # noqa: BLE001 — affinity is advisory;
            # a broken read must not take the dispatch loop down
            logging.getLogger(__name__).debug(
                "gateway: prefix_match failed: %r", e)
            return {"hbm": 0, "total": 0, "tiers": []}

    def _dispatch_to(self, rep: Replica, req: GatewayRequest, now: float
                     ) -> Optional[GatewayRequest]:
        """Dispatch one queued request onto ``rep``.  Returns None when
        the request left the queue (dispatched, or terminally failed);
        returns the request itself when a TRANSIENT failure put it into
        retry backoff and the caller must hold it queued."""
        queue_s = now - req.submitted_at
        # one child span per engine attempt (reroute re-dispatches mint a
        # fresh one): the engine binds its rid to this context, so the
        # attempt's whole timeline carries the shared trace_id
        ctx = req.trace.child() if req.trace is not None else None
        mnt = req.max_new_tokens
        if self._brownout is not None and self._brownout.level >= 1:
            # rung 1+ clamps the generation budget — the service sheds
            # WORK before it sheds REQUESTS
            mnt = min(mnt, self.resilience.brownout_clamp)
        try:
            rid = rep.engine.add_request(
                req.prompt, mnt,
                on_token=self._make_on_token(rep, req), trace_ctx=ctx,
                **req.sampling)
        except TransientDispatchError as e:
            return self._on_transient_dispatch_error(rep, req, now, e)
        except (ValueError, TypeError, NotImplementedError) as e:
            # a structurally unservable request (prompt over max_len,
            # sampling knobs the engine rejects): terminal "failed", the
            # loop keeps running
            req.error = repr(e)
            self._finalize(req, "failed", now)
            self._emit("failed", gid=req.gid, replica=rep.name,
                       error=repr(e), **self._trace_fields(req))
            return None
        self._breaker_note_dispatch(rep.name, now, gid=req.gid)
        req.engine_rid = rid
        req.replica = rep.name
        req.dispatched_at = now
        req.dispatch_max_new = mnt
        req.not_before = None
        req.status = "dispatched"
        rep.inflight[rid] = req
        self._stats.add("dispatched")
        self._stats.observe("queue_seconds", queue_s)
        fields = {}
        if mnt != req.max_new_tokens:
            self._rstats.add("brownout_clamped")
            fields["clamped_max_new"] = mnt
        if req.retries:
            fields["retries"] = req.retries
        self._emit("dispatch", gid=req.gid, replica=rep.name,
                   queue_s=queue_s, priority=req.priority, **fields,
                   **self._trace_fields(req, ctx))
        return None

    def _on_transient_dispatch_error(self, rep: Replica,
                                     req: GatewayRequest, now: float,
                                     exc: TransientDispatchError
                                     ) -> Optional[GatewayRequest]:
        """A retryable dispatch failure: count it on the replica's
        breaker and either schedule a backed-off retry (within the
        per-request budget) or terminate with a structured
        :class:`RetriesExhausted`.  Without a resilience policy the
        failure is terminal immediately (still structured, never
        silent)."""
        self._breaker_failure(rep.name, now, repr(exc))
        if self.resilience is None:
            req.error = repr(exc)
            self._finalize(req, "failed", now)
            self._emit("failed", gid=req.gid, replica=rep.name,
                       error=repr(exc), **self._trace_fields(req))
            return None
        if req.retries >= self.resilience.retry_budget:
            # the first attempt plus every budgeted retry failed:
            # structured terminal, never an unbounded loop
            req.error = RetriesExhausted(req.retries + 1,
                                         self.resilience.retry_budget,
                                         repr(exc))
            self._rstats.add("retries_exhausted")
            self._finalize(req, "failed", now)
            self._remit("retries_exhausted", gid=req.gid,
                        replica=rep.name, attempts=req.retries + 1,
                        error=repr(exc))
            return None
        req.retries += 1
        backoff = self.resilience.backoff_s(req.retries, self._rrng)
        req.not_before = now + backoff
        self._rstats.add("retries")
        self._remit("retry", gid=req.gid, replica=rep.name,
                    attempt=req.retries, backoff_s=round(backoff, 6),
                    error=repr(exc))
        return req

    def _make_on_token(self, rep: Replica, req: GatewayRequest):
        """The engine-facing streaming callback: forwards to the user's
        ``on_token`` under the GATEWAY id, tracks first-token/TTFT, and
        translates the engines' two sentinel signals — replay
        (``None, False``) resets the stream, terminal (``None, True``)
        resolves to expired/cancelled per what triggered the cancel.

        With hedging, a request can have TWO live engine attempts; each
        gets its own closure over the SAME handle.  Every signal is
        identity-checked against the request's current attempt fields
        ((replica, rid) pairs) — a losing/stale attempt's signals only
        clear bookkeeping, so the consumer stream is single-sourced and
        tokens are never double-delivered.  The FIRST token decides the
        hedge winner; the loser is cancelled on its engine right there."""
        def cb(_rid, tok, done):
            primary = (rep.name == req.replica
                       and _rid == req.engine_rid)
            hedge = (rep.name == req.hedge_replica
                     and _rid == req.hedge_rid)
            if req.done or not (primary or hedge):
                # terminal already, or a stale/losing attempt: nothing
                # reaches the consumer; a terminal signal just clears the
                # replica's bookkeeping entry
                if tok is None and done:
                    rep.inflight.pop(_rid, None)
                return
            if tok is None and not done:
                # engine-level preemption replay (paged pool pressure):
                # reset and forward — the rerun re-delivers from token one
                req.tokens = []
                req.first_token_at = None
                req.replays += 1
                if req.on_token is not None:
                    req.on_token(req.gid, None, False)
                return
            if tok is None and done:
                rep.inflight.pop(_rid, None)
                if req._rerouting:
                    return          # quarantine path signals separately
                now = self._clock()
                if req._pending_expiry is not None:
                    req.error = req._pending_expiry
                    req._pending_expiry = None
                    self._finalize(req, "expired", now)      # forwards the
                else:                                        # terminal sig
                    self._finalize(req, "cancelled", now)
                return
            if req.first_token_at is None:
                # TTFT is observed into the histogram at FINISH, not here:
                # a preemption/reroute would roll this attempt back, and
                # the histogram carries one sample per request — the
                # surviving attempt (the Tracer's documented semantics)
                req.first_token_at = self._clock()
                self._breaker_success(rep.name)
                if req.hedge_rid is not None:
                    # the race is decided by THIS token: promote the
                    # winner, cancel the loser
                    self._resolve_hedge(req, winner_is_hedge=hedge)
            req.tokens.append(int(tok))
            if req.on_token is not None:
                req.on_token(req.gid, int(tok), done)
        return cb

    # ----------------------------------------------------------- hedging --

    def _maybe_hedge(self, now: float):
        """Dispatch hedge attempts for TTFT-at-risk requests (module
        docstring): a dispatched request with a TTFT deadline, no first
        token, and ``hedge_ttft_frac`` of its deadline already spent gets
        ONE second attempt on a different replica — first token wins,
        loser is cancelled.  Fleet-wide concurrency is bounded by
        ``max_hedges``."""
        pol = self.resilience
        if self._hedges_live >= pol.max_hedges:
            return
        for rep in list(self._replicas.values()):
            for rid, req in list(rep.inflight.items()):
                if self._hedges_live >= pol.max_hedges:
                    return
                if (req.done or req.hedged
                        or req.ttft_deadline_s is None
                        or req.first_token_at is not None
                        or rep.name != req.replica
                        or rid != req.engine_rid):
                    continue
                waited = now - req.submitted_at
                if waited < pol.hedge_ttft_frac * req.ttft_deadline_s:
                    continue
                target = self._route(req, now, exclude=rep.name)
                if target is None:
                    continue            # nowhere to hedge right now
                self._hedge_to(target, rep, req, now, waited)

    def _hedge_to(self, target: Replica, primary: Replica,
                  req: GatewayRequest, now: float, waited: float):
        ctx = req.trace.child() if req.trace is not None else None
        try:
            rid2 = target.engine.add_request(
                req.prompt,
                req.dispatch_max_new or req.max_new_tokens,
                on_token=self._make_on_token(target, req), trace_ctx=ctx,
                **req.sampling)
        except TransientDispatchError as e:
            # a failed hedge is best-effort: count it on the target's
            # breaker, burn no retry budget — the primary attempt is
            # still running
            self._breaker_failure(target.name, now, repr(e))
            return
        except (ValueError, TypeError, NotImplementedError) as e:
            self._log.debug("gateway: hedge dispatch to %s rejected: %r",
                            target.name, e)
            return
        self._breaker_note_dispatch(target.name, now, gid=req.gid)
        req.hedged = True
        req.hedge_replica = target.name
        req.hedge_rid = rid2
        target.inflight[rid2] = req
        self._hedges_live += 1
        self._rstats.add("hedges")
        self._remit("hedge", gid=req.gid, primary=primary.name,
                    hedge=target.name, waited_s=round(waited, 6),
                    ttft_deadline_s=req.ttft_deadline_s,
                    **self._trace_fields(req, ctx))

    def _resolve_hedge(self, req: GatewayRequest, winner_is_hedge: bool):
        """First token arrived while two attempts were racing: promote
        the winning attempt into the request's primary fields and cancel
        the loser (its terminal signal is identity-swallowed — no
        double delivery, no double finalize)."""
        if winner_is_hedge:
            loser_name, loser_rid = req.replica, req.engine_rid
            req.replica, req.engine_rid = req.hedge_replica, req.hedge_rid
            self._rstats.add("hedges_won")
            what = "hedge_won"
        else:
            loser_name, loser_rid = req.hedge_replica, req.hedge_rid
            self._rstats.add("hedges_lost")
            what = "hedge_lost"
        req.hedge_replica = req.hedge_rid = None
        self._hedges_live -= 1
        self._remit(what, gid=req.gid, winner=req.replica,
                    loser=loser_name)
        self._cancel_attempt(loser_name, loser_rid)

    def _abort_hedge(self, req: GatewayRequest):
        """Tear down a still-racing hedge attempt (terminal transition,
        quarantine of its replica): cancel and clear — no winner, no
        consumer signal (no tokens were streamed while racing)."""
        if req.hedge_rid is None:
            return
        loser_name, loser_rid = req.hedge_replica, req.hedge_rid
        req.hedge_replica = req.hedge_rid = None
        self._hedges_live -= 1
        self._rstats.add("hedges_aborted")
        self._cancel_attempt(loser_name, loser_rid)

    def _cancel_attempt(self, replica_name: Optional[str],
                        rid: Optional[int]):
        rep = (None if replica_name is None
               else self._replicas.get(replica_name))
        if rep is None or rid is None:
            return
        rep.inflight.pop(rid, None)
        try:
            rep.engine.cancel(rid)
        except Exception as e:  # noqa: BLE001 — a wedged loser replica
            # must not break the winner's stream; its state is
            # best-effort host bookkeeping
            self._log.debug("gateway: losing-attempt cancel on %s "
                            "failed: %r", replica_name, e)

    def _harvest(self):
        for rep in self._replicas.values():
            self._harvest_replica(rep)

    def _harvest_replica(self, rep: Replica):
        if not hasattr(rep.engine, "pop_finished"):
            return
        try:
            finished = rep.engine.pop_finished()
        except Exception as e:  # noqa: BLE001 — harvest re-enters the
            # engine (the quarantine path re-enters the very engine whose
            # step() just raised); a broken pop_finished must not escape
            # the isolation that routed us here
            self._log.warning("gateway: pop_finished on %s raised: %r — "
                              "skipping harvest this round", rep.name, e)
            return
        for rid, tokens in finished.items():
            req = rep.inflight.pop(rid, None)
            if req is None:
                continue            # not gateway-managed (direct client)
            if req.done or not (rep.name == req.replica
                                and rid == req.engine_rid):
                continue    # stale/losing attempt retired late: the
                #             winner owns the stream and the finalize
            req.tokens = list(tokens)       # engine list is authoritative
            self._breaker_success(rep.name)
            if req.first_token_at is not None:
                ttft = req.first_token_at - req.submitted_at
                self._stats.observe("ttft_seconds", ttft)
                if self._slo is not None:
                    self._slo.observe("ttft_s", ttft)
            self._finalize(req, "finished", self._clock(), signal=False)
            self._finished[req.gid] = req.tokens

    # ------------------------------- disaggregated prefill/decode -------
    # (docs/KV_TIERING.md: prompt prefills on a `prefill` replica, the
    # resulting KV pages migrate under a byte budget into a `decode`
    # replica's TieredKVStore, then the request dispatches there and
    # admission restores the pages device-side.  Every failure falls
    # back to plain recompute dispatch — slower, never wrong.)

    def _kvemit(self, what: str, **fields):
        """A ``kvstore`` tracer event (migration/fallback transitions —
        docs/OBSERVABILITY.md table)."""
        if self.tracer is None:
            return
        self.tracer.emit("kvstore", what=what, **fields)

    def _disagg_route(self, req: GatewayRequest, now: float
                      ) -> Optional[Replica]:
        """The pipeline's admission gate: an ACTIVE ``prefill`` replica
        with headroom, for a prompt wide enough to export (>= 2 full
        blocks — the last bucket block is always recomputed, so anything
        narrower migrates nothing), with at least one page-receiving
        destination alive.  None -> the normal (recompute) path."""
        if req.no_disagg or req.gid in self._disagg:
            return None
        preps = [rep for rep in self._replicas.values()
                 if rep.state == ACTIVE and rep.role == "prefill"
                 and rep.slots_available() > 0
                 and self._breaker_allows(rep.name, now)]
        if not preps:
            return None
        cands = [rep for rep in preps
                 if self._exportable(rep.engine, req.prompt)]
        if not cands:
            return None
        if not any(rep.state == ACTIVE and rep.role != "prefill"
                   and getattr(rep.engine, "kv_store", None) is not None
                   for rep in self._replicas.values()):
            return None
        # LAST (it is the only chain-digest walk here): a routable
        # replica that ALREADY covers the prompt (full depth in any
        # tier) makes the pipeline pure overhead — the tier-aware
        # router sends the request straight to the warm replica, and
        # _route's scoring walk right after is the one that actually
        # uses the warmth; re-prefilling and re-migrating resident
        # pages would only burn budget and a prefill turn
        for rep in self._replicas.values():
            if rep.state != ACTIVE or rep.role == "prefill":
                continue
            bs = getattr(rep.engine, "bs", None)
            if isinstance(bs, int) and bs >= 1:
                m = self._match_of(rep, req)
                if (m["total"] + 1) * bs >= len(req.prompt):
                    return None
        return min(cands, key=lambda rep: rep.outstanding_tokens())

    @staticmethod
    def _exportable(engine, prompt: List[int]) -> bool:
        """Cheap width gate: the prompt spans >= 2 of the engine's KV
        blocks, so at least one full block sits below the
        always-recomputed last one.  Engines without a block size
        (contiguous) never qualify."""
        bs = getattr(engine, "bs", None)
        if not isinstance(bs, int) or bs < 1:
            return False
        return len(prompt) >= 2 * bs

    def _begin_prefill(self, prep: Replica, req: GatewayRequest,
                       now: float) -> bool:
        """Dispatch the gateway-internal prefill attempt (max_new 1 —
        the admission prefill IS the work; the sampled token is
        discarded, the decode replica re-derives it from the migrated
        pages, so the consumer stream is single-sourced).  False on any
        dispatch failure — the caller serves the request normally."""
        job = _DisaggJob(req, prep.name, now)

        def cb(_rid, tok, done, _job=job):
            # gateway-internal consumer: only terminal transitions
            # matter; a preemption replay signal (None, False) just
            # means the prefill reruns
            if tok is None and done:
                _job.prefill_failed = True         # cancelled under us
            elif done:
                _job.prefill_done = True

        ctx = req.trace.child() if req.trace is not None else None
        try:
            rid = prep.engine.add_request(req.prompt, 1, on_token=cb,
                                          trace_ctx=ctx, **req.sampling)
        except Exception as e:  # noqa: BLE001 — ANY prefill admission
            # failure (transient or structural) degrades to the normal
            # recompute path; the request is never lost to the pipeline
            self._log.debug("gateway: disagg prefill dispatch on %s "
                            "rejected (%r) — recompute path",
                            prep.name, e)
            return False
        self._breaker_note_dispatch(prep.name, now, gid=req.gid)
        job.prefill_rid = rid
        req.status = "dispatched"        # in the pipeline, not a queue
        with self._disagg_lock:
            self._disagg[req.gid] = job
        self._kvstats.add("prefill_dispatches")
        self._kvemit("prefill_start", gid=req.gid, replica=prep.name,
                     prompt_len=len(req.prompt),
                     **self._trace_fields(req, ctx))
        return True

    def _drop_job(self, job: _DisaggJob):
        """Remove the job and cancel its prefill attempt if still live
        (best-effort — a wedged prefill replica's host state must not
        block the fallback)."""
        with self._disagg_lock:
            self._disagg.pop(job.req.gid, None)
        if job.prefill_rid is not None and not job.prefill_done:
            src = self._replicas.get(job.src)
            if src is not None:
                try:
                    src.engine.cancel(job.prefill_rid)
                except Exception as e:  # noqa: BLE001 — best-effort
                    self._log.debug("gateway: disagg prefill cancel on "
                                    "%s failed: %r", job.src, e)
        # the internal prefill attempt never reaches _finalize/_harvest,
        # so a HALF_OPEN probe it claimed must be released HERE or the
        # prefill replica stays probe-locked (and pipeline-excluded)
        # forever; completion resolves it via _breaker_success instead
        cb = self._breaker(job.src)
        if cb is not None and cb.state == CircuitBreaker.HALF_OPEN \
                and cb.probe_gid == job.req.gid:
            cb.release_probe()

    def _disagg_fallback(self, job: _DisaggJob, reason: str):
        """Degrade to plain recompute: the request rejoins the FRONT of
        its priority queue (it has waited longest) flagged
        ``no_disagg``, and the normal router serves it — slower, never
        wrong, zero drops."""
        req = job.req
        self._drop_job(job)
        self._kvstats.add("migration_fallbacks")
        self._kvemit("fallback", gid=req.gid, reason=reason,
                     phase=job.phase, **self._trace_fields(req))
        self._log.debug("gateway: disagg pipeline for %d fell back (%s, "
                        "phase %s)", req.gid, reason, job.phase)
        req.no_disagg = True
        req.status = "queued"
        self._queues[req.priority].appendleft(req)
        self._queued_tokens[req.priority] += req.est_tokens

    def _pick_dest(self, job: _DisaggJob, now: float) -> bool:
        """Choose the page-receiving destination: ACTIVE non-prefill
        replicas with a kv_store whose page meta matches the exported
        pages; ``decode`` role preferred over ``unified``, least
        outstanding tokens within a role.  False when none qualifies."""
        meta = job.pages[0].meta if job.pages else None
        best = None
        for rep in self._replicas.values():
            if rep.state != ACTIVE or rep.role == "prefill":
                continue
            if getattr(rep.engine, "kv_store", None) is None:
                continue
            if meta is not None:
                try:
                    emeta = rep.engine.kv_page_meta()
                except Exception as e:  # noqa: BLE001 — an engine that
                    # cannot state its page meta cannot receive pages
                    self._log.debug("gateway: kv_page_meta on %s failed: "
                                    "%r", rep.name, e)
                    continue
                from .kv_store import _freeze_meta
                if _freeze_meta(emeta) != meta:
                    continue
            key = (rep.role != "decode", rep.outstanding_tokens())
            if best is None or key < best[0]:
                best = (key, rep)
        if best is None:
            return False
        job.dest = best[1].name
        return True

    def _advance_disagg(self, now: float):
        """One tick of every disaggregated pipeline: deadlines/timeouts,
        prefill completion -> page export -> budgeted migration chunks ->
        handoff dispatch.  Runs after harvest so a prefill that finished
        THIS tick exports immediately."""
        with self._disagg_lock:
            jobs = list(self._disagg.items())
        for gid, job in jobs:
            req = job.req
            if req.done:                 # cancelled/finalized elsewhere
                self._drop_job(job)
                continue
            waited = now - req.submitted_at
            kind = None
            if req.deadline_s is not None and waited > req.deadline_s:
                kind = "total"
            elif (req.ttft_deadline_s is not None
                    and waited > req.ttft_deadline_s):
                kind = "ttft"
            if kind is not None:
                self._drop_job(job)
                req.error = DeadlineExceeded(
                    kind, req.deadline_s if kind == "total"
                    else req.ttft_deadline_s, waited, 0)
                self._stats.add(f"expired_{kind}")
                self._emit("expired", gid=gid, deadline=kind,
                           waited_s=waited, where="migration",
                           **self._trace_fields(req))
                self._finalize(req, "expired", now)
                continue
            if now - job.phase_at > self.stall_threshold_s:
                self._disagg_fallback(job, f"{job.phase} timed out")
                continue
            if job.phase == "prefill":
                src = self._replicas.get(job.src)
                if src is None or src.state not in (ACTIVE, DRAINING) \
                        or job.prefill_failed:
                    self._disagg_fallback(job, "prefill replica lost")
                    continue
                if not job.prefill_done:
                    continue
                # a delivered prefill is a delivered dispatch: resolve
                # the breaker (closing a HALF_OPEN probe this attempt
                # claimed — harvest never sees the internal rid)
                self._breaker_success(job.src)
                try:
                    pages = src.engine.export_prefix_pages(req.prompt)
                except Exception as e:  # noqa: BLE001 — export is
                    # best-effort: recompute is always available
                    self._log.debug("gateway: page export on %s failed: "
                                    "%r", job.src, e)
                    pages = []
                if not pages:
                    self._disagg_fallback(job, "no exportable pages")
                    continue
                from .kv_store import PageMigration
                job.pages = pages
                job.migration = PageMigration(
                    pages, self.migration_bytes_per_tick)
                if not self._pick_dest(job, now):
                    self._disagg_fallback(
                        job, "no page-receiving decode replica")
                    continue
                job.phase = "migrate"
                job.phase_at = now
                self._kvstats.add("migrations_started")
                self._kvemit("migrate_start", gid=gid, src=job.src,
                             dest=job.dest, pages=len(pages),
                             bytes=job.migration.total_bytes,
                             **self._trace_fields(req))
                # fall through: the first chunk moves this very tick
            if job.phase == "migrate":
                dest = self._replicas.get(job.dest)
                if dest is None or dest.state != ACTIVE \
                        or getattr(dest.engine, "kv_store", None) is None:
                    # destination lost mid-transfer: RESUME into another
                    # one (pages live host-side in the plan), or degrade
                    old = job.dest
                    if not self._pick_dest(job, now):
                        self._disagg_fallback(job, "destination lost")
                        continue
                    job.migration.restart()
                    self._kvemit("migrate_resume", gid=gid,
                                 from_dest=old, dest=job.dest,
                                 **self._trace_fields(req))
                    dest = self._replicas[job.dest]
                moved0 = job.migration.transferred_bytes
                delivered = job.migration.advance()
                if job.migration.transferred_bytes > moved0:
                    # BYTE progress is liveness (a page wider than the
                    # budget spans many ticks with nothing delivered):
                    # the stall timeout bounds no-progress time, never
                    # total transfer time
                    job.phase_at = now
                ok = True
                for page in delivered:
                    try:
                        dest.engine.kv_store.put(page)
                    except Exception as e:  # noqa: BLE001 — a broken
                        # store degrades to recompute, never corrupts
                        self._log.debug("gateway: page delivery to %s "
                                        "failed: %r", job.dest, e)
                        ok = False
                        break
                if not ok:
                    self._disagg_fallback(job, "page delivery failed")
                    continue
                if delivered:
                    self._kvstats.add("migrated_pages", len(delivered))
                    self._kvstats.add("migrated_bytes",
                                      sum(p.nbytes for p in delivered))
                if not job.migration.done:
                    continue
                job.phase = "handoff"
                job.phase_at = now
                self._kvstats.add("migrations_completed")
                self._kvemit("migrate_done", gid=gid, dest=job.dest,
                             bytes=job.migration.total_bytes,
                             ticks=job.migration.ticks,
                             **self._trace_fields(req))
            if job.phase == "handoff":
                dest = self._replicas.get(job.dest)
                if dest is None or dest.state != ACTIVE:
                    self._disagg_fallback(job,
                                          "destination lost at handoff")
                    continue
                if req.not_before is not None and now < req.not_before:
                    continue             # retry backoff (resilience)
                if dest.slots_available() <= 0 \
                        or not self._breaker_allows(dest.name, now):
                    continue             # wait for headroom
                held = self._dispatch_to(dest, req, now)
                if held is None:
                    # dispatched (admission will restore the migrated
                    # pages), or terminally failed inside _dispatch_to —
                    # either way the pipeline is done with it
                    with self._disagg_lock:
                        self._disagg.pop(gid, None)

    def decode_pool_pressure(self) -> float:
        """Occupancy of the DECODE pool: (in-flight + queued + migrating)
        over ACTIVE non-prefill slots — the autoscaler's
        disaggregation-aware scale-up signal (prefill replicas can sit
        idle while the decode pool drowns; fleet-wide occupancy would
        average that away)."""
        reps = [r for r in self._replicas.values()
                if r.state == ACTIVE and r.role != "prefill"]
        slots = sum(_engine_slots(r.engine) for r in reps)
        busy = sum(len(r.inflight) for r in reps)
        with self._disagg_lock:
            migrating = len(self._disagg)
        queued = sum(len(q) for q in self._queues) + migrating
        return (busy + queued) / max(slots, 1)

    def prefix_index(self, prompt=None) -> Dict[str, Dict[str, Any]]:
        """The FLEET-WIDE prefix index (ROADMAP item 1): per-replica
        tier-aware views through the engines' PUBLIC prefix API.
        Without a prompt: each live replica's resident-page census
        (``{"pages": {tier: count}}``).  With one: each replica's
        tier-aware depth map for THAT prompt — exactly what the router
        scores, exposed for operators, the ops ``/kvstore`` view and
        tests."""
        out: Dict[str, Dict[str, Any]] = {}
        for name, rep in self._replicas.items():
            if rep.state not in (ACTIVE, DRAINING):
                continue
            entry: Dict[str, Any] = {"role": rep.role,
                                     "state": rep.state}
            if prompt is not None:
                entry.update(self._prefix_match(rep.engine,
                                                [int(t) for t in prompt]))
            else:
                tiers: Dict[str, int] = {}
                idx_fn = getattr(rep.engine, "prefix_index", None)
                if idx_fn is not None:
                    try:
                        for tier in idx_fn().values():
                            tiers[tier] = tiers.get(tier, 0) + 1
                    except Exception as e:  # noqa: BLE001 — census is
                        # advisory, a broken engine view reads as empty
                        self._log.debug("gateway: prefix_index on %s "
                                        "failed: %r", name, e)
                entry["pages"] = tiers
            out[name] = entry
        return out

    def _kv_stores(self):
        """Distinct attached stores (decode replicas may share one)."""
        stores, seen = [], set()
        for rep in self._replicas.values():
            st = getattr(rep.engine, "kv_store", None)
            if st is not None and id(st) not in seen:
                seen.add(id(st))
                stores.append(st)
        return stores

    def has_kv_surface(self) -> bool:
        with self._disagg_lock:
            migrating = bool(self._disagg)
        return (migrating or bool(self._kvstats.snapshot())
                or bool(self._kv_stores())
                or any(rep.role != "unified"
                       for rep in self._replicas.values()))

    def kvstore_snapshot(self) -> Dict[str, Any]:
        """JSON-able live KV-tiering view — what ``GET /kvstore``
        serves: migration counters + in-flight pipelines, per-replica
        role/store state, the fleet prefix index."""
        replicas = {}
        for name, rep in self._replicas.items():
            store = getattr(rep.engine, "kv_store", None)
            replicas[name] = {
                "role": rep.role, "state": rep.state,
                "store": None if store is None else store.snapshot()}
        with self._disagg_lock:
            jobs = list(self._disagg.values())
        return {
            "migration_bytes_per_tick": self.migration_bytes_per_tick,
            "migrations_inflight": [job.to_dict() for job in jobs],
            "counters": dict(self._kvstats.snapshot()),
            "decode_pool_pressure": round(self.decode_pool_pressure(), 4),
            "replicas": replicas,
            "prefix_index": self.prefix_index(),
        }

    def _reroute_inflight(self, rep: Replica):
        """Quarantine re-admission: completed work is harvested (never
        replayed), everything else is cancelled on the replica and
        re-queued at the FRONT of its priority queue, oldest first, after
        the documented replay signal."""
        self._harvest_replica(rep)
        moved = sorted(rep.inflight.items(),
                       key=lambda kv: kv[1].submitted_at, reverse=True)
        for rid, req in moved:
            if req.done:
                rep.inflight.pop(rid, None)
                continue
            if self._drop_hedge_twin(rep, rid, req):
                continue        # the other racing attempt carries on
            req._rerouting = True
            try:
                rep.engine.cancel(rid)
            except Exception as e:  # noqa: BLE001 — a wedged replica's
                # host state is best-effort; the request reroutes anyway
                self._log.debug("gateway: cancel on quarantined %s "
                                "failed: %r", rep.name, e)
            finally:
                req._rerouting = False
            rep.inflight.pop(rid, None)
            req.engine_rid = None
            req.replica = None
            req.tokens = []
            req.first_token_at = None
            req.replays += 1
            req.status = "queued"
            if req.on_token is not None:
                try:
                    req.on_token(req.gid, None, False)     # replay signal
                except Exception:  # noqa: BLE001 — a raising consumer must
                    # not strand the replica's remaining in-flight requests
                    self._log.exception(
                        "gateway on_token replay signal failed for %d",
                        req.gid)
            self._queues[req.priority].appendleft(req)
            self._queued_tokens[req.priority] += req.est_tokens
            self._stats.add("rerouted")
            self._emit("reroute", gid=req.gid, from_replica=rep.name,
                       **self._trace_fields(req))

    def _drop_hedge_twin(self, rep: Replica, rid: int,
                         req: GatewayRequest) -> bool:
        """Quarantine hit ONE attempt of a still-racing hedged request:
        drop just that attempt and let the twin on the healthy replica
        carry the request — no re-queue, no replay signal (no tokens
        were streamed while racing).  False when the request is not a
        racing hedge on this replica (the normal reroute applies)."""
        if req.hedge_rid is None:
            return False
        if rep.name == req.replica and rid == req.engine_rid:
            # the primary died: promote the hedge attempt
            req.replica, req.engine_rid = req.hedge_replica, req.hedge_rid
        elif not (rep.name == req.hedge_replica
                  and rid == req.hedge_rid):
            return False
        req.hedge_replica = req.hedge_rid = None
        self._hedges_live -= 1
        self._rstats.add("hedges_aborted")
        req._rerouting = True
        try:
            rep.engine.cancel(rid)
        except Exception as e:  # noqa: BLE001 — the quarantined host
            # state is best-effort; the surviving attempt carries on
            self._log.debug("gateway: hedge-twin cancel on %s failed: %r",
                            rep.name, e)
        finally:
            req._rerouting = False
        rep.inflight.pop(rid, None)
        self._remit("hedge_twin_dropped", gid=req.gid,
                    quarantined=rep.name, survivor=req.replica)
        return True

    def _unqueue(self, req: GatewayRequest):
        q = self._queues[req.priority]
        try:
            q.remove(req)
        except ValueError:
            return
        self._queued_tokens[req.priority] -= req.est_tokens

    def _finalize(self, req: GatewayRequest, status: str, now: float,
                  signal: bool = True):
        """Terminal transition.  ``signal=True`` delivers the clean
        end-of-stream ``on_token(gid, None, True)`` to the consumer —
        every early termination (shed/expired/cancelled/failed) signals;
        natural completion does not (the engine already delivered the
        last token with ``done=True``)."""
        self._abort_hedge(req)      # a racing twin never outlives its
        req.status = status         # request (no-op when not hedging)
        if status != "finished" and req.first_token_at is None \
                and req.replica is not None:
            # the attempt ended without ever delivering: a HALF_OPEN
            # probe must not stay claimed forever (the replica would be
            # silently lost from routing).  Keyed to the probe REQUEST's
            # identity — an unrelated pre-open in-flight request
            # terminating token-less must neither free nor fail a probe
            # it never held.  A deadline expiry IS the probe's verdict
            # (the replica failed to deliver in time); a client cancel
            # is nobody's fault — just free the claim.
            cb = self._breaker(req.replica)
            if cb is not None and cb.state == CircuitBreaker.HALF_OPEN \
                    and cb.probe_gid == req.gid:
                if status == "expired":
                    self._breaker_failure(req.replica, now,
                                          "half-open probe expired")
                else:
                    cb.release_probe()
        req.finished_at = now
        self._stats.add(status)
        if self._slo is not None:
            self._slo.count(status)
        if status == "finished":
            # the trace's explicit terminal marker (shed/expired/cancel/
            # failed already emit their own) — the stitched root span
            # ends here
            self._emit("finish", gid=req.gid, tokens=len(req.tokens),
                       replica=req.replica, replays=req.replays,
                       **self._trace_fields(req))
        self._terminal_order.append(req.gid)
        while len(self._terminal_order) > self.request_history:
            old = self._terminal_order.popleft()
            stale = self._requests.get(old)
            if stale is not None and stale.done:
                del self._requests[old]
        if signal and req.on_token is not None:
            try:
                req.on_token(req.gid, None, True)
            except Exception:  # noqa: BLE001 — consumer bugs must not
                # break the dispatch loop
                self._log.exception(
                    "gateway on_token terminal signal failed for %d",
                    req.gid)

    def _emit(self, what: str, **fields):
        if self.tracer is None:
            return
        self.tracer.emit("gateway", what=what, **fields)

    def _remit(self, what: str, **fields):
        """A ``resilience`` tracer event (breaker/retry/hedge/brownout
        transitions — docs/OBSERVABILITY.md table)."""
        if self.tracer is None:
            return
        self.tracer.emit("resilience", what=what, **fields)

    # -------------------------------------------------- circuit breakers --

    def _breaker(self, name: str) -> Optional[CircuitBreaker]:
        return self._breakers.get(name) if self._breakers else None

    def _breaker_allows(self, name: str, now: float) -> bool:
        cb = self._breaker(name)
        if cb is None:
            return True
        prev = cb.state
        ok = cb.allow(now)
        if prev == CircuitBreaker.OPEN and cb.state == CircuitBreaker.HALF_OPEN:
            self._rstats.add("breaker_probes")
            self._remit("breaker_half_open", replica=name)
        return ok

    def _breaker_note_dispatch(self, name: str, now: float,
                               gid: Optional[int] = None):
        cb = self._breaker(name)
        if cb is not None:
            cb.note_dispatch(now, gid=gid)

    def _breaker_failure(self, name: str, now: float, reason: str):
        cb = self._breaker(name)
        if cb is None:
            return
        if cb.record_failure(now):
            self._rstats.add("breaker_opens")
            self._remit("breaker_open", replica=name, reason=reason,
                        consecutive_failures=cb.consecutive_failures)
            self._log.warning("gateway: circuit breaker OPEN on %s (%s)",
                              name, reason)

    def _breaker_success(self, name: str):
        cb = self._breaker(name)
        if cb is None:
            return
        if cb.record_success():
            self._rstats.add("breaker_closes")
            self._remit("breaker_close", replica=name)

    def breakers_open(self) -> List[str]:
        """Names of ACTIVE replicas whose circuit breaker is OPEN and
        still inside its window right now — the autoscaler consumes this
        as a scale-up signal alongside firing SLOs (a broken replica is
        missing capacity even before the SLO math notices).  An OPEN
        breaker past its window is one routing inquiry from HALF_OPEN,
        so it stops counting — with no traffic, nothing ever routes, and
        a stale signal would otherwise pin an idle fleet at max size
        forever.  Only ACTIVE replicas count: a
        quarantined/stopped replica's breaker can never half-open (the
        routing probe is the only OPEN→HALF_OPEN path), and its missing
        capacity is already the quarantine-reap/min-bound machinery's
        problem — counting it here would turn one quarantine into a
        PERMANENT scale-up signal.  Empty without a resilience policy."""
        now = self._clock()
        return sorted(
            name for name, cb in self._breakers.items()
            if cb.effectively_open(now)
            and (rep := self._replicas.get(name)) is not None
            and rep.state == ACTIVE)

    # ----------------------------------------------------------- brownout --

    def _occupancy(self) -> float:
        """Fleet pressure: (in-flight + queued) requests over total
        ACTIVE engine slots — the same occupancy the autoscaler's
        scale-down signal reads."""
        return self._occupancy_terms()["value"]

    def _occupancy_terms(self) -> Dict[str, Any]:
        """Occupancy with its raw terms (busy/slots/queued) — the
        ``occupancy`` block of ``gateway_snapshot()``."""
        active = [rep for rep in self._replicas.values()
                  if rep.state == ACTIVE]
        slots = sum(_engine_slots(rep.engine) for rep in active)
        busy = sum(len(rep.inflight) for rep in active)
        queued = sum(len(q) for q in self._queues)
        return {"value": round((busy + queued) / max(slots, 1), 4),
                "busy_slots": busy, "total_slots": slots,
                "queued": queued}

    def _evaluate_brownout(self, now: float):
        pressure = self._occupancy()
        slo_firing = False
        if self.resilience.brownout_use_slo and self._slo is not None:
            try:
                slo_firing = any(
                    state == "firing"
                    for state in self._slo.alert_states().values())
            except Exception as e:  # noqa: BLE001 — a broken monitor
                # must not stall the admission plane
                self._log.debug("gateway: slo poll failed: %r", e)
        delta = self._brownout.evaluate(now, pressure, slo_firing)
        if delta == 0:
            return
        lvl = self._brownout.level
        self._rstats.add("brownout_ups" if delta > 0 else "brownout_downs")
        self._remit("brownout_up" if delta > 0 else "brownout_down",
                    level=lvl, label=BROWNOUT_LEVELS[lvl],
                    pressure=round(pressure, 4), slo_firing=slo_firing)
        self._log.warning("gateway: brownout %s to level %d (%s), "
                          "pressure=%.2f", "UP" if delta > 0 else "down",
                          lvl, BROWNOUT_LEVELS[lvl], pressure)

    @property
    def brownout_level(self) -> int:
        """Current brownout rung (0 = normal; index into
        :data:`BROWNOUT_LEVELS`)."""
        return 0 if self._brownout is None else self._brownout.level

    def resilience_snapshot(self) -> Optional[Dict[str, Any]]:
        """JSON-able live resilience view — what ``ops_server``'s
        ``/resilience`` route serves and the FlightRecorder dumps:
        policy knobs, per-replica breaker states, the brownout rung,
        live hedges, and every resilience counter.  None when no
        resilience policy is attached."""
        if self.resilience is None:
            return None
        return {
            "policy": self.resilience.to_dict(),
            "breakers": {name: cb.to_dict()
                         for name, cb in sorted(self._breakers.items())},
            "breakers_open": self.breakers_open(),
            "brownout": (None if self._brownout is None
                         else self._brownout.to_dict()),
            "hedges_inflight": self._hedges_live,
            "occupancy": round(self._occupancy(), 4),
            "counters": dict(self._rstats.snapshot()),
        }

    # --------------------------------------------------------- telemetry --

    def queue_depths(self) -> Dict[int, Dict[str, int]]:
        return {pri: {"depth": len(q),
                      "queued_tokens": self._queued_tokens[pri]}
                for pri, q in enumerate(self._queues)}

    def gateway_snapshot(self) -> Dict[str, Any]:
        """JSON-able live view — what ``ops_server``'s ``/gateway`` route
        serves: replica states, queue depths, counters, latency
        percentiles."""
        h_q = self._stats.histogram("queue_seconds")
        h_t = self._stats.histogram("ttft_seconds")
        counters = {k: v for k, v in self._stats.snapshot().items()}
        out = {
            "replicas": [rep.to_dict() for rep in self._replicas.values()],
            "queues": self.queue_depths(),
            "counters": counters,
            # bucket-resolution estimates (utils.stats.Histogram); exact
            # sample percentiles ride the tracer / request handles
            "queue_s": {"p50": h_q.percentile(0.50),
                        "p99": h_q.percentile(0.99)},
            "ttft_s": {"p50": h_t.percentile(0.50),
                       "p99": h_t.percentile(0.99)},
            # fleet pressure with its raw terms — what a FleetCollector
            # reads per target (resilience carries the same scalar, but
            # only when a resilience policy is configured)
            "occupancy": self._occupancy_terms(),
        }
        if self.resilience is not None:
            # breaker/brownout state rides every snapshot consumer —
            # /gateway, and the FlightRecorder's crash dumps
            out["resilience"] = self.resilience_snapshot()
        if self.has_kv_surface():
            with self._disagg_lock:
                migrating = len(self._disagg)
            # the light view; GET /kvstore serves the full one
            out["kvstore"] = {
                "counters": dict(self._kvstats.snapshot()),
                "migrations_inflight": migrating,
                "decode_pool_pressure": round(
                    self.decode_pool_pressure(), 4)}
        return out

    summary = gateway_snapshot

    def metrics(self) -> Dict[str, float]:
        out = dict(self._stats.snapshot())
        out["queued"] = float(sum(len(q) for q in self._queues))
        out["inflight"] = float(sum(len(rep.inflight)
                                    for rep in self._replicas.values()))
        return out

    def prometheus_text(self, namespace: str = "paddle_tpu_gateway") -> str:
        text = _prometheus_text(
            self._stats, namespace=namespace,
            extra_gauges={
                "queued": sum(len(q) for q in self._queues),
                "inflight": sum(len(rep.inflight)
                                for rep in self._replicas.values()),
                "replicas_active": sum(
                    1 for rep in self._replicas.values()
                    if rep.state == ACTIVE)})
        if self.resilience is not None:
            breakers = list(self._breakers.values())
            text += _prometheus_text(
                self._rstats, namespace="paddle_tpu_resilience",
                extra_gauges={
                    "brownout_level": self.brownout_level,
                    "breakers_open": sum(
                        1 for cb in breakers
                        if cb.state == CircuitBreaker.OPEN),
                    "breakers_half_open": sum(
                        1 for cb in breakers
                        if cb.state == CircuitBreaker.HALF_OPEN),
                    "hedges_inflight": self._hedges_live})
        if self.has_kv_surface():
            # fleet-aggregated tier gauges (stores deduped — decode
            # replicas may share one) under the kvstore namespace
            tier = {"dram_pages": 0.0, "dram_bytes": 0.0,
                    "disk_pages": 0.0, "disk_bytes": 0.0}
            for st in self._kv_stores():
                m = st.metrics()
                for k in tier:
                    tier[k] += float(m.get(k, 0.0))
            with self._disagg_lock:
                migrating = len(self._disagg)
            text += _prometheus_text(
                self._kvstats, namespace="paddle_tpu_kvstore",
                extra_gauges={
                    "migrations_inflight": migrating,
                    "decode_pool_pressure": self.decode_pool_pressure(),
                    **tier})
        return text
