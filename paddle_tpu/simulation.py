"""Fake-clock traffic simulation harness for the serving control plane.

Scaling policy is impossible to test against real engines on real time:
an end-to-end scale-up trajectory (SLO burn → alert dwell → spawn → warm
→ activate → recovery → idle dwell → drain) spans minutes of wall clock
and every XLA compile in between.  This module makes the whole
trajectory a deterministic CPU unit test: a **real**
:class:`~paddle_tpu.gateway.ServingGateway` (real queues, routing,
quarantine, drains) fronting **fake-timed** engines, all sharing one
injectable :class:`SimClock` — no sleeps, no device, no nondeterminism.

Pieces:

- :class:`SimClock` — the shared fake monotonic clock (``advance(dt)``).
- :class:`SimTracer` — a real :class:`~paddle_tpu.telemetry.Tracer`
  whose ``now()`` reads the sim clock, so ring timestamps,
  ``last_event_age_s`` (the gateway's stall/quarantine dial) and SLO
  windows all live on simulated time.
- :class:`SimEngine` — a host-only engine with the real scheduling
  surface (``add_request`` / ``step`` / ``pop_finished`` / ``cancel`` /
  ``warmup`` / ``compile_grid``): one token per active slot per
  ``step()``, deterministic token streams (stream equality pins
  zero-drop/replay correctness), a program-cache model that emits real
  tracer compile events (so warmup vs in-serve compile accounting — the
  PR 2/6 contracts — is exercised), and fault modes for chaos scenarios
  (``paddle_tpu.faults``): ``kill()`` replica death, ``stall(ticks)``
  temporary freeze, ``slow(factor)`` straggler, ``flaky(n)`` transient
  dispatch errors (the engine stops ticking / slows / raises while
  holding work; the gateway's stall health-check, hedging, and
  retry/breaker paths each get their natural trigger).
- workload generators — ``steady`` (Poisson), ``diurnal`` (sinusoid-
  modulated Poisson), ``flash_crowd`` (step spike) — seconds → rate
  callables, sampled per tick with a seeded Poisson draw.
- :class:`TrafficSim` — the driver: per ``dt`` tick it samples arrivals,
  submits them to the gateway, runs one gateway round (and one
  autoscaler ``evaluate()`` when attached), fires any scheduled
  injections (``at(t, fn)`` — replica death mid-burst), advances the
  clock, and samples a fleet/queue timeline.  ``run()`` returns a report:
  outcomes, TTFT percentiles (sim seconds), shed rate, the timeline, the
  autoscaler decision history, and the ``dropped`` list that must stay
  empty (the zero-drop contract across every transition).

This doubles as the scenario-diversity workload generator the ROADMAP
north star asks for: the arrival processes that drive the CPU tier-1
scenario tests (``tests/test_autoscaler.py``,
``tests/test_gateway_resilience.py``).

Everything here is stdlib + telemetry — importing it never touches JAX,
so policy tests cost milliseconds.

No reference counterpart: the reference snapshot serves static batches;
this is the traffic side of the elastic-serving control plane
(docs/AUTOSCALING.md).
"""

from __future__ import annotations

import collections
import contextlib
import logging
import math
import random
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .faults import TransientDispatchError
from .telemetry import Tracer
from .utils.stats import StatRegistry, prometheus_text as _prometheus_text

__all__ = ["SimClock", "SimTracer", "SimEngine", "TrafficSim",
           "steady", "diurnal", "flash_crowd", "sim_tokens",
           "SimFleetHost", "build_sim_fleet"]


class SimClock:
    """Deterministic fake monotonic clock: a callable returning the
    current simulated seconds, advanced explicitly."""

    def __init__(self, start: float = 0.0):
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError("time only moves forward")
        self.t += float(dt)
        return self.t


class SimTracer(Tracer):
    """A real :class:`Tracer` on simulated time: ``now()`` reads the
    injected clock, so every ring event, ``last_event_age_s`` liveness
    peek and downstream consumer (gateway health checks, SLO windows,
    trace stitching) sees the fake timebase.  The epoch is 0 — sim time
    IS the shared timebase across every sim tracer."""

    def __init__(self, clock: Callable[[], float], **kwargs):
        self._sim_clock = clock
        super().__init__(**kwargs)
        self._t0 = 0.0

    def now(self) -> float:
        return float(self._sim_clock())


def sim_tokens(prompt: Sequence[int], n: int) -> List[int]:
    """The deterministic token stream a :class:`SimEngine` emits for
    ``prompt`` — the oracle stream-equality checks compare against
    (replays and reroutes must re-deliver exactly this)."""
    seed = sum(int(t) for t in prompt) * 31 + len(prompt)
    return [(seed + 7 * i) % 997 for i in range(int(n))]


class _SimRequest:
    __slots__ = ("rid", "prompt", "max_new", "on_token", "emitted",
                 "stream", "prefill_left")

    def __init__(self, rid, prompt, max_new, on_token):
        self.rid = rid
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.on_token = on_token
        self.emitted = 0
        self.stream = sim_tokens(prompt, max_new)
        self.prefill_left = 0        # prefill ticks before tokens flow


def sim_chain_keys(prompt: Sequence[int], block_size: int) -> List[str]:
    """The sim engines' chain-digest model: one string key per FULL
    prompt block, a pure function of the tokens — identical across
    replicas, so migrated pages address the same chains everywhere
    (the real engines' (pad, tokens) rolling digest, minus the
    bucketing)."""
    toks = [int(t) for t in prompt]
    return [f"sim:{block_size}:{tuple(toks[:(i + 1) * block_size])!r}"
            for i in range(len(toks) // block_size)]


class SimEngine:
    """Host-only fake-timed serving engine (module docstring).

    ``max_slots`` concurrent requests; each ``step()`` delivers
    ``tokens_per_tick`` tokens to every active request — service time in
    sim seconds is queueing + ``ceil(max_new / tokens_per_tick)`` driver
    ticks.  ``prompt_buckets`` shape the program-cache model (one
    ``prefill:<P>`` family per bucket + one ``decode``), matching the
    real engines' grids so warmup/compile accounting is exercised, not
    faked.  ``warmup_unsupported=True`` raises ``NotImplementedError``
    from ``warmup()`` — the TP/mesh-engine shape the autoscaler must
    degrade gracefully on."""

    prefix_caching = False

    def __init__(self, *, max_slots: int = 4, tokens_per_tick: int = 1,
                 prompt_buckets: Sequence[int] = (8, 16),
                 tracer: Optional[Tracer] = None,
                 compile_wall_s: float = 0.0,
                 warmup_unsupported: bool = False,
                 draft_k: int = 0, acceptance=0.0, spec_seed: int = 0,
                 prefix_caching: bool = False, block_size: int = 4,
                 kv_store=None, prefix_capacity_blocks: int = 64,
                 page_bytes: int = 1024,
                 prefill_ticks_per_block: int = 0,
                 logger: Optional[logging.Logger] = None):
        """``draft_k > 0`` enables the SEEDED speculative-acceptance
        model: each ``step()`` becomes one spec round per active request
        — ``draft_k`` tokens drafted, a per-request acceptance
        probability decides the leading accepted run, and the request
        advances by ``lead + 1`` tokens (so throughput scales with
        acceptance exactly like the real ragged spec engine, while the
        token STREAM stays ``sim_tokens`` — replay/reroute equality
        checks hold unchanged).  ``acceptance`` is either one
        probability for every request or a ``(lo, hi)`` pair from which
        each request draws its own (seeded by ``spec_seed`` and the
        request id).  Everything is deterministic: same seeds, same
        arrival order → the same lead sequence, tick for tick.  The
        ``spec_rounds`` / ``tokens_drafted`` / ``tokens_accepted``
        counters mirror the real engine's registry names."""
        if int(max_slots) < 1:
            raise ValueError("max_slots must be >= 1")
        if int(tokens_per_tick) < 1:
            raise ValueError("tokens_per_tick must be >= 1")
        if int(draft_k) < 0:
            raise ValueError("draft_k must be >= 0")
        if int(draft_k) > 0 and int(tokens_per_tick) != 1:
            # the spec model paces by acceptance (lead + 1 per round);
            # a conflicting tokens_per_tick would be silently ignored
            raise ValueError(
                "tokens_per_tick and draft_k are mutually exclusive "
                "pacing knobs — the acceptance model replaces the fixed "
                "burst")
        if int(draft_k) == 0 and (acceptance != 0.0 or spec_seed != 0):
            # the symmetric guard: acceptance knobs without draft_k would
            # silently measure non-speculative pacing
            raise ValueError(
                "acceptance/spec_seed need draft_k > 0 (the speculative "
                "acceptance model is off without a draft budget)")
        self.S = self.max_slots = int(max_slots)
        self.tokens_per_tick = int(tokens_per_tick)
        self.draft_k = int(draft_k)
        self._acceptance = (tuple(float(a) for a in acceptance)
                           if isinstance(acceptance, (tuple, list))
                           else float(acceptance))
        probs = (self._acceptance if isinstance(self._acceptance, tuple)
                 else (self._acceptance,))
        if (len(probs) not in (1, 2)
                or any(not 0.0 <= a <= 1.0 for a in probs)
                or (len(probs) == 2 and probs[0] > probs[1])):
            raise ValueError(
                "acceptance must be a probability in [0, 1] or an "
                "ordered (lo, hi) pair of them")
        self._spec_seed = int(spec_seed)
        # ---- tier / migration model (docs/KV_TIERING.md) ----
        # the real paged engines' prefix-cache + TieredKVStore surface,
        # host-only: chains are pure token functions (sim_chain_keys),
        # the "HBM" tier is a capacity-bounded LRU of chains, eviction
        # demotes into the attached kv_store, admission restores from
        # it, and ``prefill_ticks_per_block`` makes warmth VISIBLE on
        # the fake clock (a warm block skips its prefill ticks — the
        # TTFT benefit tier-aware routing and the migration A/B pin).
        self.prefix_caching = bool(prefix_caching)
        self.bs = int(block_size)
        if self.bs < 1:
            raise ValueError("block_size must be >= 1")
        if kv_store is not None and not self.prefix_caching:
            raise ValueError("kv_store needs prefix_caching=True — pages "
                             "are addressed by prefix chain keys")
        self.kv_store = kv_store
        self.prefix_capacity_blocks = int(prefix_capacity_blocks)
        if self.prefix_capacity_blocks < 1:
            raise ValueError("prefix_capacity_blocks must be >= 1")
        self.page_bytes = int(page_bytes)
        self.prefill_ticks_per_block = int(prefill_ticks_per_block)
        if self.prefill_ticks_per_block < 0:
            raise ValueError("prefill_ticks_per_block must be >= 0")
        self._prefix: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        self.buckets = tuple(sorted(int(b) for b in prompt_buckets))
        self.tracer = tracer
        self.compile_wall_s = float(compile_wall_s)
        self.warmup_unsupported = bool(warmup_unsupported)
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        self._rids = 0
        self._queue: List[_SimRequest] = []
        self._active: Dict[int, _SimRequest] = {}
        self._finished: Dict[int, List[int]] = {}
        self._progs: set = set()
        self._in_warmup = False
        self.warmed = False
        self.dead = False
        self.in_serve_compiles = 0
        # fault modes beyond kill() (paddle_tpu.faults integration):
        # stall freezes N rounds (silent — the stall health-check sees a
        # dead tracer), slow delivers work only every factor-th round
        # (but stays visibly alive), flaky fails the next N dispatches
        self._stall_ticks = 0
        self._slow_factor = 1
        self._slow_phase = 0
        self._flaky = 0
        self.stats = StatRegistry()

    # -------------------------------------------------------------- grid --

    def compile_grid(self) -> List[str]:
        return [f"prefill:{P}" for P in self.buckets] + ["decode"]

    def _bucket_label(self, prompt_len: int) -> str:
        for P in self.buckets:
            if prompt_len <= P:
                return f"prefill:{P}"
        return f"prefill:{self.buckets[-1]}"

    def _fetch(self, label: str):
        """One program-cache access: misses outside warmup are in-serve
        compiles (the count the zero-compile acceptance pin reads)."""
        hit = label in self._progs
        if not hit:
            self._progs.add(label)
            if not self._in_warmup:
                self.in_serve_compiles += 1
                self.stats.add("in_serve_compiles")
        if self.tracer is not None:
            self.tracer.compile_event(
                "sim", label, hit=hit,
                wall_s=0.0 if hit else self.compile_wall_s)

    def warmup(self, cache_dir: Optional[str] = None, max_workers: int = 1,
               block: bool = True) -> Dict[str, Any]:
        """Precompile the full grid (instant in sim time).  With a tracer
        the run sits in an ``expected_compiles`` window keyed to the
        grid, same as the real engines' warmup."""
        if self.warmup_unsupported:
            raise NotImplementedError(
                "sim engine configured unwarmable (the TP/mesh shape)")
        grid = self.compile_grid()
        ctx = (self.tracer.expected_compiles(keys=set(grid))
               if self.tracer is not None else contextlib.nullcontext())
        self._in_warmup = True
        try:
            with ctx:
                for label in grid:
                    self._fetch(label)
        finally:
            self._in_warmup = False
        self.warmed = True
        return {"programs": len(grid), "wall_s": 0.0,
                "cache_dir": None if cache_dir is None else str(cache_dir)}

    # --------------------------------------------------------- scheduling --

    def _free_slots(self) -> List[int]:
        return list(range(self.S - len(self._active)))

    def add_request(self, prompt, max_new_tokens: int, on_token=None,
                    trace_ctx=None, **sampling) -> int:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self._flaky > 0:
            self._flaky -= 1
            self.stats.add("dispatch_errors")
            raise TransientDispatchError(
                "sim engine flaky dispatch (injected)")
        rid = self._rids
        self._rids += 1
        req = _SimRequest(rid, prompt, max_new_tokens, on_token)
        if self.tracer is not None and trace_ctx is not None:
            self.tracer.bind_trace(rid, trace_ctx)
        self._queue.append(req)
        self.stats.add("requests_admitted")
        return rid

    def step(self):
        """One scheduler round: admit queued requests into free slots
        (paying the prefill program fetch), then deliver
        ``tokens_per_tick`` tokens to every active request.  A dead
        engine does nothing — no tokens AND no tracer events, so its
        tracer's event age grows with simulated time and the gateway's
        stall health-check fires."""
        if self.dead:
            return
        if self._stall_ticks > 0:
            self._stall_ticks -= 1      # frozen AND silent: no tracer
            return                      # event, so the stall check fires
        if self._slow_factor > 1:
            self._slow_phase += 1
            if self._slow_phase % self._slow_factor != 0:
                # a straggler is SLOW, not dead: it shows liveness (the
                # tick event below keeps the stall health-check green)
                # but moves no tokens this round
                if self.tracer is not None:
                    self.tracer.tick("sim", 0.0,
                                     active=len(self._active),
                                     queued=len(self._queue), slow=True)
                return
        while self._queue and len(self._active) < self.S:
            req = self._queue.pop(0)
            self._fetch(self._bucket_label(len(req.prompt)))
            nblocks = len(req.prompt) // self.bs
            if self.prefix_caching:
                # warm blocks (HBM hit or lower-tier restore) skip their
                # prefill ticks — the tier benefit on the fake clock
                warm = self._warm_prefix(req.prompt)
                req.prefill_left = max(nblocks - warm, 0) \
                    * self.prefill_ticks_per_block
                if req.prefill_left == 0:
                    self._register_chains(req.prompt)
            else:
                req.prefill_left = nblocks * self.prefill_ticks_per_block
            self._active[req.rid] = req
        if self._active:
            self._fetch("decode")
            if self.draft_k:
                self.stats.add("spec_rounds")
        retired = []
        for rid, req in list(self._active.items()):
            if req.prefill_left > 0:
                req.prefill_left -= 1
                if req.prefill_left == 0 and self.prefix_caching:
                    self._register_chains(req.prompt)
                continue
            if self.draft_k:
                # seeded acceptance model: one spec round — draft_k
                # drafted, the leading accepted run + 1 delivered.  The
                # STREAM is unchanged (sim_tokens), only pacing scales
                # with acceptance, mirroring the real ragged spec engine.
                lead = self._spec_lead(req)
                self.stats.add("tokens_drafted", self.draft_k)
                self.stats.add("tokens_accepted", lead)
                burst = lead + 1
            else:
                burst = self.tokens_per_tick
            for _ in range(burst):
                tok = req.stream[req.emitted]
                req.emitted += 1
                # same counter name as the real serving engines, so a
                # FleetCollector's tokens/s rollup reads sim and real
                # targets through one suffix
                self.stats.add("tokens_emitted")
                done = req.emitted >= req.max_new
                if req.on_token is not None:
                    req.on_token(rid, tok, done)
                if done:
                    retired.append(rid)
                    break
        for rid in retired:
            req = self._active.pop(rid)
            self._finished[rid] = list(req.stream)
            self.stats.add("requests_finished")
            if self.tracer is not None:
                self.tracer.bind_trace(rid, None)
        if self.tracer is not None:
            self.tracer.tick("sim", 0.0, active=len(self._active),
                             queued=len(self._queue))

    def _req_acceptance(self, rid: int) -> float:
        """The request's own acceptance probability: fixed when
        ``acceptance`` is a float, drawn once (seeded by rid) from the
        ``(lo, hi)`` range otherwise."""
        a = self._acceptance
        if isinstance(a, tuple):
            lo, hi = a
            rng = random.Random((self._spec_seed << 20)
                                ^ (rid * 2654435761))
            return lo + (hi - lo) * rng.random()
        return a

    def _spec_lead(self, req: "_SimRequest") -> int:
        """Deterministic accepted-run draw for one spec round: count
        leading Bernoulli(p) successes over draft_k trials, seeded by
        (spec_seed, rid, tokens emitted so far) — same seeds replay the
        identical lead sequence."""
        p = self._req_acceptance(req.rid)
        rng = random.Random((self._spec_seed * 1000003)
                            ^ (req.rid * 7919) ^ (req.emitted << 1))
        lead = 0
        while lead < self.draft_k and rng.random() < p:
            lead += 1
        return lead

    # ---------------------------------------------- tier / migration --
    # (the real paged engines' public KV-tiering surface, host-only —
    # docs/KV_TIERING.md; the gateway's disaggregated pipeline and the
    # tier-aware router drive sim fleets through these exactly as they
    # drive real ones)

    def kv_page_meta(self):
        """Portable page signature (JSON-able lists, the KVPage meta
        contract): sim engines exchange pages iff block size and page
        width match."""
        return ["sim", self.bs, self.page_bytes]

    def attach_kv_store(self, store):
        if store is not None and not self.prefix_caching:
            raise ValueError("kv_store needs prefix_caching=True — pages "
                             "are addressed by prefix chain keys")
        self.kv_store = store
        return store

    def _enforce_prefix_capacity(self):
        from .kv_store import KVPage
        while len(self._prefix) > self.prefix_capacity_blocks:
            chain, _ = self._prefix.popitem(last=False)       # LRU first
            if self.kv_store is None:
                continue        # no lower tier: the page is DROPPED —
                #                 counting a "demotion" here would fake
                #                 tier traffic that never happened (the
                #                 real engines' store-gated discipline)
            self.kv_store.put(KVPage(chain, bytes(self.page_bytes),
                                     self.kv_page_meta()))
            self.stats.add("kvstore_demoted_blocks")
            if self.tracer is not None:
                self.tracer.emit("kvstore", what="demote",
                                 chain=chain[:48], bytes=self.page_bytes,
                                 engine="sim")

    def _register_chains(self, prompt):
        for chain in sim_chain_keys(prompt, self.bs):
            self._prefix[chain] = None
            self._prefix.move_to_end(chain)
        self._enforce_prefix_capacity()

    def _warm_prefix(self, prompt) -> int:
        """Leading warm blocks at admission: HBM hits LRU-touch, lower-
        tier hits RESTORE (store → prefix LRU), a miss stops the walk —
        the sim mirror of the real engines' restore-before-fill."""
        depth = 0
        for chain in sim_chain_keys(prompt, self.bs):
            if chain in self._prefix:
                self._prefix.move_to_end(chain)
            elif self.kv_store is not None and self.kv_store.lookup(
                    chain, meta=self.kv_page_meta()) is not None:
                self._prefix[chain] = None
                self.stats.add("kvstore_restored_blocks")
                if self.tracer is not None:
                    self.tracer.emit("kvstore", what="restore",
                                     chain=chain[:48],
                                     bytes=self.page_bytes, engine="sim")
            else:
                break
            depth += 1
        self._enforce_prefix_capacity()
        return depth

    def flush_prefix(self) -> int:
        """Demote every cached chain to the attached store (the bench /
        smoke primitive); returns the demoted count."""
        if self.kv_store is None:
            raise ValueError("flush_prefix needs an attached kv_store")
        from .kv_store import KVPage
        n = 0
        while self._prefix:
            chain, _ = self._prefix.popitem(last=False)
            self.kv_store.put(KVPage(chain, bytes(self.page_bytes),
                                     self.kv_page_meta()))
            n += 1
        self.stats.add("kvstore_demoted_blocks", n)
        return n

    def export_prefix_pages(self, prompt) -> List[Any]:
        """Leading resident pages for ``prompt`` (migration source
        primitive); stops at the first miss."""
        if not self.prefix_caching:
            return []
        from .kv_store import KVPage
        pages: List[Any] = []
        for chain in sim_chain_keys(prompt, self.bs):
            if chain in self._prefix:
                pages.append(KVPage(chain, bytes(self.page_bytes),
                                    self.kv_page_meta()))
                continue
            if self.kv_store is not None:
                page = self.kv_store.lookup(chain,
                                            meta=self.kv_page_meta())
                if page is not None:
                    pages.append(page)
                    continue
            break
        return pages

    def prefix_index(self) -> Dict[str, str]:
        """PUBLIC tier map (serving.py contract)."""
        idx = {chain: "hbm" for chain in self._prefix}
        if self.kv_store is not None:
            for chain, tier in self.kv_store.index().items():
                idx.setdefault(chain, tier)
        return idx

    def prefix_match(self, prompt) -> Dict[str, Any]:
        """PUBLIC tier-aware affinity read (serving.py contract): pure —
        no LRU touch, no restore."""
        out: Dict[str, Any] = {"hbm": 0, "total": 0, "tiers": []}
        if not self.prefix_caching:
            return out
        leading_hbm = True
        for chain in sim_chain_keys(prompt, self.bs):
            if chain in self._prefix:
                tier = "hbm"
            else:
                tier = (self.kv_store.tier_of(chain)
                        if self.kv_store is not None else None)
                if tier is None:
                    break
            if tier != "hbm":
                leading_hbm = False
            if leading_hbm:
                out["hbm"] += 1
            out["total"] += 1
            out["tiers"].append(tier)
        return out

    def cancel(self, rid: int) -> bool:
        """Release one in-flight request (queued or active) and deliver
        the terminal stream signal — the serving.py primitive the
        gateway's deadline/quarantine paths ride."""
        req = None
        for i, q in enumerate(self._queue):
            if q.rid == rid:
                req = self._queue.pop(i)
                break
        if req is None:
            req = self._active.pop(rid, None)
        if req is None:
            return False
        self.stats.add("requests_cancelled")
        if self.tracer is not None:
            self.tracer.bind_trace(rid, None)
        if req.on_token is not None:
            req.on_token(rid, None, True)
        return True

    def pending(self) -> bool:
        return bool(self._queue) or bool(self._active)

    def pop_finished(self) -> Dict[int, List[int]]:
        out, self._finished = self._finished, {}
        return out

    def kill(self):
        """Replica-death injection: freeze the engine mid-work."""
        self.dead = True

    def stall(self, ticks: int):
        """Stall injection: freeze for ``ticks`` scheduler rounds —
        silent (no tracer events), so the gateway's stall health-check
        quarantines it if the freeze outlasts ``stall_threshold_s`` —
        then resume where it left off."""
        if int(ticks) < 0:
            raise ValueError("ticks must be >= 0")
        self._stall_ticks = int(ticks)

    def slow(self, factor: int):
        """Straggler injection: serve one real round per ``factor``
        ``step()`` calls (``factor=1`` restores full speed).  Unlike a
        stall the engine stays visibly alive — slow replicas are the
        hedging workload, not the quarantine workload."""
        if int(factor) < 1:
            raise ValueError("factor must be >= 1")
        self._slow_factor = int(factor)
        self._slow_phase = 0

    def flaky(self, n: int):
        """Transient-dispatch-error injection: the next ``n``
        ``add_request`` calls raise
        :class:`~paddle_tpu.faults.TransientDispatchError` (the
        retryable class the gateway's retry/breaker path keys on)."""
        if int(n) < 0:
            raise ValueError("n must be >= 0")
        self._flaky = int(n)

    # --------------------------------------------------------- telemetry --

    def metrics(self) -> Dict[str, float]:
        out = dict(self.stats.snapshot())
        out["active"] = float(len(self._active))
        out["queued"] = float(len(self._queue))
        if self.draft_k:
            out["acceptance_rate"] = (
                float(out.get("tokens_accepted", 0))
                / max(float(out.get("tokens_drafted", 0)), 1.0))
        return out

    def prometheus_text(self, namespace: str = "paddle_tpu_sim_engine"
                        ) -> str:
        return _prometheus_text(
            self.stats, namespace=namespace,
            extra_gauges={"active": len(self._active),
                          "queued": len(self._queue),
                          "warmed": int(self.warmed),
                          "dead": int(self.dead)})


# ---------------------------------------------------------------------------
# workload generators
# ---------------------------------------------------------------------------

def steady(rate_per_s: float) -> Callable[[float], float]:
    """Constant-rate Poisson arrivals."""
    r = float(rate_per_s)
    if r < 0:
        raise ValueError("rate must be >= 0")
    return lambda t: r


def diurnal(base_per_s: float, peak_per_s: float, period_s: float,
            phase_s: float = 0.0) -> Callable[[float], float]:
    """Sinusoid-modulated arrivals: rate(t) swings ``base → peak → base``
    over ``period_s``, starting at the trough (t = ``phase_s``) — the
    day/night traffic shape."""
    base, peak = float(base_per_s), float(peak_per_s)
    if base < 0 or peak < base:
        raise ValueError("need 0 <= base <= peak")
    period = float(period_s)
    if period <= 0:
        raise ValueError("period_s must be > 0")

    def rate(t: float) -> float:
        x = 2.0 * math.pi * ((t - phase_s) / period)
        return base + (peak - base) * 0.5 * (1.0 - math.cos(x))
    return rate


def flash_crowd(base_per_s: float, spike_per_s: float, at_s: float,
                duration_s: float) -> Callable[[float], float]:
    """Step spike: ``base`` everywhere except ``[at_s, at_s +
    duration_s)`` where the rate jumps to ``spike`` — the cache-miss
    stampede / launch-event shape."""
    base, spike = float(base_per_s), float(spike_per_s)
    if base < 0 or spike < 0:
        raise ValueError("rates must be >= 0")
    t0, t1 = float(at_s), float(at_s) + float(duration_s)
    return lambda t: spike if t0 <= t < t1 else base


def _poisson(rng: random.Random, lam: float) -> int:
    """Seeded Poisson draw (Knuth for small λ, normal approximation past
    30 — per-tick λ in any sane sim sits well under that)."""
    if lam <= 0.0:
        return 0
    if lam > 30.0:
        return max(0, int(round(rng.gauss(lam, math.sqrt(lam)))))
    limit = math.exp(-lam)
    n, p = 0, 1.0
    while True:
        p *= rng.random()
        if p <= limit:
            return n
        n += 1


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

class TrafficSim:
    """Drive a workload through a real gateway on the fake clock (module
    docstring).  ``rate_fn``: seconds → arrivals/second (the generators
    above, or any callable).  ``dt``: sim seconds per driver tick — each
    tick is one arrival sample + one ``gateway.step()`` (+ one
    ``autoscaler.evaluate()``).  ``seed`` fixes the arrival process and
    request shapes — identical seeds replay identical scenarios."""

    def __init__(self, gateway, clock: SimClock,
                 rate_fn: Callable[[float], float], *, dt: float = 0.25,
                 seed: int = 0, prompt_len: Tuple[int, int] = (3, 12),
                 max_new: Tuple[int, int] = (4, 8), vocab: int = 997,
                 priority: int = 0, autoscaler=None,
                 sample_every_s: float = 1.0,
                 ttft_deadline_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 logger: Optional[logging.Logger] = None):
        if float(dt) <= 0:
            raise ValueError("dt must be > 0")
        self.gateway = gateway
        self.clock = clock
        self.rate_fn = rate_fn
        self.dt = float(dt)
        self.seed = int(seed)
        self.prompt_len = (int(prompt_len[0]), int(prompt_len[1]))
        self.max_new = (int(max_new[0]), int(max_new[1]))
        self.vocab = int(vocab)
        self.priority = int(priority)
        self.autoscaler = autoscaler
        self.sample_every_s = float(sample_every_s)
        # optional per-request deadlines: the gateway's hedging trigger
        # (TTFT-at-risk) and expiry paths need them to exist in the
        # workload, exactly like real traffic carries them
        self.ttft_deadline_s = ttft_deadline_s
        self.deadline_s = deadline_s
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        self.handles: List[Any] = []
        self.samples: List[Dict[str, Any]] = []
        self._injections: List[Tuple[float, Callable[[], None], str]] = []
        self._fired: List[str] = []
        self._last_sample_at: Optional[float] = None

    def at(self, t_s: float, fn: Callable[[], None], label: str = "event"
           ) -> "TrafficSim":
        """Schedule an injection: ``fn()`` fires on the first tick at or
        after sim time ``t_s`` (replica death mid-burst:
        ``sim.at(30, engine.kill, "kill r1")``)."""
        self._injections.append((float(t_s), fn, str(label)))
        self._injections.sort(key=lambda e: e[0])
        return self

    # ------------------------------------------------------------- drive --

    def _submit_arrivals(self, rng: random.Random, n: int):
        for _ in range(n):
            plen = rng.randint(*self.prompt_len)
            prompt = [rng.randint(1, self.vocab) for _ in range(plen)]
            self.handles.append(self.gateway.submit(
                prompt, rng.randint(*self.max_new),
                priority=self.priority,
                ttft_deadline_s=self.ttft_deadline_s,
                deadline_s=self.deadline_s))

    def _fire_due(self, t: float):
        while self._injections and self._injections[0][0] <= t:
            _ts, fn, label = self._injections.pop(0)
            self._fired.append(label)
            fn()

    def _sample(self, t: float):
        if self._last_sample_at is not None \
                and t - self._last_sample_at < self.sample_every_s - 1e-9:
            return
        self._last_sample_at = t
        reps = self.gateway.replicas()
        self.samples.append({
            "t": t,
            "active": sum(1 for r in reps if r.state == "active"),
            "draining": sum(1 for r in reps if r.state == "draining"),
            "quarantined": sum(1 for r in reps
                               if r.state == "quarantined"),
            "queued": sum(d["depth"] for d in
                          self.gateway.queue_depths().values()),
            "inflight": sum(len(r.inflight) for r in reps),
            "rate": self.rate_fn(t),
        })

    def _tick(self, rng: Optional[random.Random]):
        t = self.clock()
        self._fire_due(t)
        if rng is not None:
            self._submit_arrivals(rng,
                                  _poisson(rng, self.rate_fn(t) * self.dt))
        self.gateway.step()
        if self.autoscaler is not None:
            self.autoscaler.evaluate()
        self._sample(t)
        self.clock.advance(self.dt)

    def run(self, duration_s: float, drain: bool = True,
            max_drain_ticks: int = 100000) -> Dict[str, Any]:
        """Run the scenario for ``duration_s`` sim seconds, then (with
        ``drain=True``) keep ticking WITHOUT new arrivals until nothing
        is queued or in flight — every admitted request must reach a
        terminal state for the report's zero-drop accounting to mean
        anything.  A scenario that cannot drain inside
        ``max_drain_ticks`` stops and reports the stuck requests in
        ``dropped`` instead of raising — report honesty over an
        exception."""
        rng = random.Random(self.seed)
        end = self.clock() + float(duration_s)
        while self.clock() < end - 1e-9:
            self._tick(rng)
        if drain:
            ticks = 0
            while self.gateway.pending() and ticks < int(max_drain_ticks):
                self._tick(None)
                ticks += 1
            if self.gateway.pending():
                self._log.warning(
                    "sim: scenario did not drain in %d ticks", ticks)
        return self.report()

    # ------------------------------------------------------------ report --

    @staticmethod
    def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1,
                max(0, math.ceil(q * len(sorted_vals)) - 1))
        return sorted_vals[i]

    def report(self) -> Dict[str, Any]:
        outcomes: Dict[str, int] = {}
        ttfts: List[float] = []
        for h in self.handles:
            outcomes[h.status] = outcomes.get(h.status, 0) + 1
            if h.status == "finished" and h.first_token_at is not None:
                ttfts.append(h.first_token_at - h.submitted_at)
        ttfts.sort()
        offered = len(self.handles)
        shed = outcomes.get("shed", 0)
        # dropped = admitted but never terminal: the zero-drop contract
        # every scaling transition must preserve
        dropped = [h.gid for h in self.handles if not h.done]
        report = {
            "offered": offered,
            "outcomes": outcomes,
            "shed_rate": (shed / offered) if offered else 0.0,
            "ttft_s": {
                "n": len(ttfts),
                "p50": self._percentile(ttfts, 0.50),
                "p95": self._percentile(ttfts, 0.95),
                "p99": self._percentile(ttfts, 0.99),
                "max": ttfts[-1] if ttfts else None,
            },
            "dropped": dropped,
            "injections_fired": list(self._fired),
            "timeline": list(self.samples),
            "end_t": self.clock(),
        }
        if self.autoscaler is not None:
            report["decisions"] = self.autoscaler.decisions()
            report["fleet"] = self.autoscaler.autoscaler_snapshot()["fleet"]
        return report

# ---------------------------------------------------------------------------
# simulated fleet (rank-0 collector over fake-clock hosts)
# ---------------------------------------------------------------------------

class SimFleetHost:
    """One simulated fleet member: a :class:`SimEngine` + its
    :class:`SimTracer` + a per-host ``SLOMonitor``, all on the shared
    :class:`SimClock`, behind an UNSTARTED
    :class:`~paddle_tpu.ops_server.OpsServer` — a scrape target a
    :class:`~paddle_tpu.telemetry_fleet.FleetCollector` federates
    through ``OpsServer.render()`` without binding a single port.  This
    is the multi-host rehearsal shape (one ops server per host, a rank-0
    collector scraping them) on deterministic time."""

    def __init__(self, clock: SimClock, *, name: str = "sim0",
                 slo_resolution_s: float = 5.0, **engine_kwargs):
        from .ops_server import OpsServer
        from .telemetry_ledger import RunLedger
        from .telemetry_slo import SLOMonitor
        self.name = str(name)
        self.clock = clock
        self.tracer = SimTracer(clock)
        self.engine = SimEngine(tracer=self.tracer, **engine_kwargs)
        self.slo = SLOMonitor(clock=clock, resolution_s=slo_resolution_s)
        self.tracer.set_slo(self.slo)
        self.ledger = RunLedger(clock=clock)
        self.server = OpsServer()
        self.server.attach(self.engine, name=f"{self.name}.engine")
        self.server.attach(self.slo, name=f"{self.name}.slo")
        self.server.attach(self.ledger, name=f"{self.name}.ledger")

    def submit(self, prompt, max_new_tokens: int, **sampling) -> int:
        """Admit one request through the host's request timeline: the
        tracer sees queued/first_token/token/retired, so TTFT and ITL
        samples flow into the host's SLO monitor (and from there into a
        federating collector's merged sketches) — the bookkeeping the
        gateway layer does in a full deployment, collapsed to one
        host."""
        state = {"started": False}

        def on_token(rid, _tok, done):
            if not state["started"]:
                state["started"] = True
                self.tracer.request_event(rid, "admitted")
                self.tracer.request_event(rid, "first_token")
            self.tracer.request_event(rid, "token")
            if done:
                self.tracer.request_event(rid, "retired")

        rid = self.engine.add_request(prompt, max_new_tokens,
                                      on_token=on_token, **sampling)
        self.tracer.request_event(rid, "queued",
                                  prompt_len=len(list(prompt)))
        return rid


def build_sim_fleet(clock: SimClock, n_hosts: int = 3, *,
                    interval_s: float = 5.0, objectives=(),
                    spool_dir: Optional[str] = None, **engine_kwargs):
    """A rank-0 :class:`~paddle_tpu.telemetry_fleet.FleetCollector` on
    the shared fake clock over ``n_hosts`` :class:`SimFleetHost` s —
    returns ``(collector, hosts)``.  Drive hosts (``host.engine.step()``
    etc.), ``clock.advance(...)``, then ``collector.scrape_once()``: the
    whole federation pipeline (scrape → parse → merge → rollups → spool)
    runs deterministically with zero sockets and zero sleeps."""
    from .telemetry_fleet import FleetCollector
    if int(n_hosts) < 1:
        raise ValueError("n_hosts must be >= 1")
    hosts = [SimFleetHost(clock, name=f"sim{i}", **engine_kwargs)
             for i in range(int(n_hosts))]
    collector = FleetCollector(interval_s=interval_s, clock=clock,
                               objectives=objectives, spool_dir=spool_dir)
    for host in hosts:
        collector.add_target(host.name, server=host.server)
    return collector, hosts
