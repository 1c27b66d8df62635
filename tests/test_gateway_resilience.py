"""Gateway resilience layer (paddle_tpu/gateway.py ResiliencePolicy,
ISSUE 12): circuit-breaker open/half-open/close lifecycle, bounded retry
with backoff and a structured exhaustion terminal, hedge winner/loser
token-exactness, brownout ladder hysteresis (no flapping), step()
exception isolation, the autoscaler's breaker-open scale signal, the
chaos acceptance pin, and off-path purity (resilience at defaults
changes no program-cache keys and no outputs).

Control-plane tests run on the fake clock with SimEngines (no JAX);
only the purity pin builds a real tiny GPT engine."""

import json
import urllib.request

import pytest

from paddle_tpu.autoscaler import ElasticAutoscaler
from paddle_tpu.faults import Fault, FaultPlan, FaultyEngine
from paddle_tpu.gateway import (BROWNOUT_LEVELS, Brownout, CircuitBreaker,
                                ResiliencePolicy, RetriesExhausted,
                                ServingGateway)
from paddle_tpu.simulation import (SimClock, SimEngine, SimTracer,
                                   TrafficSim, sim_tokens, steady)


def _gw(clock, pol, **kw):
    kw.setdefault("stall_threshold_s", 60.0)
    tracer = SimTracer(clock, capacity=16384)
    return ServingGateway(clock=clock, tracer=tracer, resilience=pol,
                          **kw), tracer


def _drive(gw, clock, max_ticks=600, dt=0.25, autoscaler=None):
    for _ in range(max_ticks):
        gw.step()
        if autoscaler is not None:
            autoscaler.evaluate()
        clock.advance(dt)
        if not gw.pending():
            return
    raise AssertionError("gateway did not drain")


class TestCircuitBreakerUnit:
    def test_lifecycle_closed_open_half_open_closed(self):
        cb = CircuitBreaker(failures_to_open=2, open_s=5.0)
        assert cb.allow(0.0) and cb.state == "closed"
        assert not cb.record_failure(1.0)
        assert cb.record_failure(1.5) and cb.state == "open"
        assert not cb.allow(2.0)                  # window running
        assert cb.allow(6.6)                      # -> half_open
        assert cb.state == "half_open"
        cb.note_dispatch(6.6)
        assert not cb.allow(6.7)                  # one probe at a time
        assert cb.record_success() and cb.state == "closed"

    def test_half_open_failure_reopens(self):
        cb = CircuitBreaker(failures_to_open=1, open_s=2.0)
        cb.record_failure(0.0)
        assert cb.allow(2.5) and cb.state == "half_open"
        cb.note_dispatch(2.5)
        assert cb.record_failure(2.6) and cb.state == "open"
        assert not cb.allow(3.0)                  # window re-armed
        assert cb.allow(4.7)

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(failures_to_open=0)
        with pytest.raises(ValueError):
            CircuitBreaker(open_s=0.0)


class TestBreakerIntegration:
    def test_open_excludes_half_open_probes_close_recloses(self):
        """The full loop against a flaky replica: consecutive dispatch
        failures open the breaker (routing excludes it, event emitted),
        the window elapses into a half-open probe, the probe succeeds
        and the breaker closes."""
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=2, breaker_open_s=2.0,
                               retry_budget=5, retry_backoff_s=0.0,
                               retry_jitter=0.0, hedge=False,
                               brownout=False)
        gw, tracer = _gw(clock, pol)
        flaky = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(flaky, "flaky")
        flaky.flaky(2)
        h1 = gw.submit([1, 2], 3)
        gw.step()                                  # fail 1 -> retry
        clock.advance(0.25)
        gw.step()                                  # fail 2 -> OPEN
        assert gw.breakers_open() == ["flaky"]
        snap = gw.resilience_snapshot()
        assert snap["breakers"]["flaky"]["state"] == "open"
        # while open: nothing is routed there, the request waits
        clock.advance(0.5)
        gw.step()
        assert h1.status == "queued"
        # window elapses -> half-open probe dispatch -> success -> closed
        clock.advance(2.0)
        _drive(gw, clock)
        assert h1.status == "finished"
        assert h1.tokens == sim_tokens([1, 2], 3)
        assert gw.breakers_open() == []
        whats = [e["what"] for e in tracer.events("resilience")]
        assert "breaker_open" in whats
        assert "breaker_half_open" in whats
        assert "breaker_close" in whats
        assert whats.index("breaker_open") < whats.index("breaker_half_open") \
            < whats.index("breaker_close")

    def test_cancelled_probe_releases_half_open_claim(self):
        """Regression: a HALF_OPEN probe request cancelled before its
        first token must release the probe claim — the replica must not
        be silently excluded from routing forever."""
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=1, breaker_open_s=1.0,
                               retry_budget=0, hedge=False,
                               brownout=False)
        gw, _ = _gw(clock, pol)
        eng = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(1)
        probe_victim = gw.submit([1], 2)
        gw.step()                                  # fail -> OPEN, terminal
        assert probe_victim.status == "failed"
        clock.advance(1.5)                         # window elapses
        h_probe = gw.submit([2], 8)
        gw.step()                                  # HALF_OPEN probe claim
        assert h_probe.status == "dispatched"
        assert gw.cancel(h_probe.gid)              # cancel BEFORE a token
        assert h_probe.status == "cancelled"
        # the claim is free: the next request probes and closes the loop
        h_next = gw.submit([3], 2)
        _drive(gw, clock)
        assert h_next.status == "finished"
        assert h_next.tokens == sim_tokens([3], 2)
        assert gw.breakers_open() == []

    def test_stale_open_breaker_expires_from_the_scale_signal(self):
        """Regression: a breaker opened at the END of a burst (no
        further traffic ever routes, so allow() is never called again)
        must fall out of breakers_open() once its window elapses — a
        stale signal would pin an idle autoscaled fleet at max size
        forever."""
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=1, breaker_open_s=1.0,
                               retry_budget=0, hedge=False,
                               brownout=False)
        gw, _ = _gw(clock, pol)
        eng = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(1)
        gw.submit([1], 2)
        gw.step()                                  # fail -> OPEN
        assert gw.breakers_open() == ["a"]
        clock.advance(100.0)                       # traffic long gone
        gw.step()
        assert gw.breakers_open() == []            # window elapsed
        # the raw state is still visible to operators, honestly labeled
        assert gw.resilience_snapshot()["breakers"]["a"]["state"] == "open"

    def test_unrelated_cancel_does_not_touch_the_probe_claim(self):
        """Regression: the HALF_OPEN probe verdict is keyed to the probe
        REQUEST — cancelling a pre-open in-flight request token-lessly
        must not free the claim (a second request would join the
        half-open replica while the true probe still races)."""
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=1, breaker_open_s=1.0,
                               retry_budget=0, hedge=False,
                               brownout=False)
        gw, _ = _gw(clock, pol)
        eng = SimEngine(max_slots=8, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.stall(10 ** 6)              # park the engine: no tokens move
        q_old = gw.submit([1], 50)                 # dispatched while CLOSED
        gw.step()
        assert q_old.status == "dispatched"
        assert q_old.first_token_at is None        # token-less, pre-open
        eng.flaky(1)
        gw.submit([2], 2)
        gw.step()                                  # fail -> OPEN
        clock.advance(1.5)                         # window elapses
        probe = gw.submit([3], 2)
        gw.step()                                  # HALF_OPEN, P claimed
        assert probe.status == "dispatched"
        assert gw.cancel(q_old.gid)                # unrelated, pre-open
        waiting = gw.submit([4], 2)
        gw.step()
        # the claim is still the probe's: nothing else joins the replica
        assert waiting.status == "queued"
        cb = gw.resilience_snapshot()["breakers"]["a"]
        assert cb["state"] == "half_open"
        eng.stall(0)                               # un-park: probe lands
        _drive(gw, clock)
        assert probe.status == "finished"
        assert waiting.status == "finished"
        assert gw.breakers_open() == []

    def test_expired_probe_reopens_breaker(self):
        """A probe that blows its TTFT deadline without a token IS the
        probe's verdict: the breaker re-opens."""
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=1, breaker_open_s=1.0,
                               retry_budget=0, hedge=False,
                               brownout=False)
        gw, _ = _gw(clock, pol, stall_threshold_s=1e9)
        eng = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(1)
        gw.submit([1], 2)
        gw.step()                                  # fail -> OPEN
        clock.advance(1.5)
        eng.stall(10 ** 6)                         # wedged: no tokens ever
        h = gw.submit([2], 2, ttft_deadline_s=2.0)
        gw.step()                                  # half-open probe
        assert h.status == "dispatched"
        clock.advance(3.0)
        gw.step()                                  # ttft expiry fires
        assert h.status == "expired"
        assert gw.breakers_open() == ["a"]         # re-opened, re-armed

    def test_quarantine_counts_as_breaker_failure(self):
        clock = SimClock()
        pol = ResiliencePolicy(breaker_failures=1, breaker_open_s=100.0,
                               hedge=False, brownout=False)
        gw, _ = _gw(clock, pol)
        gw.add_replica(SimEngine(max_slots=2, tracer=SimTracer(clock)),
                       "a")
        gw.quarantine("a", reason="operator")
        # the breaker opened, but a QUARANTINED replica is not a
        # scale-up signal (its missing capacity belongs to the
        # quarantine-reap/min-bound machinery — an open breaker on a
        # benched shell could never half-open and would page forever)
        assert gw.resilience_snapshot()["breakers"]["a"]["state"] == "open"
        assert gw.breakers_open() == []
        # reinstate returns it to rotation: NOW it counts, and the
        # breaker still gates dispatch until a half-open probe succeeds
        gw.reinstate("a")
        assert gw.replica("a").state == "active"
        assert gw.breakers_open() == ["a"]


class TestRetry:
    def test_budget_exhaustion_is_structured_terminal(self):
        clock = SimClock()
        pol = ResiliencePolicy(retry_budget=2, retry_backoff_s=0.1,
                               retry_jitter=0.0, breaker_failures=100,
                               hedge=False, brownout=False)
        gw, tracer = _gw(clock, pol)
        eng = SimEngine(max_slots=2, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(100)                            # never recovers
        sig = []
        h = gw.submit([1], 2, on_token=lambda g, t, d: sig.append((t, d)))
        for _ in range(40):
            gw.step()
            clock.advance(0.25)
            if h.done:
                break
        assert h.status == "failed"
        assert isinstance(h.error, RetriesExhausted)
        assert h.error.attempts == 3 and h.error.budget == 2
        assert h.retries == 2                     # never beyond budget
        assert sig == [(None, True)]              # terminal, never silent
        whats = [e["what"] for e in tracer.events("resilience")]
        assert whats.count("retry") == 2
        assert whats.count("retries_exhausted") == 1

    def test_backoff_is_exponential_capped_and_seeded(self):
        pol = ResiliencePolicy(retry_backoff_s=0.1, retry_backoff_max_s=0.5,
                               retry_jitter=0.0, seed=0)
        import random
        rng = random.Random(0)
        assert [pol.backoff_s(a, rng) for a in (1, 2, 3, 4, 5)] == \
            [0.1, 0.2, 0.4, 0.5, 0.5]
        # jitter draws come from the gateway's seeded RNG: same seed,
        # same schedule
        polj = ResiliencePolicy(retry_backoff_s=0.1, retry_jitter=0.5,
                                seed=7)
        a = [polj.backoff_s(i, random.Random(7)) for i in (1, 2)]
        b = [polj.backoff_s(i, random.Random(7)) for i in (1, 2)]
        assert a == b
        lo, hi = 0.1 * 0.5, 0.1 * 1.5
        assert lo <= polj.backoff_s(1, random.Random(1)) <= hi

    def test_backoff_defers_without_blocking_the_queue(self):
        """A backing-off request must not head-of-line block: requests
        behind it dispatch while it waits out ``not_before``."""
        clock = SimClock()
        pol = ResiliencePolicy(retry_budget=3, retry_backoff_s=5.0,
                               retry_jitter=0.0, breaker_failures=100,
                               hedge=False, brownout=False)
        gw, _ = _gw(clock, pol)
        eng = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(1)
        h_retry = gw.submit([1], 2)               # eats the flaky failure
        h_next = gw.submit([2], 2)
        gw.step()
        assert h_retry.status == "queued" and h_retry.not_before > clock()
        assert h_next.status in ("dispatched", "finished")
        _drive(gw, clock)
        assert h_retry.status == "finished" and h_next.status == "finished"


class TestHedge:
    def _straggler_fleet(self, clock, pol, factor=40):
        gw, tracer = _gw(clock, pol)
        slow = SimEngine(max_slots=4, tracer=SimTracer(clock))
        fast = SimEngine(max_slots=4, tracer=SimTracer(clock))
        plan = FaultPlan([Fault("slow", at_s=0.0, factor=factor)])
        gw.add_replica(FaultyEngine(slow, plan, clock, replica="slow"),
                       "slow")
        gw.add_replica(fast, "fast")
        return gw, tracer, slow, fast

    def test_winner_token_exactness_loser_cancelled(self):
        """The hedge races a straggler: the fast replica's first token
        wins, the loser attempt is cancelled on its engine, and the
        consumer stream is exactly the oracle — no duplicates, no
        interleaving."""
        clock = SimClock()
        pol = ResiliencePolicy(hedge=True, hedge_ttft_frac=0.2,
                               max_hedges=4, brownout=False)
        gw, tracer, slow, fast = self._straggler_fleet(clock, pol)
        streams = {}
        h = gw.submit([9, 9], 6, ttft_deadline_s=5.0,
                      on_token=lambda g, t, d:
                      streams.setdefault(g, []).append((t, d)))
        _drive(gw, clock)
        assert h.status == "finished" and h.hedged
        assert h.replica == "fast"                # hedge won
        assert h.tokens == sim_tokens([9, 9], 6)
        toks = [t for t, d in streams[h.gid] if t is not None]
        assert toks == h.tokens                   # single-sourced stream
        assert streams[h.gid][-1] == (h.tokens[-1], True)
        assert slow.metrics()["requests_cancelled"] == 1    # the loser
        counters = gw.resilience_snapshot()["counters"]
        assert counters["hedges"] == 1 and counters["hedges_won"] == 1
        assert gw.resilience_snapshot()["hedges_inflight"] == 0
        whats = [e["what"] for e in tracer.events("resilience")]
        assert whats == ["hedge", "hedge_won"]

    def test_primary_win_counts_hedge_lost(self):
        """A hedge fired against a replica that delivers after all: the
        primary's token wins, the hedge attempt is the cancelled loser."""
        clock = SimClock()
        pol = ResiliencePolicy(hedge=True, hedge_ttft_frac=0.2,
                               max_hedges=4, brownout=False)
        # mild straggler: slower than the hedge trigger, faster than the
        # hedge's own queue+prefill on the other replica is NOT possible
        # in the sim (both serve next tick), so force the primary win by
        # making the hedge target slow instead
        gw, tracer = _gw(clock, pol)
        primary = SimEngine(max_slots=4, tracer=SimTracer(clock))
        laggard = SimEngine(max_slots=4, tracer=SimTracer(clock))
        plan = FaultPlan([Fault("slow", at_s=0.0, factor=13)])
        gw.add_replica(FaultyEngine(primary, plan, clock, replica="p"),
                       "p")
        gw.add_replica(FaultyEngine(laggard, plan, clock, replica="h"),
                       "h")
        h = gw.submit([4, 2], 3, ttft_deadline_s=4.0)
        _drive(gw, clock)
        assert h.status == "finished" and h.hedged
        assert h.tokens == sim_tokens([4, 2], 3)
        counters = gw.resilience_snapshot()["counters"]
        assert counters["hedges"] == 1
        assert counters.get("hedges_won", 0) + \
            counters.get("hedges_lost", 0) == 1

    def test_hedge_budget_bounds_concurrency(self):
        clock = SimClock()
        pol = ResiliencePolicy(hedge=True, hedge_ttft_frac=0.1,
                               max_hedges=1, brownout=False)
        gw, _, slow, fast = self._straggler_fleet(clock, pol, factor=400)
        hs = [gw.submit([i + 1], 4, ttft_deadline_s=8.0)
              for i in range(4)]
        peak = 0
        for _ in range(200):
            gw.step()
            peak = max(peak, gw.resilience_snapshot()["hedges_inflight"])
            clock.advance(0.25)
            if not gw.pending():
                break
        assert peak <= 1
        for h in hs:
            assert h.status == "finished"
            assert h.tokens == sim_tokens(h.prompt, 4)

    def test_no_hedge_without_ttft_deadline(self):
        clock = SimClock()
        pol = ResiliencePolicy(hedge=True, hedge_ttft_frac=0.1,
                               brownout=False)
        gw, _, slow, fast = self._straggler_fleet(clock, pol, factor=10)
        h = gw.submit([5], 3)                     # no deadline: no hedge
        _drive(gw, clock)
        assert h.status == "finished" and not h.hedged
        assert gw.resilience_snapshot()["counters"].get("hedges", 0) == 0

    def test_quarantined_primary_promotes_hedge_twin(self):
        """Quarantine hits the primary replica while a hedge is racing:
        only that attempt is dropped — the hedge twin carries the
        request to completion, no re-queue, no replay signal."""
        clock = SimClock()
        pol = ResiliencePolicy(hedge=True, hedge_ttft_frac=0.05,
                               max_hedges=4, brownout=False)
        gw, tracer = _gw(clock, pol, stall_threshold_s=4.0)
        plan = FaultPlan([Fault("crash", at_s=1.0, replica="dead")])
        dead = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(FaultyEngine(dead, plan, clock, replica="dead"),
                       "dead")
        fast = SimEngine(max_slots=4, tracer=SimTracer(clock))
        gw.add_replica(fast, "fast")
        # occupy fast so routing sends the victim to dead first
        fillers = [gw.submit([40 + i], 2) for i in range(2)]
        gw.step()
        victim = gw.submit([8, 8], 50, ttft_deadline_s=60.0)
        _drive(gw, clock)
        assert victim.status == "finished"
        assert victim.tokens == sim_tokens([8, 8], 50)
        for f in fillers:
            assert f.status == "finished"


class TestBrownout:
    def _pol(self, **kw):
        kw.setdefault("hedge", False)
        kw.setdefault("brownout", True)
        kw.setdefault("brownout_high", 2.0)
        kw.setdefault("brownout_low", 0.5)
        kw.setdefault("brownout_up_dwell_s", 0.0)
        kw.setdefault("brownout_down_dwell_s", 1.0)
        kw.setdefault("brownout_clamp", 3)
        kw.setdefault("brownout_use_slo", False)
        return ResiliencePolicy(**kw)

    def test_ladder_up_clamp_priority_shed_then_down(self):
        clock = SimClock()
        gw, tracer = _gw(clock, self._pol(), max_queue_depth=1000)
        gw.add_replica(SimEngine(max_slots=2), "a")
        hs = [gw.submit([i + 1], 9, priority=1) for i in range(12)]
        gw.step()                                  # pressure 6 -> clamp
        assert gw.brownout_level == 1
        gw.step()                                  # -> priority_only
        assert gw.brownout_level == 2
        low = gw.submit([50], 4, priority=1)
        hi = gw.submit([51], 4, priority=0)
        assert low.status == "shed"
        assert isinstance(low.error, Brownout)
        assert low.error.label == "priority_only" and low.error.level == 2
        assert hi.status == "queued"
        gw.step()                                  # -> shed_all
        assert gw.brownout_level == 3
        any_pri = gw.submit([52], 4, priority=0)
        assert any_pri.status == "shed"
        assert any_pri.error.label == "shed_all"
        # drain, keep stepping after idle: ladder walks back down
        _drive(gw, clock)
        for _ in range(40):
            gw.step()
            clock.advance(0.25)
        assert gw.brownout_level == 0
        # clamp pinned: every dispatched request got at most clamp tokens
        for h in hs:
            if h.status == "finished":
                assert len(h.tokens) <= 3
                assert h.tokens == sim_tokens(h.prompt, len(h.tokens))
        whats = [e["what"] for e in tracer.events("resilience")]
        assert whats.count("brownout_up") == 3
        assert whats.count("brownout_down") == 3

    def test_hysteresis_band_holds_no_flapping(self):
        """The ladder state machine pin: pressure parked INSIDE the
        (low, high) band neither climbs nor descends — however long it
        hovers — and dwell timers reset when pressure re-enters the
        band, so a value oscillating across one threshold cannot flap
        the rung."""
        from paddle_tpu.gateway import _BrownoutLadder
        lad = _BrownoutLadder(self._pol(brownout_high=2.0,
                                        brownout_low=0.5,
                                        brownout_up_dwell_s=0.0,
                                        brownout_down_dwell_s=1.0))
        assert lad.evaluate(0.0, 5.0, False) == +1      # climb
        assert lad.level == 1
        # hover inside the band for a long time: rung holds, forever
        for i in range(1, 200):
            assert lad.evaluate(float(i), 1.0, False) == 0
            assert lad.level == 1
        # oscillate across the LOW threshold: each re-entry into the
        # band resets the descend dwell, so the rung still holds
        t = 200.0
        for _ in range(20):
            assert lad.evaluate(t, 0.4, False) == 0     # below, dwell on
            assert lad.evaluate(t + 0.5, 1.0, False) == 0   # back in band
            t += 1.0
        assert lad.level == 1
        # sustained below-low finally descends after the dwell
        assert lad.evaluate(t, 0.4, False) == 0
        assert lad.evaluate(t + 1.1, 0.4, False) == -1
        assert lad.level == 0
        # and it never descends below the floor / climbs past the top
        assert lad.evaluate(t + 3.0, 0.0, False) == 0
        for i in range(10):
            lad.evaluate(t + 4.0 + i, 99.0, False)
        assert lad.level == len(BROWNOUT_LEVELS) - 1
        assert lad.evaluate(t + 30.0, 99.0, False) == 0

    def test_slo_firing_climbs_ladder(self):
        class FiringSLO:
            def alert_states(self):
                return {"ttft_p99": "firing"}

            def count(self, *_a, **_k):
                pass

            def observe(self, *_a, **_k):
                pass
        clock = SimClock()
        gw, _ = _gw(clock, self._pol(brownout_use_slo=True,
                                     brownout_up_dwell_s=0.5))
        gw.add_replica(SimEngine(max_slots=2), "a")
        gw.set_slo(FiringSLO())
        gw.step()                     # dwell starts (occupancy is 0!)
        assert gw.brownout_level == 0
        clock.advance(1.0)
        gw.step()
        assert gw.brownout_level == 1


class TestStepIsolation:
    def test_raising_engine_quarantined_others_untouched(self):
        """The satellite regression: an engine raising mid-tick must
        quarantine THAT replica and replay its in-flight work — the
        other replica's requests in the same gateway tick proceed."""
        clock = SimClock()
        tracer = SimTracer(clock, capacity=8192)
        gw = ServingGateway(clock=clock, tracer=tracer,
                            stall_threshold_s=60.0)   # resilience OFF
        plan = FaultPlan([Fault("garble", at_s=0.0, count=1)])
        bad = SimEngine(max_slots=2, tracer=SimTracer(clock))
        ok = SimEngine(max_slots=2, tracer=SimTracer(clock))
        gw.add_replica(FaultyEngine(bad, plan, clock, replica="bad"),
                       "bad")
        gw.add_replica(ok, "ok")
        hs = [gw.submit([i + 3, 1], 6) for i in range(4)]
        for _ in range(100):
            gw.step()                 # must never raise
            clock.advance(0.25)
            if not gw.pending():
                break
        assert gw.replica("bad").state == "quarantined"
        assert "step raised" in gw.replica("bad").reason
        for h in hs:
            assert h.status == "finished"
            assert h.tokens == sim_tokens(h.prompt, 6)
        assert gw.metrics()["step_errors"] == 1
        assert any(e["what"] == "replica_step_error"
                   for e in tracer.events("gateway"))

    def test_serving_engine_step_surfaces_errors(self):
        """serving.py satellite: a raising _step_impl ticks the
        step_errors counter and emits an engine_error event before the
        exception propagates."""
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTModel
        from paddle_tpu.serving import ContinuousBatchingEngine
        from paddle_tpu.telemetry import Tracer
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_attention_heads=2, max_position_embeddings=64,
                        compute_dtype="float32")
        model = GPTModel(cfg)
        params = {n: p._data for n, p in model.named_parameters()}
        tr = Tracer()
        eng = ContinuousBatchingEngine(model, params, max_slots=2,
                                       max_len=32, prompt_buckets=[8],
                                       tracer=tr)
        eng._step_impl = lambda: (_ for _ in ()).throw(
            RuntimeError("boom"))
        with pytest.raises(RuntimeError):
            eng.step()
        assert eng.metrics()["step_errors"] == 1
        assert "step_errors" in type(eng).metrics_schema()
        evs = tr.events("engine_error")
        assert evs and evs[-1]["what"] == "step_error"
        assert "boom" in evs[-1]["error"]


class TestAutoscalerBreakerSignal:
    def test_breaker_open_drives_scale_up(self):
        clock = SimClock()
        tracer = SimTracer(clock, capacity=8192)
        pol = ResiliencePolicy(breaker_failures=2, breaker_open_s=100.0,
                               retry_budget=5, retry_backoff_s=0.0,
                               retry_jitter=0.0, hedge=False,
                               brownout=False)
        gw = ServingGateway(clock=clock, tracer=tracer, resilience=pol)
        eng = SimEngine(max_slots=2, tracer=SimTracer(clock))
        gw.add_replica(eng, "r0")
        eng.flaky(50)
        asc = ElasticAutoscaler(
            gw, lambda: SimEngine(max_slots=2, tracer=SimTracer(clock)),
            min_replicas=1, max_replicas=3, scale_up_cooldown_s=1.0,
            tracer=tracer, clock=clock)
        h = gw.submit([4, 4], 3)
        for _ in range(30):
            gw.step()
            asc.evaluate()
            clock.advance(0.25)
            if h.done:
                break
        assert h.status == "finished"             # served by the spawn
        ups = [d for d in asc.decisions() if d["action"] == "scale_up"]
        assert ups and "breaker:r0" in ups[0]["reason"]
        assert asc.breakers_open() == ["r0"]
        snap = asc.autoscaler_snapshot()
        assert snap["signals"]["breakers_open"] == ["r0"]

    def test_gateway_without_resilience_reports_no_breakers(self):
        clock = SimClock()
        gw = ServingGateway(clock=clock)
        asc = ElasticAutoscaler(gw, lambda: SimEngine(), clock=clock)
        assert asc.breakers_open() == []


class TestChaosAcceptance:
    def test_seeded_plan_pin(self):
        """The ISSUE 12 acceptance pin: the same steady load and the same
        seeded fault plan — a 40x slow straggler, a replica crash
        mid-burst, a transient dispatch-error window, a stall — against
        resilience off and on.  On both sides every admitted request
        reaches a terminal outcome and every finished stream is an exact
        oracle prefix; on the resilient side retries stay within budget
        and at least as much of the load finishes.  The p99 TTFT compared
        is SIMULATED seconds on the injected clock: a property of the
        failure-response policy, not a timing of any machine."""
        rate, horizon, dt, seed = 2.0, 120.0, 0.25, 0
        plan = FaultPlan([
            Fault("slow", at_s=20.0, duration_s=40.0, factor=40,
                  replica="r0"),
            Fault("crash", at_s=30.0, replica="r1"),
            Fault("dispatch_error", at_s=45.0, duration_s=6.0,
                  replica="r2"),
            Fault("stall", at_s=70.0, duration_s=12.0, replica="r2"),
        ], seed=7)
        pol = ResiliencePolicy(
            retry_budget=3, retry_backoff_s=0.25, retry_backoff_max_s=2.0,
            retry_jitter=0.5, seed=seed, breaker_failures=3,
            breaker_open_s=2.5, hedge=True, hedge_ttft_frac=0.05,
            max_hedges=8, brownout=True, brownout_high=3.0,
            brownout_low=1.0, brownout_down_dwell_s=5.0, brownout_clamp=6,
            brownout_use_slo=False)

        def run(policy):
            clock = SimClock()
            gw, tracer = _gw(clock, policy, stall_threshold_s=4.0,
                             max_queue_depth=256)
            wrappers = []
            for i in range(3):
                w = FaultyEngine(
                    SimEngine(max_slots=8, tracer=SimTracer(clock)), plan,
                    clock, replica=f"r{i}")
                wrappers.append(w)
                gw.add_replica(w, f"r{i}")
            sim = TrafficSim(gw, clock, steady(rate), dt=dt, seed=seed,
                             ttft_deadline_s=60.0)
            rep = sim.run(horizon)
            assert not rep["dropped"], rep["dropped"]
            assert sum(rep["outcomes"].values()) == rep["offered"]
            for h in sim.handles:
                if h.status == "finished":
                    assert h.tokens == sim_tokens(h.prompt, len(h.tokens)), \
                        (h.gid, h.tokens)
            assert any(w.injected() for w in wrappers)  # the plan fired
            return rep, sim, gw, tracer

        off, _, _, _ = run(None)
        on, sim, gw, tracer = run(pol)
        assert off["offered"] == on["offered"]
        assert all(h.retries <= pol.retry_budget for h in sim.handles)
        assert on["ttft_s"]["p99"] < off["ttft_s"]["p99"]   # simulated s
        assert on["outcomes"]["finished"] >= off["outcomes"]["finished"]
        counters = gw.resilience_snapshot()["counters"]
        assert counters.get("retries_exhausted", 0) <= 1
        assert tracer.events("resilience")         # the decision timeline
        assert len(plan.to_dict()["faults"]) == 4  # the plan serializes


class TestObservability:
    def test_ops_resilience_route_and_404(self):
        from paddle_tpu.ops_server import OpsServer
        clock = SimClock()
        pol = ResiliencePolicy(brownout=False, hedge=False)
        gw, _ = _gw(clock, pol)
        gw.add_replica(SimEngine(max_slots=2), "a")
        srv = OpsServer()
        srv.attach(gw, "gw")
        url = srv.start()
        try:
            snap = json.loads(urllib.request.urlopen(
                url + "/resilience", timeout=10).read())
            assert snap["breakers"]["a"]["state"] == "closed"
            assert snap["policy"]["retry_budget"] == pol.retry_budget
            txt = urllib.request.urlopen(url + "/metrics",
                                         timeout=10).read().decode()
            assert "paddle_tpu_resilience_brownout_level 0" in txt
            assert "paddle_tpu_resilience_breakers_open 0" in txt
        finally:
            srv.stop()
        # a gateway WITHOUT a policy: /resilience is 404, not a lie
        srv2 = OpsServer()
        srv2.attach(ServingGateway(clock=clock), "bare")
        url2 = srv2.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url2 + "/resilience", timeout=10)
            assert ei.value.code == 404
        finally:
            srv2.stop()

    def test_flight_recorder_dumps_breaker_brownout_state(self, tmp_path):
        from paddle_tpu.telemetry_ledger import FlightRecorder
        clock = SimClock()
        pol = ResiliencePolicy(brownout=True, hedge=False)
        gw, _ = _gw(clock, pol)
        gw.add_replica(SimEngine(max_slots=2), "a")
        fr = FlightRecorder(str(tmp_path)).add_source(gw, "gateway")
        out = fr.dump("test")
        data = json.load(open(f"{out}/gateway.json"))
        res = data["resilience"]
        assert res["breakers"]["a"]["state"] == "closed"
        assert res["brownout"]["label"] == "normal"

    def test_retry_hedge_events_carry_trace_ids(self):
        """Resilience events for a traced request carry enough identity
        (gid) to join the request's stitched trace."""
        clock = SimClock()
        pol = ResiliencePolicy(retry_budget=2, retry_backoff_s=0.0,
                               retry_jitter=0.0, breaker_failures=100,
                               hedge=False, brownout=False)
        gw, tracer = _gw(clock, pol)
        eng = SimEngine(max_slots=2, tracer=SimTracer(clock))
        gw.add_replica(eng, "a")
        eng.flaky(1)
        h = gw.submit([1], 2)
        _drive(gw, clock)
        retries = [e for e in tracer.events("resilience")
                   if e["what"] == "retry"]
        assert retries and retries[0]["gid"] == h.gid


class TestOffPathPurity:
    @pytest.fixture(scope="class")
    def model_and_params(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTModel
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_position_embeddings=96,
                        compute_dtype="float32")
        model = GPTModel(cfg)
        params = {n: p._data for n, p in model.named_parameters()}
        return model, params

    def test_resilience_at_defaults_changes_no_programs_or_outputs(
            self, model_and_params):
        """The off-path purity pin: the same workload through a gateway
        with a ResiliencePolicy attached (no faults injected) and one
        without produces token-identical outputs from an IDENTICAL
        program-cache key population — resilience is host-side control
        flow only."""
        from paddle_tpu.serving import PagedContinuousBatchingEngine
        model, params = model_and_params
        prompts = [([5, 17, 3], 8), ([40, 2], 6), ([61], 5)]

        def run(pol):
            model.__dict__.pop("_serving_programs", None)
            eng = PagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                prompt_buckets=[8, 16])
            clock = SimClock()
            gw = ServingGateway(clock=clock, resilience=pol)
            gw.add_replica(eng, "a")
            handles = [gw.submit(p, n, ttft_deadline_s=1e9)
                       for p, n in prompts]
            for _ in range(300):
                gw.step()
                clock.advance(0.01)
                if not gw.pending():
                    break
            keys = set(model.__dict__["_serving_programs"])
            return [tuple(h.tokens) for h in handles], keys

        toks_off, keys_off = run(None)
        toks_on, keys_on = run(ResiliencePolicy())
        assert toks_on == toks_off
        assert keys_on == keys_off
        assert all(t for t in toks_on)
