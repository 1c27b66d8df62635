"""Elastic autoscaler (paddle_tpu/autoscaler.py, ISSUE 11): closed-loop
SLO-driven fleet scaling over the fake-clock simulation harness.

Every scenario runs a REAL ServingGateway + real SLOMonitor + real
ElasticAutoscaler against fake-timed SimEngines on one injected clock —
whole scale-up/scale-down trajectories are deterministic CPU tests: the
flash-crowd acceptance loop (SLO fires → spawn + warm + activate with
zero in-serve compiles → resolve → idle drains back to min), sustained-
idle scale-down, replica death mid-burst, diurnal load tracking, fleet
bounds, per-direction cooldowns, hysteresis no-flap at the idle
boundary, the expected-compiles grid registration on spawned replicas,
and the GET /autoscaler ops view.  Zero dropped requests is asserted
across every transition."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from paddle_tpu.autoscaler import DECISIONS, ElasticAutoscaler
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.simulation import (SimClock, SimEngine, SimTracer,
                                   TrafficSim, diurnal, flash_crowd,
                                   sim_tokens, steady)
from paddle_tpu.telemetry_slo import Objective, SLOMonitor


def _slo(clock, tracer=None, ttft_target=2.0):
    return SLOMonitor([
        Objective.latency("ttft_p99", "ttft_s", ttft_target,
                          compliance=0.9, windows=(30.0, 10.0),
                          burn_threshold=1.0, for_s=2.0, clear_s=10.0),
        Objective.ratio("shed_rate", "shed", "submitted", 0.05,
                        windows=(30.0, 10.0), burn_threshold=1.0,
                        for_s=2.0, clear_s=10.0),
    ], clock=clock, resolution_s=1.0, tracer=tracer)


class _Fleet:
    """One wired-up closed loop: gateway + SLO + autoscaler + the list of
    every factory-spawned engine (for post-hoc compile accounting)."""

    def __init__(self, clock, *, replicas=1, with_slo=True,
                 stall_threshold_s=30.0, max_queue_depth=64,
                 warmup_unsupported=False, **asc_kw):
        self.clock = clock
        self.tracer = SimTracer(clock, capacity=16384)
        self.gw = ServingGateway(clock=clock, tracer=self.tracer,
                                 stall_threshold_s=stall_threshold_s,
                                 max_queue_depth=max_queue_depth)
        self.spawned = []

        def factory():
            eng = SimEngine(max_slots=4, tracer=SimTracer(clock),
                            warmup_unsupported=warmup_unsupported)
            self.spawned.append(eng)
            return eng

        self.factory = factory
        for i in range(replicas):
            eng = SimEngine(max_slots=4, tracer=SimTracer(clock))
            eng.warmup()
            self.gw.add_replica(eng, f"r{i}")
        self.slo = _slo(clock, tracer=self.tracer) if with_slo else None
        if self.slo is not None:
            self.gw.set_slo(self.slo)
        kw = dict(min_replicas=1, max_replicas=4,
                  scale_up_cooldown_s=5.0, scale_down_cooldown_s=15.0,
                  idle_utilization=0.2, idle_dwell_s=20.0,
                  tracer=self.tracer, clock=clock)
        kw.update(asc_kw)
        self.asc = ElasticAutoscaler(self.gw, factory, slo=self.slo,
                                     **kw)


class TestFlashCrowdAcceptance:
    def test_closed_loop_end_to_end(self):
        """The acceptance scenario: TTFT SLO fires → replica spawned +
        AOT-warmed + activated (ZERO in-serve compiles on every spawned
        replica) → alert resolves → sustained idle drains the fleet back
        to min size — zero dropped requests, fleet bounds respected on
        every timeline sample, and the full decision timeline visible
        via GET /autoscaler and tracer ``autoscale`` events."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)
        sim = TrafficSim(fl.gw, clk,
                         flash_crowd(0.02, 8.0, 20.0, 30.0),
                         dt=0.25, seed=0, autoscaler=fl.asc)
        rep = sim.run(240.0)

        # --- zero drops across every transition, nothing unaccounted
        assert rep["dropped"] == []
        assert sum(rep["outcomes"].values()) == rep["offered"]
        finished = [h for h in sim.handles if h.status == "finished"]
        for h in finished:
            assert h.tokens == sim_tokens(h.prompt, h.max_new_tokens)

        # --- the loop actually closed: fired → scaled up → resolved
        actions = [d["action"] for d in rep["decisions"]]
        assert "scale_up" in actions and "activate" in actions
        assert "scale_down" in actions and "removed" in actions
        ups = [d for d in rep["decisions"] if d["action"] == "scale_up"]
        assert all(d["reason"].startswith("slo:") for d in ups)
        whats = [t["what"] for t in fl.slo.snapshot()["transitions"]
                 if t["objective"] == "ttft_p99"]
        assert "firing" in whats and "resolved" in whats
        assert whats.index("firing") < whats.index("resolved")

        # --- spawned replicas were warmed BEFORE activation: zero
        # in-serve compiles on every one of them
        assert len(fl.spawned) >= 1
        for eng in fl.spawned:
            assert eng.warmed
            assert eng.in_serve_compiles == 0, eng.metrics()

        # --- bounds respected at every sample; back to min at the end
        assert all(1 <= s["active"] + s["draining"] <= 4
                   for s in rep["timeline"])
        assert max(s["active"] for s in rep["timeline"]) >= 2
        assert rep["fleet"]["active"] == 1          # drained back to min
        assert rep["fleet"]["pending_spawns"] == 0

        # --- decision timeline rides the tracer...
        ev = fl.tracer.events("autoscale")
        assert [e["what"] for e in ev] == actions
        assert all("fleet_active" in e for e in ev)

        # --- ...and GET /autoscaler serves it live
        from paddle_tpu.ops_server import OpsServer
        srv = OpsServer()
        srv.attach(fl.asc, "asc")
        url = srv.start()
        try:
            snap = json.loads(urllib.request.urlopen(
                url + "/autoscaler", timeout=10).read())
            assert [d["action"] for d in snap["decisions"]] == actions
            assert snap["fleet"]["active"] == 1
            assert snap["policy"]["min_replicas"] == 1
            assert snap["policy"]["max_replicas"] == 4
            txt = urllib.request.urlopen(url + "/metrics",
                                         timeout=10).read().decode()
            assert "paddle_tpu_autoscaler_fleet_size 1" in txt
            assert "paddle_tpu_autoscaler_scale_ups" in txt
        finally:
            srv.stop()

    def test_fixed_fleet_same_load_is_worse(self):
        """The same offered load on a fixed single-replica fleet sheds
        and tails out where the autoscaled fleet does not (p99 TTFT in
        SIMULATED seconds: a property of the scaling policy)."""
        def run(autoscaled):
            clk = SimClock()
            fl = _Fleet(clk, replicas=1)
            sim = TrafficSim(fl.gw, clk, flash_crowd(0.5, 8.0, 10.0, 20.0),
                             dt=0.25, seed=1,
                             autoscaler=fl.asc if autoscaled else None)
            return sim.run(90.0)
        fixed, auto = run(False), run(True)
        assert fixed["offered"] == auto["offered"]
        assert fixed["shed_rate"] > auto["shed_rate"]
        assert auto["ttft_s"]["p99"] < fixed["ttft_s"]["p99"]
        assert fixed["dropped"] == auto["dropped"] == []


class TestScaleUpPolicy:
    def _firing_fleet(self):
        """A fleet whose TTFT objective is made to fire by direct sample
        injection — policy unit tests without a traffic sim."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)
        for _ in range(50):
            fl.slo.observe("ttft_s", 10.0)      # way over the 2s target
        return clk, fl

    def test_one_spawn_per_decision_and_cooldown(self):
        clk, fl = self._firing_fleet()
        # dwell: pending → firing needs for_s=2 on the fake clock
        fl.asc.evaluate()
        clk.advance(3.0)
        made = fl.asc.evaluate()
        assert [d["action"] for d in made] == ["scale_up"]   # step limit
        made = fl.asc.evaluate()                 # same instant: cooldown
        assert [d["action"] for d in made] == ["activate"]
        clk.advance(2.0)                         # < 5s cooldown
        assert fl.asc.evaluate() == []
        clk.advance(4.0)                         # past cooldown
        made = fl.asc.evaluate()
        assert [d["action"] for d in made] == ["scale_up"]

    def test_max_bound_caps_fleet(self):
        clk, fl = self._firing_fleet()
        for _ in range(40):
            clk.advance(6.0)
            for _ in range(5):
                fl.slo.observe("ttft_s", 10.0)   # keep the alert burning
            fl.asc.evaluate()
        reps = fl.gw.replicas()
        assert sum(1 for r in reps if r.state == "active") == 4
        assert fl.asc.fleet_size() == 4
        ups = [d for d in fl.asc.decisions() if d["action"] == "scale_up"]
        assert len(ups) == 3                     # 1 seed + 3 spawned = max

    def test_spawn_failed_is_a_recorded_decision(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)

        def broken():
            raise RuntimeError("no capacity anywhere")
        asc = ElasticAutoscaler(fl.gw, broken, slo=fl.slo,
                                min_replicas=2, max_replicas=4,
                                clock=clk)
        made = asc.evaluate()                    # min-bound spawn attempt
        assert [d["action"] for d in made] == ["spawn_failed"]
        assert "no capacity" in made[0]["error"]
        assert asc.metrics()["spawn_failures"] == 1
        # the loop keeps running — further evaluates don't raise
        clk.advance(1.0)
        asc.evaluate()

    def test_spawn_failure_backoff_bounds_retries(self):
        """A persistently broken factory is retried once per
        scale_up_cooldown_s window, not once per evaluate() round — even
        on the otherwise cooldown-exempt min-bound path (the retry storm
        would otherwise flood the log and churn the decision history)."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)

        def broken():
            raise RuntimeError("no capacity anywhere")
        asc = ElasticAutoscaler(fl.gw, broken, slo=fl.slo,
                                min_replicas=2, max_replicas=4,
                                scale_up_cooldown_s=30.0, clock=clk)
        assert [d["action"] for d in asc.evaluate()] == ["spawn_failed"]
        for _ in range(29):                      # inside the backoff
            clk.advance(1.0)
            assert asc.evaluate() == []
        clk.advance(2.0)                         # window elapsed → retry
        assert [d["action"] for d in asc.evaluate()] == ["spawn_failed"]
        assert asc.metrics()["spawn_failures"] == 2

    def test_factory_falls_back_to_gateway_registration(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)
        fl.gw.register_replica_factory(fl.factory)
        asc = ElasticAutoscaler(fl.gw, None, min_replicas=2,
                                max_replicas=4, clock=clk)
        made = asc.evaluate()
        assert [d["action"] for d in made] == ["scale_up"]
        assert made[0]["reason"] == "min_bound"
        with pytest.raises(TypeError):
            fl.gw.register_replica_factory("not callable")

    def test_warm_async_future_defers_activation(self):
        clk = SimClock()

        class SlowWarmFuture:
            def __init__(self):
                self.ready_at = clk() + 10.0

            def done(self):
                return clk() >= self.ready_at

            def result(self):
                return {"programs": 3, "wall_s": 10.0}

        class SlowWarmEngine(SimEngine):
            def warmup(self, cache_dir=None, max_workers=1, block=True):
                if block:
                    return super().warmup(cache_dir=cache_dir)
                super().warmup(cache_dir=cache_dir)
                return SlowWarmFuture()

        gw = ServingGateway(clock=clk, tracer=SimTracer(clk))
        seed = SimEngine(max_slots=4)
        seed.warmup()
        gw.add_replica(seed, "r0")
        asc = ElasticAutoscaler(gw, lambda: SlowWarmEngine(max_slots=4),
                                min_replicas=2, max_replicas=4,
                                warm_async=True, clock=clk)
        made = asc.evaluate()
        assert [d["action"] for d in made] == ["scale_up"]
        assert made[0]["pending"] is True
        assert asc.metrics()["pending_spawns"] == 1
        clk.advance(5.0)
        assert asc.evaluate() == []              # future not done yet
        assert len(gw.replicas()) == 1
        clk.advance(6.0)
        made = asc.evaluate()
        assert [d["action"] for d in made] == ["activate"]
        assert made[0]["spawn_wait_s"] == pytest.approx(11.0)
        assert len(gw.replicas()) == 2


class TestScaleDownPolicy:
    def _idle_fleet(self, replicas=3, **asc_kw):
        clk = SimClock()
        fl = _Fleet(clk, replicas=replicas, with_slo=False, **asc_kw)
        return clk, fl

    def test_sustained_idle_drains_to_min_never_below(self):
        clk, fl = self._idle_fleet(3)
        for _ in range(400):
            clk.advance(1.0)
            fl.gw.step()
            fl.asc.evaluate()
        downs = [d for d in fl.asc.decisions()
                 if d["action"] == "scale_down"]
        assert len(downs) == 2                   # 3 → 1, never below min
        reps = fl.gw.replicas()
        assert len(reps) == 1                    # stopped shells removed
        assert reps[0].state == "active"
        # spacing respects dwell + down-cooldown on the fake clock
        assert downs[1]["ts"] - downs[0]["ts"] >= 20.0

    def test_scale_down_picks_least_loaded_and_finishes_inflight(self):
        clk, fl = self._idle_fleet(3, idle_dwell_s=5.0,
                                   scale_down_cooldown_s=5.0)
        # one long request occupies r0: occupancy 1/12 < 0.2 is still
        # idle, but the victim must be an EMPTY replica, and the
        # in-flight request must finish untouched
        h = fl.gw.submit([1, 2, 3], 40)
        fl.gw.step()
        busy = h.replica
        for _ in range(70):
            clk.advance(1.0)
            fl.gw.step()
            fl.asc.evaluate()
        downs = [d for d in fl.asc.decisions()
                 if d["action"] == "scale_down"]
        assert downs and downs[0]["replica"] != busy
        assert h.status == "finished"
        assert h.tokens == sim_tokens([1, 2, 3], 40)

    def test_recent_scale_up_blocks_scale_down(self):
        """The never-tear-down-what-you-just-added rule: a fresh spawn
        re-arms the scale-down cooldown even under instant idle."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1, idle_dwell_s=2.0,
                    scale_down_cooldown_s=30.0, min_replicas=1)
        # force a min-bound spawn by starting a second autoscaler with
        # min_replicas=2, then reuse its clock state: simpler — drive a
        # spawn through firing SLO
        for _ in range(50):
            fl.slo.observe("ttft_s", 10.0)
        fl.asc.evaluate()
        clk.advance(3.0)
        fl.asc.evaluate()                        # scale_up at t=3
        fl.asc.evaluate()                        # activate
        # let the alert clear: samples age out of the 30s windows
        clk.advance(25.0)                        # t=28: up was at t=3
        for _ in range(10):
            clk.advance(1.0)
            fl.asc.evaluate()
        # t=38: idle dwell long satisfied, but 38 - 3 = 35 >= 30 only
        # now; before t=33 no scale_down may have happened
        downs = [d for d in fl.asc.decisions()
                 if d["action"] == "scale_down"]
        assert all(d["ts"] - 3.0 >= 30.0 for d in downs)

    def test_hysteresis_band_no_flapping(self):
        """Occupancy hovering at the idle threshold cannot flap: inside
        the band [thresh, thresh*resume) a running dwell keeps running
        but a new one never starts; only a clear bounce above the band
        resets — mirroring the SLO engine's resolve hysteresis."""
        clk, fl = self._idle_fleet(3, idle_dwell_s=10.0,
                                   scale_down_cooldown_s=5.0,
                                   idle_utilization=0.2,
                                   idle_resume_ratio=1.5)
        asc = fl.asc
        occ = {"v": 0.2}
        real_util = asc.utilization

        def fake_util():
            out = real_util()
            out["occupancy"] = occ["v"]
            return out
        asc.utilization = fake_util
        # AT the threshold: never starts a dwell, never decides
        for _ in range(30):
            clk.advance(1.0)
            assert asc.evaluate() == []
        assert asc._idle_since is None
        # below: dwell starts
        occ["v"] = 0.19
        asc.evaluate()
        started = asc._idle_since
        assert started is not None
        # bounce INTO the band (0.2 <= occ < 0.3): dwell keeps running
        occ["v"] = 0.29
        clk.advance(1.0)
        asc.evaluate()
        assert asc._idle_since == started        # not reset — no flap
        # clear bounce ABOVE the band: dwell resets
        occ["v"] = 0.31
        clk.advance(1.0)
        asc.evaluate()
        assert asc._idle_since is None
        # sustained below → exactly one decision after the dwell
        occ["v"] = 0.1
        made = []
        for _ in range(12):
            clk.advance(1.0)
            made.extend(asc.evaluate())
        acts = [d["action"] for d in made]
        assert acts.count("scale_down") == 1     # one decision, no flap
        assert set(acts) <= {"scale_down", "removed"}


class TestReplicaDeath:
    def test_death_mid_burst_is_replaced_and_recovers(self):
        """Replica death during a flash crowd: the gateway quarantines
        the stalled replica on the fake clock, its in-flight work
        replays elsewhere, and the autoscaler back-fills the lost
        capacity — zero drops, oracle streams, bounds held."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=2, stall_threshold_s=3.0,
                    min_replicas=2, max_replicas=4)
        sim = TrafficSim(fl.gw, clk, flash_crowd(0.05, 6.0, 10.0, 20.0),
                         dt=0.25, seed=4, autoscaler=fl.asc)
        sim.at(15.0, fl.gw.replica("r0").engine.kill, "kill r0")
        rep = sim.run(120.0)
        assert rep["injections_fired"] == ["kill r0"]
        # the quarantined shell was reaped: drained (no in-flight — the
        # quarantine already rerouted it) and removed, so a long-lived
        # elastic fleet doesn't grow one dead entry per death
        assert "r0" not in [r.name for r in fl.gw.replicas()]
        acts = [d["action"] for d in fl.asc.decisions()]
        assert "reap" in acts and "removed" in acts
        assert rep["dropped"] == []
        assert rep["outcomes"].get("finished", 0) > 0
        for h in sim.handles:
            if h.status == "finished":
                assert h.tokens == sim_tokens(h.prompt, h.max_new_tokens)
        # lost capacity was back-filled: active never ends below min
        assert rep["fleet"]["active"] >= 2
        assert all(s["active"] + s["draining"] <= 4
                   for s in rep["timeline"])

    def test_min_bound_replacement_ignores_cooldown(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=2, min_replicas=2, max_replicas=4,
                    scale_up_cooldown_s=1000.0, with_slo=False)
        fl.gw.submit([1], 4)                     # work → stall detectable
        fl.gw.step()
        assert fl.asc.evaluate() == []           # healthy: nothing to do
        fl.gw.quarantine("r0")
        made = fl.asc.evaluate()
        # one round: the benched shell is reaped (drain → remove) AND the
        # min-bound back-fill spawns, cooldown notwithstanding
        assert [d["action"] for d in made] == ["reap", "removed",
                                               "scale_up"]
        assert made[0]["replica"] == "r0"
        assert made[-1]["reason"] == "min_bound"
        assert "r0" not in [r.name for r in fl.gw.replicas()]

    def test_reap_disabled_keeps_shell_for_reinstate(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=2, min_replicas=1, max_replicas=4,
                    with_slo=False, reap_quarantined=False)
        fl.gw.quarantine("r0")
        assert fl.asc.evaluate() == []
        assert fl.gw.replica("r0").state == "quarantined"
        fl.gw.reinstate("r0")                    # operator path preserved
        assert fl.gw.replica("r0").state == "active"


class TestDiurnal:
    def test_fleet_tracks_the_sinusoid(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=1, idle_dwell_s=15.0,
                    scale_down_cooldown_s=10.0, scale_up_cooldown_s=4.0)
        sim = TrafficSim(fl.gw, clk, diurnal(0.05, 10.0, 120.0),
                         dt=0.25, seed=6, autoscaler=fl.asc,
                         sample_every_s=2.0)
        rep = sim.run(300.0)                     # 2.5 periods
        assert rep["dropped"] == []
        peak = max(s["active"] for s in rep["timeline"])
        assert peak >= 2                         # grew into the peak
        assert all(1 <= s["active"] + s["draining"] <= 4
                   for s in rep["timeline"])
        # shrank again after a peak (the trough between diurnal peaks is
        # short relative to resolve + dwell + cooldown, so full return
        # to min is the flash-crowd test's job — here the fleet must
        # demonstrably track DOWN as well as up)
        t_peak = next(s["t"] for s in rep["timeline"]
                      if s["active"] == peak)
        assert any(s["active"] < peak for s in rep["timeline"]
                   if s["t"] > t_peak)
        assert any(d["action"] == "scale_down"
                   for d in rep["decisions"])


class TestExpectedCompileWindow:
    def test_unwarmable_replica_grid_registered(self):
        """A spawned replica whose engine cannot warm (TP/mesh shape)
        still activates, and its warmup grid is registered on its tracer
        via a held-open expected_compiles window: first-dispatch misses
        are tagged expected and never arm the recompile-storm warning."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1, warmup_unsupported=True,
                    min_replicas=2, with_slo=False)
        fl.asc.evaluate()                        # min-bound spawn
        made = fl.asc.evaluate()
        assert [d["action"] for d in made] == ["activate"]
        assert made[0]["warmed"] is False
        eng = fl.spawned[0]
        eng.tracer.recompile_warn_threshold = 1  # hair trigger
        # serve through the new replica: route there by loading r0
        for _ in range(6):
            fl.gw.submit([1, 2], 3)
        for _ in range(20):
            clk.advance(0.25)
            fl.gw.step()
        misses = [e for e in eng.tracer.events("compile")
                  if not e["hit"]]
        assert misses, "the unwarmed replica must have compiled"
        assert all(e["expected"] for e in misses)
        assert not eng.tracer._warned_storm
        assert eng.in_serve_compiles > 0         # honest engine-side count

    def test_window_closes_on_drain_and_close(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=1, warmup_unsupported=True,
                    min_replicas=2, with_slo=False,
                    idle_dwell_s=5.0, scale_down_cooldown_s=5.0)
        fl.asc.evaluate()
        fl.asc.evaluate()                        # activate
        eng = fl.spawned[0]
        assert eng.tracer._warmup_depth == 1     # window held open
        fl.asc.min_replicas = 1                  # now it may drain
        for _ in range(30):
            clk.advance(1.0)
            fl.gw.step()
            fl.asc.evaluate()
        # one of the two replicas was drained; if it was the spawned one
        # its window is closed — force the other case through close()
        fl.asc.close()
        assert eng.tracer._warmup_depth == 0
        # close() is idempotent and detaches evaluate()
        fl.asc.close()
        assert fl.asc.evaluate() == []


class TestObservability:
    def test_snapshot_prometheus_and_ops_404(self):
        clk = SimClock()
        fl = _Fleet(clk, replicas=2, with_slo=True)
        snap = fl.asc.autoscaler_snapshot()
        assert snap["policy"]["min_replicas"] == 1
        assert snap["fleet"]["active"] == 2
        assert snap["signals"]["firing"] == []
        assert snap["signals"]["utilization"]["total_slots"] == 8
        assert snap["last_decision"] == "none"
        prom = fl.asc.prometheus_text()
        assert "paddle_tpu_autoscaler_fleet_size 2" in prom
        assert "paddle_tpu_autoscaler_pending_spawns 0" in prom
        assert "paddle_tpu_autoscaler_last_decision 0" in prom
        assert DECISIONS[0] == "none"
        from paddle_tpu.ops_server import OpsServer
        srv = OpsServer()
        url = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + "/autoscaler", timeout=10)
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_scrape_races_decision_churn_guard_clean(self, lock_sanitizer):
        """Regression for the unlocked ``_pending``/``_decisions``/
        ``_firing`` reads: ``decisions()`` used to iterate the deque bare
        while ``_record`` appended from the evaluate path (a RuntimeError
        on a real ops thread), and ``metrics()`` read ``len(_firing)``
        outside the lock the class itself documents.  The sanitizer
        harvests the ``# guarded-by:`` declarations straight from the
        source, so EVERY access — scrape thread or evaluate path — must
        now hold the declared lock or this test fails at teardown."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1, scale_up_cooldown_s=0.0)
        asc = fl.asc
        wired = lock_sanitizer.instrument_guards(asc)
        assert ("_pending", "_state_lock") in wired
        assert ("_decisions", "_state_lock") in wired
        assert lock_sanitizer.guard(asc, "_firing", "_firing_lock")
        errors, stop = [], threading.Event()

        def scrape():
            try:
                while not stop.is_set():
                    asc.decisions()
                    asc.metrics()
                    asc.autoscaler_snapshot()
                    asc.prometheus_text()
                    asc.firing()
                    asc.fleet_size()
            except Exception as e:  # noqa: BLE001 — repro harness
                errors.append(e)

        threads = [threading.Thread(target=scrape, name=f"scrape{i}")
                   for i in range(3)]
        for t in threads:
            t.start()
        try:
            sim = TrafficSim(fl.gw, clk, flash_crowd(2.0, 30.0, 2.0, 10.0),
                             autoscaler=asc)
            sim.run(30.0)                 # spawn + activate churn
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors
        assert asc.decisions()            # churn actually happened

    def test_slo_subscription_seeds_from_firing_state(self):
        """An autoscaler attached mid-incident sees the already-firing
        alert (alert_states seeding) and unsubscribes on close()."""
        clk = SimClock()
        slo = _slo(clk)
        for _ in range(50):
            slo.observe("ttft_s", 10.0)
        slo.evaluate()
        clk.advance(3.0)
        slo.evaluate()
        assert slo.alert_states()["ttft_p99"] == "firing"
        gw = ServingGateway(clock=clk)
        eng = SimEngine()
        eng.warmup()
        gw.add_replica(eng, "r0")
        asc = ElasticAutoscaler(gw, lambda: SimEngine(), slo=slo,
                                clock=clk)
        assert asc.firing() == ["ttft_p99"]
        asc.close()
        assert slo.unsubscribe(asc._on_slo_transition) is False

    def test_watched_objectives_filter(self):
        clk = SimClock()
        slo = _slo(clk)
        gw = ServingGateway(clock=clk)
        eng = SimEngine()
        eng.warmup()
        gw.add_replica(eng, "r0")
        asc = ElasticAutoscaler(gw, lambda: SimEngine(), slo=slo,
                                objectives=("shed_rate",), clock=clk)
        for _ in range(50):
            slo.observe("ttft_s", 10.0)          # fires ttft_p99 only
        asc.evaluate()
        clk.advance(3.0)
        made = asc.evaluate()
        assert asc.firing() == []                # unwatched: no signal
        assert all(d["action"] != "scale_up" for d in made)


class TestGatewayPrimitives:
    def test_remove_replica_contract(self):
        clk = SimClock()
        gw = ServingGateway(clock=clk)
        eng = SimEngine()
        eng.warmup()
        gw.add_replica(eng, "a")
        with pytest.raises(ValueError):
            gw.remove_replica("a")               # active: refuse
        gw.drain("a")
        assert gw.is_drained("a")
        gw.remove_replica("a")
        with pytest.raises(KeyError):
            gw.replica("a")
        assert gw.metrics()["replicas_removed"] == 1
        # the name is reusable after removal
        eng2 = SimEngine()
        eng2.warmup()
        gw.add_replica(eng2, "a")
        assert gw.replica("a").state == "active"

    def test_firing_set_safe_under_cross_thread_transition_churn(self):
        """SLO transitions arrive on whatever thread drives
        slo.evaluate() — ops-server HTTP scrape threads included — so
        the subscriber callback must never tear the control loop's
        firing() read (an unlocked set raises 'Set changed size during
        iteration' out of evaluate() and kills the serving loop)."""
        clk = SimClock()
        fl = _Fleet(clk, replicas=1)
        stop = threading.Event()

        def churn():
            i = 0
            while not stop.is_set():
                fl.asc._on_slo_transition(
                    {"objective": f"o{i % 50}",
                     "what": "firing" if i % 2 == 0 else "resolved"})
                i += 1

        t = threading.Thread(target=churn)
        t.start()
        try:
            for _ in range(2000):
                fl.asc.firing()              # must never raise
        finally:
            stop.set()
            t.join()

    def test_slo_subscribe_hook_contract(self):
        """SLOMonitor.subscribe delivers every transition; a raising
        subscriber is isolated; unsubscribe stops delivery."""
        clk = SimClock()
        slo = _slo(clk)
        seen = []

        def boom(ev):
            raise RuntimeError("subscriber bug")
        slo.subscribe(boom)
        slo.subscribe(seen.append)
        with pytest.raises(TypeError):
            slo.subscribe("nope")
        for _ in range(50):
            slo.observe("ttft_s", 10.0)
        slo.evaluate()                           # pending (boom isolated)
        clk.advance(3.0)
        slo.evaluate()                           # firing
        whats = [e["what"] for e in seen]
        assert whats == ["pending", "firing"]
        assert all(e["objective"] == "ttft_p99" for e in seen)
        assert slo.unsubscribe(seen.append) is True
        assert slo.unsubscribe(seen.append) is False
