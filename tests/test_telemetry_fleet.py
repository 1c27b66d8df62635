"""Fleet observability plane (paddle_tpu/telemetry_fleet.py, ISSUE 19):
cross-process telemetry federation, the durable metric spool, and the
fleet rollups.

The acceptance pins run entirely on a fake clock: a collector over >= 3
mixed targets whose rollups match hand-computed merges (global goodput
from summed ledger seconds, fleet TTFT p99 from an independently built
PercentileSketch merge), a killed target flipping to ``stale`` within
the window WITHOUT corrupting the surviving rollups, the spool surviving
a simulated crash with no duplicate and no lost durable samples, and
``GET /fleet`` + ``tools/fleet_top.py`` rendering the SAME snapshot.
The emitter/parser drift guard round-trips every Prometheus emitter
family in the tree through the collector's own parser, and the off-path
purity pin shows engine lowerings are byte-identical with a collector
scraping the process vs. none attached."""

import importlib.util
import json
import os
import pathlib
import urllib.error
import urllib.request

import pytest

from paddle_tpu.autoscaler import ElasticAutoscaler
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.simulation import (SimClock, SimEngine, SimFleetHost,
                                   SimTracer, build_sim_fleet)
from paddle_tpu.telemetry_fleet import (FleetCollector, ParsedSample,
                                        TelemetrySpool,
                                        parse_prometheus_text,
                                        render_sample, replay_regressions)
from paddle_tpu.telemetry_ledger import FlightRecorder, RunLedger
from paddle_tpu.telemetry_memory import MemoryLedger
from paddle_tpu.telemetry_slo import (Objective, PercentileSketch,
                                      SLOMonitor)
from paddle_tpu.utils.stats import (StatRegistry, prom_sample,
                                    prometheus_text)

_TOOLS = pathlib.Path(__file__).parent.parent / "tools"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _fetch_target(metrics_text, extra=None):
    """A ``fetch(path)`` transport over canned payloads — the fake-clock
    harness the module docstring names."""
    extra = dict(extra or {})

    def fetch(path):
        if path == "/metrics":
            return metrics_text
        return extra.get(path)

    return fetch


def _ledger_payload(compute_s, elapsed_s):
    return {"goodput": compute_s / elapsed_s, "elapsed_s": elapsed_s,
            "buckets_s": {"compute": compute_s}}


# ---------------------------------------------------------------------------
# the Prometheus parser
# ---------------------------------------------------------------------------

class TestPrometheusParser:
    def test_names_labels_values_and_types(self):
        text = ("# HELP x_total ignored\n"
                "# TYPE x_total counter\n"
                "x_total 3\n"
                'x_bucket{le="0.5",route="a"} 2\n'
                "y_gauge -0.25\n")
        parsed = parse_prometheus_text(text)
        assert parsed["errors"] == []
        assert parsed["types"] == {"x_total": "counter"}
        assert parsed["samples"] == [
            ParsedSample("x_total", {}, 3.0),
            ParsedSample("x_bucket", {"le": "0.5", "route": "a"}, 2.0),
            ParsedSample("y_gauge", {}, -0.25)]

    def test_label_escaping_round_trip(self):
        """The parser is the exact inverse of ``prom_escape_label`` —
        backslashes, quotes, and newlines survive a full round trip."""
        nasty = 'back\\slash "quote"\nnewline'
        line = prom_sample("m", 1.5, {"name": nasty, "plain": "v"})
        parsed = parse_prometheus_text(line)
        assert parsed["errors"] == []
        (s,) = parsed["samples"]
        assert s.labels == {"name": nasty, "plain": "v"}
        assert render_sample(s) == line

    def test_garbage_collected_not_raised(self):
        """One corrupt line must not void the rest of the scrape."""
        text = ("good 1\n"
                "}{ total garbage\n"
                "bad_value{a=\"b\"} not_a_float\n"
                "also_good 2\n")
        parsed = parse_prometheus_text(text)
        assert [s.name for s in parsed["samples"]] == ["good",
                                                       "also_good"]
        assert len(parsed["errors"]) == 2


# ---------------------------------------------------------------------------
# emitter/parser drift guard: every prometheus_text family round-trips
# ---------------------------------------------------------------------------

def _assert_round_trips(text):
    """Every sample line an emitter produced must parse cleanly AND
    re-render byte-identically through the shared ``prom_sample``
    renderer — the no-drift contract between every emitter and the ONE
    parser."""
    parsed = parse_prometheus_text(text)
    assert parsed["errors"] == [], parsed["errors"]
    n_sample_lines = 0
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        n_sample_lines += 1
        one = parse_prometheus_text(line)
        assert len(one["samples"]) == 1, line
        assert render_sample(one["samples"][0]) == line
    assert n_sample_lines == len(parsed["samples"])
    assert n_sample_lines > 0, "emitter produced no samples"


class TestEmitterParserDriftGuard:
    def test_stats_registry_family(self):
        reg = StatRegistry()
        reg.add("requests", 7)
        reg.set("gauge_like", 0.125)
        reg.observe("latency_s", 0.05, bounds=(0.01, 0.1, 1.0))
        reg.observe("latency_s", 5.0)
        _assert_round_trips(prometheus_text(
            reg, namespace="paddle_tpu",
            extra_gauges={"derived": 1.75}))

    def test_serving_tracer_family(self):
        clk = SimClock()
        host = SimFleetHost(clk, name="drift")
        host.submit([1, 2, 3, 4], 4)
        for _ in range(8):
            clk.advance(0.05)
            host.engine.step()
        _assert_round_trips(host.tracer.prometheus_text())
        _assert_round_trips(host.engine.prometheus_text())

    def test_gateway_family(self):
        clk = SimClock()
        gw = ServingGateway(clock=clk, tracer=SimTracer(clk))
        eng = SimEngine(max_slots=2, tracer=SimTracer(clk))
        eng.warmup()
        gw.add_replica(eng, "r0")
        _assert_round_trips(gw.prometheus_text())

    def test_ledger_family(self):
        led = RunLedger()
        led.record("compute", 1.25)
        led.record("data_wait", 0.5)
        _assert_round_trips(led.prometheus_text())

    def test_memory_family(self):
        mem = MemoryLedger()
        mem.account("kv_pages", 1 << 20, space="device")
        mem.account("params", 1 << 18, space="host")
        _assert_round_trips(mem.prometheus_text())

    def test_slo_family(self):
        clk = FakeClock()
        mon = SLOMonitor([
            Objective.latency("ttft_p99", "ttft_s", 0.5),
            Objective.ratio("shed_rate", "shed", "submitted", 0.05),
            Objective.floor("goodput_floor", "goodput", 0.5)],
            clock=clk, resolution_s=1.0)
        for i in range(10):
            mon.observe("ttft_s", 0.1 * i, now=float(i))
            mon.observe("goodput", 0.7, now=float(i))
            mon.count("submitted", now=float(i))
        clk.t = 10.0
        mon.evaluate(10.0)
        _assert_round_trips(mon.prometheus_text())

    def test_autoscaler_family(self):
        clk = SimClock()
        gw = ServingGateway(clock=clk, tracer=SimTracer(clk))
        eng = SimEngine(max_slots=2, tracer=SimTracer(clk))
        eng.warmup()
        gw.add_replica(eng, "r0")
        asc = ElasticAutoscaler(gw, None, min_replicas=1, max_replicas=2,
                                clock=clk)
        asc.evaluate()
        _assert_round_trips(asc.prometheus_text())

    def test_kvstore_family(self):
        np = pytest.importorskip("numpy")
        from paddle_tpu.kv_store import KVPage, TieredKVStore
        st = TieredKVStore(dram_capacity_bytes=1 << 20)
        arr = np.full(64, 3, np.float32)
        st.put(KVPage(b"k" * 32, (arr,), ["t", 1]))
        st.lookup(b"k" * 32)
        st.lookup(b"z" * 32)
        _assert_round_trips(st.prometheus_text())

    def test_fleet_collector_family(self):
        """The federation gauges round-trip through the collector's OWN
        parser — the plane can federate itself one level up."""
        clk = FakeClock()
        col = FleetCollector(interval_s=5.0, clock=clk)
        col.add_target("a", fetch=_fetch_target(
            "a_tokens_emitted 5\n",
            {"/ledger": _ledger_payload(30.0, 100.0)}))
        col.scrape_once()
        _assert_round_trips(col.prometheus_text())


# ---------------------------------------------------------------------------
# fleet rollups: hand-computed merges (the acceptance pins)
# ---------------------------------------------------------------------------

class TestFleetRollups:
    def test_goodput_and_skew_match_hand_computed_merge(self):
        """3 targets with known ledger seconds: global goodput is
        sum(compute)/sum(elapsed) — the RunLedger.aggregate merge
        discipline — and straggler skew is max/mean compute."""
        clk = FakeClock()
        col = FleetCollector(interval_s=5.0, clock=clk)
        seconds = {"h0": (30.0, 100.0), "h1": (60.0, 100.0),
                   "h2": (90.0, 100.0)}
        for name, (c, e) in seconds.items():
            col.add_target(name, fetch=_fetch_target(
                f"{name}_tokens_emitted 0\n",
                {"/ledger": _ledger_payload(c, e)}))
        snap = col.scrape_once()
        roll = snap["rollup"]
        assert roll["targets"] == 3 and roll["targets_ok"] == 3
        assert roll["goodput_global"] == pytest.approx(
            (30.0 + 60.0 + 90.0) / 300.0, rel=1e-12)
        assert roll["straggler_skew"] == pytest.approx(
            90.0 / ((30.0 + 60.0 + 90.0) / 3.0), rel=1e-12)
        by = {r["target"]: r for r in snap["targets"]}
        assert by["h1"]["compute_s"] == 60.0
        assert by["h1"]["elapsed_s"] == 100.0
        assert by["h1"]["goodput"] == pytest.approx(0.6)

    def test_fleet_ttft_p99_matches_hand_built_sketch_merge(self):
        """The merged percentile is a real quantile of the union of
        samples: the collector's number (through serialize → transport →
        reconstruct → merge) equals a PercentileSketch built by hand
        from every raw observation — not an average of per-target
        quantiles."""
        clk = FakeClock()
        samples = {"h0": [0.1, 0.2, 0.3, 3.0],
                   "h1": [0.5, 0.5, 0.5, 0.5, 0.5],
                   "h2": [1.0, 2.0]}
        monitors = {}
        for name, values in samples.items():
            mon = SLOMonitor(clock=clk, resolution_s=5.0)
            for i, v in enumerate(values):
                mon.observe("ttft_s", v, now=0.1 * i)
            monitors[name] = mon
        col = FleetCollector(interval_s=5.0, clock=clk)
        for name, mon in monitors.items():
            col.add_target(name, fetch=_fetch_target(
                f"{name}_tokens_emitted 0\n", {"/slo": mon.snapshot()}))
        roll = col.scrape_once()["rollup"]

        hand = PercentileSketch()
        for values in samples.values():
            per_host = PercentileSketch()
            for v in values:
                per_host.add(v)
            hand.merge(per_host)
        assert roll["fleet_ttft_p99"] == pytest.approx(
            hand.quantile(0.99), rel=1e-12)
        assert roll["fleet_ttft_p50"] == pytest.approx(
            hand.quantile(0.50), rel=1e-12)
        # and the naive wrong merge (mean of per-target p99s) differs —
        # the pin is meaningful
        naive = sum(
            max(vs) for vs in samples.values()) / len(samples)
        assert roll["fleet_ttft_p99"] != pytest.approx(naive, rel=0.01)

    def test_tokens_per_s_from_counter_deltas(self):
        clk = FakeClock()
        box = {"h0": 0.0, "h1": 0.0}

        def make(name):
            def fetch(path):
                if path == "/metrics":
                    return f"{name}_tokens_emitted {box[name]}\n"
                return None
            return fetch

        col = FleetCollector(interval_s=5.0, clock=clk)
        col.add_target("h0", fetch=make("h0"))
        col.add_target("h1", fetch=make("h1"))
        first = col.scrape_once()
        assert first["rollup"]["tokens_per_s"] is None  # no delta yet
        box["h0"], box["h1"] = 50.0, 25.0
        clk.advance(5.0)
        roll = col.scrape_once()["rollup"]
        assert roll["tokens_per_s"] == pytest.approx(75.0 / 5.0)
        # counter reset (target restarted): rate withheld, not negative
        box["h0"] = 3.0
        clk.advance(5.0)
        snap = col.scrape_once()
        by = {r["target"]: r for r in snap["targets"]}
        assert by["h0"]["tokens_per_s"] is None
        assert by["h1"]["tokens_per_s"] == pytest.approx(0.0)

    def test_scalar_rollups_drive_fleet_regression_alert(self):
        """A floor objective on ``goodput_global`` IS the live fleet
        regression detector: sustained low goodput fires through the
        multi-window burn machinery on the collector's own clock."""
        clk = FakeClock()
        col = FleetCollector(
            interval_s=5.0, clock=clk,
            objectives=[Objective.floor(
                "goodput_floor", "goodput_global", 0.5, compliance=0.9,
                windows=(30.0, 10.0), burn_threshold=1.0, for_s=2.0,
                clear_s=10.0)])
        col.add_target("h0", fetch=_fetch_target(
            "h0_tokens_emitted 0\n",
            {"/ledger": _ledger_payload(20.0, 100.0)}))
        fired = False
        for _ in range(20):
            fired = fired or \
                col.scrape_once()["slo"]["alerts_firing"] >= 1
            clk.advance(5.0)
        assert fired


# ---------------------------------------------------------------------------
# staleness: a dead target is a labeled gap, never a silent merge
# ---------------------------------------------------------------------------

class TestStaleness:
    def _mortal_fleet(self, clk):
        """3 targets; h2's transport dies when told to."""
        dead = {"h2": False}
        monitors = {}
        seconds = {"h0": (30.0, 100.0), "h1": (60.0, 100.0),
                   "h2": (90.0, 100.0)}
        ttfts = {"h0": [0.1, 0.2], "h1": [0.3, 0.4], "h2": [5.0, 6.0]}
        col = FleetCollector(interval_s=5.0, clock=clk)  # stale at 15s
        for name, (c, e) in seconds.items():
            mon = SLOMonitor(clock=clk, resolution_s=5.0)
            for i, v in enumerate(ttfts[name]):
                mon.observe("ttft_s", v, now=0.1 * i)
            monitors[name] = mon

            def fetch(path, name=name):
                if dead.get(name):
                    raise OSError(f"{name} unreachable")
                if path == "/metrics":
                    return f"{name}_tokens_emitted 0\n"
                if path == "/ledger":
                    return _ledger_payload(*seconds[name])
                if path == "/slo":
                    return monitors[name].snapshot()
                return None

            col.add_target(name, fetch=fetch)
        return col, dead

    def test_killed_target_flips_stale_without_corrupting_rollups(self):
        clk = FakeClock()
        col, dead = self._mortal_fleet(clk)
        roll = col.scrape_once()["rollup"]
        assert roll["targets_ok"] == 3
        assert roll["goodput_global"] == pytest.approx(180.0 / 300.0)

        dead["h2"] = True
        clk.advance(5.0)
        snap = col.scrape_once()       # failed, but within the window
        by = {r["target"]: r for r in snap["targets"]}
        assert by["h2"]["status"] == "ok"      # last good scrape recent
        assert by["h2"]["consecutive_failures"] == 1
        # past stale_after_s (3 * interval): labeled stale, with its age
        # and last error — and EXCLUDED from every rollup
        clk.advance(15.0)
        snap = col.scrape_once()
        by = {r["target"]: r for r in snap["targets"]}
        assert by["h2"]["status"] == "stale"
        assert by["h2"]["age_s"] > col.stale_after_s
        assert "unreachable" in by["h2"]["error"]
        roll = snap["rollup"]
        assert roll["targets_ok"] == 2 and roll["targets_stale"] == 1
        assert roll["goodput_global"] == pytest.approx(90.0 / 200.0)
        assert roll["straggler_skew"] == pytest.approx(60.0 / 45.0)
        # h2's 5-6s TTFTs must not haunt the merged percentile
        hand = PercentileSketch()
        for v in (0.1, 0.2, 0.3, 0.4):
            hand.add(v)
        assert roll["fleet_ttft_p99"] == pytest.approx(
            hand.quantile(0.99), rel=1e-12)

    def test_never_scraped_is_down_and_backoff_bounds_retries(self):
        clk = FakeClock()
        calls = {"n": 0}

        def fetch(path):
            calls["n"] += 1
            raise OSError("never up")

        col = FleetCollector(interval_s=5.0, clock=clk,
                             backoff_max_s=60.0)
        col.add_target("ghost", fetch=fetch)
        snap = col.scrape_once()
        assert snap["targets"][0]["status"] == "down"
        assert snap["rollup"]["targets_down"] == 1
        n_after_first = calls["n"]
        # consecutive failures back off exponentially: an immediate
        # re-scrape round skips the target entirely
        col.scrape_once()
        assert calls["n"] == n_after_first
        clk.advance(5.0)               # past the first 5s backoff
        col.scrape_once()
        assert calls["n"] == n_after_first + 1

    def test_http_targets_over_real_ops_servers(self):
        """Two STARTED ops servers scraped over real HTTP; stopping one
        flips it to stale while the survivor stays ok."""
        clk = SimClock()
        h0, h1 = SimFleetHost(clk, name="h0"), SimFleetHost(clk, name="h1")
        h0.submit([1, 2, 3], 3)
        for _ in range(6):
            clk.advance(0.05)
            h0.engine.step()
            h1.engine.step()
        fclk = FakeClock()
        col = FleetCollector(interval_s=5.0, clock=fclk, timeout_s=5.0)
        url0, url1 = h0.server.start(), h1.server.start()
        try:
            col.add_target("h0", url0)
            col.add_target("h1", url1)
            roll = col.scrape_once()["rollup"]
            assert roll["targets_ok"] == 2
            h1.server.stop()
            fclk.advance(20.0)         # past stale_after_s
            snap = col.scrape_once()
            by = {r["target"]: r for r in snap["targets"]}
            assert by["h0"]["status"] == "ok"
            assert by["h1"]["status"] == "stale"
            assert by["h1"]["error"] is not None
        finally:
            h0.server.stop()
            h1.server.stop()


# ---------------------------------------------------------------------------
# the durable spool
# ---------------------------------------------------------------------------

class TestTelemetrySpool:
    def test_rotation_and_retention(self, tmp_path):
        sp = TelemetrySpool(str(tmp_path), segment_bytes=1024,
                            max_segments=2)
        pad = "x" * 100
        for i in range(60):
            sp.append({"i": i, "pad": pad})
        sp.close()
        names = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("spool-"))
        assert len(names) == 2          # retention cap holds
        recs = TelemetrySpool(str(tmp_path), segment_bytes=1024,
                              max_segments=2).records()
        seqs = [r["seq"] for r in recs]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert seqs[-1] == 60           # newest records survive

    def test_torn_tail_without_newline_is_truncated(self, tmp_path):
        sp = TelemetrySpool(str(tmp_path))
        for i in range(5):
            sp.append({"i": i})
        sp.close()
        (seg,) = [f for f in os.listdir(tmp_path)
                  if f.startswith("spool-")]
        with open(tmp_path / seg, "a") as f:
            f.write('{"i": 5, "seq": 6')      # crash mid-write
        sp2 = TelemetrySpool(str(tmp_path))
        recs = sp2.records()
        assert [r["i"] for r in recs] == [0, 1, 2, 3, 4]
        assert sp2.append({"i": "post"}) == 6  # seq resumes, no gap
        assert [r["seq"] for r in sp2.records()] == [1, 2, 3, 4, 5, 6]

    def test_torn_tail_with_newline_is_truncated(self, tmp_path):
        """A torn write that DID land its newline is still unparseable
        JSON — dropped the same way."""
        sp = TelemetrySpool(str(tmp_path))
        for i in range(3):
            sp.append({"i": i})
        sp.close()
        (seg,) = [f for f in os.listdir(tmp_path)
                  if f.startswith("spool-")]
        with open(tmp_path / seg, "a") as f:
            f.write('{"i": 3, "se\n')
        sp2 = TelemetrySpool(str(tmp_path))
        assert [r["i"] for r in sp2.records()] == [0, 1, 2]
        assert sp2.append({"i": 3}) == 4

    def test_validation(self, tmp_path):
        with pytest.raises(ValueError):
            TelemetrySpool(str(tmp_path), segment_bytes=10)
        with pytest.raises(ValueError):
            TelemetrySpool(str(tmp_path), max_segments=1)

    def test_collector_spool_survives_simulated_crash(self, tmp_path):
        """The end-to-end crash pin: scrape → kill the process mid-write
        (emulated by a torn tail) → a NEW collector resumes the spool
        with no duplicate and no lost durable samples."""
        clk = FakeClock()
        spool_dir = str(tmp_path / "spool")

        def build():
            c = FleetCollector(interval_s=5.0, clock=clk,
                               spool_dir=spool_dir)
            c.add_target("h0", fetch=_fetch_target(
                "h0_tokens_emitted 0\n",
                {"/ledger": _ledger_payload(30.0, 100.0)}))
            return c

        col = build()
        col.scrape_once()
        clk.advance(5.0)
        col.scrape_once()
        before = col.spool.records()
        col.stop()                     # closes the spool
        # crash: a torn half-record at the tail of the open segment
        segs = sorted(f for f in os.listdir(spool_dir)
                      if f.startswith("spool-"))
        with open(os.path.join(spool_dir, segs[-1]), "a") as f:
            f.write('{"kind": "rollup", "ts": 99')
        col2 = build()
        assert col2.spool.records() == before   # nothing durable lost
        clk.advance(5.0)
        col2.scrape_once()
        seqs = [r["seq"] for r in col2.spool.records()]
        assert seqs == list(range(1, len(seqs) + 1))  # no dup, no gap
        # per-scrape shape: one target row + one rollup per round
        kinds = [r["kind"] for r in col2.spool.records()]
        assert kinds == ["target", "rollup"] * 3


# ---------------------------------------------------------------------------
# surfaces: GET /fleet, fleet_top, federation gauges, FlightRecorder
# ---------------------------------------------------------------------------

class TestFleetSurfaces:
    def _collector(self, clk):
        col = FleetCollector(interval_s=5.0, clock=clk)
        mon = SLOMonitor(clock=clk, resolution_s=5.0)
        for v in (0.1, 0.4, 0.9):
            mon.observe("ttft_s", v, now=0.1)
        col.add_target("h0", fetch=_fetch_target(
            "h0_tokens_emitted 4\n",
            {"/ledger": _ledger_payload(30.0, 100.0),
             "/slo": mon.snapshot()}))
        return col

    def test_fleet_route_and_dashboard_render_same_snapshot(self):
        """GET /fleet over real HTTP serves the same object
        ``fleet_snapshot()`` returns, and fleet_top renders identical
        frames from either — one snapshot, every surface."""
        fleet_top = _load_tool("fleet_top")
        clk = FakeClock()
        col = self._collector(clk)
        col.scrape_once()
        srv = OpsServer()
        srv.attach(col, "fleet")
        url = srv.start()
        try:
            via_http = json.loads(urllib.request.urlopen(
                url + "/fleet", timeout=10).read())
        finally:
            srv.stop()
        local = col.fleet_snapshot()
        assert via_http == json.loads(json.dumps(local))
        frame_http = fleet_top.render_fleet(via_http)
        frame_local = fleet_top.render_fleet(local)
        assert frame_http == frame_local
        assert "h0" in frame_local and "ok" in frame_local

    def test_fleet_route_404_without_collector(self):
        srv = OpsServer()
        srv.attach(SLOMonitor(), "slo")
        url = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + "/fleet", timeout=10)
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_dashboard_marks_stale_targets_visible(self):
        fleet_top = _load_tool("fleet_top")
        clk = FakeClock()
        boom = {"on": False}

        def fetch(path):
            if boom["on"]:
                raise OSError("scrape refused")
            return "h_tokens_emitted 0\n" if path == "/metrics" else None

        col = FleetCollector(interval_s=5.0, clock=clk)
        col.add_target("mortal", fetch=fetch)
        col.scrape_once()
        boom["on"] = True
        clk.advance(20.0)
        frame = fleet_top.render_fleet(col.scrape_once())
        assert "stale" in frame
        assert "scrape refused" in frame   # the labeled gap, visible

    def test_prerender_snapshot_shape_before_first_scrape(self):
        col = FleetCollector(interval_s=5.0, clock=FakeClock())
        col.add_target("h0", fetch=_fetch_target("x_tokens_emitted 0\n"))
        snap = col.fleet_snapshot()
        assert snap["targets"] == [] and snap["scrapes"] == 0
        assert snap["rollup"]["targets_down"] == 1

    def test_flight_recorder_dumps_fleet_json(self, tmp_path):
        clk = FakeClock()
        col = FleetCollector(interval_s=5.0, clock=clk,
                             spool_dir=str(tmp_path / "spool"))
        col.add_target("h0", fetch=_fetch_target(
            "h0_tokens_emitted 0\n",
            {"/ledger": _ledger_payload(30.0, 100.0)}))
        col.scrape_once()
        fr = FlightRecorder(str(tmp_path / "crash"))
        fr.add_source(col, "fleet")
        out_dir = fr.dump("test")
        assert out_dir is not None
        payload = json.loads(
            (pathlib.Path(out_dir) / "fleet.json").read_text())
        assert payload["snapshot"]["rollup"]["targets_ok"] == 1
        assert payload["spool_tail"][-1]["kind"] == "rollup"


# ---------------------------------------------------------------------------
# the sim fleet: whole federation pipeline on one fake clock
# ---------------------------------------------------------------------------

class TestSimFleet:
    def test_three_host_pipeline_end_to_end(self, tmp_path):
        clk = SimClock()
        col, hosts = build_sim_fleet(clk, 3, interval_s=5.0,
                                     spool_dir=str(tmp_path))
        for host in hosts:
            host.submit([1, 2, 3, 4], 6)
        for _ in range(40):
            clk.advance(0.05)
            for host in hosts:
                host.engine.step()
                host.ledger.record("compute", 0.05)
        col.scrape_once()
        clk.advance(5.0)
        snap = col.scrape_once()
        roll = snap["rollup"]
        assert roll["targets_ok"] == 3
        assert roll["fleet_ttft_p99"] is not None
        assert [r["status"] for r in snap["targets"]] == ["ok"] * 3
        # second scrape has token deltas (all emitted in window 1 → 0/s
        # now is legitimate; the field must be present, not None)
        assert roll["tokens_per_s"] is not None
        assert snap["spool"]["seq"] == 8    # (3 targets + 1 rollup) * 2

    def test_build_sim_fleet_validates(self):
        with pytest.raises(ValueError):
            build_sim_fleet(SimClock(), 0)


# ---------------------------------------------------------------------------
# collector as an autoscaler signal
# ---------------------------------------------------------------------------

class _StubFleet:
    def __init__(self, p99):
        self.p99 = p99

    def fleet_snapshot(self):
        return {"rollup": {"fleet_ttft_p99": self.p99}}


class TestAutoscalerFleetSignal:
    def _gw(self, clk, replicas=1):
        gw = ServingGateway(clock=clk, tracer=SimTracer(clk))
        for i in range(replicas):
            eng = SimEngine(max_slots=2, tracer=SimTracer(clk))
            eng.warmup()
            gw.add_replica(eng, f"r{i}")
        return gw

    def test_hot_fleet_ttft_triggers_scale_up(self):
        clk = SimClock()
        gw = self._gw(clk)
        spawned = []

        def factory():
            eng = SimEngine(max_slots=2, tracer=SimTracer(clk))
            spawned.append(eng)
            return eng

        asc = ElasticAutoscaler(gw, factory, min_replicas=1,
                                max_replicas=3, clock=clk,
                                fleet=_StubFleet(1.2),
                                fleet_ttft_high=0.5)
        made = asc.evaluate()
        assert [d["action"] for d in made] == ["scale_up"]
        assert "fleet_ttft:1.200" in made[0]["reason"]
        snap = asc.autoscaler_snapshot()
        assert snap["signals"]["fleet_ttft_p99"] == 1.2
        assert snap["signals"]["fleet_ttft_high"] == 0.5

    def test_cool_fleet_ttft_does_not_trigger(self):
        clk = SimClock()
        gw = self._gw(clk)
        asc = ElasticAutoscaler(gw, None, min_replicas=1, max_replicas=3,
                                clock=clk, fleet=_StubFleet(0.1),
                                fleet_ttft_high=0.5)
        assert asc.evaluate() == []

    def test_broken_fleet_poll_never_takes_controller_down(self):
        clk = SimClock()

        class Broken:
            def fleet_snapshot(self):
                raise RuntimeError("collector died")

        asc = ElasticAutoscaler(self._gw(clk), None, min_replicas=1,
                                max_replicas=3, clock=clk, fleet=Broken(),
                                fleet_ttft_high=0.5)
        assert asc.fleet_ttft_p99() is None
        assert asc.evaluate() == []

    def test_ctor_validation(self):
        clk = SimClock()
        with pytest.raises(TypeError):
            ElasticAutoscaler(self._gw(clk), None, fleet=object())
        with pytest.raises(ValueError):
            ElasticAutoscaler(self._gw(clk), None,
                              fleet=_StubFleet(1.0), fleet_ttft_high=0.0)


# ---------------------------------------------------------------------------
# offline regression detection
# ---------------------------------------------------------------------------

class TestReplayRegressions:
    def test_throughput_drop_fires_floor_objective(self):
        records = []
        for i in range(24):
            ts = 5.0 * i
            rate = 100.0 if i < 6 else 5.0    # the regression
            records.append({"kind": "rollup", "ts": ts,
                            "tokens_per_s": rate, "seq": i + 1})
            records.append({"kind": "target", "ts": ts,
                            "target": "h0", "seq": 1000 + i})
        snap = replay_regressions(
            records,
            [Objective.floor("tokens_floor", "tokens_per_s", 50.0,
                             compliance=0.9, windows=(30.0, 10.0),
                             burn_threshold=1.0, for_s=2.0,
                             clear_s=10.0)],
            resolution_s=5.0)
        assert snap["replayed_records"] == 24   # target rows ignored
        fired = [t for t in snap.get("transitions", [])
                 if t.get("what") == "firing"
                 and t.get("objective") == "tokens_floor"]
        assert fired, snap

    def test_empty_records(self):
        snap = replay_regressions(
            [], [Objective.floor("f", "tokens_per_s", 1.0)])
        assert snap["replayed_records"] == 0


# ---------------------------------------------------------------------------
# off-path purity: the collector is a pure pull reader
# ---------------------------------------------------------------------------

class TestOffPathPurity:
    def test_lowerings_byte_identical_with_collector_scraping(self):
        """The PR 2 pin extended to the federation plane: an engine whose
        ops server a FleetCollector actively scrapes lowers byte-
        identical programs to a bare engine — the collector reads
        surfaces that already existed and touches nothing on-device."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.models.gpt import GPTConfig, GPTModel
        from paddle_tpu.serving import ContinuousBatchingEngine

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_attention_heads=2,
                        max_position_embeddings=64,
                        compute_dtype="float32")

        def build():
            paddle.seed(0)
            model = GPTModel(cfg)
            params = {n: p._data for n, p in model.named_parameters()}
            return ContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32,
                prompt_buckets=[8])

        def lowered_texts(eng):
            ck, cv = eng._alloc_caches()
            pre = eng._build_prefill(8).lower(
                eng.params, ck, cv, jnp.zeros((1, 8), jnp.int32),
                jnp.int32(0), jnp.int32(0), jax.random.key(0),
                eng._scratch_presence(), eng._plane_operands()).as_text()
            ck, cv = eng._alloc_caches()
            z = jnp.zeros(eng.S, jnp.int32)
            dec = eng._build_decode().lower(
                eng.params, ck, cv, z, z, z,
                jnp.zeros(eng.S, bool), jax.random.key(0),
                eng._scratch_presence(), z,
                eng._plane_operands()).as_text()
            return pre, dec

        scraped = build()
        srv = OpsServer()
        srv.attach(scraped)
        col = FleetCollector(interval_s=5.0, clock=FakeClock())
        col.add_target("local", server=srv)
        col.scrape_once()              # actively federated
        bare = build()
        for a, b in zip(lowered_texts(scraped), lowered_texts(bare)):
            assert a == b
