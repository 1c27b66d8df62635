"""Live ops endpoint: a stdlib HTTP server over the telemetry surfaces.

Everything PR 2/4/8 record — tracer ring buffers, TrainMonitor counters,
engine stat registries, the goodput ledger — lives in process memory and is
only visible post-hoc through JSONL dumps.  :class:`OpsServer` makes it
live: a ``ThreadingHTTPServer`` (stdlib only, no new deps) that any engine,
``TrainMonitor``, ``Tracer`` or ``RunLedger`` can be attached to, serving

``GET /metrics``
    merged Prometheus text exposition of every attached source — serving
    (``paddle_tpu_serving_*``) and training (``paddle_tpu_train_*``)
    namespaces side by side, engine registries, ledger gauges
    (``paddle_tpu_ledger_*``), plus the server's own uptime gauge.
``GET /healthz``
    liveness JSON; **503** when the last observed step/tick/heartbeat is
    older than ``stall_threshold_s`` — the load-balancer / watchdog dial.
    ``?probe=1`` additionally runs an in-process compute probe (a jitted
    matmul ROUND-TRIP to host, never a bare ``jax.devices()`` — a half-up
    backend enumerates devices while compile/execute hangs), bounded by
    ``probe_timeout_s``.
``GET /ledger``
    the attached :class:`~paddle_tpu.telemetry_ledger.RunLedger`
    snapshot(s) as JSON (404 when none is attached).
``GET /trace``
    ring-buffer tail: the last ``?n=`` events (default 256) per attached
    tracer/monitor, optionally filtered by ``?kind=``.
``GET /gateway``
    the attached :class:`~paddle_tpu.gateway.ServingGateway` snapshot(s)
    as JSON — replica states, per-priority queue depths, shed/reroute/
    drain counters, queue/TTFT percentiles (404 when none is attached).
``GET /requests``
    recent end-to-end request traces (``?n=`` newest, default 64):
    trace_id, status, replicas touched — stitched live from every
    attached tracer's ring by
    :class:`~paddle_tpu.telemetry.RequestTraceIndex`.
``GET /request/<trace_id>``
    ONE stitched request timeline: the full cross-source span tree
    (gateway root → per-dispatch engine attempts → queued/prefill/
    decode phases, preempt markers) plus the raw event sequence (404
    for an unknown trace).
``GET /resilience``
    the attached gateway's resilience view (PR 12): per-replica circuit
    breaker states, the brownout ladder rung, live hedges, and the
    retry/hedge/brownout counters (404 when no attached gateway carries
    a resilience policy).
``GET /slo``
    the attached :class:`~paddle_tpu.telemetry_slo.SLOMonitor` snapshot:
    objectives, live burn rates, alert states, SLIs, and the recent
    transition ring (404 when none is attached); scraping evaluates, so
    the states are current as of the request.
``GET /autoscaler``
    the attached :class:`~paddle_tpu.autoscaler.ElasticAutoscaler`
    snapshot: policy knobs, fleet/pending-spawn state, live signals
    (firing objectives, utilization, idle dwell), and the bounded
    decision history (404 when none is attached).  A pure read — it
    never advances the control loop.
``GET /kvstore``
    the KV-tiering view (docs/KV_TIERING.md): attached gateways'
    ``kvstore_snapshot()`` (migration counters + in-flight pipelines,
    per-replica role/store state, the fleet-wide tier-aware prefix
    index) plus any directly attached
    :class:`~paddle_tpu.kv_store.TieredKVStore` snapshots (404 when
    nothing KV-tiered is attached).
``GET /memory``
    the attached :class:`~paddle_tpu.telemetry_memory.MemoryLedger`
    snapshot(s): per-pool live/peak bytes in device and host space, KV
    tier bytes, per-device totals from the last census, and the
    watermark-crossing tail (404 when none is attached).  A pure read —
    it never runs a census; callers decide when the live-array walk
    happens.
``GET /fleet``
    the attached :class:`~paddle_tpu.telemetry_fleet.FleetCollector`
    snapshot(s): per-target scrape status (``ok``/``stale``/``down``
    with ages and last errors), the fleet rollups (global goodput,
    fleet MFU, merged TTFT/ITL percentiles, straggler skew), fleet SLO
    burn, and spool stats (404 when none is attached).  A pure read of
    the LAST scrape — it never triggers one.

Zero cost when not started: constructing the server binds nothing and
touches no hot path — sources are only read inside request handlers.
``start()`` binds (``port=0`` → ephemeral) and serves on a daemon thread.

Example::

    from paddle_tpu.ops_server import OpsServer
    srv = OpsServer(port=9100, stall_threshold_s=120)
    srv.attach(engine)          # engine registry + its tracer, if any
    srv.attach(monitor)         # TrainMonitor
    srv.attach(ledger)          # RunLedger
    url = srv.start()
    # curl $url/metrics ; curl $url/healthz ; curl $url/ledger
"""

from __future__ import annotations

import json
import logging
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["OpsServer", "compute_probe"]


def compute_probe(timeout_s: float = 10.0, n: int = 256) -> Dict[str, Any]:
    """In-process compute health probe: health is a jitted ``n×n`` matmul
    round-trip to host (compile + execute + fetch), never a bare device
    enumeration.  Runs on a worker thread bounded by ``timeout_s``; on
    timeout the thread is abandoned (reported unhealthy), not killed — an
    in-process probe cannot kill its own interpreter."""
    result: Dict[str, Any] = {}

    def run():
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np
            t0 = time.perf_counter()
            x = jnp.ones((n, n), jnp.float32)
            # tpulint: disable=jit-in-hot-loop(one-shot probe — paying trace+compile+execute is the health check itself)
            v = float(np.asarray(jax.jit(lambda a: a @ a)(x)[0, 0]))
            result.update(ok=True, value=v,
                          wall_s=round(time.perf_counter() - t0, 4),
                          devices=len(jax.devices()))
        except Exception as e:       # the probe verdict IS the error report
            result.update(ok=False, error=repr(e))

    t = threading.Thread(target=run, daemon=True, name="ops-compute-probe")
    t.start()
    t.join(timeout_s)
    if not result:
        return {"ok": False,
                "error": f"compute probe timed out after {timeout_s}s "
                         f"(dispatch or compile hung — half-up backend)"}
    return result


class _Handler(BaseHTTPRequestHandler):
    server_version = "paddle-tpu-ops/1"
    protocol_version = "HTTP/1.1"

    def do_GET(self):          # noqa: N802 — http.server contract
        ops: "OpsServer" = self.server.ops     # type: ignore[attr-defined]
        parsed = urllib.parse.urlsplit(self.path)
        route = parsed.path.rstrip("/") or "/"
        query = urllib.parse.parse_qs(parsed.query)
        try:
            if route == "/metrics":
                self._send(200, ops._render_metrics(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif route == "/healthz":
                payload, ok = ops._render_healthz(
                    run_probe=query.get("probe", ["0"])[0]
                    not in ("0", "", "false"))
                self._send(200 if ok else 503,
                           json.dumps(payload, indent=2),
                           "application/json")
            elif route == "/ledger":
                payload = ops._render_ledger()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no ledger attached"}), "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/trace":
                n = int(query.get("n", ["256"])[0])
                kind = query.get("kind", [None])[0]
                self._send(200, json.dumps(ops._render_trace(n, kind)),
                           "application/json")
            elif route == "/gateway":
                payload = ops._render_gateway()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no gateway attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/requests":
                n = int(query.get("n", ["64"])[0])
                self._send(200, json.dumps(ops._render_requests(n),
                                           indent=2), "application/json")
            elif route.startswith("/request/"):
                trace_id = route[len("/request/"):]
                payload = ops._render_request(trace_id)
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": f"unknown trace {trace_id!r}"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/resilience":
                payload = ops._render_resilience()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no resilience-enabled gateway "
                                  "attached"}), "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/slo":
                payload = ops._render_slo()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no slo monitor attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/autoscaler":
                payload = ops._render_autoscaler()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no autoscaler attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/kvstore":
                payload = ops._render_kvstore()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "nothing KV-tiered attached (no "
                                  "kv-surface gateway, no TieredKVStore)"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/memory":
                payload = ops._render_memory()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no memory ledger attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/fleet":
                payload = ops._render_fleet()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no fleet collector attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            elif route == "/train":
                payload = ops._render_train()
                if payload is None:
                    self._send(404, json.dumps(
                        {"error": "no train supervisor attached"}),
                        "application/json")
                else:
                    self._send(200, json.dumps(payload, indent=2),
                               "application/json")
            else:
                self._send(404, json.dumps(
                    {"error": f"unknown route {route!r}", "routes":
                     ["/metrics", "/healthz", "/ledger", "/trace",
                      "/gateway", "/requests", "/request/<trace_id>",
                      "/resilience", "/slo", "/autoscaler", "/kvstore",
                      "/memory", "/fleet", "/train"]}),
                    "application/json")
        except Exception as e:
            ops._log.warning("ops server: %s failed: %r", route, e)
            try:
                self._send(500, json.dumps({"error": repr(e)}),
                           "application/json")
            except OSError:
                pass                      # client went away mid-error

    def _send(self, code: int, body: str, ctype: str):
        data = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, fmt, *args):     # route through logging, not stderr
        self.server.ops._log.debug(        # type: ignore[attr-defined]
            "ops server: %s", fmt % args)


class OpsServer:
    """Attachable live ops endpoint (module docstring).

    ``stall_threshold_s``: /healthz turns 503 when no attached source has
    shown activity (train step, scheduler tick, explicit ``heartbeat()``)
    for longer than this.  ``probe_timeout_s`` bounds the optional
    ``?probe=1`` compute probe."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 stall_threshold_s: float = 120.0,
                 probe_timeout_s: float = 10.0,
                 logger: Optional[logging.Logger] = None):
        self.host = host
        self.port = int(port)
        self.stall_threshold_s = float(stall_threshold_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        self._lock = threading.Lock()
        self._tracers: List[Tuple[str, Any]] = []   # Tracer / TrainMonitor
        self._engines: List[Tuple[str, Any]] = []
        self._ledgers: List[Tuple[str, Any]] = []
        self._gateways: List[Tuple[str, Any]] = []
        self._slos: List[Tuple[str, Any]] = []      # SLOMonitor
        self._autoscalers: List[Tuple[str, Any]] = []
        self._kvstores: List[Tuple[str, Any]] = []  # TieredKVStore
        self._memories: List[Tuple[str, Any]] = []  # MemoryLedger
        self._fleets: List[Tuple[str, Any]] = []    # FleetCollector
        self._trains: List[Tuple[str, Any]] = []    # TrainSupervisor
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._started_at = time.monotonic()
        self._last_beat = time.monotonic()

    # ------------------------------------------------------------ attach --
    def attach(self, obj, name: Optional[str] = None) -> "OpsServer":
        """Attach a telemetry source; kind is detected:

        - ``FleetCollector`` (has ``fleet_snapshot``) → /fleet + its
          ``paddle_tpu_fleet_*`` federation gauges on /metrics;
        - ``RunLedger`` (has ``snapshot``/``record``) → /ledger + gauges;
        - ``MemoryLedger`` (has ``memory_snapshot``) → /memory +
          /metrics pool/watermark byte gauges;
        - ``ElasticAutoscaler`` (has ``autoscaler_snapshot``) →
          /autoscaler + /metrics fleet/decision gauges;
        - ``ServingGateway`` (has ``gateway_snapshot``) → /gateway +
          /metrics (its ``.tracer``, when set, is attached too);
        - ``TieredKVStore`` (has ``tier_of``/``put``) → /kvstore +
          /metrics tier gauges (attached gateways contribute their
          replicas' stores to /kvstore without this);
        - ``SLOMonitor`` (has ``add_objective``/``evaluate``) → /slo +
          /metrics burn-rate/alert gauges;
        - ``TrainSupervisor`` (has ``train_snapshot``) → /train +
          /metrics ``paddle_tpu_train_resilience_*`` counters (its
          ``.tracer``, when set, is attached too);
        - ``Tracer`` / ``TrainMonitor`` (has ``events`` +
          ``prometheus_text``) → /metrics + /trace + liveness;
        - a serving engine (has ``prometheus_text``; its ``.tracer``, when
          set, is attached too) → /metrics (+ tracer surfaces).

        Every attached tracer additionally feeds the request-trace
        stitcher behind ``/requests`` and ``/request/<trace_id>``; an
        attached gateway also contributes its replicas' engine tracers,
        enumerated live at query time (drain-swapped replacements
        included), so ``attach(gateway)`` alone serves full stitched
        cross-replica timelines.
        """
        with self._lock:
            if hasattr(obj, "fleet_snapshot"):
                # FleetCollector: checked first — it also exposes
                # prometheus_text, and must not fall through to the
                # engine shape; its federation gauges still join /metrics
                base = name or f"fleet{len(self._fleets)}"
                self._fleets.append((base, obj))
                self._engines.append((base, obj))   # /metrics exposition
            elif hasattr(obj, "autoscaler_snapshot"):
                base = name or f"autoscaler{len(self._autoscalers)}"
                self._autoscalers.append((base, obj))
                self._engines.append((base, obj))   # /metrics exposition
            elif hasattr(obj, "add_objective") and hasattr(obj, "evaluate"):
                self._slos.append((name or f"slo{len(self._slos)}", obj))
            elif hasattr(obj, "gateway_snapshot"):
                base = name or f"gateway{len(self._gateways)}"
                self._gateways.append((base, obj))
                self._engines.append((base, obj))   # /metrics exposition
                tracer = getattr(obj, "tracer", None)
                if tracer is not None:
                    self._tracers.append((f"{base}.tracer", tracer))
            elif hasattr(obj, "tier_of") and hasattr(obj, "put"):
                # TieredKVStore: /kvstore + its gauges on /metrics
                self._kvstores.append(
                    (name or f"kvstore{len(self._kvstores)}", obj))
            elif hasattr(obj, "memory_snapshot"):
                # MemoryLedger: checked before the RunLedger shape — both
                # expose prometheus_text, only this one serves /memory
                self._memories.append(
                    (name or f"memory{len(self._memories)}", obj))
            elif hasattr(obj, "train_snapshot"):
                # TrainSupervisor: /train + its resilience counters on
                # /metrics (+ its tracer's surfaces)
                base = name or f"train{len(self._trains)}"
                self._trains.append((base, obj))
                self._engines.append((base, obj))   # /metrics exposition
                tracer = getattr(obj, "tracer", None)
                if tracer is not None:
                    self._tracers.append((f"{base}.tracer", tracer))
            elif hasattr(obj, "snapshot") and hasattr(obj, "record"):
                self._ledgers.append(
                    (name or f"ledger{len(self._ledgers)}", obj))
            elif hasattr(obj, "events") and hasattr(obj, "prometheus_text"):
                self._tracers.append(
                    (name or f"tracer{len(self._tracers)}", obj))
            elif hasattr(obj, "prometheus_text"):
                base = name or f"engine{len(self._engines)}"
                self._engines.append((base, obj))
                tracer = getattr(obj, "tracer", None)
                if tracer is not None:
                    self._tracers.append((f"{base}.tracer", tracer))
            else:
                raise TypeError(
                    f"unsupported ops-server source: {type(obj).__name__} "
                    f"(want a RunLedger, Tracer, TrainMonitor, or engine)")
        return self

    def heartbeat(self):
        """Explicit liveness tick for loops with no attached tracer."""
        self._last_beat = time.monotonic()

    # --------------------------------------------------------- lifecycle --
    def start(self) -> str:
        """Bind and serve on a daemon thread; returns the base URL
        (``port=0`` resolves to the ephemeral port actually bound)."""
        if self._httpd is not None:
            return self.url
        httpd = ThreadingHTTPServer((self.host, self.port), _Handler)
        httpd.daemon_threads = True
        httpd.ops = self                        # type: ignore[attr-defined]
        self._httpd = httpd
        self.port = httpd.server_address[1]
        self._started_at = time.monotonic()
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(target=httpd.serve_forever,
                                        daemon=True, name="ops-server")
        self._thread.start()
        self._log.info("ops server listening on %s", self.url)
        return self.url

    def stop(self):
        httpd, self._httpd = self._httpd, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ----------------------------------------------------------- renders --
    def _sources(self):
        with self._lock:
            return (list(self._tracers), list(self._engines),
                    list(self._ledgers))

    def last_activity_age_s(self) -> float:
        """Seconds since the newest sign of life: an explicit heartbeat, or
        the latest event on any attached tracer/monitor (their ring
        timestamps are seconds on the tracer's own clock — ``now() - ts``
        is the event's age)."""
        tracers, _, _ = self._sources()
        age = time.monotonic() - self._last_beat
        for _name, tr in tracers:
            inner = getattr(tr, "tracer", tr)      # TrainMonitor wraps one
            try:
                if hasattr(inner, "last_event_age_s"):
                    ev_age = inner.last_event_age_s()   # O(1), no ring copy
                else:
                    evs = inner.events()
                    ev_age = (max(0.0, inner.now() - evs[-1]["ts"])
                              if evs else None)
                if ev_age is not None:
                    age = min(age, ev_age)
            except Exception as e:
                self._log.debug("ops server: activity scan failed on %s: "
                                "%r", _name, e)
        return age

    def _render_metrics(self) -> str:
        tracers, engines, ledgers = self._sources()
        with self._lock:
            slos = list(self._slos)
            kvstores = list(self._kvstores)
            memories = list(self._memories)
        parts = []
        for _name, obj in tracers + engines:
            parts.append(obj.prometheus_text())
        for _name, led in ledgers + memories:
            parts.append(led.prometheus_text())
        for _name, slo in slos:
            parts.append(slo.prometheus_text())
        for kname, store in kvstores:
            # namespaced per attachment so two attached stores cannot
            # collide in one exposition; the user-supplied name is
            # sanitized — one bad character would make the WHOLE
            # exposition unparseable, not just this store's family
            safe = re.sub(r"[^a-zA-Z0-9_]", "_", kname)
            parts.append(store.prometheus_text(
                namespace=f"paddle_tpu_kvstore_{safe}"))
        from .utils.stats import StatRegistry, prometheus_text as _pt
        parts.append(_pt(
            StatRegistry(), namespace="paddle_tpu_ops",
            extra_gauges={
                "uptime_seconds": time.monotonic() - self._started_at,
                "last_activity_age_seconds": self.last_activity_age_s(),
                "sources": len(tracers) + len(engines) + len(ledgers)}))
        return "".join(parts)

    def _render_healthz(self, run_probe: bool = False
                        ) -> Tuple[Dict[str, Any], bool]:
        age = self.last_activity_age_s()
        ok = age <= self.stall_threshold_s
        out: Dict[str, Any] = {
            "last_step_age_s": round(age, 3),
            "stall_threshold_s": self.stall_threshold_s,
            "stalled": not ok,
        }
        if run_probe:
            probe = compute_probe(self.probe_timeout_s)
            out["probe"] = probe
            ok = ok and bool(probe.get("ok"))
        out["ok"] = ok
        return out, ok

    def _render_ledger(self) -> Optional[Dict[str, Any]]:
        _, _, ledgers = self._sources()
        if not ledgers:
            return None
        if len(ledgers) == 1:
            return ledgers[0][1].snapshot()
        return {name: led.snapshot() for name, led in ledgers}

    def _render_memory(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            memories = list(self._memories)
        if not memories:
            return None
        if len(memories) == 1:
            return memories[0][1].memory_snapshot()
        return {name: ml.memory_snapshot() for name, ml in memories}

    def _render_gateway(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            gateways = list(self._gateways)
        if not gateways:
            return None
        if len(gateways) == 1:
            return gateways[0][1].gateway_snapshot()
        return {name: gw.gateway_snapshot() for name, gw in gateways}

    def _render_trace(self, n: int, kind: Optional[str]) -> Dict[str, Any]:
        tracers, _, _ = self._sources()
        n = max(1, min(int(n), 65536))
        events: Dict[str, List[Dict[str, Any]]] = {}
        for name, tr in tracers:
            evs = tr.events(kind) if kind else tr.events()
            events[name] = evs[-n:]
        return {"n": n, "kind": kind, "events": events}

    def _trace_index(self):
        """A fresh request-trace stitcher over every attached tracer —
        a pure pull reader of their bounded rings, so building one per
        request costs nothing beyond the scan it was going to do.

        Attached gateways contribute their CURRENT replicas' engine
        tracers, enumerated per query rather than snapshotted at
        ``attach()`` — a drain-swapped replacement replica shows up in
        ``/request/<id>`` without re-attaching anything."""
        from .telemetry import RequestTraceIndex
        tracers, _, _ = self._sources()
        with self._lock:
            gateways = list(self._gateways)
        seen = {id(tr) for _name, tr in tracers}
        for base, gw in gateways:
            enumerate_tracers = getattr(gw, "replica_tracers", None)
            if enumerate_tracers is None:
                continue
            for rname, tr in enumerate_tracers():
                if id(tr) not in seen:
                    seen.add(id(tr))
                    tracers.append((f"{base}.{rname}", tr))
        idx = RequestTraceIndex()
        for name, tr in tracers:
            try:
                idx.add_source(tr, name)
            except TypeError:
                pass                    # source without a usable ring
        return idx

    def _render_requests(self, n: int) -> Dict[str, Any]:
        n = max(1, min(int(n), 4096))
        return {"n": n, "requests": self._trace_index().recent(n)}

    def _render_request(self, trace_id: str) -> Optional[Dict[str, Any]]:
        if not trace_id:
            return None
        return self._trace_index().trace(trace_id)

    def _render_resilience(self) -> Optional[Dict[str, Any]]:
        """Resilience views of attached gateways; None when no attached
        gateway has a resilience policy (their ``resilience_snapshot``
        returns None)."""
        with self._lock:
            gateways = list(self._gateways)
        views = []
        for name, gw in gateways:
            snap_fn = getattr(gw, "resilience_snapshot", None)
            if snap_fn is None:
                continue
            snap = snap_fn()
            if snap is not None:
                views.append((name, snap))
        if not views:
            return None
        if len(views) == 1:
            return views[0][1]
        return dict(views)

    def _render_slo(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            slos = list(self._slos)
        if not slos:
            return None
        if len(slos) == 1:
            return slos[0][1].snapshot()
        return {name: slo.snapshot() for name, slo in slos}

    def _render_kvstore(self) -> Optional[Dict[str, Any]]:
        """KV-tiering views: every attached gateway with a live KV
        surface (roles, stores, or migration traffic) plus directly
        attached stores; None when nothing KV-tiered is attached."""
        with self._lock:
            gateways = list(self._gateways)
            kvstores = list(self._kvstores)
        views: Dict[str, Any] = {}
        for name, gw in gateways:
            snap_fn = getattr(gw, "kvstore_snapshot", None)
            surface = getattr(gw, "has_kv_surface", None)
            if snap_fn is None:
                continue
            if surface is not None and not surface():
                continue
            views[name] = snap_fn()
        for name, store in kvstores:
            views[name] = store.snapshot()
        if not views:
            return None
        if len(views) == 1:
            return next(iter(views.values()))
        return views

    def _render_autoscaler(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            autoscalers = list(self._autoscalers)
        if not autoscalers:
            return None
        if len(autoscalers) == 1:
            return autoscalers[0][1].autoscaler_snapshot()
        return {name: asc.autoscaler_snapshot()
                for name, asc in autoscalers}

    def _render_fleet(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            fleets = list(self._fleets)
        if not fleets:
            return None
        if len(fleets) == 1:
            return fleets[0][1].fleet_snapshot()
        return {name: fc.fleet_snapshot() for name, fc in fleets}

    def _render_train(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            trains = list(self._trains)
        if not trains:
            return None
        if len(trains) == 1:
            return trains[0][1].train_snapshot()
        return {name: sup.train_snapshot() for name, sup in trains}

    #: JSON routes a FleetCollector scrapes, mapped to their renderers —
    #: the in-process (server=) scrape path of ``render()``
    _RENDERS = {"/metrics": "_render_metrics",
                "/ledger": "_render_ledger",
                "/slo": "_render_slo",
                "/gateway": "_render_gateway",
                "/kvstore": "_render_kvstore",
                "/memory": "_render_memory",
                "/autoscaler": "_render_autoscaler",
                "/resilience": "_render_resilience",
                "/fleet": "_render_fleet",
                "/train": "_render_train"}

    def render(self, route: str):
        """Render one scrape surface WITHOUT a socket: the text
        exposition for ``/metrics``, the JSON payload (or ``None`` when
        nothing of that kind is attached — the 404 case) for the other
        scrapeable routes.  This is how a ``FleetCollector`` federates an
        in-process server (``add_target(name, server=srv)``) — bench and
        the sim fleet scrape unstarted servers through it, so no test or
        benchmark needs to bind a port to get fleet rollups."""
        fn = self._RENDERS.get(route)
        if fn is None:
            raise ValueError(f"unrenderable route {route!r} "
                             f"(want one of {sorted(self._RENDERS)})")
        return getattr(self, fn)()
