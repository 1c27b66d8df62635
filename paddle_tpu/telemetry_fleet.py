"""Fleet observability plane: cross-process telemetry federation, a
durable metric spool, and fleet-level rollups.

Every observability layer so far — tracer rings (PR 8), the goodput
ledger (PR 7), SLO burn (PR 10), the memory ledger (PR 17) — lives in
ONE process and dies with it.  :class:`FleetCollector` is the pull-based
federation plane over the per-process ops endpoints
(:class:`~paddle_tpu.ops_server.OpsServer`): it scrapes N targets'
``/metrics`` + JSON surfaces on an interval, parses the Prometheus text
itself (ONE parser, round-trip-tested against every emitter family so
emitters and parser cannot drift), spools every sample to disk so metric
history finally survives process death, and computes **fleet rollups**
no single process can see:

- **global goodput** — fleet compute-seconds over fleet elapsed-seconds,
  the same merge discipline as ``RunLedger.aggregate`` (PR 7), computed
  from the scraped ``/ledger`` snapshots;
- **fleet MFU** — per-target MFU gauges weighted by each target's costed
  wall (``model_flops_wall_seconds``), so an idle replica cannot dilute
  the fleet number;
- **merged TTFT/ITL percentiles** — each target's ``/slo`` response
  carries its time-bucketed :class:`~paddle_tpu.telemetry_slo
  .PercentileSketch` es serialized (``sketch_buckets``); the collector
  reconstructs and **merges** them (the DDSketch merge that motivated
  the log-bucketed design), so ``fleet ttft_p99`` is a real quantile of
  the union of samples, not an average of per-replica quantiles;
- **straggler skew** — max per-target compute-seconds over the mean
  (1.0 = perfectly balanced), mirroring the cross-replica accounting of
  ``fleet.metrics.all_reduce_metrics`` at the ops layer;
- **fleet SLO burn** — an internal :class:`~paddle_tpu.telemetry_slo
  .SLOMonitor` on the collector's clock re-runs the multi-window
  burn-rate machinery over the MERGED series: closed sketch buckets
  feed ``ttft_s``/``itl_s`` exactly once (per-target bucket cursors
  dedup re-scrapes), and every scrape observes the scalar rollups
  (``goodput_global``, ``tokens_per_s``, …) — a ``floor`` objective on
  ``tokens_per_s`` IS the fleet throughput-regression detector.

**Scrape semantics.**  Each target is scraped with a per-target timeout;
a failing target backs off exponentially (bounded by
``backoff_max_s``) and is marked — never silently merged:

- ``ok``      — scraped successfully within ``stale_after_s``;
- ``stale``   — previously healthy, but the last good scrape is older
  than ``stale_after_s``: its data is EXCLUDED from every rollup and
  the gap is labeled in the snapshot (status, age, consecutive
  failures, last error);
- ``down``    — never scraped successfully.

**The spool.**  :class:`TelemetrySpool` is an append-only JSONL segment
store (``spool-<n>.jsonl``): size-based rotation at ``segment_bytes``,
retention capped at ``max_segments`` (oldest deleted), every record
stamped with a monotonic ``seq``.  Restart resumes the open segment:
a torn tail line (crash mid-write) is truncated, ``seq`` continues from
the last durable record — no duplicates, no silently lost durable
samples.  It is the time-series complement of the FlightRecorder's
point-in-time dumps; the collector itself is a FlightRecorder source
(``to_dict`` → last fleet snapshot + spool tail as ``fleet.json``).

**Surfaces.**  ``GET /fleet`` on an :class:`OpsServer` the collector is
attached to; ``paddle_tpu_fleet_*`` federation gauges on the
collector's own ``prometheus_text`` (per-target ``up``/age/goodput/
TTFT labeled gauges + the rollups); ``tools/fleet_top.py`` renders the
same ``fleet_snapshot()`` as a live terminal dashboard; and
:func:`replay_regressions` re-runs the burn-rate machinery over spooled
rollup records post-hoc — the offline regression detector.

Targets come in three transports, all sharing one scrape path:

- ``url=``     a live ops endpoint scraped over HTTP (stdlib urllib,
  per-request timeout);
- ``server=``  an in-process :class:`OpsServer` (rendered directly, no
  socket — what the sim fleet uses);
- ``fetch=``   a callable ``fetch(path) -> str | dict | None`` (the
  fake-clock test harness; ``None`` = endpoint absent).

Zero cost when absent: nothing in the serving/train hot paths knows the
collector exists — it is a pure pull reader over surfaces that were
already being exported, so engine/train lowerings are byte-identical
with or without one (the PR 2 off-path discipline, pinned by test).

The clock is injectable (``clock=``): scrape cadence, staleness and
burn-rate lifecycles are all testable on a fake clock with no sleeps.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
import urllib.error
import urllib.request
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Tuple)

from .telemetry_slo import Objective, PercentileSketch, SLOMonitor
from .utils.stats import StatRegistry, prom_sample, prometheus_text

__all__ = ["FleetCollector", "TelemetrySpool", "ParsedSample",
           "parse_prometheus_text", "replay_regressions"]


# --------------------------------------------------------------------------
# Prometheus text parser (the emitter's inverse — utils/stats.py)
# --------------------------------------------------------------------------

class ParsedSample(NamedTuple):
    """One exposition sample: metric name, label dict (string values,
    insertion order preserved — the emitter's order), float value."""
    name: str
    labels: Dict[str, str]
    value: float


#: ``name{labels} value`` / ``name value`` — names as the emitter's
#: ``_prom_name`` sanitizer produces them.
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$')
#: one label pair; the value body is any run of non-quote/non-backslash
#: chars or escape pairs — the exact language ``prom_escape_label`` emits.
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape_label(value: str) -> str:
    """Inverse of ``utils.stats.prom_escape_label``: ``\\\\`` → ``\\``,
    ``\\"`` → ``"``, ``\\n`` → newline, left to right."""
    out: List[str] = []
    i, n = 0, len(value)
    while i < n:
        c = value[i]
        if c == "\\" and i + 1 < n:
            nxt = value[i + 1]
            if nxt == "\\":
                out.append("\\")
                i += 2
                continue
            if nxt == '"':
                out.append('"')
                i += 2
                continue
            if nxt == "n":
                out.append("\n")
                i += 2
                continue
        out.append(c)
        i += 1
    return "".join(out)


def parse_prometheus_text(text: str) -> Dict[str, Any]:
    """Parse one text exposition (format 0.0.4, the dialect every
    ``prometheus_text`` emitter in this tree produces through
    ``utils.stats.prom_sample``) into::

        {"samples": [ParsedSample, ...],      # exposition order
         "types":   {metric_name: kind},      # from # TYPE lines
         "errors":  [unparseable line, ...]}  # never raises mid-scrape

    Unparseable lines are collected, not raised — one corrupt line from
    a half-written response must not void the rest of the scrape."""
    samples: List[ParsedSample] = []
    types: Dict[str, str] = {}
    errors: List[str] = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(line)
            continue
        name, label_body, raw = m.group(1), m.group(2), m.group(3)
        labels: Dict[str, str] = {}
        if label_body:
            consumed = 0
            for lm in _LABEL_RE.finditer(label_body):
                labels[lm.group(1)] = _unescape_label(lm.group(2))
                consumed += 1
            if consumed == 0 and label_body.strip():
                errors.append(line)
                continue
        try:
            value = float(raw)
        except ValueError:
            errors.append(line)
            continue
        samples.append(ParsedSample(name, labels, value))
    return {"samples": samples, "types": types, "errors": errors}


def render_sample(sample: ParsedSample) -> str:
    """Re-render one parsed sample through the shared emitter helper —
    the round-trip the drift-guard test pins: for every line an emitter
    produced, ``render_sample(parse(line)) == line``."""
    return prom_sample(sample.name, sample.value, sample.labels or None)


# --------------------------------------------------------------------------
# durable spool
# --------------------------------------------------------------------------

_SEGMENT_RE = re.compile(r"^spool-(\d{8})\.jsonl$")


class TelemetrySpool:
    """Append-only JSONL segment spool (module docstring): size-based
    rotation, retention caps, crash-safe resume.  Records are dicts; the
    spool stamps each with a monotonic ``seq`` that survives restart —
    the no-duplicate/no-loss contract the fleet test pins."""

    def __init__(self, directory: str, *, segment_bytes: int = 262144,
                 max_segments: int = 8,
                 logger: Optional[logging.Logger] = None):
        if int(segment_bytes) < 1024:
            raise ValueError("segment_bytes must be >= 1024")
        if int(max_segments) < 2:
            raise ValueError("max_segments must be >= 2 (rotation needs "
                             "a current segment plus at least one kept)")
        self.directory = str(directory)
        self.segment_bytes = int(segment_bytes)
        self.max_segments = int(max_segments)
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        os.makedirs(self.directory, exist_ok=True)
        # append/rotate/retention and the seq counter are driven from the
        # scrape thread while /fleet handlers call tail()/segments()
        self._lock = threading.Lock()
        self._seq = 0                 # guarded-by: _lock
        self._seg_index = 1           # guarded-by: _lock
        self._seg_bytes = 0           # guarded-by: _lock
        self._fh = None               # guarded-by: _lock
        self._resume()

    # ------------------------------------------------------------ resume --

    def _segment_paths(self) -> List[Tuple[int, str]]:
        out = []
        for fn in os.listdir(self.directory):
            m = _SEGMENT_RE.match(fn)
            if m:
                out.append((int(m.group(1)),
                            os.path.join(self.directory, fn)))
        out.sort()
        return out

    def _resume(self):
        """Crash-safe resume: repair a torn tail line on the newest
        segment (truncate — the record was never durable), recover the
        last durable ``seq``, and continue appending to that segment
        when it is still under the size cap."""
        segments = self._segment_paths()
        if not segments:
            return
        idx, path = segments[-1]
        with open(path, "rb") as f:
            data = f.read()
        good = data
        if data:
            if not data.endswith(b"\n"):
                cut = data.rfind(b"\n")
                good = data[:cut + 1] if cut >= 0 else b""
            # a torn write that DID land its newline still shows up as
            # unparseable JSON on the final line — drop it the same way
            while good:
                last = good[:-1].rfind(b"\n")
                tail = good[last + 1:]
                try:
                    json.loads(tail)
                    break
                except ValueError:
                    good = good[:last + 1] if last >= 0 else b""
        if good != data:
            self._log.warning(
                "telemetry spool: truncating torn tail of %s "
                "(%d -> %d bytes)", path, len(data), len(good))
            with open(path, "wb") as f:
                f.write(good)
        # the last durable seq across every surviving segment
        for _idx, p in reversed(segments):
            last_rec = None
            try:
                with open(p, "r") as f:
                    for line in f:
                        line = line.strip()
                        if line:
                            last_rec = line
            except OSError:
                continue
            if last_rec is not None:
                try:
                    self._seq = int(json.loads(last_rec).get("seq", 0))
                    break
                except (ValueError, TypeError):
                    continue
        size = os.path.getsize(path)
        if size < self.segment_bytes:
            self._seg_index = idx
            self._seg_bytes = size
        else:
            self._seg_index = idx + 1
            self._seg_bytes = 0

    # ------------------------------------------------------------ append --

    def _segment_path(self, idx: int) -> str:
        return os.path.join(self.directory, f"spool-{idx:08d}.jsonl")

    def _open_locked(self):
        if self._fh is None:
            self._fh = open(self._segment_path(self._seg_index), "a")
            self._seg_bytes = self._fh.tell()

    def _rotate_locked(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        self._seg_index += 1
        self._seg_bytes = 0
        # retention: drop oldest beyond the cap (the current, about-to-
        # open segment counts toward it)
        segments = self._segment_paths()
        excess = len(segments) + 1 - self.max_segments
        for _idx, path in segments[:max(excess, 0)]:
            try:
                os.remove(path)
            except OSError as e:
                self._log.warning("telemetry spool: retention unlink "
                                  "failed for %s: %r", path, e)

    def append(self, record: Dict[str, Any]) -> int:
        """Write one record (stamped ``seq``), flushed to the OS before
        returning — a record handed back as appended is durable against
        process death (fsync is deliberately NOT paid per record; the
        spool is telemetry, not a WAL)."""
        with self._lock:
            if self._seg_bytes >= self.segment_bytes:
                self._rotate_locked()
            self._open_locked()
            self._seq += 1
            rec = dict(record)
            rec["seq"] = self._seq
            line = json.dumps(rec) + "\n"
            self._fh.write(line)
            self._fh.flush()
            self._seg_bytes += len(line)
            return self._seq

    def close(self):
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    # ------------------------------------------------------------- reads --

    def records(self) -> List[Dict[str, Any]]:
        """Every durable record, oldest first (bounded by retention)."""
        with self._lock:
            if self._fh is not None:
                self._fh.flush()
        out: List[Dict[str, Any]] = []
        for _idx, path in self._segment_paths():
            try:
                with open(path, "r") as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            pass          # torn tail of a live segment
            except OSError:
                continue
        return out

    def tail(self, n: int = 64) -> List[Dict[str, Any]]:
        return self.records()[-max(int(n), 1):]

    def stats(self) -> Dict[str, Any]:
        segments = self._segment_paths()
        with self._lock:
            seq = self._seq
        return {"directory": self.directory,
                "segments": len(segments),
                "bytes": sum(os.path.getsize(p) for _i, p in segments),
                "segment_bytes": self.segment_bytes,
                "max_segments": self.max_segments,
                "seq": seq}


# --------------------------------------------------------------------------
# collector
# --------------------------------------------------------------------------

#: the per-process ops surfaces one scrape covers; /metrics is the one
#: REQUIRED endpoint (its failure fails the scrape), the JSON surfaces
#: are optional per target (a train host has no /gateway — absence is
#: normal, not an error).
SCRAPE_ENDPOINTS = ("/metrics", "/ledger", "/slo", "/gateway",
                    "/kvstore", "/memory", "/autoscaler")


class _Target:
    """One scrape target's state.  Mutated only under the collector's
    lock (scrape thread vs /fleet + /metrics handler threads)."""

    __slots__ = ("name", "url", "server", "fetch", "last_ok_at",
                 "last_attempt_at", "failures", "backoff_until", "error",
                 "metrics", "endpoints", "prev_tokens", "tokens_per_s",
                 "bucket_cursors", "scrapes")

    def __init__(self, name: str, url: Optional[str],
                 server: Any, fetch: Optional[Callable[[str], Any]]):
        self.name = name
        self.url = url
        self.server = server
        self.fetch = fetch
        self.last_ok_at: Optional[float] = None
        self.last_attempt_at: Optional[float] = None
        self.failures = 0
        self.backoff_until: Optional[float] = None
        self.error: Optional[str] = None
        self.metrics: Dict[str, Any] = {"samples": [], "types": {}}
        self.endpoints: Dict[str, Any] = {}
        self.prev_tokens: Optional[Tuple[float, float]] = None
        self.tokens_per_s: Optional[float] = None
        # per-metric sketch-bucket cursor: newest bucket key already
        # merged into the fleet SLO feed — the exactly-once dedup that
        # keeps overlapping scrapes from double-counting samples
        self.bucket_cursors: Dict[str, float] = {}
        self.scrapes = 0


class FleetCollector:
    """Cross-process telemetry federation (module docstring).

    ``interval_s`` paces the background loop (``start()``); with an
    injectable ``clock`` tests drive ``scrape_once(now)`` directly.
    ``stale_after_s`` (default ``3 * interval_s``) is the labeled-gap
    window; ``timeout_s`` bounds each HTTP request; failures back off
    exponentially from ``interval_s`` up to ``backoff_max_s``.
    ``objectives`` seed the internal fleet :class:`SLOMonitor` (burn on
    the merged series — the live regression detector); ``spool_dir``
    enables the durable spool."""

    def __init__(self, *, interval_s: float = 5.0, timeout_s: float = 2.0,
                 stale_after_s: Optional[float] = None,
                 backoff_max_s: float = 60.0,
                 spool_dir: Optional[str] = None,
                 spool_segment_bytes: int = 262144,
                 spool_max_segments: int = 8,
                 objectives: Iterable[Objective] = (),
                 slo_resolution_s: Optional[float] = None,
                 clock: Optional[Callable[[], float]] = None,
                 logger: Optional[logging.Logger] = None):
        if float(interval_s) <= 0:
            raise ValueError("interval_s must be > 0")
        if float(timeout_s) <= 0:
            raise ValueError("timeout_s must be > 0")
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self.stale_after_s = (3.0 * self.interval_s
                              if stale_after_s is None
                              else float(stale_after_s))
        if self.stale_after_s <= 0:
            raise ValueError("stale_after_s must be > 0")
        self.backoff_max_s = float(backoff_max_s)
        self._clock = clock if clock is not None else time.monotonic
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        # targets / snapshot / merged sketches are written by the scrape
        # thread and read by ops-server handler threads (/fleet, the
        # federation gauges) and FlightRecorder dumps
        self._lock = threading.Lock()
        self._targets: Dict[str, _Target] = {}    # guarded-by: _lock
        self._snapshot: Optional[Dict[str, Any]] = None  # guarded-by: _lock
        self._scrapes = 0                         # guarded-by: _lock
        self.registry = StatRegistry()  # guarded-by: none (locks internally)
        # guarded-by: none (set once here; TelemetrySpool serializes its
        # own appends/reads under its private _lock)
        self.spool = (None if spool_dir is None else TelemetrySpool(
            spool_dir, segment_bytes=spool_segment_bytes,
            max_segments=spool_max_segments, logger=self._log))
        # the fleet burn/regression monitor rides the collector clock;
        # its resolution defaults to the scrape interval so one scrape
        # lands in one bucket
        self.slo = SLOMonitor(
            objectives, clock=self._clock,
            resolution_s=(self.interval_s if slo_resolution_s is None
                          else float(slo_resolution_s)),
            logger=self._log)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()  # guarded-by: none (Event is thread-safe)

    # ----------------------------------------------------------- targets --

    def add_target(self, name: str, url: Optional[str] = None, *,
                   server: Any = None,
                   fetch: Optional[Callable[[str], Any]] = None
                   ) -> "FleetCollector":
        """Register one scrape target under a unique ``name`` — exactly
        one transport: ``url`` (HTTP ops endpoint), ``server`` (an
        in-process :class:`OpsServer`, rendered without a socket), or
        ``fetch`` (a ``fetch(path)`` callable)."""
        given = [t for t in (url, server, fetch) if t is not None]
        if len(given) != 1:
            raise ValueError("add_target wants exactly one of url=, "
                             "server=, fetch=")
        if server is not None and not hasattr(server, "render"):
            raise TypeError(f"server= target must be an OpsServer-like "
                            f"object with .render(), got "
                            f"{type(server).__name__}")
        with self._lock:
            if name in self._targets:
                raise ValueError(f"target {name!r} already registered")
            self._targets[name] = _Target(
                str(name), None if url is None else url.rstrip("/"),
                server, fetch)
        return self

    def remove_target(self, name: str) -> bool:
        with self._lock:
            return self._targets.pop(name, None) is not None

    def targets(self) -> List[str]:
        with self._lock:
            return sorted(self._targets)

    # ------------------------------------------------------------ scrape --

    def _fetch_http(self, base: str, path: str) -> Any:
        req = urllib.request.Request(base + path,
                                     headers={"Accept": "*/*"})
        try:
            with urllib.request.urlopen(req,
                                        timeout=self.timeout_s) as resp:
                body = resp.read().decode("utf-8", "replace")
        except urllib.error.HTTPError as e:
            if e.code == 404:
                return None       # endpoint absent on this target: normal
            raise
        if path == "/metrics":
            return body
        return json.loads(body)

    def _fetch_one(self, tgt: _Target, path: str) -> Any:
        if tgt.url is not None:
            return self._fetch_http(tgt.url, path)
        if tgt.server is not None:
            return tgt.server.render(path)
        return tgt.fetch(path)

    def _scrape_target(self, tgt: _Target, now: float) -> bool:
        """Scrape every endpoint of one target; True on success.  Only
        ``/metrics`` is load-bearing — a JSON surface that errors is
        logged and skipped (absence of /gateway on a train host must
        not mark the host dead)."""
        try:
            text = self._fetch_one(tgt, "/metrics")
            if text is None:
                raise ValueError("target has no /metrics")
            parsed = parse_prometheus_text(text)
        except Exception as e:  # noqa: BLE001 — the verdict is recorded,
            # never raised: a dead target is a labeled gap
            self._on_failure(tgt, now, e)
            return False
        endpoints: Dict[str, Any] = {}
        for path in SCRAPE_ENDPOINTS[1:]:
            try:
                payload = self._fetch_one(tgt, path)
            except Exception as e:  # noqa: BLE001
                self._log.debug("fleet: %s%s failed: %r",
                                tgt.name, path, e)
                payload = None
            if payload is not None:
                endpoints[path.lstrip("/")] = payload
        with self._lock:
            tgt.metrics = parsed
            tgt.endpoints = endpoints
            tgt.last_ok_at = now
            tgt.failures = 0
            tgt.backoff_until = None
            tgt.error = None
            tgt.scrapes += 1
            self._update_tokens_locked(tgt, now)
        self.registry.add("scrapes_ok")
        return True

    def _on_failure(self, tgt: _Target, now: float, err: Exception):
        with self._lock:
            tgt.failures += 1
            tgt.error = repr(err)
            backoff = min(self.interval_s * (2.0 ** (tgt.failures - 1)),
                          self.backoff_max_s)
            tgt.backoff_until = now + backoff
        self.registry.add("scrape_errors")
        self._log.debug("fleet: scrape of %s failed (%d consecutive, "
                        "backoff %.1fs): %r", tgt.name, tgt.failures,
                        backoff, err)

    @staticmethod
    def _counter_sum(parsed: Dict[str, Any], suffix: str) -> float:
        return sum(s.value for s in parsed["samples"]
                   if s.name.endswith(suffix) and not s.labels)

    def _update_tokens_locked(self, tgt: _Target, now: float):
        """Per-target token throughput: delta of the token counters
        (serving ``tokens_emitted`` + train ``train_tokens``) between
        this scrape and the previous one, over the wall between them."""
        total = (self._counter_sum(tgt.metrics, "_tokens_emitted")
                 + self._counter_sum(tgt.metrics, "_train_tokens"))
        prev = tgt.prev_tokens
        tgt.prev_tokens = (now, total)
        if prev is None:
            tgt.tokens_per_s = None
            return
        prev_at, prev_total = prev
        dt = now - prev_at
        if dt <= 0:
            return
        # counter reset (restarted target) shows as a negative delta:
        # restart the rate from this scrape rather than report nonsense
        delta = total - prev_total
        tgt.tokens_per_s = (None if delta < 0 else delta / dt)

    def _status(self, tgt: _Target, now: float) -> str:
        if tgt.last_ok_at is None:
            return "down"
        if now - tgt.last_ok_at > self.stale_after_s:
            return "stale"
        return "ok"

    def scrape_once(self, now: Optional[float] = None) -> Dict[str, Any]:
        """One scrape round over every due target, then rollups: the
        fleet snapshot (also retained for ``fleet_snapshot()`` /
        ``GET /fleet``), spooled when a spool is configured."""
        now = self._clock() if now is None else float(now)
        with self._lock:
            targets = list(self._targets.values())
        self.registry.add("scrape_rounds")
        for tgt in targets:
            with self._lock:
                in_backoff = (tgt.backoff_until is not None
                              and now < tgt.backoff_until)
                tgt.last_attempt_at = now
            if in_backoff:
                continue
            self._scrape_target(tgt, now)
        snapshot = self._build_snapshot(now)
        with self._lock:
            self._scrapes += 1
            snapshot["scrapes"] = self._scrapes
            self._snapshot = snapshot
        if self.spool is not None:
            for row in snapshot["targets"]:
                self.spool.append({"kind": "target", "ts": now, **row})
            self.spool.append({"kind": "rollup", "ts": now,
                               **snapshot["rollup"]})
            snapshot["spool"] = self.spool.stats()
        return snapshot

    # ----------------------------------------------------------- rollups --

    @staticmethod
    def _target_sketches(tgt: _Target) -> Dict[str, PercentileSketch]:
        """Reconstruct one target's per-metric sketches by merging every
        serialized sketch bucket from its last /slo response."""
        slo = tgt.endpoints.get("slo") or {}
        buckets = (slo.get("sketch_buckets") or {}).get("metrics") or {}
        out: Dict[str, PercentileSketch] = {}
        for metric, per_key in buckets.items():
            merged = None
            for _key, blob in per_key.items():
                sk = PercentileSketch.from_dict(blob)
                merged = sk if merged is None else merged.merge(sk)
            if merged is not None and merged.n:
                out[metric] = merged
        return out

    def _feed_slo_locked(self, tgt: _Target, now: float):
        """Exactly-once feed of CLOSED sketch buckets into the fleet SLO
        monitor: buckets newer than the target's cursor and older than
        one resolution (still-filling buckets wait for the next scrape)
        merge into the fleet series; the cursor advances."""
        slo = tgt.endpoints.get("slo") or {}
        export = slo.get("sketch_buckets") or {}
        res = float(export.get("resolution_s") or 0.0)
        for metric, per_key in (export.get("metrics") or {}).items():
            cursor = tgt.bucket_cursors.get(metric)
            newest_merged = cursor
            for key_s, blob in per_key.items():
                key = float(key_s)
                if cursor is not None and key <= cursor:
                    continue
                if res > 0 and key + res > float(slo.get("now", now)):
                    continue                    # still filling
                self.slo.observe_sketch(
                    metric, PercentileSketch.from_dict(blob), now=now)
                if newest_merged is None or key > newest_merged:
                    newest_merged = key
            if newest_merged is not None:
                tgt.bucket_cursors[metric] = newest_merged

    def _build_snapshot(self, now: float) -> Dict[str, Any]:
        with self._lock:
            targets = list(self._targets.values())
            rows: List[Dict[str, Any]] = []
            ok_rows: List[Tuple[_Target, Dict[str, Any]]] = []
            for tgt in targets:
                status = self._status(tgt, now)
                ledger = tgt.endpoints.get("ledger") or {}
                gw = tgt.endpoints.get("gateway") or {}
                resil = gw.get("resilience") or {}
                occ = gw.get("occupancy") or {}
                sketches = self._target_sketches(tgt)
                ttft = sketches.get("ttft_s")
                mfu_samples = [s.value for s in tgt.metrics["samples"]
                               if s.name.endswith("_mfu")
                               and not s.labels]
                row = {
                    "target": tgt.name,
                    "status": status,
                    "url": tgt.url,
                    "age_s": (None if tgt.last_ok_at is None
                              else round(now - tgt.last_ok_at, 3)),
                    "scrapes": tgt.scrapes,
                    "consecutive_failures": tgt.failures,
                    "error": tgt.error,
                    "goodput": ledger.get("goodput"),
                    "compute_s": (ledger.get("buckets_s")
                                  or {}).get("compute"),
                    "elapsed_s": ledger.get("elapsed_s"),
                    "mfu": (max(mfu_samples) if mfu_samples else None),
                    "ttft_p99": (ttft.quantile(0.99) if ttft else None),
                    "ttft_p50": (ttft.quantile(0.50) if ttft else None),
                    "tokens_per_s": tgt.tokens_per_s,
                    "occupancy": occ.get("value"),
                    "queued": occ.get("queued"),
                    "breakers_open": resil.get("breakers_open"),
                    "brownout_level": resil.get("brownout_level"),
                }
                rows.append(row)
                if status == "ok":
                    ok_rows.append((tgt, row))
                    self._feed_slo_locked(tgt, now)
            # ---- merged percentiles over the healthy targets only: a
            # stale target's last sketches must not haunt the rollup
            merged: Dict[str, PercentileSketch] = {}
            for tgt, _row in ok_rows:
                for metric, sk in self._target_sketches(tgt).items():
                    if metric in merged:
                        merged[metric].merge(sk)
                    else:
                        fresh = PercentileSketch(alpha=sk.alpha)
                        merged[metric] = fresh.merge(sk)
        computes = [r["compute_s"] for _t, r in ok_rows
                    if r["compute_s"] is not None]
        elapsed = [r["elapsed_s"] for _t, r in ok_rows
                   if r["elapsed_s"] is not None
                   and r["compute_s"] is not None]
        goodput_global = (sum(computes) / max(sum(elapsed), 1e-9)
                          if computes and elapsed else None)
        skew = None
        if len(computes) >= 2 and sum(computes) > 0:
            skew = max(computes) / (sum(computes) / len(computes))
        # fleet MFU: per-target MFU weighted by its costed wall so idle
        # targets cannot dilute the number; unweighted mean as fallback
        mfu_rows = []
        for tgt, row in ok_rows:
            if row["mfu"] is None:
                continue
            wall = self._counter_sum(tgt.metrics,
                                     "_model_flops_wall_seconds")
            mfu_rows.append((row["mfu"], wall))
        fleet_mfu = None
        if mfu_rows:
            wsum = sum(w for _m, w in mfu_rows)
            if wsum > 0:
                fleet_mfu = sum(m * w for m, w in mfu_rows) / wsum
            else:
                fleet_mfu = sum(m for m, _w in mfu_rows) / len(mfu_rows)
        rates = [r["tokens_per_s"] for _t, r in ok_rows
                 if r["tokens_per_s"] is not None]
        ttft_m = merged.get("ttft_s")
        itl_m = merged.get("itl_s")
        rollup = {
            "targets": len(rows),
            "targets_ok": sum(1 for r in rows if r["status"] == "ok"),
            "targets_stale": sum(1 for r in rows
                                 if r["status"] == "stale"),
            "targets_down": sum(1 for r in rows
                                if r["status"] == "down"),
            "goodput_global": goodput_global,
            "fleet_mfu": fleet_mfu,
            "fleet_ttft_p99": (ttft_m.quantile(0.99) if ttft_m else None),
            "fleet_ttft_p50": (ttft_m.quantile(0.50) if ttft_m else None),
            "fleet_itl_p99": (itl_m.quantile(0.99) if itl_m else None),
            "straggler_skew": skew,
            "tokens_per_s": (sum(rates) if rates else None),
        }
        # the scalar rollup series feed the fleet burn monitor — a
        # floor objective on any of these is a live regression detector
        for metric, value in (("goodput_global", goodput_global),
                              ("tokens_per_s", rollup["tokens_per_s"]),
                              ("fleet_mfu", fleet_mfu),
                              ("straggler_skew", skew)):
            if value is not None:
                self.slo.observe(metric, float(value), now=now)
        slo_rows = self.slo.evaluate(now)
        return {
            "now": now,
            "interval_s": self.interval_s,
            "stale_after_s": self.stale_after_s,
            "targets": rows,
            "rollup": rollup,
            "slo": {"status": slo_rows,
                    "alerts_firing": sum(1 for r in slo_rows
                                         if r["state"] == "firing")},
        }

    # ---------------------------------------------------------- surfaces --

    def fleet_snapshot(self) -> Dict[str, Any]:
        """The last scrape's snapshot — what ``GET /fleet`` serves and
        ``tools/fleet_top.py`` renders (one snapshot, two views).  A
        collector that never scraped reports its configuration and an
        empty target list rather than erroring."""
        with self._lock:
            if self._snapshot is not None:
                snap = dict(self._snapshot)
            else:
                snap = {"now": None, "scrapes": 0,
                        "interval_s": self.interval_s,
                        "stale_after_s": self.stale_after_s,
                        "targets": [],
                        "rollup": {"targets": len(self._targets),
                                   "targets_ok": 0, "targets_stale": 0,
                                   "targets_down": len(self._targets)},
                        "slo": None}
        if self.spool is not None:
            snap["spool"] = self.spool.stats()
        return snap

    def to_dict(self) -> Dict[str, Any]:
        """FlightRecorder source contract: the crash dump's ``fleet.json``
        — last fleet snapshot plus the spool tail, so a post-mortem
        shows what the rest of the fleet looked like."""
        out = {"snapshot": self.fleet_snapshot()}
        if self.spool is not None:
            out["spool_tail"] = self.spool.tail(64)
        return out

    def prometheus_text(self, namespace: str = "paddle_tpu_fleet") -> str:
        """The federation gauges: rollups plus per-target labeled
        ``up``/staleness/goodput/TTFT gauges — what a meta-collector one
        level up would scrape."""
        snap = self.fleet_snapshot()
        lines = [prometheus_text(self.registry, namespace=namespace)
                 .rstrip("\n")]
        rollup = snap.get("rollup") or {}
        for key in ("targets", "targets_ok", "targets_stale",
                    "targets_down", "goodput_global", "fleet_mfu",
                    "fleet_ttft_p99", "fleet_itl_p99", "straggler_skew",
                    "tokens_per_s"):
            v = rollup.get(key)
            if v is not None:
                lines.append(f"# TYPE {namespace}_{key} gauge")
                lines.append(prom_sample(f"{namespace}_{key}", v))
        per_target = (("up", lambda r: 1.0 if r["status"] == "ok"
                       else 0.0),
                      ("age_seconds", lambda r: r["age_s"]),
                      ("goodput", lambda r: r["goodput"]),
                      ("ttft_p99_seconds", lambda r: r["ttft_p99"]),
                      ("tokens_per_second", lambda r: r["tokens_per_s"]))
        for suffix, get in per_target:
            rows = [(r["target"], get(r)) for r in snap.get("targets", [])]
            rows = [(t, v) for t, v in rows if v is not None]
            if not rows:
                continue
            lines.append(f"# TYPE {namespace}_target_{suffix} gauge")
            for target, v in rows:
                lines.append(prom_sample(f"{namespace}_target_{suffix}",
                                         v, {"target": target}))
        spool = snap.get("spool")
        if spool is not None:
            for key in ("segments", "bytes", "seq"):
                lines.append(f"# TYPE {namespace}_spool_{key} gauge")
                lines.append(prom_sample(f"{namespace}_spool_{key}",
                                         spool[key]))
        return "\n".join(lines) + "\n"

    # --------------------------------------------------------- lifecycle --

    def start(self) -> "FleetCollector":
        """Scrape on a daemon thread every ``interval_s`` (real-clock
        deployments; fake-clock tests call ``scrape_once`` directly)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    self.scrape_once()
                except Exception:  # noqa: BLE001 — the loop must survive
                    # any one broken scrape round
                    self._log.exception("fleet: scrape round failed")
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="fleet-collector")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=5)
        if self.spool is not None:
            self.spool.close()


# --------------------------------------------------------------------------
# offline regression detection over a spool
# --------------------------------------------------------------------------

def replay_regressions(records: Iterable[Dict[str, Any]],
                       objectives: Iterable[Objective], *,
                       resolution_s: float = 5.0,
                       horizon_s: float = 3600.0) -> Dict[str, Any]:
    """Re-run the multi-window burn-rate machinery over spooled
    ``rollup`` records (``TelemetrySpool.records()`` or any JSONL tail):
    every numeric rollup field becomes a sample series named after the
    field (``tokens_per_s``, ``goodput_global``, …) at its recorded
    ``ts``, the objectives are evaluated at each step, and the final
    snapshot (status rows + every transition fired during the replay) is
    returned — the offline complement of the collector's live fleet SLO
    monitor, e.g. a ``floor`` objective on ``tokens_per_s`` firing on a
    throughput drop between scrape windows."""
    rollups = [r for r in records if r.get("kind") == "rollup"
               and r.get("ts") is not None]
    rollups.sort(key=lambda r: float(r["ts"]))
    last_ts = float(rollups[-1]["ts"]) if rollups else 0.0
    mon = SLOMonitor(objectives, clock=lambda: last_ts,
                     resolution_s=resolution_s, horizon_s=horizon_s)
    for rec in rollups:
        ts = float(rec["ts"])
        for key, value in rec.items():
            if key in ("kind", "ts", "seq") or value is None:
                continue
            if isinstance(value, (int, float)) \
                    and not isinstance(value, bool):
                mon.observe(key, float(value), now=ts)
        mon.evaluate(ts)
    snap = mon.snapshot(last_ts)
    snap["replayed_records"] = len(rollups)
    return snap
