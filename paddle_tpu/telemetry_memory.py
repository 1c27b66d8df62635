"""Memory ledger: exhaustive byte attribution for device and host pools.

The goodput ledger (``telemetry_ledger.RunLedger``) answers *where did the
wall clock go*; nothing answered *where did the bytes go*.  The system
makes byte claims it could not measure — weight-update sharding pins a
">=1.8x opt-HBM reduction" analytically (arXiv:2004.13336), the tiered KV
store migrates pages HBM → DRAM → disk with only page-count telemetry —
and the next scale-out tier (MPMD, multi-host transport) will debug OOMs
blind without live/peak bytes per pool.  :class:`MemoryLedger` partitions
bytes the way the goodput ledger partitions seconds:

==========================  ==============================================
pool                        bytes held by …
==========================  ==============================================
``params``                  model parameter trees (incl. buffers — BN
                            stats ride the model, not the optimizer)
``optimizer_state``         optimizer slot trees / fused flat shards
                            (incl. AMP scaler state)
``grads_comm_buffers``      gradient / collective staging state (EF
                            residuals, comm buffers)
``kv_pages``                paged-attention KV: per tier — ``hbm``
                            (device-resident caches), ``dram`` / ``disk``
                            (the TieredKVStore's host tiers)
``executables``             serialized compiled programs (the AOT
                            executable cache's blobs — a host-side proxy
                            for device code size)
``activations_workspace``   live intermediates registered explicitly by a
                            harness (activation stashes, microbatch
                            workspace)
``other``                   the residual — live arrays nothing registered
==========================  ==============================================

Two spaces, two source kinds:

- **device**: refreshed by :meth:`MemoryLedger.census` — ONE
  ``jax.live_arrays()`` walk classifying every live array by identity
  against the registered pytrees (trainers register state through
  ``register_train_state``; engines through ``attach_memory``), with
  addressable-shard bytes (what devices actually hold: a replicated array
  on R devices costs R×, a 1/R shard costs 1×) and per-device totals.
  The residual lands in ``other`` — the conservation invariant is
  ``sum(pool device bytes) == census total`` by construction, with
  over/under-registration *visible*, never silently clipped.
- **host**: event-driven ``account()`` deltas at the allocation sites
  (``kv_store`` tier transitions, the AOT cache's blob writes), mirrored
  per KV tier.

Peaks are ``set_max``-style watermarks (global per space and per pool);
every new watermark appends to a bounded ring and, with a tracer
attached, emits a ``memory`` event — so an OOM's approach survives in the
flight recorder.  :meth:`forensics` is the OOM post-mortem payload (top
pools, recent growth, largest arrays with tree paths, allocator stats);
``FlightRecorder`` writes it as a ``*-forensics.json`` section beside the
regular dump.

This module is the **single accounting point** for raw memory
introspection: ``jax.live_arrays()`` and PJRT ``memory_stats()`` calls
anywhere else are tpulint findings (``raw-memory-introspection``), the
same authority pattern as ``sharding_rules`` for ``PartitionSpec``.
Everything is zero-cost when no ledger is active: one ``is None`` check
per seam (:func:`current_memory_ledger` / :func:`account_bytes`).
"""

from __future__ import annotations

import collections
import json
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["MemoryLedger", "POOLS", "SPACES", "KV_TIERS",
           "set_active_memory_ledger", "current_memory_ledger",
           "account_bytes", "live_array_census", "device_allocator_stats",
           "chrome_counters_from_memory_dump"]

#: The exhaustive pool classification, in display order.  ``other`` is the
#: census residual — live arrays nothing registered — never written to
#: directly.
POOLS: Tuple[str, ...] = (
    "params", "optimizer_state", "grads_comm_buffers", "kv_pages",
    "executables", "activations_workspace", "other")

SPACES: Tuple[str, ...] = ("device", "host")

#: KV page tiers (kv_store.py's ladder): ``hbm`` is device space, the
#: host tiers mirror the TieredKVStore's DRAM/disk byte counters.
KV_TIERS: Tuple[str, ...] = ("hbm", "dram", "disk")

#: state-dict key → pool, for ``register_train_state`` (the trainer
#: builders' ``state0`` layout: jit/functional.py, distributed/*).
_STATE_KEY_POOL = {"params": "params", "buffers": "params",
                   "opt": "optimizer_state", "scaler": "optimizer_state",
                   "comm_e": "grads_comm_buffers"}

#: how many largest-array rows a census retains for forensics
_TOP_ARRAYS = 8


def _leaf_bytes(leaf) -> int:
    """Logical bytes of one array-like leaf (size × itemsize; sharded
    arrays count their global shape — the addressable view is computed
    separately in the census)."""
    import numpy as np
    if not hasattr(leaf, "dtype"):
        return 0
    item = np.dtype(leaf.dtype).itemsize
    shape = getattr(leaf, "shape", ())
    return int(np.prod(shape)) * item if shape else item


def _addressable_bytes(arr) -> int:
    """Bytes this process's devices actually hold for ``arr``: the sum of
    addressable shard bytes (replicated on R devices → R× logical; a 1/R
    shard → 1× logical).  Falls back to logical bytes for arrays without
    a shard view (committed single-device, numpy)."""
    shards = getattr(arr, "addressable_shards", None)
    if not shards:
        return _leaf_bytes(arr)
    total = 0
    for sh in shards:
        data = getattr(sh, "data", None)
        total += _leaf_bytes(data) if data is not None else 0
    return total


def live_array_census(groups: Dict[str, Any]) -> Dict[str, int]:
    """One ``jax.live_arrays()`` walk classifying every live array by
    identity into the named groups (``{name: pytree}``); unmatched arrays
    land in ``other``.  Returns ``{<name>_bytes: ..., other_bytes: ...,
    total_bytes: ..., arrays: ...}`` in logical bytes — the shared
    classifier behind ``TrainMonitor.hbm_census`` and
    :meth:`MemoryLedger.census` (this module is the single accounting
    point for the raw walk)."""
    import jax

    ids: Dict[str, set] = {}
    for name, tree in groups.items():
        ids[name] = {id(l) for l in jax.tree_util.tree_leaves(tree)
                     if hasattr(l, "dtype")}
    counts = {f"{name}_bytes": 0 for name in groups}
    counts["other_bytes"] = 0
    n_arrays = 0
    for a in jax.live_arrays():
        if getattr(a, "is_deleted", lambda: False)():
            continue
        n_arrays += 1
        b = _leaf_bytes(a)
        for name, idset in ids.items():
            if id(a) in idset:
                counts[f"{name}_bytes"] += b
                break
        else:
            counts["other_bytes"] += b
    counts["total_bytes"] = sum(counts.values())
    counts["arrays"] = n_arrays
    return counts


def device_allocator_stats(device_index: int = 0) -> Dict[str, int]:
    """Per-device allocator stats from the PJRT client (≙ the reference's
    STAT_gpu0_mem_size family fed by the CUDA allocator).  THE authority
    for the raw ``memory_stats()`` call — ``utils.stats
    .device_memory_stats`` delegates here; calling it anywhere else is a
    tpulint finding.  Empty dict when the backend exposes nothing (CPU)."""
    import jax
    devs = jax.local_devices()
    if device_index >= len(devs):
        return {}
    stats = devs[device_index].memory_stats() or {}
    return {k: int(v) for k, v in stats.items()}


class MemoryLedger:
    """Exhaustive byte attribution across :data:`POOLS` (module
    docstring).  ``capacity`` bounds the retained ``(ts, space, pool,
    bytes)`` sample series (the chrome counter track / flight-recorder
    payload); ``ring`` bounds the watermark-crossing event ring.  All
    mutation is under one lock; ``account`` is a dict add — cheap enough
    for per-page kv seams, and seams only reach it when a ledger is
    active."""

    def __init__(self, capacity: int = 4096, ring: int = 256,
                 tracer=None, logger: Optional[logging.Logger] = None):
        if capacity < 1 or ring < 1:
            raise ValueError("capacity and ring must be >= 1")
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._bytes: Dict[Tuple[str, str], int] = {
            (s, p): 0 for s in SPACES for p in POOLS}
        self._peak: Dict[Tuple[str, str], int] = dict(self._bytes)
        self._peak_total: Dict[str, int] = {s: 0 for s in SPACES}
        self._kv_tiers: Dict[str, int] = {t: 0 for t in KV_TIERS}
        self._kv_tier_peak: Dict[str, int] = {t: 0 for t in KV_TIERS}
        self._trees: Dict[str, Dict[str, Any]] = {}   # name -> registration
        self._series: collections.deque = collections.deque(maxlen=capacity)
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._n_watermarks = 0
        self._n_census = 0
        self._largest: List[Dict[str, Any]] = []
        self._per_device: Dict[str, int] = {}
        self._census_meta: Optional[Dict[str, Any]] = None
        self._tracer = tracer
        self._prev_active: Optional["MemoryLedger"] = None
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)

    # ------------------------------------------------------------- clock --
    def now(self) -> float:
        return time.monotonic() - self._t0

    def set_tracer(self, tracer):
        """Attach a ``telemetry.Tracer``: watermark crossings emit
        ``memory`` events into its ring, so OOM approach survives in the
        flight recorder next to tick/compile spans."""
        self._tracer = tracer
        return self

    # ------------------------------------------------------ registration --
    def register_tree(self, pool: str, tree, name: Optional[str] = None,
                      ) -> str:
        """Register a pytree's leaves under ``pool`` for census
        classification (device space).  Re-registering a ``name`` replaces
        the previous registration — trainers whose donated state is
        rebuilt every step re-register the fresh tree (the
        ``instrument_train_step`` seam).  Returns the registration name."""
        if pool not in POOLS or pool == "other":
            raise ValueError(f"unknown pool {pool!r}; one of "
                             f"{[p for p in POOLS if p != 'other']}")
        import jax
        leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
        ids: Dict[int, Tuple[str, int]] = {}
        for path, leaf in leaves_with_path:
            if not hasattr(leaf, "dtype"):
                continue
            ids[id(leaf)] = (jax.tree_util.keystr(path), _leaf_bytes(leaf))
        name = name or f"{pool}{len(self._trees)}"
        with self._lock:
            self._trees[name] = {"pool": pool, "ids": ids}
        return name

    def unregister_tree(self, name: str) -> bool:
        with self._lock:
            return self._trees.pop(name, None) is not None

    def register_train_state(self, state: Dict[str, Any],
                             name: str = "train_state") -> str:
        """Register a trainer ``state`` dict by its conventional top-level
        keys (params/buffers → params, opt/scaler → optimizer_state,
        comm_e → grads_comm_buffers; unknown keys ride along as params'
        siblings are not invented — they stay unregistered and show up in
        ``other``, which is the honest place for state this table does
        not understand)."""
        buckets: Dict[str, list] = {}
        for key, sub in state.items():
            pool = _STATE_KEY_POOL.get(key)
            if pool is not None:
                buckets.setdefault(pool, []).append((key, sub))
        for pool, subs in buckets.items():
            self.register_tree(pool, dict(subs), name=f"{name}.{pool}")
        # drop pools this state no longer carries (a re-registered state
        # without comm_e must not leave stale ids classifying)
        with self._lock:
            stale = [n for n in self._trees
                     if n.startswith(f"{name}.") and
                     n.split(".", 1)[1] not in buckets]
            for n in stale:
                del self._trees[n]
        return name

    # ------------------------------------------------------------ ingest --
    def account(self, pool: str, delta: int, space: str = "host",
                tier: Optional[str] = None):
        """Attribute a byte delta to ``pool`` in ``space`` (the
        event-driven path: kv tier transitions, executable-cache blob
        writes).  ``tier`` additionally mirrors the delta onto a KV tier
        counter.  Negative deltas release; totals clamp at zero (a
        release crossing zero indicates a missed account and is logged
        once per ledger rather than going negative silently)."""
        if pool not in POOLS:
            raise ValueError(f"unknown pool {pool!r}; one of {POOLS}")
        if space not in SPACES:
            raise ValueError(f"unknown space {space!r}; one of {SPACES}")
        if tier is not None and tier not in KV_TIERS:
            raise ValueError(f"unknown kv tier {tier!r}; one of {KV_TIERS}")
        events = []
        with self._lock:
            key = (space, pool)
            new = self._bytes[key] + int(delta)
            if new < 0:
                self._log.warning(
                    "memory ledger: %s/%s released below zero (delta %d); "
                    "clamping — an allocation site is not accounting",
                    space, pool, delta)
                new = 0
            self._bytes[key] = new
            if tier is not None:
                t = max(0, self._kv_tiers[tier] + int(delta))
                self._kv_tiers[tier] = t
                if t > self._kv_tier_peak[tier]:
                    self._kv_tier_peak[tier] = t
            events = self._note_locked(space, pool, new)
        self._emit_events(events)

    def set_bytes(self, pool: str, value: int, space: str = "host",
                  tier: Optional[str] = None):
        """Absolute-value twin of :meth:`account` for sources that track
        their own totals (the kv store's tier counters on snapshot
        resync)."""
        with self._lock:
            cur = self._bytes[(space, pool)] if tier is None \
                else self._kv_tiers[tier]
        self.account(pool, int(value) - cur, space=space, tier=tier)

    def _note_locked(self, space: str, pool: str, total: int):
        """Record one sample and any watermark crossings (caller holds
        the lock).  Returns tracer events to emit outside the lock."""
        ts = time.monotonic() - self._t0
        self._series.append((ts, space, pool, total))
        events = []
        if total > self._peak[(space, pool)]:
            prev = self._peak[(space, pool)]
            self._peak[(space, pool)] = total
            self._n_watermarks += 1
            ev = {"ts": round(ts, 6), "space": space, "pool": pool,
                  "bytes": total, "prev_bytes": prev}
            self._ring.append(ev)
            events.append(ev)
        space_total = sum(v for (s, _p), v in self._bytes.items()
                          if s == space)
        if space_total > self._peak_total[space]:
            self._peak_total[space] = space_total
        return events

    def _emit_events(self, events):
        tr = self._tracer
        if tr is None or not events:
            return
        for ev in events:
            tr.emit("memory", what="watermark", **ev)

    # ------------------------------------------------------------ census --
    def census(self) -> Dict[str, Any]:
        """Refresh the device-space pools from ONE ``jax.live_arrays()``
        walk: every live array is classified by identity against the
        registered trees; the residual is ``other``.  Pool bytes are
        **addressable** (what this process's devices hold); ``logical``
        keeps the global-shape view beside it.  Also refreshes per-device
        totals and the largest-array forensics rows.  Conservation:
        ``sum(pools.values()) == total_bytes`` by construction."""
        import jax

        with self._lock:
            id_pool: Dict[int, Tuple[str, str]] = {}
            for reg in self._trees.values():
                pool = reg["pool"]
                for i, (path, _b) in reg["ids"].items():
                    id_pool[i] = (pool, path)
        pools = {p: 0 for p in POOLS}
        logical = {p: 0 for p in POOLS}
        per_device: Dict[str, int] = {}
        rows: List[Dict[str, Any]] = []
        n_arrays = 0
        for a in jax.live_arrays():
            if getattr(a, "is_deleted", lambda: False)():
                continue
            n_arrays += 1
            lb = _leaf_bytes(a)
            ab = _addressable_bytes(a)
            pool, path = id_pool.get(id(a), ("other", None))
            pools[pool] += ab
            logical[pool] += lb
            shards = getattr(a, "addressable_shards", None) or ()
            for sh in shards:
                dev = getattr(sh, "device", None)
                data = getattr(sh, "data", None)
                if dev is not None:
                    per_device[str(dev)] = per_device.get(str(dev), 0) \
                        + (_leaf_bytes(data) if data is not None else 0)
            rows.append({"pool": pool, "path": path, "bytes": ab,
                         "shape": list(getattr(a, "shape", ())),
                         "dtype": str(getattr(a, "dtype", "?"))})
        rows.sort(key=lambda r: -r["bytes"])
        total = sum(pools.values())
        events = []
        with self._lock:
            for p in POOLS:
                self._bytes[("device", p)] = pools[p]
                events.extend(self._note_locked("device", p, pools[p]))
            hbm_kv = pools["kv_pages"]
            self._kv_tiers["hbm"] = hbm_kv
            if hbm_kv > self._kv_tier_peak["hbm"]:
                self._kv_tier_peak["hbm"] = hbm_kv
            self._largest = rows[:_TOP_ARRAYS]
            self._per_device = per_device
            self._n_census += 1
            self._census_meta = {"ts": round(time.monotonic() - self._t0, 6),
                                 "arrays": n_arrays, "total_bytes": total,
                                 "other_bytes": pools["other"]}
        self._emit_events(events)
        census = {"pools": pools, "logical": logical,
                  "per_device": per_device, "total_bytes": total,
                  "logical_total_bytes": sum(logical.values()),
                  "arrays": n_arrays, "largest": rows[:_TOP_ARRAYS]}
        tr = self._tracer
        if tr is not None:
            tr.emit("memory", what="census", arrays=n_arrays,
                    total_bytes=total,
                    **{f"{p}_bytes": v for p, v in pools.items()})
        return census

    # ----------------------------------------------------------- queries --
    def memory_snapshot(self) -> Dict[str, Any]:
        """One JSON-able snapshot: per-pool live and peak bytes in both
        spaces, KV tier bytes, per-device totals from the last census, and
        the tail of the watermark ring.  The ``ops_server`` detection
        method (``/memory``) and the schema the tests pin.  Invariant:
        ``sum(pool device_bytes) == totals.device_bytes`` (``other`` is
        the census residual, so conservation holds by construction)."""
        with self._lock:
            by = dict(self._bytes)
            peak = dict(self._peak)
            pools = {p: {"device_bytes": by[("device", p)],
                         "host_bytes": by[("host", p)],
                         "device_peak_bytes": peak[("device", p)],
                         "host_peak_bytes": peak[("host", p)]}
                     for p in POOLS}
            totals = {
                "device_bytes": sum(by[("device", p)] for p in POOLS),
                "host_bytes": sum(by[("host", p)] for p in POOLS),
                "device_peak_bytes": self._peak_total["device"],
                "host_peak_bytes": self._peak_total["host"],
            }
            return {
                "pools": pools,
                "kv_tiers": {t: {"bytes": self._kv_tiers[t],
                                 "peak_bytes": self._kv_tier_peak[t]}
                             for t in KV_TIERS},
                "totals": totals,
                "per_device": dict(self._per_device),
                "census": dict(self._census_meta)
                if self._census_meta else None,
                "counts": {"watermarks": self._n_watermarks,
                           "census_runs": self._n_census,
                           "registered_trees": len(self._trees)},
                "watermarks": list(self._ring)[-16:],
            }

    def forensics(self, window: int = 64) -> Dict[str, Any]:
        """The OOM post-mortem payload the flight recorder writes as a
        dump section: pools ranked by live bytes, recent growth per pool
        over the last ``window`` retained samples, the largest live
        arrays (with tree paths) from the last census, the watermark
        ring, and the allocator's own stats where the backend exposes
        them.  Never raises — a crash handler that crashes destroys the
        evidence."""
        try:
            with self._lock:
                by = dict(self._bytes)
                series = list(self._series)[-window:]
                largest = list(self._largest)
                ring = list(self._ring)
            top = sorted(
                ({"space": s, "pool": p, "bytes": v}
                 for (s, p), v in by.items() if v > 0),
                key=lambda r: -r["bytes"])
            first_seen: Dict[Tuple[str, str], int] = {}
            last_seen: Dict[Tuple[str, str], int] = {}
            for ts, space, pool, total in series:
                key = (space, pool)
                first_seen.setdefault(key, total)
                last_seen[key] = total
            growth = [{"space": s, "pool": p,
                       "delta_bytes": last_seen[(s, p)] - first_seen[(s, p)]}
                      for (s, p) in last_seen
                      if last_seen[(s, p)] != first_seen[(s, p)]]
            growth.sort(key=lambda r: -r["delta_bytes"])
            try:
                alloc = device_allocator_stats()
            except Exception as e:  # pragma: no cover - backend-specific
                alloc = {"error": repr(e)}
            return {"top_pools": top, "recent_growth": growth,
                    "largest_arrays": largest, "watermarks": ring,
                    "allocator": alloc}
        except Exception as e:  # pragma: no cover - crash-path guard
            self._log.warning("memory ledger: forensics failed: %s", e)
            return {"error": repr(e)}

    # ----------------------------------------------------------- exports --
    def prometheus_text(self, namespace: str = "paddle_tpu_memory") -> str:
        """Text exposition of the snapshot: per-pool live/peak byte gauges
        in both spaces, per-tier KV bytes, space totals, and event
        counters — what ``ops_server`` merges into ``GET /metrics``."""
        from .utils.stats import StatRegistry, prometheus_text as _pt
        snap = self.memory_snapshot()
        gauges: Dict[str, float] = {}
        for p, row in snap["pools"].items():
            for field, v in row.items():
                gauges[f"{p}_{field}"] = v
        for t, row in snap["kv_tiers"].items():
            gauges[f"kv_{t}_bytes"] = row["bytes"]
            gauges[f"kv_{t}_peak_bytes"] = row["peak_bytes"]
        for field, v in snap["totals"].items():
            gauges[f"total_{field}"] = v
        if snap["census"]:
            gauges["live_arrays"] = snap["census"]["arrays"]
        counters = {"watermark_events_total": snap["counts"]["watermarks"],
                    "census_runs_total": snap["counts"]["census_runs"]}
        return _pt(StatRegistry(), namespace=namespace,
                   extra_gauges=gauges, extra_counters=counters)

    def to_dict(self) -> Dict[str, Any]:
        """Snapshot + retained sample series + forensics — the
        ``dump_json`` payload and the flight-recorder artifact."""
        with self._lock:
            series = [[ts, s, p, b] for ts, s, p, b in self._series]
        return {"kind": "memory", "snapshot": self.memory_snapshot(),
                "series": series, "forensics": self.forensics()}

    def dump_json(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)

    def to_chrome_counters(self, pid: str = "paddle_tpu.memory"
                           ) -> List[Dict[str, Any]]:
        """Chrome-trace counter ("C") events: per-pool live bytes after
        each retained sample, one stacked track per space — merges next
        to tracer span rows (``tools/trace_to_chrome.py --memory``)."""
        return chrome_counters_from_memory_dump(self.to_dict(), pid=pid)

    # ---------------------------------------------------------- lifecycle --
    def activate(self) -> "MemoryLedger":
        """Install as the process-wide active memory ledger (the seam the
        kv_store / aot-cache / trainer instrumentation reports through).
        Also a context manager."""
        self._prev_active = set_active_memory_ledger(self)
        return self

    def deactivate(self):
        set_active_memory_ledger(self._prev_active)
        self._prev_active = None

    __enter__ = activate

    def __exit__(self, *exc):
        self.deactivate()
        return False


def chrome_counters_from_memory_dump(data: Dict[str, Any],
                                     pid: str = "paddle_tpu.memory"
                                     ) -> List[Dict[str, Any]]:
    """``MemoryLedger.to_dict()`` / ``dump_json`` payload → chrome counter
    events (offline twin of ``to_chrome_counters``, used by
    ``tools/trace_to_chrome.py --memory``).  One counter track per space
    so device HBM and host bytes stack separately on the timeline."""
    out: List[Dict[str, Any]] = [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": pid}}]
    cur: Dict[str, Dict[str, int]] = {s: {} for s in SPACES}
    for ts, space, pool, total in data.get("series", []):
        if space not in cur:
            continue
        cur[space][pool] = total
        out.append({"name": f"{space}_memory_bytes", "ph": "C", "pid": pid,
                    "ts": float(ts) * 1e6,
                    "args": dict(cur[space])})
    return out


# --------------------------------------------------------------------------
# process-wide active memory ledger
# --------------------------------------------------------------------------

_active_memory: Optional[MemoryLedger] = None


def set_active_memory_ledger(ledger: Optional[MemoryLedger]
                             ) -> Optional[MemoryLedger]:
    """Install the process-wide active memory ledger (or None) and return
    the previous one — the ``set_active_ledger`` convention.  Seams that
    cannot be threaded a handle (kv tier transitions, aot blob writes,
    the per-step state re-registration) report through this."""
    global _active_memory
    prev = _active_memory
    _active_memory = ledger
    return prev


def current_memory_ledger() -> Optional[MemoryLedger]:
    return _active_memory


def account_bytes(pool: str, delta: int, space: str = "host",
                  tier: Optional[str] = None):
    """``account`` on the active ledger; a no-op when none is active (the
    one-check-zero-cost contract every seam shares)."""
    led = _active_memory
    if led is None:
        return
    led.account(pool, delta, space=space, tier=tier)
