"""AOT compilation: persistent executable cache + warmup planner.

The reference keeps compiled programs ACROSS requests and restarts — the
Executor program cache (L6) and ``analysis_predictor``'s serialized inference
programs (L7) mean a serving process never pays a compile on the request
path.  This module is the TPU-native equivalent for a framework whose
"program" is an XLA executable:

1. **Persistent executable cache** (:class:`ExecutableCache`): compiled
   programs keyed by (program digest, input avals/shardings, mesh,
   jax + jaxlib version, backend) and serialized to a cache directory via
   ``jax.experimental.serialize_executable``.  A second process pointing at
   the same directory deserializes instead of recompiling.  Entries whose
   recorded environment no longer matches (jax upgraded, different backend,
   different mesh) are refused at load time — never silently executed.

2. **XLA compilation-cache fallback** (:func:`enable_persistent_compilation_
   cache`): programs that cannot be explicitly serialized (or that dispatch
   through ``jax.jit``'s own call path, like the serving engines' programs)
   still persist across processes through ``jax.config``'s compilation-cache
   settings — the second process re-traces (cheap) and skips the XLA compile
   (the expensive part).  The in-process jit cache is the second level on
   top.

3. **Warmup planner** (:func:`run_warmup` / :func:`warmup_async`): engines
   and step builders declare their compile grid (``engine.compile_grid()``
   enumerates the bucket/table-width program families behind
   ``serving_paged.py`` — the ragged engine's grid is one program per
   (token_budget, table-width) bucket, plus one narrow program for the
   rounds of decode rows only; with a draft model attached speculation
   swaps the family and keeps one row count, it never widens the grid;
   training steps AOT-compile via
   :func:`compile_aot`), and the planner precompiles it — optionally on a
   background thread — before traffic.  Progress reports through the
   telemetry tracer: compile events gain a ``provenance`` tag
   (``cold`` = fresh XLA compile, ``disk`` = served from the persistent
   cache, ``warm`` = already in process) and warmup-window misses never arm
   the recompile-storm warning.

See docs/COMPILATION.md for the cache layout and the soundness conditions
for disk reuse.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = ["ExecutableCache", "WarmupTask", "compile_aot",
           "enable_persistent_compilation_cache", "fingerprint",
           "mesh_signature", "run_warmup", "serialization_supported",
           "warmup_async"]

SCHEMA_VERSION = 1
_MANIFEST = "manifest.json"
_log = logging.getLogger(__name__)


def _versions() -> Tuple[str, str]:
    import jaxlib
    return jax.__version__, jaxlib.__version__


def backend_name(backend: Optional[str] = None) -> str:
    return backend if backend is not None else jax.default_backend()


def mesh_signature(mesh) -> Optional[str]:
    """Canonical string for a ``jax.sharding.Mesh``: axis layout plus the
    device kinds under it.  Executables bake in device assignment, so a
    cache entry compiled for one mesh must never load on another."""
    if mesh is None:
        return None
    devs = list(mesh.devices.flat)
    kinds = sorted({getattr(d, "device_kind", str(d)) for d in devs})
    axes = tuple((str(k), int(v)) for k, v in dict(mesh.shape).items())
    return f"axes={axes}|kinds={kinds}|n={len(devs)}"


def _rules_digest() -> str:
    """The active sharding-rules digest (distributed/sharding_rules.py).
    Lazy import: jit/ must stay importable without the distributed layer
    (and vice versa — sharding_rules itself never imports jit/)."""
    from ..distributed.sharding_rules import sharding_rules_digest
    return sharding_rules_digest()


def fingerprint(*parts, mesh=None, backend: Optional[str] = None,
                include_env: bool = True) -> str:
    """Stable hex digest over ``parts`` — THE cache-key helper.  By default
    the compile environment (jax + jaxlib version, backend, mesh signature,
    sharding-rules digest) is folded in, so a key computed under one
    toolchain — or one sharding-rule table — can never alias an executable
    built under another.  Parts are ``repr``-canonicalized; pass
    shapes/dtypes, program text, or config tuples — not live arrays."""
    h = hashlib.blake2b(digest_size=16)
    env: Tuple[Any, ...] = ()
    if include_env:
        jaxv, jaxlibv = _versions()
        env = (jaxv, jaxlibv, backend_name(backend), mesh_signature(mesh),
               _rules_digest())
    for p in env + tuple(parts):
        h.update(repr(p).encode())
        h.update(b"\x00")
    return h.hexdigest()


def serialization_supported() -> bool:
    """Whether the installed jax can serialize compiled executables."""
    try:
        from jax.experimental import serialize_executable  # noqa: F401
    except ImportError:
        return False
    return True


class ExecutableCache:
    """Persistent compiled-executable store (module docstring, point 1).

    Layout::

        <cache_dir>/manifest.json      versioned index: digest -> entry
        <cache_dir>/<digest>.bin       pickled (payload, in_tree, out_tree)
                                       from serialize_executable.serialize
        <cache_dir>/xla/               XLA compilation-cache fallback files
                                       (enable_persistent_compilation_cache)

    Every manifest entry records the environment it was compiled under
    (jax, jaxlib, backend, mesh signature); :meth:`get` refuses mismatching
    entries (counted in ``invalidated``) — a stale executable is recompiled,
    never run.  Deserialized executables are memoized in-process (the
    second-level cache), so repeated ``get`` calls cost a dict lookup.
    """

    def __init__(self, cache_dir, backend: Optional[str] = None):
        self.dir = str(cache_dir)
        os.makedirs(self.dir, exist_ok=True)
        self.backend = backend_name(backend)
        self._lock = threading.Lock()
        self._mem: Dict[str, Any] = {}
        self.hits_memory = 0
        self.hits_disk = 0
        self.misses = 0
        self.invalidated = 0

    # ------------------------------------------------------------ manifest --

    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, _MANIFEST)

    @contextlib.contextmanager
    def _manifest_write_lock(self):
        """Cross-PROCESS exclusion for the manifest read-modify-write: the
        advertised use is multi-process (tools/warmup.py at image build +
        a serving host warming the same dir), and two concurrent put()s
        under only the instance lock would last-writer-win, orphaning the
        loser's payload as a silent permanent miss.  flock on a sidecar
        lock file; readers need nothing (os.replace keeps the manifest
        itself always-consistent)."""
        with open(os.path.join(self.dir, "manifest.lock"), "w") as f:
            try:
                import fcntl
            except ImportError:           # non-POSIX: in-process lock only
                yield
                return
            fcntl.flock(f, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(f, fcntl.LOCK_UN)

    def _load_manifest(self) -> Dict[str, Any]:
        try:
            with open(self._manifest_path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return {"version": SCHEMA_VERSION, "entries": {}}
        except (OSError, ValueError) as e:
            _log.warning("aot cache manifest %s unreadable (%s) — treating "
                         "as empty", self._manifest_path, e)
            return {"version": SCHEMA_VERSION, "entries": {}}
        if data.get("version") != SCHEMA_VERSION:
            _log.warning("aot cache manifest version %r != %d — ignoring "
                         "existing entries", data.get("version"),
                         SCHEMA_VERSION)
            return {"version": SCHEMA_VERSION, "entries": {}}
        return data

    def _write_atomic(self, path: str, blob: bytes) -> None:
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)

    def _digest(self, key) -> str:
        # env rides the digest too, but the manifest entry is the AUTHORITY:
        # invalidation must be observable (and warn), not a silent miss
        return fingerprint("exec", key, backend=self.backend,
                           include_env=False)

    # ------------------------------------------------------------- put/get --

    def put(self, key, compiled, mesh=None) -> bool:
        """Serialize one compiled executable under ``key``.  Returns False
        (and leaves the cache untouched) when the executable does not
        support serialization — callers fall back to the XLA
        compilation-cache wiring."""
        try:
            from jax.experimental import serialize_executable as se
        except ImportError:
            return False
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
        except (ValueError, TypeError) as e:
            _log.warning("aot cache: %r not serializable (%s); relying on "
                         "the XLA compilation-cache fallback", key, e)
            return False
        digest = self._digest(key)
        blob = pickle.dumps((payload, in_tree, out_tree), protocol=4)
        jaxv, jaxlibv = _versions()
        # the executable's OWN devices: deserialize_and_load otherwise loads
        # onto every device of the backend, and a one-device program then
        # refuses its one-shard arguments
        device_ids = [d.id for d in
                      compiled._executable._unloaded_executable.device_list]
        with self._lock, self._manifest_write_lock():
            fname = digest + ".bin"
            self._write_atomic(os.path.join(self.dir, fname), blob)
            manifest = self._load_manifest()   # re-read UNDER the lock:
            # merges entries another process wrote since our last look
            manifest["entries"][digest] = {
                "key": str(key), "file": fname, "jax": jaxv,
                "jaxlib": jaxlibv, "backend": self.backend,
                "mesh": mesh_signature(mesh), "rules": _rules_digest(),
                "devices": device_ids,
                "bytes": len(blob), "created_at": time.time()}
            self._write_atomic(self._manifest_path,
                               json.dumps(manifest, indent=2,
                                          sort_keys=True).encode())
            self._mem[digest] = compiled
        # serialized-blob bytes feed the memory ledger's `executables`
        # pool (a host-side proxy for compiled-program size) — one
        # attribute check when no ledger is active
        from ..telemetry_memory import account_bytes
        account_bytes("executables", len(blob), space="host")
        return True

    def get(self, key, mesh=None):
        """The executable cached under ``key``, or None on a miss OR an
        environment mismatch (jax/jaxlib/backend/mesh/sharding-rules drift
        invalidates the entry — a recompile is cheaper than a wrong
        program; a stale-spec executable restored from disk must be
        impossible)."""
        digest = self._digest(key)
        with self._lock:
            if digest in self._mem:
                self.hits_memory += 1
                return self._mem[digest]
            entry = self._load_manifest()["entries"].get(digest)
        if entry is None:
            self.misses += 1
            return None
        jaxv, jaxlibv = _versions()
        want = {"jax": jaxv, "jaxlib": jaxlibv, "backend": self.backend,
                "mesh": mesh_signature(mesh), "rules": _rules_digest()}
        for field, expect in want.items():
            if entry.get(field) != expect:
                self.invalidated += 1
                _log.warning(
                    "aot cache entry %r invalidated: %s was %r, now %r — "
                    "recompiling", entry.get("key"), field,
                    entry.get(field), expect)
                return None
        try:
            with open(os.path.join(self.dir, entry["file"]), "rb") as f:
                blob = f.read()
        except OSError as e:
            self.misses += 1
            _log.warning("aot cache entry %r lost its payload (%s)",
                         entry.get("key"), e)
            return None
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = pickle.loads(blob)
            by_id = {d.id: d for d in jax.devices(self.backend)}
            compiled = se.deserialize_and_load(
                payload, in_tree, out_tree, backend=self.backend,
                execution_devices=[by_id[i] for i in entry["devices"]])
        except Exception as e:  # noqa: BLE001 — a corrupt/incompatible
            # payload must degrade to a recompile, never kill serving
            self.invalidated += 1
            _log.warning("aot cache entry %r failed to deserialize (%s) — "
                         "recompiling", entry.get("key"), e)
            return None
        with self._lock:
            self._mem[digest] = compiled
            self.hits_disk += 1
        # a disk restore brings the blob into process memory too
        from ..telemetry_memory import account_bytes
        account_bytes("executables", len(blob), space="host")
        return compiled

    def contains(self, key) -> bool:
        digest = self._digest(key)
        with self._lock:
            if digest in self._mem:
                return True
            return digest in self._load_manifest()["entries"]

    def entries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._load_manifest()["entries"].values())

    def stats(self) -> Dict[str, int]:
        return {"hits_memory": self.hits_memory, "hits_disk": self.hits_disk,
                "misses": self.misses, "invalidated": self.invalidated}


# ---------------------------------------------------------------------------
# XLA compilation-cache fallback wiring
# ---------------------------------------------------------------------------

#: where the XLA cache lives when nobody says otherwise: a fixed,
#: git-ignored directory of the checkout.  The path is part of what a
#: cached program is found by, so it is never a temporary name, a pid or a
#: time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compilation_cache(cache_dir=None) -> str:
    """Turn on jax's XLA persistent compilation cache and drop the
    min-compile-time / min-entry-size gates so EVERY program persists —
    serving programs are many and small, and the whole point is that none
    of them compiles twice.  Where it lives, one rule:

    1. ``JAX_COMPILATION_CACHE_DIR`` set: there — the cache was placed
       from outside, jax already reads it, and code sets no other;
    2. else the caller's ``<cache_dir>/xla``;
    3. else ``DEFAULT_CACHE_DIR`` (``<checkout>/.jax_cache``).

    Idempotent; returns the XLA cache directory."""
    xla_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not xla_dir:
        xla_dir = (DEFAULT_CACHE_DIR if cache_dir is None
                   else os.path.join(str(cache_dir), "xla"))
    os.makedirs(xla_dir, exist_ok=True)
    changed = False
    if jax.config.jax_compilation_cache_dir != xla_dir:
        jax.config.update("jax_compilation_cache_dir", xla_dir)
        changed = True
    if jax.config.jax_persistent_cache_min_compile_time_secs != 0.0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        changed = True
    if jax.config.jax_persistent_cache_min_entry_size_bytes != -1:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        changed = True
    if changed:
        # jax latches cache-enablement at the FIRST compile of the process
        # (is_cache_used memoizes per task); wiring the dir after any
        # compile has happened — the normal case for an engine warming
        # post-construction — needs the latch reset or nothing persists
        from jax.experimental.compilation_cache.compilation_cache import \
            reset_cache
        reset_cache()
    return xla_dir


def persistent_cache_dir() -> Optional[str]:
    """The currently wired XLA compilation-cache dir (None = not wired)."""
    return jax.config.jax_compilation_cache_dir


class _DirProvenance:
    """Compile-provenance resolver consulted by the Tracer at compile-event
    time: executable files newly written to the XLA cache dir since the
    last check mean that compile PAID XLA ("cold"); none mean it was served
    from disk ("disk").  Exact for sequential warmup (events fire right
    after each program's first dispatch); concurrent compiles can smear
    attribution between simultaneous tasks."""

    def __init__(self, xla_dir: str):
        self.dir = xla_dir
        self._lock = threading.Lock()
        self._seen = set(os.listdir(xla_dir))

    def __call__(self) -> str:
        with self._lock:
            try:
                now = set(os.listdir(self.dir))
            except OSError:
                return "cold"
            new = now - self._seen
            self._seen = now
        # "-cache" files hold executables; "-atime" stamps ride along on
        # reads too, so only a new executable counts as a cold compile
        return "cold" if any(f.endswith("-cache") for f in new) else "disk"


# ---------------------------------------------------------------------------
# warmup planner
# ---------------------------------------------------------------------------

class WarmupTask:
    """One program family to precompile: ``run()`` must fetch AND dispatch
    the program once (scratch operands), so the XLA compile — not just the
    Python closure build — happens during warmup."""

    __slots__ = ("label", "run")

    def __init__(self, label: str, run: Callable[[], None]):
        self.label = str(label)
        self.run = run

    def __repr__(self):
        return f"WarmupTask({self.label!r})"


def run_warmup(tasks: Sequence[WarmupTask], *, tracer=None, cache_dir=None,
               max_workers: int = 1,
               logger: Optional[logging.Logger] = None) -> Dict[str, Any]:
    """Execute a warmup plan.  ``cache_dir`` wires the persistent XLA
    compilation cache first, so the compiles both PERSIST for later
    processes and RESOLVE provenance (cold vs disk) for this one.  With a
    ``tracer`` the whole run executes inside its ``expected_compiles``
    window: compile events are tagged and the recompile-storm warning
    ignores them.  ``max_workers > 1`` compiles concurrently (provenance
    attribution may smear across simultaneous tasks).  Returns a report:
    ``{"programs", "wall_s", "tasks": [{"label", "wall_s"}, ...],
    "cache_dir"}``."""
    log = logger if logger is not None else _log
    resolver = None
    if cache_dir is not None:
        resolver = _DirProvenance(
            enable_persistent_compilation_cache(cache_dir))
    t0 = time.perf_counter()

    def one(task: WarmupTask) -> Dict[str, Any]:
        tt = time.perf_counter()
        task.run()
        return {"label": task.label, "wall_s": time.perf_counter() - tt}

    # scope the expected window to THIS grid's labels: with warmup_async,
    # live traffic compiles concurrently — its misses must still arm the
    # recompile-storm warning
    ctx = (tracer.expected_compiles(resolver,
                                    keys={t.label for t in tasks})
           if tracer is not None else contextlib.nullcontext())
    with ctx:
        if max_workers and int(max_workers) > 1:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=int(max_workers),
                    thread_name_prefix="aot-warmup") as ex:
                results = list(ex.map(one, tasks))
        else:
            results = [one(t) for t in tasks]
    wall = time.perf_counter() - t0
    log.info("aot warmup: %d programs in %.2fs%s", len(results), wall,
             f" (cache: {cache_dir})" if cache_dir else "")
    return {"programs": len(results), "wall_s": wall, "tasks": results,
            "cache_dir": None if cache_dir is None else str(cache_dir)}


def warmup_async(tasks: Sequence[WarmupTask], **kwargs
                 ) -> "concurrent.futures.Future":
    """``run_warmup`` on a background thread — engines warm while the host
    finishes startup; traffic admitted mid-warmup simply compiles what it
    needs (the warmup task then hits).  Returns the Future of the report."""
    ex = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="aot-warmup-driver")
    fut = ex.submit(run_warmup, tasks, **kwargs)
    ex.shutdown(wait=False)
    return fut


# ---------------------------------------------------------------------------
# training-step AOT
# ---------------------------------------------------------------------------

def compile_aot(step, example_args: Sequence[Any], *, cache: Optional[
        ExecutableCache] = None, mesh=None, label: str = "step",
        monitor=None, key_extra: Tuple = ()):
    """AOT-compile a step via ``.lower().compile()`` with persistent-cache
    reuse — the training-side warmup primitive (``make_train_step`` /
    ``make_gpt_train_step`` steps expose ``lower``; plain callables are
    jitted first).  ``example_args`` may be arrays or ShapeDtypeStructs.

    Key: (label, digest of the lowered StableHLO text + jax/jaxlib/backend/
    mesh + ``key_extra``) — the program CONTENT keys the cache, so any
    config change that alters the lowering misses naturally.  Returns
    ``(compiled, provenance)`` with provenance ``"cold" | "disk" | "warm"``;
    with a ``monitor`` (``telemetry.TrainMonitor``) the compile — or the
    disk load — is recorded as a compile event with that provenance, and
    a cold compile additionally carries the executable's XLA
    cost-analysis FLOPs/bytes (free — the program was just compiled;
    the result seeds ``hapi/dynamic_flops``'s digest cache), the
    per-step model-FLOPs source of the training-side MFU summary."""
    lower = getattr(step, "lower", None)
    lowered = (lower(*example_args) if lower is not None
               else jax.jit(step).lower(*example_args))
    # env stays OUT of the key: the manifest entry is the environment
    # authority, so jax/backend/mesh drift hits the OBSERVABLE
    # invalidation path (warning + counter, entry overwritten in place)
    # instead of silently missing and stranding orphaned payloads
    key = (label, fingerprint("aot_step", lowered.as_text(), *key_extra,
                              include_env=False))

    def _cost(compiled_exe):
        try:
            from ..hapi.dynamic_flops import cost_of_compiled
            return cost_of_compiled(compiled_exe, lowered=lowered)
        except Exception:  # noqa: BLE001 — best-effort telemetry only
            return None

    if cache is not None:
        mem_before = cache.hits_memory
        t0 = time.perf_counter()
        cached = cache.get(key, mesh=mesh)
        if cached is not None:
            provenance = "warm" if cache.hits_memory > mem_before else "disk"
            if monitor is not None:
                monitor.record_compile((f"{label}_aot",),
                                       time.perf_counter() - t0,
                                       provenance=provenance,
                                       cost=_cost(cached))
            return cached, provenance
    t0 = time.perf_counter()
    compiled = lowered.compile()
    wall = time.perf_counter() - t0
    if monitor is not None:
        monitor.record_compile((f"{label}_aot",), wall, provenance="cold",
                               cost=_cost(compiled))
    if cache is not None:
        cache.put(key, compiled, mesh=mesh)
    return compiled, "cold"
