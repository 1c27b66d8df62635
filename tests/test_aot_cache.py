"""AOT bucket warmup + persistent executable cache (ISSUE 7).

The tentpole contracts under test:
- disk round trip: compile → serialize → fresh-cache-instance reload →
  identical outputs;
- environment drift (jax version / backend / mesh) INVALIDATES an entry —
  a stale executable recompiles, never runs;
- a warmed engine serves its first request with ZERO compile events (the
  compile-once contract), token-for-token identical to a cold engine;
- a second process reusing the cache dir records ``provenance: disk``
  compile events and writes no new XLA cache files (skipped recompilation);
- purity: lowerings are byte-identical with and without warmup
  instrumentation (extends the PR 4 off-path purity suite).
"""

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit.aot import ExecutableCache, compile_aot, fingerprint
from paddle_tpu.jit.bucketing import bucketize, pow2_bucket, pow2_grid
from paddle_tpu.jit.functional import make_train_step, warm_train_step
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.optimizer import Momentum
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                PagedContinuousBatchingEngine,
                                RaggedPagedContinuousBatchingEngine)
from paddle_tpu.telemetry import TrainMonitor, Tracer

# 1 layer keeps every warmup compile cheap; the program FAMILIES (the thing
# under test) are layer-count independent
CFG = dict(vocab_size=64, hidden_size=32, num_layers=1,
           num_attention_heads=2, max_position_embeddings=64,
           compute_dtype="float32")


def _model():
    paddle.seed(0)
    model = GPTModel(GPTConfig(**CFG))
    params = {n: p._data for n, p in model.named_parameters()}
    return model, params


def _ragged(tracer=None, **kw):
    model, params = _model()
    eng = RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=2, max_len=32, block_size=8,
        prompt_buckets=[8, 16], token_budget=12, tracer=tracer, **kw)
    return model, eng


def _serve(eng, prompt=(1, 2, 3, 4), n=3):
    rid = eng.add_request(list(prompt), n)
    return eng.run_to_completion(max_ticks=200)[rid]


@pytest.fixture
def restore_compilation_cache(monkeypatch):
    """enable_persistent_compilation_cache mutates process-global jax
    config; put it back so later tests see the default state.  The tests
    that use it place the cache themselves, so a cache placed from outside
    (JAX_COMPILATION_CACHE_DIR, which would win) is taken away."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    from jax._src.compilation_cache import reset_cache
    reset_cache()


# ------------------------------------------------------------- key helper --

class TestKeyHelper:
    def test_fingerprint_stable_and_part_sensitive(self):
        a = fingerprint("prog", (1, 2), "f32")
        assert a == fingerprint("prog", (1, 2), "f32")
        assert a != fingerprint("prog", (1, 3), "f32")
        assert a != fingerprint("prog2", (1, 2), "f32")

    def test_fingerprint_env_sensitive(self):
        # backend is part of the default environment fold-in
        a = fingerprint("prog")
        assert a != fingerprint("prog", backend="tpu-imaginary")
        assert a == fingerprint("prog", backend=jax.default_backend())

    def test_fingerprint_folds_in_sharding_rules_digest(self):
        """Editing the sharding-rule table (ISSUE 16) changes the default
        env fold-in, so layout-sensitive keys miss instead of aliasing."""
        from paddle_tpu.distributed import sharding_rules as sr
        a = fingerprint("prog")
        sr.register_rules(sr.ShardingRules([(r".*", None)],
                                           name="test_fp_rules"))
        try:
            assert fingerprint("prog") != a
            # explicit env exclusion stays rule-blind (compile_aot's key)
            assert (fingerprint("prog", include_env=False)
                    == fingerprint("prog", include_env=False))
        finally:
            sr.unregister_rules("test_fp_rules")
        assert fingerprint("prog") == a

    def test_pow2_grid_is_exactly_the_view_cols_image(self):
        assert pow2_grid(8) == (1, 2, 4, 8)
        assert pow2_grid(1) == (1,)
        # non-power-of-two cap: the clamp value itself is a bucket
        assert pow2_grid(6) == (1, 2, 4, 6)
        assert pow2_bucket(5, 8) == 8
        assert pow2_bucket(5, 6) == 6
        assert pow2_bucket(0, 8) == 1
        for cap in (1, 2, 6, 8, 16):
            for need in range(1, cap + 1):
                assert pow2_bucket(need, cap) in pow2_grid(cap), (need, cap)


# --------------------------------------------------- where the cache goes --

class TestCacheDirRule:
    """One rule (jit/aot.py): JAX_COMPILATION_CACHE_DIR, else the caller's
    directory, else a fixed directory of the checkout."""

    def test_env_wins_and_code_sets_no_other(self, tmp_path, monkeypatch,
                                             restore_compilation_cache):
        from paddle_tpu.jit.aot import enable_persistent_compilation_cache
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", outside)  # as at import
        got = enable_persistent_compilation_cache(tmp_path / "caller")
        assert got == outside == jax.config.jax_compilation_cache_dir
        assert os.path.isdir(outside)
        assert not (tmp_path / "caller").exists()

    def test_callers_directory_without_env(self, tmp_path,
                                           restore_compilation_cache):
        from paddle_tpu.jit.aot import enable_persistent_compilation_cache
        got = enable_persistent_compilation_cache(tmp_path)
        assert got == os.path.join(str(tmp_path), "xla")
        assert jax.config.jax_compilation_cache_dir == got

    def test_checkout_directory_without_either(self, monkeypatch, tmp_path,
                                               restore_compilation_cache):
        from paddle_tpu.jit import aot
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert aot.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        # the rule itself, without writing into the checkout
        monkeypatch.setattr(aot, "DEFAULT_CACHE_DIR",
                            str(tmp_path / ".jax_cache"))
        got = aot.enable_persistent_compilation_cache()
        assert got == str(tmp_path / ".jax_cache") and os.path.isdir(got)
        assert jax.config.jax_compilation_cache_dir == got

    def test_every_program_persists(self, tmp_path,
                                    restore_compilation_cache):
        from paddle_tpu.jit.aot import enable_persistent_compilation_cache
        enable_persistent_compilation_cache(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


# ------------------------------------------------------ persistent cache --

class TestExecutableCache:
    def _compiled(self):
        f = jax.jit(lambda x: x * 3 + 1)
        x = jnp.arange(8.0)
        return f.lower(x).compile(), x

    def test_disk_round_trip_identical_outputs(self, tmp_path):
        compiled, x = self._compiled()
        want = np.asarray(compiled(x))
        cache = ExecutableCache(tmp_path)
        assert cache.put("prog", compiled)
        # fresh instance = fresh-process-style: no in-memory entries
        fresh = ExecutableCache(tmp_path)
        got = fresh.get("prog")
        assert got is not None and fresh.hits_disk == 1
        np.testing.assert_array_equal(np.asarray(got(x)), want)
        # second-level in-process cache: same object, no re-deserialize
        assert fresh.get("prog") is got and fresh.hits_memory == 1

    def test_entry_records_the_executables_own_devices(self, tmp_path):
        """jax reloads a serialized executable onto every device of the
        backend unless told which: the entry keeps the program's own."""
        dev = jax.devices()[3]
        f = jax.jit(lambda x: x + 1)
        x = jax.device_put(jnp.arange(4.0), dev)    # committed: f follows it
        cache = ExecutableCache(tmp_path)
        assert cache.put("on3", f.lower(x).compile())
        assert cache.entries()[0]["devices"] == [dev.id]
        got = ExecutableCache(tmp_path).get("on3")
        out = got(x)
        assert out.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(out), [1.0, 2.0, 3.0, 4.0])

    def test_entry_without_devices_degrades_to_recompile(self, tmp_path):
        compiled, _ = self._compiled()
        ExecutableCache(tmp_path).put("prog", compiled)
        path = os.path.join(str(tmp_path), "manifest.json")
        with open(path) as fh:
            manifest = json.load(fh)
        for entry in manifest["entries"].values():
            del entry["devices"]                 # an entry from before
        with open(path, "w") as fh:
            json.dump(manifest, fh)
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog") is None and fresh.invalidated == 1

    def test_miss_is_none(self, tmp_path):
        cache = ExecutableCache(tmp_path)
        assert cache.get("never-put") is None and cache.misses == 1

    def _tamper(self, tmp_path, field, value):
        path = os.path.join(str(tmp_path), "manifest.json")
        with open(path) as f:
            manifest = json.load(f)
        for entry in manifest["entries"].values():
            entry[field] = value
        with open(path, "w") as f:
            json.dump(manifest, f)

    def test_jax_version_mismatch_invalidates(self, tmp_path):
        compiled, _ = self._compiled()
        ExecutableCache(tmp_path).put("prog", compiled)
        self._tamper(tmp_path, "jax", "0.0.0")
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog") is None and fresh.invalidated == 1

    def test_backend_mismatch_invalidates(self, tmp_path):
        compiled, _ = self._compiled()
        ExecutableCache(tmp_path).put("prog", compiled)
        self._tamper(tmp_path, "backend", "tpu-imaginary")
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog") is None and fresh.invalidated == 1

    def test_mesh_mismatch_invalidates(self, tmp_path):
        compiled, _ = self._compiled()
        ExecutableCache(tmp_path).put("prog", compiled, mesh=None)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog", mesh=mesh) is None
        assert fresh.invalidated == 1
        # matching mesh=None still loads
        assert fresh.get("prog") is not None

    def test_sharding_rules_mismatch_invalidates(self, tmp_path):
        """A stale-SPEC executable restored from disk must be impossible
        (ISSUE 16): the manifest records the sharding-rules digest, so an
        entry serialized under one rule table refuses to load under
        another — same observable path as jax/backend/mesh drift."""
        from paddle_tpu.distributed import sharding_rules as sr
        compiled, _ = self._compiled()
        ExecutableCache(tmp_path).put("prog", compiled)
        # manifest tamper = an entry written by a process with other rules
        self._tamper(tmp_path, "rules", "0" * 32)
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog") is None and fresh.invalidated == 1
        # the live direction too: put under today's rules, register a new
        # rule set, and a fresh-process get must invalidate
        ExecutableCache(tmp_path).put("prog", compiled)
        sr.register_rules(sr.ShardingRules([(r".*", ("data",))],
                                           name="test_aot_rules"))
        try:
            fresh2 = ExecutableCache(tmp_path)
            assert fresh2.get("prog") is None and fresh2.invalidated == 1
        finally:
            sr.unregister_rules("test_aot_rules")
        # rules restored: the entry loads again
        assert ExecutableCache(tmp_path).get("prog") is not None

    def test_corrupt_payload_degrades_to_recompile(self, tmp_path):
        compiled, _ = self._compiled()
        cache = ExecutableCache(tmp_path)
        cache.put("prog", compiled)
        [entry] = cache.entries()
        with open(os.path.join(str(tmp_path), entry["file"]), "wb") as f:
            f.write(b"not a pickle")
        fresh = ExecutableCache(tmp_path)
        assert fresh.get("prog") is None and fresh.invalidated == 1


# ------------------------------------------------------- training-step AOT --

class TestCompileAot:
    def test_cold_then_disk_then_warm(self, tmp_path):
        step = jax.jit(lambda s, x: s + x)
        args = (jnp.ones((4,)), jnp.arange(4.0))
        c1, prov1 = compile_aot(step, args, cache=ExecutableCache(tmp_path),
                                label="t")
        assert prov1 == "cold"
        cache2 = ExecutableCache(tmp_path)
        c2, prov2 = compile_aot(step, args, cache=cache2, label="t")
        assert prov2 == "disk"
        np.testing.assert_array_equal(np.asarray(c1(*args)),
                                      np.asarray(c2(*args)))
        _, prov3 = compile_aot(step, args, cache=cache2, label="t")
        assert prov3 == "warm"

    def test_monitor_records_provenance(self, tmp_path):
        mon = TrainMonitor()
        step = jax.jit(lambda s, x: s - x)
        args = (jnp.ones((4,)), jnp.arange(4.0))
        compile_aot(step, args, cache=ExecutableCache(tmp_path), label="t",
                    monitor=mon)
        compile_aot(step, args, cache=ExecutableCache(tmp_path), label="t",
                    monitor=mon)
        provs = [e["provenance"] for e in mon.events("compile")]
        assert provs == ["cold", "disk"]
        assert mon.summary()["compile"]["cold"] == 1
        assert mon.summary()["compile"]["disk"] == 1

    def test_warm_train_step_matches_live_dispatch(self, tmp_path):
        """The functional.py AOT seam: the warmed executable IS the step's
        own program (lower passes through the telemetry wrappers), so a
        compiled first step equals a live first step bit-for-bit."""
        paddle.seed(0)
        layer = nn.Linear(4, 3)
        step, state = make_train_step(
            layer, nn.MSELoss(), Momentum(learning_rate=0.1, momentum=0.9),
            donate=False)
        rest = (jax.random.key(0), np.float32(0.1), [jnp.ones((8, 4))],
                [jnp.zeros((8, 3))])
        compiled, prov = warm_train_step(step, (state,) + rest,
                                         cache=ExecutableCache(tmp_path))
        assert prov == "cold"
        _, (loss_aot, _) = compiled(state, *rest)
        _, (loss_live, _) = step(state, *rest)
        assert float(loss_aot) == float(loss_live)

    @pytest.mark.slow
    def test_gpt_train_step_exposes_lower(self):
        """make_gpt_train_step's arg-reorder closure passes .lower through
        (the gpt AOT seam) — lowering succeeds and the AOT compile runs."""
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.gpt import make_gpt_train_step
        from paddle_tpu.optimizer import AdamW
        paddle.seed(0)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        model = GPTModel(GPTConfig(**CFG))
        step, state = make_gpt_train_step(model, AdamW(3e-4), hcg,
                                          remat=False)
        assert hasattr(step, "lower")
        x = jnp.zeros((2, 8), jnp.int32)
        args = (state, jax.random.key(0), np.float32(3e-4), x, x)
        compiled, prov = warm_train_step(step, args, label="gpt")
        assert prov == "cold"
        _, loss = compiled(*args)
        assert np.isfinite(float(loss))


# --------------------------------------------------------- tracer window --

class TestExpectedCompiles:
    def test_warmup_window_disarms_storm_and_resolves_provenance(self,
                                                                 caplog):
        tr = Tracer(recompile_warn_threshold=1)
        tr.tick("E", 0.01)                    # post-warmup from here on
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.telemetry"):
            with tr.expected_compiles(lambda: "disk"):
                tr.compile_event("E", ("k", 1), False, 0.1)
        assert not [r for r in caplog.records
                    if "recompile storm" in r.getMessage()]
        [ev] = tr.events("compile")
        assert ev["expected"] and ev["provenance"] == "disk"
        assert tr.summary()["compile"]["post_warmup_misses"] == 0
        assert tr.summary()["compile"]["disk"] == 1
        # outside the window: default provenance cold, storm arms
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.telemetry"):
            tr.compile_event("E", ("k", 2), False, 0.1)
        assert [r for r in caplog.records
                if "recompile storm" in r.getMessage()]
        assert tr.events("compile")[-1]["provenance"] == "cold"
        assert not tr.events("compile")[-1]["expected"]

    def test_window_scoped_to_grid_keys(self):
        """With warmup_async, live traffic compiles inside the window —
        only the DECLARED grid's misses are excused (code-review catch:
        an unscoped window would mute a real storm for the whole
        warmup)."""
        tr = Tracer(recompile_warn_threshold=1)
        tr.tick("E", 0.01)
        with tr.expected_compiles(lambda: "disk",
                                  keys={"prefill:8", "seg:8:01"}):
            tr.compile_event("E", ("prefill", 8, ("sig",)), False, 0.1)
            # task labels may extend the event label (bools end the
            # label's int run): seg:8 matches task seg:8:01
            tr.compile_event("E", ("seg", 8, True, False, ("sig",)),
                             False, 0.1)
            tr.compile_event("E", ("decode", 4, ("sig",)), False, 0.1)
        evs = tr.events("compile")
        assert [e["expected"] for e in evs] == [True, True, False]
        # the off-grid miss kept default provenance and armed the storm
        assert evs[2]["provenance"] == "cold"
        assert tr.summary()["compile"]["post_warmup_misses"] == 1


# ------------------------------------------------------------ engine warmup --

class TestEngineWarmup:
    def test_warmed_engine_zero_compiles_and_oracle_outputs(self):
        """THE acceptance assertions: after warmup the whole served
        workload fetches only cache hits — zero compile misses, zero
        compile ring events — and outputs are token-for-token identical
        to a cold engine's (scratch dispatch uses a constant key and
        fresh donated caches, never live state).  Also pins purity
        (extends the PR 4 suite): the ragged program's lowering is
        byte-identical between the warmed+traced engine and a bare cold
        one — warmup instrumentation never reaches a compiled program or
        its cache key."""
        _, cold = _ragged()
        want = _serve(cold)
        tr = Tracer()
        _, eng = _ragged(tracer=tr)
        report = eng.warmup(max_workers=1)
        grid = eng.compile_grid()
        assert report["programs"] == len(grid)
        assert [t["label"] for t in report["tasks"]] == grid
        # the ragged grid is exactly one program per table-width bucket
        assert grid == [f"ragged_step:12:{C}" for C in pow2_grid(eng.MB)]
        assert all(e["expected"] for e in tr.events("compile"))
        misses0 = eng._compile_misses
        events0 = len(tr.events("compile"))
        assert _serve(eng) == want
        assert eng._compile_misses == misses0
        assert len(tr.events("compile")) == events0
        # purity: lowering identical with and without warmup
        # instrumentation (same scratch avals on both sides)
        C = 2
        text_inst = eng._build_ragged_step(eng.token_budget, C).lower(
            *eng._ragged_scratch_args(C)).as_text()
        text_bare = cold._build_ragged_step(cold.token_budget, C).lower(
            *cold._ragged_scratch_args(C)).as_text()
        assert text_inst == text_bare

    def test_second_process_reuses_disk(self, tmp_path,
                                        restore_compilation_cache):
        """THE cross-process acceptance: a second engine (fresh model,
        fresh closures — a fresh process in jit-cache terms) warming
        against the same cache dir records provenance: disk for every
        program and writes NO new XLA cache files."""
        tr1 = Tracer()
        _, eng1 = _ragged(tracer=tr1)
        eng1.warmup(cache_dir=tmp_path, max_workers=1)
        assert [e["provenance"] for e in tr1.events("compile")] \
            == ["cold"] * len(eng1.compile_grid())
        xla_dir = os.path.join(str(tmp_path), "xla")
        files_before = set(os.listdir(xla_dir))
        assert any(f.endswith("-cache") for f in files_before)

        tr2 = Tracer()
        _, eng2 = _ragged(tracer=tr2)
        eng2.warmup(cache_dir=tmp_path, max_workers=1)
        evs = tr2.events("compile")
        assert evs and all(e["provenance"] == "disk" for e in evs)
        new = {f for f in os.listdir(xla_dir)
               if f.endswith("-cache")} - files_before
        assert new == set(), f"XLA recompiled: {new}"
        assert int(tr2.registry.value("compile_disk")) == len(evs)
        # and the warmed second engine serves compile-free too
        misses = eng2._compile_misses
        _serve(eng2)
        assert eng2._compile_misses == misses

    @pytest.mark.slow
    def test_paged_engine_grid_covers_serving(self):
        """The paged engine's declared grid (prefill buckets + seg
        variants + decode per table width) really covers a chunked
        workload: zero misses after warmup."""
        model, params = _model()
        eng = PagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=32, block_size=8,
            prompt_buckets=[8, 16], prefill_chunk=8)
        labels = eng.compile_grid()
        assert "prefill:8" in labels and "decode:1" in labels
        assert any(lbl.startswith("seg:8:") for lbl in labels)
        eng.warmup(max_workers=1)
        misses = eng._compile_misses
        rid = eng.add_request(list(range(1, 13)), 3)   # chunked bucket 16
        out = eng.run_to_completion(max_ticks=200)
        assert eng._compile_misses == misses
        assert len(out[rid]) == 3

    @pytest.mark.slow
    def test_contiguous_engine_warmup_async(self):
        """Base-engine grid + warmup_async: the background Future warms
        the same grid, and the engine then serves compile-free."""
        model, params = _model()
        eng = ContinuousBatchingEngine(model, params, max_slots=2,
                                       max_len=32, prompt_buckets=[8])
        fut = eng.warmup(max_workers=1, block=False)
        report = fut.result(timeout=300)
        assert report["programs"] == len(eng.compile_grid()) == 2
        misses = eng._compile_misses
        rid = eng.add_request([1, 2, 3], 4)
        out = eng.run_to_completion(max_ticks=100)
        assert eng._compile_misses == misses
        assert len(out[rid]) == 4


    @pytest.mark.slow
    def test_speculative_engines_warmup(self):
        """With a draft the ragged engine's grid is ONE fused
        draft+verify program per table-width bucket (no dual-pool
        prefill / seg / spec-round families), at one bucket or two, and
        a warmed engine serves with zero in-serve misses."""
        model, params = _model()
        paddle.seed(1)
        draft = GPTModel(GPTConfig(**CFG))
        dparams = {n: p._data for n, p in draft.named_parameters()}
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=32, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=2)
        labels = eng.compile_grid()
        assert labels == [f"ragged_spec:{eng.token_budget}:{C}"
                          for C in pow2_grid(eng.MB)]
        eng.warmup(max_workers=1)
        m0 = eng._compile_misses
        rid = eng.add_request([1, 2, 3], 4)
        out = eng.run_to_completion(max_ticks=100)
        assert eng._compile_misses == m0 and len(out[rid]) == 4

        model.__dict__.pop("_serving_programs", None)
        eng2 = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=32, block_size=8,
            prompt_buckets=[8, 16], draft_model=draft, draft_params=dparams,
            draft_k=2)
        labels = eng2.compile_grid()
        assert all(lbl.startswith("ragged_spec:") for lbl in labels)
        assert len(labels) == len(pow2_grid(eng2.MB))
        eng2.warmup(max_workers=1)
        m0 = eng2._compile_misses
        eng2.add_request([1, 2, 3], 4)
        eng2.add_request(list(range(1, 13)), 3)      # bucket 16 spans steps
        eng2.run_to_completion(max_ticks=200)
        assert eng2._compile_misses == m0

    def test_ragged_spec_grid_zero_compiles_and_purity(self):
        """The spec-enabled ragged grid: SAME SIZE as the plain ragged
        grid (speculation adds zero program families), zero in-serve
        compiles after warmup, and the fused program's lowering is
        byte-identical between a warmed+traced engine and a bare cold
        one (warmup instrumentation never reaches a compiled program)."""
        model, params = _model()
        paddle.seed(2)
        draft = GPTModel(GPTConfig(**CFG))
        dparams = {n: p._data for n, p in draft.named_parameters()}

        def make(tracer=None):
            return RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=8,
                prompt_buckets=[8, 16], token_budget=12, tracer=tracer,
                draft_model=draft, draft_params=dparams, draft_k=2)

        cold = make()
        want = _serve(cold)
        tr = Tracer()
        eng = make(tracer=tr)
        report = eng.warmup(max_workers=1)
        grid = eng.compile_grid()
        assert report["programs"] == len(grid)
        assert grid == [f"ragged_spec:12:{C}" for C in pow2_grid(eng.MB)]
        _, plain = _ragged()
        assert len(grid) == len(plain.compile_grid())
        misses0 = eng._compile_misses
        events0 = len(tr.events("compile"))
        assert _serve(eng) == want
        assert eng._compile_misses == misses0
        assert len(tr.events("compile")) == events0
        C = 2
        text_inst = eng._build_ragged_spec_step(eng.token_budget, C).lower(
            *eng._ragged_spec_scratch_args(C)).as_text()
        text_bare = cold._build_ragged_spec_step(cold.token_budget, C).lower(
            *cold._ragged_spec_scratch_args(C)).as_text()
        assert text_inst == text_bare


# ------------------------------------------------------------- hapi flops --

class TestDynamicFlopsCache:
    def test_cost_analysis_cached_per_lowered_program(self, monkeypatch):
        """flops() used to re-lower and re-COMPILE the model every call;
        the compile+cost result is now cached on the lowered-program
        digest — a repeat query re-lowers (cheap) but never compiles."""
        from paddle_tpu.hapi import dynamic_flops
        paddle.seed(0)
        net = nn.Linear(4, 3)
        first = dynamic_flops.flops(net, (1, 4))
        calls = [0]
        orig = jax.stages.Lowered.compile

        def counting(self, *a, **kw):
            calls[0] += 1
            return orig(self, *a, **kw)

        monkeypatch.setattr(jax.stages.Lowered, "compile", counting)
        assert dynamic_flops.flops(net, (1, 4)) == first
        assert calls[0] == 0
        # a different input shape is a different program: re-measures
        dynamic_flops.flops(net, (2, 4))
        assert calls[0] == 1

    def test_config_changes_are_not_conflated(self):
        """Same class, same param shapes, different config (stride) must
        not collide: the key is the lowered PROGRAM, not (class,
        shapes)."""
        from paddle_tpu.hapi import dynamic_flops
        paddle.seed(0)
        a = dynamic_flops.flops(nn.Conv2D(3, 8, 3, stride=1, padding=1),
                                (1, 3, 16, 16))
        b = dynamic_flops.flops(nn.Conv2D(3, 8, 3, stride=2, padding=1),
                                (1, 3, 16, 16))
        assert a > 0 and b > 0 and a != b


# --------------------------------------------------------------- bucketize --

class TestBucketizeWarmup:
    def test_warmup_precompiles_every_bucket(self):
        calls = [0]

        def fn(x):
            calls[0] += 1          # trace-time counter: one trace per bucket
            return x * 2

        wrapped = bucketize(fn, buckets=(4, 8), axis=1)
        warmed = wrapped.warmup(jnp.ones((2, 3)))
        assert warmed == [4, 8]
        assert set(wrapped.bucket_calls) == {4, 8}
        assert calls[0] == 2
        # live calls land on warmed buckets: no new traces
        wrapped(jnp.ones((2, 3)))
        wrapped(jnp.ones((2, 7)))
        assert calls[0] == 2


# ------------------------------------------------------------------- CLI --

class TestWarmupCLI:
    @pytest.mark.slow
    def test_main_warms_and_reports(self, tmp_path, capsys,
                                    restore_compilation_cache):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_warmup_cli", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "tools", "warmup.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        rc = mod.main(["--cache-dir", str(tmp_path), "--engine", "ragged",
                       "--preset", "tiny", "--max-len", "32",
                       "--block-size", "8", "--token-budget", "12",
                       "--buckets", "8"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out.strip())
        assert report["programs"] >= 1
        assert report["compile"]["misses"] >= 1
        assert os.path.isdir(os.path.join(str(tmp_path), "xla"))
