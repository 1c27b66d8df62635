#!/usr/bin/env bash
# Fast import-time regression gate: `pytest --collect-only` over tests/
# must be CLEAN (a single broken import silently deselects a whole module
# from the tier-1 run — round 5's `from jax import shard_map` regression
# hid tests/test_spmd_vma_seam.py for a full round).  Run before pushing;
# tests/test_collect_smoke.py enforces the same invariant in-suite.
set -uo pipefail
cd "$(dirname "$0")/.."
out=$(JAX_PLATFORMS=cpu python -m pytest tests/ -q --collect-only \
      -p no:cacheprovider 2>&1)
rc=$?
echo "$out" | tail -3
if [ "$rc" -ne 0 ]; then
    echo "COLLECT SMOKE FAILED: import-time error in tests/ (rc=$rc)"
    exit 1
fi
# telemetry surface: the observability modules must import clean and the
# trace CLI must self-describe (its --help path exercises arg wiring
# without needing xprof)
if ! JAX_PLATFORMS=cpu python -c \
    "import paddle_tpu.telemetry, paddle_tpu.utils.stats, paddle_tpu.profiler" \
    >/dev/null 2>&1; then
    echo "COLLECT SMOKE FAILED: telemetry module import"
    exit 1
fi
if ! JAX_PLATFORMS=cpu python tools/trace_to_chrome.py --help >/dev/null 2>&1; then
    echo "COLLECT SMOKE FAILED: tools/trace_to_chrome.py --help"
    exit 1
fi
# training telemetry surface: TrainMonitor + the fit callback re-export must
# import clean, and a training JSONL dump must convert through the trace
# CLI's merge loader (the --engine-trace ingestion path, exercised without
# xprof/xplane files)
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'PYEOF'
import importlib.util, os, tempfile
from paddle_tpu.telemetry import TrainMonitor
from paddle_tpu.callbacks import TelemetryCallback  # noqa: F401 re-export
mon = TrainMonitor()
mon.record_step(0.01, trainer="smoke", examples=4, tokens=8)
mon.record_sync(0.001, loss=1.25)
path = os.path.join(tempfile.mkdtemp(), "train.jsonl")
mon.dump_jsonl(path)
spec = importlib.util.spec_from_file_location(
    "_t2c_smoke", "tools/trace_to_chrome.py")
t2c = importlib.util.module_from_spec(spec)
spec.loader.exec_module(t2c)
ct = t2c._load_engine_trace(path)
assert any(e.get("name") == "train_step" for e in ct["traceEvents"]), ct
assert any(e.get("name") == "sync" for e in ct["traceEvents"]), ct
PYEOF
then
    echo "COLLECT SMOKE FAILED: training telemetry import / JSONL merge"
    exit 1
fi
# grad-comm surface: the policy layer must import clean, the int8 local
# round trip must run, and the byte model must clear the 3.5x contract
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'GCEOF'
import jax.numpy as jnp
from paddle_tpu.distributed.grad_comm import (
    compressed_all_reduce, compressed_reduce_scatter,  # noqa: F401
    resolve_policy, wire_bytes)
p = resolve_policy("int8_ef")
tree = {"w": jnp.ones((8, 64), jnp.float32)}
out, e = p.apply_local(tree, None)
assert e is not None and out["w"].shape == (8, 64)
wb = wire_bytes(tree, p)
assert wb["pre_bytes"] / wb["post_bytes"] >= 3.5, wb
GCEOF
then
    echo "COLLECT SMOKE FAILED: grad_comm policy layer"
    exit 1
fi
# AOT surface: jit.aot must import clean, a tiny warmup→serve round trip
# must record ZERO in-serve compile misses (the compile-once contract),
# and the warmup CLI must self-describe
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'AOTEOF'
from paddle_tpu.jit.aot import (ExecutableCache, compile_aot,  # noqa: F401
                                fingerprint, run_warmup, warmup_async)
from paddle_tpu.jit import warm_train_step  # noqa: F401 functional seam
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=64,
                compute_dtype="float32")
model = GPTModel(cfg)
params = {n: p._data for n, p in model.named_parameters()}
eng = RaggedPagedContinuousBatchingEngine(
    model, params, max_slots=2, max_len=32, block_size=8,
    prompt_buckets=[8], token_budget=12)
report = eng.warmup(max_workers=1)
assert report["programs"] == len(eng.compile_grid()) >= 1, report
before = eng._compile_misses
eng.add_request([1, 2, 3], 2)
out = eng.run_to_completion(max_ticks=50)
assert eng._compile_misses == before, "warmup missed a program family"
assert all(len(v) == 2 for v in out.values()), out
AOTEOF
then
    echo "COLLECT SMOKE FAILED: jit.aot import / warmup round trip"
    exit 1
fi
if ! python tools/warmup.py --help >/dev/null 2>&1; then
    echo "COLLECT SMOKE FAILED: tools/warmup.py --help"
    exit 1
fi
# goodput ledger + ops server surface: modules import clean, a tiny train
# run's ledger buckets sum to its elapsed wall time (the exhaustiveness
# invariant), and a LIVE /metrics scrape returns the merged exposition
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'LEDEOF'
import urllib.request
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.hapi import Model
from paddle_tpu.callbacks import GoodputCallback
from paddle_tpu.telemetry_ledger import (FlightRecorder, RunLedger,  # noqa
                                         current_ledger)
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.optimizer import Adam
paddle.seed(0)
m = Model(nn.Linear(4, 2), inputs=[None])
m.prepare(Adam(0.01, parameters=m.parameters()), nn.MSELoss())
cb = GoodputCallback()
xs = np.ones((8, 4), "float32"); ys = np.zeros((8, 2), "float32")
m.fit([(xs, ys)] * 6, epochs=1, verbose=0, callbacks=[cb])
snap = cb.last_snapshot
total = sum(snap["buckets_s"].values())
assert abs(total - snap["elapsed_s"]) <= 0.01 * snap["elapsed_s"] + 1e-9, snap
assert snap["overflow_s"] == 0.0, snap
assert current_ledger() is None   # symmetric teardown
srv = OpsServer()
srv.attach(cb.ledger)
url = srv.start()
txt = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
assert "paddle_tpu_ledger_goodput" in txt, txt[:400]
code = urllib.request.urlopen(url + "/ledger", timeout=10).status
assert code == 200
srv.stop()
LEDEOF
then
    echo "COLLECT SMOKE FAILED: goodput ledger / ops server round trip"
    exit 1
fi
# serving gateway surface: the module must import clean, a tiny
# two-replica submit→stream→drain round trip must finish with zero drops
# (streamed tokens intact, drained replica stopped), and the gateway CLI
# must self-describe
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'GWEOF'
from paddle_tpu.gateway import (DeadlineExceeded, Overloaded,  # noqa: F401
                                ServingGateway)
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
from paddle_tpu.telemetry import Tracer
cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=64,
                compute_dtype="float32")
model = GPTModel(cfg)
params = {n: p._data for n, p in model.named_parameters()}
def eng():
    return RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=2, max_len=32, block_size=8,
        prompt_buckets=[8], token_budget=12, tracer=Tracer())
gw = ServingGateway(tracer=Tracer())
gw.add_replica(eng(), "a")
gw.add_replica(eng(), "b")
streams = {}
r1 = gw.submit([1, 2, 3], 3,
               on_token=lambda g, t, d: streams.setdefault(g, [])
               .append((t, d)))
r2 = gw.submit([4, 5], 2)
gw.step()
gw.drain("a")
got = gw.run_to_completion(max_ticks=200)
assert r1.status == r2.status == "finished", (r1.status, r2.status)
assert gw.is_drained("a")
assert [t for t, d in streams[r1.gid]] == r1.tokens
assert streams[r1.gid][-1][1] is True
assert sorted(got) == sorted([r1.gid, r2.gid])
assert gw.replica("a").engine.blocks_in_use == 0
GWEOF
then
    echo "COLLECT SMOKE FAILED: serving gateway round trip"
    exit 1
fi
if ! python tools/serve_gateway.py --help >/dev/null 2>&1; then
    echo "COLLECT SMOKE FAILED: tools/serve_gateway.py --help"
    exit 1
fi
# request-tracing + SLO surface: telemetry_slo must import clean, a tiny
# gateway round trip must serve live /slo + /requests + /request/<id>
# (one stitched trace, no orphan spans), and the chrome flow-event merge
# (gateway dump + engine dump through trace_to_chrome's loader) must
# carry matching s/f flow ids
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'SLOEOF'
import importlib.util, json, os, tempfile, urllib.request
from paddle_tpu.telemetry import RequestTraceIndex, TraceContext, Tracer
from paddle_tpu.telemetry_slo import Objective, PercentileSketch, SLOMonitor
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=64,
                compute_dtype="float32")
model = GPTModel(cfg)
params = {n: p._data for n, p in model.named_parameters()}
def eng():
    return RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=2, max_len=32, block_size=8,
        prompt_buckets=[8], token_budget=12, tracer=Tracer())
slo = SLOMonitor()
slo.add_objective(Objective.latency("ttft_p99", "ttft_s", 0.5))
gw = ServingGateway(tracer=Tracer())
gw.set_slo(slo)
gw.add_replica(eng(), "a")
gw.add_replica(eng(), "b")
r = gw.submit([1, 2, 3], 3)
gw.run_to_completion(max_ticks=200)
assert r.status == "finished" and r.trace is not None
srv = OpsServer()
srv.attach(gw); srv.attach(gw.replica("a").engine)
srv.attach(gw.replica("b").engine); srv.attach(slo)
url = srv.start()
snap = json.loads(urllib.request.urlopen(url + "/slo", timeout=10).read())
assert snap["objectives"][0]["name"] == "ttft_p99"
recents = json.loads(urllib.request.urlopen(
    url + "/requests", timeout=10).read())["requests"]
assert any(x["trace_id"] == r.trace.trace_id for x in recents)
one = json.loads(urllib.request.urlopen(
    url + f"/request/{r.trace.trace_id}", timeout=10).read())
ids = {s["span_id"] for s in one["spans"]}
assert all(s["parent_span_id"] in ids for s in one["spans"]
           if s["parent_span_id"] is not None), one["spans"]
assert sum(1 for s in one["spans"] if s["parent_span_id"] is None) == 1
srv.stop()
# flow-event chrome merge: gateway + engine dumps through the CLI loader
d = tempfile.mkdtemp()
gp, ep = os.path.join(d, "gw.jsonl"), os.path.join(d, "eng.jsonl")
gw.tracer.dump_jsonl(gp)
gw.replica(r.replica).engine.tracer.dump_jsonl(ep)
spec = importlib.util.spec_from_file_location(
    "_t2c_slo_smoke", "tools/trace_to_chrome.py")
t2c = importlib.util.module_from_spec(spec)
spec.loader.exec_module(t2c)
merged = []
for i, p in enumerate((gp, ep)):
    merged.extend(t2c._suffix_pids(
        t2c._load_engine_trace(p), i)["traceEvents"])
starts = {e["id"] for e in merged if e.get("ph") == "s"}
finishes = {e["id"] for e in merged if e.get("ph") == "f"}
assert starts and starts & finishes, (starts, finishes)
SLOEOF
then
    echo "COLLECT SMOKE FAILED: request-tracing / SLO round trip"
    exit 1
fi
# elastic autoscaler + simulation harness: both modules must import clean
# (no JAX needed — they are host-only), and a tiny fake-clock round trip
# must close the loop both ways — one SLO-driven scale-up (spawn → warm →
# activate, zero in-serve compiles) and one sustained-idle drain-down
# (zero drops) — with the decision timeline served by a live /autoscaler
# scrape
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'ASCEOF'
import json, urllib.request
from paddle_tpu.autoscaler import DECISIONS, ElasticAutoscaler
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.simulation import (SimClock, SimEngine, SimTracer,
                                   TrafficSim, flash_crowd, steady)
from paddle_tpu.telemetry_slo import Objective, SLOMonitor
clock = SimClock()
tracer = SimTracer(clock, capacity=8192)
gw = ServingGateway(clock=clock, tracer=tracer)
spawned = []
def factory():
    spawned.append(SimEngine(max_slots=2, tracer=SimTracer(clock)))
    return spawned[-1]
seed = SimEngine(max_slots=2, tracer=SimTracer(clock))
seed.warmup()
gw.add_replica(seed, "r0")
slo = SLOMonitor([Objective.latency(
    "ttft_p99", "ttft_s", 1.0, compliance=0.9, windows=(20.0, 5.0),
    burn_threshold=1.0, for_s=1.0, clear_s=5.0)],
    clock=clock, resolution_s=1.0, tracer=tracer)
gw.set_slo(slo)
asc = ElasticAutoscaler(gw, factory, slo=slo, min_replicas=1,
                        max_replicas=2, scale_up_cooldown_s=2.0,
                        scale_down_cooldown_s=5.0, idle_utilization=0.3,
                        idle_dwell_s=8.0, tracer=tracer, clock=clock)
sim = TrafficSim(gw, clock, flash_crowd(0.02, 6.0, 5.0, 15.0),
                 dt=0.25, seed=0, autoscaler=asc)
rep = sim.run(90.0)
assert rep["dropped"] == [], rep["dropped"]
acts = [d["action"] for d in rep["decisions"]]
assert "scale_up" in acts and "activate" in acts, acts
assert "scale_down" in acts and "removed" in acts, acts
assert all(e.warmed and e.in_serve_compiles == 0 for e in spawned)
assert rep["fleet"]["active"] == 1
assert [e["what"] for e in tracer.events("autoscale")] == acts
srv = OpsServer()
srv.attach(asc, "asc")
url = srv.start()
snap = json.loads(urllib.request.urlopen(url + "/autoscaler",
                                         timeout=10).read())
assert [d["action"] for d in snap["decisions"]] == acts
assert snap["policy"]["max_replicas"] == 2
txt = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
assert "paddle_tpu_autoscaler_fleet_size 1" in txt
assert "paddle_tpu_autoscaler_last_decision" in txt
srv.stop()
assert DECISIONS[0] == "none"
ASCEOF
then
    echo "COLLECT SMOKE FAILED: autoscaler / simulation round trip"
    exit 1
fi
# fault-injection + gateway resilience: faults.py must import clean (no
# JAX — it is the host-only chaos layer), and a tiny fake-clock
# crash -> retry -> recover round trip must close the loop — a flaky
# dispatch window retried within budget, the breaker opening and
# re-closing through a half-open probe, a crashed replica quarantined
# with its work replayed token-exactly — with breaker/brownout state
# served by a live /resilience scrape
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'RESEOF'
import json, urllib.request
from paddle_tpu.faults import (Fault, FaultPlan, FaultyEngine,
                               TransientDispatchError)
from paddle_tpu.gateway import ResiliencePolicy, ServingGateway
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.simulation import SimClock, SimEngine, SimTracer, sim_tokens
clock = SimClock()
tracer = SimTracer(clock, capacity=8192)
pol = ResiliencePolicy(retry_budget=3, retry_backoff_s=0.1,
                       retry_jitter=0.0, breaker_failures=2,
                       breaker_open_s=2.0, hedge=False, brownout=False)
gw = ServingGateway(clock=clock, tracer=tracer, stall_threshold_s=4.0,
                    resilience=pol)
plan = FaultPlan([Fault("dispatch_error", at_s=0.0, duration_s=1.0),
                  Fault("crash", at_s=6.0)])
bad = SimEngine(max_slots=2, tracer=SimTracer(clock))
gw.add_replica(FaultyEngine(bad, plan, clock, replica="bad"), "bad")
gw.add_replica(SimEngine(max_slots=2, tracer=SimTracer(clock)), "ok")
hs = [gw.submit([i + 1, 2], 20) for i in range(4)]
for _ in range(200):
    gw.step()
    clock.advance(0.25)
    if not gw.pending():
        break
assert all(h.status == "finished" for h in hs), [h.status for h in hs]
assert all(h.tokens == sim_tokens(h.prompt, 20) for h in hs)
snap = gw.resilience_snapshot()
assert snap["counters"]["retries"] >= 1
assert snap["counters"]["breaker_opens"] >= 1
assert all(h.retries <= pol.retry_budget for h in hs)
assert gw.replica("bad").state == "quarantined"   # the crash, detected
whats = [e["what"] for e in tracer.events("resilience")]
assert "retry" in whats and "breaker_open" in whats
srv = OpsServer()
srv.attach(gw, "gw")
url = srv.start()
live = json.loads(urllib.request.urlopen(url + "/resilience",
                                         timeout=10).read())
assert live["breakers"]["bad"]["state"] in ("closed", "open", "half_open")
assert live["brownout"] is None                    # disabled -> honest
txt = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
assert "paddle_tpu_resilience_retries" in txt
srv.stop()
RESEOF
then
    echo "COLLECT SMOKE FAILED: faults / gateway-resilience round trip"
    exit 1
fi
# ragged speculative surface: a tiny draft+target round trip through the
# unified ragged spec engine — warmed grid (ZERO in-serve compiles), a
# mixed spec/non-spec tick, the stream equal to the plain-decode oracle
# (the greedy contract), and acceptance stats live in metrics/Prometheus
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'SPECEOF'
import numpy as np
import jax.numpy as jnp
import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=64,
                compute_dtype="float32")
paddle.seed(0)
model = GPTModel(cfg)
params = {n: p._data for n, p in model.named_parameters()}
paddle.seed(1)
draft = GPTModel(cfg)
dparams = {n: p._data for n, p in draft.named_parameters()}
eng = RaggedPagedContinuousBatchingEngine(
    model, params, max_slots=2, max_len=32, block_size=8,
    prompt_buckets=[8], draft_model=draft, draft_params=dparams,
    draft_k=2)
report = eng.warmup(max_workers=1)
assert report["programs"] == len(eng.compile_grid()) >= 1, report
before = eng._compile_misses
rid = eng.add_request([1, 2, 3], 4)
rid2 = eng.add_request([4, 5], 3, spec=False)   # mixed spec/non-spec tick
out = eng.run_to_completion(max_ticks=100)
assert eng._compile_misses == before, "spec grid missed a family"
oracle = model.generate(params, jnp.asarray([[1, 2, 3]], jnp.int32), 4,
                        greedy=True)
assert out[rid] == [int(t) for t in np.asarray(oracle)[0]], out
assert len(out[rid2]) == 3, out
m = eng.metrics()
assert m["tokens_drafted"] > 0 and 0.0 <= m["acceptance_rate"] <= 1.0
assert "tokens_accepted" in eng.prometheus_text()
SPECEOF
then
    echo "COLLECT SMOKE FAILED: ragged speculative round trip"
    exit 1
fi
# tiered KV store + disaggregation surface: kv_store must import, a tiny
# real-engine demote -> evict-from-HBM -> lookup -> restore round trip
# must stay token-exact vs the solo oracle with the allocator balanced,
# and a sim disaggregated fleet (prefill role -> byte-budgeted migration
# -> decode role) must serve a live /kvstore scrape with the migration
# counted and the kvstore gauge family on /metrics
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'KVEOF'
import json, urllib.request
import numpy as np
import jax.numpy as jnp
import paddle_tpu as paddle
from paddle_tpu.kv_store import KVPage, PageMigration, TieredKVStore
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=64,
                compute_dtype="float32")
paddle.seed(0)
model = GPTModel(cfg)
params = {n: p._data for n, p in model.named_parameters()}
store = TieredKVStore()
eng = RaggedPagedContinuousBatchingEngine(
    model, params, max_slots=2, max_len=48, block_size=8,
    prompt_buckets=[8, 32], enable_prefix_cache=True, kv_store=store)
prompt = list(range(1, 21))
rid = eng.add_request(prompt, 4)
out1 = eng.run_to_completion(max_ticks=200)
n = eng.flush_prefix()                     # demote: HBM empties
assert n > 0 and len(eng._prefix_cache) == 0
assert store.snapshot()["dram"]["pages"] == n
rid2 = eng.add_request(prompt, 4)          # lookup -> restore
out2 = eng.run_to_completion(max_ticks=200)
oracle = model.generate(params, jnp.asarray([prompt], jnp.int32), 4,
                        greedy=True)
want = [int(t) for t in np.asarray(oracle)[0]]
assert out1[rid] == want and out2[rid2] == want, "restore diverged"
m = eng.metrics()
assert m["kvstore_restored_blocks"] >= 1
assert m["blocks_allocated"] == m["blocks_released"]
assert eng.prefix_match(prompt)["total"] >= 1
# sim disaggregated fleet + live /kvstore
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.simulation import SimClock, SimEngine, SimTracer, sim_tokens
clock = SimClock()
gw = ServingGateway(clock=clock, tracer=SimTracer(clock),
                    migration_bytes_per_tick=1024)
gw.add_replica(SimEngine(max_slots=2, prefix_caching=True, block_size=4,
                         tracer=SimTracer(clock)), "pf", role="prefill")
gw.add_replica(SimEngine(max_slots=2, prefix_caching=True, block_size=4,
                         kv_store=TieredKVStore(),
                         tracer=SimTracer(clock)), "dc", role="decode")
h = gw.submit(list(range(1, 17)), 6)
for _ in range(200):
    gw.step(); clock.advance(0.25)
    if not gw.pending():
        break
assert h.status == "finished" and h.tokens == sim_tokens(h.prompt, 6)
assert gw.kvstore_snapshot()["counters"]["migrations_completed"] == 1
srv = OpsServer(); srv.attach(gw, "gw")
url = srv.start()
live = json.loads(urllib.request.urlopen(url + "/kvstore",
                                         timeout=10).read())
assert live["counters"]["migrated_bytes"] > 0
assert live["replicas"]["dc"]["store"] is not None
txt = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
assert "paddle_tpu_kvstore_migrations_completed" in txt
srv.stop()
KVEOF
then
    echo "COLLECT SMOKE FAILED: kv_store tiering / disaggregation round trip"
    exit 1
fi
# sharding-rules surface: the resolver must import clean and round-trip a
# tiny rule table, the rules digest must be LIVE in the AOT fingerprint
# environment (register -> fingerprint moves -> unregister -> restores),
# and a 2-replica weight-update-sharded train step (arXiv:2004.13336)
# must train while holding exactly half the replicated optimizer HBM
if ! JAX_PLATFORMS=cpu \
     XLA_FLAGS="--xla_force_host_platform_device_count=2" \
     python - >/dev/null 2>&1 <<'SREOF'
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec
from paddle_tpu.distributed import sharding_rules as sr
from paddle_tpu.distributed.update_sharding import (
    make_dp_update_sharded_train_step, update_sharding_rules)
from paddle_tpu.distributed.zero import per_device_state_bytes
from paddle_tpu.jit.aot import fingerprint
from paddle_tpu.optimizer import Adam
assert jax.device_count() == 2
specs = sr.ShardingRules([(r"w", ("data", None)), (r".*", None)]).resolve(
    {"w": np.zeros((8, 4), np.float32), "step": np.zeros((), np.float32)})
assert specs["w"] == PartitionSpec("data")
assert specs["step"] == PartitionSpec()
fp0 = fingerprint("smoke")
sr.register_rules(sr.ShardingRules([(r".*", ("data",))],
                                   name="smoke_probe"))
assert fingerprint("smoke") != fp0          # digest is in the env
sr.unregister_rules("smoke_probe")
assert fingerprint("smoke") == fp0
mesh = Mesh(np.array(jax.devices()), ("data",))
params = {"w": jnp.ones((8, 4), jnp.float32)}
def loss_of(p, x):
    return jnp.mean((x @ p["w"]) ** 2)
step, state = make_dp_update_sharded_train_step(
    loss_of, params, Adam(0.05), mesh)
assert per_device_state_bytes(state) == 2 * 8 * 4 * 4 // 2  # Adam m+v / R
x = jnp.ones((4, 8), jnp.float32)
state, l0 = step(state, np.float32(0.05), x)
state, l1 = step(state, np.float32(0.05), x)
assert float(l1) < float(l0)
flat = update_sharding_rules().resolve(
    {"opt": {"slots": {"flat": np.zeros((4,), np.float32)}}})
assert flat["opt"]["slots"]["flat"] == PartitionSpec("data")
SREOF
then
    echo "COLLECT SMOKE FAILED: sharding-rules / update-sharding round trip"
    exit 1
fi
# memory-ledger surface: telemetry_memory must import clean, one train
# step under an active ledger must attribute params + optimizer state,
# one census must conserve bytes (sum of pools == total, residual
# honest in `other`), the KV-store seam must resync tier bytes, and a
# LIVE /memory scrape beside /metrics must serve the snapshot with the
# memory gauge family merged in
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'MEMEOF'
import json, urllib.request
import numpy as np
import jax.numpy as jnp
import jax
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit.functional import make_train_step
from paddle_tpu.kv_store import KVPage, TieredKVStore
from paddle_tpu.ops_server import OpsServer
from paddle_tpu.optimizer import Momentum
from paddle_tpu.telemetry import TrainMonitor
from paddle_tpu.telemetry_memory import (MemoryLedger,
                                         current_memory_ledger)
assert current_memory_ledger() is None      # off by default
paddle.seed(0)
ml = MemoryLedger()
with ml:
    # monitor= is the re-registration seam: the donated state is rebuilt
    # each step and the fresh ids re-registered after the call
    step, state = make_train_step(nn.Linear(4, 3), nn.MSELoss(),
                                  Momentum(learning_rate=0.1, momentum=0.9),
                                  monitor=TrainMonitor())
    state, _ = step(state, jax.random.key(0), np.float32(0.1),
                    [jnp.ones((8, 4))], [jnp.zeros((8, 3))])
    store = TieredKVStore()
    store.put(KVPage(b"k" * 32, (np.ones((64,), np.float32),), ["m"]))
walk = ml.census()
assert sum(walk["pools"].values()) == walk["total_bytes"], walk
assert walk["pools"]["params"] > 0 and walk["pools"]["optimizer_state"] > 0
snap = ml.memory_snapshot()
assert snap["kv_tiers"]["dram"]["bytes"] == 64 * 4, snap["kv_tiers"]
assert current_memory_ledger() is None      # symmetric teardown
srv = OpsServer()
srv.attach(ml, name="mem")
url = srv.start()
live = json.loads(urllib.request.urlopen(url + "/memory",
                                         timeout=10).read())
assert sum(p["device_bytes"] for p in live["pools"].values()) \
    == live["totals"]["device_bytes"], live["totals"]
txt = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
assert "paddle_tpu_memory_params_device_bytes" in txt, txt[:400]
assert "paddle_tpu_memory_total_device_bytes" in txt
srv.stop()
counters = [e for e in ml.to_chrome_counters() if e.get("ph") == "C"]
assert counters, "no chrome counter events"
MEMEOF
then
    echo "COLLECT SMOKE FAILED: memory-ledger round trip"
    exit 1
fi
# fleet observability plane: a FleetCollector over TWO live ops servers
# must federate both (rollups merged), flip a killed target to a labeled
# `stale` gap without corrupting the survivor's rollups, spool every
# sample durably, and RESUME the spool (seq continues, no duplicates)
# across a collector restart — the crash-survival contract
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'FLEETEOF'
import json, tempfile, urllib.request
from paddle_tpu.simulation import SimClock, SimFleetHost
from paddle_tpu.telemetry_fleet import FleetCollector
from paddle_tpu.ops_server import OpsServer

class FakeClock:
    t = 0.0
    def __call__(self):
        return self.t

clk, fclk = SimClock(), FakeClock()
spool_dir = tempfile.mkdtemp()
hosts = [SimFleetHost(clk, name=f"h{i}") for i in range(2)]
for h in hosts:
    h.submit([1, 2, 3, 4], 4)
for _ in range(12):
    clk.advance(0.05)
    for h in hosts:
        h.engine.step()
        h.ledger.record("compute", 0.05)
urls = [h.server.start() for h in hosts]
col = FleetCollector(interval_s=5.0, clock=fclk, timeout_s=5.0,
                     spool_dir=spool_dir)
for h, url in zip(hosts, urls):
    col.add_target(h.name, url)
snap = col.scrape_once()
assert snap["rollup"]["targets_ok"] == 2, snap["rollup"]
assert snap["rollup"]["fleet_ttft_p99"] is not None
# GET /fleet serves the SAME snapshot the collector holds
front = OpsServer()
front.attach(col, name="fleet")
furl = front.start()
live = json.loads(urllib.request.urlopen(furl + "/fleet",
                                         timeout=10).read())
assert live["rollup"] == json.loads(json.dumps(snap["rollup"]))
front.stop()
# kill one host: past the staleness window it is a LABELED gap and the
# survivor's rollup stands alone
hosts[1].server.stop()
fclk.t += 20.0
snap = col.scrape_once()
by = {r["target"]: r["status"] for r in snap["targets"]}
assert by == {"h0": "ok", "h1": "stale"}, by
assert snap["rollup"]["targets_stale"] == 1
seq_before = col.spool.stats()["seq"]
assert seq_before >= 6                   # 2 rounds * (targets + rollup)
records = col.spool.records()
col.stop()
hosts[0].server.stop()
# restart: the spool resumes — history intact, seq continues, no dups
col2 = FleetCollector(interval_s=5.0, clock=fclk, spool_dir=spool_dir)
assert col2.spool.records() == records
assert col2.spool.append({"kind": "probe"}) == seq_before + 1
FLEETEOF
then
    echo "COLLECT SMOKE FAILED: fleet federation round trip"
    exit 1
fi
# training resilience (ISSUE 20): a tiny train child starts an async
# two-phase checkpoint save and SIGKILLs itself mid-save; the parent must
# resume from the newest COMMITted step (the torn dir counted-skipped,
# never loaded) and the resumed loss curve must equal the uninterrupted
# oracle BIT-EXACTLY.  ckpt_fsck must agree the root is resumable.
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'RESEOF'
import os, signal, subprocess, sys, tempfile
import jax, jax.numpy as jnp, numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit.functional import make_train_step
from paddle_tpu.optimizer import Momentum
from paddle_tpu.train_resilience import (CheckpointManager,
                                         ResumableIterator, TrainSupervisor)

def trainer():
    paddle.seed(0)
    layer = nn.Linear(8, 4)
    step, state = make_train_step(layer, nn.MSELoss(),
                                  Momentum(learning_rate=0.1, momentum=0.9))
    r = np.random.RandomState(1)
    batches = [([jnp.asarray(r.randn(4, 8), jnp.float32)],
                [jnp.asarray(r.randn(4, 4), jnp.float32)])
               for _ in range(8)]
    return step, state, ResumableIterator(batches)

def supervise(root, **kw):
    step, state, data = trainer()
    return TrainSupervisor(step, state, CheckpointManager(root),
                           base_key=jax.random.PRNGKey(0), lr=0.1,
                           data=data, save_every=4, backoff_s=0.0, **kw)

td = tempfile.mkdtemp()
oracle = supervise(os.path.join(td, "oracle")).run(16)
assert oracle["completed"] and len(oracle["losses"]) == 16

# the child trains 8 steps with async saves, then dies mid-async-save
root = os.path.join(td, "crash")
child = r'''
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit.functional import make_train_step
from paddle_tpu.optimizer import Momentum
from paddle_tpu.train_resilience import (CheckpointManager,
                                         ResumableIterator, TrainSupervisor)
paddle.seed(0)
layer = nn.Linear(8, 4)
step, state = make_train_step(layer, nn.MSELoss(),
                              Momentum(learning_rate=0.1, momentum=0.9))
r = np.random.RandomState(1)
batches = [([jnp.asarray(r.randn(4, 8), jnp.float32)],
            [jnp.asarray(r.randn(4, 4), jnp.float32)]) for _ in range(8)]
def die_mid_save(t, sup):
    if t == 8:
        sup._save(t)                      # async save now in flight
        os.kill(os.getpid(), signal.SIGKILL)
sup = TrainSupervisor(step, state, CheckpointManager(%r),
                      base_key=jax.random.PRNGKey(0), lr=0.1,
                      data=ResumableIterator(batches), save_every=4,
                      backoff_s=0.0, async_save=True,
                      on_boundary=die_mid_save)
sup.run(16)
''' % (os.getcwd(), root)
proc = subprocess.run([sys.executable, "-c", child], capture_output=True,
                      timeout=300)
assert proc.returncode == -signal.SIGKILL, proc.stderr.decode()[-2000:]

# fsck agrees the root is resumable despite the kill
import tools.ckpt_fsck as fsck
assert fsck.main([root, "verify"]) == 0

# resume: the tail of the loss curve must equal the oracle bit-exactly
sup2 = supervise(root)
res = sup2.run(16)
assert res["completed"], res
first = res["first_step"]
assert 0 < first <= 8, first              # resumed from a committed step
assert res["losses"] == oracle["losses"][first:], "loss curve diverged"
assert res["final_loss"] == oracle["final_loss"]
RESEOF
then
    echo "COLLECT SMOKE FAILED: train-resilience crash/resume round trip"
    exit 1
fi
# tpulint gate, per-file rules + whole-program concurrency passes: any NEW
# violation vs tools/tpulint_baseline.json fails (exit 1, rule id +
# file:line printed above); a STALE baseline (violations burned down but
# baseline not shrunk) fails with exit 3 — regenerate via
# `python tools/tpulint.py --write-baseline --program paddle_tpu tools`.
# The linter is stdlib-only (no JAX import), so the full sweep costs
# seconds (<30 s is the budget tests/test_tpulint_gate.py enforces).
python tools/tpulint.py --program paddle_tpu tools
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    echo "COLLECT SMOKE FAILED: tpulint (rc=$lint_rc; 1=new violations," \
         "3=stale baseline — see docs/STATIC_ANALYSIS.md)"
    exit 1
fi
# lock-discipline sanitizer smoke: the runtime complement to --program.
# A sanitizer-instrumented threaded round trip over a real gateway's
# scrape surface must record ZERO violations, and the sanitizer itself
# must still CATCH a deliberate lock-order inversion (the detector is
# alive, not just silent).
if ! JAX_PLATFORMS=cpu python - >/dev/null 2>&1 <<'SANEOF'
import threading
from paddle_tpu.analysis import LockSanitizer
from paddle_tpu.gateway import ServingGateway
from paddle_tpu.simulation import SimClock, SimEngine, SimTracer
san = LockSanitizer("smoke")
clock = SimClock()
gw = ServingGateway(clock=clock, tracer=SimTracer(clock))
gw.add_replica(SimEngine(max_slots=2, tracer=SimTracer(clock)), "r0")
san.instrument(gw)
stop = threading.Event()
errors = []
def scrape():
    try:
        while not stop.is_set():
            gw.gateway_snapshot(); gw.prometheus_text()
    except Exception as e:
        errors.append(e)
t = threading.Thread(target=scrape)
t.start()
h = gw.submit([1, 2, 3], 8)
for _ in range(200):
    gw.step(); clock.advance(0.25)
    if not gw.pending():
        break
stop.set(); t.join()
assert not errors, errors
assert h.status == "finished"
san.assert_clean()
bad = LockSanitizer("canary")
a = bad.wrap(threading.Lock(), "a")
b = bad.wrap(threading.Lock(), "b")
with a:
    with b: pass
with b:
    with a: pass
assert any(v["kind"] == "lock-order-inversion" for v in bad.violations())
SANEOF
then
    echo "COLLECT SMOKE FAILED: lock-sanitizer threaded smoke"
    exit 1
fi
echo "collect smoke OK"
