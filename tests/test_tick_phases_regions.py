"""One clock for host and chip (ISSUE 25): the phases and the pack the
ragged engine records on each ``tick`` event, the tick number that joins
request events to rounds, ``add_request(due_at=)``, the no-tracer contract
of the phase helper, and the named regions of the compiled programs.

The regions are metadata: what is checked here is the ``op_name`` of the
compiled HLO, forward and backward; what a device trace makes of them is
``benchmarks/tests/test_xregion.py``."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.jit.bucketing import select_bucket
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.ops.ragged_paged_attention import MIN_RUN, grouped_rows
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                RaggedPagedContinuousBatchingEngine)
from paddle_tpu.telemetry import (PART_CALL, PART_KEY, PART_OPERANDS,
                                  PART_STATS, PARTS, PHASE_DISPATCH,
                                  PHASE_SYNC, PHASE_UNPACK, PHASES, Tracer)

REGIONS = ("embed", "layers", "attn", "mlp", "kv_write", "head", "optimizer",
           "flash_attention", "ragged_paged_attention")
PROMPTS = [[5, 17, 3], [40, 2], [9, 9, 9, 9, 9, 1], [61], [8, 30, 12, 4],
           [77, 13, 2, 5, 6, 7, 8]]
BUDGETS = [10, 4, 7, 12, 3, 8]
# engine geometry and requests per scenario; a preemption and a dry pool are
# the cases a pack rebuilt from request events gives up on (the benchmark
# reads the tick's own ``rows``: serve.packed_rows)
SCENARIOS = {
    "plain": (dict(), PROMPTS, BUDGETS),
    "preemption": (dict(max_slots=2, num_blocks=8, prompt_buckets=[8],
                        token_budget=10), PROMPTS[:2], [14, 14]),
    "dry_pool": (dict(max_slots=3, num_blocks=6, prompt_buckets=[16],
                      token_budget=24),
                 [list(range(1, 15)), list(range(2, 16)), [3, 4, 5]],
                 [4, 4, 4]),
    "spec": (dict(draft=True), PROMPTS[:4], BUDGETS[:4]),
}
SCENARIO_IDS = ["plain", "preemption", "dry_pool", "spec"]
SPANS = {}      # scenario -> [(span name, its stats)] in the order opened


@pytest.fixture(scope="module")
def model_and_params():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    compute_dtype="float32")
    model = GPTModel(cfg)
    params = {n: p._data for n, p in model.named_parameters()}
    return model, params


def _ragged(model, params, tracer=None, draft=False, **kw):
    cfg = dict(max_slots=3, max_len=32, block_size=4,
               prompt_buckets=[8, 16], token_budget=12)
    cfg.update(kw)
    if draft:       # the model drafts for itself: every proposal accepted
        cfg.update(draft_model=model, draft_params=params, draft_k=2)
    return RaggedPagedContinuousBatchingEngine(model, params,
                                               tracer=tracer, **cfg)


@pytest.fixture(scope="module")
def served(model_and_params):
    """{scenario: (engine, tracer, prompts, outputs)}, each served once."""
    model, params = model_and_params
    out = {}
    real = telemetry._annotation
    with pytest.MonkeyPatch.context() as mp:
        for name in SCENARIO_IDS:
            kw, prompts, budgets = SCENARIOS[name]
            opened = SPANS[name] = []
            mp.setattr(telemetry, "_annotation",
                       lambda span, _to=opened, **stats:
                       _to.append((span, stats)) or real(span, **stats))
            tr = Tracer()
            eng = _ragged(model, params, tracer=tr, **kw)
            for p, n in zip(prompts, budgets):
                eng.add_request(p, n)
            got = eng.run_to_completion(max_ticks=500)
            assert len(got) == len(prompts)
            out[name] = (eng, tr, prompts, got)
    return out


# ------------------------------------------------------ the tick event --

@pytest.mark.parametrize("scenario", SCENARIO_IDS)
def test_tick_carries_number_phases_and_rows(served, scenario):
    eng, tr, prompts, got = served[scenario]
    ticks = tr.events("tick")
    assert [e["tick"] for e in ticks] == list(range(1, len(ticks) + 1))
    rows_of = {}
    for e in ticks:
        assert set(e["phases"]) <= set(PHASES)
        assert all(v >= 0 for v in e["phases"].values())
        assert sum(e["phases"].values()) <= e["dur_s"]
        rows = e.get("rows", [])
        assert sum(n for _, n, _ in rows) == e.get("budget_used", 0)
        assert e.get("budget_used", 0) <= eng.token_budget
        if rows:    # a round that ran the program went through all five
            assert set(e["phases"]) == set(PHASES)
            assert len({rid for rid, _, _ in rows}) == len(rows)
            # the kernel's MXU runs: a prefill chunk of MIN_RUN rows or
            # more, never a decode row or a verify chunk
            assert e["grouped_rows"] == sum(
                n for _, n, _ in rows if n >= MIN_RUN) \
                == grouped_rows(rows, eng.token_budget)
        for rid, n, kv_end in rows:
            assert n >= 1 and 0 <= kv_end <= eng.max_len
            rows_of.setdefault(rid, []).append((n, kv_end))
        extra = e.get("budget_used", 0) - e.get("decode_rows", 0) \
            - e.get("prefill_tokens", 0)        # the verify chunks' K rows
        assert extra % eng.K == 0 and (scenario == "spec" or extra == 0)
    assert set(rows_of) == set(range(len(prompts)))     # all were packed


def test_pack_is_recorded_only_with_a_tracer(model_and_params, served):
    model, params = model_and_params
    eng = _ragged(model, params)
    eng.add_request(PROMPTS[2], 3)
    eng.run_to_completion(max_ticks=50)
    assert "grouped_rows" not in eng._tick_note \
        and "rows" not in eng._tick_note
    assert any(e["grouped_rows"] for e in served["plain"][1].events("tick")
               if e.get("rows"))


def test_rows_survive_a_forced_preemption(served):
    eng, tr, prompts, got = served["preemption"]
    assert eng.preemptions >= 1
    victim = next(tl.rid for tl in tr.timelines() if tl.replays)
    P = select_bucket(len(prompts[victim]), eng.buckets)
    prefill = [n for e in tr.events("tick") for rid, n, _ in e.get("rows", [])
               if rid == victim and n > 1]
    # the victim's prompt was packed once per attempt
    assert sum(prefill) == P * (1 + next(
        tl.replays for tl in tr.timelines() if tl.rid == victim))


def test_rows_show_the_chunk_a_dry_pool_shrank(served):
    eng, tr, prompts, got = served["dry_pool"]
    filled, shrunk = {}, 0
    for e in tr.events("tick"):
        for rid, n, kv_end in e.get("rows", []):
            P = select_bucket(len(prompts[rid]), eng.buckets)
            if filled.get(rid, 0) < P:          # still a prefill chunk
                filled[rid] = filled.get(rid, 0) + n
                if filled[rid] < P and e["budget_used"] < eng.token_budget:
                    shrunk += 1     # budget left, prompt left: no blocks
    assert shrunk >= 1


def test_verify_chunks_are_rows_of_k_plus_one(served):
    eng, tr, prompts, got = served["spec"]
    assert eng.spec_rounds >= 1
    sizes = {n for e in tr.events("tick") if not e.get("prefill_tokens")
             for _, n, _ in e.get("rows", [])}
    assert sizes <= {1, eng.K + 1} and eng.K + 1 in sizes


# ------------------------------------- the parts, and the round's kind --

def _dispatch_parts(scenario):
    # the fused draft + verify program is greedy and takes no key
    return (PART_OPERANDS, PART_CALL) if scenario == "spec" \
        else (PART_OPERANDS, PART_KEY, PART_CALL)


def test_parts_are_named_apart_from_the_phases():
    assert PARTS == (PART_OPERANDS, PART_KEY, PART_CALL, PART_STATS)
    assert not set(PARTS) & set(PHASES) and len(set(PARTS)) == 4
    for part in PARTS:      # each lies inside the phase its name begins with
        assert part.rsplit(".", 1)[0] in (PHASE_DISPATCH, PHASE_SYNC)


@pytest.mark.parametrize("scenario", SCENARIO_IDS)
def test_parts_partition_dispatch_on_the_tick_event(served, scenario):
    eng, tr, prompts, got = served[scenario]
    ran = [e for e in tr.events("tick") if e.get("rows")]
    assert ran
    shares = []
    for e in tr.events("tick"):
        assert set(e["parts"]) <= set(PARTS)
        assert not set(e["parts"]) & set(e["phases"])
        assert sum(e["phases"].values()) <= e["dur_s"]  # as it was
    for e in ran:
        assert tuple(e["parts"]) == _dispatch_parts(scenario)
        assert all(v >= 0 for v in e["parts"].values())
        whole = e["phases"][PHASE_DISPATCH]
        assert sum(e["parts"].values()) <= whole
        shares.append(sum(e["parts"].values()) / whole)
    # a part ends on the clock reading that begins the next, the last on
    # the phase's own: what is left is the phase's entry to its first part
    assert sorted(shares)[len(shares) // 2] > 0.98


@pytest.mark.parametrize("scenario", SCENARIO_IDS)
def test_spans_after_the_pack_carry_the_round_kind(served, scenario):
    eng, tr, prompts, got = served[scenario]
    by_tick = {}
    for name, stats in SPANS[scenario]:
        by_tick.setdefault(stats["tick"], []).append((name, stats))
    ticks = {e["tick"]: e for e in tr.events("tick")}
    assert set(by_tick) == set(ticks)
    narrow = 0
    for number, spans in by_tick.items():
        e = ticks[number]
        names = [name for name, _ in spans]
        assert names[:3] == [telemetry.PHASE_TICK, telemetry.PHASE_ADMIT,
                             telemetry.PHASE_PACK]
        for _, stats in spans[:3]:      # opened before the pack is known
            assert stats == {"tick": number}
        if not e.get("rows"):
            assert len(spans) == 3
            continue
        assert names[3:] == [PHASE_DISPATCH, *_dispatch_parts(scenario),
                             PHASE_SYNC, PHASE_UNPACK]
        kind = {"tick": number, "chunk_rows": e["prefill_tokens"]}
        assert all(stats == kind for _, stats in spans[3:])
        assert e["rows_run"] in (eng.token_budget, eng.narrow_rows)
        narrow += e["rows_run"] != eng.token_budget
        if e["rows_run"] == eng.narrow_rows != 0:
            assert kind["chunk_rows"] == 0      # decode rows only
    assert narrow == eng.metrics()["narrow_steps"]


def test_the_stats_read_is_a_part_only_where_the_model_has_tick_stats(
        served):
    """``engine.sync.stats`` brackets the noting of the model's counters
    (they come back behind the tokens, in the one vector the round
    reads), which happens only with a tracer: GPT names none and has no
    such span; the latent model's tick returns them and has one."""
    from benchmarks.lib import weights_pangu
    from paddle_tpu.models.pangu_moe import (TICK_STATS, PanguMoeConfig,
                                             PanguMoeModel)
    assert all(PART_STATS not in e["parts"] for name in SCENARIO_IDS
               for e in served[name][1].events("tick"))
    cfg = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
               first_k_dense_replace=1, num_attention_heads=4,
               q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
               moe_intermediate_size=12, n_shared_experts=1,
               num_experts_per_tok=3, routed_scaling_factor=2.5,
               norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=25600000,
               max_position_embeddings=128, initializer_range=0.2)
    paddle.seed(0)
    model = PanguMoeModel(PanguMoeConfig(
        **cfg, n_routed_experts=16, experts_held=range(4, 8),
        compute_dtype="float32"))
    params = weights_pangu.make_params(
        dict(cfg, n_routed_experts=4, router_width=16, experts_held=[4, 8]),
        7, "float32")
    outs = {}
    for tr in (Tracer(), None):
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=3, max_len=64, block_size=8,
            num_blocks=20, token_budget=16,
            prompt_buckets=list(range(8, 65, 8)), tracer=tr)
        eng.add_request(list(range(1, 20)), 4)
        outs[tr is None] = eng.run_to_completion(max_ticks=50)
        if tr is None:
            continue
        ran = [e for e in tr.events("tick") if e.get("rows")]
        assert ran and all(set(TICK_STATS) <= set(e) for e in ran)
        for e in ran:
            assert tuple(e["parts"]) == (PART_OPERANDS, PART_KEY, PART_CALL,
                                         PART_STATS)
            assert 0 <= e["parts"][PART_STATS] <= e["phases"][PHASE_SYNC]
    assert outs[True] == outs[False]    # and the tokens are the same


@pytest.mark.parametrize("scenario", SCENARIO_IDS)
def test_request_events_carry_the_tick_that_emitted_them(served, scenario):
    eng, tr, prompts, got = served[scenario]
    # the tracer's own clock at both ends of the round: no tolerance
    spans = {e["tick"]: (e["ts_open"], e["ts"]) for e in tr.events("tick")}
    inside = [e for e in tr.events("request") if e["what"] != "queued"]
    assert inside and all("tick" not in e for e in tr.events("request")
                          if e["what"] == "queued")
    for e in inside:
        lo, hi = spans[e["tick"]]
        assert lo <= e["ts"] <= hi
    # a request's timeline joins the rounds that served it by number
    for rid in range(len(prompts)):
        rounds = {e["tick"] for e in inside
                  if e["rid"] == rid and e["what"] == "token"}
        packed = {e["tick"] for e in tr.events("tick")
                  if any(r[0] == rid for r in e.get("rows", []))}
        assert rounds and rounds <= packed


def test_base_engine_ticks_carry_the_same_phase_names(model_and_params):
    model, params = model_and_params
    tr = Tracer()
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32,
                                   prompt_buckets=[8], tracer=tr)
    eng.add_request(PROMPTS[0], 4)
    eng.run_to_completion(max_ticks=50)
    ticks = tr.events("tick")
    assert ticks and all(set(e["phases"]) <= set(PHASES) for e in ticks)
    assert any(set(e["phases"]) == set(PHASES) for e in ticks)
    assert all(sum(e["phases"].values()) <= e["dur_s"] for e in ticks)


def test_base_engine_dispatch_has_the_same_three_parts(model_and_params,
                                                       monkeypatch):
    model, params = model_and_params
    opened, real = [], telemetry._annotation
    monkeypatch.setattr(telemetry, "_annotation",
                        lambda span, **stats: opened.append((span, stats))
                        or real(span, **stats))
    tr = Tracer()
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32,
                                   prompt_buckets=[8], tracer=tr)
    eng.add_request(PROMPTS[0], 4)
    eng.run_to_completion(max_ticks=50)
    ran = [e for e in tr.events("tick") if PHASE_DISPATCH in e["phases"]]
    assert ran
    for e in ran:
        assert tuple(e["parts"]) == (PART_OPERANDS, PART_KEY, PART_CALL)
        assert sum(e["parts"].values()) <= e["phases"][PHASE_DISPATCH]
    # its prefill runs inside engine.admit, so a round has no kind to say
    assert {span for span, _ in opened} == {telemetry.PHASE_TICK, *PHASES,
                                            PART_OPERANDS, PART_KEY,
                                            PART_CALL}
    assert all(set(stats) == {"tick"} for _, stats in opened)


# -------------------------------------------------- no tracer, no span --

@pytest.mark.parametrize("engine", ["ragged", "base"])
def test_without_a_tracer_no_phase_and_no_annotation_is_entered(
        model_and_params, monkeypatch, engine):
    model, params = model_and_params

    def boom(*a, **kw):
        raise AssertionError("entered with tracing off")

    for meth in ("open_tick", "phase", "tick", "request_event"):
        monkeypatch.setattr(Tracer, meth, boom)
    monkeypatch.setattr(telemetry, "_annotation", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    eng = (_ragged(model, params) if engine == "ragged" else
           ContinuousBatchingEngine(model, params, max_slots=2, max_len=32,
                                    prompt_buckets=[8]))
    eng.add_request(PROMPTS[0], 5)
    assert len(eng.run_to_completion(max_ticks=100)) == 1


@pytest.mark.parametrize("engine", ["ragged", "spec", "base"])
def test_without_a_tracer_no_part_is_entered_and_the_tokens_are_the_same(
        model_and_params, monkeypatch, engine):
    model, params = model_and_params

    def make(tracer):
        if engine == "base":
            return ContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, prompt_buckets=[8],
                tracer=tracer)
        return _ragged(model, params, tracer=tracer, draft=engine == "spec")

    def serve(eng):
        for p, n in zip(PROMPTS[:3], BUDGETS[:3]):
            eng.add_request(p, n)
        return eng.run_to_completion(max_ticks=200)

    tr = Tracer()
    with_tracer = serve(make(tr))
    assert any(e["parts"] for e in tr.events("tick"))

    def boom(*a, **kw):
        raise AssertionError("entered with tracing off")

    monkeypatch.setattr(telemetry._Phase, "__init__", boom)
    monkeypatch.setattr(telemetry._Phase, "__enter__", boom)
    monkeypatch.setattr(telemetry._Phase, "part", boom)
    monkeypatch.setattr(telemetry, "_annotation", boom)
    eng = make(None)
    assert serve(eng) == with_tracer
    assert eng._tick_note == {}


def test_phase_outside_a_round_is_only_a_span():
    tr = Tracer()
    with tr.phase(telemetry.PHASE_PACK):
        pass
    note = tr.open_tick()
    with tr.phase(telemetry.PHASE_PACK):
        time.sleep(0.001)
    with tr.phase(telemetry.PHASE_PACK):
        pass
    ev = tr.tick("E", 0.5, **note)
    assert ev["tick"] == 1 and list(ev["phases"]) == [telemetry.PHASE_PACK]
    assert 0.001 <= ev["phases"][telemetry.PHASE_PACK] < 0.5
    assert tr.open_tick()["tick"] == 2


def test_a_part_runs_to_the_next_part_or_to_the_phases_end(monkeypatch):
    opened, real = [], telemetry._annotation
    monkeypatch.setattr(telemetry, "_annotation",
                        lambda span, **stats: opened.append((span, stats))
                        or real(span, **stats))
    tr = Tracer()
    with tr.phase(PHASE_DISPATCH) as part:  # outside a round: spans only
        part(PART_KEY)
    assert opened == [(PHASE_DISPATCH, {}), (PART_KEY, {})]
    note = tr.open_tick()
    tr.span_stats(chunk_rows=0)             # the engine's word on the round
    with tr.phase(PHASE_DISPATCH) as part:
        time.sleep(0.002)                   # before the first part
        part(PART_OPERANDS)
        time.sleep(0.001)
        part(PART_CALL)
        time.sleep(0.003)
    with tr.phase(PHASE_SYNC) as part:
        time.sleep(0.001)
        part(PART_STATS)
    ev = tr.tick("E", 0.5, **note)
    assert list(ev["phases"]) == [PHASE_DISPATCH, PHASE_SYNC]
    assert list(ev["parts"]) == [PART_OPERANDS, PART_CALL, PART_STATS]
    whole, parts = ev["phases"][PHASE_DISPATCH], ev["parts"]
    assert parts[PART_OPERANDS] >= 0.001 and parts[PART_CALL] >= 0.003
    # what came before the first part is the phase's alone
    assert parts[PART_OPERANDS] + parts[PART_CALL] <= whole - 0.002
    assert 0 <= parts[PART_STATS] <= ev["phases"][PHASE_SYNC] - 0.001
    kind = {"tick": 1, "chunk_rows": 0}
    assert opened[3:] == [(name, kind) for name in (
        PHASE_DISPATCH, PART_OPERANDS, PART_CALL, PHASE_SYNC, PART_STATS)]
    # the word is the round's: the next round's spans start without it,
    # and outside a round there is nothing to say it of
    tr.span_stats(chunk_rows=7)
    tr.open_tick()
    with tr.phase(PHASE_DISPATCH):
        pass
    assert opened[-1] == (PHASE_DISPATCH, {"tick": 2})


# ------------------------------------------------------------- due_at --

def test_ttft_counts_from_the_time_a_request_was_due(model_and_params):
    model, params = model_and_params
    tr = Tracer()
    eng = _ragged(model, params, tracer=tr)
    late = 0.25
    r0 = eng.add_request(PROMPTS[0], 3, due_at=time.monotonic() - late)
    r1 = eng.add_request(PROMPTS[1], 3)
    eng.run_to_completion(max_ticks=100)
    tls = {tl.rid: tl for tl in tr.timelines()}
    assert tls[r1].due_at is None
    assert tls[r1].ttft_s == tls[r1].first_token_at - tls[r1].queued_at
    assert tls[r0].due_at == pytest.approx(tls[r0].queued_at - late,
                                           abs=0.01)
    assert tls[r0].ttft_s == tls[r0].first_token_at - tls[r0].due_at
    queued = [e for e in tr.events("request") if e["what"] == "queued"]
    assert "due_at" in queued[0] and "due_at" not in queued[1]
    # metrics(): the mean of one late and one punctual request
    assert eng.metrics()["mean_ttft_s"] == pytest.approx(
        (tls[r0].ttft_s + tls[r1].ttft_s) / 2, abs=0.01)
    assert eng.metrics()["mean_ttft_s"] > late / 2


# ---------------------------------------------- regions in the programs --

def _op_names(text, kinds=("dot", "convolution")):
    """[(kind, op_name)] of every operation of these kinds in HLO text."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \S+ (%s)\(" % "|".join(kinds), line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


def _region(op_name):
    words = re.findall(r"[A-Za-z_][A-Za-z0-9_.\-]*", op_name)
    return next((w for w in reversed(words) if w in REGIONS), None)


@pytest.fixture(scope="module")
def train_hlo():
    """Compiled HLO of a small ``make_gpt_train_step`` program, the flash
    kernels interpreted (their body's products are then in the text)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import make_gpt_train_step
    from paddle_tpu.ops import attention
    from paddle_tpu.optimizer import AdamW
    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTModel(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_attention_heads=2,
        max_position_embeddings=128, compute_dtype="bfloat16"))
    step, state = make_gpt_train_step(
        model, AdamW(3e-4, weight_decay=0.01),
        fleet.get_hybrid_communicate_group(), remat=False)
    x = jnp.zeros((2, 128), jnp.int32)
    use_pallas, attention._use_pallas = attention._use_pallas, lambda: True
    try:
        return step.lower(state, jax.random.key(0), np.float32(3e-4), x,
                          x).compile().as_text()
    finally:
        attention._use_pallas = use_pallas


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_every_product_of_the_train_step_lies_in_a_region(train_hlo,
                                                          direction):
    names = [n for _, n in _op_names(train_hlo)
             if ("transpose(" in n) == (direction == "backward")]
    assert len(names) >= 5
    assert all(_region(n) for n in names), \
        [n for n in names if not _region(n)]
    found = {_region(n) for n in names}
    assert {"attn", "mlp", "head", "flash_attention"} <= found
    # innermost wins: the kernel's products are the kernel's, not attn's
    assert all(_region(n) == "flash_attention" for n in names
               if "flash_attention_" in n)


def test_the_optimizer_is_a_region_of_the_train_step(train_hlo):
    names = [n for _, n in _op_names(train_hlo, ("fusion", "multiply",
                                                 "sqrt", "divide"))]
    assert any(_region(n) == "optimizer" for n in names)
    assert {"jvp(layers)", "transpose(jvp(layers))"} <= {
        w for n in names for w in n.split("/")}


@pytest.mark.parametrize("pool", ["float", "int8"])
def test_every_product_of_the_ragged_tick_lies_in_a_region(pool):
    paddle.seed(3)
    model = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_attention_heads=4,
        max_position_embeddings=96, compute_dtype="float32",
        kv_cache_dtype="int8" if pool == "int8" else None))
    params = {n: p._data for n, p in model.named_parameters()}
    paddle.set_flags({"FLAGS_paged_attn_interpret": True})
    try:
        eng = _ragged(model, params)
        C = eng.MB
        text = eng._build_ragged_step(eng.token_budget, C).lower(
            *eng._ragged_scratch_args(C)).compile().as_text()
    finally:
        paddle.set_flags({"FLAGS_paged_attn_interpret": False})
    names = [n for _, n in _op_names(text)]
    assert len(names) >= 5 and all(_region(n) for n in names), names
    assert {"attn", "mlp", "head"} <= {_region(n) for n in names}
    # the kernel multiplies row by row, not on the MXU: no product, but
    # what its interpreted body does lies under its own name
    body = [n for _, n in _op_names(text, ("multiply", "dynamic-slice"))
            if "/ragged_paged_attention/while/" in n]
    assert body and all(_region(n) == "ragged_paged_attention" for n in body)
    scatters = [n for _, n in _op_names(text, ("scatter", "fusion"))
                if "kv_write" in n]
    assert scatters and all(_region(n) == "kv_write" for n in scatters)
