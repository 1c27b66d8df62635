"""The sparse cell (``dsv32-serve-longctx``): its rehearsal is correct and
can fail — on an altered token and on a program that skips the selection —
its configuration is the published one but for its cuts, its operation
counts against hand-counted cases, its traffic by ``test_schedule.py``'s
rules, and its new reader on a small trace recorded on a v5e by
``benchmarks/tools/record_trace_sparse.py`` (a few ragged ticks of a small
share: 1 dense + 2 expert layers, a 4-head indexer keeping 64 positions)."""

import contextlib
import copy
import io
import json
import os
import types

import pytest

from benchmarks.lib import (harness, opcount_sparse, schedule, weights_dsv32,
                            xplane)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(HERE, "testdata", "trace_sparse.xplane.pb")
CELL, TRAFFIC = "dsv32-serve-longctx", "longctx-backlog"
AMONG = ["embed", "layers", "attn", "mlp", "kv_write", "head",
         "ragged_latent_attention", "router", "experts", "shared_expert",
         "indexer", "ragged_index_scores", "select",
         "ragged_sparse_latent_attention"]


@pytest.fixture(scope="module")
def sound():
    """ONE sound rehearsal for the whole module (80 s of interpreted
    kernels): its result, what it printed, and what ``check_served`` was
    given, so that a test can put other tokens, another program or the
    controls through the same comparison without serving again."""
    from benchmarks import run
    from benchmarks.lib import serve_sparse
    check, given, out = serve_sparse.check_served, {}, io.StringIO()

    def keep(ctx, *args):
        given.update(ctx=ctx, args=args)
        return check(ctx, *args)
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setattr(serve_sparse, "check_served", keep)
        assert run.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "2", "--trace", "0", "--rehearse"]) == 0
    out = out.getvalue()
    line = [x for x in out.splitlines() if x.startswith("[bench] rehearsal")]
    return types.SimpleNamespace(
        result=json.loads(line[-1].split("rehearsal: ", 1)[1]), out=out,
        **given)


def compared_again(sound, done=None, **over):
    """``check_served`` once more over the sound rehearsal's model,
    weights and served requests (or ``done``): the checks it made."""
    from benchmarks.lib import serve_sparse
    ctx = copy.copy(sound.ctx)
    ctx.checks = []
    vars(ctx).update(over)
    cfg, model, params, served = sound.args
    serve_sparse.check_served(ctx, cfg, model, params, done or served)
    return ctx.checked()


def test_a_sound_rehearsal_is_correct(sound):
    result, out = sound.result, sound.out
    assert result["correct"] is True, out
    assert list(result["checks"]) == [
        "backlog_requests_left_at_close", "served_mean_logit_gap",
        "route_near_tie_share", "selection_overlap",
        "selection_overlap_first_layer", "compiles_in_window",
        "tracer_events_dropped"]
    assert result["checks"]["selection_overlap"]["value"] == 1.0
    assert result["checks"]["selection_overlap_first_layer"]["value"] == 1.0
    # the selection bit: fewer positions attended than scored
    note = next(x for x in out.splitlines() if "index candidates" in x)
    scored, kept = (int(w.strip(",;")) for w in
                    (note.split("window ")[1].split()[0],
                     note.split("selected ")[1].split()[0]))
    assert 0 < kept < scored


def test_the_selection_is_read_from_a_pack_of_several_slots(sound):
    """The replay packs the compared requests' served rows into ONE tick,
    each request in a slot of its own, as the timed tick holds them."""
    import numpy as np
    from benchmarks.lib import serve_sparse
    cfg, model, params, served = sound.args
    pick, eng = served[:2], sound.ctx.traffic["engine"]
    two = serve_sparse.program_selection(model, params, pick, eng)
    r, (mask, at) = pick[1], two[1]                 # the second slot's
    (alone, at_1), = serve_sparse.program_selection(model, params, [r], eng)
    n = len(r.prompt) + len(r.tokens) - 1
    rows = mask.shape[1]                    # half the budget a request
    assert at == n - rows and at_1 + alone.shape[1] == n
    a, b = (np.unpackbits(np.asarray(m), axis=-1) for m in (mask, alone))
    assert (a == b[:, -rows:]).all()
    # the last row keeps index_topk positions, all of them its own
    assert (a[:, -1].sum(-1) == min(cfg["index_topk"], n)).all()
    assert not a[:, -1, n:].any()


def test_an_altered_token_is_not_correct(sound):
    served = []
    for r in sound.args[3]:
        r = copy.copy(r)
        r.tokens = [(int(t) + 7) % 500 + 1 for t in r.tokens]
        served.append(r)
    checks = compared_again(sound, done=served)
    assert checks["served_mean_logit_gap"]["ok"] is False
    # what the program selects does not hang on the token served last
    assert checks["selection_overlap_first_layer"]["ok"] is True


def test_selection_switched_off_is_not_correct(sound, monkeypatch):
    """A program that scores and then attends everything: its own
    selection, read from the tick's functions, is the whole context."""
    import jax.numpy as jnp
    from paddle_tpu.models import pangu_moe
    choose = pangu_moe.ragged_index_select

    def everything(*args, **kw):
        scores, thr = choose(*args, **kw)
        return scores, jnp.zeros_like(thr).at[:, 0].set(
            -2 ** 31).at[:, 1].set(2 ** 30)
    monkeypatch.setattr(pangu_moe, "ragged_index_select", everything)
    checks = compared_again(sound)
    for name in ("selection_overlap", "selection_overlap_first_layer"):
        assert checks[name]["ok"] is False, name
    assert checks["selection_overlap"]["value"] < 0.7


def test_a_control_run_is_not_correct(sound):
    """``--control``: the controls and the witness go through the run's
    own checks; the two controls that select by position fail them at any
    size, and the witness (the reference at the stated precision) passes."""
    checks = compared_again(sound, control=True)
    names = ["served_mean_logit_gap", "route_near_tie_share",
             "selection_overlap", "selection_overlap_first_layer"]
    assert list(checks) == names + [
        f"{c}.{n}" for c in ("control_int8", "control_dense",
                             "control_recent", "witness_bfloat16")
        for n in names]
    assert all(checks[n]["ok"] for n in names)
    assert all(checks[f"witness_bfloat16.{n}"]["ok"] for n in names)
    for c in ("dense", "recent"):
        assert checks[f"control_{c}.selection_overlap"]["ok"] is False, c
        assert checks[f"control_{c}.selection_overlap"]["value"] < 0.7


# the published config.json (the catalog beside the model-configs guide,
# row "DeepSeek-V3.2-Exp")
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    cfg = harness.load_json("configs", "deepseek-v3.2-exp-ep16.json")
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"]) == set(cfg["cut"])
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]
    # inside the guide's floors: 4 expert layers, 8+ experts, 1/8 vocabulary
    assert weights_dsv32.stack_layers(cfg) == {"dense": 1, "moe": 4}
    assert weights_dsv32.held(cfg) == (0, 16)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the arithmetic of the cut: 4,636 M parameters, 9.27 GB in bfloat16
    assert weights_dsv32.param_count(cfg) == 4_635_518_208
    for key in ("published", "cut", "assumed", "deployment"):
        assert cfg[key], key
    assert "16 chips" in cfg["deployment"]


def test_the_cell_is_the_issues():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    assert tr["prompt_len"] == {"dist": "uniform", "min": 16384,
                                "max": 49152}
    assert tr["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert (tr["arrival"], tr["backlog_tokens"], tr["ramp_s"],
            tr["schedule_seed"]) == ("backlog", 2_400_000, 8, 0)
    eng = tr["engine"]
    assert (eng["max_slots"], eng["max_len"], eng["block_size"],
            eng["num_blocks"], eng["token_budget"]) == (
                6, 49664, 16, 18624, 2048)
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_len"] // 16
    assert tr["compare_requests"] == 3
    # both leaves: 5 layers x (640 + 128) numbers x 2 bytes a token
    cfg = harness.load_json("configs", "deepseek-v3.2-exp-ep16.json")
    per_token = cfg["num_hidden_layers"] * (640 + cfg["index_head_dim"]) * 2
    assert per_token == 7680
    assert per_token * eng["num_blocks"] * 16 == 2_288_517_120
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v3.2-exp-ep16", TRAFFIC, 1)
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 22 and all(m["workloads"] == [CELL] and
                                   m["moves"] == "serve_tok_s" for m in mine)


# ------------------------------------ the traffic, by test_schedule's rules --

def test_schedule_is_the_cells_and_not_the_seeds():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    a = schedule.build_schedule(tr, 45.0)
    b = schedule.build_schedule(tr, 45.0)
    assert schedule.digest(a) == schedule.digest(b) == "eb6e6d22d497084e"
    pa = schedule.prompt_tokens(a, 1, 16160)
    pb = schedule.prompt_tokens(a, 2 ** 31 + 7, 16160)
    assert [len(p) for p in pa] == [len(p) for p in pb] and pa != pb
    assert all(1 <= t < 16160 for p in pb for t in p)
    assert all(s.due_s == -tr["ramp_s"] for s in a)
    for s in a:
        assert 16384 <= s.prompt_len <= 49152 and 128 <= s.output_len <= 512
        assert s.prompt_len + s.output_len <= tr["engine"]["max_len"]
    assert len(a) == 70                 # 2,411,442 tokens


def test_no_engine_the_chip_allows_drains_the_backlog():
    """45 k rows/s over the ramp and the window would be needed; a
    2,048-row chunk round at the chip's peaks is far over 45 ms."""
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        window = json.load(f)["run_seconds"]
    sched = schedule.build_schedule(tr, window)
    tokens = sum(s.prompt_len + s.output_len for s in sched)
    assert tokens >= tr["backlog_tokens"]
    assert tokens / (tr["ramp_s"] + window) >= 45_000


# ------------------------------------------------ operation counts by hand --

@pytest.mark.parametrize("rows,flops,nbytes", [
    # one decode row scoring 100 keys: 100 x 64 heads x 128 x 2; 100 keys of
    # 128 and the row's 64 x 128 queries, two bytes each
    ([(1, 100)], 2 * 100 * 64 * 128, (100 * 128 + 64 * 128) * 2),
    # a 4-row chunk ending at key 10 scores 7 + 8 + 9 + 10 = 34 keys
    ([(4, 10)], 2 * 34 * 64 * 128, (10 * 128 + 4 * 64 * 128) * 2),
    ([(1, 100), (4, 10)], 2 * 134 * 64 * 128,
     (110 * 128 + 5 * 64 * 128) * 2),
    ([], 0, 0)], ids=["decode", "chunk", "two", "empty"])
def test_opcount_index_scores_by_hand(rows, flops, nbytes):
    assert opcount_sparse.ragged_index_scores(rows) == (flops, nbytes)


@pytest.mark.parametrize("rows,topk,sel", [
    ([(1, 100)], 2048, 100),            # a context under k keeps all of it
    ([(1, 40000)], 2048, 2048),         # over k: k
    ([(4, 10)], 2048, 34),              # 7 + 8 + 9 + 10
    ([(4, 10)], 8, 7 + 8 + 8 + 8),      # the first row under k, three over
    ([(3, 2049)], 2048, 2047 + 2048 + 2048),
    ([(2048, 32768)], 2048, 2048 * 2048),
    ([(1, 100), (4, 10)], 8, 8 + 31)],
    ids=["under", "over", "chunk", "edge", "at-k", "deep-chunk", "two"])
def test_opcount_sparse_latent_by_hand(rows, topk, sel):
    """Per selected key and head 2 x (576 + 512) FLOP; 576 numbers a
    selected key, and a row's query (128 x 576) and output (128 x 512)."""
    f, b = opcount_sparse.ragged_sparse_latent_attention(rows, topk)
    n = sum(r for r, _ in rows)
    assert f == 2 * sel * 128 * 1088
    assert b == (sel * 576 + n * 128 * 1088) * 2


def test_a_row_at_32k_is_a_sixteenth_of_dense_work():
    from benchmarks.lib import opcount_latent
    sparse, _ = opcount_sparse.ragged_sparse_latent_attention([(1, 32768)])
    dense, _ = opcount_latent.ragged_latent_attention([(1, 32768)])
    assert dense / sparse == 16.0
    index, _ = opcount_sparse.ragged_index_scores([(1, 32768)])
    assert index == pytest.approx(0.537e9, rel=1e-2)       # the issue's 0.54


# ------------------------------------------------- readers, on the trace --

@pytest.fixture(scope="module")
def ctx():
    with open(TRACE.replace(".xplane.pb", ".ticks.json")) as f:
        side = json.load(f)
    red = xplane.Reduction(TRACE, host_spans=("engine_step",))
    notes = []
    ticks = {int(k): [tuple(r) for r in rows]
             for k, rows in side["sparse_ticks"].items()}
    return types.SimpleNamespace(
        obs={"xplane": red, "sparse_ticks": ticks},
        config=side["config"], device_kind=side["device_kind"],
        note=notes.append, notes=notes)


def scope_share(ctx, *scopes):
    reader = harness.load_module("readers", "xplane_scope")
    return reader.read({"scopes": list(scopes), "among": AMONG}, ctx)


def test_the_new_regions_are_in_the_trace(ctx):
    shares = {s: scope_share(ctx, s) or 0.0 for s in AMONG}
    for s in ("indexer", "ragged_index_scores", "select",
              "ragged_sparse_latent_attention", "kv_write", "attn", "mlp",
              "router", "experts"):
        assert shares[s] > 0, s
    assert sum(shares.values()) <= 100.0 + 1e-6
    # everything the indexer adds lies inside the accepted ``attn`` region
    from benchmarks.lib import xregion
    regions = xregion.Named(ctx.obs["xplane"]).shares()
    inner = sum(shares[s] for s in (
        "attn", "ragged_latent_attention", "indexer", "ragged_index_scores",
        "select", "ragged_sparse_latent_attention"))
    assert regions["attn"] == pytest.approx(inner, abs=1e-6)
    # the three layer metrics read what the files name
    for name in ("indexer_share", "select_share", "sparse_attn_share"):
        how = harness.load_json("layer_metrics", name + ".longctx.json")
        value = harness.load_module("readers", how["reader"]).read(how, ctx)
        assert 0.0 < value < 100.0, name


@pytest.mark.parametrize("metric", ["indexer_roofline",
                                    "sparse_attn_roofline"])
def test_the_sparse_rooflines_read_the_recorded_ticks(ctx, metric):
    how = harness.load_json("layer_metrics", metric + ".longctx.json")
    reader = harness.load_module("readers", how["reader"])
    value = reader.read(how, ctx)
    assert 0.0 < value <= 100.0
    assert any(f"{how['opcount']} roofline: least" in n for n in ctx.notes)
    # rounds whose rows are unknown are left out; with none known, with no
    # such kernel (a program without the mechanism: the parent) or with no
    # trace: nothing, and no raise
    some = dict(list(ctx.obs["sparse_ticks"].items())[1:])
    fewer = types.SimpleNamespace(**{**vars(ctx), "obs": dict(
        ctx.obs, sparse_ticks=some)})
    assert 0.0 < reader.read(how, fewer) <= 100.0
    none = types.SimpleNamespace(**{**vars(ctx), "obs": dict(
        ctx.obs, sparse_ticks={-1: [(1, 1)]})})
    assert reader.read(how, none) is None
    assert reader.read(dict(how, kernels=["no_such_kernel"]), ctx) is None
    assert reader.read(how, types.SimpleNamespace(obs={})) is None


def test_the_kept_share_reads_the_programs_counters():
    how = harness.load_json("layer_metrics", "index_kept_share.longctx.json")
    reader = harness.load_module("readers", how["reader"])
    ctx = types.SimpleNamespace(obs={"counters": {
        "index_candidates": 4000, "index_selected": 500}})
    assert reader.read(how, ctx) == 12.5
    assert reader.read(how, types.SimpleNamespace(
        obs={"counters": {}})) is None          # the parent: no such counter
