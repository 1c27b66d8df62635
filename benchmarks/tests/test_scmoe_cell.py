"""The LongCat-Flash cell (``longcat-serve-toolturns``): its rehearsal is
correct and can fail — on an altered token and on the reference in int8 —,
its configuration is the catalog's row but for its cuts, its parameter
count by hand, its traffic by ``test_schedule.py``'s rules, and the
metrics that are the cell's own read what the program counts."""

import contextlib
import copy
import io
import json
import os
import types

import numpy as np
import pytest

from benchmarks.lib import harness, opcount, schedule, weights_longcat

CELL, TRAFFIC, CONFIG = ("longcat-serve-toolturns", "toolturns-backlog",
                         "longcat-flash-chat-ep32")


@pytest.fixture(scope="module")
def sound():
    """ONE sound rehearsal for the whole module: its result, what it
    printed, and what ``check_served`` was given, so that a test can put
    other tokens or the control through the same comparison without
    serving again."""
    from benchmarks import run
    from benchmarks.lib import serve_scmoe
    check, given, out = serve_scmoe.check_served, {}, io.StringIO()

    def keep(ctx, *args):
        given.update(ctx=ctx, args=args)
        return check(ctx, *args)
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setattr(serve_scmoe, "check_served", keep)
        assert run.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "2", "--trace", "0", "--rehearse"]) == 0
    out = out.getvalue()
    line = [x for x in out.splitlines() if x.startswith("[bench] rehearsal")]
    return types.SimpleNamespace(
        result=json.loads(line[-1].split("rehearsal: ", 1)[1]), out=out,
        **given)


def compared_again(sound, done=None, **over):
    from benchmarks.lib import serve_scmoe
    ctx = copy.copy(sound.ctx)
    ctx.checks = []
    vars(ctx).update(over)
    cfg, params, served = sound.args
    with contextlib.redirect_stdout(io.StringIO()):
        serve_scmoe.check_served(ctx, cfg, params, done or served)
    return ctx.checked()


def test_a_sound_rehearsal_is_correct(sound):
    result, out = sound.result, sound.out
    assert result["correct"] is True, out
    assert list(result["checks"]) == [
        "backlog_requests_left_at_close", "served_logit_gap",
        "route_near_tie_share", "compiles_in_window",
        "tracer_events_dropped"]
    assert "to zero-compute experts" in out
    assert "decode-only rounds on a program of 8 rows" in out


def test_the_rehearsal_counts_what_zero_experts_change(sound):
    """The tick's four counters reach the readers: a third of the pairs go
    to the 8 zero-compute experts of 24 outputs, and a row sends two of its
    three choices to real experts, within what 2 s of random routing
    scatter."""
    counters = sound.ctx.obs["counters"]
    share = counters["zero_pairs"] / counters["routed_pairs"]
    assert 0.2 < share < 0.47
    assert counters["real_pairs_per_row"] == pytest.approx(
        3 * (1 - share), rel=1e-9)
    for name, want in (("zero_pair_share", 100 * share),
                       ("real_pairs_per_row", 3 * (1 - share))):
        how = harness.load_json("layer_metrics", f"{name}.toolturns.json")
        reader = harness.load_module("readers", how["reader"])
        assert reader.read(how, sound.ctx) == pytest.approx(want)
    assert sound.ctx.obs["series"]["expert_rows_max_over_mean"]


def test_an_altered_token_is_not_correct(sound):
    served = []
    for r in sound.args[2]:
        r = copy.copy(r)
        r.tokens = [(int(t) + 7) % 500 + 1 for t in r.tokens]
        served.append(r)
    assert compared_again(sound, done=served)["served_logit_gap"]["ok"] \
        is False


def test_a_control_run_goes_through_the_runs_own_check(sound):
    """``--control``: the reference in int8 is read by the run's own
    check, under the cell's limit, and the sound reading beside it."""
    checks = compared_again(sound, control=True)
    assert list(checks) == ["served_logit_gap", "route_near_tie_share",
                            "control_int8_served_logit_gap"]
    assert checks["control_int8_served_logit_gap"]["limit"] == \
        checks["served_logit_gap"]["limit"]


class Served:
    def __init__(self, prompt, tokens):
        self.prompt, self.tokens, self.out_len = prompt, tokens, len(tokens)


def test_int8_moves_gaps_and_margins_further_than_bfloat16():
    """At every position the token the lower precision puts first, read
    against the float32 reference at the rehearsal's size (24 router
    outputs, scores ~30 x the cell's: the cell's limits are not this
    size's, PERF.md section 6 has the readings they are set from): on
    every seed int8 reads a wider mean gap than bfloat16 and moves the
    route margins three times as far, and further than the cell's
    epsilon."""
    from benchmarks.lib import serve_scmoe
    cfg = harness.merge(
        harness.load_json("configs", CONFIG + ".json"),
        harness.load_json("traffic", TRAFFIC + ".json")["rehearse"]["config"])
    eps = harness.load_json("limits", CELL + ".json")[
        "route_margin_eps"]["limit"]
    rng = np.random.default_rng(0)
    reqs = [Served(rng.integers(1, 512, n).tolist(),
                   rng.integers(1, 512, 100).tolist()) for n in (60, 150)]
    for seed in (1, 2, 3):
        params = weights_longcat.make_params(cfg, seed, "float32")
        got = {}
        for lower in ("bfloat16", "int8"):
            with contextlib.redirect_stdout(io.StringIO()):
                got[lower] = serve_scmoe.served_gap(
                    serve_scmoe.served_positions(
                        cfg, params, reqs, lower=lower, pad_to=32), eps)
        sound, control = got["bfloat16"], got["int8"]
        assert sound["tokens"] == control["tokens"] == 200
        assert sound["near"] == control["near"] < 20    # the reference's
        assert control["mean"] > 1.5 * sound["mean"], (seed, got)
        assert control["margin_moved"][1] > 2 * sound["margin_moved"][1]
        assert control["margin_moved"][1] > eps


# the published config.json (the catalog beside the model-configs guide,
# row "LongCat-Flash-Chat")
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    cfg = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"]) == set(cfg["cut"])
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (4, 16, 16384)
    # the floors: four whole layers, at least 8 experts, an eighth of the
    # vocabulary; the router keeps its width and its top 12
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"] \
        + PUBLISHED["zero_expert_num"] == 768
    assert weights_longcat.real_experts(cfg) == 512
    assert weights_longcat.held(cfg) == (0, 16)
    assert cfg["num_hidden_layers"] == 2 * cfg["num_layers"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]


def test_param_count_by_hand():
    """The arithmetic of the cut: 5,172.7 M parameters, 10.35 GB in
    bfloat16; and the latent pool's bytes a token."""
    cfg = harness.load_json("configs", CONFIG + ".json")
    H, nh, I, F = 6144, 64, 12288, 2048
    mla = (H * 1536 + 1536                  # W_qa and its norm
           + 1536 * nh * (128 + 64)         # W_qb
           + H * (512 + 64) + 512           # W_kva and its norm
           + 512 * nh * (128 + 128)         # W_kvb
           + nh * 128 * H)                  # W_o
    assert mla == 90_572_800
    sublayer = mla + 3 * H * I + 2 * H      # + the dense FFN, two norms
    router = H * 768 + 768                  # + the selection bias
    expert = 3 * H * F
    assert expert == 37_748_736
    layer = 2 * sublayer + router + 16 * expert
    assert layer - 16 * expert == 638_874_368       # "638.9 M"
    total = 4 * layer + 2 * 16384 * H + H           # + the final norm
    assert weights_longcat.param_count(cfg) == total == 5_172_749_312
    assert f"{total:,} parameters" in cfg["deployment"]
    row = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    assert 2 * cfg["num_layers"] * row * 2 == 10_240


def test_the_program_makes_the_tables_leaves():
    """The benchmark's table is the program's parameter dictionary."""
    from benchmarks.lib import serve_scmoe
    from paddle_tpu.models.longcat_flash import LongcatFlashModel
    cfg = harness.load_json("configs", CONFIG + ".json")
    ours = LongcatFlashModel.param_table(serve_scmoe.model_config(cfg))
    assert ours == weights_longcat.param_table(cfg)


def test_the_cell_is_the_issues():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    assert tr["prompt_len"] == {"dist": "uniform", "min": 2048, "max": 8192}
    assert tr["output_len"] == {"dist": "uniform", "min": 32, "max": 128}
    assert (tr["arrival"], tr["backlog_tokens"], tr["ramp_s"], tr["drain_s"],
            tr["schedule_seed"], tr["prefix_sharing"]) == (
                "backlog", 2_400_000, 8, 0, 0, 0)
    eng = tr["engine"]
    assert (eng["max_slots"], eng["max_len"], eng["block_size"],
            eng["num_blocks"], eng["token_budget"]) == (
                16, 8320, 16, 8320, 2048)
    # never preempts: every slot can hold the longest request
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_len"] \
        // eng["block_size"]
    assert eng["max_len"] == tr["prompt_len"]["max"] + tr["output_len"]["max"]
    assert eng["warm_table_widths"] == [128, 256, 512, 520]
    assert (tr["compare_requests"], tr["reference_pad_to"], tr["trace_s"]) \
        == (3, 1024, 6)
    # the pool: 8,320 x 16 positions of 10,240 B = 1.36 GB
    assert (eng["num_blocks"] + 1) * eng["block_size"] * 10_240 \
        == 1_363_312_640
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 22 and all(m["workloads"] == [CELL] and
                                   m["moves"] == "serve_tok_s" for m in mine)
    assert all(m["name"].endswith(".toolturns") for m in mine)
    # the file may hold 128 (the issue's 23 made 129, refused before any
    # run: idle_under_dispatch_share is the one left out)
    assert len(bench["per_layer"]) <= 128
    assert "idle_under_dispatch_share.toolturns" not in {
        m["name"] for m in mine}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve_tok_s"]["workloads"][-1] == CELL
    assert len(bench["workloads"]) == 7
    assert bench["workloads"][-1]["name"] == CELL       # appended


# ------------------------------------ the traffic, by test_schedule's rules --

def test_schedule_is_the_cells_and_not_the_seeds():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    a = schedule.build_schedule(tr, 45.0)
    b = schedule.build_schedule(tr, 45.0)
    assert schedule.digest(a) == schedule.digest(b)
    pa = schedule.prompt_tokens(a, 1, 16384)
    pb = schedule.prompt_tokens(a, 2 ** 31 + 7, 16384)
    assert [len(p) for p in pa] == [len(p) for p in pb] and pa != pb
    assert all(1 <= t < 16384 for p in pb for t in p)
    assert all(s.due_s == -tr["ramp_s"] for s in a)
    for s in a:
        assert 2048 <= s.prompt_len <= 8192 and 32 <= s.output_len <= 128
        assert s.prompt_len + s.output_len <= tr["engine"]["max_len"]
    assert 430 <= len(a) <= 490         # "about 450 requests"


def test_no_engine_the_chip_allows_drains_the_backlog():
    """45 k rows/s over the ramp and the window would be needed; the
    products outside attention alone, at the chip's peak, allow 37 k (1.33
    GFLOP a row a layer, PERF.md section 4)."""
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        window = json.load(f)["run_seconds"]
    sched = schedule.build_schedule(tr, window)
    tokens = sum(s.prompt_len + s.output_len for s in sched)
    assert tokens >= tr["backlog_tokens"]
    assert tokens / (tr["ramp_s"] + window) >= 45_000
    cfg = harness.load_json("configs", CONFIG + ".json")
    # what a row multiplies by: everything but the embedding, the head
    # (sampled rows only), and of the 16 held experts the 0.25 it reaches
    dense = weights_longcat.param_count(cfg) - 2 * 16384 * 6144 \
        - 4 * 16 * 3 * 6144 * 2048
    per_row = 2 * (dense + 4 * 0.25 * 3 * 6144 * 2048)
    assert per_row / 4 == pytest.approx(1.33e9, rel=0.05)
    assert opcount.peaks("TPU v5 lite")["bf16_flops"] / per_row < 38_000


# --------------------------------------------- the metrics, by their files --

def test_the_scope_metrics_name_what_the_program_has():
    """Each scope a ``.toolturns`` metric reads is a ``jax.named_scope`` of
    the model's tick (``tests/test_longcat_flash.py`` finds them in the
    lowered program), all four scope metrics partition by one list, and
    the latent kernel's roofline counts ``num_hidden_layers`` = 2 x
    ``num_layers`` calls a round."""
    among = None
    for name, scope in (("dense_ffn_share", "dense_ffn"),
                        ("zero_experts_share", "zero_experts"),
                        ("experts_share", "experts"),
                        ("router_share", "router")):
        how = harness.load_json("layer_metrics", f"{name}.toolturns.json")
        assert how["reader"] == "xplane_scope" and how["scopes"] == [scope]
        assert among in (None, how["among"])
        among = how["among"]
    assert {"attn", "mlp", "ragged_latent_attention", "router", "experts",
            "dense_ffn", "zero_experts", "head", "embed", "layers",
            "kv_write"} == set(among)
    how = harness.load_json("layer_metrics",
                            "latent_attn_roofline.toolturns.json")
    assert how["reader"] == "xplane_kernel_latent"
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert cfg["num_hidden_layers"] == 8 and cfg["num_attention_heads"] == 64
