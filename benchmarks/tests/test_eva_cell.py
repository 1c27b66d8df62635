"""The EvaByte cell (``evabyte-serve-bytedocs``): its rehearsal is correct
and can fail — on an altered token, on the reference in int8, on summaries
switched off and on one window of them — its configuration is the
published one but for its cut, its operation count against hand-counted
cases, its traffic by ``test_schedule.py``'s rules, and its reader over
recorded rounds."""

import contextlib
import copy
import io
import json
import os
import types

import pytest

from benchmarks.lib import (harness, opcount, opcount_eva, schedule,
                            weights_evabyte)

CELL, TRAFFIC, CONFIG = ("evabyte-serve-bytedocs", "bytedocs-backlog",
                         "evabyte-6.5b-pp2")


@pytest.fixture(scope="module")
def sound():
    """ONE sound rehearsal for the whole module (interpreted kernels): its
    result, what it printed, and what ``check_served`` was given, so that
    a test can put other tokens or the controls through the same
    comparison without serving again."""
    from benchmarks import run
    from benchmarks.lib import serve_eva
    check, given, out = serve_eva.check_served, {}, io.StringIO()

    def keep(ctx, *args):
        given.update(ctx=ctx, args=args)
        return check(ctx, *args)
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setattr(serve_eva, "check_served", keep)
        assert run.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "2", "--trace", "0", "--rehearse"]) == 0
    out = out.getvalue()
    line = [x for x in out.splitlines() if x.startswith("[bench] rehearsal")]
    return types.SimpleNamespace(
        result=json.loads(line[-1].split("rehearsal: ", 1)[1]), out=out,
        **given)


def compared_again(sound, done=None, **over):
    from benchmarks.lib import serve_eva
    ctx = copy.copy(sound.ctx)
    ctx.checks = []
    vars(ctx).update(over)
    cfg, params, served = sound.args
    serve_eva.check_served(ctx, cfg, params, done or served)
    return ctx.checked()


def test_a_sound_rehearsal_is_correct(sound):
    result, out = sound.result, sound.out
    assert result["correct"] is True, out
    assert list(result["checks"]) == [
        "backlog_requests_left_at_close", "served_logit_gap",
        "compiles_in_window", "tracer_events_dropped"]
    # float32 against float32: the served tokens are the reference's own
    assert result["checks"]["served_logit_gap"]["value"] <= 1e-5
    assert "compared 3 requests" in out
    assert "'layout': 'eva'" in out and "'slot/32', 'slot/32'" in out


def test_the_rehearsal_crosses_windows_and_never_drains(sound):
    """Prompts of 1.25 to 6.25 windows of 32; a backlog of 40,000 tokens,
    which the CPU does not get through in 2.5 s."""
    tr = harness.merge(harness.load_json("traffic", TRAFFIC + ".json"),
                       harness.load_json("traffic",
                                         TRAFFIC + ".json")["rehearse"])
    assert tr["backlog_tokens"] >= 40_000
    assert tr["prompt_len"]["min"] > 32 and tr["prompt_len"]["max"] > 5 * 32
    eng = tr["engine"]
    assert eng["token_budget"] == 32 + eng["max_slots"]
    left = sound.result["checks"]["backlog_requests_left_at_close"]["value"]
    assert left > 100
    assert "chunks closed" in sound.out


def test_an_altered_token_is_not_correct(sound):
    served = []
    for r in sound.args[2]:
        r = copy.copy(r)
        r.tokens = [(int(t) + 7) % 300 + 1 for t in r.tokens]
        served.append(r)
    assert compared_again(sound, done=served)["served_logit_gap"]["ok"] \
        is False


def test_a_control_run_is_not_correct(sound):
    """``--control``: the three controls and the witness go through the
    run's own check; each control fails it and the witness passes."""
    checks = compared_again(sound, control=True)
    assert list(checks) == ["served_logit_gap"] + [
        f"{c}.served_logit_gap" for c in (
            "control_int8", "control_no_summaries",
            "control_previous_window", "witness_bfloat16")]
    assert checks["served_logit_gap"]["ok"]
    assert checks["witness_bfloat16.served_logit_gap"]["ok"]
    for c in ("int8", "no_summaries", "previous_window"):
        assert checks[f"control_{c}.served_logit_gap"]["ok"] is False, c
    # no summaries, or one window of them, is nowhere near
    for c in ("no_summaries", "previous_window"):
        assert checks[f"control_{c}.served_logit_gap"]["value"] > 0.1, c


# the published config.json (the catalog beside the model-configs guide,
# row "EvaByte")
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def test_the_configuration_is_the_published_one_but_for_its_cut():
    cfg = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"]) == set(cfg["cut"])
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] * 2 == PUBLISHED["num_hidden_layers"]
    for key in ("published", "cut", "assumed", "deployment"):
        assert cfg[key], key
    assert "chunk_summary_form" in cfg["assumed"]
    assert "first of two pipeline stages" in cfg["cut"]["num_hidden_layers"] \
        .lower()
    assert "each layer whole on one chip" in cfg["deployment"]
    # the arithmetic of the cut, re-derived from the table
    H, I, nh = 4096, 11008, 32
    layer = 4 * H * H + 3 * H * I + 2 * H + 2 * nh * (H // nh)
    assert layer == 202_391_552
    assert weights_evabyte.param_count(cfg) == 16 * layer + 320 * H \
        + H * 8 * 320 + H == 3_250_065_408
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_program_makes_the_tables_leaves():
    """The benchmark's weights are the program's parameter dictionary:
    names, shapes and kinds, at the rehearsal's size."""
    from benchmarks.lib import serve_eva
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    cfg = harness.merge(harness.load_json("configs", CONFIG + ".json"),
                        tr["rehearse"]["config"])
    model = serve_eva.meta_model(cfg)
    mine = weights_evabyte.param_table(cfg)
    theirs = type(model).param_table(model.config)
    assert {n: s for n, (s, _) in mine.items()} \
        == {n: tuple(s) for n, (s, _) in theirs.items()}
    assert {n for n, (_, i) in mine.items() if i == "head_vector"} \
        == {n for n, (_, i) in theirs.items() if i == "head_vector"}


def test_the_cell_is_the_issues():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    assert tr["prompt_len"] == {"dist": "uniform", "min": 8192, "max": 31744}
    assert tr["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (tr["arrival"], tr["backlog_tokens"], tr["ramp_s"],
            tr["schedule_seed"], tr["prefix_sharing"]) == (
                "backlog", 2_400_000, 8, 0, 0)
    eng = tr["engine"]
    assert (eng["max_slots"], eng["max_len"], eng["block_size"],
            eng["num_blocks"], eng["token_budget"]) == (
                6, 32768, 16, 768, 2176)
    cfg = harness.load_json("configs", CONFIG + ".json")
    W, chunk = cfg["window_size"], cfg["chunk_size"]
    # a block of 16 summaries names 256 positions; 128 blocks a sequence
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_len"] \
        // (eng["block_size"] * chunk)
    assert eng["token_budget"] == 17 * 128 >= W + eng["max_slots"]
    assert (tr["compare_requests"], tr["reference_pad_to"], tr["trace_s"]) \
        == (3, W, 6)
    # both leaves, each held once: K and V of 4,096 bfloat16 a row
    row = 2 * cfg["hidden_size"] * 2
    layers = cfg["num_hidden_layers"]
    assert eng["max_slots"] * layers * W * row == 3_221_225_472
    assert (eng["num_blocks"] + 1) * eng["block_size"] * layers * row \
        == 3_225_419_776
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 18 and all(m["workloads"] == [CELL] and
                                   m["moves"] == "serve_tok_s" for m in mine)
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 6


# ------------------------------------ the traffic, by test_schedule's rules --

def test_schedule_is_the_cells_and_not_the_seeds():
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    a = schedule.build_schedule(tr, 45.0)
    b = schedule.build_schedule(tr, 45.0)
    assert schedule.digest(a) == schedule.digest(b)
    pa = schedule.prompt_tokens(a, 1, 320)
    pb = schedule.prompt_tokens(a, 2 ** 31 + 7, 320)
    assert [len(p) for p in pa] == [len(p) for p in pb] and pa != pb
    assert all(1 <= t < 320 for p in pb for t in p)
    assert all(s.due_s == -tr["ramp_s"] for s in a)
    for s in a:
        assert 8192 <= s.prompt_len <= 31744 and 64 <= s.output_len <= 256
        assert s.prompt_len + s.output_len <= tr["engine"]["max_len"]
    assert 110 <= len(a) <= 130         # "about 120 requests"


def test_no_engine_the_chip_allows_drains_the_backlog():
    """45 k rows/s over the ramp and the window would be needed; the
    products alone, at the chip's peak, allow 30 k."""
    tr = harness.load_json("traffic", TRAFFIC + ".json")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        window = json.load(f)["run_seconds"]
    sched = schedule.build_schedule(tr, window)
    tokens = sum(s.prompt_len + s.output_len for s in sched)
    assert tokens >= tr["backlog_tokens"]
    assert tokens / (tr["ramp_s"] + window) >= 45_000
    cfg = harness.load_json("configs", CONFIG + ".json")
    per_row = 2 * (weights_evabyte.param_count(cfg) - 320 * 4096)
    assert opcount.peaks("TPU v5 lite")["bf16_flops"] / per_row < 31_000


# ------------------------------------------------ operation counts by hand --

@pytest.mark.parametrize("rows,keys,nbytes", [
    # a decode row at position 99 (window 0): 100 exact keys, no summary;
    # K and V of 100 rows, the row's query and output
    ([(1, 100)], 100, 2 * 100 + 2),
    # a decode row at position 5000: window 2, 905 exact keys (4096..5000)
    # and the 256 summaries of windows 0 and 1
    ([(1, 5001)], 905 + 256, 2 * (905 + 256) + 2),
    # a whole window, rows 2048..4095: exact keys 1 + 2 + ... + 2048, and
    # 128 summaries for each row; the window's 2,048 keys and the 128
    # summaries are read once
    ([(2048, 4096)], 2048 * 2049 // 2 + 2048 * 128,
     2 * (2048 + 128) + 2 * 2048),
    # rows 2040..2055 as the engine packs them: never one run; counted
    # window by window: 8 rows at the end of window 0 and 8 at the start
    # of window 1
    ([(16, 2056)], sum(range(2041, 2049)) + sum(range(1, 9)) + 8 * 128,
     2 * 2048 + 2 * 8 + 2 * (8 + 128) + 2 * 8),
])
def test_opcount_eva_by_hand(rows, keys, nbytes):
    heads, hd = 32, 128
    flops, got = opcount_eva.ragged_eva_attention(rows, heads, hd)
    assert flops == 4 * heads * hd * keys
    assert got == nbytes * heads * hd * 2


def test_a_row_at_32k_attends_under_an_eighth_of_full_attention():
    exact, summaries = opcount_eva.keys_of(32767)
    assert (exact, summaries) == (2048, 1920)
    assert exact + summaries == 3968 < 32768 / 8


# --------------------------------------------------------------- the reader --

_LOAD = harness.load_module     # the real one: a test patches the name


class _Named:
    t0, t1 = 0, 10 ** 12
    # three rounds inside the traced window and one that sticks out
    ticks = [(100, 200, 7), (300, 400, 8), (500, 600, 9), (-5, 50, 6)]


def _read(monkeypatch, how, calls, ticks, busy_s=1.0):
    from benchmarks.lib import xregion
    reader = _LOAD("readers", "xplane_kernel_eva")
    cfg = harness.load_json("configs", CONFIG + ".json")
    notes = []
    ctx = types.SimpleNamespace(
        obs={"xplane": types.SimpleNamespace(busy_s=lambda: busy_s),
             "eva_ticks": ticks},
        config=cfg, device_kind="TPU v5 lite", note=notes.append)
    monkeypatch.setattr(xregion, "load", lambda ctx: _Named)
    monkeypatch.setattr(
        reader.harness, "load_module",
        lambda kind, name: types.SimpleNamespace(
            kernel_calls=lambda red, stems: calls))
    return reader.read(how, ctx), notes


def test_the_roofline_reads_the_recorded_rounds(monkeypatch):
    how = harness.load_json("layer_metrics", "eva_attn_roofline.bytedocs.json")
    assert (how["reader"], how["kernels"], how["as"]) == (
        "xplane_kernel_eva", ["ragged_eva_attention"], "roofline")
    rows = [(2048, 6144), (1, 9000)]
    least, side = opcount.roofline_s(*opcount_eva.ragged_eva_attention(rows),
                                     "TPU v5 lite")
    assert side == "compute"
    # round 7: 16 calls of twice the least time; round 8: 15 calls, left
    # out; round 9: no rows recorded, left out
    ns = int(2 * least * 1e9)
    calls = [(100 + i, 100 + i + ns) for i in range(16)] \
        + [(300 + i, 300 + i + ns) for i in range(15)]
    value, notes = _read(monkeypatch, how, sorted(calls), {7: rows, 8: rows})
    assert value == pytest.approx(50.0, rel=1e-6)
    assert any("round 8 has 15 kernel calls" in n for n in notes)
    none, _ = _read(monkeypatch, how, [], {7: rows})
    assert none is None                 # a program without the kernel


def test_the_share_is_the_kernels_time_over_busy_time(monkeypatch):
    how = harness.load_json("layer_metrics", "eva_attn_share.bytedocs.json")
    value, _ = _read(monkeypatch, how, [(0, 10 ** 8), (5, 2 * 10 ** 8 + 5)],
                     {7: [(1, 1)]}, busy_s=1.5)
    assert value == pytest.approx(20.0)


def test_the_scope_and_counter_metrics_name_what_the_program_has():
    from paddle_tpu.models import evabyte
    for name, scope in (("eva_summary_share", "eva_summarize"),
                        ("state_write_share", "kv_write")):
        how = harness.load_json("layer_metrics", name + ".bytedocs.json")
        assert how["reader"] == "xplane_scope" and how["scopes"] == [scope]
        assert {"attn", "mlp", "ragged_eva_attention", "eva_summarize",
                "kv_write"} <= set(how["among"])
    how = harness.load_json("layer_metrics", "summary_key_share.bytedocs.json")
    assert (how["counter"], how["over"]) == ("eva_summary_keys", "eva_keys")
    assert evabyte.TICK_STATS == ("eva_window_keys", "eva_summary_keys",
                                  "eva_chunks_closed")
    reader = harness.load_module("readers", "engine_counter")
    ctx = types.SimpleNamespace(obs={"counters": {"eva_summary_keys": 30,
                                                  "eva_keys": 120}})
    assert reader.read(how, ctx) == 25.0
