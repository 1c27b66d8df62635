"""The latent cell (``pangu-serve-longdocs``): its rehearsal is correct and
can fail, its operation count against hand-counted cases, its control (the
reference in int8) reads above the limit at a size a test can hold, and its
new readers on a small trace recorded on a v5e by
``benchmarks/tools/record_trace_latent.py`` (a few ragged ticks of a small
share: 1 dense + 2 expert layers, 16 experts routed, 4 held)."""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.lib import (harness, opcount_latent, serve_latent,
                            weights_pangu, xplane)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(HERE, "testdata", "trace_latent.xplane.pb")
AMONG = ["embed", "layers", "attn", "mlp", "kv_write", "head",
         "ragged_latent_attention", "router", "experts", "shared_expert"]


def rehearse(capsys):
    from benchmarks import run
    assert run.main(["--workload", "pangu-serve-longdocs", "--seed", "11",
                     "--seconds", "2", "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = [x for x in out.splitlines() if x.startswith("[bench] rehearsal")]
    return json.loads(line[-1].split("rehearsal: ", 1)[1]), out


def test_a_sound_rehearsal_is_correct(capsys):
    result, out = rehearse(capsys)
    assert result["correct"] is True, out
    assert list(result["checks"]) == [
        "backlog_requests_left_at_close", "served_logit_gap",
        "route_near_tie_share", "compiles_in_window",
        "tracer_events_dropped"]
    assert "expert pairs routed in the window" in out


def test_an_altered_token_is_not_correct(capsys, monkeypatch):
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine as E
    record = E._record
    monkeypatch.setattr(E, "_record", lambda self, slot, tok: record(
        self, slot, (int(tok) + 7) % 500 + 1))
    result, out = rehearse(capsys)
    assert result["correct"] is False, out
    assert "served_logit_gap" in out and "FAILED" in out


# the published config.json (the catalog beside the model-configs guide)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    cfg = harness.load_json("configs", "openpangu-ultra-moe-718b-ep16.json")
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(cfg["reduced"]) == set(cfg["cut"])
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]
    # the arithmetic of the cut: 4,919 M parameters, 9.84 GB in bfloat16
    assert weights_pangu.param_count(cfg) == 4_919_139_840
    assert weights_pangu.stack_layers(cfg) == {"dense": 1, "moe": 4}
    assert weights_pangu.held(cfg) == (0, 16)


@pytest.mark.parametrize("rows,flops,nbytes", [
    # one decode row over 100 keys: 100 keys x 128 heads x 2 x (576 + 512);
    # 100 latent rows of 576 and one row's query (128 x 576) and output
    # (128 x 512), two bytes each
    ([(1, 100)], 2 * 100 * 128 * 1088, (100 * 576 + 128 * 1088) * 2),
    # a 4-row chunk ending at key 10 attends 7 + 8 + 9 + 10 = 34 keys
    ([(4, 10)], 2 * 34 * 128 * 1088, (10 * 576 + 4 * 128 * 1088) * 2),
    # two sequences add
    ([(1, 100), (4, 10)], 2 * 134 * 128 * 1088,
     (110 * 576 + 5 * 128 * 1088) * 2),
    ([], 0, 0)], ids=["decode", "chunk", "two", "empty"])
def test_opcount_latent_by_hand(rows, flops, nbytes):
    assert opcount_latent.ragged_latent_attention(rows) == (flops, nbytes)


def test_opcount_latent_sits_at_the_ridge_for_one_row():
    """128 heads x 2,176 FLOP over 1,152 bytes a key: 241.8 FLOP a byte,
    against the v5e's 240.5 (197e12 / 819e9)."""
    f, b = opcount_latent.ragged_latent_attention([(1, 10 ** 9)])
    assert f / b == pytest.approx(128 * 2176 / 1152, rel=1e-3)


class Served:
    def __init__(self, prompt, tokens):
        self.prompt, self.tokens, self.out_len = prompt, tokens, len(tokens)


def tiny():
    cfg = harness.load_json("configs", "openpangu-ultra-moe-718b-ep16.json")
    over = harness.load_json("traffic", "longdocs-backlog.json")[
        "rehearse"]["config"]
    return harness.merge(cfg, over)    # the rehearsal's initialisation: 0.1


def test_int8_reads_above_the_limit_and_float32_below():
    """At every position the token the lower precision puts first, read
    against the float32 reference: bfloat16 stays under the cell's limit,
    int8 reads over it, at the rehearsal's size."""
    cfg = tiny()
    limits = {k: v["limit"] for k, v in harness.load_json(
        "limits", "pangu-serve-longdocs.json").items()}
    rng = np.random.default_rng(0)
    reqs = [Served(rng.integers(1, 512, n).tolist(),
                   rng.integers(1, 512, 100).tolist()) for n in (60, 150)]
    sound, control = [], []
    for seed in (1, 2, 3):
        params = weights_pangu.make_params(cfg, seed, "float32")
        eps = limits["route_margin_eps"]
        sound.append(serve_latent.served_gap(
            cfg, params, reqs, eps, lower="bfloat16", pad_to=32))
        control.append(serve_latent.served_gap(
            cfg, params, reqs, eps, lower="int8", pad_to=32))
    assert all(g["tokens"] == 200 for g in sound + control)
    assert max(g["widest"] for g in control) > limits["served_logit_gap"], \
        control
    assert min(g["mean"] for g in control) > 3 * max(
        g["mean"] for g in sound), (sound, control)
    # the control moves the route margins by more than the epsilon that
    # calls a route a near-tie; bfloat16 moves them by less
    assert min(g["margin_moved"][1] for g in control) > eps


# ------------------------------------------------- readers, on the trace --

@pytest.fixture(scope="module")
def ctx():
    with open(TRACE.replace(".xplane.pb", ".ticks.json")) as f:
        side = json.load(f)
    red = xplane.Reduction(TRACE, host_spans=("engine_step",))
    notes = []
    ticks = {int(k): [tuple(r) for r in rows]
             for k, rows in side["latent_ticks"].items()}
    return types.SimpleNamespace(
        obs={"xplane": red, "latent_ticks": ticks},
        config=side["config"], device_kind=side["device_kind"],
        note=notes.append, notes=notes)


def scope_share(ctx, *scopes):
    reader = harness.load_module("readers", "xplane_scope")
    return reader.read({"scopes": list(scopes), "among": AMONG}, ctx)


def test_scopes_partition_the_own_time(ctx):
    shares = {s: scope_share(ctx, s) or 0.0 for s in AMONG}
    assert all(shares[s] > 0 for s in ("attn", "mlp", "router", "experts",
                                       "shared_expert", "kv_write",
                                       "ragged_latent_attention", "head"))
    assert sum(shares.values()) <= 100.0 + 1e-6
    assert sum(shares.values()) > 75.0          # the rest is unscoped
    assert scope_share(ctx, *AMONG) == pytest.approx(sum(shares.values()))
    # the accepted region reader sees the same operations one level up:
    # what lies under router / experts / shared_expert is its ``mlp``
    from benchmarks.lib import xregion
    regions = xregion.Named(ctx.obs["xplane"]).shares()
    inner = sum(shares[s] for s in ("mlp", "router", "experts",
                                    "shared_expert"))
    assert regions["mlp"] == pytest.approx(inner, abs=1e-6)
    assert regions["attn"] == pytest.approx(
        shares["attn"] + shares["ragged_latent_attention"], abs=1e-6)


def test_a_scope_no_operation_lies_under_reads_none(ctx):
    assert scope_share(ctx, "optimizer") is None
    reader = harness.load_module("readers", "xplane_scope")
    empty = types.SimpleNamespace(obs={})
    assert reader.read({"scopes": ["experts"], "among": AMONG}, empty) is None


def test_the_latent_roofline_reads_the_recorded_ticks(ctx):
    reader = harness.load_module("readers", "xplane_kernel_latent")
    how = {"kernels": ["ragged_latent_attention"]}
    value = reader.read(how, ctx)
    assert 0.0 < value <= 100.0
    calls = reader.kernel_calls(ctx.obs["xplane"],
                                {"ragged_latent_attention"})
    assert len(calls) == 3 * len(ctx.obs["latent_ticks"])
    assert any("latent roofline: least" in n for n in ctx.notes)
    # rounds whose rows are unknown are left out; with none known, with no
    # such kernel or with no trace: nothing, and no raise
    some = dict(list(ctx.obs["latent_ticks"].items())[1:])
    fewer = types.SimpleNamespace(**{**vars(ctx), "obs": dict(
        ctx.obs, latent_ticks=some)})
    assert 0.0 < reader.read(how, fewer) <= 100.0
    none = types.SimpleNamespace(**{**vars(ctx), "obs": dict(
        ctx.obs, latent_ticks={-1: [(1, 1)]})})
    assert reader.read(how, none) is None
    assert reader.read({"kernels": ["no_such_kernel"]}, ctx) is None
    assert reader.read(how, types.SimpleNamespace(obs={})) is None
