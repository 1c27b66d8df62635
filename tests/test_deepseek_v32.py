"""DeepSeek-V3.2-Exp through the program: the model class of
``models/pangu_moe.py`` under the published keys, the lightning indexer's
two kernels and the exact selection between them, the group-limited router
and the cache spec of two leaves on one table, each against the plain
reference (``benchmarks/lib/reference_deepseek_v32.py``) or ``jax.numpy``
at widths a CPU holds.  Seeded weights, float32 and bfloat16."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models._decode import CacheLeaf, build_pools
from paddle_tpu.models.pangu_moe import (INDEX_STATS, TICK_STATS,
                                         PanguMoeConfig, PanguMoeModel,
                                         yarn_inv_freq, yarn_mscale)
from paddle_tpu.ops import moe
from paddle_tpu.ops.index_select import (score_key, select_threshold_ref,
                                         select_threshold_rows, selected)
from paddle_tpu.ops.ragged_index_scores import (ragged_index_scores_ref,
                                                ragged_index_scores_rows)
from paddle_tpu.ops.ragged_latent_attention import \
    ragged_latent_attention_ref
from paddle_tpu.ops.ragged_sparse_latent_attention import (
    ragged_sparse_latent_attention_ref, ragged_sparse_latent_attention_rows)
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
from paddle_tpu.telemetry import Tracer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import harness  # noqa: E402
from benchmarks.lib import reference_deepseek_v32 as ref  # noqa: E402
from benchmarks.lib import weights_dsv32  # noqa: E402

# the configuration file's keys at a small size: 16 experts routed in 4
# groups of which 2 stay, top-3, this share holds 4 (ids 4-7); 1 dense + 2
# expert layers; a 4-head indexer that keeps 8 positions
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
CFG = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=16,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, intermediate_size=48, moe_intermediate_size=12,
           n_routed_experts=4, router_width=16, experts_held=[4, 8],
           n_shared_experts=1, num_experts_per_tok=3,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
           rope_theta=10000, max_position_embeddings=128, rope_scaling=YARN,
           n_group=4, topk_group=2, topk_method="noaux_tc",
           sandwich_norm=False, index_topk=8, index_n_heads=4,
           index_head_dim=8, initializer_range=0.2, router_bias_std=0.05)
TOL = {"float32": 2e-5, "bfloat16": 0.25}
REF = dict(block=8, head_group=2, segments=2)


def build(dtype, seed=7, cfg=CFG):
    paddle.seed(0)
    first, stop = cfg["experts_held"]
    skip = ("router_width", "experts_held", "n_routed_experts",
            "router_bias_std", "rope_scaling")
    model = PanguMoeModel(PanguMoeConfig(
        **{k: v for k, v in cfg.items() if k not in skip},
        rope_scaling={k: v for k, v in cfg["rope_scaling"].items()
                      if k != "mscale"},
        n_routed_experts=cfg["router_width"],
        experts_held=range(first, stop), compute_dtype=dtype))
    params = weights_dsv32.make_params(cfg, seed, dtype)
    table = PanguMoeModel.param_table(model.config)
    assert {n: v.shape for n, v in params.items()} \
        == {n: shape for n, (shape, _) in table.items()}
    return model, params


@pytest.fixture
def interpret(request):
    paddle.set_flags({"FLAGS_paged_attn_interpret": request.param})
    yield request.param
    paddle.set_flags({"FLAGS_paged_attn_interpret": False})


def engine(model, params, token_budget=16, **kw):
    return RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=3, max_len=64, block_size=8, num_blocks=20,
        token_budget=token_budget, prompt_buckets=list(range(8, 65, 8)),
        **kw)


# ----------------------------------------------------------- the model --

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference_where_selection_bites(dtype):
    """48 positions, 8 kept: from position 8 on a row attends a sixth of
    its context, and which sixth is the indexer's."""
    model, params = build(dtype)
    ids = np.random.default_rng(1).integers(1, 96, (2, 48))
    h, _ = model.prefill(params, jnp.asarray(ids), 48)
    got = model.decode_logits(params, h)
    for b in range(2):
        want, _, chosen = ref.logits(CFG, params, jnp.asarray(ids[b]), **REF)
        worst = jnp.abs(got[b] - want).max(-1)          # a position
        if dtype == "float32":
            assert float(worst.max()) < TOL[dtype]
        else:
            # bfloat16 index scores flip a kept position here and there
            # (8 of up to 48, at these widths), and a row that attends
            # another key is another row: most positions agree
            assert float(jnp.median(worst)) < TOL[dtype]
        assert float(want.std()) > 0.5      # the comparison is not of zeros
        assert chosen.shape == (3, 48, 48)
        assert chosen.sum(-1).tolist() == [
            [min(t + 1, 8) for t in range(48)]] * 3
    if dtype == "float32":      # and the selection matters: dense differs
        dense, _, _ = ref.logits(CFG, params, jnp.asarray(ids[0]),
                                 select="dense", **REF)
        assert float(jnp.abs(dense - want).max()) > 0.1


def test_a_context_under_index_topk_is_dense_latent_attention():
    """With ``index_topk`` at or over the context every position is kept:
    the logits are those of the same weights with selection off."""
    cfg = dict(CFG, index_topk=64)
    model, params = build("float32", cfg=cfg)
    ids = jnp.asarray(np.random.default_rng(2).integers(1, 96, (1, 40)))
    h, _ = model.prefill(params, ids, 40)
    got = model.decode_logits(params, h)[0]
    kept, _, _ = ref.logits(cfg, params, ids[0], **REF)
    dense, _, _ = ref.logits(cfg, params, ids[0], select="dense", **REF)
    assert float(jnp.abs(kept - dense).max()) == 0.0
    assert float(jnp.abs(got - dense).max()) < TOL["float32"]


@pytest.mark.parametrize("interpret,dtype", [
    (True, "float32"), (True, "bfloat16"), (False, "float32")],
    indirect=["interpret"], ids=["kernel-float32", "kernel-bfloat16",
                                 "xla-float32"])
def test_engine_prefill_then_decode_matches_the_reference(dtype, interpret):
    """Served tokens through the two paged leaves (chunked prefill, mixed
    ticks, left-padded buckets, every context over ``index_topk``) against
    the reference's full forward over prompt + served tokens; the
    indexer's counters on the ``tick`` event and both leaves' bytes on the
    ``cache`` event."""
    model, params = build(dtype)
    tracer = Tracer()
    eng = engine(model, params, tracer=tracer)
    ids = np.random.default_rng(2).integers(1, 96, 40)
    prompts = [ids[:21].tolist(), ids[5:18].tolist(), ids[3:32].tolist(),
               ids[:9].tolist()]
    served = {}
    rids = [eng.add_request(p, 6, on_token=lambda rid, t, d:
                            served.setdefault(rid, []).append(int(t)))
            for p in prompts]
    eng.run_to_completion()
    for rid, p in zip(rids, prompts):
        full = p + served[rid][:-1]
        L = -(-len(full) // 16) * 16
        logits, _, _ = ref.logits(CFG, params, jnp.asarray(
            full + [0] * (L - len(full))), **REF)
        rows = logits[len(p) - 1:len(full)]
        gap = rows.max(-1) - jnp.take_along_axis(
            rows, jnp.asarray(served[rid])[:, None], -1)[:, 0]
        assert float(gap.max()) < TOL[dtype], (rid, gap)
    ticks = [e for e in tracer.events("tick") if e.get("budget_used")]
    assert ticks and all(set(TICK_STATS + INDEX_STATS) <= set(e)
                         for e in ticks)
    for e in ticks:
        # [rid, rows, kv_end] a sequence; the bucket's left pad is under 8
        ctx = sum(sum(range(kv - n + 1, kv + 1)) for _, n, kv in e["rows"])
        assert ctx - 8 * e["budget_used"] <= e["index_candidates"] // 3 \
            <= ctx
        assert e["budget_used"] <= e["index_selected"] // 3 \
            <= 8 * e["budget_used"]
        assert e["index_selected"] <= e["index_candidates"]
    (cache,) = tracer.events("cache")
    item = jnp.dtype(dtype).itemsize
    assert cache["layout"] == "latent" and cache["leaf_bytes"] == [
        n * 21 * 8 * w * item for n in (1, 2) for w in (128, 8)]
    assert cache["pool_bytes"] == sum(cache["leaf_bytes"])


@pytest.mark.parametrize("interpret", [False, True], indirect=True,
                         ids=["xla", "kernel"])
def test_narrow_rounds_give_the_wide_programs_tokens(interpret):
    """With an indexer too: rounds of decode rows only run the tick at 8
    rows, over the widest table (64 positions against ``index_topk`` 8: the
    selection bites in the narrow program as in the wide ones), and serve
    the budget-wide program's tokens; the indexer's counters come back
    from either."""
    model, params = build("float32")
    ids = np.random.default_rng(2).integers(1, 96, 40)
    prompts = [ids[:21].tolist(), ids[5:18].tolist(), ids[3:32].tolist(),
               ids[:9].tolist()]

    def serve(eng):
        rids = [eng.add_request(p, 6) for p in prompts]
        done = eng.run_to_completion()
        return [done[r] for r in rids]

    tr = Tracer()
    eng = engine(model, params, token_budget=24, tracer=tr)
    wide = engine(model, params, token_budget=24)
    assert eng.narrow_rows == 8
    wide.narrow_rows = 0
    assert serve(eng) == serve(wide)
    assert 0 < eng.narrow_steps < eng.ragged_steps and eng.mixed_steps
    assert wide.narrow_steps == 0 and wide.ragged_steps == eng.ragged_steps
    narrow = [k for k in tr.events("tick")
              if k.get("budget_used") and not k["prefill_tokens"]]
    assert narrow and all(k["rows_run"] == 8 for k in narrow)
    assert all(0 < k["index_selected"] <= k["index_candidates"]
               for k in narrow)


def test_without_a_tracer_the_tick_returns_what_it_returned():
    """The regions and counters are inert: the same tokens with and
    without a tracer, and no ``tick`` note is taken without one."""
    model, params = build("float32")
    prompt = np.random.default_rng(3).integers(1, 96, 27).tolist()
    out = []
    for tracer in (None, Tracer()):
        eng = engine(model, params, tracer=tracer)
        rid = eng.add_request(prompt, 5)
        out.append(list(eng.run_to_completion()[rid]))
    assert out[0] == out[1]
    got = model.generate(params, jnp.asarray([prompt]), 5)[0].tolist()
    assert got == out[0]                # the dense cache selects alike


def test_cache_spec_states_two_leaves_on_one_table():
    model, _ = build("bfloat16")
    spec = model.cache_spec()
    assert spec.layout == "latent"
    assert spec.tick_stats == TICK_STATS + INDEX_STATS
    assert spec.pools == (
        (CacheLeaf(1, (128,), "bfloat16"), CacheLeaf(1, (8,), "bfloat16")),
        (CacheLeaf(2, (128,), "bfloat16"), CacheLeaf(2, (8,), "bfloat16")))
    pools = build_pools(spec, (21, 8))
    assert [p.shape for p in jax.tree.leaves(pools)] == [
        (1, 21, 8, 128), (1, 21, 8, 8), (2, 21, 8, 128), (2, 21, 8, 8)]


def test_param_count_of_the_cut():
    """1 dense + 4 expert layers of the published widths, 16 experts held,
    an eighth of the vocabulary: MLA 187.11 M, indexer 13.96 M, a dense
    layer 597.4 M, an expert layer 951.6 M, embedding + head 231.7 M."""
    cfg = harness.load_json("configs", "deepseek-v3.2-exp-ep16.json")
    assert weights_dsv32.param_count(cfg) == 4_635_518_208
    table = weights_dsv32.param_table(cfg)
    size = lambda *names: sum(math.prod(table[n][0]) for n in names)
    assert size(*(f"dense_{n}" for n in weights_dsv32.INDEXER)) == 13_959_424
    assert size("dense_q_a_w", "dense_q_a_norm_w", "dense_q_b_w",
                "dense_kv_a_w", "dense_kv_a_norm_w", "dense_kv_b_w",
                "dense_o_w") == 187_107_328
    assert size("wte", "lm_head", "norm_f_w") == 2 * 16160 * 7168 + 7168
    from benchmarks.lib import serve_sparse
    program = PanguMoeModel.param_table(serve_sparse.model_config(cfg))
    assert {n: s for n, (s, _) in program.items()} \
        == {n: s for n, (s, _) in table.items()}


# ------------------------------------------------- rotary scaling (YaRN) --

def test_yarn_frequencies_and_softmax_scale_by_hand():
    """64 rotary columns, theta 10000, factor 40 over 4096: the correction
    dims are 10 (32 rotations, rounded down) and 23 (1 rotation, rounded
    up); frequency j is plain under 10, divided by 40 from 23 on, and
    blended by (j - 10) / 13 between."""
    rs = dict(factor=40, original_max_position_embeddings=4096,
              beta_fast=32, beta_slow=1, mscale_all_dim=1, type="yarn")
    inv = yarn_inv_freq(64, 10000, **rs)
    plain = [10000 ** (-2 * j / 64) for j in range(32)]
    assert inv[:11] == pytest.approx(plain[:11], rel=1e-12)
    assert inv[23:] == pytest.approx([f / 40 for f in plain[23:]], rel=1e-12)
    r = (16 - 10) / 13
    assert inv[16] == pytest.approx(plain[16] * (1 - r) + plain[16] / 40 * r,
                                    rel=1e-12)
    assert yarn_mscale(**rs) == pytest.approx(1.36889, abs=1e-5)
    assert yarn_mscale(**rs) ** 2 == pytest.approx(1.87385, abs=1e-5)
    cfg = harness.load_json("configs", "deepseek-v3.2-exp-ep16.json")
    assert np.asarray(ref.yarn_inv_freq(cfg)) == pytest.approx(
        np.asarray(inv), rel=1e-5)
    assert ref.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.87385,
                                                   rel=1e-5)
    model, _ = build("float32")
    assert model._scale == pytest.approx(
        12 ** -0.5 * yarn_mscale(**rs) ** 2, rel=1e-12)


# ------------------------------------------------------------ the router --

def plain_group_route(s, bias, k, n_group, topk_group, scaling):
    """DeepSeek-V3's choice a row at a time, in numpy."""
    idx, w = [], []
    for row in np.asarray(s, np.float64):
        c = row + np.asarray(bias, np.float64)
        groups = c.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        c = np.where(np.isin(np.arange(len(c)) // groups.shape[1], kept),
                     c, -np.inf)
        top = np.argsort(-c, kind="stable")[:k]
        idx.append(top)
        w.append(row[top] / (row[top].sum() + 1e-20) * scaling)
    return np.asarray(idx), np.asarray(w)


def test_group_limited_routing_against_a_plain_one():
    rng = np.random.default_rng(6)
    T, H, E = 40, 8, 32
    m = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(H, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.2, jnp.float32)
    idx, w = moe.route_sigmoid_topk(m, gate, 4, 2.5, bias=bias, n_group=8,
                                    topk_group=3)
    s = jax.nn.sigmoid(jnp.matmul(m, gate,
                                  precision=jax.lax.Precision.HIGHEST))
    want_idx, want_w = plain_group_route(s, bias, 4, 8, 3, 2.5)
    assert np.sort(np.asarray(idx), -1).tolist() \
        == np.sort(want_idx, -1).tolist()
    order = np.argsort(np.asarray(idx), -1)
    want_order = np.argsort(want_idx, -1)
    assert np.take_along_axis(np.asarray(w), order, -1) == pytest.approx(
        np.take_along_axis(want_w, want_order, -1), rel=1e-5)
    # the bias chooses and never weighs: the weights are made from s
    assert float(jnp.abs(w.sum(-1) - 2.5).max()) < 1e-5
    # chosen experts lie in at most 3 groups of 4
    assert max(len(set((row // 4).tolist())) for row in np.asarray(idx)) <= 3
    # the reference's own router agrees
    ref_idx, ref_w, _, _ = ref.route(
        dict(num_experts_per_tok=4, n_group=8, topk_group=3,
             routed_scaling_factor=2.5), s, bias)
    assert np.asarray(ref_idx).tolist() == np.asarray(idx).tolist()
    assert float(jnp.abs(ref_w - w).max()) < 1e-6


def test_one_group_and_no_bias_is_the_plain_router_to_the_bit():
    rng = np.random.default_rng(7)
    m = jnp.asarray(rng.normal(size=(30, 8)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    plain = moe.route_sigmoid_topk(m, gate, 3, 2.5)
    for kw in (dict(n_group=1, topk_group=1), dict(bias=None),
               dict(bias=jnp.zeros((16,)), n_group=1)):
        idx, w = moe.route_sigmoid_topk(m, gate, 3, 2.5, **kw)
        assert np.asarray(idx).tolist() == np.asarray(plain[0]).tolist()
        assert np.asarray(w).tobytes() == np.asarray(plain[1]).tobytes()
    lowered = lambda **kw: jax.jit(lambda m, g: moe.route_sigmoid_topk(
        m, g, 3, 2.5, **kw)).lower(m, gate).as_text()
    assert lowered() == lowered(n_group=1, topk_group=1, bias=None)


@pytest.mark.parametrize("held", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer_with_group_routing(held):
    """The 16 / held ranks of one expert layer — each routing over all 16
    experts in 4 groups with the selection bias and computing its own —
    and the shared expert counted once add up to the uncut layer, which
    the reference computes with every expert held."""
    rng = np.random.default_rng(4)
    T, H, F, E, k = 12, 8, 6, 16, 4
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    gate, w_g, w_u, w_d = f(H, E), f(E, H, F), f(E, H, F), f(E, F, H)
    s_g, s_u, s_d = f(H, F), f(H, F), f(F, H)
    bias, m = f(E) * 0.3, f(T, H)
    idx, w = moe.route_sigmoid_topk(m, gate, k, 2.5, bias=bias, n_group=4,
                                    topk_group=2)
    total = moe.gated_mlp(m, s_g, s_u, s_d)
    rows = 0
    for first in range(0, E, held):
        sl = slice(first, first + held)
        part, n = moe.held_experts_ffn(m, idx, w, w_g[sl], w_u[sl], w_d[sl],
                                       first)
        total, rows = total + part, rows + int(n.sum())
    assert rows == T * k                       # every pair, exactly once
    cfg = dict(num_experts_per_tok=k, experts_held=[0, E],
               n_routed_experts=E, routed_scaling_factor=2.5, n_group=4,
               topk_group=2)
    want, margin = ref._experts(cfg, dict(
        router_w=gate, router_bias=bias, e_gate_w=w_g, e_up_w=w_u,
        e_down_w=w_d, s_gate_w=s_g, s_up_w=s_u, s_down_w=s_d), m, None)
    assert float(jnp.abs(total - want).max()) < 1e-3 * float(
        jnp.abs(want).max())
    assert bool(jnp.all(margin >= 0)) and bool(jnp.all(jnp.isfinite(margin)))


# ----------------------------------------- kernel 1: the index scores --

def index_pack(rng, dtype, nh=4, D=8, NB=20, bs=4, S=3, C=8):
    """``test_pangu_moe.pack``'s mixed pack for the indexer: a 10-row
    chunk, a decode row deep in its sequence, a 9-row chunk behind a left
    pad, 4 padding rows; garbage in the trash block."""
    T = 24
    q = jnp.asarray(rng.normal(size=(T, nh, D)), dtype)
    w = jnp.asarray(rng.normal(size=(T, nh)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(NB + 1, bs, D)), dtype)
    pool = pool.at[0].set(1e4)
    table = jnp.asarray(rng.permutation(NB)[:S * C].reshape(S, C) + 1
                        if NB >= S * C else rng.integers(1, NB + 1, (S, C)),
                        jnp.int32)
    row_seq = jnp.asarray([0] * 10 + [1] + [2] * 9 + [0] * 4, jnp.int32)
    row_pos = jnp.asarray(list(range(5, 15)) + [30] + list(range(3, 12))
                          + [-1] * 4, jnp.int32)
    pads = jnp.asarray([2, 0, 3], jnp.int32)
    return q, w, pool, table, row_seq, row_pos, pads


def valid_columns(row_pos, K):
    return (jnp.arange(K)[None, :] <= row_pos[:, None])


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 5e-2)])
@pytest.mark.parametrize("blocks_per_step", [1, 2, 8])
def test_index_scores_kernel_against_jax_numpy(dtype, tol, blocks_per_step):
    rng = np.random.default_rng(0)
    q, w, pool, table, seq, pos, _ = index_pack(rng, jnp.dtype(dtype))
    got = ragged_index_scores_rows(q, w, pool, table, seq, pos,
                                   interpret=True,
                                   blocks_per_step=blocks_per_step)
    want = ragged_index_scores_ref(q, w, pool, table, seq, pos)
    # by hand: I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])
    keys = pool[table].reshape(3, -1, 8).astype(jnp.float32)[seq]
    hand = jnp.sum(jax.nn.relu(jnp.einsum(
        "thd,tkd->thk", q.astype(jnp.float32), keys)) * w[:, :, None], 1)
    ok = valid_columns(pos, 32)
    assert float(jnp.abs(jnp.where(ok, want - hand, 0)).max()) < tol
    assert float(jnp.abs(jnp.where(ok, got - want, 0)).max()) < tol
    assert got.dtype == jnp.float32 and got.shape == (24, 32)


def test_index_scores_kernel_reads_a_layer_of_a_stack_in_place():
    rng = np.random.default_rng(1)
    q, w, pool, table, seq, pos, _ = index_pack(rng, jnp.float32)
    stack = jnp.stack([pool * 0 + 7.0, pool, pool * 0 - 3.0])
    want = ragged_index_scores_ref(q, w, pool, table, seq, pos)
    ok = valid_columns(pos, 32)
    for fn, kw in ((ragged_index_scores_rows, {"interpret": True}),
                   (ragged_index_scores_ref, {})):
        got = jax.jit(lambda ly: fn(q, w, stack, table, seq, pos, layer=ly,
                                    **kw))(jnp.int32(1))
        assert float(jnp.abs(jnp.where(ok, got - want, 0)).max()) < 2e-5


# --------------------------------------------- step 2: the exact top-k --

def top_k_mask(scores, lo, hi, k):
    """What ``lax.top_k`` selects, a row at a time: the oracle."""
    out = np.zeros(scores.shape, bool)
    for t, (a, b) in enumerate(zip(lo, hi)):
        if b < a:
            continue
        row = jnp.asarray(scores[t, a:b + 1])
        _, idx = jax.lax.top_k(row, min(k, b - a + 1))
        out[t, a + np.asarray(idx)] = True
    return out


@pytest.mark.parametrize("how", ["kernel", "xla"])
@pytest.mark.parametrize("case", ["distinct", "repeated", "all-equal",
                                  "signed-zeros"])
def test_selection_is_exactly_top_k_with_ties_to_the_lower_position(how,
                                                                    case):
    rng = np.random.default_rng(3)
    T, K, k = 16, 64, 8
    scores = rng.normal(size=(T, K)).astype(np.float32)
    if case == "repeated":      # a handful of values: ties at every edge
        scores = rng.integers(-2, 3, (T, K)).astype(np.float32)
    elif case == "all-equal":
        scores = np.full((T, K), 0.5, np.float32)
    elif case == "signed-zeros":
        scores = np.where(rng.random((T, K)) < 0.5, 0.0, 1.0).astype(
            np.float32) * np.where(rng.random((T, K)) < 0.3, -1, 1)
    seq = np.asarray([0] * 6 + [1] * 6 + [2] * 4, np.int32)
    pos = np.asarray([3, 7, 8, 20, 40, 63, 2, 9, 10, 30, 50, 63,
                      5, 12, 33, -1], np.int32)
    pads = np.asarray([0, 2, 5], np.int32)
    fn = select_threshold_rows if how == "kernel" else select_threshold_ref
    kw = {"interpret": True} if how == "kernel" else {}
    thr = fn(jnp.asarray(scores), jnp.asarray(seq), jnp.asarray(pos),
             jnp.asarray(pads), k=k, **kw)
    lo = pads[seq]
    got = np.asarray(selected(jnp.asarray(scores), thr, jnp.asarray(lo),
                              jnp.asarray(pos)))
    if case == "signed-zeros":
        # -0.0 sorts under +0.0 in the key; top_k takes them as equal:
        # the sets agree wherever no zero lies at the edge, and the count
        # is exact everywhere
        assert got.sum(-1).tolist() == [
            max(min(k, h - l + 1), 0) for l, h in zip(lo, pos)]
        return
    want = top_k_mask(scores, lo, pos, k)
    assert (got == want).all(), np.argwhere(got != want)
    assert got.sum(-1).tolist() == [max(min(k, h - l + 1), 0)
                                    for l, h in zip(lo, pos)]
    if case == "all-equal":     # every tie: the lowest positions win
        for t in range(T - 1):
            assert np.flatnonzero(got[t]).tolist() == list(
                range(lo[t], min(lo[t] + k, pos[t] + 1)))


def test_score_key_preserves_the_order_of_floats():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf],
                    jnp.float32)
    key = np.asarray(score_key(x))
    assert (np.diff(key) > 0).all()


# ---------------------------- kernel 3: attention over the selected --

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("blocks_per_step", [1, 4, 8])
def test_sparse_latent_kernel_against_jax_numpy(dtype, tol, blocks_per_step):
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    nh, R, Dr, k = 4, 32, 8, 6
    _, _, _, table, seq, pos, pads = index_pack(rng, dt, NB=40)
    qa = jnp.asarray(rng.normal(size=(24, nh, R)), dt)
    qr = jnp.asarray(rng.normal(size=(24, nh, Dr)), dt)
    pool = jnp.asarray(rng.normal(size=(41, 4, 64)), dt).at[0].set(1e4)
    scores = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    thr = select_threshold_ref(scores, seq, pos, pads, k=k)
    got = ragged_sparse_latent_attention_rows(
        qa, qr, pool, scores, thr, table, seq, pos, pads, scale=0.3,
        interpret=True, blocks_per_step=blocks_per_step)
    want = ragged_sparse_latent_attention_ref(
        qa, qr, pool, scores, thr, table, seq, pos, pads, scale=0.3)
    f32 = lambda t: t.astype(jnp.float32)
    assert float(jnp.abs(f32(got) - f32(want)).max()) < tol
    assert bool(jnp.all(got[-4:] == 0))
    # by hand: a softmax over the selected positions alone
    sel = selected(scores, thr, pads[seq], pos)
    assert sel.sum(-1).tolist() == [
        max(min(k, int(p - pads[s]) + 1), 0) for s, p in zip(seq, pos)]
    dense = f32(pool[table].reshape(3, -1, 64)[seq])
    sc = (jnp.einsum("thr,tkr->thk", f32(qa), dense[..., :R])
          + jnp.einsum("thd,tkd->thk", f32(qr), dense[..., R:R + Dr])) * 0.3
    p = jax.nn.softmax(jnp.where(sel[:, None], sc, -jnp.inf), -1)
    hand = jnp.einsum("thk,tkr->thr", jnp.where(sel[:, None], p, 0.0),
                      dense[..., :R])
    assert float(jnp.abs(f32(want)[:20] - hand[:20]).max()) < tol
    # and it is NOT attention over everything
    every = ragged_latent_attention_ref(qa, qr, pool, table, seq, pos, pads,
                                        scale=0.3)
    assert float(jnp.abs(f32(every) - f32(want)).max()) > 0.1


def test_selecting_everything_is_the_accepted_kernel():
    """Rows whose context is at most ``k`` attend all of it: the sparse
    kernel then gives what the accepted latent kernel gives."""
    rng = np.random.default_rng(5)
    _, _, _, table, seq, pos, pads = index_pack(rng, jnp.float32, NB=40)
    qa = jnp.asarray(rng.normal(size=(24, 4, 32)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(24, 4, 8)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(41, 4, 40)), jnp.float32)
    scores = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    thr = select_threshold_rows(scores, seq, pos, pads, k=32, interpret=True)
    got = ragged_sparse_latent_attention_rows(
        qa, qr, pool, scores, thr, table, seq, pos, pads, scale=0.3,
        interpret=True)
    want = ragged_latent_attention_ref(qa, qr, pool, table, seq, pos, pads,
                                       scale=0.3)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_the_tick_returns_the_selection_it_applied():
    """``selection_of`` hands out the mask of the attention, layer by
    layer: each real row keeps ``min(index_topk, context)`` positions of
    its own context, and a program too narrow for a choice keeps all."""
    model, params = build("float32")
    C, bs, T = 8, 8, 16
    rng = np.random.default_rng(8)
    table = jnp.asarray(rng.permutation(2 * C).reshape(2, C) + 1, jnp.int32)
    pads = jnp.asarray([0, 3], jnp.int32)
    seq = np.asarray([0] * 9 + [1] * 5 + [-1] * 2, np.int32)
    pos = np.asarray(list(range(20, 29)) + list(range(3, 8)) + [-1] * 2,
                     np.int32)
    toks = jnp.asarray(rng.integers(1, 96, T) * (pos >= 0), jnp.int32)
    h = model._embed_ragged(params, toks, None, None, None)
    pools = build_pools(model.cache_spec(), (2 * C + 1, bs))
    args = (params, h, pools, table, jnp.asarray(seq), jnp.asarray(pos),
            pads)
    plain = model.decode_ragged(*args)
    h2, _, stats, mask = model.decode_ragged(*args, selection_of=(4, 12))
    assert len(plain) == 3 and float(jnp.abs(plain[0] - h2).max()) == 0.0
    assert mask.shape == (3, 12, C * bs) and mask.dtype == jnp.bool_
    ctx = [p - (3 if s == 1 else 0) + 1 if p >= 0 else 0
           for s, p in zip(seq[4:], pos[4:])]
    assert mask.sum(-1).tolist() == [[min(c, 8) for c in ctx]] * 3
    assert stats.tolist()[3:] == [
        3 * sum(p - (3 if s == 1 else 0) + 1 for s, p in zip(seq, pos)
                if p >= 0),
        3 * sum(min(p - (3 if s == 1 else 0) + 1, 8)
                for s, p in zip(seq, pos) if p >= 0)]
    narrow = PanguMoeModel(PanguMoeConfig(**{
        **{k: getattr(model.config, k) for k in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "rms_norm_eps",
            "rope_theta", "rope_scaling", "n_group", "topk_group",
            "topk_method", "sandwich_norm", "index_n_heads",
            "index_head_dim", "experts_held")},
        "index_topk": 64, "compute_dtype": "float32"}))
    _, _, _, every = narrow.decode_ragged(*args, selection_of=(4, 12))
    assert every.sum(-1).tolist() == [ctx] * 3
