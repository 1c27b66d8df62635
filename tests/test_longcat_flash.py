"""LongCat-Flash through the program: the model, its softmax router with
zero-compute experts, the two-leaf-a-layer latent cache and the ragged
engine, each against the plain reference
(``benchmarks/lib/reference_longcat_flash.py``) at widths a CPU holds; and
that reference against the public implementation in ``transformers``.
Seeded weights, float32 and bfloat16."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models._decode import CacheLeaf
from paddle_tpu.models._mla import (mla_attend_dense, mla_in, mla_out,
                                    mla_softmax_scale)
from paddle_tpu.models.longcat_flash import (TICK_STATS, LongcatFlashConfig,
                                             LongcatFlashModel)
from paddle_tpu.ops import moe
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
from paddle_tpu.telemetry import Tracer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import reference_longcat_flash as ref  # noqa: E402
from benchmarks.lib import weights_longcat  # noqa: E402

# the configuration file's keys at a small size: 16 real experts and 8
# zero-compute ones routed, top-3, of which this share holds 4 real ones
# (ids 4-7); 2 layers = 4 sublayers
CFG = dict(vocab_size=96, hidden_size=32, num_layers=2, num_attention_heads=4,
           ffn_hidden_size=48, q_lora_rank=16, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
           mla_scale_q_lora=True, mla_scale_kv_lora=True,
           expert_ffn_hidden_size=12, moe_topk=3, n_routed_experts=4,
           router_width=24, zero_expert_num=8, zero_expert_type="identity",
           experts_held=[4, 8], routed_scaling_factor=6.0, rms_norm_eps=1e-5,
           rope_theta=10000000, max_position_embeddings=128,
           initializer_range=0.2)
# float32: the summation order of the products.  bfloat16: weights,
# activations and the cached latents are rounded to 8 bits of mantissa, and
# four sublayers deep a logit of standard deviation ~1.1 moves by up to
# ~0.1; the positions whose ROUTE the rounding can move (a reference margin
# under NEAR, several times the bfloat16 step of a score of ~0.1) are left
# out, as the benchmark's `correct` leaves them out (flips were seen at
# margins up to 1.8e-3 over three seeds, none above)
# every expert real and held: the uncut model, 8 real + 4 zero-compute
UNCUT = dict(CFG, n_routed_experts=8, router_width=12, zero_expert_num=4,
             experts_held=[0, 8])
TOL = {"float32": 3e-5, "bfloat16": 0.25}
NEAR = {"float32": 0.0, "bfloat16": 2.5e-3}


def program_config(cfg, dtype):
    own = ("router_width", "experts_held", "n_routed_experts")
    return LongcatFlashConfig(
        **{k: v for k, v in cfg.items() if k not in own},
        n_routed_experts=weights_longcat.real_experts(cfg),
        experts_held=range(*cfg["experts_held"]), compute_dtype=dtype)


def build(dtype, seed=7, cfg=CFG):
    paddle.seed(0)
    model = LongcatFlashModel(program_config(cfg, dtype))
    params = weights_longcat.make_params(cfg, seed, dtype)
    table = LongcatFlashModel.param_table(model.config)
    assert {n: v.shape for n, v in params.items()} \
        == {n: shape for n, (shape, _) in table.items()}
    return model, params


@pytest.fixture
def interpret(request):
    paddle.set_flags({"FLAGS_paged_attn_interpret": request.param})
    yield request.param
    paddle.set_flags({"FLAGS_paged_attn_interpret": False})


def engine(model, params, **kw):
    return RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=3, max_len=64, block_size=8, num_blocks=20,
        token_budget=24, prompt_buckets=list(range(8, 65, 8)), **kw)


def far(margin, dtype):
    keep = margin >= NEAR[dtype]
    assert float(keep.mean()) > 0.5         # the comparison keeps its power
    return keep


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    model, params = build(dtype)
    ids = np.random.default_rng(1).integers(1, 96, (2, 32))
    h, _ = model.prefill(params, jnp.asarray(ids), 32)
    got = model.decode_logits(params, h)
    for b in range(2):
        want, margin = ref.logits(CFG, params, jnp.asarray(ids[b]), block=16,
                                  head_group=2)
        gap = jnp.abs(got[b] - want).max(-1)
        assert float(jnp.where(far(margin, dtype), gap, 0).max()) < TOL[dtype]
        assert float(want.std()) > 0.5      # the comparison is not of zeros


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("interpret", [True, False], indirect=True,
                         ids=["kernel", "xla"])
def test_engine_prefill_then_decode_matches_the_reference(dtype, interpret):
    """Served tokens through the paged latent cache (chunked prefill, mixed
    ticks, left-padded buckets, decode rounds on the narrow program) against
    the reference's full forward over prompt + served tokens, on logits;
    and the tick counters on the event."""
    model, params = build(dtype)
    tracer = Tracer()
    eng = engine(model, params, tracer=tracer)
    assert eng.narrow_rows == 8             # 3 slots rounded up to 8 < 24 / 2
    ids = np.random.default_rng(2).integers(1, 96, 48)
    prompts = [ids[:29].tolist(), ids[5:18].tolist(), ids[3:40].tolist(),
               ids[:9].tolist()]
    served = {}
    rids = [eng.add_request(p, 6, on_token=lambda rid, t, d:
                            served.setdefault(rid, []).append(int(t)))
            for p in prompts]
    eng.run_to_completion()
    for rid, p in zip(rids, prompts):
        full = p + served[rid][:-1]
        L = -(-len(full) // 16) * 16
        logits, margin = ref.logits(CFG, params, jnp.asarray(
            full + [0] * (L - len(full))), block=16, head_group=2)
        rows = logits[len(p) - 1:len(full)]
        gap = rows.max(-1) - jnp.take_along_axis(
            rows, jnp.asarray(served[rid])[:, None], -1)[:, 0]
        keep = margin[len(p) - 1:len(full)] >= NEAR[dtype]
        assert float(jnp.where(keep, gap, 0).max()) < TOL[dtype], (rid, gap)
    ticks = [e for e in tracer.events("tick") if e.get("budget_used")]
    assert ticks and all(set(TICK_STATS) <= set(e) for e in ticks)
    assert eng.narrow_steps > 0 and eng.narrow_steps < len(ticks)
    for e in ticks:         # every pair is a real expert's or a zero one's
        assert e["expert_pairs"] + e["zero_pairs"] == e["budget_used"] * 3 * 2
        assert 0 <= e["expert_rows_max"] <= e["expert_rows"] \
            <= e["expert_pairs"]
    assert sum(e["expert_rows"] for e in ticks) > 0
    assert sum(e["zero_pairs"] for e in ticks) > 0
    # what is static is said once, when the engine is built: ONE leaf of
    # 2 x layers rows
    (cache,) = tracer.events("cache")
    assert cache["layout"] == "latent" and cache["pool_bytes"] == \
        4 * 21 * 8 * 128 * jnp.dtype(dtype).itemsize


def test_generate_agrees_with_the_engine():
    model, params = build("float32")
    prompt = np.random.default_rng(3).integers(1, 96, 19).tolist()
    eng = engine(model, params)
    rid = eng.add_request(prompt, 5)
    want = eng.run_to_completion()[rid]
    got = model.generate(params, jnp.asarray([prompt]), 5)[0].tolist()
    assert got == list(want)


def test_cache_spec_states_two_rows_of_one_leaf_a_layer():
    model, _ = build("bfloat16")
    spec = model.cache_spec()
    assert spec.layout == "latent" and spec.tick_stats == TICK_STATS
    assert spec.pools == (CacheLeaf(4, (128,), "bfloat16"),)
    assert model.ragged_narrow_rounds is True


def test_the_tick_has_no_branch_on_the_pack():
    """Decode-only rounds are a program of their own rows, chosen by the
    host: nothing in the tick is a ``cond`` (which would copy each layer's
    weights out of their stack, PERF.md section 6, PR 39)."""
    model, params = build("float32")
    eng = engine(model, params)
    for T in (24, eng.narrow_rows):
        text = eng._build_ragged_step(T, 8).lower(
            *eng._ragged_scratch_args(8, T)).as_text()
        assert "stablehlo.case" not in text and "stablehlo.if" not in text


def test_the_tick_names_the_scopes_the_cells_metrics_read():
    """``attn`` (both sublayers), ``mlp`` containing ``dense_ffn``,
    ``router``, ``experts`` and ``zero_experts``, all inside ``layers``;
    ``embed`` and ``head`` outside: what the ``.toolturns`` metrics'
    ``xplane_scope`` / ``xplane_region`` readers partition the step by."""
    import re
    model, params = build("float32")
    eng = engine(model, params)
    text = eng._build_ragged_step(24, 8).lower(
        *eng._ragged_scratch_args(8)).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("(?:jit\(run\)/)?([a-z_/]+)/[^/"]*"', text))
    assert {"mlp/dense_ffn", "mlp/router", "mlp/experts", "mlp/zero_experts",
            "attn", "attn/kv_write", "attn/ragged_latent_attention", "mlp",
            "embed", "head", "layers"} <= paths, sorted(paths)
    # the identity term is the zero experts' and nothing else's
    assert not any(p.startswith("attn") and "experts" in p for p in paths)


def test_only_identity_zero_experts_are_written():
    with pytest.raises(ValueError, match="identity"):
        LongcatFlashConfig(zero_expert_type="copy")
    with pytest.raises(ValueError, match="experts_held"):
        LongcatFlashConfig(n_routed_experts=8, zero_expert_num=4,
                           experts_held=range(6, 10))   # 8, 9 are zero ones
    assert LongcatFlashConfig().router_width == 768
    assert LongcatFlashConfig().q_scale == 2.0
    assert LongcatFlashConfig().kv_scale == pytest.approx(12 ** 0.5)


# ------------------------------------------------------------ the router --

def routed(rng, T=12, H=8, E=12, k=4, bias=None, scaling=6.0):
    gate = jnp.asarray(rng.normal(size=(H, E)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    return m, gate, moe.route_softmax_topk(m, gate, k, scaling, bias)


def test_router_softmax_over_every_output_unnormalised_times_scaling():
    rng = np.random.default_rng(4)
    m, gate, (idx, w) = routed(rng)
    p = np.asarray(jax.nn.softmax(np.asarray(m) @ np.asarray(gate), -1))
    assert p.sum(-1) == pytest.approx(1.0)      # over all 12, zero ones too
    want = np.argsort(-p, -1)[:, :4]
    assert np.asarray(idx).tolist() == want.tolist()
    assert np.asarray(w) == pytest.approx(
        6.0 * np.take_along_axis(p, want, -1), rel=1e-5)
    # not normalised over the chosen: the weights add to 6 x what the top
    # four hold of the softmax, not to the scaling
    assert float(w.sum(-1).max()) < 6.0 - 5e-3 and float(w.sum(-1).min()) < 5.0


def test_router_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.default_rng(4)
    m, gate, (idx0, w0) = routed(rng)
    rng = np.random.default_rng(4)
    bias = jnp.zeros(12).at[11].set(10.0).at[3].set(-10.0)
    _, _, (idx, w) = routed(rng, bias=bias)
    p = jax.nn.softmax(m @ gate, -1)
    assert bool(jnp.all(idx[:, 0] == 11)) and not bool(jnp.any(idx == 3))
    assert bool(jnp.any(idx0 == 3))             # it was chosen without one
    assert np.asarray(w) == pytest.approx(
        6.0 * np.asarray(jnp.take_along_axis(p, idx, -1)), rel=1e-5)
    assert float(w[:, 0].max()) < 6.0           # p, not p + 10
    _, _, (idx1, w1) = routed(np.random.default_rng(4), bias=jnp.zeros(12))
    assert idx1.tolist() == idx0.tolist() and w1.tolist() == w0.tolist()


# ---------------------------------------------------- zero-compute experts --

def expert_weights(rng, E, H=8, F=6):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return f(E, H, F), f(E, H, F), f(E, F, H)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four ranks of a deployment with 8 real and 4 zero-compute experts,
    each routing over all 12 outputs and computing its own 2 real experts:
    their partial sums, plus the zero experts' term and (in the layer) the
    dense path counted once, add up to the uncut reference's branch."""
    rng = np.random.default_rng(5)
    T, H, F, E, Z, k = 16, 8, 6, 8, 4, 5
    w_g, w_u, w_d = expert_weights(rng, E, H, F)
    m, gate, (idx, w) = routed(rng, T, H, E + Z, k)
    total, zero_pairs = moe.identity_experts(m, idx, w, E)
    rows = 0
    for first in range(0, E, 2):
        sl = slice(first, first + 2)
        part, n = moe.held_experts_ffn(m, idx, w, w_g[sl], w_u[sl], w_d[sl],
                                       first, n_real=E)
        total, rows = total + part, rows + int(n.sum())
    assert rows + int(zero_pairs) == T * k      # every pair, exactly once
    assert 0 < int(zero_pairs) < T * k
    cfg = dict(moe_topk=k, experts_held=[0, E], n_routed_experts=E,
               router_width=E + Z, zero_expert_num=Z,
               routed_scaling_factor=6.0)
    want, _ = ref._branch(cfg, dict(
        router_w=gate, router_bias=jnp.zeros(E + Z), e_gate_w=w_g,
        e_up_w=w_u, e_down_w=w_d), m, None)
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_the_shares_add_up_to_the_uncut_layer_through_the_whole_layer():
    """The same through a whole layer of the reference: what rank ``r``
    of four computes (attention, both dense paths, the zero experts' term,
    its two experts) and what the other three ranks' experts contribute —
    the program's partial sums on the branch's input — add up to the
    uncut layer; the dense path and the zero term are in every rank's
    output and are counted once."""
    cfg = dict(UNCUT, num_layers=1)
    params = weights_longcat.make_params(cfg, 13, "float32")
    sl = {n: params[f"layers_{n}"][0]
          for n in weights_longcat.SUBLAYER + weights_longcat.BRANCH}
    x = jnp.asarray(np.random.default_rng(12).normal(size=(16, 32)),
                    jnp.float32)
    whole, _ = ref._layer(cfg, None, 16, 2, x, sl)
    # the branch's input, as the layer makes it
    sub0 = {n: sl[n][0] for n in weights_longcat.SUBLAYER}
    m0 = ref._rms(x + ref._attention(cfg, sub0, x, None, 16, 2),
                  sub0["ln3_w"], cfg["rms_norm_eps"])
    idx, w = moe.route_softmax_topk(m0, sl["router_w"], cfg["moe_topk"],
                                    cfg["routed_scaling_factor"])
    part = lambda r: moe.held_experts_ffn(
        m0, idx, w, *(sl[n][2 * r:2 * r + 2]
                      for n in ("e_gate_w", "e_up_w", "e_down_w")),
        2 * r, n_real=8)[0]
    for r in range(4):
        held = slice(2 * r, 2 * r + 2)
        mine = dict(sl, **{n: sl[n][held]
                           for n in ("e_gate_w", "e_up_w", "e_down_w")})
        rank, _ = ref._layer(dict(cfg, n_routed_experts=2,
                                  experts_held=[2 * r, 2 * r + 2]),
                             None, 16, 2, x, mine)
        total = rank + sum(part(o) for o in range(4) if o != r)
        assert float(jnp.abs(total - whole).max()) < 1e-4 * float(
            jnp.abs(whole).max())
        assert float(jnp.abs(rank - whole).max()) > 1e-3    # a real share


@pytest.mark.parametrize("case", ["all-zero", "all-held", "padding-rows"])
def test_zero_experts_are_exact_under_any_routing(case):
    """A bias that forces every choice onto zero-compute experts gives
    ``s = (sum w) * m`` exactly and no real pair; one that forces every
    choice onto held experts fills ``min(k, Eh)`` buffer rows a token and
    drops none."""
    rng = np.random.default_rng(6)
    T, H, F, E, Z, k, first, Eh = 10, 8, 6, 8, 6, 4, 2, 4
    w_g, w_u, w_d = expert_weights(rng, Eh, H, F)
    bias = jnp.zeros(E + Z)
    if case == "all-zero":
        bias = bias.at[E:].set(10.0)
    else:
        bias = bias.at[first:first + Eh].set(10.0)
    m, gate, (idx, w) = routed(rng, T, H, E + Z, k, bias)
    valid = jnp.arange(T) < 4 if case == "padding-rows" else None
    part, rows = moe.held_experts_ffn(m, idx, w, w_g, w_u, w_d, first, valid,
                                      n_real=E)
    ident, zero_pairs = moe.identity_experts(m, idx, w, E, valid)
    real = T if valid is None else 4
    if case == "all-zero":
        assert bool(jnp.all(idx >= E)) and int(rows.sum()) == 0
        assert int(zero_pairs) == T * k
        assert bool(jnp.all(part == 0))
        assert np.asarray(ident) == pytest.approx(
            np.asarray(w.sum(-1, keepdims=True) * m), rel=1e-6)
        return
    assert int(zero_pairs) == 0 and bool(jnp.all(ident == 0))
    assert rows.tolist() == [real] * Eh         # min(k, Eh) = 4 rows a token
    want = np.zeros((T, H), np.float32)
    for t in range(real):
        for j in range(k):
            e = int(idx[t, j]) - first
            y = (jax.nn.silu(m[t] @ w_g[e]) * (m[t] @ w_u[e])) @ w_d[e]
            want[t] += float(w[t, j]) * np.asarray(y)
    assert float(np.abs(np.asarray(part) - want).max()) < 1e-4 * max(
        1.0, float(np.abs(want).max()))


def test_a_layer_of_the_expert_stacks_is_read_in_place():
    """``layer=``: the grouped products over a whole stack's experts with
    every other layer's group empty give what the layer's own slice gives,
    to the bit, under ``jit`` with a traced layer."""
    rng = np.random.default_rng(10)
    L, Eh = 3, 4
    stacks = [jnp.stack(ws) for ws in zip(*(expert_weights(rng, Eh)
                                            for _ in range(L)))]
    m, _, (idx, w) = routed(rng, T=10, E=12, k=4)
    valid = jnp.arange(10) < 7
    fn = jax.jit(lambda ly: moe.held_experts_ffn(
        m, idx, w, *stacks, 2, valid, n_real=8, layer=ly))
    for ly in range(L):
        want, rows = moe.held_experts_ffn(
            m, idx, w, *(a[ly] for a in stacks), 2, valid, n_real=8)
        got, got_rows = fn(jnp.int32(ly))
        assert got_rows.tolist() == rows.tolist() and int(rows.sum()) > 0
        assert bool(jnp.all(got == want))


def test_held_experts_must_be_real_ones():
    rng = np.random.default_rng(7)
    w_g, w_u, w_d = expert_weights(rng, 4)
    m, _, (idx, w) = routed(rng)
    with pytest.raises(ValueError, match="real experts"):
        moe.held_experts_ffn(m, idx, w, w_g, w_u, w_d, 6, n_real=8)


# ------------------------------------------------- the two scale factors --

@pytest.mark.parametrize("q_lora,kv_lora", [(True, True), (True, False),
                                            (False, True), (False, False)])
def test_absorbed_attention_over_the_scaled_latent_is_the_expanded_one(
        q_lora, kv_lora):
    """One sublayer: the absorbed form over the cached row ``[N(c) * sqrt(H
    / kv_lora_rank) ; rope(k_r)]`` equals the reference's expanded
    attention; the query carries ``sqrt(H / q_lora_rank)`` on both its
    parts, and ``k_r`` no factor at all."""
    cfg = dict(CFG, mla_scale_q_lora=q_lora, mla_scale_kv_lora=kv_lora)
    model, params = build("float32", cfg=cfg)
    c = model.config
    assert c.q_scale == (2 ** 0.5 if q_lora else 1.0)
    assert c.kv_scale == (2 ** 0.5 if kv_lora else 1.0)
    sub = {n: params[f"layers_{n}"][1, 0] for n in weights_longcat.SUBLAYER}
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 16, 32)),
                    jnp.float32)
    pos = jnp.arange(16)[None]
    q_nope, q_r, latent, a, _ = mla_in(c, sub, x, pos, c.q_scale, c.kv_scale,
                                       c.lora_norm_eps)
    pads = jnp.zeros(1, jnp.int32)
    got = mla_out(c, sub, x, mla_attend_dense(
        c, sub, x, latent, q_nope, q_r, 0, pads)) - x
    want = ref._attention(cfg, sub, x[0], None, 16, 2)
    assert float(jnp.abs(got[0] - want).max()) < 2e-5 * max(
        1.0, float(jnp.abs(want).max()))
    assert mla_softmax_scale(c) == 12 ** -0.5
    # what the cache holds: the scaled normed latent, the unscaled rotary key
    kv = a @ sub["kv_a_w"]
    normed = ref._rms(kv[..., :16], sub["kv_a_norm_w"], c.lora_norm_eps)
    assert np.asarray(latent[..., :16]) == pytest.approx(
        np.asarray(normed * c.kv_scale), abs=1e-5)
    assert np.asarray(latent[0, :, 16:20]) == pytest.approx(
        np.asarray(ref._rope(kv[0, :, 16:], pos[0], c.rope_theta)), abs=1e-5)
    assert bool(jnp.all(latent[..., 20:] == 0)) and latent.shape[-1] == 128


# --------------------------- the reference against the public implementation --

def interleaved(w, d):
    """The last ``d`` columns of w, laid out for a rotary that pairs
    neighbours, from the rotate-half layout: column i of the first half
    goes to 2 i, of the second half to 2 i + 1."""
    w = np.array(w)
    rot = w[..., -d:]
    out = np.empty_like(rot)
    out[..., 0::2], out[..., 1::2] = rot[..., :d // 2], rot[..., d // 2:]
    w[..., -d:] = out
    return w


def test_the_reference_is_the_public_implementation():
    """``LongcatFlashForCausalLM`` of the installed ``transformers`` at a
    small uncut configuration with the same seeded weights copied in: the
    one place the file's interleaved rotary layout and the reference's
    rotate-half are mapped onto each other (a permutation of the rotary
    columns of W_qb, per head, and of W_kva)."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.longcat_flash")
    cfg = UNCUT
    params = weights_longcat.make_params(cfg, 11, "float32")
    # a selection bias that changes some choices, as a checkpoint's would
    params["layers_router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(5), params["layers_router_bias"].shape)
    hcfg = hf.LongcatFlashConfig(
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_layers", "num_attention_heads",
            "ffn_hidden_size", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "expert_ffn_hidden_size", "moe_topk", "zero_expert_num",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta",
            "max_position_embeddings")},
        n_routed_experts=8, head_dim=cfg["qk_rope_head_dim"],
        num_hidden_layers=2 * cfg["num_layers"], attn_implementation="eager")
    model = hf.LongcatFlashForCausalLM(hcfg).eval()
    nh, nope, rope = 4, cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    p = lambda name, *at: np.asarray(params[f"layers_{name}"][at])
    with torch.no_grad():
        put = lambda dst, a: dst.copy_(t(a))
        put(model.model.embed_tokens.weight, params["wte"])
        put(model.model.norm.weight, params["norm_f_w"])
        put(model.lm_head.weight, np.asarray(params["lm_head"]).T)
        for l, layer in enumerate(model.model.layers):
            for j in (0, 1):
                att = layer.self_attn[j]
                put(layer.input_layernorm[j].weight, p("ln1_w", l, j))
                put(layer.post_attention_layernorm[j].weight,
                    p("ln3_w", l, j))
                put(att.q_a_proj.weight, p("q_a_w", l, j).T)
                put(att.q_a_layernorm.weight, p("q_a_norm_w", l, j))
                q_b = p("q_b_w", l, j).reshape(-1, nh, nope + rope)
                put(att.q_b_proj.weight,
                    interleaved(q_b, rope).reshape(q_b.shape[0], -1).T)
                put(att.kv_a_proj_with_mqa.weight,
                    interleaved(p("kv_a_w", l, j), rope).T)
                put(att.kv_a_layernorm.weight, p("kv_a_norm_w", l, j))
                put(att.kv_b_proj.weight, p("kv_b_w", l, j).T)
                put(att.o_proj.weight, p("o_w", l, j).T)
                for mine, theirs in (("gate_w", "gate_proj"),
                                     ("up_w", "up_proj"),
                                     ("down_w", "down_proj")):
                    put(getattr(layer.mlps[j], theirs).weight,
                        p(mine, l, j).T)
            put(layer.mlp.router.classifier.weight, p("router_w", l).T)
            put(layer.mlp.router.e_score_correction_bias,
                p("router_bias", l))
            for e in range(8):
                for mine, theirs in (("e_gate_w", "gate_proj"),
                                     ("e_up_w", "up_proj"),
                                     ("e_down_w", "down_proj")):
                    put(getattr(layer.mlp.experts[e], theirs).weight,
                        p(mine, l, e).T)
        ids = np.random.default_rng(9).integers(1, 96, 32)
        want = model(torch.tensor(ids[None])).logits[0].numpy()
    got, margin = ref.logits(cfg, params, jnp.asarray(ids), block=16,
                             head_group=2)
    assert float(want.std()) > 0.5
    # float32 against float32: the order of the sums, and torch's own
    # matrix products
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-4
    # the margin is of this configuration: every output is "mine" here
    assert float(margin.min()) >= 0 and bool(jnp.all(jnp.isfinite(margin)))
