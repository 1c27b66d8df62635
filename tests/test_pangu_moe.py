"""openPangu-Ultra-MoE through the program: the model, the latent kernel,
the held-experts layer and the engine's cache spec, each against the plain
reference (``benchmarks/lib/reference_pangu_moe.py``) at widths a CPU
holds.  Seeded weights, float32 and bfloat16."""

import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models._decode import CacheLeaf
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.models.pangu_moe import (TICK_STATS, PanguMoeConfig,
                                         PanguMoeModel)
from paddle_tpu.ops import moe
from paddle_tpu.ops.ragged_latent_attention import (
    ragged_latent_attention_ref, ragged_latent_attention_rows)
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
from paddle_tpu.telemetry import Tracer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import reference_pangu_moe as ref  # noqa: E402
from benchmarks.lib import weights_pangu  # noqa: E402

# the configuration file's keys at a small size: 16 experts routed, top-3,
# of which this share holds 4 (ids 4-7); 1 dense + 2 expert layers
CFG = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=16,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, intermediate_size=48, moe_intermediate_size=12,
           n_routed_experts=4, router_width=16, experts_held=[4, 8],
           n_shared_experts=1, num_experts_per_tok=3,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-5,
           rope_theta=25600000, max_position_embeddings=128,
           initializer_range=0.2)
TOL = {"float32": 2e-5, "bfloat16": 0.25}


def build(dtype, seed=7, cfg=CFG):
    paddle.seed(0)
    first, stop = cfg["experts_held"]
    skip = ("router_width", "experts_held", "n_routed_experts")
    model = PanguMoeModel(PanguMoeConfig(
        **{k: v for k, v in cfg.items() if k not in skip},
        n_routed_experts=cfg["router_width"],
        experts_held=range(first, stop), compute_dtype=dtype))
    params = weights_pangu.make_params(cfg, seed, dtype)
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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    model, params = build(dtype)
    ids = np.random.default_rng(1).integers(1, 96, (2, 32))
    h, _ = model.prefill(params, jnp.asarray(ids), 32)
    got = model.decode_logits(params, h)
    for b in range(2):
        want, _ = ref.logits(CFG, params, jnp.asarray(ids[b]), block=16,
                             head_group=2)
        assert float(jnp.abs(got[b] - want).max()) < TOL[dtype]
        assert float(want.std()) > 0.5      # the comparison is not of zeros


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("interpret", [True, False], indirect=True,
                         ids=["kernel", "xla"])
def test_engine_prefill_then_decode_matches_the_reference(dtype, interpret):
    """Served tokens through the paged latent cache (chunked prefill,
    mixed ticks, left-padded buckets) against the reference's full forward
    over prompt + served tokens; and the tick counters on the event."""
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
        logits, _ = ref.logits(CFG, params, jnp.asarray(
            full + [0] * (L - len(full))), block=16, head_group=2)
        rows = logits[len(p) - 1:len(full)]
        gap = rows.max(-1) - jnp.take_along_axis(
            rows, jnp.asarray(served[rid])[:, None], -1)[:, 0]
        assert float(gap.max()) < TOL[dtype], (rid, gap)
    ticks = [e for e in tracer.events("tick") if e.get("budget_used")]
    assert ticks and all(set(TICK_STATS) <= set(e) for e in ticks)
    layers = model.config.num_expert_layers
    for e in ticks:
        assert e["expert_pairs"] == e["budget_used"] * 3 * layers
        assert 0 <= e["expert_rows_max"] <= e["expert_rows"] \
            <= e["expert_pairs"]
    assert sum(e["expert_rows"] for e in ticks) > 0
    # what is static is said once, when the engine is built
    (cache,) = tracer.events("cache")
    assert cache["layout"] == "latent" and cache["pool_bytes"] == \
        3 * 21 * 8 * 128 * jnp.dtype(dtype).itemsize


def test_generate_agrees_with_the_engine():
    model, params = build("float32")
    prompt = np.random.default_rng(3).integers(1, 96, 19).tolist()
    eng = engine(model, params)
    rid = eng.add_request(prompt, 5)
    want = eng.run_to_completion()[rid]
    got = model.generate(params, jnp.asarray([prompt]), 5)[0].tolist()
    assert got == list(want)


@pytest.mark.parametrize("interpret", [False, True], indirect=True,
                         ids=["xla", "kernel"])
def test_narrow_rounds_give_the_wide_programs_tokens(interpret):
    """Rounds of decode rows only run the tick at 8 rows (3 slots, a
    budget of 24), rounds with a chunk — some of them beside decode rows —
    the budget-wide one: greedy tokens are those of the budget-wide
    program alone, which is what every round ran while this model's tick
    held a branch on the pack instead (``_decode.rowwise``, until
    PR 46)."""
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
    assert eng.narrow_rows == 8 and PanguMoeModel.ragged_narrow_rounds
    wide.narrow_rows = 0
    assert serve(eng) == serve(wide)
    assert 0 < eng.narrow_steps < eng.ragged_steps and eng.mixed_steps
    assert wide.narrow_steps == 0 and wide.ragged_steps == eng.ragged_steps
    for k in tr.events("tick"):
        if k.get("budget_used"):
            assert k["rows_run"] == (24 if k["prefill_tokens"] else 8)


def pack(rng, dtype, nh=4, R=32, Dr=8, NB=20, bs=4, S=3, C=8, W=None):
    """A mixed pack: a prefill chunk of 10 rows, one decode row deep in
    its sequence, a 9-row chunk from position 0 behind a left pad, and 4
    padding rows; the trash block holds garbage that must not be read."""
    T = 24
    qa = jnp.asarray(rng.normal(size=(T, nh, R)), dtype)
    qr = jnp.asarray(rng.normal(size=(T, nh, Dr)), dtype)
    pool = jnp.asarray(rng.normal(size=(NB + 1, bs, W or R + Dr)), dtype)
    pool = pool.at[0].set(1e4)
    table = jnp.asarray(rng.integers(1, NB + 1, (S, C)), jnp.int32)
    row_seq = jnp.asarray([0] * 10 + [1] + [2] * 9 + [0] * 4, jnp.int32)
    row_pos = jnp.asarray(list(range(5, 15)) + [30] + list(range(3, 12))
                          + [-1] * 4, jnp.int32)
    pads = jnp.asarray([2, 0, 3], jnp.int32)
    return qa, qr, pool, table, row_seq, row_pos, pads


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("blocks_per_step", [1, 2, 4, 16])
@pytest.mark.parametrize("padded", [False, True], ids=["exact", "padded-row"])
def test_latent_kernel_matches_its_reference(dtype, tol, blocks_per_step,
                                             padded):
    rng = np.random.default_rng(0)
    args = pack(rng, jnp.dtype(dtype), W=64 if padded else None)
    got = ragged_latent_attention_rows(
        *args, scale=0.3, interpret=True, blocks_per_step=blocks_per_step)
    want = ragged_latent_attention_ref(*args, scale=0.3)
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < tol
    assert bool(jnp.all(got[-4:] == 0)) and bool(jnp.all(want[-4:] == 0))
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))


def test_latent_kernel_reads_a_layer_of_a_stack_in_place():
    rng = np.random.default_rng(1)
    qa, qr, pool, *rest = pack(rng, jnp.float32)
    stack = jnp.stack([pool * 0 + 7.0, pool, pool * 0 - 3.0])
    want = ragged_latent_attention_ref(qa, qr, pool, *rest, scale=0.3)
    for fn, kw in ((ragged_latent_attention_rows, {"interpret": True}),
                   (ragged_latent_attention_ref, {})):
        got = jax.jit(lambda ly: fn(qa, qr, stack, *rest, scale=0.3,
                                    layer=ly, **kw))(jnp.int32(1))
        assert float(jnp.abs(got - want).max()) < 2e-5


def expert_weights(rng, E=16, H=8, F=6):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return f(H, E), f(E, H, F), f(E, H, F), f(E, F, H)


@pytest.mark.parametrize("held", [1, 2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The 16 / held shares of one expert layer — each routing over all
    16 experts and computing its own — and the shared expert counted once
    add up to the uncut layer, which the reference computes with every
    expert held."""
    rng = np.random.default_rng(4)
    T, H, F, E, k = 12, 8, 6, 16, 4
    gate, w_g, w_u, w_d = expert_weights(rng, E, H, F)
    s_g, s_u = (jnp.asarray(rng.normal(size=(H, F)), jnp.float32)
                for _ in range(2))
    s_d = jnp.asarray(rng.normal(size=(F, H)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    idx, w = moe.route_sigmoid_topk(m, gate, k, 2.5)
    total = moe.gated_mlp(m, s_g, s_u, s_d)
    rows = 0
    for first in range(0, E, held):
        sl = slice(first, first + held)
        part, n = moe.held_experts_ffn(m, idx, w, w_g[sl], w_u[sl], w_d[sl],
                                       first)
        total, rows = total + part, rows + int(n.sum())
    assert rows == T * k                       # every pair, exactly once
    cfg = dict(num_experts_per_tok=k, experts_held=[0, E],
               n_routed_experts=E, routed_scaling_factor=2.5)
    want, _ = ref._experts(cfg, dict(
        router_w=gate, e_gate_w=w_g, e_up_w=w_u, e_down_w=w_d, s_gate_w=s_g,
        s_up_w=s_u, s_down_w=s_d), m, None)
    assert float(jnp.abs(total - want).max()) < 1e-3 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("case", ["all-to-one-held", "none-held",
                                  "all-held-experts-full", "padding-rows"])
def test_no_pair_is_dropped_under_the_worst_imbalance(case):
    rng = np.random.default_rng(5)
    T, H, F, k, Eh, first = 10, 8, 6, 4, 3, 5
    _, w_g, w_u, w_d = expert_weights(rng, Eh, H, F)
    m = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    valid = None
    if case == "all-to-one-held":       # every row: expert 6 and 3 unheld
        idx = np.tile([6, 0, 1, 2], (T, 1))
    elif case == "none-held":
        idx = np.tile([0, 1, 2, 3], (T, 1))
    elif case == "all-held-experts-full":   # every row: all 3 held + 1
        idx = np.tile([5, 6, 7, 9], (T, 1))
    else:
        idx = np.tile([6, 5, 1, 2], (T, 1))
        valid = jnp.arange(T) < 4
    idx = jnp.asarray(idx, jnp.int32)
    got, rows = moe.held_experts_ffn(m, idx, w, w_g, w_u, w_d, first, valid)
    want = np.zeros((T, H), np.float32)
    count = np.zeros(Eh, np.int32)
    for t in range(T if valid is None else 4):
        for j in range(k):
            e = int(idx[t, j]) - first
            if 0 <= e < Eh:
                y = (jax.nn.silu(m[t] @ w_g[e]) * (m[t] @ w_u[e])) @ w_d[e]
                want[t] += float(w[t, j]) * np.asarray(y)
                count[e] += 1
    assert rows.tolist() == count.tolist()
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4 * max(
        1.0, float(np.abs(want).max()))


def test_cache_spec_states_what_each_model_caches():
    model, _ = build("bfloat16")
    spec = model.cache_spec()
    assert spec.layout == "latent" and spec.tick_stats == TICK_STATS
    assert spec.pools == (CacheLeaf(1, (128,), "bfloat16"),
                          CacheLeaf(2, (128,), "bfloat16"))
    gpt = GPTModel(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                             num_attention_heads=4,
                             max_position_embeddings=96))
    kv = gpt.cache_spec()
    assert kv.layout == "kv" and kv.tick_stats == ()
    assert kv.pools == (CacheLeaf(2, (4, 8), "bfloat16"),) * 2


@pytest.mark.parametrize("how", ["attach", "constructor", "page_meta"])
def test_the_kv_store_refuses_a_latent_cache(how):
    from paddle_tpu.kv_store import TieredKVStore
    model, params = build("float32")
    with pytest.raises(NotImplementedError, match="K/V cache layout"):
        if how == "constructor":
            engine(model, params, enable_prefix_cache=True,
                   kv_store=TieredKVStore())
        eng = engine(model, params, enable_prefix_cache=True)
        if how == "attach":
            eng.attach_kv_store(TieredKVStore())
        eng.kv_page_meta()


def test_the_bucketed_paged_engine_refuses_a_latent_cache():
    """Its prefill and decode programs read a K and a V pool: refused when
    the engine is built, by name, not inside the first prefill."""
    from paddle_tpu.serving_paged import PagedContinuousBatchingEngine
    model, params = build("float32")
    with pytest.raises(NotImplementedError,
                       match="PagedContinuousBatchingEngine is written for "
                             "the K/V cache layout; PanguMoeModel"):
        PagedContinuousBatchingEngine(model, params, max_slots=3, max_len=64,
                                      block_size=8, num_blocks=20)


def test_prefix_lookup_works_on_a_latent_cache():
    """Keyed on token ids, not on leaves: a second request with the same
    prompt reuses the first one's blocks and serves the same tokens."""
    model, params = build("float32")
    eng = engine(model, params, enable_prefix_cache=True)
    prompt = np.random.default_rng(6).integers(1, 96, 24).tolist()
    a = eng.add_request(prompt, 4)
    first = eng.run_to_completion()[a]
    b = eng.add_request(prompt, 4)
    assert eng.run_to_completion()[b] == first and eng.prefix_hits >= 1


# sha256 of the Pangu tick's lowering under this suite's conftest, by
# (dtype, kernels interpreted, table width).  Taken on the tree before
# PR 36 (commit 3a775a4), re-taken in PR 43 (the tick's one packed operand,
# the stream's key, tokens and counters as one vector) and in PR 46, which
# moved what the text was pinned to keep: the two ``stablehlo.case`` of
# ``_decode.rowwise`` a stack are gone with the function (a round of
# decode rows only runs the narrow program instead), and the expert
# stacks are no ``xs`` of the layer scan — the three grouped products take
# the whole stack with every other layer's group empty
# (``held_experts_ffn(layer=)``); and in the four texts that hold the
# interpreted kernel, its walk asks for a group's deepest block once
# (``ops/ragged_latent_attention.py``: the same column, a third of a tick
# program's lowering time less).  What the DeepSeek-V3.2-Exp keys add is
# still reached only through keys this configuration lacks
PARENT_TICK = {
    ("float32", False, 4):
        "8ff10516c3a83b89afb9bcfc333a89f1c64c085593530eb9c625cbe9098a2364",
    ("float32", False, 8):
        "0573dd1e468da5157aab2f4c0f0486d7a723a9a69ae4e54481cc6bdc3a3cfd44",
    ("float32", True, 4):
        "23df76810a83defc6f429a2c84d417444cd2996304b5fd67e4c51aa5f9e96755",
    ("float32", True, 8):
        "d72cd5547700c16ba55b848ae9c18b4683c5e9ff3752b8753409e0c4c55d60ca",
    ("bfloat16", False, 4):
        "aef22a075755192a68fa60037065cf82aa968f397d98a00f6d3ceb809512fd63",
    ("bfloat16", False, 8):
        "587e448f66037d6e4c58f666dfd87ff525b18f86758ffaa9a70e25f289996181",
    ("bfloat16", True, 4):
        "c0f6fe81873dda812a7c7bac9a7bee91995eb470dcbae91fd4d27ae7bde22eb1",
    ("bfloat16", True, 8):
        "5b9f67e16986f0084e27f3a1f121de002672b3a240cbf298f960d98f6986aea6",
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("interpret", [True, False], indirect=True,
                         ids=["kernel", "xla"])
@pytest.mark.parametrize("cols", [4, 8])
def test_the_pangu_tick_lowers_as_at_the_parent(dtype, interpret, cols):
    """What DeepSeek-V3.2-Exp added to the model class, the router and the
    latent kernel is reached only through keys the Pangu configuration
    does not have: its tick lowers to the pinned program to the byte (PR
    46's: no branch on the pack, no expert stack sliced by the scan)."""
    import hashlib
    model, params = build(dtype)
    eng = engine(model, params)
    text = eng._build_ragged_step(16, cols).lower(
        *eng._ragged_scratch_args(cols)).as_text()
    if not interpret:       # an interpreted kernel is loops and branches
        assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_TICK[dtype, interpret, cols]


def _mlir_type(shape, dtype):
    name = {"float32": "f32", "int8": "i8"}[str(dtype)]
    return "tensor<" + "x".join(map(str, shape)) + "x" + name + ">"


@pytest.mark.parametrize("case", ["float", "int8", "kernel", "spec",
                                  "per-request"])
def test_the_gpt_tick_holds_its_pools_once(case):
    """The "kv" layout's tick as it is lowered, five engines: every pool
    leaf is donated into an output, the layer ``while`` has each leaf in
    its carry once (as ``xs``/``ys`` of the scan a leaf is there twice,
    the stack read and the stack written), and no layer's pool is sliced
    out of a leaf or written back into one.

    What may remain: the gather fallback reads a layer by indexing the
    stack (``ragged_attention_ref``), once a leaf; the Pallas interpreter
    moves single blocks.  The draft of the spec engine proposes through
    ``decode_step`` over ``PagedKV``, which keeps its own scan: it has one
    layer, so its leaves are told from the target's by their type."""
    paddle.seed(3)
    mk = lambda layers: GPTModel(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=layers,
        num_attention_heads=4, max_position_embeddings=96,
        compute_dtype="float32",
        kv_cache_dtype="int8" if case == "int8" else None))
    params_of = lambda m: {n: p._data for n, p in m.named_parameters()}
    model = mk(2)
    kw = {}
    if case == "spec":
        draft = mk(1)
        kw = dict(draft_model=draft, draft_params=params_of(draft), draft_k=2)
    if case == "per-request":
        kw = dict(per_request_sampling=True)
    paddle.set_flags({"FLAGS_paged_attn_interpret": case == "kernel"})
    try:
        eng = RaggedPagedContinuousBatchingEngine(
            model, params_of(model), max_slots=3, max_len=64, block_size=8,
            num_blocks=12, token_budget=16, **kw)
        if case == "spec":
            text = eng._build_ragged_spec_step(16, 4).lower(
                *eng._ragged_spec_scratch_args(4)).as_text()
        else:
            text = eng._build_ragged_step(16, 4).lower(
                *eng._ragged_scratch_args(4)).as_text()
    finally:
        paddle.set_flags({"FLAGS_paged_attn_interpret": False})
    leaves = jax.tree.leaves(eng.caches)
    want = collections.Counter(_mlir_type(x.shape, x.dtype) for x in leaves)
    one_layer = {_mlir_type((1,) + x.shape[1:], x.dtype) for x in leaves}
    assert sum(want.values()) == (4 if case == "int8" else 2)
    types = lambda s: re.findall(r"tensor<[^>]*>", s)

    main = next(line for line in text.splitlines()
                if "func.func public @main(" in line)
    args = re.findall(r"%arg\d+: (tensor<[^>]*>)( \{[^}]*\})?",
                      main.split(") -> (")[0])
    donated = [attr for t, attr in args if t in want]
    assert len(donated) == sum(want.values())
    assert all("tf.aliasing_output" in attr for attr in donated)

    carries = [collections.Counter(t for t in types(
                   line.rsplit(") : ", 1)[1]) if t in want)
               for line in text.splitlines() if "stablehlo.while(" in line]
    carries = [c for c in carries if c]
    assert carries and all(c == want for c in carries), carries

    def moved(op):      # (the leaf, the piece sliced out or written in)
        found = []
        for line in text.splitlines():
            if f"stablehlo.{op} " in line:
                operands, result = line.rsplit(" : (", 1)[1].split(") -> ")
                piece = types(operands)[1] if op == "dynamic_update_slice" \
                    else result.strip()
                if types(operands)[0] in want and piece in one_layer:
                    found.append(line.strip())
        return found

    assert not moved("dynamic_update_slice")
    assert len(moved("dynamic_slice")) == (
        0 if case == "kernel" else sum(want.values()))
