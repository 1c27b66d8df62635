"""EvaByte through the ragged engine, small and seeded on the CPU: window
32, chunk 4, 2-3 layers, 4 heads of 16 (docs/CACHE_SPEC.md: a leaf that
does not page beside one that pages by chunk).

Tolerances.  The model and the plain reference are float32 here and differ
only in the order of their sums (one softmax over a pack's staged keys
against one over a whole window and every summary): logits agree to 2e-5,
the greedy tokens are the reference's own, and a served token's logit lies
within 1e-5 of the reference's best.  The interpreted kernels multiply at
``Precision.HIGHEST`` in float32 and keep the same bound.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from benchmarks.lib import reference_evabyte as ref
from benchmarks.lib import serve_eva, weights_evabyte
from paddle_tpu.models._decode import (CacheLeaf, CacheSpec, build_pools,
                                       tokens_per_row)
from paddle_tpu.models.evabyte import EvaByteConfig, EvaByteModel
from paddle_tpu.serving import (PagedContinuousBatchingEngine,
                                RaggedPagedContinuousBatchingEngine)
from paddle_tpu.telemetry import Tracer

W, CHUNK = 32, 4
CFG = dict(vocab_size=320, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, intermediate_size=96, num_pred_heads=8,
           chunk_size=CHUNK, window_size=W, max_position_embeddings=256,
           rope_theta=100000.0, rms_norm_eps=1e-5, norm_add_unit_offset=True,
           init_std=0.05, compute_dtype="float32")
ENGINE = dict(max_slots=3, max_len=256, block_size=4, num_blocks=48,
              token_budget=W + 3)


@pytest.fixture(scope="module")
def params():
    return weights_evabyte.make_params(CFG, 5, "float32")


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    # 3+ windows; a prompt that ends 6 rows before a window's end, so that
    # decoding crosses it; unequal lengths, left pads of 3, 2 and 0
    return [rng.integers(1, 320, n).tolist() for n in (101, 58, 48)]


@pytest.fixture()
def interpret():
    paddle_tpu.set_flags({"FLAGS_paged_attn_interpret": True})
    yield
    paddle_tpu.set_flags({"FLAGS_paged_attn_interpret": False})


def engine(params, tracer=None, **over):
    return serve_eva.build_engine(CFG, dict(ENGINE, **over), params, tracer)


def serve(eng, prompts, out_len=12):
    got = {}

    def on_token(rid, tok, done):
        if tok is None:                   # preempted: the stream restarts
            got[rid] = []
        else:
            got.setdefault(rid, []).append(tok)
    rids = [eng.add_request(p, out_len, on_token=on_token) for p in prompts]
    eng.run_to_completion()
    return [got[r] for r in rids]


def reference_logits(params, ids):
    L = -(-len(ids) // W) * W
    h = ref.hidden(CFG, params, jnp.asarray(ids + [0] * (L - len(ids)),
                                            jnp.int32))
    return np.asarray(ref.logits(CFG, params, h))[:len(ids)]


def check_served(params, prompts, served):
    for p, toks in zip(prompts, served):
        logits = reference_logits(params, p + toks[:-1])[len(p) - 1:]
        assert logits.argmax(-1).tolist() == toks
        assert (logits.max(-1)
                - logits[np.arange(len(toks)), toks]).max() <= 1e-5


def test_engine_tokens_are_the_references(params, prompts):
    """Chunked prefill over 3+ windows, decoding across a window's end,
    three slots of unequal length in one pack."""
    tr = Tracer()
    served = serve(engine(params, tr), prompts)
    check_served(params, prompts, served)
    ticks = tr.events("tick")
    assert any(k["decode_rows"] and k["prefill_tokens"] for k in ticks)
    assert 58 + 12 > 2 * W > 58          # decoding crossed a window's end
    # every chunk closed once: floor(positions / chunk) a sequence
    assert sum(k["eva_chunks_closed"] for k in ticks) == sum(
        (len(p) + 12 - 1) // CHUNK for p in prompts)
    (cache,) = tr.events("cache")
    assert cache["layout"] == "eva"
    assert cache["leaf_rows"] == [f"slot/{W}"] * 2 + [f"table/{CHUNK}"] * 2
    L, S, nh, hd = 2, 3, 4, 16
    assert cache["leaf_bytes"] == [L * S * W * nh * hd * 4] * 2 \
        + [L * 49 * 4 * nh * hd * 4] * 2


def test_engine_through_the_interpreted_kernels(params, prompts, interpret):
    served = serve(engine(params), prompts[:2], out_len=8)
    check_served(params, prompts[:2], served)


def test_forward_is_the_references_full_pass(params):
    model = serve_eva.meta_model(CFG)
    for n, p in model.named_parameters():
        p._data = params[n]
    ids = np.random.default_rng(3).integers(1, 320, (2, 3 * W)).tolist()
    got = np.asarray(model.forward(jnp.asarray(ids, jnp.int32)))
    for row, mine in zip(ids, got):
        np.testing.assert_allclose(mine, reference_logits(params, row),
                                   atol=2e-5)


def test_reference_is_the_literal_sets(params):
    """The reference's attention against a per-position loop over E(p)
    and R(p), written from the issue's equations and nothing else."""
    L, nh, hd = 3 * W, 4, 16
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((L, nh, hd)).astype(np.float32)
               for _ in range(3))
    phi, mu = (rng.standard_normal((nh, hd)).astype(np.float32)
               for _ in range(2))
    s = hd ** -0.5
    ks = np.zeros((L // CHUNK, nh, hd), np.float32)
    vs = np.zeros_like(ks)
    for c in range(L // CHUNK):
        for h in range(nh):
            kj = k[c * CHUNK:(c + 1) * CHUNK, h]
            a = np.exp(s * kj @ phi[h])
            a /= a.sum()
            ks[c, h] = a @ kj + mu[h]
            vs[c, h] = a @ v[c * CHUNK:(c + 1) * CHUNK, h]
    got_ks, got_vs = ref.chunk_summaries(CFG, jnp.asarray(k), jnp.asarray(v),
                                         phi, mu)
    np.testing.assert_allclose(got_ks, ks, atol=1e-5)
    np.testing.assert_allclose(got_vs, vs, atol=1e-5)
    want = np.zeros((L, nh, hd), np.float32)
    for p in range(L):
        E = [m for m in range(L) if m // W == p // W and m <= p]
        R = [c for c in range(L // CHUNK) if c // (W // CHUNK) < p // W]
        assert len(E) == p % W + 1 and len(R) == p // W * (W // CHUNK)
        for h in range(nh):
            e = np.exp(s * k[E, h] @ q[p, h])
            r = np.exp(s * ks[R, h] @ q[p, h])
            want[p, h] = (e @ v[E, h] + r @ vs[R, h]) / (e.sum() + r.sum())
    got = ref.attend(CFG, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(phi), jnp.asarray(mu))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the controls leave out what their names say
    off = ref.attend(CFG, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(phi), jnp.asarray(mu), summaries="off")
    np.testing.assert_allclose(off[:W], want[:W], atol=2e-5)
    assert np.abs(np.asarray(off[W:]) - want[W:]).max() > 1e-2
    prev = ref.attend(CFG, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(phi), jnp.asarray(mu), summaries="previous")
    np.testing.assert_allclose(prev[:2 * W], want[:2 * W], atol=2e-5)
    assert np.abs(np.asarray(prev[2 * W:]) - want[2 * W:]).max() > 1e-2


def test_no_pack_crosses_a_window_and_a_tick_stays_full(params, prompts):
    """A filler's rows of one pack lie in one window (counted from its
    first real row), so a chunk is cut at the boundary; at a budget of
    window + slots the tick is still full while two prompts are filling,
    and ``rows`` still sums to ``budget_used``."""
    tr = Tracer()
    eng = engine(params, tr)
    packs = []
    build = eng._build_pack

    def spy():
        pack = build()
        if pack is not None:
            packs.append((pack[1].copy(), pack[2].copy(),
                          eng._pad.copy()))
        return pack
    eng._build_pack = spy
    serve(eng, prompts)
    cut = 0
    for seq, pos, pad in packs:
        for s in set(seq[seq >= 0].tolist()):
            logical = pos[seq == s] - pad[s]
            logical = logical[logical >= 0]
            assert len(set((logical // W).tolist())) <= 1
            cut += logical.size and logical[-1] % W == W - 1
    assert cut >= 6                       # chunks did end at boundaries
    ticks = tr.events("tick")
    for k in ticks:
        assert sum(r[1] for r in k["rows"]) == k["budget_used"]
    both = [k for k in ticks if k["prefill_tokens"]
            and len(k["rows"]) - k["decode_rows"] >= 2]
    assert both and all(k["budget_used"] == k["token_budget"] for k in both)


def _tick(model, params, pools, table, toks, seq, pos):
    pads = jnp.zeros((table.shape[0],), jnp.int32)
    h = model._embed_ragged(params, jnp.asarray(toks, jnp.int32), None,
                            None, None)
    h, pools, stats = model.decode_ragged(
        params, h, pools, table, jnp.asarray(seq, jnp.int32),
        jnp.asarray(pos, jnp.int32), pads)
    return model.decode_logits(params, h[0]), pools, np.asarray(stats)


def test_closed_windows_and_open_summaries_are_unreachable(params):
    """Poison what a row must not read: the window leaf's rows beyond its
    position (the closed window's, not yet overwritten) and the summaries
    of its own window.  Nothing changes."""
    model = serve_eva.meta_model(CFG)
    ids = np.random.default_rng(7).integers(1, 320, 2 * W + 10)
    C = 256 // (4 * CHUNK)
    table = 1 + jnp.arange(C, dtype=jnp.int32)[None]
    pools = build_pools(model.cache_spec(), (C + 1, 4), slots=1)
    at = 0
    for n in (W, W, 6):                   # two windows, then 6 rows more
        _, pools, stats = _tick(model, params, pools, table,
                                ids[at:at + n], [0] * n, range(at, at + n))
        assert stats[2] == (at + n) // CHUNK - at // CHUNK
        at += n
    rest = ids[at:]
    args = (rest, [0] * len(rest), range(at, at + len(rest)))
    want, _, stats = _tick(model, params, pools, table, *args)
    # keys attended: each row its window rows so far and 2 windows' chunks
    assert stats[0] == 2 * sum(p % W + 1 for p in args[2])
    assert stats[1] == 2 * len(rest) * 2 * (W // CHUNK)
    (wk, wv), (sk, sv) = pools
    wk = wk.at[:, 0, 10:].set(1e4)        # window 1's rows 10.. still there
    wv = wv.at[:, 0, 10:].set(1e4)
    own = 2 * (W // CHUNK)                # window 2's first summaries
    blk, off = 1 + own // 4, own % 4
    sk = sk.at[:, blk, off:].set(1e4).at[:, blk + 1:].set(1e4)
    sv = sv.at[:, blk, off:].set(1e4).at[:, blk + 1:].set(1e4)
    got, _, _ = _tick(model, params, ((wk, wv), (sk, sv)), table, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_preempted_and_readmitted_gives_the_same_tokens(params, prompts):
    """A pool too small for three long sequences: the youngest is
    preempted and recomputed; greedy tokens are what an ample pool
    gives."""
    ample = serve(engine(params), prompts, out_len=40)
    eng = engine(params, num_blocks=13)   # 13 blocks of 16 positions
    tight = serve(eng, prompts, out_len=40)
    assert eng.preemptions >= 1
    assert tight == ample


def test_a_tracer_changes_nothing(params, prompts):
    assert serve(engine(params, Tracer()), prompts[:2], 6) \
        == serve(engine(params, None), prompts[:2], 6)


def one_width(eng):
    """The engine with every round through its budget-wide program: what
    a model that says ``ragged_narrow_rounds = False`` gets."""
    eng.narrow_rows = 0
    return eng


@pytest.mark.parametrize("case", ["plain", "kernels", "preempted",
                                  "long_decode"])
def test_narrow_rounds_give_the_wide_programs_tokens(params, prompts, case,
                                                     request):
    """Rounds of decode rows only run the tick at 8 rows (3 slots, a
    budget of 35): the same tokens as the budget-wide program alone,
    through the XLA path and the interpreted kernels, across a window's
    end and a preemption."""
    if case == "kernels":
        request.getfixturevalue("interpret")
    over = dict(num_blocks=13) if case == "preempted" else {}
    which = prompts[:2] if case == "kernels" else prompts
    out_len = {"plain": 12, "kernels": 8, "preempted": 40,
               "long_decode": 2 * W + 5}[case]
    tr = Tracer()
    eng = engine(params, tr, **over)
    assert eng.narrow_rows == 8
    narrow = serve(eng, which, out_len)
    wide_eng = one_width(engine(params, **over))
    assert narrow == serve(wide_eng, which, out_len)
    assert 0 < eng.narrow_steps < eng.ragged_steps
    assert wide_eng.narrow_steps == 0
    if case == "preempted":
        assert eng.preemptions >= 1
    ticks = [k for k in tr.events("tick") if k.get("budget_used")]
    for k in ticks:
        assert k["token_budget"] == W + 3
        assert k["rows_run"] == (W + 3 if k["prefill_tokens"] else 8)
    # a decode row that closes a chunk closes it in the narrow program too
    assert any(k["rows_run"] == 8 and k["eva_chunks_closed"] for k in ticks)
    # exactly one program more than the table widths the wide rounds took
    keys = sorted(k[1:3] for k in eng.model._serving_programs)
    assert [k for k in keys if k[0] == 8] == [(8, eng.MB)]
    assert all(k[0] in (8, W + 3) for k in keys)


@pytest.mark.parametrize("rows", [8, 16])
def test_decode_rows_alone_equal_the_same_rows_in_a_wide_pack(params, rows):
    """The tick at ``rows`` rows against the tick at a chunk's width over
    the same three decode rows (one of them closing a chunk, one in its
    third window): hidden states, both leaves and the counters."""
    model = serve_eva.meta_model(CFG)
    S, per_block = 3, 4
    C = 256 // (per_block * CHUNK)
    table = 1 + jnp.arange(S * C, dtype=jnp.int32).reshape(S, C)
    rng = np.random.default_rng(3)
    pools = jax.tree.map(
        lambda z: jnp.asarray(rng.standard_normal(z.shape) * 0.3, z.dtype),
        build_pools(model.cache_spec(), (S * C + 1, per_block), slots=S))
    at = [2 * W + 6, CHUNK * 5 - 1, 40]

    def tick(T):
        seq = list(range(S)) + [-1] * (T - S)
        pos = at + [-1] * (T - S)
        toks = [11, 12, 13] + [0] * (T - S)
        return _tick(model, params, pools, table, toks, seq, pos)

    few, wide = tick(rows), tick(W + 3)
    np.testing.assert_allclose(np.asarray(few[0][:S]),
                               np.asarray(wide[0][:S]), atol=2e-5)
    for a, b in zip(jax.tree.leaves(few[1]), jax.tree.leaves(wide[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_array_equal(few[2], wide[2])
    assert few[2][2] == 1                 # the one chunk that closed


@pytest.mark.parametrize("rows", [8, W + 3], ids=["narrow", "wide"])
def test_the_tick_has_no_branch(params, rows):
    """No ``cond`` at either width: an array a branch closes over is an
    operand of the conditional, and inside the layer scan that copies
    every layer's weights out of their stack in every round."""
    eng = engine(params)
    text = eng._build_ragged_step(rows, eng.MB).lower(
        *eng._ragged_scratch_args(eng.MB, rows)).as_text()
    assert "stablehlo.while" in text
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


@pytest.mark.parametrize("what", ["prefix_cache", "kv_store", "draft",
                                  "bucketed_engine", "generate"])
def test_refused_by_name(params, what):
    model = serve_eva.meta_model(CFG)
    kw = dict(max_slots=2, max_len=256, block_size=4, num_blocks=32,
              prompt_buckets=[16, 32])
    with pytest.raises(NotImplementedError, match="EvaByteModel.*'eva'"):
        if what == "prefix_cache":
            RaggedPagedContinuousBatchingEngine(
                model, params, enable_prefix_cache=True, **kw)
        elif what == "kv_store":
            RaggedPagedContinuousBatchingEngine(
                model, params, enable_prefix_cache=True, kv_store=object(),
                **kw)
        elif what == "draft":
            from paddle_tpu.models.gpt import GPTConfig, GPTModel
            draft = GPTModel(GPTConfig(
                vocab_size=320, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_position_embeddings=256))
            RaggedPagedContinuousBatchingEngine(
                model, params, draft_model=draft, draft_params={}, **kw)
        elif what == "bucketed_engine":
            PagedContinuousBatchingEngine(model, params, **kw)
        else:
            model.generate(params, jnp.zeros((1, 8), jnp.int32), 4)


def test_spec_helpers():
    leaf = CacheLeaf(2, (4, 16), "float32")
    assert (leaf.tokens_per_row, leaf.slot_rows) == (1, 0)
    spec = EvaByteModel.cache_spec(serve_eva.meta_model(CFG))
    assert tokens_per_row(spec) == CHUNK and spec.row_boundary == W
    assert tokens_per_row(CacheSpec(pools=(leaf, leaf))) == 1
    with pytest.raises(ValueError, match="same"):
        tokens_per_row(CacheSpec(pools=(leaf, leaf._replace(
            tokens_per_row=4))))
    with pytest.raises(ValueError, match="slots"):
        build_pools(spec, (9, 4))
    (wk, _), (sk, _) = build_pools(spec, (9, 4), slots=3)
    assert wk.shape == (2, 3, W, 4, 16) and sk.shape == (2, 9, 4, 4, 16)


def test_config_refuses_a_chunk_that_straddles_a_window():
    with pytest.raises(ValueError, match="straddles"):
        EvaByteConfig(chunk_size=24, window_size=2048)


# ------------------------------------------------------------- kernels --

def _leaves(rng, S, NB, bs, nh, hd, dtype):
    mk = lambda *shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32).astype(dtype)
    return (mk(2, S, W, nh, hd), mk(2, S, W, nh, hd),
            mk(2, NB + 1, bs, nh, hd), mk(2, NB + 1, bs, nh, hd))


@pytest.mark.parametrize("nh,hd,dtype,tol", [
    (4, 16, "float32", 2e-5),       # a head is a lane slice
    (16, 128, "float32", 2e-5),     # a head is a strided load
    (16, 128, "bfloat16", 2e-2),    # two heads to a 32-bit row
], ids=["lanes-f32", "strided-f32", "strided-bf16"])
def test_attention_kernel_interpreted(nh, hd, dtype, tol):
    from paddle_tpu.ops.ragged_eva_attention import (
        ragged_eva_attention_ref, ragged_eva_attention_rows)
    rng = np.random.default_rng(11)
    S, bs, C = 3, 4, 6
    leaves = _leaves(rng, S, S * C, bs, nh, hd, dtype)
    table = jnp.asarray(1 + rng.permutation(S * C).reshape(S, C), jnp.int32)
    # a run of 20 rows in window 2 of slot 1, a decode row in window 0 of
    # slot 0 and one in window 1 of slot 2, a run cut by the step's edge,
    # padding
    seq = [1] * 20 + [0, 2] + [0] * 9 + [-1] * 9
    pos = list(range(2 * W + 5, 2 * W + 25)) + [7, W + 31] \
        + list(range(W + 3, W + 12)) + [-1] * 9
    q = jnp.asarray(rng.standard_normal((40, nh, hd)),
                    jnp.float32).astype(dtype)
    args = (q, *leaves, table, jnp.asarray(seq, jnp.int32),
            jnp.asarray(pos, jnp.int32))
    kw = dict(chunk=CHUNK, scale=hd ** -0.5, layer=jnp.int32(1))
    want = ragged_eva_attention_ref(*args, **kw)
    got = ragged_eva_attention_rows(*args, **kw, interpret=True,
                                    rows_per_step=20, keys_per_step=8)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert not np.asarray(got, np.float32)[31:].any()


@pytest.mark.parametrize("nh,hd,dtype,tol", [
    (4, 16, "float32", 1e-5), (16, 128, "bfloat16", 2e-2)],
    ids=["f32", "bf16"])
def test_summarize_kernel_interpreted(nh, hd, dtype, tol):
    from paddle_tpu.ops.eva_summarize import (eva_summarize_ref,
                                              eva_summarize_rows)
    rng = np.random.default_rng(13)
    wk, wv, _, _ = _leaves(rng, 3, 1, 4, nh, hd, dtype)
    phi, mu = (jnp.asarray(rng.standard_normal((nh, hd)), jnp.float32)
               .astype(dtype) for _ in range(2))
    seq = jnp.asarray([2, 0, 0, 1, 0], jnp.int32)
    at = jnp.asarray([7, 0, 3, 5, 0], jnp.int32)
    kw = dict(chunk=CHUNK, scale=hd ** -0.5, layer=jnp.int32(1))
    want = eva_summarize_ref(wk, wv, phi, mu, seq, at, **kw)
    got = eva_summarize_rows(wk, wv, phi, mu, seq, at, **kw, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=tol)
    # against the equations, chunk 7 of slot 2, head 1
    k = np.asarray(wk[1, 2, 28:32, 1], np.float32)
    a = np.exp(hd ** -0.5 * k @ np.asarray(phi[1], np.float32))
    np.testing.assert_allclose(
        np.asarray(want[0][0, 1], np.float32),
        a / a.sum() @ k + np.asarray(mu[1], np.float32), atol=tol)
