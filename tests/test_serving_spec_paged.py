"""Paged speculative continuous batching
(RaggedPagedContinuousBatchingEngine with draft_model= / draft_params= /
draft_k= and the storage knobs: block_size, num_blocks, prefix cache): the
two serving accelerations composed.  The draft pool shares the target's
block tables and allocator — so outputs must stay bit-lossless vs plain
greedy (at every block size), and the paged allocator's
deferral/preemption must hold under tight pools.  Beyond-reference (the
snapshot has no serving scheduler)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine


import functools


@functools.lru_cache(maxsize=None)
def _models(kv=None):
    """Memoized per kv flag: all tests share the same model OBJECTS, so
    compiled serving programs (cached on the model) are built once per
    signature for the whole file instead of once per test."""
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    compute_dtype="float32", kv_cache_dtype=kv)
    model = GPTModel(cfg)
    params = {n: p._data for n, p in model.named_parameters()}
    dcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                     num_attention_heads=4, max_position_embeddings=96,
                     compute_dtype="float32", kv_cache_dtype=kv)
    draft = GPTModel(dcfg)
    dparams = {n: p._data for n, p in draft.named_parameters()}
    return model, params, draft, dparams


def _solo(model, params, p, n):
    out = model.generate(params, jnp.asarray([p], jnp.int32), n,
                         greedy=True)
    return [int(t) for t in np.asarray(out)[0]]


REQS = [([5, 17, 3], 10), ([40, 2], 6), ([61], 8), ([9, 9, 1], 7)]


class TestPagedSpeculative:
    @pytest.mark.parametrize("K", [1, 3])
    def test_lossless_vs_solo_and_contiguous(self, K):
        """Mixed budgets through 2 slots (retirement + reuse): outputs
        equal plain greedy solo at a fine block size AND at the coarsest
        one (gcd(max_len, bucket): a slot's pages all but contiguous),
        token for token, with the same round count."""
        model, params, draft, dparams = _models()
        paged = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=K)
        rids = [paged.add_request(p, n) for p, n in REQS]
        got = paged.run_to_completion(max_ticks=300)
        cont = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=K)
        rids_c = [cont.add_request(p, n) for p, n in REQS]
        got_c = cont.run_to_completion(max_ticks=300)
        for rid, rc, (p, n) in zip(rids, rids_c, REQS):
            want = _solo(model, params, p, n)
            assert got[rid] == want, f"paged diverged (K={K})"
            assert got_c[rc] == want
        assert paged.spec_rounds == cont.spec_rounds  # same acceptance schedule
        assert paged.blocks_in_use == 0

    def test_tight_pool_preempts_and_stays_exact(self):
        """Two long requests cannot both fit: the younger is preempted
        and rerun, outputs stay greedy-exact, high water respects the
        cap — the paged allocator composing with spec growth spans."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            num_blocks=10, prompt_buckets=[8], draft_model=draft,
            draft_params=dparams, draft_k=2)
        r0 = eng.add_request([5, 17, 3], 24)   # P+mnt+K-1 = 33 -> 9 blocks
        r1 = eng.add_request([40, 2], 24)
        got = eng.run_to_completion(max_ticks=500)
        assert eng.preemptions >= 1
        assert eng.blocks_high_water <= 10
        assert got[r0] == _solo(model, params, [5, 17, 3], 24)
        assert got[r1] == _solo(model, params, [40, 2], 24)

    def test_int8_pools(self):
        """int8 target AND draft pools through the shared tables."""
        model, params, draft, dparams = _models(kv="int8")
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=2)
        rids = [eng.add_request(p, n) for p, n in REQS[:3]]
        got = eng.run_to_completion(max_ticks=300)
        for rid, (p, n) in zip(rids, REQS[:3]):
            assert got[rid] == _solo(model, params, p, n)

    def test_program_count_bounded(self):
        model, params, draft, dparams = _models()
        model.__dict__.pop("_serving_programs", None)

        def make():
            return RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=48, block_size=4,
                prompt_buckets=[8], draft_model=draft, draft_params=dparams,
                draft_k=2)

        eng = make()
        for p, n in REQS[:3]:
            eng.add_request(p, n)
        eng.run_to_completion(max_ticks=300)
        n_progs = len(model._serving_programs)
        eng2 = make()
        eng2.add_request(REQS[3][0], REQS[3][1])
        eng2.run_to_completion(max_ticks=300)
        assert len(model._serving_programs) == n_progs

    def test_v1_scope_guards(self):
        model, params, draft, dparams = _models()
        # sampler knobs the greedy round would ignore: rejected loudly
        with pytest.raises(NotImplementedError, match="min_new_tokens"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=48, block_size=4,
                prompt_buckets=[8], draft_model=draft, draft_params=dparams,
                min_new_tokens=2)
        # prefill_chunk is the bucketed engines' knob: the ragged engine
        # chunks prefill through token_budget (TestPagedSpecChunked)
        with pytest.raises(ValueError, match="prefill_chunk"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=48, block_size=8,
                prompt_buckets=[8], draft_model=draft, draft_params=dparams,
                prefill_chunk=4)


class TestPagedSpecFuzz:
    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [0, 3, 6])
    def test_random_schedules_match_solo(self, seed):
        """Randomized paged-speculative schedules: random draft_k, block
        size, pool size (down to the deferral regime), prompts, budgets,
        and staggered admission — every request equals solo greedy and
        the pool drains to zero."""
        model, params, draft, dparams = _models()
        rng = np.random.RandomState(100 + seed)
        K = int(rng.choice([1, 2, 4]))
        bs = int(rng.choice([4, 8]))
        worst = -(-(16 + 11 + K - 1) // bs)
        nb = int(rng.randint(worst, worst * 3))
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=int(rng.randint(1, 4)), max_len=48,
            block_size=bs, num_blocks=nb, prompt_buckets=[8, 16],
            draft_model=draft, draft_params=dparams, draft_k=K)
        reqs = []
        for _ in range(int(rng.randint(3, 8))):
            p = [int(t) for t in rng.randint(1, 97, rng.randint(1, 15))]
            n = int(rng.randint(1, 12))
            reqs.append((eng.add_request(p, n), p, n))
            for _ in range(int(rng.randint(0, 3))):
                eng.step()
        got = eng.run_to_completion(max_ticks=1000)
        for rid, p, n in reqs:
            assert got[rid] == _solo(model, params, p, n), \
                (seed, K, bs, nb, rid)
        assert eng.blocks_in_use == 0


class TestPagedSpecPrefixCache:
    def test_identical_prompt_hit_lossless_same_rounds(self):
        """Prefix caching composes with speculation: shared tables mean a
        cached prompt block holds BOTH models' k/v, so a hit is lossless
        AND keeps the same acceptance schedule (equal round counts cold
        vs warm — the cached DRAFT prefix must be right, not just the
        target's)."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            prompt_buckets=[16], draft_model=draft, draft_params=dparams,
            draft_k=2, enable_prefix_cache=True)
        LONG = list(range(3, 17))
        r0 = eng.add_request(LONG, 8)
        g0 = eng.run_to_completion(max_ticks=200)
        cold = eng.spec_rounds
        r1 = eng.add_request(LONG, 8)
        g1 = eng.run_to_completion(max_ticks=200)
        want = _solo(model, params, LONG, 8)
        assert g0[r0] == want and g1[r1] == want
        assert eng.prefix_hits == 1 and eng.prefix_blocks_reused == 3
        assert eng.spec_rounds == 2 * cold

    def test_concurrent_sharing_with_speculation(self):
        """Two same-prefix requests decode speculatively side by side with
        refcounted shared blocks; both stay exact."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            prompt_buckets=[16], draft_model=draft, draft_params=dparams,
            draft_k=2, enable_prefix_cache=True)
        a = [7] * 2 + list(range(20, 32))
        b = a[:8] + list(range(70, 76))         # same length, shared 8
        r0 = eng.add_request(a, 6)
        eng.step()                              # a admitted + decoding
        r1 = eng.add_request(b, 10)
        got = eng.run_to_completion(max_ticks=300)
        assert got[r0] == _solo(model, params, a, 6)
        assert got[r1] == _solo(model, params, b, 10)
        assert eng.prefix_hits == 1 and eng.prefix_blocks_reused == 2

    def test_int8_dual_pool_prefix(self):
        model, params, draft, dparams = _models(kv="int8")
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=8,
            prompt_buckets=[16], draft_model=draft, draft_params=dparams,
            draft_k=2, enable_prefix_cache=True)
        LONG = list(range(3, 17))
        r0 = eng.add_request(LONG, 6)
        eng.run_to_completion(max_ticks=200)
        r1 = eng.add_request(LONG, 6)
        got = eng.run_to_completion(max_ticks=200)
        assert eng.prefix_hits == 1
        assert got[r1] == _solo(model, params, LONG, 6)


class TestPagedSpecChunked:
    def test_chunked_fill_under_speculative_decode(self):
        """A long prompt chunk-fills over several rounds (a token budget
        of 10 leaves it 4 rows a tick beside two slots' K+1 verify rows)
        while another request decodes SPECULATIVELY next door; both
        outputs lossless."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=64, block_size=4,
            prompt_buckets=[4, 16], draft_model=draft, draft_params=dparams,
            draft_k=2, token_budget=10)
        r0 = eng.add_request([40, 2], 20)      # bucket 4: decodes all test
        LONG = list(range(3, 19))              # bucket 16, pad 0: 4 segs
        r1 = eng.add_request(LONG, 8)
        got = eng.run_to_completion(max_ticks=300)
        assert got[r0] == _solo(model, params, [40, 2], 20)
        assert got[r1] == _solo(model, params, LONG, 8)

    def test_chunked_plus_prefix_plus_speculation(self):
        """All three compose: under a 10-row token budget a warm prefix
        hit whose suffix fits one chunk admits in one tick, stays
        lossless, and keeps the acceptance schedule."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=64, block_size=4,
            prompt_buckets=[16], draft_model=draft, draft_params=dparams,
            draft_k=2, token_budget=10, enable_prefix_cache=True)
        LONG = list(range(3, 17))
        r0 = eng.add_request(LONG, 8)
        g0 = eng.run_to_completion(max_ticks=300)
        cold = eng.spec_rounds
        r1 = eng.add_request(LONG, 8)
        g1 = eng.run_to_completion(max_ticks=300)
        want = _solo(model, params, LONG, 8)
        assert g0[r0] == want and g1[r1] == want
        assert eng.prefix_hits == 1
        assert eng.spec_rounds == 2 * cold


class TestCancel:
    """Engine.cancel(rid) with a draft attached (ISSUE 9): the
    shared-table allocator releases BOTH pools' blocks through one
    cancel, and the remaining request stays bit-lossless."""

    def test_cancel_releases_shared_tables(self):
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=64, block_size=4,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=2)
        sig = []
        r0 = eng.add_request([5, 17, 3], 20,
                             on_token=lambda r, t, d: sig.append((t, d)))
        r1 = eng.add_request([40, 2], 6)
        eng.step()
        assert eng.cancel(r0)                  # active mid-spec-round
        assert sig[-1] == (None, True)
        got = eng.run_to_completion(max_ticks=200)
        assert sorted(got) == [r1]
        assert got[r1] == _solo(model, params, [40, 2], 6)
        assert eng.blocks_in_use == 0
        m = eng.metrics()
        assert m["requests_cancelled"] == 1
        assert m["blocks_allocated"] == m["blocks_released"]

    def test_cancel_contiguous_speculative(self):
        """At the coarsest block size (gcd(max_len, bucket)) a cancel
        mid-speculation releases clean too."""
        model, params, draft, dparams = _models()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=64, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=2)
        r0 = eng.add_request([5, 17, 3], 20)
        r1 = eng.add_request([61], 8)
        eng.step()
        assert eng.cancel(r0)
        got = eng.run_to_completion(max_ticks=200)
        assert sorted(got) == [r1]
        assert got[r1] == _solo(model, params, [61], 8)
        assert eng.metrics()["requests_cancelled"] == 1
