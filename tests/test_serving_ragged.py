"""Ragged mixed prefill+decode serving engine
(paddle_tpu/serving_paged.py: RaggedPagedContinuousBatchingEngine): ONE
compiled program per scheduler tick serves any mixture of admission
prefill chunks and in-flight decode rows — no per-bucket prefill program
family, no separate decode tick — while every request's tokens stay
oracle-exact vs solo model.generate(), across fp32 and int8 KV pools,
prefix-cache hits, preemption, and per-request sampling planes.

No reference counterpart (the reference serves static batches only); the
oracle is the framework's own single-request generation path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine


@pytest.fixture(scope="module")
def model_and_params():
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=96,
                    compute_dtype="float32")
    model = GPTModel(cfg)
    params = {n: p._data for n, p in model.named_parameters()}
    return model, params


def _solo_greedy(model, params, prompt, n, **kw):
    out = model.generate(params, jnp.asarray([prompt], jnp.int32), n,
                         greedy=True, **kw)
    return [int(t) for t in np.asarray(out)[0]]


PROMPTS = [[5, 17, 3], [40, 2], [9, 9, 9, 9, 9, 1], [61], [8, 30, 12, 4],
           [77, 13, 2, 5, 6, 7, 8]]


class TestRaggedParity:
    def test_interleaved_matches_solo_generate(self, model_and_params):
        """Six ragged requests through 3 slots with retirement and
        re-admission: token-for-token solo parity, clean allocator."""
        model, params = model_and_params
        budgets = [10, 4, 7, 12, 3, 8]
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=3, max_len=32, block_size=4,
            prompt_buckets=[8, 16], token_budget=12)
        rids = [eng.add_request(p, n) for p, n in zip(PROMPTS, budgets)]
        got = eng.run_to_completion(max_ticks=300)
        assert sorted(got) == sorted(rids)
        for rid, p, n in zip(rids, PROMPTS, budgets):
            assert got[rid] == _solo_greedy(model, params, p, n), \
                f"request {rid} diverged"
        assert eng.blocks_in_use == 0

    def test_one_program_serves_the_mixed_tick(self, model_and_params):
        """THE tentpole claim: a workload mixing admissions into running
        decode dispatches ONLY ragged_step programs — no per-bucket
        prefill family, no cached-prefill family, no separate decode
        programs — and at least one step really carried prefill AND
        decode rows.  Program count stays bounded by table-width buckets
        (and a fresh engine adds none)."""
        model, params = model_and_params
        model.__dict__.pop("_serving_programs", None)

        def make():
            return RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=3, max_len=32, block_size=4,
                prompt_buckets=[8, 16], token_budget=12)

        eng = make()
        r0 = eng.add_request(PROMPTS[0], 8)
        eng.step()                               # r0 prefills + first token
        r1 = eng.add_request(PROMPTS[5], 6)      # arrives mid-decode
        r2 = eng.add_request(PROMPTS[1], 5)
        got = eng.run_to_completion(max_ticks=200)
        kinds = {k[0] for k in model._serving_programs}
        assert kinds == {"ragged_step"}, kinds
        assert eng.mixed_steps >= 1
        n_progs = len(model._serving_programs)
        eng2 = make()                            # same shapes: no new progs
        eng2.add_request(PROMPTS[2], 5)
        eng2.run_to_completion(max_ticks=200)
        assert len(model._serving_programs) == n_progs
        for rid, p, n in [(r0, PROMPTS[0], 8), (r1, PROMPTS[5], 6),
                          (r2, PROMPTS[1], 5)]:
            assert got[rid] == _solo_greedy(model, params, p, n)

    def test_prompt_longer_than_budget_spans_steps(self, model_and_params):
        """A bucket-16 prompt under a budget of 6 rows prefills across
        several ragged steps (chunking is inherent — no prefill_chunk
        knob) while a short request decodes next to it."""
        model, params = model_and_params
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            prompt_buckets=[4, 16], token_budget=6)
        r0 = eng.add_request([40, 2], 12)              # bucket 4
        long_p = list(range(3, 17))                    # bucket 16 > budget
        r1 = eng.add_request(long_p, 5)
        got = eng.run_to_completion(max_ticks=300)
        assert got[r0] == _solo_greedy(model, params, [40, 2], 12)
        assert got[r1] == _solo_greedy(model, params, long_p, 5)
        assert eng.mixed_steps >= 1

    @pytest.mark.parametrize("interp", [
        False,
        pytest.param(True, marks=pytest.mark.slow),  # interpret-mode
        # Pallas is minutes-scale on CPU; the quick tier keeps the
        # cheaper kernel_on_off interpret coverage
    ])
    def test_int8_kv_pool(self, interp):
        """int8 (values, scales) pools ride the ragged step with dequant
        fused into the kernel (interpret arm) or the gather fallback:
        parity vs solo generate on the SAME int8-cached model."""
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_position_embeddings=96,
                        compute_dtype="float32", kv_cache_dtype="int8")
        model = GPTModel(cfg)
        params = {n: p._data for n, p in model.named_parameters()}
        set_flags({"FLAGS_paged_attn_interpret": interp})
        try:
            eng = RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=8,
                prompt_buckets=[8], token_budget=10)
            budgets = [9, 5, 7]
            rids = [eng.add_request(p, n)
                    for p, n in zip(PROMPTS[:3], budgets)]
            got = eng.run_to_completion(max_ticks=200)
        finally:
            set_flags({"FLAGS_paged_attn_interpret": False})
        for rid, p, n in zip(rids, PROMPTS[:3], budgets):
            assert got[rid] == _solo_greedy(model, params, p, n), \
                f"int8 request {rid} diverged (interp={interp})"

    def test_kernel_on_off_identical(self, model_and_params):
        """Engine outputs are token-identical with the ragged Pallas
        kernel (interpret mode) vs the XLA gather fallback."""
        model, params = model_and_params

        def run(interp):
            set_flags({"FLAGS_paged_attn_interpret": interp})
            try:
                model.__dict__.pop("_serving_programs", None)
                eng = RaggedPagedContinuousBatchingEngine(
                    model, params, max_slots=3, max_len=32, block_size=4,
                    prompt_buckets=[8, 16], token_budget=12)
                rids = [eng.add_request(p, n)
                        for p, n in zip(PROMPTS[:4], [9, 5, 7, 6])]
                got = eng.run_to_completion(max_ticks=200)
                return [got[r] for r in rids]
            finally:
                set_flags({"FLAGS_paged_attn_interpret": False})
                model.__dict__.pop("_serving_programs", None)

        assert run(True) == run(False)


class TestRaggedAllocator:
    @pytest.mark.parametrize("interp", [
        False,
        pytest.param(True, marks=pytest.mark.slow),  # interpret-mode
        # Pallas is minutes-scale on CPU; the quick tier keeps the
        # cheaper kernel_on_off interpret coverage
    ])
    def test_preemption_stays_exact_and_signals_replay(self, interp,
                                                       model_and_params):
        """Two long requests over a pool that fits one: the younger is
        preempted and rerun; outputs stay greedy-exact (kernel interpret
        arm included) and the streaming consumer receives the documented
        on_token(rid, None, False) replay signal before the re-delivered
        prefix."""
        model, params = model_and_params
        events = []
        set_flags({"FLAGS_paged_attn_interpret": interp})
        try:
            eng = RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                num_blocks=8, prompt_buckets=[8], token_budget=10)
            r0 = eng.add_request(PROMPTS[0], 14)
            r1 = eng.add_request(PROMPTS[1], 14,
                                 on_token=lambda rid, tok, done:
                                 events.append((rid, tok, done)))
            got = eng.run_to_completion(max_ticks=500)
        finally:
            set_flags({"FLAGS_paged_attn_interpret": False})
        assert eng.preemptions >= 1
        assert got[r0] == _solo_greedy(model, params, PROMPTS[0], 14)
        assert got[r1] == _solo_greedy(model, params, PROMPTS[1], 14)
        resets = [i for i, (rid, tok, _) in enumerate(events)
                  if tok is None]
        assert resets, "preempted request never got the replay signal"
        # the stream AFTER the last reset is the complete, exact answer
        tail = [tok for rid, tok, _ in events[resets[-1] + 1:]]
        assert tail == got[r1]
        assert eng.blocks_in_use == 0

    @pytest.mark.parametrize("interp", [
        False,
        pytest.param(True, marks=pytest.mark.slow),  # interpret-mode
        # Pallas is minutes-scale on CPU; the quick tier keeps the
        # cheaper kernel_on_off interpret coverage
    ])
    def test_prefix_cache_reuses_blocks(self, interp, model_and_params):
        """Same-pad shared prefix: the second admission pins the cached
        chain and computes only the suffix rows; outputs stay exact on
        both the kernel (interpret) and gather arms."""
        model, params = model_and_params
        set_flags({"FLAGS_paged_attn_interpret": interp})
        try:
            eng = RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=64, block_size=4,
                prompt_buckets=[16], token_budget=20,
                enable_prefix_cache=True)
            sysp = list(range(7, 19))
            p1, p2 = sysp + [1], sysp + [2]  # same length => shared chain
            ra = eng.add_request(p1, 6)
            got = eng.run_to_completion(max_ticks=200)
            rb = eng.add_request(p2, 6)
            got2 = eng.run_to_completion(max_ticks=200)
        finally:
            set_flags({"FLAGS_paged_attn_interpret": False})
        assert eng.prefix_hits >= 1
        assert eng.prefix_blocks_reused >= 1
        assert got[ra] == _solo_greedy(model, params, p1, 6)
        assert got2[rb] == _solo_greedy(model, params, p2, 6)

    def test_per_request_planes(self, model_and_params):
        """Heterogeneous deterministic configs in one ragged batch — the
        per-request data planes ride the single mixed program."""
        model, params = model_and_params
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=3, max_len=48, block_size=4,
            prompt_buckets=[8], token_budget=12, per_request_sampling=True)
        probe = _solo_greedy(model, params, PROMPTS[0], 8)
        eos = probe[1]
        cases = [(PROMPTS[0], 8, {}),
                 (PROMPTS[1], 7, dict(repetition_penalty=5.0)),
                 (PROMPTS[0], 8, dict(min_new_tokens=4, eos_token_id=eos))]
        rids = [eng.add_request(p, n, **c) for p, n, c in cases]
        got = eng.run_to_completion(max_ticks=300)
        for rid, (p, n, c) in zip(rids, cases):
            assert got[rid] == _solo_greedy(model, params, p, n, **c), \
                f"request {rid} cfg={c}"

    def test_ctor_validation(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError, match="token_budget"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=4, max_len=32, block_size=4,
                token_budget=2)
        with pytest.raises(NotImplementedError, match="ticks_per_sync"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                ticks_per_sync=2)
        with pytest.raises(ValueError, match="prefill_chunk"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                prefill_chunk=8)


class TestRaggedSpec:
    """Speculative decoding INSIDE the ragged engine (ISSUE 13): the
    draft's K proposals and the target's verification ride the SAME
    flattened pack as plain decode rows and admission prefill chunks —
    one fused compiled program per (token_budget, table-width) bucket,
    outputs equal to plain greedy decode by the models/_decode.py
    greedy_verify contract."""

    @pytest.fixture(scope="class")
    def draft_and_params(self):
        paddle.seed(77)
        dcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                         num_attention_heads=4,
                         max_position_embeddings=96,
                         compute_dtype="float32")
        draft = GPTModel(dcfg)
        return draft, {n: p._data for n, p in draft.named_parameters()}

    def test_mixed_spec_nonspec_single_program(self, model_and_params,
                                               draft_and_params):
        """THE tentpole pin: spec and non-spec requests share a tick
        (admission prefill included), and the whole workload dispatches
        ONLY the fused ragged_spec family — one program per
        (token_budget, table-width) bucket, asserted via the PR 2
        compile counters."""
        model, params = model_and_params
        draft, dparams = draft_and_params
        model.__dict__.pop("_serving_programs", None)
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=3, max_len=48, block_size=4,
            prompt_buckets=[8, 16], draft_model=draft,
            draft_params=dparams, draft_k=3)
        r0 = eng.add_request(PROMPTS[0], 9)              # speculates
        eng.step()                                       # r0 activates
        r1 = eng.add_request(PROMPTS[5], 6, spec=False)  # plain rows
        r2 = eng.add_request(PROMPTS[1], 5)              # speculates
        got = eng.run_to_completion(max_ticks=300)
        kinds = {k[0] for k in model._serving_programs}
        assert kinds == {"ragged_spec"}, kinds
        # one compiled program per (token_budget, C) bucket, nothing else
        assert eng._compile_misses == len(model._serving_programs)
        assert eng.mixed_steps >= 1 and eng.spec_rounds >= 1
        assert eng.tokens_drafted > 0
        for rid, p, n in [(r0, PROMPTS[0], 9), (r1, PROMPTS[5], 6),
                          (r2, PROMPTS[1], 5)]:
            assert got[rid] == _solo_greedy(model, params, p, n), rid
        assert eng.blocks_in_use == 0

    @pytest.mark.parametrize("block_size", [4, 8])
    def test_perfect_draft_rounds_stats_rollback(self, model_and_params,
                                                 block_size):
        """Self-draft: every proposal accepted — one request of N tokens
        finishes in exactly ceil((N-1)/(K+1)) rounds (the observable that
        catches silent acceptance degradation), at a fine block size and
        at the coarsest (gcd(max_len, bucket)); acceptance_rate exactly
        1.0 on the registry-backed stats, spec counters in the Prometheus
        exposition (the gateway /metrics merge concatenates it), and the
        rejected-page rollback leaves a clean allocator."""
        model, params = model_and_params
        K, N = 3, 13
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=1, max_len=48, block_size=block_size,
            prompt_buckets=[8], draft_model=model, draft_params=params,
            draft_k=K)
        rid = eng.add_request([5, 17, 3], N)
        got = eng.run_to_completion(max_ticks=100)
        assert got[rid] == _solo_greedy(model, params, [5, 17, 3], N)
        assert eng.spec_rounds == -(-(N - 1) // (K + 1))
        m = eng.metrics()
        assert m["acceptance_rate"] == 1.0
        assert m["tokens_drafted"] == eng.spec_rounds * K
        assert m["tokens_accepted"] == m["tokens_drafted"]
        assert eng.blocks_in_use == 0
        text = eng.prometheus_text()
        assert "tokens_accepted" in text and "acceptance_rate" in text
        assert m["blocks_allocated"] == m["blocks_released"]

    @pytest.mark.parametrize("seed", [0, 1])
    def test_stream_prefix_fuzz_with_cancels(self, model_and_params,
                                             draft_and_params, seed):
        """Prefix-of-oracle parity under chaos: random spec/non-spec
        mixes over tight pools (preemption replays mid-round) with
        random mid-flight cancels — every finished request equals solo
        generate, every cancelled stream is a PREFIX of it (after the
        documented replay reset), and the allocator quiesces clean."""
        model, params = model_and_params
        draft, dparams = draft_and_params
        rng = np.random.RandomState(300 + seed)
        K = int(rng.choice([1, 2, 4]))
        bs = int(rng.choice([2, 4]))
        worst = -(-(16 + 11 + K - 1) // bs)
        nb = int(rng.randint(worst, worst * 2))
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=int(rng.randint(1, 4)), max_len=48,
            block_size=bs, num_blocks=nb, prompt_buckets=[8, 16],
            draft_model=draft, draft_params=dparams, draft_k=K)
        streams = {}

        def on_token(rid, tok, done):
            if tok is None and not done:
                streams[rid] = []            # replay reset: discard
            elif tok is not None:
                streams.setdefault(rid, []).append(tok)

        reqs = []
        for _ in range(int(rng.randint(4, 8))):
            p = [int(t) for t in rng.randint(1, 97, rng.randint(1, 15))]
            n = int(rng.randint(1, 12))
            rid = eng.add_request(p, n, on_token=on_token,
                                  spec=bool(rng.rand() < 0.7))
            reqs.append((rid, p, n))
            for _ in range(int(rng.randint(0, 3))):
                eng.step()
            if rng.rand() < 0.3:
                eng.cancel(reqs[int(rng.randint(0, len(reqs)))][0])
        got = eng.run_to_completion(max_ticks=800)
        for rid, p, n in reqs:
            want = _solo_greedy(model, params, p, n)
            stream = streams.get(rid, [])
            if rid in got:
                assert got[rid] == want, (seed, rid, K, bs, nb)
                assert stream == want, (seed, rid)
            else:
                assert stream == want[:len(stream)], (seed, rid)
        assert eng.blocks_in_use == 0
        m = eng.metrics()
        assert m["blocks_allocated"] == m["blocks_released"]

    def test_moe_target_plain_and_spec_ragged(self):
        """ErnieMoe's new decode_ragged path on the unified engine: a
        plain (non-spec) ragged run AND a GPT-drafted spec run over the
        same MoE target both match the MoE's solo generation — the
        mixin-contract coverage for the non-GPT family."""
        from paddle_tpu.models.ernie_moe import (ErnieMoeConfig,
                                                 ErnieMoeModel)
        paddle.seed(41)
        cfg = ErnieMoeConfig(vocab_size=97, hidden_size=32, num_layers=2,
                             num_attention_heads=4, num_experts=4,
                             top_k=2, max_position_embeddings=96,
                             compute_dtype="float32")
        moe = ErnieMoeModel(cfg)
        mparams = {n: p._data for n, p in moe.named_parameters()}
        paddle.seed(79)
        dcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                         num_attention_heads=4,
                         max_position_embeddings=96,
                         compute_dtype="float32")
        draft = GPTModel(dcfg)
        dparams = {n: p._data for n, p in draft.named_parameters()}
        for kw in ({}, dict(draft_model=draft, draft_params=dparams,
                            draft_k=2)):
            eng = RaggedPagedContinuousBatchingEngine(
                moe, mparams, max_slots=2, max_len=48, block_size=4,
                prompt_buckets=[8], **kw)
            rids = [eng.add_request(p, n)
                    for p, n in zip(PROMPTS[:3], (7, 5, 6))]
            got = eng.run_to_completion(max_ticks=300)
            for rid, p, n in zip(rids, PROMPTS[:3], (7, 5, 6)):
                assert got[rid] == _solo_greedy(moe, mparams, p, n), \
                    (bool(kw), rid)
            assert eng.blocks_in_use == 0

    def test_spec_true_needs_draft_and_guards(self, model_and_params):
        model, params = model_and_params
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=32, block_size=4,
            prompt_buckets=[8])
        with pytest.raises(ValueError, match="draft_model"):
            eng.add_request([1, 2, 3], 4, spec=True)
        paddle.seed(78)
        dcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                         num_attention_heads=4,
                         max_position_embeddings=96,
                         compute_dtype="float32")
        draft = GPTModel(dcfg)
        dparams = {n: p._data for n, p in draft.named_parameters()}
        with pytest.raises(NotImplementedError, match="greedy-only"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                prompt_buckets=[8], draft_model=draft,
                draft_params=dparams, per_request_sampling=True)
        with pytest.raises(NotImplementedError, match="repetition"):
            RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=2, max_len=32, block_size=4,
                prompt_buckets=[8], draft_model=draft,
                draft_params=dparams, repetition_penalty=2.0)
        # over-proposal slack is charged on spec requests only
        spec_eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=1, max_len=20, block_size=4,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=4)
        with pytest.raises(ValueError, match="exceeds max_len"):
            spec_eng.add_request([1, 2, 3], 10)    # 8 + 10 + 3 > 20
        spec_eng.add_request([1, 2, 3], 10, spec=False)   # plain: fits


class TestRaggedFuzz:
    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_scenarios_match_solo(self, seed):
        """Randomized mixed-batch stress: random prompts/budgets/arrival
        times under randomly drawn engine configs INCLUDING tight pools
        (deferral + preemption), token budgets, prefix caching, penalty,
        eos, and int8 — every request's tokens must equal solo generate()
        with the same knobs, and the allocator must quiesce clean."""
        rng = np.random.RandomState(seed)
        kv = "int8" if rng.rand() < 0.5 else None
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_position_embeddings=96,
                        compute_dtype="float32", kv_cache_dtype=kv)
        model = GPTModel(cfg)
        params = {n: p._data for n, p in model.named_parameters()}

        penalty = float(rng.choice([1.0, 4.0]))
        eos = int(rng.randint(0, 97)) if rng.rand() < 0.5 else None
        bs = int(rng.choice([2, 4, 8]))
        budget = int(rng.choice([6, 10, 16]))
        prefix = bool(rng.rand() < 0.5)
        slots = int(rng.randint(1, 4))
        budget = max(budget, slots)
        # worst single request: bucket 16 + decode budget of 11
        worst = -(-(16 + 11 - 1) // bs)
        nb = int(rng.randint(worst, worst * 3))
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=slots, max_len=48, block_size=bs,
            num_blocks=nb, prompt_buckets=[8, 16], token_budget=budget,
            enable_prefix_cache=prefix, repetition_penalty=penalty,
            eos_token_id=eos)

        sysp = [int(t) for t in rng.randint(1, 97, 9)]
        reqs = []
        for _ in range(int(rng.randint(4, 9))):
            p = (sysp + [int(t) for t in rng.randint(1, 97,
                                                     rng.randint(1, 6))]
                 if rng.rand() < 0.4 else
                 [int(t) for t in rng.randint(1, 97, rng.randint(1, 15))])
            n = int(rng.randint(1, 12))
            reqs.append((eng.add_request(p, n), p, n))
            for _ in range(int(rng.randint(0, 3))):
                eng.step()
        got = eng.run_to_completion(max_ticks=800)

        for rid, p, n in reqs:
            want = _solo_greedy(model, params, p, n,
                                repetition_penalty=penalty)
            if eos is not None and eos in want:
                want = want[:want.index(eos) + 1]
            assert got[rid] == want, (
                f"seed={seed} bs={bs} nb={nb} budget={budget} "
                f"penalty={penalty} eos={eos} kv={kv} prefix={prefix} "
                f"preempt={eng.preemptions}")
        if prefix:
            cached = sum(1 for b in eng._prefix_cache.values()
                         if eng._refs.get(b, 0) == 0)
            assert eng.blocks_in_use == cached
        else:
            assert eng.blocks_in_use == 0


class TestPoolsCarriedWhole:
    """``CausalDecoderMixin.decode_ragged`` carries both pools whole
    through the layer scan and addresses the layer in place.  The oracle
    is the walk it replaced, written plainly: a Python loop over the
    layers, each block on its own layer's pool sliced out of the stack.

    Served tokens are pinned by the tests above, which this class does not
    repeat: mixed prefill + decode by TestRaggedParity
    .test_interleaved_matches_solo_generate and
    .test_one_program_serves_the_mixed_tick, int8 pools (kernel on and
    off) by .test_int8_kv_pool, a preemption with replay by
    TestRaggedAllocator.test_preemption_stays_exact_and_signals_replay,
    the fused draft + verify tick and the MoE target by TestRaggedSpec."""

    @staticmethod
    def _models(family, kv_cache_dtype):
        paddle.seed(23)
        kw = dict(vocab_size=97, hidden_size=32, num_layers=3,
                  num_attention_heads=4, max_position_embeddings=96,
                  compute_dtype="float32")
        if family == "gpt":
            return GPTModel(GPTConfig(kv_cache_dtype=kv_cache_dtype, **kw))
        from paddle_tpu.models.ernie_moe import (ErnieMoeConfig,
                                                 ErnieMoeModel)
        return ErnieMoeModel(ErnieMoeConfig(num_experts=4, top_k=2, **kw))

    @pytest.mark.parametrize("interp", [False, True],
                             ids=["gather", "kernel"])
    @pytest.mark.parametrize("family,kv", [
        ("gpt", None), ("gpt", "int8"), ("ernie-moe", None)],
        ids=["gpt-float", "gpt-int8", "ernie-moe-float"])   # no int8 MoE
    def test_equals_a_loop_over_sliced_pools(self, family, kv, interp):
        from paddle_tpu.models._decode import build_pools
        model = self._models(family, kv)
        params = {n: p._data for n, p in model.named_parameters()}
        L, S, C, bs, T = 3, 3, 4, 4, 16
        rng = np.random.default_rng(5)
        zeros = build_pools(model.cache_spec(), (S * C + 1, bs))
        pools = jax.tree.map(
            lambda z: jnp.asarray(rng.standard_normal(z.shape) * 0.5
                                  if z.dtype != jnp.int8 else
                                  rng.integers(-127, 128, z.shape),
                                  z.dtype), zeros)
        table = jnp.arange(1, S * C + 1, dtype=jnp.int32).reshape(S, C)
        # a prefill chunk (seq 0, positions 2..7, two of them left pad), a
        # decode row deep in seq 1, a chunk's head for seq 2, padding rows
        row_seq = jnp.asarray([0] * 6 + [1] + [2] * 4 + [-1] * 5, jnp.int32)
        row_pos = jnp.asarray(list(range(2, 8)) + [13] + list(range(4))
                              + [-1] * 5, jnp.int32)
        pads = jnp.asarray([2, 0, 0], jnp.int32)
        toks = jnp.asarray(rng.integers(1, 97, T), jnp.int32)

        def whole(params, pools):
            h = model._embed_ragged(params, toks, row_seq, row_pos, pads)
            return model.decode_ragged(params, h, pools, table, row_seq,
                                       row_pos, pads)

        # the block compiled alone and called once a layer: unrolled into
        # one program, XLA's CPU backend fuses across the layers and rounds
        # the last bit another way than inside a loop's body
        block = jax.jit(lambda sl, h, pck, pcv: model._block_decode_ragged(
            sl, h, pck, pcv, table, row_seq, row_pos, pads))

        def sliced(params, pools):
            h = model._embed_ragged(params, toks, row_seq, row_pos, pads)
            pck, pcv = pools
            ks, vs = [], []
            for i in range(L):
                sl = {k: params[k][i] for k in model.stacked_param_names()}
                at = lambda pool: jax.tree.map(lambda p: p[i], pool)
                h, k, v = block(sl, h, at(pck), at(pcv))
                ks.append(k)
                vs.append(v)
            stack = lambda xs: jax.tree.map(lambda *p: jnp.stack(p), *xs)
            return h, (stack(ks), stack(vs))

        set_flags({"FLAGS_paged_attn_interpret": interp})
        try:
            got = jax.jit(whole)(params, pools)
            want = sliced(params, pools)
        finally:
            set_flags({"FLAGS_paged_attn_interpret": False})
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        assert len(got) == len(want) == (5 if kv else 3)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class OneWidthGPT(GPTModel):
    """A model that declines the narrow program (none in the tree does
    since PR 46): every round through the budget-wide tick."""
    ragged_narrow_rounds = False


def _serve_scenario(model, params, scenario, tracer=None):
    """One of the scenarios below through a 3-slot engine with a 24-row
    budget (over twice the 8 rows of the narrow program).  Returns
    (tokens by request in order of admission, the engine)."""
    kw = dict(max_slots=3, max_len=64, block_size=4,
              prompt_buckets=[8, 16], token_budget=24, tracer=tracer)
    if scenario == "preemption":
        kw.update(max_slots=2, num_blocks=8, prompt_buckets=[8],
                  max_len=32)
    if scenario == "prefix_hit":
        kw.update(prompt_buckets=[16], enable_prefix_cache=True)
    if scenario == "planes":
        kw.update(per_request_sampling=True)
    eng = RaggedPagedContinuousBatchingEngine(model, params, **kw)
    out = []
    if scenario == "decode_stretch":
        rids = [eng.add_request(p, 14) for p in PROMPTS[:3]]
    elif scenario == "admit_mid_decode":
        rids = [eng.add_request(PROMPTS[0], 12)]
        for _ in range(4):
            eng.step()                  # prefill, then decode-only rounds
        rids += [eng.add_request(PROMPTS[5], 6),
                 eng.add_request(PROMPTS[1], 9)]
    elif scenario == "preemption":
        rids = [eng.add_request(PROMPTS[0], 14),
                eng.add_request(PROMPTS[1], 14)]
    elif scenario == "finish_frees_slot":
        rids = [eng.add_request(p, n)
                for p, n in zip(PROMPTS, [10, 4, 7, 12, 3, 8])]
    elif scenario == "prefix_hit":
        sysp = list(range(7, 19))
        rids = [eng.add_request(sysp + [1], 6)]
        out.append(eng.run_to_completion(max_ticks=200)[rids[0]])
        rids = [eng.add_request(sysp + [2], 6)]
    elif scenario == "planes":
        rids = [eng.add_request(PROMPTS[0], 8),
                eng.add_request(PROMPTS[1], 7, repetition_penalty=5.0),
                eng.add_request(PROMPTS[0], 8, min_new_tokens=4,
                                eos_token_id=PROMPTS[0][1])]
    got = eng.run_to_completion(max_ticks=500)
    return out + [got[r] for r in rids], eng


class TestNarrowProgram:
    """A round of decode rows only runs the tick built at ``narrow_rows``
    rows (PR 39): the host chooses the program from the pack, and the
    tokens are what the budget-wide program alone serves."""

    @pytest.fixture(scope="class")
    def one_width(self, model_and_params):
        model, params = model_and_params
        return OneWidthGPT(model.config), params

    @pytest.mark.parametrize("scenario", [
        "decode_stretch", "admit_mid_decode", "preemption",
        "finish_frees_slot", "prefix_hit", "planes"])
    def test_same_tokens_with_and_without(self, scenario, model_and_params,
                                          one_width):
        from paddle_tpu.telemetry import Tracer
        tr = Tracer()
        narrow, eng = _serve_scenario(*model_and_params, scenario, tr)
        wide, eng_w = _serve_scenario(*one_width, scenario)
        assert narrow == wide
        assert eng.narrow_rows == 8 and eng_w.narrow_rows == 0
        assert 0 < eng.narrow_steps < eng.ragged_steps
        assert eng_w.narrow_steps == 0
        assert eng_w.ragged_steps == eng.ragged_steps
        if scenario == "preemption":
            assert eng.preemptions >= 1
        if scenario == "prefix_hit":
            assert eng.prefix_hits >= 1
        # a pack with no chunk went narrow, any pack with one wide; the
        # event's budget is the engine's whichever program ran
        ticks = [k for k in tr.events("tick") if k.get("budget_used")]
        assert len(ticks) == eng.ragged_steps
        for k in ticks:
            assert k["token_budget"] == 24
            assert k["rows_run"] == (24 if k["prefill_tokens"] else 8)
            assert k["budget_used"] <= k["rows_run"]
        assert sum(k["rows_run"] == 8 for k in ticks) == eng.narrow_steps
        m = eng.metrics()
        assert m["narrow_steps"] == eng.narrow_steps
        assert m["ragged_steps"] == eng.ragged_steps

    def test_exactly_one_program_more(self, model_and_params, one_width):
        """The narrow program is ONE, at the widest table, whatever
        widths the traffic reaches; the grid names it and a warmed engine
        compiles nothing when a decode-only round comes."""
        from paddle_tpu.serving_paged import pow2_grid
        model, params = model_and_params
        served = {}
        for m in (model, one_width[0]):
            m.__dict__.pop("_serving_programs", None)
            _, eng = _serve_scenario(m, params, "finish_frees_slot")
            served[type(m).__name__] = (
                sorted(k[1:3] for k in m._serving_programs), eng)
        keys_w, eng_w = served["OneWidthGPT"]
        keys_n, eng = served["GPTModel"]
        assert all(T == 24 for T, _ in keys_w)
        # the one narrow program, and of the wide ones no more than before
        # (a width that only decode-only rounds reached is never built)
        assert [k for k in keys_n if k[0] != 24] == [(8, eng.MB)]
        assert set(keys_n) - {(8, eng.MB)} <= set(keys_w)
        wide_grid = [f"ragged_step:24:{C}" for C in pow2_grid(eng.MB)]
        assert eng_w.compile_grid() == wide_grid
        assert eng.compile_grid() == wide_grid + [f"ragged_step:8:{eng.MB}"]
        model.__dict__.pop("_serving_programs", None)
        _, fresh = _serve_scenario(model, params, "prefix_hit")
        fresh.warmup()
        misses = fresh._compile_misses
        fresh.add_request(PROMPTS[3], 9)
        fresh.run_to_completion(max_ticks=100)
        assert fresh.narrow_steps and fresh._compile_misses == misses

    def test_a_budget_within_twice_the_slots_has_no_narrow_program(
            self, model_and_params):
        model, params = model_and_params
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=3, max_len=32, block_size=4,
            prompt_buckets=[8], token_budget=16)
        assert eng.narrow_rows == 0
        eng.add_request(PROMPTS[0], 6)
        eng.run_to_completion(max_ticks=100)
        assert eng.narrow_steps == 0 and eng.ragged_steps >= 6

    def test_the_spec_engine_compiles_what_it_did(self, model_and_params):
        """The fused draft+verify program keeps one width: the grid is one
        ``ragged_spec`` program a table width and nothing else runs."""
        from paddle_tpu.serving_paged import pow2_grid
        model, params = model_and_params
        paddle.seed(77)
        draft = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=1,
            num_attention_heads=4, max_position_embeddings=96,
            compute_dtype="float32"))
        model.__dict__.pop("_serving_programs", None)
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=2, max_len=48, block_size=4,
            prompt_buckets=[8], token_budget=40, draft_model=draft,
            draft_params={n: p._data for n, p in draft.named_parameters()},
            draft_k=3)
        assert eng.narrow_rows == 0
        assert eng.compile_grid() == [f"ragged_spec:40:{C}"
                                      for C in pow2_grid(eng.MB)]
        rid = eng.add_request(PROMPTS[0], 9)
        got = eng.run_to_completion(max_ticks=100)
        assert got[rid] == _solo_greedy(model, params, PROMPTS[0], 9)
        assert {k[0] for k in model._serving_programs} == {"ragged_spec"}
        assert eng.narrow_steps == 0

    @pytest.mark.parametrize("rows", [8, 24], ids=["narrow", "wide"])
    def test_a_model_that_declines_lowers_what_it_did(
            self, rows, model_and_params, one_width):
        """What a model says about the narrow program changes which
        programs its engine builds and not one byte of a program: the
        tick of ``rows`` rows lowers to the same text from either."""
        def text(m, params):
            eng = RaggedPagedContinuousBatchingEngine(
                m, params, max_slots=3, max_len=64, block_size=4,
                prompt_buckets=[8, 16], token_budget=24)
            return eng._build_ragged_step(rows, 4).lower(
                *eng._ragged_scratch_args(4, rows)).as_text()
        assert text(*model_and_params) == text(*one_width)

    def test_the_latent_model_takes_it(self):
        """``PanguMoeModel`` declined the narrow program and kept a
        branch on the pack inside its one width until PR 46; no model in
        the tree declines now (``OneWidthGPT`` above stands in for one):
        the narrow program is in its grid and its decode-only rounds run
        it."""
        from paddle_tpu.models.pangu_moe import PanguMoeModel
        from paddle_tpu.serving_paged import pow2_grid
        assert PanguMoeModel.ragged_narrow_rounds is True
        assert GPTModel.ragged_narrow_rounds is True
        model, params, geometry = _pangu()
        eng = RaggedPagedContinuousBatchingEngine(model, params, **geometry)
        assert eng.narrow_rows == 8
        assert eng.compile_grid() == [
            f"ragged_step:32:{C}" for C in pow2_grid(eng.MB)] \
            + [f"ragged_step:8:{eng.MB}"]
        eng.add_request(list(range(1, 12)), 6)
        eng.run_to_completion(max_ticks=100)
        assert 0 < eng.narrow_steps < eng.ragged_steps

    # sha256 of the GPT tick's lowering, by (interpreted kernel, dtype):
    # 3 slots, 64 positions in blocks of 8, a 24-row budget, 4 table
    # columns; under the suite's settings (tests/conftest.py: matmul
    # precision "highest").  Taken at the parent of PR 39 (commit 4946d47)
    # and re-taken in PR 43, whose tick takes one packed operand and the
    # stream's key and returns the next key: against the parent's text
    # (41edd05) only the parameters, their slices and the key's split
    # moved (CHANGES.md, PR 43)
    PARENT_TICK = {
        (False, "float32"):
            "b80250b058a90f420a9947e454e663593b766722d1d9df00a89b5b871ffb9476",
        (False, "bfloat16"):
            "78d458ea1cd9fd2ea4d5350362cd6ad8d7ab2e40d68df3ced83071d18bf5b300",
        (True, "float32"):
            "0d24d7ae8bcd917eecccc59c0255f0126ded6ef8e91f6e705649f5ed4bb13c63",
        (True, "bfloat16"):
            "ba6dcc03d62feb0db53e53794f1e80a9ac449b657b8f70bb6a46ccb9330b56b4",
    }

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("interp", [False, True], ids=["xla", "kernel"])
    def test_the_wide_gpt_tick_lowers_as_at_the_parent(self, interp, dtype):
        """A round with a chunk runs the program it ran: the narrow
        program is one more key, not a change to the budget-wide tick."""
        import hashlib
        paddle.seed(3)
        model = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=96,
            compute_dtype=dtype))
        params = {n: p._data for n, p in model.named_parameters()}
        set_flags({"FLAGS_paged_attn_interpret": interp})
        try:
            eng = RaggedPagedContinuousBatchingEngine(
                model, params, max_slots=3, max_len=64, block_size=8,
                num_blocks=12, token_budget=24)
            text = eng._build_ragged_step(24, 4).lower(
                *eng._ragged_scratch_args(4)).as_text()
        finally:
            set_flags({"FLAGS_paged_attn_interpret": False})
        assert hashlib.sha256(text.encode()).hexdigest() \
            == self.PARENT_TICK[interp, dtype]


# ------------------------------------------------------------------------
# A round crosses to the device once each way and runs one program (ISSUE
# 43): one packed int32 operand in, the sampling key kept on the device,
# tokens and tick counters read back as one vector
# ------------------------------------------------------------------------

EXECUTE = "PjRtCpuExecutable::Execute"  # one event a program run (CPU client)


def _programs_run(fn):
    """How many compiled programs ``fn()`` dispatches, counted in a
    profiler trace of the host: the device's own record, so a program
    reached through the jit fast path counts like any other."""
    import glob
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            fn()
        path, = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(path)
        return sum(ev.name == EXECUTE for plane in data.planes
                   for line in plane.lines for ev in line.events)


class TestProgramsOfOneModel:
    """Engines of one model share its program cache, the narrow program
    with the rest: every entry is a ``jax.jit`` function, built in the
    foreground by the round that first needs it and counted as the one
    miss it is, so an engine that brings other operands finds the key and
    ``jax.jit`` compiles the form it lacks."""

    def _serve(self, eng):
        rids = [eng.add_request(p, n)
                for p, n in zip(PROMPTS[:4], [9, 4, 7, 6])]
        got = eng.run_to_completion(max_ticks=200)
        return [got[r] for r in rids]

    def test_one_miss_a_program_and_one_form_of_each(self, model_and_params):
        from paddle_tpu.telemetry import Tracer
        model, params = model_and_params
        model.__dict__.pop("_serving_programs", None)
        tr = Tracer()
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, tracer=tr, **GPT_GEOMETRY)
        first = self._serve(eng)
        keys = sorted(k[1:3] for k in model._serving_programs)
        assert (8, eng.MB) in keys and eng.narrow_steps > 0
        m = eng.metrics()
        assert m["compile_misses"] == len(keys)
        assert sorted(e["key"] for e in tr.events("compile")
                      if not e["hit"]) == sorted(
            f"ragged_step:{T}:{C}" for T, C in keys)
        # wide rounds that follow narrow ones take what those returned
        # (pools, presence, key) as they took their own: no program gains
        # a second compiled form on the serving path, where no counter
        # would see it
        assert self._serve(eng) == first
        assert eng.metrics()["compile_misses"] == m["compile_misses"]
        assert {k[1:3]: run._cache_size()
                for k, run in model._serving_programs.items()} \
            == {k: 1 for k in keys}
        # a second engine of the model finds every program built
        again = RaggedPagedContinuousBatchingEngine(model, params,
                                                    **GPT_GEOMETRY)
        assert self._serve(again) == first
        assert again.metrics()["compile_misses"] == 0

    @pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
    def test_an_engine_with_params_of_another_dtype_is_served(
            self, dtype, model_and_params):
        """The key holds the engine's geometry and nothing of its
        operands' dtypes: the second engine hits every key and each
        program it runs compiles a second form for its params."""
        model, params = model_and_params
        cast = {n: p.astype(dtype) for n, p in params.items()}
        model.__dict__.pop("_serving_programs", None)
        alone = self._serve(RaggedPagedContinuousBatchingEngine(
            model, cast, **GPT_GEOMETRY))
        model.__dict__.pop("_serving_programs", None)
        eng = RaggedPagedContinuousBatchingEngine(model, params,
                                                  **GPT_GEOMETRY)
        self._serve(eng)
        other = RaggedPagedContinuousBatchingEngine(model, cast,
                                                    **GPT_GEOMETRY)
        assert self._serve(other) == alone
        assert other.narrow_steps > 0
        assert other.metrics()["compile_misses"] == 0
        assert {run._cache_size()
                for run in model._serving_programs.values()} == {2}


def _pangu():
    """A tiny latent-attention model (its spec names ``tick_stats``), its
    weights and an engine's geometry."""
    from paddle_tpu.models.pangu_moe import PanguMoeConfig, PanguMoeModel
    paddle.seed(0)
    model = PanguMoeModel(PanguMoeConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=12,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        max_position_embeddings=128, compute_dtype="float32"))
    params = {n: p._data for n, p in model.named_parameters()}
    return model, params, dict(max_slots=3, max_len=64, block_size=8,
                               num_blocks=20, token_budget=32,
                               prompt_buckets=list(range(8, 65, 8)))


GPT_GEOMETRY = dict(max_slots=3, max_len=64, block_size=4,
                    prompt_buckets=[8, 16], token_budget=24)


class TestOneCrossing:
    def test_the_counter_counts_programs(self):
        """The control of ``_programs_run``: k calls of a jitted function
        read k, an eager ``jax.random.split`` (the program ``_next_key``
        dispatched before every round) reads one more each."""
        f = jax.jit(lambda x: x + 1)
        x, key = jnp.zeros(4), jax.random.key(0)
        jax.block_until_ready((f(x), jax.random.split(key)))
        assert _programs_run(lambda: [f(x) for _ in range(3)]) == 3
        assert _programs_run(lambda: (f(x), jax.random.split(key))) == 2

    @pytest.mark.parametrize("case", ["gpt", "gpt-sampled", "gpt-tracer",
                                      "latent", "latent-tracer"])
    def test_a_round_is_one_transfer_one_program_one_read(
            self, case, model_and_params, monkeypatch):
        """Rounds of both programs (budget-wide with a chunk, narrow with
        decode rows only; the latent model keeps one width), with and
        without ``tick_stats`` and a tracer to note them.  Each hands the
        program ONE host array, the packed buffer, which the call's own
        argument handling sends (every other leaf is on the device
        already); makes no transfer beside it — an explicit one is
        counted, an implicit one outside the call raises under the guard
        —; runs ONE program and reads ONE array back."""
        from paddle_tpu.telemetry import Tracer
        from paddle_tpu.serving_paged import _packed_shapes
        from jax._src import array as jax_array
        tracer = Tracer() if case.endswith("tracer") else None
        if case.startswith("latent"):
            model, params, geometry = _pangu()
            assert model.cache_spec().tick_stats
        else:
            (model, params), geometry = model_and_params, GPT_GEOMETRY
            assert not model.cache_spec().tick_stats
        kw = dict(greedy=False, temperature=0.8,
                  key=jax.random.key(5)) if case == "gpt-sampled" else {}
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, tracer=tracer, **geometry, **kw)
        eng.warmup()
        puts, reads, handed = [], [], []
        real_put, real_asarray = jax.device_put, jnp.asarray
        monkeypatch.setattr(jax, "device_put", lambda x, *a, **k:
                            puts.append(x) or real_put(x, *a, **k))
        monkeypatch.setattr(jnp, "asarray", lambda x, *a, **k:
                            puts.append(x) or real_asarray(x, *a, **k))
        # reads: ``np.asarray`` of a device array (on the CPU it goes by
        # the buffer protocol, past every method of the array) and whatever
        # else fetches an array's value (``int()``, ``tolist()``, ...)
        real_np_asarray, real_value = np.asarray, jax_array.ArrayImpl._value
        monkeypatch.setattr(np, "asarray", lambda x, *a, **k: (
            isinstance(x, jax.Array) and reads.append(x.shape),
            real_np_asarray(x, *a, **k))[1])

        def value(self):
            reads.append(self.shape)
            return real_value.fget(self)

        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(value))
        real_prog = eng._ragged_prog

        def prog(C, T=None):    # the call alone may send what it is handed
            run = real_prog(C, T)

            def call(*args):
                words = sum(map(np.prod, _packed_shapes(
                    eng.token_budget if T is None else T, C, eng.S)))
                handed.append([(type(x), x.dtype, x.shape)
                               for x in jax.tree.leaves(args)
                               if not isinstance(x, jax.Array)]
                              == [(np.ndarray, np.int32, (words,))])
                with jax.transfer_guard_host_to_device("allow"):
                    return run(*args)
            return call

        monkeypatch.setattr(eng, "_ragged_prog", prog)
        eng.add_request(list(range(1, 12)), 5)
        eng.add_request(PROMPTS[5], 7)
        rounds = []

        def serve():
            with jax.transfer_guard_host_to_device("disallow"):
                while eng.pending():
                    before = (len(handed), len(puts), len(reads),
                              eng.narrow_steps)
                    eng.step()
                    rounds.append((len(handed) - before[0],
                                   len(puts) - before[1],
                                   len(reads) - before[2],
                                   eng.narrow_steps - before[3]))

        assert _programs_run(serve) == eng.ragged_steps == len(rounds) >= 7
        assert all(r[:3] == (1, 0, 1) for r in rounds), rounds
        assert all(handed)
        narrow = sum(r[3] for r in rounds)
        assert narrow == eng.narrow_steps < len(rounds)
        assert (narrow > 0) == bool(eng.narrow_rows)
        S, names = eng.S, model.cache_spec().tick_stats
        assert set(reads) == {(S + len(names),)}
        if tracer is not None and names:
            ran = [e for e in tracer.events("tick") if e.get("rows")]
            assert ran and all(set(names) <= set(e) for e in ran)

    def test_a_sampled_engine_draws_the_parents_stream(self):
        """The key chain, bit for bit: ``self._key, sub = split(self._key)``
        on the host, then the tick's own ``split(sub)[1]`` — now both
        inside the tick, which hands the next key back.  The sampler here
        draws from its key alone (one token for every slot), so each
        round's tokens say which key the tick sampled with; rounds with a
        chunk and decode-only rounds (the narrow program) share the one
        stream."""
        paddle.seed(11)
        model = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=96,
            compute_dtype="float32"))    # its own program cache
        params = {n: p._data for n, p in model.named_parameters()}
        key = jax.random.key(1234)
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, greedy=False, key=key, **GPT_GEOMETRY)
        draw = lambda k: jax.random.randint(k, (), 0, 97, jnp.int32)
        eng._sample = lambda logits, k: jnp.broadcast_to(
            draw(k), logits.shape[:1])
        emitted = []
        for p, n in zip(PROMPTS[:4], [6, 3, 8, 5]):
            eng.add_request(p, n, on_token=lambda rid, tok, done:
                            emitted[-1].append(tok))
        kinds = set()
        while eng.pending():
            emitted.append([])
            before = eng.ragged_steps, eng.narrow_steps
            eng.step()
            assert eng.ragged_steps == before[0] + 1
            kinds.add(eng.narrow_steps - before[1])
            key, sub = jax.random.split(key)            # _next_key()
            want = int(draw(jax.random.split(sub)[1]))  # the tick's split
            assert emitted[-1] and set(emitted[-1]) == {want}
        assert kinds == {0, 1} and len(emitted) >= 8
        assert (jax.random.key_data(eng._key)
                == jax.random.key_data(key)).all()

    @pytest.mark.parametrize("case", ["gpt", "latent"])
    def test_a_warmed_sampled_engine_draws_what_an_unwarmed_one_draws(
            self, case, model_and_params):
        """The warm-up runs the tick with a constant key and discards the
        key it gets back: it does not advance the stream."""
        if case == "latent":
            model, params, geometry = _pangu()
        else:
            (model, params), geometry = model_and_params, GPT_GEOMETRY
        got = []
        for warm in (True, False):
            eng = RaggedPagedContinuousBatchingEngine(
                model, params, greedy=False, temperature=0.9,
                key=jax.random.key(77), **geometry)
            if warm:
                eng.warmup()
                assert (jax.random.key_data(eng._key)
                        == jax.random.key_data(jax.random.key(77))).all()
            for p, n in zip(PROMPTS[:4], [6, 3, 8, 5]):
                eng.add_request(p, n)
            got.append(eng.run_to_completion(max_ticks=200))
        assert got[0] == got[1]
        greedy = RaggedPagedContinuousBatchingEngine(model, params,
                                                     **geometry)
        for p, n in zip(PROMPTS[:4], [6, 3, 8, 5]):
            greedy.add_request(p, n)
        assert greedy.run_to_completion(max_ticks=200) != got[0]

    @pytest.mark.parametrize("T,C", [(24, 16), (24, 4), (8, 16), (8, 1)],
                             ids=["wide-full", "wide-cut", "narrow-full",
                                  "narrow-cut"])
    def test_the_packed_layout_round_trips(self, T, C):
        """Every field cut from the buffer INSIDE a program equals the
        host array it was filled from — at the widest table and at a
        column cut (``C < MB``), at the budget's rows and at
        ``narrow_rows`` — and the buffer holds ``3 T + S C + 4 S``
        words."""
        from paddle_tpu.serving_paged import _pack_operands, _packed_fields
        S, MB = 3, 16
        rng = np.random.default_rng(T * 100 + C)
        ints = lambda *shape: rng.integers(-5, 1 << 20, shape).astype(
            np.int32)
        table = ints(S, MB)
        host = (ints(T), ints(T), ints(T), table[:, :C], ints(S), ints(S),
                rng.integers(0, 2, S).astype(bool), ints(S))
        buf = _pack_operands(T, C, S, *host)
        assert buf.dtype == np.int32 and buf.shape == (3 * T + S * C
                                                        + 4 * S,)

        @jax.jit
        def cut(packed):
            f = _packed_fields(packed, T, C, S)
            return (*f[:6], f[6] != 0, f[7])

        for got, want in zip(cut(jax.device_put(buf)), host, strict=True):
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(np.asarray(got), want)
        # the mask's words are 0 / 1; emitted counts may come as a list
        assert set(_packed_fields(buf, T, C, S)[6]) <= {0, 1}
        again = _pack_operands(T, C, S, *host[:7], host[7].tolist())
        np.testing.assert_array_equal(again, buf)
