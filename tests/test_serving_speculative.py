"""Speculative continuous batching (RaggedPagedContinuousBatchingEngine with
draft_model= / draft_params= / draft_k=): draft proposals + one verify chunk
per round, per-slot acceptance — outputs must be BIT-LOSSLESS vs the plain
engine (greedy acceptance takes the longest argmax-matching prefix, the
models/_decode.py speculative contract), while a good draft cuts the round
count.  The block size is gcd(max_len, bucket) throughout: the coarsest
paging each geometry allows (tests/test_serving_spec_paged.py pages finer)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTModel
from paddle_tpu.serving import (ContinuousBatchingEngine,
                                RaggedPagedContinuousBatchingEngine)


@pytest.fixture(scope="module")
def models():
    paddle.seed(31)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=3,
                    num_attention_heads=4, max_position_embeddings=96,
                    compute_dtype="float32")
    target = GPTModel(cfg)
    tparams = {n: p._data for n, p in target.named_parameters()}
    dcfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                     num_attention_heads=4, max_position_embeddings=96,
                     compute_dtype="float32")
    draft = GPTModel(dcfg)
    dparams = {n: p._data for n, p in draft.named_parameters()}
    return target, tparams, draft, dparams


PROMPTS = [[5, 17, 3], [40, 2], [9, 9, 9, 9, 1], [61, 8, 30]]
BUDGETS = [12, 6, 9, 4]


class TestSpeculativeEngine:
    def test_lossless_vs_plain_engine(self, models):
        """Random 1-layer draft: every request's tokens equal the plain
        engine's (which equal solo generate) — acceptance only changes how
        fast, never what."""
        target, tparams, draft, dparams = models
        plain = ContinuousBatchingEngine(target, tparams, max_slots=2,
                                         max_len=48, prompt_buckets=[8])
        prids = [plain.add_request(p, n) for p, n in zip(PROMPTS, BUDGETS)]
        want = plain.run_to_completion(max_ticks=300)

        spec = RaggedPagedContinuousBatchingEngine(
            target, tparams, max_slots=2, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=3)
        srids = [spec.add_request(p, n) for p, n in zip(PROMPTS, BUDGETS)]
        got = spec.run_to_completion(max_ticks=300)
        for pr, sr in zip(prids, srids):
            assert got[sr] == want[pr], "speculative engine is not lossless"

    def test_perfect_draft_round_count(self, models):
        """Draft == target: every proposal accepted, so one request of N
        tokens finishes in ceil((N-1)/(K+1)) rounds after admission — the
        observable that catches silent acceptance degradation (the
        round-3 draft-cache-hole bug class)."""
        target, tparams, _, _ = models
        K, N = 3, 13
        spec = RaggedPagedContinuousBatchingEngine(
            target, tparams, max_slots=1, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=target, draft_params=tparams,
            draft_k=K)
        rid = spec.add_request(PROMPTS[0], N)
        got = spec.run_to_completion(max_ticks=100)
        assert len(got[rid]) == N
        assert spec.spec_rounds == -(-(N - 1) // (K + 1)), \
            (spec.spec_rounds, N, K)

    def test_eos_retires_and_slot_reuse_stays_lossless(self, models):
        """EOS mid-round discards the accepted tail; the freed slot's next
        occupant (on both caches) still matches the plain engine."""
        target, tparams, draft, dparams = models
        probe = ContinuousBatchingEngine(target, tparams, max_slots=1,
                                         max_len=48, prompt_buckets=[8])
        pid = probe.add_request(PROMPTS[0], 10)
        full = probe.run_to_completion(max_ticks=100)[pid]
        eos = full[4]
        cut = full.index(eos) + 1

        spec = RaggedPagedContinuousBatchingEngine(
            target, tparams, max_slots=1, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=3, eos_token_id=int(eos))
        r0 = spec.add_request(PROMPTS[0], 10)
        r1 = spec.add_request(PROMPTS[3], 4)
        got = spec.run_to_completion(max_ticks=200)
        assert got[r0] == full[:cut]
        solo = target.generate(tparams, jnp.asarray([PROMPTS[3]], jnp.int32),
                               4, greedy=True)
        assert got[r1] == [int(t) for t in np.asarray(solo)[0]]

    def test_mid_flight_admission_isolated(self, models):
        """A request admitted while another is mid-speculation must not
        perturb it (slot isolation under variable per-row advance)."""
        target, tparams, draft, dparams = models
        spec = RaggedPagedContinuousBatchingEngine(
            target, tparams, max_slots=2, max_len=48, block_size=8,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=3)
        r0 = spec.add_request(PROMPTS[0], 12)
        for _ in range(2):
            spec.step()
        r1 = spec.add_request(PROMPTS[1], 6)
        got = spec.run_to_completion(max_ticks=200)
        for rid, p, n in ((r0, PROMPTS[0], 12), (r1, PROMPTS[1], 6)):
            solo = target.generate(tparams, jnp.asarray([p], jnp.int32), n,
                                   greedy=True)
            assert got[rid] == [int(t) for t in np.asarray(solo)[0]]

    def test_budget_includes_overproposal_slack(self, models):
        target, tparams, draft, dparams = models
        spec = RaggedPagedContinuousBatchingEngine(
            target, tparams, max_slots=1, max_len=20, block_size=4,
            prompt_buckets=[8], draft_model=draft, draft_params=dparams,
            draft_k=4)
        with pytest.raises(ValueError, match="exceeds max_len"):
            spec.add_request([1, 2, 3], 10)   # 8 + 10 + 3 > 20
        spec.add_request([1, 2, 3], 9)        # 8 + 9 + 3 == 20: fits
        spec.add_request([1, 2, 3], 1)        # budget 1: prefill only,
        # no round runs, so no over-proposal slack is charged

    def test_draft_validation(self, models):
        target, tparams, _, _ = models
        paddle.seed(9)
        bad_vocab = GPTModel(GPTConfig(
            vocab_size=50, hidden_size=16, num_layers=1,
            num_attention_heads=4, max_position_embeddings=96,
            compute_dtype="float32"))
        bv = {n: p._data for n, p in bad_vocab.named_parameters()}
        with pytest.raises(ValueError, match="vocab"):
            RaggedPagedContinuousBatchingEngine(
                target, tparams, max_slots=1, max_len=32, block_size=8,
                prompt_buckets=[8], draft_model=bad_vocab, draft_params=bv)
        short_pos = GPTModel(GPTConfig(
            vocab_size=97, hidden_size=16, num_layers=1,
            num_attention_heads=4, max_position_embeddings=16,
            compute_dtype="float32"))
        sp = {n: p._data for n, p in short_pos.named_parameters()}
        with pytest.raises(ValueError, match="DRAFT"):
            RaggedPagedContinuousBatchingEngine(
                target, tparams, max_slots=1, max_len=32, block_size=8,
                prompt_buckets=[8], draft_model=short_pos, draft_params=sp)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_speculative_fuzz_matches_solo(models, seed):
    """Randomized speculative scenarios (draft_k, slots, budgets, staggered
    admission, optional EOS): every request equals solo greedy generate —
    the lossless claim under scheduler composition."""
    target, tparams, draft, dparams = models
    rng = np.random.RandomState(100 + seed)
    K = int(rng.choice([1, 2, 4]))
    eos = int(rng.randint(0, 97)) if rng.rand() < 0.5 else None
    spec = RaggedPagedContinuousBatchingEngine(
        target, tparams, max_slots=int(rng.randint(1, 4)), max_len=48,
        block_size=8, prompt_buckets=[8], draft_model=draft,
        draft_params=dparams, draft_k=K, eos_token_id=eos)
    reqs = []
    for _ in range(int(rng.randint(3, 7))):
        p = [int(t) for t in rng.randint(1, 97, rng.randint(1, 9))]
        n = int(rng.randint(1, 12))
        reqs.append((spec.add_request(p, n), p, n))
        for _ in range(int(rng.randint(0, 3))):
            spec.step()
    got = spec.run_to_completion(max_ticks=500)
    for rid, p, n in reqs:
        solo = target.generate(tparams, jnp.asarray([p], jnp.int32), n,
                               greedy=True)
        want = [int(t) for t in np.asarray(solo)[0]]
        if eos is not None and eos in want:
            want = want[:want.index(eos) + 1]
        assert got[rid] == want, (seed, rid, K, eos)


def test_speculative_engine_int8_target(models):
    """Speculative batching over an int8-cache target (and fp draft): the
    quantized pair flows through the verify chunk's tuple-dispatch writes;
    outputs equal the int8 model's own solo generation."""
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    paddle.seed(31)   # same seed as the fixture target: identical weights
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=3,
                    num_attention_heads=4, max_position_embeddings=96,
                    compute_dtype="float32", kv_cache_dtype="int8")
    target = GPTModel(cfg)
    tparams = {n: p._data for n, p in target.named_parameters()}
    _, _, draft, dparams = models
    spec = RaggedPagedContinuousBatchingEngine(
        target, tparams, max_slots=2, max_len=48, block_size=8,
        prompt_buckets=[8], draft_model=draft, draft_params=dparams, draft_k=3)
    rids = [spec.add_request(p, n) for p, n in zip(PROMPTS[:3], (8, 5, 7))]
    got = spec.run_to_completion(max_ticks=200)
    assert spec.caches[0][0].dtype == jnp.int8
    for rid, p, n in zip(rids, PROMPTS[:3], (8, 5, 7)):
        solo = target.generate(tparams, jnp.asarray([p], jnp.int32), n,
                               greedy=True)
        assert got[rid] == [int(t) for t in np.asarray(solo)[0]], rid


def test_cross_family_moe_target_gpt_draft(models):
    """The engine's draft and target only meet through the mixin contract:
    ERNIE-MoE target + GPT draft (the round-3 cross-family pairing, now on
    the batched scheduler) stays lossless vs the MoE's solo generation."""
    from paddle_tpu.models.ernie_moe import ErnieMoeConfig, ErnieMoeModel
    paddle.seed(41)
    cfg = ErnieMoeConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_attention_heads=4, num_experts=4, top_k=2,
                         max_position_embeddings=96,
                         compute_dtype="float32")
    target = ErnieMoeModel(cfg)
    tparams = {n: p._data for n, p in target.named_parameters()}
    _, _, draft, dparams = models   # GPT 1-layer draft, same vocab
    spec = RaggedPagedContinuousBatchingEngine(
        target, tparams, max_slots=2, max_len=48, block_size=8,
        prompt_buckets=[8], draft_model=draft, draft_params=dparams, draft_k=3)
    rids = [spec.add_request(p, n) for p, n in zip(PROMPTS[:3], (7, 5, 6))]
    got = spec.run_to_completion(max_ticks=200)
    for rid, p, n in zip(rids, PROMPTS[:3], (7, 5, 6)):
        solo = target.generate(tparams, jnp.asarray([p], jnp.int32), n,
                               greedy=True)
        assert got[rid] == [int(t) for t in np.asarray(solo)[0]], rid
