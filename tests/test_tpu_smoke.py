"""TPU smoke suite — Mosaic-path (non-interpret) evidence on real hardware.

Every Pallas kernel runs in ``interpret=True`` on the CPU suite, so a
Mosaic miscompile is invisible there (≙ the reference's device-gated CI,
tools/ci_op_benchmark.sh).  This suite runs the same kernels through the
real Mosaic compiler and checks numerics against the dense/XLA path
on-device.  ``chip_smoke.py`` at the repo root is the quicker proof and the
one the driver runs; tests/test_aot_tpu_compile.py compiles the same
kernels for a described chip with no chip attached.

Run (on the chip only — deselected everywhere else by the ``tpu`` marker):
    PADDLE_TPU_TEST_TPU=1 python -m pytest -m tpu -q tests/test_tpu_smoke.py
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


def _on_tpu():
    import os
    if os.environ.get("PADDLE_TPU_TEST_TPU") != "1":
        return False  # the CPU suite never asks for the backend at import
    return jax.default_backend() == "tpu"


skip_unless_tpu = pytest.mark.skipif(not _on_tpu(),
                                     reason="requires real TPU backend")


def _sync(x):
    """Wait for the device, then hand the value to numpy."""
    return np.asarray(jax.block_until_ready(x))


@skip_unless_tpu
class TestFlashMosaic:
    def _qkv(self, B=2, H=8, L=512, D=64, dtype=jnp.bfloat16, seed=0):
        r = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(r.standard_normal((B, L, H, D)), dtype=dtype)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_fwd_matches_dense(self, causal):
        from paddle_tpu.ops.attention import dense_attention, flash_attention
        q, k, v = self._qkv()
        out_f = _sync(jax.jit(
            lambda a, b, c: flash_attention(a, b, c, causal=causal))(q, k, v))
        out_d = _sync(jax.jit(
            lambda a, b, c: dense_attention(a, b, c, causal=causal))(q, k, v))
        np.testing.assert_allclose(out_f.astype(np.float32),
                                   out_d.astype(np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_flash_bwd_matches_dense(self):
        from paddle_tpu.ops.attention import dense_attention, flash_attention
        q, k, v = self._qkv(L=256)

        def loss_flash(a, b, c):
            return flash_attention(a, b, c, causal=True).astype(
                jnp.float32).sum()

        def loss_dense(a, b, c):
            return dense_attention(a, b, c, causal=True).astype(
                jnp.float32).sum()

        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(_sync(a).astype(np.float32),
                                       _sync(b).astype(np.float32),
                                       rtol=5e-2, atol=5e-2)

    def test_flash_long_sequence_runs(self):
        """L=8192 flash step executes on hardware (long-context proof)."""
        from paddle_tpu.ops.attention import flash_attention
        q, k, v = self._qkv(B=1, L=8192)
        out = _sync(jax.jit(
            lambda a, b, c: flash_attention(a, b, c, causal=True))(q, k, v))
        assert out.shape == (1, 8192, 8, 64)
        assert np.isfinite(out.astype(np.float32)).all()


@skip_unless_tpu
class TestFusedLossMosaic:
    def test_fused_ce_matches_xla(self):
        from paddle_tpu.ops.loss import softmax_cross_entropy_mean
        r = np.random.RandomState(0)
        logits = jnp.asarray(r.standard_normal((8, 128, 1024)), jnp.bfloat16)
        labels = jnp.asarray(r.randint(0, 1024, (8, 128)))

        fused = float(_sync(jax.jit(softmax_cross_entropy_mean)(
            logits, labels)))
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ref = float(_sync(-jnp.take_along_axis(
            lp, labels[..., None], axis=-1).mean()))
        assert abs(fused - ref) < 2e-2, (fused, ref)


@skip_unless_tpu
class TestTrainStepMosaic:
    def test_gpt_train_step_runs_and_descends(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.gpt import (GPTConfig, GPTModel,
                                           make_gpt_train_step)
        from paddle_tpu.optimizer import AdamW

        paddle.seed(0)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        cfg = GPTConfig(vocab_size=2048, hidden_size=256, num_layers=2,
                        num_attention_heads=8, max_position_embeddings=256,
                        compute_dtype="bfloat16")
        model = GPTModel(cfg)
        step, state = make_gpt_train_step(model, AdamW(1e-3), hcg,
                                          remat=False)
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randint(0, 2048, (4, 256)))
        y = jnp.asarray(r.randint(0, 2048, (4, 256)))
        losses = []
        for i in range(8):
            state, loss = step(state, jax.random.key(i), np.float32(1e-3),
                               x, y)
            losses.append(float(_sync(loss)))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses


@skip_unless_tpu
class TestPagedAttentionMosaic:
    def test_paged_decode_matches_gather_fallback(self):
        """The in-kernel block-table walk (scalar-prefetch index maps)
        through the REAL Mosaic compiler vs the XLA gather fallback —
        the serving engine's decode hot path."""
        from paddle_tpu.models._decode import PagedKV, cached_attention
        from paddle_tpu.ops.paged_attention import paged_decode_attention
        r = np.random.RandomState(0)
        S, nh, hd, NB1, bs, C = 8, 12, 64, 33, 32, 8
        pk = jnp.asarray(r.standard_normal((NB1, bs, nh, hd)), jnp.bfloat16)
        pv = jnp.asarray(r.standard_normal((NB1, bs, nh, hd)), jnp.bfloat16)
        table = jnp.asarray(r.randint(0, NB1, (S, C)), jnp.int32)
        t = jnp.asarray(r.randint(0, C * bs, S), jnp.int32)
        pad = jnp.minimum(jnp.asarray(r.randint(0, bs, S), jnp.int32), t)
        q = jnp.asarray(r.standard_normal((S, nh, hd)), jnp.bfloat16)
        got = _sync(jax.jit(lambda *a: paged_decode_attention(*a))(
            q, pk, pv, table, t, pad))
        ref = _sync(jax.jit(lambda q_, k_, v_, t_, p_: cached_attention(
            q_[:, None], PagedKV(k_, table), PagedKV(v_, table), t_,
            pad_lens=p_))(q, pk, pv, t, pad))[:, 0]
        np.testing.assert_allclose(got.astype(np.float32),
                                   ref.astype(np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_ragged_mixed_pack_matches_gather_fallback(self):
        """The ragged mixed prefill+decode kernel (per-ROW table walk)
        through the REAL Mosaic compiler vs the XLA gather fallback —
        the one-program serving step's hot path."""
        from paddle_tpu.ops.ragged_paged_attention import (
            ragged_attention_ref, ragged_paged_attention, ragged_rows)
        r = np.random.RandomState(0)
        S, nh, hd, NB1, bs, C, T = 8, 12, 64, 33, 32, 8, 64
        pk = jnp.asarray(r.standard_normal((NB1, bs, nh, hd)), jnp.bfloat16)
        pv = jnp.asarray(r.standard_normal((NB1, bs, nh, hd)), jnp.bfloat16)
        table = jnp.asarray(r.randint(0, NB1, (S, C)), jnp.int32)
        # mixed pack: prefill chunks + single decode rows + an idle seq
        q_lens = np.array([16, 1, 0, 8, 1, 1, 24, 4])
        cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
        kv = jnp.asarray([q + int(r.randint(0, C * bs - q + 1)) if q else 0
                          for q in q_lens], jnp.int32)
        pad = jnp.asarray([int(r.randint(0, 8)) for _ in range(S)],
                          jnp.int32)
        q = jnp.asarray(r.standard_normal((T, nh, hd)), jnp.bfloat16)
        got = _sync(jax.jit(lambda *a: ragged_paged_attention(*a))(
            q, pk, pv, table, cu, kv, pad))
        rs, rp = ragged_rows(cu, kv, T)
        ref = _sync(jax.jit(lambda *a: ragged_attention_ref(*a))(
            q, pk, pv, table, rs, rp, pad))
        n_real = int(q_lens.sum())
        np.testing.assert_allclose(got[:n_real].astype(np.float32),
                                   ref[:n_real].astype(np.float32),
                                   rtol=2e-2, atol=2e-2)
