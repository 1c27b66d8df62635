"""ZeRO stage 1/2/3 contractual tests on the virtual 8-device mesh.

Oracles (reference methodology, test_dist_base.py:1457):
- loss parity: each stage must reproduce the unsharded run bit-for-tolerance;
- memory contract: per-device optimizer-state bytes shrink ~1/shard;
- found_inf / dynamic loss scale: non-finite steps skip the update and back
  off the scale (check_finite_and_unscale + update_loss_scaling semantics);
- master weights: half params update through fp32 masters.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.zero import (make_zero_train_step,
                                         per_device_state_bytes)
from paddle_tpu.optimizer import Adam

needs8 = pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")


def _mlp_params(seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(r.standard_normal(s).astype(np.float32) * 0.1,
                                dtype=dtype)
    return {"w1": mk(16, 32), "b1": mk(32), "w2": mk(32, 8), "b2": mk(8)}


def _loss_of(params, x, y):
    h = jnp.tanh(x @ params["w1"].astype(jnp.float32)
                 + params["b1"].astype(jnp.float32))
    logits = h @ params["w2"].astype(jnp.float32) + params["b2"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _mesh(sharding, dp=1):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": sharding}
    fleet.fleet.init(is_collective=True, strategy=strategy)
    return fleet.fleet.get_hybrid_communicate_group().mesh


def _batch(seed=1):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.standard_normal((16, 16)).astype(np.float32)),
            jnp.asarray(r.randint(0, 8, 16)))


@needs8
class TestZeroParity:
    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_loss_parity_vs_unsharded(self, stage):
        x, y = _batch()

        def run(sharding, st):
            mesh = _mesh(sharding)
            step, state = make_zero_train_step(
                _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=st)
            losses = []
            for _ in range(5):
                state, loss = step(state, np.float32(1e-2), x, y)
                losses.append(float(loss))
            return losses

        serial = run(1, 1)
        sharded = run(4, stage)
        np.testing.assert_allclose(serial, sharded, rtol=1e-5, atol=1e-6)

    def test_state_bytes_shrink(self):
        x, y = _batch()

        def bytes_at(sharding):
            mesh = _mesh(sharding)
            step, state = make_zero_train_step(
                _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=1)
            state, _ = step(state, np.float32(1e-2), x, y)
            return per_device_state_bytes(state)

        full = bytes_at(1)
        shard4 = bytes_at(4)
        # all params here have a 4-divisible dim → expect ~1/4
        assert shard4 <= full / 4 + 64, (full, shard4)

    def test_unshardable_param_warns(self):
        mesh = _mesh(4)
        params = _mlp_params()
        params["odd"] = jnp.ones((3, 3), jnp.float32)  # no 4-divisible dim
        with pytest.warns(UserWarning, match="no dim divisible"):
            make_zero_train_step(
                lambda p, x, y: _loss_of(p, x, y) + jnp.sum(p["odd"]) * 0.0,
                params, Adam(1e-2), mesh, zero_stage=3)


@needs8
class TestGPTZero:
    @pytest.mark.parametrize("stage", [2, 3])
    def test_gpt_parity_dp_x_sharding(self, stage):
        """Flagship path: GPT under dp2 x sharding4 ZeRO matches serial."""
        from paddle_tpu.models.gpt import GPTConfig, GPTModel, make_gpt_train_step
        from paddle_tpu.optimizer import AdamW

        x = jnp.asarray(np.random.RandomState(0).randint(0, 128, (8, 16)))
        y = jnp.asarray(np.random.RandomState(1).randint(0, 128, (8, 16)))

        def run(dp, sharding, st):
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": 1,
                                       "pp_degree": 1,
                                       "sharding_degree": sharding}
            fleet.fleet.init(is_collective=True, strategy=strategy)
            hcg = fleet.fleet.get_hybrid_communicate_group()
            paddle.seed(11)
            cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_attention_heads=2, max_position_embeddings=32,
                            compute_dtype="float32")
            model = GPTModel(cfg)
            step, state = make_gpt_train_step(model, AdamW(1e-3), hcg,
                                              remat=False, zero_stage=st)
            losses = []
            for i in range(3):
                state, loss = step(state, jax.random.key(0), np.float32(1e-3),
                                   x, y)
                losses.append(float(loss))
            return losses

        serial = run(1, 1, 1)
        sharded = run(2, 4, stage)
        np.testing.assert_allclose(serial, sharded, rtol=2e-5, atol=1e-6)


    def test_zero3_step_gathers_one_layer_and_matches_one_device(self):
        """The compiled ZeRO-3 step of a GPT whose blocks are stacked for
        the layer scan (L = 8, and no other dim of it is 8): inside the
        scan's loops no all-gather has the layer count among its dims (one
        layer an iteration, never the stack), the block weights ARE
        gathered there, and the block gradients are reduced over the four
        devices (the CPU compiler writes a reduce-scatter as an all-reduce
        and a slice).  Loss and updated parameters are the one-device
        step's."""
        from paddle_tpu.distributed.sharding_rules import loop_collectives
        from paddle_tpu.models.gpt import (GPTConfig,
                                           make_sharded_gpt_train_step)
        from paddle_tpu.optimizer import Momentum

        L, H, I = 8, 64, 128
        cfg = GPTConfig(vocab_size=128, hidden_size=H, num_layers=L,
                        num_attention_heads=2, intermediate_size=I,
                        max_position_embeddings=256,
                        compute_dtype="float32")
        # 256 positions a row: the rows a device owns outweigh a layer's
        # weights, as they do at real sizes, so the partitioner moves the
        # weights and not the rows
        x = jnp.asarray(np.random.RandomState(0).randint(0, 128, (4, 256)))
        y = jnp.asarray(np.random.RandomState(1).randint(0, 128, (4, 256)))
        lr = np.float32(0.1)

        def run(sharding, stage):
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                       "pp_degree": 1,
                                       "sharding_degree": sharding}
            fleet.fleet.init(is_collective=True, strategy=strategy)
            hcg = fleet.fleet.get_hybrid_communicate_group()
            step, state = make_sharded_gpt_train_step(
                cfg, Momentum(0.1, momentum=0.9), hcg, zero_stage=stage,
                seed=3, remat="dots", donate=False)
            text = step.lower(state, lr, jax.random.key(0), x,
                              y).compile().as_text()
            losses = []
            for _ in range(3):
                state, loss = step(state, lr, jax.random.key(0), x, y)
                losses.append(float(loss))
            return losses, state["params"], text

        serial, p1, _ = run(1, 0)
        sharded, p4, text = run(4, 3)
        np.testing.assert_allclose(serial, sharded, rtol=2e-5, atol=1e-6)
        for k in p1:
            np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p4[k]),
                                       rtol=2e-5, atol=2e-6, err_msg=k)
            if k.startswith("blocks_"):    # the scanned axis stays whole
                assert p4[k].sharding.shard_shape(p4[k].shape)[0] == L, k

        rows = loop_collectives(text)

        def weights_of(ops):
            """Result dims of those collectives in the loops, as sorted
            (rows, columns) of a block matrix (a leading 1 dropped)."""
            return {tuple(sorted(d[-2:])) for r in rows if r["op"] in ops
                    for d in r["dims"] if len(d) >= 2}

        gathers = [d for r in rows if r["op"] == "all-gather"
                   for d in r["dims"]]
        assert gathers and all(L not in d for d in gathers), gathers
        matrices = {(H, 3 * H), (H, H), (H, I)}   # qkv, proj, fc1 and fc2
        assert matrices <= weights_of(("all-gather",))
        assert matrices <= weights_of(("all-reduce", "reduce-scatter"))


@needs8
class TestLossScaling:
    def test_found_inf_skips_update_and_backs_off(self):
        mesh = _mesh(4)
        step, state = make_zero_train_step(
            _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=2,
            dynamic_loss_scale=True, init_loss_scale=1024.0)
        x, y = _batch()
        bad_x = x.at[0, 0].set(jnp.inf)
        p_before = jax.tree_util.tree_map(np.asarray, state["params"])
        state, loss = step(state, np.float32(1e-2), bad_x, y)
        assert bool(state["scaler"]["found_inf"])
        assert float(state["scaler"]["scale"]) == 512.0
        assert int(state["opt"]["step"]) == 0
        for k, v in state["params"].items():
            np.testing.assert_array_equal(np.asarray(v), p_before[k])
        # a following finite step proceeds normally
        state, loss = step(state, np.float32(1e-2), x, y)
        assert not bool(state["scaler"]["found_inf"])
        assert int(state["opt"]["step"]) == 1

    def test_scale_grows_after_interval(self):
        mesh = _mesh(4)
        step, state = make_zero_train_step(
            _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=1,
            dynamic_loss_scale=True, init_loss_scale=256.0, growth_interval=2)
        x, y = _batch()
        for _ in range(2):
            state, _ = step(state, np.float32(1e-2), x, y)
        assert float(state["scaler"]["scale"]) == 512.0
        assert int(state["scaler"]["good_steps"]) == 0


@needs8
class TestMasterWeights:
    def test_bf16_params_track_fp32_master(self):
        mesh = _mesh(4)
        step, state = make_zero_train_step(
            _loss_of, _mlp_params(dtype=jnp.bfloat16), Adam(1e-2), mesh,
            zero_stage=2)
        assert state["master"], "half params must enable master weights"
        x, y = _batch()
        for _ in range(3):
            state, loss = step(state, np.float32(1e-2), x, y)
        for k, m in state["master"].items():
            assert m.dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(state["params"][k]),
                np.asarray(m.astype(jnp.bfloat16)))
        assert np.isfinite(float(loss))


@needs8
class TestShardedInit:
    """make_sharded_gpt_train_step: params initialize DIRECTLY sharded on
    the mesh (no host-side full-size copy — the 6.7B enabler)."""

    def test_shards_and_trains(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.gpt import (GPTConfig,
                                           make_sharded_gpt_train_step)
        from paddle_tpu.optimizer import AdamW

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 8}
        fleet.fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.fleet.get_hybrid_communicate_group()

        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_attention_heads=4, max_position_embeddings=64,
                        compute_dtype="float32")
        step, state = make_sharded_gpt_train_step(cfg, AdamW(1e-3), hcg,
                                                  zero_stage=3)
        w = state["params"]["blocks_fc1_w"]
        full = int(np.prod(w.shape))
        assert int(np.prod(w.addressable_shards[0].data.shape)) == full // 8
        m1 = state["opt"]["slots"]["blocks_fc1_w"]["moment1"]
        assert int(np.prod(m1.addressable_shards[0].data.shape)) == full // 8

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, 512, (8, 32)))
        losses = []
        for i in range(5):
            state, loss = step(state, np.float32(1e-3), jax.random.key(i),
                               x, x)
            losses.append(float(np.asarray(loss)))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses

    def test_bert_and_ernie_sharded_init(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.distributed import fleet
        from paddle_tpu.models.bert import (BertConfig,
                                            make_sharded_bert_train_step)
        from paddle_tpu.models.ernie_moe import (
            ErnieMoeConfig, make_sharded_ernie_moe_train_step)
        from paddle_tpu.optimizer import AdamW

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 8}
        fleet.fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.fleet.get_hybrid_communicate_group()
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, 512, (8, 32)))

        cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, max_position_embeddings=64,
                         compute_dtype="float32")
        step, state = make_sharded_bert_train_step(cfg, AdamW(1e-3), hcg,
                                                   zero_stage=3)
        w = state["params"]["blocks_fc1_w"]
        assert int(np.prod(w.addressable_shards[0].data.shape)) \
            == int(np.prod(w.shape)) // 8
        nsp = jnp.asarray(rng.randint(0, 2, (8,)))
        state, loss = step(state, np.float32(1e-3), ids, ids, nsp)
        assert np.isfinite(float(np.asarray(loss)))

        ecfg = ErnieMoeConfig(vocab_size=512, hidden_size=64, num_layers=2,
                              num_attention_heads=4, num_experts=4,
                              max_position_embeddings=64,
                              compute_dtype="float32")
        estep, estate = make_sharded_ernie_moe_train_step(
            ecfg, AdamW(1e-3), hcg, zero_stage=3)
        estate, eloss = estep(estate, np.float32(1e-3), ids, ids)
        assert np.isfinite(float(np.asarray(eloss)))


class TestOffload:
    """sharding_configs offload=True: optimizer state in host memory, update
    on the host backend (≙ reference DygraphShardingOptimizer offload)."""

    @needs8
    def test_loss_and_param_parity_vs_on_device(self):
        x, y = _batch()
        mesh = _mesh(4)
        step_d, state_d = make_zero_train_step(
            _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=1)
        step_h, state_h = make_zero_train_step(
            _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=1,
            offload=True)
        for i in range(3):
            state_d, loss_d = step_d(state_d, np.float32(1e-2), x, y)
            state_h, loss_h = step_h(state_h, np.float32(1e-2), x, y)
            np.testing.assert_allclose(float(loss_d), float(loss_h),
                                       rtol=1e-5, atol=1e-6, err_msg=f"step {i}")
        for k in state_d["params"]:
            np.testing.assert_allclose(np.asarray(state_d["params"][k]),
                                       np.asarray(state_h["params"][k]),
                                       rtol=2e-5, atol=2e-6, err_msg=k)

    @needs8
    def test_optimizer_state_lives_on_host(self):
        mesh = _mesh(4)
        step, state = make_zero_train_step(
            _loss_of, _mlp_params(dtype=jnp.bfloat16), Adam(1e-2), mesh,
            zero_stage=1, offload=True)
        cpu0 = jax.devices("cpu")[0]
        for leaf in jax.tree_util.tree_leaves(state["opt"]["slots"]):
            assert leaf.devices() == {cpu0}, leaf.devices()
        for leaf in jax.tree_util.tree_leaves(state["master"]):
            assert leaf.devices() == {cpu0}
        # params stay on the mesh (half dtype → fp32 masters exist)
        assert state["master"], "bf16 params must have host masters"
        x, y = _batch()
        state, loss = step(state, np.float32(1e-2), x, y)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(state["opt"]["slots"]):
            assert leaf.devices() == {cpu0}  # stays host-resident post-step

    @needs8
    def test_found_inf_skips_update(self):
        mesh = _mesh(2)
        step, state = make_zero_train_step(
            _loss_of, _mlp_params(), Adam(1e-2), mesh, zero_stage=1,
            offload=True)
        before = {k: np.asarray(v) for k, v in state["params"].items()}
        x, y = _batch()
        bad = x.at[0, 0].set(jnp.inf)      # inf input -> non-finite grads
        state, _ = step(state, np.float32(1e-2), bad, y)
        for k, v in state["params"].items():
            np.testing.assert_array_equal(np.asarray(v), before[k], err_msg=k)
