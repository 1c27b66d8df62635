"""Sharding-rules layer tests (distributed/sharding_rules.py +
distributed/update_sharding.py).

Three concerns:

1. Resolver semantics — first-match ordering, scalar exemption, unmatched
   and indivisible policies (with replication-fallback accounting in the
   stats registry), rank fitting, optimizer-state and KV-pool trees.
2. Digest stability — rule-content digests, spec-tree digests, and the
   process-global ``sharding_rules_digest`` that jit/aot.py folds into
   executable-cache environments.
3. Trainer parity pins — the five re-based trainers must lower exactly as
   before the move (where specs came from functions moved verbatim, the
   pin is ``is``-identity on the re-exported functions: the same function
   object computes the same specs), and the NEW weight-update-sharded DP
   trainer must be loss- and param-identical to the replicated GSPMD
   baseline over a 10-step run while holding ~R× less optimizer HBM.
"""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from paddle_tpu.distributed import sharding_rules as sr
from paddle_tpu.distributed.update_sharding import (
    make_dp_update_sharded_train_step, update_sharding_rules)
from paddle_tpu.utils.stats import get_all_stats


def _mesh(n, names=("data",), shape=None):
    devs = np.array(jax.devices()[:n])
    if shape is not None:
        devs = devs.reshape(shape)
    return Mesh(devs, names)


# (data, sharding) -> the axes a batch of 8 rows is split over: "data" first,
# then "sharding", each where its size is over 1 and still divides the rows
BATCH_AXES_CASES = [
    (1, 1, ()), (1, 2, ("sharding",)), (1, 4, ("sharding",)),
    (2, 1, ("data",)), (2, 2, ("data", "sharding")),
    (2, 4, ("data", "sharding")),
    (4, 1, ("data",)), (4, 2, ("data", "sharding")),
    (4, 4, ("data",)),                       # 16 does not divide 8
]

GPT_STACKED = ["blocks_ln1_w", "blocks_ln1_b", "blocks_qkv_w", "blocks_qkv_b",
               "blocks_proj_w", "blocks_proj_b", "blocks_ln2_w",
               "blocks_ln2_b", "blocks_fc1_w", "blocks_fc1_b",
               "blocks_fc2_w", "blocks_fc2_b"]
ERNIE_STACKED = ["blocks_ln1_w", "blocks_ln1_b", "blocks_qkv_w",
                 "blocks_qkv_b", "blocks_proj_w", "blocks_proj_b",
                 "blocks_ln2_w", "blocks_ln2_b", "blocks_gate_w",
                 "blocks_expert_w1", "blocks_expert_b1", "blocks_expert_w2",
                 "blocks_expert_b2"]
UNSTACKED = ["wte", "wpe", "lnf_w", "lnf_b"]
STACKED_CASES = [("gpt", n) for n in GPT_STACKED] + \
    [("ernie", n) for n in ERNIE_STACKED]
UNSTACKED_CASES = [(m, n) for m in ("gpt", "ernie") for n in UNSTACKED]


def _fallback_stats():
    s = get_all_stats()
    return (s.get("sharding_replicated_fallback_bytes", 0),
            s.get("sharding_replicated_fallback_leaves", 0))


# ==========================================================================
# 1. resolver semantics
# ==========================================================================

class TestResolver:
    def test_first_match_wins_in_rule_order(self):
        rules = sr.ShardingRules([
            (r"w", ("data",)),
            (r".*", None),
        ])
        assert rules.spec_for("w1", np.zeros((8,))) == P("data")
        assert rules.spec_for("b1", np.zeros((8,))) == P()
        # reversed order: the general rule shadows the specific one
        shadowed = sr.ShardingRules([(r".*", None), (r"w", ("data",))])
        assert shadowed.spec_for("w1", np.zeros((8,))) == P()

    def test_search_not_fullmatch(self):
        rules = sr.ShardingRules([(r"attn/", ("model",))],
                                 unmatched="raise")
        assert rules.spec_for("block0/attn/wq",
                              np.zeros((4, 4))) == P("model")

    def test_scalar_and_size1_leaves_are_always_replicated(self):
        rules = sr.ShardingRules([(r".*", ("data",))])
        assert rules.spec_for("step", np.zeros(())) == P()
        assert rules.spec_for("beta_pow", np.zeros((1,))) == P()
        # the same rule DOES shard a real vector
        assert rules.spec_for("v", np.zeros((8,))) == P("data")

    def test_unmatched_raise_names_the_path(self):
        rules = sr.ShardingRules([(r"^w$", ("data",))], name="strict")
        with pytest.raises(ValueError, match=r"no rule matches path 'b'"):
            rules.spec_for("b", np.zeros((8,)))

    def test_unmatched_replicate_warns_and_accounts_bytes(self):
        rules = sr.ShardingRules([(r"^w$", ("data",))],
                                 unmatched="replicate")
        b0, l0 = _fallback_stats()
        leaf = np.zeros((8,), np.float32)     # 32 bytes
        with pytest.warns(UserWarning, match="stays fully replicated"):
            assert rules.spec_for("b", leaf) == P()
        b1, l1 = _fallback_stats()
        assert b1 - b0 == leaf.nbytes
        assert l1 - l0 == 1

    def test_indivisible_replicate_drops_the_axis_with_accounting(self):
        mesh = _mesh(2)
        rules = sr.ShardingRules([(r".*", ("data",))], mesh=mesh)
        b0, _ = _fallback_stats()
        with pytest.warns(UserWarning, match="indivisible|replicated"):
            spec = rules.spec_for("odd", np.zeros((7, 4), np.float32))
        assert spec == P()                    # dropped entry, squeezed
        b1, _ = _fallback_stats()
        assert b1 - b0 == 7 * 4 * 4
        # divisible leaf under the same rules still shards
        assert rules.spec_for("even", np.zeros((8, 4))) == P("data")

    def test_indivisible_raise(self):
        rules = sr.ShardingRules([(r".*", ("data",))], mesh=_mesh(2),
                                 indivisible="raise")
        with pytest.raises(ValueError, match="does not divide dim 0"):
            rules.spec_for("odd", np.zeros((7,)))

    def test_rank_fit_trims_pads_and_squeezes(self):
        rules = sr.ShardingRules([(r".*", ("data", None))])
        # 1-D leaf: trailing entry trimmed
        assert rules.spec_for("v", np.zeros((8,))) == P("data")
        # 3-D leaf: padded with None then squeezed back
        assert rules.spec_for("t", np.zeros((8, 4, 2))) == P("data")
        # squeeze keeps equality rank-independent: P("data", None) never
        # leaks out of the resolver
        assert rules.spec_for("m", np.zeros((8, 4))) == P("data")

    def test_tuple_axis_entry_divisibility_uses_product_degree(self):
        mesh = _mesh(4, ("data", "model"), shape=(2, 2))
        rules = sr.ShardingRules([(r".*", (("data", "model"),))], mesh=mesh)
        assert rules.spec_for("v", np.zeros((8,))) == P(("data", "model"))
        with pytest.warns(UserWarning):
            assert rules.spec_for("odd", np.zeros((6,))) == P()

    def test_rule_spec_forms_are_equivalent(self):
        leaf = np.zeros((8, 4))
        for form in [P("data"), ("data",), ["data"]]:
            assert sr.ShardingRules([(r".*", form)]).spec_for(
                "x", leaf) == P("data")
        for form in [None, P(), ()]:
            assert sr.ShardingRules([(r".*", form)]).spec_for(
                "x", leaf) == P()

    def test_bad_policy_and_bad_spec_type_raise(self):
        with pytest.raises(ValueError, match="unmatched"):
            sr.ShardingRules([], unmatched="bogus")
        with pytest.raises(ValueError, match="indivisible"):
            sr.ShardingRules([], indivisible="bogus")
        with pytest.raises(TypeError, match="rule spec"):
            sr.ShardingRules([(r".*", 5)])

    def test_resolve_preserves_tree_structure(self):
        tree = {"a": {"w": np.zeros((8, 4)), "b": np.zeros((4,))},
                "n": [np.zeros((8,)), np.zeros(())]}
        specs = sr.ShardingRules([
            (r"/w$", ("data", None)),
            (r".*", None),
        ]).resolve(tree)
        assert specs == {"a": {"w": P("data"), "b": P()},
                         "n": [P(), P()]}

    def test_resolve_state_slots_inherit_their_params_rule(self):
        """Optimizer slots resolve under ``params/<pname>`` so ONE rule
        table covers params and their moments; scalar slot leaves (beta
        powers) stay exempt and opt/step is pinned replicated."""
        state = {
            "params": {"w": np.zeros((8, 4)), "b": np.zeros((4,))},
            "opt": {"step": np.zeros(()),
                    "slots": {"w": {"m": np.zeros((8, 4)),
                                    "beta1_pow": np.zeros((1,))},
                              "b": {"m": np.zeros((4,))}}},
            "buffers": {},
        }
        specs = sr.ShardingRules([
            (r"^params/w$", ("data", None)),
            (r".*", None),
        ]).resolve_state(state)
        assert specs["params"] == {"w": P("data"), "b": P()}
        assert specs["opt"]["step"] == P()
        assert specs["opt"]["slots"]["w"]["m"] == P("data")
        assert specs["opt"]["slots"]["w"]["beta1_pow"] == P()
        assert specs["opt"]["slots"]["b"]["m"] == P()

    def test_kv_pool_tree_resolves_like_any_pytree(self):
        """KV-cache pools are plain trees to the resolver: page pools
        shard their head dim on 'model', everything else replicates."""
        pool = {"layers": [{"k": np.zeros((16, 8, 4, 64)),
                            "v": np.zeros((16, 8, 4, 64))} for _ in range(2)],
                "page_table": np.zeros((32,), np.int32)}
        specs = sr.ShardingRules([
            (r"layers/\d+/[kv]$", (None, None, "model", None)),
            (r"page_table", None),
        ]).resolve(pool)
        assert specs["layers"][0]["k"] == P(None, None, "model")
        assert specs["layers"][1]["v"] == P(None, None, "model")
        assert specs["page_table"] == P()

    def test_shardings_builds_namedshardings_on_the_mesh(self):
        mesh = _mesh(2)
        tree = {"w": np.zeros((8, 4)), "s": np.zeros(())}
        sh = sr.ShardingRules([(r".*", ("data",))],
                              mesh=mesh).shardings(tree)
        assert sh["w"] == NamedSharding(mesh, P("data"))
        assert sh["s"] == NamedSharding(mesh, P())
        # unbound rules need an explicit mesh
        with pytest.raises(ValueError, match="needs a mesh"):
            sr.ShardingRules([(r".*", None)]).shardings(tree)

    def test_match_partition_rules_functional_shorthand(self):
        tree = {"w": np.zeros((8,)), "b": np.zeros((4,))}
        specs = sr.match_partition_rules(
            [(r"w", ("data",)), (r".*", None)], tree)
        assert specs == {"w": P("data"), "b": P()}
        with pytest.raises(ValueError, match="no rule matches"):
            sr.match_partition_rules([(r"w", ("data",))], tree)


class TestSpecConstructors:
    def test_make_and_replicated(self):
        assert sr.make_spec("data", None) == P("data", None)
        assert sr.replicated_spec() == P()

    def test_replica_stacked_spec_pads_to_leaf_rank(self):
        assert sr.replica_stacked_spec(np.zeros((4, 2, 3)),
                                       "data") == P("data", None, None)
        assert sr.replica_stacked_spec(np.zeros((4,)), "data") == P("data")

    def test_batch_spec_without_a_batch_size_takes_every_axis_over_one(self):
        assert sr.batch_spec(_mesh(2)) == P("data")
        assert sr.batch_spec(_mesh(1)) == P()
        # "model" is no data-parallel axis: it never carries the batch
        assert sr.batch_spec(_mesh(2, ("model",))) == P()
        assert sr.batch_spec(
            _mesh(4, ("data", "sharding"), shape=(2, 2))) == \
            P(("data", "sharding"))

    def test_activation_batch_spec_per_mesh_shape(self):
        assert sr.activation_batch_spec(_mesh(2)) == P("data", None, None)
        assert sr.activation_batch_spec(
            _mesh(2, ("data", "sep"), shape=(1, 2))) == P(None, "sep", None)
        assert sr.activation_batch_spec(
            _mesh(4, ("data", "sep"), shape=(2, 2))) == P("data", "sep", None)
        assert sr.activation_batch_spec(
            _mesh(2, ("sharding",))) == P("sharding", None, None)
        assert sr.activation_batch_spec(_mesh(1)) is None

    @pytest.mark.parametrize("data,sharding,axes", BATCH_AXES_CASES,
                             ids=[f"data{d}-sharding{s}"
                                  for d, s, _ in BATCH_AXES_CASES])
    def test_batch_axes_agree_everywhere(self, data, sharding, axes):
        """One helper names the axes a batch of 8 is split over; the batch
        spec, the activation spec and the attention kernels' shard_map
        specs all say the same."""
        mesh = AbstractMesh((data, sharding), ("data", "sharding"))
        entry = axes or None
        assert sr.data_parallel_axes(mesh, 8) == axes
        assert sr.batch_spec(mesh, 8) == (P(axes) if axes else P())
        assert sr.activation_batch_spec(mesh, 8) == \
            (P(entry, None, None) if axes else None)
        specs, sharded = sr.attention_specs(mesh, 8, 4)
        assert specs["qkv"] == P(entry, None, None, None)
        assert specs["kmask"] == P(entry, None)
        assert specs["stat"] == P(entry, None, None)
        assert sharded == axes

    def test_constrain_helpers_leave_a_single_replica_alone(self):
        x = jnp.zeros((8, 4, 2))
        assert sr.constrain_batch(x, None) is x
        assert sr.constrain_batch(x, _mesh(1)) is x
        assert sr.constrain_activation(x, _mesh(1)) is x
        key = jax.random.key(0)
        assert sr.constrain_batch(
            key, _mesh(2, ("sharding",))) is key      # a scalar: no rows
        mesh = _mesh(2, ("sharding",))
        out = jax.jit(lambda a: sr.constrain_batch(a, mesh))(x)
        assert out.sharding.spec == P("sharding")
        odd = jnp.zeros((3, 4))                       # 2 does not divide 3
        assert sr.constrain_batch(odd, mesh) is odd

    def test_sep_activation_spec(self):
        assert sr.sep_activation_spec() == P(None, "sep", None, None)
        assert sr.sep_activation_spec(ndim=3) == P(None, "sep", None)

    def test_override_leading_axis(self):
        assert sr.override_leading_axis(P(None, "model"), 3,
                                        "pipe") == P("pipe", "model", None)
        assert sr.override_leading_axis(P(), 2, "pipe") == P("pipe", None)

    def test_resolve_flat_shard_spec_divisible(self):
        assert sr.resolve_flat_shard_spec("r", 8, _mesh(2),
                                          "data") == P("data")
        # trivial axis: replicated WITHOUT fallback noise (nothing lost)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert sr.resolve_flat_shard_spec("r", 8, _mesh(1),
                                              "data") == P()

    def test_resolve_flat_shard_spec_indivisible_accounts(self):
        b0, l0 = _fallback_stats()
        with pytest.warns(UserWarning, match="stays fully replicated"):
            spec = sr.resolve_flat_shard_spec("resid", 7, _mesh(2), "data")
        assert spec == P()
        b1, l1 = _fallback_stats()
        assert b1 - b0 == 7 * 4 and l1 - l0 == 1

    def test_replication_fallback_emits_tracer_event(self):
        events = []

        class Tracer:
            def emit(self, event, **kw):
                events.append((event, kw))

        with pytest.warns(UserWarning):
            sr.replication_fallback("unit-test", "x", 128, axis="data",
                                    degree=2, tracer=Tracer())
        assert events == [("sharding_fallback",
                           {"kind": "unit-test", "name": "x", "bytes": 128,
                            "axis": "data", "degree": 2})]


# ==========================================================================
# 2. digest stability
# ==========================================================================

class TestDigests:
    def test_rules_digest_is_content_not_name(self):
        a = sr.ShardingRules([(r"w", ("data",))], name="a")
        b = sr.ShardingRules([(r"w", ("data",))], name="b")
        assert a.digest() == b.digest()
        assert re.fullmatch(r"[0-9a-f]{32}", a.digest())

    def test_rules_digest_is_order_policy_and_spec_sensitive(self):
        base = sr.ShardingRules([(r"w", ("data",)), (r".*", None)])
        assert base.digest() != sr.ShardingRules(
            [(r".*", None), (r"w", ("data",))]).digest()
        assert base.digest() != sr.ShardingRules(
            [(r"w", ("model",)), (r".*", None)]).digest()
        assert base.digest() != sr.ShardingRules(
            [(r"w", ("data",)), (r".*", None)],
            unmatched="replicate").digest()

    def test_rules_digest_equates_spec_forms(self):
        assert sr.ShardingRules([(r"w", ("data", None))]).digest() == \
            sr.ShardingRules([(r"w", P("data", None))]).digest()
        assert sr.ShardingRules([(r"w", None)]).digest() == \
            sr.ShardingRules([(r"w", P())]).digest()

    def test_spec_tree_digest_stable_and_content_sensitive(self):
        t1 = {"a": P("data"), "b": {"c": None}}
        t2 = {"b": {"c": None}, "a": P("data")}       # key order irrelevant
        assert sr.spec_tree_digest(t1) == sr.spec_tree_digest(t2)
        assert sr.spec_tree_digest(t1) != sr.spec_tree_digest(
            {"a": P("model"), "b": {"c": None}})
        # None vs P() are DIFFERENT digest inputs (None means
        # "unconstrained", P() means "replicated")
        assert sr.spec_tree_digest({"a": None}) != \
            sr.spec_tree_digest({"a": P()})

    def test_global_digest_tracks_registration(self):
        d0 = sr.sharding_rules_digest()
        rules = sr.ShardingRules([(r".*", ("data",))], name="test_digest")
        try:
            sr.register_rules(rules)
            d1 = sr.sharding_rules_digest()
            assert d1 != d0
            # idempotent: re-registering identical content changes nothing
            sr.register_rules(rules)
            assert sr.sharding_rules_digest() == d1
        finally:
            sr.unregister_rules("test_digest")
        assert sr.sharding_rules_digest() == d0


# ==========================================================================
# 3. trainer parity pins
# ==========================================================================

class TestTrainerParityPins:
    """The five re-based trainers import their spec logic from
    sharding_rules.  Where the functions moved VERBATIM, ``is``-identity
    is the strongest possible parity pin: the trainer calls the same
    function object, so it computes byte-identical specs and lowers
    identically.  (Behavioral 10-step parity for the one NEW trainer is
    TestUpdateSharding below; the five existing trainers keep their own
    suites in test_distributed.py et al.)"""

    def test_spmd_rebased_on_sharding_rules(self):
        from paddle_tpu.distributed import spmd
        assert spmd.build_param_specs is sr.build_param_specs
        assert spmd.build_state_shardings is sr.build_state_shardings
        assert spmd.batch_spec is sr.batch_spec

    def test_zero_rebased_on_sharding_rules(self):
        from paddle_tpu.distributed import zero
        assert zero.build_param_specs is sr.build_param_specs
        assert zero.resolve_flat_shard_spec is sr.resolve_flat_shard_spec

    def test_localsgd_and_dgc_rebased_on_sharding_rules(self):
        from paddle_tpu.distributed import dgc, localsgd
        assert localsgd.replica_stacked_spec is sr.replica_stacked_spec
        assert dgc.replica_stacked_spec is sr.replica_stacked_spec

    def test_pipeline_engine_rebased_via_spmd_reexport(self):
        from paddle_tpu.distributed import pipeline_engine
        assert pipeline_engine.build_param_specs is sr.build_param_specs
        assert pipeline_engine.build_state_shardings is \
            sr.build_state_shardings

    def test_build_param_specs_tp_pp_zero_semantics(self):
        """The moved inference still honors _dims_mapping / _pipe_stacked /
        zero_stage — the catalog rows 'tp', 'pp', 'zero3'."""
        mesh = _mesh(4, ("data", "model"), shape=(2, 2))

        class Leaf(np.ndarray):
            pass

        w = np.zeros((8, 6), np.float32).view(Leaf)
        w._dims_mapping = {1: "model"}
        odd = np.zeros((8, 5), np.float32).view(Leaf)
        odd._dims_mapping = {1: "model"}      # 5 % 2 != 0 -> dropped
        specs = sr.build_param_specs({"w": w, "odd": odd, "b":
                                      np.zeros((4,), np.float32)}, mesh)
        assert specs["w"] == P(None, "model")
        assert specs["odd"] == P(None, None)
        assert specs["b"] == P(None)


class _MLP:
    """Tiny 2-layer MLP as a functional loss for the parity run."""

    @staticmethod
    def params(seed=0):
        rng = np.random.default_rng(seed)
        return {
            "w1": jnp.asarray(rng.normal(size=(8, 16)) * 0.1, jnp.float32),
            "b1": jnp.zeros((16,), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(16, 4)) * 0.1, jnp.float32),
            "b2": jnp.zeros((4,), jnp.float32),
        }

    @staticmethod
    def loss(params, x, y):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        p = h @ params["w2"] + params["b2"]
        return jnp.mean((p - y) ** 2)

    @staticmethod
    def batch(seed=1):
        rng = np.random.default_rng(seed)
        return (jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
                jnp.asarray(rng.normal(size=(4, 4)), jnp.float32))


class TestUpdateSharding:
    """arXiv:2004.13336 equivalence: reduce-scatter + shard update +
    all-gather must be step-for-step identical to all-reduce + replicated
    update, with the optimizer state held at 1/R per replica."""

    def test_rule_table_layout(self):
        tree = {"params": {"w": np.zeros((8,))},
                "opt": {"step": np.zeros(()),
                        "slots": {"flat": np.zeros((16,))}},
                "comm_e": np.zeros((2, 16))}
        specs = update_sharding_rules("data").resolve(tree)
        assert specs["params"]["w"] == P()
        assert specs["opt"]["step"] == P()
        assert specs["opt"]["slots"]["flat"] == P("data")
        assert specs["comm_e"] == P("data")

    def test_ten_step_parity_with_replicated_gspmd_baseline(self):
        from paddle_tpu.distributed.spmd import make_gspmd_step_from_loss
        from paddle_tpu.distributed.zero import per_device_state_bytes
        from paddle_tpu.optimizer import Adam

        mesh = _mesh(2)
        x, y = _MLP.batch()
        lr = np.float32(0.05)

        # fresh params per builder: both steps donate their state
        ref_step, ref_state = make_gspmd_step_from_loss(
            _MLP.loss, _MLP.params(), Adam(0.05), mesh)
        us_step, us_state = make_dp_update_sharded_train_step(
            _MLP.loss, _MLP.params(), Adam(0.05), mesh)

        ref_bytes = per_device_state_bytes(ref_state)
        us_bytes = per_device_state_bytes(us_state)
        n = sum(int(np.prod(v.shape)) for v in _MLP.params().values())
        assert ref_bytes == 2 * n * 4          # Adam m+v, fully replicated
        assert us_bytes == ref_bytes // 2      # the R=2 saving, exactly

        ref_losses, us_losses = [], []
        for _ in range(10):
            ref_state, rl = ref_step(ref_state, lr, x, y)
            us_state, ul = us_step(us_state, lr, x, y)
            ref_losses.append(float(rl))
            us_losses.append(float(ul))

        np.testing.assert_allclose(us_losses, ref_losses,
                                   rtol=1e-5, atol=1e-7)
        assert ref_losses[-1] < ref_losses[0]  # both actually trained
        for k in ref_state["params"]:
            np.testing.assert_allclose(
                np.asarray(us_state["params"][k]),
                np.asarray(ref_state["params"][k]),
                rtol=1e-4, atol=1e-6, err_msg=k)

    def test_int8_ef_policy_composes(self):
        """Under int8_ef the reduce-scatter seam quantizes and the error
        residual rides per-replica stacked state — the step still trains."""
        from paddle_tpu.optimizer import SGD

        mesh = _mesh(2)
        step, state = make_dp_update_sharded_train_step(
            _MLP.loss, _MLP.params(), SGD(0.05), mesh, grad_comm="int8_ef")
        assert state["comm_e"].shape[0] == 2   # one residual per replica
        x, y = _MLP.batch()
        losses = []
        for _ in range(5):
            state, loss = step(state, np.float32(0.05), x, y)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        # the residual actually carries quantization error
        assert float(jnp.abs(state["comm_e"]).max()) > 0

    def test_replicated_args_ride_whole(self):
        """An RNG key in the batch position marked replicated must reach
        every replica un-split."""
        from paddle_tpu.optimizer import SGD

        def loss_with_key(params, key, x, y):
            noise = jax.random.normal(key, x.shape) * 1e-3
            return _MLP.loss(params, x + noise, y)

        mesh = _mesh(2)
        step, state = make_dp_update_sharded_train_step(
            loss_with_key, _MLP.params(), SGD(0.05), mesh,
            replicated_args=(0,))
        x, y = _MLP.batch()
        state, loss = step(state, np.float32(0.05), jax.random.key(0), x, y)
        assert np.isfinite(float(loss))

    def test_guards_refuse_unsupported_optimizers_and_meshes(self):
        from paddle_tpu.nn import ClipGradByGlobalNorm
        from paddle_tpu.optimizer import Adam, Lamb

        mesh = _mesh(2)
        with pytest.raises(NotImplementedError, match="grad_clip"):
            make_dp_update_sharded_train_step(
                _MLP.loss, _MLP.params(),
                Adam(0.05, grad_clip=ClipGradByGlobalNorm(1.0)), mesh)
        with pytest.raises(NotImplementedError, match="multi_precision"):
            make_dp_update_sharded_train_step(
                _MLP.loss, _MLP.params(),
                Adam(0.05, multi_precision=True), mesh)
        with pytest.raises(NotImplementedError, match="per-param-identity"):
            make_dp_update_sharded_train_step(
                _MLP.loss, _MLP.params(), Lamb(0.05), mesh)
        hybrid = _mesh(4, ("data", "model"), shape=(2, 2))
        with pytest.raises(NotImplementedError, match="non-trivial axes"):
            make_dp_update_sharded_train_step(
                _MLP.loss, _MLP.params(), Adam(0.05), hybrid)


# ==========================================================================
# 5. the ZeRO-3 layout: weights split inside the layer, never on the axis
#    the layer scan slices; what a step must move, from the shapes alone
# ==========================================================================

LAYERS = 8          # the toy models' depth: no other dim of theirs is 8


@pytest.fixture(scope="module")
def meta_models():
    """{"gpt" | "ernie": (model, abstract params)} — built under
    eval_shape, so only shapes and metadata are real."""
    from paddle_tpu.core import rng
    from paddle_tpu.models.ernie_moe import ErnieMoeConfig, ErnieMoeModel
    from paddle_tpu.models.gpt import GPTConfig, GPTModel

    def meta(cls, cfg):
        holder = {}

        def build(key):
            with rng.rng_scope(key):
                holder["model"] = cls(cfg)
            return {n: p._data
                    for n, p in holder["model"].named_parameters()}

        shapes = jax.eval_shape(build, jax.random.key(0))
        return holder["model"], shapes

    return {
        "gpt": meta(GPTModel, GPTConfig(
            vocab_size=128, hidden_size=64, num_layers=LAYERS,
            num_attention_heads=2, intermediate_size=256,
            max_position_embeddings=32)),
        "ernie": meta(ErnieMoeModel, ErnieMoeConfig(
            vocab_size=128, hidden_size=64, num_layers=LAYERS,
            num_attention_heads=2, num_experts=4, expert_hidden_size=128,
            max_position_embeddings=32)),
    }


def _sharding_mesh(n):
    return _mesh(n, ("data", "sharding"), shape=(1, n))


def _first_divisible(shape, n):
    """The rule before PR 26, and still the rule for a parameter no scan
    stacks: the first dim that ``n`` divides."""
    entries = [None] * len(shape)
    for d, size in enumerate(shape):
        if size % n == 0:
            entries[d] = "sharding"
            break
    return P(*entries)


class TestZero3Layout:
    def test_the_literal_names_are_the_models_own(self, meta_models):
        assert GPT_STACKED == meta_models["gpt"][0].stacked_param_names()
        assert ERNIE_STACKED == meta_models["ernie"][0].stacked_param_names()
        for model, shapes in meta_models.values():
            assert set(shapes) == set(model.stacked_param_names()) | \
                set(UNSTACKED)

    @pytest.mark.parametrize("degree", [2, 4], ids=["sharding2", "sharding4"])
    @pytest.mark.parametrize("which,name", STACKED_CASES,
                             ids=[f"{m}-{n}" for m, n in STACKED_CASES])
    def test_stacked_parameter_is_split_inside_the_layer(
            self, meta_models, which, name, degree):
        model, shapes = meta_models[which]
        mesh = _sharding_mesh(degree)
        shape = shapes[name].shape
        assert shape[0] == LAYERS
        spec = sr.build_param_specs(shapes, mesh, model, 3)[name]
        assert len(spec) == len(shape)
        assert spec[0] is None                 # the scanned axis stays whole
        at = [d for d, e in enumerate(spec) if e == "sharding"]
        assert len(at) == 1 and at[0] >= 1
        inside = [d for d in range(1, len(shape)) if shape[d] % degree == 0]
        assert shape[at[0]] == max(shape[d] for d in inside)
        # optimizer slots follow their parameter
        assert sr._slot_spec(spec, shapes[name], mesh, 3) == spec

    @pytest.mark.parametrize("degree", [2, 4], ids=["sharding2", "sharding4"])
    @pytest.mark.parametrize("which,name", STACKED_CASES,
                             ids=[f"{m}-{n}" for m, n in STACKED_CASES])
    def test_below_stage_3_a_stacked_parameter_is_as_before(
            self, meta_models, which, name, degree):
        model, shapes = meta_models[which]
        mesh = _sharding_mesh(degree)
        for stage in (0, 1, 2):
            spec = sr.build_param_specs(shapes, mesh, model, stage)[name]
            assert spec == P(*[None] * len(shapes[name].shape))
            # ... and its slots still take the first divisible dim
            assert sr._slot_spec(spec, shapes[name], mesh, stage) == (
                spec if stage == 0
                else _first_divisible(shapes[name].shape, degree))

    @pytest.mark.parametrize("degree", [2, 4], ids=["sharding2", "sharding4"])
    @pytest.mark.parametrize("which,name", UNSTACKED_CASES,
                             ids=[f"{m}-{n}" for m, n in UNSTACKED_CASES])
    def test_unstacked_parameter_keeps_the_first_divisible_dim(
            self, meta_models, which, name, degree):
        model, shapes = meta_models[which]
        mesh = _sharding_mesh(degree)
        spec = sr.build_param_specs(shapes, mesh, model, 3)[name]
        assert spec == _first_divisible(shapes[name].shape, degree)
        for stage in (0, 1, 2):
            assert sr.build_param_specs(shapes, mesh, model, stage)[name] \
                == P(*[None] * len(shapes[name].shape))

    def test_with_no_layer_the_old_rule_stands(self, meta_models):
        """Stackedness reaches the rule through the layer; a bare call (as
        tests/test_northstar_67b.py makes) knows of no scan."""
        _, shapes = meta_models["gpt"]
        mesh = _sharding_mesh(4)
        specs = sr.build_param_specs(shapes, mesh, None, 3)
        assert specs["blocks_qkv_w"] == P("sharding", None, None)
        assert sr._spec_for_param("blocks_qkv_w", shapes["blocks_qkv_w"],
                                  mesh, {}, 3, False) == \
            P("sharding", None, None)

    def test_a_stacked_leaf_with_no_divisible_dim_inside_stays_whole(self):
        class Layer:
            stacked_param_names = staticmethod(lambda: ["odd", "vec"])
            named_parameters = staticmethod(lambda: [])

        shapes = {"odd": jax.ShapeDtypeStruct((LAYERS, 3, 5), jnp.float32),
                  "vec": jax.ShapeDtypeStruct((LAYERS, 12), jnp.float32)}
        specs = sr.build_param_specs(shapes, _sharding_mesh(4), Layer(), 3)
        assert specs["odd"] == P(None, None, None)    # never dim 0
        assert specs["vec"] == P(None, "sharding")

    def test_the_catalog_says_so_and_its_digest_moved(self):
        rows = dict(sr._RULE_CATALOG)
        assert "never dim 0" in rows["zero3"]
        assert "data_parallel_axes" in rows["batch"]
        assert sr.CATALOG_VERSION >= 2

    def test_layout_comm_counts_what_a_step_must_move(self, meta_models):
        model, shapes = meta_models["gpt"]
        mesh = _sharding_mesh(4)
        stacked = model.stacked_param_names()
        specs = sr.build_param_specs(shapes, mesh, model, 3)
        good = sr.zero3_layout_comm(shapes, specs, mesh, stacked)
        nbytes = {n: int(np.prod(a.shape)) * 4 for n, a in shapes.items()}
        total = sum(nbytes.values())
        assert good["scan_axis_leaves"] == 0
        assert good["gather_bytes"] == sum(2 * (b * 3 // 4)
                                           for b in nbytes.values())
        assert good["reduce_bytes"] == sum(b * 3 // 4
                                           for b in nbytes.values())
        assert good["gather_bytes"] <= 2 * total
        # the gauges a dashboard reads, set when the specs are built
        stats = get_all_stats()
        assert stats["sharding_zero3_scan_axis_leaves"] == 0
        assert stats["sharding_zero3_gather_bytes"] == good["gather_bytes"]
        # the layout before PR 26: every stacked leaf split on the scanned
        # axis, so each of the scan's iterations gathers the whole stack
        bad_specs = sr.build_param_specs(shapes, mesh, None, 3)
        bad = sr.zero3_layout_comm(shapes, bad_specs, mesh, stacked)
        assert bad["scan_axis_leaves"] == len(stacked)
        extra = sum(2 * (LAYERS - 1) * (nbytes[n] * 3 // 4) for n in stacked)
        assert bad["gather_bytes"] == good["gather_bytes"] + extra
        assert bad["reduce_bytes"] == good["reduce_bytes"]

    def test_layout_comm_all_reduces_a_replicated_leaf(self):
        shapes = {"w": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
        out = sr.zero3_layout_comm(shapes, {"w": P(None, None)},
                                   _sharding_mesh(2))
        assert out == {"gather_bytes": 0, "reduce_bytes": 2 * 30,
                       "scan_axis_leaves": 0}


_HLO = """\
HloModule toy

%fused_gather (p: bf16[1,16,48]) -> bf16[1,64,48] {
  %p = bf16[1,16,48]{2,1,0} parameter(0)
  ROOT %all-gather.1 = bf16[1,64,48]{2,1,0:T(8,128)(2,1)} all-gather(%p), dimensions={1}
}

%body.1 (arg: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %arg = (s32[], f32[8,64]) parameter(0)
  %fusion.1 = bf16[1,64,48]{2,1,0} fusion(%x), kind=kCustom, calls=%fused_gather
  %all-reduce.2 = (f32[64]{0}, f32[64,192]{1,0}, /*index=2*/f32[192]{0}) all-reduce(%a, %b, %c), to_apply=%add
  %ags = (bf16[16,48], bf16[64,48]) all-gather-start(%y), dimensions={0}
  ROOT %t = (s32[], f32[8,64]) tuple(%i, %h)
}

%cond.1 (arg: (s32[], f32[8,64])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,64]) -> f32[8,64] {
  %all-gather.9 = f32[8,64,64]{2,1,0} all-gather(%w), dimensions={0}
  %while.1 = (s32[], f32[8,64]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,64] get-tuple-element(%while.1), index=1
}
"""


def test_loop_collectives_reads_bodies_and_what_they_call():
    rows = sr.loop_collectives(_HLO)
    got = sorted((r["op"], r["dims"], r["computation"]) for r in rows)
    assert got == [
        ("all-gather", [(1, 64, 48)], "fused_gather"),
        ("all-gather", [(16, 48), (64, 48)], "body.1"),
        ("all-reduce", [(64,), (64, 192), (192,)], "body.1"),
    ]
    # the whole-stack gather in the entry computation is outside any loop
    assert all(LAYERS not in d for r in rows for d in r["dims"])
