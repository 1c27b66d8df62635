"""Automatic cross-replica weight-update sharding for plain data parallel.

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv:2004.13336): in vanilla data parallelism every replica
all-reduces the full gradient, then runs the SAME optimizer update on the
SAME full parameter + optimizer state — R-times-redundant work holding
R-times-redundant optimizer HBM.  The paper's observation is that the
all-reduce already factors into reduce-scatter + all-gather, and the
weight update is elementwise, so it can be slid between the two halves:

    reduce-scatter grads      -> each replica owns 1/R of the mean grad
    update the 1/R shard      -> optimizer state lives ONLY as shards
    all-gather updated params -> replicas re-converge, bit-identically

Total wire bytes are unchanged (a ring all-reduce IS reduce-scatter +
all-gather); optimizer-state HBM and update-step FLOPs per replica drop
~R×.  This module implements that schedule inside one ``shard_map`` over
the replica axis, composing with the grad-comm policies of
``distributed/grad_comm.py``: ``policy.reduce_scatter`` is the seam, so
under ``int8_ef`` the only wire hop before the update is the int8
``all_to_all`` (the policy docstring calls this exact seam out) and the
error-feedback residual rides per-replica state, as in localsgd.

Array layouts come from a :class:`~.sharding_rules.ShardingRules` table
(see docs/SHARDING.md) — nothing here constructs a raw ``PartitionSpec``:

    params     -> replicated          (the model tree replicas consume)
    opt slots  -> P(axis) flat shards (the ~R× saving; scalar slot leaves
                                       like beta-power accumulators are
                                       scalar-exempt and stay replicated)
    comm_e     -> per-replica stacked (each replica's own EF residual)

The optimizer state is kept FLAT: one fused (n_pad,) vector per slot over
the whole param tree (the ``grad_comm`` flatten, zero-padded so R always
divides), because the reduce-scatter shard boundary cuts across parameter
boundaries.  ``zero.per_device_state_bytes`` measures the saving
directly; ``tests/test_sharding_rules.py::TestUpdateSharding`` pins it at
exactly half at R=2.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

from .grad_comm import (_flatten_tree, _tree_size, _unflatten_tree,
                        comm_info, resolve_policy)
from .sharding_rules import ShardingRules, make_spec, replicated_spec
from .spmd import shard_map as _shard_map

__all__ = ["make_dp_update_sharded_train_step", "update_sharding_rules"]


def update_sharding_rules(axis: str = "data") -> ShardingRules:
    """The rule table governing this trainer's state layout (module
    docstring): flat optimizer shards and EF residuals ride the replica
    axis, everything else (model params, counters) replicates.  Scalar
    exemption keeps beta-power-style (1,) slot leaves replicated."""
    return ShardingRules(
        [
            (r"^opt/slots(/|$)", make_spec(axis)),
            (r"^comm_e$", make_spec(axis)),
            (r".*", replicated_spec()),
        ],
        unmatched="raise", name=f"dp_update_sharding[{axis}]")


def _reject_unsupported(optimizer):
    """The flat-shard update is only valid for optimizers whose functional
    update is elementwise over the parameter vector.  Refuse loudly where
    the fused flat layout would silently change semantics."""
    if getattr(optimizer, "_grad_clip", None) is not None:
        raise NotImplementedError(
            "update sharding with grad_clip: the clip norm is GLOBAL over "
            "the gradient tree, but each replica only holds a 1/R shard — "
            "computing it locally would clip by the wrong norm.  Needs a "
            "psum of the local square-sums before the clip; not wired yet.")
    if getattr(optimizer, "_wants_param_name", False) or \
            getattr(optimizer, "_per_tensor_norms", False):
        raise NotImplementedError(
            "update sharding with a per-param-identity rule (Lars/Lamb "
            "trust ratios): the fused flat shard spans parameter "
            "boundaries, so per-param norms are not computable on it.")
    if getattr(optimizer, "_multi_precision", False):
        raise NotImplementedError(
            "update sharding with multi_precision: master-weight slots "
            "need a sharded fp32 authority copy (ZeRO-style); use "
            "make_zero_train_step for that regime.")


def make_dp_update_sharded_train_step(loss_of: Callable,
                                      params0: Dict[str, Any], optimizer,
                                      mesh: Mesh, axis: str = "data",
                                      donate: bool = True, monitor=None,
                                      grad_comm=None,
                                      replicated_args: tuple = ()):
    """Build a plain-DP train step with the weight update sharded over
    ``axis`` (arXiv:2004.13336; see module docstring for the schedule).

    ``loss_of(params, *batch) -> scalar`` (mean over its batch rows);
    batch leading dims split evenly over ``axis``.  Returns
    ``(step, state0)`` with ``step(state, lr, *batch) -> (state, loss)``,
    loss being the cross-replica mean.  ``state["params"]`` is the
    ordinary replicated param tree; ``state["opt"]["slots"]["flat"]``
    holds the fused flat slot vectors, sharded 1/R per replica
    (``zero.per_device_state_bytes`` sees exactly the shard).

    ``grad_comm``: ``"fp32"`` (default) / ``"bf16"`` / ``"int8_ef"`` / a
    policy instance — the reduce-scatter runs under the policy in WIRE
    mode, so int8 really moves int8 on the grad hop.

    ``replicated_args``: positional indices into ``*batch`` that are NOT
    batch-sharded (an RNG key, a step index) and ride replicated instead.
    """
    policy = resolve_policy(grad_comm)
    _reject_unsupported(optimizer)
    extra = [a for a in mesh.axis_names if a != axis and mesh.shape[a] > 1]
    if extra:
        raise NotImplementedError(
            f"update sharding is the PLAIN data-parallel regime "
            f"(arXiv:2004.13336): mesh has non-trivial axes {extra} beyond "
            f"{axis!r} — use make_zero_train_step / the GSPMD builders for "
            f"hybrid meshes")
    replicated_args = tuple(sorted(set(int(i) for i in replicated_args)))
    R = mesh.shape[axis]
    n = _tree_size(params0)
    # one padding formula for every entry point: stateless policies pad to
    # a multiple of R, int8 to block*R (matching policy.residual_for)
    multiple = int(getattr(policy, "block", 1)) * max(R, 1)
    n_pad = -(-n // multiple) * multiple
    shard_len = n_pad // R

    flat0, meta0 = _flatten_tree(params0, multiple, total=n_pad)
    # optimizer state over the fused flat vector: slots are (n_pad,) and
    # shard 1/R over `axis`; value-dependent inits (e.g. accumulators
    # seeded from the param) see the exact padded param vector
    opt0 = optimizer.init_state({"flat": flat0})
    state0 = {"params": params0, "opt": opt0}
    if policy.stateful:
        e0 = policy.residual_for(params0, axis_size=R)
        # per-replica stacked residual (localsgd's layout): each replica
        # carries its OWN full-length accumulated quantization error
        state0["comm_e"] = jnp.zeros((R,) + e0.shape, e0.dtype)

    state_specs = update_sharding_rules(axis).resolve(state0)
    state0 = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        state0, state_specs)

    def body(state, lr, *batch):
        # inside shard_map: params replicated, opt slot leaves are this
        # replica's (shard_len,) slice, batch rows are this replica's share
        params = state["params"]
        loss, grads = jax.value_and_grad(loss_of)(params, *batch)

        e = state["comm_e"][0] if policy.stateful else None
        # the paper's first half: each replica receives the 1/R shard of
        # the cross-replica MEAN gradient (int8: the one wire hop here is
        # the quantized all_to_all)
        g_shard, meta, new_e = policy.reduce_scatter(grads, axis, e)

        # this replica's current param shard, sliced from the replicated
        # tree (no second authority copy: params stay 1× replicated)
        flat_p, _ = _flatten_tree(params, multiple, total=n_pad)
        p_shard = lax.dynamic_slice_in_dim(
            flat_p, lax.axis_index(axis) * shard_len, shard_len)

        # the update touches 1/R of the state — the ~R× FLOP/HBM saving
        new_sh, new_opt = optimizer.update(
            {"flat": g_shard}, state["opt"], {"flat": p_shard}, lr=lr)

        # the paper's second half: all-gather the updated shards back into
        # the replicated param tree (same bytes the all-reduce second half
        # would have moved)
        flat_new = lax.all_gather(new_sh["flat"], axis, tiled=True)
        new_params = _unflatten_tree(flat_new, meta)

        out = {"params": new_params, "opt": new_opt}
        if policy.stateful:
            out["comm_e"] = new_e[None]
        return out, lax.pmean(loss, axis)

    batch_spec = make_spec(axis)

    # shard_map specs are positional; rebuild per-call for variadic batches
    @functools.lru_cache(maxsize=8)
    def _compiled(n_batch):
        b_specs = tuple(replicated_spec() if i in replicated_args
                        else batch_spec for i in range(n_batch))
        w = _shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, replicated_spec()) + b_specs,
            out_specs=(state_specs, replicated_spec()),
            # check_vma off: the updated params are rebuilt from an
            # all_gather of per-replica shards — value-identical on every
            # replica, but not statically provable through the
            # dynamic-slice/update/gather round trip (dgc.py's rationale)
            check_vma=False)
        return jax.jit(w, donate_argnums=(0,) if donate else ())

    def step(state, lr, *batch):
        return _compiled(len(batch))(state, jnp.asarray(lr, jnp.float32),
                                     *batch)

    from ..telemetry import instrument_train_step
    from ..telemetry_memory import current_memory_ledger
    _ml = current_memory_ledger()
    if _ml is not None:
        # allocation-site registration: the sharded flat slots land in
        # the `optimizer_state` pool as 1/R addressable shards, so a
        # census MEASURES the paper's ~R× HBM saving (bench pins it)
        _ml.register_train_state(state0, name="dp_update_sharded")
    return instrument_train_step(step, monitor, "dp_update_sharded",
                                 comm=comm_info(params0, policy)), state0
