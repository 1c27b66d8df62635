"""ZeRO sharding (stages 1-3) with contractual semantics.

Reference capabilities being matched (TPU-natively, not by program surgery):
- fleet/meta_optimizers/sharding_optimizer.py:45 — the 1800-line static-graph
  ZeRO surgeon (_split_program:803, _prune_main_program:936,
  _add_broadcast_allreduce:1045) → sharding annotations + GSPMD.
- dygraph_optimizer/sharding_optimizer_stage2.py:46 + internal_storage.py:28 —
  rank-aligned fused grad/param buffers → NamedSharding over the "sharding"
  mesh axis (XLA lays out and fuses; alignment is the compiler's job).
- hybrid_parallel_optimizer.py:173 — found_inf / global-norm-clip / update
  ordering under hybrid parallelism.
- operators/amp/check_finite_and_unscale_op.cc + update_loss_scaling_op.cc —
  dynamic loss scaling semantics.

The contract per stage (all under one jit; XLA emits the collectives):
- stage 1: optimizer state (slots + fp32 master weights) sharded 1/N over
  the "sharding" axis.
- stage 2: + gradients reduce-scattered: the grad pytree is constrained to
  the slot sharding right after value_and_grad, so the data-parallel
  reduction becomes reduce_scatter over the axis instead of all_reduce.
- stage 3: + parameters stored sharded; gathered on use (GSPMD inserts
  all-gathers at the consuming matmuls and frees them after — the
  gather/release schedule the reference implements by hand).

Update ordering (one step): scaled loss → grads → unscale → found_inf (any
non-finite, global) → [optimizer's global-norm clip] → update → select
old/new state by found_inf → loss-scale update.  The step counter and
loss-scale bookkeeping only advance on finite steps.

Tensors with no dimension divisible by the sharding degree stay replicated
and are WARNED about with a byte count (reference pads to alignment,
internal_storage.py:28 — here the tradeoff is explicit instead of silent).
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from .sharding_rules import (_slot_spec, build_param_specs,
                             replicated_spec, resolve_flat_shard_spec)

_HALF_DTYPES = (jnp.bfloat16, jnp.float16)


def _warn_unsharded(kind: str, failures, degree: int):
    if not failures:
        return
    total = sum(b for _, b in failures)
    names = ", ".join(n for n, _ in failures[:5])
    warnings.warn(
        f"ZeRO: {len(failures)} {kind} tensor(s) have no dim divisible by "
        f"sharding degree {degree} and stay fully replicated "
        f"({total / 1e6:.2f} MB per device): {names}"
        + (", ..." if len(failures) > 5 else ""))


def zero_state_specs(params0: Dict[str, Any], mesh: Mesh, layer=None,
                     zero_stage: int = 1):
    """(param_specs, slot_specs) for the stage, with replication accounting."""
    p_specs = build_param_specs(params0, mesh, layer, zero_stage)
    s_specs = {k: _slot_spec(p_specs[k], p, mesh, max(zero_stage, 1))
               for k, p in params0.items()}
    deg = mesh.shape.get("sharding", 1)
    if deg > 1:
        def nbytes(p):
            return int(jnp.size(p)) * jnp.dtype(p.dtype).itemsize
        _warn_unsharded("optimizer-state", [
            (k, nbytes(p)) for k, p in params0.items()
            if "sharding" not in s_specs[k]], deg)
        if zero_stage >= 3:
            _warn_unsharded("parameter", [
                (k, nbytes(p)) for k, p in params0.items()
                if "sharding" not in p_specs[k]], deg)
    return p_specs, s_specs


def make_zero_train_step(loss_of: Callable, params0: Dict[str, Any], optimizer,
                         mesh: Mesh, layer=None, zero_stage: int = 1,
                         master_weights: Optional[bool] = None,
                         dynamic_loss_scale: bool = False,
                         init_loss_scale: float = 2.0 ** 15,
                         growth_interval: int = 1000,
                         backoff_factor: float = 0.5,
                         growth_factor: float = 2.0,
                         donate: bool = True,
                         offload: bool = False,
                         monitor=None,
                         grad_comm=None):
    """Build the sharded train step.

    ``loss_of(params, *batch) -> scalar``.  Returns ``(step, state0)`` with
    ``step(state, lr, *batch) -> (state, loss)``.  state = {params, opt,
    master, scaler}; scaler = {scale, good_steps, found_inf} (found_inf from
    the LAST step, for GradScaler-style inspection).

    ``monitor``: optional ``telemetry.TrainMonitor`` — wraps the returned
    step with host-side timing outside the jit boundary (compiled program
    identical either way; ``None`` returns the bare step).

    ``grad_comm``: gradient-communication policy (``"fp32"`` default /
    ``"bf16"`` / ``"int8_ef"`` / a ``grad_comm.GradCommPolicy``), applied
    to the unscaled fp32 gradients RIGHT BEFORE the stage-2 sharding
    constraint — the reduce-scatter seam — so the value GSPMD scatters is
    the policy's compressed-then-decompressed gradient.  On this GSPMD
    path XLA owns the collective schedule, so the policy governs numerics
    + byte accounting; the true int8-hop composition lives in the
    shard_map trainers (docs/DISTRIBUTED_COMM.md).  Stateful policies add
    a flat ``"comm_e"`` error-feedback residual to the state, sharded
    over the "sharding" axis when divisible.

    ``offload=True`` (≙ sharding_configs offload) routes through
    ``make_zero_offload_train_step``: optimizer slots + masters in host
    memory, update on the host CPU backend (no dynamic loss scaling there —
    offload targets memory-bound fp32/bf16 runs).
    """
    from .grad_comm import apply_policy_local, comm_info, resolve_policy
    policy = resolve_policy(grad_comm)
    if offload and policy.name != "fp32":
        raise NotImplementedError(
            "offload=True with grad_comm != 'fp32' is not wired: the "
            "offload path's wire is PCIe (host<->device), not ICI — "
            "compressing it is a different policy axis")
    if offload:
        if dynamic_loss_scale:
            raise NotImplementedError(
                "offload=True with dynamic_loss_scale is not supported; "
                "use static scaling (the offload path keeps found_inf "
                "skip-update semantics)")
        return make_zero_offload_train_step(
            loss_of, params0, optimizer, mesh, layer=layer,
            zero_stage=zero_stage, master_weights=master_weights,
            monitor=monitor)
    if master_weights is None:
        master_weights = any(p.dtype in _HALF_DTYPES
                             for p in jax.tree_util.tree_leaves(params0))

    p_specs, s_specs = zero_state_specs(params0, mesh, layer, zero_stage)
    # fp32 masters ONLY for half-precision params (reference multi_precision
    # semantics) — duplicating already-fp32 tensors would double their memory
    half_keys = {k for k, p in params0.items() if p.dtype in _HALF_DTYPES} \
        if master_weights else set()
    master0 = {k: params0[k].astype(jnp.float32) for k in half_keys}
    # slots track the update-precision copy (fp32 master where one exists)
    upd_params0 = {k: master0.get(k, p) for k, p in params0.items()}
    opt_state0 = optimizer.init_state(upd_params0)
    scaler0 = {
        "scale": jnp.asarray(init_loss_scale if dynamic_loss_scale else 1.0,
                             jnp.float32),
        "good_steps": jnp.zeros([], jnp.int32),
        "found_inf": jnp.zeros([], jnp.bool_),
    }
    state0 = {"params": params0, "opt": opt_state0, "master": master0,
              "scaler": scaler0}
    if policy.stateful:
        state0["comm_e"] = policy.residual_for(params0)

    rep = NamedSharding(mesh, replicated_spec())
    p_sh = {k: NamedSharding(mesh, p_specs[k]) for k in params0}
    s_sh = {k: NamedSharding(mesh, s_specs[k]) for k in params0}

    def slot_tree_sh(slots_of_param, k):
        return {sn: (s_sh[k] if hasattr(v, "shape") and v.ndim > 0 else rep)
                for sn, v in slots_of_param.items()}

    state_sh = {
        "params": p_sh,
        "opt": {"step": rep,
                "slots": {k: slot_tree_sh(v, k)
                          for k, v in state0["opt"]["slots"].items()}},
        "master": {k: s_sh[k] for k in master0},
        "scaler": {k: rep for k in scaler0},
    }
    if policy.stateful:
        # flat EF residual rides the "sharding" axis when divisible (block
        # padding makes power-of-two degrees always divide); an indivisible
        # length degrades to replication WITH byte accounting
        # (resolve_flat_shard_spec warns + bumps
        # sharding_replicated_fallback_bytes — never silently)
        state_sh["comm_e"] = NamedSharding(
            mesh, resolve_flat_shard_spec(
                "comm_e", int(state0["comm_e"].shape[0]), mesh, "sharding",
                tracer=getattr(monitor, "tracer", None)))

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state, lr, *batch):
        scale = state["scaler"]["scale"]

        def scaled_loss(p):
            return loss_of(p, *batch) * scale

        loss_s, grads = jax.value_and_grad(scaled_loss)(state["params"])
        loss = loss_s / scale
        inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv, grads)

        # found_inf BEFORE clip (check_finite_and_unscale ordering), and
        # before grad-comm compression (quantizing a non-finite tree is
        # undefined; the step is skipped either way)
        found_inf = functools.reduce(
            jnp.logical_or,
            [jnp.any(~jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)],
            jnp.zeros([], jnp.bool_))

        # the reduce-scatter seam: compress here so the value the stage-2
        # constraint scatters is the policy's dequantized grad
        grads, comm_state = apply_policy_local(policy, grads, state,
                                               found_inf=found_inf)
        if zero_stage >= 2:
            # stage-2 contract: gradients land reduce-scattered over the
            # sharding axis (GSPMD turns the dp reduction + this constraint
            # into reduce_scatter; ≙ ShardingOptimizerStage2 grad buckets)
            grads = {k: jax.lax.with_sharding_constraint(
                g, s_sh[k]) for k, g in grads.items()}

        upd_params = {k: state["master"].get(k, p)
                      for k, p in state["params"].items()}
        with jax.named_scope("optimizer"):  # a region, like the model's
            new_upd, new_opt = optimizer.update(grads, state["opt"],
                                                upd_params, lr=lr)

        def sel(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(found_inf, o, n), new, old)

        new_upd = sel(new_upd, upd_params)
        new_opt = {"step": jnp.where(found_inf, state["opt"]["step"],
                                     new_opt["step"]),
                   "slots": sel(new_opt["slots"], state["opt"]["slots"])}

        new_master = {k: jax.lax.with_sharding_constraint(new_upd[k], s_sh[k])
                      for k in half_keys}
        new_params = {k: (new_master[k].astype(params0[k].dtype)
                          if k in half_keys else new_upd[k])
                      for k in new_upd}
        new_params = {k: jax.lax.with_sharding_constraint(v, p_sh[k])
                      for k, v in new_params.items()}

        if dynamic_loss_scale:
            good = jnp.where(found_inf, 0, state["scaler"]["good_steps"] + 1)
            grow = good >= growth_interval
            new_scale = jnp.where(
                found_inf, jnp.maximum(scale * backoff_factor, 1.0),
                jnp.where(grow, scale * growth_factor, scale))
            good = jnp.where(grow, 0, good)
        else:
            new_scale, good = scale, state["scaler"]["good_steps"]

        new_state = {"params": new_params, "opt": new_opt, "master": new_master,
                     "scaler": {"scale": new_scale, "good_steps": good,
                                "found_inf": found_inf}, **comm_state}
        return new_state, loss

    state0 = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state0, state_sh,
        is_leaf=lambda x: hasattr(x, "shape"))
    from ..telemetry import instrument_train_step
    return instrument_train_step(step, monitor, "zero",
                                 comm=comm_info(params0, policy)), state0


def make_zero_offload_train_step(loss_of: Callable, params0: Dict[str, Any],
                                 optimizer, mesh: Mesh, layer=None,
                                 zero_stage: int = 1,
                                 master_weights: Optional[bool] = None,
                                 monitor=None):
    """CPU-offload variant (≙ reference sharding_configs ``offload=True`` /
    DygraphShardingOptimizer offload): optimizer slots + fp32 masters live in
    HOST memory; each step ships fp32 grads host-ward, runs the update on the
    host CPU backend, and ships the compute-dtype params back.  Device HBM
    then holds only params + activations — the optimizer states (2× fp32 for
    Adam, + masters) move off-chip at the price of PCIe/host traffic per
    step.

    Two jitted phases orchestrated in Python (one jit cannot span backends):
      device: grads = ∇(loss·scale), found_inf, loss
      host:   (new_master/new_upd, new_opt) = optimizer.update(...)
    Returns (step, state0); state = {params(dev), opt(host), master(host),
    scaler(host)}.  step(state, lr, *batch) -> (state, loss).
    """
    del master_weights  # the offload path is always master-weighted: the
    # host keeps THE authoritative fp32 copy of every param ("master" for
    # half params, same role for fp32 params) so no step ever fetches params
    # from device — per-step traffic is exactly grads down + params up
    cpu0 = jax.devices("cpu")[0]
    p_specs, s_specs = zero_state_specs(params0, mesh, layer, zero_stage)
    p_sh = {k: NamedSharding(mesh, p_specs[k]) for k in params0}
    s_sh = {k: NamedSharding(mesh, s_specs[k]) for k in params0}

    master0 = {k: np.asarray(p, np.float32) for k, p in params0.items()}
    opt_state0 = optimizer.init_state(master0)

    host = functools.partial(jax.device_put, device=cpu0)
    state0 = {
        "params": {k: jax.device_put(v, p_sh[k]) for k, v in params0.items()},
        "opt": jax.tree_util.tree_map(host, opt_state0),
        "master": {k: host(v) for k, v in master0.items()},
        "scaler": {"scale": host(jnp.ones([], jnp.float32)),
                   "good_steps": host(jnp.zeros([], jnp.int32)),
                   "found_inf": host(jnp.zeros([], jnp.bool_))},
    }

    @jax.jit
    def grad_phase(params, *batch):
        loss, grads = jax.value_and_grad(lambda p: loss_of(p, *batch))(params)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        if zero_stage >= 2:
            # stage-2 contract holds on the offload path too: grads land
            # reduce-scattered so peak HBM never sees the replicated tree
            grads = {k: jax.lax.with_sharding_constraint(g, s_sh[k])
                     for k, g in grads.items()}
        found_inf = functools.reduce(
            jnp.logical_or,
            [jnp.any(~jnp.isfinite(g))
             for g in jax.tree_util.tree_leaves(grads)],
            jnp.zeros([], jnp.bool_))
        return loss, grads, found_inf

    @jax.jit
    def host_phase(grads, opt, master, lr, found_inf):
        with jax.named_scope("optimizer"):
            new_upd, new_opt = optimizer.update(grads, opt, master, lr=lr)

        def sel(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(found_inf, o, n), new, old)

        new_master = sel(new_upd, master)
        new_opt = {"step": jnp.where(found_inf, opt["step"], new_opt["step"]),
                   "slots": sel(new_opt["slots"], opt["slots"])}
        new_params = {k: new_master[k].astype(params0[k].dtype)
                      for k in new_master}
        return new_params, new_opt, new_master

    def step(state, lr, *batch):
        loss, grads, found_inf = grad_phase(state["params"], *batch)
        g_host = jax.tree_util.tree_map(host, grads)
        fi_host = host(found_inf)
        new_params, new_opt, new_master = host_phase(
            g_host, state["opt"], state["master"],
            host(jnp.asarray(lr, jnp.float32)), fi_host)
        new_state = {
            "params": {k: jax.device_put(v, p_sh[k])
                       for k, v in new_params.items()},
            "opt": new_opt,
            "master": new_master,
            "scaler": {"scale": state["scaler"]["scale"],
                       "good_steps": state["scaler"]["good_steps"],
                       "found_inf": fi_host},
        }
        return new_state, loss

    from ..telemetry import instrument_train_step
    return instrument_train_step(step, monitor, "zero_offload"), state0


def per_device_state_bytes(state) -> int:
    """Addressable bytes of the optimizer state (slots + master) on device 0 —
    the quantity ZeRO shrinks ~1/shard (assertion hook for tests/benchmarks)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves({"opt": state["opt"]["slots"],
                                           "master": state.get("master", {})}):
        if hasattr(leaf, "addressable_shards"):
            shard = leaf.addressable_shards[0]
            total += int(shard.data.size) * leaf.dtype.itemsize
    return total
