"""SPMD hybrid-parallel engine.

This module is the TPU-native replacement for the reference's entire
program-rewriting distributed stack:

- meta-optimizers inserting c_allreduce/c_broadcast (sharding_optimizer.py,
  raw_program_optimizer.py, tensor_parallel_optimizer.py) → sharding
  annotations + GSPMD;
- NCCL ring bootstrap (gen_comm_id_helper.cc, collective_helper.h) → a
  ``jax.sharding.Mesh``;
- the 1F1B SectionWorker / PipelineParallel runtime (section_worker.cc,
  pipeline_parallel.py) → a shard_map micro-batch pipeline over the "pipe"
  mesh axis with ``ppermute`` hops (explicit only on that axis; all other
  axes stay under GSPMD via partial-auto shard_map).

Sharding rules (build_param_specs):
- TP:   params carry ``_dims_mapping = {dim: axis}`` (set by mp_layers) →
        PartitionSpec entries on "model".
- PP:   params carry ``_pp_stage`` or are stage-stacked on dim 0 ("pipe").
- ZeRO: optimizer slots (+ params at stage 3) additionally sharded over
        "sharding": a free divisible dim, never the axis a layer scan slices.
- DP:   batch dim of inputs on the data-parallel axes ("data", "sharding");
        params replicated over "data".
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..core import rng
from ..core.tensor import Tensor

# Sharding-spec inference lives in sharding_rules.py (THE array-layout
# module) since PR 16; re-exported here because every trainer and half the
# test suite historically imported it from spmd.
from .sharding_rules import (_slot_spec, _spec_for_param, batch_spec,
                             build_param_specs, build_state_shardings,
                             constrain_batch, replicated_spec)


# --------------------------------------------------------------------------
# shard_map micro-batch pipeline as a lax.scan over ticks.
#
# Schedule: M+S-1 ticks, each tick runs one stage body per device and one
# ppermute hop — the same tick count (and thus the same bubble fraction
# (S-1)/(M+S-1)) as the reference's 1F1B (section_worker.cc:62-137).  The
# scan body is constant-size, so the jaxpr does NOT grow with M (the round-1
# unrolled reduce blew up compile time past M≈32).  1F1B's remaining benefit
# over GPipe is activation scheduling; here per-tick jax.checkpoint bounds
# stored residuals to the tick boundaries (one micro-batch activation per
# tick) and interiors are recomputed in the backward scan — the TPU analog
# of 1F1B's bounded in-flight window.
# --------------------------------------------------------------------------

# The VMA seam, in the installed JAX's spelling (0.9.0) and pinned by
# tests/test_spmd_vma_seam.py: avals carry ``.vma``, the cast to varying is
# ``lax.pcast``, and ``shard_map`` is the top-level export with ``check_vma``
# and ``axis_names``.  Every call site in the framework takes ``shard_map``
# from here.  A JAX that spells any of these differently fails at this
# import, not by turning the pipeline's varying-cast into a no-op.
from jax import shard_map  # noqa: E402,F401 (re-exported)
from jax._src.core import get_aval as _get_aval  # noqa: E402

_pcast = jax.lax.pcast


def ensure_varying(x, axis):
    """Mark ``x`` device-varying over ``axis`` for shard_map's VMA checker,
    as a no-op when it already is (pcast rejects varying→varying)."""
    # no blanket except here: if get_aval or .vma fails on a valid pipeline
    # carry, that is an incompatibility to surface, not to swallow
    if axis in _get_aval(x).vma:
        return x
    return _pcast(x, (axis,), to="varying")


def spmd_pipeline(stage_fn: Callable, stage_params, microbatches, n_stages: int,
                  axis: str = "pipe", remat_ticks: bool = True):
    """Run inside shard_map over ``axis``.

    stage_fn(stage_params, x, microbatch_index) -> y ; stage_params is the
    LOCAL stage's parameter shard (leading stage dim already split away).
    ``microbatches``: (M, mb, ...) — meaningful on stage 0, replicated
    elsewhere.  Returns (M, mb, ...) outputs meaningful on the LAST stage
    (broadcast back to all stages).
    """
    M = microbatches.shape[0]
    S = n_stages
    stage = jax.lax.axis_index(axis)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(state, t):
        mb_idx = jnp.minimum(t, M - 1)
        inp = jnp.where(stage == 0, microbatches[mb_idx], state)
        y = stage_fn(stage_params, inp, mb_idx)
        return jax.lax.ppermute(y, axis, fwd_perm), y

    if remat_ticks:
        tick = jax.checkpoint(tick)
    # shard_map varying-manual-axes check (jax>=0.7): the carry becomes
    # device-varying after the first ppermute, so the init must be too
    carry0 = ensure_varying(jnp.zeros_like(microbatches[0]), axis)
    _, ys = jax.lax.scan(tick, carry0, jnp.arange(M + S - 1))
    # ticks S-1 .. M+S-2 are the last stage's M finished micro-batches
    outputs = ys[S - 1:]
    # broadcast final outputs from the last stage to every stage
    # (masked psum — ppermute can't scatter one source to many)
    outputs = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    outputs = jax.lax.psum(outputs, axis)
    return outputs


def spmd_pipeline_interleaved(stage_fn: Callable, chunk_params, microbatches,
                              n_stages: int, n_chunks: int, axis: str = "pipe",
                              remat_ticks: bool = True):
    """Megatron-style interleaved (virtual-pipeline) schedule as a lax.scan.

    ≙ the reference's virtual_pipeline_degree path (pipeline_parallel.py
    _forward_backward_pipeline interleaved branch; pp_layers.py
    get_stage_from_index maps layer→(stage, chunk)).  Device ``d`` holds
    ``V = n_chunks`` model chunks; chunk ``v`` on device ``d`` is global
    stage ``g = v*S + d``.  Each scan tick executes ONE chunk (cost ≈ 1/V of
    a non-interleaved stage) and one ring ``ppermute`` hop:

    - slot count is ``M*V + S - 1`` chunk-slots, so fill+drain cost is
      ``(S-1)/V`` stage-times instead of ``S-1`` — the bubble shrinks by the
      virtual degree, same as the reference's interleaved 1F1B;
    - the schedule is conflict-free: device-local clock ``w = u - d`` decodes
      uniquely to ``(microbatch, chunk) = (q//V*S + w%S, q%V)``, ``q = w//S``
      (requires ``M % S == 0``, the same constraint Megatron imposes);
    - AD reverses the scan, so the backward sweep gets the same reduced
      bubble; ``jax.checkpoint`` on the tick bounds live activations to one
      micro-batch per slot.

    ``stage_fn(chunk_local_params, x, mb_index, chunk_index) -> y``;
    ``chunk_params``: device-local pytree with leading dim ``V``;
    ``microbatches``: (M, mb, ...) meaningful on stage 0.  Returns
    (M, mb, ...) finished outputs broadcast from the last stage.
    """
    M = microbatches.shape[0]
    S, V = n_stages, n_chunks
    if M % S:
        raise ValueError(
            f"n_microbatches ({M}) must be a multiple of the pipeline "
            f"degree ({S}) for the interleaved schedule")
    stage = jax.lax.axis_index(axis)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, u):
        # device-local chunk clock; clipped decode is safe because inactive
        # slots' outputs are never selected by an active receiver
        w = jnp.clip(u - stage, 0, M * V - 1)
        j = w % S
        q = w // S
        v = q % V
        m = (q // V) * S + j
        chp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
            chunk_params)
        inp = jnp.where((stage == 0) & (v == 0), microbatches[m], carry)
        y = stage_fn(chp, inp, m, v)
        return jax.lax.ppermute(y, axis, fwd_perm), y

    if remat_ticks:
        tick = jax.checkpoint(tick)
    carry0 = ensure_varying(jnp.zeros_like(microbatches[0]), axis)
    _, ys = jax.lax.scan(tick, carry0, jnp.arange(M * V + S - 1))
    # micro-batch m = r*S + j leaves chunk V-1 on the last stage at slot
    # u = S*V*(r+1) + j - 1  (w_out = j + S*(V-1) + S*V*r, u = w_out + S-1)
    m_idx = jnp.arange(M)
    out_slots = S * V * (m_idx // S + 1) + (m_idx % S) - 1
    outputs = ys[out_slots]
    outputs = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, axis)


# --------------------------------------------------------------------------
# distributed train step builder
# --------------------------------------------------------------------------

def make_spmd_train_step(layer, loss_fn, optimizer, hcg, zero_stage: int = 0,
                         accumulate_steps: int = 1, donate: bool = True,
                         monitor=None, grad_comm=None):
    """GSPMD train step over the hybrid mesh (dp × sharding × model [+ sep]).

    ≙ §3.3 of the survey: what the reference achieves by rewriting the
    program with c_ops, we achieve by jitting the SAME step function with
    NamedSharding on params/optimizer-state/batch.  XLA inserts: dp grad
    allreduce (Reducer), mp activation allreduces (TP), ZeRO
    reduce-scatter/all-gathers — scheduled on ICI.
    """
    from ..jit.functional import functionalize, _wrap, _unwrap, wrap_tree
    from .grad_comm import apply_policy_local, comm_info, resolve_policy

    policy = resolve_policy(grad_comm)
    mesh = hcg.mesh
    apply_fn, params0, buffers0 = functionalize(layer)
    opt_state0 = optimizer.init_state(params0)
    state0 = {"params": params0, "opt": opt_state0, "buffers": buffers0}

    p_specs = build_param_specs(params0, mesh, layer, zero_stage)
    state_sh = build_state_shardings(state0, p_specs, mesh, zero_stage, params0)
    if policy.stateful:
        state0["comm_e"] = policy.residual_for(params0)
        state_sh["comm_e"] = NamedSharding(mesh, replicated_spec())

    def place(state):
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), state, state_sh,
            is_leaf=lambda x: hasattr(x, "shape"))

    def loss_of(p, b, key, inputs, labels):
        out, new_b = apply_fn(p, b, *inputs, rng_key=key, training=True)
        main = out[0] if isinstance(out, (list, tuple)) else out
        loss_t = loss_fn(_wrap(main), *wrap_tree(labels))
        return _unwrap(loss_t), (new_b, main)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state, key, lr, inputs, labels):
        if accumulate_steps > 1:
            def micro(idx, acc):
                g_acc, l_acc = acc
                mb = jax.tree_util.tree_map(
                    lambda x: x.reshape((accumulate_steps,
                                         x.shape[0] // accumulate_steps)
                                        + x.shape[1:])[idx], (inputs, labels))
                (l, _), g = jax.value_and_grad(loss_of, has_aux=True)(
                    state["params"], state["buffers"],
                    jax.random.fold_in(key, idx), mb[0], mb[1])
                return (jax.tree_util.tree_map(jnp.add, g_acc, g), l_acc + l)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, state["params"])
            grads, loss = jax.lax.fori_loop(
                0, accumulate_steps, micro, (zeros, jnp.zeros([], jnp.float32)))
            grads = jax.tree_util.tree_map(lambda g: g / accumulate_steps, grads)
            loss = loss / accumulate_steps
            new_b = state["buffers"]
        else:
            (loss, (new_b, _)), grads = jax.value_and_grad(loss_of, has_aux=True)(
                state["params"], state["buffers"], key, inputs, labels)
        grads, comm_state = apply_policy_local(policy, grads, state)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"], lr=lr)
        # keep shardings stable across steps
        new_params = jax.lax.with_sharding_constraint(
            new_params, {k: NamedSharding(mesh, p_specs[k]) for k in new_params})
        return {"params": new_params, "opt": new_opt, "buffers": new_b,
                **comm_state}, loss

    from ..telemetry import instrument_train_step
    return instrument_train_step(step, monitor, "spmd",
                                 comm=comm_info(params0, policy)), \
        place(state0), state_sh


def _make_gspmd_step(loss_of, optimizer, mesh, p_specs, donate,
                     grad_comm=None):
    """The shared jitted step kernel: fwd+bwd+update with params
    re-constrained each step so shardings stay stable under donation, and
    every batch operand pinned to the data-parallel axes INSIDE the step
    (callers hand it uncommitted arrays): with the rows split and the
    weights split inside the layer, a weight gradient is a partial sum
    per device that lands on a split layout — a reduce-scatter.

    ``grad_comm``: gradient-communication policy applied in LOCAL mode at
    the post-backward seam (GSPMD owns the collective schedule here —
    the policy pins the exchanged gradient's numerics and byte
    accounting; see distributed/grad_comm.py).  Stateful policies thread
    a flat ``"comm_e"`` residual through the state."""
    from .grad_comm import apply_policy_local, resolve_policy
    policy = resolve_policy(grad_comm)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state, lr, *batch):
        batch = jax.tree_util.tree_map(
            lambda x: constrain_batch(x, mesh), batch)
        loss, grads = jax.value_and_grad(loss_of)(state["params"], *batch)
        grads, comm_state = apply_policy_local(policy, grads, state)
        with jax.named_scope("optimizer"):  # a region, like the model's
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"], lr=lr)
        new_params = jax.lax.with_sharding_constraint(
            new_params, {k: NamedSharding(mesh, p_specs[k]) for k in new_params})
        return {"params": new_params, "opt": new_opt, "buffers": {},
                **comm_state}, loss
    return step


def make_gspmd_step_from_loss(loss_of, params0, optimizer, mesh, layer=None,
                              zero_stage: int = 0, donate: bool = True,
                              grad_comm=None):
    """Shared GSPMD train-step builder for functional models (gpt/bert/ernie).

    ``loss_of(params, *batch) -> scalar loss``.  Returns (step, state0) where
    ``step(state, lr, *batch) -> (state, loss)``; params/opt-state sharded by
    build_param_specs.  ``grad_comm`` as in ``_make_gspmd_step``.
    """
    from .grad_comm import resolve_policy
    policy = resolve_policy(grad_comm)
    p_specs = build_param_specs(params0, mesh, layer, zero_stage)
    opt_state0 = optimizer.init_state(params0)
    state0 = {"params": params0, "opt": opt_state0, "buffers": {}}
    state_sh = build_state_shardings(state0, p_specs, mesh,
                                     max(zero_stage, 1), params0)
    if policy.stateful:
        state0["comm_e"] = policy.residual_for(params0)
        state_sh["comm_e"] = NamedSharding(mesh, replicated_spec())
    step = _make_gspmd_step(loss_of, optimizer, mesh, p_specs, donate, policy)
    state0 = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state0, state_sh,
        is_leaf=lambda x: hasattr(x, "shape"))
    return step, state0


def shard_batch(batch, hcg):
    """Each leaf placed with its rows over the data-parallel axes that
    divide them (sharding_rules.batch_spec)."""
    mesh = hcg.mesh

    def put(x):
        x = getattr(x, "_data", x)
        return jax.device_put(
            x, NamedSharding(mesh, batch_spec(mesh, x.shape[0])))
    return jax.tree_util.tree_map(put, batch)


def make_gspmd_sharded_init_step(loss_of, build_params, optimizer, mesh,
                                 meta_layer=None, zero_stage: int = 0,
                                 donate: bool = True, seed: int = 0,
                                 grad_comm=None):
    """Like make_gspmd_step_from_loss, but the TrainState is *initialized
    directly sharded on the mesh*: ``build_params(key)`` runs under jit with
    per-leaf out_shardings, so each device materializes only its shard and
    the host never holds a full-size copy (the 6.7B fp32 params alone are
    ~27GB host-side otherwise).  ≙ the reference's per-rank startup programs
    after sharding_optimizer pruning; the scaling-book "init on the mesh".
    """
    from .grad_comm import resolve_policy
    policy = resolve_policy(grad_comm)
    key0 = jax.random.key(seed)

    def init_state(key):
        params = build_params(key)
        state = {"params": params, "opt": optimizer.init_state(params),
                 "buffers": {}}
        if policy.stateful:
            state["comm_e"] = policy.residual_for(params)
        return state

    # one abstract trace serves both the param specs and the state layout
    state_abs = jax.eval_shape(init_state, key0)
    abs_params = state_abs["params"]
    p_specs = build_param_specs(abs_params, mesh, meta_layer, zero_stage)
    state_sh = build_state_shardings(state_abs, p_specs, mesh,
                                     max(zero_stage, 1), abs_params)
    if policy.stateful:
        state_sh["comm_e"] = NamedSharding(mesh, replicated_spec())
    # tpulint: disable=jit-in-hot-loop(one-shot sharded init at builder time, never per step)
    state0 = jax.jit(init_state, out_shardings=state_sh)(key0)
    step = _make_gspmd_step(loss_of, optimizer, mesh, p_specs, donate, policy)
    return step, state0
