"""Mixture-of-Experts: gating, capacity dispatch, expert parallelism.

Reference capability: expert parallelism via ``global_scatter``/``global_gather``
(python/paddle/distributed/utils.py:57,179 — ragged ncclSend/Recv loops keyed
by per-expert counts, operators/collective/global_scatter_op.cu.cc) plus
``alltoall`` (collective.py:1488).  The gating network itself lives in
downstream repos (SURVEY.md §2.4 EP row).

TPU-native design: XLA requires static shapes, so the ragged count-driven
exchange becomes **capacity-based dispatch** (GShard/Switch style): each
expert receives at most ``capacity`` tokens; dispatch/combine are one-hot
einsum contractions; expert layout rides a mesh axis and the cross-device
exchange is the all_to_all GSPMD infers from the sharding constraint on the
``(E, C, H)`` dispatched tensor (≙ the whole global_scatter/gather machinery).
Overflowed tokens pass through the residual connection (standard practice).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _resolve_capacity(T, E, k, capacity, capacity_factor):
    if capacity is None:
        capacity = int(math.ceil(k * T / E * capacity_factor))
        capacity = max(4, -(-capacity // 4) * 4)  # multiple of 4 for tiling
    return capacity


def _gating_rounds(logits, k, C, jitter_key):
    """The shared top-k selection loop (single source of truth for gating
    semantics — both the mask-building and index-building wrappers consume
    it).  Yields per-round (idx, pos, keep, gate) plus final (probs, ce_acc,
    denom): idx (T,) chosen expert, pos (T,) slot in that expert's buffer,
    keep (T,) within-capacity, gate (T,) raw selected prob."""
    T, E = logits.shape
    if jitter_key is not None:
        logits = logits + jax.random.uniform(jitter_key, logits.shape,
                                             logits.dtype, -1e-2, 1e-2)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    fill = jnp.zeros((E,), jnp.int32)
    masked = probs
    ce_acc = jnp.zeros((E,), jnp.float32)  # dispatched-token fractions
    denom = jnp.zeros((T,), jnp.float32)   # Σ of the k selected gate probs
    rounds = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                    # (T,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)     # (T, E)
        # position of each token within its chosen expert's buffer
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot       # (T, E)
        pos = jnp.sum(pos_in_e * onehot, axis=-1) + fill[idx]  # (T,)
        keep = pos < C
        gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        rounds.append((idx, pos, keep, gate))
        fill = fill + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
        ce_acc = ce_acc + jnp.mean(onehot.astype(jnp.float32), axis=0)
        denom = denom + gate
        masked = jnp.where(onehot.astype(bool), -jnp.inf, masked)
    return rounds, probs, ce_acc, denom


def topk_gating(logits, k: int = 2, capacity: Optional[int] = None,
                capacity_factor: float = 1.25, jitter_key=None):
    """Top-k gating with static per-expert capacity.

    Args:
      logits: (T, E) raw gate scores.
      k: experts per token (1 = Switch, 2 = GShard default).
      capacity: tokens per expert; default ceil(k * T / E * capacity_factor),
        rounded up to a multiple of 4 for TPU-friendly tiling.
    Returns:
      combine:  (T, E, C) float — combine weights (gate probs at slot).
      dispatch: (T, E, C) bool  — dispatch mask.
      aux_loss: scalar load-balancing loss (Switch §2.2: E * Σ_e m_e · c_e).
    """
    T, E = logits.shape
    C = _resolve_capacity(T, E, k, capacity, capacity_factor)
    rounds, probs, ce_acc, denom = _gating_rounds(logits, k, C, jitter_key)
    combine = jnp.zeros((T, E, C), jnp.float32)
    dispatch = jnp.zeros((T, E, C), bool)
    for idx, pos, keep, gate in rounds:
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        slot = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[:, None]
        contrib = onehot[:, :, None] * slot[:, None, :]
        combine = combine + gate[:, None, None] * contrib
        dispatch = dispatch | (contrib > 0)
    if k > 1:
        # GShard renormalization: selected gates sum to 1 over the chosen k
        # (k=1 keeps the raw prob — Switch convention)
        combine = combine / jnp.maximum(denom, 1e-9)[:, None, None]
    me = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(me * ce_acc / k)
    return combine, dispatch, aux_loss


def moe_dispatch(x, dispatch):
    """x: (T, H), dispatch: (T, E, C) → (E, C, H)."""
    return jnp.einsum("th,tec->ech", x.astype(jnp.float32),
                      dispatch.astype(jnp.float32)).astype(x.dtype)


def moe_combine(expert_out, combine, dtype=None):
    """expert_out: (E, C, H), combine: (T, E, C) → (T, H)."""
    out = jnp.einsum("ech,tec->th", expert_out.astype(jnp.float32), combine)
    return out.astype(dtype or expert_out.dtype)


def expert_ffn(expert_in, w1, b1, w2, b2, activation=jax.nn.gelu):
    """Per-expert FFN. expert_in: (E, C, H); w1: (E, H, I); w2: (E, I, H)."""
    dt = expert_in.dtype
    h = jnp.einsum("ech,ehi->eci", expert_in, w1.astype(dt)) + \
        b1.astype(dt)[:, None, :]
    h = activation(h)
    return jnp.einsum("eci,eih->ech", h, w2.astype(dt)) + \
        b2.astype(dt)[:, None, :]


def moe_ffn(x, gate_w, w1, b1, w2, b2, k: int = 2,
            capacity_factor: float = 1.25, mesh=None, expert_axis: str = "data",
            jitter_key=None, activation=jax.nn.gelu):
    """Full MoE FFN over tokens, with optional expert parallelism.

    x: (T, H) tokens.  Experts sharded over ``expert_axis`` when ``mesh`` is
    given: the sharding constraint on the (E, C, H) dispatched tensor makes
    GSPMD emit the token all_to_all (≙ global_scatter), and the constraint
    back to token layout after the expert FFN emits the reverse exchange
    (≙ global_gather).

    Returns (out (T, H), aux_loss).
    """
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # (T, E)
    combine, dispatch, aux = topk_gating(logits, k=k,
                                         capacity_factor=capacity_factor,
                                         jitter_key=jitter_key)
    expert_in = moe_dispatch(x, dispatch)                        # (E, C, H)
    if mesh is not None and mesh.shape.get(expert_axis, 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = NamedSharding(mesh, P(expert_axis, None, None))
        expert_in = lax.with_sharding_constraint(expert_in, spec)
    expert_out = expert_ffn(expert_in, w1, b1, w2, b2, activation)
    if mesh is not None and mesh.shape.get(expert_axis, 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        expert_out = lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(expert_axis, None, None)))
    out = moe_combine(expert_out, combine, dtype=x.dtype)
    return out, aux


# ---------------------------------------------------------------------------
# Index-based dispatch (gather/scatter) — O(T·H) data movement instead of the
# GShard einsum's O(T·E·C·H) matmul.  At ERNIE bench shapes (T=4096, E=8,
# C≈1280, H=768) the einsum dispatch+combine costs ~2x the expert FFN's own
# FLOPs and materializes (T, E, C) fp32 masks (~170MB); the index path moves
# each token once.  ≙ the reference's global_scatter/global_gather, which is
# likewise an index exchange, not a matmul
# (operators/collective/global_scatter_op.cu.cc).
# ---------------------------------------------------------------------------

def topk_gating_indices(logits, k: int = 2, capacity: Optional[int] = None,
                        capacity_factor: float = 1.25, jitter_key=None):
    """Top-k gating that returns slot indices instead of (T, E, C) masks.

    Returns:
      expert_idx (T, k) int32 — chosen expert per token/choice
      slot_idx   (T, k) int32 — position in that expert's buffer; == C when
        the token overflowed capacity (dropped)
      gates      (T, k) f32  — combine weights (GShard-renormalized over the
        selected k when k > 1; zero for dropped slots)
      aux_loss   scalar load-balance loss (same formula as topk_gating)
      capacity   the static per-expert capacity C used
    """
    T, E = logits.shape
    C = _resolve_capacity(T, E, k, capacity, capacity_factor)
    rounds, probs, ce_acc, denom = _gating_rounds(logits, k, C, jitter_key)
    expert_idx = jnp.stack([r[0].astype(jnp.int32) for r in rounds], axis=1)
    slot_idx = jnp.stack([jnp.where(r[2], r[1], C).astype(jnp.int32)
                          for r in rounds], axis=1)
    gate_k = jnp.stack([jnp.where(r[2], r[3], 0.0) for r in rounds], axis=1)
    if k > 1:
        # same denominator as topk_gating: all selected probs incl. dropped
        gate_k = gate_k / jnp.maximum(denom, 1e-9)[:, None]
    me = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(me * ce_acc / k)
    return expert_idx, slot_idx, gate_k, aux_loss, C


def moe_ffn_indices(x, gate_w, w1, b1, w2, b2, k: int = 2,
                    capacity_factor: float = 1.25, mesh=None,
                    expert_axis: str = "data", jitter_key=None,
                    activation=jax.nn.gelu):
    """moe_ffn with gather/scatter dispatch — numerically equivalent to the
    einsum path (see tests), O(T·H) data movement."""
    T, H = x.shape
    E = gate_w.shape[-1]
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    expert_idx, slot_idx, gates, aux, C = topk_gating_indices(
        logits, k=k, capacity_factor=capacity_factor, jitter_key=jitter_key)

    # flat slot id e*C + c; dropped slots land in a trash row at E*C
    flat = jnp.where(slot_idx < C, expert_idx * C + slot_idx, E * C)  # (T, k)
    buf = jnp.zeros((E * C + 1, H), x.dtype)
    # slots are unique by construction (cumsum positions), so .set is exact;
    # only the trash row sees duplicate writes (value irrelevant)
    buf = buf.at[flat.reshape(-1)].set(
        jnp.repeat(x, k, axis=0), unique_indices=False)
    expert_in = buf[:E * C].reshape(E, C, H)
    if mesh is not None and mesh.shape.get(expert_axis, 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        expert_in = lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(expert_axis, None, None)))
    expert_out = expert_ffn(expert_in, w1, b1, w2, b2, activation)
    if mesh is not None and mesh.shape.get(expert_axis, 1) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        expert_out = lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(expert_axis, None, None)))
    out_flat = jnp.concatenate(
        [expert_out.reshape(E * C, H), jnp.zeros((1, H), expert_out.dtype)])
    picked = out_flat[flat]                                   # (T, k, H)
    out = jnp.sum(picked.astype(jnp.float32)
                  * gates[..., None], axis=1).astype(x.dtype)
    return out, aux


def moe_ffn_gather(x, gate_w, w1, b1, w2, b2, k: int = 2,
                   activation=jax.nn.gelu):
    """Capacity-FREE MoE FFN via per-token expert-weight gather — the
    inference/decode dispatch (≙ the reference's no-drop serving path).

    No (E, C, H) buffer and no wasted rows: expert FLOPs are exactly O(k·T)
    at the price of gathering k weight slices per token, which wins when T
    is small (the per-token decode loop).  Numerically equal to
    ``moe_ffn_indices`` at a no-drop capacity (same renormalized top-k
    combine weights); returns the output only — the aux load-balance loss is
    a training quantity.
    """
    T, H = x.shape
    logits32 = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits32, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)              # (T, k)
    if k > 1:  # GShard renormalization over the selected k
        gates = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    else:
        gates = top_p
    w1s = jnp.take(w1, top_e, axis=0)                   # (T, k, H, I)
    b1s = jnp.take(b1, top_e, axis=0)                   # (T, k, I)
    h1 = activation(jnp.einsum("th,tkhi->tki", x, w1s.astype(x.dtype))
                    + b1s.astype(x.dtype))
    w2s = jnp.take(w2, top_e, axis=0)                   # (T, k, I, H)
    b2s = jnp.take(b2, top_e, axis=0)                   # (T, k, H)
    out = jnp.einsum("tki,tkih->tkh", h1, w2s.astype(x.dtype)) \
        + b2s.astype(x.dtype)
    # combine in fp32 like moe_ffn_indices — the numerical-equality contract
    # must hold at bf16 compute dtype too
    return jnp.sum(out.astype(jnp.float32) * gates[..., None],
                   axis=1).astype(x.dtype)


# ---------------------------------------------------------------------------
# routing over more experts than are held, no capacity, no drop (DeepSeek-V3 /
# Pangu Ultra MoE / LongCat-Flash serving: one chip's share of a layer whose
# routed experts are spread expert-parallel over many)
# ---------------------------------------------------------------------------

def route_sigmoid_topk(x, gate_w, k: int, scaling: float = 1.0,
                       normalize: bool = True, *, bias=None,
                       n_group: int = 1, topk_group: int = 1):
    """Scores ``sigmoid(x W_g)`` in float32 over ALL routed experts, the
    ``k`` largest, their weights ``s / (sum s + 1e-20) * scaling``
    (``normalize=False``: ``s * scaling``).  x (T, H); gate_w (H, E).
    Returns (idx (T, k) int32, w (T, k) float32).  No capacity: routing
    never drops a token.

    With ``bias`` (E,) and ``n_group`` > 1 the choice is DeepSeek-V3's
    (arXiv:2412.19437 §2.1.2, ``topk_method: noaux_tc``): the experts are
    chosen by ``c = s + bias``; a group of ``E / n_group`` consecutive
    experts scores the sum of its two largest ``c``; the ``topk_group``
    best groups stay and the ``k`` largest ``c`` among their experts are
    chosen.  The weights are made from ``s``, never from ``c``.  With
    neither, this is the plain function, to the bit."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None and n_group == 1:
        top, idx = jax.lax.top_k(s, k)
    else:
        c = s if bias is None else s + bias.astype(jnp.float32)
        if n_group > 1:
            T, E = c.shape
            by_group = c.reshape(T, n_group, E // n_group)
            score = jax.lax.top_k(by_group, 2)[0].sum(-1)       # (T, G)
            kept = jax.lax.top_k(score, topk_group)[1]          # (T, g)
            keep = (kept[:, :, None] == jnp.arange(n_group)).any(1)
            c = jnp.where(keep[:, :, None], by_group,
                          -jnp.inf).reshape(T, E)
        idx = jax.lax.top_k(c, k)[1]
        top = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), top * scaling


def route_softmax_topk(x, gate_w, k: int, scaling: float = 1.0, bias=None):
    """Scores ``p = softmax(x W_r)`` in float32 over ALL the router's
    outputs (LongCat-Flash: the real experts and then the zero-compute
    ones), the ``k`` largest of ``p + bias`` (``bias`` (E,): the trained
    selection bias, ``e_score_correction_bias``), their weights ``scaling
    * p``: made from ``p``, never from ``p + bias``, and not normalised
    over the chosen.  x (T, H); gate_w (H, E).  Returns (idx (T, k) int32,
    w (T, k) float32).  No capacity: routing never drops a token."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    if bias is None:
        top, idx = jax.lax.top_k(p, k)
    else:
        idx = jax.lax.top_k(p + bias.astype(jnp.float32), k)[1]
        top = jnp.take_along_axis(p, idx, axis=-1)
    return idx.astype(jnp.int32), top * scaling


def identity_experts(x, idx, w, n_real: int, valid=None):
    """The zero-compute (identity) experts' part of a routed layer:
    ``(sum_{e chosen, e >= n_real} w_e) * x``.  The router's outputs
    ``[n_real, E)`` name experts without weights that return their input;
    every chip applies them to the rows it serves.  x (T, H); idx / w
    (T, k) from the router; ``valid`` (T,) bool leaves rows out.  Returns
    (out (T, H) float32, the pairs they took, int32)."""
    zero = idx >= n_real
    if valid is not None:
        zero = zero & valid[:, None]
    with jax.named_scope("zero_experts"):
        share = jnp.sum(jnp.where(zero, w, 0.0), -1, keepdims=True)
        return share * x.astype(jnp.float32), jnp.sum(zero, dtype=jnp.int32)


def held_experts_ffn(x, idx, w, w_gate, w_up, w_down, first: int,
                     valid=None, n_real: Optional[int] = None, layer=None):
    """The held experts' part of a routed layer, dropless:
    ``sum_{e held} w_e * W_down_e(silu(W_gate_e x) * (W_up_e x))``.

    x (T, H); idx / w (T, k) from the router over all its outputs; w_gate
    / w_up (Eh, H, F), w_down (Eh, F, H): the experts ``[first, first +
    Eh)`` held here.  ``valid`` (T,) bool leaves rows out (the pack's
    padding rows).  Returns (out (T, H) float32, rows (Eh,) int32: the
    pairs each held expert computed).

    Token-expert pairs whose expert is held are sorted by expert (the
    others sort behind them) and go through three grouped products
    (``jax.lax.ragged_dot``).  No pair is dropped, whatever the imbalance:
    the row buffer holds ``T * min(k, Eh)`` rows, and no routing can fill
    more — a token's ``k`` choices are distinct (``top_k``), so at most
    ``min(k, Eh)`` of them are held, and the held pairs, sorted first,
    all lie inside the buffer.  Rows behind them are computed by no group
    and weigh nothing.

    ``idx`` may also name outputs that are no real expert (zero-compute
    experts, ``identity_experts``): they are never held — ``n_real``, the
    number of real experts, where the caller passes it, must cover the
    held range — so they sort behind the buffer with the absent experts'
    pairs and the bound stands.

    With ``layer`` (a traced index) the three weights are a whole stack's,
    ``(L, Eh, ...)``, and that layer's experts are read in place: the
    grouped products run over all ``L * Eh`` experts with every other
    layer's group empty.  A grouped product is a kernel call, its operands
    buffers: sliced out of the stack by a layer scan, the layer's experts
    are copied in every round (1.2 GB a layer at LongCat-Flash's widths)."""
    T, k = idx.shape
    Eh = w_gate.shape[0 if layer is None else 1]
    if n_real is not None and not 0 <= first <= first + Eh <= n_real:
        raise ValueError(f"held experts [{first}, {first + Eh}) are not "
                         f"among the {n_real} real experts")
    R = T * min(k, Eh)
    local = idx - first
    held = (local >= 0) & (local < Eh)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, Eh).reshape(-1)           # (T*k,)
    with jax.named_scope("router"):                        # the sort
        order = jnp.argsort(key, stable=True)
        rows = jnp.zeros(Eh + 1, jnp.int32).at[key].add(1)[:Eh]
        xs = x[order[:R] // k]                             # (R, H)
    with jax.named_scope("experts"):
        sizes = rows
        if layer is not None:
            L = w_gate.shape[0]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(L * Eh, jnp.int32), rows, (layer * Eh,))
            w_gate, w_up, w_down = (a.reshape((L * Eh,) + a.shape[2:])
                                    for a in (w_gate, w_up, w_down))
        g = jax.lax.ragged_dot(xs, w_gate.astype(x.dtype), sizes)
        u = jax.lax.ragged_dot(xs, w_up.astype(x.dtype), sizes)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype),
                               w_down.astype(x.dtype), sizes)
    with jax.named_scope("router"):                        # the un-sort
        # each pair reads its row back by its place in the sorted order
        # (a gather: a scatter-add of the rows costs fifteen times as much
        # on the chip); pairs not held lie behind the buffer and weigh 0
        place = jnp.minimum(jnp.argsort(order), R - 1)
        y = jnp.where(held.reshape(-1, 1),
                      y[place].astype(jnp.float32) * w.reshape(-1, 1), 0.0)
        out = y.reshape(T, k, -1).sum(1)
    return out, rows


def gated_mlp(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * (W_up x))``, no biases: the dense MLP and
    the shared expert of the gated-SiLU families."""
    dt = x.dtype
    return (jax.nn.silu(x @ w_gate.astype(dt)) * (x @ w_up.astype(dt))) \
        @ w_down.astype(dt)
