"""Attention kernels.

TPU-native replacement for the reference's fused attention stack
(operators/fused/fused_attention_op.cu, fmha_ref.h:57): a Pallas
flash-attention kernel (online-softmax, O(L) memory) with an XLA einsum
fallback.  Layout convention: (batch, seq, heads, head_dim) — BLHD, matching
paddle's MultiHeadAttention internals.

Forward supports causal masking, an additive key-padding mask (the BERT
(B, 1, 1, L) shape — reference fused_attention_op.cu consumes the same
broadcast mask), and in-kernel attention-probability dropout driven by a
position-based counter RNG (same bits in forward and backward by
construction, like the reference's seeded dropout in
fused_dropout_helper.h).  The backward is a pair of Pallas kernels
(dQ and dK/dV) that recompute probabilities blockwise from the saved
logsumexp — neither pass materializes the (L, L) score matrix.

Caveat (standard for flash attention): every query row must have at least
one unmasked key, else its logsumexp is -inf and gradients NaN.  Causal +
key-padding masks used by the model zoo satisfy this (CLS is never padded).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.flags import flag
from ..core.tensor import Tensor, apply

_NEG_INF = -1e30


def _use_pallas() -> bool:
    return flag("FLAGS_use_pallas_kernels") and jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Dense XLA path (also the reference implementation for tests)
# ---------------------------------------------------------------------------

def dense_attention(q, k, v, mask=None, causal=False, scale=None, dropout_p=0.0,
                    dropout_key=None):
    """q,k,v: (B, L, H, D) raw arrays. mask: additive, broadcastable to (B,H,Lq,Lk)."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * jnp.asarray(scale, q.dtype)
    if causal:
        Lq, Lk = scores.shape[-2], scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        cmask = (col <= row + (Lk - Lq))
        scores = jnp.where(cmask, scores, jnp.asarray(_NEG_INF, scores.dtype))
    if mask is not None:
        scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v)


# ---------------------------------------------------------------------------
# Portable in-kernel dropout RNG: murmur3-finalizer hash of (seed, bh, row,
# col).  Position-based, so forward and both backward kernels reproduce the
# exact same keep-mask regardless of their block decomposition, and it lowers
# on both Mosaic (TPU) and the interpret path (CPU tests) — pltpu.prng_* has
# no CPU lowering.
# ---------------------------------------------------------------------------

def position_hash_keep(mixed_seed, row0, col0, shape, dropout_p):
    """Shared keep-mask core: murmur3-finalize hash((row, col) ⊕ mixed_seed)
    ≥ p·2³².  ``mixed_seed`` is a uint32 scalar the caller pre-mixes with any
    extra coordinates (head index etc.); both the attention and fused-LN
    kernels use this one pipeline so the RNG cannot diverge between them."""
    rows = jnp.uint32(row0) + lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jnp.uint32(col0) + lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = (rows * jnp.uint32(0x9E3779B1)) ^ (cols * jnp.uint32(0x85EBCA77))
    x = x ^ mixed_seed
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return x >= thresh


def _dropout_keep(seed, bh, q0, k0, shape, dropout_p):
    mixed = seed.astype(jnp.uint32) + jnp.uint32(bh) * jnp.uint32(0xC2B2AE3D)
    return position_hash_keep(mixed, q0, k0, shape, dropout_p)


# ---------------------------------------------------------------------------
# Pallas flash attention: forward
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, causal, scale, dropout_p,
                      block_q, block_k, n_k):
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale  # (block_q, D)
        k = k_ref[0].astype(jnp.float32)          # (block_k, D)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s + km_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            rows = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref[0], bh, qi * block_q, ki * block_k,
                                 p.shape, dropout_p)
            p_v = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        else:
            p_v = p
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p_v, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    if causal:
        # skip fully-masked kv blocks
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _run():
            body()
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l))[:, 0]


def _flash_fwd_pallas(q, k, v, kmask, seed, causal, scale, dropout_p,
                      block_q, block_k, n_heads, interpret):
    """q,k,v: (BH, L, D); kmask: (B, L) additive. Returns (out, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    grid = (BH, L // block_q, L // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, scale=scale, dropout_p=dropout_p,
        block_q=block_q, block_k=block_k, n_k=L // block_k)
    H = n_heads
    # Row-stat operands (kmask, lse) ride a unit sublane dim: Mosaic requires
    # the last-two block dims be (mult-of-8, mult-of-128) or equal the array
    # dims, so (B, L) with block (1, block) is illegal while (B, 1, L) with
    # block (1, 1, block) is fine.
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed (1,)
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // H, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, L), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seed, q, k, v, kmask.reshape(kmask.shape[0], 1, L))
    return out, lse.reshape(BH, L)


# ---------------------------------------------------------------------------
# Pallas flash attention: backward (blockwise recompute from saved lse)
#
# P  = exp(S - lse)            (true softmax probs, recomputed per block)
# Pd = keep ∘ P / (1-p)        (dropout-applied probs)
# dV = Pd^T dO
# dPd = dO V^T ;  dS = Pd ∘ dPd - P ∘ delta,   delta = rowsum(dO ∘ O)
# dQ = scale · dS K ;  dK = scale · dS^T Q
# ---------------------------------------------------------------------------

def _bwd_block(q, k, v, do, lse, delta, km, keep_args, causal, scale,
               dropout_p, q0, k0):
    """Shared recompute math. q/do: (bq, D); k/v: (bk, D); lse/delta: (bq,).
    Returns (p, pd, ds) all (bq, bk) fp32."""
    s = lax.dot_general(q.astype(jnp.float32) * scale, k.astype(jnp.float32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + km.astype(jnp.float32)[None, :]
    if causal:
        rows = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])
    if dropout_p > 0.0:
        seed, bh = keep_args
        keep = _dropout_keep(seed, bh, q0, k0, p.shape, dropout_p)
        pd = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    else:
        pd = p
    dpd = lax.dot_general(do.astype(jnp.float32), v.astype(jnp.float32),
                          (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    ds = pd * dpd - p * delta[:, None]
    return p, pd, ds


def _flash_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, km_ref, dq_ref, acc_ref, *, causal, scale,
                         dropout_p, block_q, block_k, n_k):
    from jax.experimental import pallas as pl

    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body():
        _, _, ds = _bwd_block(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], km_ref[0, 0], (seed_ref[0], bh), causal, scale,
            dropout_p, qi * block_q, ki * block_k)
        acc_ref[:] += scale * lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _run():
            body()
    else:
        body()

    @pl.when(ki == n_k - 1)
    def _fin():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, km_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          causal, scale, dropout_p, block_q, block_k, n_q):
    from jax.experimental import pallas as pl

    bh, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body():
        _, pd, ds = _bwd_block(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], km_ref[0, 0], (seed_ref[0], bh), causal, scale,
            dropout_p, qi * block_q, ki * block_k)
        dv_acc[:] += lax.dot_general(
            pd, do_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += scale * lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip q blocks strictly above the diagonal (no row attends this kv)
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _run():
            body()
    else:
        body()

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, kmask, seed, do, lse, delta, causal, scale,
                      dropout_p, block_q, block_k, n_heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    H = n_heads
    common = dict(causal=causal, scale=scale, dropout_p=dropout_p,
                  block_q=block_q, block_k=block_k)
    data_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
    ]
    # unit sublane dim for row stats — see _flash_fwd_pallas
    kmask3 = kmask.reshape(kmask.shape[0], 1, L)
    lse3 = lse.reshape(BH, 1, L)
    delta3 = delta.reshape(BH, 1, L)

    def qspec(im):
        return pl.BlockSpec((1, block_q, D), im)

    def kspec(im):
        return pl.BlockSpec((1, block_k, D), im)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_k=L // block_k, **common),
        name="flash_attention_dq",
        grid=(BH, L // block_q, L // block_k),
        in_specs=data_specs + [
            qspec(lambda b, i, j: (b, i, 0)),
            kspec(lambda b, i, j: (b, j, 0)),
            kspec(lambda b, i, j: (b, j, 0)),
            qspec(lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // H, 0, j)),
        ],
        out_specs=qspec(lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(seed, q, k, v, do, lse3, delta3, kmask3)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=L // block_q, **common),
        name="flash_attention_dkv",
        grid=(BH, L // block_k, L // block_q),
        in_specs=data_specs + [
            qspec(lambda b, j, i: (b, i, 0)),
            kspec(lambda b, j, i: (b, j, 0)),
            kspec(lambda b, j, i: (b, j, 0)),
            qspec(lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b // H, 0, j)),
        ],
        out_specs=[kspec(lambda b, j, i: (b, j, 0)),
                   kspec(lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, L, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, L, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )(seed, q, k, v, do, lse3, delta3, kmask3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (BLHD public layout)
# ---------------------------------------------------------------------------

def _to_bh(x):
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _from_bh(x, B, H):
    BH, L, D = x.shape
    return x.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _per_shard(local, mesh, q_shape, in_kinds, out_kinds):
    """``local(sharded_axes, *arrays)`` calls Pallas on per-shard arrays;
    this returns it as a function of the whole arrays.  GSPMD cannot
    partition a Pallas call, so under a mesh the kernels sit in a
    ``shard_map`` with the batch split over the data-parallel axes and the
    heads over "model" (sharding_rules.attention_specs);
    ``in_kinds``/``out_kinds`` name each operand's layout there.  With no
    mesh (or one device) ``local`` runs on the arrays as they are."""
    if mesh is None or mesh.size == 1:
        return functools.partial(local, ())
    from ..distributed.sharding_rules import attention_specs
    from ..distributed.spmd import shard_map
    specs, axes = attention_specs(mesh, q_shape[0], q_shape[2])
    return shard_map(functools.partial(local, axes), mesh=mesh,
                     in_specs=tuple(specs[kd] for kd in in_kinds),
                     out_specs=tuple(specs[kd] for kd in out_kinds),
                     check_vma=False)


def _shard_seed(seed, axes):
    """Fold this shard's mesh coordinates into the dropout seed: the
    in-kernel keep-mask hashes LOCAL (batch·head, row, col) positions, so
    without this every shard would replay shard 0's masks.  Forward and
    backward fold identically (same mesh, same axes)."""
    for ax in axes:
        seed = seed * jnp.uint32(0x9E3779B1) \
            + lax.axis_index(ax).astype(jnp.uint32) + jnp.uint32(1)
    return seed


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention(q, k, v, kmask, seed, causal, scale, dropout_p, block,
                     mesh=None):
    out, _ = _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p, block,
                        mesh)
    return out


def _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p, block, mesh):
    """Returns (out (B,L,H,D), lse (B,H,L))."""
    interpret = jax.default_backend() != "tpu"

    def local(axes, q, k, v, kmask, seed):
        B, L, H, D = q.shape
        qkv = _to_bh(q), _to_bh(k), _to_bh(v)
        # the region and the kernels' names say what they are, wherever
        # this file moves: a trace finds the kernels by them
        with jax.named_scope("flash_attention"):
            out, lse = _flash_fwd_pallas(
                *qkv, kmask, _shard_seed(seed, axes),
                causal, scale, dropout_p, block, block, H, interpret)
        return _from_bh(out, B, H), lse.reshape(B, H, L)

    return _per_shard(local, mesh, q.shape,
                      ("qkv", "qkv", "qkv", "kmask", "rep"),
                      ("qkv", "stat"))(q, k, v, kmask, seed)


def _flash_fwd_rule(q, k, v, kmask, seed, causal, scale, dropout_p, block,
                    mesh):
    out, lse = _flash_fwd(q, k, v, kmask, seed, causal, scale, dropout_p,
                          block, mesh)
    return out, (q, k, v, kmask, seed, out, lse)


def _flash_bwd_rule(causal, scale, dropout_p, block, mesh, res, g):
    q, k, v, kmask, seed, out, lse = res
    interpret = jax.default_backend() != "tpu"

    def local(axes, q, k, v, kmask, seed, out, lse, g):
        B, L, H, D = q.shape
        do = _to_bh(g)
        o = _to_bh(out)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        qkv = _to_bh(q), _to_bh(k), _to_bh(v)
        with jax.named_scope("flash_attention"):
            dq, dk, dv = _flash_bwd_pallas(
                *qkv, kmask, _shard_seed(seed, axes),
                do, lse.reshape(B * H, L), delta, causal, scale, dropout_p,
                block, block, H, interpret)
        return (_from_bh(dq, B, H).astype(q.dtype),
                _from_bh(dk, B, H).astype(k.dtype),
                _from_bh(dv, B, H).astype(v.dtype))

    dq, dk, dv = _per_shard(
        local, mesh, q.shape,
        ("qkv", "qkv", "qkv", "kmask", "rep", "qkv", "stat", "qkv"),
        ("qkv", "qkv", "qkv"))(q, k, v, kmask, seed, out, lse, g)
    return dq, dk, dv, jnp.zeros_like(kmask), None


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal=False, scale=None, key_mask=None,
                    dropout_p=0.0, dropout_seed=None, mesh=None):
    """Public flash attention on raw arrays, (B,L,H,D).

    mesh: the ``jax.sharding.Mesh`` the caller's program is partitioned
    over, when it is — the Pallas kernels then run on each device's own
    batch rows and heads (see _per_shard); the dense path needs no mesh.

    key_mask: optional additive mask over keys, shape (B, Lk) (or any shape
    reshapeable to it, e.g. the BERT (B,1,1,Lk) padding mask).  dropout_p
    applies to attention probabilities; dropout_seed (uint32 scalar) selects
    the deterministic in-kernel keep-mask.

    Limitation: key_mask is treated as a constant — its cotangent on the
    Pallas path is zero.  Do not feed a *learned* additive bias through
    key_mask; use dense_attention(mask=...) for differentiable biases.
    """
    B, L, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if dropout_p > 0.0 and dropout_seed is None:
        # a silent default seed would replay one fixed keep-mask every step
        # (and the dense fallback would apply no dropout at all)
        raise ValueError("dropout_p > 0 requires dropout_seed (vary it per "
                         "step, e.g. jax.random.bits(key, (), jnp.uint32))")
    # choose the largest block size that tiles L exactly; overridable for
    # per-chip tuning (PADDLE_TPU_FLASH_BLOCK=256 etc.)
    import os as _os
    override = int(_os.environ.get("PADDLE_TPU_FLASH_BLOCK", "0"))
    if override and L % override == 0:
        block = override
    else:
        block = next((b for b in (512, 256, 128) if L % b == 0), None)
    if _use_pallas() and block is not None and q.shape == k.shape:
        kmask = (jnp.zeros((B, L), jnp.float32) if key_mask is None
                 else key_mask.reshape(B, L).astype(jnp.float32))
        seed = (jnp.zeros((1,), jnp.uint32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.uint32).reshape(1))
        return _flash_attention(q, k, v, kmask, seed, causal, scale,
                                float(dropout_p), block, mesh)
    mask4 = None if key_mask is None else \
        key_mask.reshape(B, 1, 1, k.shape[1]).astype(jnp.float32)
    dkey = None
    if dropout_p > 0.0 and dropout_seed is not None:
        dkey = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.uint32).reshape(()))
    return dense_attention(q, k, v, mask=mask4, causal=causal, scale=scale,
                           dropout_p=dropout_p, dropout_key=dkey)


def _is_key_padding_mask(m, B, Lk) -> bool:
    """True for masks that broadcast over heads and query rows: (B,1,1,Lk),
    (1,1,1,Lk) or (B,1,Lk).  A 2-D (B,Lk) mask is deliberately NOT accepted:
    it is ambiguous with a (Lq,Lk) positional mask when B == Lq, which dense
    attention broadcasts over batch — different semantics."""
    if m is None:
        return False
    shape = tuple(m.shape)
    return shape in ((B, 1, 1, Lk), (1, 1, 1, Lk), (B, 1, Lk))


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Tensor-level entry (BLHD), used by nn.MultiHeadAttention / F.sdpa."""
    from ..core import rng
    B, Lk = key.shape[0], key.shape[1]
    raw_mask = getattr(attn_mask, "_data", attn_mask)
    dropout_key = None
    p = dropout_p if training else 0.0
    if p > 0.0:
        dropout_key = rng.next_key()

    if raw_mask is None or _is_key_padding_mask(raw_mask, B, Lk):
        def f(q, k, v, m, dk):
            seed = None if dk is None else \
                jax.random.bits(dk, (), jnp.uint32)
            km = None if m is None else jnp.broadcast_to(
                m.astype(jnp.float32).reshape(m.shape[0], Lk), (B, Lk))
            return flash_attention(q, k, v, causal=is_causal, key_mask=km,
                                   dropout_p=p, dropout_seed=seed)
        return apply(f, query, key, value, attn_mask,
                     None if dropout_key is None else Tensor(dropout_key))

    def f(q, k, v, m, dk):
        return dense_attention(q, k, v, mask=m, causal=is_causal,
                               dropout_p=p if dk is not None else 0.0,
                               dropout_key=dk)
    return apply(f, query, key, value, attn_mask,
                 None if dropout_key is None else Tensor(dropout_key))
