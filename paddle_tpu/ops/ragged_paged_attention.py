"""Ragged paged attention: ONE Pallas kernel over a flattened mixed batch
of prefill chunks and decode rows (arxiv 2604.15464, PAPERS.md), walking
each sequence's block table in-kernel via scalar prefetch.

The paged decode kernel (ops/paged_attention.py) issues exactly one query
per slot, so prefill and decode tokens can never share a device program —
every prompt bucket compiles its own prefill family and the engine pays a
separate decode tick.  This kernel removes the split: the query batch is a
flattened ``(total_q, nh, hd)`` ragged pack where sequence ``s`` owns rows
``[cu_q_lens[s], cu_q_lens[s+1])`` at kv positions
``[kv_lens[s] - q_len[s], kv_lens[s])`` — a decode row is just a sequence
with ``q_len == 1`` and a prefill chunk one with ``q_len == n``.  Causality
is per ROW (query at kv position p attends positions <= p), so any mixture
of admission prefill and in-flight decode runs as one program.

A speculative VERIFY chunk is the same shape by construction: a slot's
``[prev, d_0..d_{K-1}]`` rows at kv positions ``[t, t+K]`` are a
``q_len == K+1`` sequence — each draft row attends its predecessors'
freshly scattered k/v under the per-row causal rule, so both the kernel
and the gather fallback are verify-aware with no extra code path (the
ragged spec engine's fused draft+verify tick rides exactly this).

int8 ``(values, scales)`` pools (models/_decode.py quantize_kv layout) are
supported IN-KERNEL: the scale plane rides its own block spec and the
dequantize multiply fuses into the k/v read — no fp copy of the pool ever
materializes (the gather fallback's dequant transient disappears).

Grid is (total_q, table columns); the k/v BlockSpec index maps read the
prefetched table — ``table[row_seq[i], j]`` selects which physical pool
block the next DMA fetches, clamped to the row's last in-range column so
skipped steps cost neither DMA nor compute (the ops/paged_attention.py
discipline, generalized from one-row-per-slot to one-row-per-token).

Gated like every Pallas kernel here: real Mosaic lowering on TPU via
FLAGS_use_pallas_kernels, ``interpret=True`` for CPU CI
(FLAGS_paged_attn_interpret), with ``ragged_attention_ref`` as the XLA
gather fallback/oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def ragged_rows(cu_q_lens, kv_lens, total_q: int):
    """Expand the per-sequence ragged metadata into per-ROW metadata.

    cu_q_lens (S+1,) int32 nondecreasing with cu_q_lens[0] == 0: sequence
    ``s`` owns rows [cu_q_lens[s], cu_q_lens[s+1]) of the flattened pack
    (q_len == 0 sequences own no rows).  kv_lens (S,) int32: kv extent of
    each sequence AFTER this step's writes — its rows sit at kv positions
    [kv_lens[s] - q_len[s], kv_lens[s]).

    Returns (row_seq, row_pos), both (total_q,) int32: the owning sequence
    (clamped to [0, S)) and the kv position of every row; padding rows
    beyond cu_q_lens[S] get row_pos == -1 (the kernel and fallback mask
    them to garbage-but-finite output).
    """
    cu = jnp.asarray(cu_q_lens, jnp.int32)
    kv = jnp.asarray(kv_lens, jnp.int32)
    S = kv.shape[0]
    rows = jnp.arange(total_q, dtype=jnp.int32)
    seq = jnp.searchsorted(cu[1:], rows, side="right").astype(jnp.int32)
    valid = seq < S
    seq_c = jnp.minimum(seq, S - 1)
    q_len = jnp.diff(cu)
    pos = kv[seq_c] - q_len[seq_c] + (rows - cu[seq_c])
    return seq_c, jnp.where(valid, pos, jnp.int32(-1))


def _ragged_kernel(table_ref, seq_ref, pos_ref, pad_ref, q_ref, *rest,
                   bs, n_cols, scale, quantized):
    from jax.experimental import pallas as pl

    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None

    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale       # (nh, hd)
        k = k_ref[0].astype(jnp.float32)               # (bs, nh, hd)
        v = v_ref[0].astype(jnp.float32)
        if quantized:                                  # fused dequant
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # broadcast-multiply-and-reduce, rank 3 with heads on sublanes —
        # see ops/paged_attention.py for why not a head-batched dot_general
        sc = jnp.sum(q[None] * k, axis=-1, keepdims=True)   # (bs, nh, 1)
        pos = j * bs + lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        valid = (pos <= pos_ref[i]) & (pos >= pad_ref[seq_ref[i]])
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_ref[:]                              # (nh, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
        p = jnp.exp(sc - m_new[None])                  # (bs, nh, 1)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=0)
        acc_ref[:] = acc_ref[:] * alpha + jnp.sum(p * v, axis=0)
        m_ref[:] = m_new

    # columns past the row's kv position: the clamped index map re-fetches
    # the row's last in-range block, which Pallas does not re-DMA, and
    # pl.when skips the FLOPs — padding rows (pos == -1) skip every column
    @pl.when(j * bs <= pos_ref[i])
    def _run():
        body()

    @pl.when(j == n_cols - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def ragged_attention_rows(q, pool_k, pool_v, table, row_seq, row_pos,
                          pad_lens=None, *, layer=None, interpret=False):
    """Row-metadata entry point (the engine packs rows directly).

    q (T, nh, hd); pool_k/pool_v (NB+1, bs, nh, hd) — or int8
    ``(values, scales)`` pairs with scales (NB+1, bs, nh) — or, with
    ``layer`` (a traced int32 scalar), the pools of a whole stack
    (L, NB+1, bs, nh[, hd]) of which the kernel reads that layer's blocks
    in place (no slice of the stack is ever made); table (S, C)
    int32 (block 0 = trash); row_seq (T,) int32 in [0, S); row_pos (T,)
    int32 kv position per row, -1 for padding rows; pad_lens (S,) int32
    left-pad masks (positions < pad masked), or None.

    Returns (T, nh, hd) in q's dtype; each row's output is attention over
    its sequence's pool positions [pad, row_pos] (garbage-but-finite
    zeros for padding rows).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    quantized = isinstance(pool_k, tuple)
    if layer is not None:
        # a stack's blocks numbered through: block b of layer i is block
        # i * (NB + 1) + b of the (L * (NB + 1), bs, ...) view (leading
        # dims merged: nothing moves), and the table is offset to match.
        # The kernel and its index maps need not know the layer; padding
        # rows read layer 0's trash block
        n_blocks = jax.tree.leaves(pool_k)[0].shape[1]
        pool_k, pool_v = jax.tree.map(
            lambda p: p.reshape((-1,) + p.shape[2:]), (pool_k, pool_v))
        table = table + jnp.asarray(layer, jnp.int32) * n_blocks
    vals_k = pool_k[0] if quantized else pool_k
    NB1, bs = vals_k.shape[:2]
    S, C = table.shape
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_ragged_kernel, bs=bs, n_cols=C, scale=scale,
                               quantized=quantized)

    def kv_map(i, j, tb, rs, rp, pp):
        # clamp to the row's deepest in-range column; padding rows (-1)
        # map to the trash block
        col = jnp.minimum(j, jnp.maximum(rp[i], 0) // bs)
        return (jnp.where(rp[i] < 0, 0, tb[rs[i], col]), 0, 0, 0)

    def kv_scale_map(i, j, tb, rs, rp, pp):
        return kv_map(i, j, tb, rs, rp, pp)[:3]

    val_spec = pl.BlockSpec((1, bs, nh, hd), kv_map)
    scale_spec = pl.BlockSpec((1, bs, nh), kv_scale_map)
    in_specs = [pl.BlockSpec((1, nh, hd), lambda i, j, tb, rs, rp, pp:
                             (i, 0, 0))]
    operands = [q]
    for pool in (pool_k, pool_v):
        if quantized:
            in_specs += [val_spec, scale_spec]
            operands += [pool[0], pool[1]]
        else:
            in_specs.append(val_spec)
            operands.append(pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                   # table, row_seq, row_pos, pad
        grid=(T, C),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, hd),
                               lambda i, j, tb, rs, rp, pp: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, hd), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
        ],
    )
    # the region and the kernel's name say what it is, wherever this file
    # moves: a trace finds the kernel by them
    with jax.named_scope("ragged_paged_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, nh, hd), q.dtype),
            interpret=interpret,
            name="ragged_paged_attention",
        )(table.astype(jnp.int32), jnp.asarray(row_seq, jnp.int32),
          jnp.asarray(row_pos, jnp.int32), jnp.asarray(pad_lens, jnp.int32),
          *operands)


def ragged_attention_ref(q, pool_k, pool_v, table, row_seq, row_pos,
                         pad_lens=None, *, layer=None):
    """XLA fallback/oracle: densify each row's table-selected blocks and
    reuse cached_attention's kq=1 per-row form — EXACTLY the numerics of
    the paged engine's gather path, so kernel parity tests pin against
    the same oracle the serving engine is locked to.  int8 pools
    dequantize after the gather (only selected blocks pay the convert).
    With ``layer`` the pools are a whole stack's, indexed here."""
    from ..models._decode import cached_attention, dequantize_cache

    if layer is not None:
        pool_k, pool_v = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, layer, 0, keepdims=False),
            (pool_k, pool_v))
    S, C = table.shape
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)

    def dense(pool):
        picked = jax.tree.map(lambda p: p[table], pool)   # (S, C, bs, …)
        g = dequantize_cache(picked, q.dtype)
        g = g.reshape((S, C * g.shape[2]) + g.shape[3:])
        return g[seq]                                     # (T, C·bs, nh, hd)

    out = cached_attention(q[:, None], dense(pool_k), dense(pool_v),
                           jnp.asarray(row_pos, jnp.int32),
                           pad_lens=pad_lens[seq])
    return out[:, 0]


def ragged_paged_attention(q, pool_k, pool_v, table, cu_q_lens, kv_lens,
                           pad_lens=None, *, layer=None, interpret=False):
    """Ragged paged attention over per-SEQUENCE metadata (the PAPERS.md
    kernel interface): q (T, nh, hd) flattened mixed batch, cu_q_lens
    (S+1,) cumulative query lengths, kv_lens (S,) post-write kv extents,
    ``table`` (S, C) block tables into the (NB+1, bs, nh, hd) pools
    (int8 ``(values, scales)`` pairs supported — dequant fused into the
    in-kernel gather), or a whole stack's pools and ``layer``.  Rows past
    cu_q_lens[S] are padding.  See ragged_attention_rows for the row-level
    contract."""
    row_seq, row_pos = ragged_rows(cu_q_lens, kv_lens, q.shape[0])
    return ragged_attention_rows(q, pool_k, pool_v, table, row_seq,
                                 row_pos, pad_lens, layer=layer,
                                 interpret=interpret)
