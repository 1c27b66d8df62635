"""Ragged paged LATENT attention over the SELECTED kv positions of each
row (DeepSeek sparse attention): ``ops/ragged_latent_attention.py``'s
absorbed attention, its softmax taken over the positions a lightning
indexer chose — the ``min(k, context)`` largest index scores, exactly
(``ops/index_select.py``) — and no others.

    o_lat[t, h] = sum_{s in S_t} softmax_{S_t}(score[t, h, :])[s] c_kv[s]

The selection arrives as what the indexer left: the row's index scores
(T, C * bs) float32 and its threshold ``(v, p)`` (T, >= 2) int32; a key
block's part of the mask is rebuilt from them where the block is used.

This form WALKS every block up to the row's position and masks: it is the
accepted kernel with one more term in ``valid`` (the walk, the MXU operand
of a chunk's rows, the online softmax are that file's, shared), so it
costs what attention over everything costs and its share of the roofline
of the selected positions' work says so.  With a selection spread over the
whole context, as random weights give, every block holds a selected key
and a block-skipping walk would save nothing; what saves the work is a
gather of the selected rows (docs/GENERATION.md, PERF.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .index_select import score_key, selected
from .ragged_latent_attention import (BLOCKS_PER_STEP, ROWS_PER_STEP,
                                      _NEG_INF, _largest_divisor,
                                      _latent_kernel)


def _sparse_kernel(table_ref, seq_ref, pos_ref, pad_ref, layer_ref, qa_ref,
                   qr_ref, s_ref, thr_ref, pool_ref, o_ref, *scratch, bs,
                   kb, **kw):
    from jax.experimental import pallas as pl

    nh = qa_ref.shape[1]
    keys = kb * bs

    def select(lo, n, g):
        at = pl.multiple_of(g * keys, keys)
        key = score_key(s_ref[pl.ds(lo, n), pl.ds(at, keys)])  # (n, keys)
        thr = thr_ref[pl.ds(lo, n)]
        v, p = thr[:, 0:1], thr[:, 1:2]
        col = at + lax.broadcasted_iota(jnp.int32, key.shape, 1)
        sel = ((key > v) | ((key == v) & (col <= p))).astype(jnp.int32)
        return jnp.broadcast_to(sel[:, None, :], (n, nh, keys)).reshape(
            n * nh, keys) != 0

    _latent_kernel(table_ref, seq_ref, pos_ref, pad_ref, layer_ref, qa_ref,
                   qr_ref, pool_ref, o_ref, *scratch, bs=bs, kb=kb,
                   select=select, **kw)


def ragged_sparse_latent_attention_rows(
        q_abs, q_r, pool, scores, thr, table, row_seq, row_pos,
        pad_lens=None, *, scale, layer=None, interpret=False,
        blocks_per_step=BLOCKS_PER_STEP, rows_per_step=ROWS_PER_STEP):
    """``ragged_latent_attention_rows`` with the selection: scores (T,
    C * bs) float32 as ``ragged_index_scores_rows`` wrote them, thr (T,
    >= 2) int32 as ``select_threshold_rows`` did.  A row attends the kv
    positions in [pad, row_pos] that ``index_select.selected`` names."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, R = q_abs.shape
    Dr = q_r.shape[-1]
    if layer is None:
        pool, layer = pool[None], 0
    _, NB1, bs, width = pool.shape
    assert width >= R + Dr, (pool.shape, R, Dr)
    S, C = table.shape
    assert scores.shape == (T, C * bs), (scores.shape, T, C, bs)
    kb = _largest_divisor(C, blocks_per_step)
    rows = _largest_divisor(T, rows_per_step)
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    kernel = functools.partial(_sparse_kernel, bs=bs, kb=kb, rows=rows,
                               scale=float(scale), rank=R, rope=Dr)
    row_map = lambda i, *_: (i, 0, 0)
    M = rows * nh
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # table, row_seq, row_pos, pad, layer
        grid=(T // rows,),
        in_specs=[pl.BlockSpec((rows, nh, R), row_map),
                  pl.BlockSpec((rows, nh, Dr), row_map),
                  pl.BlockSpec((rows, C * bs), lambda i, *_: (i, 0)),
                  pl.BlockSpec((rows, thr.shape[1]), lambda i, *_: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the pool stays put
        out_specs=pl.BlockSpec((rows, nh, R), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, kb * bs, width), pool.dtype),    # double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((M, R), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
        ],
    )
    with jax.named_scope("ragged_sparse_latent_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, nh, R), q_abs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="ragged_sparse_latent_attention",
        )(table.astype(jnp.int32), row_seq,
          jnp.asarray(row_pos, jnp.int32), jnp.asarray(pad_lens, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q_abs, q_r.astype(q_abs.dtype), scores, thr, pool)


def ragged_sparse_latent_attention_ref(q_abs, q_r, pool, scores, thr, table,
                                       row_seq, row_pos, pad_lens=None, *,
                                       scale, layer=None):
    """XLA fallback and oracle: ``ragged_latent_attention_ref`` with the
    selected positions alone valid.  Same contract as
    ``ragged_sparse_latent_attention_rows``."""
    T, nh, R = q_abs.shape
    S, C = table.shape
    if layer is not None:
        pool = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    bs = pool.shape[1]
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    with jax.named_scope("ragged_sparse_latent_attention"):
        dense = pool[table].reshape(S, C * bs, pool.shape[-1])[seq]
        c_kv = dense[..., :R]
        k_r = dense[..., R:R + q_r.shape[-1]]
        sc = jnp.einsum("thr,tkr->thk", q_abs, c_kv,
                        preferred_element_type=jnp.float32)
        sc = sc + jnp.einsum("thd,tkd->thk", q_r.astype(q_abs.dtype), k_r,
                             preferred_element_type=jnp.float32)
        valid = selected(scores, thr, pad_lens[seq], row_pos)
        sc = jnp.where(valid[:, None, :], sc * scale, _NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        p = jnp.where(valid[:, None, :], p, 0.0).astype(pool.dtype)
        out = jnp.einsum("thk,tkr->thr", p, c_kv,
                         preferred_element_type=jnp.float32)
        return out.astype(q_abs.dtype)
