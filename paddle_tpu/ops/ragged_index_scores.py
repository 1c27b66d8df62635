"""Index scores of a lightning indexer over a PAGED key cache (DeepSeek
sparse attention, the DeepSeek-V3.2-Exp report's DSA section): every token
caches one indexer key ``k^I`` of ``D`` numbers per layer (128: exactly one
lane tile), shared by the indexer's heads, in a pool of its own on the
table the latent pool uses.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        for pad <= s <= t

``q`` (T, nh, D) are the pack's indexer queries, ``w`` (T, nh) their
signed float32 head weights.  The output is (T, C * bs) float32, column
``s`` the kv position ``s`` of the row's own sequence; a column outside
``[pad, row_pos]`` holds nothing meaningful (the walk stops at the row's
own position and writes no further): what reads the scores masks by
position (``ops/index_select.py``).

The walk is ``ops/ragged_latent_attention.py``'s: same flattened pack,
same scalar-prefetched table / row_seq / row_pos, the pool left in HBM and
copied ``blocks_per_step`` table-selected blocks at a time into a double
buffer.  Heads are rows of the MXU's left operand — ``(rows * nh, D) x
(D, keys)`` for a run of consecutive rows of one sequence, ``(nh, D) x
(D, keys)`` for a decode row — and the ReLU, the per-head weight and the
sum over heads run on the VPU in float32.

Gated like its siblings: Mosaic on the TPU, ``interpret=True`` for CPU CI,
``ragged_index_scores_ref`` the XLA fallback and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .ragged_latent_attention import (BLOCKS_PER_STEP, ROWS_PER_STEP,
                                      _largest_divisor)


def _scores_kernel(table_ref, seq_ref, pos_ref, layer_ref, q_ref, w_ref,
                   pool_ref, o_ref, buf, sem, *, bs, kb, rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nh = q_ref.shape[1]
    D = q_ref.shape[2]
    keys = kb * bs
    r0 = pl.program_id(0) * rows
    layer = layer_ref[0]
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if buf.dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))

    def score(lo, n):
        """Pack rows [r0 + lo, r0 + lo + n): one sequence at consecutive
        kv positions, one (n * nh, D) operand over every key block up to
        the last row's position."""
        M = n * nh
        seq = seq_ref[r0 + lo]
        last = pos_ref[r0 + lo] + (n - 1)
        steps = last // keys + 1
        # once a group of rows, not once a copy: each ``//`` of a traced
        # integer is a dozen operations to trace and lower, and a step's
        # copies asked 48 times (a third of a tick program's lowering)
        deepest = last // bs

        def copies(g, slot):
            for k in range(kb):
                col = jnp.minimum(g * kb + k, deepest)
                yield pltpu.make_async_copy(
                    pool_ref.at[layer, table_ref[seq, col]],
                    buf.at[slot, pl.ds(k * bs, bs)], sem.at[slot])

        def start(g, slot):
            for c in copies(g, slot):
                c.start()

        q = q_ref[pl.ds(lo, n)].reshape(M, D)
        w = w_ref[pl.ds(lo * nh, M)]                       # (M, 1) float32
        start(0, 0)

        def step(g, _):
            slot = g % 2

            @pl.when(g + 1 < steps)
            def _prefetch():
                start(g + 1, 1 - slot)

            for c in copies(g, slot):
                c.wait()
            s = dot(q, buf[slot], (((1,), (1,)), ((), ())))    # (M, keys)
            s = jnp.maximum(s, 0.0) * w
            o_ref[pl.ds(lo, n), pl.ds(pl.multiple_of(g * keys, keys),
                                      keys)] = \
                s.reshape(n, nh, keys).sum(axis=1)
            return 0

        lax.fori_loop(0, steps, step, 0)

    first = pos_ref[r0]
    together = first >= 0
    real = first >= 0
    for k in range(1, rows):
        together &= (seq_ref[r0 + k] == seq_ref[r0]) \
            & (pos_ref[r0 + k] == first + k)
        real |= pos_ref[r0 + k] >= 0

    @pl.when(together)
    def _chunk():
        score(0, rows)

    @pl.when(jnp.logical_not(together) & real)
    def _each():
        def one(r, _):
            @pl.when(pos_ref[r0 + r] >= 0)
            def _row():
                score(r, 1)
            return 0
        lax.fori_loop(0, rows, one, 0)


def ragged_index_scores_rows(q, w, pool, table, row_seq, row_pos, *,
                             layer=None, interpret=False,
                             blocks_per_step=BLOCKS_PER_STEP,
                             rows_per_step=ROWS_PER_STEP):
    """q (T, nh, D); w (T, nh) float32; pool (NB+1, bs, D), block 0 the
    trash block — or a stack's pools (L, NB+1, bs, D) and ``layer`` (a
    traced int32 scalar), read in place; table (S, C) int32; row_seq (T,)
    int32; row_pos (T,) int32 kv position, -1 for padding rows.

    Returns (T, C * bs) float32: column ``s`` of row ``t`` is ``I[t, s]``
    for ``s <= row_pos[t]`` (positions under the sequence's left pad
    included: they are scored and masked by the reader); the other columns
    and the padding rows are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, D = q.shape
    if layer is None:
        pool, layer = pool[None], 0
    _, NB1, bs, width = pool.shape
    assert width == D, (pool.shape, D)
    S, C = table.shape
    kb = _largest_divisor(C, blocks_per_step)
    rows = _largest_divisor(T, rows_per_step)
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    kernel = functools.partial(_scores_kernel, bs=bs, kb=kb, rows=rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # table, row_seq, row_pos, layer
        grid=(T // rows,),
        in_specs=[pl.BlockSpec((rows, nh, D), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((rows * nh, 1), lambda i, *_: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, C * bs), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((2, kb * bs, D), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    with jax.named_scope("ragged_index_scores"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, C * bs), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="ragged_index_scores",
        )(table.astype(jnp.int32), row_seq, jnp.asarray(row_pos, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q.astype(pool.dtype), w.astype(jnp.float32).reshape(T * nh, 1),
          pool)


def ragged_index_scores_ref(q, w, pool, table, row_seq, row_pos, *,
                            layer=None):
    """XLA fallback and oracle: densify each sequence's table-selected
    blocks, then the same products, ReLU, weights and sum.  Same contract
    as ``ragged_index_scores_rows``; every column is written."""
    S, C = table.shape
    if layer is not None:
        pool = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    bs = pool.shape[1]
    seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    with jax.named_scope("ragged_index_scores"):
        dense = pool[table].reshape(S, C * bs, pool.shape[-1])[seq]
        s = jnp.einsum("thd,tkd->thk", q.astype(pool.dtype), dense,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jnp.maximum(s, 0.0)
                       * w.astype(jnp.float32)[:, :, None], axis=1)
