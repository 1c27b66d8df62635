"""Ragged paged attention over a LATENT cache (multi-head latent attention,
DeepSeek-V2 §2.1, in its absorbed form): every token caches one row
``[c_kv ; k_r]`` of ``R + Dr`` numbers per layer (512 + 64), shared by all
heads, and the value of a key is the first ``R`` columns of the same row.
The pool may store the row wider (the model pads it to whole 128-lane
tiles, which a tiled device layout does anyway): columns past ``R + Dr``
are never read.

    score[h, key] = (q_abs[h] . c_kv[key] + q_r[h] . k_r[key]) * scale
    o_lat[h]      = sum_key softmax(score)[h, key] * c_kv[key]

``q_abs = q_nope W_kvb[K, h]^T`` and the way back ``o = o_lat W_kvb[V, h]``
are the model's (models/pangu_moe.py); the kernel never sees a per-head
key or value.

Derived from ops/ragged_paged_attention.py's table walk (same flattened
pack, same per-ROW causality, same scalar-prefetched table / row_seq /
row_pos / pads, same trash block 0), with two differences that matter.

The keys have no head axis, so heads are rows of the MXU's left operand:
the scores are ``(M, R) x (R, keys)`` + ``(M, Dr) x (Dr, keys)`` and the
output ``(M, keys) x (keys, R)``, where the per-head kernel's ``M`` is a
run's rows alone.  And ``M`` is more than one pack row's heads wherever
the pack allows it: a grid step takes ``rows_per_step`` consecutive pack
rows, and if they are one sequence at consecutive kv positions (the
inside of a prefill chunk) they share every key block and go through the
products as one ``(rows * nh, .)`` operand — the key blocks are fetched
once for all of them, and the MXU's weights (the keys) are loaded once
per ``rows * nh`` streamed rows instead of once per ``nh``.  A step whose
rows are not such a run (decode rows, a chunk's edge) takes them one
after another; a step of padding rows does nothing.

The walk is the kernel's own: the pool stays in HBM (``memory_space=ANY``)
and each inner step copies ``blocks_per_step`` table-selected blocks into
one half of a double buffer while the other half is being used, for
exactly as many steps as the rows' kv positions need — there is no grid
step for a column a row does not reach.

bfloat16 (or float32) in; scores, softmax and the accumulator in float32;
the probabilities go to the pool's dtype for the second product.

Gated like its sibling: Mosaic on TPU under FLAGS_use_pallas_kernels,
``interpret=True`` for CPU CI (FLAGS_paged_attn_interpret),
``ragged_latent_attention_ref`` the XLA gather fallback and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
BLOCKS_PER_STEP = 16        # table columns (blocks of keys) per inner step
ROWS_PER_STEP = 8           # pack rows per grid step


def _latent_kernel(table_ref, seq_ref, pos_ref, pad_ref, layer_ref, qa_ref,
                   qr_ref, pool_ref, o_ref, buf, sem, acc_ref, m_ref, l_ref,
                   *, bs, kb, rows, scale, rank, rope, select=None):
    """``select(lo, n, g)``, where given, is a further (n * nh, keys) mask
    of inner step ``g``'s keys for pack rows [r0 + lo, r0 + lo + n): the
    sparse sibling's (ops/ragged_sparse_latent_attention.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nh = qa_ref.shape[1]
    keys = kb * bs
    r0 = pl.program_id(0) * rows
    layer = layer_ref[0]
    # bfloat16 operands multiply exactly in one MXU pass; a global
    # "highest" default would ask Mosaic for a float32 product of
    # bfloat16 vectors, which it refuses
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if buf.dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))

    def attend(lo, n):
        """Pack rows [r0 + lo, r0 + lo + n) (``n`` static, ``lo`` may be
        traced): one sequence at consecutive kv positions, so they share
        every key block and ride the MXU as one (n * nh, .) operand."""
        M = n * nh
        seq = seq_ref[r0 + lo]
        first = pos_ref[r0 + lo]
        last = first + (n - 1)
        steps = last // keys + 1
        # once a group of rows, not once a copy: each ``//`` of a traced
        # integer is a dozen operations to trace and lower, and a step's
        # copies asked 48 times (a third of a tick program's lowering)
        deepest = last // bs
        pad = pad_ref[seq]

        def copies(g, slot):
            for k in range(kb):
                # clamp to the rows' deepest in-range column: a step's
                # tail re-reads that block and masks it
                col = jnp.minimum(g * kb + k, deepest)
                yield pltpu.make_async_copy(
                    pool_ref.at[layer, table_ref[seq, col]],
                    buf.at[slot, pl.ds(k * bs, bs)], sem.at[slot])

        def start(g, slot):
            for c in copies(g, slot):
                c.start()

        acc_ref[0:M] = jnp.zeros((M, rank), jnp.float32)
        m_ref[0:M] = jnp.full((M, 1), _NEG_INF, jnp.float32)
        l_ref[0:M] = jnp.zeros((M, 1), jnp.float32)
        q_abs = qa_ref[pl.ds(lo, n)].reshape(M, rank)
        q_r = qr_ref[pl.ds(lo, n)].reshape(M, rope)
        # each row's own kv position, constant over its nh heads
        row_pos = first + lax.broadcasted_iota(
            jnp.int32, (n, nh, keys), 0).reshape(M, keys)
        start(0, 0)

        def step(g, _):
            slot = g % 2

            @pl.when(g + 1 < steps)
            def _prefetch():
                start(g + 1, 1 - slot)

            for c in copies(g, slot):
                c.wait()
            kv = buf[slot]                             # (keys, W)
            c_kv, k_r = kv[:, :rank], kv[:, rank:rank + rope]
            dims = (((1,), (1,)), ((), ()))            # contract the last
            sc = (dot(q_abs, c_kv, dims) + dot(q_r, k_r, dims)) * scale
            key_pos = g * keys + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            valid = (key_pos <= row_pos) & (key_pos >= pad)
            if select is not None:
                valid &= select(lo, n, g)
            sc = jnp.where(valid, sc, _NEG_INF)
            m_prev = m_ref[0:M]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[0:M] = alpha * l_ref[0:M] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[0:M] = acc_ref[0:M] * alpha + dot(
                p.astype(c_kv.dtype), c_kv, (((1,), (0,)), ((), ())))
            m_ref[0:M] = m_new
            return 0

        lax.fori_loop(0, steps, step, 0)
        out = acc_ref[0:M] / jnp.maximum(l_ref[0:M], 1e-30)
        o_ref[pl.ds(lo, n)] = out.reshape(n, nh, rank).astype(o_ref.dtype)

    first = pos_ref[r0]
    together = first >= 0
    real = first >= 0
    for k in range(1, rows):
        together &= (seq_ref[r0 + k] == seq_ref[r0]) \
            & (pos_ref[r0 + k] == first + k)
        real |= pos_ref[r0 + k] >= 0

    @pl.when(together)
    def _chunk():                # a prefill chunk's rows: one operand
        attend(0, rows)

    @pl.when(jnp.logical_not(together))
    def _each():                 # decode rows, a chunk's edge, padding
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(real)
        def _rows():
            def one(r, _):
                @pl.when(pos_ref[r0 + r] >= 0)
                def _row():
                    attend(r, 1)
                return 0
            lax.fori_loop(0, rows, one, 0)


def ragged_latent_attention_rows(q_abs, q_r, pool, table, row_seq, row_pos,
                                 pad_lens=None, *, scale, layer=None,
                                 interpret=False,
                                 blocks_per_step=BLOCKS_PER_STEP,
                                 rows_per_step=ROWS_PER_STEP):
    """q_abs (T, nh, R); q_r (T, nh, Dr); pool (NB+1, bs, W), W >= R + Dr
    (columns past R + Dr are padding and never read), block 0 the trash
    block — or, with ``layer`` (a traced int32 scalar), the pools of a
    whole stack (L, NB+1, bs, W) of which the kernel reads that layer's
    blocks in place (no slice of the stack is ever made); table (S, C)
    int32; row_seq (T,) int32 (padding rows may carry any value);
    row_pos (T,) int32 kv position per row, -1 for padding rows; pad_lens
    (S,) left-pad lengths (positions below masked) or None; ``scale`` the
    model's softmax scale (1 / sqrt(nope + rope), not a function of R).

    Returns (T, nh, R) in q_abs's dtype: each row's attention over its
    sequence's pool positions [pad, row_pos], in latent space (zeros for
    padding rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, R = q_abs.shape
    Dr = q_r.shape[-1]
    if layer is None:
        pool, layer = pool[None], 0
    _, NB1, bs, width = pool.shape
    assert width >= R + Dr, (pool.shape, R, Dr)
    S, C = table.shape
    kb = min(int(blocks_per_step), C)
    rows = _largest_divisor(T, rows_per_step)
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    # the engine marks padding rows with sequence -1; the kernel reads
    # ``pad_lens[row_seq]`` and ``table[row_seq]`` by it
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)

    kernel = functools.partial(_latent_kernel, bs=bs, kb=kb, rows=rows,
                               scale=float(scale), rank=R, rope=Dr)
    row_map = lambda i, tb, rs, rp, pp, ly: (i, 0, 0)
    M = rows * nh
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # table, row_seq, row_pos, pad, layer
        grid=(T // rows,),
        in_specs=[pl.BlockSpec((rows, nh, R), row_map),
                  pl.BlockSpec((rows, nh, Dr), row_map),
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
    with jax.named_scope("ragged_latent_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, nh, R), q_abs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="ragged_latent_attention",
        )(table.astype(jnp.int32), row_seq,
          jnp.asarray(row_pos, jnp.int32), jnp.asarray(pad_lens, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q_abs, q_r.astype(q_abs.dtype), pool)


def _largest_divisor(n, want):
    """The largest divisor of ``n`` not above ``want``."""
    d = min(int(want), int(n))
    while n % d:
        d -= 1
    return d


def ragged_latent_attention_ref(q_abs, q_r, pool, table, row_seq, row_pos,
                                pad_lens=None, *, scale, layer=None):
    """XLA fallback and oracle: densify each sequence's table-selected
    blocks, then the same scores, float32 softmax and latent output per
    row.  Same contract as ``ragged_latent_attention_rows``."""
    T, nh, R = q_abs.shape
    S, C = table.shape
    if layer is not None:
        pool = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    bs = pool.shape[1]
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    with jax.named_scope("ragged_latent_attention"):
        dense = pool[table].reshape(S, C * bs, pool.shape[-1])[seq]
        c_kv = dense[..., :R]                              # (T, C*bs, R)
        k_r = dense[..., R:R + q_r.shape[-1]]
        sc = jnp.einsum("thr,tkr->thk", q_abs, c_kv,
                        preferred_element_type=jnp.float32)
        sc = sc + jnp.einsum("thd,tkd->thk", q_r.astype(q_abs.dtype), k_r,
                             preferred_element_type=jnp.float32)
        pos = jnp.arange(C * bs)[None, :]
        valid = (pos <= row_pos[:, None]) & (pos >= pad_lens[seq][:, None])
        sc = jnp.where(valid[:, None, :], sc * scale, _NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        p = jnp.where(valid[:, None, :], p, 0.0).astype(pool.dtype)
        out = jnp.einsum("thk,tkr->thr", p, c_kv,
                         preferred_element_type=jnp.float32)
        return out.astype(q_abs.dtype)
