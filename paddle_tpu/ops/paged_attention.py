"""Pallas paged-attention decode kernel: attention over a block pool,
walking each slot's block table IN-KERNEL via scalar prefetch — no dense
gathered view ever materializes (the PagedKV.gather fallback's transient
disappears; PAPERS.md ragged paged attention, reshaped for this engine's
slot/table layout).

One query per slot (the serving engine's decode tick).  Grid is
(slots, table columns); the k/v BlockSpec index maps read the PREFETCHED
table — ``table[s, j]`` selects which physical pool block the next DMA
fetches — and an online-softmax accumulator runs across the column
dimension exactly like ops/attention.py's flash forward.  Per-slot clocks
and left-pad masks ride along as prefetched scalars.

Beyond the reference snapshot (no serving scheduler there; SURVEY §2.3).
Gated like every Pallas kernel here: real Mosaic lowering on TPU via
FLAGS_use_pallas_kernels, ``interpret=True`` for CPU CI.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _paged_decode_kernel(table_ref, t_ref, pad_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, bs, n_cols,
                         scale):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
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
        # Scores and the weighted sum are broadcast-multiply-and-reduce,
        # all rank 3 with heads on sublanes: Mosaic refuses the head-batched
        # dot_general whose rank-2 left operand has no free dimension, and
        # one query row could not fill the MXU anyway.
        sc = jnp.sum(q[None] * k, axis=-1, keepdims=True)   # (bs, nh, 1)
        pos = j * bs + lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        valid = (pos <= t_ref[s]) & (pos >= pad_ref[s])
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_ref[:]                              # (nh, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
        p = jnp.exp(sc - m_new[None])                  # (bs, nh, 1)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=0)
        acc_ref[:] = acc_ref[:] * alpha + jnp.sum(p * v, axis=0)
        m_ref[:] = m_new

    # columns past the clock: the clamped index map (see in_specs) makes
    # every skipped step re-map to the slot's LAST in-range block, which
    # Pallas does not re-fetch — pl.when then skips the FLOPs, so the
    # table tail costs neither DMA nor compute
    @pl.when(j * bs <= t_ref[s])
    def _run():
        body()

    @pl.when(j == n_cols - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q, pool_k, pool_v, table, t, pad_lens=None,
                           *, interpret=False):
    """Single-position attention over table-selected pool blocks.

    q (S, nh, hd); pool_k/pool_v (NB+1, bs, nh, hd); table (S, C) int32
    (inactive rows pre-zeroed to the trash block by the caller); t (S,)
    int32 per-slot clocks (query attends positions <= t); pad_lens (S,)
    int32 left-pad masks (positions < pad masked), or None.

    Returns (S, nh, hd) in q's dtype.  Exactly cached_attention's kq=1
    semantics over a PagedKV — tests pin the parity against the gather
    fallback."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, nh, hd = q.shape
    NB1, bs = pool_k.shape[:2]
    C = table.shape[1]
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_paged_decode_kernel, bs=bs, n_cols=C,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                   # table, t, pad
        grid=(S, C),
        in_specs=[
            pl.BlockSpec((1, nh, hd), lambda s, j, tb, tt, pp: (s, 0, 0)),
            # column clamped to the slot's clock: steps past it fetch the
            # same block again, which Pallas skips — real DMA savings for
            # short rows in a deep table
            pl.BlockSpec((1, bs, nh, hd),
                         lambda s, j, tb, tt, pp:
                         (tb[s, jnp.minimum(j, tt[s] // bs)], 0, 0, 0)),
            pl.BlockSpec((1, bs, nh, hd),
                         lambda s, j, tb, tt, pp:
                         (tb[s, jnp.minimum(j, tt[s] // bs)], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh, hd),
                               lambda s, j, tb, tt, pp: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, hd), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), jnp.asarray(t, jnp.int32),
      jnp.asarray(pad_lens, jnp.int32), q, pool_k, pool_v)
