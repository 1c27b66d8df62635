"""Exact top-k selection over a row of index scores, as a THRESHOLD: for
each pack row the pair ``(v, p)`` such that the selected kv positions are

    S_t = { s in [pad, row_pos] :  key(I[t, s]) > v
                                   or (key(I[t, s]) == v and s <= p) }

with ``|S_t| = min(k, row_pos - pad + 1)`` exactly, a tie at the edge
going to the LOWER position — the set ``jax.lax.top_k`` returns.  ``key``
is the order-preserving map of a float32 onto int32 (``score_key``).

No sort and no list of indices: the k-th largest key is built bit by bit
from the top, one counting pass over the row per bit (32 passes), and the
last tied position the same way over the positions (17 passes, kv
positions under 2**17).  What consumes the selection
(``ops/ragged_sparse_latent_attention.py``) turns ``(v, p)`` back into a
mask a block of keys at a time.  ``lax.approx_max_k`` would be a different
model.

``select_threshold_rows`` is the Pallas kernel (a group of pack rows a
grid step, their scores resident in VMEM; padding rows skipped),
``select_threshold_ref`` the XLA fallback and oracle, ``selected`` the
mask either stands for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .ragged_latent_attention import ROWS_PER_STEP, _largest_divisor

_INT_MIN = -(2 ** 31)
POS_BITS = 17           # kv positions below 2**17 = 131,072
LANES = 128             # width of the threshold's rows: (v, p, zeros)


def score_key(x):
    """float32 -> int32, order-preserving (``a < b`` iff ``key(a) <
    key(b)``; -0.0 sorts under +0.0)."""
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def selected(scores, thr, lo, hi):
    """The mask ``(v, p)`` stands for: scores (T, K) float32, thr (T, >=2)
    int32, lo / hi (T,) the first and last valid column of each row.
    Returns (T, K) bool."""
    col = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    key = score_key(scores)
    v, p = thr[:, 0:1], thr[:, 1:2]
    valid = (col >= lo[:, None]) & (col <= hi[:, None])
    return valid & ((key > v) | ((key == v) & (col <= p)))


def _count(cond):
    # counts stay under 2**24: exact in float32 (an int32 lane reduction
    # is not something every Mosaic lowers)
    return jnp.sum(jnp.where(cond, 1.0, 0.0), axis=1, keepdims=True)


def _threshold(keys, want):
    """(v, p) (rows, 1) int32 each.  ``keys()`` gives the masked keys
    (rows, K) (invalid columns at INT_MIN) each time a pass reads them
    (the kernel reads its VMEM scratch again; a value that size would not
    stay in registers anyway); want (rows, 1) float32 >= 1: the number to
    select."""
    top = jnp.where(_count(keys() >= 0) >= want, 0,
                    _INT_MIN).astype(jnp.int32)

    def value_bit(_, c):
        v, bit = c
        trial = v + bit
        return jnp.where(_count(keys() >= trial) >= want, trial, v), \
            bit >> 1

    v, _ = lax.fori_loop(0, 31, value_bit,
                         (top, jnp.full_like(top, 1 << 30)))
    need = want - _count(keys() > v)           # of the tied keys, >= 1

    def pos_bit(_, c):
        q, bit = c
        trial = q + bit
        key = keys()
        col = lax.broadcasted_iota(jnp.int32, key.shape, 1)
        under = _count((key == v) & (col < trial))
        return jnp.where(under < need, trial, q), bit >> 1

    # the largest q with fewer than ``need`` tied columns under it: the
    # ``need``-th tied column itself
    p, _ = lax.fori_loop(0, POS_BITS, pos_bit,
                         (jnp.zeros_like(top),
                          jnp.full_like(top, 1 << (POS_BITS - 1))))
    return v, p


def _select_kernel(seq_ref, pos_ref, pad_ref, s_ref, o_ref, key_ref, *,
                   rows, k):
    from jax.experimental import pallas as pl

    r0 = pl.program_id(0) * rows
    real = pos_ref[r0] >= 0
    for r in range(1, rows):
        real |= pos_ref[r0 + r] >= 0

    @pl.when(jnp.logical_not(real))
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real)
    def _rows():
        K = s_ref.shape[1]
        sub = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        lo = jnp.zeros((rows, 1), jnp.int32)
        hi = jnp.full((rows, 1), -1, jnp.int32)
        for r in range(rows):
            lo = jnp.where(sub == r, pad_ref[seq_ref[r0 + r]], lo)
            hi = jnp.where(sub == r, pos_ref[r0 + r], hi)
        col = lax.broadcasted_iota(jnp.int32, (rows, K), 1)
        key_ref[...] = jnp.where((col >= lo) & (col <= hi),
                                 score_key(s_ref[...]), _INT_MIN)
        # a padding row inside a real group selects nothing: want 1 of 0
        want = jnp.clip(hi - lo + 1, 1, k).astype(jnp.float32)
        v, p = _threshold(lambda: key_ref[...], want)
        lane = lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
        o_ref[...] = jnp.where(lane == 0, v, jnp.where(lane == 1, p, 0))


def select_threshold_rows(scores, row_seq, row_pos, pad_lens, *, k,
                          interpret=False, rows_per_step=ROWS_PER_STEP):
    """scores (T, K) float32 (column = kv position); row_seq / row_pos
    (T,) int32, -1 positions for padding rows; pad_lens (S,) int32.
    Returns (T, 128) int32: column 0 the threshold key ``v``, column 1 the
    last tied position ``p`` (zeros elsewhere and for padding rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, K = scores.shape
    assert K <= 1 << POS_BITS, K
    S = pad_lens.shape[0]
    rows = _largest_divisor(T, rows_per_step)
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # row_seq, row_pos, pad
        grid=(T // rows,),
        in_specs=[pl.BlockSpec((rows, K), lambda i, *_: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, K), jnp.int32)],
    )
    with jax.named_scope("select"):
        return pl.pallas_call(
            functools.partial(_select_kernel, rows=rows, k=int(k)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, LANES), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="index_select",
        )(row_seq, jnp.asarray(row_pos, jnp.int32),
          jnp.asarray(pad_lens, jnp.int32), scores)


def select_threshold_ref(scores, row_seq, row_pos, pad_lens, *, k):
    """XLA fallback and oracle, by ``lax.top_k``'s own order: the k-th
    entry of the sorted keys is ``v``, and ``p`` the position of the last
    tied key it returned.  Same contract as ``select_threshold_rows``
    (two columns)."""
    T, K = scores.shape
    S = pad_lens.shape[0]
    seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    hi = jnp.asarray(row_pos, jnp.int32)
    lo = jnp.asarray(pad_lens, jnp.int32)[seq]
    with jax.named_scope("select"):
        col = jnp.arange(K, dtype=jnp.int32)[None, :]
        valid = (col >= lo[:, None]) & (col <= hi[:, None])
        key = jnp.where(valid, score_key(scores), _INT_MIN)
        top, idx = lax.top_k(key, min(int(k), K))
        last = jnp.clip(jnp.minimum(hi - lo + 1, k) - 1, 0,
                        top.shape[1] - 1)[:, None]
        v = jnp.take_along_axis(top, last, axis=1)
        # top_k lists tied keys by rising position: the last it took
        p = jnp.max(jnp.where((top == v) & (jnp.arange(top.shape[1])[None]
                                            <= last), idx, -1),
                    axis=1, keepdims=True)
        return jnp.where(hi[:, None] >= 0,
                         jnp.concatenate([v, p.astype(jnp.int32)], 1), 0)
