"""Shared KV-cache decode machinery for the causal LMs (GPT, ERNIE-MoE).

≙ the reference snapshot's incremental decode stack: MultiHeadAttention
.Cache/gen_cache k/v (python/paddle/nn/layer/transformer.py:151) +
dynamic_decode/BeamSearchDecoder (python/paddle/nn/decode.py) +
sampling_id/top_k ops (operators/sampling_id_op.cc).  (The later-Paddle
ecosystem's paddlenlp generation_utils / fused_multi_transformer CacheKV
are NOT in this snapshot.)  One module so the mask/scale/precision
conventions and the sampler cannot drift between model families.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class CacheLeaf(NamedTuple):
    """One cache leaf as the model states it: ``layers`` of them stacked,
    ``tail`` the shape of one row's entry, ``dtype`` its dtype, and how a
    row is addressed (docs/CACHE_SPEC.md):

    - per token by the block table (the default): a paged engine stores it
      as ``(layers, NB + 1, block_size) + tail`` (block 0 the trash block),
      a dense cache as ``(layers, B, max_len) + tail``;
    - ``tokens_per_row`` > 1: by the table still, one row per that many
      positions (a leaf that pages BY CHUNK): a block of ``block_size``
      rows names ``block_size * tokens_per_row`` positions;
    - ``slot_rows`` > 0: the leaf does not page.  ``(layers, slots,
      slot_rows) + tail``, a sequence's own rows addressed by its slot
      and ``position mod slot_rows``, allocated and freed with the
      slot."""
    layers: int
    tail: Tuple[int, ...]
    dtype: str
    tokens_per_row: int = 1
    slot_rows: int = 0


class CacheSpec(NamedTuple):
    """What a model caches (docs/CACHE_SPEC.md).  The serving engines read
    this, never the model's class.

    ``pools``: a tuple of entries in the order ``decode_ragged`` takes and
    returns them, each a pytree of ``CacheLeaf`` (an int8 K plane is a
    ``(values, scales)`` pair of leaves; a latent stack with a lightning
    indexer a ``(latent row, indexer key)`` pair, two leaves on the one
    block table).  ``layout``: "kv" for one K and
    one V entry per head and layer (what the tiered KV store and the
    bucketed paged programs are written for); any other name is the
    model's own.  ``tick_stats``: names of the int32 counters
    ``decode_ragged`` returns as a third output, one vector entry each
    (empty: it returns two outputs).  ``row_boundary`` > 0: no pack may
    hold rows of one sequence on both sides of a multiple of it (counted
    from the sequence's first real position)."""
    pools: tuple
    layout: str = "kv"
    tick_stats: Tuple[str, ...] = ()
    row_boundary: int = 0


def spec_leaves(spec: CacheSpec):
    return jax.tree.leaves(spec.pools,
                           is_leaf=lambda x: isinstance(x, CacheLeaf))


def tokens_per_row(spec: CacheSpec) -> int:
    """Positions one row of the spec's table-addressed leaves stands for.
    They share one table and one allocator, so they must agree."""
    per = {leaf.tokens_per_row for leaf in spec_leaves(spec)
           if not leaf.slot_rows}
    if len(per) > 1:
        raise ValueError(f"leaves on one block table must cover the same "
                         f"positions a row, got {sorted(per)}")
    return per.pop() if per else 1


def build_pools(spec: CacheSpec, lead: Tuple[int, ...], slots=None):
    """Zeroed storage for ``spec``: every table-addressed leaf ``(layers,)
    + lead + tail`` (``lead`` is ``(NB + 1, block_size)`` for a block
    pool), every leaf that does not page ``(layers, slots, slot_rows) +
    tail``."""
    def one(leaf):
        at = tuple(lead)
        if leaf.slot_rows:
            if slots is None:
                raise ValueError("a leaf that does not page needs `slots`")
            at = (int(slots), leaf.slot_rows)
        return jnp.zeros((leaf.layers,) + at + leaf.tail,
                         jnp.dtype(leaf.dtype))
    return jax.tree.map(one, spec.pools,
                        is_leaf=lambda x: isinstance(x, CacheLeaf))


def rms_norm(x, w, eps, unit_offset=False):
    """RMSNorm over the last axis in float32, back in x's dtype:
    ``x / sqrt(mean(x^2) + eps) * w`` — or ``* (1 + w)`` under
    ``unit_offset`` (a scale stored as its distance from one)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    w32 = w.astype(jnp.float32)
    return (y * (1.0 + w32 if unit_offset else w32)).astype(x.dtype)


def rope_rotate_half(x, pos, inv_freq):
    """Rotate-half rotary positions over the last axis of x (..., heads,
    D) at positions ``pos`` (...,), ``inv_freq`` the D / 2 frequencies."""
    D = x.shape[-1]
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq  # (..,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def cached_attention(q, ck, cv, t, pad_lens=None):
    """Attention for new tokens written at cache slots [t, t+k) against a
    static KV cache: query row i attends to positions ≤ t + i (causal within
    the chunk, full history before it; slots beyond hold zeros or stale
    values).  q (B, k, nh, hd) — k = 1 is the plain decode step, k > 1 is the
    chunk form used by speculative-decoding verification.  ``pad_lens`` (B,)
    int32 additionally masks the first pad_lens[b] cache slots (left-padded
    prompts).  Shared by the GPT and ERNIE-MoE decode paths so the mask/
    scale/precision conventions cannot drift."""
    if isinstance(ck, PagedKV):
        from ..core.flags import flag
        kernel_ok = (q.shape[1] == 1                 # the decode tick
                     and not isinstance(ck.pool, tuple))   # fp pools only
        # FLAGS_use_pallas_kernels stays the authoritative kill switch: off,
        # no kernel runs anywhere.  The interpret arm applies only OFF-TPU
        # (CPU CI of the in-kernel table walk)
        interp = (bool(flag("FLAGS_paged_attn_interpret"))
                  and jax.default_backend() != "tpu")
        use = flag("FLAGS_use_pallas_kernels") and \
            (jax.default_backend() == "tpu" or interp)
        if kernel_ok and use:
            from ..ops.paged_attention import paged_decode_attention
            S = q.shape[0]
            t_vec = jnp.broadcast_to(jnp.asarray(t), (S,))
            pad_vec = (None if pad_lens is None
                       else jnp.broadcast_to(jnp.asarray(pad_lens), (S,)))
            o = paged_decode_attention(q[:, 0], ck.pool, cv.pool, ck.table,
                                       t_vec, pad_vec, interpret=interp)
            return o[:, None]
        # fallback: densify this layer's table-selected blocks
        ck = ck.gather(q.dtype)
        cv = cv.gather(q.dtype)
    kq = q.shape[1]
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck) / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    row = jnp.arange(kq)[:, None]
    col = jnp.arange(ck.shape[1])[None, :]
    t_arr = jnp.asarray(t)
    if t_arr.ndim == 0:                                # one slot for all rows
        mask = (col <= t_arr + row)[None, None]        # (1, 1, k, max_len)
    else:                                              # per-row slots (B,)
        mask = (col[None, None] <=
                t_arr[:, None, None, None] + row[None, None])
    if pad_lens is not None:
        pos = jnp.arange(ck.shape[1])
        mask = mask & (pos[None, :] >= pad_lens[:, None])[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, cv)


@jax.tree_util.register_pytree_node_class
class PagedKV:
    """One k-or-v cache over a BLOCK POOL + slot block table (the serving
    engine's paged layout, flowing through the same decode code path as
    dense caches via dispatch in write_cache/cached_attention).

    ``pool``: (NB+1, bs, nh, hd) — or with a leading layer axis, which
    lax.scan over layers slices off; block 0 is the reserved trash block.
    int8 pools are (values, scales) pairs.  ``table``: (S, C) int32 —
    C table columns cover every ACTIVE row's positions; inactive rows'
    table rows must be pre-zeroed by the caller (their writes then land
    in trash even where the clamped column lookup would alias a real
    block).  As a pytree, scanning over layers slices pool and table
    together (the engine broadcasts the table across layers)."""

    def __init__(self, pool, table):
        self.pool = pool
        self.table = table

    def tree_flatten(self):
        return (self.pool, self.table), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def block_size(self):
        # axis 1 of the PER-LAYER pool is bs for BOTH planes — the value
        # plane is (NB+1, bs, nh, hd), the int8 scale plane (NB+1, bs, nh)
        # is one rank short, so a from-the-right index would be wrong
        vals = self.pool[0] if isinstance(self.pool, tuple) else self.pool
        return vals.shape[1]

    def gather(self, dtype):
        """Dense (S, C·bs, nh, hd) view of the table-selected blocks —
        the XLA fallback read path (one layer at a time inside the layer
        scan, so the transient is 1/L of the all-layer view; a Pallas
        kernel walking the table in-kernel replaces this on TPU).
        Gather FIRST, then dequantize: only the S·C selected blocks pay
        the int8→fp convert, never the whole pool."""
        picked = jax.tree.map(lambda p: p[self.table], self.pool)
        g = dequantize_cache(picked, dtype)        # (S, C, bs, nh, hd)
        return g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                         + g.shape[3:])

    def write(self, chunk, t):
        """Write a (S, kq, …) chunk at per-row positions [t, t+kq) through
        the table (column lookup clamped; pre-zeroed inactive rows land in
        trash)."""
        if isinstance(self.pool, tuple):
            vals, scales = self.pool
            q, s = quantize_kv(chunk)
            return PagedKV((PagedKV(vals, self.table).write(q, t).pool,
                            PagedKV(scales, self.table).write(s, t).pool),
                           self.table)
        bs = self.block_size
        t_arr = jnp.asarray(t)
        B, kq = chunk.shape[:2]
        if t_arr.ndim == 0:
            t_arr = jnp.broadcast_to(t_arr, (B,))
        rows = jnp.arange(B)[:, None]
        slots = t_arr[:, None] + jnp.arange(kq)[None, :]   # (S, kq)
        col = jnp.minimum(slots // bs, self.table.shape[1] - 1)
        pb = self.table[rows, col]
        off = slots % bs
        pool = self.pool.at[pb, off].set(chunk.astype(self.pool.dtype))
        return PagedKV(pool, self.table)


def ragged_attention(q_rows, pool_k, pool_v, table, row_seq, row_pos,
                     pad_lens=None, layer=None):
    """Attention for a flattened ragged pack of rows over ONE layer's block
    pools (the mixed prefill+decode serving step): q_rows (T, nh, hd),
    pools (NB+1, bs, nh, hd) — int8 ``(values, scales)`` pairs included —
    or a stack's pools (L, NB+1, bs, nh, hd) and ``layer``, read in place;
    table (S, C), row_seq/row_pos (T,) per-row metadata (see
    ops/ragged_paged_attention.ragged_rows), pad_lens (S,).

    Dispatches between the Pallas in-kernel table walk (compiled on the TPU;
    interpreted off it where FLAGS_paged_attn_interpret asks, for CPU CI;
    never with FLAGS_use_pallas_kernels off: ``_pallas_dispatch``) and the XLA
    gather fallback; int8 pools take the kernel too (dequant fused in-kernel)."""
    from ..ops.ragged_paged_attention import (ragged_attention_ref,
                                              ragged_attention_rows)
    use, interp = _pallas_dispatch()
    if use:
        return ragged_attention_rows(q_rows, pool_k, pool_v, table,
                                     row_seq, row_pos, pad_lens,
                                     layer=layer, interpret=interp)
    return ragged_attention_ref(q_rows, pool_k, pool_v, table, row_seq,
                                row_pos, pad_lens, layer=layer)


def _pallas_dispatch():
    """(use the Pallas kernel, run it interpreted): the flag convention
    ``ragged_attention`` and ``cached_attention`` share."""
    from ..core.flags import flag
    interp = (bool(flag("FLAGS_paged_attn_interpret"))
              and jax.default_backend() != "tpu")
    use = flag("FLAGS_use_pallas_kernels") and \
        (jax.default_backend() == "tpu" or interp)
    return bool(use), interp


def ragged_latent_attention(q_abs, q_r, pool, table, row_seq, row_pos,
                            pad_lens=None, *, scale, layer=None):
    """Absorbed latent (MLA) attention for a flattened ragged pack over
    ONE layer's latent pool: q_abs (T, nh, R), q_r (T, nh, Dr), pool
    (NB+1, bs, W >= R + Dr) — or a stack's pools (L, NB+1, bs, W) and
    ``layer``, read in place — the value of a key being its first R
    columns; output (T, nh, R) in latent space.  Dispatched like
    ``ragged_attention`` (ops/ragged_latent_attention.py)."""
    from ..ops.ragged_latent_attention import (
        ragged_latent_attention_ref, ragged_latent_attention_rows)
    use, interp = _pallas_dispatch()
    if use:
        return ragged_latent_attention_rows(
            q_abs, q_r, pool, table, row_seq, row_pos, pad_lens,
            scale=scale, layer=layer, interpret=interp)
    return ragged_latent_attention_ref(q_abs, q_r, pool, table, row_seq,
                                       row_pos, pad_lens, scale=scale,
                                       layer=layer)


def ragged_index_select(q_idx, w_idx, pool, table, row_seq, row_pos,
                        pad_lens, *, k, layer=None):
    """A lightning indexer's choice for a flattened ragged pack over ONE
    layer's pool of indexer keys (NB+1, bs, D) — or a stack's (L, NB+1,
    bs, D) and ``layer``: the index scores (T, C * bs) float32 of q_idx
    (T, nh, D) with head weights w_idx (T, nh) (region ``indexer``,
    ops/ragged_index_scores.py), and the threshold (T, >= 2) int32 that
    stands for each row's ``min(k, context)`` largest, exactly (region
    ``select``, ops/index_select.py).  Dispatched like
    ``ragged_attention``."""
    from ..ops.index_select import (select_threshold_ref,
                                    select_threshold_rows)
    from ..ops.ragged_index_scores import (ragged_index_scores_ref,
                                           ragged_index_scores_rows)
    use, interp = _pallas_dispatch()
    with jax.named_scope("indexer"):
        if use:
            scores = ragged_index_scores_rows(
                q_idx, w_idx, pool, table, row_seq, row_pos, layer=layer,
                interpret=interp)
        else:
            scores = ragged_index_scores_ref(
                q_idx, w_idx, pool, table, row_seq, row_pos, layer=layer)
    if use:
        thr = select_threshold_rows(scores, row_seq, row_pos, pad_lens, k=k,
                                    interpret=interp)
    else:
        thr = select_threshold_ref(scores, row_seq, row_pos, pad_lens, k=k)
    return scores, thr


def ragged_sparse_latent_attention(q_abs, q_r, pool, scores, thr, table,
                                   row_seq, row_pos, pad_lens=None, *,
                                   scale, layer=None):
    """``ragged_latent_attention`` over the kv positions that ``(scores,
    thr)`` of ``ragged_index_select`` name, and no others
    (ops/ragged_sparse_latent_attention.py)."""
    from ..ops.ragged_sparse_latent_attention import (
        ragged_sparse_latent_attention_ref,
        ragged_sparse_latent_attention_rows)
    use, interp = _pallas_dispatch()
    if use:
        return ragged_sparse_latent_attention_rows(
            q_abs, q_r, pool, scores, thr, table, row_seq, row_pos,
            pad_lens, scale=scale, layer=layer, interpret=interp)
    return ragged_sparse_latent_attention_ref(
        q_abs, q_r, pool, scores, thr, table, row_seq, row_pos, pad_lens,
        scale=scale, layer=layer)


def ragged_write(pool, chunk, table, row_seq, row_pos, layer=None):
    """Scatter a flattened ragged chunk (T, nh, hd) into ONE layer's block
    pool at each row's (table-mapped block, offset); padding rows
    (row_pos < 0) land in the trash block.  int8 pools quantize the chunk
    and write both planes (quantize_kv layout).  With ``layer`` the pool
    is a whole stack's (L, NB+1, bs, ...) — each plane of an int8 pair
    too — and the rows land in that layer's blocks, in place."""
    if isinstance(pool, tuple):
        vals, scales = pool
        with jax.named_scope("kv_write"):
            q, s = quantize_kv(chunk)
        return (ragged_write(vals, q, table, row_seq, row_pos, layer),
                ragged_write(scales, s, table, row_seq, row_pos, layer))
    with jax.named_scope("kv_write"):
        bs = pool.shape[1 if layer is None else 2]
        seq = jnp.clip(row_seq, 0, table.shape[0] - 1)
        col = jnp.clip(row_pos // bs, 0, table.shape[1] - 1)
        pb = jnp.where(row_pos >= 0, table[seq, col], 0)
        off = jnp.where(row_pos >= 0, row_pos % bs, 0)
        if layer is not None:
            return pool.at[layer, pb, off].set(chunk.astype(pool.dtype))
        return pool.at[pb, off].set(chunk.astype(pool.dtype))


def slot_write(pool, chunk, row_seq, row_pos, layer):
    """Write a flattened ragged chunk (T, ...) into a leaf that does not
    page, a whole stack's ``(L, slots, rows, ...)``: each row at ``[layer,
    row_seq, row_pos mod rows]``, in place; a padding row (row_pos < 0)
    is dropped."""
    with jax.named_scope("kv_write"):
        S, R = pool.shape[1:3]
        seq = jnp.where(row_pos >= 0, jnp.clip(row_seq, 0, S - 1), S)
        return pool.at[layer, seq, row_pos % R].set(
            chunk.astype(pool.dtype), mode="drop")


def eva_summarize(win_k, win_v, phi, mu, seq, chunk_at, *, chunk, scale,
                  layer=None):
    """The summaries ``(k~, v~)``, each (N, nh, hd), of the N chunks
    ``chunk_at`` (index inside the window) of slots ``seq``, read from
    the window leaf (ops/eva_summarize.py).  Dispatched like
    ``ragged_attention``."""
    from ..ops.eva_summarize import eva_summarize_ref, eva_summarize_rows
    use, interp = _pallas_dispatch()
    if use:
        return eva_summarize_rows(win_k, win_v, phi, mu, seq, chunk_at,
                                  chunk=chunk, scale=scale, layer=layer,
                                  interpret=interp)
    return eva_summarize_ref(win_k, win_v, phi, mu, seq, chunk_at,
                             chunk=chunk, scale=scale, layer=layer)


def ragged_eva_attention(q, window, summaries, table, row_seq, row_pos, *,
                         chunk, scale, layer=None):
    """EVA attention for a flattened ragged pack: q (T, nh, hd) over
    ``window`` = (K, V) of a leaf that does not page (slots, W, nh, hd)
    and ``summaries`` = (K~, V~) of a leaf paged by chunk (NB+1, bs, nh,
    hd) — or whole stacks' and ``layer``, read in place; one softmax over
    a row's window rows [0, pos mod W] and the summaries of every earlier
    window (ops/ragged_eva_attention.py).  Dispatched like
    ``ragged_attention``."""
    from ..ops.ragged_eva_attention import (ragged_eva_attention_ref,
                                            ragged_eva_attention_rows)
    use, interp = _pallas_dispatch()
    if use:
        return ragged_eva_attention_rows(
            q, *window, *summaries, table, row_seq, row_pos, chunk=chunk,
            scale=scale, layer=layer, interpret=interp)
    return ragged_eva_attention_ref(
        q, *window, *summaries, table, row_seq, row_pos, chunk=chunk,
        scale=scale, layer=layer)


def write_cache(cache, chunk, t):
    """Write a (B, kq, nh, hd) k/v chunk into the cache at slots [t, t+kq):
    scalar ``t`` → one dynamic_update_slice; per-row (B,) ``t`` → scatter
    (batched speculative decoding, rows at different positions).

    ``cache`` may be a quantized pair ``(values_int8, scales)`` (see
    ``quantize_kv``) — the chunk is quantized and both planes written —
    or a ``PagedKV`` (block-pool writes through the slot table)."""
    with jax.named_scope("kv_write"):
        if isinstance(cache, PagedKV):
            return cache.write(chunk, t)
        if isinstance(cache, tuple):
            vals, scales = cache
            q, s = quantize_kv(chunk)
            return (write_cache(vals, q, t), write_cache(scales, s, t))
        t_arr = jnp.asarray(t)
        if t_arr.ndim == 0:
            # rank-generic: the int8 scale plane is (B, T, nh), one rank
            # short of the (B, T, nh, hd) value plane
            return jax.lax.dynamic_update_slice(
                cache, chunk.astype(cache.dtype),
                (0, t_arr) + (0,) * (cache.ndim - 2))
        B, kq = chunk.shape[:2]
        rows = jnp.arange(B)[:, None]
        slots = t_arr[:, None] + jnp.arange(kq)[None, :]
        return cache.at[rows, slots].set(chunk.astype(cache.dtype))


def quantize_kv(x):
    """Symmetric int8 quantization of a k/v tensor over its LAST axis (one
    scale per (…, head, position) vector): HBM traffic for the decode-loop
    cache reads — the serving bottleneck — drops to half of bf16.

    Beyond this reference snapshot (its decode cache is fp only —
    MultiHeadAttention.Cache, python/paddle/nn/layer/transformer.py:151;
    int8 cache-KV serving arrives in the later-Paddle ecosystem's
    fused_multi_transformer path).  TPU-shape: the scale plane rides NEXT
    TO the int8 plane and dequantization fuses into the attention einsum's
    operand read, so no fp copy of the cache ever materializes."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=False)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_cache(cache, dtype):
    """(values_int8, scales) → dense ``dtype`` array; plain arrays pass
    through (so attention call sites stay cache-format agnostic).
    ``PagedKV`` defers to attention time (cached_attention gathers —
    or a Pallas kernel reads the pool directly)."""
    if isinstance(cache, PagedKV):
        return cache
    if isinstance(cache, tuple):
        vals, scales = cache
        return (vals.astype(jnp.float32) * scales[..., None]).astype(dtype)
    return cache


def filter_logits(logits32, temperature, top_k, top_p):
    """The temperature → top-k → nucleus (top-p) filtering pipeline on the
    last axis of an (..., V) fp32 logits array (position-generic: used for
    the single decode position and for speculative verify chunks)."""
    logits32 = logits32 / jnp.asarray(max(temperature, 1e-6), jnp.float32)
    if top_k is not None:
        vals, _ = jax.lax.top_k(logits32, top_k)
        logits32 = jnp.where(logits32 < vals[..., -1:], -jnp.inf, logits32)
    if top_p is not None:
        # nucleus: keep the smallest prefix of the sorted vocab with
        # cumulative probability ≥ top_p (the boundary token stays)
        srt = jnp.flip(jnp.sort(logits32, -1), -1)
        cdf = jnp.cumsum(jax.nn.softmax(srt, -1), -1)
        n_keep = jnp.sum(cdf < top_p, -1) + 1
        kth = jnp.take_along_axis(srt, (n_keep - 1)[..., None], -1)
        logits32 = jnp.where(logits32 < kth, -jnp.inf, logits32)
    return logits32


def apply_repetition_penalty(logits32, presence, penalty):
    """Reference generation_utils / HF RepetitionPenaltyLogitsProcessor
    semantics: for every token already seen in the row (prompt + generated,
    tracked in the (B, V) ``presence`` mask), positive logits divide by the
    penalty and negative logits multiply — both push the token down for
    penalty > 1.  ``penalty`` may be a scalar or a per-row (B,) vector
    (the serving engine's per-request planes); 1.0 is an exact no-op."""
    penalty = jnp.asarray(penalty)
    if penalty.ndim == 1:
        penalty = penalty[:, None]
    pen = jnp.where(logits32 > 0, logits32 / penalty, logits32 * penalty)
    return jnp.where(presence, pen, logits32)


def seed_presence(ids, vocab_size, pad_lens=None):
    """(B, P) prompt ids → (B, V) bool presence plane for the repetition
    penalty, pad positions excluded — ONE copy of the seeding invariant,
    shared by generate() and the serving engine's admission prefill."""
    B, P = ids.shape
    valid = (jnp.ones_like(ids, dtype=bool) if pad_lens is None else
             jnp.arange(P)[None, :] >= pad_lens[:, None])
    return jnp.zeros((B, vocab_size), bool).at[
        jnp.arange(B)[:, None], ids].max(valid)


def suppress_eos(logits32, eos_token_id, suppress):
    """Mask the EOS column with -inf while ``suppress`` — scalar bool (one
    window for the whole batch) or (B,) bool (per-row windows, the serving
    engine's case).  The min_new_tokens contract (HF
    MinNewTokensLengthLogitsProcessor)."""
    col = jnp.arange(logits32.shape[-1]) == eos_token_id
    sup = jnp.asarray(suppress)
    if sup.ndim == 0:
        sup = sup[None]
    return jnp.where(sup[:, None] & col[None, :], -jnp.inf, logits32)


def filter_logits_rows(logits32, temperature, top_k, top_p):
    """``filter_logits`` with PER-ROW parameters as traced data — the
    serving engine's per-request sampling planes (one compiled program for
    any mix of configs; row params are operands, not constants).

    (B, V) fp32 logits; temperature/top_p (B,) fp32, top_k (B,) int32.
    Disabled encodings are exact no-ops: top_k <= 0 or > V keeps every
    token; top_p >= 2.0 is the None encoding (cdf < 2 always holds, so the
    cut sits at the global minimum and nothing is masked)."""
    l = logits32 / jnp.maximum(temperature, 1e-6)[:, None]
    V = l.shape[-1]
    srt = jnp.flip(jnp.sort(l, -1), -1)
    k = jnp.where((top_k <= 0) | (top_k > V), V, top_k)
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], -1)
    l = jnp.where(l < kth, -jnp.inf, l)
    # nucleus on the (possibly top-k-masked) logits, same order as
    # filter_logits: keep the smallest sorted prefix with cdf >= top_p.
    # No second sort needed — masking only floors values strictly below
    # kth to -inf, which preserves srt's descending order
    srt2 = jnp.where(srt < kth, -jnp.inf, srt)
    cdf = jnp.cumsum(jax.nn.softmax(srt2, -1), -1)
    n_keep = jnp.sum(cdf < top_p[:, None], -1) + 1
    kth2 = jnp.take_along_axis(srt2, (jnp.minimum(n_keep, V) - 1)[:, None],
                               -1)
    return jnp.where(l < kth2, -jnp.inf, l)


def make_row_sampler():
    """Per-row sampler over the per-request planes: greedy rows argmax,
    sampling rows draw categorically from the row-filtered logits —
    one program serves any mixture."""
    def sample(logits32, key, temperature, top_k, top_p, greedy):
        l = filter_logits_rows(logits32[:, -1, :], temperature, top_k,
                               top_p)
        return jnp.where(greedy, jnp.argmax(l, -1),
                         jax.random.categorical(key, l, -1)
                         ).astype(jnp.int32)
    return sample


def suppress_eos_rows(logits32, eos_ids, suppress):
    """Per-row EOS suppression for per-request windows: ``eos_ids`` (B,)
    int32 with -1 = this row has no EOS; ``suppress`` (B,) bool."""
    col = jnp.arange(logits32.shape[-1])[None, :] == eos_ids[:, None]
    return jnp.where(col & suppress[:, None], -jnp.inf, logits32)


def make_token_sampler(temperature, top_k, top_p, greedy):
    """Shared last-position sampler for the decode loops (GPT + ERNIE-MoE):
    the filter_logits pipeline then argmax or categorical.  ``logits32`` is
    (B, 1, V) fp32."""
    def sample(logits32, key):
        logits32 = filter_logits(logits32[:, -1, :], temperature, top_k,
                                 top_p)
        if greedy:
            return jnp.argmax(logits32, -1).astype(jnp.int32)
        return jax.random.categorical(key, logits32, -1).astype(jnp.int32)
    return sample


def greedy_verify(d, tpred, active=None):
    """THE greedy speculative-acceptance contract, shared by
    ``generate_speculative`` and the ragged serving engine's fused
    draft+verify step so the semantics cannot drift: accept the longest
    prefix of the draft proposals ``d`` (B, K) that matches the target's
    argmax predictions ``tpred`` (B, K+1) position for position, and
    emit the target's own prediction at the first mismatch (or the bonus
    position when everything matched) — by construction the emitted
    stream equals plain greedy decode token for token.

    ``active`` (B,) bool optionally masks rows whose proposals are
    garbage (a mixed spec/non-spec batch): masked rows get ``lead`` 0,
    so their emitted token is simply ``tpred[:, 0]`` — plain greedy
    decode through the same code path.

    Returns ``(lead, block)``: per-row accepted counts and the (B, K+1)
    token block whose first ``lead + 1`` entries are the round's emitted
    tokens (``d_0..d_{lead-1}``, then the replacement at ``lead``)."""
    B, K = d.shape
    lead = jnp.sum(jnp.cumprod(
        (d == tpred[:, :K]).astype(jnp.int32), axis=1), axis=1)
    if active is not None:
        lead = jnp.where(active, lead, 0)
    repl = jnp.take_along_axis(
        tpred, jnp.minimum(lead, K)[:, None], 1)[:, 0]
    block = jnp.concatenate([d, jnp.zeros((B, 1), jnp.int32)], axis=1)
    block = block.at[jnp.arange(B), lead].set(repl)
    return lead, block


def speculative_accept(q_probs, p_probs, d_tokens, key):
    """Leviathan/Chen acceptance-rejection for one speculative round — the
    output token sequence is distributed EXACTLY as autoregressive sampling
    from the target distributions ``p`` (the lossless-in-distribution
    guarantee; tests/test_generate.py checks the marginal empirically).

    q_probs (B, K, V): draft distributions the K proposed tokens were drawn
    from; p_probs (B, K+1, V): target distributions at the same positions
    plus the bonus position; d_tokens (B, K): the draft proposals.

    Returns (lead (B,), repl (B,)): per row, the count of accepted draft
    tokens and the replacement token for position ``lead`` — drawn from the
    residual distribution norm(max(p - q, 0)) on rejection, or from the
    bonus target distribution when every proposal was accepted.
    """
    B, K, V = q_probs.shape
    key_u, key_r = jax.random.split(key)
    u = jax.random.uniform(key_u, (B, K))
    qd = jnp.take_along_axis(q_probs, d_tokens[..., None], -1)[..., 0]
    pd = jnp.take_along_axis(p_probs[:, :K], d_tokens[..., None], -1)[..., 0]
    accept = u * qd < pd                  # u < p/q without dividing by 0
    lead = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    # residual distribution at the first rejected position (bonus p when
    # lead == K); gather per-row with a clamped index then overwrite
    idx = jnp.minimum(lead, K - 1)
    p_at = jnp.take_along_axis(p_probs, idx[:, None, None]
                               .repeat(V, -1), 1)[:, 0]          # (B, V)
    q_at = jnp.take_along_axis(q_probs, idx[:, None, None]
                               .repeat(V, -1), 1)[:, 0]
    resid = jnp.maximum(p_at - q_at, 0.0)
    resid = resid / jnp.maximum(jnp.sum(resid, -1, keepdims=True), 1e-20)
    dist = jnp.where((lead == K)[:, None], p_probs[:, K], resid)
    repl = jax.random.categorical(
        key_r, jnp.log(jnp.maximum(dist, 1e-20)), -1).astype(jnp.int32)
    return lead, repl


def validate_sampler_args(vocab_size, top_k, top_p, greedy, key):
    """Common generate() argument validation (fail before tracing)."""
    if not greedy and key is None:
        raise ValueError("sampling (greedy=False) requires key")
    if top_k is not None and not 1 <= int(top_k) <= vocab_size:
        raise ValueError(f"top_k must be in [1, vocab_size={vocab_size}], "
                         f"got {top_k}")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")



class CausalDecoderMixin:
    """KV-cache generation shared by the causal LMs (GPT, ERNIE-MoE).

    ≙ the reference snapshot's MultiHeadAttention.Cache/gen_cache
    incremental decode (python/paddle/nn/layer/transformer.py:151) driven
    by dynamic_decode (python/paddle/nn/decode.py).  TPU-native shape: the cache is a
    STATIC (num_layers, B, max_len, nh, hd) buffer written with
    dynamic_update_slice, the decode loop is one lax.scan — a single XLA
    program regardless of how many tokens are generated, memoized per
    signature.

    Host-class contract: ``self.config`` (vocab_size, compute_dtype,
    max_position_embeddings, num_layers, num_attention_heads, hidden_size),
    ``prefill(params, ids, max_len) -> (h, caches)``,
    ``decode_step(params, h, caches, t) -> (h, caches)``,
    ``_block_decode_ragged(sl, h, pool_k, pool_v, table, row_seq, row_pos,
    pad_lens, layer) -> (h, pool_k, pool_v)`` (for ``decode_ragged``),
    ``decode_logits(params, h) -> fp32 (B, 1, V)``, and wte/wpe param keys.
    """

    def _prefill_embed(self, params, input_ids, pad_lens):
        """Embed a (left-padded) prompt: positions shift by the per-row pad
        length so real tokens get logical positions 0..n-1."""
        dt = jnp.dtype(self.config.compute_dtype)
        P = input_ids.shape[1]
        with jax.named_scope("embed"):
            pos = jnp.maximum(jnp.arange(P)[None, :] - pad_lens[:, None], 0)
            h = jnp.take(params["wte"], input_ids, axis=0) \
                + jnp.take(params["wpe"], pos, axis=0)
            return h.astype(dt)

    @staticmethod
    def _prefill_key_mask(P, pad_lens):
        """Additive key mask for a left-padded prompt: finite -1e30 on pad
        columns (all-pad causal rows then produce garbage-but-finite values
        that nothing reads, instead of NaNs)."""
        return jnp.where(jnp.arange(P)[None, :] < pad_lens[:, None],
                         -1e30, 0.0).astype(jnp.float32)

    @staticmethod
    def _validate_prompt_mask(prompt_mask, input_ids):
        """Eager checks (mask is a host array at generate() time): shape
        match, LEFT padding only (per-row nondecreasing, last column real),
        at least one real token per row."""
        import numpy as _np
        m = _np.asarray(prompt_mask)
        if m.shape != tuple(input_ids.shape):
            raise ValueError(f"prompt_mask shape {m.shape} != input_ids "
                             f"shape {tuple(input_ids.shape)}")
        if not _np.isin(m, (0, 1)).all():
            raise ValueError("prompt_mask must be 0/1")
        if (m.sum(axis=1) == 0).any():
            raise ValueError("prompt_mask has an all-padding row")
        if (_np.diff(m.astype(_np.int8), axis=1) < 0).any() or \
                not m[:, -1].all():
            raise ValueError(
                "prompt_mask must be LEFT-padded (zeros then ones; the last "
                "position must be a real token) — right-padded masks would "
                "silently generate from a pad position")

    def _embed_one(self, params, tok, t, pad_lens=None):
        """Embed one token per row at cache slot ``t`` (scalar or per-row
        (B,)): (B,) -> (B, 1, H).  With left-padded prompts the LOGICAL
        position is t - pad_lens[b]."""
        dt = jnp.dtype(self.config.compute_dtype)
        with jax.named_scope("embed"):
            wte = jnp.take(params["wte"], tok[:, None], axis=0)
            t_arr = jnp.asarray(t)
            if pad_lens is not None:
                wpe = params["wpe"][t_arr - pad_lens][:, None, :]
            elif t_arr.ndim == 0:
                wpe = params["wpe"][t_arr][None, None, :]
            else:
                wpe = params["wpe"][t_arr][:, None, :]
            return (wte + wpe).astype(dt)

    def cache_spec(self) -> CacheSpec:
        """One K and one V entry per head and layer (int8: each a
        ``(values, scales)`` pair) — what GPT and ERNIE-MoE cache."""
        c = self.config
        nh = c.num_attention_heads
        hd = c.hidden_size // nh
        if getattr(c, "kv_cache_dtype", None) == "int8":
            one = (CacheLeaf(c.num_layers, (nh, hd), "int8"),
                   CacheLeaf(c.num_layers, (nh,), "float32"))
        else:
            one = CacheLeaf(c.num_layers, (nh, hd),
                            str(jnp.dtype(c.compute_dtype)))
        return CacheSpec(pools=(one, one))

    def init_cache(self, batch_size: int, max_len: int):
        return build_pools(self.cache_spec(), (batch_size, max_len))

    def generate(self, params, input_ids, max_new_tokens: int,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, greedy: bool = True, key=None,
                 prompt_mask=None, repetition_penalty: float = 1.0,
                 min_new_tokens: int = 0, eos_token_id: Optional[int] = None):
        """Autoregressive generation with a static KV cache.

        input_ids (B, P) int32; returns (B, max_new_tokens) generated ids.
        greedy=True → argmax decoding; else temperature (+ optional top-k
        and/or nucleus top-p) sampling with ``key``.  The whole decode loop
        is ONE compiled program per (P, max_new_tokens, temperature, top_k,
        top_p, greedy) signature, memoized on the model — vary only the
        prompt content (and bucket P via paddle.jit.bucketize) for serving
        cache hits.

        ``prompt_mask`` (B, P), 1 = real token, 0 = padding: prompts must be
        LEFT-padded (real tokens at the end, so the last position is always
        real).  Pad positions are excluded from attention and position ids
        shift by the per-row pad length — pad lengths are traced data, so
        ragged batches share one compiled program per bucket.

        ``repetition_penalty`` > 1 pushes already-seen tokens (prompt +
        generated) down (reference generation_utils semantics);
        ``min_new_tokens`` masks ``eos_token_id`` for the first n emissions.
        """
        c = self.config
        B, P = input_ids.shape
        if max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        max_len = P + max_new_tokens
        if max_len > c.max_position_embeddings:
            raise ValueError(f"P + max_new_tokens = {max_len} exceeds "
                             f"max_position_embeddings ({c.max_position_embeddings})")
        validate_sampler_args(c.vocab_size, top_k, top_p, greedy, key)
        if repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        if min_new_tokens > 0 and eos_token_id is None:
            raise ValueError("min_new_tokens needs eos_token_id (it works "
                             "by suppressing EOS)")
        if eos_token_id is not None and not 0 <= eos_token_id < c.vocab_size:
            raise ValueError(f"eos_token_id {eos_token_id} outside vocab "
                             f"(size {c.vocab_size}) — suppression would be "
                             f"a silent no-op")
        key = jax.random.key(0) if key is None else key
        run = self._gen_program(P, max_new_tokens, float(temperature),
                                None if top_k is None else int(top_k),
                                None if top_p is None else float(top_p),
                                greedy, masked=prompt_mask is not None,
                                repetition_penalty=float(repetition_penalty),
                                min_new_tokens=int(min_new_tokens),
                                # eos only shapes the program when it
                                # suppresses; don't fragment the jit cache
                                # (and recompile) per tokenizer eos id
                                eos_token_id=(eos_token_id
                                              if min_new_tokens > 0
                                              else None))
        if prompt_mask is None:
            return run(params, jnp.asarray(input_ids), key)
        self._validate_prompt_mask(prompt_mask, input_ids)
        pad_lens = (P - jnp.sum(jnp.asarray(prompt_mask, jnp.int32), axis=1)) \
            .astype(jnp.int32)
        return run(params, jnp.asarray(input_ids), key, pad_lens)

    def _gen_program(self, P, max_new_tokens, temperature, top_k, top_p,
                     greedy, masked=False, repetition_penalty=1.0,
                     min_new_tokens=0, eos_token_id=None):
        """Build (and memoize) the jitted prefill+decode program for one
        (P, max_new_tokens, temperature, top_k, top_p, greedy, processors)
        signature — repeated generate() calls with the same signature hit
        the jit cache instead of recompiling the whole model."""
        cache_key = (P, max_new_tokens, temperature, top_k, top_p, greedy,
                     masked, repetition_penalty, min_new_tokens, eos_token_id)
        progs = self.__dict__.setdefault("_gen_programs", {})
        if cache_key in progs:
            return progs[cache_key]
        max_len = P + max_new_tokens
        sample = make_token_sampler(temperature, top_k, top_p, greedy)
        V = self.config.vocab_size
        track = repetition_penalty != 1.0  # presence mask only when needed

        def process(logits32, presence, n_emitted):
            """(B, 1, V) logits through the pre-filter processors."""
            l2 = logits32[:, -1, :]
            if track:
                l2 = apply_repetition_penalty(l2, presence,
                                              repetition_penalty)
            if min_new_tokens > 0:
                l2 = suppress_eos(l2, eos_token_id,
                                  n_emitted < min_new_tokens)
            return l2[:, None, :]

        @jax.jit
        def run(params, input_ids, key, pad_lens=None):
            B = input_ids.shape[0]
            presence = seed_presence(input_ids, V, pad_lens) if track \
                else None
            h, caches = self.prefill(params, input_ids, max_len,
                                     pad_lens=pad_lens)
            key, k0 = jax.random.split(key)
            tok0 = sample(process(self.decode_logits(params, h[:, -1:]),
                                  presence, 0), k0)
            if track:
                presence = presence.at[jnp.arange(B), tok0].set(True)

            def body(carry, i):
                tok, caches, key, presence = carry
                t = P + i  # this token's slot in the cache
                h = self._embed_one(params, tok, t, pad_lens=pad_lens)
                h, caches = self.decode_step(params, h, caches, t,
                                             pad_lens=pad_lens)
                key, sub = jax.random.split(key)
                ntok = sample(process(self.decode_logits(params, h),
                                      presence, i + 1), sub)
                if track:
                    presence = presence.at[jnp.arange(B), ntok].set(True)
                return (ntok, caches, key, presence), ntok

            (last, _, _, _), toks = jax.lax.scan(
                body, (tok0, caches, key, presence),
                jnp.arange(max_new_tokens - 1))
            return jnp.concatenate([tok0[:, None], toks.T], axis=1)

        progs[cache_key] = run
        return run

    def _embed_chunk(self, params, toks, t0, pad_lens=None):
        """Embed a token chunk at cache slots [t0, t0+k).

        toks (k,) with scalar t0 → (1, k, H); toks (B, k) with t0 (B,) →
        (B, k, H) (per-row slots — batched speculative decoding).  With
        left-padded prompts (``pad_lens``) logical positions shift by the
        per-row pad length, matching _embed_one/_prefill_embed."""
        dt = jnp.dtype(self.config.compute_dtype)
        if toks.ndim == 1:
            k = toks.shape[0]
            pos = t0 + jnp.arange(k)
            if pad_lens is not None:
                pos = jnp.maximum(pos - pad_lens[0], 0)
            return (jnp.take(params["wte"], toks, axis=0)[None]
                    + params["wpe"][pos][None]).astype(dt)
        B, k = toks.shape
        pos = jnp.asarray(t0)[:, None] + jnp.arange(k)[None, :]   # (B, k)
        if pad_lens is not None:
            pos = jnp.maximum(pos - pad_lens[:, None], 0)
        return (jnp.take(params["wte"], toks, axis=0)
                + jnp.take(params["wpe"], pos, axis=0)).astype(dt)

    def _embed_ragged(self, params, toks, row_seq, row_pos, pad_lens):
        """Embed a flattened ragged pack: toks (T,) one token per row,
        row_seq (T,) owning sequence, row_pos (T,) kv position (-1 for
        padding rows), pad_lens (S,) per-sequence left-pad lengths.
        Logical positions shift by the owning sequence's pad (the
        _embed_one/_embed_chunk convention); returns (1, T, H)."""
        dt = jnp.dtype(self.config.compute_dtype)
        with jax.named_scope("embed"):
            seq = jnp.clip(row_seq, 0, pad_lens.shape[0] - 1)
            pos = jnp.clip(row_pos - pad_lens[seq], 0,
                           params["wpe"].shape[0] - 1)
            h = jnp.take(params["wte"], toks, axis=0) + params["wpe"][pos]
            return h[None].astype(dt)

    # the ragged engine builds this tick a second time at a few rows —
    # its slots, rounded up to 8 — and dispatches a round of decode rows
    # only to that program: the host knows the pack before it dispatches.
    # A class whose tick must stay one program at one width says False
    # (none in the tree since PR 46; the engine's tests build one)
    ragged_narrow_rounds = True

    def decode_ragged(self, params, h, pools, table, row_seq, row_pos,
                      pad_lens):
        """All blocks for one mixed ragged step (the serving engine's
        fused prefill+decode tick), for the "kv" cache layout: h (1, T, H)
        from _embed_ragged, ``pools`` = (pool_ck, pool_cv) stacked over
        layers (int8 ``(values, scales)`` pairs included), table (S, C)
        shared across layers, row metadata per
        ops/ragged_paged_attention.ragged_rows.  Returns (h_out, pools).

        The scan carries both pools whole and hands the host class's
        ``_block_decode_ragged`` the layer's index: the block writes and
        attends in that layer's blocks in place.  (As ``xs``/``ys`` of the
        scan, every layer's pool is sliced out of one stack and written
        into a second one each tick, and the program holds the pool twice.)

        Speculative VERIFY chunks are just another ragged row group: a
        slot's [prev, d_0..d_{K-1}] rows at kv positions [t, t+K] ride
        the same write-then-attend order (each draft row attends its
        predecessors' freshly written k/v), so the ragged spec engine
        needs no separate verify program — the pack IS the verify."""
        stacked = {k: params[k] for k in self.stacked_param_names()}

        def body(carry, xs):
            sl, i = xs
            return self._block_decode_ragged(
                sl, *carry, table, row_seq, row_pos, pad_lens, layer=i), None

        with jax.named_scope("layers"):
            (h, *pools), _ = jax.lax.scan(
                body, (h, *pools),
                (stacked, jnp.arange(self.config.num_layers)))
        return h, tuple(pools)

    def generate_speculative(self, params, input_ids, max_new_tokens: int,
                             draft_model, draft_params, draft_k: int = 4,
                             greedy: bool = True, temperature: float = 1.0,
                             top_k: Optional[int] = None,
                             top_p: Optional[float] = None, key=None,
                             return_rounds: bool = False):
        """Speculative decoding (≙ the draft-and-verify serving
        optimization; LOSSLESS — greedy mode is bit-identical to this
        model's greedy ``generate``, and sampling mode draws from EXACTLY
        the target's filtered distribution via Leviathan/Chen
        acceptance-rejection, `speculative_accept`).

        Per round: the draft proposes ``draft_k`` tokens one at a time
        (argmax in greedy mode, sampled from its filtered distribution in
        sampling mode); the target verifies all of them (plus one bonus
        token) in ONE chunked cache step (cached_attention's k-query form).
        The accepted prefix + a correction/resample are kept, so each round
        emits 1..draft_k+1 tokens at the cost of one target chunk — the
        speedup is the draft's acceptance rate.  The draft cache is then
        re-ingested from the same verify chunk (its sequential loop never
        fed the last proposal, which would leave a permanent zero-kv hole
        after a fully-accepted round); stale slots from rejected tokens are
        always rewritten as the next round's input before anything reads
        them.

        Batched: rows accept independently (per-row cache slots via the
        vectorized write/attention offsets); finished rows keep writing
        into the buffer's slack region until the slowest row completes.
        The draft must share the vocabulary.  In sampling mode both models
        apply the SAME temperature/top-k/top-p filter; the draft proposes
        from its filtered distribution and rejections resample from the
        residual norm(max(p - q, 0)).
        """
        c = self.config
        B, P = input_ids.shape
        if draft_model.config.vocab_size != c.vocab_size:
            raise ValueError(
                f"draft vocab ({draft_model.config.vocab_size}) != target "
                f"vocab ({c.vocab_size}) — speculative acceptance compares "
                f"token ids")
        if max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        K = int(draft_k)
        if K < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        need = P + max_new_tokens + K
        for m, who in ((c, "target"), (draft_model.config, "draft")):
            if need > m.max_position_embeddings:
                raise ValueError(
                    f"P + max_new_tokens + draft_k = {need} exceeds the "
                    f"{who}'s max_position_embeddings "
                    f"({m.max_position_embeddings})")
        validate_sampler_args(c.vocab_size, top_k, top_p, greedy, key)
        key = jax.random.key(0) if key is None else key
        run = self._spec_program(
            draft_model, P, max_new_tokens, K, greedy, float(temperature),
            None if top_k is None else int(top_k),
            None if top_p is None else float(top_p))
        toks, rounds = run(params, draft_params, jnp.asarray(input_ids), key)
        return (toks, rounds) if return_rounds else toks

    def _spec_program(self, draft_model, P, max_new_tokens, K, greedy,
                      temperature, top_k, top_p):
        # keyed by the draft's config signature with a weakref identity
        # check: one entry per signature (bounded memory — a fresh draft
        # instance replaces, never accumulates), and a recycled id() can
        # never alias a dead draft
        import weakref
        dcfg = draft_model.config
        cache_key = ("spec", type(draft_model).__name__, dcfg.vocab_size,
                     dcfg.num_layers, dcfg.hidden_size, P, max_new_tokens, K,
                     greedy, temperature, top_k, top_p)
        progs = self.__dict__.setdefault("_gen_programs", {})
        entry = progs.get(cache_key)
        if entry is not None:
            ref, cached_run = entry
            if ref() is draft_model:
                return cached_run
        N = max_new_tokens
        buf_len = P + N + K + 1  # slack: a round may write past P+N-1
        max_len = buf_len

        def filt(logits):
            return filter_logits(logits.astype(jnp.float32), temperature,
                                 top_k, top_p)

        sample0 = make_token_sampler(temperature, top_k, top_p, greedy)

        @jax.jit
        def run(params, dparams, ids, key):
            B = ids.shape[0]
            rows = jnp.arange(B)
            h, tc = self.prefill(params, ids, max_len)
            _, dc = draft_model.prefill(dparams, ids, max_len)
            key, k0 = jax.random.split(key)
            tok0 = sample0(self.decode_logits(params, h[:, -1:]), k0)  # (B,)
            buf = jnp.zeros((B, buf_len), jnp.int32) \
                .at[:, :P].set(ids.astype(jnp.int32))
            buf = buf.at[:, P].set(tok0)

            def cond(st):
                return jnp.any(st[1] < P + N)

            # B == 1 keeps the scalar slot index: dynamic_update_slice /
            # dynamic_slice instead of scatter/gather on the latency path
            def slot(t_vec):
                return t_vec if B > 1 else t_vec[0]

            def body(st):
                buf, n, tc, dc, key, rounds = st                # n (B,)
                prev = buf[rows, n - 1]                         # (B,)
                key, kd, ka = jax.random.split(key, 3)

                def dstep(carry, i):
                    tok, dc = carry
                    hh = draft_model._embed_one(dparams, tok, slot(n - 1 + i))
                    hh, dc = draft_model.decode_step(dparams, hh, dc,
                                                     slot(n - 1 + i))
                    ql = filt(draft_model.decode_logits(dparams, hh)[:, -1])
                    if greedy:
                        ntok = jnp.argmax(ql, -1).astype(jnp.int32)
                        qout = jnp.zeros((ql.shape[0], 0))  # probs unused
                    else:
                        ntok = jax.random.categorical(
                            jax.random.fold_in(kd, i), ql, -1) \
                            .astype(jnp.int32)
                        qout = jax.nn.softmax(ql, -1)
                    return (ntok, dc), (ntok, qout)

                (_, dc), (d, qp) = jax.lax.scan(dstep, (prev, dc),
                                                jnp.arange(K))
                d = d.T                                         # (B, K)

                # verify: ONE target chunk over [prev, d_0..d_{K-1}] gives
                # the target's filtered logits for positions n..n+K
                inp = jnp.concatenate([prev[:, None], d], axis=1)  # (B, K+1)
                hin = self._embed_chunk(params, inp[0] if B == 1 else inp,
                                        slot(n - 1))
                hv, tc = self.decode_step(params, hin, tc, slot(n - 1))
                tl = filt(self.decode_logits(params, hv))       # (B, K+1, V)
                # re-ingest the chunk into the DRAFT cache: the sequential
                # draft loop never fed d_{K-1}, so slot n+K-1 would stay a
                # zero-kv hole after a fully-accepted round (permanently
                # degrading acceptance; outputs stay correct so only a
                # round-count test can see it)
                dh = draft_model._embed_chunk(dparams,
                                              inp[0] if B == 1 else inp,
                                              slot(n - 1))
                _, dc = draft_model.decode_step(dparams, dh, dc, slot(n - 1))
                if greedy:
                    # ONE copy of the greedy acceptance rule (greedy_verify)
                    # shared with the ragged serving engine's fused
                    # draft+verify step; only the first lead+1 entries of
                    # the block are ever read (rows advance by lead + 1)
                    tpred = jnp.argmax(tl, -1).astype(jnp.int32)
                    lead, cand = greedy_verify(d, tpred)
                else:
                    q_probs = jnp.swapaxes(qp, 0, 1)            # (B, K, V)
                    p_probs = jax.nn.softmax(tl, -1)            # (B, K+1, V)
                    lead, repl = speculative_accept(q_probs, p_probs, d, ka)
                    d_ext = jnp.concatenate(
                        [d, jnp.zeros((B, 1), jnp.int32)], axis=1)
                    cand = jnp.where(
                        jnp.arange(K + 1)[None] < lead[:, None],
                        d_ext, repl[:, None])
                slots = n[:, None] + jnp.arange(K + 1)[None]
                buf = buf.at[rows[:, None], slots].set(cand)
                n = jnp.minimum(n + lead + 1, P + N)
                return (buf, n, tc, dc, key, rounds + 1)

            n0 = jnp.full((B,), P + 1)
            buf, n, tc, dc, key, rounds = jax.lax.while_loop(
                cond, body, (buf, n0, tc, dc, key, jnp.zeros((), jnp.int32)))
            return buf[:, P:P + N], rounds

        progs[cache_key] = (weakref.ref(draft_model), run)
        return run

    def generate_beam(self, params, input_ids, max_new_tokens: int,
                      num_beams: int = 4, length_penalty: float = 1.0,
                      eos_token_id: Optional[int] = None):
        """Beam-search decoding on the KV cache (≙ generation_utils
        BeamSearchScorer semantics, fixed length budget).

        Returns (sequences (B, max_new_tokens), scores (B,)) for the best
        beam per batch row; ``scores`` are summed log-probs divided by
        length**length_penalty.  ``eos_token_id``: beams that emit EOS are
        frozen (EOS repeats, log-prob stops accumulating) so shorter
        hypotheses compete under the penalty.

        TPU shape: beams fold into the batch dim (B*K), the cache reorder is
        one take_along_axis per step, and the whole search is a single
        lax.scan — no dynamic shapes, no host sync inside the loop.
        """
        c = self.config
        B, P = input_ids.shape
        K = int(num_beams)
        if not 1 <= K <= c.vocab_size:
            raise ValueError(f"num_beams must be in [1, vocab_size="
                             f"{c.vocab_size}], got {num_beams}")
        if eos_token_id is not None and not 0 <= eos_token_id < c.vocab_size:
            raise ValueError(f"eos_token_id {eos_token_id} outside the vocab "
                             f"[0, {c.vocab_size}) — EOS freezing would "
                             f"silently never trigger")
        if max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32), jnp.zeros((B,), jnp.float32)
        max_len = P + max_new_tokens
        if max_len > c.max_position_embeddings:
            raise ValueError(f"P + max_new_tokens = {max_len} exceeds "
                             f"max_position_embeddings ({c.max_position_embeddings})")
        run = self._beam_program(P, max_new_tokens, K, float(length_penalty),
                                 eos_token_id)
        return run(params, jnp.asarray(input_ids))

    def _beam_program(self, P, max_new_tokens, K, length_penalty,
                      eos_token_id):
        cache_key = ("beam", P, max_new_tokens, K, length_penalty,
                     eos_token_id)
        progs = self.__dict__.setdefault("_gen_programs", {})
        if cache_key in progs:
            return progs[cache_key]
        c = self.config
        max_len = P + max_new_tokens
        V = c.vocab_size
        NEG = jnp.float32(-1e30)

        def logprobs_last(params, h):
            return jax.nn.log_softmax(
                self.decode_logits(params, h)[:, -1, :].astype(jnp.float32),
                -1)

        @jax.jit
        def run(params, input_ids):
            B = input_ids.shape[0]
            h, caches = self.prefill(params, input_ids, max_len)
            lp0 = logprobs_last(params, h)                      # (B, V)
            # beams start identical: only beam 0 is live at step 0
            top_lp, top_tok = jax.lax.top_k(lp0, K)             # (B, K)
            cum = top_lp
            if eos_token_id is not None:
                finished0 = top_tok == eos_token_id
            else:
                finished0 = jnp.zeros((B, K), bool)
            # per-beam hypothesis length (tokens incl. EOS): finished beams
            # keep the length at which they finished so the length penalty
            # ranks short hypotheses correctly (BeamSearchScorer semantics)
            lengths0 = jnp.where(finished0, 1.0,
                                 float(max_new_tokens)).astype(jnp.float32)
            # tile caches per beam: (nl, B, ...) -> (nl, B*K, ...)
            caches = jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, K, axis=1), caches)

            def body(carry, i):
                tok, caches, cum, finished, lengths = carry
                t = P + i
                hh = self._embed_one(params, tok, t)
                hh, caches = self.decode_step(params, hh, caches, t)
                lp = logprobs_last(params, hh).reshape(B, K, V)
                if eos_token_id is not None:
                    # frozen beams: only EOS continues, at zero cost
                    eos_only = jnp.full((V,), NEG).at[eos_token_id].set(0.0)
                    lp = jnp.where(finished[..., None], eos_only[None, None],
                                   lp)
                total = cum[..., None] + lp                      # (B, K, V)
                flat = total.reshape(B, K * V)
                cum, idx = jax.lax.top_k(flat, K)                # (B, K)
                parent = idx // V
                ntok = (idx % V).astype(jnp.int32)
                if eos_token_id is not None:
                    was = jnp.take_along_axis(finished, parent, axis=1)
                    lengths = jnp.take_along_axis(lengths, parent, axis=1)
                    newly = ~was & (ntok == eos_token_id)
                    # token emitted at body step i is hypothesis token i+2
                    lengths = jnp.where(newly, (i + 2).astype(jnp.float32),
                                        lengths)
                    finished = was | newly
                # reorder caches to the surviving beams
                def reorder(a):
                    nl = a.shape[0]
                    ab = a.reshape((nl, B, K) + a.shape[2:])
                    pidx = parent.reshape((1, B, K) + (1,) * (ab.ndim - 3))
                    return jnp.take_along_axis(ab, pidx, axis=2).reshape(a.shape)
                caches = jax.tree_util.tree_map(reorder, caches)
                tok = ntok.reshape(B * K)
                return (tok, caches, cum, finished, lengths), (ntok, parent)

            (_, _, cum, _, lengths), (toks, parents) = jax.lax.scan(
                body, (top_tok.reshape(B * K), caches, cum, finished0,
                       lengths0),
                jnp.arange(max_new_tokens - 1))

            # backtrace: walk parents from the best final beam to step 0
            scores = cum / jnp.power(lengths, length_penalty)
            best = jnp.argmax(scores, axis=1)                    # (B,)

            def back(k, step):
                st, sp = step                                    # (B,K) each
                tok_t = jnp.take_along_axis(st, k[:, None], 1)[:, 0]
                k = jnp.take_along_axis(sp, k[:, None], 1)[:, 0]
                return k, tok_t

            k_last, toks_rev = jax.lax.scan(
                back, best, (toks[::-1], parents[::-1]))
            first = jnp.take_along_axis(top_tok, k_last[:, None], 1)[:, 0]
            seq = jnp.concatenate([first[:, None], toks_rev[::-1].T], axis=1)
            best_score = jnp.take_along_axis(scores, best[:, None], 1)[:, 0]
            return seq, best_score

        progs[cache_key] = run
        return run




def save_generate_program(model, params, path: str, prompt_len: int,
                          max_new_tokens: int, batch_size: int = 1,
                          temperature: float = 1.0, top_k=None, top_p=None,
                          greedy: bool = True, masked: bool = False,
                          platforms=("cpu", "tpu")):
    """Export one generation program as a self-contained serving artifact.

    ≙ jit.save's ``__model__`` + params layout (save_inference_model), but
    for the full prefill+decode loop: the StableHLO program (jax.export
    bytes) plus pickled weights.  The exported function takes
    (input_ids (B, P) int32, seed uint32[, pad_lens int32 when
    ``masked=True`` — left-padded ragged prompts]) — the PRNG key is built
    inside the program so no key types cross the serialization boundary.
    Lowered for every platform in ``platforms`` so a CPU-built artifact
    serves on TPU.

    Files: path + ".genmodel" (program), path + ".genparams" (weights),
    path + ".genmeta" (shapes/sampler signature).
    """
    import pickle

    import numpy as _np
    from jax import export as jax_export

    # same eager contract as generate(): fail here, not at serve time
    if max_new_tokens <= 0:
        raise ValueError("max_new_tokens must be positive for an exported "
                         "program (an empty program is not a useful artifact)")
    max_len = prompt_len + max_new_tokens
    if max_len > model.config.max_position_embeddings:
        raise ValueError(
            f"prompt_len + max_new_tokens = {max_len} exceeds "
            f"max_position_embeddings ({model.config.max_position_embeddings})")
    validate_sampler_args(model.config.vocab_size, top_k, top_p, greedy,
                          key=object())  # key is generated in-program

    run = model._gen_program(prompt_len, max_new_tokens, float(temperature),
                             None if top_k is None else int(top_k),
                             None if top_p is None else float(top_p), greedy,
                             masked=masked)

    if masked:
        def entry(params, input_ids, seed, pad_lens):
            return run(params, input_ids, jax.random.key(seed), pad_lens)
        extra = [jax.ShapeDtypeStruct((batch_size,), jnp.int32)]
    else:
        def entry(params, input_ids, seed):
            return run(params, input_ids, jax.random.key(seed))
        extra = []

    p_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    exported = jax_export.export(jax.jit(entry), platforms=list(platforms))(
        p_shapes,
        jax.ShapeDtypeStruct((batch_size, prompt_len), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.uint32), *extra)
    with open(path + ".genmodel", "wb") as f:
        f.write(exported.serialize())
    with open(path + ".genparams", "wb") as f:
        pickle.dump(jax.tree_util.tree_map(_np.asarray, params), f)
    with open(path + ".genmeta", "wb") as f:
        pickle.dump({"prompt_len": prompt_len, "batch_size": batch_size,
                     "max_new_tokens": max_new_tokens,
                     "temperature": temperature, "top_k": top_k,
                     "top_p": top_p, "greedy": greedy, "masked": masked,
                     "platforms": tuple(platforms)}, f)


def load_generate_program(path: str):
    """Load a save_generate_program artifact.  Returns (fn, meta) where
    ``fn(input_ids, seed=0[, prompt_mask=...]) -> (B, max_new_tokens)``
    has the weights baked in; ``prompt_mask`` is accepted (and required)
    when the artifact was exported with ``masked=True``."""
    import pickle

    from jax import export as jax_export

    with open(path + ".genmodel", "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(path + ".genparams", "rb") as f:
        params = pickle.load(f)
    with open(path + ".genmeta", "rb") as f:
        meta = pickle.load(f)

    def fn(input_ids, seed=0, prompt_mask=None):
        ids = jnp.asarray(input_ids, jnp.int32)
        args = [params, ids, jnp.asarray(seed, jnp.uint32)]
        if meta["masked"]:
            if prompt_mask is None:
                raise ValueError("this artifact was exported masked=True; "
                                 "pass prompt_mask")
            CausalDecoderMixin._validate_prompt_mask(prompt_mask, ids)
            args.append((ids.shape[1] - jnp.sum(
                jnp.asarray(prompt_mask, jnp.int32), axis=1)).astype(jnp.int32))
        elif prompt_mask is not None:
            raise ValueError("artifact exported without masked=True cannot "
                             "serve ragged prompts")
        return exported.call(*args)

    return fn, meta
