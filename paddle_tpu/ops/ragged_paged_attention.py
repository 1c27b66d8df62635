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

The walk is the kernel's own (PR 33; the sibling for a latent cache,
ops/ragged_latent_attention.py, got it first).  The grid is sized by the
rows that exist: one step per ``ROWS_PER_STEP`` pack rows, none per table
column.  The pools stay in HBM (``memory_space=ANY``) and are addressed
``pool[layer, table[seq, col]]``; a step of the walk copies
``BLOCKS_PER_STEP`` table-selected K blocks and V blocks into one half of
a double buffer while the other half is used, for exactly as many steps as
the rows' kv positions need.

A grid step is cut into RUNS: consecutive pack rows that are one sequence
at consecutive kv positions (a prefill chunk, wherever in the step it
starts; a verify chunk; a decode row is a run of one).  A run of
``MIN_RUN`` rows or more shares every key block and goes through the MXU
head by head as ONE ``(rows, hd) x (hd, keys)`` / ``(rows, keys) x (keys,
hd)`` operand — the step's other rows ride along masked and are not
written — bfloat16 operands, float32 scores, softmax and accumulator, the
probabilities to the pool's dtype for the second product.  A shorter run
is memory-bound and goes row by row on the VPU in float32, over the same
staged walk.  A step of padding rows does nothing.

How a head's ``(keys, hd)`` comes out of a staged ``(keys, nh, hd)`` block
is chosen from the shapes (``_form``): a sublane-strided load of 32-bit
rows where the tail is whole tiles (16 heads of 128: the cells), a lane
slice where it is not (gpt2-small's 12 heads of 64).  int8 ``(values,
scales)`` pools (models/_decode.py quantize_kv layout) are dequantised
IN-KERNEL and no fp copy of the pool ever materializes: the values are
staged as they are, and the scale planes — a thirty-second of the bytes —
are gathered by the table outside with keys on lanes, so the multiply
lands on the scores and the probabilities.

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

from .ragged_latent_attention import _largest_divisor

_NEG_INF = -1e30
ROWS_PER_STEP = 128         # pack rows per grid step (the MXU's M)
BLOCKS_PER_STEP = 16        # table columns (blocks of keys) per inner step
MIN_RUN = 8                 # a shorter run of rows goes row by row


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


def grouped_rows(pack_rows, token_budget, rows_per_step=ROWS_PER_STEP,
                 min_run=MIN_RUN):
    """How many of a pack's rows the kernel takes through the MXU as one
    operand with others, from the engine's own record of the pack
    (``_note_pack``'s ``[rid, rows, kv_end]`` per sequence, in pack order:
    a sequence's rows are consecutive pack rows at consecutive kv
    positions).  A sequence's rows are cut at the grid steps' edges; a
    piece of at least ``min_run`` rows is one operand, a shorter one goes
    row by row.  Shares ``ROWS_PER_STEP`` / ``MIN_RUN`` with the kernel."""
    rows = _largest_divisor(token_budget, rows_per_step)
    at = grouped = 0
    for _, n, _ in pack_rows:
        end = at + n
        while at < end:
            piece = min(end, (at // rows + 1) * rows) - at
            if piece >= min_run:
                grouped += piece
            at += piece
    return grouped


def _form(nh, hd, bs, q_dtype, pool_dtype):
    """How a head's ``(keys, hd)`` comes out of a staged block, from the
    shapes alone: ``(strided, vpu_rows)``.

    ``strided``: the block is staged as its ``(keys * nh, hd)`` view and a
    head is a sublane-strided load of 32-bit rows (two bfloat16 or four
    int8 heads share one: the upstream TPU kernel's load, PAPERS.md).
    Mosaic gives that for ``hd`` whole lanes and ``nh`` whole sublane
    tiles, and the view costs nothing in HBM.  Otherwise (gpt2-small's 12
    heads of 64) the block is staged as ``(keys, nh * hd)`` and a head is
    a lane slice; the device keeps such a pool in another order and the
    compiler re-orders it for any kernel (PERF.md section 7).

    ``vpu_rows``: a run shorter than ``MIN_RUN`` (a decode row) goes row
    by row on the VPU in float32 instead of riding the MXU alone under a
    mask: needs the float block as ``(bs, nh, hd)``, heads on whole
    sublane tiles.  int8 pools have their scales with keys on lanes,
    which is the MXU path's layout: every row there is a run."""
    q_pack = 4 // jnp.dtype(q_dtype).itemsize
    p_pack = 4 // jnp.dtype(pool_dtype).itemsize
    strided = (hd % 128 == 0 and nh % (8 * q_pack) == 0
               and nh % p_pack == 0 and (bs * nh) % (8 * p_pack) == 0)
    vpu_rows = (strided and jnp.issubdtype(pool_dtype, jnp.floating)
                and nh % (8 * p_pack) == 0)
    return strided, vpu_rows


def _ragged_kernel(table_ref, seq_ref, pos_ref, pad_ref, layer_ref, q_ref,
                   *rest, bs, kb, rows, nh, hd, scale, quantized, strided,
                   vpu_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quantized:       # scales: (S, steps, nh8, keys), gathered outside
        (pool_k, pool_v, ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         sem, *scratch) = rest
    else:
        pool_k, pool_v, o_ref, kbuf, vbuf, sem, *scratch = rest
    keys = kb * bs
    per_block = kbuf.shape[1] // kb     # staged rows a block: bs * nh or bs
    r0 = pl.program_id(0) * rows
    layer = layer_ref[0]
    # the operands of the products: the pool's dtype, or q's over int8
    dtype = q_ref.dtype if quantized else kbuf.dtype
    # bfloat16 operands multiply exactly in one MXU pass; a global
    # "highest" default would ask Mosaic for a float32 product of
    # bfloat16 vectors, which it refuses
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))

    def operand(x):      # (an int8 reaches bfloat16 through float32)
        return (x.astype(jnp.float32) if quantized else x).astype(dtype)

    def walk(seq, last, pad, consume, carry):
        """Stage sequence ``seq``'s blocks, ``kb`` a step, into one half
        of the double buffer while ``consume(g, slot, carry)`` uses the
        other, over the key steps that hold positions [pad, last].
        (``lax.div`` / ``rem``, and the copies as a rolled loop: a ``//``
        on a traced value and sixteen unrolled descriptors at each of
        three sites were most of the time the tick took to lower.)"""
        last_col = lax.div(last, bs)
        steps = lax.div(last, keys) + 1
        first = jnp.minimum(lax.div(pad, keys), steps - 1)

        def copies(g, slot, start):
            def go(c):
                c.start() if start else c.wait()

            def one(k, _):
                # clamp to the deepest in-range column: a step's tail
                # re-reads that block and the mask drops it (a wait needs
                # the copy's shape only)
                blk = table_ref[seq, jnp.minimum(g * kb + k, last_col)] \
                    if start else 0
                at = pl.ds(pl.multiple_of(k * per_block, per_block),
                           per_block)
                for pool, buf in ((pool_k, kbuf), (pool_v, vbuf)):
                    go(pltpu.make_async_copy(
                        pool.at[layer, blk], buf.at[slot, at],
                        sem.at[slot]))
                return 0
            lax.fori_loop(0, kb, one, 0)
            if quantized:
                for hbm, buf in ((ks_hbm, ksbuf), (vs_hbm, vsbuf)):
                    go(pltpu.make_async_copy(
                        hbm.at[seq, g if start else 0], buf.at[slot],
                        sem.at[slot]))

        copies(first, lax.rem(first, 2), True)

        def step(g, carry):
            slot = lax.rem(g, 2)

            @pl.when(g + 1 < steps)
            def _prefetch():
                copies(g + 1, 1 - slot, True)

            copies(g, slot, False)
            return consume(g, slot, carry)

        return lax.fori_loop(first, steps, step, carry)

    def attend_row(r):
        """One pack row (a decode row, a verify chunk's, a run too short
        for the MXU): memory-bound, so multiply-and-reduce on the VPU in
        float32, a block of keys at a time."""
        seq, p = seq_ref[r0 + r], pos_ref[r0 + r]
        pad = pad_ref[seq]
        q = q_ref[r].astype(jnp.float32) * scale            # (nh, hd)

        def consume(g, slot, carry):
            def block(k, carry):
                m_prev, l, acc = carry
                at = pl.ds(pl.multiple_of(k * per_block, per_block),
                           per_block)
                kk = kbuf[slot, at].astype(jnp.float32).reshape(bs, nh, hd)
                vv = vbuf[slot, at].astype(jnp.float32).reshape(bs, nh, hd)
                # rank 3 with heads on sublanes — see
                # ops/paged_attention.py for why not a head-batched dot
                sc = jnp.sum(q[None] * kk, axis=-1, keepdims=True)
                kpos = (g * kb + k) * bs + lax.broadcasted_iota(
                    jnp.int32, sc.shape, 0)
                valid = (kpos <= p) & (kpos >= pad)
                sc = jnp.where(valid, sc, _NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
                pr = jnp.where(valid, jnp.exp(sc - m_new[None]), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                return (m_new, alpha * l + jnp.sum(pr, axis=0),
                        acc * alpha + jnp.sum(pr * vv, axis=0))
            # the blocks of this step that hold positions up to ``p``
            n_blocks = jnp.minimum(lax.div(p, bs) - g * kb + 1, kb)
            return lax.fori_loop(0, n_blocks, block, carry)

        _, l, acc = walk(seq, p, pad, consume, (
            jnp.full((nh, 1), _NEG_INF, jnp.float32),
            jnp.zeros((nh, 1), jnp.float32),
            jnp.zeros((nh, hd), jnp.float32)))
        o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    acc_ref, m_ref, l_ref = scratch[:3]
    if strided:
        x32, qh_ref = scratch[3:]
        pack = 4 // jnp.dtype(kbuf.dtype).itemsize  # heads a 32-bit row

        def each_head(per, body):
            """``body(j)`` over the heads, ``per`` at a time, as a rolled
            loop: the body is lowered once, not once a head."""
            lax.fori_loop(0, nh // per, lambda j, _: body(j) or 0, 0)

        def heads(buf, slot, j):
            """Heads ``pack * j ...`` of the staged block, each (keys,
            hd): rows ``h, h + nh, ...`` of the (keys * nh, hd) view,
            ``pack`` of them to a 32-bit row, the first in its low bits."""
            if pack == 1:
                return [buf[slot, pl.ds(j, keys, stride=nh), :]]
            both = buf.at[slot].bitcast(jnp.int32)[
                pl.ds(j, keys, stride=nh // pack), :]
            if pack == 2:       # a bfloat16 is a float32's high half
                return [pltpu.bitcast(half, jnp.float32).astype(dtype)
                        for half in (both << 16, both & jnp.int32(-65536))]
            return [((both << (24 - 8 * i)) >> 24).astype(
                jnp.float32).astype(dtype) for i in range(pack)]

    def one_head(h, q_h, k_h, v_h, ks_h, vs_h, valid):
        """Head ``h`` of a run over one key step: q_h (rows, hd), k_h and
        v_h (keys, hd); for int8 pools ks_h / vs_h (1, keys), the
        dequantising multiply taken to the scores and the probabilities."""
        sc = dot(q_h, k_h, (((1,), (1,)), ((), ()))) * scale
        if quantized:
            sc = sc * ks_h
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(pr, axis=1, keepdims=True)
        if quantized:
            pr = pr * vs_h
        acc_ref[h] = acc_ref[h] * alpha + dot(
            pr.astype(dtype), v_h, (((1,), (0,)), ((), ())))
        m_ref[h] = m_new

    def attend_run(s, n):
        """Rows [s, s + n) of this step: one sequence at consecutive kv
        positions, so they share every key block.  They go through the
        MXU head by head as one (rows, hd) operand — the step's other
        rows ride along masked and are not written."""
        seq, first = seq_ref[r0 + s], pos_ref[r0 + s]
        pad = pad_ref[seq]
        i = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        in_run = (i >= s) & (i < s + n)
        # a row outside the run attends nothing
        row_pos = jnp.where(in_run, first + i - s, -1)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if strided:     # q head-major, through a 32-bit strided load
            x32[...] = q_ref[...].reshape(rows * nh, hd).astype(jnp.float32)

            @functools.partial(each_head, 1)
            def _q_head_major(h):
                qh_ref[h] = x32[pl.ds(h, rows, stride=nh), :].astype(dtype)

        def consume(g, slot, carry):
            kpos = g * keys + lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1)
            valid = (kpos <= row_pos) & (kpos >= pad)

            def scales(h):
                if not quantized:
                    return None, None
                return (ksbuf[slot, pl.ds(h, 1), :],
                        vsbuf[slot, pl.ds(h, 1), :])

            if strided:
                @functools.partial(each_head, pack)
                def _heads(j):
                    for i, (k_h, v_h) in enumerate(zip(
                            heads(kbuf, slot, j), heads(vbuf, slot, j))):
                        h = pack * j + i
                        one_head(h, qh_ref[h], k_h, v_h, *scales(h), valid)
            else:
                for h in range(nh):
                    at = slice(h * hd, (h + 1) * hd)
                    one_head(h, q_ref[:, at].astype(dtype),
                             operand(kbuf[slot, :, at]),
                             operand(vbuf[slot, :, at]),
                             *scales(h), valid)
            return carry

        walk(seq, first + n - 1, pad, consume, 0)
        if strided:
            @functools.partial(each_head, 1)
            def _o_row_major(h):
                x32[pl.ds(h, rows, stride=nh), :] = (
                    acc_ref[h] / jnp.maximum(l_ref[h], 1e-30))
            i3 = lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
            o_ref[...] = jnp.where(
                (i3 >= s) & (i3 < s + n),
                x32[...].reshape(o_ref.shape).astype(o_ref.dtype),
                o_ref[...])
        else:
            for h in range(nh):
                at = slice(h * hd, (h + 1) * hd)
                out = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                o_ref[:, at] = jnp.where(in_run, out.astype(o_ref.dtype),
                                         o_ref[:, at])

    o_ref[...] = jnp.zeros_like(o_ref)      # padding rows read zeros

    def segment(s):
        """The run that starts at row ``s`` of the step — the rows after
        it, up to the step's end, that are the same sequence at the next
        kv positions: through the MXU if long enough, else row by row."""
        seq, first = seq_ref[r0 + s], pos_ref[r0 + s]

        def continues(n):   # (``&`` reads both sides: stay inside SMEM)
            at = jnp.minimum(r0 + s + n, seq_ref.shape[0] - 1)
            return ((s + n < rows) & (seq_ref[at] == seq)
                    & (pos_ref[at] == first + n))
        n = lax.while_loop(continues, lambda n: n + 1, 1)
        as_run = (n >= MIN_RUN) if vpu_rows else True

        @pl.when((first >= 0) & as_run)
        def _run():
            attend_run(s, n)

        if vpu_rows:
            @pl.when((first >= 0) & (n < MIN_RUN))
            def _rows():
                lax.fori_loop(s, s + n, lambda r, _: attend_row(r) or 0, 0)
        return s + n

    lax.while_loop(lambda s: s < rows, segment, 0)


def ragged_attention_rows(q, pool_k, pool_v, table, row_seq, row_pos,
                          pad_lens=None, *, layer=None, interpret=False,
                          rows_per_step=ROWS_PER_STEP,
                          blocks_per_step=BLOCKS_PER_STEP):
    """Row-metadata entry point (the engine packs rows directly).

    q (T, nh, hd); pool_k/pool_v (NB+1, bs, nh, hd) — or int8
    ``(values, scales)`` pairs with scales (NB+1, bs, nh) — or, with
    ``layer`` (a traced int32 scalar), the pools of a whole stack
    (L, NB+1, bs, nh[, hd]) of which the kernel reads that layer's blocks
    in place (no slice of the stack is ever made); table (S, C)
    int32 (block 0 = trash); row_seq (T,) int32 (padding rows may carry
    any value); row_pos (T,) int32 kv position per row, -1 for padding
    rows; pad_lens (S,) int32 left-pad masks (positions < pad masked),
    or None.

    Returns (T, nh, hd) in q's dtype; each row's output is attention over
    its sequence's pool positions [pad, row_pos] (zeros for padding rows
    and for a row inside its sequence's left pad).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    quantized = isinstance(pool_k, tuple)
    (vals_k, scales_k), (vals_v, scales_v) = (
        (pool_k, pool_v) if quantized else ((pool_k, None), (pool_v, None)))
    if layer is None:
        vals_k, vals_v, scales_k, scales_v = jax.tree.map(
            lambda p: p[None], (vals_k, vals_v, scales_k, scales_v))
        layer = 0
    L, NB1, bs = vals_k.shape[:3]
    S, C = table.shape
    kb = min(int(blocks_per_step), C)
    keys = kb * bs
    rows = _largest_divisor(T, rows_per_step)
    strided, vpu_rows = _form(nh, hd, bs, q.dtype, vals_k.dtype)
    if pad_lens is None:
        pad_lens = jnp.zeros((S,), jnp.int32)
    # the engine marks padding rows with sequence -1; the kernel reads
    # ``pad_lens[row_seq]`` and ``table[row_seq]`` by it
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
    table = table.astype(jnp.int32)

    # a block as the kernel stages it: whole tiles either way
    tail = (bs * nh, hd) if strided else (bs, nh * hd)
    operands = [p.reshape((L, NB1) + tail) for p in (vals_k, vals_v)]
    scratch = [pltpu.VMEM((2, kb * tail[0], tail[1]), vals_k.dtype)] * 2
    if quantized:
        # the scale planes, a thirty-second of the pool's bytes, gathered
        # by the table out here with keys on lanes (a (bs, nh) plane's 16
        # lanes are no tile Mosaic copies): (S, steps, nh8, keys)
        steps, nh8 = -(-C // kb), -(-nh // 8) * 8

        def dense(scales):
            s = scales[layer, table].reshape(S, C * bs, nh)
            s = jnp.pad(s, ((0, 0), (0, steps * keys - C * bs),
                            (0, nh8 - nh)))
            return s.reshape(S, steps, keys, nh8).swapaxes(2, 3)
        operands += [dense(scales_k), dense(scales_v)]
        scratch += [pltpu.VMEM((2, nh8, keys), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((nh, rows, hd), jnp.float32),
                pltpu.VMEM((nh, rows, 1), jnp.float32),
                pltpu.VMEM((nh, rows, 1), jnp.float32)]
    if strided:
        scratch += [pltpu.VMEM((rows * nh, hd), jnp.float32),  # q in, o out
                    pltpu.VMEM((nh, rows, hd),                 # q, head-major
                               q.dtype if quantized else vals_k.dtype)]
    # q and the output: rows of heads for the strided loads, flat lanes
    # where a head is a lane slice
    q_tail = (nh, hd) if strided else (nh * hd,)
    row_spec = pl.BlockSpec((rows,) + q_tail,
                            lambda i, *_: (i,) + (0,) * len(q_tail))

    kernel = functools.partial(
        _ragged_kernel, bs=bs, kb=kb, rows=rows, nh=nh, hd=hd,
        scale=1.0 / (hd ** 0.5), quantized=quantized, strided=strided,
        vpu_rows=vpu_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,          # table, row_seq, row_pos, pad, layer
        grid=(T // rows,),
        in_specs=[row_spec]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(operands),  # stay put
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    # the region and the kernel's name say what it is, wherever this file
    # moves: a trace finds the kernel by them
    with jax.named_scope("ragged_paged_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T,) + q_tail, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="ragged_paged_attention",
        )(table, row_seq, jnp.asarray(row_pos, jnp.int32),
          jnp.asarray(pad_lens, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q.reshape((T,) + q_tail), *operands)
    return out.reshape(T, nh, hd)


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
