"""EVA attention for a flattened ragged pack: one softmax over a sequence's
exact window rows AND the chunk summaries of everything before the window
(Zheng et al., ICLR 2023, arXiv:2302.04542, in the deterministic form a
byte-level release runs it: docs/CACHE_SPEC.md).

Two kinds of state, two kernels (the other is ops/eva_summarize.py):

- the WINDOW leaf ``(L, slots, W, nh, hd)`` does not page: a sequence's K
  (and V) rows of its current window at ``[slot, position mod W]``;
- the SUMMARY leaf ``(L, NB + 1, bs, nh, hd)`` pages by chunk: one row per
  ``chunk`` positions, addressed by the block table, a block of ``bs``
  summaries naming ``bs * chunk`` positions.

``ragged_eva_attention_rows`` attends.  A row at position ``p`` of a
sequence reads its window rows ``[0, p mod W]`` (causal, exact) and then
its summaries ``[0, (p // W) * (W // chunk))`` — every chunk of every
EARLIER window; the current window's summaries are in the leaf and masked
— under one running softmax.  The walk is ops/ragged_paged_attention.py's
(PR 33): a grid of ``ROWS_PER_STEP`` pack rows, both leaves left in HBM
and staged ``keys`` at a time through a double buffer (a window step is
one contiguous copy, a summary step ``keys / bs`` table-selected blocks),
a run of ``MIN_RUN`` rows or more of one sequence in one window through
the MXU head by head as one operand, a shorter one (a decode row) row by
row on the VPU in float32.  A run never crosses a window: the walk cuts
it there, and the engine never packs one that does (``CacheSpec.
row_boundary``: the window leaf has one window's room).

Gated like every Pallas kernel here (``_decode._pallas_dispatch``), with
``*_ref`` as the XLA fallback and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .ragged_latent_attention import _largest_divisor
from .ragged_paged_attention import _form

_NEG_INF = -1e30
ROWS_PER_STEP = 128         # pack rows per grid step (the MXU's M)
KEYS_PER_STEP = 256         # window rows, or summaries, per inner step
MIN_RUN = 8                 # a shorter run of rows goes row by row


# ------------------------------------------------------------ attention --

def _eva_kernel(table_ref, seq_ref, pos_ref, layer_ref, q_ref, win_k, win_v,
                sum_k, sum_v, o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
                *scratch, W, per_window, bs, kb, rows, nh, hd, scale,
                strided, vpu_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    keys = kb * bs
    per_key = kbuf.shape[1] // keys      # staged rows a key: nh or 1
    per_block = bs * per_key
    r0 = pl.program_id(0) * rows
    layer = layer_ref[0]
    dtype = kbuf.dtype
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))

    def walk(seq, last_w, n_sum, consume, carry):
        """Stage sequence ``seq``'s window rows [0, last_w] and then its
        summaries [0, n_sum), ``keys`` a step, into one half of the double
        buffer while ``consume(g, slot, carry, in_window, base)`` uses the
        other (``base``: the window row, or the summary, of the step's
        first key)."""
        steps_w = lax.div(last_w, keys) + 1
        steps = steps_w + lax.div(n_sum + keys - 1, keys)
        last_col = lax.div(jnp.maximum(n_sum - 1, 0), bs)

        def copies(g, slot, start):
            def go(c):
                c.start() if start else c.wait()

            @pl.when(g < steps_w)
            def _window():
                n = keys * per_key
                at = pl.ds(pl.multiple_of(g * n, n), n) if start \
                    else pl.ds(0, n)
                for pool, buf in ((win_k, kbuf), (win_v, vbuf)):
                    go(pltpu.make_async_copy(
                        pool.at[layer, seq, at], buf.at[slot], sem.at[slot]))

            @pl.when(g >= steps_w)
            def _summaries():
                def one(k, _):
                    # clamp to the deepest column in range: a step's tail
                    # re-reads that block and the mask drops it
                    blk = table_ref[seq, jnp.minimum(
                        (g - steps_w) * kb + k, last_col)] if start else 0
                    at = pl.ds(pl.multiple_of(k * per_block, per_block),
                               per_block)
                    for pool, buf in ((sum_k, kbuf), (sum_v, vbuf)):
                        go(pltpu.make_async_copy(
                            pool.at[layer, blk], buf.at[slot, at],
                            sem.at[slot]))
                    return 0
                lax.fori_loop(0, kb, one, 0)

        copies(0, 0, True)

        def step(g, carry):
            slot = lax.rem(g, 2)

            @pl.when(g + 1 < steps)
            def _prefetch():
                copies(g + 1, 1 - slot, True)

            copies(g, slot, False)
            in_window = g < steps_w
            base = jnp.where(in_window, g, g - steps_w) * keys
            return consume(g, slot, carry, in_window, base)

        return lax.fori_loop(0, steps, step, carry)

    def attend_row(r):
        """One pack row (a decode row, a run too short for the MXU):
        memory-bound, so multiply-and-reduce on the VPU in float32, a
        block of keys at a time."""
        seq, p = seq_ref[r0 + r], pos_ref[r0 + r]
        p_w = lax.rem(p, W)
        n_sum = lax.div(p, W) * per_window
        q = q_ref[r].astype(jnp.float32) * scale            # (nh, hd)

        def consume(g, slot, carry, in_window, base):
            # the keys of this step that the row may attend: window rows
            # up to its own, or summaries of the earlier windows
            bound = jnp.where(in_window, p_w + 1, n_sum)

            def block(k, carry):
                m_prev, l, acc = carry
                at = pl.ds(pl.multiple_of(k * per_block, per_block),
                           per_block)
                kk = kbuf[slot, at].astype(jnp.float32).reshape(bs, nh, hd)
                vv = vbuf[slot, at].astype(jnp.float32).reshape(bs, nh, hd)
                sc = jnp.sum(q[None] * kk, axis=-1, keepdims=True)
                kpos = base + k * bs + lax.broadcasted_iota(
                    jnp.int32, sc.shape, 0)
                valid = kpos < bound
                sc = jnp.where(valid, sc, _NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
                pr = jnp.where(valid, jnp.exp(sc - m_new[None]), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                return (m_new, alpha * l + jnp.sum(pr, axis=0),
                        acc * alpha + jnp.sum(pr * vv, axis=0))
            n_blocks = jnp.clip(lax.div(bound - base + bs - 1, bs), 0, kb)
            return lax.fori_loop(0, n_blocks, block, carry)

        _, l, acc = walk(seq, p_w, n_sum, consume, (
            jnp.full((nh, 1), _NEG_INF, jnp.float32),
            jnp.zeros((nh, 1), jnp.float32),
            jnp.zeros((nh, hd), jnp.float32)))
        o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    if strided:
        x32, qh_ref = scratch
        pack = 4 // jnp.dtype(dtype).itemsize       # heads a 32-bit row

        def each_head(per, body):
            lax.fori_loop(0, nh // per, lambda j, _: body(j) or 0, 0)

        def heads(buf, slot, j):
            """Heads ``pack * j ...`` of the staged keys, each (keys, hd):
            rows ``h, h + nh, ...`` of the (keys * nh, hd) view, ``pack``
            of them to a 32-bit row (ops/ragged_paged_attention.py)."""
            if pack == 1:
                return [buf[slot, pl.ds(j, keys, stride=nh), :]]
            both = buf.at[slot].bitcast(jnp.int32)[
                pl.ds(j, keys, stride=nh // pack), :]
            return [pltpu.bitcast(half, jnp.float32).astype(dtype)
                    for half in (both << 16, both & jnp.int32(-65536))]

    def one_head(h, q_h, k_h, v_h, valid):
        sc = dot(q_h, k_h, (((1,), (1,)), ((), ()))) * scale
        sc = jnp.where(valid, sc, _NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        pr = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(pr, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + dot(
            pr.astype(dtype), v_h, (((1,), (0,)), ((), ())))
        m_ref[h] = m_new

    def attend_run(s, n):
        """Rows [s, s + n) of this step: one sequence at consecutive
        positions of one window, so they share every key.  Through the
        MXU head by head as one (rows, hd) operand — the step's other rows
        ride along masked and are not written."""
        seq, first = seq_ref[r0 + s], pos_ref[r0 + s]
        first_w = lax.rem(first, W)
        n_sum = lax.div(first, W) * per_window
        i = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        in_run = (i >= s) & (i < s + n)
        row_w = jnp.where(in_run, first_w + i - s, -1)
        sum_bound = jnp.where(in_run, n_sum, 0)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if strided:     # q head-major, through a 32-bit strided load
            x32[...] = q_ref[...].reshape(rows * nh, hd).astype(jnp.float32)

            @functools.partial(each_head, 1)
            def _q_head_major(h):
                qh_ref[h] = x32[pl.ds(h, rows, stride=nh), :].astype(dtype)

        def consume(g, slot, carry, in_window, base):
            kpos = base + lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
            w = in_window.astype(jnp.int32)
            valid = kpos < w * (row_w + 1) + (1 - w) * sum_bound
            if strided:
                @functools.partial(each_head, pack)
                def _heads(j):
                    for i, (k_h, v_h) in enumerate(zip(
                            heads(kbuf, slot, j), heads(vbuf, slot, j))):
                        h = pack * j + i
                        one_head(h, qh_ref[h], k_h, v_h, valid)
            else:
                for h in range(nh):
                    at = slice(h * hd, (h + 1) * hd)
                    one_head(h, q_ref[:, at].astype(dtype),
                             kbuf[slot, :, at], vbuf[slot, :, at], valid)
            return carry

        walk(seq, first_w + n - 1, n_sum, consume, 0)
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
        """The run that starts at row ``s`` of the step: the rows after
        it, up to the step's end and the window's, that are the same
        sequence at the next positions."""
        seq, first = seq_ref[r0 + s], pos_ref[r0 + s]

        def continues(n):   # (``&`` reads both sides: stay inside SMEM)
            at = jnp.minimum(r0 + s + n, seq_ref.shape[0] - 1)
            return ((s + n < rows) & (seq_ref[at] == seq)
                    & (pos_ref[at] == first + n)
                    & (lax.rem(first + n, W) != 0))
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


def ragged_eva_attention_rows(q, win_k, win_v, sum_k, sum_v, table, row_seq,
                              row_pos, *, chunk, scale, layer=None,
                              interpret=False, rows_per_step=ROWS_PER_STEP,
                              keys_per_step=KEYS_PER_STEP):
    """q (T, nh, hd); win_k / win_v (S, W, nh, hd) the window leaf;
    sum_k / sum_v (NB + 1, bs, nh, hd) the summary leaf — or, with
    ``layer`` (a traced int32 scalar), whole stacks' (L, ...) read in
    place; table (S, C) int32 names a sequence's summary blocks (block 0 =
    trash); row_seq (T,) the slot of each row (padding rows may carry any
    value); row_pos (T,) its position counted from the sequence's first
    real one, -1 for padding rows.  A run of rows of one sequence lies in
    one window.

    Returns (T, nh, hd) in q's dtype (zeros for padding rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, nh, hd = q.shape
    if layer is None:
        win_k, win_v, sum_k, sum_v = (
            p[None] for p in (win_k, win_v, sum_k, sum_v))
        layer = 0
    L, S, W = win_k.shape[:3]
    NB1, bs = sum_k.shape[1:3]
    if W % chunk or W % bs:
        raise ValueError(f"chunk ({chunk}) and the summary block ({bs}) "
                         f"must divide the window ({W})")
    keys = bs * _largest_divisor(W // bs, max(int(keys_per_step) // bs, 1))
    kb = keys // bs
    rows = _largest_divisor(T, rows_per_step)
    strided, vpu_rows = _form(nh, hd, bs, q.dtype, win_k.dtype)
    row_seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)

    # a key as the kernel stages it: whole tiles either way
    if strided:
        operands = [win_k.reshape(L, S, W * nh, hd),
                    win_v.reshape(L, S, W * nh, hd),
                    sum_k.reshape(L, NB1, bs * nh, hd),
                    sum_v.reshape(L, NB1, bs * nh, hd)]
        stage = (keys * nh, hd)
    else:
        operands = [win_k.reshape(L, S, W, nh * hd),
                    win_v.reshape(L, S, W, nh * hd),
                    sum_k.reshape(L, NB1, bs, nh * hd),
                    sum_v.reshape(L, NB1, bs, nh * hd)]
        stage = (keys, nh * hd)
    scratch = [pltpu.VMEM((2,) + stage, win_k.dtype)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((nh, rows, hd), jnp.float32),
                pltpu.VMEM((nh, rows, 1), jnp.float32),
                pltpu.VMEM((nh, rows, 1), jnp.float32)]
    if strided:
        scratch += [pltpu.VMEM((rows * nh, hd), jnp.float32),  # q in, o out
                    pltpu.VMEM((nh, rows, hd), win_k.dtype)]   # q, head-major
    q_tail = (nh, hd) if strided else (nh * hd,)
    row_spec = pl.BlockSpec((rows,) + q_tail,
                            lambda i, *_: (i,) + (0,) * len(q_tail))
    kernel = functools.partial(
        _eva_kernel, W=W, per_window=W // chunk, bs=bs, kb=kb, rows=rows,
        nh=nh, hd=hd, scale=scale, strided=strided, vpu_rows=vpu_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,              # table, row_seq, row_pos, layer
        grid=(T // rows,),
        in_specs=[row_spec]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(operands),  # stay put
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    with jax.named_scope("ragged_eva_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T,) + q_tail, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 << 20),
            interpret=interpret,
            name="ragged_eva_attention",
        )(table.astype(jnp.int32), row_seq, jnp.asarray(row_pos, jnp.int32),
          jnp.asarray(layer, jnp.int32).reshape(1),
          q.reshape((T,) + q_tail), *operands)
    return out.reshape(T, nh, hd)


def ragged_eva_attention_ref(q, win_k, win_v, sum_k, sum_v, table, row_seq,
                             row_pos, *, chunk, scale, layer=None):
    """XLA fallback and oracle: each row's window and table-selected
    summaries gathered dense, one float32 softmax over both."""
    if layer is not None:
        win_k, win_v, sum_k, sum_v = (
            lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
            for p in (win_k, win_v, sum_k, sum_v))
    S, W, nh, hd = win_k.shape
    bs = sum_k.shape[1]
    C = table.shape[1]
    with jax.named_scope("ragged_eva_attention"):
        seq = jnp.clip(jnp.asarray(row_seq, jnp.int32), 0, S - 1)
        pos = jnp.asarray(row_pos, jnp.int32)
        n_sum = (pos // W) * (W // chunk)

        def dense(win, sums):
            s = sums[table].reshape(S, C * bs, nh, hd)      # by sequence
            return jnp.concatenate([win, s], axis=1)[seq]   # (T, W+C*bs, ..)
        k, v = dense(win_k, sum_k), dense(win_v, sum_v)
        sc = jnp.einsum("thd,tkhd->thk", q, k,
                        preferred_element_type=jnp.float32) * scale
        at = jnp.arange(W + C * bs)[None, :]
        valid = jnp.where(at < W, at <= (pos % W)[:, None],
                          at - W < n_sum[:, None]) & (pos >= 0)[:, None]
        sc = jnp.where(valid[:, None, :], sc, _NEG_INF)
        p = jax.nn.softmax(sc, -1)
        p = jnp.where(valid[:, None, :], p, 0.0).astype(q.dtype)
        return jnp.einsum("thk,tkhd->thd", p, v).astype(q.dtype)
