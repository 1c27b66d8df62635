"""Ragged paged attention kernel (ops/ragged_paged_attention.py): the
in-kernel block-table walk over a flattened mixed prefill+decode pack must
reproduce the gather fallback exactly — including per-row causal clocks,
left-pad masks, int8 (values, scales) pools with in-kernel dequant,
zero-length sequences, and padding rows.  CPU CI runs interpret mode; the
Mosaic lowering is exercised by the -m tpu smoke suite on hardware."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models._decode import quantize_kv
from paddle_tpu.ops.ragged_paged_attention import (MIN_RUN, ROWS_PER_STEP,
                                                   grouped_rows,
                                                   ragged_attention_ref,
                                                   ragged_attention_rows,
                                                   ragged_paged_attention,
                                                   ragged_rows)


def _case(seed, S=4, nh=4, hd=16, NB1=11, bs=8, C=4, T=24, quantized=False):
    rng = np.random.RandomState(seed)
    pk = jnp.asarray(rng.randn(NB1, bs, nh, hd), jnp.float32)
    pv = jnp.asarray(rng.randn(NB1, bs, nh, hd), jnp.float32)
    if quantized:
        pk = quantize_kv(pk)
        pv = quantize_kv(pv)
    table = jnp.asarray(rng.randint(0, NB1, (S, C)), jnp.int32)
    # random ragged q lengths summing to <= T (zero-length rows included)
    q_lens = rng.randint(0, 6, S)
    while q_lens.sum() > T:
        q_lens[rng.randint(S)] = 0
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    # kv extent AFTER the writes: at least the sequence's own rows
    kv = jnp.asarray([rng.randint(q, C * bs + 1) if q else 0
                      for q in q_lens], jnp.int32)
    pad = jnp.asarray([rng.randint(0, max(int(k) - int(q), 0) + 1)
                       for k, q in zip(kv, q_lens)], jnp.int32)
    q = jnp.asarray(rng.randn(T, nh, hd), jnp.float32)
    return q, pk, pv, table, cu, kv, pad, int(q_lens.sum())


class TestRaggedRows:
    def test_row_expansion(self):
        cu = jnp.asarray([0, 3, 3, 4, 6], jnp.int32)   # q_lens 3, 0, 1, 2
        kv = jnp.asarray([10, 0, 5, 2], jnp.int32)
        seq, pos = ragged_rows(cu, kv, 8)
        np.testing.assert_array_equal(np.asarray(seq)[:6],
                                      [0, 0, 0, 2, 3, 3])
        # positions: seq0 rows at 7..9, seq2 decode row at 4, seq3 at 0..1
        np.testing.assert_array_equal(np.asarray(pos),
                                      [7, 8, 9, 4, 0, 1, -1, -1])


class TestRaggedKernelParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_matches_gather_fallback(self, seed):
        q, pk, pv, table, cu, kv, pad, n_real = _case(seed)
        rs, rp = ragged_rows(cu, kv, q.shape[0])
        ref = ragged_attention_ref(q, pk, pv, table, rs, rp, pad)
        got = ragged_paged_attention(q, pk, pv, table, cu, kv, pad,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got)[:n_real],
                                   np.asarray(ref)[:n_real],
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.parametrize("seed", [5, 6])
    def test_int8_pools_dequant_in_kernel(self, seed):
        """int8 (values, scales) pools take the kernel path with the
        dequantize fused into the k/v read — parity with the fallback's
        gather-then-dequantize."""
        q, pk, pv, table, cu, kv, pad, n_real = _case(seed, quantized=True)
        rs, rp = ragged_rows(cu, kv, q.shape[0])
        ref = ragged_attention_ref(q, pk, pv, table, rs, rp, pad)
        got = ragged_paged_attention(q, pk, pv, table, cu, kv, pad,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got)[:n_real],
                                   np.asarray(ref)[:n_real],
                                   rtol=2e-5, atol=2e-5)

    def test_pure_decode_matches_paged_decode_kernel(self):
        """A pack of q_len == 1 rows IS the old decode kernel's workload:
        outputs must match ops/paged_attention.py row for row (the ragged
        kernel strictly generalizes it)."""
        from paddle_tpu.ops.paged_attention import paged_decode_attention
        rng = np.random.RandomState(9)
        S, nh, hd, NB1, bs, C = 4, 4, 16, 11, 8, 4
        pk = jnp.asarray(rng.randn(NB1, bs, nh, hd), jnp.float32)
        pv = jnp.asarray(rng.randn(NB1, bs, nh, hd), jnp.float32)
        table = jnp.asarray(rng.randint(0, NB1, (S, C)), jnp.int32)
        t = jnp.asarray(rng.randint(0, C * bs, S), jnp.int32)
        pad = jnp.minimum(jnp.asarray(rng.randint(0, bs, S), jnp.int32), t)
        q = jnp.asarray(rng.randn(S, nh, hd), jnp.float32)
        old = paged_decode_attention(q, pk, pv, table, t, pad,
                                     interpret=True)
        cu = jnp.arange(S + 1, dtype=jnp.int32)         # one row per slot
        got = ragged_paged_attention(q, pk, pv, table, cu, t + 1, pad,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(old),
                                   rtol=2e-5, atol=2e-5)

    def test_no_pad_and_empty_pack(self):
        """pad_lens=None defaults to zeros; an all-padding pack (zero real
        rows) is garbage-but-finite."""
        q, pk, pv, table, cu, kv, pad, _ = _case(11)
        rs, rp = ragged_rows(cu, kv, q.shape[0])
        ref = ragged_attention_ref(q, pk, pv, table, rs, rp, None)
        got = ragged_paged_attention(q, pk, pv, table, cu, kv, None,
                                     interpret=True)
        n_real = int(np.asarray(cu)[-1])
        np.testing.assert_allclose(np.asarray(got)[:n_real],
                                   np.asarray(ref)[:n_real],
                                   rtol=2e-5, atol=2e-5)
        empty = ragged_paged_attention(
            q, pk, pv, table, jnp.zeros_like(cu), jnp.zeros_like(kv),
            None, interpret=True)
        assert np.isfinite(np.asarray(empty)).all()


def _pack(entries, T):
    """Row metadata of a pack: ``entries`` is (sequence, rows, first kv
    position) in pack order — a decode row is one row, a chunk ``n``
    consecutive rows at consecutive positions; padding rows (sequence
    -1, position -1, as the engine marks them) fill the budget."""
    row_seq = np.full(T, -1, np.int32)
    row_pos = np.full(T, -1, np.int32)
    at = 0
    for seq, n, first in entries:
        row_seq[at:at + n] = seq
        row_pos[at:at + n] = first + np.arange(n)
        at += n
    assert at <= T
    return row_seq, row_pos


# name: (entries, T, pads by sequence, geometry and the kernel's step sizes)
_WALKS = {
    # the docs cell's chunk round: 13 decode rows at 1-2 k keys, then a
    # 499-row chunk that starts 13 rows into the first grid step
    "docs-chunk-round": dict(
        entries=[(s, 1, 1024 + 71 * s) for s in range(13)]
        + [(13, 499, 512)],
        T=512, S=14, C=128, bs=16, nh=8, hd=128, NB1=120),
    # a chunk whose keys span several key steps (16 keys each) and end
    # inside a block, behind two decode rows
    "chunk-ends-inside-a-block": dict(
        entries=[(0, 1, 37), (1, 1, 5), (2, 40, 21)],
        T=64, S=3, C=8, bs=8, nh=8, hd=128, NB1=30,
        rows_per_step=32, blocks_per_step=2),
    # a bucketed prompt's first chunk: positions below the pad are the
    # bucket's left-pad rows, which attend nothing
    "left-pad-rows": dict(
        entries=[(0, 1, 30), (1, 40, 0)], pads=[3, 11],
        T=48, S=2, C=8, bs=8, nh=8, hd=128, NB1=30,
        rows_per_step=16, blocks_per_step=2),
    # speculative verify chunks of K + 1 = 5 rows: shorter than MIN_RUN
    "verify-chunks": dict(
        entries=[(0, 5, 17), (1, 1, 40), (2, 5, 3)],
        T=16, S=3, C=8, bs=8, nh=8, hd=128, NB1=30,
        rows_per_step=16, blocks_per_step=2),
    "padding-rows-only": dict(
        entries=[], T=32, S=2, C=4, bs=8, nh=8, hd=128, NB1=9,
        rows_per_step=16, blocks_per_step=2),
    # 40 rows in steps of 10 (the largest divisor under 16)
    "budget-not-a-multiple-of-the-step": dict(
        entries=[(0, 1, 9), (1, 33, 2)],
        T=40, S=2, C=8, bs=8, nh=8, hd=128, NB1=30,
        rows_per_step=16, blocks_per_step=2),
    # int8 pools, four heads to a 32-bit row, scales with keys on lanes
    "int8-pools": dict(
        entries=[(0, 1, 37), (1, 1, 5), (2, 40, 21)], pads=[2, 0, 4],
        T=64, S=3, C=8, bs=8, nh=8, hd=128, NB1=30, quantized=True,
        rows_per_step=32, blocks_per_step=2),
    # bfloat16: two heads to a 32-bit row
    "bfloat16-pools": dict(
        entries=[(0, 1, 37), (1, 1, 5), (2, 40, 21)], pads=[2, 0, 4],
        T=64, S=3, C=8, bs=8, nh=16, hd=128, NB1=30,
        dtype=jnp.bfloat16, tol=2e-2,
        rows_per_step=32, blocks_per_step=2),
    # gpt2-small's heads: no strided form, a head is a lane slice
    "hd64-12-heads": dict(
        entries=[(0, 1, 37), (1, 1, 5), (2, 40, 21)], pads=[2, 0, 4],
        T=64, S=3, C=8, bs=8, nh=12, hd=64, NB1=30,
        rows_per_step=32, blocks_per_step=2),
}


def _walk_case(name, layers=None):
    w = dict(_WALKS[name])
    rng = np.random.RandomState(len(name))
    dtype = w.get("dtype", jnp.float32)
    S, C, bs, nh, hd, NB1, T = (w[k] for k in
                                ("S", "C", "bs", "nh", "hd", "NB1", "T"))
    lead = (layers,) if layers else ()

    def pool():
        p = jnp.asarray(rng.randn(*lead, NB1, bs, nh, hd), dtype)
        return quantize_kv(p) if w.get("quantized") else p

    pk, pv = pool(), pool()
    table = jnp.asarray(rng.randint(1, NB1, (S, C)), jnp.int32)
    row_seq, row_pos = _pack(w["entries"], T)
    pad = jnp.asarray(w.get("pads", [0] * S), jnp.int32)
    q = jnp.asarray(rng.randn(T, nh, hd), dtype)
    steps = {k: w[k] for k in ("rows_per_step", "blocks_per_step") if k in w}
    return q, pk, pv, table, row_seq, row_pos, pad, steps, w.get("tol", 2e-5)


def _ref_rows(q, pk, pv, table, row_seq, row_pos, pad, rows, n=64):
    """The gather oracle for pack rows ``rows``, a slice at a time (it
    densifies every row's whole table: 512 rows of 2,048 keys at once are
    gigabytes)."""
    return np.concatenate([
        np.asarray(ragged_attention_ref(
            q[at], pk, pv, table, row_seq[at], row_pos[at], pad),
            np.float32)
        for at in (rows[i:i + n] for i in range(0, len(rows), n))])


def _attending(table, row_seq, row_pos, pad):
    """Rows that attend at least one key: real, and not a left-pad row."""
    seq = np.clip(row_seq, 0, table.shape[0] - 1)
    return (row_pos >= 0) & (row_pos >= np.asarray(pad)[seq])


class TestTheWalk:
    """What the kernel's own walk adds (PR 33): runs of rows through the
    MXU as one operand wherever they start in a grid step, rows one by
    one, key steps staged through a double buffer — against the gather
    oracle, in every form the shapes select."""

    @pytest.mark.parametrize("name", list(_WALKS))
    def test_matches_gather_fallback(self, name):
        q, pk, pv, table, rs, rp, pad, steps, tol = _walk_case(name)
        got = np.asarray(ragged_attention_rows(
            q, pk, pv, table, rs, rp, pad, interpret=True, **steps),
            np.float32)
        live = _attending(table, rs, rp, pad)
        # every row of a small pack; of the 512-row one every fifth and
        # the rows either side of each grid step's edge and the chunk's
        T = len(rp)
        edges = np.r_[0:T:5, [e + d for e in (13, 128, 256, 384, 512)
                              for d in (-2, -1, 0, 1)]]
        rows = np.flatnonzero(live) if T <= 128 else \
            np.intersect1d(np.flatnonzero(live), edges)
        if len(rows):
            ref = _ref_rows(q, pk, pv, table, rs, rp, pad, rows)
            np.testing.assert_allclose(got[rows], ref, rtol=tol, atol=tol)
        # padding rows and left-pad rows read zeros, not garbage
        assert not got[~live].any()

    @pytest.mark.parametrize("name", ["chunk-ends-inside-a-block",
                                      "int8-pools", "hd64-12-heads"])
    def test_a_stack_addressed_by_layer(self, name):
        q, pk, pv, table, rs, rp, pad, steps, _ = _walk_case(name, layers=3)
        fn = lambda pk, pv, layer: ragged_attention_rows(
            q, pk, pv, table, rs, rp, pad, layer=layer, interpret=True,
            **steps)
        whole = jax.jit(fn)                     # the layer is a traced value
        for i in (0, 2):
            lk, lv = jax.tree.map(lambda p: p[i], (pk, pv))
            np.testing.assert_array_equal(
                np.asarray(whole(pk, pv, jnp.int32(i))),
                np.asarray(fn(lk, lv, None)))


class TestGroupedRows:
    """``grouped_rows`` reads the engine's record of a pack; the kernel
    reads row_seq / row_pos.  Both must cut the same runs."""

    @staticmethod
    def _from_rows(row_seq, row_pos, rows, min_run):
        grouped = s = 0
        T = len(row_pos)
        while s < T:
            n = 1
            while ((s + n) % rows and s + n < T and row_pos[s] >= 0
                   and row_seq[s + n] == row_seq[s]
                   and row_pos[s + n] == row_pos[s] + n):
                n += 1
            if row_pos[s] >= 0 and n >= min_run:
                grouped += n
            s += n
        return grouped

    @pytest.mark.parametrize("name,rows,want", [
        ("docs-chunk-round", 128, 499),     # 115 + 3 x 128: every chunk row
        ("chunk-ends-inside-a-block", 32, 40),      # 30 + 10
        ("verify-chunks", 16, 0),           # 5 rows are under MIN_RUN
        ("padding-rows-only", 16, 0),
        ("budget-not-a-multiple-of-the-step", 10, 29),  # 9 + 10 + 10 (+ 4)
    ])
    def test_counts_the_runs_the_kernel_cuts(self, name, rows, want):
        w = _WALKS[name]
        row_seq, row_pos = _pack(w["entries"], w["T"])
        # the engine's record: [request id, rows, kv end] per sequence
        record = [[100 + seq, n, first + n] for seq, n, first in
                  w["entries"]]
        got = grouped_rows(record, w["T"],
                           w.get("rows_per_step", ROWS_PER_STEP))
        assert got == self._from_rows(row_seq, row_pos, rows, MIN_RUN)
        assert got == want

    def test_decode_round_groups_nothing(self):
        assert grouped_rows([[i, 1, 1500 + i] for i in range(14)], 512) == 0


def _stack_case(seed, quantized, L=3, NB1=11):
    """``_case`` with a pool per layer, stacked as the engine stores them
    (every leaf gains a leading layer axis)."""
    q, _, _, table, cu, kv, pad, _ = _case(seed, NB1=NB1)
    layers = [_case(seed + 100 * i, NB1=NB1, quantized=quantized)[1:3]
              for i in range(L)]
    pk, pv = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return q, pk, pv, layers, table, cu, kv, pad


class TestAddressedByLayer:
    """With ``layer`` the pools are a whole stack's and that layer's
    blocks are read, or written, in place: the same bits as the call on
    ``stack[layer]`` — padding rows and left pads included (``_case``
    draws both)."""

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    @pytest.mark.parametrize("path", ["kernel", "ref"])
    def test_attention_over_a_stack_equals_the_sliced_call(self, path,
                                                           quantized):
        q, pk, pv, layers, table, cu, kv, pad = _stack_case(21, quantized)
        rs, rp = ragged_rows(cu, kv, q.shape[0])
        assert (np.asarray(rp) < 0).any() and (np.asarray(pad) > 0).any()
        if path == "kernel":
            fn = lambda pk, pv, layer: ragged_paged_attention(
                q, pk, pv, table, cu, kv, pad, layer=layer, interpret=True)
        else:
            fn = lambda pk, pv, layer: ragged_attention_ref(
                q, pk, pv, table, rs, rp, pad, layer=layer)
        whole = jax.jit(fn)                     # the layer is a traced value
        for i, (lk, lv) in enumerate(layers):
            np.testing.assert_array_equal(
                np.asarray(whole(pk, pv, jnp.int32(i))),
                np.asarray(fn(lk, lv, None)))

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    def test_write_lands_in_its_layer_only(self, quantized):
        """Both planes of an int8 pair: the scale plane used to be written
        with the layer dropped (into the stack's first axis as if it were
        the block axis)."""
        from paddle_tpu.models._decode import ragged_write
        q, pk, _, layers, table, cu, kv, _ = _stack_case(22, quantized,
                                                         NB1=17)
        # a block of its own per (sequence, column): no two rows collide
        table = jnp.arange(1, table.size + 1,
                           dtype=jnp.int32).reshape(table.shape)
        rs, rp = ragged_rows(cu, kv, q.shape[0])
        # both sides compiled: the quantizer's division rounds another
        # way op by op
        write = jax.jit(lambda pool, layer: ragged_write(
            pool, q, table, rs, rp, layer))
        for i, (lk, _) in enumerate(layers):
            got = write(pk, jnp.int32(i))
            want = write(lk, None)
            for g, w, before in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want),
                                    jax.tree.leaves(pk)):
                assert g.shape == before.shape
                np.testing.assert_array_equal(np.asarray(g[i]),
                                              np.asarray(w))
                others = np.arange(before.shape[0]) != i
                np.testing.assert_array_equal(np.asarray(g)[others],
                                              np.asarray(before)[others])
