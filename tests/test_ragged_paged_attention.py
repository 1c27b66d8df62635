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
from paddle_tpu.ops.ragged_paged_attention import (ragged_attention_ref,
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
