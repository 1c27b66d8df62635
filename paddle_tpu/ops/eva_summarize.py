"""Closing a chunk of EVA attention (ops/ragged_eva_attention.py has the
layout and the attention): for each chunk whose last row is in the pack,
the chunk's ``chunk`` rotated keys and values are read back from the window
leaf ``(L, slots, W, nh, hd)`` and reduced, per head, to ``k~ = sum_j a_j
k_j + mu`` and ``v~ = sum_j a_j v_j`` with ``a = softmax_j(scale * phi .
k_j)``.  One grid step a chunk, the chunk found by the index maps; the
same operation for a prefill chunk's hundred closures and a decode row's
one.  The caller writes the result to the summary leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _summarize_kernel(layer_ref, seq_ref, chunk_ref, k_ref, v_ref, phi_ref,
                      mu_ref, ks_ref, vs_ref, *, scale):
    k = k_ref[...].astype(jnp.float32)                  # (chunk, nh, hd)
    v = v_ref[...].astype(jnp.float32)
    sc = jnp.sum(k * phi_ref[...].astype(jnp.float32)[None], axis=-1,
                 keepdims=True) * scale                  # (chunk, nh, 1)
    e = jnp.exp(sc - jnp.max(sc, axis=0, keepdims=True))
    a = e / jnp.sum(e, axis=0, keepdims=True)
    ks_ref[...] = (jnp.sum(a * k, axis=0)
                   + mu_ref[...].astype(jnp.float32)).astype(ks_ref.dtype)
    vs_ref[...] = jnp.sum(a * v, axis=0).astype(vs_ref.dtype)


def eva_summarize_rows(win_k, win_v, phi, mu, seq, chunk_at, *, chunk,
                       scale, layer=None, interpret=False):
    """The summaries of N chunks: win_k / win_v (S, W, nh, hd) — or a
    stack's (L, S, W, nh, hd) and ``layer`` —, phi / mu (nh, hd), ``seq``
    (N,) the slot of each chunk and ``chunk_at`` (N,) its index inside
    the window, ``[0, W // chunk)``.  Returns (k~, v~), each (N, nh, hd)
    in the leaf's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layer is None:
        win_k, win_v, layer = win_k[None], win_v[None], 0
    L, S, W, nh, hd = win_k.shape
    N = seq.shape[0]
    view = (L, S, W // chunk, chunk, nh, hd)
    rows = pl.BlockSpec(
        (None, None, None, chunk, nh, hd),
        lambda i, layer, seq, at: (layer[0], seq[i], at[i], 0, 0, 0))
    whole = pl.BlockSpec((nh, hd), lambda i, *_: (0, 0))
    out = pl.BlockSpec((None, nh, hd), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(N,),
        in_specs=[rows, rows, whole, whole], out_specs=[out, out])
    with jax.named_scope("eva_summarize"):
        return pl.pallas_call(
            functools.partial(_summarize_kernel, scale=scale),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((N, nh, hd), win_k.dtype)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="eva_summarize",
        )(jnp.asarray(layer, jnp.int32).reshape(1),
          jnp.clip(jnp.asarray(seq, jnp.int32), 0, S - 1),
          jnp.clip(jnp.asarray(chunk_at, jnp.int32), 0, W // chunk - 1),
          win_k.reshape(view), win_v.reshape(view), phi, mu)


def eva_summarize_ref(win_k, win_v, phi, mu, seq, chunk_at, *, chunk, scale,
                      layer=None):
    """XLA fallback and oracle of ``eva_summarize_rows``."""
    if layer is not None:
        win_k, win_v = (lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
                        for w in (win_k, win_v))
    S, W, nh, hd = win_k.shape
    with jax.named_scope("eva_summarize"):
        seq = jnp.clip(seq, 0, S - 1)
        at = jnp.clip(chunk_at, 0, W // chunk - 1)
        k, v = (w.reshape(S, W // chunk, chunk, nh, hd)[seq, at]
                .astype(jnp.float32) for w in (win_k, win_v))
        sc = jnp.einsum("nchd,hd->nch", k, phi.astype(jnp.float32)) * scale
        a = jax.nn.softmax(sc, axis=1)[..., None]
        ks = jnp.sum(a * k, axis=1) + mu.astype(jnp.float32)
        return (ks.astype(win_k.dtype),
                jnp.sum(a * v, axis=1).astype(win_k.dtype))
