"""The plain reference of the first pipeline stage of EvaByte: the forward
pass in straightforward ``jax.numpy``, float32, every product at
``Precision.HIGHEST``.  No cache, no kernel, no pack; it imports nothing of
the program.

Published description: the model's ``config.json`` (``model_type:
evabyte``, ``attention_class: eva``) and EVA attention (Zheng et al., ICLR
2023, arXiv:2302.04542: exact terms for the local set, one control-variate
term per remote chunk), in the deterministic form of the configuration
file's ``chunk_summary_form``: the proposal's sample is a learned vector
per head.

    n(x) = x / sqrt(mean(x^2) + eps) * (1 + g)     (norm_add_unit_offset)
    a = n1(x); q, k, v = a W_q, a W_k, a W_v; rotate-half rotary on q, k
    window w(p) = p // window_size; chunk c(p) = p // chunk_size
    chunk c, head h: a_cj = softmax_j(s phi_h . k_j) over its keys;
                     k~_c = sum_j a_cj k_j + mu_h; v~_c = sum_j a_cj v_j
    E(p) = {m : w(m) = w(p), m <= p}; R(p) = {c : c // (W / chunk) < w(p)}
    o_p = [sum_E exp(s q.k_m) v_m + sum_R exp(s q.k~_c) v~_c]
          / [sum_E exp(s q.k_m) + sum_R exp(s q.k~_c)],  s = head_dim^-1/2
    x = x + o W_o;  b = n2(x);  x = x + (silu(b W_gate) * b W_up) W_down
    logits = n_f(x) W_head, of which head 0 (the first vocab_size columns)

Departures, each to make it fit beside the bfloat16 weights on one chip:
the layers run under ``lax.scan`` over the stacked weights (a weight is
upcast where it is used); attention is taken one window of query rows at a
time, the row-wise parts a block of rows at a time.  The number of
positions is a multiple of ``window_size`` (the caller pads; a pad row is
causal and changes nothing before it).

``lower`` names the control of ``correct``, as in ``reference_gpt``:
``"int8"`` rounds the operands of every product to 255 levels, one scale
per row of an activation and per column of a weight; ``"bfloat16"`` rounds
them to bfloat16.  ``summaries`` names two more: ``"off"`` attends the
window alone, ``"previous"`` the window and the previous window's chunks
alone — what a program that forgot its summaries, or kept one window of
them, would compute.
"""

import jax
import jax.numpy as jnp

from .weights_evabyte import BLOCK

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _lowered(x, lower, axis):
    if lower is None:
        return x
    if lower == "int8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(x / scale) * scale
    if lower == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    raise ValueError(f"unknown lower precision {lower!r}")


def _matmul(x, w, lower):
    """x (..., K) float32 times w (K, N), upcast here."""
    return jnp.matmul(_lowered(x, lower, -1),
                      _lowered(w.astype(F32), lower, 0), precision=HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, pos, theta):
    """Rotate-half over the last axis of x (L, heads, D) at positions (L,)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _by_rows(fn, x, block):
    split = lambda a: a.reshape((-1, block) + a.shape[1:])
    join = lambda o: o.reshape((-1,) + o.shape[2:])
    return jax.tree.map(join, jax.lax.map(fn, jax.tree.map(split, x)))


def chunk_summaries(cfg, k, v, phi, mu):
    """k, v (L, nh, hd) -> k~, v~ (L / chunk, nh, hd)."""
    chunk = cfg["chunk_size"]
    L, nh, hd = k.shape
    kc, vc = (t.reshape(L // chunk, chunk, nh, hd) for t in (k, v))
    s = hd ** -0.5
    a = jax.nn.softmax(
        s * jnp.einsum("cjhd,hd->cjh", kc, phi.astype(F32),
                       precision=HIGHEST), axis=1)[..., None]
    return jnp.sum(a * kc, 1) + mu.astype(F32), jnp.sum(a * vc, 1)


def attend(cfg, q, k, v, phi, mu, lower=None, summaries="all"):
    """EVA attention of rotated q, k and v, each (L, nh, hd): (L, nh, hd).
    One window of query rows at a time, one softmax over the window's
    causal keys and the summaries of every earlier window."""
    L, nh, hd = q.shape
    W, chunk = cfg["window_size"], cfg["chunk_size"]
    s = hd ** -0.5
    ks, vs = chunk_summaries(cfg, k, v, phi, mu)
    low = lambda t, axis=-1: _lowered(t, lower, axis)
    q, k, ks = low(q), low(k), low(ks)
    c_window = jnp.arange(L // chunk) // (W // chunk)   # a chunk's window

    def window(w):
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, w * W, W, axis=0)
        qw, kw, vw = at(q), at(k), at(v)
        exact = s * jnp.einsum("qhd,khd->hqk", qw, kw, precision=HIGHEST)
        i = jnp.arange(W)
        exact = jnp.where((i[None, :] <= i[:, None])[None], exact, -jnp.inf)
        remote = s * jnp.einsum("qhd,chd->hqc", qw, ks, precision=HIGHEST)
        seen = {"all": c_window < w, "previous": c_window == w - 1,
                "off": c_window < 0}[summaries]
        remote = jnp.where(seen[None, None, :], remote, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([exact, remote], -1), -1)
        p_e, p_r = low(p[..., :W]), low(p[..., W:])
        return jnp.einsum("hqk,khd->qhd", p_e, low(vw, 0),
                          precision=HIGHEST) \
            + jnp.einsum("hqc,chd->qhd", p_r, low(vs, 0), precision=HIGHEST)

    return jax.lax.map(window, jnp.arange(L // W)).reshape(L, nh, hd)


def _attention(cfg, sl, x, lower, block, summaries):
    """o W_o of EVA attention over n1(x), x (L, H): (L, H)."""
    L, H = x.shape
    nh = cfg["num_attention_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(L)

    def project(xb):
        return _matmul(_norm(xb, sl["ln1_w"], eps), sl["qkv_w"], lower)
    qkv = _by_rows(project, x, block).reshape(L, 3, nh, H // nh)
    o = attend(cfg, _rope(qkv[:, 0], pos, theta), _rope(qkv[:, 1], pos, theta),
               qkv[:, 2], sl["adaptive_phi"], sl["adaptive_mu_k"], lower,
               summaries).reshape(L, H)
    return _by_rows(lambda ob: _matmul(ob, sl["o_w"], lower), o, block)


def hidden(cfg, params, ids, lower=None, block=None, summaries="all"):
    """ids (L,), L a multiple of ``window_size`` -> the stage's output
    (L, H) float32, before the final norm."""
    W = cfg["window_size"]
    L = ids.shape[0]
    assert L % W == 0, (L, W)
    block = block or min(W, L)
    eps = cfg["rms_norm_eps"]
    x = jnp.take(params["wte"], ids, axis=0).astype(F32)

    def layer(x, sl):
        x = x + _attention(cfg, sl, x, lower, block, summaries)

        def mlp(xb):
            b = _norm(xb, sl["ln2_w"], eps)
            return _matmul(jax.nn.silu(_matmul(b, sl["gate_w"], lower))
                           * _matmul(b, sl["up_w"], lower), sl["down_w"],
                           lower)
        return x + _by_rows(mlp, x, block), None

    x, _ = jax.lax.scan(layer, x, {n: params[f"blocks_{n}"] for n in BLOCK})
    return x


def logits(cfg, params, h, lower=None):
    """Head 0 of the untied head over the final norm of h (..., H)."""
    a = _norm(h, params["norm_f_w"], cfg["rms_norm_eps"])
    return _matmul(a, params["lm_head"], lower)[..., :cfg["vocab_size"]]
