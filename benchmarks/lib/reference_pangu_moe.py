"""The plain reference of one chip's share of openPangu-Ultra-MoE: the
forward pass in straightforward ``jax.numpy``, float32, every product at
``Precision.HIGHEST``.  No cache, no kernel, no absorbed form, no sorting,
its own routing; it imports nothing of the program.

Published description: the model's ``config.json`` (``model_type:
pangu_ultra_moe``); multi-head latent attention as DeepSeek-V2
(arXiv:2405.04434 §2.1), whose key names the config uses; the sandwich norm
as Pangu Ultra (arXiv:2504.07866 §2.2); the router as DeepSeek-V3
(arXiv:2412.19437 §2.1.2: sigmoid scores, the top-k normalised, times
``routed_scaling_factor``), without its group limit or selection bias,
which the config does not name.

    x = x + N2(MLA(N1(x)));  x = x + N4(F(N3(x)))        (RMSNorm, eps 1e-5)
    MLA: c_q = N(a W_qa); q = c_q W_qb -> heads of [nope ; rope]
         [c_kv ; k_r] = a W_kva; c_kv = N(c_kv); rope on k_r and q_r
         (rotate-half, theta from the config); [k_nope ; v] = c_kv W_kvb
         score = (q_nope . k_nope + q_r . k_r) / sqrt(nope + rope), causal
         softmax, o = sum p v, heads concatenated, W_o
    F dense:  W_down(silu(W_gate m) * (W_up m))
    F expert: s = sigmoid(m W_g) over ``router_width``; the k largest;
              w = s / (sum s + 1e-20) * routed_scaling_factor;
              sum over the HELD experts of w_e E_e(m), + E_shared(m)

The share: only the experts ``experts_held`` are summed; what the others
would add is left out, here as in the program, and the partial result goes
on to the next layer.

Departures, each to make it fit beside the bfloat16 weights on one chip:
the layers of a stack run under ``lax.scan`` over the stacked weights (one
block compiled; a weight is upcast where it is used, never a whole layer
at once); attention is taken a group of heads and, inside it, a block of
query rows at a time, and everything that is row-wise (norms, projections,
MLPs, the router) a block of rows at a time; the held experts are applied
one after another to every row of a block, weighted by zero where the row
was not routed to them.  In the control, a weight's rounding scales are
per column of the slice a head group uses.

``lower`` names the control of ``correct``, as in ``reference_gpt``:
``"int8"`` rounds the operands of every product (the router's and the
attention's included) to 255 levels, one scale per activation row and per
weight column; ``"bfloat16"`` rounds them to bfloat16.

Beside the hidden states the reference returns, per position, the route
margin: the smallest distance, over the expert layers and the held
experts, between a held expert's score and the edge of the top k (the
(k+1)-th score for one inside it, the k-th for one outside).  Where it is
small the routing of this chip's experts is itself a near-tie, and a
comparison of logits there compares two different sets of experts.
"""

import functools

import jax
import jax.numpy as jnp

from .weights_pangu import STACKS, held

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _round_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _lowered(x, lower, axis):
    if lower is None:
        return x
    if lower == "int8":
        return _round_int8(x, axis)
    if lower == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    raise ValueError(f"unknown lower precision {lower!r}")


def _matmul(x, w, lower):
    """x (..., K) float32 times w (K, N), upcast here."""
    return jnp.matmul(_lowered(x, lower, -1), _lowered(w.astype(F32), lower, 0),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """Rotate-half over the last axis of x (L, ..., D) at positions (L,)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _gated(m, gate, up, down, lower):
    return _matmul(jax.nn.silu(_matmul(m, gate, lower))
                   * _matmul(m, up, lower), down, lower)


def _by_rows(fn, x, block):
    """``fn`` over blocks of ``block`` rows of x (a tree of (L, ...)
    arrays), the results joined again."""
    split = lambda a: a.reshape((-1, block) + a.shape[1:])
    join = lambda o: o.reshape((-1,) + o.shape[2:])
    return jax.tree.map(join, jax.lax.map(fn, jax.tree.map(split, x)))


def _attention(cfg, sl, x, lower, block, head_group):
    """W_o(non-absorbed causal MLA of N1(x)) for x (L, H): (L, H).  A
    group of heads at a time (their keys and values are made from the
    latents once), and inside it a block of query rows at a time."""
    L, H = x.shape
    nh, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hg = min(head_group, nh)
    G, pos = nh // hg, jnp.arange(L)

    def project(xb):
        a = _rms(xb, sl["ln1_w"], eps)
        return (_rms(_matmul(a, sl["q_a_w"], lower), sl["q_a_norm_w"], eps),
                _matmul(a, sl["kv_a_w"], lower))
    c_q, kv = _by_rows(project, x, block)           # (L, Rq), (L, R + rope)
    c_kv = _rms(kv[:, :R], sl["kv_a_norm_w"], eps)
    k_r = _rope(kv[:, R:], pos, theta)
    scale = (nope + rope) ** -0.5
    by_group = lambda w, rows, per: jnp.moveaxis(
        w.reshape(rows, G, hg * per), 1, 0)

    def group(acc, ws):
        q_b, kv_b, o_w = ws
        q = _matmul(c_q, q_b, lower).reshape(L, hg, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], pos, theta)], -1)
        kvb = _matmul(c_kv, kv_b, lower).reshape(L, hg, nope + v)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_r[:, None, :], (L, hg, rope))], -1)
        val = kvb[..., nope:]
        q, k, val = (_lowered(t, lower, -1) for t in (q, k, val))

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
            at = start + jnp.arange(block)
            s = jnp.where(pos[None, :] <= at[:, None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              val, precision=HIGHEST)

        o = jax.lax.map(rows, jnp.arange(0, L, block)).reshape(L, hg * v)
        return acc + _matmul(o, o_w, lower), None

    acc, _ = jax.lax.scan(group, jnp.zeros((L, H), F32), (
        by_group(sl["q_b_w"], cfg["q_lora_rank"], nope + rope),
        by_group(sl["kv_b_w"], R, nope + v),
        sl["o_w"].reshape(G, hg * v, H)))
    return acc


def _experts(cfg, sl, m, lower):
    """(the held experts' weighted sum + the shared expert, the route
    margin of the held experts) of m (rows, H)."""
    k = cfg["num_experts_per_tok"]
    first, stop = held(cfg)
    s = jax.nn.sigmoid(_matmul(m, sl["router_w"], lower))
    top, idx = jax.lax.top_k(s, k + 1)
    # how far the nearest HELD expert lies from the edge of the top k: one
    # inside it from the first score left out, one outside it from the last
    # score taken.  Below that distance a rounding of the scores changes
    # which of this chip's experts run; any other near-tie swaps two
    # experts that both run elsewhere, and moves nothing here
    mine = s[:, first:stop]
    inside = mine >= top[:, k - 1:k]
    margin = jnp.where(inside, mine - top[:, k:k + 1],
                       top[:, k - 1:k] - mine).min(-1)
    top, idx = top[:, :k], idx[:, :k]
    w = top * cfg["routed_scaling_factor"] / (
        top.sum(-1, keepdims=True) + 1e-20
        if cfg.get("norm_topk_prob", True) else 1.0)

    def one(acc, xs):
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)          # (rows,)
        return acc + w_e[:, None] * _gated(m, gate, up, down, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(first, stop), sl["e_gate_w"], sl["e_up_w"],
         sl["e_down_w"]))
    shared = _gated(m, sl["s_gate_w"], sl["s_up_w"], sl["s_down_w"], lower)
    return routed + shared, margin


def _block(cfg, lower, block, head_group, expert, x, sl):
    eps = cfg["rms_norm_eps"]
    att = _attention(cfg, sl, x, lower, block, head_group)

    def rest(xa):
        xb, ab = xa
        xb = xb + _rms(ab, sl["ln2_w"], eps)
        m = _rms(xb, sl["ln3_w"], eps)
        if expert:
            f, margin = _experts(cfg, sl, m, lower)
        else:
            f = _gated(m, sl["gate_w"], sl["up_w"], sl["down_w"], lower)
            margin = jnp.full((xb.shape[0],), jnp.inf, F32)
        return xb + _rms(f, sl["ln4_w"], eps), margin

    return _by_rows(rest, (x, att), block)


def hidden(cfg, params, ids, lower=None, block=512, head_group=16):
    """(final hidden states (L, H) after the last norm, route margin (L,))
    of one sequence ``ids`` (L,); L a multiple of ``block``."""
    x = params["wte"][ids].astype(F32)
    margin = jnp.full((ids.shape[0],), jnp.inf, F32)
    for stack in ("dense", "moe"):
        stacked = {n: params[f"{stack}_{n}"] for n in STACKS[stack]}
        layer = functools.partial(_block, cfg, lower, block, head_group,
                                  stack == "moe")
        x, margins = jax.lax.scan(layer, x, stacked)
        margin = jnp.minimum(margin, margins.min(0, initial=jnp.inf))
    return _rms(x, params["norm_f_w"], cfg["rms_norm_eps"]), margin


def logits(cfg, params, ids, lower=None, block=512, head_group=16):
    """float32 logits (L, V) through the untied head, and the margin."""
    h, margin = hidden(cfg, params, ids, lower, block, head_group)
    return _matmul(h, params["lm_head"], lower), margin
