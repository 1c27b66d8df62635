"""The plain reference of one chip's share of LongCat-Flash: the forward
pass from token ids to logits in straightforward ``jax.numpy``, float32,
every product at ``Precision.HIGHEST``.  No cache, no kernel, no absorbed
form, no sorting, its own routing; it imports nothing of the program.

It follows the public implementation, ``transformers``' ``models/
longcat_flash/modeling_longcat_flash.py`` (``LongcatFlashDecoderLayer``,
``LongcatFlashMLA``, ``LongcatFlashTopkRouter``, ``LongcatFlashMoE``), which
``tests/test_longcat_flash.py`` holds it against at a small uncut size:

    layer (j = 0, 1 the layer's sublayers; RMSNorm, eps ``rms_norm_eps``):
      x = x + MLA_0(N_in0(x));  m0 = N_post0(x);  s = MoE(m0)
      x = x + FFN_0(m0)
      x = x + MLA_1(N_in1(x));  m1 = N_post1(x)
      x = x + FFN_1(m1) + s
    MLA: c_q = N(a W_qa); q = (c_q W_qb) * sqrt(H / q_lora_rank) -> heads
         of [nope ; rope];  [c ; k_r] = a W_kva;  c = N(c) * sqrt(H /
         kv_lora_rank) (k_r is not scaled);  [k_nope ; v] = c W_kvb;  rope
         on q_r and k_r;  score = (q . k) / sqrt(nope + rope), causal
         softmax;  heads concatenated, W_o.  The two norms N on the
         low-rank latents have the implementation's default eps
         (``lora_norm_eps``, 1e-6), not ``rms_norm_eps``.
    FFN: W_down(silu(W_gate m) * (W_up m))
    MoE: p = softmax(m W_r) over ``router_width`` = real + zero-compute
         outputs; the ``moe_topk`` largest of p + bias; w = p *
         ``routed_scaling_factor`` (from p, not normalised);
         sum over the HELD real experts of w_e E_e(m)
         + (sum over the chosen zero-compute experts of w_e) * m

The share: only the real experts ``experts_held`` are summed, and every
zero-compute expert (they have no weights: the chip that serves a row
applies them); what the other real experts would add is left out, here as
in the program, and the partial result goes on.

Departures from that file, each also under ``assumed`` in the
configuration file: rotary positions are rotate-half over the 64 rotary
columns where the file de-interleaves them first (``apply_rotary_pos_emb_
interleave``: a fixed permutation of the columns of W_qb's and W_kva's
rotary parts, so under random weights the same model; the test maps one
layout onto the other); ``e_score_correction_bias`` is the parameter
``layers_router_bias`` (zeros unless a test sets it); the router has no
bias of its own (``router_bias`` false) and its weights are not
normalised over the chosen (the file has no ``norm_topk_prob``).  And,
to make it fit beside the bfloat16 weights on one chip, as
``reference_pangu_moe`` does: the layers run under ``lax.scan`` over the
stacked weights; attention is taken a group of heads and, inside it, a
block of query rows at a time, everything row-wise a block of rows at a
time; the held experts are applied one after another to every row of a
block, weighted by zero where the row was not routed to them.

``lower`` names the control of ``correct`` (``"int8"``: the operands of
every product rounded to 255 levels, one scale per activation row and per
weight column; ``"bfloat16"``: rounded to bfloat16), as in
``reference_pangu_moe``, whose primitives these are.

Beside the hidden states the reference returns, per position, the route
margin: the smallest distance, over the layers, between the edge of the
top k and the score (``p + bias``) of any output whose choice changes
THIS chip's sum — a held real expert or any zero-compute expert (the
(k+1)-th score for one inside the top k, the k-th for one outside).
Where it is small the set of experts this chip computes is itself a
near-tie, and a comparison of logits there compares two different sets.
"""

import jax
import jax.numpy as jnp

from .reference_pangu_moe import (F32, HIGHEST, _by_rows, _gated, _lowered,
                                  _matmul, _rms, _rope)
from .weights_longcat import BRANCH, SUBLAYER, held, real_experts

LORA_NORM_EPS = 1e-6


def _attention(cfg, sub, x, lower, block, head_group):
    """W_o(non-absorbed causal MLA of N_in(x)) for x (L, H): (L, H).  A
    group of heads at a time (their keys and values are made from the
    latents once), and inside it a block of query rows at a time."""
    L, H = x.shape
    nh, R, Rq = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                 cfg["q_lora_rank"])
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    lora_eps = cfg.get("lora_norm_eps", LORA_NORM_EPS)
    q_scale = (H / Rq) ** 0.5 if cfg.get("mla_scale_q_lora") else 1.0
    kv_scale = (H / R) ** 0.5 if cfg.get("mla_scale_kv_lora") else 1.0
    hg = min(head_group, nh)
    G, pos = nh // hg, jnp.arange(L)

    def project(xb):
        a = _rms(xb, sub["ln1_w"], eps)
        return (_rms(_matmul(a, sub["q_a_w"], lower), sub["q_a_norm_w"],
                     lora_eps),
                _matmul(a, sub["kv_a_w"], lower))
    c_q, kv = _by_rows(project, x, block)           # (L, Rq), (L, R + rope)
    c_kv = _rms(kv[:, :R], sub["kv_a_norm_w"], lora_eps) * kv_scale
    k_r = _rope(kv[:, R:], pos, theta)              # not scaled
    scale = (nope + rope) ** -0.5
    by_group = lambda w, rows, per: jnp.moveaxis(
        w.reshape(rows, G, hg * per), 1, 0)

    def group(acc, ws):
        q_b, kv_b, o_w = ws
        q = _matmul(c_q, q_b, lower).reshape(L, hg, nope + rope) * q_scale
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
        by_group(sub["q_b_w"], Rq, nope + rope),
        by_group(sub["kv_b_w"], R, nope + v),
        sub["o_w"].reshape(G, hg * v, H)))
    return acc


def _branch(cfg, sl, m, lower):
    """(the held experts' weighted sum + the zero-compute experts' term,
    the route margin) of m (rows, H)."""
    k, n_real = cfg["moe_topk"], real_experts(cfg)
    first, stop = held(cfg)
    p = jax.nn.softmax(_matmul(m, sl["router_w"], lower), axis=-1)
    c = p + sl["router_bias"].astype(F32)           # chosen by p + bias
    top, idx = jax.lax.top_k(c, k + 1)
    # an output's choice changes this chip's sum if it is a held real
    # expert or any zero-compute expert; its distance from the edge of the
    # top k: inside it from the first score left out, outside it from the
    # last score taken.  A near-tie between two absent real experts moves
    # nothing here
    e = jnp.arange(c.shape[-1])
    mine = ((e >= first) & (e < stop)) | (e >= n_real)
    inside = c >= top[:, k - 1:k]
    dist = jnp.where(inside, c - top[:, k:k + 1], top[:, k - 1:k] - c)
    margin = jnp.where(mine, dist, jnp.inf).min(-1)
    idx = idx[:, :k]
    w = jnp.take_along_axis(p, idx, -1) * cfg["routed_scaling_factor"]

    def one(acc, xs):
        i, gate, up, down = xs
        w_e = jnp.sum(jnp.where(idx == i, w, 0.0), -1)          # (rows,)
        return acc + w_e[:, None] * _gated(m, gate, up, down, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (jnp.arange(first, stop), sl["e_gate_w"], sl["e_up_w"],
         sl["e_down_w"]))
    zero = jnp.sum(jnp.where(idx >= n_real, w, 0.0), -1)        # identity
    return routed + zero[:, None] * m, margin


def _layer(cfg, lower, block, head_group, x, sl):
    eps = cfg["rms_norm_eps"]
    s = margin = None
    for j in (0, 1):
        sub = {n: sl[n][j] for n in SUBLAYER}
        att = _attention(cfg, sub, x, lower, block, head_group)

        def rest(args, j=j, sub=sub):
            xb, ab, *sb = args
            xb = xb + ab
            m = _rms(xb, sub["ln3_w"], eps)
            f = _gated(m, sub["gate_w"], sub["up_w"], sub["down_w"], lower)
            if j == 0:      # the shortcut branch leaves here ...
                return (xb + f,) + _branch(cfg, sl, m, lower)
            return xb + f + sb[0]       # ... and lands here

        if j == 0:
            x, s, margin = _by_rows(rest, (x, att), block)
        else:
            x = _by_rows(rest, (x, att, s), block)
    return x, margin


def hidden(cfg, params, ids, lower=None, block=512, head_group=16):
    """(final hidden states (L, H) after the last norm, route margin (L,))
    of one sequence ``ids`` (L,); L a multiple of ``block``."""
    x = params["wte"][ids].astype(F32)
    stacked = {n: params[f"layers_{n}"] for n in SUBLAYER + BRANCH}
    x, margins = jax.lax.scan(
        lambda x, sl: _layer(cfg, lower, block, head_group, x, sl),
        x, stacked)
    return (_rms(x, params["norm_f_w"], cfg["rms_norm_eps"]),
            margins.min(0, initial=jnp.inf))


def logits(cfg, params, ids, lower=None, block=512, head_group=16):
    """float32 logits (L, V) through the untied head, and the margin."""
    h, margin = hidden(cfg, params, ids, lower, block, head_group)
    return _matmul(h, params["lm_head"], lower), margin
