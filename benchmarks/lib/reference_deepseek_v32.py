"""The plain reference of one chip's share of DeepSeek-V3.2-Exp: the forward
pass in straightforward ``jax.numpy``, float32, every product at
``Precision.HIGHEST``.  No cache, no kernel, no absorbed form, no
threshold search, its own routing; it imports nothing of the program.

Published description: the model's ``config.json`` (``model_type:
deepseek_v32``); the lightning indexer and the top-k selection as the
DeepSeek-V3.2-Exp report's DSA section and the release's
``inference/model.py``; the router as DeepSeek-V3 (arXiv:2412.19437
§2.1.2); multi-head latent attention as DeepSeek-V2 (arXiv:2405.04434
§2.1); the rotary scaling as YaRN (arXiv:2309.00071).

    x = x + MLA(N(x));  x = x + F(N(x))              (RMSNorm, eps 1e-6)
    MLA: c_q = N(a W_qa); q = c_q W_qb -> heads of [nope ; rope]
         [c_kv ; k_r] = a W_kva; c_kv = N(c_kv); rope on k_r and q_r
         (rotate-half, YaRN frequencies); [k_nope ; v] = c_kv W_kvb
         score = (q_nope . k_nope + q_r . k_r) * (nope + rope)^-1/2 * m^2,
         m = 0.1 * mscale_all_dim * ln(factor) + 1;
         softmax over the SELECTED positions S_t only; o = sum p v; W_o
    indexer (one a layer): q^I = c_q W^I_qb -> nhi heads of Di;
         k^I = LayerNorm(a W^I_k) (scale and bias, eps 1e-6), one key a
         position; rope on the first ``qk_rope_head_dim`` columns of both;
         w = a W^I_w * nhi^-1/2 * Di^-1/2 (signed);
         I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]),  s <= t;
         S_t = the min(index_topk, t + 1) positions of largest I[t, s], a
         tie to the lower s (``lax.top_k``'s order)
    F dense:  W_down(silu(W_gate m) * (W_up m))
    F expert: s = sigmoid(m W_g) over ``router_width``; c = s + bias; a
         group (router_width / n_group consecutive experts) scores the sum
         of its two largest c; the ``topk_group`` best groups stay; the k
         largest c among their experts are chosen; w = s / (sum s + 1e-20)
         * routed_scaling_factor, from s; sum over the HELD experts of
         w_e E_e(m), + E_shared(m)

Departures from the release, each stated in the configuration file too:
the Hadamard rotation of q^I and k^I is left out (it leaves every dot
product as it was); the release's FP8 indexer is float32 here (the
configuration states bfloat16: v5e has no FP8); the multi-token-prediction
module is not built (it does not enter the main model's logits).

The share: only the experts ``experts_held`` are summed; what the others
would add is left out, here as in the program.

Departures to make it fit beside the bfloat16 weights on one chip, as
``reference_pangu_moe``: the layers of a stack run under ``lax.scan``;
attention is taken a group of heads and, inside it, a block of query rows
at a time, the index scores a block of rows and a group of indexer heads
at a time, everything row-wise a block of rows at a time (the MLP's rows
written back where they were read, the attention's output added onto the
residual stream where it lies: one (L, H) buffer); the held experts are
applied one after another, each to the rows of a block that chose it and
to no other.  A layer's selection is kept between the two as a bit-packed
mask (L, L / 8).  The rows are cut into ``segments`` equal runs and a
run's rows are given the keys up to the run's end only (the later keys
are masked for every one of its rows anyway): the same numbers for about
5/8 of the products.

``lower`` names the control of ``correct`` ("int8" | "bfloat16", as
``reference_pangu_moe``), applied to the indexer's products too.
``select`` names two more: "dense" attends every position (selection
off), "recent" the most recent ``index_topk`` — a program that skips the
selection, or selects by position, must read as not correct.

Beside the hidden states the reference returns, per position, the route
margin: how far the routing of THIS chip's experts is from changing, the
smallest over the expert layers of (the gap between the last group kept
and the first dropped, the held experts' distance in ``c`` from the edge
of the top k among the kept groups); and, for a window of rows, each
layer's selection as a mask.
"""

import functools
import math

import jax
import jax.numpy as jnp

from .weights_dsv32 import STACKS, held

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


def _layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def yarn_inv_freq(cfg):
    """The rotary frequencies over the ``qk_rope_head_dim`` columns: the
    plain ``theta^(-2j/D)`` and the same over ``factor``, blended per
    frequency by the linear ramp between the two correction dims."""
    D, theta, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        cfg["rope_scaling"]

    def correction_dim(rotations):
        return D * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), D - 1)
    j = jnp.arange(D // 2, dtype=F32)
    f = theta ** (-2.0 * j / D)
    ramp = jnp.clip((j - low) / ((high - low) or 0.001), 0.0, 1.0)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, inv):
    """Rotate-half over the last axis of x (L, ..., D) at positions (L,)."""
    D = x.shape[-1]
    ang = pos.astype(F32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _gated(m, gate, up, down, lower):
    return _matmul(jax.nn.silu(_matmul(m, gate, lower))
                   * _matmul(m, up, lower), down, lower)


class _Layer:
    """One layer of a stack's parameters: ``sl[name]`` takes that layer's
    slice of the stacked leaf where it is used (as the ``xs`` of the layer
    scan, a whole layer's weights would be copied out, 1.9 GB, for the
    layer's whole run)."""

    def __init__(self, stacked, i):
        self.stacked, self.i = stacked, i

    def __getitem__(self, name):
        return jax.lax.dynamic_index_in_dim(self.stacked[name], self.i, 0,
                                            keepdims=False)


def _runs(L, segments, block):
    """[(first row, rows, keys)] of the runs the rows are cut into."""
    n = segments if L % (segments * block) == 0 else 1
    return [(j * (L // n), L // n, (j + 1) * (L // n)) for j in range(n)]


def _index_rows(cfg, sl, a, pos, lower):
    """The indexer's row-wise side of a block of rows: its one key a
    position (rows, Di) and its signed head weights (rows, nhi)."""
    nhi, Di, Dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                   cfg["qk_rope_head_dim"])
    inv = yarn_inv_freq(cfg)
    key = _layer_norm(_matmul(a, sl["idx_k_w"], lower),
                      sl["idx_k_norm_w"], sl["idx_k_norm_b"])
    key = jnp.concatenate([_rope(key[:, :Dr], pos, inv), key[:, Dr:]], -1)
    return key, _matmul(a, sl["idx_w_w"], lower) * (nhi * Di) ** -0.5


def _selection(cfg, sl, c_q, key, w, lower, block, segments, select):
    """Each position's selected keys as a bit-packed mask (L, L / 8):
    the indexer's top ``index_topk`` (or a control's choice)."""
    L = c_q.shape[0]
    k = cfg["index_topk"]
    nhi, Di, Dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                   cfg["qk_rope_head_dim"])
    inv = yarn_inv_freq(cfg)
    hg = min(8, nhi)
    key = _lowered(key, lower, -1)
    packed = []
    for first, rows, keys in _runs(L, segments, block):
        def chosen(start, keys=keys):
            at = start + jnp.arange(block)
            col = jnp.arange(keys)
            causal = col[None, :] <= at[:, None]
            if select != "indexer":         # a control: chosen by position
                back = keys if select == "dense" else k
                sel = causal & (col[None, :] > at[:, None] - back)
                return jnp.packbits(jnp.pad(sel, ((0, 0), (0, L - keys))),
                                    axis=-1)
            qb = _matmul(jax.lax.dynamic_slice_in_dim(c_q, start, block, 0),
                         sl["idx_q_b_w"], lower).reshape(block, nhi, Di)
            qb = _lowered(jnp.concatenate(
                [_rope(qb[..., :Dr], at, inv), qb[..., Dr:]], -1), lower, -1)
            wb = jax.lax.dynamic_slice_in_dim(w, start, block, 0)

            def heads(acc, g):
                qg = jax.lax.dynamic_slice_in_dim(qb, g * hg, hg, 1)
                wg = jax.lax.dynamic_slice_in_dim(wb, g * hg, hg, 1)
                s = jnp.einsum("qhd,kd->qhk", qg, key[:keys],
                               precision=HIGHEST)
                return acc + jnp.sum(jax.nn.relu(s) * wg[:, :, None], 1), None
            scores, _ = jax.lax.scan(heads, jnp.zeros((block, keys), F32),
                                     jnp.arange(nhi // hg))
            scores = jnp.where(causal, scores, -jnp.inf)
            # the k largest, a tie to the lower position: top_k lists
            # equal values by rising position, so of the values equal to
            # the k-th it took those up to the last it lists
            top, idx = jax.lax.top_k(scores, min(k, keys))
            kth = top[:, -1:]
            last = jnp.max(jnp.where(top == kth, idx, -1), -1, keepdims=True)
            sel = (scores > kth) | ((scores == kth) & (col[None, :] <= last))
            return jnp.packbits(jnp.pad(sel & causal,
                                        ((0, 0), (0, L - keys))), axis=-1)

        packed.append(jax.lax.map(
            chosen, first + jnp.arange(0, rows, block)).reshape(rows, -1))
    return jnp.concatenate(packed)


def _attention(cfg, sl, x, lower, block, head_group, segments, select):
    """(x + W_o(non-absorbed causal MLA of N(x) over the selected
    positions) for x (L, H): (L, H), the selection bit-packed (L, L / 8))."""
    L, H = x.shape
    nh, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    inv = yarn_inv_freq(cfg)
    hg = min(head_group, nh)
    G, pos = nh // hg, jnp.arange(L)

    def project(xs):
        xb, pb = xs
        a = _rms(xb, sl["ln1_w"], eps)
        return (_rms(_matmul(a, sl["q_a_w"], lower), sl["q_a_norm_w"], eps),
                _matmul(a, sl["kv_a_w"], lower),
                *_index_rows(cfg, sl, a, pb, lower))
    c_q, kv, key, w = jax.tree.map(
        lambda o: o.reshape((L,) + o.shape[2:]), jax.lax.map(
            lambda i: project((jax.lax.dynamic_slice_in_dim(x, i, block, 0),
                               i + jnp.arange(block))),
            jnp.arange(0, L, block)))
    packed = _selection(cfg, sl, c_q, key, w, lower, block, segments, select)
    del key, w
    c_kv = _rms(kv[:, :R], sl["kv_a_norm_w"], eps)
    k_r = _rope(kv[:, R:], pos, inv)
    scale = softmax_scale(cfg)
    by_group = lambda w, rows, per: jnp.moveaxis(
        w.reshape(rows, G, hg * per), 1, 0)

    def group(acc, ws):
        q_b, kv_b, o_w = ws
        q = _matmul(c_q, q_b, lower).reshape(L, hg, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], pos, inv)], -1)
        kvb = _matmul(c_kv, kv_b, lower).reshape(L, hg, nope + v)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            k_r[:, None, :], (L, hg, rope))], -1)
        val = kvb[..., nope:]
        q, k, val = (_lowered(t, lower, -1) for t in (q, k, val))
        for first, rows, keys in _runs(L, segments, block):
            kk, vv = k[:keys], val[:keys]

            def some(i, acc, kk=kk, vv=vv, keys=keys, first=first):
                start = first + i * block
                qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
                s = jnp.einsum("qhd,khd->hqk", qb, kk,
                               precision=HIGHEST) * scale
                sel = jnp.unpackbits(jax.lax.dynamic_slice_in_dim(
                    packed, start, block, 0), axis=-1)[:, :keys] != 0
                s = jnp.where(sel[None], s, -jnp.inf)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                               vv, precision=HIGHEST).reshape(block, hg * v)
                # this group's part of W_o, added where the rows lie
                return jax.lax.dynamic_update_slice_in_dim(
                    acc, jax.lax.dynamic_slice_in_dim(acc, start, block, 0)
                    + _matmul(o, o_w, lower), start, 0)

            acc = jax.lax.fori_loop(0, rows // block, some, acc)
        return acc, None

    # the groups' outputs are added onto the residual stream itself: the
    # block's x + MLA(N(x)) with one (L, H) buffer and not three
    acc, _ = jax.lax.scan(group, x, (
        by_group(sl["q_b_w"], cfg["q_lora_rank"], nope + rope),
        by_group(sl["kv_b_w"], R, nope + v),
        sl["o_w"].reshape(G, hg * v, H)))
    return acc, packed


def route(cfg, s, bias):
    """DeepSeek-V3's choice: (idx (rows, k), weights (rows, k), the
    biased scores with the dropped groups at -inf, the gap between the
    last group kept and the first dropped)."""
    k, ng, kg = (cfg["num_experts_per_tok"], cfg["n_group"],
                 cfg["topk_group"])
    rows, E = s.shape
    c = s + bias.astype(F32)
    groups = c.reshape(rows, ng, E // ng)
    score = jax.lax.top_k(groups, 2)[0].sum(-1)                 # (rows, ng)
    best, which = jax.lax.top_k(score, min(kg + 1, ng))
    kept = (which[:, :kg, None] == jnp.arange(ng)).any(1)       # (rows, ng)
    gap = best[:, kg - 1] - best[:, kg] if ng > kg \
        else jnp.full((rows,), jnp.inf, F32)
    c = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(rows, E)
    idx = jax.lax.top_k(c, k)[1]
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = top * cfg["routed_scaling_factor"] / (
        top.sum(-1, keepdims=True) + 1e-20
        if cfg.get("norm_topk_prob", True) else 1.0)
    return idx, w, c, gap


def _experts(cfg, sl, m, lower):
    """(the held experts' weighted sum + the shared expert, the route
    margin of the held experts) of m (rows, H)."""
    k = cfg["num_experts_per_tok"]
    first, stop = held(cfg)
    s = jax.nn.sigmoid(_matmul(m, sl["router_w"], lower))
    idx, w, c, gap = route(cfg, s, sl["router_bias"])
    # how far the nearest HELD expert lies from the edge of the top k, in
    # the biased scores among the kept groups: one inside it from the
    # first score left out, one outside it from the last score taken (a
    # held expert of a dropped group is infinitely far: only the groups'
    # gap can bring it back)
    top = jax.lax.top_k(c, k + 1)[0]
    mine = c[:, first:stop]
    inside = mine >= top[:, k - 1:k]
    margin = jnp.where(inside, mine - top[:, k:k + 1],
                       top[:, k - 1:k] - mine).min(-1)
    margin = jnp.minimum(margin, gap)

    def one(acc, j):
        # a held expert is applied to the rows that chose it and to no
        # other: those rows first, then ``some`` rows at a time as often
        # as it takes to pass them (of the last ones some did not choose
        # it: their weight is 0)
        took = idx == first + j
        w_e = jnp.sum(jnp.where(took, w, 0.0), -1)              # (rows,)
        rows = jnp.argsort(~took.any(-1), stable=True)
        n = jnp.sum(took)                   # a row chooses it once at most
        ws = [sl[name][j] for name in ("e_gate_w", "e_up_w", "e_down_w")]

        def chunk(i, acc):
            at = jax.lax.dynamic_slice_in_dim(rows, i * some, some)
            return acc.at[at].add(w_e[at][:, None]
                                  * _gated(m[at], *ws, lower))

        return jax.lax.fori_loop(0, -(-n // some), chunk, acc), None

    some = math.gcd(128, m.shape[0])
    routed, _ = jax.lax.scan(one, jnp.zeros_like(m),
                             jnp.arange(stop - first))
    shared = _gated(m, sl["s_gate_w"], sl["s_up_w"], sl["s_down_w"], lower)
    return routed + shared, margin


def _block(cfg, lower, block, head_group, segments, select, window, expert,
           x, sl):
    eps = cfg["rms_norm_eps"]
    x, packed = _attention(cfg, sl, x, lower, block, head_group, segments,
                           select)

    def rest(i, xm):
        x, margins = xm
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)
        m = _rms(xb, sl["ln3_w"], eps)
        if expert:
            f, margin = _experts(cfg, sl, m, lower)
        else:
            f = _gated(m, sl["gate_w"], sl["up_w"], sl["down_w"], lower)
            margin = jnp.full((block,), jnp.inf, F32)
        return (jax.lax.dynamic_update_slice_in_dim(x, xb + f, i * block, 0),
                jax.lax.dynamic_update_slice_in_dim(margins, margin,
                                                    i * block, 0))

    # a block of rows at a time, written back where it was read; an expert
    # layer eight blocks at a time where they divide the rows: the more
    # rows at once, the fewer times a held expert's weights are read for
    # the few rows that chose it
    if expert and x.shape[0] % (8 * block) == 0:
        block *= 8
    x, margin = jax.lax.fori_loop(
        0, x.shape[0] // block, rest,
        (x, jnp.zeros((x.shape[0],), F32)))
    start, n = window
    chosen = jnp.unpackbits(jax.lax.dynamic_slice_in_dim(
        packed, start, n, 0), axis=-1)[:, :x.shape[0]] != 0
    return x, (margin, chosen)


def hidden(cfg, params, ids, lower=None, block=256, head_group=4,
           segments=4, select="indexer", window=None):
    """Of one sequence ``ids`` (L,), for its rows ``window = (start, n)``
    (``start`` may be traced; all of them by default): the final hidden
    states (n, H) after the last norm, the route margin (n,), and each
    layer's selection, bool (layers, n, L).  L a multiple of ``block`` (of
    ``segments * block`` for the rows to be cut into runs)."""
    window = window or (0, ids.shape[0])
    x = params["wte"][ids].astype(F32)
    margin = jnp.full((ids.shape[0],), jnp.inf, F32)
    chosen = []
    for stack in ("dense", "moe"):
        stacked = {n: params[f"{stack}_{n}"] for n in STACKS[stack]}
        layer = functools.partial(_block, cfg, lower, block, head_group,
                                  segments, select, window, stack == "moe")
        x, (margins, sel) = jax.lax.scan(
            lambda x, i: layer(x, _Layer(stacked, i)), x,
            jnp.arange(stacked["ln1_w"].shape[0]))
        margin = jnp.minimum(margin, margins.min(0, initial=jnp.inf))
        chosen.append(sel)
    rows = lambda t: jax.lax.dynamic_slice_in_dim(t, *window, axis=0)
    return _rms(rows(x), params["norm_f_w"], cfg["rms_norm_eps"]), \
        rows(margin), jnp.concatenate(chosen)


def logits(cfg, params, ids, lower=None, **kw):
    """float32 logits (n, V) of the window's rows through the untied
    head, their margin and their selection."""
    h, margin, chosen = hidden(cfg, params, ids, lower, **kw)
    return _matmul(h, params["lm_head"], lower), margin, chosen
