"""Multi-head latent attention (DeepSeek-V2 §2.1) as the models that use it
share it: the projections of a row, the absorbed and the dense-cache
attention round them, the output projection, the rotary frequencies.

``models/pangu_moe.py`` (openPangu-Ultra-MoE, DeepSeek-V3.2-Exp: one MLA
a layer) and ``models/longcat_flash.py`` (LongCat-Flash: two a layer, and
two scale factors on the low-rank projections) call these; each takes the
model's configuration ``c`` — any object with the published MLA keys as
attributes (``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rms_norm_eps``, ``rope_theta``; ``rope_scaling`` and ``sandwich_norm``
where the family has them) and ``MlaGeometry``'s two widths — and the
sublayer's parameters ``sl`` under the names ``ln1_w``, ``q_a_w``,
``q_a_norm_w``, ``q_b_w``, ``kv_a_w``, ``kv_a_norm_w``, ``kv_b_w``,
``o_w`` (``ln2_w`` under ``sandwich_norm``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ._decode import rms_norm, rope_rotate_half


def yarn_inv_freq(D, theta, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1, **_):
    """The ``D / 2`` rotary frequencies under YaRN (arXiv:2309.00071, as
    DeepSeek's ``precompute_freqs_cis`` writes it): frequency ``j`` is the
    blend ``f_j / factor * r_j + f_j * (1 - r_j)`` of the interpolated and
    the plain ``f_j = theta ** (-2j / D)``, ``r`` the linear ramp from 0
    at the correction dim of ``beta_fast`` rotations over the original
    context (rounded down) to 1 at that of ``beta_slow`` (rounded up)."""
    def correction_dim(rotations):
        return D * math.log(original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), D - 1)
    if low == high:
        high += 0.001
    f = [theta ** (-2.0 * j / D) for j in range(D // 2)]
    ramp = [min(max((j - low) / (high - low), 0.0), 1.0)
            for j in range(D // 2)]
    return [fj / factor * r + fj * (1.0 - r) for fj, r in zip(f, ramp)]


def yarn_mscale(factor, mscale_all_dim=1.0, **_):
    """``m = 0.1 * mscale_all_dim * ln(factor) + 1``: the softmax scale is
    multiplied by ``m**2``."""
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 \
        else 1.0


class MlaGeometry:
    """What a cached row is, from the published ranks: mixed into the
    configuration classes of the MLA models."""

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self):
        """Columns of a cached row: ``latent_width`` and zeros up to the
        next multiple of 128.  A tiled device layout pads a row to whole
        128-lane tiles whatever its logical width; stating the padded
        width keeps the pool's default layout row-major, which is the
        layout the kernel's block DMAs need (at the logical 576 the
        compiler stores the pool block-minor and transposes the whole of
        it in and out of every kernel call)."""
        return -(-self.latent_width // 128) * 128


def mla_rope(c, x, pos):
    """Rotate-half rotary positions over the last axis of x (..., D)
    at positions ``pos`` (broadcast against x's leading axes but the
    last two: x is (..., heads, D) and pos (...,))."""
    D = x.shape[-1]
    scaling = getattr(c, "rope_scaling", None)
    if scaling is None:
        inv = c.rope_theta ** (
            -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    else:
        inv = jnp.asarray(yarn_inv_freq(D, c.rope_theta, **scaling),
                          jnp.float32)
    return rope_rotate_half(x, pos, inv)


def mla_in(c, sl, x, pos, q_scale=1.0, kv_scale=1.0, lora_eps=None):
    """N1 and the MLA projections of x (..., H) at logical positions
    ``pos`` (...,): q_nope (..., nh, nope), q_r (..., nh, rope) after
    rotation, and the row to cache (..., latent_row): c_kv, k_r, zeros;
    then the two things an indexer projects from, the normed input ``a``
    and the query's latent ``c_q``.

    ``q_scale`` multiplies the whole query (both parts) and ``kv_scale``
    the normed latent ``c_kv``, never ``k_r`` (LongCat-Flash's
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora``): the scaled ``c_kv`` is
    what the cache holds, so the absorbed form needs no further factor.
    ``lora_eps`` is the epsilon of the two low-rank norms where it is not
    the model's ``rms_norm_eps``.  At 1.0 / None the program is the one
    without them, to the bit."""
    dt = x.dtype
    nh, R = c.num_attention_heads, c.kv_lora_rank
    eps = c.rms_norm_eps if lora_eps is None else lora_eps
    a = rms_norm(x, sl["ln1_w"], c.rms_norm_eps)
    c_q = rms_norm(a @ sl["q_a_w"].astype(dt), sl["q_a_norm_w"], eps)
    q = (c_q @ sl["q_b_w"].astype(dt)).reshape(
        x.shape[:-1] + (nh, c.qk_nope_head_dim + c.qk_rope_head_dim))
    if q_scale != 1.0:
        q = q * jnp.asarray(q_scale, dt)
    q_nope, q_r = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    kv = a @ sl["kv_a_w"].astype(dt)
    kv_norm_w = sl["kv_a_norm_w"]
    if kv_scale != 1.0:     # inside the norm's float32: one rounding
        kv_norm_w = kv_norm_w.astype(jnp.float32) * kv_scale
    c_kv = rms_norm(kv[..., :R], kv_norm_w, eps)
    k_r = mla_rope(c, kv[..., None, R:], pos)[..., 0, :]
    pad = jnp.zeros(x.shape[:-1] + (c.latent_row - c.latent_width,), dt)
    return (q_nope, mla_rope(c, q_r, pos),
            jnp.concatenate([c_kv, k_r, pad], -1), a, c_q)


def mla_kv_b(c, sl, dt):
    """W_kvb as (R, nh, nope) for keys and (R, nh, v) for values."""
    w = sl["kv_b_w"].astype(dt).reshape(
        c.kv_lora_rank, c.num_attention_heads,
        c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def mla_softmax_scale(c):
    """``(nope + rope) ** -0.5``, times YaRN's ``m ** 2`` where the
    configuration scales its rotary frequencies."""
    scale = float(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    scaling = getattr(c, "rope_scaling", None)
    if scaling is not None:
        scale *= yarn_mscale(**scaling) ** 2
    return scale


def mla_out(c, sl, x, o):
    """Heads concatenated, W_o, N2 (under ``sandwich_norm``),
    residual: o (..., nh, v)."""
    o = o.reshape(o.shape[:-2] + (-1,)) @ sl["o_w"].astype(x.dtype)
    return x + (rms_norm(o, sl["ln2_w"], c.rms_norm_eps)
                if getattr(c, "sandwich_norm", False) else o)


def mla_attend_dense(c, sl, x, cache, q_nope, q_r, t0, pad_lens,
                     chosen=None):
    """Absorbed attention of x's rows (B, k, ...) at cache slots
    [t0, t0 + k) over a dense latent cache (B, Lmax, R + rope);
    ``chosen`` (B, k, Lmax) bool narrows each row's keys."""
    R, W = c.kv_lora_rank, c.latent_width
    w_k, w_v = mla_kv_b(c, sl, x.dtype)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k)
    sc = jnp.einsum("bqhr,bkr->bhqk", q_abs, cache[..., :R],
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("bqhd,bkd->bhqk", q_r, cache[..., R:W],
                     preferred_element_type=jnp.float32)
    k = jnp.arange(cache.shape[1])
    mask = k[None, None, :] <= (t0 + jnp.arange(x.shape[1]))[None, :, None]
    mask = mask & (k[None, None, :] >= pad_lens[:, None, None])
    if chosen is not None:
        mask = mask & chosen
    sc = jnp.where(mask[:, None], sc * mla_softmax_scale(c), -1e30)
    p = jax.nn.softmax(sc, -1).astype(x.dtype)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", p, cache[..., :R])
    return jnp.einsum("bqhr,rhd->bqhd", o_lat, w_v)
