"""The plain reference: GPT-2's forward pass, loss, gradients and AdamW in
straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")``.  No kernel, no cache, no batching trick; it imports nothing
of the program.  Published description: Radford et al. 2019 (pre-LayerNorm
blocks, learned positions, tied output head); AdamW as Loshchilov & Hutter
2019.

Departures, each to make it fit beside nothing but its own weights: the
layers run under ``lax.scan`` over the stacked weights (one block compiled,
each layer's weights upcast as it is reached), and the training loss is
taken over blocks of rows whose gradients are summed, each block's
activations recomputed in its backward pass.

``lower`` names the control of ``correct``: the same mathematics in int8,
the precision below bfloat16 that a later change might be tempted by.
``"int8"`` (serving) rounds the operands of every matrix product to 255
levels, one scale per activation row and per weight column, as W8A8
serving does.  ``"int8_train"`` rounds with one scale per tensor and rounds
the output gradients of every product too, as a first int8 training step
would; with finer scales the rounding errors average out of every norm
compared and int8 reads closer to float32 than bfloat16 does (PERF.md).
"""

import functools

import jax
import jax.numpy as jnp

from .weights import gpt_dims

HIGHEST = jax.lax.Precision.HIGHEST


def _round_int8(x, axis):
    """x rounded to 255 levels, one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _round_tensor(x):
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _matmul_int8_train(x, w):
    return jnp.matmul(_round_tensor(x), _round_tensor(w), precision=HIGHEST)


def _mm_fwd(x, w):
    return _matmul_int8_train(x, w), (_round_tensor(x), _round_tensor(w))


def _mm_bwd(saved, dy):
    x, w = saved
    dy = _round_tensor(dy)
    dx = jnp.matmul(dy, w.T, precision=HIGHEST)
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    return dx, jnp.matmul(x2.T, dy2, precision=HIGHEST)


_matmul_int8_train.defvjp(_mm_fwd, _mm_bwd)


def _matmul(x, w, lower):
    if lower == "int8_train":
        return _matmul_int8_train(x, w)
    if lower == "int8":
        x, w = _round_int8(x, -1), _round_int8(w, 0)
    elif lower == "bfloat16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif lower is not None:
        raise ValueError(f"unknown lower precision {lower!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu(x, kind):
    if kind == "gelu_new":      # GPT-2's tanh form
        return 0.5 * x * (1 + jnp.tanh(
            0.7978845608028654 * (x + 0.044715 * x ** 3)))
    if kind == "gelu":
        return 0.5 * x * (1 + jax.lax.erf(x / 1.4142135623730951))
    raise ValueError(f"unknown activation {kind!r}")


def _block(cfg, lower, h, sl):
    """One pre-LayerNorm block on h (B, L, H); ``sl`` this layer's
    weights, upcast here."""
    _, H, nh, _, _, _ = gpt_dims(cfg)
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    sl = {k: v.astype(jnp.float32) for k, v in sl.items()}
    B, L, _ = h.shape
    hd = H // nh
    a = _layer_norm(h, sl["blocks_ln1_w"], sl["blocks_ln1_b"], eps)
    qkv = _matmul(a, sl["blocks_qkv_w"], lower) + sl["blocks_qkv_b"]
    q, k, v = (t.reshape(B, L, nh, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    if lower == "int8":
        q, k, v = (_round_int8(t, -1) for t in (q, k, v))
    elif lower == "int8_train":     # gradients pass straight through
        q, k, v = (t + jax.lax.stop_gradient(_round_tensor(t) - t)
                   for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST)
    att = att.transpose(0, 2, 1, 3).reshape(B, L, H)
    h = h + _matmul(att, sl["blocks_proj_w"], lower) + sl["blocks_proj_b"]
    m = _layer_norm(h, sl["blocks_ln2_w"], sl["blocks_ln2_b"], eps)
    f = _gelu(_matmul(m, sl["blocks_fc1_w"], lower) + sl["blocks_fc1_b"],
              cfg.get("activation_function", "gelu_new"))
    return h + _matmul(f, sl["blocks_fc2_w"], lower) + sl["blocks_fc2_b"]


def hidden(cfg, params, ids, lower=None, remat=False):
    """Final hidden states (B, L, H), after the last LayerNorm."""
    L = ids.shape[-1]
    wte = params["wte"].astype(jnp.float32)
    h = wte[ids] + params["wpe"].astype(jnp.float32)[:L]
    stacked = {k: v for k, v in params.items() if k.startswith("blocks_")}
    block = functools.partial(_block, cfg, lower)
    if remat:
        block = jax.checkpoint(block)
    h, _ = jax.lax.scan(lambda c, sl: (block(c, sl), None), h, stacked)
    return _layer_norm(h, params["lnf_w"].astype(jnp.float32),
                       params["lnf_b"].astype(jnp.float32),
                       cfg.get("layer_norm_epsilon", 1e-5))


def logits(cfg, params, ids, lower=None):
    """float32 logits (B, L, V); the output head is the embedding."""
    h = hidden(cfg, params, ids, lower)
    return _matmul(h, params["wte"].astype(jnp.float32).T, lower)


def loss_sum(cfg, params, ids, labels, lower=None):
    """Sum over positions of the next-token cross-entropy (labels given,
    as the program is given them)."""
    h = hidden(cfg, params, ids, lower, remat=True)
    lg = _matmul(h, params["wte"].astype(jnp.float32).T, lower)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


def loss_and_grads(cfg, params, ids, labels, rows_per_block, lower=None,
                   grad_shardings=None):
    """Mean loss over the batch and its gradients, a block of rows at a
    time; ``grad_shardings`` lays the gradients out as the weights are."""
    fn = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg, lower=lower)),
        out_shardings=None if grad_shardings is None
        else (None, grad_shardings))
    total, grads = 0.0, None
    for at in range(0, ids.shape[0], rows_per_block):
        loss, g = fn(params, ids[at:at + rows_per_block],
                     labels[at:at + rows_per_block])
        total = total + loss
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = ids.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


@functools.partial(jax.jit, static_argnames=("lr", "wd", "b1", "b2", "eps"),
                   donate_argnums=(0, 1, 2))
def adamw(params, m, v, grads, t, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    """One step of AdamW with decoupled decay on every parameter, in the
    form of Paddle's ``adamw`` operator, which is what the program states
    it implements: the bias corrections fold into the step size, so
    epsilon is added to the uncorrected sqrt(v)."""
    def one(p, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        return p * (1 - lr * wd) - lr_t * m / (jnp.sqrt(v) + eps), m, v
    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)
