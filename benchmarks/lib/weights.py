"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program as an input;
the plain reference makes its own from the same seed, so it takes nothing
the program has touched.  GPT-2's published initialisation: normal(0,
initializer_range), the two projections into the residual stream scaled by
1/sqrt(2 * layers), LayerNorm at one and biases at zero.  The names and
the stacked-over-layers layout are the program's parameter dictionary.
"""

import math

import jax
import jax.numpy as jnp


def key_of(seed):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def gpt_dims(cfg):
    """(layers, hidden, heads, ff, vocab, positions) of a GPT-2 style
    ``config.json``."""
    ff = cfg.get("n_inner") or 4 * cfg["n_embd"]
    return (cfg["n_layer"], cfg["n_embd"], cfg["n_head"], ff,
            cfg["vocab_size"], cfg["n_positions"])


def gpt_param_table(cfg):
    """name -> (shape, standard deviation | "ones" | "zeros")."""
    L, H, _, F, V, P = gpt_dims(cfg)
    std = cfg.get("initializer_range", 0.02)
    res = std / math.sqrt(2 * L)
    return {
        "wte": ((V, H), std), "wpe": ((P, H), std),
        "blocks_ln1_w": ((L, H), "ones"), "blocks_ln1_b": ((L, H), "zeros"),
        "blocks_qkv_w": ((L, H, 3 * H), std),
        "blocks_qkv_b": ((L, 3 * H), "zeros"),
        "blocks_proj_w": ((L, H, H), res), "blocks_proj_b": ((L, H), "zeros"),
        "blocks_ln2_w": ((L, H), "ones"), "blocks_ln2_b": ((L, H), "zeros"),
        "blocks_fc1_w": ((L, H, F), std), "blocks_fc1_b": ((L, F), "zeros"),
        "blocks_fc2_w": ((L, F, H), res), "blocks_fc2_b": ((L, H), "zeros"),
        "lnf_w": ((H,), "ones"), "lnf_b": ((H,), "zeros"),
    }


def gpt_param_count(cfg):
    return sum(math.prod(shape) for shape, _ in gpt_param_table(cfg).values())


def build_gpt_params(table, dtype, key):
    """Traceable: every leaf of ``table`` from ``key``."""
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(table.items())):
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif init == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = (init * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def make_gpt_params(cfg, seed, dtype, shardings=None):
    """The whole parameter dictionary in one jitted call, in ``dtype``;
    ``shardings`` (name -> sharding) places each leaf as it is made."""
    table = gpt_param_table(cfg)
    fn = jax.jit(lambda key: build_gpt_params(table, jnp.dtype(dtype), key),
                 out_shardings=shardings)
    return fn(key_of(seed))
