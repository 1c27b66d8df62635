"""The parameter table of an ``evabyte`` configuration file, in the
program's names (``paddle_tpu/models/evabyte.py``), made from ``--seed`` on
the device in one jitted call.  ``init_std`` normal weights (the published
key), no bias anywhere, the norms' scales stored as their distance from one
(``norm_add_unit_offset``) and so at zero; ``adaptive_phi`` and
``adaptive_mu_k`` normal x ``head_dim ** -0.5`` clipped to +-1 (``assumed``
in the configuration file: the release's initialiser is not in its
``config.json``).

A layer holds ``4 H^2 + 3 H I + 2 H + 2 nh hd`` numbers: Q, K, V (one
``qkv_w``) and O, the gated MLP's three matrices, two norms, and the two
per-head vectors of the chunk summary.  Beside the layers: the byte
embedding, the final norm and the untied head of ``num_pred_heads`` x
``vocab_size`` columns.
"""

import math

import jax
import jax.numpy as jnp

from .weights import build_gpt_params, key_of

BLOCK = ("ln1_w", "qkv_w", "o_w", "adaptive_phi", "adaptive_mu_k", "ln2_w",
         "gate_w", "up_w", "down_w")
HEAD_VECTORS = ("blocks_adaptive_phi", "blocks_adaptive_mu_k")


def param_table(cfg):
    """name -> (shape, standard deviation | "zeros" | "head_vector")."""
    H, I, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    nh = cfg["num_attention_heads"]
    hd, std = H // nh, cfg["init_std"]
    assert cfg["norm_add_unit_offset"]
    block = {"ln1_w": ((H,), "zeros"), "qkv_w": ((H, 3 * H), std),
             "o_w": ((H, H), std), "adaptive_phi": ((nh, hd), "head_vector"),
             "adaptive_mu_k": ((nh, hd), "head_vector"),
             "ln2_w": ((H,), "zeros"), "gate_w": ((H, I), std),
             "up_w": ((H, I), std), "down_w": ((I, H), std)}
    table = {"wte": ((cfg["vocab_size"], H), std),
             "lm_head": ((H, cfg["num_pred_heads"] * cfg["vocab_size"]), std),
             "norm_f_w": ((H,), "zeros")}
    for name in BLOCK:
        shape, init = block[name]
        table[f"blocks_{name}"] = ((L,) + shape, init)
    return table


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in param_table(cfg).values())


def make_params(cfg, seed, dtype):
    """The whole parameter dictionary in one jitted call, in ``dtype``."""
    table = param_table(cfg)
    plain = {n: v for n, v in table.items() if v[1] != "head_vector"}

    def build(key):
        out = build_gpt_params(plain, jnp.dtype(dtype), key)
        for i, name in enumerate(HEAD_VECTORS):
            shape = table[name][0]
            x = jax.random.normal(jax.random.fold_in(key, 1000 + i), shape,
                                  jnp.float32) * shape[-1] ** -0.5
            out[name] = jnp.clip(x, -1.0, 1.0).astype(dtype)
        return out

    return jax.jit(build)(key_of(seed))
