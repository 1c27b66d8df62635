"""The parameter table of a ``deepseek_v32`` configuration file, in the
program's names (``paddle_tpu/models/pangu_moe.py`` under that file's
keys), made from ``--seed`` on the device in one jitted call.  It is
``weights_pangu``'s table (MLA, the dense MLP, the expert layer, embedding
and head; ``initializer_range`` normal weights, norm scales at one) with
what the published keys change:

- ``sandwich_norm`` false: no ``ln2_w`` / ``ln4_w``;
- a lightning indexer in every layer (``index_n_heads`` x
  ``index_head_dim``): ``idx_q_b_w`` (from the MLA's ``c_q``), ``idx_k_w``
  and the LayerNorm on its key (``idx_k_norm_w`` at one, ``idx_k_norm_b``
  at zero), ``idx_w_w`` (the head weights);
- ``topk_method`` "noaux_tc": ``router_bias`` a layer, the selection bias
  (a trained buffer in a checkpoint; here normal with the file's
  ``router_bias_std``, from ``--seed`` like every other leaf).
"""

import math

import jax
import jax.numpy as jnp

from . import weights_pangu
from .weights import build_gpt_params, key_of
from .weights_pangu import held, stack_layers  # noqa: F401

INDEXER = ("idx_q_b_w", "idx_k_w", "idx_k_norm_w", "idx_k_norm_b", "idx_w_w")
_GONE = ("ln2_w", "ln4_w")
STACKS = {
    stack: tuple(n for n in names if n not in _GONE) + INDEXER
    + (("router_bias",) if stack == "moe" else ())
    for stack, names in weights_pangu.STACKS.items()}


def param_table(cfg):
    """name -> (shape, standard deviation | "ones" | "zeros")."""
    assert not cfg["sandwich_norm"] and cfg["topk_method"] == "noaux_tc"
    H, std = cfg["hidden_size"], cfg.get("initializer_range", 0.02)
    nhi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    table = {n: v for n, v in weights_pangu.param_table(cfg).items()
             if not n.endswith(_GONE)}
    for stack, n in stack_layers(cfg).items():
        table.update({
            f"{stack}_idx_q_b_w": ((n, cfg["q_lora_rank"], nhi * Di), std),
            f"{stack}_idx_k_w": ((n, H, Di), std),
            f"{stack}_idx_k_norm_w": ((n, Di), "ones"),
            f"{stack}_idx_k_norm_b": ((n, Di), "zeros"),
            f"{stack}_idx_w_w": ((n, H, nhi), std)})
    table["moe_router_bias"] = (
        (stack_layers(cfg)["moe"], cfg["router_width"]),
        cfg["router_bias_std"])
    return table


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in param_table(cfg).values())


def make_params(cfg, seed, dtype):
    """The whole parameter dictionary in one jitted call, in ``dtype``."""
    table = param_table(cfg)
    return jax.jit(lambda key: build_gpt_params(
        table, jnp.dtype(dtype), key))(key_of(seed))
