"""The parameter table of a ``longcat_flash`` configuration file, in the
program's names (``paddle_tpu/models/longcat_flash.py``), made from
``--seed`` on the device in one jitted call (``weights.build_gpt_params``:
each leaf from its own fold of the key).  ``initializer_range`` normal
weights, norms at one, the router's selection bias at zero, no other bias
(``assumed`` in the configuration file).

The file's own keys: ``router_width`` (the router's outputs: the published
``n_routed_experts`` real experts and then ``zero_expert_num`` zero-compute
ones), ``experts_held`` ``[first, stop)`` (the real experts this chip
holds; the file's ``n_routed_experts`` is their number).  Every leaf of the
one stack is stacked over ``num_layers``; a sublayer's own (its MLA, its
two norms, its dense MLP) over ``(num_layers, 2)``.
"""

import math

import jax
import jax.numpy as jnp

from .weights import build_gpt_params, key_of

SUBLAYER = ("ln1_w", "q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w", "kv_a_norm_w",
            "kv_b_w", "o_w", "ln3_w", "gate_w", "up_w", "down_w")
BRANCH = ("router_w", "router_bias", "e_gate_w", "e_up_w", "e_down_w")


def held(cfg):
    """(first, stop) of the real experts held here."""
    first, stop = cfg["experts_held"]
    assert stop - first == cfg["n_routed_experts"], cfg["experts_held"]
    assert 0 <= first and stop <= real_experts(cfg), cfg["experts_held"]
    return int(first), int(stop)


def real_experts(cfg):
    """How many of the router's outputs are experts with weights: the
    others, the last ``zero_expert_num``, are zero-compute."""
    return cfg["router_width"] - cfg["zero_expert_num"]


def param_table(cfg):
    """name -> (shape, standard deviation | "ones" | "zeros")."""
    H, nh, L = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_layers"]
    R, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    I, F, Eh = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"], \
        cfg["n_routed_experts"]
    std = cfg.get("initializer_range", 0.02)
    one = lambda *shape: (shape, "ones")
    w = lambda *shape: (shape, std)
    sub = {"ln1_w": one(H), "ln3_w": one(H),
           "q_a_w": w(H, cfg["q_lora_rank"]),
           "q_a_norm_w": one(cfg["q_lora_rank"]),
           "q_b_w": w(cfg["q_lora_rank"], nh * (nope + rope)),
           "kv_a_w": w(H, R + rope), "kv_a_norm_w": one(R),
           "kv_b_w": w(R, nh * (nope + v)), "o_w": w(nh * v, H),
           "gate_w": w(H, I), "up_w": w(H, I), "down_w": w(I, H)}
    branch = {"router_w": w(H, cfg["router_width"]),
              "router_bias": ((cfg["router_width"],), "zeros"),
              "e_gate_w": w(Eh, H, F), "e_up_w": w(Eh, H, F),
              "e_down_w": w(Eh, F, H)}
    table = {"wte": w(cfg["vocab_size"], H), "lm_head": w(H, cfg["vocab_size"]),
             "norm_f_w": one(H)}
    for name in SUBLAYER:
        shape, init = sub[name]
        table[f"layers_{name}"] = ((L, 2) + shape, init)
    for name in BRANCH:
        shape, init = branch[name]
        table[f"layers_{name}"] = ((L,) + shape, init)
    return table


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in param_table(cfg).values())


def make_params(cfg, seed, dtype):
    """The whole parameter dictionary in one jitted call, in ``dtype``."""
    table = param_table(cfg)
    return jax.jit(lambda key: build_gpt_params(
        table, jnp.dtype(dtype), key))(key_of(seed))
