"""The parameter table of a ``pangu_ultra_moe`` configuration file, in the
program's names (``paddle_tpu/models/pangu_moe.py``), made from ``--seed``
on the device in one jitted call (``weights.build_gpt_params``: each leaf
from its own fold of the key).  ``initializer_range`` normal weights,
norms at one, no bias anywhere (``assumed`` in the configuration file).

The file's own keys: ``router_width`` (the router's outputs: the published
``n_routed_experts``), ``experts_held`` ``[first, stop)`` (the routed
experts this chip holds; the file's ``n_routed_experts`` is their number).
Each stack's leaves are stacked over its layers: ``dense_*`` the
``first_k_dense_replace`` leading layers, ``moe_*`` the expert layers.
"""

import math

import jax
import jax.numpy as jnp

from .weights import build_gpt_params, key_of

MLA = ("ln1_w", "q_a_w", "q_a_norm_w", "q_b_w", "kv_a_w", "kv_a_norm_w",
       "kv_b_w", "o_w", "ln2_w", "ln3_w", "ln4_w")
STACKS = {"dense": MLA + ("gate_w", "up_w", "down_w"),
          "moe": MLA + ("router_w", "e_gate_w", "e_up_w", "e_down_w",
                        "s_gate_w", "s_up_w", "s_down_w")}


def held(cfg):
    """(first, stop) of the routed experts held here."""
    first, stop = cfg["experts_held"]
    assert stop - first == cfg["n_routed_experts"], cfg["experts_held"]
    return int(first), int(stop)


def stack_layers(cfg):
    return {"dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}


def param_table(cfg):
    """name -> (shape, standard deviation | "ones")."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    R, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    std = cfg.get("initializer_range", 0.02)
    one = lambda *shape: (shape, "ones")
    w = lambda *shape: (shape, std)
    mla = {"ln1_w": one(H), "ln2_w": one(H), "ln3_w": one(H),
           "ln4_w": one(H), "q_a_w": w(H, cfg["q_lora_rank"]),
           "q_a_norm_w": one(cfg["q_lora_rank"]),
           "q_b_w": w(cfg["q_lora_rank"], nh * (nope + rope)),
           "kv_a_w": w(H, R + rope), "kv_a_norm_w": one(R),
           "kv_b_w": w(R, nh * (nope + v)), "o_w": w(nh * v, H)}
    I, F = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Eh, Fs = cfg["n_routed_experts"], F * cfg["n_shared_experts"]
    own = {"dense": {"gate_w": w(H, I), "up_w": w(H, I), "down_w": w(I, H)},
           "moe": {"router_w": w(H, cfg["router_width"]),
                   "e_gate_w": w(Eh, H, F), "e_up_w": w(Eh, H, F),
                   "e_down_w": w(Eh, F, H), "s_gate_w": w(H, Fs),
                   "s_up_w": w(H, Fs), "s_down_w": w(Fs, H)}}
    table = {"wte": w(cfg["vocab_size"], H), "lm_head": w(H, cfg["vocab_size"]),
             "norm_f_w": one(H)}
    for stack, n in stack_layers(cfg).items():
        for name, (shape, init) in {**mla, **own[stack]}.items():
            table[f"{stack}_{name}"] = ((n,) + shape, init)
    return table


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in param_table(cfg).values())


def make_params(cfg, seed, dtype):
    """The whole parameter dictionary in one jitted call, in ``dtype``."""
    table = param_table(cfg)
    return jax.jit(lambda key: build_gpt_params(
        table, jnp.dtype(dtype), key))(key_of(seed))
