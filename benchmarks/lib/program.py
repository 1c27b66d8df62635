"""The only file that touches the system under test: how a configuration
file becomes the program's model, train step and serving engine.  Entry
points are the ones a user calls (``GPTModel``, ``make_gpt_train_step``,
``make_sharded_gpt_train_step``, ``RaggedPagedContinuousBatchingEngine``,
``fleet.init``, ``jit.aot.enable_persistent_compilation_cache``).
"""

import jax

from .weights import key_of


def gpt_config(cfg, **extra):
    """The program's ``GPTConfig`` from a GPT-2 style ``config.json``."""
    from paddle_tpu.models.gpt import GPTConfig
    act = cfg.get("activation_function", "gelu_new")
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
        max_position_embeddings=cfg["n_positions"],
        initializer_range=cfg.get("initializer_range", 0.02),
        layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-5),
        hidden_act="gelu_approx" if act == "gelu_new" else "gelu",
        compute_dtype=cfg.get("compute_dtype", "bfloat16"),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True), **extra)


def meta_model(cfg, **extra):
    """The program's model object with no weights on the device: built
    under ``eval_shape`` (as ``make_sharded_gpt_train_step`` builds its
    own), so only its configuration and pure functions are real."""
    from paddle_tpu.core import rng
    from paddle_tpu.models.gpt import GPTModel
    holder = {}

    def build(key):
        with rng.rng_scope(key):
            holder["model"] = GPTModel(gpt_config(cfg, **extra))
        return {n: p._data for n, p in holder["model"].named_parameters()}

    jax.eval_shape(build, jax.random.key(0))
    return holder["model"]


def init_fleet(**degrees):
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, **degrees}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def build_train_step(cfg, traffic, seed, make_params):
    """(step(state, batch_x, batch_y) -> (state, loss), state) through the
    builder the traffic file names, with the benchmark's weights in the
    program's own layout.  ``make_params(shardings)`` makes them."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt
    from paddle_tpu.optimizer import AdamW

    paddle.seed(int(seed) % (2 ** 31 - 1))
    opt = traffic["optimizer"]
    lr = np.float32(opt["lr"])
    optimizer = AdamW(opt["lr"], weight_decay=opt["weight_decay"])
    key = key_of(seed)
    remat = traffic.get("remat", False)
    if traffic["builder"] == "make_gpt_train_step":
        hcg = init_fleet()
        model = meta_model(cfg, scan_unroll=traffic.get("scan_unroll", 1))
        params = make_params(None)
        for name, p in model.named_parameters():
            p._data = params[name]
        inner, state = gpt.make_gpt_train_step(
            model, optimizer, hcg, remat=remat)
        del params

        def step(state, x, y):
            return inner(state, key, lr, x, y)
    elif traffic["builder"] == "make_sharded_gpt_train_step":
        hcg = init_fleet(sharding_degree=traffic["sharding_degree"])
        inner, state = gpt.make_sharded_gpt_train_step(
            gpt_config(cfg), optimizer, hcg,
            zero_stage=traffic["zero_stage"],
            seed=int(seed) % (2 ** 31 - 1), remat=remat)
        shardings = {n: p.sharding for n, p in state["params"].items()}
        for p in state["params"].values():
            p.delete()
        state["params"] = make_params(shardings)

        def step(state, x, y):
            return inner(state, lr, key, x, y)
    else:
        raise ValueError(f"unknown builder {traffic['builder']!r}")
    return step, state


def build_engine(cfg, engine, params, tracer):
    """The ragged paged engine with the deployment the traffic file
    states (slots, pool, budget); prompt buckets at every multiple of the
    block, so a prompt is padded by less than one block."""
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    bs = engine["block_size"]
    return RaggedPagedContinuousBatchingEngine(
        meta_model(cfg), params, max_slots=engine["max_slots"],
        max_len=engine["max_len"], block_size=bs,
        num_blocks=engine["num_blocks"],
        prompt_buckets=list(range(bs, engine["max_len"] + 1, bs)),
        token_budget=engine["token_budget"], tracer=tracer)


def table_widths(engine):
    """The table-width buckets the engine compiles one program for."""
    from paddle_tpu.jit.bucketing import pow2_grid
    return list(pow2_grid(engine["max_len"] // engine["block_size"]))
