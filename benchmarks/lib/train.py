"""The training driver: one compiled step with its state, driven from the
seed through its first steps (which the plain reference follows), then
handed, the same object, to the timed window.
"""

import gc
import math
import statistics
import time

import numpy as np

from . import program, reference_gpt, weights


def batch_of(seed, k, B, L, vocab):
    """Step k's rows, all different, from the seed: (inputs, next-token
    labels)."""
    rng = np.random.Generator(np.random.PCG64([int(seed), int(k)]))
    ids = rng.integers(0, vocab, (B, L + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)


def worst_leaf_gap(got, ref):
    """Largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref)


def run(ctx):
    import jax
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    B, L, V = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    n_check = traffic.get("check_steps", 3)
    table = weights.gpt_param_table(cfg)

    def make_params(shardings):
        return weights.make_gpt_params(cfg, ctx.seed, "float32", shardings)

    step, state = program.build_train_step(cfg, traffic, ctx.seed,
                                           make_params)

    def put(x, y):
        return jnp.asarray(x), jnp.asarray(y)

    # ---- the first steps, through the window's own call and feed
    got = {"loss": []}
    b1 = traffic["optimizer"].get("beta1", 0.9)
    for k in range(n_check):
        state, loss = step(state, *put(*batch_of(ctx.seed, k, B, L, V)))
        got["loss"].append(float(loss))
        if k == 0:      # the first gradient, as the optimizer got it
            m1 = {n: s["moment1"] for n, s in state["opt"]["slots"].items()}
            got["grad"] = {n: float(v) / (1 - b1)
                           for n, v in leaf_norms(m1).items()}
            del m1
    delta = jax.jit(lambda p, key: {
        n: jnp.sqrt(jnp.sum(jnp.square(p[n] - w)))
        for n, w in weights.build_gpt_params(
            table, jnp.float32, key).items()})
    got["delta"] = {n: float(v) for n, v in
                    delta(state["params"], weights.key_of(ctx.seed)).items()}
    ctx.note(f"first losses {got['loss']}")

    # ---- the window: the same step and state
    k = n_check
    step_ms, losses = [], []
    ctx.open_window(time.monotonic())
    t_open = t = time.monotonic()
    while t - t_open < ctx.seconds:
        if ctx.trace and not ctx.tracing and \
                t - t_open >= ctx.seconds - ctx.trace_s:
            ctx.start_trace()
        with ctx.span("next_batch"):
            x, y = put(*batch_of(ctx.seed, k, B, L, V))
        with ctx.span("train_step"):
            state, loss = step(state, x, y)
        with ctx.span("fetch_loss"):
            losses.append(float(loss))
        k += 1
        t, t_prev = time.monotonic(), t
        step_ms.append((t - t_prev) * 1e3)
    if ctx.tracing:
        ctx.stop_trace()
    elapsed = t - t_open
    ctx.close_window(compiles=0)
    steps = len(step_ms)
    bad = sum(1 for x in losses if not math.isfinite(x))
    ctx.note(f"steps {steps} elapsed_s {elapsed:.4f} last loss {losses[-1]}")
    ctx.obs["series"]["step_ms"] = step_ms
    ctx.obs["train"] = {"tokens_per_step": B * L, "batch": B, "seq_len": L,
                        "steps": steps, "elapsed_s": elapsed}
    ctx.read_memory()

    # ---- the reference follows the first steps, on the freed device
    del state, step, x, y, loss
    gc.collect()
    ref = reference_run(ctx, cfg, traffic, n_check)
    for k in range(n_check):
        ctx.check(f"loss_gap_step{k + 1}",
                  abs(got["loss"][k] - ref["loss"][k]),
                  ctx.limits["loss_gap"])
    ctx.check("grad_norm_gap", worst_leaf_gap(got["grad"], ref["grad"]),
              ctx.limits["grad_norm_gap"])
    ctx.check("param_change_gap",
              worst_leaf_gap(got["delta"], ref["delta"]),
              ctx.limits["param_change_gap"])
    ctx.check("compiles_in_window", ctx.compiles_in_window, 0)
    if ctx.control:
        low = reference_run(ctx, cfg, traffic, n_check, lower="int8_train")
        gaps = [abs(a - b) for a, b in zip(low["loss"], ref["loss"])]
        ctx.note(f"control loss_gap {max(gaps)!r}")
        ctx.note("control grad_norm_gap "
                 f"{worst_leaf_gap(low['grad'], ref['grad'])!r}")
        ctx.note("control param_change_gap "
                 f"{worst_leaf_gap(low['delta'], ref['delta'])!r}")
    return {"end_to_end": {"train_tok_s": steps * B * L / elapsed},
            "attempted": steps, "failed": bad}


def reference_run(ctx, cfg, traffic, n_steps, lower=None):
    """Losses, first-gradient norms and parameter-change norms of the
    plain reference over the first steps' batches."""
    import jax
    import jax.numpy as jnp
    B, L, V = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    opt = traffic["optimizer"]
    shardings = reference_shardings(cfg, ctx.chips)
    params = weights.make_gpt_params(cfg, ctx.seed, "float32", shardings)
    start = jax.tree.map(jnp.copy, params)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    m, v = zeros(), zeros()
    out = {"loss": []}
    for k in range(n_steps):
        x, y = batch_of(ctx.seed, k, B, L, V)
        loss, grads = reference_gpt.loss_and_grads(
            cfg, params, jnp.asarray(x), jnp.asarray(y),
            traffic.get("reference_rows_per_block", 4), lower, shardings)
        out["loss"].append(float(loss))
        if k == 0:
            out["grad"] = {n: float(g) for n, g in leaf_norms(grads).items()}
        params, m, v = reference_gpt.adamw(
            params, m, v, grads, jnp.float32(k + 1), lr=opt["lr"],
            wd=opt["weight_decay"])
    diff = jax.tree.map(jnp.subtract, params, start)
    out["delta"] = {n: float(d) for n, d in leaf_norms(diff).items()}
    return out


def reference_shardings(cfg, chips):
    """One chip: none.  Four: each weight split along its last axis that
    four divides, over a mesh of the benchmark's own, so the float32
    weights, gradients and Adam state fit."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if chips == 1:
        return None
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("ref",))
    out = {}
    for name, (shape, _) in weights.gpt_param_table(cfg).items():
        spec = [None] * len(shape)
        for ax in range(len(shape) - 1, 0 if len(shape) > 1 else -1, -1):
            if shape[ax] % chips == 0:
                spec[ax] = "ref"
                break
        out[name] = NamedSharding(mesh, P(*spec))
    return out
