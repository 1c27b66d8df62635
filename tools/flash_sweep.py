"""Flash-attention plan sweep: the candidates of ``ops.attention.flash_plan``
(the backward's form, the block, the group a block is walked in) timed on
the chip, one JSON line each and a final "best" line.

    python tools/flash_sweep.py                    # gpt2s-train's own step
    python tools/flash_sweep.py --workload c1p3b-train-x4      # four chips
    python tools/flash_sweep.py --kernels --shape 2,2048,16,128

Without ``--kernels`` a candidate is timed on the training cell's own
compiled step (its configuration and traffic files, built by the benchmark's
``program.build_train_step``), ITERS steps on one batch with the clock
stopped after the last loss is ready.  With it, on one layer's causal
attention alone, forward and forward + backward, ``(B, L, H, D)`` bfloat16
with its layout transposes: a compile of seconds instead of a minute, for
ranking many candidates (the table in PERF.md section 6, PR 37, is from
both).

A candidate reaches the kernels through ``attention._plan_override``, which
``flash_plan`` reads when a program is traced: one process, no environment
variable, and every candidate builds a new jitted step, so nothing stale is
reused.  The chosen plans live in ``flash_plan`` itself.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ITERS = 30


def candidates(L, forms):
    from paddle_tpu.ops.attention import FlashPlan
    return [FlashPlan(form, block, block, sub) for form in forms
            for block in (2048, 1024, 512, 256, 128) if L % block == 0
            for sub in sorted({128, 256, block}) if sub <= block]


def timed(fn, args, iters):
    """Milliseconds a call, the calls queued one after another."""
    import jax
    out = jax.block_until_ready(fn(*args))      # compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def kernels_under(plan, q, k, v, g):
    """One layer's attention under ``plan``: forward, forward + backward."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention

    attention._plan_override = plan

    def forward(q, k, v):
        return attention.flash_attention(q, k, v, causal=True)

    def loss(q, k, v, g):
        return (forward(q, k, v).astype(jnp.float32)
                * g.astype(jnp.float32)).sum()

    return {"forward_ms": round(timed(jax.jit(forward), (q, k, v), ITERS), 4),
            "forward_backward_ms": round(timed(
                jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, k, v, g),
                ITERS), 4)}


def sweep_kernels(shape, plans):
    import jax
    import jax.numpy as jnp

    q, k, v, g = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.key(0), 4))
    for plan in plans:
        yield plan, kernels_under(plan, q, k, v, g)


def cell_files(workload, rehearse):
    """The cell's configuration and traffic, as benchmarks/run.py loads
    them."""
    from benchmarks.lib import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        cfg = harness.merge(cfg, harness.load_json(
            "configs", "rehearse-overrides.json"))
        traffic = harness.merge(traffic, traffic.get("rehearse", {}))
    return cfg, traffic


def step_under(plan, cfg, traffic, batch):
    """The cell's step, built and compiled under ``plan``, then ITERS
    steps on one batch (each consumes the state the one before made)."""
    import jax
    import numpy as np
    from benchmarks.lib import program, weights
    from paddle_tpu.ops import attention

    attention._plan_override = plan
    step, state = program.build_train_step(
        cfg, traffic, 0, lambda sh: weights.make_gpt_params(
            cfg, 0, "float32", sh))
    state, loss = step(state, *batch)       # compiles
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        state, loss = step(state, *batch)
    loss = float(loss)
    dt = time.perf_counter() - t0
    if not np.isfinite(loss):
        raise FloatingPointError(f"non-finite loss {loss} under {plan}")
    B, L = traffic["batch"], traffic["seq_len"]
    return {"tokens_per_s": round(B * L * ITERS / dt, 1),
            "step_ms": round(dt / ITERS * 1e3, 3), "loss": round(loss, 4)}


def sweep_step(cfg, traffic, plans):
    import jax.numpy as jnp
    from benchmarks.lib import train

    batch = [jnp.asarray(a) for a in train.batch_of(
        0, 0, traffic["batch"], traffic["seq_len"], cfg["vocab_size"])]
    for plan in plans:
        yield plan, step_under(plan, cfg, traffic, batch)
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="gpt2s-train")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--shape", default="16,1024,12,64",
                    help="B,L,H,D of --kernels")
    ap.add_argument("--forms", default="fused,split")
    ap.add_argument("--plans", default="",
                    help="only these, as form:block:sub,...")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny, on the CPU (where attention takes the dense "
                         "path): the control flow only, no number")
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu.core.device import local_devices
    from paddle_tpu.ops.attention import FlashPlan
    # raises where there is no chip
    device = jax.devices()[0] if args.rehearse else local_devices("tpu")[0]
    shape = tuple(int(x) for x in args.shape.split(","))
    if args.kernels:
        L = shape[1]
    else:
        cfg, traffic = cell_files(args.workload, args.rehearse)
        L = traffic["seq_len"]
    if args.plans:
        plans = [FlashPlan(f, int(b), int(b), int(s)) for f, b, s in
                 (p.split(":") for p in args.plans.split(","))]
    else:
        plans = candidates(L, args.forms.split(","))
    rows = sweep_kernels(shape, plans) if args.kernels \
        else sweep_step(cfg, traffic, plans)
    key = "forward_backward_ms" if args.kernels else "step_ms"
    best = None
    for plan, numbers in rows:
        line = dict(plan._asdict(), **numbers, device=device.device_kind,
                    on=args.shape if args.kernels else args.workload)
        print(json.dumps(line), flush=True)
        if best is None or line[key] < best[key]:
            best = line
    print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    main()
