"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of gpt2-small (12 layers x 768, 12 heads, 1024 positions, bf16;
weights random from ``--seed``), in ONE process, because a chip belongs to
one process at a time:

    kernels  flash forward and backward, fused cross-entropy, paged decode
             and ragged paged attention (bf16 and int8 pools): each compiled
             by the chip's compiler, shown to be in the compiled program, and
             compared with its XLA oracle — a quiet dense fallback fails here
    train    ``make_gpt_train_step`` at 16 x 1024 tokens a step, five steps at
             learning rate 3e-4; losses finite and lower
    serve    ``RaggedPagedContinuousBatchingEngine``: ``warmup()``, eight
             requests of mixed prompt length served to the end with no
             compile after warm-up, first-token logits against the plain
             forward pass

``--chips 4`` runs instead the sharded step and what it is compared with, and
no other phase: the same step on one device of the four, under dp2 x mp2 and
under ZeRO-3 over four — losses against the one-device run, state bytes per
device.

Output: one JSON object per phase, with the compile cache's hits and misses
in that phase, and as the LAST line, only if every phase passed,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

A phase that fails raises: the exit code is not 0 and that line is not
printed.  Without a TPU the script fails before any phase.  ``--rehearse``
runs the same phases at a tiny size on virtual CPU devices (Pallas kernels
interpreted) to check the script's own control flow; its last line is not
the line above, and nothing it prints is a device figure.
"""

import argparse
import json
import os
import sys
import time

# gpt2-small's widths, the vocabulary padded to a multiple of 128
GPT2_SMALL = dict(vocab_size=50304, hidden_size=768, num_layers=12,
                  num_attention_heads=12, max_position_embeddings=1024,
                  compute_dtype="bfloat16", scan_unroll=12)
LEARNING_RATE = 3e-4

REAL = dict(
    cfg=GPT2_SMALL, train_batch=(16, 1024), train_steps=5,
    attn=(4, 1024, 12, 64),             # B, L, H, D of the flash cases
    ce=(4096, 50304),                   # tokens, vocabulary
    # serving at head dim 64: 8 slots x 512 positions, 16-token blocks
    slots=8, max_len=512, block=16, budget=256, buckets=[64, 128],
    prompts=[7, 23, 41, 64, 77, 100, 128, 128],
    new_tokens=[24, 16, 32, 8, 20, 12, 28, 16],
    ragged_q_lens=[100, 1, 0, 60, 1, 1, 80, 13],
    tol=dict(fwd=2e-2, bwd=2e-2, logits=5e-2),
    sharded_steps=2, sharded_loss_tol=1e-3)

TINY = dict(
    cfg=dict(vocab_size=512, hidden_size=64, num_layers=2,
             num_attention_heads=4, max_position_embeddings=128,
             compute_dtype="float32", scan_unroll=2),
    train_batch=(4, 128), train_steps=5,
    attn=(1, 128, 2, 16), ce=(64, 512),
    slots=4, max_len=64, block=8, budget=24, buckets=[8, 16],
    prompts=[3, 5, 8, 8, 11, 16, 2, 13], new_tokens=[4, 3, 5, 2, 4, 3, 5, 2],
    ragged_q_lens=[9, 1, 0, 14],
    tol=dict(fwd=1e-4, bwd=1e-3, logits=1e-3),
    sharded_steps=2, sharded_loss_tol=1e-4)


class SmokeFailure(AssertionError):
    """A phase found the system wrong; never caught."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def emit(**record):
    print(json.dumps(record), flush=True)


class CacheCounter:
    """Hits and misses of jax's persistent compilation cache, from the
    events jax itself records for every compile request."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.requests, self.hits

    def since(self, mark):
        requests, hits = self.requests - mark[0], self.hits - mark[1]
        return {"hits": hits, "misses": requests - hits}


def max_err(got, want):
    """Largest difference, taken where the arrays are (some are a GiB)."""
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                 - jnp.asarray(want, jnp.float32))))


def scaled_err(got, want):
    """Largest difference as a share of the oracle's largest value."""
    import jax.numpy as jnp
    return max_err(got, want) / float(
        jnp.max(jnp.abs(jnp.asarray(want, jnp.float32))))


# --------------------------------------------------------------- kernels --

def kernel_case(name, fn, oracle, args, tol, on_chip, compare=None,
                fp32_logits_bytes=None):
    """Compile ``fn`` for the device, show the kernel in the compiled
    program, run it and compare with ``oracle`` run as plain XLA."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    record = {"case": name}
    if on_chip and fp32_logits_bytes is None:
        n = compiled.as_text().count("tpu_custom_call")
        check(n > 0, f"{name}: no Pallas kernel in the compiled program "
                     f"(a dense fallback was taken)")
        record["kernels_in_program"] = n
    elif on_chip:
        # the fused loss is XLA's own fusion, not a Pallas call: what
        # shows it is that no float32 copy of the logits is ever held
        temp = compiled.memory_analysis().temp_size_in_bytes
        check(temp < fp32_logits_bytes,
              f"{name}: {temp} temp bytes, a float32 copy of the logits is "
              f"{fp32_logits_bytes}")
        record["temp_bytes"] = temp
        record["fp32_logits_bytes"] = fp32_logits_bytes
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(jax.jit(oracle)(*args))
    err = (compare or max_err)(got, want)
    check(err <= tol, f"{name}: differs from its oracle by {err} > {tol}")
    record["max_err"], record["tol"] = err, tol
    return record


def phase_kernels(size, seed, on_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models._decode import (PagedKV, cached_attention,
                                           quantize_kv, ragged_attention)
    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops.loss import softmax_cross_entropy_mean
    from paddle_tpu.ops.ragged_paged_attention import (ragged_attention_ref,
                                                       ragged_rows)

    rng = np.random.RandomState(seed)       # the small integer inputs
    dt = jnp.dtype(size["cfg"]["compute_dtype"])
    tol = size["tol"]
    cases = []
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def normal(*shape):                     # the large ones, on the device
        return jax.random.normal(next(keys), shape, dt)

    # ---- flash attention, forward and backward
    B, L, H, D = size["attn"]
    q, k, v, g = (normal(B, L, H, D) for _ in range(4))
    if on_chip:
        def flash(q, k, v):
            return A.flash_attention(q, k, v, causal=True)
    else:
        # off the TPU the public entry takes the dense path, which would
        # compare the oracle with itself: drive the kernel, interpreted
        def flash(q, k, v):
            return A._flash_attention(
                q, k, v, None, jnp.zeros((1,), jnp.uint32), True, D ** -0.5,
                0.0, A.flash_plan(L, D, True, dt))

    def dense(q, k, v):
        return A.dense_attention(q, k, v, causal=True)

    def grads_of(attn):
        def loss(q, k, v, g):
            return (attn(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    def worst(got, want):
        # gradients are sums over up to L keys, so their size, and with it
        # what one bfloat16 rounding costs, grows with L: compare to scale
        return max(scaled_err(a, b) for a, b in zip(got, want))

    cases.append(kernel_case("flash_fwd", flash, dense, (q, k, v),
                             tol["fwd"], on_chip))
    cases.append(kernel_case("flash_bwd", grads_of(flash), grads_of(dense),
                             (q, k, v, g), tol["bwd"], on_chip,
                             compare=worst))

    # ---- fused cross-entropy, value and gradient
    N, V = size["ce"]
    logits = normal(N, V) * 2
    labels = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)

    def naive_ce(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()

    def ce_err(got, want):
        (lg, dg), (lw, dw) = got, want
        return max(abs(float(lg) - float(lw)), scaled_err(dg, dw))

    cases.append(kernel_case(
        "fused_ce", jax.value_and_grad(softmax_cross_entropy_mean),
        jax.value_and_grad(naive_ce), (logits, labels), tol["fwd"], on_chip,
        compare=ce_err, fp32_logits_bytes=N * V * 4))

    # ---- paged decode and ragged paged attention, through the dispatchers
    # the models call (models/_decode.py), so their fallbacks are in reach
    cfg = size["cfg"]
    nh = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // nh
    S, bs = size["slots"], size["block"]
    C = size["max_len"] // bs
    NB1 = S * C + 1
    pk, pv = normal(NB1, bs, nh, hd), normal(NB1, bs, nh, hd)
    table = jnp.asarray(rng.randint(1, NB1, (S, C)), jnp.int32)
    t = jnp.asarray(rng.randint(0, C * bs, S), jnp.int32)
    pad = jnp.minimum(jnp.asarray(rng.randint(0, bs, S), jnp.int32), t)
    q1 = normal(S, nh, hd)

    def paged(q, pk, pv, table, t, pad):
        return cached_attention(q[:, None], PagedKV(pk, table),
                                PagedKV(pv, table), t, pad_lens=pad)

    def paged_gather(q, pk, pv, table, t, pad):
        return cached_attention(q[:, None], PagedKV(pk, table).gather(dt),
                                PagedKV(pv, table).gather(dt), t,
                                pad_lens=pad)

    cases.append(kernel_case("paged_decode", paged, paged_gather,
                             (q1, pk, pv, table, t, pad), tol["fwd"],
                             on_chip))

    q_lens = np.asarray(size["ragged_q_lens"])
    T = size["budget"]
    n_real = int(q_lens.sum())
    check(n_real <= T and len(q_lens) <= S, "ragged pack does not fit")
    q_lens = np.concatenate([q_lens, np.zeros(S - len(q_lens), np.int64)])
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    kv = jnp.asarray([n + int(rng.randint(0, C * bs - n + 1)) if n else 0
                      for n in q_lens], jnp.int32)
    rpad = jnp.asarray(rng.randint(0, bs // 2, S), jnp.int32)
    row_seq, row_pos = ragged_rows(cu, kv, T)
    qr = normal(T, nh, hd)

    def real_rows(got, want):
        return max_err(got[:n_real], want[:n_real])

    for label, pools in (("ragged_paged", (pk, pv)),
                         ("ragged_paged_int8",
                          (quantize_kv(pk), quantize_kv(pv)))):
        cases.append(kernel_case(
            label, ragged_attention, ragged_attention_ref,
            (qr, pools[0], pools[1], table, row_seq, row_pos, rpad),
            tol["fwd"], on_chip, compare=real_rows))
    return {"cases": cases}


# ----------------------------------------------------------------- train --

def build_step(size, seed, zero_stage=0, **degrees):
    """The one-chip train path: fleet.init, GPTModel, make_gpt_train_step."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig, GPTModel, make_gpt_train_step
    from paddle_tpu.optimizer import AdamW

    paddle.seed(seed)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, **degrees}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTModel(GPTConfig(**size["cfg"]))
    return make_gpt_train_step(
        model, AdamW(LEARNING_RATE, weight_decay=0.01),
        fleet.get_hybrid_communicate_group(), remat=False,
        zero_stage=zero_stage)


def train_batch(size, seed):
    import jax.numpy as jnp
    import numpy as np
    B, L = size["train_batch"]
    rng = np.random.RandomState(seed)
    V = size["cfg"]["vocab_size"]
    return (jnp.asarray(rng.randint(0, V, (B, L))),
            jnp.asarray(rng.randint(0, V, (B, L))))


def step_args(size, seed):
    """What a step takes after its state: key, learning rate, one batch."""
    import jax
    import numpy as np
    return (jax.random.key(seed), np.float32(LEARNING_RATE),
            *train_batch(size, seed))


def run_steps(step, state, args, n_steps):
    """n_steps on one batch; returns (state, losses, seconds per step),
    each step timed around block_until_ready."""
    import jax
    losses, walls = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        state, loss = step(state, *args)
        jax.block_until_ready(loss)
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return state, losses, walls


def phase_train(size, seed, on_chip):
    import numpy as np
    step, state = build_step(size, seed)
    args = step_args(size, seed)
    t0 = time.perf_counter()
    compiled = step.lower(state, *args).compile()
    record = {"compile_s": round(time.perf_counter() - t0, 2)}
    if on_chip:
        n = compiled.as_text().count("tpu_custom_call")
        # flash forward + the one fused backward in every layer
        want = 2 * size["cfg"]["num_layers"]
        check(n >= want, f"train step holds {n} Pallas kernels, expected "
                         f"{want}: attention fell back to the dense path")
        record["kernels_in_program"] = n
    _, losses, walls = run_steps(compiled, state, args, size["train_steps"])
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    record["losses"] = losses
    if on_chip:
        B, L = size["train_batch"]
        record["step_s"] = walls
        record["tokens_per_step"] = B * L
    return record


# ----------------------------------------------------------------- serve --

def phase_serve(size, seed, on_chip, counter):
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine

    paddle.seed(seed)
    cfg = dict(size["cfg"])
    cfg.pop("scan_unroll")
    model = GPTModel(GPTConfig(**cfg))
    params = {n: p._data for n, p in model.named_parameters()}
    eng = RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=size["slots"], max_len=size["max_len"],
        block_size=size["block"], prompt_buckets=size["buckets"],
        token_budget=size["budget"])
    t0 = time.perf_counter()
    report = eng.warmup()
    record = {"warmup_programs": report["programs"],
              "warmup_s": round(time.perf_counter() - t0, 2)}
    misses = eng.metrics()["compile_misses"]
    warmed = counter.mark()

    rng = np.random.RandomState(seed)
    V = cfg["vocab_size"]
    reqs = [([int(t) for t in rng.randint(1, V, n)], m)
            for n, m in zip(size["prompts"], size["new_tokens"])]
    rids = []
    t0 = time.perf_counter()
    while len(rids) < len(reqs):
        # two arrivals a tick, so prefill chunks and decode rows share steps
        for prompt, n_new in reqs[len(rids):len(rids) + 2]:
            rids.append(eng.add_request(prompt, n_new))
        eng.step()
    out = eng.run_to_completion(max_ticks=10 * sum(size["new_tokens"]))
    out.update(eng.pop_finished())
    wall = time.perf_counter() - t0
    for rid, (_, n_new) in zip(rids, reqs):
        check(len(out.get(rid, ())) == n_new,
              f"request {rid}: {len(out.get(rid, ()))} of {n_new} tokens")
    m = eng.metrics()
    check(m["compile_misses"] == misses,
          f"{m['compile_misses'] - misses} programs compiled after warm-up")
    check(m["step_errors"] == 0, f"{m['step_errors']} step errors")
    record.update(requests=len(reqs), tokens=int(m["tokens_emitted"]),
                  mixed_steps=int(eng.mixed_steps),
                  ragged_steps=int(eng.ragged_steps),
                  engine_compiles_after_warmup=m["compile_misses"] - misses,
                  xla_compile_requests_while_serving=sum(
                      counter.since(warmed).values()))
    if on_chip:
        record["serve_s"] = round(wall, 3)

    # first-token logits of request 0: the engine's device path (ragged
    # embed, decode_ragged over a fresh pool, decode_logits) against the
    # plain forward pass, and the token the engine emitted against both
    prompt = reqs[0][0]
    n = len(prompt)
    ids = jnp.asarray(prompt, jnp.int32)
    h = model.scan_blocks(params, model.embed_fn(params, ids[None]),
                          remat=False)
    plain = np.asarray(model.head_fn(params, h)[0, -1])
    bs = size["block"]
    n_blocks = -(-n // bs)
    T = n_blocks * bs
    table = jnp.zeros((1, n_blocks), jnp.int32).at[0].set(
        jnp.arange(1, n_blocks + 1))
    row_pos = jnp.where(jnp.arange(T) < n, jnp.arange(T), -1).astype(jnp.int32)
    zeros = jnp.zeros((T,), jnp.int32)
    toks = zeros.at[:n].set(ids)
    no_pad = jnp.zeros((1,), jnp.int32)
    hr = model._embed_ragged(params, toks, zeros, row_pos, no_pad)
    hr, _ = model.decode_ragged(params, hr, eng._alloc_caches(), table,
                                zeros, row_pos, no_pad)
    ragged = np.asarray(model.decode_logits(params, hr[:, n - 1:n])[0, -1])
    err = max_err(ragged, plain)
    tol = size["tol"]["logits"]
    check(err <= tol, f"first-token logits differ by {err} > {tol}")
    first = out[rids[0]][0]
    check(plain[first] >= plain.max() - tol,
          f"engine's first token {first} is not the forward pass's choice")
    record.update(first_token_logits_max_err=err, logits_tol=tol,
                  first_token=int(first))
    return record


# --------------------------------------------------------------- sharded --

def state_bytes(state, devices):
    import jax
    held = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def phase_sharded(size, seed, on_chip, devices):
    """One device of the four, dp2 x mp2, ZeRO-3 over four: same step."""
    import numpy as np
    layouts = [("one_device", {}),
               ("dp2_mp2", dict(dp_degree=2, mp_degree=2)),
               ("zero3_x4", dict(sharding_degree=4, zero_stage=3))]
    runs = {}
    for name, kw in layouts:
        step, state = build_step(size, seed, **kw)
        held = state_bytes(state, devices)
        t0 = time.perf_counter()
        state, losses, walls = run_steps(step, state, step_args(size, seed),
                                         size["sharded_steps"])
        runs[name] = {"losses": losses, "state_bytes_per_device": held}
        if on_chip:
            runs[name]["first_step_s"] = round(walls[0], 2)
            runs[name]["step_s"] = walls[1:]
        emit(layout=name, seconds=round(time.perf_counter() - t0, 2),
             **runs[name])
        del step, state

    one = runs["one_device"]
    full = max(one["state_bytes_per_device"])
    check(sorted(one["state_bytes_per_device"])[:-1] == [0, 0, 0],
          "the one-device run is not on one device")
    tol = size["sharded_loss_tol"]
    for name, share in (("dp2_mp2", 0.5), ("zero3_x4", 0.25)):
        run = runs[name]
        check(all(np.isfinite(run["losses"])), f"{name}: {run['losses']}")
        gap = max(abs(a - b) for a, b in zip(run["losses"], one["losses"]))
        check(gap <= tol, f"{name}: losses {run['losses']} differ from one "
                          f"device's {one['losses']} by {gap} > {tol}")
        held = run["state_bytes_per_device"]
        # "about a half / a quarter": what no rule shards (LayerNorm,
        # positions, some biases) stays whole on every device
        check(min(held) > 0 and max(held) <= (share + 0.1) * full,
              f"{name}: state bytes per device {held}, one device holds "
              f"{full}: not spread to about {share} each")
        run["max_loss_gap"], run["share_of_one_device"] = gap, max(held) / full
    return {"layouts": runs, "loss_tol": tol}


# ------------------------------------------------------------------ main --

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded step and its one-device reference "
                         "only")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on virtual CPU devices; proves the "
                         "script, not the chip")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = not args.rehearse
    if on_chip and device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU and found {device}; --rehearse "
              f"runs the tiny CPU rehearsal", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices and found "
              f"{device}", file=sys.stderr)
        return 1

    import paddle_tpu as paddle
    from paddle_tpu.jit.aot import enable_persistent_compilation_cache
    cache_dir = enable_persistent_compilation_cache()
    counter = CacheCounter(jax)
    size = REAL if on_chip else TINY
    if not on_chip:
        paddle.set_flags({"FLAGS_paged_attn_interpret": True})
    emit(phase="start", device=device, rehearsal=args.rehearse,
         seed=args.seed, compile_cache=cache_dir)

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(
            size, args.seed, on_chip, devices[:4]))]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(size, args.seed, on_chip)),
            ("train", lambda: phase_train(size, args.seed, on_chip)),
            ("serve", lambda: phase_serve(size, args.seed, on_chip,
                                          counter))]
    for name, run in phases:
        mark, t0 = counter.mark(), time.perf_counter()
        record = run()
        emit(phase=name, ok=True, seconds=round(time.perf_counter() - t0, 2),
             cache=counter.since(mark), **record)

    if on_chip:
        emit(ok=True, device=device)
    else:
        emit(rehearsal=True, phases=[name for name, _ in phases],
             device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
