"""Profile-driven fused-kernel decision data (VERDICT r2 #9).

Measures, on the current backend, whether XLA already fuses the patterns the
reference hand-fuses (fused_layernorm_residual_dropout_bias.h,
distributed_fused_lamb): if the jitted composite runs at HBM-bandwidth
roofline, a Pallas kernel can't win and the justified decision is "delegate
to XLA fusion".

Prints one JSON line per pattern:
  {"pattern": ..., "ms": ..., "gbps": ..., "roofline_frac": ...}

roofline_frac = achieved bytes/s over the chip's HBM peak (v5e: 819 GB/s).
>0.6 → XLA is already memory-bound on the fused region; no kernel needed.

Run on the chip:  python tools/fused_probe.py   (never run there yet)
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

HBM_PEAK = {"v5e": 819e9, "v5p": 2765e9, "v4": 1228e9}


def _sync(x):
    return np.asarray(jax.tree_util.tree_leaves(x)[0].ravel()[0:1])


def timeit(fn, *args, iters=50):
    fn = jax.jit(fn)
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def ln_residual_dropout(B=16, L=1024, H=768, dtype=jnp.bfloat16):
    """y = LayerNorm(x + dropout(residual)) — the reference's fused op."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.standard_normal((B, L, H)), dtype)
    res = jnp.asarray(r.standard_normal((B, L, H)), dtype)
    g = jnp.ones((H,), jnp.float32)
    b = jnp.zeros((H,), jnp.float32)
    key = jax.random.key(0)

    def f(x, res, g, b):
        keep = jax.random.bernoulli(key, 0.9, res.shape)
        h = x + jnp.where(keep, res / 0.9, 0).astype(x.dtype)
        m = h.mean(-1, keepdims=True).astype(jnp.float32)
        v = jnp.var(h.astype(jnp.float32), axis=-1, keepdims=True)
        return ((h - m) * jax.lax.rsqrt(v + 1e-5) * g + b).astype(x.dtype)

    dt = timeit(f, x, res, g, b)
    nbytes = (x.size + res.size) * x.dtype.itemsize * 2  # r+w of both streams
    return dt, nbytes


def adamw_update(n_params=124 * 10**6, dtype=jnp.float32):
    """Single fused AdamW step over one flat 124M buffer (gpt2s-sized)."""
    r = np.random.RandomState(0)
    p = jnp.asarray(r.standard_normal(n_params // 4), dtype)  # 31M fits CPU too
    g = jnp.asarray(r.standard_normal(p.size), dtype)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)

    def f(p, g, m, v):
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.999 * v + 0.001 * g * g
        up = m2 / (jnp.sqrt(v2) + 1e-8) + 0.01 * p
        return p - 3e-4 * up, m2, v2

    dt = timeit(f, p, g, m, v)
    nbytes = p.size * p.dtype.itemsize * 7  # r: p,g,m,v  w: p,m,v
    return dt, nbytes


def softmax_xent_block(B=16, L=1024, V=50304):
    """LM-head CE region: logits -> loss (the fused-CE bwd feed)."""
    r = np.random.RandomState(0)
    h = jnp.asarray(r.standard_normal((B * L, 768)), jnp.bfloat16)
    w = jnp.asarray(r.standard_normal((768, V)), jnp.bfloat16)
    y = jnp.asarray(r.randint(0, V, (B * L,)))

    def f(h, w, y):
        logits = (h @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
        return (lse - gold).mean()

    dt = timeit(f, h, w, y, iters=10)
    nbytes = (h.size + w.size) * 2 + B * L * V * 4
    return dt, nbytes


def main():
    kind = getattr(jax.devices()[0], "device_kind", "cpu").lower()
    peak = next((v for k, v in HBM_PEAK.items() if k in kind), None)
    for name, probe in [("ln_residual_dropout", ln_residual_dropout),
                        ("adamw_update", adamw_update),
                        ("softmax_xent_block", softmax_xent_block)]:
        dt, nbytes = probe()
        gbps = nbytes / dt / 1e9
        print(json.dumps({
            "pattern": name, "ms": round(dt * 1e3, 3),
            "gbps": round(gbps, 1),
            "roofline_frac": round(gbps * 1e9 / peak, 3) if peak else None,
            "backend": kind}), flush=True)


if __name__ == "__main__":
    main()
