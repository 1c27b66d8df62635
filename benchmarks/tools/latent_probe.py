"""The latent attention kernel against its XLA reference on the chip, at
the cell's geometry (128 heads, 512 + 64 in a 640-wide row, 16-token
blocks, a 16 x 1040 table) with few rows: prefill chunks, decode rows deep
in their sequences, padding rows marked with sequence -1 as the engine
marks them, a layer of a stack read in place.  Exits 1 on a mismatch;
here, on the CPU, it runs the kernel interpreted at a small size.

    python3 benchmarks/tools/latent_probe.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.ragged_latent_attention import (
        ragged_latent_attention_ref, ragged_latent_attention_rows)
    on_chip = jax.devices()[0].platform == "tpu"
    nh, R, Dr, W, bs, S = (128, 512, 64, 640, 16, 16) if on_chip \
        else (4, 32, 8, 128, 4, 4)
    rng = np.random.default_rng(0)
    worst = 0.0
    for C in ((1, 8, 128, 1040) if on_chip else (1, 4, 12)):
        NB = S * C
        top = C * bs - 1
        # four decode rows, a prefill chunk (whole row groups of it share
        # their key blocks), padding up to whole groups of 8 and beyond
        chunk = list(range(max(top - 27, 0), top + 1))
        pos = [top, top // 2, 0, min(5, top)] + chunk
        pos += [-1] * (-len(pos) % 8 + 8)
        T = len(pos)
        seq = [0, 1, 2, 3] + [5 % S] * len(chunk)
        seq += [-1] * (T - len(seq))
        pool = jnp.asarray(rng.normal(size=(3, NB + 1, bs, W)), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(NB)[:S * C].reshape(S, C) + 1,
                            jnp.int32)
        qa = jnp.asarray(rng.normal(size=(T, nh, R)), jnp.bfloat16)
        qr = jnp.asarray(rng.normal(size=(T, nh, Dr)), jnp.bfloat16)
        args = (qa, qr, pool, table, jnp.asarray(seq, jnp.int32),
                jnp.asarray(pos, jnp.int32),
                jnp.asarray([0, 3, 0, 1] + [0] * (S - 4), jnp.int32))
        kw = dict(scale=(R + Dr) ** -0.5, layer=jnp.int32(1))
        got = jax.jit(lambda *a: ragged_latent_attention_rows(
            *a, interpret=not on_chip, **kw))(*args)
        want = jax.jit(lambda *a: ragged_latent_attention_ref(*a, **kw))(
            *args)
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)).max())
        print(f"[probe] C={C} rows={T} max|kernel - reference| {err:.5f}",
              flush=True)
        worst = max(worst, err)
    ok = worst < 0.05
    print(f"[probe] {'ok' if ok else 'MISMATCH'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
