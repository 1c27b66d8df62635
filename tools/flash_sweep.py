"""Flash-attention block-size sweep on chip_smoke.py's gpt2-small train step,
16 x 1024 tokens (a builder's round-2 profile put the flash backward at ~11 ms/step;
block size is the main lever).  Never run on the chip since.

Each block size runs in a FRESH child process because
``PADDLE_TPU_FLASH_BLOCK`` is read at trace time and jit caches the kernel.
The parent never imports JAX and the children run one after another, so
the chip has one process at a time.

Run on the chip:  python tools/flash_sweep.py
Prints one JSON line per block size and a final "best" line.
"""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

BLOCKS = [None, 128, 256, 512]   # None = auto (largest divisor)


def child(block):
    env = dict(os.environ)
    if block:
        env["PADDLE_TPU_FLASH_BLOCK"] = str(block)
    env["_FLASH_SWEEP_CHILD"] = "1"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        capture_output=True, text=True, cwd=root)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else {"error": proc.stderr[-300:]}


ITERS = 30


def measure():
    """One child: chip_smoke's gpt2-small train step (its geometry, seed and
    learning rate), compiled once, then ITERS steps on one batch with the
    clock stopped after block_until_ready on the last loss (each step
    consumes the state the one before produced)."""
    import time

    import jax
    import numpy as np

    import chip_smoke
    from paddle_tpu.core.device import local_devices

    device = local_devices("tpu")[0]        # raises where there is no chip
    size = chip_smoke.REAL
    step, state = chip_smoke.build_step(size, 0)
    args = chip_smoke.step_args(size, 0)
    state, loss = step(state, *args)        # compiles
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        state, loss = step(state, *args)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    loss = float(loss)
    if not np.isfinite(loss):
        raise FloatingPointError(f"non-finite loss {loss}")
    B, L = size["train_batch"]
    print(json.dumps({
        "value": round(B * L * ITERS / dt, 1), "unit": "tokens/s",
        "loss": round(loss, 4), "device": device.device_kind,
        "flash_block": os.environ.get("PADDLE_TPU_FLASH_BLOCK", "auto")}),
        flush=True)


def main():
    if os.environ.get("_FLASH_SWEEP_CHILD") == "1":
        measure()
        return
    results = []
    for b in BLOCKS:
        r = child(b)
        r.setdefault("flash_block", b if b else "auto")
        print(json.dumps(r), flush=True)
        if "value" in r and r.get("value"):
            results.append(r)
    if results:
        best = max(results, key=lambda r: r["value"])
        print(json.dumps({"best_block": best["flash_block"],
                          "tokens_per_sec": best["value"]}), flush=True)


if __name__ == "__main__":
    main()
