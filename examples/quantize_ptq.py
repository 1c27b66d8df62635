"""Post-training int8 quantization of a conv classifier, end to end.

Trains a small conv net on synthetic data (eager), calibrates + converts it
to int8 (per-channel conv scales, int8 MXU matmul path on TPU), and compares
float vs int8 eval accuracy.

Run on CPU:  JAX_PLATFORMS=cpu python examples/quantize_ptq.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.quantization import PostTrainingQuantization


def make_data(n, rng):
    x = rng.randn(n, 3, 16, 16).astype("float32")
    # class = which quadrant carries the strongest mean signal
    y = rng.randint(0, 4, n)
    for i, c in enumerate(y):
        h, w = divmod(int(c), 2)
        x[i, :, h * 8:(h + 1) * 8, w * 8:(w + 1) * 8] += 1.5
    return x, y.astype("int64")


def accuracy(model, x, y):
    model.eval()
    preds = np.asarray(model(paddle.to_tensor(x))._data).argmax(-1)
    return float((preds == y).mean())


def main():
    paddle.seed(0)
    rng = np.random.RandomState(0)
    xtr, ytr = make_data(512, rng)
    xte, yte = make_data(256, rng)

    model = nn.Sequential(
        nn.Conv2D(3, 16, 3, padding=1), nn.ReLU(), nn.MaxPool2D(2),
        nn.Conv2D(16, 32, 3, padding=1), nn.ReLU(),
        # pool to 2x2, NOT 1x1: the label is *which quadrant* lights up,
        # so the head needs spatial information
        nn.AdaptiveAvgPool2D(2), nn.Flatten(), nn.Linear(32 * 4, 4))
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())

    model.train()
    for epoch in range(4):
        for i in range(0, len(xtr), 64):
            xb = paddle.to_tensor(xtr[i:i + 64])
            yb = paddle.to_tensor(ytr[i:i + 64])
            loss = nn.functional.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step(); opt.clear_grad()
        print(f"epoch {epoch}: loss {float(loss.numpy()):.4f}")

    acc_fp32 = accuracy(model, xte, yte)

    calib = [paddle.to_tensor(xtr[i:i + 64]) for i in range(0, 256, 64)]
    qmodel = PostTrainingQuantization(model).calibrate(calib).convert()
    acc_int8 = accuracy(qmodel, xte, yte)

    print(f"fp32 accuracy: {acc_fp32:.3f}")
    print(f"int8 accuracy: {acc_int8:.3f}")
    assert acc_int8 > acc_fp32 - 0.03, "int8 conversion lost >3% accuracy"


if __name__ == "__main__":
    main()
