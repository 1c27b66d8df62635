"""Model FLOPs utilisation of training: FLOPs of a step by the
Megatron/PaLM convention (``opcount.transformer_train_flops``) times steps
per second over chips times the peak, in percent."""

from benchmarks.lib import opcount
from benchmarks.lib.weights import gpt_dims


def read(how, ctx):
    train = ctx.obs.get("train")
    if not train:
        return None
    layers, hidden, _, ff, vocab, _ = gpt_dims(ctx.config)
    flops = opcount.transformer_train_flops(
        train["batch"], train["seq_len"], layers, hidden, ff, vocab)
    rate = flops * train["steps"] / train["elapsed_s"]
    peak = opcount.peaks(ctx.device_kind)["bf16_flops"]
    return 100.0 * rate / (ctx.chips * peak)
