"""`correct` can fail: the control (the reference computed in int8, the
precision below the configurations' bfloat16) reads well above what
bfloat16 reads, at a size a test can hold; and a run whose timed path is
broken underneath comes out not correct.  The readings at the cells' own
sizes, on the chip, are in PERF.md."""

import json

import numpy as np

from benchmarks.lib import harness, program, serve, train, weights


def tiny():
    cfg = harness.merge(
        harness.load_json("configs", "cerebras-gpt-1.3b.json"),
        harness.load_json("configs", "rehearse-overrides.json"))
    # a wider initialisation than GPT-2's: at this width the token's own
    # embedding would otherwise win every argmax whatever the precision
    return dict(cfg, n_layer=4, n_embd=128, n_inner=512, n_positions=256,
                initializer_range=0.2)


class Served:
    def __init__(self, prompt, tokens):
        self.prompt, self.tokens, self.out_len = prompt, tokens, len(tokens)


def test_int8_reads_above_bfloat16_when_serving():
    """At every position the token the lower precision puts first, read
    against the float32 reference."""
    cfg = tiny()
    rng = np.random.default_rng(0)
    reqs = [Served(rng.integers(1, 512, n).tolist(),
                   rng.integers(1, 512, 100).tolist()) for n in (60, 150)]
    sound, control = [], []
    for seed in (1, 2, 3):
        params = weights.make_gpt_params(cfg, seed, "float32")
        sound.append(serve.served_gap(cfg, params, reqs, "bfloat16")[1])
        control.append(serve.served_gap(cfg, params, reqs, "int8")[1])
    # the mean gap over the served tokens; the widest swings by its nature
    assert min(control) > 3 * max(sound), (sound, control)


class Ctx:
    seed, chips = 5, 1


def test_int8_reads_above_bfloat16_when_training():
    cfg = tiny()
    traffic = {"batch": 4, "seq_len": 64, "reference_rows_per_block": 2,
               "optimizer": {"lr": 3e-4, "weight_decay": 0.01}}
    ref = train.reference_run(Ctx, cfg, traffic, 2)
    bf16 = train.reference_run(Ctx, cfg, traffic, 2, lower="bfloat16")
    int8 = train.reference_run(Ctx, cfg, traffic, 2, lower="int8_train")
    sound = train.worst_leaf_gap(bf16["grad"], ref["grad"])
    control = train.worst_leaf_gap(int8["grad"], ref["grad"])
    assert control > 3 * sound, (sound, control)


def rehearse(capsys, workload):
    from benchmarks import run
    assert run.main(["--workload", workload, "--seed", "11", "--seconds",
                     "2", "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr().out
    line = [x for x in out.splitlines() if x.startswith("[bench] rehearsal")]
    return json.loads(line[-1].split("rehearsal: ", 1)[1]), out


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    build = program.build_train_step

    def broken(*args, **kw):
        step, state = build(*args, **kw)
        last = []

        def frozen(state, x, y):
            if last:            # after the first step nothing moves
                return state, last[0]
            state, loss = step(state, x, y)
            last.append(loss)
            return state, loss
        return frozen, state

    monkeypatch.setattr(program, "build_train_step", broken)
    result, out = rehearse(capsys, "gpt2s-train")
    assert result["correct"] is False, out
    assert "param_change_gap" in out and "FAILED" in out


def test_an_altered_token_is_not_correct(capsys, monkeypatch):
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine as E
    record = E._record

    def altered(self, slot, tok):
        return record(self, slot, (int(tok) + 7) % 500 + 1)

    monkeypatch.setattr(E, "_record", altered)
    result, out = rehearse(capsys, "c1p3b-serve-docs")
    assert result["correct"] is False, out
    assert "served_logit_gap" in out and "FAILED" in out


def test_a_sound_rehearsal_is_correct(capsys):
    result, out = rehearse(capsys, "c1p3b-serve-docs")
    assert result["correct"] is True, out
