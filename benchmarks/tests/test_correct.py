"""`correct` can fail: the control (the reference computed in int8, the
precision below the configurations' bfloat16) reads well above what
bfloat16 reads, at a size a test can hold; and a run whose timed path is
broken underneath comes out not correct.  The readings at the cells' own
sizes, on the chip, are in PERF.md."""

import json
import types

import numpy as np
import pytest

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
    """(the rehearsal's line, everything the run printed: standard output,
    then standard error)."""
    from benchmarks import run
    assert run.main(["--workload", workload, "--seed", "11", "--seconds",
                     "2", "--trace", "0", "--rehearse"]) == 0
    io = capsys.readouterr()
    line = [x for x in io.out.splitlines()
            if x.startswith("[bench] rehearsal")]
    return strict(line[-1].split("rehearsal: ", 1)[1]), io.out + io.err


def strict(text):
    """``json.loads`` that refuses NaN and Infinity, which are not JSON."""
    def refuse(word):
        raise ValueError(f"{word} is not JSON")
    return json.loads(text, parse_constant=refuse)


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
    # the line names the check that failed, with its value and its limit,
    # and standard error ends with the checks
    failed = {n: c for n, c in result["checks"].items() if not c["ok"]}
    assert list(failed) == ["served_logit_gap"]
    assert failed["served_logit_gap"]["value"] > \
        failed["served_logit_gap"]["limit"] == 0.06
    assert [x for x in out.splitlines()[-len(result["checks"]):]
            if "check served_logit_gap" in x and '"ok": false' in x]


def test_a_sound_rehearsal_is_correct(capsys):
    result, out = rehearse(capsys, "c1p3b-serve-docs")
    assert result["correct"] is True, out


def test_the_result_line_says_what_was_compared():
    """Every check by name, in call order, with its value, its limit (or
    ``at_least``) and whether it held; ``correct`` is their conjunction;
    a value that is not finite is ``null`` and not ok, never ``NaN``."""
    from benchmarks import run
    args = types.SimpleNamespace(workload="c", seed=1, seconds=2.0, trace=0,
                                 rehearse=False)
    result = {"attempted": 3, "failed": 0}

    def line(checks):
        ctx = harness.Context(args, {"chips": 1}, {}, {}, {}, 0.0)
        for name, value, limit, at_least in checks:
            ctx.check(name, value, limit, at_least=at_least)
        text = json.dumps(run.result_line(
            ctx, result, {"setup_s": {"value": 1.5, "unit": "s"}},
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}),
            default=float)
        return strict(text)

    sound = [("backlog_requests_left_at_close", 1595, None, 1),
             ("served_logit_gap", np.float32(0.03), 0.06, None),
             ("compiles_in_window", 0, 0, None)]
    got = line(sound)
    assert list(got) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert got["correct"] is True
    assert list(got["checks"]) == [c[0] for c in sound]
    assert got["checks"]["backlog_requests_left_at_close"] == \
        {"value": 1595, "at_least": 1, "ok": True}
    assert got["checks"]["served_logit_gap"] == \
        {"value": pytest.approx(0.03), "limit": 0.06, "ok": True}

    got = line(sound + [("route_near_tie_share", 0.41, 0.4, None),
                        ("loss_gap_step1", float("nan"), 6e-4, None),
                        ("metric serve_tok_s measured", 0, None, 1)])
    assert got["correct"] is False
    assert [n for n, c in got["checks"].items() if not c["ok"]] == \
        ["route_near_tie_share", "loss_gap_step1",
         "metric serve_tok_s measured"]
    assert got["checks"]["route_near_tie_share"]["value"] == 0.41
    assert got["checks"]["loss_gap_step1"] == \
        {"value": None, "limit": 6e-4, "ok": False}
    assert got["correct"] == all(c["ok"] for c in got["checks"].values())


def test_the_kernels_rows_are_the_packs_the_engine_recorded():
    """``ragged_ticks`` from the ``tick`` events' ``rows``: per sequence
    (real rows, keys the last of them attends), the bucket's left-pad
    rows taken out, whatever a preemption did to the order; only ticks
    wholly inside the traced window."""
    # request: prompt length -> left-pad rows of its bucket (block 16)
    pads = {1: -1000 % 16, 2: -1500 % 16, 3: -1175 % 16, 4: -1175 % 16}
    assert pads == {1: 8, 2: 4, 3: 9, 4: 9}
    ticks = [
        # straddles the window's opening: its kernel calls are not all
        # in the trace
        {"start": 9.8, "end": 10.1, "decode_rows": 1, "budget_used": 1,
         "rows": [[1, 1, 1012]]},
        # request 1's fifth decode row (its 1,008 bucket positions and 5
        # tokens: 1,005 real keys), a chunk in the middle of request 2's
        # prompt (rows 512-811 of its bucket), request 3's first chunk
        # with its 9 left-pad rows
        {"start": 10.1, "end": 10.5, "decode_rows": 1, "budget_used": 501,
         "rows": [[1, 1, 1013], [2, 300, 808], [3, 200, 191]]},
        # request 3 was preempted and its first chunk is replayed, longer;
        # request 4's first chunk is 5 of its 9 pad rows and nothing else
        {"start": 10.5, "end": 10.9, "decode_rows": 1, "budget_used": 318,
         "rows": [[1, 1, 1014], [3, 312, 303], [4, 5, 0]]},
        # straddles the window's close
        {"start": 10.9, "end": 11.3, "decode_rows": 2, "budget_used": 2,
         "rows": [[1, 1, 1015], [2, 1, 1505]]},
    ]
    got = serve.traced_packs(ticks, (10.0, 11.0), pads)
    assert got == [[(1, 1005), (300, 808), (191, 191)],
                   [(1, 1006), (303, 303)]]
    assert [sum(n for n, _ in rows) for rows in got] == \
        [501 - 9, 318 - 9 - 5]
    # a request the benchmark cannot place keeps the engine's own count
    assert serve.packed_rows(ticks[0], {}) == [(1, 1012)]
