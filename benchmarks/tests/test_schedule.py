import json
import os

import pytest

from benchmarks.lib import harness, schedule, stats

SERVING = ["chat-open", "docs-backlog", "longdocs-backlog"]
# a backlog cell: its traffic file, the backlog_tokens it had until PR 32,
# the requests that gave (all that a window reaches at the accepted
# rates), their digest, the configuration's vocabulary, and the rows/s it
# takes to drain the backlog inside the ramp and the window.  docs:
# 2,500,792 tokens over 4 + 45 s = 51.0 k rows/s, over what the chip's
# peaks allow the deployment (of 80 rounds 40 carry a 512-row chunk,
# 1.35 TFLOP of products + ~1 ms of attention = 7.8 ms at 197 TFLOP/s,
# and 40 carry 14 decode rows and read 2.6 GB of weights, 3.2 ms at 819
# GB/s: 21,040 rows in 0.44 s, 48 k rows/s; with the KV those rounds read
# counted too, 32 k).  longdocs: 2,408,466 over 8 + 45 s = 45.4 k rows/s
# against ~19 k if every 2,048-row tick ran at the peaks (35 ms of
# products + 74 ms of absorbed attention).  PERF.md section 4.
BACKLOGS = {
    "docs-backlog": dict(old_tokens=100_000, n=64, head="d70d17c84f40a383",
                         vocab=50257, rate=50_000),
    "longdocs-backlog": dict(old_tokens=600_000, n=58,
                             head="671250684be69318", vocab=19200,
                             rate=45_000)}


def traffic(name):
    return harness.load_json("traffic", name + ".json")


@pytest.mark.parametrize("name", SERVING)
def test_schedule_is_the_cells_and_not_the_seeds(name):
    """--seed never reaches the generator: the schedule is a function of
    the traffic file alone, so two runs print one digest."""
    a = schedule.build_schedule(traffic(name), 45.0)
    b = schedule.build_schedule(traffic(name), 45.0)
    assert schedule.digest(a) == schedule.digest(b)
    pa = schedule.prompt_tokens(a, 1, 50257)
    pb = schedule.prompt_tokens(a, 2 ** 31 + 7, 50257)
    assert [len(p) for p in pa] == [len(p) for p in pb]
    assert pa != pb
    assert all(1 <= t < 50257 for p in pb for t in p)


@pytest.mark.parametrize("name", SERVING)
def test_lengths_keep_to_the_files_clipping(name):
    tr = traffic(name)
    sched = schedule.build_schedule(tr, 51.0)
    assert len(sched) > 20
    for s in sched:
        assert tr["prompt_len"]["min"] <= s.prompt_len <= tr["prompt_len"]["max"]
        assert tr["output_len"]["min"] <= s.output_len <= tr["output_len"]["max"]
        assert s.prompt_len + s.output_len <= tr["engine"]["max_len"]


def test_a_longer_window_extends_the_stream():
    tr = traffic("chat-open")
    short = schedule.build_schedule(tr, 10.0)
    long = schedule.build_schedule(tr, 45.0)
    assert long[:len(short)] == short
    assert all(-tr["ramp_s"] <= s.due_s < 45.0 for s in long)
    rate = len([s for s in long if s.due_s >= 0]) / 45.0
    assert rate == pytest.approx(tr["rate_rps"], rel=0.35)


def test_chat_lengths_have_the_stated_medians():
    tr = traffic("chat-open")
    sched = schedule.build_schedule(dict(tr, rate_rps=200.0), 51.0)
    assert stats.percentile([s.prompt_len for s in sched], 50) == \
        pytest.approx(tr["prompt_len"]["median"], rel=0.1)
    assert stats.percentile([s.output_len for s in sched], 50) == \
        pytest.approx(tr["output_len"]["median"], rel=0.1)


def test_bursts_keep_the_mean_rate():
    tr = dict(traffic("chat-open"), burst=8, rate_rps=40.0)
    sched = schedule.build_schedule(tr, 51.0)
    dues = [s.due_s for s in sched]
    assert len(set(dues)) * 8 == len(dues)
    assert len(dues) / (51.0 + tr["ramp_s"]) == pytest.approx(40.0, rel=0.3)


def test_backlog_is_due_before_the_ramp_and_never_drains():
    """Everything is enqueued before the window; at the recorded rate the
    engine cannot finish it inside the ramp and the longest window."""
    tr = traffic("docs-backlog")
    sched = schedule.build_schedule(tr, 51.0)
    assert all(s.due_s == -tr["ramp_s"] for s in sched)
    tokens = sum(s.prompt_len + s.output_len for s in sched)
    assert tokens >= tr["backlog_tokens"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        longest = json.load(f)["run_seconds"]
    served = tr["recorded_serve_tok_s"] * (tr["ramp_s"] + longest)
    assert tokens >= 1.2 * served


@pytest.mark.parametrize("name", sorted(BACKLOGS))
def test_a_longer_backlog_starts_with_the_shorter_one(name):
    """The requests a window reaches are the ones it reached before the
    backlog was lengthened, to the token, for any --seed."""
    tr, was = traffic(name), BACKLOGS[name]
    n, vocab = was["n"], was["vocab"]
    long = schedule.build_schedule(tr, 45.0)
    short = schedule.build_schedule(
        dict(tr, backlog_tokens=was["old_tokens"]), 45.0)
    assert len(short) == n < len(long) and long[:n] == short
    assert schedule.digest(long[:n]) == was["head"]
    for seed in (7, 2 ** 31 + 11):
        assert schedule.prompt_tokens(long, seed, vocab)[:n] == \
            schedule.prompt_tokens(short, seed, vocab)


@pytest.mark.parametrize("name", sorted(BACKLOGS))
def test_no_engine_the_chip_allows_drains_a_backlog(name):
    """A backlog cell that drains measures an idle engine, and its run is
    refused as not correct: an edit to a backlog, a ramp or the window
    that lets a fast engine drain it fails here and not on the chip."""
    tr = traffic(name)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        window = json.load(f)["run_seconds"]
    sched = schedule.build_schedule(tr, window)
    tokens = sum(s.prompt_len + s.output_len for s in sched)
    assert tokens / (tr["ramp_s"] + window) >= BACKLOGS[name]["rate"]


def test_percentile_interpolates_and_counts_failures_as_infinite():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([10.0] * 9 + [float("inf")], 90) == float("inf")
    assert stats.percentile([10.0] * 19 + [float("inf")], 90) == 10.0
    assert stats.percentile([], 90) is None
    assert stats.quartile_spread([100, 101, 102, 103, 104, 105]) == \
        pytest.approx(3.5 / 102.5)
