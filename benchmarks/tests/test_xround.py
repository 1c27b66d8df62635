"""The serving round by kind (``benchmarks/lib/xround.py`` and the reader
on top of it), on a small trace recorded on a v5e by
``benchmarks/tools/record_trace_round.py`` from the program whose spans say
the round's kind and whose ``engine.dispatch`` has its three parts: nine
ragged rounds of a two-layer GPT, four with a prefill chunk (64 rows) and
five of decode rows only (the 8-row program), under two marked windows:
``bench_window`` round all of them and ``cut_window``, which opens inside
round 11 and closes inside round 16.  ``trace_round.ticks.json`` holds the
same rounds' ``tick`` events.  (Recorded while the spans also carried
``rows_run`` and ``decode_rows``, which nothing read and the program no
longer writes; ``trace_round_early.xplane.pb`` is the tool's next
recording, of the program as it is, in a session whose device line lies a
millisecond early against the host's.)"""

import json
import os
import types

import pytest

from benchmarks.lib import harness, xplane, xregion, xround

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(HERE, "testdata", "trace_round.xplane.pb")
TICKS = os.path.join(HERE, "testdata", "trace_round.ticks.json")
EARLY = os.path.join(HERE, "testdata", "trace_round_early.xplane.pb")
PHASES_ONLY = os.path.join(HERE, "testdata", "trace_named.xplane.pb")
NO_SPANS = os.path.join(HERE, "testdata", "trace.xplane.pb")
CHUNK_ROUNDS, DECODE_ROUNDS = [10, 11, 12, 13], [14, 15, 16, 17, 18]

CELLS = ("docs", "bytedocs")
ROUND_METRICS = [f"decode_rounds_time_share.{c}"
                 for c in CELLS + ("longdocs", "longctx")] + [
    f"{name}.{c}" for c in CELLS for name in (
        "round_ms_p50_chunk", "round_ms_p50_decode",
        "idle_under_operands_share", "idle_under_key_share",
        "idle_decode_rounds_share", "operands_host_ms_p50",
        "key_host_ms_p50")]
SYNC_METRICS = ["idle_under_sync_share.docs", "idle_under_sync_share.bytedocs"]


def _rounds(window):
    named = xregion.Named(xplane.Reduction(
        TRACE, window_span=window, host_spans=("engine_step",)))
    return named, xround.Rounds(named, TRACE)


@pytest.fixture(scope="module")
def whole():
    return _rounds("bench_window")


@pytest.fixture(scope="module")
def cut():
    return _rounds("cut_window")


@pytest.fixture(scope="module")
def ticks():
    with open(TICKS) as f:
        return {t["tick"]: t for t in json.load(f)}


# ------------------------------------------------------------- the kinds --

def test_every_round_says_its_kind_and_its_program(whole, ticks):
    _, rounds = whole
    assert [r.number for r in rounds.rounds] == CHUNK_ROUNDS + DECODE_ROUNDS
    assert [r.number for r in rounds.of_kind(xround.CHUNK)] == CHUNK_ROUNDS
    assert [r.number for r in rounds.of_kind(xround.DECODE)] == DECODE_ROUNDS
    for r in rounds.rounds:     # the trace alone against the tick events
        event = ticks[r.number]
        assert (r.kind == xround.CHUNK) == bool(event["prefill_tokens"])
        assert event["rows_run"] == (64 if r.kind == xround.CHUNK else 8)
        assert r.wall_ms == pytest.approx(1e3 * event["dur_s"], rel=0.05)


def test_a_round_cut_by_either_edge_of_the_window_is_left_out(cut, whole):
    named, rounds = cut
    assert [r.number for r in rounds.rounds] == [12, 13, 14, 15]
    assert [r.kind for r in rounds.rounds] == [xround.CHUNK] * 2 \
        + [xround.DECODE] * 2
    # the window does hold a part of round 11 and of round 16
    assert [n for _, _, n in named.ticks] == [11, 12, 13, 14, 15, 16]
    inside = {r.number: r for r in whole[1].rounds}
    for r in rounds.rounds:     # a whole round reads as it does in the
        full = inside[r.number]                 # window round everything
        assert (r.start, r.end, r.kind) == (full.start, full.end, full.kind)
        assert dict(r.spans) == dict(full.spans)


@pytest.mark.parametrize("part", xround.DISPATCH_PARTS)
def test_the_parts_are_the_tick_events_parts(whole, ticks, part):
    """A child's span on the trace is the clock pair whose seconds go to
    the ``tick`` event's ``parts``: the same milliseconds, round by
    round."""
    for r in whole[1].rounds:
        assert len(r.spans[part]) == 1
        assert r.host_ms(part) == pytest.approx(
            1e3 * ticks[r.number]["parts"][part], rel=0.05, abs=0.01)


def test_the_parts_follow_one_another_and_partition_dispatch(whole):
    _, rounds = whole
    for r in rounds.rounds:
        (ds, de), = r.spans[xround.DISPATCH]
        edges = [r.spans[p][0] for p in xround.DISPATCH_PARTS]
        assert ds <= edges[0][0] and edges[-1][1] <= de
        assert all(a[1] <= b[0] for a, b in zip(edges, edges[1:]))
    p50, off, number = rounds.partition()
    assert 0 <= p50 <= off < 0.02 and number in CHUNK_ROUNDS + DECODE_ROUNDS


def test_gpt_has_no_stats_read_and_so_no_such_span(whole):
    _, rounds = whole
    assert all(xround.STATS not in r.spans for r in rounds.rounds)
    assert all(r.host_ms(xround.STATS) is None for r in rounds.rounds)
    assert rounds.idle_under(xround.STATS) is None


# -------------------------------------------------------------- the idle --

@pytest.mark.parametrize("window", ["bench_window", "cut_window"])
def test_idle_is_cut_at_the_spans_edges(window, whole, cut):
    named, rounds = whole if window == "bench_window" else cut
    gaps = sum(b - a for a, b in named.gaps)
    assert rounds.idle_ns(rounds.t0, rounds.t1) == gaps
    # a moment inside a gap takes the part of the gap before it
    a, b = max(named.gaps, key=lambda g: g[1] - g[0])
    mid = (a + b) // 2
    assert rounds.idle_ns(a, mid) == mid - a
    assert rounds.idle_ns(mid, b) == b - mid
    assert rounds.idle_ns(a - 1, b + 1) == b - a
    under = {p: rounds.idle_under(p) for p in xround.DISPATCH_PARTS}
    assert all(v > 0 for v in under.values())
    whole_ns = rounds.idle_under(xround.DISPATCH)
    # what lies between the parts is their spans' own entry and exit
    assert sum(under.values()) <= whole_ns
    assert sum(under.values()) == pytest.approx(whole_ns, rel=0.02)
    by_kind = [rounds.idle_under(xregion.TICK, k) for k in xround.KINDS]
    assert sum(by_kind) == rounds.idle_under(xregion.TICK) <= gaps


def test_idle_under_a_phase_is_what_the_accepted_reader_reads(whole):
    """Over a window that cuts no round, the idle time under
    ``engine.dispatch`` and ``engine.sync`` is ``Named.idle_by_phase``'s
    to the nanosecond: the two readers cut the same gaps at the same
    edges."""
    named, rounds = whole
    idle = named.idle_by_phase()
    for phase in (xround.DISPATCH, xround.SYNC, "engine.pack",
                  "engine.unpack"):
        assert rounds.idle_under(phase) == idle[phase]


@pytest.mark.parametrize("window", ["bench_window", "cut_window"])
def test_idle_under_sync_is_launch_middle_and_read_back(window, whole, cut):
    _, rounds = whole if window == "bench_window" else cut
    total = [0, 0, 0]
    for r in rounds.rounds:
        split = rounds.sync_split(r)
        assert all(ns >= 0 for ns in split)
        (s, e), = r.spans[xround.SYNC]
        assert sum(split) == rounds.idle_ns(s, e)
        total = [a + b for a, b in zip(total, split)]
    assert sum(total) == rounds.idle_under(xround.SYNC)
    launch, middle, back = total
    # the program is running when the host enters engine.sync, and the
    # chip has finished before the host wakes: all of it is read-back
    assert launch == 0 and back > 0 and back > 10 * middle


def test_sync_split_on_gaps_made_by_hand():
    """One ``engine.sync`` of [100, 200] over gaps that begin before it,
    lie inside it, and run past its end."""
    named = types.SimpleNamespace(
        t0=0, t1=300, ticks=[],
        gaps=[(50, 120), (140, 150), (160, 165), (180, 260)])
    rounds = xround.Rounds(named, TRACE)
    r = xround.Round(1, 90, 270)
    r.spans[xround.SYNC].append((100, 200))
    assert rounds.sync_split(r) == (20, 15, 20)
    assert rounds.idle_ns(100, 200) == 55
    named.gaps = [(50, 260)]
    assert xround.Rounds(named, TRACE).sync_split(r) == (0, 0, 100)
    #                                                   idle throughout


def test_the_program_begins_inside_the_call_that_starts_it(whole):
    """On a trace whose two clocks agree the chip begins after the host
    has called and before the call returns."""
    _, rounds = whole
    for r in rounds.rounds:
        (s, e), = r.spans[xround.CALL]
        assert 0 < rounds.call_lead_ns(r) < e - s


def test_a_session_that_laid_the_device_line_early_says_so():
    """The same nine rounds of the same program recorded in another
    session on the chip machine (the tool's second run of PR 42): its
    first operation lies a millisecond BEFORE the call that starts it, so
    that session laid the device's line early against the host's.  The
    rounds and their kinds read as ever; the log line says it."""
    named = xregion.Named(xplane.Reduction(
        EARLY, window_span="bench_window", host_spans=("engine_step",)))
    rounds = xround.Rounds(named, EARLY)
    assert [r.number for r in rounds.of_kind(xround.CHUNK)] == CHUNK_ROUNDS
    assert [r.number for r in rounds.of_kind(xround.DECODE)] == DECODE_ROUNDS
    for r in rounds.rounds:
        assert -1_100_000 < rounds.call_lead_ns(r) < -900_000
    assert "call opens: us p50 -990.0, least -1065.5 (" in rounds.describe()


@pytest.mark.parametrize("early", [0, 30_000, 800_000])
def test_a_device_line_laid_early_reads_as_a_negative_lead(early):
    """One round: dispatch [0, 4 ms] with its call from 3.5, sync to 20;
    the chip runs ``_threefry_split`` for a microsecond at 3.2 and the
    program from 3.6 to 18, its operations 2 us apart.  The same line
    laid ``early`` ns before the host's reads the lead that much less."""
    ms = 1_000_000
    ops = [(3.2 * ms, 3.2 * ms + 1000)] + [
        (3.6 * ms + k * 100_000, 3.6 * ms + (k + 1) * 100_000 - 2000)
        for k in range(144)]
    gaps, at = [], -ms
    for s, e in ops:
        gaps.append((at - early, s - early))
        at = e
    gaps.append((at - early, 21 * ms))
    named = types.SimpleNamespace(t0=-ms, t1=21 * ms, ticks=[], gaps=gaps)
    r = xround.Round(1, -0.5 * ms, 20.5 * ms)
    r.spans[xround.DISPATCH].append((0, 4 * ms))
    r.spans[xround.CALL].append((3.5 * ms, 4 * ms))
    r.spans[xround.SYNC].append((4 * ms, 20 * ms))
    assert xround.Rounds(named, TRACE).call_lead_ns(r) == 100_000 - early
    del r.spans[xround.CALL]        # an engine with no such part
    assert xround.Rounds(named, TRACE).call_lead_ns(r) is None


# ----------------------------------------------- the readers, by data file --

def _ctx(path, window="bench_window"):
    red = xplane.Reduction(path, window_span=window,
                           host_spans=("train_step", "engine_step"))
    notes = []
    return types.SimpleNamespace(obs={"xplane": red}, note=notes.append,
                                 notes=notes)


def _read(name, ctx):
    how = harness.load_json("layer_metrics", name + ".json")
    return harness.load_module("readers", how["reader"]).read(how, ctx)


@pytest.fixture(scope="module")
def ctx():
    return _ctx(TRACE)


def test_the_new_metrics_are_twenty_and_benchmark_json_has_them():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    by_reader = sorted(
        name for name in entries if harness.load_json(
            "layer_metrics", name + ".json")["reader"] == "xplane_round")
    assert by_reader == sorted(ROUND_METRICS) and len(by_reader) == 18
    for name in ROUND_METRICS + SYNC_METRICS:
        assert entries[name]["moves"] == "serve_tok_s"
        assert entries[name]["source"] == "program_span"
        assert len(entries[name]["workloads"]) == 1
    for name in SYNC_METRICS:   # the accepted reader, with a data file
        how = harness.load_json("layer_metrics", name + ".json")
        assert (how["reader"], how["phases"], how["idle"]) == (
            "xplane_phase", ["engine.sync"], True)


@pytest.mark.parametrize("cell", CELLS)
def test_key_and_sync_idle_say_they_are_judged_by_their_sum(cell):
    key, sync = (f"idle_under_{n}_share.{cell}" for n in ("key", "sync"))
    assert harness.load_json("layer_metrics", key + ".json")["sum_with"] \
        == [sync]
    assert harness.load_json("layer_metrics", sync + ".json")["sum_with"] \
        == [key]


@pytest.mark.parametrize("name", ROUND_METRICS + SYNC_METRICS)
def test_every_new_metric_reads_a_number_on_the_recorded_trace(name, ctx):
    value = _read(name, ctx)
    assert isinstance(value, float) and value > 0
    if name.rsplit(".", 1)[0].endswith("_share"):
        assert value < 100


@pytest.mark.parametrize("name", ROUND_METRICS)
@pytest.mark.parametrize("path", [PHASES_ONLY, NO_SPANS],
                         ids=["phases-only", "no-spans"])
def test_an_older_programs_trace_reads_nothing_and_raises_nothing(name,
                                                                  path):
    """The parent's program: five phases with a ``tick`` and no kind, no
    child (``trace_named``); PR 24's: no span at all (``trace``)."""
    assert _read(name, _ctx(path)) is None


def test_not_traced_reads_nothing():
    ctx = types.SimpleNamespace(obs={}, note=print)
    assert all(_read(name, ctx) is None for name in ROUND_METRICS)


def test_the_readings_are_the_rounds_own(ctx, whole):
    named, rounds = whole
    window = rounds.window
    wall = {k: [r.wall_ms for r in rounds.of_kind(k)] for k in xround.KINDS}
    assert _read("decode_rounds_time_share.docs", ctx) == pytest.approx(
        100.0 * sum(wall["decode"]) / (sum(wall["chunk"])
                                       + sum(wall["decode"])))
    assert 50 < _read("decode_rounds_time_share.longctx", ctx) < 60
    assert _read("round_ms_p50_chunk.docs", ctx) == pytest.approx(
        (sorted(wall["chunk"])[1] + sorted(wall["chunk"])[2]) / 2)
    assert _read("round_ms_p50_decode.bytedocs", ctx) == \
        sorted(wall["decode"])[2]
    assert _read("operands_host_ms_p50.docs", ctx) == sorted(
        r.host_ms(xround.OPERANDS) for r in rounds.rounds)[4]
    assert _read("key_host_ms_p50.bytedocs", ctx) == sorted(
        r.host_ms(xround.KEY) for r in rounds.rounds)[4]
    assert _read("idle_decode_rounds_share.docs", ctx) == pytest.approx(
        100.0 * rounds.idle_under(xregion.TICK, xround.DECODE) / window)
    parts = (_read("idle_under_operands_share.docs", ctx)
             + _read("idle_under_key_share.docs", ctx)
             + 100.0 * rounds.idle_under(xround.CALL) / window)
    # the three add to the accepted metric of the phase, less the spans'
    # own entry and exit: 2% of it at most, as the host's milliseconds
    assert parts == pytest.approx(
        _read("idle_under_dispatch_share.docs", ctx), rel=0.02)
    assert _read("idle_under_sync_share.docs", ctx) == pytest.approx(
        100.0 * sum(sum(rounds.sync_split(r)) for r in rounds.rounds)
        / window)


def test_the_log_line_says_what_is_no_metric(ctx):
    _read("decode_rounds_time_share.docs", ctx)
    _read("round_ms_p50_chunk.docs", ctx)
    lines = [n for n in ctx.notes if n.startswith("rounds in the trace: ")]
    assert len(lines) == 1      # once a run, whatever is read
    line = lines[0]
    for said in ("9 whole rounds", "chunk: 4 rounds", "decode: 5 rounds",
                 "engine.dispatch.call ", "engine.sync.stats none",
                 "launch 0.000", "read-back ", "between operations ",
                 "the program's first operation after engine.dispatch.call "
                 "opens: us p50 2", ", least 1",
                 "parts against engine.dispatch: p50 ", ", widest "):
        assert said in line, (said, line)


def test_a_trace_of_one_kind_reads_its_mix_and_no_idle_of_the_other():
    """A window that holds decode-only rounds alone (the tail of a latent
    cell's trace may): the mix is 100, the idle inside chunk rounds 0, a
    chunk round's p50 is not there to read."""
    named = xregion.Named(xplane.Reduction(
        TRACE, window_span="cut_window", host_spans=("engine_step",)))
    named.ticks = [t for t in named.ticks if t[2] in (14, 15)]
    rounds = xround.Rounds(named, TRACE)
    assert [r.kind for r in rounds.rounds] == [xround.DECODE] * 2
    assert rounds.decode_time_share() == 100.0
    assert rounds.idle_under(xregion.TICK, xround.CHUNK) == 0
    assert rounds.idle_under(xregion.TICK, xround.DECODE) > 0
    assert rounds.of_kind(xround.CHUNK) == []
    assert "chunk: 0 rounds" in rounds.describe()
