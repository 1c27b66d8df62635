"""Regions and phases (``benchmarks/lib/xregion.py`` and the two readers
on top of it), on a small trace recorded on a v5e by
``benchmarks/tools/record_trace_named.py`` from the program that names
them: two training steps and a few ragged serving ticks of a two-layer
GPT, a ``Tracer`` attached to the engine."""

import os
import types

import pytest

from benchmarks.lib import harness, xplane, xregion

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(HERE, "testdata", "trace_named.xplane.pb")
OLD = os.path.join(HERE, "testdata", "trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.Reduction(TRACE, host_spans=("train_step", "engine_step"))


@pytest.fixture(scope="module")
def named(red):
    return xregion.Named(red)


@pytest.mark.parametrize("path,region", [
    ("jit(step)/jvp(layers)/while/body/closed_call/attn/dot_general:",
     "attn"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/mlp/mul",
     "mlp"),
    ("jit(step)/transpose(jvp(layers))/while/body/dynamic_update_slice",
     "layers"),
    ("jit(step)/transpose(jvp(head))/head/dot_general", "head"),
    ("jit(loss)/transpose(jvp(flash_attention))/flash_attention_dq/"
     "pallas_call", "flash_attention"),
    ("jit(run)/layers/while/body/attn/kv_write/scatter", "kv_write"),
    ("jit(run)/layers/while/body/attn/ragged_paged_attention/"
     "ragged_paged_attention/pallas_call", "ragged_paged_attention"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(step)/transpose(jvp())/while/body/dynamic_slice:", None),
    ("jit(headroom)/attnx/mlp_2", None), ("", None), (None, None)],
    ids=["forward", "backward", "scan-own", "jvp-wrapped", "custom-vjp",
         "innermost", "kernel", "optimizer", "unnamed", "lookalikes",
         "empty", "none"])
def test_region_of_takes_the_innermost_name_through_the_wrappers(path,
                                                                  region):
    assert xregion.region_of(path) == region


def test_shares_add_to_100_and_cover_the_busy_time(red, named):
    shares = named.shares()
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-6)
    # own time never counts a loop and its body twice: it is the busy
    # time, up to the few cycles consecutive operations overlap by
    own_s = sum(named.by_region.values()) / 1e9
    assert own_s == pytest.approx(red.busy_s(), rel=0.02)
    assert {"attn", "mlp", "head", "optimizer", "layers",
            "flash_attention", "ragged_paged_attention"} <= set(shares)
    assert xregion.COLLECTIVE not in shares     # one chip
    assert shares.get(xregion.UNSCOPED, 0.0) < 25.0


def test_backward_operations_resolve_to_their_block(named, red):
    """``transpose(jvp(layers))/while/body/.../attn`` is ``attn``: were the
    wrappers not read through, the backward half of the step (two thirds
    of a block's products) would be missing from the blocks."""
    s = named.shares()
    blocks = s["attn"] + s["mlp"] + s["flash_attention"]
    assert blocks > 2 * s["head"] > 0


def test_kernels_are_found_by_region_as_by_their_source_file(red, named):
    for region, stem in (("flash_attention", "attention"),
                         ("ragged_paged_attention",
                          "ragged_paged_attention")):
        by_file = red.kernel_s({stem})
        by_name = named.kernel_s(region)
        assert by_name[1] == by_file[1] > 0
        assert by_name[0] == pytest.approx(by_file[0], rel=1e-9)


def test_what_no_path_names_is_listed_by_name(named):
    """The whole-pool layout copies and the ``while`` itself carry no
    path; they are the unscoped rest, listed by what they are."""
    assert {"copy", "while"} <= set(named.ops[xregion.UNSCOPED])
    for region, ns in named.by_region.items():
        assert sum(named.ops[region].values()) == ns


def test_phases_partition_the_rounds(named):
    rounds = named.tick_phase_ms()
    assert len(rounds) >= 4
    numbers = [n for _, _, n in named.ticks]
    assert numbers == sorted(numbers) and None not in numbers
    for (s, e, _), r in zip(named.ticks, rounds):
        assert set(r) == set(xregion.PHASES)
        assert sum(r.values()) <= (e - s) / 1e6
        assert r["engine.sync"] > 0


def test_idle_under_phases_plus_the_rest_is_the_idle_time(red, named):
    idle = named.idle_by_phase()
    assert set(idle) <= set(xregion.PHASES) | {xregion.IN_TICK,
                                               xregion.OUTSIDE}
    assert sum(idle.values()) / 1e9 == pytest.approx(
        red.window_s - red.busy_s(), rel=1e-6)
    # the chip waits while the host packs and dispatches, and the
    # training steps of this trace idle outside any round
    assert idle["engine.dispatch"] > 0 and idle[xregion.OUTSIDE] > 0


def _ctx(path, spans):
    red = xplane.Reduction(path, host_spans=spans)
    notes = []
    return types.SimpleNamespace(obs={"xplane": red}, note=notes.append,
                                 notes=notes)


def _read(name, ctx):
    how = harness.load_json("layer_metrics", name + ".json")
    return harness.load_module("readers", how["reader"]).read(how, ctx)


def test_readers_on_the_named_trace(red):
    ctx = _ctx(TRACE, ("train_step", "engine_step"))
    train = [_read(n + ".train", ctx) for n in
             ("attn_share", "mlp_share", "head_share", "optimizer_share",
              "unscoped_share")]
    assert all(v is not None and v >= 0 for v in train)
    assert sum(train) == pytest.approx(100.0, abs=1e-6)   # no collectives
    docs = [_read(n + ".docs", ctx) for n in
            ("attn_share", "mlp_share", "layers_own_share",
             "unscoped_share")]
    assert sum(docs) == pytest.approx(100.0, abs=1e-6)
    window = red.window_s
    sched = _read("idle_under_sched_share.docs", ctx)
    disp = _read("idle_under_dispatch_share.docs", ctx)
    assert 0 <= sched and 0 < disp
    assert sched + disp < 100.0 * (window - red.busy_s()) / window
    assert _read("sched_host_ms_p50.docs", ctx) > 0
    assert _read("dispatch_host_ms_p50.docs", ctx) > 0
    assert any("idle by phase" in n for n in ctx.notes)
    assert any("device time by region" in n for n in ctx.notes)


def test_readers_give_nothing_for_a_program_that_names_nothing():
    """The parent commit's program: no region, no engine span.  Every new
    metric is left out, and nothing raises."""
    ctx = _ctx(OLD, ("train_step", "engine_step"))
    for name in ("attn_share.train", "unscoped_share.train",
                 "layers_own_share.docs", "unscoped_share.docs",
                 "sched_host_ms_p50.docs", "idle_under_sched_share.docs",
                 "dispatch_host_ms_p50.docs"):
        assert _read(name, ctx) is None
    ctx = types.SimpleNamespace(obs={}, note=print)     # not traced
    assert _read("attn_share.train", ctx) is None
    assert _read("sched_host_ms_p50.docs", ctx) is None


def test_collectives_are_a_class_of_their_own_and_say_whose_path(red):
    """On four chips: an ``all-gather`` XLA inserted for a sharded weight
    carries its consumer's path; it and the asynchronous halves count as
    collectives, and which region they named is kept."""
    dev = xplane.DeviceOps("/device:TPU:0")
    path = "jit(step)/jvp(layers)/while/body/closed_call/%s/dot_general:"
    ops = {"%all-gather.1 = bf16[8]{0} all-gather(bf16[2]{0} %p)": "mlp",
           "%async-collective-done.3 = bf16[8]{0} async-done(%s)": "attn",
           "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %a)": "attn"}
    for i, name in enumerate(ops):
        dev.self_ns[name] = 100 * (i + 1)
        dev.leaves.append((red.t0 + 1000 * i, red.t0 + 1000 * i + 100, name))
    fake = types.SimpleNamespace(
        t0=red.t0, t1=red.t1, path=TRACE, devices=[dev],
        meta={dev.name: {n: {"tf_op": path % r} for n, r in ops.items()}},
        is_collective=lambda d, n: "all-gather" in n)
    named = xregion.Named(fake)
    assert dict(named.by_region) == {xregion.COLLECTIVE: 300, "attn": 300}
    assert dict(named.collective_regions) == {"mlp": 100, "attn": 200}
    assert named.shares()[xregion.COLLECTIVE] == pytest.approx(50.0)
