"""The reduction, on a small trace recorded on a v5e by
``benchmarks/tools/record_trace.py`` (three training steps and a few
ragged serving ticks of a two-layer GPT)."""

import os

import pytest

from benchmarks.lib import xplane, xproto

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "trace.xplane.pb")
SPANS = ("train_step", "fetch_loss", "engine_step", "add_requests")


@pytest.fixture(scope="module")
def red():
    return xplane.Reduction(TRACE, host_spans=SPANS)


def test_intervals():
    total, merged = xplane.union_ns([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert total == 23 and merged == [[0, 12], [20, 31]]
    assert xplane.subtract_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert xplane.subtract_ns([(0, 10)], []) == 10
    assert xplane.subtract_ns([(0, 10)], [(0, 10)]) == 0


def test_metadata_names_the_source_of_a_kernel():
    meta = xproto.event_metadata(TRACE)["/device:TPU:0"]
    sources = {os.path.basename(str(m.get("source", "")).split(":")[0])
               for name, m in meta.items() if "tpu_custom_call" in name}
    assert sources == {"attention.py", "ragged_paged_attention.py"}


def test_busy_is_inside_the_window(red):
    assert len(red.devices) == 1
    assert 0 < red.busy_s() < red.window_s
    assert 0 < red.idle_share() < 1


def test_kernels_are_found_by_their_source_file(red):
    ragged_s, ragged_calls = red.kernel_s({"ragged_paged_attention"})
    flash_s, flash_calls = red.kernel_s({"attention"})
    # 6 ticks x 2 layers; 3 steps x 2 layers x (forward, dQ, dK/dV)
    assert ragged_calls == 12 and flash_calls == 18
    assert 0 < ragged_s < red.busy_s() and 0 < flash_s < red.busy_s()
    assert red.kernel_s({"no_such_kernel"}) == (0.0, 0.0)


def test_own_time_never_counts_a_loop_and_its_body_twice(red):
    top = red.top_ops(50)
    # consecutive operations may overlap by a few cycles
    assert sum(s for _, s in top) <= red.busy_s() * 1.02
    names = [n for n, _ in top]
    assert "ragged_paged_attention" in names and "attention" in names


def test_one_chip_has_no_collectives(red):
    assert red.collective_s() == (0.0, 0.0)


def test_idle_gaps_are_named_by_the_benchmarks_spans(red):
    gaps = dict(red.idle_gaps())
    assert set(gaps) <= set(SPANS) | {"between_spans"}
    assert "engine_step" in gaps
    assert sum(gaps.values()) == pytest.approx(
        red.window_s - red.busy_s(), rel=1e-6)
