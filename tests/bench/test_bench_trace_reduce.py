"""The trace reduction on a small trace recorded on a TPU v5 lite: two
rounds of a bf16 matmul, a 3 ms host sleep and a fused fleet tick
[4, 20, 128, 6], each inside the harness's annotations, the whole inside
`bench.window` (`data/v5e_small.xplane.pb`)."""
from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_trace(str(TRACE))


def test_busy_and_idle_within_the_window(summary):
    assert summary.devices == 1
    assert 0.006 < summary.window_s < 1.0            # two 3 ms sleeps at least
    assert 0.0 < summary.busy_s < summary.window_s
    assert 0.0 < summary.idle_share < 1.0


def test_device_time_per_module(summary):
    assert summary.module_calls["jit__fused_tick_impl"] == 2
    # device and host clocks of a trace agree to about a millisecond, so a
    # program that starts at the window's first instant may fall outside it
    assert summary.module_calls["jit__lambda"] in (1, 2)
    assert all(v > 0 for v in summary.module_s.values())
    # op time inside the window is no more than the busy union can hold
    assert sum(s for _, s in summary.top_ops) >= summary.busy_s * 0.5


def test_device_time_of_every_op(summary):
    # the heaviest ops are the head of the whole table, which readers of an
    # op outside them read
    assert summary.top_ops == sorted(([k, v] for k, v in summary.op_s.items()),
                                     key=lambda x: -x[1])[:10]
    assert len(summary.op_s) >= len(summary.top_ops) and all(
        v > 0 for v in summary.op_s.values())
    assert sum(summary.op_s.values()) >= summary.busy_s


def test_longest_gap_is_named_by_the_host_annotation(summary):
    label, seconds = summary.idle_gaps[0]
    assert label == "bench.sleep" and seconds >= 0.003
    assert summary.idle_gaps == sorted(summary.idle_gaps, key=lambda g: -g[1])
    assert len(summary.idle_gaps) <= 10 and len(summary.top_ops) <= 10
    assert summary.idle_by_label["bench.sleep"] >= 0.006


def test_names_and_union():
    assert tr.module_name("jit__fused_tick_impl(5394440332057533963)") == "jit__fused_tick_impl"
    assert tr.op_name("%fusion.12 = f32[8,20]{1,0} fusion(f32[8] %p)") == "fusion"
    assert tr.op_name("%convert_reduce_fusion = f32[] fusion()") == "convert_reduce_fusion"
    assert tr.merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
