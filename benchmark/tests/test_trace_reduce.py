"""``trace_reduce`` on small traces recorded on the chip
(``record_trace_fixture.py``, kept under ``fixtures/``), one chip and four,
and its interval arithmetic on hand-made events."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import allreduce_time_share

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def test_short_name():
    text = ("%fwd.36 = (bf16[64,2560,128]{2,1,0}, f32[64,2560,128]) "
            "custom-call(...), custom_call_target=\"tpu_custom_call\"")
    assert tr.short_name(text) == "fwd"
    assert tr.short_name("%all-reduce-start.2 = f32[] all-reduce-start()") == \
        "all-reduce-start"
    assert tr.short_name("%while.2 = (s32[]) while(...)") == "while"


def test_union_merges_overlaps():
    total, merged = tr._union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [[0, 3], [5, 8]]
    assert tr._union([]) == (0.0, [])


def test_self_times_take_the_body_out_of_the_while():
    events = [(0, 100, "%while.1 = w"), (10, 40, "%fwd.3 = k"),
              (40, 90, "%fusion.7 = f"), (45, 50, "%copy.1 = c")]
    self_ns = tr._self_times(events)
    assert self_ns == {"while": 20.0, "fwd": 30.0, "fusion": 45.0, "copy": 5.0}


@pytest.mark.parametrize("chips", [1, 4])
def test_recorded_trace(chips):
    path = os.path.join(FIXTURES, f"trace_{chips}chip.xplane.pb")
    reduced = tr.reduce_file(path, chips)
    assert reduced.n_devices == chips
    # a mean over the cell's devices: above 0, never above the window
    assert 0.0 < reduced.busy_s <= reduced.window_s
    per_device = [b[0] * 1e-9 for b in reduced._busy.values()]
    assert all(0.0 < b <= reduced.window_s for b in per_device)
    assert reduced.busy_s == pytest.approx(sum(per_device) / chips)
    assert sum(per_device) > reduced.busy_s or chips == 1
    bd = reduced.breakdown()
    assert bd["device_ops"] and len(bd["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in bd["device_ops"])
    # the pauses between steps are idle and are named by the host's span
    assert any(name == "bench/pause" for name, _ in bd["idle_gaps"])
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        reduced.window_s - per_device[0], rel=1e-6)

    class View:
        trace = reduced
    share = allreduce_time_share.read(View)
    if chips == 1:
        assert share is None  # nothing to read: the metric is left out
    else:
        assert 0.0 < share < 100.0


def test_a_cell_device_that_ran_nothing_counts_as_idle():
    path = os.path.join(FIXTURES, "trace_1chip.xplane.pb")
    one = tr.reduce_file(path, 1)
    two = tr.reduce_file(path, 2)
    assert two.n_devices == 2
    assert two.busy_s == pytest.approx(one.busy_s / 2)
