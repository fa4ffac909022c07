"""The trace reduction (benchmark/trace_reduce.py): on hand-made planes,
and on a small trace recorded on the v5e and committed as a fixture
(8 pairs x 241 nodes, three merge_wave calls in the window)."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import harness, trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "merge_v5e.xplane.pb.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


def _planes():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 1000, 9000),
        ("tick", 1000, 3000), ("wait", 4000, 2000), ("offer", 6000, 500)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 1500, 1000), ("fusion.2", 2000, 1000),
                    ("copy", 7000, 1000), ("early", 0, 500)],
        "XLA Modules": [("jit_step", 1500, 9000)]})
    return [host, dev, _plane("/host:metadata", {})]


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = trace_reduce.reduce_planes(_planes(), harness.WINDOW,
                                     harness.ANNOTATIONS)
    assert red["window_s"] == 9000 / 1e9
    assert red["busy_s"] == (1500 + 1000) / 1e9   # [1500,3000) + [7000,8000)
    assert red["n_devices"] == 1 and red["n_events"] == 3
    assert red["breakdown"]["device_ops"][0][0] in ("fusion.1", "fusion.2")


def test_idle_gaps_go_to_what_the_host_was_doing():
    red = trace_reduce.reduce_planes(_planes(), harness.WINDOW,
                                     harness.ANNOTATIONS)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # [1000,1500) mid 1250 in tick; [3000,7000) mid 5000 in wait;
    # [8000,10000) mid 9000 in nothing
    assert gaps == {"tick": 500 / 1e9, "wait": 4000 / 1e9,
                    "other": 2000 / 1e9}


def test_a_trace_without_one_window_is_refused():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="window"):
        trace_reduce.reduce_planes(planes, harness.WINDOW,
                                   harness.ANNOTATIONS)


def test_op_names_are_shortened():
    assert trace_reduce.op_name("%fusion.4 = s32[8]{0} fusion(%p)") == \
        "%fusion.4"
    assert trace_reduce.op_name("copy") == "copy"


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]


def test_the_recorded_chip_trace():
    red = trace_reduce.reduce_file(FIXTURE, harness.WINDOW,
                                   harness.ANNOTATIONS)
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["spans"]["merge_wave"] == 3
    ops = red["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and all(t > 0 for _n, t in ops)
    assert all(n.startswith("%") and " " not in n for n, _t in ops)
    labels = {n for n, _t in red["breakdown"]["idle_gaps"]}
    assert labels <= set(harness.ANNOTATIONS) | {"other"}
    assert "wait" in labels
