"""The reduction by the program's spans and phase scopes
(benchmark/trace_spans.py): on hand-made planes, on the recorded chip
fixture, and through whole traced runs of the tiny cells."""

import json
import os
import time
from types import SimpleNamespace as NS

import pytest

import bench_tiny

from benchmark import harness, spans, trace_reduce, trace_spans

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "merge_v5e.xplane.pb.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e[0], start_ns=e[1], duration_ns=e[2],
                               stats=e[3] if len(e) > 3 else [])
                            for e in evs])
        for ln, evs in lines.items()])


def _serve_planes():
    """A window with a tick whose idle time falls at three depths of
    program spans: serve.tick > serve.apply > sync.apply_delta."""
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 10000),
        ("tick", 0, 8000),
        ("serve.tick", 100, 7800, [("ops", 3), ("batches", 2),
                                   ("wait_us_sum", 40), ("kind", "x")]),
        ("serve.apply", 200, 3000, [("ops", 3), ("batches", 2)]),
        ("sync.apply_delta", 300, 1000, [("ops", 2)]),
        ("sync.apply_delta", 1500, 1000, [("ops", 1)]),
        ("serve.waves", 4000, 3500, [("tenants", 1), ("full", 1)]),
        ("serve.tick", 20000, 10, [("ops", 5)]),        # after the window
        ("not.in.catalogue", 300, 50),
        ("wait", 8000, 2000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("%fusion.1", 5000, 1000)],
        "XLA Modules": [("jit_step(1)", 5000, 1000)]})
    return [host, dev]


def _reduce(planes, scopes=None):
    return trace_spans.reduce_planes(planes, harness.WINDOW,
                                     harness.ANNOTATIONS,
                                     spans.PROGRAM_SPANS, scopes)


def test_program_spans_in_the_window_with_summed_stats():
    red = _reduce(_serve_planes())
    got = red["program_spans"]
    assert set(got) == {"serve.tick", "serve.apply", "sync.apply_delta",
                        "serve.waves"}
    assert got["serve.tick"]["count"] == 1      # the late one is outside
    assert got["serve.tick"]["seconds"] == pytest.approx(7800 / 1e9)
    # integer stats sum; strings are not summed
    assert got["serve.tick"]["stats"] == {"ops": 3, "batches": 2,
                                          "wait_us_sum": 40}
    assert got["sync.apply_delta"]["count"] == 2
    assert got["sync.apply_delta"]["stats"] == {"ops": 3}
    assert got["serve.waves"]["stats"] == {"tenants": 1, "full": 1}


def test_idle_gaps_go_to_the_innermost_covering_span():
    red = _reduce(_serve_planes())
    gaps = dict(red["breakdown"]["idle_gaps"])
    # idle [0,5000): its middle, 2500, lies in serve.apply (the second
    # delta ends there); idle [6000,10000): 8000 is where wait starts
    assert gaps == {"serve.apply": 5000 / 1e9, "wait": 4000 / 1e9}


def test_innermost_labels_three_levels_exactly():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 1000), ("tick", 0, 1000),
        ("serve.tick", 0, 1000), ("serve.apply", 100, 500),
        ("sync.apply_delta", 200, 100)]})
    dev = _plane("/device:TPU:0", {"XLA Ops": [
        ("%x", 0, 100), ("%y", 140, 50), ("%z", 300, 600)]})
    red = _reduce([host, dev])
    gaps = dict(red["breakdown"]["idle_gaps"])
    # [100,140) mid 120 in serve.apply; [190,300) mid 245 in the delta;
    # [900,1000) mid 950 in serve.tick only
    assert gaps == {"serve.apply": 40 / 1e9, "sync.apply_delta": 110 / 1e9,
                    "serve.tick": 100 / 1e9}


def _merge_planes():
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 10000), ("merge_wave", 0, 10000),
        ("wave.merge", 0, 10000, [("pairs", 4)])]})
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_kernel(11)", 1000, 4000),
                        ("jit_digest(22)", 6000, 2000)],
        # %while.1 holds %fusion.2 (both phase E); the digest module
        # has its own %while.1, which no scope names
        "XLA Ops": [("%while.1 = s32[] while(...)", 1000, 3000),
                    ("%fusion.2", 1500, 1000),
                    ("%fusion.3", 4000, 500),
                    ("%while.1", 6000, 2000)]})
    return [host, dev]


SCOPES = {("jit_kernel", "%while.1"): "v5.E",
          ("jit_kernel", "%fusion.2"): "v5.E",
          ("jit_kernel", "%fusion.3"): "v5.F"}


def test_scopes_are_keyed_by_module_and_count_nested_ops_once():
    red = _reduce(_merge_planes(), SCOPES)
    assert red["device_scopes"] == {"v5.E": pytest.approx(3000 / 1e9),
                                    "v5.F": pytest.approx(500 / 1e9)}
    ops = dict(red["breakdown"]["device_ops"])
    assert ops == {"v5.E/%while.1": 3000 / 1e9, "v5.E/%fusion.2": 1000 / 1e9,
                   "v5.F/%fusion.3": 500 / 1e9, "%while.1": 2000 / 1e9}


def test_without_a_map_op_names_are_trace_reduce_s():
    planes = _merge_planes()
    mine = _reduce(planes)
    theirs = trace_reduce.reduce_planes(planes, harness.WINDOW,
                                        harness.ANNOTATIONS)
    assert mine["device_scopes"] == {}
    assert mine["breakdown"]["device_ops"] == \
        theirs["breakdown"]["device_ops"]
    # the idle gaps now name the program span inside merge_wave
    assert dict(mine["breakdown"]["idle_gaps"]) == {
        "wave.merge": pytest.approx(
            sum(t for _n, t in theirs["breakdown"]["idle_gaps"]))}


def test_hlo_text_gives_the_scope_map():
    text = "\n".join([
        "HloModule jit_batched_merge_weave_v5, is_scheduled=true",
        "ENTRY %main {",
        '  %while.27 = (s32[], s32[4]{0}) while(%t), condition=%c, '
        'body=%b, metadata={op_name="jit(f)/vmap(v5.E)/while"}',
        '  ROOT %fusion.9 = s32[4]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/vmap(v5.A)/jit(g)/vmap(v5.F)/add"}',
        "  %fusion.10 = s32[4]{0} fusion(%p), kind=kLoop",
        '  %copy.1 = s32[4]{0} copy(%p), metadata={op_name="jit(f)/x"}',
        "}"])
    assert trace_spans.hlo_scopes(text) == (
        "jit_batched_merge_weave_v5",
        {"%while.27": "v5.E", "%fusion.9": "v5.F"})
    assert trace_spans.scope_map([text])[
        ("jit_batched_merge_weave_v5", "%while.27")] == "v5.E"
    assert trace_spans.module_name("jit_f(7288864446063331437)") == "jit_f"


def test_the_recorded_chip_trace_reduces_as_before():
    """The committed fixture predates the program's spans: without a
    map, ops and idle gaps read as trace_reduce reads them."""
    mine = trace_spans.reduce_file(FIXTURE, harness.WINDOW,
                                   harness.ANNOTATIONS, spans.PROGRAM_SPANS)
    theirs = trace_reduce.reduce_file(FIXTURE, harness.WINDOW,
                                      harness.ANNOTATIONS)
    assert mine["window_s"] == theirs["window_s"]
    assert mine["breakdown"] == theirs["breakdown"]
    assert mine["program_spans"] == {}


MERGE_CELLS = ("merge.fleet1024x10k.div1000", "merge.fleet1024x10k.div8")
NEW_MERGE_METRICS = ("views_ms.merge", "upload_ms.merge", "fetch_ms.merge",
                     "scatter_ms.merge")


@pytest.mark.parametrize("cell", MERGE_CELLS)
def test_traced_tiny_runs_report_the_span_metrics(tmp_path, capsys,
                                                  monkeypatch, cell):
    """A traced run of a tiny merge cell reports every metric that
    reads the program's spans. The CPU trace has no TPU plane for
    trace_reduce and the CPU no peaks for the roofline, so the
    reduction and the device kind are stand-ins here."""
    root = bench_tiny.make_root(tmp_path)
    real_info = harness.device_info

    def v5e_info(chips, check_device):
        return dict(real_info(chips, check_device), kind="TPU v5 lite")

    monkeypatch.setattr(harness, "device_info", v5e_info)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *_a: {
        "window_s": 1.0, "busy_s": 0.5, "n_devices": 1, "n_events": 1,
        "spans": {}, "breakdown": {"device_ops": [], "idle_gaps": []}})
    code = harness.run_cell(cell, 2**31 + 23, 0.5, True,
                            time.perf_counter(), root=root,
                            check_device=False)
    assert code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out
    metrics = out["metrics"]
    for name in NEW_MERGE_METRICS:
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] > 0, name
    spec = harness.Cell(root, cell)
    assert set(NEW_MERGE_METRICS) <= {
        m["name"] for m in spec.metrics("per_layer")}
