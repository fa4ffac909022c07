"""The tree cells' comparison, driven through the harness on the CPU at a
tiny size (13 editors, so byes at two levels; 40 values; 6 ops each): a
sound run is correct; the control (the reference with concurrent
siblings in ascending order, in the program's place), a call that
leaves an editor out, a tree that hands back None (merge_all then
runs the host merge_many) and a tree that runs, then gives up on a token
overflow, come out not correct."""

import json
import os
import time

import pytest

import bench_tiny

from benchmark import harness, trace_reduce

CELLS = ("tree.editors100x10k.append", "tree.editors100x10k.edit")
TREE_METRICS = ("assemble_ms.tree", "materialize_ms.tree",
                "frontier_ms.tree", "device_busy_ms.tree", "tree_roofline",
                "device_idle_pct.tree")


def _root(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "editors100x10k.json")
    cfg = harness.load_json(path)
    cfg.update(editors=13, doc_values=40, ops_per_editor=6)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


def _run(tmp_path, capsys, cell, control=False, plant=None, trace=False):
    root = _root(tmp_path)
    drv = harness.Cell(root, cell).driver()
    if plant is not None:
        plant(drv)
    code = harness.run_cell(cell, 2**31 + 91, 0.5, trace,
                            time.perf_counter(), root=root,
                            check_device=False, control=control, driver=drv)
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _one_editor_left_out(drv):
    real = drv._call
    drv._call = lambda replicas: real(list(replicas[:-1]))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell)
    assert out["correct"] is True, out
    assert out["compared"] == {
        "root_wrong": {"value": 0, "limit": 0},
        "roots_unstable": {"value": 0, "limit": 0},
        "calls_off_tree": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"merge_ms", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell, control=True)
    assert out["correct"] is False
    assert out["compared"]["root_wrong"]["value"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_an_editor_left_out_is_not_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell, plant=_one_editor_left_out)
    assert out["correct"] is False
    assert out["compared"]["root_wrong"]["value"] == 1
    assert out["failed"] == out["attempted"]


def test_a_call_off_the_tree_is_not_correct(tmp_path, capsys, monkeypatch):
    from cause_tpu.parallel import tree

    monkeypatch.setattr(tree, "merge_all_tree", lambda handles: None)
    out = _run(tmp_path, capsys, CELLS[0])
    assert out["correct"] is False
    assert out["compared"]["calls_off_tree"] == {"value": 1, "limit": 0}
    # the host path weaves the same document
    assert out["compared"]["root_wrong"]["value"] == 0


def test_a_tree_that_gives_up_is_not_correct(tmp_path, capsys, monkeypatch):
    """The tree runs (its ``tree.converge`` span is recorded), overflows
    a starved token budget and hands the call to the host: the counted
    ``tree.fallback`` makes the call off the tree."""
    from cause_tpu import benchgen

    monkeypatch.setattr(benchgen, "v5_token_budget", lambda lanes: 1)
    out = _run(tmp_path, capsys, CELLS[1])
    assert out["correct"] is False
    assert out["compared"]["calls_off_tree"] == {"value": 1, "limit": 0}
    assert out["compared"]["root_wrong"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_tiny_runs_report_the_tree_metrics(tmp_path, capsys,
                                                  monkeypatch, cell):
    """A traced run reports all six per-layer metrics. The CPU trace has
    no TPU plane for trace_reduce and the CPU no peaks for the roofline,
    so the reduction and the device kind are stand-ins here."""
    real_info = harness.device_info

    def v5e_info(chips, check_device):
        return dict(real_info(chips, check_device), kind="TPU v5 lite")

    monkeypatch.setattr(harness, "device_info", v5e_info)
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *_a: {
        "window_s": 1.0, "busy_s": 0.5, "n_devices": 1, "n_events": 1,
        "spans": {}, "breakdown": {"device_ops": [], "idle_gaps": []}})
    out = _run(tmp_path, capsys, cell, trace=True)
    assert out["correct"] is True, out
    metrics = out["metrics"]
    assert set(metrics) == set(TREE_METRICS)
    for name in TREE_METRICS:
        assert metrics[name]["value"] > 0, name
    assert 0 < metrics["tree_roofline"]["value"] < 100
