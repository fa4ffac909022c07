"""The merge cells' comparison, driven through the harness on the CPU at
a tiny size: a sound run is correct; the control (the reference with
concurrent siblings in ascending order, in the program's place) and
each fault planted under the timed path come out not correct."""

import time

import numpy as np
import pytest

import bench_tiny

from benchmark import harness

CELLS = ("merge.fleet1024x10k.div1000", "merge.fleet1024x10k.div8")


def _run(tmp_path, capsys, cell, control=False, plant=None):
    root = bench_tiny.make_root(tmp_path)
    drv = harness.Cell(root, cell).driver()
    if plant is not None:
        plant(drv)
    code = harness.run_cell(cell, 2**31 + 77, 1.0, False,
                            time.perf_counter(), root=root,
                            check_device=False, control=control, driver=drv)
    assert code == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return harness.json.loads(line)


def _state_unchanged(drv):
    real = drv._call
    drv._call = lambda pairs: real([(a, a) for a, _b in pairs])


def _half_batch(drv):
    real = drv._call

    def call(pairs):
        h = len(pairs) // 2
        return real(list(pairs[:h]) + [(a, a) for a, _b in pairs[h:]])

    drv._call = call


def _answer_altered(drv):
    real = drv._call

    def call(pairs):
        res = real(pairs)
        rank = np.array(res.rank)
        kept = np.flatnonzero(rank[0] < 2 * res.capacity)
        i, j = kept[1], kept[-1]
        rank[0, i], rank[0, j] = rank[0, j], rank[0, i]
        res.rank = rank
        return res

    drv._call = call


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell)
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    assert out["compared"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"merge_ms", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell, control=True)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] == 4


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_planted_fault_is_not_correct(tmp_path, capsys, plant):
    out = _run(tmp_path, capsys, CELLS[0], plant=plant)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_stderr_ends_with_the_compared_numbers(tmp_path, capsys):
    root = bench_tiny.make_root(tmp_path)
    harness.run_cell(CELLS[1], 5, 0.5, False, time.perf_counter(),
                     root=root, check_device=False)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2:] == ["compared rows_wrong=0 limit=0",
                        "compared rows_unstable=0 limit=0"]
