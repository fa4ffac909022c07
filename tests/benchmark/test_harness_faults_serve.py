"""The serve cells' comparison, driven through the harness on the CPU at
a tiny size: a sound run is correct; the control (the reference with
concurrent siblings in ascending order, in the service's place) and
each fault planted under the timed path come out not correct."""

import json
import time

import pytest

import bench_tiny

from benchmark import harness

CELLS = ("serve.t64x10k.append", "serve.t64x10k.edit")


def _run(tmp_path, capsys, cell, control=False, plant=None):
    root = bench_tiny.make_root(tmp_path)
    drv = harness.Cell(root, cell).driver()
    if plant is not None:
        real_warmup = drv.warmup

        def warmup(state, ctx):
            real_warmup(state, ctx)
            plant(state)       # the window's path, not the warm-up's

        drv.warmup = warmup
    code = harness.run_cell(cell, 2**33 + 5, 1.0, False,
                            time.perf_counter(), root=root,
                            check_device=False, control=control, driver=drv)
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _state_unchanged(state):
    """Ticks drain the queue and apply nothing."""
    queue, svc = state["queue"], state["svc"]

    def tick(max_ops=None):
        n = sum(e.ops for e in queue.drain(svc.d_max))
        return {"ops": n}

    svc.tick = tick


def _half_batch(state):
    """Every other drained batch is lost between the queue and the
    apply."""
    queue = state["queue"]
    real = queue.drain
    seen = [0]

    def drain(*a, **k):
        kept = []
        for e in real(*a, **k):
            seen[0] += 1
            if seen[0] % 2:
                kept.append(e)
        return kept

    queue.drain = drain


def _answer_altered(state):
    """One op's value changes where the tick applies it."""
    from cause_tpu import sync

    svc = state["svc"]
    real = sync.apply_delta

    def apply_delta(handle, nodes, *a, **k):
        nodes = dict(nodes)
        nid = max(nodes)
        cause, value = nodes[nid]
        if isinstance(value, str):
            nodes[nid] = (cause, value + "!")
        return real(handle, nodes, *a, **k)

    real_apply = svc._apply_batches

    def apply_batches(*a, **k):
        sync.apply_delta = apply_delta
        try:
            return real_apply(*a, **k)
        finally:
            sync.apply_delta = real

    svc._apply_batches = apply_batches


def _journal_skipped(state):
    """Every other acknowledged batch never reaches the journal."""
    journal = state["queue"].journal
    real = journal.append
    seen = [0]

    def append(uuid, site, items, *a, **k):
        seen[0] += 1
        if seen[0] % 2:
            return real(uuid, site, items, *a, **k)
        journal._seq += 1          # the seq is handed out all the same
        return journal._seq

    journal.append = append


def _fsync_skipped(state):
    """The journal writes its records and never fsyncs them."""
    state["queue"].journal.fsync_policy = "none"


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell)
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    compared = out["compared"]
    assert compared == {k: {"value": 0, "limit": 0} for k in (
        "ops_missing", "tenants_wrong", "unjournaled_batches",
        "unsynced_batches")}
    assert set(out["metrics"]) == {"converged_ops_per_s", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tmp_path, capsys, cell):
    out = _run(tmp_path, capsys, cell, control=True)
    assert out["correct"] is False
    assert out["compared"]["tenants_wrong"]["value"] == 4


@pytest.mark.parametrize("plant,number", [
    (_state_unchanged, "tenants_wrong"), (_half_batch, "ops_missing"),
    (_answer_altered, "tenants_wrong"),
    (_journal_skipped, "unjournaled_batches"),
    (_fsync_skipped, "unsynced_batches")],
    ids=["state_unchanged", "half_batch", "answer_altered",
         "journal_skipped", "fsync_skipped"])
def test_planted_fault_is_not_correct(tmp_path, capsys, plant, number):
    out = _run(tmp_path, capsys, CELLS[0], plant=plant)
    assert out["correct"] is False
    got = out["compared"][number]
    assert got["value"] > got["limit"], out["compared"]
