"""cause_tpu.obs.costmodel — the wave cost model and the gap report.

Pins the PR-6 contract: obs-off no-op invariance (zero records, zero
cost-model state, byte-identical program-cache keys), per-wave
``wave.cost`` events joining dispatch accounting to divergence
evidence (merge_wave tokens, FleetSession delta lanes, sync delta
ops), the dispatch-floor budget arithmetic as computed fields, the
cost-vs-divergence slope with its O(doc)-vs-O(delta) verdict, the
ledger row ``cost`` extension + ``--kind gap`` summary rows, and the
``python -m cause_tpu.obs gap`` CLI over the committed ledger.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cause_tpu as c
from cause_tpu import obs
from cause_tpu import sync
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.obs import costmodel, ledger
from cause_tpu.obs import semantic
from cause_tpu.parallel import merge_wave
from cause_tpu.parallel.session import FleetSession
from cause_tpu.benchgen import _scalar_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test starts from a clean, DISABLED obs state and empty
    cost-model/semantic state, and leaves none behind."""
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING", "CAUSE_TPU_LEDGER"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    semantic.reset()
    costmodel.reset()
    yield
    obs.reset()
    semantic.reset()
    costmodel.reset()


def _fleet_base(n=20):
    base = CausalList(c_list.weave(
        c.clist(weaver="jax").extend(["w"] * n).ct
    ))
    base.ct.lanes.segments()
    return base


def _replica_pair(base, edits_a=("A",), edits_b=("B",)):
    a = CausalList(base.ct.evolve(site_id=new_site_id()))
    b = CausalList(base.ct.evolve(site_id=new_site_id()))
    for v in edits_a:
        a = a.conj(v)
    for v in edits_b:
        b = b.conj(v)
    return a, b


def _wave_costs():
    return [e["fields"] for e in obs.events()
            if e.get("ev") == "event" and e.get("name") == "wave.cost"]


# ----------------------------------------------------- obs-off no-op


def test_obs_off_is_invariant(tmp_path):
    """The PR-1 contract extended to the cost model: with obs
    disabled, a full instrumented pass (sync, a merge wave, a session
    wave) records nothing, keeps no program/pending/window state,
    opens no sink, and leaves the program-cache key mapping
    byte-identical."""
    out = str(tmp_path / "never.jsonl")
    obs.configure(enabled=False, out=out)
    key_before = sorted(_scalar_programs)

    base = _fleet_base()
    a, b = _replica_pair(base)
    sync.sync_pair(a, b)
    merge_wave([(a, b)] * 2)
    sess = FleetSession([(a, b)] * 2)
    sess.wave()
    sess.update([(a.conj("x"), b.conj("y"))] * 2)
    sess.wave()

    assert obs.events() == []
    assert obs.counters_snapshot() == {"counters": {}, "gauges": {}}
    assert not os.path.exists(out)
    # every entry point is inert and leaves no registry state
    assert costmodel.wave_begin("wave") is None
    assert costmodel.wave_cost(uuid="u") is None
    costmodel.record_dispatch("p")
    costmodel.register_program("p", {"flops": 1})
    costmodel.note_delta_ops("u", 3)
    costmodel.note_full_bag("u")
    assert costmodel._PROGRAMS == {}
    assert costmodel._PENDING_OPS == {}
    assert costmodel._PENDING_BAGS == {}
    key_after = sorted(_scalar_programs)
    assert key_after == key_before


def test_obs_off_program_cache_keys_identical(monkeypatch):
    """The dispatch accounting at the benchgen program-cache call site
    must never touch the cache keys: the same lanes hit the SAME
    single key obs-off, obs-on, and obs-off again."""
    import jax.numpy as jnp

    from cause_tpu import benchgen

    monkeypatch.setattr(benchgen, "_scalar_programs", {})
    batch = benchgen.batched_pair_lanes(
        n_replicas=2, n_base=20, n_div=4, capacity=64, hide_every=4)
    v5batch = benchgen.batched_v5_inputs(batch, 64)
    args = [jnp.asarray(batch[k] if k in batch else v5batch[k])
            for k in benchgen.LANE_KEYS5]
    u = int(benchgen.v5_token_budget(v5batch))

    obs.configure(enabled=False)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    keys_off = set(benchgen._scalar_programs)
    assert len(keys_off) == 1
    obs.configure(enabled=True)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    assert set(benchgen._scalar_programs) == keys_off
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("costmodel.dispatches", 0) == 1
    assert snap.get("costmodel.dispatches.benchgen", 0) == 1


# ---------------------------------------------------- wave.cost joins


def test_merge_wave_emits_wave_cost():
    """One merge wave, one wave.cost event: dispatches counted with
    distinct program identities, tokens vs lanes as the divergence/doc
    axes, the dispatch-floor budget computed, and the semantic digest
    summary joined on."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 4)
    (f,) = _wave_costs()
    assert f["source"] == "wave" and f["pairs"] == 4
    # kernel + digest = at least 2 device program invocations
    assert f["dispatches"] >= 2
    assert f["programs"] >= 2
    assert f["lanes"] > 0 and 0 < f["tokens"] <= f["token_budget"]
    assert f["wall_ms"] > 0
    assert f["floor_ms"] == costmodel.DISPATCH_FLOOR_MS
    assert f["floor_budget_ms"] == round(
        costmodel.DISPATCH_FLOOR_MS * f["dispatches"], 3)
    assert f["semantic"]["agreed"] is True
    assert f["full_bag"] == 0 and f["delta_ops"] == 0
    snap = obs.counters_snapshot()["counters"]
    assert snap["costmodel.waves"] == 1
    assert snap["costmodel.dispatches"] >= 2
    # Perfetto counter tracks: the per-wave gauges streamed
    gauges = {e["name"] for e in obs.events() if e.get("ev") == "gauge"}
    assert "costmodel.dispatches.wave" in gauges
    assert "costmodel.tokens.wave" in gauges


def test_degenerate_wave_records_zero_dispatches():
    """An all-fallback wave (map pairs ride the host path) still emits
    wave.cost — with zero device dispatches and the fallbacks counted
    as full-bag work. The dispatches>=1 invariant is for
    non-degenerate waves only."""
    from cause_tpu import K
    from cause_tpu.collections.cmap import CausalMap

    obs.configure(enabled=True)
    base = c.cmap().append(K("t"), "x")
    a = CausalMap(base.ct.evolve(site_id=new_site_id())).append(
        K("t"), "a")
    b = CausalMap(base.ct.evolve(site_id=new_site_id())).append(
        K("u"), "b")
    merge_wave([(a, b)])
    (f,) = _wave_costs()
    assert f["dispatches"] == 0 and f["programs"] == 0
    assert f["full_bag"] == 1 and f["lanes"] == 0


def test_session_waves_join_delta_ops():
    """The 8-replica acceptance path: the first session wave rides the
    full upload (full_bag=1, zero delta ops), the post-update wave
    carries EXACTLY the appended lane count as delta_ops — the
    divergence evidence matching what was actually shipped."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    sess = FleetSession([(a, b)] * 4)  # 8 replicas, one document
    sess.wave()
    sess.update([(a.conj("x"), b.conj("y"))] * 4)
    sess.wave()
    costs = _wave_costs()
    assert len(costs) == 2
    first, second = costs
    assert first["source"] == "session"
    assert first["full_bag"] == 1 and first["delta_ops"] == 0
    assert first["dispatches"] >= 2  # kernel + digest
    # 4 pairs x (1 appended lane per replica side) = 8 delta lanes
    assert second["delta_ops"] == 8
    assert second["full_bag"] == 0
    assert second["dispatches"] >= 2
    assert second["semantic"]["agreed"] is True
    # the resident-splice program was dispatched at update time
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("costmodel.dispatches.session", 0) >= 5
    assert snap["session.delta_update"] == 1


def test_sync_delta_ops_flow_into_next_wave_cost():
    """Delta ops noted by the sync layer drain into the document's
    next wave.cost, so the event's divergence evidence matches the
    semantic stream's own sync accounting."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    a2, b2 = sync.sync_pair(a, b)
    synced = sum(e["fields"]["nodes"] for e in obs.events()
                 if e.get("ev") == "event"
                 and e.get("name") == "sync.delta_apply")
    assert synced > 0
    merge_wave([(a2, b2)])
    (f,) = _wave_costs()
    assert f["delta_ops"] == synced
    # drained: a second wave on the same document starts from zero
    merge_wave([(a2, b2)])
    assert _wave_costs()[-1]["delta_ops"] == 0


# ----------------------------------------------------------- analysis


def test_cost_vs_divergence_verdicts():
    flat = [{"delta_ops": d, "wall_ms": 1000.0 + i, "lanes": 20480}
            for i, d in enumerate((10, 100, 400, 800))]
    got = costmodel.cost_vs_divergence(flat)
    assert got["verdict"] == "O(doc)"
    assert got["points"] == 4

    prop = [{"delta_ops": d, "wall_ms": 5.0 + 2.0 * d, "lanes": 20480}
            for d in (10, 100, 400, 800)]
    got = costmodel.cost_vs_divergence(prop)
    assert got["verdict"] == "O(delta)"
    assert got["slope_ms_per_op"] == pytest.approx(2.0, rel=1e-3)
    assert got["corr"] == pytest.approx(1.0, abs=1e-3)

    # floor-dominated but delta-correlated: a perfect fit whose slope
    # moves cost by only ~25% of its mean is still materially
    # insensitive — the verdict is about magnitude, not correlation
    floor = [{"delta_ops": d, "wall_ms": 70.0 + 0.05 * d,
              "lanes": 20480} for d in (0, 100, 200, 400)]
    assert costmodel.cost_vs_divergence(floor)["verdict"] == "O(doc)"

    # full-bag waves are excluded as unmeasured even when the live
    # rows' token count is present
    bagged = [{"tokens": 500, "full_bag": 2, "wall_ms": 9.0},
              {"tokens": 900, "full_bag": 1, "wall_ms": 9.5}]
    assert costmodel.cost_vs_divergence(bagged)["verdict"] \
        == "insufficient-data"

    assert costmodel.cost_vs_divergence([])["verdict"] \
        == "insufficient-data"
    one = [{"delta_ops": 5, "wall_ms": 9.0}]
    assert costmodel.cost_vs_divergence(one)["verdict"] \
        == "insufficient-data"
    # no divergence spread: nothing to regress over
    same = [{"delta_ops": 5, "wall_ms": 9.0},
            {"delta_ops": 5, "wall_ms": 11.0}]
    assert costmodel.cost_vs_divergence(same)["verdict"] \
        == "insufficient-data"


def test_costmodel_digest_and_ledger_row_extension(tmp_path):
    """A sidecar carrying wave.cost events lands its cost-model
    aggregate as the ledger row's ``cost`` field; a stream without
    them leaves the row unchanged (pre-PR-6 shape)."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 2)
    stream = tmp_path / "waves.jsonl"
    obs.export_jsonl(str(stream))
    digest = costmodel.costmodel_digest(obs.events())
    assert digest["waves"] == 1 and digest["dispatches"] >= 2
    assert digest["slope"]["verdict"] == "insufficient-data"

    led = str(tmp_path / "ledger.jsonl")
    row = ledger.ingest_record(
        {"platform": "cpu", "metric": "m", "value": None,
         "kernel": "v5", "config": "t"},
        source="t", obs_jsonl=str(stream), path=led, kind="soak")
    assert row["cost"]["waves"] == 1
    assert row["cost"]["dispatches"] == digest["dispatches"]

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    row2 = ledger.ingest_record(
        {"platform": "cpu", "metric": "m", "value": None},
        source="t2", obs_jsonl=str(empty), path=led, kind="soak")
    assert "cost" not in row2


# --------------------------------------------------------- gap report


def _tpu_row(value_ms=4300.0, single=4356.0):
    return {"schema": 1, "kind": "bench", "source": "bench_tpu_rX.log",
            "platform": "tpu", "fallback": False, "smoke": False,
            "kernel": "v5", "config": "default",
            "metric": "p50 batched merge+weave", "value_ms": value_ms,
            "single_dispatch_ms": single, "quarantined": False}


def test_gap_report_decomposition():
    rows = [
        _tpu_row(),
        # quarantined + smoke rows never headline
        dict(_tpu_row(1.0, 1.0), platform="cpu-fallback",
             quarantined=True),
        dict(_tpu_row(2.0, 2.0), smoke=True),
        dict(_tpu_row(15.0, 17.0), platform="cpu",
             source="bench_cpu.log"),
    ]
    waves = [
        {"ev": "event", "name": "wave.cost", "pid": 1,
         "fields": {"uuid": "u", "source": "session", "pairs": 1024,
                    "lanes": 20480 * 1024, "delta_ops": 50 * 1024,
                    "full_bag": 0, "dispatches": 2, "programs": 2,
                    "wall_ms": 4300.0, "floor_ms": 67.0,
                    "floor_budget_ms": 134.0}},
        {"ev": "event", "name": "wave.cost", "pid": 1,
         "fields": {"uuid": "u", "source": "session", "pairs": 1024,
                    "lanes": 20480 * 1024, "delta_ops": 100 * 1024,
                    "full_bag": 0, "dispatches": 2, "programs": 2,
                    "wall_ms": 4310.0, "floor_ms": 67.0,
                    "floor_budget_ms": 134.0}},
    ]
    rep = costmodel.gap_report(rows, waves)
    head = rep["headline"]
    assert head["platform"] == "tpu" and head["value_ms"] == 4300.0
    assert head["gap_x"] == 43.0
    fl = rep["dispatch_floor"]
    assert fl["dispatches_per_wave"] == 2
    assert fl["floor_budget_ms"] == pytest.approx(134.0)
    assert fl["share_of_single"] == round(67.0 / 4356.0, 4)
    # near-flat cost across an 2x divergence spread: O(doc)
    assert rep["cost_vs_divergence"]["verdict"] == "O(doc)"
    # projection: cost ∝ divergence would shrink the headline to its
    # divergence fraction (floored by the dispatch floor)
    proj = rep["projected"]
    assert proj["headline_ms"] == pytest.approx(
        max(67.0, 4300.0 * (75 / 20480)), rel=0.35)
    assert proj["gap_x"] < head["gap_x"]
    text = costmodel.render_gap(rep)
    assert "43x off target" in text or "43.0" in text.replace("43x", "43.0")
    assert "O(doc)" in text
    # total on empty inputs
    empty = costmodel.gap_report([], [])
    assert empty["headline"] is None
    assert empty["cost_vs_divergence"]["verdict"] == "insufficient-data"
    assert "NO eligible bench row" in costmodel.render_gap(empty)


def test_gap_cli_renders_committed_ledger_and_appends(tmp_path):
    """End to end: an 8-replica session stream + the COMMITTED ledger
    render through `python -m cause_tpu.obs gap`, with the slope
    verdict explicit; --append lands a --kind gap summary row that the
    ledger checker accepts."""
    out = str(tmp_path / "fleet.jsonl")
    obs.configure(enabled=True, out=out)
    base = _fleet_base()
    a, b = _replica_pair(base)
    sess = FleetSession([(a, b)] * 4)
    sess.wave()
    for n in (1, 3):  # varying divergence: the slope has spread
        nxt = [(a, b)] * 4
        for _ in range(n):
            nxt = [(x.conj("x"), y.conj("y")) for x, y in nxt]
        sess.update(nxt)
        sess.wave()
    obs.flush()

    r = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "gap", "--obs", out,
         "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["stream_waves"] == 3
    assert rep["headline"]["platform"] == "tpu"  # committed ledger
    assert rep["cost_vs_divergence"]["verdict"] in ("O(doc)",
                                                    "O(delta)")
    assert rep["dispatch_floor"]["dispatches_per_wave"] >= 2

    # the normal flow appends to the same ledger it reads: start the
    # scratch from the committed trajectory (never mutate the real one)
    led = str(tmp_path / "scratch_ledger.jsonl")
    with open(os.path.join(REPO, "measurements",
                           "ledger.jsonl")) as src:
        committed = src.read()
    with open(led, "w") as dst:
        dst.write(committed)
    n_committed = len(ledger.load(led))
    r2 = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "gap", "--obs", out,
         "--append", "--ledger", led, "--source", "test-gap"],
        capture_output=True, text=True, cwd=REPO)
    assert r2.returncode == 0, r2.stderr
    rows = ledger.load(led)
    assert len(rows) == n_committed + 1
    row = rows[-1]
    assert row["kind"] == "gap" and row["source"] == "test-gap"
    assert row["gap"]["cost_vs_divergence"]["verdict"] in (
        "O(doc)", "O(delta)")
    # the usual platform partitioning: the headline's platform tags
    # the row, so it is NOT quarantined and gates in its own gap|tpu
    # partition
    assert row["platform"] == "tpu" and not row["quarantined"]
    verdict = ledger.check(led)
    assert verdict["ok"], verdict
    assert any(lbl.startswith("gap|tpu") for lbl in verdict["partitions"])

    missing = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "gap", "--obs",
         str(tmp_path / "nope.jsonl")],
        capture_output=True, text=True, cwd=REPO)
    assert missing.returncode == 2
