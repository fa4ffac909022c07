"""cause_tpu.obs.lag — the convergence-lag tracer.

Pins the PR-9 contract: obs-off no-op invariance (zero records, zero
op-registry state, zero env reads, byte-identical
program-cache keys), op stamping at the mutation funnel and the sync
ingest path, resolution against the substrate's own wave/tree digest
agreement (create→woven at the wave, create→converged at the first
agreeing wave / final tree level), the mergeable pow2-bucket
histograms, sliding-window percentile gauges, SLO attainment + burn
rate, the full-bag replay watermark, the bounded registries, and the
``python -m cause_tpu.obs lag`` CLI (multi-stream merge included).
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
from cause_tpu.obs import costmodel, lag, semantic
from cause_tpu.obs.lag import LagHistogram
from cause_tpu.parallel import merge_wave
from cause_tpu.parallel.session import FleetSession
from cause_tpu.benchgen import _scalar_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test starts from a clean, DISABLED obs state and an empty
    lag/semantic/cost-model registry, and leaves none behind."""
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING", "CAUSE_TPU_LEDGER",
              "CAUSE_TPU_LAG_SLO_MS"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    semantic.reset()
    costmodel.reset()
    lag.reset()
    yield
    obs.reset()
    semantic.reset()
    costmodel.reset()
    lag.reset()


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


def _events(name):
    return [e["fields"] for e in obs.events()
            if e.get("ev") == "event" and e.get("name") == name]


# ----------------------------------------------------- obs-off no-op


def test_obs_off_is_invariant(tmp_path):
    """The PR-1 contract extended to the lag tracer: with obs disabled
    a full instrumented pass (mutations, sync, a merge wave, session
    waves) records nothing, keeps no op-registry state, opens no sink,
    and leaves the program-cache key mapping byte-identical."""
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
    lag.op_created("u", [(1, "s", 0)])
    lag.ops_applied("u", [(1, "s", 0)], replica="r")
    assert lag.wave_observed("u", agreed=True) is None
    assert lag.level_observed("u", agreed=True, level=0,
                              final=True) is None
    assert lag._DOCS == {}
    assert lag._REPLICAS == {}
    assert lag._HIST_WOVEN.count == 0
    assert lag._HIST_CONVERGED.count == 0
    assert lag._WINDOW == []
    assert lag.pending_ops() == 0
    key_after = sorted(_scalar_programs)
    assert key_after == key_before


# ------------------------------------------------------- histograms


def test_histogram_records_and_quantiles():
    h = LagHistogram()
    for us in (100, 200, 400, 800, 1600, 3200, 6400, 12800):
        h.record_us(us)
    assert h.count == 8
    assert h.min_us == 100 and h.max_us == 12800
    # quantiles are bucket-interpolated but clamped to observed bounds
    assert 0.1 <= h.quantile_ms(0.5) <= 3.2
    assert h.quantile_ms(1.0) == 12.8
    assert h.quantile_ms(0.0) >= 0.1
    # within √2 relative error per value: the p50 sits near the middle
    assert h.mean_ms() == round(sum(
        (100, 200, 400, 800, 1600, 3200, 6400, 12800)) / 8 / 1000, 4)


def test_histogram_merge_and_fields_roundtrip():
    h1, h2 = LagHistogram(), LagHistogram()
    for us in (50, 500, 5000):
        h1.record_us(us)
    for us in (10, 100000):
        h2.record_us(us)
    merged = LagHistogram.from_fields(h1.to_fields()).merge(
        LagHistogram.from_fields(h2.to_fields()))
    assert merged.count == 5
    assert merged.min_us == 10 and merged.max_us == 100000
    assert merged.sum_us == h1.sum_us + h2.sum_us
    # merge is a per-bucket sum: recording everything into one
    # histogram yields identical buckets
    ref = LagHistogram()
    for us in (50, 500, 5000, 10, 100000):
        ref.record_us(us)
    assert merged.buckets == ref.buckets


def test_histogram_within_us():
    h = LagHistogram()
    for us in (100, 100, 100, 100000):
        h.record_us(us)
    # 100 us sits in bucket [64, 128): a limit above the bucket counts
    # all three, the huge outlier stays out
    assert h.within_us(200) >= 3
    assert h.within_us(200) < 4
    assert h.within_us(1 << 30) == 4


# ------------------------------------------------------- resolution


def test_session_rounds_resolve_ops():
    """The steady-state loop: ops conj'd between waves resolve at the
    next agreeing wave with both lags recorded, the window gauges
    stream, pending drains to zero."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    sess = FleetSession([(a, b)] * 4)
    sess.wave()
    assert lag.pending_ops() == 0  # first wave resolved the marshal ops
    a2, b2 = a.conj("x"), b.conj("y")
    assert lag.pending_ops(a.ct.uuid) == 2
    sess.update([(a2, b2)] * 4)
    sess.wave()
    assert lag.pending_ops() == 0

    ops = _events("op.lag")
    conv = [f for f in ops if f["phase"] == "converged"]
    woven = [f for f in ops if f["phase"] == "woven"]
    assert conv and woven
    assert all(f["lag_ms"] >= 0 for f in ops)
    assert {f["site"] for f in conv} >= {a.ct.site_id, b.ct.site_id}
    wins = _events("lag.window")
    assert wins[-1]["converged_total"] == len(conv)
    assert wins[-1]["slo_ms"] == lag.SLO_DEFAULT_MS
    assert wins[-1]["hist_converged"]["count"] == len(conv)
    assert wins[-1]["window"]["p50_ms"] > 0
    gauges = {e["name"] for e in obs.events() if e.get("ev") == "gauge"}
    assert {"lag.p50_ms", "lag.p95_ms", "lag.p99_ms"} <= gauges


def test_disagreeing_wave_defers_convergence():
    """Ops are woven by any wave but converge only at the first wave
    whose digests AGREE across the fleet: a wave over pairs that
    diverged from each other leaves them pending-converged."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    a2, b2 = _replica_pair(base, edits_a=("C",), edits_b=("D",))
    merge_wave([(a, b), (a2, b2)])  # distinct digests: no agreement
    assert _events("op.lag")
    assert all(f["phase"] == "woven" for f in _events("op.lag"))
    assert lag.pending_ops(a.ct.uuid) > 0
    before = lag.pending_ops(a.ct.uuid)
    merge_wave([(a, b)] * 2)        # identical pairs agree
    conv = [f for f in _events("op.lag") if f["phase"] == "converged"]
    assert len(conv) == before
    assert lag.pending_ops(a.ct.uuid) == 0


def test_sync_apply_lag_per_replica_and_ingest_stamp():
    """The sync ingest path: ops stamped at creation record their
    apply lag against the RECEIVING replica (the worst-offender axis);
    ops foreign to the process are stamped at ingest."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    sync.sync_pair(a, b)
    reps = _events("lag.replica")
    assert {f["replica"] for f in reps} == {a.ct.site_id, b.ct.site_id}
    assert all(f["applied"] >= 1 for f in reps)
    assert all(f["hist"]["count"] >= 1 for f in reps)

    # a node id never stamped in-process: ingest stamps it, a later
    # agreeing wave resolves it
    foreign = ((a.ct.lamport_ts + 7, new_site_id(), 0),
               list(a.ct.nodes)[0], "F")
    before = lag.pending_ops(a.ct.uuid)
    merged = sync.apply_delta(a, {foreign[0]: foreign[1:]})
    assert lag.pending_ops(a.ct.uuid) == before + 1
    merge_wave([(merged, merged)] * 2)
    assert lag.pending_ops(a.ct.uuid) == 0


def test_full_bag_replay_does_not_restamp():
    """The lamport watermark: a full-bag resend replays every node of
    the document — long-converged ops must not re-enter the registry
    as freshly created (their near-zero lags would swamp the
    distribution)."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 2)
    assert lag.pending_ops(a.ct.uuid) == 0
    # the full bag: every node the document has
    lag.ops_applied(a.ct.uuid, list(a.ct.nodes), replica=b.ct.site_id)
    assert lag.pending_ops(a.ct.uuid) == 0


def test_tree_resolution():
    """Merge-tree convergence: level 0 weaves the stamped ops, only
    the FINAL level's fleet-wide agreement converges them."""
    from cause_tpu.parallel import tree as tree_mod

    obs.configure(enabled=True)
    base = _fleet_base(40)
    a, b = _replica_pair(base, edits_a=("A0", "A1"), edits_b=("B0",))
    fleet = [a, b] * 4
    assert lag.pending_ops(a.ct.uuid) > 0
    tree_mod.merge_tree(fleet)
    assert lag.pending_ops(a.ct.uuid) == 0
    wins = _events("lag.window")
    assert wins and wins[-1]["source"] == "tree"
    assert wins[-1]["converged"] > 0
    # level 0 marks woven; only the final level converges
    assert wins[0]["level"] == 0 and wins[0]["converged_total"] == 0


def test_doc_registry_is_lru_bounded(monkeypatch):
    """The op registry evicts its least-recently-touched documents
    past the bound (a long soak mints a uuid per round)."""
    obs.configure(enabled=True)
    monkeypatch.setattr(lag, "_DOC_MAX", 8)
    for i in range(20):
        lag.op_created(f"doc{i}", [(1, "s", 0)])
    assert len(lag._DOCS) == 8
    assert "doc0" not in lag._DOCS and "doc19" in lag._DOCS
    # touching an old survivor refreshes it
    lag.op_created("doc12", [(2, "s", 0)])
    lag.op_created("doc99", [(1, "s", 0)])
    assert "doc12" in lag._DOCS


# -------------------------------------------------------- read side


def _run_session_stream(out_path=None):
    obs.configure(enabled=True, out=out_path)
    base = _fleet_base()
    a, b = _replica_pair(base)
    sess = FleetSession([(a, b)] * 4)
    sess.wave()
    sess.update([(a.conj("x"), b.conj("y"))] * 4)
    sess.wave()
    obs.flush()
    return a


def test_lag_summary_and_render():
    _run_session_stream()
    rep = lag.lag_summary(obs.events())
    assert rep["ops_converged"] > 0
    assert rep["pending"] == 0
    for key in ("p50_ms", "p95_ms", "p99_ms", "max_ms"):
        assert rep["converged"][key] is not None
        assert np.isfinite(rep["converged"][key])
    assert rep["slo"]["target_ms"] == lag.SLO_DEFAULT_MS
    assert rep["slo"]["verdict"] in ("OK", "BREACH")
    assert rep["slo"]["attainment_exact"]
    text = lag.render(rep)
    assert "create→converged" in text and "SLO" in text
    # a generous override flips the verdict to OK (histogram-estimated
    # attainment: the recorded target differs)
    ok = lag.lag_summary(obs.events(), slo_ms_override=1e9)
    assert ok["slo"]["verdict"] == "OK"
    assert not ok["slo"]["attainment_exact"]
    tight = lag.lag_summary(obs.events(), slo_ms_override=1e-6)
    assert tight["slo"]["verdict"] == "BREACH"
    assert tight["slo"]["burn_rate"] >= 1.0


def test_summary_sums_across_resets():
    """A multi-fleet bench resets the tracer between fleets, so the
    stream carries one cumulative record series PER EPOCH; the read
    side must aggregate every epoch, not keep only the last."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 2)
    n1 = lag.lag_summary(obs.events())["ops_converged"]
    assert n1 > 0
    lag.reset()
    a2, b2 = _replica_pair(base, edits_a=("C",), edits_b=("D",))
    merge_wave([(a2, b2)] * 2)
    rep = lag.lag_summary(obs.events())
    assert rep["ops_converged"] == n1 + 2


def test_slo_env_and_set_slo(monkeypatch):
    obs.configure(enabled=True)
    monkeypatch.setenv("CAUSE_TPU_LAG_SLO_MS", "250")
    assert lag.slo_ms() == 250.0
    lag.set_slo(7.5)
    assert lag.slo_ms() == 7.5
    lag.set_slo(None)
    assert lag.slo_ms() == 250.0


def test_fleet_report_lag_section():
    from cause_tpu.obs.fleet import fleet_report, render

    _run_session_stream()
    rep = fleet_report(obs.events())
    assert rep["lag"]["ops_converged"] > 0
    assert rep["lag"]["p99_ms"] is not None
    assert rep["lag"]["slo"]["verdict"] in ("OK", "BREACH")
    assert "lag:" in render(rep)
    # total on an empty stream, like every other section
    empty = fleet_report([])
    assert empty["lag"]["ops_converged"] == 0
    assert "no convergence-lag records" in render(empty)


def test_fleet_render_flags_stuck_pending():
    """Zero converged with ops pending is a STUCK fleet, not an
    untraced one — the render must say so instead of 'no records'."""
    from cause_tpu.obs.fleet import fleet_report, render

    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    a2, b2 = _replica_pair(base, edits_a=("C",), edits_b=("D",))
    merge_wave([(a, b), (a2, b2)])  # divergent rows: never agree
    rep = fleet_report(obs.events())
    assert rep["lag"]["ops_converged"] == 0
    assert rep["lag"]["pending"] > 0
    assert "PENDING" in render(rep)


# -------------------------------------------------------------- CLI


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", *argv],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_lag_cli_renders_and_json(tmp_path):
    out = str(tmp_path / "events.jsonl")
    _run_session_stream(out)
    res = _run_cli("lag", out)
    assert res.returncode == 0, res.stderr
    assert "create→converged" in res.stdout and "SLO" in res.stdout
    res = _run_cli("lag", out, "--json", "--slo-ms", "1e9")
    assert res.returncode == 0, res.stderr
    rep = json.loads(res.stdout)
    assert rep["ops_converged"] > 0
    assert rep["slo"]["verdict"] == "OK"
    assert _run_cli("lag", str(tmp_path / "nope.jsonl")).returncode == 2


def test_lag_cli_merges_multiple_streams(tmp_path):
    """Satellite: multiple JSONL streams merge by timestamp — the
    cumulative per-pid records aggregate instead of clobbering."""
    out1 = str(tmp_path / "one.jsonl")
    _run_session_stream(out1)
    rep1 = lag.lag_summary(obs.events())
    # a second "process": same events under a different pid, shifted
    # timestamps — its cumulative histogram must ADD to the first's
    out2 = str(tmp_path / "two.jsonl")
    with open(out1) as f, open(out2, "w") as g:
        for line in f:
            e = json.loads(line)
            e["pid"] = 99999
            if "ts_us" in e:
                e["ts_us"] += 1
            g.write(json.dumps(e) + "\n")
    res = _run_cli("lag", out1, out2, "--json")
    assert res.returncode == 0, res.stderr
    rep = json.loads(res.stdout)
    assert rep["ops_converged"] == 2 * rep1["ops_converged"]
    # the fleet CLI accepts the same multi-stream form
    res = _run_cli("fleet", out1, out2)
    assert res.returncode == 0, res.stderr
    assert "lag:" in res.stdout
