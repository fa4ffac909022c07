"""cause_tpu.obs.semantic + obs.fleet — the CRDT-semantic fleet layer.

Pins the PR-5 contract: obs-off no-op invariance (zero records, zero
semantic state, byte-identical program-cache keys), per-wave digest
agreement vs forced-divergence ``divergence`` events with
first-differing-site provenance, staleness-gauge monotonicity while a
pair stays divergent, overflow/fallback counters on a synthetic
overflow row, the sync/gc/collection event vocabulary, the Perfetto
named semantic tracks, and the ``python -m cause_tpu.obs fleet`` CLI
(total on an empty stream).
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
from cause_tpu.gc import compact
from cause_tpu.ids import new_site_id
from cause_tpu.obs import fleet, semantic
from cause_tpu.parallel import merge_wave
from cause_tpu.benchgen import _scalar_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test starts from a clean, DISABLED obs state and empty
    divergence-monitor state, and leaves none behind."""
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    semantic.reset()
    yield
    obs.reset()
    semantic.reset()


def _fleet_base(n=20):
    """A woven jax-backed base list with a live lane view (the wave
    fast path's precondition) — one shared shape bucket so every test
    here reuses the same compiled kernels."""
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


def _events(name=None):
    evs = [e for e in obs.events() if e.get("ev") == "event"]
    if name is None:
        return evs
    return [e for e in evs if e.get("name") == name]


# ----------------------------------------------------- obs-off no-op


def test_obs_off_is_invariant(tmp_path):
    """The PR-1 contract extended to the semantic layer: with obs
    disabled, a full semantic-instrumented pass (sync, gc, lazy
    materialization, a merge wave) records nothing, keeps no monitor
    state, opens no sink, and leaves the program-cache key mapping
    byte-identical."""
    out = str(tmp_path / "never.jsonl")
    obs.configure(enabled=False, out=out)
    key_before = sorted(_scalar_programs)

    base = _fleet_base()
    a, b = _replica_pair(base)
    sync.sync_pair(a, b)
    compact(CausalList(a.ct.evolve(weaver="pure")))
    lazy = CausalList(a.ct.evolve(lazy_weave=True, weaver="pure",
                                  lanes=None)).conj("q")
    lazy.get_weave()
    res = merge_wave([(a, b)] * 2)
    assert len(res) == 2

    assert obs.events() == []
    assert obs.counters_snapshot() == {"counters": {}, "gauges": {}}
    assert not os.path.exists(out)
    assert semantic.observe_wave("u", [1], [True]) is None
    assert semantic._MON == {}  # no monitor state accumulates
    key_after = sorted(_scalar_programs)
    assert key_after == key_before


# ------------------------------------------- digest agreement / divergence


def test_wave_digest_agreement_no_divergence():
    """Identical replica pairs converge to identical digests: one
    ``wave.digest`` event with agreed=True, an all-zero staleness
    histogram, and ZERO divergence events."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 4)
    (wd,) = _events("wave.digest")
    f = wd["fields"]
    assert f["pairs"] == 4 and f["valid"] == 4
    assert f["agreed"] is True and f["distinct"] == 1
    assert f["staleness"] == {"0": 4}
    assert _events("divergence") == []
    snap = obs.counters_snapshot()
    assert snap["counters"]["wave.pairs"] == 4
    assert snap["counters"].get("fleet.divergence", 0) == 0
    assert snap["gauges"]["fleet.staleness.max"] == 0
    # the token-budget headroom gauge landed for the wave
    assert "fleet.token_headroom.wave" in snap["gauges"]


def test_forced_divergence_emits_one_event_with_provenance():
    """A pair whose replica carries an extra edit diverges from the
    fleet's modal digest: exactly ONE ``divergence`` event, carrying
    the first differing site and both version-vector entries."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    b_extra = b.conj("EXTRA")
    merge_wave([(a, b)] * 3 + [(a, b_extra)])
    (div,) = _events("divergence")
    f = div["fields"]
    assert f["pair"] == 3 and f["disagreeing"] == 1
    assert f["digest"] != f["expected"]
    # site provenance: the extra edit was minted by b's site
    assert f["site"] == b_extra.ct.site_id
    assert f["site_got"] != f["site_expected"]
    (wd,) = _events("wave.digest")
    assert wd["fields"]["agreed"] is False
    assert wd["fields"]["distinct"] == 2
    assert wd["fields"]["staleness"] == {"0": 3, "1": 1}
    assert obs.counters_snapshot()["counters"]["fleet.divergence"] == 1


def test_staleness_gauge_is_monotonic_while_divergent():
    """"Waves since last converged digest": a persistently divergent
    pair's staleness must grow by one per wave (and the max gauge must
    never decrease), then reset to zero the wave it re-converges."""
    obs.configure(enabled=True)
    base = _fleet_base()
    a, b = _replica_pair(base)
    b_extra = b.conj("EXTRA")
    diverged = [(a, b)] * 3 + [(a, b_extra)]
    for expect in (1, 2, 3):
        merge_wave(diverged)
        wd = _events("wave.digest")[-1]
        assert wd["fields"]["staleness"] == {"0": 3, str(expect): 1}
        assert obs.counters_snapshot()["gauges"][
            "fleet.staleness.max"] == expect
    gauge_samples = [e["value"] for e in obs.events()
                     if e.get("ev") == "gauge"
                     and e.get("name") == "fleet.staleness.max"]
    assert gauge_samples == sorted(gauge_samples)  # monotone while stale
    # re-convergence resets the pair to zero
    merge_wave([(a, b)] * 4)
    wd = _events("wave.digest")[-1]
    assert wd["fields"]["staleness"] == {"0": 4}
    assert obs.counters_snapshot()["gauges"]["fleet.staleness.max"] == 0
    assert len(_events("divergence")) == 3  # one per divergent wave


# ------------------------------------------------ overflow / fallback


def test_overflow_row_counters_and_fallback(monkeypatch):
    """A synthetic token-budget overflow (the budget estimator forced
    to a value far below the real union) must record the retry and the
    eventual per-row host-merge fallbacks — and the wave must still
    produce correct trees via those fallbacks."""
    from cause_tpu import benchgen

    obs.configure(enabled=True)
    base = _fleet_base()
    a = CausalList(base.ct.evolve(site_id=new_site_id()))
    b = CausalList(base.ct.evolve(site_id=new_site_id()))
    # interleaved interior appends: each stab is its own merge token,
    # so the union genuinely exceeds a starved budget (tail-only conj
    # divergence coalesces into ~1 token and can never overflow)
    nids = sorted(nid for nid in base.ct.nodes if nid != c.root_id)
    for i, cause in enumerate(nids[:12]):
        if i % 2:
            a = a.append(cause, f"a{i}")
        else:
            b = b.append(cause, f"b{i}")
    monkeypatch.setattr(benchgen, "v5_token_budget", lambda lanes: 1)
    res = merge_wave([(a, b)] * 2)
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("wave.overflow_retry", 0) >= 1
    assert snap.get("wave.fallback", 0) >= 1
    assert _events("wave.overflow_retry")
    assert res.fallback  # overflowed rows took the host path
    assert (c.causal_to_edn(res.merged(0))
            == c.causal_to_edn(a.merge(b)))
    # overflow rows carry no device digest: the wave aged them
    wd = _events("wave.digest")[-1]
    assert wd["fields"]["valid"] == 0


# ------------------------------------------------- sync / gc / lazy


def test_sync_gc_collection_vocabulary():
    """The host-side event families: delta application (path choice),
    full-bag fallback with reason, gc.compact evidence, and lazy
    -materialization stats with a real tombstone ratio."""
    obs.configure(enabled=True)
    a = c.clist("a", "b", "c")
    b = CausalList(a.ct.evolve(site_id=new_site_id())).conj("x")
    sync.sync_pair(a, b)
    (ev,) = _events("sync.delta_apply")
    assert ev["fields"]["path"] in ("incremental", "union")
    assert ev["fields"]["nodes"] == 1

    # a per-site GAP (the test_sync.py non-prefix recipe) breaks the
    # vv-delta assumption: cause-must-exist -> full-bag fallback
    doc = c.clist()
    root = c.root_id
    x1 = ((1, "siteX________", 0), root, "x1")
    z2 = ((2, "siteZ________", 0), root, "z2")
    x3 = ((3, "siteX________", 0), z2[0], "x3")
    w4 = ((4, "siteW________", 0), x1[0], "w4")
    pa = doc.insert(x1).insert(z2).insert(x3).insert(w4)
    pb = doc.insert(z2).insert(x3)
    sync.sync_pair(pa, pb)
    assert any(e["fields"]["reason"] == "cause-must-exist"
               for e in _events("sync.full_bag"))

    # delete-at-end is the GC-wholesale case: reclaimed > 0
    big = c.clist(*[str(i) for i in range(8)])
    big = big.append(list(big)[-1][0], c.hide)
    compact(big)
    gcev = _events("gc.compact")[-1]
    assert gcev["fields"]["examined"] > gcev["fields"]["reclaimed"] > 0
    assert gcev["fields"]["refused"] is False

    # lazy materialization: one hide -> nonzero tombstone ratio
    lazy = CausalList(big.ct.evolve(lazy_weave=True)).conj("tail")
    lazy.get_weave()
    mat = _events("collection.materialize")[-1]
    f = mat["fields"]
    assert f["weave_len"] >= f["values"] >= f["live"]
    assert 0 < f["tombstone_ratio"] < 1
    snap = obs.counters_snapshot()["counters"]
    assert snap["gc.nodes_reclaimed"] == gcev["fields"]["reclaimed"]
    assert snap["collection.lazy_materialize"] >= 1


# ----------------------------------------------------- perfetto tracks


def test_perfetto_semantic_named_tracks(tmp_path):
    """Semantic events land on their own NAMED instant-event tracks
    (synthetic tid + thread_name metadata), ordinary events stay on
    the emitting thread's track."""
    obs.configure(enabled=True)
    obs.event("wave.digest", pairs=2, agreed=True)
    obs.event("divergence", pair=1, site="s")
    obs.event("gc.compact", examined=5, reclaimed=1)
    obs.event("harvest.decide", cfg="x")  # NOT semantic
    path = str(tmp_path / "t.json")
    obs.export_perfetto(path, events=obs.events())
    doc = json.load(open(path))
    sem = [t for t in doc["traceEvents"]
           if t.get("cat") == "obs.semantic"]
    assert {t["name"] for t in sem} == {"wave.digest", "divergence",
                                        "gc.compact"}
    names = {t["args"]["name"] for t in doc["traceEvents"]
             if t.get("ph") == "M" and t["name"] == "thread_name"}
    assert {"semantic:wave.digest", "semantic:divergence",
            "semantic:gc"} <= names
    # each family got its own distinct synthetic tid
    assert len({t["tid"] for t in sem}) == 3
    ordinary = [t for t in doc["traceEvents"]
                if t.get("ph") == "i" and t["name"] == "harvest.decide"]
    assert ordinary and ordinary[0]["cat"] == "obs"


# ------------------------------------------------------------- the CLI


def test_fleet_report_aggregates():
    """fleet_report: last-wave-per-document staleness, divergence
    incident listing, and the counter-derived degradation rates."""
    events = [
        {"ev": "event", "name": "wave.digest", "pid": 1,
         "fields": {"uuid": "u1", "source": "wave", "wave": 1,
                    "pairs": 4, "valid": 4, "distinct": 1,
                    "agreed": True, "staleness": {"0": 4}}},
        {"ev": "event", "name": "wave.digest", "pid": 1,
         "fields": {"uuid": "u1", "source": "wave", "wave": 2,
                    "pairs": 4, "valid": 4, "distinct": 2,
                    "agreed": False, "staleness": {"0": 3, "1": 1}}},
        {"ev": "event", "name": "divergence", "pid": 1,
         "fields": {"uuid": "u1", "source": "wave", "wave": 2,
                    "pair": 3, "site": "sX", "site_expected": [2, 0],
                    "site_got": [3, 0], "disagreeing": 1}},
        {"ev": "counters", "pid": 1,
         "counters": {"sync.delta_rounds": 8, "sync.full_bag": 2,
                      "wave.pairs": 8, "wave.fallback": 1,
                      "gc.runs": 2, "gc.nodes_examined": 100,
                      "gc.nodes_reclaimed": 25,
                      "collection.lazy_materialize": 3}},
    ]
    r = fleet.fleet_report(events)
    assert r["documents"] == 1 and r["waves"] == 2
    assert r["pairs"] == 4 and r["replicas"] == 8
    # the LAST wave's histogram wins (it is the current state)
    assert r["staleness"] == {"0": 3, "1": 1}
    assert r["agreed_documents"] == 0
    (inc,) = r["divergence_incidents"]
    assert inc["site"] == "sX" and inc["pair"] == 3
    assert r["sync"]["full_bag_rate"] == 0.2
    assert r["wave"]["fallback_rate"] == 0.125
    assert r["gc"]["reclaim_rate"] == 0.25
    assert r["collections"]["lazy_materializations"] == 3
    text = fleet.render(r)
    assert "8 replicas" in text and "divergence incidents: 1" in text
    assert "sX" in text


def test_fleet_cli_empty_stream_exits_zero(tmp_path):
    """Total on nothing: an empty JSONL renders a zeroed report and
    exits 0 (a missing file exits 2)."""
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    r = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "fleet", str(empty)],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "0 replicas" in r.stdout
    assert "divergence incidents: 0" in r.stdout
    missing = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "fleet",
         str(tmp_path / "nope.jsonl")],
        capture_output=True, text=True, cwd=REPO)
    assert missing.returncode == 2


def test_fleet_cli_renders_real_session_stream(tmp_path):
    """End to end: an 8-replica (4-pair) run streamed to a sidecar
    renders replica count, a staleness histogram, and zero divergence
    incidents — the CI fleet-smoke contract, in-process."""
    out = str(tmp_path / "fleet.jsonl")
    obs.configure(enabled=True, out=out)
    base = _fleet_base()
    a, b = _replica_pair(base)
    merge_wave([(a, b)] * 4)
    merge_wave([(a.conj("n"), b.conj("n2"))] * 4)
    obs.flush()
    r = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "fleet", out,
         "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["replicas"] == 8 and rep["waves"] == 2
    assert rep["staleness"] == {"0": 4}
    assert rep["divergence_incidents"] == []
    assert rep["agreed_documents"] == 1


# ------------------------------------------------------- monitor bound


def test_semantic_monitor_lru_bound():
    """PR-5 bounded the divergence-monitor state at 4096 documents for
    uuid-churn soaks (600k rounds mint a uuid per round) but never
    pinned the eviction path: filling past the bound must evict the
    least-recently-waved documents, keep the registry at the cap, and
    LRU-refresh documents that wave again."""
    obs.configure(enabled=True)
    cap = semantic._MON_MAX
    assert cap == 4096
    for i in range(cap + 200):
        semantic.observe_wave(f"doc{i}", [1, 1], [True, True])
    assert len(semantic._MON) == cap
    # the oldest 200 evicted, the newest retained
    assert ("doc0", "wave") not in semantic._MON
    assert ("doc199", "wave") not in semantic._MON
    assert ("doc200", "wave") in semantic._MON
    assert (f"doc{cap + 199}", "wave") in semantic._MON
    # re-waving an old survivor refreshes it (state intact), so new
    # arrivals evict the now-oldest documents instead of it
    semantic.observe_wave("doc200", [1, 1], [True, True])
    assert semantic._MON[("doc200", "wave")]["wave"] == 2
    for i in range(100):
        semantic.observe_wave(f"fresh{i}", [1], [True])
    assert ("doc200", "wave") in semantic._MON
    assert ("doc300", "wave") not in semantic._MON
    assert len(semantic._MON) == cap
