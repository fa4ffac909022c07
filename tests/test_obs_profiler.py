"""obs spans on the profiler's clock.

Every ``obs.span`` also opens a profiler ``TraceMe`` whenever a JAX
profiler session records, obs on or off, with its integer and string
attributes as the event's stats. Pins: a trace recorded around a tiny
``merge_wave`` and a tiny ``SyncService.tick``, or a tiny ``merge_all``
on the reduction tree, holds the spans at each layer boundary with
their attributes; the obs ring still records only
when obs is on; with no profiler recording a disabled span is still
the shared no-op; ``obs.core`` imports without JAX.
"""

import glob
import os
import subprocess
import sys

import pytest

import cause_tpu as c
from cause_tpu import obs, serde, sync
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.obs import core as obs_core
from cause_tpu.obs import lag as obs_lag
from cause_tpu.parallel import merge_wave
from cause_tpu.serve import (IngestJournal, IngestQueue,
                             ResidencyManager, SyncService)

MERGE_SPANS = {
    "wave.merge": {"pairs"},
    "wave.views": {"pairs", "fallback"},
    "wave.assemble": {"rows", "cap"},
    "wave.upload": {"bytes"},
    "wave.kernel": {"u_max", "rows"},
    "wave.fetch": {"bytes"},
    "wave.scatter": {"copied"},
}
SERVE_SPANS = {
    "serve.tick": {"ops", "tenants", "batches", "wait_us_sum",
                   "wait_us_max"},
    "serve.apply": {"ops", "batches"},
    "sync.apply_delta": {"ops"},
    "serve.waves": {"tenants", "full"},
    "serve.batch_wave": {"bucket", "tenants", "rows"},
}

TREE_SPANS = {
    "tree.converge": {"n", "rounds"},
    "tree.level": {"level", "pairs", "path", "nodes", "merged"},
    "tree.assemble": {"level"},
    "tree.dispatch": {"level", "path", "u_max"},
    "tree.winners": {"level", "pairs"},
    "tree.establish": {"level", "ok"},
    "tree.sides": {"level"},
    "tree.materialize": {"members", "nodes"},
}


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT", "CAUSE_TPU_CHAOS"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    obs_lag.reset()
    yield
    obs.reset()
    obs_lag.reset()


def _base(n=12):
    base = CausalList(c_list.weave(
        c.clist(weaver="jax").extend(["w"] * n).ct))
    base.ct.lanes.segments()
    return base


def _pair(base):
    a = CausalList(base.ct.evolve(site_id=new_site_id()))
    b = CausalList(base.ct.evolve(site_id=new_site_id()))
    return a.conj("A"), b.conj("B")


def _service(root):
    jr = IngestJournal(os.path.join(str(root), "wal.jsonl"))
    q = IngestQueue(max_ops=4096, journal=jr)
    return SyncService(q, residency=ResidencyManager(capacity=4),
                       d_max=16)


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = {}
    for p in ProfileData.from_file(path).planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def _traced_wave_and_tick(tmp_path):
    """One merge_wave over three pairs and one service tick that
    applies two tenants' deltas, under a profiler session."""
    import jax

    pairs = [_pair(_base()) for _ in range(3)]
    svc = _service(tmp_path)
    ext = []
    for _ in range(2):
        left, right = _pair(_base())
        ext.append((svc.add_tenant(left, right), left))
    merge_wave(pairs)                        # warm: compiles outside
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        merge_wave(pairs)
        for uuid, left in ext:
            nl = left.conj("x")
            items = serde.encode_node_items(
                sync.delta_nodes(nl, sync.version_vector(left)))
            assert svc.queue.offer(uuid, nl.ct.site_id, items).admitted
        out = svc.tick(max_ops=64)
    finally:
        jax.profiler.stop_trace()
    svc.close()
    assert out["ops"] == 2 and out["tenants"] == 2
    return _host_events(trace_dir)


@pytest.mark.parametrize("obs_on", [False, True], ids=["obs_off", "obs_on"])
def test_spans_reach_the_profiler_trace(tmp_path, obs_on):
    obs.configure(enabled=obs_on)
    evs = _traced_wave_and_tick(tmp_path)
    for name, attrs in {**MERGE_SPANS, **SERVE_SPANS}.items():
        assert name in evs, (name, sorted(evs))
        assert attrs <= set(evs[name][-1]), (name, evs[name][-1])
    tick = evs["serve.tick"][-1]
    assert tick["ops"] == 2 and tick["batches"] == 2
    assert tick["tenants"] == 2
    assert 0 <= tick["wait_us_max"] <= tick["wait_us_sum"]
    assert len(evs["serve.apply"]) == 2
    assert evs["serve.waves"][-1] == {"tenants": 2, "full": 0}
    assert evs["wave.upload"][-1]["bytes"] > 0
    assert evs["wave.fetch"][-1]["bytes"] > 0
    assert evs["wave.views"][-1] == {"pairs": 3, "fallback": 0}
    ring = {e["name"] for e in obs.events() if e.get("ev") == "span"}
    if obs_on:
        assert set(MERGE_SPANS) | set(SERVE_SPANS) <= ring
    else:
        assert ring == set() and obs.events() == []


@pytest.mark.parametrize("obs_on", [False, True], ids=["obs_off", "obs_on"])
def test_tree_spans_reach_the_profiler_trace(tmp_path, obs_on):
    """A merge_all over five replicas that append at the tail: a full
    level 0, two delta levels, a bye and the root built from its window
    ranks, each under its span."""
    import jax

    obs.configure(enabled=obs_on)
    base = _base()
    fleet = [CausalList(base.ct.evolve(site_id=new_site_id())).conj(f"r{r}")
             for r in range(5)]
    c.merge_all(*fleet)                      # warm: compiles outside
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        c.merge_all(*fleet)
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(trace_dir)
    for name, attrs in TREE_SPANS.items():
        assert name in evs, (name, sorted(evs))
        assert attrs <= set(evs[name][-1]), (name, evs[name][-1])
    assert [e["path"] for e in evs["tree.level"]] == \
        ["full", "delta", "delta"]
    assert evs["tree.establish"] == [{"level": 0, "ok": 1}]
    # the root's members are the five replicas themselves
    assert evs["tree.materialize"][-1]["members"] == 5
    ring = {e["name"] for e in obs.events() if e.get("ev") == "span"}
    if obs_on:
        assert set(TREE_SPANS) <= ring
    else:
        assert ring == set() and obs.events() == []


def test_no_profiler_no_obs_is_the_shared_noop():
    obs.configure(enabled=False)
    assert obs.span("x", a=1) is obs_core._NULL_SPAN


def test_attributes_become_stats_and_strings_stay_parseable(tmp_path):
    import jax

    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with obs.span("t.outer", n=3, flag=True, kind="a,b=c#d",
                      ratio=0.5) as sp:
            sp.set(late=7)
    finally:
        jax.profiler.stop_trace()
    (stats,) = _host_events(trace_dir)["t.outer"]
    # floats are left out; the string keeps its value, defused
    assert stats == {"n": 3, "flag": 1, "kind": "a_b_c_d", "late": 7}


def test_obs_core_imports_without_jax():
    code = ("import sys; import cause_tpu.obs.core as c; "
            "c.span('x'); assert 'jax' not in sys.modules, "
            "sorted(m for m in sys.modules if m.startswith('jax'))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert p.returncode == 0, p.stderr
