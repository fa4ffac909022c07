"""cause_tpu.obs.devprof — device-program telemetry.

Pins the PR-4 tentpole contract: cost_analysis capture once per
compiled program (CPU-lowered here), the program-identity keying of
the emitted events, gauge streaming for the memory samples, and —
load-bearing, like test_obs.py's disabled-mode pins — that with obs
OFF devprof records nothing, reads no CAUSE_TPU_* env vars, and leaves
the program-cache values exactly what they were pre-devprof (plain jit
programs, not wrappers).
"""

import os

import numpy as np
import pytest

from cause_tpu import obs
from cause_tpu.obs import core as obs_core
from cause_tpu.obs import devprof


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    yield
    obs.reset()


def _toy_program():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    return jax.jit(lambda x: jnp.sum(x * 2.0))


# ----------------------------------------------------------- disabled


def test_disabled_devprof_records_nothing(monkeypatch):
    pytest.importorskip("jax")
    obs.configure(enabled=False)
    f = _toy_program()
    a = np.ones((4, 4), np.float32)

    read = []

    class _Tracker(dict):
        def get(self, key, default=None):
            read.append(key)
            return super().get(key, default)

        def __getitem__(self, key):
            read.append(key)
            return super().__getitem__(key)

        def __contains__(self, key):
            read.append(key)
            return super().__contains__(key)

    monkeypatch.setattr(obs_core.os, "environ", _Tracker(os.environ))
    assert devprof.profile_program(f, (a,), kernel="toy") is None
    assert devprof.sample_device_memory("nowhere") == {}
    assert devprof.arena_footprint(object()) == {}
    assert obs.events() == []
    assert not [k for k in read if str(k).startswith("CAUSE_TPU_")], read


def test_disabled_program_cache_stores_plain_jit_programs(monkeypatch):
    """Obs off: merge_wave_scalar's cache must hold exactly what it
    held before devprof existed — no wrapper, no events, same keys."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from cause_tpu import benchgen

    obs.configure(enabled=False)
    monkeypatch.setattr(benchgen, "_scalar_programs", {})
    batch = benchgen.batched_pair_lanes(
        n_replicas=2, n_base=20, n_div=4, capacity=64, hide_every=4)
    v5batch = benchgen.batched_v5_inputs(batch, 64)
    args = [jnp.asarray(batch[k] if k in batch else v5batch[k])
            for k in benchgen.LANE_KEYS5]
    u = int(benchgen.v5_token_budget(v5batch))
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    (key,) = benchgen._scalar_programs
    assert key == (u, "v5", u)
    program = benchgen._scalar_programs[key]
    assert not isinstance(program, devprof._ProfiledProgram)
    assert obs.events() == []


# ------------------------------------------------------------ capture


def test_cost_capture_on_cpu_lowered_program():
    obs.configure(enabled=True)
    f = _toy_program()
    a = np.ones((8, 8), np.float32)
    prof = devprof.profile_program(f, (a,), kernel="toy", k_max=3)
    assert prof is not None
    # the AOT fast path and the jit fallback agree
    assert float(prof(a)) == float(f(a))
    # a different shape falls back to the jit path, not an AOT error
    b = np.ones((2, 2), np.float32)
    assert float(prof(b)) == float(f(b))
    evs = [e for e in obs.events()
           if e.get("ev") == "event" and e["name"] == "devprof.program"]
    assert len(evs) == 1
    cost = evs[0]["fields"]["cost"]
    assert cost["flops"] > 0
    assert cost["bytes_accessed"] > 0
    assert "output_bytes" in cost
    assert evs[0]["fields"]["kernel"] == "toy"
    assert evs[0]["fields"]["k_max"] == 3
    # the compile landed as a span too
    names = {e["name"] for e in obs.events() if e["ev"] == "span"}
    assert "devprof.compile" in names


def test_program_event_keyed_by_switch_identity(monkeypatch):
    """The program event is keyed by the program's identity alone: the
    ``meta`` its cache key carries, and nothing from the environment
    (a retired strategy variable leaves no mark)."""
    obs.configure(enabled=True)
    monkeypatch.setenv("CAUSE_TPU_SORT", "matrix")
    f = _toy_program()
    prof = devprof.profile_program(f, (np.ones(4, np.float32),),
                                   kernel="toy", u_max=8)
    assert prof is not None
    (ev,) = [e for e in obs.events()
             if e.get("name") == "devprof.program"]
    assert set(ev["fields"]) == {"compile_ms", "cost", "kernel", "u_max"}
    assert (ev["fields"]["kernel"], ev["fields"]["u_max"]) == ("toy", 8)


def test_program_cache_capture_once_per_program(monkeypatch):
    """merge_wave_scalar with obs on: the miss compiles through the
    AOT path (one devprof.program event), the hit serves the wrapper
    with no second capture."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from cause_tpu import benchgen

    obs.configure(enabled=True)
    monkeypatch.setattr(benchgen, "_scalar_programs", {})
    batch = benchgen.batched_pair_lanes(
        n_replicas=2, n_base=20, n_div=4, capacity=64, hide_every=4)
    v5batch = benchgen.batched_v5_inputs(batch, 64)
    args = [jnp.asarray(batch[k] if k in batch else v5batch[k])
            for k in benchgen.LANE_KEYS5]
    u = int(benchgen.v5_token_budget(v5batch))
    out1 = np.asarray(benchgen.merge_wave_scalar(
        *args, k_max=u, kernel="v5", u_max=u))
    out2 = np.asarray(benchgen.merge_wave_scalar(
        *args, k_max=u, kernel="v5", u_max=u))
    assert out1[0] == out2[0]
    evs = [e for e in obs.events()
           if e.get("name") == "devprof.program"]
    assert len(evs) == 1
    assert evs[0]["fields"]["kernel"] == "v5"
    assert evs[0]["fields"]["cost"].get("flops", 0) > 0
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("program_cache.miss") == 1
    assert snap.get("program_cache.hit") == 1
    # the cached value is the profiled wrapper (identity keys unchanged)
    (key,) = benchgen._scalar_programs
    assert key == (u, "v5", u)
    assert isinstance(benchgen._scalar_programs[key],
                      devprof._ProfiledProgram)


# ------------------------------------------------------------- gauges


def test_memory_sample_streams_gauges_as_counter_tracks(tmp_path):
    pytest.importorskip("jax")
    import json

    obs.configure(enabled=True)
    sample = devprof.sample_device_memory("waveX")
    assert "live_arrays" in sample
    gauges = [e for e in obs.events() if e.get("ev") == "gauge"]
    assert {g["name"] for g in gauges} >= {
        "devprof.live_arrays.waveX", "devprof.live_bytes.waveX"}
    path = str(tmp_path / "trace.json")
    obs.export_perfetto(path, events=obs.events())
    doc = json.load(open(path))
    tracks = {t["name"] for t in doc["traceEvents"] if t["ph"] == "C"}
    assert "devprof.live_bytes.waveX" in tracks


def test_arena_footprint_on_a_real_lane_view():
    pytest.importorskip("jax")
    from cause_tpu.collections.clist import new_causal_list

    obs.configure(enabled=True)
    lst = new_causal_list("a", "b")
    for ch in "cdefgh":
        lst = lst.conj(ch)
    from cause_tpu.weaver import lanecache

    view = lanecache.view_for(lst.ct)
    assert view is not None
    out = devprof.arena_footprint(view.arena, site="test")
    assert out["arena_bytes"] > 0
    assert out["arena_lanes"] == view.arena.committed_n
    names = {e["name"] for e in obs.events() if e.get("ev") == "gauge"}
    assert "devprof.arena_bytes.test" in names
