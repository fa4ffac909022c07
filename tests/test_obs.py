"""cause_tpu.obs — the unified trace/metrics subsystem.

Pins the tentpole contract: span nesting and attributes, counter/gauge
aggregation, ring-buffer bounds, the child-safe JSONL sink, the
Perfetto exporter's schema, and — load-bearing — that DISABLED mode
emits nothing, opens nothing, and costs well under the ~1 microsecond
budget per no-op span (the tier-1 overhead smoke: obs must be free to
leave compiled in everywhere).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from cause_tpu import obs
from cause_tpu.obs import core as obs_core


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test starts from a clean, DISABLED obs state (no env
    carry-over) and leaves none behind."""
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING"):
        monkeypatch.delenv(k, raising=False)
    obs.reset()
    yield
    obs.reset()


# ------------------------------ spans ------------------------------


def test_span_nesting_parent_and_depth():
    obs.configure(enabled=True)
    with obs.span("outer", phase="x"):
        with obs.span("inner"):
            pass
        with obs.span("inner2"):
            pass
    evs = {e["name"]: e for e in obs.events()}
    outer, inner, inner2 = evs["outer"], evs["inner"], evs["inner2"]
    assert outer["depth"] == 0 and outer["parent"] == 0
    assert inner["parent"] == outer["id"] and inner["depth"] == 1
    assert inner2["parent"] == outer["id"] and inner2["depth"] == 1
    assert outer["attrs"] == {"phase": "x"}
    # children close before the parent: ring order inner, inner2, outer
    names = [e["name"] for e in obs.events()]
    assert names == ["inner", "inner2", "outer"]


def test_span_records_wall_time_and_identity(monkeypatch):
    obs.configure(enabled=True)
    # a retired strategy variable leaves no mark on the record: the
    # programs are a function of their inputs alone
    monkeypatch.setenv("CAUSE_TPU_SORT", "matrix")
    with obs.span("timed"):
        time.sleep(0.003)
    (e,) = obs.events()
    assert e["dur_us"] >= 3000
    assert e["pid"] == os.getpid()
    assert e["tid"] and e["platform"]
    assert "switches" not in e
    assert "matrix" not in json.dumps(e)


def test_span_set_and_error_flag():
    obs.configure(enabled=True)
    with pytest.raises(ValueError):
        with obs.span("boom") as sp:
            sp.set(extra=1)
            raise ValueError("x")
    (e,) = obs.events()
    assert e["error"] == "ValueError"
    assert e["attrs"]["extra"] == 1


# ------------------------- counters/gauges -------------------------


def test_counter_and_gauge_aggregation():
    obs.configure(enabled=True)
    obs.counter("hits").inc()
    obs.counter("hits").inc(4)
    obs.counter("misses").inc()
    obs.gauge("depth").set(3)
    obs.gauge("depth").set(7)
    snap = obs.counters_snapshot()
    assert snap["counters"] == {"hits": 5, "misses": 1}
    assert snap["gauges"] == {"depth": 7}
    obs.flush()
    last = obs.events()[-1]
    assert last["ev"] == "counters"
    assert last["counters"]["hits"] == 5
    assert last["gauges"]["depth"] == 7


# --------------------------- ring bounds ---------------------------


def test_ring_buffer_is_bounded():
    obs.configure(enabled=True, ring_size=8)
    for i in range(50):
        with obs.span(f"s{i}"):
            pass
    evs = obs.events()
    assert len(evs) == 8
    # newest survive
    assert [e["name"] for e in evs] == [f"s{i}" for i in range(42, 50)]


# ------------------------------ sink -------------------------------


def test_sink_streams_jsonl(tmp_path):
    out = str(tmp_path / "events.jsonl")
    obs.configure(enabled=True, out=out)
    with obs.span("a"):
        pass
    obs.event("decide", cfg={"CAUSE_TPU_SORT": "matrix"}, digest=42)
    # streamed as they happened — no flush/export needed
    lines = [json.loads(ln) for ln in open(out)]
    assert [ln["ev"] for ln in lines] == ["span", "event"]
    assert lines[1]["fields"]["digest"] == 42


def test_sink_survives_child_process(tmp_path):
    """The bench isolation contract: a CHILD process (env-enabled obs,
    same sidecar path) appends events the parent can read even though
    the parent never waits on obs state — and line writes from two
    processes interleave whole, never torn."""
    out = str(tmp_path / "side.jsonl")
    obs.configure(enabled=True, out=out)
    with obs.span("parent.phase"):
        pass
    env = dict(os.environ, CAUSE_TPU_OBS="1", CAUSE_TPU_OBS_OUT=out)
    code = ("from cause_tpu import obs\n"
            "with obs.span('child.phase', role='child'):\n"
            "    obs.counter('child.work').inc(2)\n"
            "obs.flush()\n")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    recs = [json.loads(ln) for ln in open(out)]
    names = {r.get("name") for r in recs}
    assert {"parent.phase", "child.phase"} <= names
    pids = {r["pid"] for r in recs}
    assert len(pids) == 2  # both processes landed in one sidecar
    counters = [r for r in recs if r["ev"] == "counters"]
    assert counters and counters[-1]["counters"]["child.work"] == 2


# ---------------------------- disabled -----------------------------


def test_disabled_emits_nothing(tmp_path):
    out = str(tmp_path / "never.jsonl")
    obs.configure(enabled=False, out=out)
    with obs.span("x", a=1) as sp:
        sp.set(b=2)
    obs.event("y", z=3)
    obs.counter("c").inc(9)
    obs.gauge("g").set(1)
    obs.flush()
    assert obs.events() == []
    assert obs.counters_snapshot() == {"counters": {}, "gauges": {}}
    assert not os.path.exists(out)  # the sink is never even opened


def test_disabled_span_overhead_smoke():
    """Tier-1 overhead gate: a disabled span() call must stay in the
    ~1 microsecond class (median), so instrumentation can live on the
    weaver/wave hot paths unconditionally."""
    obs.configure(enabled=False)
    span = obs.span
    # warm
    for _ in range(1000):
        with span("warm"):
            pass
    samples = []
    for _ in range(7):
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            with span("hot"):
                pass
        samples.append((time.perf_counter() - t0) / n)
    samples.sort()
    median = samples[len(samples) // 2]
    # budget: ~1 us with slack for CI-noise (the call is ~0.2-0.4 us)
    assert median < 2e-6, f"disabled span cost {median * 1e6:.2f} us"


def _tiny_v5_args():
    jnp = pytest.importorskip("jax.numpy")
    from cause_tpu import benchgen

    batch = benchgen.batched_pair_lanes(
        n_replicas=2, n_base=30, n_div=6, capacity=64, hide_every=8)
    v5batch = benchgen.batched_v5_inputs(batch, 64)
    args = [jnp.asarray(batch[k] if k in batch else v5batch[k])
            for k in benchgen.LANE_KEYS5]
    return args, int(benchgen.v5_token_budget(v5batch))


def test_program_cache_key_unaffected_by_obs():
    """Enabling obs must not perturb the program-cache key: the program
    built with obs off is the one served with obs on (identity is
    one-way: obs observes it, never feeds it)."""
    from cause_tpu import benchgen

    args, u = _tiny_v5_args()
    obs.configure(enabled=False)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    keys = set(benchgen._scalar_programs)
    obs.configure(enabled=True)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    assert set(benchgen._scalar_programs) == keys
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("program_cache.hit", 0) == 1
    assert snap.get("program_cache.miss", 0) == 0


# ---------------------------- perfetto -----------------------------


def test_perfetto_schema(tmp_path):
    obs.configure(enabled=True)
    with obs.span("outer", strategy="matrix"):
        with obs.span("inner"):
            pass
    obs.event("gate", outcome="match")
    obs.counter("program_cache.hit").inc(3)
    obs.flush()
    path = str(tmp_path / "trace.json")
    n = obs.export_perfetto(path, events=obs.events())
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == n
    by_ph = {}
    for t in doc["traceEvents"]:
        by_ph.setdefault(t["ph"], []).append(t)
    # complete slices for spans, instant for events, counter tracks,
    # process-name metadata
    assert {t["name"] for t in by_ph["X"]} == {"outer", "inner"}
    for t in by_ph["X"]:
        assert t["ts"] > 0 and t["dur"] >= 1
        assert t["pid"] == os.getpid() and "tid" in t
    assert by_ph["i"][0]["name"] == "gate"
    assert by_ph["i"][0]["args"]["outcome"] == "match"
    counters = {t["name"]: t["args"]["value"] for t in by_ph["C"]}
    assert counters["program_cache.hit"] == 3
    assert by_ph["M"], "process_name metadata missing"
    # span args carry the strategy attr (program provenance)
    outer = [t for t in by_ph["X"] if t["name"] == "outer"][0]
    assert outer["args"]["strategy"] == "matrix"


def test_perfetto_roundtrip_via_jsonl(tmp_path):
    jl = str(tmp_path / "ev.jsonl")
    obs.configure(enabled=True, out=jl)
    with obs.span("s"):
        pass
    obs.flush()
    # torn trailing line (abandoned-writer simulation) is skipped
    with open(jl, "a") as f:
        f.write('{"ev": "span", "name": "torn')
    evs = obs.load_jsonl(jl)
    assert [e["ev"] for e in evs] == ["span", "counters"]
    out = str(tmp_path / "t.json")
    assert obs.export_perfetto(out, jsonl=jl) >= 2


def test_cli_converts_jsonl(tmp_path):
    jl = str(tmp_path / "ev.jsonl")
    obs.configure(enabled=True, out=jl)
    with obs.span("cli.span"):
        pass
    obs.flush()
    out = str(tmp_path / "cli.perfetto.json")
    r = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", jl, "-o", out,
         "--summary"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    assert any(t["name"] == "cli.span" for t in doc["traceEvents"])
    assert "cli.span" in r.stdout  # --summary aggregate


def test_cli_summary_sums_counters_across_pids(tmp_path):
    """Counter snapshots are cumulative PER PROCESS; a shared sidecar
    (bench parent + abandoned child) must sum each pid's LAST snapshot,
    not let whichever process flushed last win."""
    jl = str(tmp_path / "multi.jsonl")
    with open(jl, "w") as f:
        for rec in (
            {"ev": "counters", "pid": 1,
             "counters": {"program_cache.miss": 2}},
            {"ev": "counters", "pid": 2,
             "counters": {"program_cache.miss": 1}},
            {"ev": "counters", "pid": 1,
             "counters": {"program_cache.miss": 5}},
        ):
            f.write(json.dumps(rec) + "\n")
    r = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", jl, "-o",
         str(tmp_path / "o.json"), "--summary"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr
    ctr = [json.loads(ln) for ln in r.stdout.splitlines()
           if "counters" in ln][0]["counters"]
    assert ctr["program_cache.miss"] == 6  # pid1's last (5) + pid2 (1)


# ------------------- instrumented-site integration ------------------


def test_program_cache_counters_and_strategy_spans():
    """End to end on the CPU backend: a tiny v5 merge_wave_scalar pass
    records program-cache miss-then-hit and emits the sort/gather/
    search spans from inside the traced kernel."""
    from cause_tpu import benchgen

    import jax

    args, u = _tiny_v5_args()
    # the spans come from tracing: this test's first call must build
    # (and trace the kernel's row pieces) afresh
    benchgen._scalar_programs.clear()
    jax.clear_caches()
    obs.configure(enabled=True)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    benchgen.merge_wave_scalar(*args, k_max=u, kernel="v5", u_max=u)
    snap = obs.counters_snapshot()["counters"]
    assert snap.get("program_cache.miss", 0) >= 1
    assert snap.get("program_cache.hit", 0) >= 1
    names = {e["name"] for e in obs.events() if e["ev"] == "span"}
    assert "weave.sort" in names
    assert "weave.gather" in names
    assert "weave.trace.v5" in names
    assert "program.build" in names
