"""cause_tpu.obs.live + cause_tpu.obs.watch — live telemetry.

Pins the PR-10 contract: obs-off invariance for the whole layer (no
records, no subscriber state, byte-identical program-cache keys),
incremental folds bit-equal to the batch reports (``lag_summary``,
``fleet_report``, ``costmodel_digest`` totals) on the committed PR-9
stream, the subscriber hook's bounded-queue semantics, alert-rule
firing / absence / burn semantics (edge-triggered: one ``live.alert``
per excursion), multi-stream tailing with rotation, the ``obs watch
--once`` render, and the stdlib Prometheus endpoint. The refactored
reducers are additionally pinned against the ``obs fleet`` / ``obs
lag`` CLI outputs, so the read-side refactor cannot have moved them.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from cause_tpu import obs
from cause_tpu.obs import costmodel, lag, live, semantic
from cause_tpu.obs import load_jsonl
from cause_tpu.obs import watch as watch_mod
from cause_tpu.obs.costmodel import CostReducer, costmodel_digest
from cause_tpu.obs.fleet import FleetReducer, fleet_report
from cause_tpu.obs.lag import LagReducer, lag_summary
from cause_tpu.obs.perfetto import CountersReducer, \
    merged_final_counters
from cause_tpu.benchgen import _scalar_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
R9_STREAM = os.path.join(REPO, "measurements", "obs_lag_r9.jsonl")


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test starts from a clean, DISABLED obs state and leaves
    none behind (the test_lag.py rule, extended to live)."""
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


def _window_event(pid=1, epoch=0, pending=0, converged=2, breach=0,
                  slo=100.0, ts_us=1000, lag_us=4000):
    """A minimal but schema-complete lag.window record."""
    h = lag.LagHistogram()
    for _ in range(converged):
        h.record_us(lag_us)
    return {"ev": "event", "name": "lag.window", "pid": pid,
            "ts_us": ts_us,
            "fields": {"uuid": "u", "source": "wave", "epoch": epoch,
                       "woven": converged, "converged": converged,
                       "pending": pending, "slo_ms": slo,
                       "slo_breach": breach,
                       "converged_total": converged,
                       "breach_total": breach,
                       "hist_woven": h.to_fields(),
                       "hist_converged": h.to_fields(),
                       "window": {"n": max(1, converged),
                                  "p50_ms": lag_us / 1000.0,
                                  "p95_ms": lag_us / 1000.0,
                                  "p99_ms": lag_us / 1000.0,
                                  "breach_frac": (breach
                                                  / max(1, converged)),
                                  "burn_rate": round(
                                      (breach / max(1, converged))
                                      / 0.01, 2)}}}


def _wave_digest(ts_us=1000, uuid="u", agreed=True, pairs=2):
    return {"ev": "event", "name": "wave.digest", "pid": 1,
            "ts_us": ts_us,
            "fields": {"uuid": uuid, "source": "wave", "wave": 1,
                       "pairs": pairs, "valid": pairs, "distinct": 1,
                       "agreed": agreed, "staleness": {"0": pairs}}}


# ----------------------------------------------- obs-off invariance


def test_obs_off_is_invariant(tmp_path):
    """The PR-1 contract extended to the live layer: with obs
    disabled, attach() returns None, nothing records, no subscriber
    state exists anywhere, and program-cache keys stay
    byte-identical."""
    out = str(tmp_path / "never.jsonl")
    obs.configure(enabled=False, out=out)
    key_before = sorted(_scalar_programs)

    assert obs.subscribe() is None
    assert live.attach() is None
    # the monitor as a pure reader still works obs-off (tailing a
    # foreign sidecar) but emits nothing locally
    mon = live.LiveMonitor(rules=["pending>0"])
    mon.feed([_window_event(pending=3)])
    fired = mon.evaluate()
    assert len(fired) == 1          # evaluated + returned...
    assert obs.events() == []       # ...but never recorded
    assert obs.counters_snapshot() == {"counters": {}, "gauges": {}}
    assert not os.path.exists(out)
    from cause_tpu.obs.core import _STATE

    assert _STATE is not None and _STATE.subscribers == ()
    key_after = sorted(_scalar_programs)
    assert key_after == key_before


# ------------------------------------------------- subscriber hook


def test_subscriber_receives_records_and_unsubscribes():
    obs.configure(enabled=True)
    sub = obs.subscribe()
    obs.event("wave.digest", uuid="u", agreed=True)
    with obs.span("x"):
        pass
    got = sub.drain()
    assert [e["ev"] for e in got] == ["event", "span"]
    assert sub.drain() == []        # drained means drained
    obs.unsubscribe(sub)
    obs.event("wave.digest", uuid="u")
    assert sub.drain() == []        # detached means detached
    obs.unsubscribe(sub)            # idempotent
    obs.unsubscribe(None)           # obs-off result is accepted


def test_subscriber_queue_is_bounded():
    obs.configure(enabled=True)
    sub = obs.subscribe(maxlen=4)
    for i in range(10):
        obs.event("e", i=i)
    got = sub.drain()
    assert len(got) == 4
    assert [e["fields"]["i"] for e in got] == [6, 7, 8, 9]  # newest win
    assert sub.dropped == 6
    obs.unsubscribe(sub)


# ------------------------------------- bit-equality vs batch reports


def test_incremental_folds_bit_equal_on_committed_stream():
    """The acceptance property: feeding the committed PR-9 stream one
    record at a time through the reducers yields BYTE-identical
    reports to the batch passes."""
    events = load_jsonl(R9_STREAM)
    assert events, "committed stream missing"
    lr, fr, cr = LagReducer(), FleetReducer(), CostReducer()
    ctr = CountersReducer()
    for e in events:
        lr.feed(e)
        fr.feed(e)
        cr.feed(e)
        ctr.feed(e)

    def j(x):
        return json.dumps(x, sort_keys=True)

    assert j(lr.report()) == j(lag_summary(events))
    assert j(fr.report()) == j(fleet_report(events))
    assert j(cr.digest()) == j(costmodel_digest(events))
    assert j(ctr.totals()) == j(merged_final_counters(events))
    # the fold engine wraps the same reducers: same numbers
    fold = live.LiveFold()
    fold.feed_many(events)
    snap = fold.snapshot(now_us=fold.last_ts_us)
    assert j(snap["lag"]) == j(lag_summary(events))
    batch_cost = costmodel_digest(events)
    for k in ("waves", "dispatches", "delta_ops", "wall_ms"):
        assert snap["cost"][k] == batch_cost[k]


def test_incremental_folds_bit_equal_epoch_scoped():
    """Epoch scoping (the multi-fleet bench rule) holds incrementally
    too."""
    events = [_window_event(epoch=0, converged=2),
              _window_event(epoch=1, converged=5, ts_us=2000)]
    lr = LagReducer()
    for e in events:
        lr.feed(e)
    for epoch in (None, 0, 1):
        assert (json.dumps(lr.report(epoch=epoch), sort_keys=True)
                == json.dumps(lag_summary(events, epoch=epoch),
                              sort_keys=True))
    assert lr.report(epoch=1)["ops_converged"] == 5


def test_reducers_pin_cli_outputs():
    """The refactor satellite: `obs fleet` / `obs lag` over the
    committed stream must still say exactly what the reducers say."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    events = load_jsonl(R9_STREAM)
    res = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "lag", R9_STREAM,
         "--json"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout) == lag_summary(events)
    res = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "fleet", R9_STREAM,
         "--json"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout) == fleet_report(events)


# ------------------------------------------------------ alert rules


def test_threshold_rule_fires_once_per_excursion():
    mon = live.LiveMonitor(rules=["pending>2"])
    mon.feed([_window_event(pending=5)])
    assert len(mon.evaluate()) == 1
    assert mon.evaluate() == []              # same excursion: silent
    mon.feed([_window_event(pending=0, ts_us=2000)])
    assert mon.evaluate() == []              # recovered: re-armed
    mon.feed([_window_event(pending=9, ts_us=3000)])
    fired = mon.evaluate()
    assert len(fired) == 1                   # new excursion fires again
    assert fired[0]["rule"] == "pending>2"
    assert fired[0]["value"] == 9
    assert len(mon.alerts) == 2


def test_burn_rule_semantics():
    """'SLO burn > 2x' reads the summed exact breach counters: 2 of 4
    ops breaching a 99% goal burns 50x the budget."""
    mon = live.LiveMonitor(rules=["burn>2"])
    mon.feed([_window_event(converged=4, breach=2)])
    fired = mon.evaluate()
    assert len(fired) == 1
    assert fired[0]["path"] == "lag.slo.burn_rate"
    assert fired[0]["value"] == 50.0
    healthy = live.LiveMonitor(rules=["burn>2"])
    healthy.feed([_window_event(converged=4, breach=0)])
    assert healthy.evaluate() == []


def test_absence_rule_semantics():
    """The wedge detector: fires when the stream keeps producing
    records but the named event goes quiet; never fires on an empty
    stream; never-seen events judge against the stream's own span."""
    mon = live.LiveMonitor(rules=["absence:wave.digest:120"])
    assert mon.evaluate() == []              # empty stream: silent
    t0 = 1_000_000_000
    mon.feed([_wave_digest(ts_us=t0)])
    # 60 s later: inside the window
    assert mon.evaluate(now_us=t0 + 60_000_000) == []
    # 200 s later: wedged
    fired = mon.evaluate(now_us=t0 + 200_000_000)
    assert len(fired) == 1 and fired[0]["kind"] == "absence"
    assert fired[0]["age_s"] == pytest.approx(200, abs=1)
    # never-seen: other records flow, the event never appears
    mon2 = live.LiveMonitor(rules=["absence:wave.digest:120"])
    mon2.feed([{"ev": "event", "name": "run.heartbeat",
                "pid": 1, "ts_us": t0, "fields": {"stage": "wave"}}])
    assert mon2.evaluate(now_us=t0 + 30_000_000) == []
    assert len(mon2.evaluate(now_us=t0 + 300_000_000)) == 1


def test_alert_emits_record_and_fires_callbacks(tmp_path):
    out = str(tmp_path / "events.jsonl")
    obs.configure(enabled=True, out=out)
    hits = []
    mon = live.LiveMonitor(rules=["pending>0"],
                           on_alert=[hits.append])
    mon.feed([_window_event(pending=1)])
    mon.evaluate()
    assert len(hits) == 1 and hits[0]["rule"] == "pending>0"
    recorded = [e for e in load_jsonl(out)
                if e.get("name") == "live.alert"]
    assert len(recorded) == 1
    assert recorded[0]["fields"]["rule"] == "pending>0"


def test_default_rules_and_parse_errors():
    rules = live.default_rules()
    assert [r.spec for r in rules] == list(live.DEFAULT_RULE_SPECS)
    with pytest.raises(ValueError):
        live.parse_rule("not a rule")
    with pytest.raises(ValueError):
        live.parse_rule("absence:wave.digest")
    with pytest.raises(ValueError):
        live.parse_rule("pending>lots")
    r = live.parse_rule("sync.full_bag_rate>=0.5")
    assert r.path == "sync.full_bag_rate" and r.op == ">=" \
        and r.limit == 0.5


def test_live_snapshot_record(tmp_path):
    out = str(tmp_path / "events.jsonl")
    obs.configure(enabled=True, out=out)
    mon = live.LiveMonitor()
    mon.feed([_wave_digest(), _window_event()])
    snap = mon.emit_snapshot()
    assert snap["fleet"]["waves"] == 1
    recorded = [e for e in load_jsonl(out)
                if e.get("name") == "live.snapshot"]
    assert len(recorded) == 1
    f = recorded[0]["fields"]
    assert f["waves"] == 1 and f["ops_converged"] == 2
    assert f["verdict"] == "OK"
    # live.* routes onto a named semantic Perfetto track
    from cause_tpu.obs.perfetto import to_chrome_trace

    doc = to_chrome_trace(load_jsonl(out))
    names = {t.get("args", {}).get("name") for t in doc["traceEvents"]
             if t.get("name") == "thread_name"}
    assert "semantic:live" in names


# --------------------------------------------- in-process attachment


def test_attach_folds_own_stream_and_counters():
    obs.configure(enabled=True)
    att = live.attach(rules=["divergence>0"])
    obs.event("wave.digest", uuid="u", source="wave", wave=1, pairs=2,
              valid=2, distinct=1, agreed=True, staleness={"0": 2})
    obs.counter("sync.full_bag").inc(3)
    snap = att.poll()
    assert snap["fleet"]["waves"] == 1
    # counters reach the live fold WITHOUT an explicit flush(), and
    # the overlay is NOT counted as a stream record — the fold's
    # record count keeps matching what the process actually emitted
    assert snap["sync"]["full_bag"] == 3
    assert snap["records"] == 1
    assert snap["alerts_total"] == 0
    att.close()


def test_attach_sees_reset_as_closed():
    """obs.reset() drops all obs state, subscribers included: the
    attachment must SEE it died (closed) instead of silently draining
    an orphaned queue forever."""
    obs.configure(enabled=True)
    att = live.attach()
    assert not att.closed
    obs.reset()
    assert att.closed
    obs.configure(enabled=True)
    obs.event("wave.digest", uuid="u")
    assert att.poll()["fleet"]["waves"] == 0  # detached: sees nothing
    att.close()  # still safe


def test_concurrent_evaluate_fires_once():
    """The edge-trigger contract under concurrency: two threads
    evaluating through one excursion must emit exactly one alert."""
    import threading

    mon = live.LiveMonitor(rules=["pending>0"])
    mon.feed([_window_event(pending=7)])
    snap = mon.snapshot()
    barrier = threading.Barrier(2)

    def run():
        barrier.wait()
        for _ in range(50):
            mon.evaluate(snap=snap)

    ts = [threading.Thread(target=run) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(mon.alerts) == 1, mon.alerts


def test_cost_reducer_bounded_points_reported():
    """Point truncation is O(1) (deque) and reported, pooled AND per
    path."""
    r = CostReducer(points_max=4)
    for i in range(10):
        r.feed({"ev": "event", "name": "wave.cost",
                "fields": {"uuid": "u", "delta_ops": i + 1,
                           "wall_ms": float(i), "dispatches": 1,
                           "lanes": 8, "path": "delta"}})
    d = r.digest()
    assert d["waves"] == 10
    assert d["points_dropped"] == 6
    assert d["slope"]["points"] == 4
    by = r.curves_by_path()
    assert by["delta"]["points_dropped"] == 6


def test_attach_survives_fold_of_own_emissions():
    """emit_snapshot/live.alert flow back into the attachment's own
    queue; the next poll folds them without recursion or drift."""
    obs.configure(enabled=True)
    att = live.attach(rules=["pending>0"])
    obs.event("lag.window", **_window_event(pending=2)["fields"])
    s1 = att.poll(emit_snapshot=True)
    assert s1["alerts_total"] == 1
    s2 = att.poll(emit_snapshot=True)
    assert s2["records"] > s1["records"]     # folded its own rollup
    assert s2["alerts_total"] == 1           # still edge-triggered
    att.close()


# ------------------------------------------------- tailing + watch


def _write_lines(path, events, mode="a"):
    with open(path, mode) as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_multi_stream_tail_with_rotation(tmp_path):
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    tail = live.MultiTailer([a, b])
    assert tail.poll() == []                 # neither exists yet
    _write_lines(a, [_wave_digest(ts_us=10, uuid="d1")], mode="w")
    _write_lines(b, [_wave_digest(ts_us=5, uuid="d2")], mode="w")
    got = tail.poll()
    # batch merged by timestamp across files
    assert [e["ts_us"] for e in got] == [5, 10]
    # torn line: buffered until its newline lands
    with open(a, "a") as f:
        f.write('{"ev": "event", "na')
    assert tail.poll() == []
    with open(a, "a") as f:
        f.write('me": "wave.digest", "ts_us": 20}\n')
    got = tail.poll()
    assert len(got) == 1 and got[0]["ts_us"] == 20
    # rotation: replaced file is re-read from byte zero
    os.remove(a)
    _write_lines(a, [_wave_digest(ts_us=30, uuid="d1")], mode="w")
    got = tail.poll()
    assert len(got) == 1 and got[0]["ts_us"] == 30
    # truncation (same inode, file SHRUNK below the read position)
    # also rewinds to byte zero
    _write_lines(b, [_wave_digest(ts_us=35, uuid="d2"),
                     _wave_digest(ts_us=36, uuid="d2")])
    assert [e["ts_us"] for e in tail.poll()] == [35, 36]
    _write_lines(b, [_wave_digest(ts_us=40, uuid="d2")], mode="w")
    got = tail.poll()
    assert len(got) == 1 and got[0]["ts_us"] == 40
    tail.close()


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", *argv],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_watch_once_renders_committed_stream():
    res = _run_cli("watch", R9_STREAM, "--once")
    assert res.returncode == 0, res.stderr
    assert "live telemetry" in res.stdout
    assert "64 replicas" in res.stdout
    assert "SLO 100 ms" in res.stdout
    assert "alerts:" in res.stdout
    # ages are judged against the stream's own end, so the wedge
    # detector stays silent on a healthy historical stream
    assert "absence:wave.digest" not in res.stdout
    # the r9 run honestly breached its 100 ms CPU SLO: burn fires
    assert "burn>2" in res.stdout


def test_watch_once_json_and_custom_rules(tmp_path):
    stream = str(tmp_path / "s.jsonl")
    _write_lines(stream, [_wave_digest(ts_us=1_000_000),
                          _window_event(ts_us=2_000_000)], mode="w")
    res = _run_cli("watch", stream, "--once", "--json",
                   "--rules", "p99>0.001")
    assert res.returncode == 0, res.stderr
    doc = json.loads(res.stdout)
    assert doc["snapshot"]["fleet"]["waves"] == 1
    assert len(doc["alerts"]) == 1
    assert doc["alerts"][0]["rule"] == "p99>0.001"
    # healthy rules: zero alerts
    res = _run_cli("watch", stream, "--once", "--json")
    assert json.loads(res.stdout)["alerts"] == []
    # malformed rule fails loudly
    res = _run_cli("watch", stream, "--once", "--rules", "garbage")
    assert res.returncode == 2
    # missing file
    res = _run_cli("watch", str(tmp_path / "nope.jsonl"), "--once")
    assert res.returncode == 2


def test_watch_render_sections():
    events = load_jsonl(R9_STREAM)
    mon = live.LiveMonitor()
    mon.feed(events)
    snap = mon.snapshot(now_us=mon.fold.last_ts_us)
    text = watch_mod.render(snap, mon.alerts, [R9_STREAM])
    for needle in ("fleet:", "lag:", "sync:", "cost:", "ages:",
                   "alerts:"):
        assert needle in text, text


def test_prometheus_endpoint_smoke():
    events = load_jsonl(R9_STREAM)
    mon = live.LiveMonitor()
    mon.feed(events)
    snap = mon.snapshot(now_us=mon.fold.last_ts_us)
    text = watch_mod.prometheus_text(snap)
    assert "cause_tpu_live_ops_converged 16" in text
    assert "cause_tpu_live_waves_total 8" in text
    assert "# TYPE cause_tpu_live_lag_p99_ms gauge" in text
    server, port = watch_mod.serve_metrics(0, lambda: snap)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert body == text
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10).read())
        assert doc["lag"]["ops_converged"] == 16
    finally:
        server.shutdown()


def test_fold_rolling_state_axes():
    """The live-only axes: waves/sec, headroom minima, heartbeat
    recency."""
    t0 = 1_000_000_000
    fold = live.LiveFold()
    fold.feed_many([
        _wave_digest(ts_us=t0),
        _wave_digest(ts_us=t0 + 30_000_000),
        {"ev": "gauge", "name": "fleet.token_headroom.wave",
         "ts_us": t0, "pid": 1, "value": 96},
        {"ev": "gauge", "name": "fleet.token_headroom.wave",
         "ts_us": t0 + 1, "pid": 1, "value": 32},
        {"ev": "gauge", "name": "fleet.token_headroom.session",
         "ts_us": t0 + 2, "pid": 1, "value": 64},
        {"ev": "event", "name": "run.heartbeat", "pid": 1,
         "ts_us": t0 + 30_000_000,
         "fields": {"item": "bench_v5", "stage": "start",
                    "elapsed": 1.0}},
    ])
    snap = fold.snapshot(now_us=t0 + 30_000_000)
    assert snap["rates"]["waves_per_s"] == pytest.approx(2 / 60.0,
                                                         rel=1e-3)
    assert snap["headroom"]["min"] == 32
    assert snap["headroom"]["min_by_site"] == {"wave": 32,
                                               "session": 64}
    assert snap["headroom"]["last_by_site"]["wave"] == 32
    assert snap["heartbeat"]["item"] == "bench_v5"
    assert snap["ages_s"]["run.heartbeat"] == 0.0
    assert snap["ages_s"]["wave.digest"] == 0.0
