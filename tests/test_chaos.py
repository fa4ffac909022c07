"""PR 11: chaos harness + self-healing fleet.

Pins the robustness layer's four contracts:

- **chaos-off invariance** — with ``CAUSE_TPU_CHAOS`` unset, the
  engine keeps zero state, the hooks are inert, no records mint
  anywhere, the quarantine registry stays empty, and the raw
  program-cache key mapping is byte-identical (the obs contract,
  verbatim);
- **validated ingest** — the legacy failure shapes (a truncated
  payload raising a bare ValueError deep inside serde, a malformed id
  being silently ADMITTED into the node bag) are pinned, and the new
  validate-before-apply boundary rejects both with ``sync.reject``
  and the document untouched; repeat offenders quarantine and
  re-admit over a validated full-bag resync; a hypothesis fuzzer
  pins "validation never admits a payload that fails round-trip";
- **the recovery ladder** — deterministic seeded injection per
  family, transient dispatch failures retried with ``recovery.retry``
  evidence, budget exhaustion stepping delta->full with the declared
  ``recovery.step`` order, stalls tripping the live heartbeat-absence
  rule;
- **checkpoint/restore** — serde round-trip of the resident session,
  restore gated on digest bit-identity, and the restored session's
  first wave riding the DELTA path (the steady-state resume the
  checkpoint exists for).
"""

import json
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

import cause_tpu as c
from cause_tpu import chaos, obs, serde, sync
from cause_tpu.collections import clist as c_list
from cause_tpu.collections import shared as s
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.obs import semantic
from cause_tpu.parallel import merge_wave, recovery
from cause_tpu.parallel.session import FleetSession
from cause_tpu.benchgen import _scalar_programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    """Every test starts with chaos DISARMED, obs disabled, and empty
    quarantine/monitor registries — and leaves none of it behind."""
    for k in ("CAUSE_TPU_CHAOS", "CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT",
              "CAUSE_TPU_OBS_RING"):
        monkeypatch.delenv(k, raising=False)
    chaos.reset()
    obs.reset()
    semantic.reset()
    sync.quarantine_reset()
    yield
    chaos.reset()
    obs.reset()
    semantic.reset()
    sync.quarantine_reset()


def _base(n=20):
    base = CausalList(c_list.weave(
        c.clist(weaver="jax").extend(["w"] * n).ct
    ))
    base.ct.lanes.segments()
    return base


def _pair(base, ea=("A",), eb=("B",)):
    a = CausalList(base.ct.evolve(site_id=new_site_id()))
    b = CausalList(base.ct.evolve(site_id=new_site_id()))
    for v in ea:
        a = a.conj(v)
    for v in eb:
        b = b.conj(v)
    return a, b


def _events(name=None):
    evs = [e for e in obs.events() if e.get("ev") == "event"]
    if name is None:
        return evs
    return [e for e in evs if e.get("name") == name]


# ----------------------------------------------- chaos-off invariance


def test_chaos_off_is_invariant(tmp_path):
    """The off-invariance contract: chaos unset means the hooks are
    inert pass-throughs, zero engine state, zero obs records, zero
    quarantine registry state, and byte-identical raw program-cache
    keys after a full sync + wave + session pass."""
    out = str(tmp_path / "never.jsonl")
    obs.configure(enabled=False, out=out)
    key_before = sorted(_scalar_programs)

    assert chaos.enabled() is False
    base = _base()
    a, b = _pair(base)
    a2, b2 = sync.sync_pair(a, b)
    assert c.causal_to_edn(a2) == c.causal_to_edn(b2)
    res = merge_wave([(a, b)] * 2)
    assert len(res) == 2
    sess = FleetSession([(a, b)] * 2)
    sess.wave()

    # hooks are inert: same-object pass-through, no log, no faults
    enc = [[[1, "site", 0], [0, "r", 0], "v"]]
    assert chaos.mangle_items(enc) is enc
    assert chaos.dispatch_fault("wave") is None
    assert chaos.budget_exhaust("session") is False
    assert chaos.should_crash("session") is False
    assert chaos.stall_point("session") == 0.0
    assert chaos.injected() == []
    assert chaos.chaos_report()["injected"] == 0

    assert obs.events() == []
    assert obs.counters_snapshot() == {"counters": {}, "gauges": {}}
    assert not os.path.exists(out)
    assert sync.quarantined() == frozenset()
    assert not sync.any_quarantined()
    key_after = sorted(_scalar_programs)
    assert key_after == key_before


# ------------------------------------------- deterministic injection


def _drive_hooks():
    """One fixed hook-call sequence across every family."""
    fired = []
    for i in range(12):
        enc = [[[t, f"s{t}", 0], [0, "r", 0], f"v{t}"]
               for t in range(1, 4)]
        got = chaos.mangle_items(enc, "sync.delta")
        if got is not enc:
            fired.append(("payload", i, json.dumps(got)))
        try:
            chaos.dispatch_fault("session")
        except chaos.InjectedDispatchError:
            fired.append(("dispatch", i))
        if chaos.budget_exhaust("session"):
            fired.append(("exhaust", i))
        if chaos.should_crash("session"):
            fired.append(("crash", i))
    return fired


def test_each_family_fires_deterministically_by_seed():
    """The repro contract: the same plan over the same call sequence
    injects the same faults at the same points — including the
    mangled payload BYTES — and a different seed moves the
    probabilistic firings."""
    plan = {"seed": 7, "faults": [
        {"family": "payload", "site": "sync.delta", "mode": "corrupt",
         "prob": 0.35},
        {"family": "dispatch", "site": "session", "mode": "raise",
         "at": [3, 9]},
        {"family": "dispatch", "site": "session", "mode": "exhaust",
         "at": [5]},
        {"family": "crash", "site": "session", "at": [7]},
    ]}
    runs = []
    for _ in range(2):
        chaos.configure(plan=plan)
        runs.append((_drive_hooks(), [
            {k: v for k, v in r.items() if k != "ts_us"}
            for r in chaos.injected()]))
        chaos.reset()
    assert runs[0] == runs[1]
    fams = {r["family"] for r in runs[0][1]}
    assert fams == {"payload", "dispatch", "crash"}
    # the probabilistic payload schedule is seed-dependent
    chaos.configure(plan={**plan, "seed": 8})
    other = _drive_hooks()
    chaos.reset()
    assert [f for f in other if f[0] == "payload"] != \
        [f for f in runs[0][0] if f[0] == "payload"]


def test_suspended_consumes_no_counters():
    """The oracle contract: hook calls inside ``chaos.suspended()``
    neither fire nor advance any spec's invocation counter — the
    fault lands at the same ``at`` index with or without interleaved
    suspended traffic."""
    plan = {"seed": 1, "faults": [
        {"family": "crash", "site": "session", "at": [2]}]}
    chaos.configure(plan=plan)
    assert not chaos.should_crash("session")         # seq 1
    with chaos.suspended():
        for _ in range(5):
            assert not chaos.should_crash("session")  # consumed: no
    assert chaos.should_crash("session")             # seq 2 -> fires


# ------------------------------- validated ingest: the legacy seam


def test_legacy_malformed_payload_seam_is_pinned():
    """SATELLITE REGRESSION: what an unvalidated ingest does today.
    A truncated triple raises a bare ValueError from deep inside the
    serde decode (no boundary, no CausalError); a malformed id (int
    site) is WORSE — it decodes fine and the merge silently ADMITS
    it into the node bag. Both shapes are exactly what
    validate_node_items now refuses at the boundary."""
    base = c.clist(*"hello")
    peer = CausalList(base.ct.evolve(site_id=new_site_id())).conj("x")
    enc = serde.encode_node_items(
        sync.delta_nodes(peer, sync.version_vector(base)))

    truncated = [list(x) for x in enc]
    truncated[0] = truncated[0][:2]
    with pytest.raises(ValueError):  # NOT CausalError: deep unpack
        sync.apply_delta(base, serde.decode_node_items(truncated))

    bad_id = [list(x) for x in enc]
    bad_id[0] = [[bad_id[0][0][0], 12345, bad_id[0][0][2]],
                 bad_id[0][1], bad_id[0][2]]
    admitted = sync.apply_delta(base,
                                serde.decode_node_items(bad_id))
    # the mis-weave: a node keyed by an int "site" is now IN the tree
    assert any(not isinstance(nid[1], str)
               for nid in admitted.ct.nodes), \
        "legacy seam closed? update this pin and the boundary test"

    # the new boundary rejects both shapes as CausalError, pre-merge
    for bad in (truncated, bad_id):
        with pytest.raises(s.CausalError) as ei:
            sync.checked_decode(bad)
        assert "payload-invalid" in ei.value.info["causes"]


def test_validate_rejects_each_mangle_mode():
    """Every payload fault family is detectable: structure catches
    truncate/duplicate/reorder/bad-ids, the checksum catches
    corrupt/drop (any post-CRC change)."""
    enc = [[[1, "sa", 0], [0, "root", 0], "a"],
           [[2, "sb", 0], [1, "sa", 0], "b"],
           [[3, "sc", 1], [2, "sb", 0], "c"]]
    crc = sync.payload_checksum(enc)
    sync.validate_node_items(enc)  # the clean payload passes
    assert sync.checked_decode(enc, crc)

    cases = {
        "truncate": [enc[0][:2], enc[1], enc[2]],
        "duplicate": [enc[0], enc[0], enc[1], enc[2]],
        "reorder": [enc[2], enc[1], enc[0]],
        "bad-id": [[[1, 99, 0], enc[0][1], "a"], enc[1], enc[2]],
        "bad-cause": [[enc[0][0], [1, 2], "a"], enc[1], enc[2]],
        "not-a-list": {"nodes": 1},
    }
    for name, bad in cases.items():
        with pytest.raises(s.CausalError) as ei:
            sync.checked_decode(bad, crc)
        assert "payload-invalid" in ei.value.info["causes"], name
    for name, mangled in {
        "corrupt": [[enc[0][0], enc[0][1], "POISON"], enc[1], enc[2]],
        "drop": [enc[0], enc[2]],
    }.items():
        with pytest.raises(s.CausalError) as ei:
            sync.checked_decode(mangled, crc)
        assert "payload-checksum" in ei.value.info["causes"], name


def _stream_sync(a, b):
    """One framed anti-entropy round over a real socketpair (the
    test_sync idiom): returns (a', b') or raises the first error."""
    s1, s2 = socket.socketpair()
    out, err = {}, {}

    def run(name, handle, sock):
        try:
            with sock.makefile("rwb") as stream:
                out[name] = sync.sync_stream(handle, stream)
        except Exception as e:  # noqa: BLE001 - surfaced below
            err[name] = e
        finally:
            sock.close()

    ta = threading.Thread(target=run, args=("a", a, s1))
    tb = threading.Thread(target=run, args=("b", b, s2))
    ta.start(); tb.start(); ta.join(30); tb.join(30)
    if err:
        raise next(iter(err.values()))
    return out["a"], out["b"]


def test_stream_reject_at_boundary_document_untouched():
    """The boundary in situ: a chaos-corrupted delta frame over a
    real socket is rejected (``sync.reject``, document untouched by
    the poison) and the round heals over the validated full bag —
    both ends converge to the clean merge."""
    obs.configure(enabled=True)
    chaos.configure(plan={"seed": 5, "faults": [
        {"family": "payload", "site": "sync.delta", "mode": "corrupt",
         "times": 1, "prob": 1.0}]})
    base = c.clist(*"hello")
    a = CausalList(base.ct.evolve(site_id=new_site_id())).conj("!")
    b = CausalList(base.ct.evolve(site_id=new_site_id())).cons("<")
    a2, b2 = _stream_sync(a, b)
    assert c.causal_to_edn(a2) == c.causal_to_edn(b2)
    assert c.causal_to_edn(a2) == c.causal_to_edn(a.merge(b))
    assert chaos.CORRUPT_MARKER not in json.dumps(
        c.causal_to_edn(a2), default=str)
    rejects = _events("sync.reject")
    assert len(rejects) == 1
    assert rejects[0]["fields"]["why"] == "payload-checksum"
    # the heal is evidenced as a payload-reject full bag
    reasons = {e["fields"]["reason"]
               for e in _events("sync.full_bag")}
    assert "payload-reject" in reasons
    assert _events("chaos.inject"), "the fault itself is evidenced"


def test_quarantine_roundtrip_full_bag_readmission():
    """Repeat offenders: QUARANTINE_AFTER consecutive rejects
    quarantine the sending replica (``sync.quarantine``), a
    quarantined replica's pairs are routed out of the device wave to
    the validating host merge, and the next sync round's full-bag
    resync re-admits it (``sync.readmit``) — the full cycle."""
    obs.configure(enabled=True)
    base = _base()
    a, b = _pair(base)
    peer = b.ct.site_id
    chaos.configure(plan={"seed": 2, "faults": [
        {"family": "payload", "site": "sync.delta", "mode": "corrupt",
         "prob": 1.0, "times": 2 * sync.QUARANTINE_AFTER}]})
    for i in range(sync.QUARANTINE_AFTER):
        # fresh divergence every round so the b->a delta is nonempty
        b = b.conj(f"q{i}")
        a, b = sync.sync_pair(a, b)
        assert c.causal_to_edn(a) == c.causal_to_edn(b)  # healed
    assert sync.is_quarantined(peer)
    assert peer in sync.quarantined()
    (qev,) = _events("sync.quarantine")
    assert qev["fields"]["peer"] == peer
    assert qev["fields"]["rejects"] == sync.QUARANTINE_AFTER

    # quarantined OUT of the device wave: the pair host-merges
    res = merge_wave([(a, b), (a, b)])
    assert res.fallback == [0, 1]
    assert not res.digest_valid.any()
    assert (c.causal_to_edn(res.merged(0))
            == c.causal_to_edn(a.merge(b)))
    assert obs.counters_snapshot()["counters"]["wave.quarantined"] == 2
    steps = [e["fields"] for e in _events("recovery.step")]
    assert any(st["reason"] == "quarantined" and st["to"] == "host"
               for st in steps)

    # the road back in: the next sync round goes straight to the
    # (trusted, validated) full bag and re-admits
    b = b.conj("back")
    a, b = sync.sync_pair(a, b)
    assert c.causal_to_edn(a) == c.causal_to_edn(b)
    assert not sync.is_quarantined(peer)
    (rev,) = _events("sync.readmit")
    assert rev["fields"]["peer"] == peer
    reasons = [e["fields"]["reason"] for e in _events("sync.full_bag")]
    assert "quarantined" in reasons


def test_payload_fuzzer_validation_implies_roundtrip():
    """Seeded payload fuzzer: any byte-level mutation of a real
    encoded payload either FAILS validation+checksum, or decodes and
    re-encodes to exactly the admitted bytes — validation never
    admits a payload that fails round-trip."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import strategies as st

    base = c.clist(*"fuzzme")
    peer = CausalList(base.ct.evolve(site_id=new_site_id()))
    for i in range(6):
        peer = peer.conj(f"v{i}")
    enc = serde.encode_node_items(
        sync.delta_nodes(peer, sync.version_vector(base)))
    crc = sync.payload_checksum(enc)
    blob = json.dumps(enc)

    @hypothesis.settings(max_examples=120, deadline=None)
    @hypothesis.given(st.integers(0, len(blob) - 1),
                      st.characters(min_codepoint=32, max_codepoint=126))
    def prop(pos, ch):
        mutated = blob[:pos] + ch + blob[pos + 1:]
        try:
            data = json.loads(mutated)
        except ValueError:
            return  # not even JSON: the frame reader drops it
        try:
            nodes = sync.checked_decode(data, crc)
        except s.CausalError as e:
            assert {"payload-invalid", "payload-checksum"} \
                & set(e.info["causes"])
            return
        # admitted: must round-trip bit-for-bit through the codec
        assert serde.encode_node_items(nodes) == data == enc

    prop()


# --------------------------------------------------- recovery ladder


def test_ladder_order_and_transient_retry():
    """The declared ladder order is the policy; a transient dispatch
    failure costs a ``recovery.retry``, not the wave; a
    non-transient error propagates immediately; exhaustion emits
    ``recovery.exhausted`` and re-raises."""
    assert recovery.LADDER == ("delta", "full", "double_budget",
                               "host")
    obs.configure(enabled=True)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise chaos.InjectedDispatchError("flake")
        return "ok"

    assert recovery.run_dispatch("wave", flaky) == "ok"
    (rt,) = _events("recovery.retry")
    assert rt["fields"]["site"] == "wave"

    with pytest.raises(ValueError):
        recovery.run_dispatch(
            "wave", lambda: (_ for _ in ()).throw(ValueError("hard")))
    assert len(_events("recovery.retry")) == 1  # no retry of hard errors

    def always():
        raise chaos.InjectedDispatchError("forever")

    with pytest.raises(chaos.InjectedDispatchError):
        recovery.run_dispatch("tree", always, retries=1, backoff_s=0)
    (ex,) = _events("recovery.exhausted")
    assert ex["fields"]["attempts"] == 2


def test_session_dispatch_fault_retried_and_budget_exhaust_steps():
    """Injected faults at the session's dispatch seam: a ``raise``
    fault is retried transparently (same digests as the clean run),
    and a budget-exhaust fault steps delta->full with the declared
    ``recovery.step`` reason while staying bit-identical."""
    base = _base()
    a, b = _pair(base)
    control = FleetSession([(a, b)] * 2)
    d_control = [control.wave()]
    ca, cb = a, b
    for r in range(2):
        ca, cb = ca.conj(f"x{r}"), cb.conj(f"y{r}")
        control.update([(ca, cb)] * 2)
        d_control.append(control.wave())

    obs.configure(enabled=True)
    chaos.configure(plan={"seed": 9, "faults": [
        {"family": "dispatch", "site": "session", "mode": "raise",
         "at": [1]},
        {"family": "dispatch", "site": "session", "mode": "exhaust",
         "at": [2]},
    ]})
    sess = FleetSession([(a, b)] * 2)
    d = [sess.wave()]
    fa, fb = a, b
    for r in range(2):
        fa, fb = fa.conj(f"x{r}"), fb.conj(f"y{r}")
        sess.update([(fa, fb)] * 2)
        d.append(sess.wave())
    for got, want in zip(d, d_control):
        assert np.array_equal(got, want)
    assert len(_events("recovery.retry")) >= 1
    steps = [e["fields"] for e in _events("recovery.step")]
    assert any(st["from"] == "delta" and st["to"] == "full"
               and st["reason"] == "budget-exhaustion"
               for st in steps)
    rep = chaos.chaos_report()
    assert rep["by_family"]["dispatch"] == 2


def test_update_degradations_are_evidenced():
    """Every update-level delta->full bounce is a declared
    ``recovery.step``: shrink the fleet's delta budget to force a
    delta-overflow degradation and read the reason off the event."""
    obs.configure(enabled=True)
    base = _base()
    a, b = _pair(base)
    sess = FleetSession([(a, b)] * 2, d_max=2)
    sess.wave()
    big_a = a
    for i in range(8):  # way past d_max=2
        big_a = big_a.conj(f"big{i}")
    sess.update([(big_a, b)] * 2)
    steps = [e["fields"] for e in _events("recovery.step")]
    assert any(st["site"] == "session" and st["from"] == "delta"
               and st["to"] == "full"
               and st["reason"] == "delta-overflow" for st in steps)


def test_stall_trips_heartbeat_absence_alert():
    """The stall fault exists to trip PR-10's wedge detector: replay
    the stalled session's own stream through a LiveMonitor whose
    absence window is shorter than the injected stall — exactly one
    live.alert fires across the stall gap, then the arriving digest
    re-arms the rule. Warm phase runs obs-off (the BENCH_LAG rule)
    so compile spikes never imitate the stall."""
    from cause_tpu.obs.live import LiveMonitor

    chaos.configure(plan={"seed": 4, "faults": [
        {"family": "stall", "site": "session", "ms": 900,
         "at": [4]}]})
    base = _base()
    a, b = _pair(base)
    sess = FleetSession([(a, b)] * 2)
    sess.wave()                       # stall seq 1 (obs off, warm)
    a, b = a.conj("s"), b.conj("t")
    sess.update([(a, b)] * 2)
    sess.wave()                       # seq 2: warms the delta program
    obs.configure(enabled=True)
    a, b = a.conj("u"), b.conj("v")
    sess.update([(a, b)] * 2)
    sess.wave()                       # seq 3: clean measured wave
    a, b = a.conj("w"), b.conj("x")
    sess.update([(a, b)] * 2)
    sess.wave()                       # seq 4: stalls 900 ms
    assert chaos.chaos_report()["by_family"]["stall"] == 1
    mon = LiveMonitor(rules=["absence:wave.digest:0.6"],
                      source="test")
    fired = []
    for e in obs.events():
        ts = e.get("ts_us")
        if isinstance(ts, (int, float)):
            # evaluate BEFORE feeding: the age the monitor sees at
            # this record's arrival is the gap since the last digest
            fired += mon.evaluate(now_us=int(ts))
        mon.feed([e])
    assert len(fired) == 1, fired
    assert fired[0]["rule"] == "absence:wave.digest:0.6"


# ------------------------------------------------ checkpoint/restore


def test_checkpoint_restore_digest_identity_and_delta_resume():
    """The serde checkpoint round-trip: restore is gated on digest
    bit-identity, restores the delta frontier, and the restored
    session's first wave RIDES THE DELTA PATH (wave.cost
    path="delta") with digests bit-identical to both the original
    session and a full-width control."""
    base = _base(40)
    a, b = _pair(base)
    sess = FleetSession([(a, b)] * 4)
    sess.wave()
    a, b = a.conj("x"), b.conj("y")
    sess.update([(a, b)] * 4)
    d1 = sess.wave()
    assert sess._delta is not None
    blob = json.dumps(sess.checkpoint())  # JSON all the way down

    restored = FleetSession.restore(json.loads(blob))
    assert restored._delta is not None, "frontier lost in restore"
    assert np.array_equal(restored._last_digest, d1)
    assert restored._delta["w_cap"] == sess._delta["w_cap"]
    assert np.array_equal(restored._delta["s"], sess._delta["s"])

    obs.configure(enabled=True)
    a2, b2 = a.conj("p"), b.conj("q")
    restored.update([(a2, b2)] * 4)
    d2 = restored.wave()
    costs = [e["fields"] for e in _events("wave.cost")]
    assert costs and costs[-1]["path"] == "delta", costs
    obs.configure(enabled=False)
    control = FleetSession([(a2, b2)] * 4, delta=False)
    assert np.array_equal(d2, control.wave())
    # and the original (never-crashed) session agrees too
    sess.update([(a2, b2)] * 4)
    assert np.array_equal(d2, sess.wave())


def test_checkpoint_restore_to_file_and_gates(tmp_path):
    """checkpoint_to/restore(path) round-trips; a tampered digest
    refuses restore (checkpoint-mismatch); an unwaved session has
    nothing to checkpoint; a frontier that no longer validates is
    dropped (session restores full-width, still correct)."""
    base = _base()
    a, b = _pair(base)
    sess = FleetSession([(a, b)] * 2)
    sess.wave()
    path = str(tmp_path / "sess.ckpt.json")
    sess.checkpoint_to(path)
    restored = FleetSession.restore(path)
    assert np.array_equal(restored._last_digest, sess._last_digest)

    from cause_tpu.parallel.session import _pack_arr, _unpack_arr

    ck = json.load(open(path))
    ck["digest"] = _pack_arr(_unpack_arr(ck["digest"]) + 1)  # tamper
    with pytest.raises(s.CausalError) as ei:
        FleetSession.restore(ck)
    assert "checkpoint-mismatch" in ei.value.info["causes"]

    with pytest.raises(s.CausalError) as ei:
        FleetSession([(a, b)] * 2).checkpoint()  # no wave yet
    assert "no-wave" in ei.value.info["causes"]

    ck2 = json.load(open(path))
    if ck2.get("delta") is not None:
        ck2["delta"]["w_cap"] = 1  # window can no longer fit: drop
        r2 = FleetSession.restore(ck2)
        assert r2._delta is None
        assert np.array_equal(r2._last_digest, sess._last_digest)

    with pytest.raises(s.CausalError):
        FleetSession.restore({"~causal_session": 999})


def test_eviction_raced_with_checkpoint_restores_bit_identically(
        tmp_path):
    """PR 12: a document evicted to host MID-SESSION (lanecache LRU
    residency under memory pressure) restores bit-identically — the
    spill is a checkpoint-grade pack, the touch is a digest-gated
    restore, and a service-level checkpoint taken while the tenant
    sits spilled still round-trips the same digests."""
    from cause_tpu.serve import ResidencyManager

    base = _base(30)
    rm = ResidencyManager(capacity=1, spill_dir=str(tmp_path / "sp"))
    a, b = _pair(base)
    hot = FleetSession([(a, b)] * 2)
    hot.wave()
    a, b = a.conj("h1"), b.conj("h2")
    hot.update([(a, b)] * 2)
    d_mid = hot.wave()  # mid-session state: waved after real edits
    rm.insert("victim", hot)
    a2, b2 = _pair(base, ("C",), ("D",))
    other = FleetSession([(a2, b2)] * 2)
    other.wave()
    rm.insert("other", other)  # races "victim" out to disk
    assert rm.spilled() == ["victim"]
    # a drain-grade checkpoint_all taken WHILE the victim is spilled
    out = rm.checkpoint_all(str(tmp_path / "ckpt"))
    assert set(out) == {"victim", "other"}
    from_pack = FleetSession.restore(
        str(tmp_path / "ckpt" / "victim.ckpt.json"))
    assert np.array_equal(from_pack._last_digest, d_mid)
    # the touch restores through the digest gate, bit-identically,
    # and resumes STEADY-STATE delta waves (the frontier rode the pack)
    back = rm.get("victim")
    assert np.array_equal(back._last_digest, d_mid)
    a3, b3 = a.conj("x"), b.conj("y")
    back.update([(a3, b3)] * 2)
    d_next = back.wave()
    control = FleetSession([(a3, b3)] * 2, delta=False)
    assert np.array_equal(d_next, control.wave())


def test_restore_refuses_pack_torn_during_spill(tmp_path):
    """PR 12: a spill pack torn mid-write (truncated JSON) refuses
    restore through the declared checkpoint-mismatch gate — never a
    bare json error, never a silently wrong session."""
    from cause_tpu.serve import ResidencyManager

    base = _base()
    rm = ResidencyManager(capacity=1, spill_dir=str(tmp_path / "sp"))
    a, b = _pair(base)
    s1 = FleetSession([(a, b)] * 2)
    s1.wave()
    rm.insert("t1", s1)
    a2, b2 = _pair(base, ("C",), ("D",))
    s2 = FleetSession([(a2, b2)] * 2)
    s2.wave()
    rm.insert("t2", s2)  # evicts t1 to disk
    (path,) = [rm._spilled[u] for u in rm.spilled()]
    blob = open(path).read()
    with open(path, "w") as f:
        f.write(blob[:len(blob) // 2])  # the torn spill
    with pytest.raises(s.CausalError) as ei:
        rm.get("t1")
    assert "checkpoint-mismatch" in ei.value.info["causes"]
    # the refusal cost a loud error, never a wrong answer: the other
    # tenant is untouched and still serves
    assert np.array_equal(rm.get("t2")._last_digest, s2._last_digest)


def test_restore_emits_recovery_evidence():
    obs.configure(enabled=True)
    base = _base()
    a, b = _pair(base)
    sess = FleetSession([(a, b)] * 2)
    sess.wave()
    ck = sess.checkpoint()
    FleetSession.restore(ck)
    (ev,) = _events("recovery.restore")
    assert ev["fields"]["site"] == "session"
    assert ev["fields"]["pairs"] == 2
    snap = obs.counters_snapshot()["counters"]
    assert snap["recovery.restores"] == 1
    assert snap["session.checkpoint"] == 1


# ----------------------------------------------------- fleet read side


def test_fleet_report_carries_ingest_and_recovery_sections():
    obs.configure(enabled=True)
    base = _base()
    a, b = _pair(base)
    chaos.configure(plan={"seed": 3, "faults": [
        {"family": "payload", "site": "sync.delta", "mode": "drop",
         "times": 1, "prob": 1.0}]})
    b = b.conj("d")
    a, b = sync.sync_pair(a, b)
    obs.flush()
    from cause_tpu.obs.fleet import fleet_report, render

    rep = fleet_report(obs.events())
    assert rep["sync"]["rejects"] == 1
    assert rep["sync"]["quarantined"] == 0
    assert rep["recovery"]["chaos_injected"] == 1
    text = render(rep)
    assert "payload reject(s)" in text
    assert "chaos fault(s) injected" in text


def test_live_defaults_include_quarantine_and_storm_rules():
    from cause_tpu.obs import live

    specs = set(live.DEFAULT_RULE_SPECS)
    assert "quarantined>0" in specs
    assert "recovery_per_wave>1" in specs
    r = live.parse_rule("quarantined>0")
    assert r.path == "sync.quarantined"
    r2 = live.parse_rule("recovery_per_wave>1")
    assert r2.path == "recovery.per_wave"
    # a snapshot with a quarantined replica fires the default rule
    fold = live.LiveFold()
    mon = live.LiveMonitor(rules=["quarantined>0"], source="t")
    mon.feed([{"ev": "counters", "pid": 1, "ts_us": 1,
               "counters": {"sync.quarantine": 1}}])
    fired = mon.evaluate(now_us=2)
    assert len(fired) == 1 and fired[0]["value"] == 1
    assert fold.snapshot(now_us=2)["recovery"]["steps"] == 0


# ----------------------------------------------------- subprocess smoke


@pytest.mark.slow
def test_chaos_soak_subprocess_smoke(tmp_path):
    """The acceptance instrument end to end: a seeded multi-family
    plan over an 8-replica fleet, run as a real subprocess — exit 0,
    exactly the planned number of chaos.inject events, every family
    detected, bit-identical convergence, and a --kind chaos row that
    passes ledger --check on a scratch ledger."""
    plan = {
        "seed": 11, "replicas": 8, "rounds": 4, "doc": 30,
        "faults": [
            {"family": "payload", "site": "sync.delta",
             "mode": "corrupt", "at": [3]},
            {"family": "payload", "site": "sync.delta",
             "mode": "truncate", "at": [20]},
            {"family": "dispatch", "site": "session", "mode": "raise",
             "at": [2]},
            {"family": "dispatch", "site": "session",
             "mode": "exhaust", "at": [3]},
            {"family": "crash", "site": "session", "at": [2]},
            {"family": "stall", "site": "session", "ms": 120,
             "at": [4]},
        ],
    }
    plan_path = tmp_path / "plan.json"
    plan_path.write_text(json.dumps(plan))
    obs_path = tmp_path / "chaos.jsonl"
    ledger_path = tmp_path / "ledger.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CAUSE_TPU_LEDGER=str(ledger_path))
    env.pop("CAUSE_TPU_OBS", None)
    env.pop("CAUSE_TPU_CHAOS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "soak.py"),
         "--chaos", str(plan_path), "--obs-out", str(obs_path)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    from cause_tpu.obs import load_jsonl
    from cause_tpu.obs.fleet import fleet_report

    evs = load_jsonl(str(obs_path))
    injects = [e for e in evs if e.get("ev") == "event"
               and e.get("name") == "chaos.inject"]
    assert len(injects) == 6, injects  # exactly the planned schedule
    assert {(e["fields"]["family"]) for e in injects} \
        == {"payload", "dispatch", "crash", "stall"}
    rep = fleet_report(evs)
    assert rep["divergence_incidents"] == []
    assert rep["sync"]["rejects"] >= 2
    assert rep["recovery"]["restores"] >= 1
    (done,) = [e for e in evs if e.get("ev") == "event"
               and e.get("name") == "chaos.done"]
    assert done["fields"]["converged_bit_identical"] is True
    rows = [json.loads(ln) for ln in
            open(ledger_path).read().splitlines() if ln.strip()]
    assert any(r.get("kind") == "chaos" for r in rows)
    check = subprocess.run(
        [sys.executable, "-m", "cause_tpu.obs", "ledger", "--check",
         "--ledger", str(ledger_path)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=REPO)
    assert check.returncode == 0, check.stdout + check.stderr
