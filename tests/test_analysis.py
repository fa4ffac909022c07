"""causelint (cause_tpu.analysis) — rule families, suppressions,
reporters, CLI gating, and the shipped-tree zero-findings contract.

Fixture modules live in tests/analysis_fixtures/ and are parsed, never
imported: the analyzer is AST-only, which is also why every test here
is cheap (no jax tracing anywhere).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from cause_tpu.analysis import core, report

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIX = os.path.join(HERE, "analysis_fixtures")


def run_api(*paths, root=REPO):
    return core.run([os.path.join(FIX, p) if not os.path.isabs(p)
                     and not os.path.exists(p) else p for p in paths],
                    root=root)


def run_cli(*args, cwd=REPO):
    env = dict(os.environ)
    if os.path.abspath(cwd) != REPO:  # keep cause_tpu importable
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "cause_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd, timeout=120, env=env,
    )


def rules_of(result):
    return sorted({f.rule for f in result.findings})


# ------------------------------------------------------- rule families

def test_tid_bad_fixture():
    res = run_api(os.path.join(FIX, "tid_bad.py"))
    # the traced read is a jit-purity hazard (JPH001) as well
    assert rules_of(res) == ["JPH001", "TID001"]
    tid1 = [f for f in res.findings if f.rule == "TID001"]
    # both the traced unregistered read and the host read of a
    # retired switch
    assert len(tid1) == 2
    assert {"CAUSE_TPU_TYPO", "CAUSE_TPU_SORT"} == {
        name for f in tid1
        for name in ("CAUSE_TPU_TYPO", "CAUSE_TPU_SORT")
        if name in f.message}


def test_tid_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "tid_good.py"))
    assert res.findings == []


def test_jph_bad_fixture():
    res = run_api(os.path.join(FIX, "jph_bad.py"))
    rules = rules_of(res)
    for expected in ("JPH001", "JPH002", "JPH003", "JPH004", "JPH005",
                     "JPH006"):
        assert expected in rules, (expected, rules)
    # float() on a traced parameter is JPH005 too
    jph5 = [f for f in res.findings if f.rule == "JPH005"]
    assert len(jph5) == 2


def test_jph_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "jph_good.py"))
    assert res.findings == []


def test_obs_bad_fixture():
    res = run_api(os.path.join(FIX, "obs", "obs_bad.py"))
    obs1 = [f for f in res.findings if f.rule == "OBS001"]
    # one literal read of an unregistered knob + one unprovable
    # non-literal key
    assert len(obs1) == 2


def test_obs_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "obs", "obs_good.py"))
    assert res.findings == []


def test_obs_unguarded_call_on_traced_path():
    res = run_api(os.path.join(FIX, "obs_caller_bad.py"))
    obs2 = [f for f in res.findings if f.rule == "OBS002"]
    # exactly one: flush() flagged, the guarded span() factory is not
    assert len(obs2) == 1
    assert obs2[0].message.startswith("obs.flush()")


def test_devprof_unguarded_call_on_traced_path():
    """OBS003 (PR-4): devprof APIs do real work when obs is on —
    jit-reachable code must gate them behind obs.enabled(). Exactly
    two findings — the plain unguarded call and the body of a negated
    test (obs-off-only, never useful); every guard spelling (nested
    if, devprof.enabled, aliased import, early return, else of a
    negated test) is sanctioned."""
    res = run_api(os.path.join(FIX, "devprof_caller_bad.py"))
    obs3 = [f for f in res.findings if f.rule == "OBS003"]
    assert len(obs3) == 2, [f.message for f in obs3]
    assert all("sample_device_memory" in f.message for f in obs3)
    assert rules_of(res) == ["OBS003"]


def test_semantic_unguarded_call_on_traced_path():
    """OBS004 (PR-5): the CRDT-semantic event layer assembles real
    payloads (staleness bookkeeping, weave scans) when obs is on —
    jit-reachable code must gate it behind obs.enabled(). Exactly two
    findings — the plain unguarded call and the body of a negated
    test; every OBS003 guard spelling (nested if, semantic.enabled,
    aliased module, early return, else of a negated test) is
    sanctioned."""
    res = run_api(os.path.join(FIX, "semantic_caller_bad.py"))
    obs4 = [f for f in res.findings if f.rule == "OBS004"]
    assert len(obs4) == 2, [f.message for f in obs4]
    assert "observe_wave" in obs4[0].message
    assert "sync_applied" in obs4[1].message
    assert rules_of(res) == ["OBS004"]


def test_costmodel_unguarded_call_on_traced_path():
    """OBS005 (PR-6): the wave cost model takes registry locks and
    builds per-wave dispatch records when obs is on — jit-reachable
    code must gate it behind obs.enabled(). Exactly two findings —
    the plain unguarded call and the body of a negated test; every
    OBS003/OBS004 guard spelling (nested if, costmodel.enabled,
    aliased module, early return, else of a negated test) is
    sanctioned."""
    res = run_api(os.path.join(FIX, "costmodel_caller_bad.py"))
    obs5 = [f for f in res.findings if f.rule == "OBS005"]
    assert len(obs5) == 2, [f.message for f in obs5]
    assert "record_dispatch" in obs5[0].message
    assert "note_full_bag" in obs5[1].message
    assert rules_of(res) == ["OBS005"]


def test_lag_unguarded_call_on_traced_path():
    """OBS006 (PR-9): the convergence-lag tracer reads monotonic
    clocks and mutates the bounded op registry when obs is on —
    jit-reachable code must gate it behind obs.enabled(). Exactly two
    findings — the plain unguarded call and the body of a negated
    test; every OBS003-OBS005 guard spelling (nested if, lag.enabled,
    aliased module, early return, else of a negated test) is
    sanctioned."""
    res = run_api(os.path.join(FIX, "lag_caller_bad.py"))
    obs6 = [f for f in res.findings if f.rule == "OBS006"]
    assert len(obs6) == 2, [f.message for f in obs6]
    assert "op_created" in obs6[0].message
    assert "level_observed" in obs6[1].message
    assert rules_of(res) == ["OBS006"]


def test_live_unguarded_call_on_traced_path():
    """OBS007 (PR-10): the live-telemetry layer drains subscriber
    queues, folds records and evaluates alert rules when obs is on —
    jit-reachable code must gate it behind obs.enabled(). Exactly
    three findings — the plain unguarded call, a distinctive bare
    name, and the body of a negated test; every OBS003-OBS006 guard
    spelling is sanctioned, and generic verbs (feed/poll) on non-live
    objects never flag."""
    res = run_api(os.path.join(FIX, "live_caller_bad.py"))
    obs7 = [f for f in res.findings if f.rule == "OBS007"]
    assert len(obs7) == 3, [f.message for f in obs7]
    assert "attach" in obs7[0].message
    assert "LiveMonitor" in obs7[1].message
    assert "attach" in obs7[2].message
    assert rules_of(res) == ["OBS007"]


def test_xtrace_unguarded_call_on_traced_path():
    """XTR001 (PR-19): the cross-process tracer takes the span-
    registry lock, mints span ids and assembles hop/clock payloads
    when obs is on — jit-reachable code must gate it behind
    obs.enabled(). Exactly two findings — the plain unguarded hop and
    a generic verb reached through the module qualifier; every
    OBS003-007 guard spelling (nested if, xtrace.enabled, aliased
    module, early return) is sanctioned."""
    res = run_api(os.path.join(FIX, "xtrace_caller_bad.py"))
    xtr = [f for f in res.findings if f.rule == "XTR001"]
    assert len(xtr) == 2, [f.message for f in xtr]
    assert "hop" in xtr[0].message
    assert "reset" in xtr[1].message
    assert rules_of(res) == ["XTR001"]


def test_chaos_unguarded_call_on_traced_path():
    """CHS001 (PR-11): chaos-engine hooks advance seeded RNG streams
    under the engine lock and recovery telemetry assembles event
    payloads when enabled — jit-reachable code must gate both behind
    chaos.enabled()/obs.enabled(). Exactly three findings — two plain
    unguarded calls and the body of a negated test; every OBS003-007
    guard spelling is sanctioned, and the ladder's own execution seam
    (recovery.run_dispatch) is sanctioned unguarded by design."""
    res = run_api(os.path.join(FIX, "chaos_caller_bad.py"))
    chs = [f for f in res.findings if f.rule == "CHS001"]
    assert len(chs) == 3, [f.message for f in chs]
    assert "stall_point" in chs[0].message
    assert "recovery.step" in chs[1].message
    assert "recovery.step" in chs[2].message
    assert rules_of(res) == ["CHS001"]


def test_serve_unguarded_call_on_traced_path():
    """SRV001 (PR-12): the sync-service layer takes admission-queue
    locks, appends to the write-ahead journal and packs/restores
    checkpoint-grade state — host lifecycle work that must never sit
    on a traced path unguarded. Exactly three findings — the plain
    unguarded call, a distinctive bare name, and the body of a
    negated test; every OBS003-007/CHS001 guard spelling is
    sanctioned, and generic verbs (offer/drain) on non-serve objects
    never flag."""
    res = run_api(os.path.join(FIX, "serve_caller_bad.py"))
    srv = [f for f in res.findings if f.rule == "SRV001"]
    assert len(srv) == 3, [f.message for f in srv]
    assert "IngestQueue" in srv[0].message
    assert "SyncService" in srv[1].message
    assert "IngestJournal" in srv[2].message
    assert rules_of(res) == ["SRV001"]


def test_batch_scheduler_unguarded_call_on_traced_path():
    """SRV001 extended (PR-18): the cross-tenant batch scheduler
    marshals heterogeneous window packs and walks per-tenant frontiers
    on the host before its one fused dispatch — same
    never-on-a-traced-path contract as the rest of the serve layer.
    Exactly four findings — the plain unguarded constructor, a
    distinctive bare name, ``wave_fleet`` on an opaque receiver, and
    the body of a negated test; every guard spelling is sanctioned."""
    res = run_api(os.path.join(FIX, "batch_caller_bad.py"))
    srv = [f for f in res.findings if f.rule == "SRV001"]
    assert len(srv) == 4, [f.message for f in srv]
    assert "BatchScheduler" in srv[0].message
    assert "BatchScheduler" in srv[1].message
    assert "wave_fleet" in srv[2].message
    assert "BatchScheduler" in srv[3].message
    assert rules_of(res) == ["SRV001"]


def test_net_unguarded_call_on_traced_path():
    """NET001 (PR-13): the network-transport layer blocks on sockets,
    sleeps out reconnect backoff and mutates connection state — host
    transport work that must never sit on a traced path unguarded.
    Exactly three findings — the plain unguarded module-qualified
    call, a distinctive bare name, and the body of a negated test;
    every OBS003-007/CHS001/SRV001 guard spelling is sanctioned, and
    generic verbs (pump/read) on non-net objects never flag."""
    res = run_api(os.path.join(FIX, "net_caller_bad.py"))
    net = [f for f in res.findings if f.rule == "NET001"]
    assert len(net) == 3, [f.message for f in net]
    assert "net.dial" in net[0].message
    assert "NetClient" in net[1].message
    assert "net.Backoff" in net[2].message
    assert rules_of(res) == ["NET001"]


def test_wal_unguarded_call_on_traced_path():
    """DSK001 (PR-15): the durable-storage layer fsyncs descriptors,
    rotates/retires segment files and walks segment directories
    re-checking CRCs — host storage work that must never sit on a
    traced path unguarded. Exactly three findings — the plain
    unguarded module-qualified call, a distinctive bare name, and the
    body of a negated test; every OBS003-007/CHS001/SRV001/NET001
    guard spelling is sanctioned, and generic verbs (append/gc) on
    non-WAL objects never flag. The fixture spells the module without
    its ``serve`` parent qualifier, so the findings are DSK001's
    alone — no SRV001 shadows."""
    res = run_api(os.path.join(FIX, "wal_caller_bad.py"))
    dsk = [f for f in res.findings if f.rule == "DSK001"]
    assert len(dsk) == 3, [f.message for f in dsk]
    assert "wal.open_journal" in dsk[0].message
    assert "scrub_wal" in dsk[1].message
    assert "wal.open_journal" in dsk[2].message
    assert rules_of(res) == ["DSK001"]


def test_ship_unguarded_call_on_traced_path():
    """SHP001 (PR 20): the telemetry-shipping layer spawns pump
    threads, dials sockets and persists WAL segments when obs is on —
    none of that may sit on a traced path unguarded. Exactly three
    findings — the plain unguarded module-qualified factory, a
    distinctive bare name, and the collector constructor under a
    local alias; guarded spellings are sanctioned, and generic verbs
    (pump/flush) on non-ship objects never flag."""
    res = run_api(os.path.join(FIX, "ship_caller_bad.py"))
    shp = [f for f in res.findings if f.rule == "SHP001"]
    assert len(shp) == 3, [f.message for f in shp]
    assert "attach_exporter" in shp[0].message
    assert "attach_exporter" in shp[1].message
    assert "CollectorServer" in shp[2].message
    assert rules_of(res) == ["SHP001"]


def test_lck_guard_bad_fixture():
    """LCK001 (PR 17), seeded historical bug: PR 12's boundary-reject
    stats — written under the lock in the spawning thread's loop,
    bumped lock-free in a thread-reachable helper. Exactly one
    finding: the lock-free bump (the locked write and the dunder
    __init__ stores are sanctioned)."""
    res = run_api(os.path.join(FIX, "lck_guard_bad.py"))
    lck = [f for f in res.findings if f.rule == "LCK001"]
    assert len(lck) == 1, [f.message for f in lck]
    assert "self.stats" in lck[0].message
    assert "BoundaryServer._reject" in lck[0].message
    assert rules_of(res) == ["LCK001"]


def test_lck_watermark_bad_fixture():
    """LCK001 (PR 17), seeded historical bug: PR 13's non-atomic
    filter -> offer -> advance — the watermark seeded under the RLock
    but advanced lock-free after the journal append. Exactly one
    finding: the escaped advance."""
    res = run_api(os.path.join(FIX, "lck_watermark_bad.py"))
    lck = [f for f in res.findings if f.rule == "LCK001"]
    assert len(lck) == 1, [f.message for f in lck]
    assert "self._wm" in lck[0].message
    assert "_wm_lock" in lck[0].message
    assert rules_of(res) == ["LCK001"]


def test_lck_order_bad_fixture():
    """LCK002 (PR 17): both edges of the A->B / B->A order cycle flag
    (each side is one deadlock half), plus the reacquisition of a
    non-reentrant Lock through a resolved helper call."""
    res = run_api(os.path.join(FIX, "lck_order_bad.py"))
    lck = [f for f in res.findings if f.rule == "LCK002"]
    assert len(lck) == 3, [f.message for f in lck]
    assert sum("lock-order cycle" in f.message for f in lck) == 2
    reacq = [f for f in lck if "reacquisition" in f.message]
    assert len(reacq) == 1 and "_settle" in reacq[0].message
    assert rules_of(res) == ["LCK002"]


def test_lck_block_bad_fixture():
    """LCK003 (PR 17): a direct os.fsync inside the lock region and a
    lock-held call into a helper that sleeps — both flagged, with the
    blocking op named."""
    res = run_api(os.path.join(FIX, "lck_block_bad.py"))
    lck = [f for f in res.findings if f.rule == "LCK003"]
    assert len(lck) == 2, [f.message for f in lck]
    assert "fsync" in lck[0].message
    assert "_settle" in lck[1].message and "sleep" in lck[1].message
    assert rules_of(res) == ["LCK003"]


def test_lck_reentrant_bad_fixture():
    """LCK004 (PR 17), seeded historical bug: PR 15's fsync-failure
    reentrancy — the seal step reachable from itself through an error
    path. Both members of the commit cycle flag, naming the cycle."""
    res = run_api(os.path.join(FIX, "lck_reentrant_bad.py"))
    lck = [f for f in res.findings if f.rule == "LCK004"]
    assert len(lck) == 2, [f.message for f in lck]
    assert all("error path" in f.message for f in lck)
    assert all("_seal_locked" in f.message for f in lck)
    assert rules_of(res) == ["LCK004"]


def test_dur_rename_bad_fixture():
    """DUR001/DUR002 (PR 17), seeded historical bug: PR 15 review's
    missing tmp-fsync before the atomic rename, plus the missing
    directory fsync after it (the fixture lives under a ``serve``
    directory so the wal.fsync_dir idiom applies)."""
    res = run_api(os.path.join(FIX, "serve", "dur_rename_bad.py"))
    assert rules_of(res) == ["DUR001", "DUR002"]
    d1 = [f for f in res.findings if f.rule == "DUR001"]
    assert len(d1) == 1 and "torn" in d1[0].message


def test_dur_rename_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "serve", "dur_rename_good.py"))
    assert res.findings == []


def test_dur_ack_bad_fixture():
    """DUR003 (PR 17): the ack returned lexically before the journal
    append that records the batch — exactly the early return flags,
    the post-append ack is sanctioned."""
    res = run_api(os.path.join(FIX, "dur_ack_bad.py"))
    dur = [f for f in res.findings if f.rule == "DUR003"]
    assert len(dur) == 1, [f.message for f in dur]
    assert "journal-before-ack" in dur[0].message
    assert rules_of(res) == ["DUR003"]


def test_dur_crashpoint_bad_fixture():
    """DUR004 (PR 17): a chaos crash seam firing while the lock is
    held — the simulated failure matches no real process death."""
    res = run_api(os.path.join(FIX, "dur_crashpoint_bad.py"))
    dur = [f for f in res.findings if f.rule == "DUR004"]
    assert len(dur) == 1, [f.message for f in dur]
    assert "should_crash" in dur[0].message
    assert rules_of(res) == ["DUR004"]


def test_evd_bad_fixture():
    """EVD001 (PR 17): a serve-boundary raise with no obs evidence on
    the path flags; the twin fixture that counters + events first is
    clean."""
    res = run_api(os.path.join(FIX, "serve", "evd_bad.py"))
    evd = [f for f in res.findings if f.rule == "EVD001"]
    assert len(evd) == 1, [f.message for f in evd]
    assert "raise CausalError" in evd[0].message
    assert rules_of(res) == ["EVD001"]


def test_evd_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "serve", "evd_good.py"))
    assert res.findings == []


def test_lca_bad_fixture():
    res = run_api(os.path.join(FIX, "lca_bad.py"))
    lca = [f for f in res.findings if f.rule == "LCA001"]
    assert len(lca) == 2  # aliased store + direct .arena.col store


def test_lca_good_fixture_is_clean():
    res = run_api(os.path.join(FIX, "lca_good.py"))
    assert res.findings == []


# -------------------------------------------------------- suppressions

def test_suppressions_same_line_and_next_line():
    res = run_api(os.path.join(FIX, "suppressed.py"))
    # two real violations neutralized; the wrong-family token does not
    # suppress the TID001 AND is itself reported as a stale
    # suppression (GEN002) on the full-rule run
    assert len(res.suppressed) == 2
    assert rules_of(res) == ["GEN002", "TID001"]
    tid = [f for f in res.findings if f.rule == "TID001"]
    assert "CAUSE_TPU_SEARCH" in tid[0].snippet


def test_unused_suppression_only_reported_on_full_runs():
    res = core.run([os.path.join(FIX, "suppressed.py")], root=REPO,
                   rule_ids=["TID001"])
    # under a rule subset, "unused" just means "rule not run"
    assert rules_of(res) == ["TID001"]


def test_suppression_inside_string_is_inert(tmp_path):
    # the suppression-syntax EXAMPLE inside the string literal sits on
    # the line right above the real violation: a raw line-regex parser
    # would treat it as live and shield the finding; the tokenizing
    # parser only honors real comments
    mod = tmp_path / "mod.py"
    mod.write_text(
        'DOC = """example:\n'
        '# causelint: disable-next-line=TID001 -- just an example\n'
        '"""; import os; FLIP = os.environ.get("CAUSE_TPU_SORT")\n'
    )
    res = core.run([str(mod)], root=str(tmp_path))
    assert rules_of(res) == ["TID001"]
    assert res.suppressed == []


def test_suppression_parser():
    supps = core.parse_suppressions([
        'x = 1  # causelint: disable=TID001 -- why not',
        '# causelint: disable-next-line=JPH001,JPH002',
        'y = 2',
    ])
    assert supps[1][0].tokens == {"TID001"}
    assert supps[1][0].reason == "why not"
    assert supps[3][0].tokens == {"JPH001", "JPH002"}


# -------------------------------------------------- reachability depth

def test_transitive_reachability(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""\
        import os
        import jax

        def helper(x):
            return os.environ.get("HELPER_VAR", "")

        @jax.jit
        def traced(x):
            return helper(x)

        def host_only(x):
            return os.environ.get("HOST_VAR", "")
    """))
    res = core.run([str(tmp_path / "mod.py")], root=str(tmp_path))
    jph1 = [f for f in res.findings if f.rule == "JPH001"]
    assert len(jph1) == 1
    assert "HELPER_VAR" in jph1[0].message  # flagged through the call
    assert not any("HOST_VAR" in f.message for f in res.findings)


# ------------------------------------------------------ JSON reporter

def test_json_reporter_schema():
    out = run_cli(os.path.join(FIX, "jph_bad.py"), "--format", "json")
    assert out.returncode == 1
    data = json.loads(out.stdout)
    for key in ("version", "tool", "files", "total", "suppressed",
                "baseline_filtered", "counts", "findings"):
        assert key in data, key
    assert data["tool"] == "causelint" and data["version"] == 1
    assert data["total"] == len(data["findings"]) > 0
    assert sum(data["counts"].values()) == data["total"]
    for f in data["findings"]:
        for key in ("rule", "family", "path", "line", "col", "message",
                    "snippet", "fingerprint"):
            assert key in f, key
        assert f["rule"].startswith(f["family"])


# ------------------------------------------------------- CLI contract

def test_cli_exit_codes():
    assert run_cli(os.path.join(FIX, "tid_bad.py")).returncode == 1
    assert run_cli(os.path.join(FIX, "tid_good.py")).returncode == 0
    assert run_cli("/nonexistent/nope.py").returncode == 2
    assert run_cli(".", "--rules", "NOT_A_RULE").returncode == 2


@pytest.mark.parametrize("fixture", [
    "tid_bad.py", "jph_bad.py", os.path.join("obs", "obs_bad.py"),
    "obs_caller_bad.py", "devprof_caller_bad.py",
    "semantic_caller_bad.py", "costmodel_caller_bad.py",
    "lag_caller_bad.py", "live_caller_bad.py",
    "xtrace_caller_bad.py",
    "chaos_caller_bad.py", "serve_caller_bad.py",
    "batch_caller_bad.py", "net_caller_bad.py",
    "wal_caller_bad.py", "ship_caller_bad.py", "lca_bad.py",
    "lck_guard_bad.py", "lck_watermark_bad.py", "lck_order_bad.py",
    "lck_block_bad.py", "lck_reentrant_bad.py", "dur_ack_bad.py",
    "dur_crashpoint_bad.py",
    os.path.join("serve", "dur_rename_bad.py"),
    os.path.join("serve", "evd_bad.py"),
])
def test_cli_gates_each_known_bad_fixture(fixture):
    assert run_cli(os.path.join(FIX, fixture)).returncode == 1


def test_cli_list_rules():
    out = run_cli("--list-rules")
    assert out.returncode == 0
    for rid in ("TID001", "JPH001", "JPH006",
                "OBS001", "OBS002", "OBS003", "OBS004", "OBS005",
                "OBS006", "OBS007", "XTR001", "CHS001", "SRV001",
                "NET001",
                "DSK001", "SHP001", "LCA001", "GEN001", "LCK001",
                "LCK002",
                "LCK003", "LCK004", "DUR001", "DUR002", "DUR003",
                "DUR004", "EVD001"):
        assert rid in out.stdout


def test_cli_works_without_jax_or_numpy(tmp_path):
    """The CI lint job runs from a bare checkout before the test
    matrix installs anything: block jax AND numpy outright and the
    CLI must still analyze the whole tree."""
    script = tmp_path / "blocked.py"
    script.write_text(textwrap.dedent("""\
        import os
        import sys

        sys.path.insert(0, os.getcwd())

        class Blocker:
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in ("jax", "jaxlib", "numpy"):
                    raise ImportError("BLOCKED: " + name)
                return None

        sys.meta_path.insert(0, Blocker())
        sys.argv = ["causelint", "cause_tpu", "scripts", "bench.py"]
        import runpy
        runpy.run_module("cause_tpu.analysis", run_name="__main__")
    """))
    out = subprocess.run([sys.executable, str(script)],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert "0 finding(s)" in out.stdout


# --------------------------------------------- incremental cache mode

def _write_flagged(path):
    """A module with one deterministic, file-local finding (GEN001)."""
    path.write_text("def broken(:\n")


def test_cache_warm_hit_replays_without_reanalyzing(tmp_path):
    mod = tmp_path / "mod.py"
    cache = tmp_path / "cache.json"
    _write_flagged(mod)
    first = core.cached_run([str(mod)], root=str(tmp_path),
                            cache_path=str(cache))
    assert rules_of(first) == ["GEN001"]
    # tamper with the cached verdict but leave the key fields intact:
    # a warm hit must replay the (tampered) payload verbatim, proving
    # the second run never re-analyzed the file
    payload = json.loads(cache.read_text())
    payload["findings"][0][4] = "TAMPERED-SENTINEL"
    cache.write_text(json.dumps(payload))
    second = core.cached_run([str(mod)], root=str(tmp_path),
                             cache_path=str(cache))
    assert second.findings[0].message == "TAMPERED-SENTINEL"


def test_cache_invalidates_on_content_change(tmp_path):
    mod = tmp_path / "mod.py"
    cache = tmp_path / "cache.json"
    _write_flagged(mod)
    assert core.cached_run([str(mod)], root=str(tmp_path),
                           cache_path=str(cache)).exit_code == 1
    mod.write_text("def fixed():\n    return 1\n")
    res = core.cached_run([str(mod)], root=str(tmp_path),
                          cache_path=str(cache))
    assert res.findings == [] and res.exit_code == 0
    # and the cache now records the clean verdict for the new hash
    assert json.loads(cache.read_text())["findings"] == []


def test_cache_invalidates_on_ruleset_version_bump(tmp_path):
    mod = tmp_path / "mod.py"
    cache = tmp_path / "cache.json"
    _write_flagged(mod)
    core.cached_run([str(mod)], root=str(tmp_path),
                    cache_path=str(cache))
    # simulate a cache written by an older analyzer: same hashes,
    # stale rule-set version, poisoned verdict
    payload = json.loads(cache.read_text())
    payload["ruleset"] = payload["ruleset"] - 1
    payload["findings"] = []
    cache.write_text(json.dumps(payload))
    res = core.cached_run([str(mod)], root=str(tmp_path),
                          cache_path=str(cache))
    assert rules_of(res) == ["GEN001"]  # re-analyzed, not replayed
    refreshed = json.loads(cache.read_text())
    from cause_tpu.analysis.rules import RULESET_VERSION
    assert refreshed["ruleset"] == RULESET_VERSION


def test_cache_keyed_on_rule_selection(tmp_path):
    mod = tmp_path / "mod.py"
    cache = tmp_path / "cache.json"
    _write_flagged(mod)
    full = core.cached_run([str(mod)], root=str(tmp_path),
                           cache_path=str(cache))
    assert rules_of(full) == ["GEN001"]
    # poison the full-run verdict: a different rule selection keys
    # differently, so it must re-analyze instead of replaying this
    payload = json.loads(cache.read_text())
    payload["findings"][0][4] = "TAMPERED-SENTINEL"
    cache.write_text(json.dumps(payload))
    sub = core.cached_run([str(mod)], root=str(tmp_path),
                          rule_ids=["TID001"], cache_path=str(cache))
    assert sub.findings and sub.findings[0].message != "TAMPERED-SENTINEL"


def test_corrupt_cache_falls_back_to_analysis(tmp_path):
    mod = tmp_path / "mod.py"
    cache = tmp_path / "cache.json"
    _write_flagged(mod)
    cache.write_text("{not json")
    res = core.cached_run([str(mod)], root=str(tmp_path),
                          cache_path=str(cache))
    assert rules_of(res) == ["GEN001"]


def _git(cwd, *args):
    out = subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        capture_output=True, text=True, cwd=cwd, timeout=60)
    assert out.returncode == 0, out.stderr
    return out


def test_changed_mode_reports_only_diffed_files(tmp_path):
    _write_flagged(tmp_path / "a.py")
    _write_flagged(tmp_path / "b.py")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")

    # nothing changed yet: fast exit 0, even though both files have
    # findings a full run would gate on
    out = run_cli("--changed", "HEAD", ".", cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "no analyzed files changed" in out.stdout

    # touch b.py (still flagged) and add an untracked c.py: both are
    # reported, the unchanged a.py is filtered from the report
    (tmp_path / "b.py").write_text("def broken(:  # still\n")
    _write_flagged(tmp_path / "c.py")
    out = run_cli("--changed", "HEAD", "--format", "json", ".",
                  cwd=str(tmp_path))
    assert out.returncode == 1
    data = json.loads(out.stdout)
    flagged = sorted(os.path.basename(f["path"])
                     for f in data["findings"])
    assert flagged == ["b.py", "c.py"]


def test_changed_mode_with_bad_ref_runs_full(tmp_path):
    _write_flagged(tmp_path / "a.py")
    _git(tmp_path, "init", "-q")
    out = run_cli("--changed", "no-such-ref", ".", cwd=str(tmp_path))
    assert out.returncode == 1
    assert "running the full analysis" in out.stderr


# ----------------------------------------------------------- baseline

def test_baseline_freezes_existing_findings_only(tmp_path):
    mod = tmp_path / "mod.py"
    base = tmp_path / "base.json"
    with open(os.path.join(FIX, "tid_bad.py")) as f:
        mod.write_text(f.read())
    wrote = run_cli(str(mod), "--write-baseline", str(base))
    assert wrote.returncode == 0
    frozen = json.loads(base.read_text())
    assert frozen["fingerprints"]
    # frozen findings no longer gate
    assert run_cli(str(mod), "--baseline", str(base)).returncode == 0
    # a NEW violation still does (and line shifts don't unfreeze)
    mod.write_text("X_NEW = 0\n" + mod.read_text()
                   + '\nNEW = os.environ.get("CAUSE_TPU_SCATTER")\n')
    out = run_cli(str(mod), "--baseline", str(base))
    assert out.returncode == 1
    assert "CAUSE_TPU_SCATTER" in out.stdout
    assert out.stdout.count(": TID") == 1  # only the new one


def test_missing_baseline_is_empty(tmp_path):
    fps = report.load_baseline(str(tmp_path / "absent.json"))
    assert fps == set()


def test_rules_gen_only_runs_no_family_rules():
    """--rules GEN001 selects the driver's parse check alone — it must
    NOT silently expand to every rule (empty selection != full run)."""
    out = run_cli(os.path.join(FIX, "tid_bad.py"), "--rules", "GEN001")
    assert out.returncode == 0, out.stdout
    assert "TID" not in out.stdout


def test_duplicate_lines_get_distinct_fingerprints(tmp_path):
    """Freezing one occurrence of a flagged line must not baseline a
    LATER identical copy of it: duplicates carry an occurrence index."""
    mod = tmp_path / "mod.py"
    base = tmp_path / "base.json"
    line = 'F = os.environ.get("CAUSE_TPU_SORT")\n'
    mod.write_text(line)
    assert run_cli(str(mod), "--write-baseline",
                   str(base)).returncode == 0
    assert run_cli(str(mod), "--baseline", str(base)).returncode == 0
    mod.write_text(line + line)  # a new identical violation
    out = run_cli(str(mod), "--baseline", str(base))
    assert out.returncode == 1
    assert out.stdout.count("TID001") == 1  # only the new copy gates


# -------------------------------------------- the shipped-tree ratchet

def test_shipped_tree_has_zero_findings():
    """The acceptance gate: the tree causelint ships with is clean
    (every intentional exception carries an explicit suppression with
    a reason)."""
    res = core.run([os.path.join(REPO, "cause_tpu"),
                    os.path.join(REPO, "scripts"),
                    os.path.join(REPO, "bench.py")], root=REPO)
    assert res.findings == [], [
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in res.findings]
    # the recorded exceptions all carry a reason string (the PR-17
    # LCK/DUR/EVD triage added six: wal close-fsync + gc seam, the
    # native build lock, residency's caller-fsynced dir swaps, and
    # the pre-stream restore raise); the stage-prefix profiler's A/B
    # env flips took their three with them when it was removed, and
    # the strategy switches' A/B flips and obs snapshot theirs
    assert len(res.suppressed) >= 6


def test_syntax_error_becomes_gen_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    res = core.run([str(bad)], root=str(tmp_path))
    assert rules_of(res) == ["GEN001"]
    assert res.exit_code == 1
