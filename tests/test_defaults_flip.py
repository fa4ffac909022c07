"""The measured-defaults flip loop (VERDICT r4 weak #4 / next #3):
harvest certifies a config on chip -> decide_defaults writes
cause_tpu/_tpu_defaults.json -> switches.TPU_DEFAULTS ships it as the
default in every later process. These tests pin the whole loop offline
(the chip only supplies the numbers)."""

import json
import os
import sys

import pytest

import cause_tpu.switches as sw

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts")


def _harvest():
    if _SCRIPTS not in sys.path:
        sys.path.insert(0, _SCRIPTS)
    import harvest

    return harvest


# ---------------------- switches-side loading ----------------------


def test_load_measured_absent_and_corrupt(tmp_path):
    assert sw._load_measured(str(tmp_path / "nope.json")) == {}
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert sw._load_measured(str(p)) == {}
    p.write_text("[1, 2]")  # wrong shape
    assert sw._load_measured(str(p)) == {}


def test_load_measured_filters_to_trace_switches(tmp_path):
    p = tmp_path / "d.json"
    p.write_text(json.dumps({
        "switches": {"CAUSE_TPU_GATHER": "rowgather",
                     "NOT_A_SWITCH": "x",
                     "CAUSE_TPU_SORT": ""},
        "kernel": "v5",
    }))
    data = sw._load_measured(str(p))
    flips = {k: str(v) for k, v in data.get("switches", {}).items()
             if k in sw.TRACE_SWITCHES and v}
    assert flips == {"CAUSE_TPU_GATHER": "rowgather"}


def test_resolve_uses_defaults_only_on_tpu(monkeypatch):
    """On the CPU test backend, a populated TPU_DEFAULTS must not leak
    into resolve() (the streaming strategies are TPU answers to TPU
    costs); the explicit env value always wins; "xla" forces ""."""
    monkeypatch.setattr(
        sw, "TPU_DEFAULTS", {"CAUSE_TPU_GATHER": "rowgather"})
    monkeypatch.delenv("CAUSE_TPU_GATHER", raising=False)
    assert sw.resolve("CAUSE_TPU_GATHER") == ""  # cpu backend
    monkeypatch.setenv("CAUSE_TPU_GATHER", "rowgather")
    assert sw.resolve("CAUSE_TPU_GATHER") == "rowgather"
    monkeypatch.setenv("CAUSE_TPU_GATHER", "xla")
    assert sw.resolve("CAUSE_TPU_GATHER") == ""


def test_measured_kernel_default():
    # the default argument comes back when nothing is certified
    if not sw._MEASURED.get("kernel"):
        assert sw.measured_kernel("v5") == "v5"
    else:  # a certified kernel must be a real kernel name
        assert sw.measured_kernel("v5") in ("v5", "v5w", "v5f", "v4")


# ---------------------- harvest decide side ------------------------


def _results(run="w1", **p50s):
    return {name: {"p50_amortized_ms": v, "run": run}
            for name, v in p50s.items()}


def test_decide_flips_certified_winner(tmp_path, capsys):
    h = _harvest()
    path = str(tmp_path / "_tpu_defaults.json")
    h.decide_defaults(
        done={"verify_beststream", "bench_beststream"},
        results=_results(bench_xla_base=3750.0, bench_beststream=3000.0),
        plat="tpu", path=path)
    rec = json.loads(open(path).read())
    assert rec["kernel"] == "v5"
    assert rec["switches"] == {
        k: v for k, v in h.BESTSTREAM.items() if v != "xla"}
    assert rec["evidence"]["p50_amortized_ms"] == 3000.0
    # the record round-trips through the switches loader
    data = sw._load_measured(path)
    assert data["switches"] == rec["switches"]


def test_decide_flips_the_timed_cfg_not_the_constant(tmp_path):
    """Reduced-certification support: when the bench record carries
    the cfg it actually ran (the digest gate's MATCH-REDUCED subset),
    decide_defaults must flip exactly that — not the static BESTSTREAM
    constant the record may have been reduced from."""
    h = _harvest()
    path = str(tmp_path / "_tpu_defaults.json")
    results = _results(bench_xla_base=3750.0, bench_beststream=3000.0)
    reduced = {"CAUSE_TPU_GATHER": "rowgather",
               "CAUSE_TPU_SCATTER": "hint"}
    results["bench_beststream"]["cfg"] = dict(reduced)
    h.decide_defaults(
        done={"verify_beststream", "bench_beststream"},
        results=results, plat="tpu", path=path)
    rec = json.loads(open(path).read())
    assert rec["switches"] == reduced
    # and the switches loader ships exactly the reduced set
    data = sw._load_measured(path)
    assert data["switches"] == reduced


def test_certified_env_prefers_state_cfg(tmp_path, monkeypatch):
    """The watcher's phase-2 wave env must ride the cfg the digest
    gate certified (full or reduced), from the state file."""
    h = _harvest()
    p = tmp_path / "state.json"
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": ["verify_beststream"],
        "results": {"verify_beststream": {
            "verdict": "MATCH-REDUCED",
            "cfg": {"CAUSE_TPU_GATHER": "rowgather"}}},
    }))
    monkeypatch.setattr(h, "STATE_PATH", str(p))
    assert h.certified_env() == "CAUSE_TPU_GATHER=rowgather"
    # no verify record -> the static BESTSTREAM flips
    p.write_text(json.dumps({
        "version": h.STATE_VERSION, "done": [], "results": {}}))
    want = " ".join(f"{k}={v}" for k, v in sorted(
        (k, v) for k, v in h.BESTSTREAM.items() if v != "xla"))
    assert h.certified_env() == want


def test_cfgless_certification_forces_reverify(tmp_path, monkeypatch):
    """A verify_beststream 'done' whose record carries no cfg (written
    by code predating the cfg field) must not survive load: the static
    BESTSTREAM may have gained strategies since, and acting on the old
    verdict would time/ship a combination it never checked."""
    h = _harvest()
    p = tmp_path / "state.json"
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": ["verify_beststream", "fleet64"],
        "results": {},
    }))
    monkeypatch.setattr(h, "STATE_PATH", str(p))
    done, _ = h.load_state()
    assert "verify_beststream" not in done and "fleet64" in done
    # with a cfg-bearing record it survives
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": ["verify_beststream"],
        "results": {"verify_beststream": {
            "verdict": "MATCH", "cfg": {"CAUSE_TPU_GATHER": "rowgather"}}},
    }))
    done, _ = h.load_state()
    assert "verify_beststream" in done


def test_decide_requires_timed_cfg_to_match_certified_cfg(tmp_path):
    """A bench_beststream record whose cfg differs from what the
    digest gate certified (e.g. timed before a reduction) must not
    flip defaults."""
    h = _harvest()
    path = str(tmp_path / "d.json")
    results = _results(bench_xla_base=3750.0, bench_beststream=3000.0)
    results["bench_beststream"]["cfg"] = dict(h.flips_of(h.BESTSTREAM))
    results["verify_beststream"] = {
        "verdict": "MATCH-REDUCED",
        "cfg": {"CAUSE_TPU_GATHER": "rowgather"}}
    h.decide_defaults(done={"verify_beststream"}, results=results,
                      plat="tpu", path=path)
    assert not os.path.exists(path)
    # agreement -> flips the certified/timed cfg
    results["bench_beststream"]["cfg"] = {"CAUSE_TPU_GATHER": "rowgather"}
    h.decide_defaults(done={"verify_beststream"}, results=results,
                      plat="tpu", path=path)
    rec = json.loads(open(path).read())
    assert rec["switches"] == {"CAUSE_TPU_GATHER": "rowgather"}


def test_persisted_suspects_reseed_from_reduced_record():
    """A MATCH-REDUCED certification puts verify_beststream in done,
    so later windows run no suspect re-derivation — the dropped
    strategies must ride the record and re-seed the gate, or the next
    window times the digest-contradicted config (review finding)."""
    h = _harvest()
    results = {
        "verify_beststream": {
            "verdict": "MATCH-REDUCED",
            "cfg": {"CAUSE_TPU_GATHER": "rowgather"},
            "suspects": ["CAUSE_TPU_SORT=matrix"],
        },
        "bench_v5": {"p50_amortized_ms": 1.0},  # no suspects field
    }
    assert h.persisted_suspects(results) == {"CAUSE_TPU_SORT=matrix"}
    assert h.persisted_suspects({}) == set()


def test_certified_env_cfgless_claim_returns_sentinel(tmp_path,
                                                      monkeypatch):
    """ADVICE r5 medium: a pre-migration state file claims
    verify_beststream done but its record carries no cfg. The watcher
    greps the RAW file, sees a certification, and asks certified_env
    for the phase-2 env — which must return the shipped-default
    sentinel (empty string), mirroring load_state()'s cfgless-record
    re-verify guard, so the watcher never ships the static (matrix
    -sort-bearing) BESTSTREAM flips uncertified."""
    h = _harvest()
    p = tmp_path / "state.json"
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": ["verify_beststream"],
        "results": {},
    }))
    monkeypatch.setattr(h, "STATE_PATH", str(p))
    assert h.certified_env() == ""
    # a cfgless RESULTS record (done or not) is the same claim
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": [],
        "results": {"verify_beststream": {"verdict": "MATCH"}},
    }))
    assert h.certified_env() == ""
    # a version-mismatched file whose raw text still claims the
    # certification: load_state discards everything, and the watcher's
    # grep still matches — sentinel again, never the static flips
    p.write_text(json.dumps({
        "version": h.STATE_VERSION - 1,
        "done": ["verify_beststream"],
        "results": {"verify_beststream": {
            "cfg": {"CAUSE_TPU_GATHER": "rowgather"}}},
    }))
    assert h.certified_env() == ""


def test_decide_cfgless_bench_record_falls_back_to_vcfg(tmp_path):
    """ADVICE r5 low: when the bench record lacks cfg, the flip must
    ship the CERTIFIED vcfg — not flips_of(BESTSTREAM), which can
    differ from a reduced certification (exactly the drift the
    coherence check exists to prevent)."""
    h = _harvest()
    path = str(tmp_path / "d.json")
    results = _results(bench_xla_base=3750.0, bench_beststream=3000.0)
    reduced = {"CAUSE_TPU_GATHER": "rowgather"}
    results["verify_beststream"] = {
        "verdict": "MATCH-REDUCED", "cfg": dict(reduced)}
    # note: NO cfg on the bench record
    assert "cfg" not in results["bench_beststream"]
    h.decide_defaults(done={"verify_beststream"}, results=results,
                      plat="tpu", path=path)
    rec = json.loads(open(path).read())
    assert rec["switches"] == reduced  # vcfg, not the static constant


def test_decide_requires_digest_certification(tmp_path):
    h = _harvest()
    path = str(tmp_path / "d.json")
    h.decide_defaults(
        done={"bench_beststream"},  # no verify_beststream
        results=_results(bench_xla_base=3750.0, bench_beststream=1000.0),
        plat="tpu", path=path)
    assert not os.path.exists(path)


def test_decide_requires_margin(tmp_path):
    h = _harvest()
    path = str(tmp_path / "d.json")
    h.decide_defaults(
        done={"verify_beststream"},
        results=_results(bench_xla_base=1000.0, bench_beststream=995.0),
        plat="tpu", path=path)  # 0.5% < the 2% margin
    assert not os.path.exists(path)


def test_decide_requires_same_window(tmp_path):
    """A candidate from one window vs a baseline persisted from
    another must NOT certify: PERF.md records ~14% cross-day drift at
    identical code+shape, so a cross-window 2% margin is load noise
    (round-5 review finding)."""
    h = _harvest()
    path = str(tmp_path / "d.json")
    results = _results(run="w1", bench_xla_base=3750.0)
    results.update(_results(run="w2", bench_beststream=3000.0))
    h.decide_defaults(done={"verify_beststream"}, results=results,
                      plat="tpu", path=path)
    assert not os.path.exists(path)


def test_decide_never_ships_mosaic_combination(tmp_path):
    """A MOSAICSTREAM certification is under kernel v5w/v5f — the
    global switch defaults apply to v5 paths it was never digest
    -checked against, so it must never be written (round-5 review
    finding); it is reported informationally only."""
    h = _harvest()
    path = str(tmp_path / "d.json")
    h.decide_defaults(
        done={"verify_mosaicstream", "verify_v5f"},
        results=_results(bench_xla_base=3750.0,
                         bench_mosaicstream=1000.0,
                         bench_v5f=500.0),
        plat="tpu", path=path)
    assert not os.path.exists(path)


def test_decide_revokes_on_suspects(tmp_path):
    """Shipped defaults contradicted by a later digest MISMATCH must
    be revoked — a certification must not outlive its evidence."""
    h = _harvest()
    path = str(tmp_path / "d.json")
    h.decide_defaults(
        done={"verify_beststream"},
        results=_results(bench_xla_base=3750.0, bench_beststream=3000.0),
        plat="tpu", path=path)
    assert os.path.exists(path)
    h.decide_defaults(
        done=set(), results={}, plat="tpu", path=path,
        suspects={"CAUSE_TPU_GATHER=rowgather"})
    assert not os.path.exists(path)


def test_decide_needs_baseline(tmp_path):
    h = _harvest()
    path = str(tmp_path / "d.json")
    h.decide_defaults(
        done={"verify_beststream"},
        results=_results(bench_beststream=100.0),
        plat="tpu", path=path)
    assert not os.path.exists(path)


def test_state_version_discards_stale_entries(tmp_path, monkeypatch):
    """done/results recorded under an older item-definition vocabulary
    must not survive a STATE_VERSION bump (round-5 review finding: a
    stale verify_beststream 'done' under the old pallas-containing
    config must not certify the new XLA-only one)."""
    h = _harvest()
    p = tmp_path / "state.json"
    p.write_text(json.dumps({
        "version": h.STATE_VERSION - 1,
        "done": ["verify_beststream"],
        "results": {"bench_xla_base": {"p50_amortized_ms": 1.0}},
    }))
    monkeypatch.setattr(h, "STATE_PATH", str(p))
    done, results = h.load_state()
    assert done == set() and results == {}


def test_shipped_defaults_recertify_every_window(tmp_path, monkeypatch):
    """Once a defaults file exists, verify_beststream is never loaded
    as done: the shipped config re-certifies in every window."""
    h = _harvest()
    p = tmp_path / "state.json"
    p.write_text(json.dumps({
        "version": h.STATE_VERSION,
        "done": ["verify_beststream", "fleet64"],
        "results": {"verify_beststream": {
            "verdict": "MATCH",
            "cfg": {"CAUSE_TPU_GATHER": "rowgather"}}},
    }))
    monkeypatch.setattr(h, "STATE_PATH", str(p))
    d = tmp_path / "_tpu_defaults.json"
    d.write_text("{}")
    monkeypatch.setattr(h, "defaults_file_path", lambda: str(d))
    done, _ = h.load_state()
    assert "verify_beststream" not in done and "fleet64" in done
    d.unlink()
    done, _ = h.load_state()
    assert "verify_beststream" in done


# ---------------------- mosaic gating ------------------------------


def test_beststream_is_mosaic_free():
    """The certifiable/watcher/bench candidate config must never name
    a Mosaic strategy: the v5e compiler refuses the Pallas euler walk
    and fused F-phase kernels (CHANGES.md, PR 21)."""
    h = _harvest()
    eff = {f"{k}={v}" for k, v in h.BESTSTREAM.items() if v != "xla"}
    assert not (eff & h.MOSAIC_VALUES)
    # and the aspirational config IS gated
    eff_m = {f"{k}={v}" for k, v in h.MOSAICSTREAM.items() if v != "xla"}
    assert eff_m & h.MOSAIC_VALUES


def test_bench_alt_config_is_mosaic_free():
    """bench.py's self-selection alt path must not set a Mosaic
    switch when no certified defaults exist. The alt config is now the
    single shared constant (switches.BESTSTREAM_FLIPS — import, never
    restate), so the constant is what must stay Mosaic-free; the
    source grep keeps guarding against a reintroduced hand-written
    env block."""
    for k, v in sw.BESTSTREAM_FLIPS.items():
        assert f"{k}={v}" not in _harvest().MOSAIC_VALUES, (k, v)
    assert _harvest().BESTSTREAM == _harvest().cfg_of(
        **sw.BESTSTREAM_FLIPS)
    src = open(os.path.join(os.path.dirname(_SCRIPTS), "bench.py")).read()
    import re

    sets = re.findall(
        r'os\.environ\["(CAUSE_TPU_\w+)"\]\s*=\s*"(\w[\w-]*)"', src)
    for k, v in sets:
        assert f"{k}={v}" not in _harvest().MOSAIC_VALUES, (k, v)
