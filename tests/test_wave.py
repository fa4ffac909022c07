"""Batched API-level merge waves: device wave == per-pair merge, with
cached lanes doing the marshal and digests reporting convergence."""

import numpy as np
import pytest

import cause_tpu as c
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.parallel import make_mesh, merge_wave
from cause_tpu.weaver import lanecache


def warm(cl):
    return CausalList(c_list.weave(cl.ct))


def make_pairs(n_pairs, n_base=60, n_div=8, weaver="jax"):
    """Divergent replica pairs of one document, caches warmed."""
    base = warm(c.clist(weaver=weaver).extend(
        [f"w{i}" for i in range(n_base)]
    ))
    pairs = []
    for p in range(n_pairs):
        a = CausalList(base.ct.evolve(site_id=new_site_id())).extend(
            [f"a{p}.{i}" for i in range(n_div)]
        )
        b = CausalList(base.ct.evolve(site_id=new_site_id())).extend(
            [f"b{p}.{i}" for i in range(n_div)]
        )
        b = b.append(list(b)[-1][0], c.hide)
        pairs.append((a, b))
    return pairs


def test_wave_matches_pairwise_merge():
    pairs = make_pairs(6)
    res = merge_wave(pairs)
    assert res.kernel == "v5" and not res.fallback
    for i, (a, b) in enumerate(pairs):
        got = res.merged(i)
        ref = a.merge(b)
        assert c.causal_to_edn(got) == c.causal_to_edn(ref), i
        assert got.get_nodes() == ref.get_nodes()
        # the merged handle carries a fresh lane cache for the next wave
        assert got.ct.lanes is not None
        assert got.ct.lanes.n == len(got.ct.nodes)


def test_wave_digests_detect_divergence_and_convergence():
    pairs = make_pairs(4)
    res = merge_wave(pairs)
    # different pairs diverge -> different digests (w.h.p.)
    assert len(set(res.digest.tolist())) == len(pairs)
    # merging the same pair twice converges -> equal digests
    res2 = merge_wave([pairs[0], pairs[0]])
    assert res2.digest[0] == res2.digest[1]


def test_wave_second_round_reuses_merged_cache():
    pairs = make_pairs(3)
    res = merge_wave(pairs)
    merged = [res.merged(i) for i in range(len(pairs))]
    # keep editing and wave again: merged handles' caches extend
    nxt = []
    for i, m in enumerate(merged):
        a = CausalList(m.ct.evolve(site_id=new_site_id())).conj(f"x{i}")
        b = CausalList(m.ct.evolve(site_id=new_site_id())).conj(f"y{i}")
        assert a.ct.lanes is not None and b.ct.lanes is not None
        nxt.append((a, b))
    res2 = merge_wave(nxt)
    assert not res2.fallback
    for i, (a, b) in enumerate(nxt):
        assert c.causal_to_edn(res2.merged(i)) == c.causal_to_edn(a.merge(b))


def test_wave_sharded_over_mesh():
    mesh = make_mesh(8)
    pairs = make_pairs(8, n_base=40, n_div=4)
    res = merge_wave(pairs, mesh=mesh)
    assert not res.fallback
    for i, (a, b) in enumerate(pairs):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(a.merge(b))


def test_wave_guards_and_fallbacks():
    pairs = make_pairs(2)
    # uuid mismatch raises like any merge
    with pytest.raises(c.CausalError):
        merge_wave([(pairs[0][0], c.clist("z", weaver="jax"))])
    # a pure-weaver pair still works (lane cache builds on demand)
    base = c.clist(weaver="pure").extend(["p"] * 10)
    a = CausalList(base.ct.evolve(site_id=new_site_id())).conj("1")
    b = CausalList(base.ct.evolve(site_id=new_site_id())).conj("2")
    res = merge_wave([(a, b)])
    assert c.causal_to_edn(res.merged(0)) == c.causal_to_edn(a.merge(b))


def test_union_views_equals_scratch_union():
    from cause_tpu.collections import shared as s
    from cause_tpu.weaver.arrays import NodeArrays

    pairs = make_pairs(1, n_base=30, n_div=5)
    a, b = pairs[0]
    va, vb = lanecache.view_for(a.ct), lanecache.view_for(b.ct)
    u = lanecache.union_views(va, vb)
    assert u is not None
    union_ct = s.union_nodes(a.ct, b.ct)
    na = NodeArrays.from_nodes_map(union_ct.nodes)
    assert u.node_arrays().nodes == na.nodes
    assert np.array_equal(u.node_arrays().cause_idx[: u.n],
                          na.cause_idx[: na.n])


def test_wave_mesh_survives_fallback_shrink():
    """A pair that falls back must not break mesh divisibility — the
    live batch pads internally (regression: shard_map requires the
    replica axis to divide the mesh)."""
    from cause_tpu.parallel import make_mesh

    mesh = make_mesh(8)
    pairs = make_pairs(8, n_base=30, n_div=3)
    # poison one pair with an id beyond the PackSpec ts bound -> its
    # lane cache can't build and it falls back to the host merge
    a, b = pairs[3]
    big = ((1 << 31) - 1, a.get_site_id(), 0)
    a_bad = a.insert((big, c.root_id, "huge-ts"))
    b_bad = b.insert((big, c.root_id, "huge-ts"))
    pairs[3] = (a_bad, b_bad)
    res = merge_wave(pairs, mesh=mesh)
    assert res.fallback == [3]
    assert not res.digest_valid[3] and res.digest_valid[0]
    for i, (x, y) in enumerate(pairs):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(x.merge(y))


def test_wave_merged_validates_conflicting_bodies(monkeypatch):
    """merged() must raise on conflicting duplicate ids exactly like
    a.merge(b) — never return a weave/nodes-inconsistent tree. The
    wave-time sampled spot-check is disabled here so the test pins the
    merged()-level validation specifically (with default sampling the
    wave itself usually raises first — see the spotcheck tests)."""
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 0)
    pairs = make_pairs(1, n_base=20, n_div=2)
    a, b = pairs[0]
    evil_id = (500, a.get_site_id(), 0)
    a2 = a.insert((evil_id, c.root_id, "mine"))
    b2 = b.insert((evil_id, c.root_id, "theirs"))
    res = merge_wave([(a2, b2)])
    with pytest.raises(c.CausalError) as ei:
        res.merged(0)
    assert "append-only" in ei.value.info["causes"]


def test_wave_works_for_sets_and_counters():
    """Sets and counters are list-shaped trees: merge_wave converges
    them like any list fleet."""
    from cause_tpu.collections.ccounter import CausalCounter
    from cause_tpu.collections.cset import CausalSet

    sbase = c.cset("seed", weaver="jax")
    spairs = []
    for p in range(3):
        a = CausalSet(sbase.ct.evolve(site_id=new_site_id())).add(f"a{p}")
        b = CausalSet(sbase.ct.evolve(site_id=new_site_id())).discard(
            "seed"
        )
        spairs.append((a, b))
    res = merge_wave(spairs)
    assert not res.fallback, "set wave demoted to the host path"
    for i, (a, b) in enumerate(spairs):
        assert res.merged(i).causal_to_edn() == a.merge(b).causal_to_edn()
        assert res.merged(i).causal_to_edn() == {f"a{i}"}

    cbase_ = c.ccounter(10, weaver="jax")
    cpairs = []
    for p in range(3):
        a = CausalCounter(cbase_.ct.evolve(site_id=new_site_id())).increment(p)
        b = CausalCounter(cbase_.ct.evolve(site_id=new_site_id())).decrement(1)
        cpairs.append((a, b))
    res = merge_wave(cpairs)
    assert not res.fallback, "counter wave demoted to the host path"
    for i, (a, b) in enumerate(cpairs):
        assert res.merged(i).value() == a.merge(b).value() == 9 + i


def test_wave_routes_maps_to_the_correct_path():
    """CausalMap pairs must NOT ride the list-lane wave (their weave is
    a per-key dict; list lanes would mint a list-semantics weave) —
    they fall back to the correct per-pair merge, and FleetSession
    rejects them outright (regression: merged() returned a CausalMap
    whose weave was a list)."""
    from cause_tpu import K
    from cause_tpu.collections.cmap import CausalMap
    from cause_tpu.parallel.session import FleetSession

    base = c.cmap().append(K("t"), "x")
    a = CausalMap(base.ct.evolve(site_id=new_site_id())).append(K("t"), "a")
    b = CausalMap(base.ct.evolve(site_id=new_site_id())).append(K("u"), "b")
    res = merge_wave([(a, b)])
    assert res.fallback == [0]
    m = res.merged(0)
    assert isinstance(m.ct.weave, dict)
    assert c.causal_to_edn(m) == c.causal_to_edn(a.merge(b))
    with pytest.raises(c.CausalError):
        FleetSession([(a, b)])


def test_wave_overflow_rows_retry_on_device():
    """A spiky row outside the sampled token budget retries with a
    doubled budget instead of silently demoting to the host merge
    (soak-found: session digests diverged from wave digests purely
    because of budget-sampling fallbacks)."""
    pairs = make_pairs(5, n_base=40, n_div=6)
    a, b = pairs[2]
    for j in range(12):  # interior tombstones explode pair 2's segments
        a = a.append(list(a)[2 + j][0], c.hide)
    pairs[2] = (a, b)
    res = merge_wave(pairs)
    assert not res.fallback
    assert res.digest_valid.all()
    for i, (x, y) in enumerate(pairs):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(x.merge(y))


def _corrupt_pair(n_base=80, n_div=6):
    """A replica pair where B's copy of one shared-base node differs
    ONLY in its string payload (same id, same value class) — the
    append-only violation the device kernels cannot see (jaxw5 module
    caveat: host value bytes never reach the device)."""
    from cause_tpu.collections import clist as clmod
    from cause_tpu.collections.shared import refresh_caches

    base = c.clist(weaver="jax").extend([f"w{i}" for i in range(n_base)])
    a = CausalList(base.ct.evolve(site_id=new_site_id())).extend(
        [f"a{i}" for i in range(n_div)]
    )
    victim = list(base)[n_base // 2][0]
    nodes2 = dict(base.ct.nodes)
    cause, _val = nodes2[victim]
    nodes2[victim] = (cause, "CORRUPT")
    b_ct = refresh_caches(
        clmod.weave,
        base.ct.evolve(nodes=nodes2, yarns={}, site_id=new_site_id()),
    )
    b = CausalList(b_ct).extend([f"b{i}" for i in range(n_div)])
    return a, b


def test_value_byte_corruption_quarantines_pair(monkeypatch):
    """VERDICT r3 Weak #4 + ADVICE r4 #1: the device-only wave path
    must detect twins differing only in one string payload — and
    quarantine THAT pair instead of failing the wave's healthy pairs.
    Full-coverage sampling makes the probabilistic check
    deterministic for the test."""
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 10**9)
    a, b = _corrupt_pair()
    healthy = make_pairs(2)
    res = merge_wave([healthy[0], (a, b), healthy[1]])
    assert res.poisoned == [1]
    with pytest.raises(c.CausalError) as ei:
        res.merged(1)
    assert "append-only" in ei.value.info["causes"]
    # the healthy pairs are untouched
    for i in (0, 2):
        x, y = healthy[0] if i == 0 else healthy[1]
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(
            x.merge(y))


def test_value_byte_corruption_trips_session_spotcheck(monkeypatch):
    from cause_tpu.parallel import wave as wave_mod
    from cause_tpu.parallel.session import FleetSession

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 10**9)
    a, b = _corrupt_pair()
    with pytest.raises(c.CausalError) as ei:
        FleetSession([(a, b)])
    assert "append-only" in ei.value.info["causes"]


def test_spotcheck_disabled_documents_blind_spot(monkeypatch):
    """With sampling off the wave completes (the historical device
    -only behavior) — and materializing the pair still raises via the
    full host validation, which is the API-path guarantee."""
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 0)
    a, b = _corrupt_pair()
    res = merge_wave([(a, b)])
    assert not res.fallback
    with pytest.raises(c.CausalError):
        res.merged(0)


def test_spotcheck_clean_pairs_pass(monkeypatch):
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 10**9)
    pairs = make_pairs(3)
    res = merge_wave(pairs)
    assert not res.fallback
    for i, (x, y) in enumerate(pairs):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(x.merge(y))


def test_corrupt_pair_after_fallback_remaps_pair_index(monkeypatch):
    """The spot-check sees the COMPACTED live list; when a fallback
    pair precedes the corrupt one, info["pair"] must still name the
    WAVE index (round-5 review finding: without the remap a caller
    quarantining by info["pair"] hits a healthy pair)."""
    from cause_tpu.collections.cmap import CausalMap
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 10**9)
    m = c.cmap()
    ma = CausalMap(m.ct.evolve(site_id=new_site_id())).append(c.K("x"), "1")
    mb = CausalMap(m.ct.evolve(site_id=new_site_id())).append(c.K("y"), "2")
    a, b = _corrupt_pair()
    healthy = make_pairs(1)
    res = merge_wave([(ma, mb), healthy[0], (a, b)])
    assert res.fallback == [0]          # the map pair (host path)
    assert res.poisoned == [2]
    with pytest.raises(c.CausalError) as ei:
        res.merged(2)
    assert ei.value.info["pair"] == 2   # wave index, not live index 1
    x, y = healthy[0]
    assert c.causal_to_edn(res.merged(1)) == c.causal_to_edn(x.merge(y))


def test_corrupt_fallback_pair_poisons_itself(monkeypatch):
    """A corrupt replica that is ALSO off the device domain (host
    fallback path) must poison its own pair, not abort the wave for
    the healthy pairs (round-5 review finding: the eager fallback
    a.merge(b) used to raise out of merge_wave)."""
    from cause_tpu.parallel import wave as wave_mod

    monkeypatch.setattr(wave_mod, "_BODY_SAMPLE", 10**9)
    # force EVERY pair onto the host fallback path
    monkeypatch.setattr(wave_mod.lanecache, "view_for", lambda ct: None)
    a, b = _corrupt_pair()
    healthy = make_pairs(2)
    res = merge_wave([healthy[0], (a, b), healthy[1]])
    assert res.poisoned == [1]
    assert sorted(res.fallback) == [0, 2]
    with pytest.raises(c.CausalError) as ei:
        res.merged(1)
    assert "append-only" in ei.value.info["causes"]
    assert ei.value.info["pair"] == 1
    for i, (x, y) in ((0, healthy[0]), (2, healthy[1])):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(x.merge(y))
