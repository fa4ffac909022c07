"""Batched API-level merge waves: device wave == per-pair merge, with
cached lanes doing the marshal and digests reporting convergence."""

import sys
import threading

import numpy as np
import pytest

import cause_tpu as c
from cause_tpu import obs
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.obs import lag as obs_lag
from cause_tpu.parallel import make_mesh, merge_wave
from cause_tpu.parallel import wave as wave_mod
from cause_tpu.weaver import lanecache


@pytest.fixture
def obs_on(monkeypatch):
    """Obs recording for one test, from a clean state and back."""
    for k in ("CAUSE_TPU_OBS", "CAUSE_TPU_OBS_OUT", "CAUSE_TPU_CHAOS"):
        monkeypatch.delenv(k, raising=False)
    obs.configure(enabled=True, reset=True)
    obs_lag.reset()
    yield
    obs.reset()
    obs_lag.reset()


@pytest.fixture
def fresh_staging(monkeypatch):
    """merge_wave's shared staging, empty for this test."""
    monkeypatch.setattr(wave_mod, "_STAGING", wave_mod.WaveBuffers())


def _scatter_copied():
    """``copied`` of the last ``wave.scatter`` span in the obs ring."""
    spans = [e for e in obs.events()
             if e.get("ev") == "span" and e.get("name") == "wave.scatter"]
    return spans[-1]["attrs"]["copied"]


def _staging_counts():
    return (obs.counter("wave.staging.reuse").value,
            obs.counter("wave.staging.alloc").value)


def _assert_merges(res, pairs):
    for i, (a, b) in enumerate(pairs):
        assert c.causal_to_edn(res.merged(i)) == c.causal_to_edn(
            a.merge(b)), i


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


@pytest.mark.parametrize("map_pair", [False, True],
                         ids=["device_only", "with_host_fallback"])
def test_wave_guards_and_fallbacks(obs_on, map_pair):
    pairs = make_pairs(2)
    # uuid mismatch raises like any merge
    with pytest.raises(c.CausalError):
        merge_wave([(pairs[0][0], c.clist("z", weaver="jax"))])
    # a pure-weaver pair still works (lane cache builds on demand)
    base = c.clist(weaver="pure").extend(["p"] * 10)
    a = CausalList(base.ct.evolve(site_id=new_site_id())).conj("1")
    b = CausalList(base.ct.evolve(site_id=new_site_id())).conj("2")
    wave = [(a, b)]
    if map_pair:
        # a map pair runs the host merge: the device row lands in the
        # result by the per-row fill, not by handing the fetch over
        from cause_tpu.collections.cmap import CausalMap

        m = c.cmap().append(c.K("t"), "x")
        wave.insert(0, (
            CausalMap(m.ct.evolve(site_id=new_site_id())).append(
                c.K("t"), "a"),
            CausalMap(m.ct.evolve(site_id=new_site_id())).append(
                c.K("u"), "b")))
    res = merge_wave(wave)
    assert res.fallback == ([0] if map_pair else [])
    assert res.digest_valid.tolist() == ([False, True] if map_pair
                                         else [True])
    assert _scatter_copied() == (1 if map_pair else 0)
    assert res.digest.dtype == np.uint32
    _assert_merges(res, wave)


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


@pytest.mark.parametrize("fallback_pair", [False, True],
                         ids=["device_only", "with_host_fallback"])
def test_wave_overflow_rows_retry_on_device(obs_on, monkeypatch,
                                            fallback_pair):
    """A spiky row outside the sampled token budget retries with a
    doubled budget instead of silently demoting to the host merge
    (soak-found: session digests diverged from wave digests purely
    because of budget-sampling fallbacks). With a host-fallback pair in
    the same wave, the retried rows still land in their own rows."""
    from cause_tpu import benchgen

    # a budget sampled at half the tokens: the spiky row overflows it
    # and fits the doubled one
    budget = benchgen.v5_token_budget
    monkeypatch.setattr(benchgen, "v5_token_budget",
                        lambda lanes: int(budget(lanes)) // 2)
    pairs = make_pairs(5, n_base=40, n_div=6)
    a, b = pairs[2]
    for j in range(12):  # interior tombstones explode pair 2's segments
        a = a.append(list(a)[2 + j][0], c.hide)
    pairs[2] = (a, b)
    if fallback_pair:
        # ids beyond the PackSpec ts bound: no lane cache, host merge
        x, y = make_pairs(1, n_base=40, n_div=6)[0]
        big = ((1 << 31) - 1, x.get_site_id(), 0)
        pairs.insert(1, (x.insert((big, c.root_id, "huge-ts")),
                         y.insert((big, c.root_id, "huge-ts"))))
    res = merge_wave(pairs)
    assert obs.counter("wave.overflow_retry").value >= 1
    assert res.fallback == ([1] if fallback_pair else [])
    assert res.digest_valid.tolist() == [
        not (fallback_pair and i == 1) for i in range(len(pairs))]
    assert _scatter_copied() == (5 if fallback_pair else 0)
    _assert_merges(res, pairs)


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


def _hide_interior(pairs, row, k):
    """Pair ``row`` with ``k`` interior tombstones on side a: more
    segments in that row, the same lane capacity."""
    a, b = pairs[row]
    for j in range(k):
        a = a.append(list(a)[2 + 3 * j][0], c.hide)
    pairs[row] = (a, b)
    return pairs


def _shape(pairs):
    views = [(lanecache.view_for(a.ct), lanecache.view_for(b.ct))
             for a, b in pairs]
    segs = [va.segments()["sg_len"].shape[0] + vb.segments()["sg_len"].shape[0]
            for va, vb in views]
    return views, [max(va.n, vb.n) for va, vb in views], segs


def test_shared_staging_reuse_across_fleets_is_exact(obs_on, fresh_staging):
    """Two default-staging waves of one shape on different fleets: the
    second has shorter rows (the shrink gap) and fewer segments where
    the first had the most. Its lanes, tables and results are those of
    assembly into fresh buffers, and the first wave's result still
    materializes after the second rewrote the staging."""
    from cause_tpu.weaver.segments import SEG_LANE_KEYS

    first = _hide_interior(make_pairs(3, n_base=100, n_div=8), 0, 6)
    second = _hide_interior(make_pairs(3, n_base=70, n_div=3), 2, 5)
    _v1, n1, segs1 = _shape(first)
    views2, n2, segs2 = _shape(second)
    assert max(n2) < min(n1) and segs2[0] < segs1[0]

    res1 = merge_wave(first)
    assert _staging_counts() == (0, 1)
    shape = wave_mod._STAGING.shape
    res2 = merge_wave(second)
    assert wave_mod._STAGING.shape == shape        # one shape, reused
    assert _staging_counts() == (1, 1)

    cap = res2.capacity
    fresh = wave_mod._assemble_rows(views2, cap)   # fresh WaveBuffers
    staged = wave_mod._STAGING.lanes
    for k in ("hi", "lo", "cci", "vc", "valid", "seg", "sg_valid",
              "sg_len"):
        assert np.array_equal(staged[k], fresh[k]), k
    live = fresh["sg_valid"]
    for k in SEG_LANE_KEYS:
        assert np.array_equal(staged[k][live], fresh[k][live]), k
    # the derived lanes, as the row-by-row expressions write them
    for r, (va, vb) in enumerate(views2):
        base = 0
        for t, v in enumerate((va, vb)):
            a, n, off = v.arena, v.n, t * cap
            sl = slice(off, off + n)
            ci = a.cause_idx[:n]
            segs = v.segments()
            assert np.array_equal(staged["lo"][r, sl],
                                  a.spec.pack_lo(a.site[:n], a.tx[:n]))
            assert np.array_equal(staged["cci"][r, sl],
                                  np.where(ci >= 0, ci + off, -1))
            assert np.array_equal(staged["seg"][r, sl],
                                  segs["run_of_lane"][:n] + base)
            base += segs["sg_len"].shape[0]

    ref = merge_wave(second, ctx=wave_mod.WaveBuffers())
    for k in ("rank", "visible", "digest", "digest_valid"):
        assert np.array_equal(getattr(res2, k), getattr(ref, k)), k
    _assert_merges(res2, second)
    _assert_merges(res1, first)


def test_concurrent_waves_never_share_staging(obs_on, fresh_staging,
                                              monkeypatch):
    """Two threads wave at once: the one that finds the shared staging
    taken assembles into buffers of its own (``wave.staging.alloc``),
    and both results are right."""
    fleets = [make_pairs(3, n_base=60, n_div=4) for _ in range(2)]
    merge_wave(make_pairs(3, n_base=60, n_div=4))  # staging takes the shape
    before = _staging_counts()
    # both threads must be inside assembly at the same time
    met = threading.Barrier(2, timeout=120)
    assemble = wave_mod._assemble_rows

    def meet_then_assemble(*args, **kw):
        met.wait()
        return assemble(*args, **kw)

    monkeypatch.setattr(wave_mod, "_assemble_rows", meet_then_assemble)
    results, errors = [None, None], []

    def run(k):
        try:
            results[k] = merge_wave(fleets[k])
        except Exception as err:  # surfaced by the assert below
            errors.append(err)

    threads = [threading.Thread(target=run, args=(k,)) for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert not errors, errors
    reuse, alloc = _staging_counts()
    assert (reuse - before[0], alloc - before[1]) == (1, 1)
    for res, pairs in zip(results, fleets):
        _assert_merges(res, pairs)


def test_staging_under_thread_contention(obs_on, fresh_staging):
    """More waving threads than cores, switching every few microseconds:
    every wave is right and every call is counted once."""
    n_threads, calls = 8, 2
    fleets = [make_pairs(2, n_base=30, n_div=3) for _ in range(n_threads)]
    results = [[] for _ in range(n_threads)]
    errors = []

    def run(k):
        try:
            for _ in range(calls):
                results[k].append(merge_wave(fleets[k]))
        except Exception as err:  # surfaced by the assert below
            errors.append(err)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert sum(_staging_counts()) == n_threads * calls
    for got, pairs in zip(results, fleets):
        assert len(got) == calls
        for res in got:
            _assert_merges(res, pairs)


def test_failed_wave_releases_and_resets_staging(obs_on, fresh_staging,
                                                 monkeypatch):
    """A wave that raises after taking the shared staging gives it back
    emptied (its rows may be half written), and the next wave is right."""
    pairs = make_pairs(3, n_base=40, n_div=4)
    merge_wave(pairs)
    dispatch = wave_mod._dispatch_wave

    def fail(*args, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(wave_mod, "_dispatch_wave", fail)
    with pytest.raises(RuntimeError):
        merge_wave(pairs)
    assert wave_mod._STAGING.shape is None
    assert not wave_mod._STAGING_LOCK.locked()
    monkeypatch.setattr(wave_mod, "_dispatch_wave", dispatch)
    res = merge_wave(pairs)
    assert _staging_counts() == (0, 2)
    _assert_merges(res, pairs)
