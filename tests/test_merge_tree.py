"""The hierarchical merge reduction tree (PR 8): fleet convergence in
ceil(log2(n)) batched device rounds, bit-identical to the flat
pairwise fold.

Pins the tentpole contract:

- bit-identity at multiple shapes — odd replica counts (bye lanes),
  tombstoned suffixes, duplicated replicas (window twin dedupe),
  degenerate n=1/n=2 trees — against folding ``merge`` in input order;
- ``merge_all`` routes >=4 device-weaver list replicas through the
  tree (flat ``merge_many`` retained behind ``tree=False`` and for
  pure-weaver / small fleets), result identical either way;
- a mid-tree full-width bounce (window outgrowing ``w_budget``, the
  pow2-growth analogue of the session's re-upload bounce) does not
  corrupt later levels;
- per-level observability: ``tree.level`` + ``wave.digest`` with
  ``source="tree"`` per level, per-level ``wave.cost`` joins with the
  round index, level count == ceil(log2(n)), post-level-0 levels ride
  the delta path, and ``obs gap``'s tree decomposition renders;
- obs-off invariance: identical convergence with zero records;
- ``FleetSession.converge`` delegates to the tree (flat fold behind
  ``tree=False``) without disturbing the resident wave state;
- many editors of one document: mid-document edits run every level at
  full width (establishment fails, ``tree.establish`` carries
  ``ok=0``), tail appends one full level then delta; ``tree.level``'s
  ``nodes``/``merged`` are the handles' lane counts; a forced token
  overflow is a counted ``tree.fallback``;
- full-level winners stay symbolic: one host handle, at the root, whose
  node union still catches a body conflict between any two replicas.
"""

import functools
import random

import numpy as np
import pytest

import cause_tpu as c
from cause_tpu import obs
from cause_tpu.collections import clist as c_list
from cause_tpu.collections.shared import CausalError
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.obs import costmodel, semantic
from cause_tpu.parallel import tree as tree_mod
from cause_tpu.parallel.session import FleetSession


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset()
    semantic.reset()
    costmodel.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()
    semantic.reset()
    costmodel.reset()


def warm(cl):
    return CausalList(c_list.weave(cl.ct))


def make_base(n=40):
    base = warm(c.clist(weaver="jax").extend(
        [f"w{i}" for i in range(n)]
    ))
    base.ct.lanes.segments()
    return base


def make_fleet(base, n, n_div=4, hide_every=0):
    fleet = []
    for r in range(n):
        h = CausalList(base.ct.evolve(site_id=new_site_id()))
        for i in range(n_div):
            h = h.conj(f"r{r}.{i}")
            if hide_every and i and i % hide_every == 0:
                h = h.conj(c.hide)
        fleet.append(h)
    return fleet


def fold(handles):
    return functools.reduce(lambda a, b: a.merge(b), handles)


def assert_identical(got, want):
    assert got.ct.nodes == want.ct.nodes
    assert got.ct.weave == want.ct.weave
    assert got.ct.lamport_ts == want.ct.lamport_ts


# ------------------------------------------------------- bit identity


@pytest.mark.parametrize("n,n_div,hide_every", [
    (4, 3, 0),
    (5, 4, 0),    # odd: a bye lane at level 0
    (7, 2, 2),    # odd twice (7 -> 4 -> 2 -> 1), tombstoned suffixes
    (8, 5, 3),
])
def test_tree_bit_identical_to_fold(n, n_div, hide_every):
    base = make_base()
    fleet = make_fleet(base, n, n_div=n_div, hide_every=hide_every)
    root, rep = tree_mod.merge_tree_report(fleet)
    assert_identical(root, fold(fleet))
    assert len(rep["levels"]) == rep["rounds"] == tree_mod.tree_rounds(n)
    # level 0 establishes; later levels ride the delta window path
    assert rep["levels"][0]["path"] == "full"
    assert all(lv["path"] == "delta" for lv in rep["levels"][1:])


def test_tree_rounds_arithmetic():
    assert tree_mod.tree_rounds(1) == 0
    assert tree_mod.tree_rounds(2) == 1
    assert tree_mod.tree_rounds(3) == 2
    assert tree_mod.tree_rounds(5) == 3
    assert tree_mod.tree_rounds(64) == 6
    assert tree_mod.tree_rounds(1024) == 10


def test_degenerate_trees():
    base = make_base()
    a, b = make_fleet(base, 2, n_div=3)
    # n=1: the tree IS the input
    root, rep = tree_mod.merge_tree_report([a])
    assert root is a and rep["rounds"] == 0 and rep["levels"] == []
    # n=2: one full-width level, no delta rounds
    root, rep = tree_mod.merge_tree_report([a, b])
    assert_identical(root, a.merge(b))
    assert [lv["path"] for lv in rep["levels"]] == ["full"]
    # n=3: bye at level 0, delta root round
    root, rep = tree_mod.merge_tree_report([a, b, a])
    assert_identical(root, a.merge(b))
    assert rep["levels"][0]["byes"] == 1
    assert len(rep["levels"]) == 2


def test_duplicated_replicas_dedupe_in_windows():
    """A symmetric fleet ([a, b] repeated) pools identical sides at
    every post-0 level — the window twin dedupe must collapse them and
    every level must agree."""
    base = make_base()
    a, b = make_fleet(base, 2, n_div=3)
    root, rep = tree_mod.merge_tree_report([a, b] * 8)
    assert_identical(root, a.merge(b))
    assert all(lv["agreed"] for lv in rep["levels"])
    assert len(rep["levels"]) == 4


def test_flat_fold_equals_merge_fold():
    base = make_base()
    fleet = make_fleet(base, 5, n_div=3)
    assert_identical(tree_mod.flat_fold(fleet), fold(fleet))


# ------------------------------------------------------ merge_all API


def test_merge_all_routes_through_tree():
    base = make_base()
    fleet = make_fleet(base, 6, n_div=3, hide_every=2)
    want = fold(fleet)
    obs.configure(enabled=True)
    via_tree = c.merge_all(fleet[0], *fleet[1:])
    tl = [e for e in obs.events() if e.get("ev") == "event"
          and e.get("name") == "tree.level"]
    assert tl, "merge_all did not route through the tree"
    obs.configure(enabled=False)
    assert_identical(via_tree, want)
    # the flat path stays behind tree=False, same result
    obs.reset()
    obs.configure(enabled=True)
    via_flat = c.merge_all(fleet[0], *fleet[1:], tree=False)
    tl = [e for e in obs.events() if e.get("ev") == "event"
          and e.get("name") == "tree.level"]
    assert not tl, "tree=False must not route through the tree"
    obs.configure(enabled=False)
    assert via_flat.ct.nodes == want.ct.nodes
    assert via_flat.ct.weave == want.ct.weave


def test_merge_all_small_and_pure_fleets_stay_flat():
    base = make_base()
    a, b, x = make_fleet(base, 3, n_div=2)
    obs.configure(enabled=True)
    out = c.merge_all(a, b, x)  # < 4 inputs: merge_many
    tl = [e for e in obs.events() if e.get("ev") == "event"
          and e.get("name") == "tree.level"]
    assert not tl
    obs.configure(enabled=False)
    assert out.ct.nodes == fold([a, b, x]).ct.nodes
    # pure-weaver handles never touch the device path
    pbase = warm(c.clist().extend(["p"] * 12))
    pf = [CausalList(pbase.ct.evolve(site_id=new_site_id())).conj(f"x{r}")
          for r in range(5)]
    out = c.merge_all(pf[0], *pf[1:])
    assert out.ct.nodes == fold(pf).ct.nodes
    assert out.ct.weave == fold(pf).ct.weave


# ------------------------------------------------- mid-tree full bounce


def test_mid_tree_bounce_does_not_corrupt_later_levels():
    """Pooled windows outgrowing w_budget bounce that level (and, the
    windows only growing up the tree, the levels after it) to full
    document width — the result must stay bit-identical and the
    remaining rounds must still run."""
    base = make_base()
    fleet = make_fleet(base, 16, n_div=2)
    root, rep = tree_mod.merge_tree_report(fleet, w_budget=9)
    assert_identical(root, fold(fleet))
    paths = [lv["path"] for lv in rep["levels"]]
    assert len(paths) == 4
    assert "delta" in paths[1:], paths      # delta engaged before the
    assert "full" in paths[1:], paths       # bounce, full after it
    # tiny budget: every level bounces, result still exact
    root2, rep2 = tree_mod.merge_tree_report(fleet, w_budget=2)
    assert_identical(root2, fold(fleet))
    assert all(lv["path"] == "full" for lv in rep2["levels"])


# ----------------------------------------------------- observability


def test_tree_level_events_and_gap_join():
    base = make_base()
    fleet = make_fleet(base, 8, n_div=3)
    obs.configure(enabled=True)
    root, rep = tree_mod.merge_tree_report(fleet)
    evs = obs.events()
    obs.configure(enabled=False)
    assert_identical(root, fold(fleet))

    tl = [e["fields"] for e in evs if e.get("ev") == "event"
          and e.get("name") == "tree.level"]
    wd = [e["fields"] for e in evs if e.get("ev") == "event"
          and e.get("name") == "wave.digest"
          and e["fields"].get("source") == "tree"]
    wc = [e["fields"] for e in evs if e.get("ev") == "event"
          and e.get("name") == "wave.cost"
          and e["fields"].get("source") == "tree"]
    div = [e for e in evs if e.get("ev") == "event"
           and e.get("name") == "divergence"]
    assert not div, "mid-tree distinct subtrees must not mint incidents"
    rounds = tree_mod.tree_rounds(8)
    assert len(tl) == len(wd) == len(wc) == rounds
    assert sorted(f["level"] for f in tl) == list(range(rounds))
    assert [f["level"] for f in wc] == list(range(rounds))
    # level 0 full, the rest delta — and >= half of post-0 is delta
    assert wc[0]["path"] == "full"
    post = [f["path"] for f in wc[1:]]
    assert sum(1 for p in post if p == "delta") >= len(post) / 2
    assert all(f["dispatches"] >= 1 for f in wc)
    assert all(f["delta_ops"] > 0 for f in wc[1:])
    assert tl[-1]["final"] is True

    # the gap report's per-level decomposition
    dec = costmodel.tree_decomposition(evs)
    assert dec is not None and dec["rounds"] == rounds
    assert dec["post_level0_delta_share"] == 1.0
    assert all(lv["wall_ms"] > 0 for lv in dec["levels"])
    rep_dict = costmodel.gap_report([], evs)
    assert rep_dict["tree"]["rounds"] == rounds
    rendered = costmodel.render_gap(rep_dict)
    assert "merge tree" in rendered and "level 0" in rendered


def test_obs_off_invariance():
    base = make_base()
    fleet = make_fleet(base, 6, n_div=3)
    assert not obs.enabled()
    root, rep = tree_mod.merge_tree_report(fleet)
    assert obs.events() == []
    # no semantic monitor state, no cost-model state
    assert costmodel._PROGRAMS == {} and costmodel._PENDING_OPS == {}
    assert semantic._MON == {}
    # identical routing decisions with obs on
    obs.configure(enabled=True)
    root_on, rep_on = tree_mod.merge_tree_report(fleet)
    obs.configure(enabled=False)
    assert_identical(root, root_on)
    assert [lv["path"] for lv in rep["levels"]] == \
        [lv["path"] for lv in rep_on["levels"]]


# -------------------------------------------------- session converge


def test_session_converge_tree_and_fold():
    base = make_base()
    fleet = make_fleet(base, 4, n_div=3)
    pairs = [(fleet[0], fleet[1]), (fleet[2], fleet[3])]
    sess = FleetSession(pairs)
    sess.wave()
    want = fold(fleet)
    assert_identical(sess.converge(), want)
    got_flat = sess.converge(tree=False)
    assert got_flat.ct.nodes == want.ct.nodes
    assert got_flat.ct.weave == want.ct.weave
    # the resident wave state survives convergence
    d = sess.wave()
    assert d.shape == (2,)


# ---------------------------------------------------- generator twin


def test_tree_fleet_handles_generator():
    from cause_tpu import benchgen

    fleet = benchgen.tree_fleet_handles(5, 30, 4, hide_every=2)
    assert len(fleet) == 5
    assert all(h.ct.weaver == "jax" for h in fleet)
    root, rep = tree_mod.merge_tree_report(fleet)
    assert_identical(root, fold(fleet))
    assert len(rep["levels"]) == tree_mod.tree_rounds(5)


# ------------------------------------------- many editors, one document


def make_editors(base, n, ops, seed, hide_share=0.3):
    """``n`` editors off ``base``, each typing ``ops`` ops at cursors
    drawn from anywhere in the shared document: inserts, and hides of a
    document value (never twice by one editor). Every edit lies outside
    the delta domain."""
    rng = random.Random(seed)
    ids = [nd[0] for nd in base.ct.weave][1:]
    fleet = []
    for r in range(n):
        h = CausalList(base.ct.evolve(site_id=new_site_id()))
        hidden = set()
        for i in range(ops):
            target = rng.choice(ids)
            if rng.random() < hide_share and target not in hidden:
                hidden.add(target)
                h = h.append(target, c.hide)
            else:
                h = h.append(target, f"e{r}.{i}")
        fleet.append(h)
    return fleet


def pure_fold(handles):
    """The pairwise fold on the pure weaver, the semantics' oracle."""
    return fold([CausalList(h.ct.evolve(weaver="pure", lanes=None))
                 for h in handles])


def observed_tree(fleet):
    """The tree's root and report, with its spans and counters."""
    obs.configure(enabled=True)
    root, rep = tree_mod.merge_tree_report(fleet)
    spans = [e for e in obs.events() if e.get("ev") == "span"]
    counters = obs.counters_snapshot()["counters"]
    obs.configure(enabled=False)
    return root, rep, spans, counters


def level_counts(fleet, paths, prefix):
    """Per level, (nodes, merged) as the node sets give them: the lanes
    both sides of each pair hold and their union's, at a delta level
    each side's lanes past the shared ``prefix`` plus its anchor."""
    groups = [set(h.ct.nodes) for h in fleet]
    out = []
    for path in paths:
        pairs = [(groups[i], groups[i + 1])
                 for i in range(0, len(groups) - 1, 2)]
        if path == "full":
            out.append((sum(len(a) + len(b) for a, b in pairs),
                        sum(len(a | b) for a, b in pairs)))
        else:
            out.append((sum(2 + len(a) + len(b) - 2 * prefix
                            for a, b in pairs),
                        sum(1 + len(a | b) - prefix for a, b in pairs)))
        groups = [a | b for a, b in pairs] + groups[2 * len(pairs):]
    return out


@pytest.mark.parametrize("n", [13, 100])
def test_mid_document_editors_run_every_level_full_width(n):
    base = make_base(30)
    fleet = make_editors(base, n, 2, seed=n)
    root, rep, spans, counters = observed_tree(fleet)
    assert_identical(root, pure_fold(fleet))
    rounds = tree_mod.tree_rounds(n)
    assert [lv["path"] for lv in rep["levels"]] == ["full"] * rounds
    assert counters["tree.level.full"] == rounds
    assert "tree.level.delta" not in counters
    ok = [e["attrs"]["ok"] for e in spans if e["name"] == "tree.establish"]
    assert len(ok) == rounds - 1 and set(ok) == {0}
    winners = [e["attrs"] for e in spans if e["name"] == "tree.winners"]
    assert [w["pairs"] for w in winners] == \
        [lv["pairs"] for lv in rep["levels"]]


def test_tail_appends_ride_one_full_level_then_delta():
    base = make_base(30)
    fleet = make_fleet(base, 13, n_div=3, hide_every=2)
    root, rep, spans, counters = observed_tree(fleet)
    assert_identical(root, pure_fold(fleet))
    rounds = tree_mod.tree_rounds(13)
    assert [lv["path"] for lv in rep["levels"]] == \
        ["full"] + ["delta"] * (rounds - 1)
    assert counters["tree.level.full"] == 1
    assert counters["tree.level.delta"] == rounds - 1
    names = [e["name"] for e in spans]
    assert names.count("tree.sides") == rounds - 1
    assert [e["attrs"]["ok"] for e in spans
            if e["name"] == "tree.establish"] == [1]
    paths = [e["attrs"]["path"] for e in spans
             if e["name"] == "tree.dispatch"]
    assert paths == ["full"] + ["delta"] * (rounds - 1)
    # level 0's few runs rank at the cap, the one width it has
    level0 = next(e["attrs"] for e in spans if e["name"] == "tree.dispatch")
    assert level0["k"] == level0["u_max"]
    assert counters["v5.runs.full"] == 1
    assert "v5.runs.narrow" not in counters
    # the root is the one subtree built from its window ranks; its
    # members are the replicas themselves
    (mat,) = [e["attrs"] for e in spans if e["name"] == "tree.materialize"]
    assert mat["members"] == 13 and mat["nodes"] == len(root.ct.nodes)


@pytest.mark.parametrize("edits", ["tail", "anywhere"])
def test_level_node_counts_match_the_handles(edits):
    base = make_base(30)
    if edits == "tail":
        fleet = make_fleet(base, 7, n_div=3, hide_every=2)
    else:
        fleet = make_editors(base, 7, 3, seed=7)
    _root, rep, spans, _counters = observed_tree(fleet)
    got = [(e["attrs"]["nodes"], e["attrs"]["merged"]) for e in spans
           if e["name"] == "tree.level"]
    paths = [lv["path"] for lv in rep["levels"]]
    assert got == level_counts(fleet, paths, len(base.ct.nodes))


def test_a_forced_token_overflow_is_a_counted_fallback(monkeypatch):
    from cause_tpu import benchgen

    base = make_base(30)
    # interior edits make many merge tokens a row: more than the
    # starved budget holds, doubled or not
    fleet = make_editors(base, 5, 12, seed=5)
    monkeypatch.setattr(benchgen, "v5_token_budget", lambda lanes: 1)
    obs.configure(enabled=True)
    out = c.merge_all(fleet[0], *fleet[1:])
    spans = {e["name"] for e in obs.events() if e.get("ev") == "span"}
    counters = obs.counters_snapshot()["counters"]
    obs.configure(enabled=False)
    assert counters["tree.fallback"] == 1
    assert counters["tree.fallback.token-overflow"] == 1
    assert "tree.converge" in spans       # the tree ran, then gave up
    assert out.ct.weave == pure_fold(fleet).ct.weave


def test_full_levels_rank_runs_below_the_token_budget():
    base = make_base(120)
    fleet = make_editors(base, 8, 12, seed=8)
    root, rep, spans, counters = observed_tree(fleet)
    assert_identical(root, pure_fold(fleet))
    disp = [e["attrs"] for e in spans if e["name"] == "tree.dispatch"]
    assert [d["path"] for d in disp] == ["full"] * 3
    assert all(d["k"] < d["u_max"] for d in disp), disp
    assert counters["v5.runs.narrow"] == 3
    assert "v5.runs.full" not in counters


# ------------------------------------------- symbolic full-level winners


def clashing(base, fleet, i, j):
    """``fleet`` with replica ``j`` replaced by one that mints, on
    replica ``i``'s site, the id of ``i``'s first op with another body."""
    out = list(fleet)
    out[j] = CausalList(base.ct.evolve(
        site_id=fleet[i].ct.site_id)).conj("clash")
    return out


@pytest.mark.parametrize("edits,j,paths", [
    ("tail", 1, ["full", "delta", "delta"]),      # meet at level 0
    ("tail", 2, ["full", "delta", "delta"]),      # meet at a delta level
    ("anywhere", 2, ["full", "full", "full"]),    # meet at a full level
])
def test_body_conflicts_raise_wherever_the_replicas_meet(edits, j, paths):
    base = make_base(30)
    if edits == "tail":
        fleet = make_fleet(base, 8, n_div=3)
    else:
        fleet = make_editors(base, 8, 3, seed=8)
    fleet = clashing(base, fleet, 0, j)
    with pytest.raises(CausalError) as want:
        fold(fleet)
    obs.configure(enabled=True)
    with pytest.raises(CausalError) as got:
        tree_mod.merge_tree(fleet)
    levels = [e["attrs"]["path"] for e in obs.events()
              if e.get("ev") == "span" and e["name"] == "tree.level"]
    obs.configure(enabled=False)
    assert levels == paths
    with pytest.raises(CausalError) as got_all:
        c.merge_all(fleet[0], *fleet[1:])
    for err in (got.value, got_all.value):
        assert err.info["causes"] == want.value.info["causes"] == \
            {"append-only", "edits-not-allowed"}
        assert err.info["existing_node"] == want.value.info["existing_node"]


@pytest.mark.parametrize("edits", ["tail", "anywhere", "bounce"])
def test_full_level_winners_stay_symbolic_until_the_root(edits):
    base = make_base(30)
    w_budget = None
    if edits == "tail":
        fleet = make_fleet(base, 13, n_div=3, hide_every=2)
    elif edits == "anywhere":
        fleet = make_editors(base, 13, 2, seed=13)
    else:
        # level 1 rides delta with level 0's third winner as its bye;
        # level 2's windows outgrow the budget and bounce to full width
        fleet = make_fleet(base, 6, n_div=2)
        w_budget = 6
    obs.configure(enabled=True)
    root, rep = tree_mod.merge_tree_report(fleet, w_budget=w_budget)
    spans = [e for e in obs.events() if e.get("ev") == "span"]
    counters = obs.counters_snapshot()["counters"]
    obs.configure(enabled=False)
    assert_identical(root, pure_fold(fleet))
    paths = [lv["path"] for lv in rep["levels"]]
    pairs = [lv["pairs"] for lv in rep["levels"]]
    assert counters["tree.winner.deferred"] == sum(
        p for p, path in zip(pairs, paths) if path == "full")
    mats = [e["attrs"] for e in spans if e["name"] == "tree.materialize"]
    # the root's is the last span the tree closes
    assert spans[-1]["name"] == "tree.materialize"
    assert mats[-1] == {"members": len(fleet),
                        "nodes": len(root.ct.nodes)}
    if edits == "tail":
        assert paths == ["full", "delta", "delta", "delta"]
        assert counters["tree.winner.deferred"] == 6
        assert "tree.winner.union" not in counters
        assert len(mats) == 1
    elif edits == "anywhere":
        # every winner but the root's feeds a full level: its level
        # unions its two views, inside that level's tree.winners span
        assert paths == ["full"] * 4
        assert counters["tree.winner.union"] == 6 + 3 + 2
        assert len(mats) == 1
    else:
        # the bounce level unions its inputs itself: the delta winner
        # and the bye carried through the delta level
        assert paths == ["full", "delta", "full"]
        assert counters["tree.winner.union"] == 2
        assert [m["members"] for m in mats] == [4, 2, 6]
