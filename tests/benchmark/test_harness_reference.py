"""The plain reference (benchmark/reference/weave.py) agrees with the
program's semantics-defining pure weaver (cause_tpu/weaver/pure.py) on
seeded small fleets and edit streams, and its control does not."""

import pytest

from benchmark import build
from benchmark.gen import edits, fleet
from benchmark.reference import weave as ref

SEEDS = [1, 2, 3, 2**31 + 9, 2**40 + 3, 987654321]


def _pure(handle):
    from cause_tpu.collections.clist import CausalList

    return CausalList(handle.ct.evolve(weaver="pure", lanes=None))


def _program_view(handle):
    """(woven ids, visible (id, value) pairs) as the pure weaver has
    them, values in the generator's spelling."""
    def plain(v):
        return v if isinstance(v, str) else fleet.HIDE

    return ([n[0] for n in handle.ct.weave],
            [(n[0], plain(n[2])) for n in handle])


def _reference_view(nodes, order="descending"):
    woven = ref.weave(nodes, order)
    return woven, [(n, nodes[n][1]) for n, v
                   in zip(woven, ref.visible(woven, nodes)) if v]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("hide_every", [2, 3, 8])
def test_fleet_pairs_agree_with_the_pure_merge(seed, hide_every):
    base, desc = fleet.fleet(seed, 3, 40, 17, hide_every)
    doc = build.document(base)
    pairs = [(build.side(doc, a), build.side(doc, b)) for a, b in desc]
    for (a, b), (da, db) in zip(pairs, desc):
        nodes = dict(base.nodes)
        nodes.update(da.nodes)
        nodes.update(db.nodes)
        got = _program_view(_pure(a).merge(_pure(b)))
        assert got == _reference_view(nodes)
        assert got != _reference_view(nodes, "ascending")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cursor", ["anywhere", "tail"])
def test_edit_streams_agree_with_the_pure_weaver(seed, cursor):
    import cause_tpu as c
    from cause_tpu import sync
    from cause_tpu.collections.clist import CausalList

    (t,) = edits.tenants(seed, 1, 30, 5, 3)
    mix = {"run_len": [1, 2, 3, 4], "insert_share": 0.7, "cursor": cursor}
    prod = edits.Producer(seed, mix, [t], 10.0)
    for _ in range(25):
        _due, tt, st = prod.next_batch()
        prod.mint_on(tt, st)
        for nid, cause, v in st.pending:
            t.admitted[nid] = (cause, v)
        st.pending = []
    nodes = t.nodes()
    fresh = c.clist(weaver="pure").ct.evolve(uuid=t.base.uuid,
                                             site_id=t.base.site)
    h = CausalList(fresh).extend(t.base.values)
    prog = {nid: (cause, c.hide if v == fleet.HIDE else v)
            for nid, (cause, v) in nodes.items() if nid not in h.ct.nodes}
    h = sync.apply_delta(h, prog)
    assert _program_view(h) == _reference_view(nodes)


def test_value_ids_follow_the_paste_layout():
    base, desc = fleet.fleet(4, 2, 9000, 5, 8)
    assert [base.value_id(j) for j in range(base.n)] == list(base.nodes)
    a = desc[0][0]
    assert [a.value_id(j) for j in range(a.n)] == list(a.nodes)
    base.release()
    assert base.nodes is None and base.value_id(8192)[0] == 2


def test_a_special_that_targets_a_special_is_refused():
    nodes = {(1, "a", 0): (ref.ROOT, "x"), (2, "a", 0): ((1, "a", 0), ":hide"),
             (3, "a", 0): ((2, "a", 0), ":hide")}
    with pytest.raises(ValueError, match="targets a special"):
        ref.weave(nodes)


def test_a_missing_cause_is_an_error():
    with pytest.raises(ValueError, match="unreachable"):
        ref.weave({(1, "a", 0): ((9, "zz", 0), "x")})


def test_visibility_follows_hides_only():
    nodes = {(1, "a", 0): (ref.ROOT, "x"), (2, "a", 0): ((1, "a", 0), "y"),
             (3, "b", 0): ((1, "a", 0), ":hide")}
    woven = ref.weave(nodes)
    assert woven == [ref.ROOT, (1, "a", 0), (3, "b", 0), (2, "a", 0)]
    assert ref.visible(woven, nodes) == [False, False, False, True]
