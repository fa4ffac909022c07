"""Replicas for the row-level tests, made the way the benchmark makes
them (``benchmark/gen`` describes the documents, ``benchmark/build.py``
builds the program's handles) at test size, and the pure weaver's
answer to compare every row against."""

import functools
import json
import os

from benchmark import build
from benchmark.gen import edits, editors, fleet

MIXES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "mixes")


def mix(name: str) -> dict:
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


def with_minted(h, minted):
    """``h`` holding a client's minted ``(id, cause, value)`` ops,
    applied as the serve path applies them."""
    from cause_tpu import serde, sync

    if not minted:
        return h
    items = serde.encode_node_items(
        {nid: (cause, build.value(v)) for nid, cause, v in minted})
    return sync.apply_delta(h, serde.decode_node_items(items))


def divergent_pairs(seed: int, pairs: int, cap: int, divergent: int):
    """``pairs`` replica pairs of one ``cap``-node document, each side
    ``divergent`` values past it, every 8th a hide (the merge cells'
    fleet at capacity ``cap``)."""
    base, desc = fleet.fleet(seed, pairs, cap - 1 - divergent, divergent, 8)
    doc = build.document(base)
    return [(build.side(doc, a), build.side(doc, b)) for a, b in desc]


def typed_pairs(seed: int, pairs: int, doc_values: int, batches: int,
                mix_name: str):
    """One replica pair per tenant (the serve cells' tenants at test
    size), after ``batches`` runs of the serve mix ``mix_name`` minted
    on their sites."""
    ts = edits.tenants(seed, pairs, doc_values, 8, 8)
    prod = edits.Producer(seed + 1, mix(mix_name), ts, 1.0)
    for _ in range(batches):
        _due, t, st = prod.next_batch()
        prod.mint_on(t, st)
    out = []
    for t in ts:
        doc = build.document(t.base)
        out.append(tuple(with_minted(build.side(doc, side), st.pending)
                         for side, st in zip(t.sides, t.sites)))
    return out, prod


def more_runs(pairs, prod, batches: int):
    """``pairs`` after ``batches`` more runs of ``prod``'s mix: each
    replica gains its site's newly minted ops."""
    ts = prod.tenants
    seen = [[len(st.pending) for st in t.sites] for t in ts]
    for _ in range(batches):
        _due, t, st = prod.next_batch()
        prod.mint_on(t, st)
    return [tuple(with_minted(h, st.pending[n:])
                  for h, st, n in zip(pair, t.sites, seen[i]))
            for i, (pair, t) in enumerate(zip(pairs, ts))]


def editor_replicas(seed: int, n: int, doc_values: int, ops: int,
                    mix_name: str):
    """``n`` editors' replicas of one document (the tree cells at test
    size), each holding its own ``ops`` ops."""
    from cause_tpu.collections.clist import CausalList

    base, eds = editors.editors(seed, n, doc_values, ops, mix(mix_name))
    doc = build.document(base)
    out = []
    for d in eds:
        if d.values:
            out.append(build.side(doc, d))
        else:
            fork = CausalList(doc.ct.evolve(site_id=d.site))
            out.append(with_minted(
                fork, [(nid, c, v) for nid, (c, v) in d.nodes.items()]))
    return out


def pure(h):
    """``h`` on the pure weaver."""
    from cause_tpu.collections.clist import CausalList

    return CausalList(h.ct.evolve(weaver="pure", lanes=None))


def woven(h):
    """(weave ids, visible ids in weave order) of a handle."""
    return [n[0] for n in h.ct.weave], [n[0] for n in h]


def pure_merge(*handles):
    """What the pure weaver makes of merging ``handles`` in order."""
    return woven(functools.reduce(lambda x, y: x.merge(y),
                                  [pure(h) for h in handles]))


def kernel_rows(res, pairs):
    """Per row of a ``merge_wave`` result, (weave ids, visible ids) as
    the kernel's rank and visibility lanes give them."""
    import numpy as np

    from cause_tpu.weaver import lanecache

    out = []
    for i, (a, b) in enumerate(pairs):
        va, vb = lanecache.view_for(a.ct), lanecache.view_for(b.ct)
        cap = res.capacity
        rank = res.rank[i]
        keep = np.flatnonzero(rank < 2 * cap)
        order = keep[np.argsort(rank[keep], kind="stable")]
        ids = [(va.arena.nodes[j] if j < cap else
                vb.arena.nodes[j - cap])[0] for j in order]
        shown = [n for n, v in zip(ids, res.visible[i][order]) if v]
        out.append((ids, shown))
    return out


def assert_wave_rows(pairs):
    """``merge_wave`` over ``pairs`` weaves every row, on the device
    and as materialized, as the pure weaver merges the pair."""
    from cause_tpu.parallel import merge_wave

    res = merge_wave(pairs)
    assert not res.fallback and not res.poisoned
    lanes = kernel_rows(res, pairs)
    for i, (a, b) in enumerate(pairs):
        want = pure_merge(a, b)
        assert lanes[i] == want, i
        assert woven(res.merged(i)) == want, i
    return res
