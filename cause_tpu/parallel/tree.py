"""Hierarchical merge reduction tree: fleet convergence in O(log n)
pipelined device rounds.

``merge_all`` / flat fleet convergence is a SEQUENCE: folding n
replicas through pairwise merges pays n-1 sequential wave rounds, each
a full-document-width dispatch — the dominant remaining term of the
north-star gap once the per-wave cost went delta-native (PR 7). This
module is the Tascade-shaped answer (arXiv:2311.15810, atomic-free
asynchronous reduction trees): pair the n replicas up and batch each
TREE LEVEL as one device burst —

- **level 0** (first contact) runs the full-width batched v5 kernel +
  digest over all n/2 pairs in ONE fused dispatch
  (``wave.dispatch_full_rows``), exactly the establishment policy of
  the delta-native session: the level's output ranks freeze the fleet
  frontier (the shared converged lane prefix, its weave-final anchor,
  and the prefix's exact uint32 digest contribution);
- **levels 1..L** ride PR 7's delta path: each surviving subtree is a
  *symbolic* record — the frozen prefix plus a pooled, id-sorted
  **side** of its members' divergent lanes (partial aggregation: a
  level merges two sides by one vectorized merge-dedupe, never
  re-materializing the subtree) — and a level's n/2^k pairwise merges
  become ONE ``weaver.jaxwd.batched_delta_weave`` dispatch over the
  anchor+side windows, returning each subtree's TOTAL document digest
  (prefix digest + window terms) bit-identical to a full-width weave;
- **pipelining**: a delta level's windows depend only on the pooled
  host sides, not on the previous level's device output — so the host
  merges level k+1's sides while level k executes on device, and only
  the per-level digest fetch synchronizes (the per-level convergence
  evidence the flight recorder records);
- **the root** materializes ONCE: prefix weave ++ root-window weave
  (the PR-7 factorization, n-way) after a delta level, the final rank
  row after a full one, with the same append-only node union
  validation any fold of ``merge`` performs, run once over every
  leaf. Intermediate winners never pay host materialization — the
  O(doc)-per-level Python cost that makes the flat fold slow: a full
  level's winners stay lane views too (the pair's two views and its
  rank row), unioned into one view only where the next level runs
  full width.

A level whose window outgrows ``w_budget`` (or whose establishment
fails: no shared prefix, tombstoned anchor, out-of-domain causes)
**bounces** to a full-width batched level — one union view per
survivor, the document-width kernel, re-establishment — and later
levels ride delta again. Convergence is bit-identical to the pairwise
fold in every regime (the weave is a pure function of the node set;
pinned in tests/test_merge_tree.py), and every level lands
``tree.level`` + ``wave.digest(source="tree")`` semantic events and a
per-level ``wave.cost`` join (``python -m cause_tpu.obs gap`` renders
the per-level decomposition).

Round count: ceil(log2(n)) levels (odd survivor counts carry a bye
lane to the next level). This is also the multi-chip seam (ROADMAP
item 3): subtrees per chip, one cross-chip root round.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from .. import chaos as _chaos
from .. import obs
from ..collections import shared as s
from ..weaver import lanecache
from ..weaver.arrays import I32_MAX, next_pow2
from ..weaver.segments import (SEG_LANE_KEYS, _TABLE_DTYPES,
                               concat_seg_tables, tree_segments)
from . import recovery as _recovery
from .wave import _assemble_rows, delta_domain_ok, dispatch_full_rows

__all__ = [
    "merge_tree",
    "merge_tree_report",
    "merge_all_tree",
    "flat_fold",
    "tree_rounds",
]


def tree_rounds(n: int) -> int:
    """ceil(log2(n)) — the tree's round count for ``n`` replicas (0
    for a single replica; byes don't add rounds: survivors halve,
    rounding up, every level)."""
    return (int(n) - 1).bit_length() if n > 1 else 0


# ------------------------------------------------------------- sides


class _Side:
    """One subtree's pooled divergent lanes: id-sorted, deduped, with
    causes carried as packed ids (-1 = the anchor) so window-local
    cause indices re-derive by one searchsorted per level. ``nodes``
    parallels the lanes for root materialization."""

    __slots__ = ("keys", "hi", "lo", "vc", "cause_key", "nodes")

    def __init__(self, keys, hi, lo, vc, cause_key, nodes):
        self.keys = keys
        self.hi = hi
        self.lo = lo
        self.vc = vc
        self.cause_key = cause_key
        self.nodes = nodes

    def __len__(self) -> int:
        return len(self.keys)


def _side_of(view, sp: int, anchor: int) -> _Side:
    """The divergent side of one view w.r.t. the fleet frontier:
    lanes ``[sp, n)`` with causes re-keyed by packed id (anchor ->
    -1). Lanes are already id-sorted (arena layout); the delta-domain
    check (every cause inside the window or on the anchor) ran at
    establishment."""
    a = view.arena
    n = view.n - sp
    sl = slice(sp, view.n)
    hi = a.ts[sl].astype(np.int32)
    lo = a.spec.pack_lo(a.site[sl], a.tx[sl]).astype(np.int32)
    keys = (hi.astype(np.int64) << 32) | (lo.astype(np.int64)
                                          & 0xFFFFFFFF)
    ci = a.cause_idx[sl]
    ci_c = np.clip(ci, 0, max(0, view.n - 1))
    c_hi = a.ts[ci_c].astype(np.int64)
    c_lo = (a.spec.pack_lo(a.site[ci_c], a.tx[ci_c]).astype(np.int64)
            & 0xFFFFFFFF)
    cause_key = np.where(ci == anchor, np.int64(-1), (c_hi << 32) | c_lo)
    nodes = list(a.nodes[sp:view.n])
    if n > 1 and not bool(np.all(keys[1:] >= keys[:-1])):
        order = np.argsort(keys, kind="stable")
        keys, hi, lo = keys[order], hi[order], lo[order]
        cause_key = cause_key[order]
        vc = a.vclass[sl][order]
        nodes = [nodes[int(i)] for i in order]
        return _Side(keys, hi, lo, vc, cause_key, nodes)
    return _Side(keys, hi, lo, a.vclass[sl].copy(), cause_key, nodes)


def _merge_sides(x: _Side, y: _Side) -> _Side:
    """Partial aggregation: the level-k+1 side is one vectorized
    merge-dedupe of the two level-k sides — O(side), no subtree
    re-materialization, no per-node Python beyond the kept-node list."""
    keys = np.concatenate([x.keys, y.keys])
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    dup = np.zeros(len(ks), bool)
    dup[1:] = ks[1:] == ks[:-1]
    keep = order[~dup]

    def take(ax, ay):
        return np.concatenate([ax, ay])[keep]

    nodes_cat = x.nodes + y.nodes
    return _Side(
        ks[~dup], take(x.hi, y.hi), take(x.lo, y.lo),
        take(x.vc, y.vc), take(x.cause_key, y.cause_key),
        [nodes_cat[int(i)] for i in keep],
    )


# ---------------------------------------------------------- subtrees


class _Sub:
    """One surviving subtree: ``members`` are its leaf handles, ``views``
    the lane views the levels read. A leaf carries its ``handle``; a
    winner carries none until it is the root. A full-level winner keeps
    the pair's two input views and its fetched rank row over them
    (``ranks``, ``wcap`` = the level's cap, no ``sides_lr``); a later
    full level collapses them into their one union view. A delta-level
    winner pools its members' views and carries the pooled ``side``
    plus the level output that builds the root (``ranks``/``wcap``/
    ``sides_lr`` — window ranks stay ON DEVICE until the root asks)."""

    __slots__ = ("handle", "members", "views", "side", "ranks", "wcap",
                 "sides_lr")

    def __init__(self, handle=None, members=None, views=None, side=None,
                 ranks=None, wcap=0, sides_lr=None):
        self.handle = handle
        self.members = members or ([handle] if handle is not None else [])
        self.views = views or []
        self.side = side
        self.ranks = ranks
        self.wcap = wcap
        self.sides_lr = sides_lr


def _union_nodes(members) -> dict:
    """N-way node union with the append-only validation every merge
    path shares (earlier members win the union so a conflict reports
    the body already in the merge target)."""
    nodes: dict = {}
    for h in reversed(members):
        nodes.update(h.ct.nodes)
    for h in members:
        if not (h.ct.nodes.items() <= nodes.items()):
            for nid, body in h.ct.nodes.items():
                if nodes[nid] != body:
                    raise s.CausalError(
                        "This node is already in the tree and can't "
                        "be changed.",
                        {"causes": {"append-only", "edits-not-allowed"},
                         "existing_node": (nid,) + nodes[nid]},
                    )
    return nodes


def _outside_domain():
    """The error a fleet the device lanes cannot hold raises."""
    return s.CausalError(
        "fleet outside the device domain (PackSpec overflow "
        "or map-shaped tree)",
        {"causes": {"outside-domain"}},
    )


def _union(sub: _Sub) -> None:
    """Collapse a winner's views into their one union view, the view a
    full level reads: counted ``tree.winner.union``."""
    union = lanecache.union_views_many(sub.views)
    if union is None:
        raise _outside_domain()
    sub.views = [union]
    obs.counter("tree.winner.union").inc()


def _view_of(sub: _Sub):
    """The one lane view a full level reads for ``sub``. A winner whose
    union its producing level did not build (a delta winner at a
    window bounce, a bye carried through a delta level, an injected
    budget exhaustion) builds it here, under ``tree.materialize``."""
    if len(sub.views) > 1:
        with obs.span("tree.materialize", members=len(sub.members)) as sp:
            _union(sub)
            sp.set(nodes=sub.views[0].n)
    return sub.views[0]


def _materialize(sub: _Sub, state: Optional[dict]):
    """The root's host handle — the one subtree that becomes one: its
    weave from the final level's ranks (a full-width rank row, or the
    prefix weave ++ the window weave, the PR-7 factorization n-way),
    its nodes validated over every leaf."""
    if sub.handle is not None:
        return sub.handle
    with obs.span("tree.materialize", members=len(sub.members)) as sp:
        if sub.sides_lr is None:
            weave = _full_weave(sub)
        else:
            weave = _window_weave(sub, state)
        handle = _build_handle(sub, weave)
        sp.set(nodes=len(handle.ct.nodes))
    return handle


def _full_weave(sub: _Sub) -> list:
    """A full-level winner's weave: its pair's lanes in rank order."""
    va, vb = sub.views
    cap = sub.wcap
    rank_row = sub.ranks
    keep = np.flatnonzero(rank_row < 2 * cap)
    order = keep[np.argsort(rank_row[keep], kind="stable")]
    an, bn = va.arena.nodes, vb.arena.nodes
    return [an[j] if j < cap else bn[j - cap] for j in order.tolist()]


def _window_weave(sub: _Sub, state: dict) -> list:
    """A delta-level winner's weave: the prefix weave ++ the window
    weave from its window ranks."""
    assert state is not None and sub.ranks is not None
    wcap = sub.wcap
    n_w = 2 * wcap
    rw = np.asarray(sub.ranks)
    side_l, side_r = sub.sides_lr
    win_nodes: List = [None] * n_w
    for t, side in ((0, side_l), (1, side_r)):
        off = t * wcap
        for j, nd in enumerate(side.nodes):
            win_nodes[off + 1 + j] = nd
    mask = rw < n_w
    mask[0] = False
    mask[wcap] = False
    idx = np.flatnonzero(mask)
    order = idx[np.argsort(rw[idx], kind="stable")]
    prefix_order = np.argsort(state["pr"], kind="stable")
    v0 = state["prefix_view"]
    weave = [v0.arena.nodes[int(j)] for j in prefix_order]
    weave += [win_nodes[int(i)] for i in order]
    return weave


def _build_handle(sub: _Sub, weave: list):
    """``_materialize``'s handle: ``weave`` with the validated node
    union of the leaves and the union lane view, set on ``sub``."""
    nodes = _union_nodes(sub.members)
    union = lanecache.union_views_many(sub.views)
    if union is not None and union.n != len(weave):  # pragma: no cover
        raise s.CausalError(
            "merge-tree materialization inconsistency (weave length "
            "!= union size) — please report",
            {"causes": {"tree-internal"},
             "weave": len(weave), "union": union.n},
        )
    yarns: dict = {}
    if union is not None:
        for nd in union.arena.nodes[:union.n]:
            yarns.setdefault(nd[0][1], []).append(nd)
    first = sub.members[0]
    lamport = max(
        max(h.ct.lamport_ts for h in sub.members),
        max(nid[0] for nid in nodes),
    )
    ct = first.ct.evolve(nodes=nodes, yarns=yarns, weave=weave,
                         lamport_ts=lamport, lanes=union)
    sub.handle = type(first)(ct)
    return sub.handle


# ----------------------------------------------------- establishment


def _establish(check_views, rank0, vis0, cap) -> Optional[dict]:
    """Freeze the fleet delta frontier from a completed full level:
    the shared converged lane prefix across EVERY view that feeds
    later levels, its weave-final anchor (derived from pair 0's output
    ranks, with the same permutation sanity check the delta session
    runs), and the prefix's frozen digest contribution. None when any
    check fails — later levels then run full width (correct, O(doc)).

    ``check_views`` are the level's INPUT views (pair sides + byes'
    views): pair 0's rank row indexes input lanes, the delta domain
    must hold for every divergent lane that exists anywhere in the
    fleet, and every survivor's sides slice these same views — their
    first ``s`` lanes must BE the prefix."""
    from .mesh import mix32_np

    v0 = check_views[0]
    sp = v0.n
    for v in check_views[1:]:
        sp = min(sp, lanecache.shared_prefix_len(v0, v))
        if sp < 1:
            return None
    ra = rank0[:sp]
    rb = rank0[cap:cap + sp]
    pr = np.minimum(ra, rb).astype(np.int32)
    if not bool((pr < sp).all()):
        return None
    if int(pr.max()) != sp - 1 or \
            int(np.bincount(pr, minlength=sp).max()) != 1:
        return None
    anchor = int(np.argmax(pr))
    arena = v0.arena
    if int(arena.vclass[anchor]) > 0:
        return None
    for v in check_views:
        if not delta_domain_ok(v, sp, anchor):
            return None
    keep_a = ra < 2 * cap
    vis = np.where(keep_a, vis0[:sp], vis0[cap:cap + sp])
    hi = arena.ts[:sp].astype(np.int32)
    lo = arena.spec.pack_lo(arena.site[:sp], arena.tx[:sp])
    pdig = int(np.uint32(
        mix32_np(hi, lo, pr, vis).sum(dtype=np.uint64)
        & np.uint64(0xFFFFFFFF)))
    return {
        "s": int(sp),
        "anchor": anchor,
        "anchor_hi": np.int32(arena.ts[anchor]),
        "anchor_lo": np.int32(arena.spec.pack_lo(
            arena.site[anchor:anchor + 1],
            arena.tx[anchor:anchor + 1])[0]),
        "pr": pr,
        "prefix_view": v0,
        "pdig": pdig,
    }


# ----------------------------------------------------- level windows


def _assemble_level(sides_pairs, state, wcap: int) -> Dict[str, np.ndarray]:
    """The level's ``[P, 2*wcap]`` delta-window batch: per pair, each
    tree is lane 0 = the anchor (presented as the window root) followed
    by that subtree's pooled side, causes re-derived into window
    coordinates by one searchsorted against the side's sorted keys.
    Host cost is O(total window lanes) — the per-level assembly the
    delta regime is allowed to pay."""
    P = len(sides_pairs)
    n_w = 2 * wcap
    hi = np.full((P, n_w), I32_MAX, np.int32)
    lo = np.full((P, n_w), I32_MAX, np.int32)
    cci = np.full((P, n_w), -1, np.int32)
    vc = np.zeros((P, n_w), np.int32)
    valid = np.zeros((P, n_w), bool)
    seg = np.full((P, n_w), -1, np.int32)
    per_row = []
    s_need = 8
    for r, (sl_, sr_) in enumerate(sides_pairs):
        per_tree = []
        for t, side in enumerate((sl_, sr_)):
            d = len(side)
            w = 1 + d
            off = t * wcap
            hi[r, off] = state["anchor_hi"]
            lo[r, off] = state["anchor_lo"]
            valid[r, off] = True
            local_cci = np.full(wcap, -1, np.int32)
            if d:
                hi[r, off + 1:off + w] = side.hi
                lo[r, off + 1:off + w] = side.lo
                vc[r, off + 1:off + w] = side.vc
                valid[r, off + 1:off + w] = True
                pos = np.searchsorted(side.keys, side.cause_key)
                local = np.where(side.cause_key < 0, 0,
                                 pos + 1).astype(np.int32)
                local_cci[1:w] = local
                cci[r, off + 1:off + w] = local + off
            segs = tree_segments(hi[r, off:off + wcap],
                                 lo[r, off:off + wcap],
                                 local_cci, vc[r, off:off + wcap], w)
            per_tree.append((segs, w))
        per_row.append(per_tree)
        s_need = max(s_need,
                     sum(sg["sg_len"].shape[0] for sg, _ in per_tree))
    s_max = next_pow2(s_need)
    tables = {k: np.zeros((P, s_max), _TABLE_DTYPES[k])
              for k in SEG_LANE_KEYS}
    for r, per_tree in enumerate(per_row):
        row_out = {k: tables[k][r] for k in SEG_LANE_KEYS}
        _t, bases = concat_seg_tables(per_tree, wcap, s_max,
                                      out=row_out)
        for t, ((segs, w), base) in enumerate(zip(per_tree, bases)):
            off = t * wcap
            seg[r, off:off + w] = segs["run_of_lane"][:w] + base
    lanes = {"hi": hi, "lo": lo, "cci": cci, "vc": vc, "valid": valid,
             "seg": seg}
    lanes.update(tables)
    return lanes


# ------------------------------------------------------- level runs


def _observe_level(uuid, level, digests, pairs, byes, delta_ops,
                   window, path, dispatches, final):
    from ..obs import lag as _lag
    from ..obs import semantic as _sem

    if not _sem.enabled():
        return None
    out = _sem.observe_tree_level(
        uuid, level, digests, [True] * len(digests), pairs=pairs,
        byes=byes, delta_ops=delta_ops, window=window, path=path,
        dispatches=dispatches, final=final)
    # convergence-lag resolution, tree flavor: level 0 weaves every
    # replica's stamped ops (create→woven); only the FINAL level's
    # fleet-wide digest agreement converges them — intermediate levels
    # converge subtrees, not the fleet
    _lag.level_observed(uuid, agreed=bool(out and out.get("agreed")),
                        level=level, final=final)
    return out


def _delta_level(pairs, state, level, uuid, byes, final):
    """One delta level: windows assembled from pooled sides, ONE fused
    ``batched_delta_weave`` dispatch for the whole level, the next
    level's sides merged on host WHILE the device executes (the
    pipeline), then one digest fetch. Returns ``(new_subs, digests,
    stats)``."""
    from ..benchgen import LANE_KEYS5
    from ..weaver import jaxwd

    sides_pairs = [(a.side, b.side) for a, b in pairs]
    wmax = max(max(len(l), len(r)) for l, r in sides_pairs)
    wcap = next_pow2(max(8, 1 + wmax))
    n_w = 2 * wcap
    P = len(pairs)
    delta_ops = sum(len(l) + len(r) for l, r in sides_pairs)
    if obs.enabled():
        from ..obs import costmodel as _cm

        _cm.wave_begin("tree")
    # nodes: the real window lanes the kernel reads, each side's pooled
    # lanes plus its anchor; merged: each union's anchor plus its side
    with obs.span("tree.level", level=level, pairs=P, wcap=int(wcap),
                  path="delta", nodes=int(delta_ops + 2 * P)) as lsp:
        with obs.span("tree.assemble", level=level):
            lanes = _assemble_level(sides_pairs, state, wcap)
        pdig = np.full(P, np.uint32(state["pdig"]), np.uint32)
        r0 = np.full(P, state["s"] - 1, np.int32)
        with obs.span("tree.dispatch", level=level, path="delta",
                      u_max=int(n_w)):
            rank_w, _vis_w, dig, ovf = _recovery.run_dispatch(
                "tree",
                lambda: jaxwd.batched_delta_weave(
                    *(jnp.asarray(lanes[k]) for k in LANE_KEYS5),
                    jnp.asarray(pdig), jnp.asarray(r0),
                    u_max=int(n_w), k_max=int(n_w)))
            if obs.enabled():
                from ..obs import costmodel as _cm

                _cm.record_dispatch(f"tree:delta:w{int(wcap)}",
                                    site="tree")
            # pipeline: merge the NEXT level's sides on host while the
            # window weave executes on device; the digest fetch below
            # is this level's only sync point
            with obs.span("tree.sides", level=level):
                new_subs = [
                    _Sub(members=a.members + b.members,
                         views=a.views + b.views,
                         side=_merge_sides(a.side, b.side),
                         ranks=rank_w[i], wcap=wcap,
                         sides_lr=(a.side, b.side))
                    for i, (a, b) in enumerate(pairs)
                ]
            digests = np.asarray(dig)
            overflow = bool(np.asarray(ovf).any())
        if overflow:  # pragma: no cover -
            # structurally unreachable at u_max = N_w; a future budget
            # change degrades to the full-width bounce, never to wrong
            if obs.enabled():
                from ..obs import costmodel as _cm

                _cm.wave_abandon()
            return None
        lsp.set(merged=sum(1 + len(sub.side) for sub in new_subs))
    obs.counter("tree.level.delta").inc()
    sem = _observe_level(uuid, level, digests, P, byes, delta_ops,
                         wcap, "delta", 1, final)
    if obs.enabled():
        from ..obs import costmodel as _cm

        # lanes is the O(doc) axis (each pair's two documents: frozen
        # prefix + divergent side), tokens the O(delta) window work —
        # same units as the session's wave.cost join
        doc_lanes = sum(2 * state["s"] + len(l) + len(r)
                        for l, r in sides_pairs)
        _cm.wave_cost(uuid=uuid, pairs=P,
                      lanes=doc_lanes,
                      tokens=delta_ops + 2 * P,
                      token_budget=int(n_w) * P,
                      delta_ops=delta_ops, semantic=sem,
                      path="delta", level=level)
    stats = {"level": level, "pairs": P, "byes": byes, "path": "delta",
             "window": int(wcap), "delta_ops": int(delta_ops),
             "distinct": len(set(int(d) for d in digests)),
             "agreed": len(set(int(d) for d in digests)) == 1}
    return new_subs, digests, stats


def _side_of_views(views, sp: int, anchor: int) -> _Side:
    """A subtree's side over all its views: each view's side, merged."""
    side = _side_of(views[0], sp, anchor)
    for v in views[1:]:
        side = _merge_sides(side, _side_of(v, sp, anchor))
    return side


def _outgrows(subs, w_budget: Optional[int]) -> bool:
    """Whether the subtrees' pooled windows outgrow ``w_budget``."""
    return w_budget is not None and \
        1 + max(len(sub.side) for sub in subs) > int(w_budget)


def _full_level(pairs, state, level, uuid, byes, bye_subs, final,
                w_budget):
    """One full-width level (first contact / bounce): one lane view per
    side of every pair, the fused document-width kernel+digest over the
    whole level in one dispatch, and the winners kept symbolic (leaf
    members, the pair's two views, the rank row). The delta frontier is
    (re-)established for the levels that follow; a winner's views are
    unioned only when the next level runs full width too."""
    views_pairs = [(_view_of(a), _view_of(b)) for a, b in pairs]
    check_views = [v for vp in views_pairs for v in vp]
    if not lanecache.compatible(check_views):
        raise _outside_domain()
    P = len(pairs)
    cap = next_pow2(max(max(va.n, vb.n) for va, vb in views_pairs))
    if obs.enabled():
        from ..obs import costmodel as _cm

        _cm.wave_begin("tree")
    # nodes: the real lanes of both sides of every pair; merged: the
    # real lanes of the winners (each pair's node union, the lanes its
    # rank row keeps)
    with obs.span("tree.level", level=level, pairs=P, cap=int(cap),
                  path="full",
                  nodes=sum(v.n for v in check_views)) as lsp:
        with obs.span("tree.assemble", level=level):
            lanes = _assemble_rows(views_pairs, cap)
        with obs.span("tree.dispatch", level=level, path="full") as dsp:
            rank, vis, dig, info = dispatch_full_rows(lanes, site="tree")
            dsp.set(u_max=info["u_max"], k=info["k"])
        lsp.set(merged=int((rank[:P] < 2 * cap).sum()))
    obs.counter("tree.level.full").inc()
    delta_ops = sum(
        (va.n - state["s"]) + (vb.n - state["s"])
        for va, vb in views_pairs
    ) if state is not None else 0
    sem = _observe_level(uuid, level, dig, P, byes, delta_ops,
                         cap, "full", 1 + (1 if info["retried"] else 0),
                         final)
    if obs.enabled():
        from ..obs import costmodel as _cm

        _cm.wave_cost(uuid=uuid, pairs=P, lanes=2 * int(cap) * P,
                      tokens=info["u_need"] * P,
                      token_budget=info["u_max"] * P,
                      delta_ops=delta_ops,
                      overflow_retries=info["retried"], semantic=sem,
                      path="full", level=level)
    new_subs = [
        _Sub(members=a.members + b.members, views=[va, vb],
             ranks=rank[i], wcap=cap)
        for i, ((a, b), (va, vb)) in enumerate(zip(pairs, views_pairs))
    ]
    obs.counter("tree.winner.deferred").inc(P)
    survivors = new_subs + list(bye_subs)
    new_state = None
    if len(survivors) > 1:
        # (re-)establish the frontier for the levels that follow — over
        # the level's INPUT views (the rank row indexes them) and the
        # byes' views: every survivor's views are among them
        bye_views = [v for sub in bye_subs for v in sub.views]
        with obs.span("tree.establish", level=level) as esp:
            new_state = _establish(check_views + bye_views, rank[0],
                                   vis[0], cap)
            if new_state is not None:
                sp, anchor = new_state["s"], new_state["anchor"]
                for sub in survivors:
                    sub.side = _side_of_views(sub.views, sp, anchor)
            esp.set(ok=int(new_state is not None))
        if new_state is None:
            obs.counter("tree.establish_fail").inc()
            if obs.enabled():
                # the next level cannot ride delta: declared, not
                # silent — the levels that follow run full width
                # until an establishment succeeds
                _recovery.step("tree", "delta", "full",
                               "establish-fail", uuid=uuid,
                               level=level)
    # the winners' host work: a next level at full width reads one
    # view per survivor, so union each symbolic one's views now
    next_full = len(survivors) > 1 and (
        new_state is None or _outgrows(survivors, w_budget))
    with obs.span("tree.winners", level=level, pairs=P):
        if next_full:
            for sub in survivors:
                if len(sub.views) > 1:
                    _union(sub)
    stats = {"level": level, "pairs": P, "byes": byes, "path": "full",
             "window": int(cap), "delta_ops": int(delta_ops),
             "distinct": len(set(int(d) for d in dig)),
             "agreed": len(set(int(d) for d in dig)) == 1}
    return new_subs, new_state, dig, stats


# ---------------------------------------------------------- the tree


def _merge_tree_impl(handles, w_budget: Optional[int]):
    first = handles[0]
    for h in handles[1:]:
        s.check_mergeable(first.ct, h.ct)
    uuid = str(first.ct.uuid)
    report = {"n": len(handles), "rounds": tree_rounds(len(handles)),
              "levels": []}
    if len(handles) == 1:
        return handles[0], report
    subs = [_Sub(handle=h) for h in handles]
    for sub in subs:
        v = lanecache.view_for(sub.handle.ct)
        if v is None:
            raise _outside_domain()
        sub.views = [v]
    if not lanecache.compatible([sub.views[0] for sub in subs]):
        rebuilt = [lanecache.build_view(sub.handle.ct.nodes,
                                        sub.handle.ct.uuid)
                   for sub in subs]
        if any(v is None for v in rebuilt) or not lanecache.compatible(
                rebuilt):
            raise _outside_domain()
        for sub, v in zip(subs, rebuilt):
            sub.views = [v]
    state = None
    level = 0
    with obs.span("tree.converge", n=len(handles),
                  rounds=report["rounds"]):
        while len(subs) > 1:
            pairs = [(subs[i], subs[i + 1])
                     for i in range(0, len(subs) - 1, 2)]
            bye_subs = [subs[-1]] if len(subs) % 2 else []
            byes = len(bye_subs)
            final = len(pairs) == 1 and byes == 0
            use_delta = (
                state is not None
                and all(sub.side is not None for sub in subs)
            )
            if use_delta and _outgrows(subs, w_budget):
                # mid-tree full-width bounce: the pooled windows
                # outgrew the budget — run this level at document
                # width and re-establish for the rest
                obs.counter("tree.window_bounce").inc()
                if obs.enabled():
                    _recovery.step("tree", "delta", "full",
                                   "window-budget", uuid=uuid,
                                   level=level)
                use_delta = False
            if use_delta and _chaos.enabled() \
                    and _chaos.budget_exhaust("tree"):
                # injected window-budget exhaustion: identical ladder
                # rung, identical (bit-identical) full-width bounce
                obs.counter("tree.window_bounce").inc()
                if obs.enabled():
                    _recovery.step("tree", "delta", "full",
                                   "budget-exhaustion", uuid=uuid,
                                   level=level)
                use_delta = False
            if use_delta:
                out = _delta_level(pairs, state, level, uuid, byes,
                                   final)
            else:
                out = None
            if out is None:
                new_subs, state, _dig, stats = _full_level(
                    pairs, state, level, uuid, byes, bye_subs, final,
                    w_budget)
                subs = new_subs + bye_subs
            else:
                new_subs, _dig, stats = out
                subs = new_subs + bye_subs
            report["levels"].append(stats)
            level += 1
    root = _materialize(subs[0], state)
    report["path_counts"] = {
        p: sum(1 for lv in report["levels"] if lv["path"] == p)
        for p in ("full", "delta")
    }
    return root, report


def merge_tree_report(handles: Sequence, *,
                      w_budget: Optional[int] = None) -> Tuple[object, dict]:
    """Converge a fleet of list-shaped replica handles into ONE handle
    via the merge reduction tree, returning ``(root, report)`` with
    the per-level stats (``report["levels"]``: level, pairs, byes,
    path, window, delta_ops, digest agreement). See the module
    docstring; ``w_budget`` bounds the per-side window width before a
    level bounces to full document width (None = unbounded)."""
    handles = list(handles)
    if not handles:
        raise s.CausalError("Nothing to merge.",
                            {"causes": {"empty-fleet"}})
    return _merge_tree_impl(handles, w_budget)


def merge_tree(handles: Sequence, *,
               w_budget: Optional[int] = None):
    """``merge_tree_report`` without the report: the fleet's converged
    root handle, bit-identical to folding pairwise ``merge`` over the
    same replicas, in ceil(log2(n)) batched device rounds."""
    return merge_tree_report(handles, w_budget=w_budget)[0]


def merge_all_tree(handles: Sequence):
    """``merge_all``'s tree router: the converged root for >=4
    device-weaver list-shaped handles, or None when the fleet is
    outside the tree domain (map trees, pure/native weaver, PackSpec
    overflow, token overflow) — the caller then takes the flat
    ``merge_many`` path. Raises only REAL merge errors (append-only
    body conflicts, type/uuid mismatches), exactly like the fold."""
    handles = list(handles)
    if len(handles) < 4:
        return _fallback("small-fleet")
    if getattr(handles[0].ct, "weaver", "") != "jax":
        # pure/native users picked the host oracle: never drag the
        # device path (and a jax import) into their merge_all
        return _fallback("host-weaver")
    for h in handles:
        if lanecache.view_for(h.ct) is None:
            return _fallback("no-lanes")
    try:
        return merge_tree(handles)
    except s.CausalError as err:
        causes = set(err.info.get("causes") or ())
        for cause in ("outside-domain", "token-overflow"):
            if cause in causes:
                return _fallback(cause)
        raise


def _fallback(cause: str):
    """``merge_all_tree``'s None, counted: ``tree.fallback`` and
    ``tree.fallback.<cause>``."""
    obs.counter("tree.fallback").inc()
    obs.counter(f"tree.fallback.{cause}").inc()
    return None


def flat_fold(handles: Sequence, ctx=None):
    """The O(n)-round baseline the tree replaces: fold the fleet
    through n-1 SEQUENTIAL pairwise merge waves, materializing every
    intermediate winner (each step needs a host handle to feed the
    next wave). Kept as the A/B control for ``BENCH_TREE`` and
    ``FleetSession.converge(tree=False)``."""
    from .wave import merge_wave

    handles = list(handles)
    if not handles:
        raise s.CausalError("Nothing to merge.",
                            {"causes": {"empty-fleet"}})
    acc = handles[0]
    with obs.span("tree.flat_fold", n=len(handles)):
        for h in handles[1:]:
            acc = merge_wave([(acc, h)], ctx=ctx).merged(0)
    return acc
