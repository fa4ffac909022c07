"""Batched API-level merge waves: many replica pairs, one kernel.

This is the end-to-end north-star path (BASELINE.json config 5: 1024
divergent replica pairs of 10k-node CausalLists, p50 < 100 ms on one
chip). The reference converges a fleet by running its O(n*m) pairwise
reduce-insert once per pair (shared.cljc:300-314); here a wave of
pairs becomes ONE batched v5 segment-union dispatch whose host side is
assembly of *cached* per-tree lanes and segment tables (the lane cache,
weaver/lanecache.py) — no node-dict walking, no Python-per-node work.

Contract (deliberately device-resident, unlike the reference's eager
materialization): ``merge_wave`` returns a ``WaveResult`` holding
per-pair rank/visibility lanes and convergence digests. The converged
*state* is those lanes; turning a pair back into a host ``CausalList``
(`result.merged(i)`) is on-demand, because rebuilding 1024 Python node
dicts is host-render cost the wave itself should not pay. Fleet
control planes that only need convergence checks read the digests.

Pairs outside the accelerated domain (ids beyond the PackSpec, rank
generations that cannot be aligned, or kernel overflow rows) fall back
to the ordinary per-pair ``merge`` — same trees out, just slower.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..collections import shared as s
from ..weaver import lanecache
from ..weaver.arrays import I32_MAX, next_pow2
from ..weaver.segments import SEG_LANE_KEYS, concat_seg_tables
from . import recovery as _recovery

__all__ = ["merge_wave", "WaveResult", "WaveBuffers",
           "delta_domain_ok", "assemble_delta_window",
           "dispatch_full_rows", "last_kernel_hlo"]


@lru_cache(maxsize=8)
def _digest_fn():
    from .mesh import replica_digest

    return jax.jit(jax.vmap(replica_digest))


class WaveBuffers:
    """Reusable host-side assembly buffers for repeated waves.

    Allocating and faulting in the [B, 2*cap] batch arrays dominates
    assembly cost at north-star scale (about 705 MB at 1024 pairs x
    16,384 lanes per tree); steady-state sync runs waves over the same
    fleet shape every round, so the buffers persist and each wave only
    rewrites the lanes that exist (plus re-padding the shrink gap when
    a row got shorter, and invalidating the segment-table tail). One
    set holds one shape, ``(B, cap, s_max)``: a wave of another shape
    replaces the arrays. ``merge_wave`` without ``ctx`` assembles into
    one module-level set that lives as long as the process; pass a
    set of your own via ``merge_wave(ctx=...)`` to keep staging apart
    (``FleetSession`` does)."""

    def __init__(self):
        self.shape = None
        self.lanes = None
        self.prev_n = None   # [B, 2] lanes written last wave, per tree

    def ensure(self, B: int, cap: int, s_max: int):
        shape = (B, cap, s_max)
        N = 2 * cap
        if self.shape != shape:
            self.lanes = {
                "hi": np.full((B, N), I32_MAX, np.int32),
                "lo": np.full((B, N), I32_MAX, np.int32),
                "cci": np.full((B, N), -1, np.int32),
                "vc": np.zeros((B, N), np.int32),
                "valid": np.zeros((B, N), bool),
                "seg": np.full((B, N), -1, np.int32),
                "sg_min_hi": np.zeros((B, s_max), np.int32),
                "sg_min_lo": np.zeros((B, s_max), np.int32),
                "sg_max_hi": np.zeros((B, s_max), np.int32),
                "sg_max_lo": np.zeros((B, s_max), np.int32),
                "sg_len": np.zeros((B, s_max), np.int32),
                "sg_lane0": np.zeros((B, s_max), np.int32),
                "sg_dense": np.zeros((B, s_max), bool),
                "sg_tail_special": np.zeros((B, s_max), bool),
                "sg_valid": np.zeros((B, s_max), bool),
                "sg_vsum": np.zeros((B, s_max), np.int32),
            }
            self.prev_n = np.zeros((B, 2), np.int64)
            self.shape = shape
        return self.lanes


_PAD = {
    "hi": I32_MAX, "lo": I32_MAX, "cci": -1, "vc": 0, "valid": False,
    "seg": -1,
}

# merge_wave's staging when the caller passes no ctx: one set, held by
# one wave at a time from assembly until its lanes are off the device
_STAGING = WaveBuffers()
_STAGING_LOCK = threading.Lock()


@contextlib.contextmanager
def _staging(ctx: Optional[WaveBuffers]):
    """The buffers one wave assembles into: ``ctx`` as given, else the
    shared ``_STAGING``, else (another wave holds it) a fresh set of
    this wave's own. Counts ``wave.staging.reuse`` when the shared set
    already had the wave's shape and ``wave.staging.alloc`` when the
    wave allocated staging."""
    if ctx is not None:
        yield ctx
        return
    if not _STAGING_LOCK.acquire(blocking=False):
        obs.counter("wave.staging.alloc").inc()
        yield WaveBuffers()
        return
    try:
        shape = _STAGING.shape
        try:
            yield _STAGING
        except BaseException:
            # a wave that failed mid-assembly can leave rows whose
            # prev_n no longer covers what they hold: start over
            _STAGING.shape = _STAGING.lanes = None
            raise
        obs.counter("wave.staging.reuse" if _STAGING.shape == shape
                    else "wave.staging.alloc").inc()
    finally:
        _STAGING_LOCK.release()


def delta_domain_ok(view, s: int, anchor: int,
                    start: Optional[int] = None) -> bool:
    """Whether lanes ``[start, view.n)`` stay inside the delta-wave
    domain for a pair whose shared converged prefix is ``[0, s)`` with
    anchor lane ``anchor`` (the prefix weave's final node):

    - every cause resolves inside the divergent window (lane >= s) or
      to the anchor itself — a cause stabbing any other resident lane
      would splice new weave positions into the frozen prefix;
    - no special (tombstone) targets the anchor — that would flip a
      frozen resident lane's visibility.

    ``start`` defaults to ``s`` (validate the whole divergent region,
    the rebuild-time call); updates validate only their appended tail.
    The check is O(lanes checked) vectorized numpy — the whole point
    is that steady-state rounds pay O(delta) here."""
    a = view.arena
    lo = s if start is None else start
    if lo >= view.n:
        return True
    ci = a.cause_idx[lo:view.n]
    ok = (ci >= s) | (ci == anchor)
    if not bool(np.all(ok)):
        return False
    if bool(np.any((a.vclass[lo:view.n] > 0) & (ci == anchor))):
        return False
    return True


def assemble_delta_window(views, s_arr, anchor_arr, wcap: int):
    """Build the delta wave's ``[B, 2*wcap]`` window batch from cached
    views: per tree, lane 0 is the anchor (presented as the window
    root: cause -1) followed by the divergent-suffix lanes
    ``[s, n)``, causes remapped into window coordinates (anchor -> 0,
    window lane ``j`` -> ``j - s + 1``). Returns ``(lanes, starts,
    counts)`` with ``lanes`` the ``benchgen.LANE_KEYS5`` dict and
    ``starts``/``counts`` the [B, 2] per-tree shared-prefix length and
    divergent lane count the splice program consumes; ``lanes`` holds
    every ``benchgen.LANE_KEYS5`` key. The segment tables are sized by
    the rows' segment counts (pow2), as the full wave and the tree
    size theirs: tables as wide as the window made the 1024-row delta
    program take ~25 min to compile on the v5e (PR 21). Host cost is
    O(total window lanes) — the per-wave assembly the delta path is
    allowed to pay."""
    from ..weaver.segments import _TABLE_DTYPES, tree_segments

    B = len(views)
    Nw = 2 * wcap
    hi = np.full((B, Nw), I32_MAX, np.int32)
    lo = np.full((B, Nw), I32_MAX, np.int32)
    cci = np.full((B, Nw), -1, np.int32)
    vc = np.zeros((B, Nw), np.int32)
    valid = np.zeros((B, Nw), bool)
    seg = np.full((B, Nw), -1, np.int32)
    starts = np.zeros((B, 2), np.int32)
    counts = np.zeros((B, 2), np.int32)
    per_row = []
    for r, (va, vb) in enumerate(views):
        s = int(s_arr[r])
        anchor = int(anchor_arr[r])
        per_tree = []
        for t, v in enumerate((va, vb)):
            a = v.arena
            d = v.n - s
            w = 1 + d
            off = t * wcap
            hi[r, off] = np.int32(a.ts[anchor])
            lo[r, off] = a.spec.pack_lo(a.site[anchor:anchor + 1],
                                        a.tx[anchor:anchor + 1])[0]
            valid[r, off] = True
            if d:
                sl = slice(s, v.n)
                hi[r, off + 1:off + w] = a.ts[sl]
                lo[r, off + 1:off + w] = a.spec.pack_lo(a.site[sl],
                                                        a.tx[sl])
                ci = a.cause_idx[sl]
                local = np.where(ci == anchor, 0,
                                 ci - s + 1).astype(np.int32)
                cci[r, off + 1:off + w] = local + off
                vc[r, off + 1:off + w] = a.vclass[sl]
                valid[r, off + 1:off + w] = True
            local_cci = np.full(wcap, -1, np.int32)
            if d:
                local_cci[1:w] = np.where(ci == anchor, 0, ci - s + 1)
            segs = tree_segments(hi[r, off:off + wcap],
                                 lo[r, off:off + wcap],
                                 local_cci, vc[r, off:off + wcap], w)
            per_tree.append((segs, w))
            starts[r, t] = s
            counts[r, t] = d
        per_row.append(per_tree)
    s_max = next_pow2(max(
        sum(sg["sg_len"].shape[0] for sg, _ in per_tree)
        for per_tree in per_row))
    tables = {k: np.zeros((B, s_max), _TABLE_DTYPES[k])
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
    return lanes, starts, counts


def dispatch_full_rows(lanes, site: str = "tree"):
    """One fused full-width kernel+digest dispatch over an assembled
    ``[B, 2*cap]`` v5 lane batch (``benchgen.LANE_KEYS5`` dict), with
    the pow2-quantized token budget and a doubled-budget retry for
    spiky unsampled rows: the full-width level of the merge reduction
    tree (``parallel.tree``), its only caller.

    Returns ``(rank, visible, digest, info)`` as numpy arrays plus an
    ``info`` dict (``u_need``/``u_max``/``retried`` — the caller's
    ``wave.cost`` evidence — and ``k``, the run width the kernel ran
    at). Raises ``CausalError`` if a row still
    overflows at the doubled budget (unlike ``merge_wave`` there is no
    per-pair host fallback here: the caller owns the batch)."""
    from ..benchgen import LANE_KEYS5, v5_token_budget
    from ..weaver.jaxwd import batched_weave_digest

    u_need = int(v5_token_budget(lanes))
    u_max = next_pow2(u_need)

    def _run(sub, u):
        out = _recovery.run_dispatch(
            site,
            lambda: batched_weave_digest(
                *(jnp.asarray(sub[k]) for k in LANE_KEYS5),
                u_max=int(u), k_max=int(u), with_k=True))
        if obs.enabled():
            from ..obs import costmodel as _cm

            _cm.record_dispatch(f"{site}:full:u{int(u)}", site=site)
        *out, k = (np.asarray(x) for x in out)
        count_run_width(int(k), int(u))
        return (*out, int(k))

    rank, visible, digest, overflow, k = _run(lanes, u_max)
    retried = 0
    if overflow.any():
        rows = np.flatnonzero(overflow)
        retried = len(rows)
        obs.counter("wave.overflow_retry").inc(retried)
        if obs.enabled():
            # recovery-ladder rung: the sampled token budget missed a
            # spiky row, escalate just those rows to a doubled budget
            _recovery.step(site, "full", "double_budget",
                           "token-overflow", rows=retried)
        sub = {k: lanes[k][rows] for k in LANE_KEYS5}
        r2, v2, d2, ov2, _k2 = _run(sub, 2 * u_max)
        if ov2.any():
            raise s.CausalError(
                "full-width level overflowed its doubled token budget",
                {"causes": {"token-overflow"},
                 "rows": np.flatnonzero(ov2).tolist()},
            )
        rank = np.array(rank)
        visible = np.array(visible)
        digest = np.array(digest)
        rank[rows] = r2
        visible[rows] = v2
        digest[rows] = d2
    return rank, visible, digest, {
        "u_need": u_need, "u_max": int(u_max), "retried": retried, "k": k,
    }


def _observe_semantics(pairs, digests, valid, source: str):
    """One wave's CRDT-semantic telemetry (``wave.digest`` agreement,
    per-pair staleness, ``divergence`` provenance) — obs-on callers
    only. The version-vector callback is lazy: vectors are built from
    the yarn caches only when a divergence actually needs
    first-differing-site provenance, never on the agreeing fast
    path. Returns the wave summary fields (``observe_wave``'s dict)
    so the cost model can join them onto its ``wave.cost`` event, or
    None when obs is off."""
    from ..obs import lag as _lag
    from ..obs import semantic
    from ..sync import version_vector

    if not semantic.enabled():
        return None

    def vv_of(i):
        # the merged pair's vector: pointwise max of both replicas'
        a, b = pairs[i]
        vv = {k: list(v) for k, v in version_vector(a).items()}
        for site, h in version_vector(b).items():
            h = list(h)
            if site not in vv or vv[site] < h:
                vv[site] = h
        return vv

    sem = semantic.observe_wave(pairs[0][0].ct.uuid, digests, valid,
                                vv_of=vv_of, source=source)
    # convergence-lag resolution: the wave wove every op stamped for
    # this document (create→woven), and an agreeing wave is the
    # fleet-converged visibility point (create→converged); a
    # disagreeing or degenerate wave leaves the ops pending — they
    # resolve at the first wave whose digests agree
    _lag.wave_observed(pairs[0][0].ct.uuid,
                       agreed=bool(sem and sem.get("agreed")),
                       source=source)
    return sem

# Lanes sampled per tree per wave by the body spot-check below.
# CAUSE_TPU_BODY_SAMPLE=0 disables; a value >= the tree size checks
# every lane (what the adversarial tests use).
_BODY_SAMPLE = int(os.environ.get("CAUSE_TPU_BODY_SAMPLE", "16") or 0)
_wave_seq = itertools.count()


def _sampled_body_spotcheck(views, k: Optional[int] = None) -> dict:
    """Close the device value-byte blind spot probabilistically.

    The kernels dedupe twin segments by ids/classes/structure; host
    VALUE bytes never reach the device (jaxw5 module caveat), so two
    replicas sharing an id but differing in its body — an append-only
    violation from a corrupt replica (reference rule:
    shared.cljc:169-171) — would pass the device-only wave/digest
    paths silently. ``WaveResult.merged`` validates fully, but fleets
    that read only digests never call it.

    Returns ``{pair_index: CausalError}`` for the violating pairs
    (the caller quarantines them; raising here would fail every
    healthy pair in the wave — round-4 advisor finding #1).

    This check samples ``k`` random lanes per tree per wave and
    compares bodies with the twin via its O(1) ``lane_of`` index —
    O(k) per pair instead of O(shared base), which is the entire point
    of the segment-union design. Samples rotate each wave (counter
    -seeded RNG), so repeated waves over a fleet accumulate coverage;
    at the north-star scale one wave already draws ~16k samples.
    """
    k = _BODY_SAMPLE if k is None else k
    bad: dict = {}
    if k <= 0:
        return bad
    # fresh entropy + a session counter: samples must differ both
    # across waves in one process AND across process restarts, or the
    # promised coverage accumulation never happens for one-wave-per
    # -process deployments (CLI sync rounds)
    rng = np.random.default_rng(
        [os.getpid(), time.time_ns() & 0xFFFFFFFF, next(_wave_seq)]
    )
    for pair_idx, (va, vb) in enumerate(views):
        for side, (src, dst) in enumerate(((va, vb), (vb, va))):
            ns, nd = src.n, dst.n
            if not ns or not nd:
                continue
            lanes = (range(ns) if k >= ns
                     else rng.integers(0, ns, size=k))
            sn, dn = src.arena.nodes, dst.arena.nodes
            d_lane = dst.arena.lane_of
            for ln in lanes:
                nid, cause, value = sn[int(ln)]
                j = d_lane.get(nid)
                if (j is not None and j < nd
                        and (dn[j][1] != cause or dn[j][2] != value)):
                    # same convention as check_no_conflicting_bodies:
                    # existing_node is the merge TARGET's body (dst);
                    # plus enough context to quarantine the replica.
                    # Collected per pair (round-4 advisor finding #1):
                    # one corrupt replica must poison ITS pair, not
                    # the other 1023 in the wave
                    bad[pair_idx] = s.CausalError(
                        "This node is already in the tree and can't "
                        "be changed.",
                        {"causes": {"append-only", "edits-not-allowed"},
                         "existing_node": (nid,) + tuple(dn[j][1:]),
                         "conflicting_node": (nid, cause, value),
                         "pair": pair_idx,
                         "conflicting_side": "a" if side == 0 else "b"},
                    )
                    break
            if pair_idx in bad:
                break
    return bad


def _assemble_rows(views: Sequence[Tuple["lanecache.LaneView",
                                         "lanecache.LaneView"]],
                   cap: int, bufs: Optional[WaveBuffers] = None):
    """[B, 2*cap] v5 lane batch + segment tables from cached views.
    Pure numpy copies of cached arrays — the per-wave host cost. With
    ``bufs``, batch arrays are reused across waves and only live lanes
    (plus any shrink gap vs the previous wave) are rewritten. Each lane
    is written once, straight into its batch row (``out=``): a derived
    lane makes no per-row temporary."""
    B = len(views)
    per_row_segs = [
        [(va.segments(), va.n), (vb.segments(), vb.n)]
        for va, vb in views
    ]
    s_max = next_pow2(max(
        sum(sg["sg_len"].shape[0] for sg, _ in row) for row in per_row_segs
    ))
    bufs = bufs or WaveBuffers()
    lanes = bufs.ensure(B, cap, s_max)
    hi, lo, cci = lanes["hi"], lanes["lo"], lanes["cci"]
    vc, valid, seg = lanes["vc"], lanes["valid"], lanes["seg"]
    for r, (va, vb) in enumerate(views):
        # segment tables: the shared layout helper writes straight into
        # this row's (reused) buffer views
        row_out = {k: lanes[k][r] for k in SEG_LANE_KEYS}
        _t, bases = concat_seg_tables(per_row_segs[r], cap,
                                      s_max, out=row_out)
        for t, v in enumerate((va, vb)):
            v.arena.sync_ranks()
            a, n = v.arena, v.n
            off = t * cap
            sl = slice(off, off + n)
            hi[r, sl] = a.ts[:n]
            # lo = site << tx_bits | tx (PackSpec.pack_lo), in int32
            lo_row = lo[r, sl]
            np.left_shift(a.site[:n], a.spec.tx_bits, out=lo_row,
                          dtype=np.int32)
            np.bitwise_or(lo_row, a.tx[:n], out=lo_row, dtype=np.int32)
            ci = a.cause_idx[:n]
            cci_row = cci[r, sl]
            np.add(ci, off, out=cci_row, dtype=np.int32)
            np.copyto(cci_row, -1, where=ci < 0)
            vc[r, sl] = a.vclass[:n]
            valid[r, sl] = True
            segs = per_row_segs[r][t][0]
            np.add(segs["run_of_lane"][:n], bases[t], out=seg[r, sl],
                   dtype=np.int32)
            prev = int(bufs.prev_n[r, t])
            if prev > n:  # re-pad the shrink gap
                gap = slice(off + n, off + prev)
                for key, pad in _PAD.items():
                    lanes[key][r, gap] = pad
            bufs.prev_n[r, t] = n
    return lanes


class WaveResult:
    """One wave's converged device state plus lazy host materialization.

    - ``digest``: [B] uint32 per-pair weave digests (equal digests =>
      identical converged linearizations; see mesh.replica_digest) —
      ONLY where ``digest_valid`` is True. digest_valid is False for
      TWO distinct categories a digest-only consumer must check
      separately: ``fallback`` rows (host path ran; compare their
      ``merged`` trees instead) and ``poisoned`` rows (a corrupt
      replica was caught — see the ``poisoned`` property for the
      sources; ``merged(i)`` raises that pair's CausalError — these
      rows have NO valid result);
    - ``rank``/``visible``: [B, 2*cap] per-concat-lane outputs of the
      v5 kernel (rank == 2*cap for dropped/duplicate/padding lanes);
      when every pair rode the device these are the fetched arrays
      themselves, which may be read-only: copy before writing;
    - ``merged(i)``: the converged CausalList of pair i as a host
      handle — identical to ``pairs[i][0].merge(pairs[i][1])``,
      including the append-only body validation (conflicting duplicate
      ids raise CausalError exactly like a merge would);
    - ``fallback``: indices of pairs that ran the host path instead
      (outside the device domain or kernel overflow).
    """

    def __init__(self, pairs, views, cap, rank, visible, digest,
                 fallback_results, kernel, digest_valid=None,
                 poisoned=None):
        self._pairs = pairs
        self._views = views
        self.capacity = cap
        self.rank = rank
        self.visible = visible
        self.digest = digest
        self.digest_valid = (
            digest_valid if digest_valid is not None
            else np.zeros(len(pairs), bool)
        )
        self._fallback = fallback_results  # {index: merged_handle}
        self._poisoned = poisoned or {}    # {index: CausalError}
        self.kernel = kernel

    @property
    def fallback(self):
        return sorted(self._fallback)

    @property
    def poisoned(self):
        """Pairs quarantined with their own CausalError — the rest of
        the wave is valid; ``merged(i)`` raises the pair's error
        (round-4 advisor finding #1). Three sources: the sampled body
        spot-check on device rows (probabilistic — CAUSE_TPU_BODY_SAMPLE
        tunes/disables it), and the merge-time validation of host
        fallback and overflow rows (deterministic — those pairs run
        ``a.merge(b)`` eagerly, so a corrupt replica there is caught
        even with sampling off)."""
        return sorted(self._poisoned)

    def __len__(self):
        return len(self._pairs)

    def merged(self, i: int):
        """Materialize pair ``i``'s converged tree as a host handle."""
        if i in self._poisoned:
            raise self._poisoned[i]
        if i in self._fallback:
            return self._fallback[i]
        a, b = self._pairs[i]
        va, vb = self._views[i]
        cap = self.capacity
        rank_row = self.rank[i]
        keep = np.flatnonzero(rank_row < 2 * cap)
        order = keep[np.argsort(rank_row[keep], kind="stable")]
        an, bn = va.arena.nodes, vb.arena.nodes

        def node_at(lane):
            return an[lane] if lane < cap else bn[lane - cap]

        weave = [node_at(int(j)) for j in order]
        union = lanecache.union_views(va, vb)
        nodes = dict(a.ct.nodes)
        # the same append-only validation a.merge(b) runs: a duplicate
        # id with a different body must raise, never yield a
        # weave/nodes-inconsistent tree
        s.check_no_conflicting_bodies(nodes, b.ct.nodes)
        nodes.update(b.ct.nodes)
        yarns = {}
        if union is not None:
            for nd in union.arena.nodes[: union.n]:
                yarns.setdefault(nd[0][1], []).append(nd)
        else:  # pragma: no cover - compatible views built by merge_wave
            for nid in sorted(nodes):
                yarns.setdefault(nid[1], []).append(
                    (nid, nodes[nid][0], nodes[nid][1])
                )
        lamport = max(a.ct.lamport_ts, b.ct.lamport_ts,
                      max(nid[0] for nid in nodes))
        ct = a.ct.evolve(
            nodes=nodes, yarns=yarns, weave=weave, lamport_ts=lamport,
            lanes=union,
        )
        return type(a)(ct)


def merge_wave(pairs: Sequence[Tuple[object, object]],
               mesh=None, ctx: Optional[WaveBuffers] = None) -> WaveResult:
    """Merge every (a, b) replica pair in one batched device dispatch.

    All pairs must be list-shaped handles; each pair shares a uuid/type
    (the usual merge guards). With ``mesh``, the replica axis shards
    over it (sharded_merge_weave_v5) — the batch must divide the mesh
    size. Body validation between duplicate ids follows the device
    contract (jaxw5 module caveat): run ``shared.union_nodes`` or the
    per-pair ``merge`` path when untrusted replicas are involved.

    Without ``ctx`` the wave assembles its lanes into staging shared by
    every such call in the process (one ``WaveBuffers``, held by one
    wave at a time): host memory of about 705 MB at 1024 pairs x 16,384
    lanes per tree stays allocated after the first call, and a call
    that finds the staging in use by another thread assembles into
    buffers of its own. Pass ``ctx`` to keep a set of your own. The
    result's ``rank``/``visible``/``digest`` may be read-only arrays.
    """
    pairs = list(pairs)
    if not pairs:
        raise s.CausalError("Nothing to merge.", {"causes": {"empty-fleet"}})
    with obs.span("wave.merge", pairs=len(pairs),
                  sharded=mesh is not None):
        return _merge_wave(pairs, mesh, ctx)


def _merge_wave(pairs, mesh, ctx) -> WaveResult:
    if obs.enabled():
        # open the wave cost window: device program invocations below
        # attribute to it, and ONE wave.cost event joins them to the
        # wave's divergence evidence on every exit path
        from ..obs import costmodel as _cm

        _cm.wave_begin("wave")
        # wedge triage heartbeat (PR 10): lands BEFORE the device
        # dispatch, so a live monitor distinguishes "a wave started
        # and never produced its wave.digest" (wedged dispatch) from
        # "nobody is waving" (idle) — the obs watch absence rules
        # read exactly this pairing
        obs.event("run.heartbeat", stage="wave",
                  uuid=str(pairs[0][0].ct.uuid), pairs=len(pairs))
    with obs.span("wave.views", pairs=len(pairs)) as sp:
        views, live, fallback, poisoned = _pair_views(pairs)
        sp.set(fallback=len(fallback))
    if not live:
        B = len(pairs)
        obs.counter("wave.pairs").inc(B)
        obs.counter("wave.fallback").inc(len(fallback))
        obs.counter("wave.poisoned").inc(len(poisoned))
        if obs.enabled():
            # the wave still happened; every pair ages (no device
            # digest converged it against the fleet's modal value)
            sem = _observe_semantics(pairs, np.zeros(B, np.uint32),
                                     np.zeros(B, bool), "wave")
            # a degenerate wave (all pairs host-merged/poisoned) ran
            # zero device programs: its wave.cost records that — the
            # "dispatches >= 1" invariant holds for non-degenerate
            # waves only
            from ..obs import costmodel as _cm

            _cm.wave_cost(uuid=str(pairs[0][0].ct.uuid), pairs=B,
                          lanes=0, full_bag=len(fallback),
                          poisoned=len(poisoned), semantic=sem,
                          path="full")
        return WaveResult(pairs, views, 0,
                          np.zeros((B, 0), np.int32),
                          np.zeros((B, 0), bool),
                          np.zeros(B, np.uint32), fallback, "host",
                          poisoned=poisoned)

    cap = next_pow2(max(
        max(va.n, vb.n) for i in live for va, vb in [views[i]]
    ))
    live_views = [views[i] for i in live]
    if mesh is not None and len(live_views) % mesh.size:
        # fallbacks shrank the batch below mesh divisibility: pad with
        # copies of the first live row and drop their outputs below
        pad_rows = (-len(live_views)) % mesh.size
        live_views = live_views + [live_views[0]] * pad_rows
    uuid = str(pairs[0][0].ct.uuid)
    with _staging(ctx) as bufs:
        with obs.span("wave.assemble", rows=len(live_views),
                      cap=int(cap)):
            lanes = _assemble_rows(live_views, cap, bufs=bufs)
        rank, visible, digest, overflow, info = _dispatch_wave(
            lanes, mesh, uuid)
    pipeline = info["kernel"]

    with obs.span("wave.scatter") as sp:
        B = len(pairs)
        copied = 0
        if len(live) == B and not overflow[:B].any():
            # every pair rode the device: the fetched lanes are the
            # result, [B, 2*cap] in pair order already (mesh padding
            # rows sliced off). They may be read-only.
            full_rank, full_vis, full_dig = rank[:B], visible[:B], digest[:B]
            dig_valid = np.ones(B, bool)
        else:
            full_rank = np.full((B, 2 * cap), 2 * cap, np.int32)
            full_vis = np.zeros((B, 2 * cap), bool)
            full_dig = np.zeros(B, np.uint32)
            dig_valid = np.zeros(B, bool)
            for j, i in enumerate(live):
                if bool(overflow[j]):
                    a, b = pairs[i]
                    if obs.enabled():
                        # the ladder's last rung: still overflowing at
                        # the doubled budget, this pair runs the host
                        # merge
                        _recovery.step("wave", "double_budget", "host",
                                       "token-overflow",
                                       uuid=str(a.ct.uuid), pair=i)
                    try:
                        # budget blown: host path, correct
                        fallback[i] = a.merge(b)
                    except s.CausalError as err:  # corrupt AND overflowed
                        err.info["pair"] = i
                        poisoned[i] = err
                    views[i] = None
                    continue
                full_rank[i] = rank[j]
                full_vis[i] = visible[j]
                full_dig[i] = digest[j]
                dig_valid[i] = True
                copied += 1
        sp.set(copied=copied)
        result = WaveResult(pairs, views, cap, full_rank, full_vis,
                            full_dig, fallback, pipeline, dig_valid,
                            poisoned=poisoned)
    obs.counter("wave.pairs").inc(B)
    obs.counter("wave.fallback").inc(len(fallback))
    obs.counter("wave.poisoned").inc(len(poisoned))
    if obs.enabled():
        # semantic layer: digest agreement, staleness aging, and (on
        # disagreement) one divergence event with site provenance
        sem = _observe_semantics(pairs, full_dig, dig_valid, "wave")
        # devprof wave-boundary sample: live device arrays + backend
        # memory after the dispatch settle, so per-wave residency
        # renders as a curve next to the dispatch spans
        from ..obs import devprof

        devprof.sample_device_memory("wave")
        # the cost-vs-divergence join: ONE wave.cost event carrying
        # this wave's dispatch count and program identities next to
        # its token work size (the O(delta) axis), its lane width
        # (the O(doc) axis) and the semantic digest summary
        from ..obs import costmodel as _cm

        # lanes/tokens are FLEET totals (lanes: the O(doc) transfer/
        # scan width; tokens: worst-row estimate × rows — the kernel
        # pads every row to the budget), same units as delta_ops
        _cm.wave_cost(uuid=uuid, pairs=B,
                      lanes=2 * int(cap) * B,
                      tokens=info["u_need"] * len(live_views),
                      token_budget=info["u_max"] * len(live_views),
                      full_bag=len(fallback), poisoned=len(poisoned),
                      overflow_retries=info["retried"], semantic=sem,
                      path="full")
    return result


def _dispatch_wave(lanes, mesh, uuid: str):
    """The device half of a wave over an assembled [B, 2*cap] batch:
    upload, the v5 kernel plus digest (sharded over ``mesh``
    when given), fetch, and one doubled-budget retry of the rows whose
    sampled token budget overflowed. Returns ``(rank, visible, digest,
    overflow, info)`` as host arrays in batch-row order, ``info``
    holding ``kernel``, ``u_need``, ``u_max``, ``retried`` and ``k``
    (the run width the kernel ran at, on the ``wave.kernel`` span as
    well)."""
    from ..benchgen import LANE_KEYS5, v5_token_budget
    from ..weaver.jaxw5 import batched_merge_weave_v5 as fn

    n_rows = int(lanes["hi"].shape[0])

    def dispatch_v5(sub_lanes, u):
        """Batched v5 dispatch + device digest, one scalar-free host
        fetch: upload, kernel and fetch each under a span that ends
        when its work on the device does."""
        kw = {"u_max": u, "k_max": u, "with_k": True}
        dev = _upload(sub_lanes, LANE_KEYS5)
        args = [dev[k] for k in LANE_KEYS5]
        with obs.span("wave.kernel", u_max=int(u),
                      rows=int(sub_lanes["hi"].shape[0])) as ksp:
            r, v, _c, ov, ran_at = _recovery.run_dispatch(
                "wave", lambda: fn(*args, **kw))
            d = _digest_fn()(dev["hi"], dev["lo"], r, v)
            jax.block_until_ready((r, v, d, ov))
            # the width is a scalar of the finished program, read with
            # no wait
            width = count_run_width(int(ran_at), int(u))
            ksp.set(k=width)
        _LAST_KERNEL.update(fn=fn, kw=kw, args=[
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args])
        if obs.enabled():
            # dispatch accounting: one kernel invocation plus one
            # digest invocation per dispatch_v5 call, attributed to
            # the open wave window
            from ..obs import costmodel as _cm

            _cm.record_dispatch(f"wave:v5:u{int(u)}", site="wave")
            _cm.record_dispatch("wave:digest", site="wave")
        return (*_fetch(r, v, d, ov), width)

    # pow2-quantized budget: every distinct u_max is a distinct XLA
    # program, so exact budgets would recompile on every wave whose
    # divergence shifted slightly
    u_need = int(v5_token_budget(lanes))
    u_max = next_pow2(u_need)
    if obs.enabled():
        # token-budget headroom: the pow2 slack this fleet has before
        # a divergence spike overflows the kernel and forces
        # retries/host fallbacks
        from ..obs import semantic as _sem

        _sem.token_headroom(int(u_max) - u_need, "wave")
    with obs.span("wave.dispatch", kernel="v5",
                  rows=n_rows, u_max=int(u_max),
                  sharded=mesh is not None):
        if mesh is not None:
            from .mesh import sharded_merge_weave_v5

            jl = _upload(lanes, tuple(lanes))
            with obs.span("wave.kernel", u_max=int(u_max), rows=n_rows,
                          k=int(u_max)):
                rank, visible, overflow, digest, _tv, _nc, _n_ov = (
                    sharded_merge_weave_v5(mesh, jl, u_max=u_max,
                                           k_max=u_max))
                jax.block_until_ready((rank, visible, overflow, digest))
            # the sharded step ranks its runs at the cap
            width = count_run_width(int(u_max), int(u_max))
            rank, visible, digest, overflow = _fetch(
                rank, visible, digest, overflow)
            if obs.enabled():
                # the sharded step computes kernel + digest in ONE
                # compiled program
                from ..obs import costmodel as _cm

                _cm.record_dispatch(f"wave:sharded:v5:u{int(u_max)}",
                                    site="wave")
        else:
            rank, visible, digest, overflow, width = dispatch_v5(lanes,
                                                                 u_max)
    n_retried = 0
    if overflow.any():
        # the token budget samples rows; a spiky unsampled row can
        # overflow. Retry just those rows (unsharded — a handful of
        # rows doesn't need the mesh) with a doubled budget before
        # resorting to host merges. np.array: jax host buffers can be
        # read-only.
        rows = np.flatnonzero(overflow)
        n_retried = len(rows)
        obs.counter("wave.overflow_retry").inc(len(rows))
        obs.event("wave.overflow_retry", rows=len(rows),
                  u_max=int(u_max))
        if obs.enabled():
            _recovery.step("wave", "full", "double_budget",
                           "token-overflow",
                           uuid=uuid, rows=n_retried)
        sub = {k: lanes[k][rows] for k in LANE_KEYS5}
        with obs.span("wave.dispatch.retry", rows=len(rows),
                      u_max=int(2 * u_max)):
            r2, v2, d2, ov2, _k2 = dispatch_v5(sub, 2 * u_max)
        rank = np.array(rank)
        visible = np.array(visible)
        digest = np.array(digest)
        overflow = np.array(overflow)
        rank[rows] = r2
        visible[rows] = v2
        digest[rows] = d2
        overflow[rows] = ov2

    return rank, visible, digest, overflow, {
        "kernel": "v5", "u_need": u_need, "u_max": int(u_max),
        "retried": n_retried, "k": width,
    }


def _pair_views(pairs):
    """The merge guards, each pair's cached lane views (rebuilt where
    the rank generations differ), the host merges of pairs off the
    device domain, and the sampled body spot-check. Returns ``(views,
    live, fallback, poisoned)``: ``views[i]`` is None for a pair that
    does not ride the device wave, ``live`` the indices that do."""
    for a, b in pairs:
        s.check_mergeable(a.ct, b.ct)

    from .. import sync as _sync

    quarantine_live = _sync.any_quarantined()
    views: List[Optional[Tuple[object, object]]] = []
    fallback = {}
    poisoned: dict = {}
    for i, (a, b) in enumerate(pairs):
        if quarantine_live and (
                _sync.is_quarantined(a.ct.site_id)
                or _sync.is_quarantined(b.ct.site_id)):
            # a quarantined replica is OUT of the device wave: its
            # pair runs the host merge, whose full append-only body
            # validation is exactly what a repeat payload offender
            # has to pass — a corrupt one lands in poisoned below,
            # never in the digest-only device path
            obs.counter("wave.quarantined").inc()
            if obs.enabled():
                _recovery.step("wave", "full", "host", "quarantined",
                               uuid=str(a.ct.uuid), pair=i)
            try:
                fallback[i] = a.merge(b)
            except s.CausalError as err:
                err.info["pair"] = i
                poisoned[i] = err
            views.append(None)
            continue
        # view_for returns None for map trees (they need the mapw
        # forest encoding) and off-domain ids: both take the correct
        # per-pair host merge below
        va = lanecache.view_for(a.ct)
        vb = lanecache.view_for(b.ct)
        if va is not None and vb is not None and not lanecache.compatible(
                (va, vb)):
            # stale rank generation on one side: rebuild both fresh
            va = lanecache.build_view(a.ct.nodes, a.ct.uuid)
            vb = lanecache.build_view(b.ct.nodes, b.ct.uuid)
        if va is None or vb is None or not lanecache.compatible((va, vb)):
            try:
                fallback[i] = a.merge(b)
            except s.CausalError as err:
                # the per-pair quarantine contract covers the host
                # fallback path too: a corrupt replica that is ALSO
                # off the device domain must poison its own pair, not
                # abort the other pairs' wave (mergeability of every
                # pair was already checked wave-wide above — what can
                # raise here is the merge-time body validation)
                err.info["pair"] = i
                poisoned[i] = err
            views.append(None)
        else:
            views.append((va, vb))

    live = [i for i, v in enumerate(views) if v is not None]
    # device paths never see host value bytes; the sampled host-side
    # check quarantines corrupt PAIRS (merged(i) raises for them
    # alone) instead of failing the healthy rest of the wave
    if live:
        bad = _sampled_body_spotcheck([views[i] for i in live])
        for local_idx, err in bad.items():
            i = live[local_idx]
            # the spot-check saw the COMPACTED live list; remap its
            # pair index to the wave's, or a caller quarantining by
            # info["pair"] would hit a healthy pair whenever a
            # fallback pair precedes the corrupt one
            err.info["pair"] = i
            poisoned[i] = err
            views[i] = None
        live = [i for i, v in enumerate(views) if v is not None]
    return views, live, fallback, poisoned


def count_run_width(k: int, k_max: int) -> int:
    """Count one v5 dispatch by the run width it ran at: ``v5.runs.
    narrow`` under the run cap ``k_max``, else ``v5.runs.full``.
    Returns ``k``, for the dispatch's span."""
    obs.counter("v5.runs.narrow" if k < k_max else "v5.runs.full").inc()
    return k


def _upload(lanes, keys):
    """Host to device of the named lanes, ended by blocking on them:
    the ``wave.upload`` span times the transfer itself."""
    with obs.span("wave.upload",
                  bytes=sum(int(lanes[k].nbytes) for k in keys)):
        dev = dict(zip(keys, jax.device_put([lanes[k] for k in keys])))
        jax.block_until_ready(dev)
    return dev


def _fetch(*arrays):
    """Device to host copies of a wave's outputs (``wave.fetch``)."""
    with obs.span("wave.fetch") as sp:
        out = tuple(np.asarray(x) for x in arrays)
        sp.set(bytes=sum(int(x.nbytes) for x in out))
    return out


# the program and argument shapes of the last unsharded kernel dispatch
_LAST_KERNEL: dict = {}


def last_kernel_hlo() -> str:
    """The optimized HLO text of the v5 program the last unsharded
    merge wave dispatched ("" before any). Its instructions' ``op_name``
    metadata carry the kernel's phase scopes (``v5.A`` ... ``v5.F``),
    which name a device trace's ops by phase. A compile-cache lookup:
    call it outside any timed window."""
    if not _LAST_KERNEL:
        return ""
    return _LAST_KERNEL["fn"].lower(
        *_LAST_KERNEL["args"], **_LAST_KERNEL["kw"]).compile().as_text()
