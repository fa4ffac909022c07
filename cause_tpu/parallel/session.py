"""Device-resident fleet sessions: merge waves without re-shipping the
fleet.

``merge_wave`` assembles and uploads the full [B, 2*cap] lane batch on
every call, paying the full host->device transfer (hundreds of MB per wave at north-star scale)
every time. A ``FleetSession`` keeps the batch ON DEVICE between waves
and ships only what changed:

- per edited tree, the appended delta lanes (the lane cache knows the
  previous wave's length; appends are the steady state) — a
  [B, 2, d_max] upload of a few KB;
- the per-row segment tables (always small: tens of entries per row),
  re-sent wholesale each wave;
- a jitted scatter program splices the deltas into the resident lanes
  (per-row dynamic offsets via masked index scatter — static shapes,
  no recompiles while d_max stays inside the session's budget).

A tree whose cache dropped (mid-order insert, weft) or whose delta
exceeds the budget falls back to a full re-upload of the whole batch
that wave — correct, just slower. ``wave()`` then converges the fleet
and fetches ONE small digest array; ranks and visibility stay
device-resident for on-demand materialization.

**Delta-native waves (PR 7).** Residency alone still paid a full
-document-width KERNEL per wave. After any full-width wave the session
freezes a per-pair *delta frontier* — the shared converged lane
prefix, its weave-final node (the anchor every divergent subtree
attaches under), and the prefix's exact uint32 digest contribution —
and steady-state waves dispatch ``weaver.jaxwd.batched_delta_weave``
over just the divergent WINDOW (anchor + suffix lanes), splicing
ranks/visibility back into the resident weave and returning digests
bit-identical to the full wave's. Device work per wave is then
O(divergence); first contact, domain violations
(``wave.delta_domain_ok``), window-budget overflow, and every
update-level fallback run the full kernel and re-establish.

This is the TPU-native sync-fleet loop: edit replicas on host, ship
deltas, weave ONLY the deltas on device, read digests.

**Fleet convergence (PR 8).** Waves converge PAIRS; bringing the whole
resident fleet to one state is ``converge()``, which routes through
the merge reduction tree (``parallel.tree``): ceil(log2(n)) batched
device rounds instead of the n-1 sequential pairwise waves of the
flat fold (retained behind ``converge(tree=False)`` as the A/B
control).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import chaos as _chaos
from .. import obs
from ..collections import shared as s
from ..weaver import lanecache
from ..weaver.arrays import next_pow2
from ..weaver.segments import SEG_LANE_KEYS, concat_seg_tables
from . import recovery as _recovery
from .wave import (WaveBuffers, _PAD, _assemble_rows, _digest_fn,
                   _observe_semantics, _sampled_body_spotcheck,
                   assemble_delta_window, count_run_width,
                   delta_domain_ok)

__all__ = ["FleetSession"]

_LANE_COLS = ("hi", "lo", "cci", "vc", "valid", "seg")


@partial(jax.jit, donate_argnums=(0,))
def _apply_deltas(dev: Dict[str, jnp.ndarray], deltas: Dict[str, jnp.ndarray],
                  starts, counts, b_shift, old_nb):
    """Splice per-tree delta lanes into the resident batch.

    ``deltas[col]`` is [B, 2, d_max]; ``starts``/``counts`` [B, 2] are
    each tree's previous length and delta size (concat-lane start =
    tree_offset + start). ``b_shift`` [B] re-bases tree B's OLD seg
    ordinals when tree A gained segments; ``old_nb`` [B] bounds that
    shift to B's pre-delta lanes. Buffer-donated: the resident arrays
    update in place on device.
    """
    B, N = dev["hi"].shape
    cap = N // 2
    d_max = deltas["hi"].shape[2]
    off = jnp.arange(d_max, dtype=jnp.int32)

    lane_idx = jnp.arange(N, dtype=jnp.int32)
    shift_mask = (
        (lane_idx[None, :] >= cap)
        & (lane_idx[None, :] < cap + old_nb[:, None])
        & (dev["seg"] >= 0)
    )
    out = dict(dev)
    out["seg"] = jnp.where(shift_mask, dev["seg"] + b_shift[:, None],
                           dev["seg"])

    def one_col(col, arr):
        def row(row_arr, st, ct, d):
            # masked index scatter: lanes beyond the count drop
            for t in range(2):
                idx = t * cap + st[t] + off
                idx = jnp.where(off < ct[t], idx, N)
                row_arr = row_arr.at[idx].set(d[t], mode="drop")
            return row_arr

        return jax.vmap(row)(arr, starts, counts, deltas[col])

    for col in _LANE_COLS:
        out[col] = one_col(col, out[col])
    return out


class FleetSession:
    """A device-resident batch of replica pairs converged wave after
    wave. See the module docstring; usage::

        sess = FleetSession(pairs)          # full upload once
        d0 = sess.wave()                    # digests, device-resident
        pairs = edit(pairs)                 # host-side appends
        sess.update(pairs)                  # ship deltas only
        d1 = sess.wave()
    """

    def __init__(self, pairs: Sequence[Tuple[object, object]],
                 d_max: int = 256, u_headroom: float = 2.0,
                 delta: bool = True):
        pairs = list(pairs)
        if not pairs:
            raise s.CausalError("Nothing to merge.",
                                {"causes": {"empty-fleet"}})
        for a, b in pairs:
            s.check_mergeable(a.ct, b.ct)
        # map trees (rejected by view_for — they need the mapw forest
        # encoding) and off-domain ids surface as the outside-domain
        # raise from the first _full_upload
        self.d_max = int(d_max)
        self._bufs = WaveBuffers()
        self._views: List[Tuple[object, object]] = []
        self._uploaded_n = None     # [B, 2] lane counts on device
        self._uploaded_k = None     # [B] tree-A segment counts on device
        self.capacity = 0
        self.u_max = 0
        self._u_headroom = float(u_headroom)
        self.dev = None
        # wave cost-model bookkeeping: what the LAST update shipped
        # (delta lanes vs a full O(doc) re-upload) — the next wave()'s
        # wave.cost event carries it as divergence evidence
        self._last_delta_lanes = 0
        self._last_update_full = False
        # delta-native wave state, established after each full wave
        # (see _establish_delta): None = next wave runs full width.
        # ``delta=False`` pins the session to full-width waves (the
        # A/B control and the escape hatch). Establishment costs an
        # O(doc) rank fetch, so repeated failures (a fleet whose edits
        # keep violating the delta domain) back off permanently after
        # _DELTA_FAILURE_LIMIT consecutive misses.
        self._delta_enabled = bool(delta)
        self._delta = None
        self._delta_failures = 0
        # the last wave's fetched digests: checkpoint() serializes
        # them and restore() gates on recomputing them bit-identically
        self._last_digest = None
        self._full_upload(pairs)

    _DELTA_FAILURE_LIMIT = 3

    # Batched-serving state (see window_pack/complete_window): the
    # last bucket dispatch's unspliced window output, the deferred
    # device-lane mode flag, and whether the resident lanes are behind
    # the host views. Class-level defaults so restore()d and
    # pre-existing pickled sessions get the unbatched behavior.
    _pending_window = None
    _dev_stale = False
    defer_device = False

    # ------------------------------------------------------------------
    def _collect_views(self, pairs):
        views = []
        for a, b in pairs:
            va = lanecache.view_for(a.ct)
            vb = lanecache.view_for(b.ct)
            if va is None or vb is None or not lanecache.compatible(
                    (va, vb)):
                return None
            views.append((va, vb))
        return views

    def _full_upload(self, pairs):
        with obs.span("session.full_upload", pairs=len(pairs)):
            obs.counter("session.full_upload").inc()
            return self._full_upload_inner(pairs)

    def _full_upload_inner(self, pairs):
        views = self._collect_views(pairs)
        if views is None:
            raise s.CausalError(
                "fleet outside the device domain (PackSpec overflow?)",
                {"causes": {"outside-domain"}},
            )
        cap = next_pow2(max(max(va.n, vb.n) for va, vb in views))
        if cap < self.capacity:
            cap = self.capacity  # never shrink: resident shapes are fixed
        # device-resident rounds never see host value bytes: sampled
        # append-only body check on every (re-)upload (see wave.py)
        _bad = _sampled_body_spotcheck(views)
        if _bad:
            raise next(iter(_bad.values()))
        lanes = _assemble_rows(views, cap, bufs=self._bufs)
        from ..benchgen import v5_token_budget

        u = v5_token_budget(lanes)
        # pow2-quantized (stable XLA program shapes across sessions
        # and re-uploads)
        self.u_max = max(self.u_max, next_pow2(
            int(u * self._u_headroom) + self.d_max
        ))
        if obs.enabled():
            # resident-budget headroom: how far the CURRENT fleet sits
            # below the session's compiled token ceiling
            from ..obs import semantic as _sem

            _sem.token_headroom(int(self.u_max) - int(u), "session")
        self.capacity = cap
        self.dev = {k: jnp.asarray(v) for k, v in lanes.items()}
        self._views = views
        self._uploaded_n = np.array(
            [[va.n, vb.n] for va, vb in views], np.int32
        )
        self._uploaded_k = np.array(
            [int(va.segments()["sg_len"].shape[0]) for va, _ in views],
            np.int32,
        )
        # what the delta path must verify survived unchanged: the
        # per-lane segment ordinals of every uploaded prefix (an
        # interior stab restructures them) and the interner rank
        # generation (a reassignment repacks every lo)
        self._uploaded_rol = [
            (va.segments()["run_of_lane"], vb.segments()["run_of_lane"])
            for va, vb in views
        ]
        self._gen = views[0][0].interner.generation
        self.pairs = list(pairs)
        # a full upload is the session's O(doc) degradation: the next
        # wave.cost records it as a full-bag wave with zero delta ops,
        # and the delta-wave capability drops until the next full wave
        # re-establishes the resident frontier
        self._last_delta_lanes = 0
        self._last_update_full = True
        self._delta = None
        self._dev_stale = False
        self._pending_window = None
        if obs.enabled():
            from ..obs import devprof

            devprof.sample_device_memory("session.upload")

    # ------------------------------------------------------------------
    def _degrade(self, pairs, reason: str):
        """The update-level ``delta -> full`` recovery-ladder rung:
        every full re-upload taken from the delta path is a declared,
        evidenced transition (``recovery.step``), not a silent
        bounce."""
        if obs.enabled():
            _recovery.step(
                "session", "delta", "full", reason,
                uuid=str(pairs[0][0].ct.uuid) if pairs else "")
        return self._full_upload(pairs)

    def update(self, pairs: Sequence[Tuple[object, object]]):
        """Ship this wave's edits. Appends ride the delta path; anything
        else (dropped caches, oversized deltas, capacity growth) falls
        back to a full re-upload."""
        pairs = list(pairs)
        # an update invalidates the checkpointable state until the
        # next wave: the resident pairs (and possibly capacity) move
        # ahead of the last wave's rank/visibility/digest arrays, and
        # a checkpoint mixing the two could never pass restore's
        # digest gate
        self._last_digest = None
        with obs.span("session.update", pairs=len(pairs)):
            return self._update_inner(pairs)

    def _update_inner(self, pairs):
        if len(pairs) != len(self._views):
            return self._degrade(pairs, "pair-count-change")
        views = self._collect_views(pairs)
        if views is None:
            raise s.CausalError(
                "fleet outside the device domain",
                {"causes": {"outside-domain"}},
            )
        if views[0][0].interner.generation != self._gen:
            # rank reassignment since upload: resident lo/sg packs are
            # old-generation, deltas would be new-generation
            return self._degrade(pairs, "rank-reassignment")
        B = len(pairs)
        cap = self.capacity
        d_max = self.d_max
        starts = np.zeros((B, 2), np.int32)
        counts = np.zeros((B, 2), np.int32)
        tables = {k: [] for k in SEG_LANE_KEYS}
        b_shift = np.zeros(B, np.int32)
        old_nb = np.zeros(B, np.int32)
        s_needed = 0
        for r, ((va, vb), (ova, ovb)) in enumerate(
                zip(views, self._views)):
            for t, (v, ov) in enumerate(((va, ova), (vb, ovb))):
                n0 = int(self._uploaded_n[r, t])
                if (v.arena is not ov.arena and ov.arena.nodes[:n0]
                        != v.arena.nodes[:n0]):
                    return self._degrade(pairs, "rewritten-history")
                if v.n < n0 or v.n - n0 > d_max or v.n > cap:
                    return self._degrade(pairs, "delta-overflow")
                # an append that stabbed an old interior lane
                # restructures the uploaded prefix's segment ordinals —
                # the resident seg lane would be silently stale
                if not np.array_equal(
                        v.segments()["run_of_lane"][:n0],
                        self._uploaded_rol[r][t][:n0]):
                    return self._degrade(pairs, "interior-stab")
            segs_a, segs_b = va.segments(), vb.segments()
            ka = int(segs_a["sg_len"].shape[0])
            kb = int(segs_b["sg_len"].shape[0])
            s_needed = max(s_needed, ka + kb)
        s_max = self.dev["sg_len"].shape[1]
        if s_needed > s_max:
            return self._degrade(pairs, "segment-overflow")

        # delta path committed from here on. The sampled append-only
        # body check runs once per round: here on the delta path, or
        # inside _full_upload when a branch above delegated to it (the
        # corrupt lane may be resident from a previous upload, so the
        # check always covers whole trees, not just deltas).
        _bad = _sampled_body_spotcheck(views)
        if _bad:
            raise next(iter(_bad.values()))
        obs.counter("session.delta_update").inc()

        if self._delta is not None:
            # delta-WAVE domain (stricter than the lane-splice domain
            # above): every appended lane must weave strictly after the
            # frozen resident prefix — causes inside the divergent
            # window or on the anchor, no tombstone of the anchor, and
            # the window must fit the session's compiled budget. A
            # violation only drops the delta-wave capability (the next
            # wave runs full width and re-establishes); the resident
            # lane splice stays valid either way.
            dstate = self._delta
            w_cap = dstate["w_cap"]
            for r, (va, vb) in enumerate(views):
                sp = int(dstate["s"][r])
                anchor = int(dstate["anchor"][r])
                ok = True
                for t, v in enumerate((va, vb)):
                    if v.n - sp > w_cap - 1:
                        ok = False  # window outgrew the budget
                        break
                    if not delta_domain_ok(
                            v, sp, anchor,
                            start=int(self._uploaded_n[r, t])):
                        ok = False
                        break
                if not ok:
                    obs.counter("session.delta_wave_invalidate").inc()
                    if obs.enabled():
                        # the splice stays valid; only the delta-WAVE
                        # capability drops — the next wave runs the
                        # full rung and re-establishes
                        _recovery.step(
                            "session", "delta", "full",
                            "domain-violation",
                            uuid=str(pairs[0][0].ct.uuid), pair=r)
                    self._delta = None
                    break

        # Batched serving defers the resident lane splice: with a live
        # frontier the delta wave assembles its window from host views
        # only, so the device lanes can stay behind until the next
        # full-width wave (which re-uploads). Without a live frontier
        # the next wave is full-width and needs current lanes — stale
        # residents take the declared full-upload rung instead of a
        # splice onto lanes that no longer match the bookkeeping.
        defer = self.defer_device and self._delta is not None
        if not defer and self._dev_stale:
            return self._degrade(pairs, "stale-resident-lanes")
        deltas = None
        if not defer:
            deltas = {c: np.full((B, 2, d_max), _PAD[c],
                                 self.dev[c].dtype
                                 if c != "valid" else bool)
                      for c in _LANE_COLS}

        for r, ((va, vb), _old) in enumerate(zip(views, self._views)):
            segs_a, segs_b = va.segments(), vb.segments()
            ka = int(segs_a["sg_len"].shape[0])
            old_ka = int(self._uploaded_k[r])
            b_shift[r] = ka - old_ka
            old_nb[r] = int(self._uploaded_n[r, 1])
            for t, (v, segs) in enumerate(((va, segs_a), (vb, segs_b))):
                a = v.arena
                n0 = int(self._uploaded_n[r, t])
                d = v.n - n0
                starts[r, t] = n0
                counts[r, t] = d
                if d and not defer:
                    sl = slice(n0, v.n)
                    deltas["hi"][r, t, :d] = a.ts[sl]
                    deltas["lo"][r, t, :d] = a.spec.pack_lo(
                        a.site[sl], a.tx[sl]
                    )
                    ci = a.cause_idx[sl]
                    deltas["cci"][r, t, :d] = np.where(
                        ci >= 0, ci + t * cap, -1
                    )
                    deltas["vc"][r, t, :d] = a.vclass[sl]
                    deltas["valid"][r, t, :d] = True
                    base = 0 if t == 0 else ka
                    deltas["seg"][r, t, :d] = (
                        segs["run_of_lane"][n0:v.n] + base
                    )
                self._uploaded_n[r, t] = v.n
            self._uploaded_k[r] = ka
            self._uploaded_rol[r] = (
                segs_a["run_of_lane"], segs_b["run_of_lane"]
            )
            if not defer:
                # small per-row tables, rebuilt host-side every wave
                # via the shared layout helper
                row, _bases = concat_seg_tables(
                    [(segs_a, int(self._uploaded_n[r, 0])),
                     (segs_b, int(self._uploaded_n[r, 1]))],
                    cap, s_max,
                )
                for k in SEG_LANE_KEYS:
                    tables[k].append(row[k])

        if defer:
            # batched serving: the delta wave assembles its window
            # from host views, so the resident lanes stay behind until
            # the next full-width wave re-uploads (see _full_wave)
            self._dev_stale = True
        else:
            self.dev = _apply_deltas(
                self.dev,
                {c: jnp.asarray(deltas[c]) for c in _LANE_COLS},
                jnp.asarray(starts), jnp.asarray(counts),
                jnp.asarray(b_shift), jnp.asarray(old_nb),
            )
            if obs.enabled():
                # the resident-splice program is a device dispatch too
                # — it runs outside any wave window (update-time), so
                # it counts globally; the spliced lane total is the
                # wave's measured divergence and rides the NEXT
                # wave.cost
                from ..obs import costmodel as _cm

                _cm.record_dispatch(f"session:splice:d{self.d_max}",
                                    site="session")
            for k in SEG_LANE_KEYS:
                self.dev[k] = jnp.asarray(np.stack(tables[k]))
        self._last_delta_lanes = int(counts.sum())
        self._last_update_full = False
        self._views = views
        self.pairs = pairs

    # ------------------------------------------------------------------
    def wave(self):
        """One merge wave over the resident state. Returns the [B]
        digest array (fetched); rank/visible stay on device as
        ``self.last_rank`` / ``self.last_visible``.

        Routing: when a delta frontier is established (a full wave ran
        and every divergent lane since stays inside the delta domain),
        the wave dispatches only the divergent window and splices the
        result into the resident weave — O(divergence) device work.
        First contact, domain violations, window-budget overflow, and
        every update()-level fallback run the full-width kernel
        instead, and a full wave re-establishes the frontier."""
        if _chaos.enabled():
            # the injectable seams: a stall fault sleeps here (the
            # heartbeat-absence wedge shape), a budget-exhaust fault
            # drops the delta frontier exactly like a real window
            # -budget exhaustion would — the declared ladder handles
            # both, bit-identically
            _chaos.stall_point("session")
            if self._delta is not None \
                    and _chaos.budget_exhaust("session"):
                obs.counter("session.delta_wave_invalidate").inc()
                if obs.enabled():
                    _recovery.step(
                        "session", "delta", "full",
                        "budget-exhaustion",
                        uuid=str(self.pairs[0][0].ct.uuid))
                self._delta = None
        if self._delta is not None:
            out = self._delta_wave()
            if out is not None:
                return out
        return self._full_wave()

    def _full_wave(self):
        """The full-width wave (first contact / fallback path): v5
        kernel + digest over the whole resident batch, then (re-)
        establish the delta frontier from its ranks."""
        from ..benchgen import LANE_KEYS5
        from ..weaver.jaxw5 import batched_merge_weave_v5

        # a full wave recomputes every lane's rank, superseding any
        # unspliced window output; and it reads the resident lanes, so
        # a deferred-splice session re-uploads from the current views
        # first (the O(doc) cost the batched path deferred)
        self._pending_window = None
        if self._dev_stale:
            self._full_upload(self.pairs)
        if obs.enabled():
            from ..obs import costmodel as _cm

            _cm.wave_begin("session")
            # wedge-triage heartbeat: before the dispatch, so a live
            # monitor can pair "session wave started" with the
            # wave.digest that should follow (see parallel/wave.py)
            obs.event("run.heartbeat", stage="session.wave",
                      uuid=str(self.pairs[0][0].ct.uuid),
                      pairs=len(self.pairs))
        with obs.span("session.wave", pairs=len(self.pairs),
                      u_max=int(self.u_max)) as sp:
            r, v, _c, ov, width = _recovery.run_dispatch(
                "session",
                lambda: batched_merge_weave_v5(
                    *(self.dev[k] for k in LANE_KEYS5),
                    u_max=self.u_max, k_max=self.u_max, with_k=True,
                ))
            digest = _digest_fn()(self.dev["hi"], self.dev["lo"], r, v)
            if obs.enabled():
                from ..obs import costmodel as _cm

                _cm.record_dispatch(f"session:v5:u{int(self.u_max)}",
                                    site="session")
                _cm.record_dispatch("session:digest", site="session")
            self.last_rank = r
            self.last_visible = v
            self.last_overflow = ov
            out = np.asarray(digest)
            sp.set(k=count_run_width(int(width), int(self.u_max)))
        if obs.enabled():
            # wave-boundary devprof sample: the session's whole point
            # is device residency, so its growth must be a curve
            from ..obs import devprof

            devprof.sample_device_memory("session")
        if bool(np.asarray(ov).any()):
            rows = np.flatnonzero(np.asarray(ov)).tolist()
            if obs.enabled():
                # an overflowed wave's digests are garbage — record
                # the incident, never feed them to the monitors; the
                # cost window is dropped too (fleet.session_overflow
                # is the incident record)
                from ..obs import costmodel as _cm
                from ..obs import semantic as _sem

                _sem.session_overflow(rows)
                _cm.wave_abandon()
            raise s.CausalError(
                "wave overflowed the session's token budget; raise "
                "u_headroom or re-create the session",
                {"causes": {"token-overflow"}, "rows": rows},
            )
        if obs.enabled():
            # every session digest is device-computed (overflow raised
            # above), so the whole wave feeds the divergence monitors
            sem = _observe_semantics(self.pairs, out,
                                     np.ones(len(self.pairs), bool),
                                     "session")
            # the cost-vs-divergence join, session flavor: delta ops
            # are the lanes the LAST update actually spliced (zero
            # after a full upload — that wave paid O(doc) transfer,
            # recorded as full_bag)
            from ..obs import costmodel as _cm

            _cm.wave_cost(
                uuid=str(self.pairs[0][0].ct.uuid),
                pairs=len(self.pairs),
                lanes=2 * int(self.capacity) * len(self.pairs),
                token_budget=int(self.u_max) * len(self.pairs),
                delta_ops=self._last_delta_lanes,
                full_bag=1 if self._last_update_full else 0,
                semantic=sem,
                path="full",
            )
            self._last_delta_lanes = 0
            self._last_update_full = False
        if self._delta_enabled:
            self._establish_delta(r, v)
        self._last_digest = out
        return out

    # ----------------------------------------------- delta-native wave
    def _fail_establish(self) -> None:
        self._delta_failures += 1
        obs.counter("session.delta_establish_fail").inc()

    def _establish_delta(self, rank_dev, vis_dev) -> None:
        """Derive the delta frontier from a completed full wave: the
        shared converged lane prefix per pair, the anchor (the prefix
        weave's final node — where every divergent subtree attaches),
        the frozen prefix digest contribution, and the pow2 window
        budget. Any pair outside the domain disables the delta path
        until the next full wave (correct, just O(doc)).

        Cost discipline: the shared-prefix precheck is host-only; the
        O(doc) device rank fetch happens only after it passes, and the
        visibility fetch only after every pair's rank/domain checks
        pass. _DELTA_FAILURE_LIMIT consecutive failed establishments
        stop further attempts for this session — a fleet whose edits
        keep violating the domain must not pay the fetch per wave."""
        from ..weaver.arrays import next_pow2 as _np2
        from .mesh import mix32_np

        self._delta = None
        if self._delta_failures >= self._DELTA_FAILURE_LIMIT:
            return
        B = len(self.pairs)
        cap = self.capacity
        N = 2 * cap
        s_arr = np.zeros(B, np.int32)
        anchor_arr = np.zeros(B, np.int32)
        pdig = np.zeros(B, np.uint32)
        w_now = 0
        for r, (va, vb) in enumerate(self._views):
            sp = lanecache.shared_prefix_len(va, vb)
            if sp < 1:
                return self._fail_establish()
            s_arr[r] = sp
        rank_np = np.asarray(rank_dev)
        for r, (va, vb) in enumerate(self._views):
            sp = int(s_arr[r])
            ra = rank_np[r, :sp]
            rb = rank_np[r, cap:cap + sp]
            pr = np.minimum(ra, rb)  # the kept copy's rank per node
            # the prefix must BE the weave's prefix: its ranks are
            # exactly {0..sp-1}, once each — anything else means some
            # divergent lane wove inside it and nothing can be frozen
            if not bool((pr < sp).all()):
                return self._fail_establish()
            if int(pr.max()) != sp - 1 or \
                    int(np.bincount(pr, minlength=sp).max()) != 1:
                return self._fail_establish()
            anchor = int(np.argmax(pr))
            arena = va.arena
            if int(arena.vclass[anchor]) > 0:
                # a special anchor breaks the host-jump locality
                return self._fail_establish()
            if not (delta_domain_ok(va, sp, anchor)
                    and delta_domain_ok(vb, sp, anchor)):
                return self._fail_establish()
            anchor_arr[r] = anchor
            w_now = max(w_now, va.n - sp, vb.n - sp)
        vis_np = np.asarray(vis_dev)
        for r, (va, _vb) in enumerate(self._views):
            sp = int(s_arr[r])
            arena = va.arena
            ra = rank_np[r, :sp]
            pr = np.minimum(ra, rank_np[r, cap:cap + sp])
            keep_a = ra < N
            vis = np.where(keep_a, vis_np[r, :sp],
                           vis_np[r, cap:cap + sp])
            hi = arena.ts[:sp].astype(np.int32)
            lo = arena.spec.pack_lo(arena.site[:sp], arena.tx[:sp])
            pdig[r] = np.uint32(
                mix32_np(hi, lo, pr.astype(np.int32), vis)
                .sum(dtype=np.uint64) & np.uint64(0xFFFFFFFF))
        self._delta_failures = 0
        self._delta = {
            "s": s_arr,
            "anchor": anchor_arr,
            "prefix_digest": pdig,
            # window budget: room for the current divergence plus one
            # round's worth of appends, pow2-quantized so the window
            # program's shape survives steady-state growth; outgrowing
            # it falls back to a full wave, which re-establishes with
            # the next bucket (the "budget overflow" rebuild policy)
            "w_cap": int(_np2(max(8, w_now + 1 + self.d_max))),
        }

    def _delta_wave(self):
        """The steady-state wave: weave the divergent window only,
        splice ranks/visibility into the resident weave, and return
        digests that are bit-identical to the full wave's. Returns
        None when the dispatch overflowed (never, under the
        ``u_max = N_w`` budget rule — a safety net, not a path): the
        caller then runs the full-width wave."""
        from ..benchgen import LANE_KEYS5
        from ..weaver import jaxwd

        dstate = self._delta
        wcap = dstate["w_cap"]
        n_w = 2 * wcap
        B = len(self.pairs)
        # this wave's window covers a superset of any pending one's
        # lanes (same frontier, counts grow monotonically), so its
        # splice below supersedes the unflushed output bit-for-bit
        self._pending_window = None
        if obs.enabled():
            from ..obs import costmodel as _cm

            _cm.wave_begin("session")
            obs.event("run.heartbeat", stage="session.delta_wave",
                      uuid=str(self.pairs[0][0].ct.uuid), pairs=B)
        with obs.span("session.delta_wave", pairs=B, w_cap=int(wcap)):
            with obs.span("session.delta_assemble"):
                lanes, starts, counts = assemble_delta_window(
                    self._views, dstate["s"], dstate["anchor"], wcap)
            r0 = dstate["s"].astype(np.int32) - 1
            rank_w, vis_w, digest, ovf = _recovery.run_dispatch(
                "session",
                lambda: jaxwd.batched_delta_weave(
                    *(jnp.asarray(lanes[k]) for k in LANE_KEYS5),
                    jnp.asarray(dstate["prefix_digest"]),
                    jnp.asarray(r0), u_max=n_w, k_max=n_w))
            out = np.asarray(digest)
            if bool(np.asarray(ovf).any()):  # pragma: no cover -
                # structurally unreachable at u_max = N_w; kept so a
                # future budget change degrades to correct-but-slow
                obs.counter("session.delta_wave_overflow").inc()
                self._delta = None
                if obs.enabled():
                    from ..obs import costmodel as _cm

                    _recovery.step("session", "delta", "full",
                                   "window-overflow",
                                   uuid=str(self.pairs[0][0].ct.uuid))
                    _cm.wave_abandon()
                return None
            self.last_rank, self.last_visible = jaxwd.splice_ranks(
                self.last_rank, self.last_visible, rank_w, vis_w,
                jnp.asarray(starts), jnp.asarray(counts),
                jnp.asarray(r0))
            self.last_overflow = ovf
            if obs.enabled():
                from ..obs import costmodel as _cm

                _cm.record_dispatch(f"session:delta:w{int(wcap)}",
                                    site="session")
                _cm.record_dispatch("session:delta_splice",
                                    site="session")
        if obs.enabled():
            from ..obs import devprof

            devprof.sample_device_memory("session")
            sem = _observe_semantics(self.pairs, out,
                                     np.ones(B, bool), "session")
            from ..obs import costmodel as _cm

            _cm.wave_cost(
                uuid=str(self.pairs[0][0].ct.uuid),
                pairs=B,
                lanes=2 * int(self.capacity) * B,
                tokens=int(counts.sum()) + 2 * B,
                token_budget=int(n_w) * B,
                delta_ops=self._last_delta_lanes,
                full_bag=0,
                semantic=sem,
                path="delta",
            )
            self._last_delta_lanes = 0
            self._last_update_full = False
        self._last_digest = out
        return out

    # ------------------------------------------ batched-serving hooks
    #
    # The assemble→dispatch→splice pipeline of _delta_wave, factored
    # so an external scheduler (serve.batch.BatchScheduler) can stack
    # MANY sessions' windows as rows of ONE device program per pow2
    # bucket: window_pack() hands out the host-side window spec,
    # complete_window() absorbs this session's rows of the bucket
    # dispatch's output, and the rank/visibility splice is deferred
    # (_flush_window) until something actually reads the resident
    # weave — so N tenants' waves cost one dispatch per bucket, not
    # three per tenant.

    @property
    def bucket_key(self) -> int:
        """The pow2 batch-bucket key: the established window budget,
        or 0 when the next wave must run full width (no frontier)."""
        return int(self._delta["w_cap"]) if self._delta is not None \
            else 0

    def window_pack(self):
        """The host-side delta-window spec _delta_wave would assemble,
        for an external batch scheduler: the current views, the frozen
        frontier arrays, and the pow2 window budget (the bucket key).
        None when no frontier is established — the caller falls back
        to :meth:`wave` (full-width re-establish)."""
        if self._delta is None:
            return None
        dstate = self._delta
        return {
            "views": self._views,
            "s": dstate["s"],
            "anchor": dstate["anchor"],
            "prefix_digest": dstate["prefix_digest"],
            "w_cap": int(dstate["w_cap"]),
            "rows": len(self.pairs),
        }

    def abandon_frontier(self, reason: str, site: str = "serve"):
        """Drop the delta frontier with recovery-ladder evidence: the
        batched scheduler's per-tenant fallback rung (bucket window
        overflow, injected budget exhaustion). The next wave runs full
        width and re-establishes — this tenant alone pays the slow
        path, its bucket-mates stay fast."""
        if self._delta is None:
            return
        obs.counter("session.delta_wave_invalidate").inc()
        if obs.enabled():
            _recovery.step(site, "batch", "full", reason,
                           uuid=str(self.pairs[0][0].ct.uuid))
        self._delta = None

    def complete_window(self, rank_w, vis_w, digest, starts, counts):
        """Absorb this session's rows of a bucket dispatch's output
        (host arrays, already fetched once for the whole bucket). The
        digests are bit-identical to what _delta_wave would have
        returned — same window assembly, same program, same budget —
        so they become the checkpointable wave output directly; the
        rank/visibility splice is deferred to :meth:`_flush_window`
        (checkpoint/merged) because the next wave's window covers a
        superset of these lanes anyway."""
        dstate = self._delta
        if dstate is None:
            raise s.CausalError(
                "complete_window without an established frontier",
                {"causes": {"no-frontier"}},
            )
        out = np.asarray(digest)
        self._pending_window = {
            "rank_w": np.asarray(rank_w),
            "vis_w": np.asarray(vis_w),
            "starts": np.asarray(starts, np.int32),
            "counts": np.asarray(counts, np.int32),
            "r0": dstate["s"].astype(np.int32) - 1,
        }
        if obs.enabled():
            # per-tenant semantics are unchanged by batching: the
            # wave.digest agreement, staleness and lag resolution all
            # observe THIS session's digests, same as _delta_wave
            _observe_semantics(self.pairs, out,
                               np.ones(len(self.pairs), bool),
                               "session")
        self._last_digest = out
        return out

    def _flush_window(self):
        """Splice the pending window output into the resident
        rank/visibility arrays. Deferred from complete_window: in the
        batched steady state N waves pass between materializations,
        and each window supersedes the last, so the splice runs once
        per read instead of once per wave."""
        pw = self._pending_window
        if pw is None:
            return
        self._pending_window = None
        from ..weaver import jaxwd

        self.last_rank, self.last_visible = jaxwd.splice_ranks(
            self.last_rank, self.last_visible,
            jnp.asarray(pw["rank_w"]), jnp.asarray(pw["vis_w"]),
            jnp.asarray(pw["starts"]), jnp.asarray(pw["counts"]),
            jnp.asarray(pw["r0"]))
        if obs.enabled():
            from ..obs import costmodel as _cm

            _cm.record_dispatch("session:delta_splice",
                                site="session")

    def pop_divergence(self):
        """(delta_lanes, full_bag) shipped since the last wave — the
        wave.cost divergence evidence, reset on read. The batched
        scheduler drains every bucket member and sums them onto the
        bucket's single wave.cost event."""
        d = int(self._last_delta_lanes)
        f = 1 if self._last_update_full else 0
        self._last_delta_lanes = 0
        self._last_update_full = False
        return d, f

    def converge(self, tree: bool = True,
                 w_budget: Optional[int] = None):
        """Converge the WHOLE resident fleet — every replica of every
        pair — into one host handle.

        The session's waves converge pairs; fleet-wide convergence is
        a reduction over all 2B replicas, and its default shape is the
        merge reduction tree (``parallel.tree``): ceil(log2(2B))
        batched device rounds, level 0 full width, later levels riding
        the delta window path, bit-identical to any pairwise fold.
        ``tree=False`` runs that flat fold instead — n-1 SEQUENTIAL
        pairwise waves with per-step host materialization, the O(n)
        baseline the tree replaces (kept as the A/B control and the
        escape hatch). The session's device-resident pair state is
        untouched either way: convergence reads the handles, it does
        not re-upload them."""
        from . import tree as _tree

        replicas = [h for pair in self.pairs for h in pair]
        if tree:
            return _tree.merge_tree(replicas, w_budget=w_budget)
        return _tree.flat_fold(replicas)

    def merged(self, i: int):
        """Materialize pair ``i``'s converged tree (host handle) from
        the last wave."""
        from .wave import WaveResult

        self._flush_window()
        res = WaveResult(
            self.pairs, self._views, self.capacity,
            np.asarray(self.last_rank), np.asarray(self.last_visible),
            np.zeros(len(self.pairs), np.uint32), {}, "v5",
        )
        return res.merged(i)

    # --------------------------------------------- checkpoint/restore

    CHECKPOINT_VERSION = 1

    def checkpoint(self) -> dict:
        """Serialize the session's resident state to one JSON-able
        dict: the replica pairs (serde's tagged node-bag encoding),
        the last wave's rank/visibility/digest arrays, and the delta
        frontier. A process that crashes after a checkpoint restores
        with :meth:`restore` and resumes STEADY-STATE DELTA WAVES —
        no full-width re-weave, no O(doc) frontier re-establishment
        fetch; the restore pays one lane upload plus one digest
        dispatch (the bit-identity gate). Requires at least one
        completed wave (the checkpointed state IS a wave's output)."""
        from .. import serde

        if self._last_digest is None or not hasattr(self, "last_rank"):
            raise s.CausalError(
                "nothing to checkpoint: the resident state is not a "
                "wave's output (run a wave first; an update since "
                "the last wave also invalidates it)",
                {"causes": {"no-wave"}},
            )
        self._flush_window()
        with obs.span("session.checkpoint", pairs=len(self.pairs)):
            obs.counter("session.checkpoint").inc()
            ck = {
                "~causal_session": self.CHECKPOINT_VERSION,
                "d_max": int(self.d_max),
                "u_headroom": float(self._u_headroom),
                "delta_enabled": bool(self._delta_enabled),
                "u_max": int(self.u_max),
                "capacity": int(self.capacity),
                "pairs": [[serde.to_data(a), serde.to_data(b)]
                          for a, b in self.pairs],
                "rank": _pack_arr(np.asarray(self.last_rank)),
                "visible": _pack_arr(np.asarray(self.last_visible)),
                "digest": _pack_arr(np.asarray(self._last_digest)),
            }
            if self._delta is not None:
                ck["delta"] = {
                    "s": _pack_arr(self._delta["s"]),
                    "anchor": _pack_arr(self._delta["anchor"]),
                    "prefix_digest":
                        _pack_arr(self._delta["prefix_digest"]),
                    "w_cap": int(self._delta["w_cap"]),
                }
            return ck

    def checkpoint_to(self, path: str) -> None:
        """``checkpoint()`` straight to a JSON file (atomic rename so
        a crash mid-write never leaves a torn checkpoint)."""
        import json
        import os

        blob = json.dumps(self.checkpoint())
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(blob)
            f.flush()
            # fsync BEFORE the rename: the rename can be durable
            # while the data is not, publishing a torn checkpoint —
            # and serve-side storage GC retires WAL segments on the
            # strength of this file existing
            os.fsync(f.fileno())
        os.replace(tmp, path)

    @classmethod
    def restore(cls, data) -> "FleetSession":
        """Rebuild a session from :meth:`checkpoint` output (the dict,
        or a path to a ``checkpoint_to`` file). The restore is GATED
        on digest bit-identity: the uploaded lanes plus the restored
        rank/visibility must reproduce the checkpoint's digests
        exactly (one digest dispatch), or the restore refuses
        (``causes {"checkpoint-mismatch"}``) rather than resume from
        state it cannot prove. The delta frontier is revalidated
        host-side against the rebuilt views; if it no longer holds
        the session restores WITHOUT it (the next wave runs the full
        rung and re-establishes — correct, evidenced, just O(doc))."""
        import json as _json

        from .. import serde

        if isinstance(data, str):
            try:
                with open(data) as f:
                    data = _json.load(f)
            except ValueError as e:
                # a pack torn mid-spill (truncated JSON) refuses
                # through the same declared gate as a tampered one —
                # never a bare json error at the resume site
                raise s.CausalError(
                    "checkpoint file undecodable (torn pack?)",
                    {"causes": {"checkpoint-mismatch"},
                     "why": str(e)},
                ) from None
        if not (isinstance(data, dict)
                and data.get("~causal_session") == cls.CHECKPOINT_VERSION):
            raise s.CausalError(
                "not a FleetSession checkpoint (or unknown version)",
                {"causes": {"checkpoint-mismatch"},
                 "version": (data or {}).get("~causal_session")
                 if isinstance(data, dict) else None},
            )
        with obs.span("session.restore"):
            pairs = [(serde.from_data(ea), serde.from_data(eb))
                     for ea, eb in data["pairs"]]
            obj = cls.__new__(cls)
            obj.d_max = int(data["d_max"])
            obj._bufs = WaveBuffers()
            obj._views = []
            obj._uploaded_n = None
            obj._uploaded_k = None
            obj.capacity = 0
            # pre-seed the restored budget: _full_upload keeps the max,
            # so the session compiles the same program shapes it had
            obj.u_max = int(data["u_max"])
            obj._u_headroom = float(data["u_headroom"])
            obj.dev = None
            obj._last_delta_lanes = 0
            obj._last_update_full = False
            obj._delta_enabled = bool(data["delta_enabled"])
            obj._delta = None
            obj._delta_failures = 0
            obj._last_digest = None
            for a, b in pairs:
                s.check_mergeable(a.ct, b.ct)
            obj._full_upload(pairs)
            if obj.capacity != int(data["capacity"]):
                raise s.CausalError(
                    "checkpoint capacity mismatch (divergent rebuild)",
                    {"causes": {"checkpoint-mismatch"},
                     "expected": int(data["capacity"]),
                     "got": int(obj.capacity)},
                )
            B = len(pairs)
            try:
                rank = _unpack_arr(data["rank"])
                visible = _unpack_arr(data["visible"])
                want = _unpack_arr(data["digest"])
            except (KeyError, TypeError, ValueError) as e:
                # corrupted pack (torn base64, bad dtype): refuse
                # through the same declared gate, never a bare numpy
                # error
                raise s.CausalError(
                    "checkpoint arrays undecodable",
                    {"causes": {"checkpoint-mismatch"},
                     "why": str(e)},
                ) from None
            shape = (B, 2 * obj.capacity)
            if rank.shape != shape or visible.shape != shape \
                    or want.shape != (B,):
                raise s.CausalError(
                    "checkpoint array shapes do not match the fleet",
                    {"causes": {"checkpoint-mismatch"}},
                )
            obj.last_rank = jnp.asarray(rank)
            obj.last_visible = jnp.asarray(visible)
            obj.last_overflow = jnp.zeros(B, bool)
            # THE restore gate: the rebuilt lanes + the checkpointed
            # weave outputs must reproduce the checkpointed digests
            # bit-for-bit — one digest dispatch, no full wave
            got = np.asarray(_digest_fn()(
                obj.dev["hi"], obj.dev["lo"],
                obj.last_rank, obj.last_visible))
            if not np.array_equal(got, want):
                raise s.CausalError(
                    "checkpoint digest mismatch: refusing to resume "
                    "from unprovable state",
                    {"causes": {"checkpoint-mismatch"},
                     "rows": np.flatnonzero(got != want).tolist()},
                )
            obj._last_digest = got
            delta_restored = False
            dck = data.get("delta")
            if dck is not None and obj._delta_enabled:
                frontier = {
                    "s": _unpack_arr(dck["s"]),
                    "anchor": _unpack_arr(dck["anchor"]),
                    "prefix_digest": _unpack_arr(dck["prefix_digest"]),
                    "w_cap": int(dck["w_cap"]),
                }
                if obj._frontier_valid(frontier):
                    obj._delta = frontier
                    delta_restored = True
                else:
                    obs.counter("session.restore_frontier_drop").inc()
            if obs.enabled():
                _recovery.restore_recorded(
                    "session", B, delta_restored,
                    uuid=str(pairs[0][0].ct.uuid))
            return obj

    def _frontier_valid(self, frontier: dict) -> bool:
        """Host-only revalidation of a restored delta frontier against
        the freshly rebuilt views: the shared prefix still covers
        ``s``, the anchor is a live non-special lane, every divergent
        lane is still inside the delta domain, and the window fits
        the restored budget. O(divergence) numpy — never a device
        fetch."""
        w_cap = int(frontier["w_cap"])
        for r, (va, vb) in enumerate(self._views):
            sp = int(frontier["s"][r])
            anchor = int(frontier["anchor"][r])
            if sp < 1 or anchor >= sp:
                return False
            if lanecache.shared_prefix_len(va, vb) < sp:
                return False
            if int(va.arena.vclass[anchor]) > 0:
                return False
            if va.n - sp > w_cap - 1 or vb.n - sp > w_cap - 1:
                return False
            if not (delta_domain_ok(va, sp, anchor)
                    and delta_domain_ok(vb, sp, anchor)):
                return False
        return True


def _pack_arr(arr: np.ndarray) -> dict:
    """A numpy array as a compact JSON-able dict (base64 of the raw
    bytes + dtype + shape) — rank/visibility checkpoints at fleet
    scale would be absurd as JSON number lists."""
    import base64

    arr = np.ascontiguousarray(arr)
    return {
        "dtype": str(arr.dtype),
        "shape": list(arr.shape),
        "b64": base64.b64encode(arr.tobytes()).decode("ascii"),
    }


def _unpack_arr(d: dict) -> np.ndarray:
    import base64

    raw = base64.b64decode(d["b64"])
    arr = np.frombuffer(raw, dtype=np.dtype(d["dtype"]))
    return arr.reshape([int(x) for x in d["shape"]]).copy()
