"""Cross-tenant wave batching: one fused device dispatch per pow2
bucket serves every ready tenant's delta window.

The serve tick used to pay one wave — and therefore one ~67 ms
dispatch floor (``obs.costmodel.DISPATCH_FLOOR_MS``) — per touched
tenant per tick. But the delta-native wave's device program
(``weaver.jaxwd.batched_delta_weave``) is already vmap-batched across
rows, and its window assembly (``parallel.wave.assemble_delta_window``)
is pure host work over cached views with no dependence on any
session's resident capacity. So N tenants whose frontiers share a
window budget can ride ONE dispatch: stack their windows as batch
rows, weave once, split the per-row digests back per tenant.

:class:`BatchScheduler` is that external driver, built on the
session-layer hooks factored out of ``FleetSession._delta_wave``:

- **bucket** — tenants group by ``FleetSession.bucket_key`` (the pow2
  window budget ``w_cap``); every member of a bucket shares the
  compiled XLA program shape, so the weave is one dispatch per
  DISTINCT budget, not per tenant. Batch rows are padded to the next
  pow2 with copies of row 0 (outputs discarded), so the program shape
  also survives tenant-count churn tick to tick;
- **dispatch** — one ``batched_delta_weave`` per bucket, through the
  recovery ladder's retry rung, with the injectable chaos seams the
  per-tenant path has (stall, budget exhaustion);
- **split back** — per-row digests, ranks and visibility are fetched
  once for the whole bucket and handed to each member's
  ``complete_window`` (per-tenant semantics — ``wave.digest``
  agreement, staleness, lag resolution — are observed per tenant,
  unchanged by batching; the rank splice is deferred until something
  reads the resident weave);
- **fallback** — a tenant with no frontier, or whose window overflows
  its bucket, runs its own full-width ``wave()`` (re-establish, with
  recovery-ladder evidence) WITHOUT dragging its bucket-mates down
  the slow path.

Cost accounting: each bucket emits one ``wave.cost`` with ``bucket``
and ``batch_rows`` fields (``path="batched"``), draining every member
tenant's pending delta-op evidence, so the gap report and the live
fold can attribute the dispatch-count collapse: ``floor_budget_ms``
scales with #buckets, not #tenants.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .. import chaos as _chaos
from .. import obs
from ..obs import xtrace

__all__ = ["BatchScheduler"]


class BatchScheduler:
    """Group ready sessions by pow2 bucket, run one fused delta-wave
    dispatch per bucket, split the results back per tenant."""

    def __init__(self, site: str = "serve"):
        self.site = str(site)
        # last wave_fleet's shape, for the serve.tick event
        self.last_buckets = 0
        self.last_batch_rows = 0
        self.last_fallbacks = 0
        # tick-scoped {uuid: [trace ids]} for per-tenant wave hops
        self._traces_by_uuid: Dict[str, list] = {}

    def wave_fleet(self, sessions,
                   traces_by_uuid=None) -> Dict[str, np.ndarray]:
        """One batched wave over ``{uuid: FleetSession}``: every
        session ends wave-current; returns ``{uuid: digest array}``
        bit-identical to per-tenant ``wave()`` calls.
        ``traces_by_uuid`` (PR 19, obs-on ticks) maps tenants to the
        trace ids riding this tick so each fused bucket span fans out
        per-tenant "wave" child hops. The ``serve.waves`` span's
        ``full`` counts the tenants waved full-width one at a time; the
        rest rode the delta buckets."""
        with obs.span("serve.waves", tenants=len(sessions)) as sp:
            digests = self._wave_fleet(sessions, traces_by_uuid)
            sp.set(full=self.last_fallbacks)
        return digests

    def _wave_fleet(self, sessions, traces_by_uuid):
        self._traces_by_uuid = traces_by_uuid or {}
        digests: Dict[str, np.ndarray] = {}
        fallback: List[str] = []
        buckets: Dict[int, list] = {}
        for uuid, sess in sessions.items():
            if _chaos.enabled() and sess.bucket_key \
                    and _chaos.budget_exhaust("session"):
                # injected window-budget exhaustion: this tenant alone
                # drops to the full-width rung, same as in wave()
                sess.abandon_frontier("budget-exhaustion",
                                      site=self.site)
            pack = sess.window_pack()
            if pack is None:
                fallback.append(uuid)
            else:
                buckets.setdefault(pack["w_cap"], []).append(
                    (uuid, sess, pack))
        self.last_buckets = len(buckets)
        self.last_batch_rows = 0
        for wcap in sorted(buckets):
            self._wave_bucket(wcap, buckets[wcap], digests, fallback)
        for uuid in fallback:
            # full-width re-establish, one tenant at a time: the
            # recovery evidence rode the frontier drop that put the
            # tenant here (update-level degrade, abandon_frontier)
            digests[uuid] = sessions[uuid].wave()
            if obs.enabled():
                for tr in self._traces_by_uuid.get(uuid, ()):
                    xtrace.hop("wave", tr, uuid=uuid, path="full")
        self.last_fallbacks = len(fallback)
        return digests

    def _wave_bucket(self, wcap: int, group, digests, fallback):
        from ..benchgen import LANE_KEYS5
        from ..parallel import recovery as _recovery
        from ..parallel.wave import assemble_delta_window
        from ..weaver import jaxwd
        from ..weaver.arrays import next_pow2

        import jax.numpy as jnp

        n_w = 2 * wcap
        views: list = []
        s_parts, anchor_parts, pdig_parts = [], [], []
        row_of = []  # (uuid, sess, first row, row count)
        for uuid, sess, pack in group:
            row_of.append((uuid, sess, len(views), pack["rows"]))
            views.extend(pack["views"])
            s_parts.append(np.asarray(pack["s"]))
            anchor_parts.append(np.asarray(pack["anchor"]))
            pdig_parts.append(np.asarray(pack["prefix_digest"]))
        n_real = len(views)
        n_pad = int(next_pow2(max(1, n_real)))
        if n_pad > n_real:
            # pad with copies of the first row so the program shape is
            # (wcap, pow2 rows) — stable across tenant-count churn;
            # padded rows' outputs are sliced off below
            pad = n_pad - n_real
            views = views + [views[0]] * pad
            s_parts.append(np.repeat(s_parts[0][:1], pad))
            anchor_parts.append(np.repeat(anchor_parts[0][:1], pad))
            pdig_parts.append(np.repeat(pdig_parts[0][:1], pad))
        s_arr = np.concatenate(s_parts).astype(np.int32)
        anchor_arr = np.concatenate(anchor_parts).astype(np.int32)
        pdig = np.concatenate(pdig_parts).astype(np.uint32)
        uuids = [u for u, _se, _lo, _n in row_of]
        self.last_batch_rows += n_pad
        if _chaos.enabled():
            # one stall draw per dispatch, the same rate the
            # per-tenant path pays per wave
            _chaos.stall_point("session")
        if obs.enabled():
            from ..obs import costmodel as _cm

            _cm.wave_begin(self.site)
            obs.event("run.heartbeat", stage="serve.batch_wave",
                      bucket=int(wcap), tenants=len(group),
                      batch_rows=n_pad)
        with obs.span("serve.batch_wave", bucket=int(wcap),
                      tenants=len(group), rows=n_real):
            with obs.span("serve.batch_assemble"):
                lanes, starts, counts = assemble_delta_window(
                    views, s_arr, anchor_arr, wcap)
            r0 = s_arr.astype(np.int32) - 1
            rank_w, vis_w, digest, ovf = _recovery.run_dispatch(
                "session",
                lambda: jaxwd.batched_delta_weave(
                    *(jnp.asarray(lanes[k]) for k in LANE_KEYS5),
                    jnp.asarray(pdig), jnp.asarray(r0),
                    u_max=n_w, k_max=n_w))
            # one host fetch for the whole bucket; rows split per
            # tenant below without further device work
            out = np.asarray(digest)
            ovf_np = np.asarray(ovf)
            rank_np = np.asarray(rank_w)
            vis_np = np.asarray(vis_w)
            if obs.enabled():
                from ..obs import costmodel as _cm

                _cm.record_dispatch(
                    f"serve:batch:w{int(wcap)}x{n_pad}", site="serve")
        delta_ops = 0
        full_bags = 0
        for uuid, sess, r_lo, rows in row_of:
            sl = slice(r_lo, r_lo + rows)
            if bool(ovf_np[sl].any()):  # pragma: no cover -
                # structurally unreachable at u_max = N_w (the same
                # budget rule as _delta_wave); kept so a future budget
                # change degrades this tenant alone, not its bucket
                obs.counter("serve.batch_row_overflow").inc()
                sess.abandon_frontier("window-overflow",
                                      site=self.site)
                fallback.append(uuid)
                continue
            d, f = sess.pop_divergence()
            delta_ops += d
            full_bags += f
            digests[uuid] = sess.complete_window(
                rank_np[sl], vis_np[sl], out[sl],
                starts[sl], counts[sl])
            if obs.enabled():
                # the bucket span fans out per-tenant child hops:
                # each trace's "wave" hop names the fused dispatch
                # (bucket + rows) that actually served it
                for tr in self._traces_by_uuid.get(uuid, ()):
                    xtrace.hop("wave", tr, uuid=uuid,
                               path="batched", bucket=int(wcap),
                               batch_rows=n_pad)
        if obs.enabled():
            from ..obs import costmodel as _cm
            from ..obs import devprof

            devprof.sample_device_memory("serve.batch")
            _cm.wave_cost(
                uuid=f"bucket:w{int(wcap)}",
                pairs=n_real,
                lanes=sum(2 * int(se.capacity) * n
                          for _u, se, _lo, n in row_of),
                tokens=int(counts[:n_real].sum()) + 2 * n_real,
                token_budget=int(n_w) * n_pad,
                delta_ops=delta_ops,
                full_bag=full_bags,
                path="batched",
                bucket=int(wcap),
                batch_rows=n_pad,
                uuids=uuids,
            )
