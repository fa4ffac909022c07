"""The served-sync driver: ``IngestQueue.offer`` (admission and the
write-ahead journal), then ``SyncService.tick()`` (drain, apply, the
batched delta or full-width wave), open loop at the mix's fixed rate.

One thread: at each turn it offers every batch that is due, then ticks
while the queue holds work, else waits for the next due batch. A batch's
lag runs from its due time to the end of the tick after which its
tenant's ``applied_seq`` covers it; ops offered during a tick wait for
the next one, as they would behind a tick on another thread.

Records for the readers (``ctx.data``): ``batches`` (due, ops, lag or
None, converged inside the window), ``offer_s`` and ``tick_s`` (host
clock around each call; ticks that drained nothing are not taken), the
generator's lateness.

The check runs after the post-window drain, on every tenant: every
admitted op must be in ``svc.materialize(uuid)``, and the materialized
weave (its order, and the visible values with their ids) must be the
plain reference's weave of the generator's node bag. The journal is
read back from disk: every acknowledged batch (seq, tenant, site,
items) must be in it, and fsynced as the configuration's ``batch``
policy guarantees, seen from outside the program through a wrapper
around ``os.fsync`` that the driver installs for the run: an fsync
falls between a batch's offer and the acknowledgement of the first
later batch offered at least twice ``fsync_batch_ms`` after it, or
``fsync_batch_n`` batches after it, whichever comes first.
"""

from __future__ import annotations

import bisect
import json
import os
import time

from benchmark import build, harness
from benchmark.gen import edits
from benchmark.reference import weave as ref


class FsyncCount:
    """``os.fsync`` for the run, noting when it is called: the journal's
    durability as seen from outside the program."""

    def __init__(self):
        self.real = os.fsync
        self.at = []

    def __call__(self, fd):
        self.at.append(time.perf_counter())
        return self.real(fd)


def setup(ctx):
    from cause_tpu.serve import (IngestQueue, ResidencyManager,
                                 SyncService, WriteAheadLog)

    cfg = ctx.config
    t0 = time.perf_counter()
    tenants = edits.tenants(ctx.seed, cfg["tenants"], cfg["doc_values"],
                            cfg["side_values"], cfg["hide_every"])
    handles = []
    for t in tenants:
        doc = build.document(t.base)
        handles.append([build.side(doc, d) for d in t.sides])
    wal = cfg["wal"]
    journal = WriteAheadLog(os.path.join(ctx.tmp, "wal"),
                            fsync=wal["fsync"],
                            fsync_batch_n=wal["fsync_batch_n"],
                            fsync_batch_ms=wal["fsync_batch_ms"])
    queue = IngestQueue(max_ops=cfg["queue_max_ops"], journal=journal)
    svc = SyncService(queue, residency=ResidencyManager(cfg["residency"]),
                      d_max=cfg["d_max"], batched=cfg["batched"])
    for t, (left, right) in zip(tenants, handles):
        uuid = svc.add_tenant(left, right)
        if uuid != t.base.uuid:
            raise AssertionError("tenant uuid differs from the generator's")
        t.base.release()
    ctx.note(tenants=len(tenants),
             nodes_per_tenant=len(handles[0][0].ct.nodes) + len(
                 tenants[0].sides[1].nodes),
             build_s=f"{time.perf_counter() - t0:.3f}")
    fsyncs = os.fsync = FsyncCount()
    return {"tenants": tenants, "queue": queue, "svc": svc,
            "rate": float(ctx.mix["rate_ops_per_s"]),
            "acked": [], "fsyncs": fsyncs}


def _offer(state, t, st, timer=None):
    """Offer one site's pending ops; returns the Admission. On admission
    the ops join the tenant's reference bag, and the batch joins the
    list of acknowledgements the journal must hold."""
    import jax

    from cause_tpu import serde, sync

    items = serde.encode_node_items(
        {nid: (cause, build.value(v)) for nid, cause, v in st.pending})
    crc = sync.payload_checksum(items)
    s = time.perf_counter()
    with jax.profiler.TraceAnnotation("offer"):
            adm = state["queue"].offer(t.base.uuid, st.site, items, crc=crc)
    e = time.perf_counter()
    if timer is not None:
        timer.append(e - s)
    if adm.admitted:
        state["acked"].append((adm.seq, t.base.uuid, st.site, items, s, e))
        for nid, cause, v in st.pending:
            t.admitted[nid] = (cause, v)
        st.pending = []
    return adm


def _tick(svc, timer=None):
    import jax

    s = time.perf_counter()
    with jax.profiler.TraceAnnotation("tick"):
        out = svc.tick()
    if timer is not None and out["ops"]:
        timer.append(time.perf_counter() - s)
    return out


def _drain(state, limit_s):
    svc, queue = state["svc"], state["queue"]
    deadline = time.perf_counter() + limit_s
    while (queue.depth or queue.deferred) and time.perf_counter() < deadline:
        svc.tick()


def warmup(state, ctx):
    """Every shape the window will use: for each count ``k`` of the mix's
    ``warm_ladder``, one run offered by both sites of each of ``k``
    tenants and a drain (the batched buckets by pow2 count of touched
    tenants; each replica's first edit off the delta domain, which
    rebuilds its lanes); then ``warm_ahead`` more runs offered on every
    tenant and one drain, so that the documents grow, before the window,
    further than the window will take them: a session whose token
    budget crosses a power of two waves with a program of its own, and
    the first tenant to cross it compiles that program here rather than
    in the window; then the mix itself at the cell's rate for
    ``warm_seconds``, then a drain."""
    mix, tenants = ctx.mix, state["tenants"]
    prod = edits.Producer(ctx.seed + 1, mix, tenants, state["rate"])
    t0 = time.perf_counter()
    for k in mix["warm_ladder"]:
        for t in tenants[:k]:
            for st in t.sites:
                prod.mint_on(t, st)
                _offer(state, t, st)
        _drain(state, 120)
    ladder_s = time.perf_counter() - t0
    for t in tenants:
        for i in range(int(mix.get("warm_ahead", 0))):
            st = t.sites[i % 2]
            prod.mint_on(t, st)
            _offer(state, t, st)
    _drain(state, 120)
    ahead_s = time.perf_counter() - t0 - ladder_s
    loop = Loop(state, prod, mix["warm_seconds"], record=False)
    loop.run()
    _drain(state, 120)
    ctx.note(warm_ladder_s=f"{ladder_s:.3f}", warm_ahead_s=f"{ahead_s:.3f}",
             warm_mix_s=f"{time.perf_counter() - t0 - ladder_s - ahead_s:.3f}",
             warm_batches=loop.offered,
             depth_after_warm=state["queue"].depth)


class Loop:
    """The open loop: offer what is due, tick while work is queued."""

    def __init__(self, state, producer, seconds, record=True):
        self.state = state
        self.prod = producer
        self.seconds = float(seconds)
        self.record = record
        self.offered = 0
        self.batches = []          # [due_s, ops, lag_s or None, in_window]
        self.refused = 0
        self.late_s = []
        self.offer_s = []
        self.tick_s = []
        self.ticks = 0
        self.depth_start = state["queue"].depth

    def run(self):
        import jax

        svc, queue = self.state["svc"], self.state["queue"]
        outstanding = {}           # uuid -> [(seq, batch index)]
        self.t0 = t0 = time.perf_counter()
        t_end = t0 + self.seconds
        nxt = self.prod.next_batch()
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            while nxt[0] + t0 <= now and nxt[0] < self.seconds:
                due, t, st = nxt
                self.prod.mint_on(t, st)
                n_ops = len(st.pending)
                adm = _offer(self.state, t, st,
                             self.offer_s if self.record else None)
                self.late_s.append(now - (t0 + due))
                idx = len(self.batches)
                self.batches.append([due, n_ops, None, False])
                self.offered += 1
                if adm.admitted:
                    outstanding.setdefault(t.base.uuid, []).append(
                        (adm.seq, idx))
                else:
                    self.refused += 1
                nxt = self.prod.next_batch()
            if queue.depth:
                _tick(svc, self.tick_s if self.record else None)
                self.ticks += 1
                self._resolve(outstanding, time.perf_counter(), t_end)
            else:
                wait = min(nxt[0] + t0, t_end) - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("wait"):
                        time.sleep(wait)
        self.depth_end = queue.depth
        self.outstanding = outstanding

    def _resolve(self, outstanding, t_done, t_end):
        tenants = self.state["svc"].tenants
        for uuid, lst in outstanding.items():
            applied = tenants[uuid]["applied_seq"]
            keep = []
            for seq, idx in lst:
                if seq <= applied:
                    b = self.batches[idx]
                    b[2] = t_done - (self.t0 + b[0])
                    b[3] = t_done <= t_end
                else:
                    keep.append((seq, idx))
            lst[:] = keep

    def drain(self, limit_s):
        """After the window: tick until every admitted batch converged,
        or ``limit_s`` passed."""
        svc, queue = self.state["svc"], self.state["queue"]
        deadline = time.perf_counter() + limit_s
        while (queue.depth or queue.deferred) and \
                time.perf_counter() < deadline:
            svc.tick()
            self._resolve(self.outstanding, time.perf_counter(), 0.0)
        self._resolve(self.outstanding, time.perf_counter(), 0.0)
        self.drained_at = time.perf_counter()


def window(state, ctx):
    import jax

    prod = edits.Producer(ctx.seed, ctx.mix, state["tenants"], state["rate"])
    loop = Loop(state, prod, ctx.seconds)
    with jax.profiler.TraceAnnotation("bench.window"):
        loop.run()
    loop.drain(float(ctx.mix["drain_s"]))
    horizon = loop.drained_at - loop.t0
    lags, failed = [], 0
    for due, _ops, lag, _in in loop.batches:
        if lag is None:
            failed += 1
            lag = horizon - due    # never converged: at least this late
        lags.append(lag)
    ctx.data.update(
        batches=loop.batches, lags_s=lags, offer_s=loop.offer_s,
        tick_s=loop.tick_s, attempted=len(loop.batches), failed=failed,
        converged_ops=sum(ops for _d, ops, _l, inw in loop.batches if inw))
    late = sorted(loop.late_s) or [0.0]
    ticks = sorted(loop.tick_s) or [0.0]
    ctx.note(lag_ms_p50_p95_max="/".join(
                 f"{1e3 * harness.percentile(lags, q):.1f}"
                 for q in (50, 95, 100)) if lags else "-",
             tick_ms_p50_p99_max="/".join(
                 f"{1e3 * harness.percentile(ticks, q):.1f}"
                 for q in (50, 99, 100)))
    ctx.note(offered=loop.offered, refused=loop.refused, ticks=loop.ticks,
             minted_ops=prod.minted_ops,
             depth_start=loop.depth_start, depth_end=loop.depth_end,
             gen_late_p50_ms=f"{1e3 * late[len(late) // 2]:.3f}",
             gen_late_max_ms=f"{1e3 * late[-1]:.3f}",
             drain_s=f"{loop.drained_at - loop.t0 - ctx.seconds:.3f}",
             shed=dict(state["queue"].stats))


def _shown(woven, nodes):
    """(woven ids, the visible (id, value) pairs) of a reference weave."""
    return woven, [(n, nodes[n][1]) for n, v
                   in zip(woven, ref.visible(woven, nodes)) if v]


def check(state, ctx, control=False):
    svc, cfg = state["svc"], ctx.config
    t0 = time.perf_counter()
    missing = wrong = 0
    again = edits.tenants(ctx.seed, cfg["tenants"], cfg["doc_values"],
                          cfg["side_values"], cfg["hide_every"])
    for t, fresh in zip(state["tenants"], again):
        nodes = fresh.nodes()
        nodes.update(t.admitted)
        want = _shown(ref.weave(nodes), nodes)
        if control:
            got = _shown(ref.weave(nodes, "ascending"), nodes)
            have = nodes
        else:
            mat = svc.materialize(t.base.uuid)
            have = mat.ct.nodes
            got = ([n[0] for n in mat.ct.weave],
                   [(n[0], n[2]) for n in mat])
        missing += sum(1 for nid in t.admitted if nid not in have)
        wrong += got != want
    unjournaled = _unjournaled(state)
    unsynced = _unsynced(state, cfg["wal"])
    ctx.note(tenants_compared=len(state["tenants"]),
             acked_batches=len(state["acked"]),
             fsyncs=len(state["fsyncs"].at),
             compare_s=f"{time.perf_counter() - t0:.3f}")
    return {"ops_missing": (missing, 0), "tenants_wrong": (int(wrong), 0),
            "unjournaled_batches": (unjournaled, 0),
            "unsynced_batches": (unsynced, 0)}


def _unjournaled(state) -> int:
    """Acknowledged batches that the journal on disk lacks, or holds
    with another tenant, site or items."""
    on_disk = {int(r["seq"]): r
               for r in state["queue"].journal.iter_from(0)}
    bad = 0
    for seq, uuid, site, items, _s, _e in state["acked"]:
        rec = on_disk.get(seq)
        bad += (rec is None or rec["uuid"] != uuid or rec["site"] != site
                or rec["items"] != json.loads(json.dumps(items)))
    return bad


def _unsynced(state, wal) -> int:
    """Acknowledged batches with no fsync between their offer and the
    acknowledgement of the first later batch that the ``batch`` policy
    had to sync by: one offered ``2 * fsync_batch_ms`` after this one's
    acknowledgement, or the ``fsync_batch_n``-th after it. A batch with
    no such later batch is not judged."""
    if wal["fsync"] != "batch":
        raise ValueError(f"no fsync check for policy {wal['fsync']!r}")
    acked, at = state["acked"], state["fsyncs"].at
    gap, n = 2e-3 * float(wal["fsync_batch_ms"]), int(wal["fsync_batch_n"])
    starts = [a[4] for a in acked]
    bad = 0
    for i, (_q, _u, _s, _it, s, e) in enumerate(acked):
        j = min(bisect.bisect_left(starts, e + gap, i + 1), i + n)
        if j >= len(acked):
            continue
        k = bisect.bisect_left(at, s)
        bad += k >= len(at) or at[k] > acked[j][5]
    return bad


def close(state, ctx):
    svc, queue = state["svc"], state["queue"]
    svc.close()
    queue.journal.close()
    os.fsync = state["fsyncs"].real
    state.clear()
