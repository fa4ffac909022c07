"""Bring-up smoke: drive cause_tpu's main path once on one TPU, at the
sizes its users run, and check every result against the pure weaver.

One process, phases in order; the first failure exits non-zero:

1. device — ``jax.devices()`` must report platform ``tpu`` (anything
   else exits at once, before any data is built), plus the round trip
   of a trivial jitted dispatch;
2. wave   — ``merge_wave`` over 1024 divergent pairs of 10,001-node
   CausalLists (BASELINE config 5) on the shipped v5 route;
3. delta  — ``FleetSession`` steady state: full wave, appends, delta
   wave, bit-identical to a ``merge_wave`` of the updated pairs;
4. tree   — ``merge_all`` over 64 device-weaver replicas of one
   10,001-node document, bit-identical to the pure pairwise fold;
5. serve  — a batched ``SyncService`` with 64 tenants, ops offered
   through admission and the write-ahead journal, ticked dry, every
   tenant's document equal to the pure merge of the same ops.

``--four-chips`` runs only the sharded merge wave of the same
1024-pair batch over a 4-chip mesh and the batch on one chip, and
checks their digests bit-identical.

Every line before the last states facts (sizes, seconds, bytes); the
last line is the JSON verdict the chip check reads.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import tempfile
import time

import numpy as np

PAIRS = 1024          # BASELINE config 5: replica pairs per wave
BASE_NODES = 9_000    # shared prefix of every pair
DIV_NODES = 1_000     # divergent nodes per side (every 8th a hide)
HIDE_EVERY = 8
TREE_REPLICAS = 64
TREE_DIV = 24         # ops per tree replica past the shared document
TENANTS = 64
SERVE_DOC = 10_000    # values per tenant document (+ root = 10,001)
SERVE_ROUNDS = 2      # offer rounds (the first pays the compiles)
SERVE_OPS = 3         # ops per site per round


def fail(phase: str, msg: str):
    raise SystemExit(f"chip_smoke: {phase} FAILED: {msg}")


def check(ok, phase: str, msg: str) -> None:
    if not ok:
        fail(phase, msg)


def peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", -1))


def report(phase: str, **facts) -> None:
    facts["peak_bytes_in_use"] = peak_bytes()
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def pure(h):
    """The host oracle twin of a device-weaver handle (same nodes,
    same site, pure weaver, no lane cache)."""
    from cause_tpu.collections.clist import CausalList

    return CausalList(h.ct.evolve(weaver="pure", lanes=None))


def document(values: int, prefix: str):
    """A fresh device-weaver CausalList of ``values`` nodes past the
    root. The weave is computed by the pure weaver and the tree then
    evolves to the device weaver with its lane view attached, so
    replicas forked from it extend the view per edit instead of
    rebuilding it per wave (``extend`` keeps every transaction under
    the 8,191-node id domain)."""
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.weaver import lanecache

    ct = c.clist(weaver="pure").extend(
        [f"{prefix}{i}" for i in range(values)]).ct
    return CausalList(ct.evolve(
        weaver="jax", lanes=lanecache.build_view(ct.nodes, ct.uuid)))


def divergent_side(base, tag: str, n: int):
    """A replica of ``base`` on a fresh site with ``n`` divergent nodes
    in one transaction run, every ``HIDE_EVERY``-th one a hide of the
    node before it (benchgen's suffix shape, phased so that the run
    ends on a value: appends then extend the run, which is what keeps
    a steady-state edit inside the delta-wave domain)."""
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.ids import new_site_id

    r = CausalList(base.ct.evolve(site_id=new_site_id()))
    return r.extend([c.hide if j % HIDE_EVERY == HIDE_EVERY - 1 else f"{tag}.{j}"
                     for j in range(1, n + 1)])


def build_pairs(n_pairs: int, base_nodes: int, div_nodes: int):
    base = document(base_nodes, "x")
    return [(divergent_side(base, f"a{p}", div_nodes),
             divergent_side(base, f"b{p}", div_nodes))
            for p in range(n_pairs)]


def edn_equal(got, want) -> bool:
    import cause_tpu as c

    return c.causal_to_edn(got) == c.causal_to_edn(want)


# ------------------------------------------------------------ phases


def phase_device(want_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if d0.platform != "tpu":
        fail("device", f"platform is {d0.platform!r}, not 'tpu'; this "
                       "script has no CPU path")
    if len(devs) < want_chips:
        fail("device", f"{len(devs)} device(s), {want_chips} needed")
    return info


def phase_setup() -> None:
    """Compile cache and the dispatch round trip."""
    import jax
    import jax.numpy as jnp

    from cause_tpu import benchgen

    cache = benchgen.enable_compile_cache()
    print(f"[setup] compile_cache={cache}", flush=True)

    step = jax.jit(lambda x: x + 1)
    x = jnp.int32(0)
    _, first = timed(lambda: np.asarray(step(x)))
    rts = []
    for _ in range(20):
        _, dt = timed(lambda: np.asarray(step(x)))
        rts.append(dt)
    report("setup", dispatch_first_s=f"{first:.3f}",
           dispatch_round_trip_ms_p50=f"{1e3 * float(np.median(rts)):.3f}",
           dispatch_round_trip_ms_min=f"{1e3 * min(rts):.3f}")


def _counted(fn):
    """Run ``fn`` with obs on (in memory); return (out, counters)."""
    from cause_tpu import obs

    obs.configure(enabled=True, reset=True)
    try:
        out = fn()
        counters = obs.counters_snapshot()["counters"]
        events = obs.events()
    finally:
        obs.configure(enabled=False, reset=True)
    return out, counters, events


def phase_wave(pairs, setup_s: float):
    from cause_tpu.parallel import merge_wave

    (res, counters, _ev), first = timed(
        lambda: _counted(lambda: merge_wave(pairs)))
    _, warm = timed(lambda: merge_wave(pairs))
    check(res.fallback == [], "wave", f"fallback pairs {res.fallback}")
    check(res.poisoned == [], "wave", f"poisoned pairs {res.poisoned}")
    check(counters.get("wave.overflow_retry", 0) == 0, "wave",
          f"{counters.get('wave.overflow_retry')} overflow row(s)")
    check(bool(res.digest_valid.all()), "wave", "invalid digest rows")
    n_distinct = len(set(res.digest.tolist()))
    check(n_distinct == len(pairs), "wave",
          f"{n_distinct} distinct digests for {len(pairs)} pairs")
    for i in sorted({0, len(pairs) // 2 - 1, len(pairs) - 1}):
        a, b = pairs[i]
        check(edn_equal(res.merged(i), pure(a).merge(pure(b))), "wave",
              f"row {i} differs from the pure merge")
    report("wave", pairs=len(pairs), nodes_per_tree=len(pairs[0][0].ct.nodes),
           kernel=res.kernel, capacity=res.capacity,
           setup_s=f"{setup_s:.3f}", first_call_s=f"{first:.3f}",
           warm_ms=f"{1e3 * warm:.3f}",
           wave_fallback=counters.get("wave.fallback", 0))
    return res


def phase_delta(pairs):
    from cause_tpu.parallel import merge_wave
    from cause_tpu.parallel.session import FleetSession

    def edit(ps, r):
        return [(a.conj(f"da{r}.{i}").conj(f"da{r}.{i}'"),
                 b.conj(f"db{r}.{i}")) for i, (a, b) in enumerate(ps)]

    sess, setup = timed(lambda: FleetSession(pairs))
    _, first_full = timed(sess.wave)
    upd = edit(pairs, 0)
    _, update_s = timed(lambda: sess.update(upd))
    (dig, _c, events), first_delta = timed(lambda: _counted(sess.wave))
    paths = [e["fields"].get("path") for e in events
             if e.get("name") == "wave.cost"]
    check(paths == ["delta"], "delta", f"second wave paths {paths}")
    want = merge_wave(upd).digest
    check(np.array_equal(dig, want), "delta",
          "delta digests differ from merge_wave of the updated pairs")
    upd2 = edit(upd, 1)
    (_, dig2), warm = timed(lambda: (sess.update(upd2), sess.wave()))
    check(np.array_equal(dig2, merge_wave(upd2).digest),
          "delta", "warm delta digests differ from merge_wave")
    report("delta", pairs=len(pairs), setup_s=f"{setup:.3f}",
           first_full_wave_s=f"{first_full:.3f}",
           update_s=f"{update_s:.3f}",
           first_call_s=f"{first_delta:.3f}",
           warm_ms=f"{1e3 * warm:.3f}")


def phase_tree(n_replicas: int, doc_values: int, div: int):
    import cause_tpu as c

    def build():
        base = document(doc_values, "t")
        return [divergent_side(base, f"r{r}", div)
                for r in range(n_replicas)]

    handles, setup = timed(build)
    (root, counters, _events), first = timed(
        lambda: _counted(lambda: c.merge_all(*handles)))
    levels = counters.get("tree.levels", 0)
    check(levels == (n_replicas - 1).bit_length(), "tree",
          f"{levels} tree level(s) for {n_replicas} replicas")
    _, warm = timed(lambda: c.merge_all(*handles))
    want = functools.reduce(lambda x, y: x.merge(y),
                            [pure(h) for h in handles])
    check([n[0] for n in root.get_weave()]
          == [n[0] for n in want.get_weave()], "tree",
          "weave differs from the pure fold")
    check(edn_equal(root, want), "tree", "EDN differs from the pure fold")
    report("tree", replicas=n_replicas,
           nodes_per_replica=len(handles[0].ct.nodes),
           setup_s=f"{setup:.3f}", first_call_s=f"{first:.3f}",
           warm_ms=f"{1e3 * warm:.3f}", levels=levels)


def phase_serve(n_tenants: int, doc_values: int, rounds: int, ops: int):
    from cause_tpu import serde, sync
    from cause_tpu.serve import (IngestQueue, ResidencyManager,
                                 SyncService, WriteAheadLog)

    t0 = time.perf_counter()
    tenants = []
    for t in range(n_tenants):
        base = document(doc_values, f"s{t}.")
        tenants.append((divergent_side(base, f"L{t}", HIDE_EVERY),
                        divergent_side(base, f"R{t}", HIDE_EVERY)))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_wal_") as wal:
        queue = IngestQueue(journal=WriteAheadLog(wal))
        svc = SyncService(queue, residency=ResidencyManager(n_tenants))
        try:
            uuids = [svc.add_tenant(a, b) for a, b in tenants]
            setup = time.perf_counter() - t0
            # client replicas: each site edits its own pure twin; the
            # oracle is the pure merge of the two twins at the end
            clients = [[pure(a), pure(b)] for a, b in tenants]
            walls = []
            ticks = []
            for r in range(rounds):
                for uuid, sides in zip(uuids, clients):
                    for k, h in enumerate(sides):
                        nxt = h
                        for j in range(ops):
                            nxt = nxt.conj(f"op{r}.{k}.{j}")
                        delta = {nid: body for nid, body in nxt.ct.nodes.items()
                                 if nid not in h.ct.nodes}
                        items = serde.encode_node_items(delta)
                        adm = queue.offer(uuid, str(h.ct.site_id), items,
                                          crc=sync.payload_checksum(items))
                        check(adm.admitted, "serve",
                              f"offer refused: {adm}")
                        sides[k] = nxt
                t1 = time.perf_counter()
                n = 0
                while queue.depth or queue.deferred:
                    svc.tick()
                    n += 1
                    check(n <= 10 * n_tenants, "serve", "queue never drained")
                walls.append(time.perf_counter() - t1)
                ticks.append(n)
            for uuid, (a, b) in zip(uuids, clients):
                check(edn_equal(svc.materialize(uuid), a.merge(b)), "serve",
                      f"tenant {uuid} differs from the pure merge")
        finally:
            svc.close()
            queue.journal.close()
    report("serve", tenants=n_tenants,
           nodes_per_tenant=len(tenants[0][0].ct.nodes),
           setup_s=f"{setup:.3f}", first_call_s=f"{walls[0]:.3f}",
           first_ticks=ticks[0],
           warm_ms=f"{1e3 * walls[-1]:.3f}", warm_ticks=ticks[-1])


def phase_four_chips(pairs):
    import jax

    from cause_tpu.parallel import make_mesh, merge_wave
    from cause_tpu.parallel import mesh as mesh_mod

    mesh = make_mesh(4)
    placed = []
    step = mesh_mod.sharded_merge_weave_v5

    def spy(*a, **k):
        out = step(*a, **k)
        placed.append(out[3].sharding.device_set)  # the digest array
        return out

    mesh_mod.sharded_merge_weave_v5 = spy
    try:
        res4, first4 = timed(lambda: merge_wave(pairs, mesh=mesh))
        _, warm4 = timed(lambda: merge_wave(pairs, mesh=mesh))
    finally:
        mesh_mod.sharded_merge_weave_v5 = step
    res1, first1 = timed(lambda: merge_wave(pairs))
    _, warm1 = timed(lambda: merge_wave(pairs))
    check(res4.fallback == [] and res1.fallback == [], "four_chips",
          f"fallback pairs {res4.fallback} / {res1.fallback}")
    check(bool(placed), "four_chips", "the sharded step never ran")
    devs = placed[0]
    check(len(devs) == 4, "four_chips",
          f"sharded output on {len(devs)} device(s): {devs}")
    check(np.array_equal(res4.digest, res1.digest), "four_chips",
          "sharded digests differ from the one-chip digests")
    check(len(set(res1.digest.tolist())) == len(pairs), "four_chips",
          "digests collapsed")
    report("four_chips", pairs=len(pairs),
           mesh_devices=sorted(d.id for d in devs),
           jax_devices=len(jax.devices()),
           sharded_first_call_s=f"{first4:.3f}",
           sharded_warm_ms=f"{1e3 * warm4:.3f}",
           one_chip_first_call_s=f"{first1:.3f}",
           one_chip_warm_ms=f"{1e3 * warm1:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded merge wave over 4 chips and "
                         "the same batch on one chip")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    info = phase_device(4 if args.four_chips else 1)
    phase_setup()
    pairs, setup_s = timed(lambda: build_pairs(PAIRS, BASE_NODES, DIV_NODES))
    if args.four_chips:
        phase_four_chips(pairs)
    else:
        phase_wave(pairs, setup_s)
        phase_delta(pairs)
        del pairs
        phase_tree(TREE_REPLICAS, BASE_NODES + DIV_NODES, TREE_DIV)
        phase_serve(TENANTS, SERVE_DOC, SERVE_ROUNDS, SERVE_OPS)
    print(f"[done] wall_s={time.perf_counter() - t_start:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
