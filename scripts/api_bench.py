"""API-level merge benchmarks.

Two stories, both end-to-end through the public handles (kernel-level
benchmarks bypass the host by generating lanes synthetically —
benchgen; THIS script pays every host cost honestly):

1. default: single ``CausalList.merge`` at 10k nodes per backend, with
   the jax path split into host-union / host-marshal / device-kernel.
2. ``--wave B``: a batched merge wave of B divergent replica pairs
   through ``parallel.merge_wave`` — the north-star path (BASELINE
   config 5) — split into host assembly (cached-lane gathering +
   segment tables + budgets) vs device kernel vs digest sync, plus the
   on-demand cost of materializing one merged pair back to a host
   handle. The lane cache means assembly touches numpy arrays only;
   the per-tree marshal was paid once at build time and maintained
   incrementally by the handles' edits.

Prints one JSON line per measurement.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import argparse
import json
import time

import numpy as np

def build_pair(n_base: int, n_div: int, weaver: str):
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.ids import new_site_id

    base = c.clist(weaver=weaver).extend(["x"] * n_base)
    a = CausalList(base.ct.evolve(site_id=new_site_id()))
    b = CausalList(base.ct.evolve(site_id=new_site_id()))
    a = a.extend([f"a{i}" for i in range(n_div)])
    b = b.extend([f"b{i}" for i in range(n_div)])
    return a, b


def timed(fn, reps=3):
    fn()  # warm (compiles, caches)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1000)
    return float(np.median(ts))


def claimed_platform() -> str:
    """The device this run measures, with the compile cache placed.
    Called after the pure-host fleet mint."""
    import jax

    from cause_tpu.benchgen import enable_compile_cache

    enable_compile_cache()
    return jax.devices()[0].platform


def wave_bench(args):
    import jax

    import cause_tpu as c
    from cause_tpu.collections import clist as c_list
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.ids import new_site_id
    from cause_tpu.parallel import merge_wave
    from cause_tpu.parallel.wave import WaveBuffers, _assemble_rows, _digest_fn
    from cause_tpu.weaver import lanecache
    from cause_tpu.weaver.arrays import next_pow2
    from cause_tpu.benchgen import LANE_KEYS5, v5_token_budget
    import jax.numpy as jnp

    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    B, n_base, n_div = args.wave, args.n_base, args.n_div

    t0 = time.perf_counter()
    # mint with the PURE weaver, then evolve to weaver="jax" after the
    # weave — identical wave behavior, no device work during the mint.
    ct = c.clist(weaver="pure", lazy=args.lazy).extend(["x"] * n_base).ct
    if args.lazy:
        # materialize once; non-lazy extend already wove incrementally
        # (a second full fold would be a redundant O(n^2) host pass)
        ct = c_list.weave(ct)
    # warm the base lane view host-side (pure numpy): the jax mint got
    # this as a device-weave side effect; replicas inherit the view
    # through evolve() and extend it incrementally per edit, so the
    # wave measures cached-lane assembly exactly as before
    base = CausalList(ct.evolve(
        weaver="jax",
        lanes=lanecache.build_view(ct.nodes, ct.uuid)))
    pairs = []
    for p in range(B):
        # BASELINE config-5 shape: divergent suffixes with a tombstone
        # every 8th node (tombstones break chain runs, so this is the
        # honest segment/token structure, not a best case)
        def replica(tag):
            r = CausalList(base.ct.evolve(site_id=new_site_id()))
            vals = [f"{tag}{p}.{i}" for i in range(n_div)]
            for start in range(0, n_div, 8):
                r = r.extend(vals[start:start + 8])
                r = r.append(r.tail_id(), c.hide)
            return r

        pairs.append((replica("a"), replica("b")))
    build_s = time.perf_counter() - t0
    print(json.dumps({
        "metric": "wave setup (mint replicas, incl. incremental lane cache)",
        "pairs": B, "nodes_per_tree": n_base + n_div + 1,
        "value": round(build_s, 1), "unit": "s",
    }), flush=True)

    platform = claimed_platform()

    # --- host side: view gathering + batch assembly + budget ---------
    bufs = WaveBuffers()

    def host_assemble():
        views = [(lanecache.view_for(a.ct), lanecache.view_for(b.ct))
                 for a, b in pairs]
        cap = next_pow2(max(max(va.n, vb.n) for va, vb in views))
        lanes = _assemble_rows(views, cap, bufs=bufs)
        return lanes, v5_token_budget(lanes)

    t_host = timed(host_assemble, reps=args.reps)
    lanes, u_max = host_assemble()

    # --- device side: one wave dispatch + scalar sync ----------------
    jlanes = [jnp.asarray(lanes[k]) for k in LANE_KEYS5]

    def kernel_once():
        r, v, _c_, ov = batched_merge_weave_v5(
            *jlanes, u_max=u_max, k_max=u_max
        )
        d = _digest_fn()(jlanes[0], jlanes[1], r, v)
        return int(np.asarray(d[0])), int(np.asarray(ov.sum()))

    t_kernel = timed(kernel_once, reps=args.reps)

    # amortized per-wave cost over a pipelined burst (one terminal
    # sync): the dispatch-floor-resistant number — see PERF.md
    n_burst = args.burst

    def kernel_burst():
        outs = []
        for _ in range(n_burst):
            r, v, _c_, ov = batched_merge_weave_v5(
                *jlanes, u_max=u_max, k_max=u_max
            )
            outs.append(_digest_fn()(jlanes[0], jlanes[1], r, v))
        return [int(np.asarray(d[0])) for d in outs][-1]

    t_burst = timed(kernel_burst, reps=max(1, args.reps - 1)) / n_burst

    # --- whole wave through the public API ---------------------------
    t_wave = timed(lambda: merge_wave(pairs), reps=args.reps)
    res = merge_wave(pairs)
    t_mat = timed(lambda: res.merged(0), reps=args.reps)

    # --- device-resident session: the steady-state loop --------------
    from cause_tpu.parallel.session import FleetSession

    sess = FleetSession(pairs)
    sess.wave()
    w = [0]

    def edit_all():
        w[0] += 1
        return [(x.conj(f"s{w[0]}x"), y.extend([f"s{w[0]}y"]))
                for x, y in sess.pairs]

    sess.update(edit_all())
    sess.wave()  # compile the delta path
    t_edits, t_rounds = [], []
    for _ in range(args.reps + 1):
        t0 = time.perf_counter()
        nxt = edit_all()
        t1 = time.perf_counter()
        sess.update(nxt)
        sess.wave()
        t2 = time.perf_counter()
        t_edits.append((t1 - t0) * 1000)
        t_rounds.append((t2 - t1) * 1000)
    print(json.dumps({
        "metric": "device-resident session round",
        "kernel": "v5",  # the session's resident splice is v5-only
        "pairs": B,
        "edit_all_replicas_ms": round(float(np.median(t_edits[1:])), 1),
        "delta_update_plus_wave_ms": round(
            float(np.median(t_rounds[1:])), 1
        ),
        "unit": "ms",
        "platform": platform,
    }), flush=True)

    _, n_over = kernel_once()
    print(json.dumps({
        "metric": f"merge wave {B} pairs x {n_base + n_div + 1}-node "
                  "CausalLists (API, cached lanes)",
        "host_assembly_ms": round(t_host, 1),
        "device_kernel_ms": round(t_kernel, 1),
        "device_kernel_amortized_ms": round(t_burst, 1),
        "whole_wave_ms": round(t_wave, 1),
        "materialize_one_pair_ms": round(t_mat, 2),
        "host_lt_kernel": bool(t_host < t_kernel),
        "u_max": int(u_max), "overflow_rows": n_over,
        "fallback_pairs": len(res.fallback),
        "kernel": "v5",
        "platform": platform, "unit": "ms",
    }), flush=True)


def map_bench(args):
    """Batched map-forest merge at fleet scale: B replica pairs of one
    CausalMap through mapw.batched_merge_map_weave (VERDICT r2 #4's
    bench row for maps)."""
    import jax

    import cause_tpu as c
    from cause_tpu import K
    from cause_tpu.collections.cmap import CausalMap
    from cause_tpu.ids import new_site_id
    from cause_tpu.weaver import mapw

    B = args.maps
    base = c.cmap()
    for i in range(args.n_keys):
        base = base.append(K(f"k{i}"), f"v{i}")
    pairs = []
    for p in range(B):
        a = CausalMap(base.ct.evolve(site_id=new_site_id()))
        b = CausalMap(base.ct.evolve(site_id=new_site_id()))
        for e in range(args.n_edits):
            a = a.append(K(f"k{(p + e) % args.n_keys}"), f"a{p}.{e}")
            b = b.append(K(f"x{e % 4}"), f"b{p}.{e}")
        pairs.append((a.ct.nodes, b.ct.nodes))

    platform = claimed_platform()

    t_marshal = timed(lambda: mapw.pair_rows(pairs), reps=args.reps)
    lanes, meta = mapw.pair_rows(pairs)

    # device-side digest + one scalar sync (same methodology as the
    # list wave bench: never time a full-batch device->host transfer)
    import jax.numpy as jnp
    from cause_tpu.parallel.wave import _digest_fn

    jhi = jnp.asarray(lanes["hi"])
    jlo = jnp.asarray(lanes["lo"])

    def kernel():
        o, r, v, _c_, ov = mapw.batched_merge_map_weave(lanes)
        hs = jnp.take_along_axis(jhi, o, axis=1)
        ls = jnp.take_along_axis(jlo, o, axis=1)
        d = _digest_fn()(hs, ls, r, v)
        assert not bool(np.asarray(ov.sum()))
        return int(np.asarray(d[0]))

    t_kernel = timed(kernel, reps=args.reps)
    print(json.dumps({
        "metric": f"batched map merge, {B} replica pairs x "
                  f"{args.n_keys} keys + {args.n_edits} edits/side",
        "host_marshal_ms": round(t_marshal, 1),
        "device_kernel_ms": round(t_kernel, 1),
        "capacity": meta["capacity"],
        "platform": platform,
        "unit": "ms",
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-base", type=int, default=9_000)
    ap.add_argument("--n-div", type=int, default=1_000)
    ap.add_argument("--wave", type=int, default=0,
                    help="batched wave of this many replica pairs")
    ap.add_argument("--maps", type=int, default=0,
                    help="batched MAP merge of this many replica pairs")
    ap.add_argument("--n-keys", type=int, default=32)
    ap.add_argument("--n-edits", type=int, default=16)
    ap.add_argument("--burst", type=int, default=8,
                    help="pipelined waves per amortized measurement")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--lazy", action="store_true",
                    help="lazy-weave replicas: skip the per-op host "
                         "weave splice in the edit loop")
    args = ap.parse_args()
    if args.maps:
        map_bench(args)
        return
    if args.wave:
        wave_bench(args)
        return

    platform = claimed_platform()
    for weaver in ("pure", "native", "jax"):
        a, b = build_pair(args.n_base, args.n_div, weaver)
        p50 = timed(lambda: a.merge(b))
        print(json.dumps({
            "metric": f"CausalList.merge {args.n_base}+{args.n_div} nodes",
            "weaver": weaver,
            "value": round(p50, 1),
            "unit": "ms",
        }), flush=True)

        if weaver == "jax":
            from cause_tpu.collections import shared as s
            from cause_tpu.weaver import jaxw
            from cause_tpu.weaver.arrays import NodeArrays

            union = s.union_nodes(a.ct, b.ct)
            t_union = timed(lambda: s.union_nodes(a.ct, b.ct))
            t_marshal = timed(lambda: NodeArrays.from_nodes_map(union.nodes))
            na = NodeArrays.from_nodes_map(union.nodes)
            t_kernel = timed(lambda: jaxw.weave_arrays(na))

            def rebuild():
                rank, _ = jaxw.weave_arrays(na)
                order = np.argsort(rank[: na.capacity], kind="stable")
                return [na.nodes[i] for i in order[: na.n]]

            t_rebuild = timed(rebuild)
            print(json.dumps({
                "metric": "jax merge breakdown",
                "host_union_ms": round(t_union, 1),
                "host_marshal_ms": round(t_marshal, 1),
                "device_weave_ms": round(t_kernel, 1),
                "weave_plus_rebuild_ms": round(t_rebuild, 1),
                "platform": platform,
            }), flush=True)


if __name__ == "__main__":
    main()
