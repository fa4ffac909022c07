"""The batch-merge driver: ``cause_tpu.parallel.merge_wave(pairs)``,
closed loop, on a fleet of device-weaver replica pairs built from the
seed (``benchmark/build.py``).

Records for the readers (``ctx.data``): ``calls`` (start, end) per
whole call in the window, ``window_t0``, ``first_call_s``, the fleet's
node counts, and, in the traced run, the ``wave.assemble`` spans.

The check compares what the window's calls produced: every row's digest
must be valid and the same on every call; a sample of rows drawn from
the seed (``SAMPLE_ROWS``) must weave, order and visibility both,
as the plain reference weaves the generator's node bags.
"""

from __future__ import annotations

import random
import time

import numpy as np

from benchmark import build
from benchmark.gen import fleet
from benchmark.reference import weave as ref

SAMPLE_ROWS = 64    # rows the reference weaves after the window (4-7 s)


def setup(ctx):
    cfg, mix = ctx.config, ctx.mix
    if mix["shared_values"] + mix["divergent_values"] + 1 != cfg["doc_nodes"]:
        raise ValueError("the mix's document size is not the config's")
    t0 = time.perf_counter()
    base, desc = fleet.fleet(ctx.seed, cfg["pairs"], mix["shared_values"],
                             mix["divergent_values"], mix["hide_every"])
    doc = build.document(base)
    pairs = [(build.side(doc, a), build.side(doc, b)) for a, b in desc]
    if ctx.trace:
        from cause_tpu import obs

        obs.configure(enabled=True, reset=True)  # the warm-up warms obs too
    ctx.note(pairs=len(pairs), nodes_per_replica=len(pairs[0][0].ct.nodes),
             build_s=f"{time.perf_counter() - t0:.3f}")
    ctx.data["node_counts"] = [
        (len(base.nodes) + len(a.nodes) + 1,
         len(base.nodes) + len(b.nodes) + 1,
         len(base.nodes) + len(a.nodes) + len(b.nodes) + 1)
        for a, b in desc]
    # the bags are made again from the seed for the comparison: the
    # harness holds no large object of its own through the window
    return {"pairs": pairs}


def _call(pairs):
    from cause_tpu.parallel import merge_wave

    return merge_wave(pairs)


def warmup(state, ctx):
    t0 = time.perf_counter()
    res = _call(state["pairs"])
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    _call(state["pairs"])
    ctx.note(first_call_s=f"{first:.3f}",
             second_call_s=f"{time.perf_counter() - t0:.3f}",
             kernel=res.kernel, capacity=res.capacity,
             fallback=len(res.fallback), poisoned=len(res.poisoned))
    ctx.data["first_call_s"] = first


def window(state, ctx):
    import jax

    pairs = state["pairs"]
    calls = []
    digests = []
    res = None
    if ctx.trace:
        from cause_tpu import obs

        obs.configure(enabled=True, reset=True)  # the window's spans only
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        while time.perf_counter() < t_end:
            s = time.perf_counter()
            with jax.profiler.TraceAnnotation("merge_wave"):
                res = _call(pairs)
            calls.append((s, time.perf_counter()))
            digests.append((np.array(res.digest), np.array(res.digest_valid),
                            len(res.fallback) + len(res.poisoned)))
    ctx.data["window_t0"] = t0
    ctx.data["calls"] = calls
    state["digests"] = digests
    state["result"] = res
    if ctx.trace:
        from cause_tpu import obs

        ctx.data["assemble_us"] = [
            e["dur_us"] for e in obs.events()
            if e.get("ev") == "span" and e.get("name") == "wave.assemble"]
    n_pairs = len(pairs)
    ctx.data["attempted"] = len(calls) * n_pairs
    ctx.data["failed"] = sum(bad for _d, _v, bad in digests)
    each = sorted(1e3 * (e - s) for s, e in calls)
    ctx.note(calls=len(calls), window_s=f"{calls[-1][1] - t0:.3f}",
             merge_ms=f"{1e3 * (calls[-1][1] - t0) / len(calls):.3f}",
             call_ms_min_p50_max="/".join(
                 f"{x:.1f}" for x in (each[0], each[len(each) // 2],
                                      each[-1])))


def _program_rows(state, rows):
    """(woven ids, visibility) per row as the window's last call
    produced them: the weave through ``WaveResult.merged(i)``, the
    visibility from the kernel's lanes."""
    from cause_tpu.weaver import lanecache

    res = state["result"]
    out = {}
    for i in rows:
        woven = [n[0] for n in res.merged(i).ct.weave]
        a, b = state["pairs"][i]
        va, vb = lanecache.view_for(a.ct), lanecache.view_for(b.ct)
        cap = res.capacity
        rank = res.rank[i]
        keep = np.flatnonzero(rank < 2 * cap)
        order = keep[np.argsort(rank[keep], kind="stable")]
        lane_ids = [(va.arena.nodes[j] if j < cap else
                     vb.arena.nodes[j - cap])[0] for j in order]
        vis = dict(zip(lane_ids, res.visible[i][order].tolist()))
        out[i] = (woven, [bool(vis.get(n, False)) for n in woven])
    return out


def _reference_rows(ctx, rows, sibling_order):
    cfg, mix = ctx.config, ctx.mix
    base, desc = fleet.fleet(ctx.seed, cfg["pairs"], mix["shared_values"],
                             mix["divergent_values"], mix["hide_every"])
    out = {}
    base_nodes = base.nodes
    for i in rows:
        a, b = desc[i]
        nodes = dict(base_nodes)
        nodes.update(a.nodes)
        nodes.update(b.nodes)
        woven = ref.weave(nodes, sibling_order)
        out[i] = (woven, ref.visible(woven, nodes))
    return out


def check(state, ctx, control=False):
    n = len(state["pairs"])
    rng = random.Random(ctx.seed ^ 0x5EED)
    rows = sorted(rng.sample(range(n), min(n, SAMPLE_ROWS)))
    t0 = time.perf_counter()
    want = _reference_rows(ctx, rows, "descending")
    if control:
        got = _reference_rows(ctx, rows, "ascending")
    else:
        got = _program_rows(state, rows)
    rows_wrong = sum(got[i] != want[i] for i in rows)
    d0, v0, _bad = state["digests"][0]
    unstable = ~v0
    for d, v, _bad in state["digests"][1:]:
        unstable |= ~v | (d != d0)
    ctx.note(rows_compared=len(rows), compare_s=f"{time.perf_counter() - t0:.3f}")
    return {"rows_wrong": (int(rows_wrong), 0),
            "rows_unstable": (int(unstable.sum()), 0)}


def close(state, ctx):
    state.clear()
