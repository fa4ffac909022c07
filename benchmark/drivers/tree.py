"""The many-editors driver: ``cause_tpu.merge_all(*replicas)``, closed
loop, over ``editors`` replicas of one document built from the seed
(``benchmark/gen/editors.py``), each a fresh site holding the ops it has
not yet synced. The program routes the fleet through its merge reduction
tree (``cause_tpu/parallel/tree.py``).

Replicas are built through the program's public API, as
``benchmark/build.py`` builds them: pasted ops through ``extend``, typed
ops as the serve path applies a client's minted nodes
(``serde.decode_node_items`` then ``sync.apply_delta``). Each replica is
checked to hold exactly the generator's ids.

Records for the readers (``ctx.data``): ``calls`` (start, end) per whole
call in the window and ``window_t0``, as the merge driver records them;
in the traced run (obs on), ``tree_span_us`` (the window's ``tree.*``
spans, microseconds summed by name), ``tree_converges`` (how many
``tree.converge`` spans the ring holds) and ``tree_levels`` (``nodes``
and ``merged`` of each ``tree.level`` span that carries them).

The check compares what the window's calls produced, each with limit 0:
``root_wrong``, the last call's weave ids and visible values against the
plain reference's weave of the generator's bag; ``roots_unstable``,
calls whose weave ids differ from the first call's; ``calls_off_tree``,
one more call after the window, with obs on, that shows no
``tree.converge`` span or counts a ``tree.fallback`` (the host
``merge_many`` ran instead).
"""

from __future__ import annotations

import time

from benchmark import build
from benchmark.gen import editors, fleet
from benchmark.reference import weave as ref


def _generate(ctx):
    cfg = ctx.config
    return editors.editors(ctx.seed, cfg["editors"], cfg["doc_values"],
                           cfg["ops_per_editor"], ctx.mix)


def _replica(doc, desc: fleet.Doc):
    """The program's replica of ``doc`` on ``desc``'s site holding its
    ops: pasted through ``extend``, or typed ops applied as the serve
    path applies a client's minted nodes."""
    from cause_tpu import serde, sync
    from cause_tpu.collections.clist import CausalList

    if desc.values:
        return build.side(doc, desc)
    items = serde.encode_node_items(
        {nid: (cause, build.value(v)) for nid, (cause, v)
         in desc.nodes.items()})
    h = sync.apply_delta(CausalList(doc.ct.evolve(site_id=desc.site)),
                         serde.decode_node_items(items))
    nodes = h.ct.nodes
    if len(nodes) != len(doc.ct.nodes) + len(desc.nodes) or \
            any(nid not in nodes for nid in desc.nodes):
        raise AssertionError(f"replica {desc.site}: the program's ids "
                             "differ from the generator's")
    return h


def setup(ctx):
    t0 = time.perf_counter()
    base, eds = _generate(ctx)
    doc = build.document(base)
    replicas = [_replica(doc, d) for d in eds]
    if ctx.trace:
        from cause_tpu import obs

        obs.configure(enabled=True, reset=True)  # the warm-up warms obs too
    ctx.note(editors=len(replicas),
             nodes_per_replica=len(replicas[0].ct.nodes),
             build_s=f"{time.perf_counter() - t0:.3f}")
    # the bags are made again from the seed for the comparison: the
    # harness holds no large object of its own through the window
    return {"replicas": replicas}


def _call(replicas):
    import cause_tpu as c

    return c.merge_all(*replicas)


def warmup(state, ctx):
    t0 = time.perf_counter()
    _call(state["replicas"])
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    _call(state["replicas"])
    ctx.note(first_call_s=f"{first:.3f}",
             second_call_s=f"{time.perf_counter() - t0:.3f}")
    ctx.data["first_call_s"] = first


def window(state, ctx):
    import jax

    replicas = state["replicas"]
    calls, weaves = [], []
    root = None
    if ctx.trace:
        from cause_tpu import obs

        obs.configure(enabled=True, reset=True)  # the window's spans only
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        while time.perf_counter() < t_end:
            s = time.perf_counter()
            with jax.profiler.TraceAnnotation("merge_all"):
                root = _call(replicas)
            calls.append((s, time.perf_counter()))
            weaves.append(root.ct.weave)
    ctx.data["window_t0"] = t0
    ctx.data["calls"] = calls
    ctx.data["attempted"] = len(calls)
    state["weaves"] = weaves
    state["root"] = root
    if ctx.trace:
        _collect_spans(ctx)
    each = sorted(1e3 * (e - s) for s, e in calls)
    ctx.note(calls=len(calls), window_s=f"{calls[-1][1] - t0:.3f}",
             merge_ms=f"{1e3 * (calls[-1][1] - t0) / len(calls):.3f}",
             call_ms_min_p50_max="/".join(
                 f"{x:.1f}" for x in (each[0], each[len(each) // 2],
                                      each[-1])))


def _collect_spans(ctx):
    """The window's ``tree.*`` spans from the obs ring; the first call's
    level paths, times and token budgets, and the window's counters, as
    notes."""
    from cause_tpu import obs

    us, levels, paths, budgets = {}, [], [], []
    converges = 0
    for e in obs.events():
        name = e.get("name", "")
        if e.get("ev") != "span" or not name.startswith("tree."):
            continue
        us[name] = us.get(name, 0) + e["dur_us"]
        attrs = e.get("attrs") or {}
        if name == "tree.converge":
            converges += 1
        elif name == "tree.dispatch" and converges == 0:
            budgets.append(str(attrs.get("u_max")))
        elif name == "tree.level":
            if converges == 0:
                paths.append(f"{attrs.get('path')}:{attrs.get('pairs')}:"
                             f"{e['dur_us'] / 1e3:.1f}ms")
            if "nodes" in attrs and "merged" in attrs:
                levels.append((attrs["nodes"], attrs["merged"]))
    counters = obs.counters_snapshot()["counters"]
    ctx.data.update(tree_span_us=us, tree_converges=converges,
                    tree_levels=levels)
    ctx.note(converge_spans=converges, first_call_levels=",".join(paths),
             first_call_u_max=",".join(budgets),
             tree_counters=",".join(f"{k}={v}" for k, v
                                    in sorted(counters.items())
                                    if k.startswith(("tree.", "wave."))))
    if converges:
        ctx.note(span_ms_per_call=",".join(
            f"{k}={v / 1e3 / converges:.1f}" for k, v in sorted(us.items())))


def _shown(woven, nodes):
    """(woven ids, the visible (id, value) pairs) of a reference weave."""
    return woven, [(n, nodes[n][1]) for n, v
                   in zip(woven, ref.visible(woven, nodes)) if v]


def _off_tree(state, ctx) -> int:
    """1 when one more call, with obs on, shows no ``tree.converge``, or
    counts a ``tree.fallback``: the tree ran, gave up, and ``merge_all``
    finished on the host. A program without that counter reads 0."""
    from cause_tpu import obs

    obs.configure(enabled=True, reset=True)
    _call(state["replicas"])
    seen = any(e.get("ev") == "span" and e.get("name") == "tree.converge"
               for e in obs.events())
    fell_back = obs.counters_snapshot()["counters"].get("tree.fallback", 0)
    obs.configure(enabled=False, reset=True)
    return 0 if seen and not fell_back else 1


def check(state, ctx, control=False):
    t0 = time.perf_counter()
    base, eds = _generate(ctx)
    nodes = editors.union(base, eds)
    want = _shown(ref.weave(nodes), nodes)
    if control:
        got = _shown(ref.weave(nodes, "ascending"), nodes)
    else:
        root = state["root"]
        got = ([n[0] for n in root.ct.weave], [(n[0], n[2]) for n in root])
    first = [n[0] for n in state["weaves"][0]]
    ids = [[n[0] for n in w] for w in state["weaves"]]
    unstable = sum(x != first for x in ids[1:])
    wrong_calls = sum(x != want[0] for x in ids[:-1]) + (got != want)
    ctx.data["failed"] = wrong_calls
    off = _off_tree(state, ctx)
    ctx.note(nodes_compared=len(want[0]), calls_compared=len(ids),
             compare_s=f"{time.perf_counter() - t0:.3f}")
    return {"root_wrong": (int(got != want), 0),
            "roots_unstable": (int(unstable), 0),
            "calls_off_tree": (off, 0)}


def close(state, ctx):
    state.clear()
