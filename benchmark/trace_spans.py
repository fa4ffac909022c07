"""The reduction of a profiler trace by the program's own spans and by
the kernels' phase scopes, beside ``trace_reduce`` (which it leaves as
it is and reuses):

- ``program_spans``: for each host event named in the span catalogue
  (``benchmark/spans.py``) that starts inside the window, the count,
  the total seconds and the sum of each integer stat (the span's
  attributes, which ``cause_tpu.obs`` hands the profiler);
- ``breakdown.idle_gaps``: idle device time in the window, summed by
  the INNERMOST host span covering each gap's middle, a harness
  annotation or a program span (program spans nest inside the
  harness's annotations and inside each other), else ``other``;
- given a scope map ``{(module, op): scope}`` (``scope_map``, built
  from the optimized HLO text of the modules that ran): each device op
  is named ``<scope>/<op>`` in ``breakdown.device_ops``, and
  ``device_scopes`` is the device time under each scope, the union of
  its ops' intervals, so an op nested in another of the same scope
  counts once. An op's module is the ``XLA Modules`` event around it.
  Without a map the ops keep their HLO short names, as
  ``trace_reduce`` gives them.
"""

from __future__ import annotations

import bisect
import re

from benchmark import trace_reduce as tr

MODULES_LINE = "XLA Modules"
# the phase scopes of weaver/jaxw5.py and weaver/jaxwd.py
SCOPE = re.compile(r"\b(v5\.[A-F]|wd\.digest|wd\.splice)\b")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\b[a-z][\w\-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def module_name(name: str) -> str:
    """``jit_f(7288864446063331437)`` becomes ``jit_f``."""
    return name.split("(", 1)[0]


def hlo_scopes(text: str):
    """``(module, {"%op": scope})`` from one module's optimized HLO text
    (``compiled.as_text()``): each instruction whose ``op_name`` holds
    a phase scope, under the innermost one."""
    lines = text.splitlines()
    module = lines[0].split()[1].rstrip(",") if lines else ""
    ops = {}
    for line in lines:
        m = _INSTR.match(line)
        on = _OP_NAME.search(line) if m else None
        if on:
            found = SCOPE.findall(on.group(1))
            if found:
                ops["%" + m.group(1)] = found[-1]
    return module, ops


def scope_map(texts) -> dict:
    """``{(module, op): scope}`` over several modules' HLO texts."""
    out = {}
    for text in texts:
        module, ops = hlo_scopes(text)
        for op, scope in ops.items():
            out[(module, op)] = scope
    return out


def _stats(ev) -> dict:
    return {k: v for k, v in getattr(ev, "stats", ()) or ()
            if isinstance(v, int) and not isinstance(v, bool)}


def reduce_planes(planes, window: str, annotations, names,
                  scopes=None) -> dict:
    """``planes`` as ``trace_reduce.reduce_planes`` takes them (events
    may carry ``stats``, ``(name, value)`` pairs); ``annotations`` the
    harness's, ``names`` the program's span catalogue."""
    planes = list(planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    devices = [p for p in planes if p.name.startswith(tr.DEVICE_PREFIX)
               and "TPU" in p.name]
    wins, spans = [], []
    program: dict = {}
    for p in host:
        for line in p.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if ev.name == window:
                    wins.append((s, e))
                elif ev.name in annotations or ev.name in names:
                    spans.append((s, e, ev.name, _stats(ev)))
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} '{window}' events in the trace")
    w0, w1 = wins[0]
    if not devices:
        raise ValueError("no device plane in the trace")
    for s, e, name, stats in spans:
        if name in names and w0 <= s < w1:
            agg = program.setdefault(name, {"count": 0, "seconds": 0.0,
                                            "stats": {}})
            agg["count"] += 1
            agg["seconds"] += (e - s) / 1e9
            for k, v in stats.items():
                agg["stats"][k] = agg["stats"].get(k, 0) + v
    spans.sort(key=lambda x: (x[0], -x[1]))
    starts = [s for s, _e, _n, _st in spans]
    reach = []        # the latest end among spans[:i + 1]
    for _s, e, _n, _st in spans:
        reach.append(max(e, reach[-1]) if reach else e)

    def innermost(t):
        # the covering span that started last is the innermost one
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and reach[i] > t:
            if spans[i][1] > t:
                return spans[i][2]
            i -= 1
        return "other"

    scopes = scopes or {}
    ops: dict = {}
    by_scope: dict = {}
    gap_by: dict = {}
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        mods = sorted((float(ev.start_ns), float(ev.start_ns)
                       + float(ev.duration_ns), module_name(ev.name))
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ()))
        mod_starts = [m[0] for m in mods]
        use = {tr.OPS_LINE} if tr.OPS_LINE in lines else None
        iv, scope_iv = [], {}
        for _ln, name, s, d in tr._events(p, use):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            name = tr.op_name(name)
            j = bisect.bisect_right(mod_starts, s) - 1
            module = mods[j][2] if j >= 0 and s < mods[j][1] else ""
            scope = scopes.get((module, name))
            if scope:
                scope_iv.setdefault(scope, []).append((a, b))
                name = scope + "/" + name
            ops[name] = ops.get(name, 0.0) + (b - a)
        for scope, xs in scope_iv.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + sum(
                e - s for s, e in tr.union(xs))
        merged = tr.union(iv)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                label = innermost((gs + ge) / 2)
                gap_by[label] = gap_by.get(label, 0.0) + (ge - gs)
    n_dev = len(devices)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gap_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "program_spans": program,
        "device_scopes": {k: t / n_dev / 1e9
                          for k, t in sorted(by_scope.items())},
        "breakdown": {
            "device_ops": [[n, t / n_dev / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, t / n_dev / 1e9] for n, t in top_gaps],
        },
    }


def reduce_file(path: str, window: str, annotations, names,
                scopes=None) -> dict:
    """Reduce one ``.xplane.pb`` (or a gzipped copy, ``.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return reduce_planes(data.planes, window, annotations, names, scopes)
