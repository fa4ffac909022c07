"""The reduction from a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's ``ProfileData``:

- the window is the host event named ``window`` (the harness wraps its
  measured window in a ``TraceAnnotation`` of that name);
- per device plane (``/device:TPU:<n>``), busy time is the union of the
  intervals in which an operation ran (the ``XLA Ops`` line, else every
  line of the plane), clipped to the window; ``busy_s`` is its mean
  over the device planes;
- ``breakdown.device_ops``: the ten ops (HLO short names) that took the
  most device time in the window;
- ``breakdown.idle_gaps``: idle device time in the window, summed by
  what the host was doing: the harness annotation (one of
  ``annotations``) that covers the middle of each gap, else ``other``.
"""

from __future__ import annotations

import bisect
import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(name: str) -> str:
    """An HLO op's short name: ``%fusion.12 = s32[...] fusion(...)``
    becomes ``%fusion.12``."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def _events(plane, line_names=None):
    for line in plane.lines:
        if line_names is None or line.name in line_names:
            for ev in line.events:
                yield line.name, ev.name, float(ev.start_ns), \
                    float(ev.duration_ns)


def reduce_planes(planes, window: str, annotations) -> dict:
    """``planes``: objects with ``name`` and ``lines`` (each with
    ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``),
    as ``ProfileData`` gives them."""
    planes = list(planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)
               and "TPU" in p.name]
    wins, spans = [], []
    for p in host:
        for _ln, name, s, d in _events(p):
            if name == window:
                wins.append((s, s + d))
            elif name in annotations:
                spans.append((s, s + d, name))
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} '{window}' events in the trace")
    w0, w1 = wins[0]
    if not devices:
        raise ValueError("no device plane in the trace")
    ops: dict = {}
    busy_per_dev = []
    gap_by: dict = {}
    n_events = 0
    # the harness's annotations follow one another and never nest, so
    # the one covering a point is the last to start before it
    spans.sort()
    starts = [s for s, _e, _n in spans]
    for p in devices:
        names = {ln.name for ln in p.lines}
        use = {OPS_LINE} if OPS_LINE in names else None
        iv = []
        for _ln, name, s, d in _events(p, use):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            n_events += 1
            iv.append((a, b))
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + (b - a)
        merged = union(iv)
        busy_per_dev.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = spans[i][2] if i >= 0 and mid < spans[i][1] \
                else "other"
            gap_by[label] = gap_by.get(label, 0.0) + (ge - gs)
    n_dev = len(devices)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gap_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_per_dev) / n_dev / 1e9,
        "n_devices": n_dev,
        "n_events": n_events,
        "spans": {n: sum(1 for *_x, m in spans if m == n)
                  for n in annotations},
        "breakdown": {
            "device_ops": [[n, t / n_dev / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, t / n_dev / 1e9] for n, t in top_gaps],
        },
    }


def reduce_file(path: str, window: str, annotations) -> dict:
    """Reduce one ``.xplane.pb`` (or a gzipped copy, ``.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return reduce_planes(data.planes, window, annotations)


def reduce_dir(trace_dir: str, window: str, annotations) -> dict:
    return reduce_file(find_xplane(trace_dir), window, annotations)
