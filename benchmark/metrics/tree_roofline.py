"""tree_roofline: the least time the chip could take for the kernels of
one merge_all call, as a share of the device's busy time per call.

The least time is the least bytes over the HBM peak of
benchmark/peaks.json, with the merge's per-node byte counts of
benchmark/roofline.py: over the ``tree.level`` spans of the traced
window, ``nodes`` (the real lanes both sides of each pair read) times an
id and a cause, plus ``merged`` (the real lanes of the level's unions)
times a rank and a visibility; per ``tree.converge``. It is the share of
the kernels the tree runs (the v5 kernel with its digest at a full
level, the delta window weave at a delta level). Bytes bound it: the
merge does no floating-point work. None where the spans carry no node
counts."""

from benchmark import roofline


def read(ctx):
    red, calls = ctx.data.get("trace"), ctx.data.get("calls")
    levels, n = ctx.data.get("tree_levels"), ctx.data.get("tree_converges")
    if not red or not calls or red["busy_s"] <= 0 or not levels or not n:
        return None
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    least = sum(nodes * (roofline.ID_BYTES + roofline.CAUSE_BYTES)
                + merged * roofline.OUT_BYTES for nodes, merged in levels)
    return 100.0 * least / n / peak / (red["busy_s"] / len(calls))
