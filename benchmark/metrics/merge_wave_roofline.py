"""merge_wave_roofline: the least time the chip could take for one merge
call, the merge's least bytes (benchmark/roofline.py) over the HBM peak
of benchmark/peaks.json, as a share of the device's busy time per call.
Bytes bound it: the merge does no floating-point work."""

from benchmark import roofline


def read(ctx):
    red, calls = ctx.data.get("trace"), ctx.data.get("calls")
    if not red or not calls or red["busy_s"] <= 0:
        return None
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    least_s = roofline.merge_least_bytes(ctx.data["node_counts"]) / peak
    return 100.0 * least_s / (red["busy_s"] / len(calls))
