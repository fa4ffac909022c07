"""device_idle_pct.tree: 1 - busy / traced window, from the profiler."""


def read(ctx):
    red = ctx.data.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
