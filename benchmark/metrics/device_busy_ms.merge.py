"""device_busy_ms.merge: the union of the device's op intervals in the
traced window, per merge call."""


def read(ctx):
    red, calls = ctx.data.get("trace"), ctx.data.get("calls")
    if not red or not calls or red["busy_s"] <= 0:
        return None
    return 1e3 * red["busy_s"] / len(calls)
