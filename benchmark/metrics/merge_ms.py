"""merge_ms: window start to the end of the last whole merge call, over
the calls completed (closed loop; no call starts after --seconds)."""


def read(ctx):
    calls = ctx.data.get("calls")
    if not calls:
        return None
    return 1e3 * (calls[-1][1] - ctx.data["window_t0"]) / len(calls)
