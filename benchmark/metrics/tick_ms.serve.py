"""tick_ms.serve: host clock around SyncService.tick(), mean per tick in
the window that drained ops."""


def read(ctx):
    xs = ctx.data.get("tick_s")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
