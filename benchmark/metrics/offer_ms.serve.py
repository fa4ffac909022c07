"""offer_ms.serve: host clock around IngestQueue.offer (admission and the
write-ahead journal), mean per batch offered in the window."""


def read(ctx):
    xs = ctx.data.get("offer_s")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
