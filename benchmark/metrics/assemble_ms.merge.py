"""assemble_ms.merge: the program's own span ``wave.assemble`` (host
assembly of the lanes), mean per wave in the window, obs in memory."""


def read(ctx):
    spans = ctx.data.get("assemble_us")
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3
