"""fetch_ms.merge: the program's span ``wave.fetch``, device to host of rank,
visibility, digest and overflow (its ``bytes`` attribute is their
size).
Mean per merge call in the traced window, read from the obs ring
(obs is on in the merge cells' traced runs)."""

from benchmark import spans


def read(ctx):
    return spans.ring_ms_per_call("wave.fetch")
