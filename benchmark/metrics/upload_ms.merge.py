"""upload_ms.merge: the program's span ``wave.upload``, host to device of the
assembled lanes, ended by blocking on them (its ``bytes``
attribute is their size).
Mean per merge call in the traced window, read from the obs ring
(obs is on in the merge cells' traced runs)."""

from benchmark import spans


def read(ctx):
    return spans.ring_ms_per_call("wave.upload")
