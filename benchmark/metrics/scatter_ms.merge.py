"""scatter_ms.merge: the program's span ``wave.scatter``, the per-pair
fill of the wave's result arrays and the ``WaveResult``.
Mean per merge call in the traced window, read from the obs ring
(obs is on in the merge cells' traced runs)."""

from benchmark import spans


def read(ctx):
    return spans.ring_ms_per_call("wave.scatter")
