"""views_ms.merge: the program's span ``wave.views``, the per-pair lane views
(``lanecache.view_for``), the compatibility rebuilds and the
sampled body spot-check.
Mean per merge call in the traced window, read from the obs ring
(obs is on in the merge cells' traced runs)."""

from benchmark import spans


def read(ctx):
    return spans.ring_ms_per_call("wave.views")
