"""The tree cells' program spans, as ``drivers/tree.py`` collects them
from the obs ring in the traced run: ``tree_span_us``, microseconds
summed by span name, and ``tree_converges``, the ``tree.converge``
spans (one per merge_all call on the tree)."""


def ms_per_converge(ctx, names):
    """Milliseconds of the ``names`` spans per ``tree.converge``. None
    when the ring holds none of them (obs off, or a program without
    the spans)."""
    us, n = ctx.data.get("tree_span_us"), ctx.data.get("tree_converges")
    if not n or not any(k in us for k in names):
        return None
    return sum(us.get(k, 0) for k in names) / 1e3 / n
