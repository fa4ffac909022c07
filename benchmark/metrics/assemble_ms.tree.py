"""assemble_ms.tree: the program's span ``tree.assemble`` (each level's
lane batch: full-width rows, or delta windows from the pooled sides),
milliseconds per ``tree.converge`` in the traced window, read from the
obs ring (obs is on in the tree cells' traced runs)."""

from benchmark import tree_ring


def read(ctx):
    return tree_ring.ms_per_converge(ctx, ("tree.assemble",))
