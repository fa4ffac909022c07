"""materialize_ms.tree: the program's spans ``tree.winners`` (a full
level's host handles for its winners) and ``tree.materialize`` (a
subtree's handle built from its window ranks: byes before a full level,
and the root), milliseconds per ``tree.converge`` in the traced window,
read from the obs ring (obs is on in the tree cells' traced runs)."""

from benchmark import tree_ring


def read(ctx):
    return tree_ring.ms_per_converge(ctx, ("tree.winners",
                                           "tree.materialize"))
