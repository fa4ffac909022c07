"""frontier_ms.tree: the program's spans ``tree.establish`` (freezing the
shared prefix after a full level, and each survivor's divergent side)
and ``tree.sides`` (a delta level's host merge of the next level's
sides), milliseconds per ``tree.converge`` in the traced window, read
from the obs ring (obs is on in the tree cells' traced runs)."""

from benchmark import tree_ring


def read(ctx):
    return tree_ring.ms_per_converge(ctx, ("tree.establish", "tree.sides"))
