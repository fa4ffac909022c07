"""The plain reference: a causal-tree weave of a node bag.

Independent of ``cause_tpu``: it imports nothing of the program and
reads only the generator's node bags (``{id: (cause, value)}``, ids are
``(lamport, site, tx_index)`` tuples compared as tuples).

The weave is a preorder walk of the causal tree from the root. After a
node come its specials (hides and shows that target it), newest first,
as one block: a special sticks to its target. Then come, newest first,
its other children together with the children of those specials, each
followed by its own subtree. A value is visible unless it is the root,
a special, or directly followed in the weave by a hide that targets it.
Specials do not compose: a special that targets a special is refused.

``sibling_order="ascending"`` is the control: the same walk with each
group in ascending id order, which breaks the convergence guarantee
(concurrent runs land in the wrong order).
"""

from __future__ import annotations

ROOT = (0, "0", 0)
SPECIALS = frozenset((":hide", ":h.hide", ":h.show"))
HIDING = frozenset((":hide", ":h.hide"))


def is_special(value) -> bool:
    return isinstance(value, str) and value in SPECIALS


def weave(nodes: dict, sibling_order: str = "descending") -> list:
    """The woven ids of ``nodes`` (the root first)."""
    if sibling_order not in ("descending", "ascending"):
        raise ValueError(f"unknown sibling order {sibling_order!r}")
    children: dict = {}
    for nid, (cause, _value) in nodes.items():
        children.setdefault(cause, []).append(nid)
    newest_first = sibling_order == "descending"
    special = {nid for nid, (_c, v) in nodes.items() if is_special(v)}
    for nid in special:
        if nodes[nid][0] in special:
            raise ValueError(f"special {nid} targets a special")

    def after(nid):
        """What follows ``nid``, in order: its specials, then its other
        children and its specials' children, each as a subtree."""
        kids = children.get(nid, ())
        glued = sorted((k for k in kids if k in special),
                       reverse=newest_first)
        rest = [k for k in kids if k not in special]
        for s in glued:
            rest.extend(children.get(s, ()))
        return glued, sorted(rest, reverse=newest_first)

    out = []
    stack = [ROOT]
    while stack:
        nid = stack.pop()
        out.append(nid)
        glued, rest = after(nid)
        out.extend(glued)
        stack.extend(reversed(rest))
    if len(out) != len(nodes) + (ROOT not in nodes):
        raise ValueError(f"{len(nodes) + 1 - len(out)} node(s) unreachable "
                         "from the root (a cause is missing)")
    return out


def visible(woven: list, nodes: dict) -> list:
    """Per woven id, whether its value shows."""
    out = []
    for i, nid in enumerate(woven):
        if nid == ROOT or is_special(nodes[nid][1]):
            out.append(False)
            continue
        nxt = woven[i + 1] if i + 1 < len(woven) else None
        hidden = (nxt is not None and nodes[nxt][1] in HIDING
                  and nodes[nxt][0] == nid)
        out.append(not hidden)
    return out


def visible_ids(nodes: dict, sibling_order: str = "descending") -> list:
    """The ids of the visible values, in weave order."""
    woven = weave(nodes, sibling_order)
    return [n for n, v in zip(woven, visible(woven, nodes)) if v]
