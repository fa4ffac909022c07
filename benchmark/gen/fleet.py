"""Seeded document and fleet shapes, as plain node bags.

Copied from ``chip_smoke.py`` (``document``, ``divergent_side``,
``build_pairs``) and made a function of the seed: site ids and uuids
come from the seed, never from the program's own unseeded minting.

A document is described here, not built: each description carries the
nodes it holds as ``{id: (cause, value)}``, with ids laid out by the
list's paste rule (one lamport tick per run of at most ``MAX_TX_RUN``
values, tx-index order within the run, each value caused by the one
before it, the first by the tail). The drivers hand the values to the
program's ``extend`` and check that the program made exactly these ids;
the plain reference weaves these bags and nothing the program made.
"""

from __future__ import annotations

import random
import string

ROOT = (0, "0", 0)
HIDE = ":hide"          # the tombstone marker; no value string starts with ":"
MAX_TX_RUN = 1 << 13    # values per paste transaction (tx-index domain)

_FIRST = string.ascii_letters + "_"
_REST = string.digits + _FIRST


def uid(rng: random.Random, length: int) -> str:
    """A uid of the program's alphabet (first char never a digit)."""
    return rng.choice(_FIRST) + "".join(rng.choice(_REST)
                                        for _ in range(length - 1))


def site_id(rng: random.Random) -> str:
    return uid(rng, 13)


class Doc:
    """One replica's described state: site, uuid, the values pasted on
    top of its parent, its node bag, its weave tail and lamport clock."""

    __slots__ = ("site", "uuid", "values", "nodes", "tail", "lamport",
                 "parent", "first_ts", "n")

    def __init__(self, site, uuid, values, nodes, tail, lamport, parent,
                 first_ts):
        self.site = site
        self.uuid = uuid
        self.values = values
        self.nodes = nodes
        self.tail = tail
        self.lamport = lamport
        self.parent = parent
        self.first_ts = first_ts
        self.n = len(values)

    def value_id(self, j: int):
        """The id of this replica's ``j``-th pasted value."""
        return (self.first_ts + j // MAX_TX_RUN, self.site, j % MAX_TX_RUN)

    def release(self) -> None:
        """Drop the bag and the values, keeping the ids' layout: the
        harness holds no large object of its own through the window
        (the bag is made again from the seed for the comparison)."""
        self.values = self.nodes = None

    def all_nodes(self) -> dict:
        """The whole bag: the parent's nodes plus this replica's own."""
        out = dict(self.parent.all_nodes()) if self.parent else {}
        out.update(self.nodes)
        return out


def _paste(site, values, tail, lamport):
    nodes = {}
    for start in range(0, len(values), MAX_TX_RUN):
        lamport += 1
        for i, v in enumerate(values[start:start + MAX_TX_RUN]):
            nid = (lamport, site, i)
            nodes[nid] = (tail, v)
            tail = nid
    return nodes, tail, lamport


def document(rng: random.Random, n_values: int, prefix: str) -> Doc:
    """A fresh list of ``n_values`` values past the root."""
    site, uuid = site_id(rng), uid(rng, 21)
    values = [f"{prefix}{i}" for i in range(n_values)]
    nodes, tail, lamport = _paste(site, values, ROOT, 0)
    return Doc(site, uuid, values, nodes, tail, lamport, None, 1)


def divergent_side(rng: random.Random, base: Doc, tag: str, n: int,
                   hide_every: int) -> Doc:
    """A replica of ``base`` on a fresh site with ``n`` divergent values
    pasted at its tail, every ``hide_every``-th one a hide of the value
    before it (chip_smoke's phase: the run ends on a value)."""
    values = [HIDE if j % hide_every == hide_every - 1 else f"{tag}.{j}"
              for j in range(1, n + 1)]
    site = site_id(rng)
    nodes, tail, lamport = _paste(site, values, base.tail, base.lamport)
    return Doc(site, base.uuid, values, nodes, tail, lamport, base,
               base.lamport + 1)


def fleet(seed: int, pairs: int, shared: int, divergent: int,
          hide_every: int):
    """``pairs`` replica pairs of one ``shared``-value document, each
    side ``divergent`` values past it (BASELINE config 5's shape)."""
    rng = random.Random(seed)
    base = document(rng, shared, "x")
    return base, [(divergent_side(rng, base, f"a{p}", divergent, hide_every),
                   divergent_side(rng, base, f"b{p}", divergent, hide_every))
                  for p in range(pairs)]
