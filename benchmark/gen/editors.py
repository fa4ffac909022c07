"""Seeded editors of one document, as plain node bags.

One shared document (``fleet.document``) and ``n`` editors, each a fresh
site forked from it holding ``ops`` ops it has not yet synced. The mix's
``cursor`` says where they type:

- ``"tail"``: each editor pastes its ops at the document's end, every
  ``hide_every``-th a hide of the value before it
  (``fleet.divergent_side``, chip_smoke's tree replicas; crdt-benchmarks
  B1.1 at B3's many-client concurrency);
- ``"anywhere"``: each editor types runs of ``run_len`` ops at cursors
  drawn from anywhere in the shared document, each op an insert with
  probability ``insert_share``, else a hide of its anchor, never twice
  and never of a hide (the ``edit-b4`` rules of ``edits.Producer``).

Every editor is a ``fleet.Doc`` whose ``nodes`` are its own ops, ids
predicted as the program mints them: a paste by the list's paste rule
(``values`` holds what was pasted), a typed op one lamport tick past the
editor's last (``values`` is empty; the ops go in as a client's minted
nodes).
"""

from __future__ import annotations

import random
from types import SimpleNamespace

from . import edits, fleet


def _typed(rng: random.Random, base: fleet.Doc, ops: int,
           mix: dict) -> fleet.Doc:
    """One editor typing ``ops`` ops by the mix's rules on a fresh site."""
    doc = fleet.Doc(fleet.site_id(rng), base.uuid, [], {}, base.tail,
                    base.lamport, base, base.lamport + 1)
    st = edits.Site(doc, base.lamport)
    prod = edits.Producer(rng.getrandbits(63), mix, [], 1.0)
    on = SimpleNamespace(base=base)
    while len(st.pending) < ops:
        prod.mint_on(on, st)
    doc.nodes = {nid: (cause, v) for nid, cause, v in st.pending[:ops]}
    return doc


def editors(seed: int, n: int, doc_values: int, ops: int, mix: dict):
    """The shared document and ``n`` editors of ``ops`` ops each."""
    rng = random.Random(seed)
    base = fleet.document(rng, doc_values, "x")
    cursor = mix["cursor"]
    if cursor == "tail":
        eds = [fleet.divergent_side(rng, base, f"e{k}", ops,
                                    int(mix["hide_every"]))
               for k in range(n)]
    elif cursor == "anywhere":
        eds = [_typed(rng, base, ops, mix) for _ in range(n)]
    else:
        raise ValueError(f"unknown cursor {cursor!r}")
    return base, eds


def union(base: fleet.Doc, eds) -> dict:
    """The reference's bag: the document plus every editor's ops."""
    out = dict(base.nodes)
    for d in eds:
        out.update(d.nodes)
    return out
