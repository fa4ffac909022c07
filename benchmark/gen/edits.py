"""The seeded open-loop producer for the serve cells.

Copied from ``scripts/serve_soak.py``'s producer (``_SiteState.mint``:
a thin client mints each op in O(1) and never weaves; a refused offer
keeps its ops pending and the next offer of that site re-ships them),
with two changes: the rate is fixed by the mix file (serve_soak
re-calibrates it from its own measurement on every run), and every seed
gets the same arrivals and batch sizes in another order.

Arrival ``k`` is due ``k * interval`` after the window opens, where
``interval`` is the mean run length over the offered op rate. Run
lengths cycle through seeded shuffles of the mix's ``run_len`` list,
tenants through seeded shuffles of all tenants, so the load is the same
on every seed. A batch is one site typing one run:

- ``cursor: "tail"``: appended at the site's own yarn tail (crdt-
  benchmarks B1.1, the delta-wave domain);
- ``cursor: "anywhere"``: the run starts at a value drawn from anywhere
  in the shared document (B4's shape, outside the delta-wave domain).

Each op is an insert with probability ``insert_share``, else a hide of
the op's anchor (the value just typed, or the cursor): never twice, and
never of a hide, since specials do not compose.
"""

from __future__ import annotations

import random

from . import fleet


class Site:
    """One producing site: its lamport clock, yarn tail, and the ops
    minted but not yet admitted."""

    __slots__ = ("site", "side", "ts", "tail", "pending", "hidden",
                 "specials")

    def __init__(self, side: fleet.Doc, ts: int):
        self.site = side.site
        self.side = side
        self.ts = ts
        self.tail = side.tail
        self.pending = []
        self.hidden = set()     # values this site hid
        self.specials = {nid for nid, (_c, v) in side.nodes.items()
                         if v == fleet.HIDE}

    def mint(self, cause, value):
        self.ts += 1
        nid = (self.ts, self.site, 0)
        self.pending.append((nid, cause, value))
        if value == fleet.HIDE:
            self.specials.add(nid)
        return nid


class Tenant:
    """One tenant document held as two replicas (left, right), each the
    shared document plus ``side_values`` of its own site."""

    __slots__ = ("index", "base", "sides", "sites", "admitted")

    def __init__(self, index, base, left, right):
        self.index = index
        self.base = base
        self.sides = (left, right)
        ts = max(left.lamport, right.lamport)
        self.sites = (Site(left, ts), Site(right, ts))
        self.admitted = {}          # id -> (cause, value), all sites

    def nodes(self) -> dict:
        """The reference's bag: the initial replicas plus every
        admitted op (of a tenant whose base document still holds its
        bag; ``tenants()`` makes it again from the seed)."""
        out = self.base.all_nodes()
        for side in self.sides:
            out.update(side.nodes)
        out.update(self.admitted)
        return out


def tenants(seed: int, n: int, doc_values: int, side_values: int,
            hide_every: int):
    rng = random.Random(seed)
    out = []
    for t in range(n):
        base = fleet.document(rng, doc_values, f"s{t}.")
        out.append(Tenant(
            t, base,
            fleet.divergent_side(rng, base, f"L{t}", side_values,
                                 hide_every),
            fleet.divergent_side(rng, base, f"R{t}", side_values,
                                 hide_every)))
    return out


class Producer:
    """The arrival stream of one mix over ``tenants`` at
    ``rate_ops_per_s``. ``next_batch()`` draws the next arrival,
    ``(due_s, tenant, site)``; ``mint_on`` mints its run when it is
    offered."""

    def __init__(self, seed: int, mix: dict, tenants, rate_ops_per_s: float):
        self.rng = random.Random(seed)
        self.tenants = tenants
        self.run_len = [int(x) for x in mix["run_len"]]
        self.insert_share = float(mix["insert_share"])
        self.cursor = mix["cursor"]
        if self.cursor not in ("tail", "anywhere"):
            raise ValueError(f"unknown cursor {self.cursor!r}")
        mean = sum(self.run_len) / len(self.run_len)
        self.interval_s = mean / float(rate_ops_per_s)
        self.k = 0
        self._lens = []
        self._order = []
        self.minted_ops = 0

    def _draw(self, pool, fill):
        if not pool:
            pool.extend(fill)
            self.rng.shuffle(pool)
        return pool.pop()

    def next_batch(self):
        due = self.k * self.interval_s
        self.k += 1
        t = self.tenants[self._draw(self._order,
                                    range(len(self.tenants)))]
        return due, t, t.sites[self.rng.randrange(2)]

    def mint_on(self, t: Tenant, st: Site) -> int:
        """Mint one run of this mix on site ``st`` of tenant ``t``."""
        n_ops = self._draw(self._lens, self.run_len)
        if self.cursor == "tail":
            anchor = st.tail
        else:
            anchor = t.base.value_id(self.rng.randrange(t.base.n))
        for _ in range(n_ops):
            if (self.rng.random() < self.insert_share
                    or anchor in st.hidden or anchor in st.specials):
                anchor = st.mint(anchor, f"{st.site}.{st.ts + 1}")
            else:
                st.hidden.add(anchor)
                st.mint(anchor, fleet.HIDE)
        if self.cursor == "tail":
            st.tail = anchor
        self.minted_ops += n_ops
        return n_ops
