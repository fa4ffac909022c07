"""The program's handles for the generator's described documents, built
as ``chip_smoke.py`` builds them: the document pasted on the pure weaver
and evolved to the device weaver with its lane view attached, each
replica a fresh site pasting its own values on top. Every handle is
checked to hold exactly the ids the generator described."""

from __future__ import annotations

from benchmark.gen import fleet


def value(v):
    """The program's spelling of a generator value."""
    import cause_tpu as c

    return c.hide if v == fleet.HIDE else v


def document(desc: fleet.Doc):
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.weaver import lanecache

    fresh = c.clist(weaver="pure").ct.evolve(uuid=desc.uuid,
                                             site_id=desc.site)
    ct = CausalList(fresh).extend(desc.values).ct
    if set(ct.nodes) - {fleet.ROOT} != set(desc.nodes):
        raise AssertionError(f"document {desc.uuid}: the program's ids "
                             "differ from the generator's")
    return CausalList(ct.evolve(
        weaver="jax", lanes=lanecache.build_view(ct.nodes, ct.uuid)))


def side(base, desc: fleet.Doc):
    """A replica of the program's ``base`` handle on ``desc``'s site."""
    from cause_tpu.collections.clist import CausalList

    h = CausalList(base.ct.evolve(site_id=desc.site)).extend(
        [value(v) for v in desc.values])
    nodes = h.ct.nodes
    if len(nodes) != len(base.ct.nodes) + len(desc.nodes) or \
            any(nid not in nodes for nid in desc.nodes):
        raise AssertionError(f"replica {desc.site}: the program's ids "
                             "differ from the generator's")
    return h
