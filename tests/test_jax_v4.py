"""Parity suite for the v4 marshal-resolved-cause kernel: v1 (the
direct device port of the pure semantics, itself fuzz-verified against
the pure oracle) is the reference; v4 must reproduce its ranks,
visibility, order, and conflict flags exactly, and flag overflow when
the run budget is exceeded — same contract as test_jax_v3, with the
cause-id lanes (chi, clo) replaced by the concat cause-index lane."""

import random

import numpy as np
import pytest

import jax.numpy as jnp

import cause_tpu as c
from cause_tpu import benchgen
from cause_tpu.benchgen import LANE_KEYS, LANE_KEYS4
from cause_tpu.ids import new_site_id
from cause_tpu.weaver import jaxw, jaxw4
from cause_tpu.weaver.arrays import NodeArrays

from test_list import rand_node

# Heavy differential-fuzz suite: CI runs it as a dedicated job;
# the fast default set keeps tiny-shape coverage in test_jax_smoke.py
pytestmark = pytest.mark.slow


def v1_v4_match(args_v1, args_v4, k_max):
    o1, r1, v1, c1 = jaxw.merge_weave_kernel(*args_v1)
    o4, r4, v4, c4, ovf = jaxw4.merge_weave_kernel_v4(*args_v4, k_max=k_max)
    assert not bool(ovf)
    assert np.array_equal(np.asarray(o1), np.asarray(o4))
    assert np.array_equal(np.asarray(r1), np.asarray(r4))
    assert np.array_equal(np.asarray(v1), np.asarray(v4))
    assert bool(c1) == bool(c4)


def split_args(row):
    return (
        tuple(jnp.asarray(row[k]) for k in LANE_KEYS),
        tuple(jnp.asarray(row[k]) for k in LANE_KEYS4),
    )


def tree_args(cl):
    """v1 and v4 lane tuples for one API-built tree (single tree:
    within-tree cause indices ARE concat indices)."""
    na = NodeArrays.from_nodes_map(cl.ct.nodes)
    hi, lo = na.id_lanes()
    chi, clo = na.cause_lanes()
    a1 = tuple(jnp.asarray(x)
               for x in (hi, lo, chi, clo, na.vclass, na.valid))
    a4 = tuple(jnp.asarray(x)
               for x in (hi, lo, na.cause_idx, na.vclass, na.valid))
    return a1, a4, na


@pytest.mark.parametrize(
    "nb,nd,cap,he",
    [(40, 12, 64, 3), (100, 40, 256, 5), (5, 3, 16, 2), (0, 4, 16, 0),
     (31, 1, 64, 1)],
)
def test_v4_pair_merge_parity(nb, nd, cap, he):
    row = benchgen.divergent_pair_lanes(
        n_base=nb, n_div=nd, capacity=cap, hide_every=he
    )
    a1, a4 = split_args(row)
    v1_v4_match(a1, a4, benchgen.estimate_pair_runs(row) + 8)


def test_v4_fuzz_tree_parity():
    """Random trees with chained specials (hide -> h.show -> hide ...),
    multi-site interleaving, and dangling-adjacent shapes."""
    rng = random.Random(0xBEEF)
    for _ in range(25):
        cl = c.clist(*"ab")
        sites = [new_site_id() for _ in range(3)]
        for _ in range(rng.randrange(3, 25)):
            cl = cl.insert(rand_node(rng, cl, site_id=rng.choice(sites)))
        a1, a4, na = tree_args(cl)
        v1_v4_match(a1, a4, max(8, na.capacity))


def test_v4_concat_of_two_api_trees():
    """The real merge shape: two API-built replicas' lanes concatenated
    with per-tree cause indices offset into concat coordinates —
    duplicates (the shared base) must dedupe and causes must resolve
    through the kept copies."""
    rng = random.Random(7)
    base = c.clist(*"abcdef")
    ra, rb = base, base
    sa, sb = new_site_id(), new_site_id()
    for _ in range(10):
        ra = ra.insert(rand_node(rng, ra, site_id=sa))
        rb = rb.insert(rand_node(rng, rb, site_id=sb))
    cap = 64
    # shared interner territory: both use only root/base + own site, and
    # site ranks must agree across the two marshals for id-sort parity
    from cause_tpu.weaver.arrays import SiteInterner

    sites = {i[1] for i in ra.ct.nodes} | {i[1] for i in rb.ct.nodes}
    it = SiteInterner(sites)
    naa = NodeArrays.from_nodes_map(ra.ct.nodes, capacity=cap, interner=it)
    nab = NodeArrays.from_nodes_map(rb.ct.nodes, capacity=cap, interner=it)

    def cat(xa, xb):
        return jnp.asarray(np.concatenate([xa, xb]))

    hia, loa = naa.id_lanes()
    hib, lob = nab.id_lanes()
    chia, cloa = naa.cause_lanes()
    chib, clob = nab.cause_lanes()
    a1 = (cat(hia, hib), cat(loa, lob), cat(chia, chib),
          cat(cloa, clob), cat(naa.vclass, nab.vclass),
          cat(naa.valid, nab.valid))
    ccia = naa.cause_idx
    ccib = np.where(nab.cause_idx >= 0, nab.cause_idx + cap, -1).astype(
        np.int32
    )
    a4 = (a1[0], a1[1], cat(ccia, ccib), a1[4], a1[5])
    v1_v4_match(a1, a4, 2 * cap)


def test_v4_batched_parity_and_overflow():
    batch = benchgen.batched_pair_lanes(
        n_replicas=6, n_base=40, n_div=12, capacity=64, hide_every=3
    )
    k_max = benchgen.pair_run_budget(batch)
    b1 = tuple(jnp.asarray(batch[k]) for k in LANE_KEYS)
    b4 = tuple(jnp.asarray(batch[k]) for k in LANE_KEYS4)
    o1, r1, v1, c1 = jaxw.batched_merge_weave(*b1)
    o4, r4, v4, c4, ovf = jaxw4.batched_merge_weave_v4(*b4, k_max=k_max)
    assert not np.asarray(ovf).any()
    assert np.array_equal(np.asarray(r1), np.asarray(r4))
    assert np.array_equal(np.asarray(v1), np.asarray(v4))
    assert np.array_equal(np.asarray(o1), np.asarray(o4))
    # a busted budget must flag, not silently corrupt
    *_, ovf = jaxw4.batched_merge_weave_v4(*b4, k_max=4)
    assert np.asarray(ovf).all()


def test_v4_hypothesis_random_interactions():
    """Property: any tree reachable through the public API (random
    conj/insert/hide interleavings across sites) linearizes identically
    under v4 and v1."""
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        st.lists(
            st.tuples(st.integers(0, 2), st.integers(0, 6),
                      st.integers(0, 2)),
            min_size=1, max_size=18,
        )
    )
    def prop(ops):
        cl = c.clist("s")
        sites = ["hypSiteA_____", "hypSiteB_____", "hypSiteC_____"]
        for kind, target, site_i in ops:
            site = sites[site_i]
            nodes = sorted(cl.ct.nodes)
            cause = nodes[target % len(nodes)]
            ts = cl.get_ts() + 1
            if kind == 0:
                value = "v"
            elif kind == 1:
                value = c.hide
            else:
                value = c.h_show
            cl = cl.insert(((ts, site, 0), cause, value))
        a1, a4, na = tree_args(cl)
        v1_v4_match(a1, a4, max(8, na.capacity))

    prop()


def test_v4_conflict_flag():
    """Two lanes sharing an id with different bodies raise the conflict
    flag through v4 exactly as v1."""
    row = benchgen.divergent_pair_lanes(
        n_base=10, n_div=4, capacity=32, hide_every=0
    )
    vc = row["vc"].copy()
    half = len(vc) // 2
    vc[half + 5] = 1  # shared base node, differing body
    a1 = tuple(
        jnp.asarray(x)
        for x in (row["hi"], row["lo"], row["chi"], row["clo"], vc,
                  row["valid"])
    )
    a4 = tuple(
        jnp.asarray(x)
        for x in (row["hi"], row["lo"], row["cci"], vc, row["valid"])
    )
    *_, c1 = jaxw.merge_weave_kernel(*a1)
    _, _, _, c4, _ = jaxw4.merge_weave_kernel_v4(*a4, k_max=64)
    assert bool(c1) and bool(c4)


def test_v4_cci_lane_generation():
    """benchgen's cci lanes actually point at each lane's cause: the
    id at cci must equal the cause id lanes (chi, clo)."""
    row = benchgen.divergent_pair_lanes(
        n_base=12, n_div=5, capacity=32, hide_every=2
    )
    has = row["cci"] >= 0
    ci = row["cci"][has]
    assert np.array_equal(row["hi"][ci], row["chi"][has])
    assert np.array_equal(row["lo"][ci], row["clo"][has])
    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=12, n_div=5, capacity=32, hide_every=2
    )
    flat = {k: batch[k].reshape(-1) for k in batch}
    # per-row cci is row-local; flatten with row offsets for the check
    B, M = batch["hi"].shape
    cci = (batch["cci"] + (np.arange(B) * M)[:, None]).reshape(-1)
    has = flat["cci"].reshape(-1) >= 0
    ci = cci[has]
    assert np.array_equal(flat["hi"][ci], flat["chi"][has])
    assert np.array_equal(flat["lo"][ci], flat["clo"][has])
    fleet = benchgen.fleet_lanes(
        n_replicas=3, n_base=12, n_div=5, capacity=32, hide_every=2
    )
    has = fleet["cci"] >= 0
    ci = fleet["cci"][has]
    assert np.array_equal(fleet["hi"][ci], fleet["chi"][has])
    assert np.array_equal(fleet["lo"][ci], fleet["clo"][has])
