"""Parity suite for the v5 segment-union kernel: v1 remains the device
reference (itself fuzz-verified against the pure oracle). v5 reports
rank/visibility in CONCAT lane coordinates, so v1's sorted-lane outputs
are mapped through its own order permutation before comparing."""

import random

import numpy as np
import pytest

import jax.numpy as jnp

import cause_tpu as c
from cause_tpu import benchgen
from cause_tpu.benchgen import LANE_KEYS, LANE_KEYS4, LANE_KEYS5
from cause_tpu.ids import new_site_id
from cause_tpu.weaver import jaxw, jaxw5
from cause_tpu.weaver.arrays import NodeArrays, SiteInterner

from test_list import rand_node

# The heavy differential fuzz is marked slow (CI runs it as a dedicated
# job; the fast default set keeps tiny-shape coverage in
# test_jax_smoke.py); the run-width cases at the end are fast.


def v1_concat(args_v1):
    """v1 outputs mapped to concat-lane coordinates."""
    o1, r1, v1, c1 = jaxw.merge_weave_kernel(*args_v1)
    o1, r1, v1 = np.asarray(o1), np.asarray(r1), np.asarray(v1)
    N = o1.shape[0]
    rank_c = np.full(N, N, np.int32)
    vis_c = np.zeros(N, bool)
    rank_c[o1] = r1
    vis_c[o1] = v1
    return rank_c, vis_c, bool(c1)


def run_v5(v5row, u_max=None, k_max=None):
    N = v5row["hi"].shape[0]
    if u_max is None:
        u_max = max(8, benchgen.estimate_tokens(v5row) + 8)
    if k_max is None:
        k_max = u_max
    args = tuple(jnp.asarray(v5row[k]) for k in LANE_KEYS5)
    rank, vis, conf, ovf = jaxw5.merge_weave_kernel_v5(
        *args, u_max=u_max, k_max=k_max
    )
    assert not bool(ovf), "unexpected overflow"
    return np.asarray(rank), np.asarray(vis), bool(conf)


def check_row(row, capacity):
    """row: concatenated LANE_KEYS(+cci) dict; compare v5 vs v1."""
    v5row = benchgen.v5_inputs(row, capacity)
    a1 = tuple(jnp.asarray(row[k]) for k in LANE_KEYS)
    rank_c, vis_c, c1 = v1_concat(a1)
    # v1 ranks duplicate lanes at N while v5 may keep the OTHER copy
    # of a twin (v1 keeps the first *sorted* duplicate, v5 the first
    # copy of the twin group — same id, same body, same weave). The
    # weave itself must agree: compare the (rank -> lane id) maps over
    # kept lanes and the visible id multisets.
    r5, v5_, c5 = run_v5(v5row)
    N = rank_c.shape[0]

    def weave_ids(rank, hi, lo):
        kept = rank < N
        out = sorted(zip(rank[kept], hi[kept], lo[kept]))
        return [(h, l) for _, h, l in out]

    assert weave_ids(rank_c, row["hi"], row["lo"]) == weave_ids(
        r5, row["hi"], row["lo"]
    )

    def vis_ids(vis, hi, lo, rank):
        return sorted((int(r), int(h), int(l))
                      for r, h, l, v in zip(rank, hi, lo, vis) if v)

    assert vis_ids(vis_c, row["hi"], row["lo"], rank_c) == vis_ids(
        v5_, row["hi"], row["lo"], r5
    )
    return c1, c5


@pytest.mark.slow
@pytest.mark.parametrize(
    "nb,nd,cap,he",
    [(40, 12, 64, 3), (100, 40, 256, 5), (5, 3, 16, 2), (0, 4, 16, 0),
     (31, 1, 64, 1), (200, 1, 256, 0)],
)
def test_v5_pair_merge_parity(nb, nd, cap, he):
    row = benchgen.divergent_pair_lanes(
        n_base=nb, n_div=nd, capacity=cap, hide_every=he
    )
    check_row(row, cap)


@pytest.mark.slow
def test_v5_wholesale_dedupe_actually_happens():
    """The point of v5: the shared base must ride as one token, not
    explode — token estimate for a large-base pair stays divergence-
    sized."""
    row = benchgen.divergent_pair_lanes(
        n_base=4000, n_div=32, capacity=4096, hide_every=4
    )
    v5row = benchgen.v5_inputs(row, 4096)
    n_tok = benchgen.estimate_tokens(v5row)
    assert n_tok < 4000, n_tok  # divergence-sized, not base-sized
    check_row(row, 4096)


def tree_row(cl, cap=None):
    """Single-tree concat row (one tree) from an API-built list."""
    na = NodeArrays.from_nodes_map(cl.ct.nodes, capacity=cap)
    hi, lo = na.id_lanes()
    chi, clo = na.cause_lanes()
    return {
        "hi": hi, "lo": lo, "chi": chi, "clo": clo,
        "cci": na.cause_idx, "vc": na.vclass, "valid": na.valid,
    }, na.capacity


def concat_api_rows(handles, cap):
    """Concat-row dict for K API-built replicas: shared interner,
    per-tree NodeArrays at ``cap`` lanes, cci block-offsets."""
    sites = set()
    for h in handles:
        sites |= {i[1] for i in h.ct.nodes}
    it = SiteInterner(sites)
    nas = [NodeArrays.from_nodes_map(h.ct.nodes, capacity=cap, interner=it)
           for h in handles]

    def cat(pick):
        return np.concatenate([pick(na) for na in nas])

    return {
        "hi": cat(lambda na: na.id_lanes()[0]),
        "lo": cat(lambda na: na.id_lanes()[1]),
        "chi": cat(lambda na: na.cause_lanes()[0]),
        "clo": cat(lambda na: na.cause_lanes()[1]),
        "cci": np.concatenate([
            np.where(na.cause_idx >= 0, na.cause_idx + i * cap, -1).astype(
                np.int32)
            for i, na in enumerate(nas)
        ]),
        "vc": cat(lambda na: na.vclass),
        "valid": cat(lambda na: na.valid),
    }


@pytest.mark.slow
def test_v5_fuzz_tree_parity():
    rng = random.Random(0x5E6)
    for _ in range(30):
        cl = c.clist(*"ab")
        sites = [new_site_id() for _ in range(3)]
        for _ in range(rng.randrange(3, 25)):
            cl = cl.insert(rand_node(rng, cl, site_id=rng.choice(sites)))
        row, cap = tree_row(cl)
        check_row(row, cap)


@pytest.mark.slow
def test_v5_concat_of_two_api_trees():
    rng = random.Random(77)
    base = c.clist(*"abcdef")
    ra, rb = base, base
    sa, sb = new_site_id(), new_site_id()
    for _ in range(12):
        ra = ra.insert(rand_node(rng, ra, site_id=sa))
        rb = rb.insert(rand_node(rng, rb, site_id=sb))
    check_row(concat_api_rows([ra, rb], 64), 64)


@pytest.mark.slow
def test_v5_hypothesis_random_interactions():
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
        row, cap = tree_row(cl)
        check_row(row, cap)

    prop()


@pytest.mark.slow
def test_v5_batched_parity_and_overflow():
    B, cap = 5, 64
    batch = benchgen.batched_pair_lanes(
        n_replicas=B, n_base=30, n_div=9, capacity=cap, hide_every=2
    )
    rows = [{k: batch[k][i] for k in LANE_KEYS4 + ("chi", "clo")}
            for i in range(B)]
    v5rows = [benchgen.v5_inputs(r, cap) for r in rows]
    s_max = max(v["sg_len"].shape[0] for v in v5rows)
    v5rows = [benchgen.v5_inputs(r, cap, s_max=s_max) for r in rows]
    u_max = max(benchgen.estimate_tokens(v) for v in v5rows) + 8
    stacked = {
        k: np.stack([v[k] for v in v5rows]) for k in LANE_KEYS5
    }
    args = tuple(jnp.asarray(stacked[k]) for k in LANE_KEYS5)
    rank, vis, conf, ovf = jaxw5.batched_merge_weave_v5(
        *args, u_max=u_max, k_max=u_max
    )
    assert not np.asarray(ovf).any()
    for i in range(B):
        a1 = tuple(jnp.asarray(rows[i][k]) for k in LANE_KEYS)
        rank_c, vis_c, _ = v1_concat(a1)
        N = rank_c.shape[0]

        def widx(rank, vism):
            kept = rank < N
            return (sorted(zip(rank[kept], rows[i]["hi"][kept],
                               rows[i]["lo"][kept])),
                    sorted(zip(rank[vism], rows[i]["hi"][vism])))

        assert widx(rank_c, vis_c) == widx(
            np.asarray(rank[i]), np.asarray(vis[i])
        )
    # busted token budget flags, never corrupts silently
    *_, ovf = jaxw5.batched_merge_weave_v5(*args, u_max=8, k_max=8)
    assert np.asarray(ovf).any()


@pytest.mark.slow
def test_v5_conflict_flag():
    """Dup tokens with differing bodies flag a conflict (exploded
    regions only — wholesale-deduped twins are exempt by design)."""
    row = benchgen.divergent_pair_lanes(
        n_base=10, n_div=4, capacity=32, hide_every=2
    )
    # corrupt a node in the *divergent* region of side B to collide
    # with a side-A suffix id but differ in body: give B a node with
    # A's suffix id and a different vclass
    cap = 32
    ia = 1 + 10 + 1          # a suffix-A lane
    ib = cap + 1 + 10 + 2    # a suffix-B lane
    row["hi"][ib] = row["hi"][ia]
    row["lo"][ib] = row["lo"][ia]
    row["vc"][ib] = 1 - (row["vc"][ia] & 1)
    v5row = benchgen.v5_inputs(row, cap)
    _, _, conf = run_v5(v5row, u_max=80, k_max=80)
    assert conf


@pytest.mark.slow
def test_v5_three_way_union_parity():
    """K-ary union: three replicas' lanes concatenated — twin groups of
    three (the shared base), multi-interval overlaps, and cross-replica
    causes must all resolve exactly as v1."""
    from cause_tpu.collections.clist import CausalList

    rng = random.Random(31337)
    base = c.clist(*"abcde")
    reps = []
    for _ in range(3):
        r = CausalList(base.ct.evolve(site_id=new_site_id()))
        for _ in range(8):
            r = r.insert(rand_node(rng, r, site_id=r.ct.site_id))
        reps.append(r)
    check_row(concat_api_rows(reps, 32), 32)


@pytest.mark.slow
def test_v5_adversarial_replica_fuzz():
    """Directed fuzz of the segment-union edge logic: random replica
    counts (2-4), random shared-prefix lengths, random multi-site
    interleavings with tombstone chains — every case must match v1
    exactly. Targets E1 overlap shapes, twin groups of every size, and
    cross-replica cause stabs the corpus tests don't enumerate."""
    from cause_tpu.collections.clist import CausalList

    rng = random.Random(0xD1CE)
    for case in range(40):
        n_rep = rng.randrange(2, 5)
        base = c.clist(*[f"b{i}" for i in range(rng.randrange(1, 12))])
        reps = []
        for _ in range(n_rep):
            r = CausalList(base.ct.evolve(site_id=new_site_id()))
            sites = [r.ct.site_id, new_site_id()]
            for _ in range(rng.randrange(0, 10)):
                r = r.insert(
                    rand_node(rng, r, site_id=rng.choice(sites))
                )
            reps.append(r)
        cap = 64
        check_row(concat_api_rows(reps, cap), cap)


# ------------------------------------------------------ run-width ladder
#
# The batched entry points rank the runs at the smallest width of
# ``jaxw5.run_widths(k_max)`` that holds the batch's largest run count.
# Every case below checks the batched body against the row kernel at
# the fixed ``k_max`` (bit for bit, overflow included) and against the
# pure weaver, at one shape: two trees of CAP lanes a row, ladder 128,
# 256, 512.

from functools import lru_cache, partial  # noqa: E402

import jax  # noqa: E402

from cause_tpu.collections.clist import CausalList  # noqa: E402

CAP, S_MAX, U_LADDER = 256, 512, 512


@lru_cache(maxsize=None)
def _fixed_kernel(u_max, k_max):
    return jax.jit(jax.vmap(partial(jaxw5.merge_weave_kernel_v5,
                                    u_max=u_max, k_max=k_max)))


def _fork(base):
    return CausalList(base.ct.evolve(site_id=new_site_id()))


def _doc(n):
    return c.clist(*[f"d{i}" for i in range(n)])


def _tail_pastes(base, n, hide_every=8):
    """A replica pasting ``n`` values at the end, every
    ``hide_every``-th a hide of the value before it."""
    h = _fork(base)
    for i in range(1, n + 1):
        h = h.conj(c.hide if i % hide_every == 0 else f"p{i}")
    return h


def _typed_anywhere(base, ops, rng):
    """A replica typing ``ops`` ops in runs of 1-3 at cursors anywhere
    in the document: 70% inserts (each after the last), 30% hides of
    the cursor's value, never twice."""
    h = _fork(base)
    ids = [n[0] for n in base.ct.weave][1:]
    hidden = set()
    done = 0
    while done < ops:
        cursor = rng.choice(ids)
        for _ in range(rng.randint(1, 3)):
            if rng.random() < 0.3 and cursor in ids \
                    and cursor not in hidden:
                hidden.add(cursor)
                h = h.append(cursor, c.hide)
            else:
                h = h.append(cursor, f"t{done}")
                cursor = h.ct.yarns[h.ct.site_id][-1][0]
            done += 1
    return h


def _star(base, m):
    """A replica with ``m`` values typed straight after the document's
    first one: off a one-value document, with an untouched twin, a row
    of exactly ``m + 1`` runs."""
    h = _fork(base)
    first = base.ct.weave[1][0]
    for i in range(m):
        h = h.append(first, f"s{i}")
    return h


def _pure_weave(a, b):
    """The pure weaver's merge: (weave ids, visible ids in order)."""
    def pure(h):
        return CausalList(h.ct.evolve(weaver="pure", lanes=None))

    m = pure(a).merge(pure(b))
    return [n[0] for n in m.ct.weave], [n[0] for n in m]


def _batch(pairs):
    rows = [benchgen.v5_inputs(concat_api_rows(p, CAP), CAP, s_max=S_MAX)
            for p in pairs]
    return [jnp.asarray(np.stack([r[k] for r in rows])) for k in LANE_KEYS5]


def _lane_ids(a, b):
    sites = {i[1] for h in (a, b) for i in h.ct.nodes}
    it = SiteInterner(sites)
    ids = []
    for h in (a, b):
        na = NodeArrays.from_nodes_map(h.ct.nodes, capacity=CAP,
                                       interner=it)
        ids += [n[0] for n in na.nodes] + [None] * (CAP - len(na.nodes))
    return ids


def _check_ladder(pairs, want_k, u_max=U_LADDER, k_max=U_LADDER):
    """The batched body runs at ``want_k`` and matches the fixed-width
    kernel bit for bit; rows that did not overflow weave as the pure
    weaver does. Returns the overflow flags."""
    args = _batch(pairs)
    rank, vis, conf, ovf, k = jaxw5.batched_merge_weave_v5(
        *args, u_max=u_max, k_max=k_max, with_k=True)
    assert int(k) == want_k
    fixed = _fixed_kernel(u_max, k_max)(*args)
    for got, ref in zip((rank, vis, conf, ovf), fixed):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    rank, vis, ovf = np.asarray(rank), np.asarray(vis), np.asarray(ovf)
    N = 2 * CAP
    for i, (a, b) in enumerate(pairs):
        if ovf[i]:
            continue
        ids = _lane_ids(a, b)
        order = sorted((r, l) for l, r in enumerate(rank[i]) if r < N)
        weave = [ids[l] for _, l in order]
        shown = [ids[l] for _, l in order if vis[i][l]]
        assert (weave, shown) == _pure_weave(a, b)
    return ovf


def test_run_widths_ladder():
    assert jaxw5.run_widths(64) == (64,)
    assert jaxw5.run_widths(128) == (128,)
    assert jaxw5.run_widths(1024) == (128, 256, 512, 1024)
    assert jaxw5.run_widths(32768)[-2:] == (16384, 32768)
    assert len(jaxw5.run_widths(32768)) == 9


@pytest.mark.parametrize("case", [
    "tail_pastes", "typed_anywhere", "runs_at_pow2", "runs_past_pow2",
    "one_wide_row"])
def test_batched_runs_at_the_batch_width(case):
    rng = random.Random(case)
    base = _doc(120)
    if case == "tail_pastes":
        pairs = [(_tail_pastes(base, n), _tail_pastes(base, n + 5))
                 for n in (8, 16, 32)]
        want = 128
    elif case == "typed_anywhere":
        pairs = [(_typed_anywhere(base, n, rng),
                  _typed_anywhere(base, n, rng)) for n in (8, 16, 24)]
        want = 128
    else:
        m = {"runs_at_pow2": 127, "runs_past_pow2": 128,
             "one_wide_row": 240}[case]
        one = _doc(1)
        pairs = [(_tail_pastes(base, 8), _tail_pastes(base, 8)),
                 (_star(one, m), _fork(one)),
                 (_typed_anywhere(base, 6, rng), _fork(base))]
        want = 128 if m < 128 else 256
    assert not _check_ladder(pairs, want).any()


def test_runs_past_k_max_still_overflow():
    """A row with more runs than ``k_max`` runs the tail at ``k_max``
    and is flagged, as at the fixed width; the other rows are exact."""
    base, one = _doc(120), _doc(1)
    wide = (_star(one, 200), _star(one, 200))      # 401 runs
    pairs = [wide, (_tail_pastes(base, 8), _tail_pastes(base, 9)),
             (_star(one, 20), _fork(one))]
    ovf = _check_ladder(pairs, 256, k_max=256)
    assert ovf.tolist() == [True, False, False]


