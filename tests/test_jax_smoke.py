"""Fast device-kernel smoke: tiny-shape parity for every kernel
generation, so the default (-m "not slow") test set still exercises
the v2/v3/v4/v5 device paths end to end. The heavy differential-fuzz
and adversarial suites live in test_jax_v{3,4,5}.py (marked slow; CI
runs them as a dedicated job)."""

import numpy as np

import jax.numpy as jnp

import cause_tpu as c
from cause_tpu import benchgen
from cause_tpu.benchgen import LANE_KEYS, LANE_KEYS4, LANE_KEYS5
from cause_tpu.collections.clist import CausalList
from cause_tpu.ids import new_site_id
from cause_tpu.weaver import jaxw


CAP = 64


def tiny_pair():
    return benchgen.divergent_pair_lanes(
        n_base=20, n_div=6, capacity=CAP, hide_every=3
    )


def v1_reference(row):
    args = tuple(jnp.asarray(row[k]) for k in LANE_KEYS)
    o, r, v, _ = jaxw.merge_weave_kernel(*args)
    o, r, v = np.asarray(o), np.asarray(r), np.asarray(v)
    N = o.shape[0]
    rank_c = np.full(N, N, np.int32)
    vis_c = np.zeros(N, bool)
    rank_c[o] = r
    vis_c[o] = v
    return rank_c, vis_c


def test_v2_v3_tiny_pair_parity():
    from cause_tpu.weaver import jaxw3

    row = tiny_pair()
    rank1, vis1 = v1_reference(row)
    args = tuple(jnp.asarray(row[k]) for k in LANE_KEYS)
    for kern in (jaxw.merge_weave_kernel_v2, jaxw3.merge_weave_kernel_v3):
        o, r, v, _, ov = kern(*args, 48)
        assert not bool(ov)
        o, r, v = np.asarray(o), np.asarray(r), np.asarray(v)
        N = o.shape[0]
        rank_c = np.full(N, N, np.int32)
        vis_c = np.zeros(N, bool)
        rank_c[o] = r
        vis_c[o] = v
        assert np.array_equal(rank_c, rank1), kern.__name__
        assert np.array_equal(vis_c, vis1), kern.__name__


def test_v4_tiny_pair_parity():
    from cause_tpu.weaver.jaxw4 import merge_weave_kernel_v4_jit

    row = tiny_pair()
    rank1, vis1 = v1_reference(row)
    o, r, v, _, ov = merge_weave_kernel_v4_jit(
        *(jnp.asarray(row[k]) for k in LANE_KEYS4), k_max=48
    )
    assert not bool(ov)
    o, r, v = np.asarray(o), np.asarray(r), np.asarray(v)
    N = o.shape[0]
    rank_c = np.full(N, N, np.int32)
    vis_c = np.zeros(N, bool)
    rank_c[o] = r
    vis_c[o] = v
    assert np.array_equal(rank_c, rank1)
    assert np.array_equal(vis_c, vis1)


def test_v5_tiny_pair_parity():
    from cause_tpu.weaver.jaxw5 import merge_weave_kernel_v5_jit

    row = tiny_pair()
    rank1, vis1 = v1_reference(row)
    v5row = benchgen.v5_inputs(row, CAP)
    u = benchgen.v5_token_budget(v5row)
    r, v, _, ov = merge_weave_kernel_v5_jit(
        *(jnp.asarray(v5row[k]) for k in LANE_KEYS5), u_max=u, k_max=u
    )
    assert not bool(ov)
    assert np.array_equal(np.asarray(r), rank1)
    assert np.array_equal(np.asarray(v), vis1)


def test_api_merge_parity_all_backends_extend_shape():
    """API-level pair merge on an extend-built (tx-run) tree: jax and
    native must match pure — tiny twin of the suites' big fuzz."""
    base = c.clist(weaver="jax").extend([f"w{i}" for i in range(40)])
    a = CausalList(base.ct.evolve(site_id=new_site_id())).extend(["a1", "a2"])
    b = CausalList(base.ct.evolve(site_id=new_site_id())).conj("b1")
    b = b.append(list(b)[-1][0], c.hide)
    got = c.causal_to_edn(a.merge(b))
    pure = c.causal_to_edn(
        CausalList(a.ct.evolve(weaver="pure")).merge(
            CausalList(b.ct.evolve(weaver="pure"))
        )
    )
    assert got == pure
    nat = c.causal_to_edn(
        CausalList(a.ct.evolve(weaver="native")).merge(
            CausalList(b.ct.evolve(weaver="native"))
        )
    )
    assert nat == pure


def test_graft_entry_compiles_v5_against_pure():
    """The driver's compile check runs the shipped v5 row kernel, and
    its weave is the pure weaver's merge of the same pair."""
    import jax

    import __graft_entry__ as g
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.weaver import lanecache

    a, b, cap, row, _u = g.entry_pair()
    fn, _args = g.entry()
    rank, _vis, _conflict, overflow = jax.jit(fn)(*row)
    assert not bool(overflow)
    rank = np.asarray(rank)
    keep = np.flatnonzero(rank < 2 * cap)
    order = keep[np.argsort(rank[keep], kind="stable")]
    an = lanecache.view_for(a.ct).arena.nodes
    bn = lanecache.view_for(b.ct).arena.nodes
    got = [(an[j] if j < cap else bn[j - cap])[0] for j in order]
    pure = [CausalList(h.ct.evolve(weaver="pure", lanes=None))
            for h in (a, b)]
    want = [n[0] for n in pure[0].merge(pure[1]).get_weave()]
    assert got == want
