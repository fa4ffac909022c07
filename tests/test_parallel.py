"""Multi-chip tests on the 8-virtual-device CPU mesh (conftest forces
xla_force_host_platform_device_count=8)."""

import random

import numpy as np
import pytest

import jax

import cause_tpu as c
from cause_tpu.collections import clist as c_list
from cause_tpu.collections import shared as s
from cause_tpu.ids import new_site_id
from cause_tpu.parallel import (
    make_mesh,
    sharded_merge_weave,
    sharded_merge_weave_v4,
    sharded_merge_weave_v5,
)
from cause_tpu.weaver.arrays import NodeArrays, SiteInterner

from test_jax_weaver import (
    _tree_lanes,
    build_batch,
    decode_device_weave,
    pair_lane_nodes,
)


def _require_multi_device():
    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device CPU platform")


def test_mesh_has_8_virtual_devices():
    assert len(jax.devices()) == 8


@pytest.mark.slow
def test_sharded_merge_matches_pure():
    _require_multi_device()
    rng = random.Random(5150)
    n_dev = len(jax.devices())
    B = n_dev * 2
    cap = 16
    mesh = make_mesh()
    pairs, lanes, _metas = build_batch(rng, B, cap, n_edits=4)
    order, rank, visible, digest, total_visible, n_conflicts, n_overflow = (
        sharded_merge_weave(
            mesh, lanes["hi"], lanes["lo"], lanes["chi"], lanes["clo"],
            lanes["vc"], lanes["valid"],
        )
    )
    order, rank, visible = map(np.asarray, (order, rank, visible))
    assert int(n_conflicts) == 0
    assert int(n_overflow) == 0
    # the v2 (chain-compressed) sharded kernel agrees end to end
    o2, r2, v2, d2, tv2, nc2, no2 = sharded_merge_weave(
        mesh, lanes["hi"], lanes["lo"], lanes["chi"], lanes["clo"],
        lanes["vc"], lanes["valid"], k_max=2 * cap,
    )
    assert int(no2) == 0 and int(nc2) == 0
    assert np.array_equal(np.asarray(r2), rank)
    assert np.array_equal(np.asarray(v2), visible)
    assert np.array_equal(np.asarray(d2), np.asarray(digest))
    assert int(tv2) == int(total_visible)
    # the v4 (marshal-resolved causes) sharded kernel agrees end to end
    o4, r4, v4, d4, tv4, nc4, no4 = sharded_merge_weave_v4(
        mesh, lanes["hi"], lanes["lo"], lanes["cci"],
        lanes["vc"], lanes["valid"], k_max=2 * cap,
    )
    assert int(no4) == 0 and int(nc4) == 0
    assert np.array_equal(np.asarray(r4), rank)
    assert np.array_equal(np.asarray(v4), visible)
    assert np.array_equal(np.asarray(d4), np.asarray(digest))
    assert int(tv4) == int(total_visible)
    # the v5 (segment-union) sharded kernel: same digests, totals, and
    # weave (rank arrives in concat coordinates; the digest mix-sum is
    # permutation-invariant so values must match the sorted-lane paths)
    from cause_tpu import benchgen as bg

    v5lanes = bg.batched_v5_inputs(
        {k: np.asarray(lanes[k]) for k in bg.LANE_KEYS4}, cap
    )
    u5 = bg.v5_token_budget(v5lanes)
    r5, v5_, ov5, d5, tv5, nc5, no5 = sharded_merge_weave_v5(
        mesh, v5lanes, u_max=u5, k_max=u5
    )
    assert int(no5) == 0 and int(nc5) == 0
    assert not bool(np.asarray(ov5).any())
    assert int(tv5) == int(total_visible)
    assert np.array_equal(np.asarray(d5), np.asarray(digest))
    # rank equivalence through the coordinate change
    for bidx in range(B):
        rc = np.full(rank.shape[1], rank.shape[1], np.int32)
        rc[order[bidx]] = rank[bidx]
        kept1 = rc < rank.shape[1]
        kept5 = np.asarray(r5[bidx]) < rank.shape[1]
        hi_b = np.asarray(lanes["hi"])[bidx]
        lo_b = np.asarray(lanes["lo"])[bidx]
        ids1 = sorted(zip(rc[kept1], hi_b[kept1], lo_b[kept1]))
        ids5 = sorted(zip(np.asarray(r5[bidx])[kept5], hi_b[kept5],
                          lo_b[kept5]))
        assert ids1 == ids5

    expect_total = 0
    for bidx, (a_ct, b_ct) in enumerate(pairs):
        pure = s.merge_trees(c_list.weave, a_ct, b_ct)
        expect_visible = c_list.causal_list_to_list(pure)
        expect_total += len(expect_visible)
        # reconstruct device weave for this replica
        all_nodes = pair_lane_nodes(a_ct, b_ct, cap)
        device_weave, _ = decode_device_weave(order[bidx], rank[bidx], all_nodes)
        assert device_weave == pure.weave, f"replica {bidx}"
    assert int(total_visible) == expect_total


def test_digests_detect_convergence():
    _require_multi_device()
    rng = random.Random(6)
    n_dev = len(jax.devices())
    B = n_dev
    cap = 16
    mesh = make_mesh()
    # identical pairs in every batch slot -> identical digests
    base = c.clist(*"xyz")
    a = c_list.CausalList(base.ct.evolve(site_id=new_site_id())).conj("!")
    bb = c_list.CausalList(base.ct.evolve(site_id=new_site_id())).cons("?")
    sites = {i[1] for i in a.ct.nodes} | {i[1] for i in bb.ct.nodes}
    interner = SiteInterner(sites)
    na, (ahi, alo), (achi, aclo) = _tree_lanes(a.ct, interner, cap)
    nb, (bhi, blo), (bchi, bclo) = _tree_lanes(bb.ct, interner, cap)
    row = {
        "hi": np.concatenate([ahi, bhi]),
        "lo": np.concatenate([alo, blo]),
        "chi": np.concatenate([achi, bchi]),
        "clo": np.concatenate([aclo, bclo]),
        "vc": np.concatenate([na.vclass, nb.vclass]),
        "valid": np.concatenate([na.valid, nb.valid]),
    }
    lanes = {k: np.stack([v] * B) for k, v in row.items()}
    *_, digest, _total, _conf, _ovf = sharded_merge_weave(
        mesh, lanes["hi"], lanes["lo"], lanes["chi"], lanes["clo"],
        lanes["vc"], lanes["valid"],
    )
    digest = np.asarray(digest)
    assert (digest == digest[0]).all()


def test_digest_invariant_to_input_overlap():
    """Replicas that converge to the same weave get the same digest even
    when their inputs carried different duplicate overlap (row 1 merges
    (A, B); row 2 merges (A-union-B, B) — same union, different lanes)."""
    _require_multi_device()
    cap = 32
    mesh = make_mesh()
    base = c.clist(*"xyz")
    a = c_list.CausalList(base.ct.evolve(site_id=new_site_id())).conj("!")
    bb = c_list.CausalList(base.ct.evolve(site_id=new_site_id())).cons("?")
    union = s.merge_trees(c_list.weave, a.ct, bb.ct)
    sites = {i[1] for i in union.nodes}
    interner = SiteInterner(sites)
    rows = []
    for left_ct in (a.ct, union):
        nl, (lhi, llo), (lchi, lclo) = _tree_lanes(left_ct, interner, cap)
        nr, (rhi, rlo), (rchi, rclo) = _tree_lanes(bb.ct, interner, cap)
        rows.append({
            "hi": np.concatenate([lhi, rhi]),
            "lo": np.concatenate([llo, rlo]),
            "chi": np.concatenate([lchi, rchi]),
            "clo": np.concatenate([lclo, rclo]),
            "vc": np.concatenate([nl.vclass, nr.vclass]),
            "valid": np.concatenate([nl.valid, nr.valid]),
        })
    B = len(jax.devices())
    # rows 0..B/2-1 use overlap variant 0, the rest variant 1
    lanes = {
        k: np.stack([rows[0][k]] * (B // 2) + [rows[1][k]] * (B - B // 2))
        for k in rows[0]
    }
    *_, digest, _total, n_conflicts, _ovf = sharded_merge_weave(
        mesh, lanes["hi"], lanes["lo"], lanes["chi"], lanes["clo"],
        lanes["vc"], lanes["valid"],
    )
    assert int(n_conflicts) == 0
    digest = np.asarray(digest)
    assert (digest == digest[0]).all()


