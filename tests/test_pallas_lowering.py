"""Mosaic-lowering regression guard for the Pallas kernels.

Interpret mode (what the CPU suite runs) accepts programs the real
Mosaic compiler rejects — the original euler_walk design passed every
CPU test yet failed TPU lowering with "Cannot store scalars to VMEM".
``jax.export`` with platforms=["tpu"] runs the Pallas->Mosaic lowering
from any backend. Lowering is not compiling: the chip's compiler, which
refuses kernels for VMEM or tiling, runs in tests/test_tpu_compile.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cause_tpu.weaver import pallas_ops


def _chain_tables(k, n_runs):
    """A root with a chain of children plus some siblings."""
    rng = np.random.RandomState(k)
    parent = np.full(k, -1, np.int32)
    for i in range(1, n_runs):
        parent[i] = rng.randint(0, i)
    w = np.zeros(k, np.int32)
    w[:n_runs] = rng.randint(1, 5, n_runs)
    # first-child / next-sibling from the parent table (children in
    # index order, mirroring _link_children's contract closely enough
    # for a lowering + smoke-parity test)
    fc = np.full(k, -1, np.int32)
    ns = np.full(k, -1, np.int32)
    last_child = {}
    for i in range(1, n_runs):
        p = parent[i]
        if p in last_child:
            ns[last_child[p]] = i
        else:
            fc[p] = i
        last_child[p] = i
    return (jnp.asarray(fc), jnp.asarray(ns), jnp.asarray(parent),
            jnp.asarray(w))


def test_euler_walk_exports_for_tpu(monkeypatch):
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    fc, ns, parent, w = _chain_tables(256, 40)

    def single(a, b, c, d):
        return pallas_ops.euler_walk(a, b, c, d, 256)

    jax.export.export(jax.jit(single), platforms=["tpu"])(
        fc, ns, parent, w)


def test_euler_walk_batch_exports_for_tpu(monkeypatch):
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    fc, ns, parent, w = _chain_tables(256, 40)
    B = 12  # non-multiple of the 8-row block: exercises padding
    batch = tuple(jnp.tile(x, (B, 1)) for x in (fc, ns, parent, w))

    def batched(a, b, c, d):
        return jax.vmap(
            lambda e, f, g, h: pallas_ops.euler_walk(e, f, g, h, 256)
        )(a, b, c, d)

    jax.export.export(jax.jit(batched), platforms=["tpu"])(*batch)


def test_v5w_kernel_exports_for_tpu(monkeypatch):
    """The full v5 kernel with euler='walk' must lower for TPU — the
    exact program bench.py dispatches under BENCH_KERNEL=v5w."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u,
                                      euler="walk")

    jax.export.export(jax.jit(f), platforms=["tpu"])(*args)


def test_v5_allstream_exports_for_tpu(monkeypatch):
    """The full streaming configuration (rowgather + bitonic + matrix
    search) must lower for TPU — the watcher's headline candidate."""
    monkeypatch.setenv("CAUSE_TPU_GATHER", "rowgather")
    monkeypatch.setenv("CAUSE_TPU_SORT", "bitonic")
    monkeypatch.setenv("CAUSE_TPU_SEARCH", "matrix")
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u)

    batched_merge_weave_v5.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5.clear_cache()


def test_v5_kernel_exports_for_tpu():
    """The default v5 program (pure XLA) lowers for TPU too — guards
    against a jnp construct with no TPU lowering sneaking in."""
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u)

    jax.export.export(jax.jit(f), platforms=["tpu"])(*args)


def test_walk_parity_vs_doubling_after_redesign():
    """The SMEM redesign still ranks exactly like _euler_rank."""
    from cause_tpu.weaver.jaxw import _euler_rank

    fc, ns, parent, w = _chain_tables(128, 31)
    want, _ = _euler_rank(fc, ns, parent, w)
    got = pallas_ops.euler_walk(fc, ns, parent, w, 128)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    # batched via vmap (the kernels' calling convention)
    B = 5
    batch = tuple(jnp.tile(x, (B, 1)) for x in (fc, ns, parent, w))
    got_b = jax.vmap(
        lambda a, b, c, d: pallas_ops.euler_walk(a, b, c, d, 128)
    )(*batch)
    for r in range(B):
        assert np.array_equal(np.asarray(want), np.asarray(got_b[r]))


def test_v5_scatter_hint_exports_for_tpu(monkeypatch):
    """The annotated-scatter configuration must lower for TPU."""
    monkeypatch.setenv("CAUSE_TPU_SCATTER", "hint")
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u)

    batched_merge_weave_v5.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5.clear_cache()


def test_v5_beststream_combined_exports_for_tpu(monkeypatch):
    """The exact shipped beststream combination (pallas sort +
    rowgather + matrix-table + scatter hints + euler walk) must lower
    for TPU — the program a window's alt attempt compiles."""
    from cause_tpu.weaver import pallas_ops, pallas_sort

    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_sort, "_interpret", lambda: False)
    monkeypatch.setenv("CAUSE_TPU_SORT", "pallas")
    monkeypatch.setenv("CAUSE_TPU_GATHER", "rowgather")
    monkeypatch.setenv("CAUSE_TPU_SEARCH", "matrix-table")
    monkeypatch.setenv("CAUSE_TPU_SCATTER", "hint")
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u,
                                      euler="walk")

    batched_merge_weave_v5.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5.clear_cache()


def test_fphase_kernel_exports_for_tpu(monkeypatch):
    """The fused F-phase expansion (pallas_fphase) must lower via
    Mosaic: dynamic-start window loads from the transposed tables,
    sublane-axis reductions, vector stores, and the vectorized
    visibility pass with jnp.roll."""
    from cause_tpu.weaver import pallas_fphase

    monkeypatch.setattr(pallas_fphase, "_interpret", lambda: False)
    rng = np.random.RandomState(5)
    B, N, U, S = 12, 512, 160, 64  # B pads to 16; U/S pad to 128
    lk = np.sort(np.stack([
        rng.choice(N, size=U, replace=False) for _ in range(B)
    ]), axis=1).astype(np.int32)
    lk[:, 100:] = N  # sentinel tail
    tb = rng.randint(0, N, size=(B, U)).astype(np.int32)
    cs = np.full((B, S), N, np.int32)
    ce = np.zeros((B, S), np.int32)
    cs[:, :10] = np.arange(10, dtype=np.int32) * 40
    ce[:, :10] = cs[:, :10] + 30
    vc = rng.randint(0, 4, size=(B, N)).astype(np.int32)
    seg = np.repeat(np.arange(N // 8, dtype=np.int32), 8)[None].repeat(
        B, 0).astype(np.int32)
    fl = rng.randint(0, 4, size=(B, N)).astype(np.int32)

    def f(*a):
        return jax.vmap(pallas_fphase.fphase_expand)(*a)

    jax.export.export(jax.jit(f), platforms=["tpu"])(
        *(jnp.asarray(x) for x in (lk, tb, cs, ce, vc, seg, fl)))


def test_v5_fphase_exports_for_tpu(monkeypatch):
    """The full v5 program under CAUSE_TPU_FPHASE=pallas lowers for
    TPU — the exact program the harvest ladder times."""
    monkeypatch.setenv("CAUSE_TPU_FPHASE", "pallas")
    from cause_tpu.weaver import pallas_fphase
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    monkeypatch.setattr(pallas_fphase, "_interpret", lambda: False)
    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u)

    batched_merge_weave_v5.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5.clear_cache()


def test_v5f_pipeline_exports_for_tpu(monkeypatch):
    """The full fused-token-pipeline program (jaxw5f: K1 + K2 +
    euler_walk + K4 + fphase plus the XLA glue) must lower via Mosaic
    — the exact program BENCH_KERNEL=v5f dispatches. Covers the
    in-kernel bitonic networks, MXU identity flips, one-hot chunk
    gathers, roll-based cumulative ops, window expansion, and the
    fori row loops with pl.ds I/O in all three new kernels."""
    from cause_tpu.weaver import pallas_befuse, pallas_fphase
    from cause_tpu.weaver import pallas_ops as pops
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5f import batched_merge_weave_v5f

    monkeypatch.setattr(pallas_befuse, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_fphase, "_interpret", lambda: False)
    monkeypatch.setattr(pops, "_interpret", lambda: False)
    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5f(*a, u_max=u, k_max=u)

    batched_merge_weave_v5f.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5f.clear_cache()
