"""Multi-chip execution: shard the replica axis of batched weaves/merges
over a ``jax.sharding.Mesh``.

The reference's "distributed systems layer" is the CRDT itself — any
transport that moves immutable nodes between sites converges
(reference: README.md:5). cause_tpu keeps that host-level story (nodes
are plain data; serde ships them anywhere) and adds the device-level
story the reference never had: a batch of replica merges is sharded
across chips over ICI/DCN with ``shard_map``, with XLA collectives
(psum) reducing fleet-wide convergence stats — no NCCL/MPI port, just
shardings on one jitted program.

Batched merges are embarrassingly parallel across replicas, so the
sharding is pure data parallelism on the batch axis; the collectives
carry only the small cross-replica reductions (visible-node totals,
conflict flags, digest agreement) that a control plane wants after a
merge wave.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..weaver.jaxw import merge_weave_kernel, merge_weave_kernel_v2

from jax import shard_map as _shard_map

__all__ = [
    "REPLICA_AXIS",
    "make_mesh",
    "mix32",
    "mix32_np",
    "replica_digest",
    "sharded_merge_weave",
    "sharded_merge_weave_v4",
    "sharded_merge_weave_v5",
]

REPLICA_AXIS = "replicas"


def make_mesh(n_devices: Optional[int] = None, axis: str = REPLICA_AXIS) -> Mesh:
    """A 1-D device mesh over the first ``n_devices`` devices (all by
    default). The replica batch axis of every batched kernel shards
    over this axis."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def replica_digest(hi_sorted, lo_sorted, rank, visible):
    """An order-sensitive digest of one replica's weave: replicas that
    converged to the same linearization get the same digest, whatever
    lane order their inputs arrived in (node identity and weave
    position are mixed, lane positions are not). Cheap stand-in for
    shipping whole weaves around when checking fleet convergence.

    Each lane goes through a murmur3-style avalanche before the
    permutation-invariant sum: a plain xor-of-products mix let rows
    whose lanes differ only in site ranks cancel into collisions
    (observed in the wild at 4 rows).

    SCOPE: comparable only within one interner domain (one process /
    one fleet session) — hi/lo encode interner-assigned site RANKS,
    which are first-seen-order per process. Convergence checks ACROSS
    hosts use the canonical, rank-free ``cause_tpu.content_digest``
    instead (the two-process distributed test does)."""
    m = rank.shape[0]
    kept = rank < m
    pos = jnp.where(kept, rank.astype(jnp.uint32), jnp.uint32(0))
    x = mix32(hi_sorted, lo_sorted, pos, visible)
    return jnp.sum(jnp.where(kept, x, jnp.uint32(0)))


def mix32_np(hi, lo, pos, visible):
    """Numpy twin of ``mix32``'s per-lane avalanche term — returns the
    uint32 term array (callers sum the kept lanes). The delta-native
    weave uses it to freeze a resident prefix's digest contribution
    host-side, so the arithmetic here MUST stay bit-identical to
    ``mix32`` below; tests/test_delta_weave.py pins the pair against
    each other and against ``replica_digest`` end to end."""
    x = (
        hi.astype(np.uint32) * np.uint32(0x9E3779B1)
        + lo.astype(np.uint32) * np.uint32(0x85EBCA77)
        + pos.astype(np.uint32) * np.uint32(0xC2B2AE35)
        + visible.astype(np.uint32) * np.uint32(40503)
        + np.uint32(1)
    )
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    return x


def mix32(hi, lo, pos, visible):
    """The per-lane murmur3-style avalanche term of the convergence
    digest — the ONE traced copy: ``replica_digest`` sums it over a
    replica's kept lanes, and the delta wave
    (``weaver.jaxwd.batched_delta_weave``) sums it over window lanes
    at offset positions. ``mix32_np`` above is its numpy twin."""
    x = (
        hi.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + lo.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + pos.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
        + visible.astype(jnp.uint32) * jnp.uint32(40503)
        + jnp.uint32(1)
    )
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _fleet_reductions(axis, hi, lo, rank, visible, conflict, overflow):
    """The psum-reduced fleet stats + per-replica digests every kernel
    variant reports. ``hi``/``lo`` may arrive in any per-replica lane
    order matching ``rank``'s coordinates — the digest mix-sum is
    permutation-invariant."""
    n_overflow = lax.psum(jnp.sum(overflow.astype(jnp.int32)), axis)
    digest = jax.vmap(replica_digest)(hi, lo, rank, visible)
    total_visible = lax.psum(jnp.sum(visible.astype(jnp.int32)), axis)
    n_conflicts = lax.psum(jnp.sum(conflict.astype(jnp.int32)), axis)
    return digest, total_visible, n_conflicts, n_overflow


def _fleet_stats(axis, hi, lo, order, rank, visible, conflict, overflow):
    """Sorted-lane epilogue: resort the id lanes by ``order`` (rank is
    per sorted lane for v1-v4) and attach the shared reductions."""
    hi_sorted = jnp.take_along_axis(hi, order, axis=1)
    lo_sorted = jnp.take_along_axis(lo, order, axis=1)
    digest, total_visible, n_conflicts, n_overflow = _fleet_reductions(
        axis, hi_sorted, lo_sorted, rank, visible, conflict, overflow
    )
    return (order, rank, visible, digest, total_visible, n_conflicts,
            n_overflow)


@lru_cache(maxsize=8)
def _sharded_step(mesh: Mesh, k_max: int, kernel: str):
    """The jitted sharded merge step for one mesh (cached so repeat
    merge waves hit the jit cache instead of re-tracing). ``k_max`` > 0
    runs a compressed kernel — ``kernel`` picks the sparse-irregular
    "v3" (default) or chain-compressed "v2" — with that run budget
    (overflowed rows are psum-counted fleet-wide); 0 runs the
    uncompressed kernel."""
    axis = mesh.axis_names[0]
    sharded = P(axis)
    replicated = P()
    if kernel == "v3":
        from ..weaver.jaxw3 import merge_weave_kernel_v3 as _compressed
    else:
        _compressed = merge_weave_kernel_v2

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(sharded,) * 6,
        out_specs=(sharded, sharded, sharded, sharded, replicated,
                   replicated, replicated),
    )
    def step(hi, lo, chi, clo, vc, va):
        if k_max > 0:
            order, rank, visible, conflict, overflow = jax.vmap(
                lambda *r: _compressed(*r, k_max)
            )(hi, lo, chi, clo, vc, va)
        else:
            order, rank, visible, conflict = jax.vmap(merge_weave_kernel)(
                hi, lo, chi, clo, vc, va
            )
            overflow = jnp.zeros(conflict.shape, bool)
        return _fleet_stats(axis, hi, lo, order, rank, visible, conflict,
                            overflow)

    return jax.jit(step)


def sharded_merge_weave(mesh: Mesh, hi, lo, cause_hi, cause_lo, vclass, valid,
                        k_max: int = 0, kernel: str = "v3"):
    """Run the batched merge+weave with the replica axis sharded over
    the mesh. Returns per-replica ``(order, rank, visible, digest)``
    (sharded) plus fleet-level ``(total_visible, n_conflicts,
    n_overflow)`` reduced with psum over the mesh axis. ``k_max`` > 0
    selects a compressed kernel (``kernel``: "v3" sparse-irregular,
    the default, or "v2" chain-compressed) with that per-replica run
    budget; rows counted in ``n_overflow`` carry invalid ranks and the
    caller should rerun with ``k_max=0`` (or a bigger budget).

    The batch dimension must be divisible by the mesh size.
    """
    # normalize the cache key: kernel is only consulted when k_max > 0,
    # so k_max=0 calls must not mint per-kernel duplicate programs
    step = _sharded_step(mesh, k_max, kernel if k_max > 0 else "v1")
    return step(hi, lo, cause_hi, cause_lo, vclass, valid)


@lru_cache(maxsize=8)
def _sharded_step_v4(mesh: Mesh, k_max: int):
    """The v4 twin of ``_sharded_step``: 5 lanes (cause ids replaced by
    the marshal-time concat cause-index lane), same outputs."""
    from ..weaver.jaxw4 import merge_weave_kernel_v4

    axis = mesh.axis_names[0]
    sharded = P(axis)
    replicated = P()

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(sharded,) * 5,
        out_specs=(sharded, sharded, sharded, sharded, replicated,
                   replicated, replicated),
    )
    def step(hi, lo, cci, vc, va):
        order, rank, visible, conflict, overflow = jax.vmap(
            lambda *r: merge_weave_kernel_v4(*r, k_max)
        )(hi, lo, cci, vc, va)
        return _fleet_stats(axis, hi, lo, order, rank, visible, conflict,
                            overflow)

    return jax.jit(step)


def sharded_merge_weave_v4(mesh: Mesh, hi, lo, cci, vclass, valid,
                           k_max: int):
    """``sharded_merge_weave`` for the v4 kernel: lanes carry ``cci``
    (the cause's index in the concatenated pre-sort array, resolved at
    marshal time) instead of cause id lanes. Same outputs; the batch
    dimension must be divisible by the mesh size."""
    return _sharded_step_v4(mesh, k_max)(hi, lo, cci, vclass, valid)


@lru_cache(maxsize=8)
def _sharded_step_v5(mesh: Mesh, u_max: int, k_max: int):
    """The v5 (segment-union) sharded step: node lanes + segment
    tables in, per-replica (rank, visible, digest) + fleet stats out.
    v5 reports in concat-lane coordinates and produces no ``order``;
    the digest's mix-sum is permutation-invariant, so feeding the raw
    lanes with concat-coordinate ranks yields the same digest value as
    the sorted-lane kernels."""
    from ..weaver.jaxw5 import merge_weave_kernel_v5

    axis = mesh.axis_names[0]
    sharded = P(axis)
    replicated = P()

    @partial(
        _shard_map,
        mesh=mesh,
        in_specs=(sharded,) * 16,
        out_specs=(sharded, sharded, sharded, sharded, replicated,
                   replicated, replicated),
    )
    def step(hi, lo, cci, vc, va, seg, *sg):
        rank, visible, conflict, overflow = jax.vmap(
            lambda *r: merge_weave_kernel_v5(*r, u_max=u_max, k_max=k_max)
        )(hi, lo, cci, vc, va, seg, *sg)
        digest, total_visible, n_conflicts, n_overflow = _fleet_reductions(
            axis, hi, lo, rank, visible, conflict, overflow
        )
        # per-row overflow rides out sharded: overflowed v5 rows keep
        # many plausible-looking ranks, so callers cannot reconstruct
        # the flags from rank alone
        return (rank, visible, overflow, digest, total_visible,
                n_conflicts, n_overflow)

    return jax.jit(step)


def sharded_merge_weave_v5(mesh: Mesh, lanes: dict, u_max: int,
                           k_max: int):
    """Shard the v5 segment-union merge over the mesh. ``lanes`` is the
    ``benchgen.LANE_KEYS5`` dict of [B, ...] arrays. Returns
    ``(rank, visible, overflow, digest, total_visible, n_conflicts,
    n_overflow)`` — rank/visible/overflow per replica row (no order
    array in the v5 contract; ``overflow`` rows carry garbage ranks
    and must be re-run).

    CAVEAT (narrowed in round 3 by the sg_vsum checksum lane): twin
    dedupe now verifies member value CLASSES and structure, so
    class-divergent corrupt twins explode and count in
    ``n_conflicts``; what remains device-invisible is host VALUE bytes
    (identical ids/classes/causes, different payload). Fleet control
    planes that must catch those validate bodies host-side
    (shared.union_nodes does)."""
    from ..benchgen import LANE_KEYS5

    step = _sharded_step_v5(mesh, u_max, k_max)
    return step(*(lanes[k] for k in LANE_KEYS5))
