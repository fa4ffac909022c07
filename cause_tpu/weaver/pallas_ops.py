"""Pallas/Mosaic kernels for the contracted (K-wide) weave phases.

The chain-compressed kernels (jaxw.linearize_v2, jaxw3, jaxw4, jaxw5)
shrink the causal tree to K runs, but still rank the contracted tree
with log-depth pointer doubling (``jaxw._euler_rank``) — ~12 rounds of
K-wide gathers that TPU profiling showed dominating the residual cost
(PERF.md): XLA materializes every round as an HBM-width gather pass.

A TPU core walks a K-node tree *sequentially* faster than XLA can
pointer-double it at batch width: the run tables sit in VMEM (~9 KB at
K~2k), a preorder traversal is ~2 visits per run, and each visit is a
handful of scalar loads — so ``euler_walk`` replaces the doubling with
one Pallas program per replica row.

Mosaic constraints (discovered via AOT ``jax.export`` for tpu, which
this repo regression-tests — tests/test_pallas_lowering.py — because
the first design only worked in interpret mode):

- scalar STORES to VMEM are unsupported: the per-visit ``base[cur] =
  pos`` scatter goes to an SMEM output; dynamic scalar LOADS from VMEM
  are fine, so the read-only run tables stay in VMEM;
- a batched (squeezed-leading-dim) block fails the (8, 128) tiling
  rule, so batching maps onto an explicit grid of (8 rows, K) blocks
  — ``jax.custom_batching.custom_vmap`` swaps that in when the caller
  vmaps, which is how the v4/v5 kernels reach it.

CPU runs (tests, the driver dryrun) execute the same kernels in Pallas
interpret mode — chosen at trace time from the default backend — so
the suite needs no TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["euler_walk", "euler_walk_batch"]

_ROWS = 8  # rows per grid block (the Mosaic sublane tiling unit)


def _interpret() -> bool:
    """Interpret off-TPU (tests, dryrun); compile via Mosaic on TPU."""
    return jax.default_backend() != "tpu"


def _vmem_spec(R, K):
    return pl.BlockSpec((R, K), lambda b: (b, 0),
                        memory_space=pltpu.VMEM)


def _smem_spec(R, K):
    return pl.BlockSpec((R, K), lambda b: (b, 0),
                        memory_space=pltpu.SMEM)


def _walk_kernel(fc_ref, ns_ref, parent_ref, w_ref, base_ref):
    """Preorder walk of each row's contracted forest in the block.

    state = (cur, pos, mode): mode 0 visits ``cur`` (stamp base, add
    its weight, descend to first child), mode 1 retreats (next sibling
    if any, else climb to parent). One branchless automaton step per
    iteration; terminates when the retreat climbs past the root (the
    root's parent is -1). Runs never reached from run 0 (invalid /
    overflow slots) keep the ``total`` initialization, matching
    ``_euler_rank``."""
    R, K = fc_ref.shape

    def row(r, _):
        total = jnp.sum(w_ref[r, :])

        def init(i, __):
            base_ref[r, i] = total
            return 0

        lax.fori_loop(0, K, init, 0)

        def cond(state):
            cur, _pos, _mode, steps = state
            return (cur >= 0) & (steps < 3 * K + 4)

        def body(state):
            cur, pos, mode, steps = state
            is_visit = mode == 0

            @pl.when(is_visit)
            def _():
                base_ref[r, cur] = pos

            child = fc_ref[r, cur]
            sib = ns_ref[r, cur]
            par = parent_ref[r, cur]
            npos = jnp.where(is_visit, pos + w_ref[r, cur], pos)
            ncur = jnp.where(
                is_visit,
                jnp.where(child >= 0, child, cur),
                jnp.where(sib >= 0, sib, par),
            )
            nmode = jnp.where(
                is_visit,
                jnp.where(child >= 0, 0, 1),
                jnp.where(sib >= 0, 0, 1),
            ).astype(jnp.int32)
            return ncur, npos, nmode, steps + 1

        lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)),
        )
        return 0

    lax.fori_loop(0, R, row, 0)


def euler_walk_batch(fc, ns, parent_run, run_len):
    """Weighted preorder base per run for a [B, K] batch of contracted
    forests (grid of _ROWS-row blocks; B pads up to a multiple)."""
    B, K = fc.shape
    Bp = -(-B // _ROWS) * _ROWS
    if Bp != B:
        # padded rows are empty forests (parent -1 everywhere): the
        # automaton visits run 0 and immediately terminates
        pad = ((0, Bp - B), (0, 0))
        fc = jnp.pad(fc, pad, constant_values=-1)
        ns = jnp.pad(ns, pad, constant_values=-1)
        parent_run = jnp.pad(parent_run, pad, constant_values=-1)
        run_len = jnp.pad(run_len, pad, constant_values=0)
    out = pl.pallas_call(
        _walk_kernel,
        grid=(Bp // _ROWS,),
        in_specs=[_vmem_spec(_ROWS, K)] * 4,
        out_specs=_smem_spec(_ROWS, K),
        out_shape=jax.ShapeDtypeStruct((Bp, K), jnp.int32),
        interpret=_interpret(),
    )(fc, ns, parent_run, run_len.astype(jnp.int32))
    return out[:B]


@jax.custom_batching.custom_vmap
def _euler_walk1(fc, ns, parent_run, run_len):
    """Single forest: no grid — whole-array blocks take the untiled
    path, which skips the (8, 128) blocked-shape rule that rejects a
    squeezed/partial block (verified by the AOT export tests)."""
    K = fc.shape[0]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _walk_kernel,
        in_specs=[vmem] * 4,
        out_specs=smem,
        out_shape=jax.ShapeDtypeStruct((1, K), jnp.int32),
        interpret=_interpret(),
    )(
        fc.reshape(1, K), ns.reshape(1, K), parent_run.reshape(1, K),
        run_len.astype(jnp.int32).reshape(1, K),
    )
    return out.reshape(K)


@_euler_walk1.def_vmap
def _euler_walk1_vmap(axis_size, in_batched, fc, ns, parent_run,
                      run_len):
    ops = []
    for x, b in zip((fc, ns, parent_run, run_len), in_batched):
        ops.append(x if b else jnp.broadcast_to(
            x, (axis_size,) + x.shape))
    return euler_walk_batch(*ops), True


def euler_walk(fc, ns, parent_run, run_len, k_max: int):
    """Weighted preorder base per run, for one row's contracted tree.

    Inputs are the ``[k_max]`` int32 run tables the compressed kernels
    build (first_child / next_sibling from ``_link_children``, parent
    run ids with -1 at the root/invalid slots, run lengths with 0 at
    invalid slots). Returns ``base`` ``[k_max]`` int32. Under ``vmap``
    the batch maps onto the Pallas grid via ``euler_walk_batch``."""
    assert fc.shape[-1] == k_max, (fc.shape, k_max)
    return _euler_walk1(fc, ns, parent_run, run_len)
