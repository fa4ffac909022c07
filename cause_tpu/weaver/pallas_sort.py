"""In-VMEM bitonic sort — the Pallas answer to the kernel sort cost.

The weave kernels sort [B, U] token tables four times per wave
(U ~2.3k, B ~1k). XLA's TPU ``lax.sort`` lowers a comparator loop; the
XLA-level bitonic network (``bitonic.bitonic_sort``) replaces it with
~78 elementwise compare-exchange stages, but each stage round-trips
every operand through HBM — ~10 GB per full-size sort, hopeless on
bandwidth. This module runs the SAME network inside one Pallas kernel
per 8-row block: operands load into VMEM once, all stages run
VMEM-resident on the VPU, results store once. HBM traffic collapses to
one read + one write per operand.

Mosaic shapes the design (cf. pallas_ops, tests/test_pallas_lowering):

- compare-exchange partners are fetched with ``jnp.roll`` along the
  lane axis (XOR-partner pairs at distance j never wrap, so a roll in
  each direction + a direction mask IS the partner permutation) — no
  gathers, no (nb, 2, j) reshapes whose last dim breaks the 128-lane
  tiling rule;
- the network is statically unrolled at trace time (log2(P)^2 / 2
  stages — 78 at P=4096), every stage pure elementwise select;
- batching maps onto an explicit (8, P) grid via
  ``jax.custom_batching.custom_vmap`` (a squeezed leading block dim
  fails the tiling rule), mirroring ``euler_walk``.

Contract: identical to ``bitonic.bitonic_sort`` — int32 operands,
ascending lexicographic over the first ``num_keys`` operands, an
implicit original-position key appended so the result is the unique
deterministic stable order (== stable ``lax.sort``), padding with
int32 max beyond the true length. ``CAUSE_TPU_SORT=pallas`` flips the
kernels here at trace time (see ``bitonic.sort_pairs``).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_bitonic_sort"]

_I32_MAX = jnp.iinfo(jnp.int32).max
_ROWS = 8  # rows per grid block (the Mosaic sublane tiling unit)


def _interpret() -> bool:
    """Interpret off-TPU (tests, dryrun); compile via Mosaic on TPU."""
    return jax.default_backend() != "tpu"


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _kernel_body(refs, n_ops: int, num_keys: int):
    """One block: load every operand, run the whole network in VMEM,
    store. ``refs`` = n_ops input refs + n_ops output refs. The
    original-position tie-break key is generated IN-KERNEL from
    ``broadcasted_iota`` (it is exactly arange(P) per row) rather than
    passed as an operand — one less int32 array round-tripping HBM.
    Key order: keys 0..num_keys-1, then the position key, exactly as
    bitonic.bitonic_sort."""
    ins = refs[:n_ops]
    outs = refs[n_ops:]
    R, P = ins[0].shape
    iota = lax.broadcasted_iota(jnp.int32, (R, P), 1)
    arrs = [r[:] for r in ins] + [iota]
    key_pos = list(range(num_keys)) + [n_ops]

    k = 2
    while k <= P:
        j = k // 2
        while j >= 1:
            lower = (iota & j) == 0
            # final merge (k == P): every i has bit P clear, so asc is
            # all-true automatically — no special case needed
            asc = (iota & k) == 0
            partners = [
                jnp.where(lower,
                          jnp.roll(x, -j, axis=1),
                          jnp.roll(x, j, axis=1))
                for x in arrs
            ]
            # strict total order (the iota key breaks every tie), so
            # one lexicographic compare decides the exchange
            lt = None
            eq = None
            for kp in key_pos:
                a, b = arrs[kp], partners[kp]
                this_lt = a < b
                this_eq = a == b
                if lt is None:
                    lt, eq = this_lt, this_eq
                else:
                    lt = lt | (eq & this_lt)
                    eq = eq & this_eq
            want_self = lt == (lower == asc)
            arrs = [jnp.where(want_self, x, p)
                    for x, p in zip(arrs, partners)]
            j //= 2
        k *= 2

    for o, x in zip(outs, arrs):  # arrs[n_ops] (the position key) is
        o[:] = x                  # dropped: len(outs) == n_ops


@lru_cache(maxsize=None)
def _build(n_ops: int, num_keys: int):
    """The (batched, single) pallas callables for an operand count.
    Cached so repeated traces reuse the same custom_vmap object."""

    def kernel(*refs):
        _kernel_body(refs, n_ops, num_keys)

    def batch_call(*ops):
        B, P = ops[0].shape
        Bp = -(-B // _ROWS) * _ROWS
        if Bp != B:
            # padded rows sort their (MAX-key, iota) lanes — discarded
            ops = tuple(
                jnp.pad(x, ((0, Bp - B), (0, 0)),
                        constant_values=_I32_MAX if i < num_keys else 0)
                for i, x in enumerate(ops))
        spec = pl.BlockSpec((_ROWS, P), lambda b: (b, 0),
                            memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            kernel,
            grid=(Bp // _ROWS,),
            in_specs=[spec] * n_ops,
            out_specs=[spec] * n_ops,
            out_shape=[jax.ShapeDtypeStruct((Bp, P), jnp.int32)] * n_ops,
            interpret=_interpret(),
        )(*ops)
        return tuple(x[:B] for x in out)

    @jax.custom_batching.custom_vmap
    def single(*ops):
        P = ops[0].shape[0]
        spec = pl.BlockSpec(memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            kernel,
            in_specs=[spec] * n_ops,
            out_specs=[spec] * n_ops,
            out_shape=[jax.ShapeDtypeStruct((1, P), jnp.int32)] * n_ops,
            interpret=_interpret(),
        )(*(x.reshape(1, P) for x in ops))
        return tuple(x.reshape(P) for x in out)

    @single.def_vmap
    def _single_vmap(axis_size, in_batched, *ops):
        ops = tuple(
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(ops, in_batched))
        return batch_call(*ops), (True,) * n_ops

    return single, batch_call


def pallas_bitonic_sort(operands, num_keys: int = 1):
    """Sort int32 arrays along the last axis inside one VMEM-resident
    Pallas kernel (see module docstring; contract identical to
    ``bitonic.bitonic_sort``)."""
    operands = tuple(operands)
    for x in operands:
        if x.dtype != jnp.int32:
            raise TypeError(f"pallas sort is int32-only, got {x.dtype}")
    n = operands[0].shape[-1]
    P = max(128, _next_pow2(n))
    lead = operands[0].shape[:-1]
    arrs = []
    for i, x in enumerate(operands):
        if P != n:
            fill = _I32_MAX if i < num_keys else 0
            pad = jnp.full(lead + (P - n,), fill, x.dtype)
            x = jnp.concatenate([x, pad], axis=-1)
        arrs.append(x)
    # the deterministic-stability position key is generated inside the
    # kernel (broadcasted_iota), not passed as an operand

    single, batch_call = _build(len(arrs), num_keys)
    if not lead:
        # 1-D call (the kernels' per-row form; under vmap the
        # custom_vmap rule swaps in the gridded batch kernel)
        out = single(*arrs)
    else:
        # direct multi-dim call: flatten the lead dims onto the grid
        B = 1
        for d in lead:
            B *= d
        out = batch_call(*(x.reshape(B, P) for x in arrs))
        out = tuple(x.reshape(lead + (P,)) for x in out)

    if P != n:
        out = tuple(x[..., :n] for x in out)
    return tuple(out)
