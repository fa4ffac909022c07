"""Matrix rank-sort — a comparator-free, random-access-free XLA sort.

The round-5 window-1 stage profile put the chip's cost in phase E's
token sorts and rank gathers (~3 s of the 3.75 s headline), and showed
Mosaic Pallas — the VMEM-resident fix — could not be compiled by the
remote compile helper of the time. This module is the remaining pure-XLA
answer for the sort family: compute each element's *stable-sort
position* (rank) with a BLOCKED O(n^2) comparison count — elementwise
work the VPU streams, with the reduction fused per block so no [n, n]
matrix ever materializes — then invert the permutation with a second
blocked count pass (equality-select of iota: still elementwise, zero
scatters) and apply it via the streaming 128-lane rowgather. No
comparator loop, no per-element HBM transaction anywhere.

Cost model at the kernel's token widths (n ~ 2.3k, batch 1024): two
n^2 elementwise passes ~ 10 G simple int ops (tens of ms across the
batch) + one rowgather per operand (~1.5 ms/site measured class) —
versus XLA's comparator sort whose serialized constants the round-4
arithmetic priced at 300-500 ms per sort at the same shape. The n^2
form inverts at larger n: this is a strategy for the kernels'
few-thousand-lane sort widths, not a general sort.

Semantics: identical to stable ``lax.sort`` (the implicit iota
tie-break makes rank the unique stable order), same contract as
``weaver.bitonic``: int32 operands, last-axis sort, ascending
lexicographic over the first ``num_keys`` operands; remaining operands
ride as payloads. Keys may use the full int32 range including the
``I32_MAX`` invalid-lane sentinel (sentinel lanes sort last among
reals, ahead of padding only by the iota tie-break — exactly as with
``lax.sort`` on the unpadded array).

Reference anchor: one strategy for the batched replacement of the
serial weave linearization at
/root/reference/src/causal/collections/shared.cljc:225-241; the
reference has no vectorized sort to mirror.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .gatherops import rowgather1d

__all__ = ["matrix_sort"]

_I32_MAX = jnp.iinfo(jnp.int32).max
# Query-block width for the n^2 passes: bounds the per-step compare
# intersection to [block, n] even if XLA declines to fuse it, and keeps
# the scan short (n/block steps) so loop overhead stays noise.
_BLOCK = 256


def _lex_lt_block(keys, qkeys):
    """[q, n] lexicographic compare matrix: entry [i, j] is true iff
    element j (full axis) sorts strictly before query element i. The
    final entry of each key list is the iota tie-break, so "before" is
    the stable order and the matrix rows count to unique ranks."""
    lt = None
    eq = None
    for kf, kq in zip(keys, qkeys):
        a = kf[None, :]
        b = kq[:, None]
        this_lt = a < b
        this_eq = a == b
        if lt is None:
            lt, eq = this_lt, this_eq
        else:
            lt = lt | (eq & this_lt)
            eq = eq & this_eq
    return lt


def _matrix_sort_1d(operands, num_keys: int):
    n = operands[0].shape[-1]
    p = -(-n // _BLOCK) * _BLOCK
    iota = jnp.arange(p, dtype=jnp.int32)
    keys = []
    for x in operands[:num_keys]:
        if p != n:
            x = jnp.concatenate(
                [x, jnp.full((p - n,), _I32_MAX, x.dtype)]
            )
        keys.append(x)
    # padding ties with real I32_MAX keys are broken by iota (pads sit
    # past n), so real ranks are exactly 0..n-1 and pads n..p-1
    keys.append(iota)
    starts = jnp.arange(p // _BLOCK, dtype=jnp.int32) * _BLOCK

    def rank_blk(carry, s):
        q = [lax.dynamic_slice_in_dim(k, s, _BLOCK) for k in keys]
        lt = _lex_lt_block(keys, q)
        return carry, jnp.sum(lt.astype(jnp.int32), axis=-1)

    _, ranks = lax.scan(rank_blk, None, starts)
    rank = ranks.reshape(p)

    # invert the permutation with the same blocked idiom (src[r] = the
    # element whose rank is r): equality-select of iota + sum — one
    # term survives per output, so int32 stays exact and nothing
    # scatters
    def src_blk(carry, s):
        r = s + jnp.arange(_BLOCK, dtype=jnp.int32)
        eqm = rank[None, :] == r[:, None]
        return carry, jnp.sum(
            jnp.where(eqm, iota[None, :], 0), axis=-1
        ).astype(jnp.int32)

    _, srcs = lax.scan(src_blk, None, starts)
    src = srcs.reshape(p)[:n]

    outs = []
    for i, x in enumerate(operands):
        # rowgather unconditionally: the strategy's own gather must be
        # the streaming one or a single-switch sort=matrix A/B would
        # re-import the per-element-gather cost it exists to remove
        outs.append(rowgather1d(x, src).astype(x.dtype))
    return tuple(outs)


def matrix_sort(operands, num_keys: int = 1):
    """Stable last-axis lexicographic sort (see module docstring).
    Leading batch dimensions are flattened and vmapped — the blocked
    scans batch transparently."""
    from ..obs import span

    operands = tuple(operands)
    shape = operands[0].shape
    with span("weave.sort.matrix", width=int(shape[-1]),
              n_ops=len(operands)):
        if len(shape) == 1:
            return _matrix_sort_1d(operands, num_keys)
        flat = [x.reshape((-1, shape[-1])) for x in operands]
        out = jax.vmap(lambda *o: _matrix_sort_1d(o, num_keys))(*flat)
        return tuple(x.reshape(shape) for x in out)
