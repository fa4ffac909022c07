"""TPU primitive microbenchmarks for the weave kernel's building blocks.

Methodology notes:

- sync on a device->host transfer of a scalar (``float(x)``).
- every dispatch pays a fixed round trip; per-op cost is measured as
  the *slope* between an in-jit loop of K ops and one op, not as
  single-dispatch wall time.

Usage: python scripts/tpu_microbench.py [name ...]
Names: elementwise cumsum gather rowgather lexsort2 lexsort3 scatter
       (default: all, sequentially).
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

B, M = 64, 20480  # the per-shard shape of the north-star batch


def _slope(f, args, iters=8):
    """Per-op ms via in-jit chaining: (t_many - t_one) / (iters - 1)."""

    @jax.jit
    def many(*a):
        def body(_, x):
            return f(*x)

        return lax.fori_loop(0, iters, body, a)

    @jax.jit
    def once(*a):
        return f(*a)

    float(jnp.sum(many(*args)[0]))  # compile + warm
    float(jnp.sum(once(*args)[0]))
    t0 = time.perf_counter()
    float(jnp.sum(many(*args)[0]))
    t_many = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(jnp.sum(once(*args)[0]))
    t_one = time.perf_counter() - t0
    return (t_many - t_one) / (iters - 1) * 1e3, t_one * 1e3


def _data():
    rng = np.random.default_rng(0)
    val = jnp.asarray(rng.integers(0, 1 << 20, (B, M), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, M, (B, M), dtype=np.int32))
    return val, idx


def bench_elementwise():
    val, idx = _data()
    return _slope(lambda v, i: ((v * i + 1) & 0xFFFFF, i), (val, idx))


def bench_cumsum():
    val, idx = _data()
    return _slope(lambda v, i: (jnp.cumsum(v, axis=1) & 0xFFFFF, i),
                  (val, idx))


def bench_gather():
    val, idx = _data()
    return _slope(
        lambda v, i: (jnp.take_along_axis(v, i, axis=1) & 0xFFFFF, i),
        (val, idx),
    )


def bench_rowgather():
    """Scalar gather as 128-wide row fetch + one-hot contraction: trades
    128x data amplification for the TPU's fast row-gather path."""
    val, idx = _data()

    def f(v, i):
        rows = v.reshape(B, M // 128, 128)
        fetched = jnp.take_along_axis(
            rows, (i >> 7)[:, :, None], axis=1
        )  # [B, M, 128]
        onehot = (
            lax.broadcasted_iota(jnp.int32, (B, M, 128), 2)
            == (i & 127)[:, :, None]
        )
        out = jnp.sum(fetched * onehot.astype(jnp.int32), axis=2)
        return out & 0xFFFFF, i

    return _slope(f, (val, idx))


def bench_lexsort2():
    val, idx = _data()
    # carry the full-shape permutation so fori_loop chaining is legal
    return _slope(lambda v, i: (jnp.lexsort((i, v)), i), (val, idx))


def bench_lexsort3():
    val, idx = _data()
    return _slope(lambda v, i: (jnp.lexsort((i, v, i)), i), (val, idx))


def bench_scatter():
    val, idx = _data()

    def f(v, i):
        out = jnp.zeros((B, M + 1), jnp.int32)
        out = jax.vmap(lambda o, ii, vv: o.at[ii].set(vv))(out, i, v)
        return out[:, :M], i

    return _slope(f, (val, idx))


def _tok_data():
    """Token-width shape of the v5 pipeline: [1024 rows, 2252 tokens]."""
    rng = np.random.default_rng(1)
    hi = jnp.asarray(rng.integers(0, 1 << 20, (1024, 2252),
                                  dtype=np.int32))
    lo = jnp.asarray(rng.integers(0, 1 << 20, (1024, 2252),
                                  dtype=np.int32))
    src = jnp.broadcast_to(jnp.arange(2252, dtype=jnp.int32),
                           (1024, 2252))
    return hi, lo, src


def bench_toksort():
    """lax.sort, 2 keys + payload, at the v5 token shape — the kernel's
    C-phase workhorse."""
    hi, lo, src = _tok_data()
    return _slope(
        lambda a, b, s: lax.sort((a, b, s), num_keys=2), (hi, lo, src)
    )


def bench_tokbitonic():
    """bitonic_sort at the same shape — the CAUSE_TPU_SORT=bitonic
    alternative (pure elementwise stages)."""
    from cause_tpu.weaver.bitonic import bitonic_sort

    hi, lo, src = _tok_data()
    return _slope(
        lambda a, b, s: bitonic_sort((a, b, s), num_keys=2),
        (hi, lo, src),
    )


def bench_tokgather():
    """XLA gather at the v5 query shape: 2252 queries/row from the
    20480-lane tables, 1024 rows."""
    rng = np.random.default_rng(2)
    tab = jnp.asarray(rng.integers(0, 1 << 20, (1024, 20480),
                                   dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, 20480, (1024, 2252),
                                   dtype=np.int32))
    return _slope(
        lambda t, i: (t, jnp.take_along_axis(t, i, axis=1)), (tab, idx)
    )


def bench_tokrowgather():
    """rowgather1d at the same query shape — the
    CAUSE_TPU_GATHER=rowgather alternative."""
    from cause_tpu.weaver.gatherops import rowgather1d

    rng = np.random.default_rng(2)
    tab = jnp.asarray(rng.integers(0, 1 << 20, (1024, 20480),
                                   dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, 20480, (1024, 2252),
                                   dtype=np.int32))
    return _slope(lambda t, i: (t, rowgather1d(t, i)), (tab, idx))


def bench_tokpallas():
    """pallas_bitonic_sort at the token shape — the round-4
    CAUSE_TPU_SORT=pallas candidate (VMEM-resident network)."""
    from cause_tpu.weaver.pallas_sort import pallas_bitonic_sort

    hi, lo, src = _tok_data()
    return _slope(
        lambda a, b, s: pallas_bitonic_sort((a, b, s), num_keys=2),
        (hi, lo, src),
    )


def bench_tokmatrix():
    """matrix_sort at the token shape — the round-5
    CAUSE_TPU_SORT=matrix candidate (blocked rank counting, pure-XLA
    streaming; no Mosaic compile needed)."""
    from cause_tpu.weaver.matsort import matrix_sort

    hi, lo, src = _tok_data()
    return _slope(
        lambda a, b, s: matrix_sort((a, b, s), num_keys=2),
        (hi, lo, src),
    )


def _scat_data():
    """Sorted-unique scatter targets: U=2252 distinct ascending lanes
    per row out of N=20480 — the index-stream shape the kernels'
    spread-dump rewrites guarantee."""
    rng = np.random.default_rng(3)
    tab = jnp.asarray(rng.integers(0, 1 << 20, (1024, 20480),
                                   dtype=np.int32))
    idx = jnp.asarray(np.sort(
        np.argsort(rng.random((1024, 20480)), axis=1)[:, :2252], axis=1
    ).astype(np.int32))
    val = jnp.asarray(rng.integers(-64, 64, (1024, 2252),
                                   dtype=np.int32))
    return tab, idx, val


def bench_tokscatter():
    """Plain XLA scatter-add, U values into N slots, 1024 rows."""
    tab, idx, val = _scat_data()

    def f(t, i, v):
        out = jax.vmap(lambda o, ii, vv: o.at[ii].add(vv))(t, i, v)
        return out, i, v

    return _slope(f, (tab, idx, val))


def bench_tokscatterhint():
    """The same scatter with unique_indices + indices_are_sorted —
    the CAUSE_TPU_SCATTER=hint candidate."""
    tab, idx, val = _scat_data()

    def f(t, i, v):
        out = jax.vmap(
            lambda o, ii, vv: o.at[ii].add(
                vv, unique_indices=True, indices_are_sorted=True)
        )(t, i, v)
        return out, i, v

    return _slope(f, (tab, idx, val))


def _search_bench(mode):
    import os

    from cause_tpu.weaver import gatherops

    rng = np.random.default_rng(4)
    kc = jnp.asarray(np.cumsum(
        rng.integers(0, 3, (1024, 2252)), axis=1).astype(np.int32))
    # the microbench A/Bs the search strategies against each other, so
    # flipping the one switch by name is the point of this function
    if mode:
        os.environ["CAUSE_TPU_SEARCH"] = mode  # causelint: disable=TID002 -- microbench A/Bs this switch deliberately
    else:
        os.environ.pop("CAUSE_TPU_SEARCH", None)  # causelint: disable=TID002 -- microbench A/Bs this switch deliberately
    try:
        def f(k):
            out = jax.vmap(
                lambda kk: gatherops.searchsorted_iota_right(kk, 2252)
            )(k)
            return (out,)

        return _slope(f, (kc,))
    finally:
        os.environ.pop("CAUSE_TPU_SEARCH", None)  # causelint: disable=TID002 -- microbench A/Bs this switch deliberately


def bench_searchhist():
    """searchsorted histogram form (scatter-add + cumsum) at U."""
    return _search_bench("")


def bench_searchmatrix():
    """searchsorted comparison-matrix form at U — the
    CAUSE_TPU_SEARCH=matrix candidate."""
    return _search_bench("matrix")


ALL = {
    "elementwise": bench_elementwise,
    "cumsum": bench_cumsum,
    "gather": bench_gather,
    "rowgather": bench_rowgather,
    "lexsort2": bench_lexsort2,
    "lexsort3": bench_lexsort3,
    "scatter": bench_scatter,
    "toksort": bench_toksort,
    "tokbitonic": bench_tokbitonic,
    "tokpallas": bench_tokpallas,
    "tokmatrix": bench_tokmatrix,
    "tokgather": bench_tokgather,
    "tokrowgather": bench_tokrowgather,
    "tokscatter": bench_tokscatter,
    "tokscatterhint": bench_tokscatterhint,
    "searchhist": bench_searchhist,
    "searchmatrix": bench_searchmatrix,
}

# the decision-driving subset the round-4 harvester runs in-claim
TOK_CASES = ("toksort", "tokbitonic", "tokpallas", "tokmatrix",
             "tokgather", "tokrowgather", "tokscatter",
             "tokscatterhint", "searchhist", "searchmatrix", "cumsum",
             "elementwise")


def main():
    names = sys.argv[1:] or list(ALL)
    print(f"devices: {jax.devices()}  shape: [{B}, {M}]")
    for name in names:
        per_op, once = ALL[name]()
        per_m = per_op / (B * M / 1e6)
        print(f"{name:12s}: {per_op:8.2f} ms/op  ({per_m:6.2f} ms/M-elem; "
              f"single dispatch {once:.1f} ms)", flush=True)


if __name__ == "__main__":
    main()
