"""The v3 merge+weave kernel: sparse-irregular linearization.

Profiling the v2 pipeline phase by phase on a TPU v5e (1024 pairs x
10k nodes) showed the cost is NOT the id sort (~97 ms);
it is every gather/scatter/sort pass that runs at full lane width:
the 2M-record sort-join cause resolution (~5.3 s), the full-width
pointer-doubling host jump (~1.9 s), and the full-width visibility
scatter+gather (~0.7 s). TPUs stream contiguous tiles superbly but
pay dearly for random access — so v3 restructures the whole merge so
that full-width work is *elementwise and scan only*, and every random
access (binary search, gather, scatter, sort) happens at the
chain-compressed run width K (~2k for the north-star workload,
a 10,000x narrower access stream):

- **union + adjacency, no gather**: after the one id lexsort,
  duplicate lanes are key-equal, so "my cause is the previous kept
  node" is a shifted key compare against the previous *raw* lane —
  pure elementwise.
- **compaction by binary search, not scatter**: the lanes that need
  real work (run heads, "irregular" lanes) are pulled into K static
  slots by searching the cumulative count for 1..K — K log N gathered
  elements instead of an N-wide scatter.
- **cause resolution at K**: only irregular lanes binary-search the
  sorted id lanes ((hi, lo) pair compares in int32 — no int64 needed),
  instead of sort-joining all 2M records.
- **host jumps at K**: the first-non-special-ancestor walk steps
  through a ``back1`` table built elementwise (+ K sparse updates),
  iterating only as deep as real special chains go (1-3 links),
  with query width K.
- **expansion by delta-cumsum, no gather**: per-run preorder bases
  become deltas between lane-consecutive runs (K-wide), scattered to
  K head lanes and cumsum'd — the rank of every lane materializes
  from one full-width cumsum.
- **visibility by direction-flipped scans**: "is my weave successor a
  hide targeting me" splits into the in-run case (a reversed
  forward-fill — elementwise) and the run-tail case (K-wide preorder
  successor lookup).

Semantics are identical to ``jaxw.linearize``/``linearize_v2`` (the
port-of-record pure weaver remains the oracle; parity is fuzz-tested).
Like v2 it needs a static run budget ``k_max`` and reports overflow;
unlike v2, a tree where a node's host happens to be its kept-lane
predecessor while its literal cause is a *non-adjacent* special splits
one extra run (a refinement — the preorder is unchanged because any
node with external children is always a run tail).
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .arrays import I32_MAX, VCLASS_H_HIDE, VCLASS_HIDE
from .jaxw import _euler_rank, _link_children

__all__ = [
    "merge_weave_kernel_v3",
    "batched_merge_weave_v3",
]


def _shift1(x, fill):
    """The previous lane's value (x shifted right by one)."""
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def merge_weave_kernel_v3(hi, lo, cause_hi, cause_lo, vclass, valid,
                          k_max: int):
    """Union + reweave for one replica set, sparse-irregular style.

    Same contract as ``jaxw.merge_weave_kernel_v2``: inputs are the
    concatenated (hi, lo)/(cause_hi, cause_lo)/vclass/valid lanes of
    any number of id-sorted trees (invalid lanes carry int32 max);
    returns ``(order, rank, visible, conflict, overflow)``.
    """
    N = hi.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    targets = jnp.arange(1, k_max + 1, dtype=jnp.int32)

    # ---- union: one id sort, then everything below is elementwise
    order = jnp.lexsort((lo, hi))
    h, l = hi[order], lo[order]
    ch, cl = cause_hi[order], cause_lo[order]
    vc, va = vclass[order], valid[order]

    prev_h, prev_l = _shift1(h, I32_MAX), _shift1(l, I32_MAX)
    dup = (h == prev_h) & (l == prev_l) & (idx > 0)
    keep = va & ~dup
    conflict = jnp.any(
        dup & va & (
            (ch != _shift1(ch, 0)) | (cl != _shift1(cl, 0))
            | (vc != _shift1(vc, 0))
        )
    )

    cum_keep = jnp.cumsum(keep.astype(jnp.int32))
    kidx = cum_keep - 1
    n_kept = cum_keep[-1]
    is_root = keep & (idx == 0)
    special = keep & (vc > 0)
    rel = keep & ~is_root

    # previous kept lane and its specialness, from ONE packed forward
    # fill: lane*2 | special of the last kept lane at-or-before here
    sp_pack = lax.cummax(
        jnp.where(keep, idx * 2 + special.astype(jnp.int32), -1)
    )
    sp_prev = _shift1(sp_pack, -1)
    prev_kept = jnp.where(sp_prev >= 0, sp_prev >> 1, -1)
    prev_kept_special = (sp_prev >= 0) & (sp_prev % 2 == 1)

    # adjacency: my cause id IS the previous lane's id. Duplicate lanes
    # carry the kept head's key, so the raw shift compare equals a
    # compare against the previous *kept* node — no gather.
    adj = rel & (ch == prev_h) & (cl == prev_l) & (prev_kept >= 0)
    # a non-special adjacent to a special still needs a host jump
    host_case = adj & ~special & prev_kept_special
    irregular = rel & (~adj | host_case)

    # ---- compact irregular lanes into K slots via binary search
    ir_cum = jnp.cumsum(irregular.astype(jnp.int32))
    n_irr = ir_cum[-1]
    q_lane = jnp.searchsorted(ir_cum, targets, side="left").astype(jnp.int32)
    q_valid = targets <= jnp.minimum(n_irr, k_max)
    q_c = jnp.clip(q_lane, 0, N - 1)
    q_ch, q_cl = ch[q_c], cl[q_c]
    q_adj = adj[q_c]
    q_prev = prev_kept[q_c]
    q_special = special[q_c]

    # ---- resolve irregular causes: (hi, lo) pair binary search at K
    steps = max(1, math.ceil(math.log2(max(2, N)))) + 1
    def sbody(_, c):
        lo_b, hi_b = c
        mid = (lo_b + hi_b) // 2
        ms = jnp.clip(mid, 0, N - 1)
        less = (h[ms] < q_ch) | ((h[ms] == q_ch) & (l[ms] < q_cl))
        return jnp.where(less, mid + 1, lo_b), jnp.where(less, hi_b, mid)

    # derive the carries from varying data (zeros_like, not zeros) so
    # the binary search traces under shard_map, where a replicated
    # constant carry would clash with the varying output axis
    lo_b, hi_b = lax.fori_loop(
        0, steps, sbody,
        (jnp.zeros_like(q_lane), jnp.full_like(q_lane, N)),
    )
    pos = jnp.clip(lo_b, 0, N - 1)
    found = (h[pos] == q_ch) & (l[pos] == q_cl)
    # a miss is a dangling cause: child of root (v1/v2 clip semantics)
    q_cause = jnp.where(q_adj, q_prev,
                        jnp.where(found, pos, 0)).astype(jnp.int32)

    # ---- host jump at K: walk one-step parents until non-special.
    # back1: glued specials step to their kept predecessor, irregular
    # specials to their resolved cause, non-specials to themselves.
    back1 = jnp.where(special & adj, prev_kept, idx).astype(jnp.int32)
    back1 = back1.at[
        jnp.where(q_valid & q_special, q_lane, N)
    ].set(q_cause, mode="drop")

    def wcond(c):
        host, i = c
        hs = jnp.clip(host, 0, N - 1)
        return (i < N) & jnp.any(q_valid & ~q_special & special[hs])

    def wbody(c):
        host, i = c
        hs = jnp.clip(host, 0, N - 1)
        step = q_valid & ~q_special & special[hs]
        return jnp.where(step, back1[hs], host), i + 1

    host_q, _ = lax.while_loop(wcond, wbody, (q_cause, jnp.int32(0)))
    q_parent = jnp.where(q_special, q_cause, host_q)

    # ---- glue: an adjacent child only glues if its parent has no
    # other (irregular) children; any node with external children is
    # thereby a run tail, so child runs always attach after whole runs
    extra = jnp.zeros(N, jnp.int32).at[
        jnp.where(q_valid, q_parent, N)
    ].add(1, mode="drop")
    ec_pack = lax.cummax(
        jnp.where(keep, idx * 2 + (extra > 0).astype(jnp.int32), -1)
    )
    ec_prev = _shift1(ec_pack, -1)
    prev_kept_contested = (ec_prev >= 0) & (ec_prev % 2 == 1)
    glued = adj & ~host_case & ~prev_kept_contested

    run_start = keep & ~glued
    rs_cum = jnp.cumsum(run_start.astype(jnp.int32))
    run_id = rs_cum - 1
    n_runs = rs_cum[-1]
    overflow = n_runs > k_max

    # ---- compact run heads into K slots
    head_lane = jnp.searchsorted(rs_cum, targets, side="left").astype(
        jnp.int32
    )
    r_valid = targets <= jnp.minimum(n_runs, k_max)
    head_c = jnp.clip(head_lane, 0, N - 1)

    # head parent lane: irregular heads resolved above; the rest are
    # contested-adjacent heads whose parent is their kept predecessor
    parent_full = jnp.full(N, -1, jnp.int32).at[
        jnp.where(q_valid, q_lane, N)
    ].set(q_parent, mode="drop")
    h_parent_lane = jnp.where(
        irregular[head_c], parent_full[head_c],
        jnp.where(adj[head_c], prev_kept[head_c], -1),
    )
    h_parent_lane = jnp.where(r_valid & ~is_root[head_c], h_parent_lane, -1)
    parent_run = jnp.where(
        h_parent_lane >= 0,
        run_id[jnp.clip(h_parent_lane, 0, N - 1)],
        -1,
    ).astype(jnp.int32)

    h_special = special[head_c]
    h_kidx = kidx[head_c]
    nxt_kidx = jnp.concatenate([h_kidx[1:], h_kidx[:1]])  # filler tail
    run_len = jnp.where(
        r_valid,
        jnp.where(targets == n_runs, n_kept - h_kidx, nxt_kidx - h_kidx),
        0,
    ).astype(jnp.int32)

    # ---- contracted sibling sort + Euler ranking, all at K
    parent_sort = jnp.where(r_valid & (parent_run >= 0), parent_run, k_max)
    packed = parent_sort * 2 + (~h_special).astype(jnp.int32)
    sord = jnp.lexsort((-head_c, packed))
    fc, ns = _link_children(sord, parent_sort)
    parent_up = jnp.where(r_valid & (parent_run >= 0), parent_run, -1)
    base, _ = _euler_rank(fc, ns, parent_up, run_len)

    # ---- expansion: per-run bases become deltas between lane-
    # consecutive runs; one cumsum materializes every lane's rank
    delta = jnp.where(
        r_valid, base - jnp.concatenate([jnp.zeros((1,), base.dtype),
                                         base[:-1]]), 0
    )
    delta_n = jnp.zeros(N, jnp.int32).at[
        jnp.where(r_valid, head_c, N)
    ].set(delta.astype(jnp.int32), mode="drop")
    base_ff = jnp.cumsum(delta_n)
    ffh = lax.cummax(jnp.where(run_start, kidx, -1))
    rank = jnp.where(keep, base_ff + (kidx - ffh), N).astype(jnp.int32)

    # ---- visibility: weave successor is a hide targeting me.
    # in-run: the next kept lane is a glued hide (its cause IS me) —
    # a reversed forward-fill, elementwise
    hideish = (vc == VCLASS_HIDE) | (vc == VCLASS_H_HIDE)
    kg = glued & hideish
    rpack = lax.cummax(
        jnp.where(jnp.flip(keep), idx * 2 + jnp.flip(kg).astype(jnp.int32),
                  -1)
    )
    rprev = _shift1(rpack, -1)
    killed_inrun = jnp.flip((rprev >= 0) & (rprev % 2 == 1))

    # run tails: the preorder-successor run's head may hide me (K-wide)
    run_by_pos = jnp.full(N, -1, jnp.int32).at[
        jnp.where(r_valid, jnp.clip(base, 0, N - 1), N)
    ].set(jnp.arange(k_max, dtype=jnp.int32), mode="drop")
    succ_pos = base + run_len
    succ_run = jnp.where(
        r_valid & (succ_pos < n_kept),
        run_by_pos[jnp.clip(succ_pos, 0, N - 1)],
        -1,
    )
    s_c = jnp.clip(
        jnp.where(succ_run >= 0, head_c[jnp.clip(succ_run, 0, k_max - 1)],
                  0),
        0, N - 1,
    )
    s_is_hide = (succ_run >= 0) & (
        (vc[s_c] == VCLASS_HIDE) | (vc[s_c] == VCLASS_H_HIDE)
    )
    # tail of run r = the kept lane before the NEXT run's head (lane
    # order); the last run's tail is the last kept lane overall. One
    # K-wide gather — no search.
    nxt_head = jnp.concatenate([head_c[1:], head_c[:1]])
    tail_lane = jnp.where(
        targets == n_runs,
        jnp.maximum(sp_pack[-1] >> 1, 0),
        prev_kept[jnp.clip(nxt_head, 0, N - 1)],
    ).astype(jnp.int32)
    t_c = jnp.clip(tail_lane, 0, N - 1)
    kill_tail = (
        r_valid & s_is_hide & (ch[s_c] == h[t_c]) & (cl[s_c] == l[t_c])
    )
    killed_tail = jnp.zeros(N, bool).at[
        jnp.where(kill_tail, t_c, N)
    ].set(True, mode="drop")

    visible = (
        keep & (vc == 0) & ~is_root & ~(killed_inrun | killed_tail)
    )
    return order, rank, visible, conflict, overflow


merge_weave_kernel_v3_jit = jax.jit(
    merge_weave_kernel_v3, static_argnames="k_max"
)


@partial(jax.jit, static_argnames="k_max")
def batched_merge_weave_v3(hi, lo, cause_hi, cause_lo, vclass, valid,
                           k_max: int):
    """Sparse-irregular batch: [B, M] lanes -> per-replica weave ranks.
    Same contract as ``jaxw.batched_merge_weave_v2``."""

    def row(h, l, ch, cl, vc, va):
        return merge_weave_kernel_v3(h, l, ch, cl, vc, va, k_max)

    return jax.vmap(row)(hi, lo, cause_hi, cause_lo, vclass, valid)
