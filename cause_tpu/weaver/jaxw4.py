"""The v4 merge+weave kernel: marshal-resolved causes, no device search.

TPU profiling of v3 (PERF.md, scripts/probe_stage1.py) showed the
remaining cost concentrated in exactly the places where the kernel
re-derives information the HOST already had at marshal time:

- the 15-round (hi, lo) binary search resolving irregular causes
  (~1.4 s at 1024x20k) re-discovers, per merge, which lane each cause
  id lives at — but every input tree already knows its own causes
  (``NodeArrays.cause_idx`` is computed once per tree, and insert
  validates cause-must-exist, so intra-tree resolution never fails);
- the 6-array sort-permutation moves the cause id lanes (chi, clo)
  through HBM only to feed that search.

v4 therefore changes the device contract: instead of cause *ids*, each
lane carries ``cci`` — the index of its cause in the **concatenated
pre-sort lane array** (tree offset + within-tree cause index, free at
marshal time; -1 for the root / padding). On device, cause resolution
collapses to two data movements, both O(N):

1. one id sort carrying ``(iota, vclass, cci)`` payloads — the iota
   payload IS the sort permutation ``order``;
2. ``concat2head``: scatter each sorted lane's *kept-head* position to
   its concat slot (``.at[order].set(khead)``) — the inverse
   permutation composed with duplicate-collapse in a single scatter —
   then ``cause_pos = concat2head[cci]``, one gather.

``khead`` (last non-duplicate lane at-or-before each sorted position)
redirects a cause that resolved to a *dropped duplicate* copy of a node
to the kept copy, which is what makes the trick sound for K-ary unions:
duplicate lanes are key-equal and adjacent after the sort.

Everything downstream (chain runs, contracted Euler ranking,
delta-cumsum rank expansion, direction-flipped visibility) matches
``jaxw3.merge_weave_kernel_v3`` — with the host-jump walk stepping
through ``cause_pos`` directly (a special's parent IS its cause,
shared.cljc:225-241 semantics via jaxw.linearize's derived tree T*).
Run-budget ``k_max`` + overflow flag behave exactly like v2/v3; the
pure weaver remains the oracle and v1 the device reference
(tests/test_jax_v4.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .arrays import I32_MAX, VCLASS_H_HIDE, VCLASS_HIDE
from .jaxw import _euler_rank, _link_children
from .jaxw3 import _shift1

__all__ = [
    "merge_weave_kernel_v4",
    "batched_merge_weave_v4",
]


def merge_weave_kernel_v4(hi, lo, cci, vclass, valid, k_max: int):
    """Union + reweave for one replica set, marshal-resolved causes.

    Inputs are the concatenated lanes of any number of trees, each
    individually in ascending id order: ``hi``/``lo`` int32 id lanes
    (invalid lanes MUST carry I32_MAX in both — ``NodeArrays.id_lanes``
    and ``benchgen`` guarantee it), ``cci`` the concat index of each
    lane's cause (-1 for root/none/padding), ``vclass``, ``valid``.
    Returns ``(order, rank, visible, conflict, overflow)`` exactly like
    ``jaxw3.merge_weave_kernel_v3``.
    """
    N = hi.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    targets = jnp.arange(1, k_max + 1, dtype=jnp.int32)

    # ---- union: one 2-key sort carrying (order, vclass, cci) payloads
    hi = jnp.where(valid, hi, I32_MAX)
    lo = jnp.where(valid, lo, I32_MAX)
    h, l, order, vc, cci_s = lax.sort(
        (hi, lo, idx, vclass, cci.astype(jnp.int32)), num_keys=2
    )
    va = ~((h == I32_MAX) & (l == I32_MAX))

    prev_h, prev_l = _shift1(h, -1), _shift1(l, -1)
    dup = (h == prev_h) & (l == prev_l) & (idx > 0) & va
    keep = va & ~dup

    # ---- cause resolution: concat slot -> kept head of the sorted
    # duplicate group, one scatter + one gather
    khead = lax.cummax(jnp.where(keep, idx, -1))
    concat2head = jnp.zeros(N, jnp.int32).at[order].set(khead)
    cp = concat2head[jnp.clip(cci_s, 0, N - 1)]
    cause_pos = jnp.where(va & (cci_s >= 0), cp, 0).astype(jnp.int32)

    # duplicate lanes must agree on body (cause + value class); equal
    # cause ids resolve to equal kept heads, so positions compare ids
    conflict = jnp.any(
        dup & (
            (vc != _shift1(vc, 0)) | (cause_pos != _shift1(cause_pos, 0))
        )
    )

    cum_keep = jnp.cumsum(keep.astype(jnp.int32))
    kidx = cum_keep - 1
    n_kept = cum_keep[-1]
    is_root = keep & (idx == 0)
    special = keep & (vc > 0)
    rel = keep & ~is_root

    sp_pack = lax.cummax(
        jnp.where(keep, idx * 2 + special.astype(jnp.int32), -1)
    )
    sp_prev = _shift1(sp_pack, -1)
    prev_kept = jnp.where(sp_prev >= 0, sp_prev >> 1, -1)
    prev_kept_special = (sp_prev >= 0) & (sp_prev % 2 == 1)

    # adjacency: my cause IS the previous kept node (v3 compared raw
    # shifted ids; duplicate lanes carry the head's key so the two
    # formulations agree)
    adj = rel & (cause_pos == prev_kept) & (prev_kept >= 0)
    host_case = adj & ~special & prev_kept_special
    irregular = rel & (~adj | host_case)

    # ---- compact irregular lanes into K slots
    ir_cum = jnp.cumsum(irregular.astype(jnp.int32))
    n_irr = ir_cum[-1]
    q_lane = jnp.searchsorted(ir_cum, targets, side="left").astype(jnp.int32)
    q_valid = targets <= jnp.minimum(n_irr, k_max)
    q_c = jnp.clip(q_lane, 0, N - 1)
    q_special = special[q_c]
    q_cause = cause_pos[q_c]

    # ---- host jump at K: a special's parent is its cause, so the
    # first-non-special-ancestor walk steps through cause_pos itself
    def wcond(c):
        p, i = c
        ps = jnp.clip(p, 0, N - 1)
        return (i < N) & jnp.any(q_valid & ~q_special & special[ps])

    def wbody(c):
        p, i = c
        ps = jnp.clip(p, 0, N - 1)
        step = q_valid & ~q_special & special[ps]
        return jnp.where(step, cause_pos[ps], p), i + 1

    host_q, _ = lax.while_loop(wcond, wbody, (q_cause, jnp.int32(0)))
    q_parent = jnp.where(q_special, q_cause, host_q)

    # ---- glue: an adjacent child only glues if its parent has no
    # other (irregular) children (v3 refinement: any node with external
    # children is a run tail, so child runs attach after whole runs)
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

    # ---- expansion: per-run bases -> deltas -> one cumsum
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

    # ---- visibility. in-run: next kept lane is a glued hide (its
    # cause IS me) — reversed forward-fill, elementwise
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
    # tail of run r = the kept lane before the NEXT run's head; last
    # run's tail is the last kept lane overall. Cause ids compare as
    # kept-head positions, so "succ head hides the tail" is one compare
    nxt_head = jnp.concatenate([head_c[1:], head_c[:1]])
    tail_lane = jnp.where(
        targets == n_runs,
        jnp.maximum(sp_pack[-1] >> 1, 0),
        prev_kept[jnp.clip(nxt_head, 0, N - 1)],
    ).astype(jnp.int32)
    t_c = jnp.clip(tail_lane, 0, N - 1)
    kill_tail = r_valid & s_is_hide & (cause_pos[s_c] == t_c)
    killed_tail = jnp.zeros(N, bool).at[
        jnp.where(kill_tail, t_c, N)
    ].set(True, mode="drop")

    visible = (
        keep & (vc == 0) & ~is_root & ~(killed_inrun | killed_tail)
    )
    return order, rank, visible, conflict, overflow


merge_weave_kernel_v4_jit = jax.jit(
    merge_weave_kernel_v4, static_argnames=("k_max",)
)


@partial(jax.jit, static_argnames=("k_max",))
def batched_merge_weave_v4(hi, lo, cci, vclass, valid, k_max: int):
    """Marshal-resolved batch: [B, M] lanes -> per-replica weave ranks.
    Same output contract as ``jaxw3.batched_merge_weave_v3``; inputs
    swap the cause id lanes (chi, clo) for the single ``cci`` lane."""

    def row(h, l, cc, vc, va):
        return merge_weave_kernel_v4(h, l, cc, vc, va, k_max)

    return jax.vmap(row)(hi, lo, cci, vclass, valid)
