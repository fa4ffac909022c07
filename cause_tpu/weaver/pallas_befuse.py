"""The fused v5 token pipeline (phases C-E) as VMEM-resident kernels.

jaxw5's token phases — sort, dedupe, cause redirection, run
extraction, euler ranking, kills, and the lane sort that hands off to
the F expansion — are ~40 XLA ops at token width. Each op is tiny
(~9 KB/row), but XLA lowers the sorts as comparator loops, the
scatters serially, and the cumulative ops as multi-pass reductions;
the one chip datum (PERF.md round 4: TPU slower than CPU at equal
structural work) attributes v5's cost to exactly these serializing
lowerings. This module runs the whole stretch in three Pallas kernels
(composed with the existing ``euler_walk`` and ``pallas_fphase``
kernels by ``jaxw5f``) with one HBM read/write per operand at kernel
edges:

- **K1 sort+redirect** (phase C+D minus the host walk, which jaxw5f
  hoists to XLA pre-sort where the gather strategies apply): the
  9-operand bitonic token sort, the inverse permutation (itself a
  payload-riding sort), duplicate detection, kept-head redirection of
  cause/host links, and the conflict reduction.
- **K2 run extraction** (phase E front): weighted positions,
  adjacency/host-case/contested classification, run numbering, and
  the contracted forest tables. Where jaxw5 gathers seven per-run
  values via searchsorted, K2 *compacts* them with one bitonic
  (token->run compaction = sort by run ordinal at run heads), and
  ``_link_children``'s scatters become inverse-sort rides plus one
  one-hot chunk pass.
- **K4 rank+kills+handoff** (phase E back): run bases expand to
  tokens with the fphase window trick (``run_id`` increments by at
  most 1 per token, so a 128-token tile references at most a 128-run
  window — no scatter, no cumsum), then token kills, the preorder
  -successor sort, and the final lane sort emitting ``(lk, tb_l)``
  for ``pallas_fphase``.

Every kernel processes one replica row at a time inside 8-row grid
blocks; the row computations are PURE functions on [1, P] int32
values (directly unit-testable against the jaxw5 phases with no
Pallas involved — tests/test_befuse.py does exactly that), and the
kernel bodies only loop rows and move refs. The remaining arbitrary
-index gathers are 128-wide one-hot chunks whose lane<->sublane
orientation flips ride one-MXU-dot identity contractions (exact:
every gathered value is a token index, run index, lane index, or
rank, all within +-2^24; the id lanes themselves are sort KEYS and
payloads, never gathered).

Semantics are EXACT vs jaxw5's XLA phases on non-overflow rows; on
overflow rows both pipelines return unspecified values under the same
raised flag. Reference anchor: same as jaxw5 — the weave
linearization of /root/reference/src/causal/collections/shared.cljc
:225-241 at batch width, token-granular.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .arrays import I32_MAX, VCLASS_H_HIDE, VCLASS_HIDE

__all__ = [
    "k1_sort_redirect", "k2_runs", "k4_rank_kills", "next_pow2",
    "row_k1", "row_k2", "row_k4",
]

_LANE = 128
_ROWS = 8
BIG = I32_MAX


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------
# in-kernel building blocks ([1, W] int32 values)
# ---------------------------------------------------------------------

def _eye_f32():
    i0 = lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    i1 = lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1)
    return (i0 == i1).astype(jnp.float32)


def _flip(eye, v_row):
    """[1, 128] -> [128, 1] via one MXU dot (exact within +-2^24;
    plain reshape in interpret mode — Mosaic has no cheap lane<->
    sublane relayout, XLA:CPU does)."""
    if _interpret():
        return jnp.reshape(v_row, (_LANE, 1))
    return lax.dot_general(
        eye, v_row.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def _unflip(eye, v_col):
    """[128, 1] -> [1, 128] via one MXU dot (exact within +-2^24;
    plain reshape in interpret mode)."""
    if _interpret():
        return jnp.reshape(v_col, (1, _LANE))
    return lax.dot_general(
        v_col.astype(jnp.float32), eye,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def _bitonic_vals(arrs, num_keys):
    """pallas_sort's compare-exchange network on in-kernel values:
    ascending lexicographic over the first ``num_keys`` arrays with an
    implicit original-position tie-break (== stable lax.sort).

    Interpret mode (CPU tests, dryruns) uses stable ``lax.sort``
    itself — the contract twin (tests/test_befuse.py pins the network
    against it directly) — because the unrolled network inside the
    interpreted kernels produces multi-thousand-op XLA:CPU programs
    that exhaust LLVM's memory maps at larger widths. The network path
    is what Mosaic compiles on TPU (and what the jax.export lowering
    guards pin)."""
    if _interpret():
        return list(lax.sort(tuple(arrs), num_keys=num_keys,
                             is_stable=True, dimension=1))
    R, P = arrs[0].shape
    iota = lax.broadcasted_iota(jnp.int32, (R, P), 1)
    arrs = list(arrs) + [iota]
    key_pos = list(range(num_keys)) + [len(arrs) - 1]

    k = 2
    while k <= P:
        j = k // 2
        while j >= 1:
            lower = (iota & j) == 0
            asc = (iota & k) == 0
            partners = [
                jnp.where(lower,
                          jnp.roll(x, -j, axis=1),
                          jnp.roll(x, j, axis=1))
                for x in arrs
            ]
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
            arrs = [jnp.where(want_self, x, p_)
                    for x, p_ in zip(arrs, partners)]
            j //= 2
        k *= 2
    return arrs[:-1]


def _cumsum(x):
    """Inclusive prefix sum along lanes via log-shift roll+add
    (int32 wraparound — exact, matching XLA cumsum). Reference op in
    interpret mode (see _bitonic_vals)."""
    if _interpret():
        return jnp.cumsum(x, axis=1, dtype=jnp.int32)
    _, P = x.shape
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < P:
        x = x + jnp.where(col >= s, jnp.roll(x, s, axis=1), 0)
        s *= 2
    return x


def _cummax(x):
    """Inclusive running max along lanes (reference op in interpret
    mode, see _bitonic_vals)."""
    if _interpret():
        return lax.cummax(x, axis=1)
    _, P = x.shape
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < P:
        x = jnp.maximum(
            x, jnp.where(col >= s, jnp.roll(x, s, axis=1),
                         jnp.int32(-BIG - 1)))
        s *= 2
    return x


def _shiftr(x, fill):
    """Previous lane's value (jaxw3._shift1 twin)."""
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col == 0, fill, jnp.roll(x, 1, axis=1))


def _rolln(x):
    """Next lane's value, wrapping (the concatenate([x[1:], x[:1]])
    idiom of jaxw5)."""
    return jnp.roll(x, -1, axis=1)


def _gather(eye, tables, idx, width=None):
    """``[t[0, i] for i in idx]`` for [1, W] int32 tables sharing one
    [1, Q] index vector: one-hot chunks over the first ``width``
    (default W) table lanes, MXU-contracted. ``idx`` must be
    pre-clipped to [0, width); gathered values must be within +-2^24
    (every caller gathers indices/ranks/lanes, asserted by jaxw5f)."""
    W = tables[0].shape[1]
    width = W if width is None else min(W, width)
    Q = idx.shape[1]
    if _interpret():
        return [jnp.take_along_axis(t, idx, axis=1) for t in tables]
    outs = [jnp.zeros((1, Q), jnp.float32) for _ in tables]
    for c in range(0, width, _LANE):
        i0 = c + lax.broadcasted_iota(jnp.int32, (_LANE, 1), 0)
        mask = (i0 == idx).astype(jnp.float32)        # [128, Q]
        for n, t in enumerate(tables):
            tc = _flip(eye, t[:, c:c + _LANE]).astype(jnp.float32)
            outs[n] = outs[n] + lax.dot_general(
                tc, mask, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return [o.astype(jnp.int32) for o in outs]


def _band(col, t):
    return (col >= t) & (col < t + _LANE)


def _scal_row(col8, *vals):
    """[1, 8] int32 row carrying scalars at positions 0..len-1."""
    out = jnp.zeros((1, 8), jnp.int32)
    for i, v in enumerate(vals):
        out = jnp.where(col8 == i, jnp.broadcast_to(
            jnp.reshape(v, (1, 1)), (1, 8)), out)
    return out


# ---------------------------------------------------------------------
# K1: token sort + dedupe + cause/host redirection (phases C + D)
# ---------------------------------------------------------------------

def row_k1(eye, t_hi, t_lo, t_vc, t_len, t_tsp, t_lane, cu0m, hu0m,
           U: int):
    """One row of phases C+D (pure; [1, P] int32 in/out). Mirrors
    jaxw5.merge_weave_kernel_v5 phases C..D exactly (the host walk is
    pre-resolved by the caller into ``hu0m``)."""
    P = t_hi.shape[1]
    uidx = lax.broadcasted_iota(jnp.int32, (1, P), 1)

    (st_hi, st_lo, t_src, sv_len, sv_vc, sv_tsp, sv_lane,
     sv_cu, sv_hu) = _bitonic_vals(
        (t_hi, t_lo, uidx, t_len, t_vc, t_tsp, t_lane, cu0m, hu0m),
        num_keys=2)
    inv_t = _bitonic_vals((t_src, uidx), num_keys=1)[1]

    tva = ~((st_hi == BIG) & (st_lo == BIG))
    sdup = ((st_hi == _shiftr(st_hi, -1))
            & (st_lo == _shiftr(st_lo, -1))
            & (uidx > 0) & tva)
    keep_t = tva & ~sdup

    thead = _cummax(jnp.where(keep_t, uidx, -1))
    raw_c = _gather(eye, [inv_t], jnp.clip(sv_cu, 0, U - 1))[0]
    red_c = _gather(eye, [thead], jnp.clip(raw_c, 0, U - 1))[0]
    cause_su = jnp.where(sv_cu >= 0, red_c, 0)
    raw_h = _gather(eye, [inv_t], jnp.clip(sv_hu, 0, U - 1))[0]
    red_h = _gather(eye, [thead], jnp.clip(raw_h, 0, U - 1))[0]
    host_su = jnp.where(sv_hu >= 0, red_h, 0)

    special_t = keep_t & (sv_vc > 0)
    parent_su = jnp.where(special_t, cause_su, host_su)

    conflict = jnp.sum(jnp.where(
        sdup & ((sv_vc != _shiftr(sv_vc, 0))
                | (cause_su != _shiftr(cause_su, 0))
                | (sv_len != _shiftr(sv_len, 0))),
        1, 0))

    return (sv_len, sv_vc, sv_tsp, sv_lane, keep_t.astype(jnp.int32),
            cause_su, parent_su, conflict)


# ---------------------------------------------------------------------
# K2: run extraction + contracted forest (phase E front)
# ---------------------------------------------------------------------

def row_k2(eye, sv_len, sv_vc, sv_tsp, keep_i, cause_su, parent_su,
           U: int, k_max: int, Kp: int):
    """One row of phase E's run machinery (pure). Returns the
    contracted-forest tables plus the token-level context K4 needs."""
    P = sv_len.shape[1]
    uidx = lax.broadcasted_iota(jnp.int32, (1, P), 1)
    colP = uidx
    kidx = lax.broadcasted_iota(jnp.int32, (1, Kp), 1)
    targets = kidx + 1
    keep_t = keep_i != 0
    special_t = keep_t & (sv_vc > 0)
    is_root_t = keep_t & (uidx == 0)
    rel_t = keep_t & ~is_root_t

    wcum = _cumsum(jnp.where(keep_t, sv_len, 0))
    wstart = wcum - jnp.where(keep_t, sv_len, 0)
    n_kept = wcum[:, P - 1:P]

    sp_pack = _cummax(jnp.where(
        keep_t, uidx * 2 + (sv_tsp != 0).astype(jnp.int32), -1))
    sp_prev = _shiftr(sp_pack, -1)
    prev_kept = jnp.where(sp_prev >= 0, sp_prev >> 1, -1)
    prev_kept_tsp = (sp_prev >= 0) & (sp_prev % 2 == 1)

    adj = rel_t & (cause_su == prev_kept) & (prev_kept >= 0)
    host_case = adj & ~special_t & prev_kept_tsp
    irregular = rel_t & (~adj | host_case)

    # contested parents: count irregular tokens per parent token
    # (parents are kept tokens, clipped < U by construction)
    psrc = jnp.where(irregular, parent_su, -1)
    contested_i = jnp.zeros((1, P), jnp.int32)
    u_ceil = _LANE * ((U + _LANE - 1) // _LANE)
    for c in range(0, min(P, u_ceil), _LANE):
        i0 = c + lax.broadcasted_iota(jnp.int32, (_LANE, 1), 0)
        cnt = jnp.sum((i0 == psrc).astype(jnp.int32), axis=1,
                      keepdims=True)                  # [128, 1]
        row = _unflip(eye, cnt)                       # [1, 128]
        row = jnp.pad(row, ((0, 0), (c, P - c - _LANE)))
        contested_i = jnp.where(_band(colP, c), row, contested_i)
    contested = contested_i > 0

    ec_pack = _cummax(jnp.where(
        keep_t, uidx * 2 + contested.astype(jnp.int32), -1))
    ec_prev = _shiftr(ec_pack, -1)
    prev_contested = (ec_prev >= 0) & (ec_prev % 2 == 1)
    glued = adj & ~host_case & ~prev_contested

    run_start = keep_t & ~glued
    rs_cum = _cumsum(run_start.astype(jnp.int32))
    run_id = rs_cum - 1
    n_runs = rs_cum[:, P - 1:P]

    # token->run compaction: every per-run head field in ONE sort
    h_parent_tok = jnp.where(irregular, parent_su,
                             jnp.where(adj, prev_kept, -1))
    ckey = jnp.where(run_start, run_id, BIG)
    comp = _bitonic_vals(
        (ckey, uidx, h_parent_tok, wstart,
         special_t.astype(jnp.int32), is_root_t.astype(jnp.int32)),
        num_keys=1)
    hc = comp[1][:, :Kp]
    h_parent_k = comp[2][:, :Kp]
    h_w = comp[3][:, :Kp]
    h_special = comp[4][:, :Kp] != 0
    h_root = comp[5][:, :Kp] != 0

    n_runs_b = jnp.broadcast_to(n_runs, (1, Kp))
    r_valid = targets <= jnp.minimum(n_runs_b, k_max)
    h_parent = jnp.where(r_valid & ~h_root, h_parent_k, -1)
    parent_run = jnp.where(
        h_parent >= 0,
        _gather(eye, [run_id], jnp.clip(h_parent, 0, U - 1))[0],
        -1)

    nxt_w = _rolln(h_w)
    run_w = jnp.where(
        r_valid,
        jnp.where(targets == n_runs_b,
                  jnp.broadcast_to(n_kept, (1, Kp)) - h_w,
                  nxt_w - h_w),
        0)

    parent_sort = jnp.where(r_valid & (parent_run >= 0),
                            parent_run, k_max)
    packed = parent_sort * 2 + (~h_special).astype(jnp.int32)
    _s = _bitonic_vals((packed, -hc, kidx, parent_sort), num_keys=2)
    sord, p_sorted = _s[2], _s[3]
    is_start = (kidx == 0) | (p_sorted != _shiftr(p_sorted, -7))
    same_parent_next = (_rolln(p_sorted) == p_sorted) & (kidx < Kp - 1)
    ns_sorted = jnp.where(same_parent_next, _rolln(sord), -1)
    # scatter-at-permutation == inverse-sort ride
    ns = _bitonic_vals((sord, ns_sorted), num_keys=1)[1]
    # first_child: at most one start per parent value, so the one-hot
    # chunk sum IS the scatter (+1/-1 shifts 0 into the -1 sentinel)
    fc_target = jnp.where(
        is_start & (p_sorted >= 0) & (p_sorted < k_max),
        p_sorted, -1)
    colK = kidx
    fc = jnp.zeros((1, Kp), jnp.int32)
    k_ceil = _LANE * ((k_max + _LANE - 1) // _LANE)
    for c in range(0, min(Kp, k_ceil), _LANE):
        i0 = c + lax.broadcasted_iota(jnp.int32, (_LANE, 1), 0)
        m = i0 == fc_target                           # [128, Kp]
        hit = jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)
        val = jnp.sum(jnp.where(m, sord, 0), axis=1, keepdims=True)
        row = _unflip(eye, jnp.where(hit > 0, val + 1, 0))
        row = jnp.pad(row, ((0, 0), (c, Kp - c - _LANE)))
        fc = jnp.where(_band(colK, c), row, fc)
    fc = fc - 1

    parent_up = jnp.where(r_valid & (parent_run >= 0), parent_run, -1)
    sp_last = sp_pack[:, P - 1:P]

    return (fc, ns, parent_up, run_w.astype(jnp.int32), hc, h_w,
            run_id, glued.astype(jnp.int32), prev_kept,
            n_runs, n_kept, sp_last)


# ---------------------------------------------------------------------
# K4: run-base expansion + kills + lane-sort handoff (phase E back)
# ---------------------------------------------------------------------

def row_k4(eye, base_run, hc, h_w, run_id, keep_i, sv_len, sv_vc,
           sv_lane, glued_i, prev_kept, cause_su, n_runs, sp_last,
           U: int, k_max: int, N: int, window_expand=None):
    """One row of phase E's ranking/kill tail + the lane-sort handoff
    (pure up to ``window_expand``, which the kernel overrides with a
    pl.ds-windowed form; the default is a plain chunk gather so the
    function is testable standalone)."""
    P = keep_i.shape[1]
    Kp = base_run.shape[1]
    kidx = lax.broadcasted_iota(jnp.int32, (1, Kp), 1)
    targets = kidx + 1
    keep_t = keep_i != 0
    glued = glued_i != 0
    sv_tail_lane = sv_lane + sv_len - 1

    wcum = _cumsum(jnp.where(keep_t, sv_len, 0))
    wstart = wcum - jnp.where(keep_t, sv_len, 0)
    r_valid = targets <= jnp.minimum(n_runs, k_max)

    # run->token expansion: base_ff/ffw == jaxw5's delta-scatter
    # cumsum / run-head cummax fill, telescoped to "my run's value"
    if window_expand is None:
        rid_c = jnp.clip(run_id, 0, Kp - 1)
        base_ff, hw_ff = _gather(eye, [base_run, h_w], rid_c)
    else:
        base_ff, hw_ff = window_expand(base_run, h_w, run_id)

    rank_tok = jnp.where(
        keep_t, base_ff + (wstart - hw_ff), N).astype(jnp.int32)

    hideish = (sv_vc == VCLASS_HIDE) | (sv_vc == VCLASS_H_HIDE)
    kg = glued & hideish
    vict_inrun = jnp.where(
        kg,
        _gather(eye, [sv_tail_lane], jnp.clip(prev_kept, 0, U - 1))[0],
        N)

    bkey = jnp.where(r_valid, base_run, BIG)
    b_sorted, b_src = _bitonic_vals((bkey, kidx), num_keys=1)
    succ_valid = (_rolln(b_sorted) != BIG) & (kidx < Kp - 1)
    succ_entry = jnp.where(succ_valid, _rolln(b_src), -1)
    succ_of = _bitonic_vals((b_src, succ_entry), num_keys=1)[1]
    succ_run = jnp.where(r_valid, succ_of, -1)
    s_c = jnp.clip(
        jnp.where(succ_run >= 0,
                  _gather(eye, [hc],
                          jnp.clip(succ_run, 0, Kp - 1))[0],
                  0),
        0, U - 1)
    g_hide, g_cause = _gather(
        eye, [hideish.astype(jnp.int32), cause_su], s_c)
    s_is_hide = (succ_run >= 0) & (g_hide != 0)
    nxt_head = _rolln(hc)
    tail_tok = jnp.where(
        targets == n_runs,
        jnp.maximum(sp_last >> 1, 0),
        _gather(eye, [prev_kept], jnp.clip(nxt_head, 0, U - 1))[0],
    ).astype(jnp.int32)
    kill_tail = r_valid & s_is_hide & (g_cause == tail_tok)
    vict_tail = jnp.where(
        kill_tail,
        _gather(eye, [sv_tail_lane], jnp.clip(tail_tok, 0, U - 1))[0],
        N)

    lane_key = jnp.where(keep_t & (rank_tok < N), sv_lane, N)
    lk, tb_l = _bitonic_vals((lane_key, rank_tok), num_keys=1)

    # scalar extractions stay int32: Mosaic cannot squeeze bool
    # scalars out of vector registers
    root_val = jnp.where(keep_i[0, 0] != 0, sv_lane[0, 0], N)
    overflow_k = (n_runs[0, 0] > k_max).astype(jnp.int32)

    return (lk, tb_l, vict_inrun.astype(jnp.int32),
            vict_tail.astype(jnp.int32), root_val, overflow_k)


# ---------------------------------------------------------------------
# pallas_call plumbing: 8-row blocks, fori over rows, pl.ds row I/O
# ---------------------------------------------------------------------

def _vmem(width):
    shape = (_ROWS, width)
    imap = lambda b: (b, 0)
    return pl.BlockSpec(shape, imap, memory_space=pltpu.VMEM)


def _row(ref, r):
    return ref[pl.ds(r, 1), :]


def _pad_rows(arrs, B):
    Bp = -(-B // _ROWS) * _ROWS
    if Bp == B:
        return arrs, Bp
    return [jnp.pad(x, ((0, Bp - B), (0, 0))) for x in arrs], Bp


@lru_cache(maxsize=None)
def _build_k1(U: int):
    def kernel(*refs):
        ins, outs = refs[:8], refs[8:]
        eye = _eye_f32()
        col8 = lax.broadcasted_iota(jnp.int32, (1, 8), 1)

        def body(r, _):
            res = row_k1(eye, *[_row(x, r) for x in ins], U=U)
            for o, v in zip(outs[:7], res[:7]):
                o[pl.ds(r, 1), :] = v.astype(jnp.int32)
            outs[7][pl.ds(r, 1), :] = _scal_row(col8, res[7])
            return 0

        lax.fori_loop(0, ins[0].shape[0], body, 0)

    def call(*arrs):
        B, P = arrs[0].shape
        arrs, Bp = _pad_rows(list(arrs), B)
        out = pl.pallas_call(
            kernel,
            grid=(Bp // _ROWS,),
            in_specs=[_vmem(P)] * 8,
            out_specs=[_vmem(P)] * 7 + [_vmem(8)],
            out_shape=[jax.ShapeDtypeStruct((Bp, P), jnp.int32)] * 7
            + [jax.ShapeDtypeStruct((Bp, 8), jnp.int32)],
            interpret=_interpret(),
        )(*arrs)
        return tuple(x[:B] for x in out)

    return call


@lru_cache(maxsize=None)
def _build_k2(U: int, k_max: int, Kp: int):
    def kernel(*refs):
        ins, outs = refs[:6], refs[6:]
        eye = _eye_f32()
        col8 = lax.broadcasted_iota(jnp.int32, (1, 8), 1)

        def body(r, _):
            res = row_k2(eye, *[_row(x, r) for x in ins],
                         U=U, k_max=k_max, Kp=Kp)
            for o, v in zip(outs[:9], res[:9]):
                o[pl.ds(r, 1), :] = v.astype(jnp.int32)
            outs[9][pl.ds(r, 1), :] = _scal_row(
                col8, res[9], res[10], res[11])
            return 0

        lax.fori_loop(0, ins[0].shape[0], body, 0)

    def call(*arrs):
        B, P = arrs[0].shape
        arrs, Bp = _pad_rows(list(arrs), B)
        widths = [Kp] * 6 + [P] * 3 + [8]
        out = pl.pallas_call(
            kernel,
            grid=(Bp // _ROWS,),
            in_specs=[_vmem(P)] * 6,
            out_specs=[_vmem(w) for w in widths],
            out_shape=[jax.ShapeDtypeStruct((Bp, w), jnp.int32)
                       for w in widths],
            interpret=_interpret(),
        )(*arrs)
        return tuple(x[:B] for x in out)

    return call


@lru_cache(maxsize=None)
def _build_k4(U: int, k_max: int, N: int):
    def kernel(*refs):
        ins, outs = refs[:12], refs[12:]
        (base_ref, hc_ref, hw_ref, runid_ref, keep_ref, svlen_ref,
         svvc_ref, svlane_ref, glued_ref, prevkept_ref, causesu_ref,
         scal2_ref) = ins
        eye = _eye_f32()
        col8 = lax.broadcasted_iota(jnp.int32, (1, 8), 1)
        P = keep_ref.shape[1]
        Kp = base_ref.shape[1]
        colP = lax.broadcasted_iota(jnp.int32, (1, P), 1)
        i0 = lax.broadcasted_iota(jnp.int32, (_LANE, 1), 0)

        def body(r, _):
            def window_expand(base_run, h_w, run_id):
                base_ff = jnp.zeros((1, P), jnp.int32)
                hw_ff = jnp.zeros((1, P), jnp.int32)
                for t in range(0, P, _LANE):
                    w0 = jnp.clip(runid_ref[r, t], 0, Kp - _LANE)
                    wb = _flip(eye, base_ref[pl.ds(r, 1),
                                             pl.ds(w0, _LANE)])
                    wh = _flip(eye, hw_ref[pl.ds(r, 1),
                                           pl.ds(w0, _LANE)])
                    rid_t = run_id[:, t:t + _LANE]
                    m = (w0 + i0) == rid_t  # [128 window, 128 tok]
                    bsel = jnp.sum(jnp.where(m, wb, 0), axis=0,
                                   keepdims=True)     # [1, 128]
                    hsel = jnp.sum(jnp.where(m, wh, 0), axis=0,
                                   keepdims=True)
                    bsel = jnp.pad(bsel,
                                   ((0, 0), (t, P - t - _LANE)))
                    hsel = jnp.pad(hsel,
                                   ((0, 0), (t, P - t - _LANE)))
                    base_ff = jnp.where(_band(colP, t), bsel,
                                        base_ff)
                    hw_ff = jnp.where(_band(colP, t), hsel, hw_ff)
                return base_ff, hw_ff

            res = row_k4(
                eye,
                _row(base_ref, r), _row(hc_ref, r), _row(hw_ref, r),
                _row(runid_ref, r), _row(keep_ref, r),
                _row(svlen_ref, r), _row(svvc_ref, r),
                _row(svlane_ref, r), _row(glued_ref, r),
                _row(prevkept_ref, r), _row(causesu_ref, r),
                scal2_ref[pl.ds(r, 1), 0:1],
                scal2_ref[pl.ds(r, 1), 2:3],
                U=U, k_max=k_max, N=N,
                window_expand=window_expand)
            for o, v in zip(outs[:4], res[:4]):
                o[pl.ds(r, 1), :] = v
            outs[4][pl.ds(r, 1), :] = _scal_row(col8, res[4], res[5])
            return 0

        lax.fori_loop(0, keep_ref.shape[0], body, 0)

    def call(*arrs):
        B, Kp = arrs[0].shape
        P = arrs[3].shape[1]
        arrs, Bp = _pad_rows(list(arrs), B)
        widths = [P, P, P, Kp, 8]
        out = pl.pallas_call(
            kernel,
            grid=(Bp // _ROWS,),
            in_specs=[_vmem(Kp)] * 3 + [_vmem(P)] * 8 + [_vmem(8)],
            out_specs=[_vmem(w) for w in widths],
            out_shape=[jax.ShapeDtypeStruct((Bp, w), jnp.int32)
                       for w in widths],
            interpret=_interpret(),
        )(*arrs)
        return tuple(x[:B] for x in out)

    return call


@lru_cache(maxsize=None)
def _vmappable(build, *statics):
    """Single-row calling convention over a batch kernel: the row form
    pads to a batch of one; under ``vmap`` the custom-vmap rule swaps
    in the gridded batch kernel (the pallas_sort/pallas_ops pattern,
    which is how the per-row jaxw5f pipeline reaches these)."""
    call = build(*statics)

    @jax.custom_batching.custom_vmap
    def single(*arrs):
        out = call(*[x[None] for x in arrs])
        return tuple(x[0] for x in out)

    @single.def_vmap
    def _vm(axis_size, in_batched, *arrs):
        arrs = tuple(
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(arrs, in_batched))
        out = call(*arrs)
        return out, tuple(True for _ in out)

    return single


def k1_sort_redirect(t_hi, t_lo, t_vc, t_len, t_tsp, t_lane, cu0m,
                     hu0m, U: int):
    """Per-row K1 (batch via vmap). Returns (sv_len, sv_vc, sv_tsp,
    sv_lane, keep_i, cause_su, parent_su, scal); scal[0] =
    conflict."""
    return _vmappable(_build_k1, U)(t_hi, t_lo, t_vc, t_len, t_tsp,
                                    t_lane, cu0m, hu0m)


def k2_runs(sv_len, sv_vc, sv_tsp, keep_i, cause_su, parent_su,
            U: int, k_max: int, Kp: int):
    """Per-row K2 (batch via vmap). Returns (fc, ns, parent_up,
    run_w, hc, h_w, run_id, glued_i, prev_kept, scal); scal =
    [n_runs, n_kept, sp_last, 0...]."""
    return _vmappable(_build_k2, U, k_max, Kp)(
        sv_len, sv_vc, sv_tsp, keep_i, cause_su, parent_su)


def k4_rank_kills(base_run, hc, h_w, run_id, keep_i, sv_len, sv_vc,
                  sv_lane, glued_i, prev_kept, cause_su, scal2,
                  U: int, k_max: int, N: int):
    """Per-row K4 (batch via vmap). Returns (lk, tb_l, vict_inrun,
    vict_tail, scal); scal = [root_val, overflow_k, 0...]."""
    return _vmappable(_build_k4, U, k_max, N)(
        base_run, hc, h_w, run_id, keep_i, sv_len, sv_vc, sv_lane,
        glued_i, prev_kept, cause_su, scal2)
