"""The v5 segment-union merge kernel: merge cost scales with divergence.

Every kernel so far (v1-v4) pays full node width for the union sort
and the flag/scan passes even though replicas of a shared document are
IDENTICAL over almost all of it. v5 moves the union to *segment*
granularity (per-tree chain runs, marshal-extracted by
``segments.tree_segments``) and only explodes a segment to node
tokens where replicas actually interact:

E1. its id interval overlaps another segment's (divergent edits
    interleave), unless the two are exact dense twins (the shared
    root/base prefix every replica carries — those dedupe wholesale,
    exactly: a dense segment's member ids are fully determined by
    (min, max, len));
E2. some other segment head's *cause* stabs its interior — including
    its tail when the tail is special with members before it, because
    the host jump of an external child would walk through the tail
    into the interior and split the run there (the v4 contested rule).

Everything that survives rides the union as ONE sort token carrying
its length; exploded segments contribute one token per lane. The
union pipeline is then exactly jaxw4's — adjacency, host-case, glue,
contested, chain runs, sibling sort, Euler ranking — run at token
width (~divergence size) with token lengths as weights, and the final
per-lane ranks/visibility materialize over the full lane width with
only elementwise passes, cumulative scans, and small scatters: no
full-width sort, gather, or binary search anywhere.

For the north-star shape (1024 pairs x 10k nodes, ~2k-node divergence)
that removes ~95% of the full-width work v4 still did. For a single
tree (the API reweave path) nothing explodes and the device work is
just the segment ordering. Semantics remain EXACT vs the pure oracle
and v1 (tests/test_jax_v5.py); like v2-v4 the kernel takes static
budgets (``s`` is the table size, ``u_max`` tokens, ``k_max`` runs)
and raises an overflow flag instead of corrupting.

``k_max`` is the run cap and overflow line, not the width that always
runs. Runs are maximal glued token chains, usually far fewer than the
tokens, so the batched entry points (``batched_v5``) rank the runs at
the smallest width of a power-of-two ladder from 128 up to ``k_max``
that holds the batch's largest run count, chosen on the device. A
single row (``merge_weave_kernel_v5``) and the delta window program
run at ``k_max``.

Twin-dedupe integrity: the twin test compares endpoints, length,
density, head vclass + cause, tail-specialness, AND a position
-weighted vclass checksum (``sg_vsum``), so a same-id twin whose
interior value CLASSES or structure diverge (append-only violation
from a corrupt replica) explodes and trips the node-level ``conflict``
check instead of vanishing wholesale. What the device still cannot
see is host VALUE bytes — two twins identical in ids/classes/causes
but differing in, say, the string payload of one node pass the device
unflagged; the API paths validate bodies host-side
(shared.union_nodes, WaveResult.merged) for exactly that reason.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .arrays import I32_MAX, VCLASS_H_HIDE, VCLASS_HIDE
from .jaxw import _euler_rank, _link_children
from .jaxw3 import _shift1
from .gatherops import (searchsorted_iota_right,
                        searchsorted_targets_left, take1d)

__all__ = [
    "merge_weave_kernel_v5",
    "batched_merge_weave_v5",
    "batched_v5",
    "run_widths",
]


def _sort(operands, num_keys: int = 1):
    """The kernels' sort: ``lax.sort`` along the last axis, ascending
    and lexicographic over the first ``num_keys`` operands (stable;
    the rest ride as payloads)."""
    from ..obs import span

    with span("weave.sort", width=int(operands[0].shape[-1]),
              n_ops=len(operands)):
        return lax.sort(tuple(operands), num_keys=num_keys)


def _lt(a1, a2, b1, b2):
    return (a1 < b1) | ((a1 == b1) & (a2 < b2))


def _le(a1, a2, b1, b2):
    return (a1 < b1) | ((a1 == b1) & (a2 <= b2))


def _eq(a1, a2, b1, b2):
    return (a1 == b1) & (a2 == b2)


def _pair_cummax(hi, lo):
    """Inclusive running lexicographic max over (hi, lo) pairs."""

    def op(a, b):
        ah, al = a
        bh, bl = b
        take_b = _lt(ah, al, bh, bl)
        return jnp.where(take_b, bh, ah), jnp.where(take_b, bl, al)

    return lax.associative_scan(op, (hi, lo))


def _pair_search_le(kh, kl, qh, ql, size):
    """For each query id, the rightmost index i in the sorted (kh, kl)
    arrays with key[i] <= query (-1 if none): a fori binary search,
    log2(size) rounds of table gathers."""
    from ..obs import span as _span

    with _span("weave.search", site="table", size=int(size)):
        steps = 1
        while (1 << steps) < size + 1:
            steps += 1

        def body(_, c):
            lo_b, hi_b = c
            mid = (lo_b + hi_b + 1) // 2  # invariant: key[lo_b] <= q
            ms = jnp.clip(mid, 0, size - 1)
            ok = _le(take1d(kh, ms), take1d(kl, ms), qh, ql)
            return (jnp.where(ok, mid, lo_b),
                    jnp.where(ok, hi_b, mid - 1))

        lo_b, _ = lax.fori_loop(
            0, steps, body,
            (jnp.full_like(qh, -1), jnp.full_like(qh, size - 1)),
        )
        return lo_b


def _merge_weave_kernel_v5_impl(hi, lo, cci, vclass, valid, seg,
                                sg_min_hi, sg_min_lo, sg_max_hi,
                                sg_max_lo, sg_len, sg_lane0, sg_dense,
                                sg_tail_special, sg_valid, sg_vsum,
                                u_max: int, k_max: int):
    """Union + reweave at segment granularity for one replica set.

    Node lanes as in v4 (``hi/lo/cci/vclass/valid`` — trees
    concatenated, each id-sorted) plus ``seg`` (each lane's segment
    ordinal) and the ``SEG_LANE_KEYS`` tables in ascending-lane marshal
    order. Returns ``(rank, visible, conflict, overflow)`` with rank
    and visibility indexed by CONCAT lane (not by sorted position —
    there is no full-width sorted order here).

    Each phase runs under ``jax.named_scope("v5.<phase>")``: ``A``
    segment ordering + explode/dedupe, ``B`` token construction, ``C``
    token sort + dedupe, ``D`` cause resolution, ``E`` token-width
    ranking + kills, ``F`` expansion to concat lanes. The scopes reach
    the optimized HLO's ``op_name`` metadata (and nothing else), so a
    device trace's ops can be named by phase.

    The kernel is three pieces: ``_v5_front`` (phases A-D and phase E
    up to the run count), ``_v5_run_tail`` (the rest of phase E, at a
    static run width) and ``_v5_expand`` (phase F). One row runs the
    tail at ``k_max``; ``batched_v5`` runs it at the batch's width.
    """
    fr = _v5_front(hi, lo, cci, vclass, valid, seg, sg_min_hi, sg_min_lo,
                   sg_max_hi, sg_max_lo, sg_len, sg_lane0, sg_dense,
                   sg_tail_special, sg_valid, sg_vsum,
                   u_max=u_max, k_max=k_max)
    delta_u, vict_tail = _v5_run_tail(fr, k_max)
    return _v5_expand(fr, delta_u, vict_tail)


def _v5_front(hi, lo, cci, vclass, valid, seg, sg_min_hi, sg_min_lo,
              sg_max_hi, sg_max_lo, sg_len, sg_lane0, sg_dense,
              sg_tail_special, sg_valid, sg_vsum, u_max: int, k_max: int):
    """Phases A-D and the token-width head of phase E for one row, up
    to the run table's inputs (``rs_cum``, ``n_runs``, ``head_tok``).
    Returns a dict of the arrays the run tail and phase F read.
    ``k_max`` sets ``overflow_k`` and the width of ``head_tok``."""
    # ================= A. segment ordering + explode/dedupe =========
    with jax.named_scope("v5.A"):
        N = hi.shape[0]
        S = sg_len.shape[0]
        sidx = jnp.arange(S, dtype=jnp.int32)
        BIG = I32_MAX

        kh = jnp.where(sg_valid, sg_min_hi, BIG)
        kl = jnp.where(sg_valid, sg_min_lo, BIG)
        s_mh, s_ml, s_src = _sort((kh, kl, sidx), num_keys=2)
        s_Mh = take1d(sg_max_hi, s_src)
        s_Ml = take1d(sg_max_lo, s_src)
        s_va = take1d(sg_valid, s_src)
        s_len = jnp.where(s_va, take1d(sg_len, s_src), 0)
        s_lane0 = take1d(sg_lane0, s_src)
        s_dense = take1d(sg_dense, s_src)
        s_tsp = take1d(sg_tail_special, s_src)
        s_vsum = take1d(sg_vsum, s_src)

        # head body fields (shared by the twin test and the E2 stabs)
        s_hvc = take1d(vclass, jnp.clip(s_lane0, 0, N - 1))
        c_lane = take1d(cci, jnp.clip(s_lane0, 0, N - 1))
        has_c = s_va & (c_lane >= 0)
        c_hi = jnp.where(has_c, take1d(hi, jnp.clip(c_lane, 0, N - 1)), -1)
        c_lo = jnp.where(has_c, take1d(lo, jnp.clip(c_lane, 0, N - 1)), -1)

        # twin groups: adjacent exact-equal dense segments dedupe wholesale.
        # Equality covers the endpoints, length, density, the head's value
        # class and cause id, the tail-special flag, and the position
        # -weighted vclass checksum (sg_vsum) — so a same-id segment whose
        # INTERIOR body classes differ (a corrupt replica violating
        # append-only) fails the test, explodes, and the node-level
        # duplicate check reports the conflict. Host VALUES remain a
        # host-side check (shared.union_nodes / WaveResult.merged): the
        # device never sees them.
        p_mh, p_ml = _shift1(s_mh, -1), _shift1(s_ml, -1)
        same_prev = (
            _eq(s_mh, s_ml, p_mh, p_ml)
            & _eq(s_Mh, s_Ml, _shift1(s_Mh, -1), _shift1(s_Ml, -1))
            & (s_len == _shift1(s_len, -1))
            & s_dense & _shift1(s_dense, False)
            & (s_hvc == _shift1(s_hvc, -1))
            & (s_tsp == _shift1(s_tsp, False))
            & (s_vsum == _shift1(s_vsum, -1))
            & _eq(c_hi, c_lo, _shift1(c_hi, -1), _shift1(c_lo, -1))
            & s_va & _shift1(s_va, False)
            & (sidx > 0)
        )
        grp_start = ~same_prev
        grp = jnp.cumsum(grp_start.astype(jnp.int32)) - 1

        # per-group interval tables (twins share min/max by construction).
        # Scatter indices: group ordinals at group starts (strictly
        # increasing), everything else dumped into its own spread slot past
        # S — unique by construction.
        is_start = grp_start & s_va
        gsl = jnp.where(is_start, grp, S + sidx)

        def _gtable(vals, fill):
            return jnp.full(2 * S, fill, jnp.int32).at[gsl].set(
                jnp.where(is_start, vals, fill))[:S]

        g_mh = _gtable(s_mh, BIG)
        g_ml = _gtable(s_ml, BIG)
        g_Mh = _gtable(s_Mh, -1)
        g_Ml = _gtable(s_Ml, -1)

        # E1: overlap with any earlier group (prefix pair-max of maxes,
        # exclusive) or the next group (its min is the smallest later min)
        pmh, pml = _pair_cummax(g_Mh, g_Ml)
        pmh_e, pml_e = _shift1(pmh, -1), _shift1(pml, -1)
        gi = jnp.clip(grp, 0, S - 1)
        ov_before = _le(s_mh, s_ml, take1d(pmh_e, gi), take1d(pml_e, gi))
        nxt_mh = jnp.concatenate([g_mh[1:], jnp.full((1,), BIG, jnp.int32)])
        nxt_ml = jnp.concatenate([g_ml[1:], jnp.full((1,), BIG, jnp.int32)])
        ov_after = _le(take1d(nxt_mh, gi), take1d(nxt_ml, gi), s_Mh, s_Ml)
        explode = s_va & (ov_before | ov_after)

        # E2: head-cause stabs. Candidate = rightmost group with min <= c.
        pg = _pair_search_le(g_mh, g_ml, c_hi, c_lo, S)
        pgc = jnp.clip(pg, 0, S - 1)
        # group tables for the stabbed group: len/tail-specialness of its
        # representative member (first of group; twins agree)
        rep = jnp.full(2 * S, 0, jnp.int32).at[gsl].set(
            jnp.where(is_start, sidx, 0))[:S]
        rep_pg = take1d(rep, pgc)
        r_len = take1d(s_len, rep_pg)
        r_tsp = take1d(s_tsp, rep_pg)
        gm_h, gm_l = take1d(g_mh, pgc), take1d(g_ml, pgc)
        gM_h, gM_l = take1d(g_Mh, pgc), take1d(g_Ml, pgc)
        stab = has_c & (pg >= 0) & _le(gm_h, gm_l, c_hi, c_lo) & (
            _lt(c_hi, c_lo, gM_h, gM_l)
            | (_eq(c_hi, c_lo, gM_h, gM_l) & r_tsp & (r_len > 1))
        )
        g_stabbed = jnp.zeros(S, bool).at[
            jnp.where(stab, pgc, S - 1)
        ].set(True, mode="drop")
        # make the last slot honest (it may have been used as a dump)
        g_stabbed = g_stabbed.at[S - 1].set(
            jnp.any(stab & (pgc == S - 1)))
        explode = explode | (s_va & take1d(g_stabbed, gi))

        twin_drop = same_prev & ~explode
        survive = s_va & ~explode & ~twin_drop

    # ================= B. token construction ========================
    with jax.named_scope("v5.B"):
        tok_cnt = jnp.where(survive, 1, jnp.where(s_va & explode, s_len, 0))
        tc_cum = jnp.cumsum(tok_cnt)
        tb = tc_cum - tok_cnt  # exclusive: first token slot per sorted seg
        n_tok = tc_cum[-1]
        U = u_max
        uidx = jnp.arange(U, dtype=jnp.int32)
        u_ok = uidx < jnp.minimum(n_tok, U)
        overflow_u = n_tok > U

        owner = searchsorted_iota_right(tc_cum, U)
        oc = jnp.clip(owner, 0, S - 1)
        off = uidx - take1d(tb, oc)
        o_expl = take1d(s_va, oc) & (~take1d(survive, oc))
        t_lane = jnp.clip(
            take1d(s_lane0, oc) + jnp.where(o_expl, off, 0), 0, N - 1
        )
        t_hi = jnp.where(u_ok, take1d(hi, t_lane), BIG)
        t_lo = jnp.where(u_ok, take1d(lo, t_lane), BIG)
        t_len = jnp.where(u_ok, jnp.where(o_expl, 1, take1d(s_len, oc)), 0)
        t_vc = jnp.where(u_ok, take1d(vclass, t_lane), 0)
        t_tail_lane = t_lane + t_len - 1
        t_tsp = jnp.where(
            o_expl, t_vc > 0, take1d(s_tsp, oc)
        ) & u_ok

        # token_of_lane machinery (PRESORT token ids). A cause lane inside
        # a twin-DROPPED segment copy (tree B's own copy of the shared
        # base) must resolve to the KEPT twin's token: group-start fill
        # gsp redirects any twin member to its group's first (kept) member.
        inv_s = jnp.zeros(S, jnp.int32).at[s_src].set(sidx)
        seg_expl_sorted = s_va & explode
        gsp = lax.cummax(jnp.where(grp_start, sidx, -1))

        def token_of_lane(p):
            pc = jnp.clip(p, 0, N - 1)
            m = jnp.clip(take1d(seg, pc), 0, S - 1)
            ss2 = take1d(inv_s, m)
            ex = take1d(seg_expl_sorted, ss2)
            owner_ss = jnp.where(ex, ss2, take1d(gsp, ss2))
            return (take1d(tb, owner_ss)
                    + jnp.where(ex, pc - take1d(sg_lane0, m), 0)
                    ).astype(jnp.int32)

    # ================= C. sort tokens, dedupe =======================
    with jax.named_scope("v5.C"):
        # extra variadic operands slow the comparator sort, so the
        # payloads are gathered by the sort's permutation
        st_hi, st_lo, t_src = _sort((t_hi, t_lo, uidx), num_keys=2)
        g = lambda arr: take1d(arr, t_src)  # presort -> sorted order
        sv_len, sv_vc, sv_tsp = g(t_len), g(t_vc), g(t_tsp)
        sv_lane, sv_tail_lane = g(t_lane), g(t_tail_lane)
        inv_t = jnp.zeros(U, jnp.int32).at[t_src].set(uidx)

        tva = ~((st_hi == BIG) & (st_lo == BIG))
        sdup = (
            _eq(st_hi, st_lo, _shift1(st_hi, -1), _shift1(st_lo, -1))
            & (uidx > 0) & tva
        )
        keep_t = tva & ~sdup

    # ================= D. token cause resolution ====================
    with jax.named_scope("v5.D"):
        cl = jnp.where(tva, take1d(cci, jnp.clip(sv_lane, 0, N - 1)), -1)
        cause_u = token_of_lane(cl)
        cause_su_raw = take1d(inv_t, jnp.clip(cause_u, 0, U - 1))
        # redirect to the kept head of a duplicate token group: dups are
        # adjacent after the sort, so a kept-head fill redirects them
        thead = lax.cummax(jnp.where(keep_t, uidx, -1))
        cause_su = jnp.where(
            cl >= 0, take1d(thead, jnp.clip(cause_su_raw, 0, U - 1)), 0
        ).astype(jnp.int32)

        special_t = keep_t & (sv_vc > 0)
        is_root_t = keep_t & (uidx == 0)
        rel_t = keep_t & ~is_root_t

        # host walk (lane-level, at token width): first non-special lane
        # on the cause chain
        def wcond(c):
            p, i = c
            pc = jnp.clip(p, 0, N - 1)
            on = rel_t & ~special_t & (p >= 0) & (take1d(vclass, pc) > 0)
            return (i < N) & jnp.any(on)

        def wbody(c):
            p, i = c
            pc = jnp.clip(p, 0, N - 1)
            on = rel_t & ~special_t & (p >= 0) & (take1d(vclass, pc) > 0)
            return jnp.where(on, take1d(cci, pc), p), i + 1

        host_lane, _ = lax.while_loop(wcond, wbody, (cl, jnp.int32(0)))
        host_su = jnp.where(
            host_lane >= 0,
            take1d(thead,
                   jnp.clip(take1d(inv_t,
                                   jnp.clip(token_of_lane(host_lane),
                                            0, U - 1)), 0, U - 1)),
            0,
        ).astype(jnp.int32)
        parent_su = jnp.where(special_t, cause_su, host_su)

        conflict = jnp.any(
            sdup & (
                (sv_vc != _shift1(sv_vc, 0))
                | (cause_su != _shift1(cause_su, 0))
                | (sv_len != _shift1(sv_len, 0))
            )
        )

    # ================= E. v4 pipeline at token width ================
    with jax.named_scope("v5.E"):
        wcum = jnp.cumsum(jnp.where(keep_t, sv_len, 0))
        wstart = wcum - jnp.where(keep_t, sv_len, 0)
        n_kept_nodes = wcum[-1]

        sp_pack = lax.cummax(
            jnp.where(keep_t, uidx * 2 + sv_tsp.astype(jnp.int32), -1)
        )
        sp_prev = _shift1(sp_pack, -1)
        prev_kept = jnp.where(sp_prev >= 0, sp_prev >> 1, -1)
        prev_kept_tsp = (sp_prev >= 0) & (sp_prev % 2 == 1)

        adj = rel_t & (cause_su == prev_kept) & (prev_kept >= 0)
        host_case = adj & ~special_t & prev_kept_tsp
        irregular = rel_t & (~adj | host_case)

        extra = jnp.zeros(U, jnp.int32).at[
            jnp.where(irregular, parent_su, U - 1)
        ].add(1, mode="drop")
        extra = extra.at[U - 1].set(
            jnp.sum(jnp.where(irregular & (parent_su == U - 1), 1, 0)))
        ec_pack = lax.cummax(
            jnp.where(keep_t, uidx * 2 + (extra > 0).astype(jnp.int32), -1)
        )
        ec_prev = _shift1(ec_pack, -1)
        prev_contested = (ec_prev >= 0) & (ec_prev % 2 == 1)
        glued = adj & ~host_case & ~prev_contested

        run_start = keep_t & ~glued
        rs_cum = jnp.cumsum(run_start.astype(jnp.int32))
        n_runs = rs_cum[-1]
        overflow_k = n_runs > k_max
        # each run's head token (a prefix of this serves any narrower
        # run width), and each token's run-head node offset
        head_tok = searchsorted_targets_left(rs_cum, k_max)
        ffw = lax.cummax(jnp.where(run_start, wstart, -1))

        # token-level kills inside runs (victims as lanes)
        hideish = (sv_vc == VCLASS_HIDE) | (sv_vc == VCLASS_H_HIDE)
        kg = glued & hideish
        vict_inrun = jnp.where(
            kg, take1d(sv_tail_lane, jnp.clip(prev_kept, 0, U - 1)), N
        )

    return dict(
        vclass=vclass, valid=valid, seg=seg, sg_valid=sg_valid,
        sg_lane0=sg_lane0, sg_len=sg_len, survive=survive, inv_s=inv_s,
        keep_t=keep_t, sv_lane=sv_lane, sv_tail_lane=sv_tail_lane,
        cause_su=cause_su, parent_su=parent_su, is_root_t=is_root_t,
        special_t=special_t, wstart=wstart, n_kept_nodes=n_kept_nodes,
        sp_pack=sp_pack, prev_kept=prev_kept, adj=adj,
        irregular=irregular, rs_cum=rs_cum, head_tok=head_tok, ffw=ffw,
        n_runs=n_runs, hideish=hideish, kg=kg, vict_inrun=vict_inrun,
        conflict=conflict, overflow_u=overflow_u, overflow_k=overflow_k,
    )


def _v5_run_tail(fr, k: int):
    """The run-width half of phase E for one row at static run width
    ``k``: the run tables, the sibling sort, the Euler ranking, and the
    successor kills. Returns ``(delta_u, vict_tail)``: the run bases as
    deltas at their head tokens (``_v5_expand`` sums them into token
    ranks), and per run slot the lane of a tail its successor hides
    (N where none).

    Exact at any ``k >= n_runs``: runs are the first ``n_runs`` slots
    (``r_valid``), the padding slots carry zero weight and parent no
    valid run, and they share the root's sibling group (parent ``k``,
    out of range, so nothing links to it) where the root sorts last.
    Every valid slot's tour, base, successor and kill are therefore
    the same at every such ``k``. With ``n_runs > k`` the row has
    overflowed, as ``overflow_k`` says."""
    with jax.named_scope("v5.E"):
        keep_t, wstart = fr["keep_t"], fr["wstart"]
        prev_kept, n_runs = fr["prev_kept"], fr["n_runs"]
        U = keep_t.shape[0]
        N = fr["vclass"].shape[0]
        BIG = I32_MAX
        run_id = fr["rs_cum"] - 1

        targets = jnp.arange(1, k + 1, dtype=jnp.int32)
        head_tok = fr["head_tok"][:k]
        r_valid = targets <= jnp.minimum(n_runs, k)
        hc = jnp.clip(head_tok, 0, U - 1)

        h_parent = jnp.where(
            take1d(fr["irregular"], hc), take1d(fr["parent_su"], hc),
            jnp.where(take1d(fr["adj"], hc), take1d(prev_kept, hc), -1),
        )
        h_parent = jnp.where(r_valid & ~take1d(fr["is_root_t"], hc),
                             h_parent, -1)
        parent_run = jnp.where(
            h_parent >= 0, take1d(run_id, jnp.clip(h_parent, 0, U - 1)), -1
        ).astype(jnp.int32)

        h_special = take1d(fr["special_t"], hc)
        h_w = take1d(wstart, hc)
        nxt_w = jnp.concatenate([h_w[1:], h_w[:1]])
        run_w = jnp.where(
            r_valid,
            jnp.where(targets == n_runs, fr["n_kept_nodes"] - h_w,
                      nxt_w - h_w),
            0,
        ).astype(jnp.int32)

        parent_sort = jnp.where(r_valid & (parent_run >= 0), parent_run, k)
        packed = parent_sort * 2 + (~h_special).astype(jnp.int32)
        kidx_r = jnp.arange(k, dtype=jnp.int32)
        sord = _sort((packed, -hc, kidx_r), num_keys=2)[2]
        fc, ns = _link_children(sord, parent_sort)
        parent_up = jnp.where(r_valid & (parent_run >= 0), parent_run, -1)
        base_run, _ = _euler_rank(fc, ns, parent_up, run_w)

        # expand run bases to token bases (node units): delta-scatter at
        # run-head tokens + one cumsum over U, then add within-run offset
        delta = jnp.where(
            r_valid,
            base_run - jnp.concatenate([jnp.zeros((1,), base_run.dtype),
                                        base_run[:-1]]),
            0,
        )
        # valid targets are a prefix with strictly increasing head tokens;
        # invalid ones dump into spread slots past U — the index stream is
        # globally sorted AND unique by construction (no shared dump slot,
        # no collision fix-up needed)
        scat_du = jnp.where(r_valid, hc, U + kidx_r)
        delta_u = jnp.zeros(U + k, jnp.int32).at[scat_du].set(
            delta.astype(jnp.int32))[:U]

        # preorder-successor run: the run with the next-larger base. base
        # values are node-unit positions (up to N), so find successors by
        # sorting runs on base instead of scattering over node positions.
        bkey = jnp.where(r_valid, base_run, BIG)
        b_sorted, b_src = _sort((bkey, kidx_r), num_keys=1)
        succ_in_sorted = jnp.concatenate([
            b_src[1:], jnp.full((1,), -1, jnp.int32)
        ])
        succ_valid = jnp.concatenate([
            b_sorted[1:] != BIG, jnp.zeros((1,), bool)
        ])
        succ_of = jnp.full(k, -1, jnp.int32).at[b_src].set(
            jnp.where(succ_valid, succ_in_sorted, -1)
        )
        succ_run = jnp.where(r_valid, succ_of, -1)
        s_c = jnp.clip(
            jnp.where(succ_run >= 0,
                      take1d(hc, jnp.clip(succ_run, 0, k - 1)), 0),
            0, U - 1,
        )
        s_is_hide = (succ_run >= 0) & take1d(fr["hideish"], s_c)
        nxt_head = jnp.concatenate([hc[1:], hc[:1]])
        tail_tok = jnp.where(
            targets == n_runs,
            jnp.maximum(fr["sp_pack"][-1] >> 1, 0),
            take1d(prev_kept, jnp.clip(nxt_head, 0, U - 1)),
        ).astype(jnp.int32)
        t_cc = jnp.clip(tail_tok, 0, U - 1)
        # succ head's cause must BE the run's tail node — compared at
        # token level (cause_su is duplicate-redirected; a hide arriving
        # from another replica names its own dropped copy of the tail)
        kill_tail = (r_valid & s_is_hide
                     & (take1d(fr["cause_su"], s_c) == tail_tok))
        vict_tail = jnp.where(kill_tail,
                              take1d(fr["sv_tail_lane"], t_cc), N)
    return delta_u, vict_tail


def _v5_expand(fr, delta_u, vict_tail):
    """The token ranks (the end of phase E), then phase F for one row:
    token ranks and kills expanded to concat lanes. Returns ``(rank,
    visible, conflict, overflow)``."""
    vclass, valid, seg = fr["vclass"], fr["valid"], fr["seg"]
    sg_valid, sg_lane0, sg_len = fr["sg_valid"], fr["sg_lane0"], fr["sg_len"]
    keep_t, sv_lane = fr["keep_t"], fr["sv_lane"]
    N = vclass.shape[0]
    S = sg_len.shape[0]
    U = keep_t.shape[0]
    conflict = fr["conflict"]
    with jax.named_scope("v5.E"):
        rank_tok = jnp.where(
            keep_t, jnp.cumsum(delta_u) + (fr["wstart"] - fr["ffw"]), N
        ).astype(jnp.int32)
    # ================= F. expansion to concat lanes =================
    with jax.named_scope("v5.F"):
        sidx = jnp.arange(S, dtype=jnp.int32)
        uidx = jnp.arange(U, dtype=jnp.int32)
        overflow = fr["overflow_u"] | fr["overflow_k"]
        # token base + token lane, in LANE order (sort tokens by lane) so
        # deltas scatter + cumsum reconstructs per-lane values without any
        # full-width gather
        lane_key = jnp.where(keep_t & (rank_tok < N), sv_lane, N)
        lk, tok_at = _sort((lane_key, uidx), num_keys=1)
        tb_l = take1d(rank_tok, tok_at)

        # per-lane coverage flags, the token-level kill scatters (victims
        # can duplicate — genuine scatters, U-width), and the root lane
        seg_cov = sg_valid & take1d(fr["survive"], fr["inv_s"])
        killed_sc = jnp.zeros(N + 1, bool)
        killed_sc = killed_sc.at[jnp.where(fr["kg"], fr["vict_inrun"], N)
                                 ].set(True, mode="drop")
        killed_sc = killed_sc.at[vict_tail].set(True, mode="drop")
        root_lane = jnp.zeros(N, bool).at[
            jnp.clip(sv_lane[0], 0, N - 1)
        ].set(keep_t[0])

        tl_l = jnp.where(lk < N, lk, 0)
        ok_l = lk < N
        d_base = jnp.where(
            ok_l,
            tb_l - jnp.concatenate([jnp.zeros((1,), jnp.int32), tb_l[:-1]]),
            0,
        )
        d_lane = jnp.where(
            ok_l,
            tl_l - jnp.concatenate([jnp.zeros((1,), jnp.int32), tl_l[:-1]]),
            0,
        )
        # kept tokens sit in the sorted prefix with strictly increasing
        # lanes; the rest dump into spread slots past N — sorted + unique
        # by construction
        scat = jnp.where(ok_l, tl_l, N + uidx)
        bits = (N - 1).bit_length()
        if 2 * bits <= 30:
            # base and lane are both < N, so their delta streams pack into
            # one int32 place-value pair: ONE scatter + ONE cumsum instead
            # of two of each (deltas may be negative, but the cumsum is
            # exact and every prefix total is a valid packed (base, lane))
            d_pack = d_base * (1 << bits) + d_lane
            pack_n = jnp.zeros(N + U, jnp.int32).at[scat].add(d_pack)[:N]
            pack_fill = jnp.cumsum(pack_n)
            base_fill = pack_fill >> bits
            lane_fill = pack_fill & ((1 << bits) - 1)
        else:  # concat width N > 32k (per-tree capacity > 16k): packed
               # pairs would overflow int32
            base_n = jnp.zeros(N + U, jnp.int32).at[scat].add(d_base)[:N]
            lane_n = jnp.zeros(N + U, jnp.int32).at[scat].add(d_lane)[:N]
            base_fill = jnp.cumsum(base_n)
            lane_fill = jnp.cumsum(lane_n)
        has_tok = jnp.zeros(N + U, bool).at[scat].set(True)[:N]
        lane_idx = jnp.arange(N, dtype=jnp.int32)

        # per-lane coverage flags from the segment tables (marshal order =
        # ascending lane order): covered = lane belongs to a token that is
        # kept, either via its own token (exploded) or its segment's token
        # spread dump slots past N keep both index streams unique (segment
        # starts/ends are distinct for live segments: disjoint ascending)
        cov_cnt = jnp.zeros(N + 1 + S, jnp.int32)
        cov_cnt = cov_cnt.at[
            jnp.where(seg_cov, sg_lane0, N + 1 + sidx)
        ].add(1)
        cov_cnt = cov_cnt.at[
            jnp.where(seg_cov, sg_lane0 + sg_len, N + 1 + sidx)
        ].add(-1)
        in_surviving = jnp.cumsum(cov_cnt[:N]) > 0

        # surviving-segment lanes take the seg token's base + offset (their
        # own has_tok is only set at the head lane — the fill carries it);
        # exploded lanes have their own token scatter; everything else
        # (padding, dropped twins, duplicate tokens) ranks at N
        rank_lane = jnp.where(
            valid & (in_surviving | has_tok),
            base_fill + (lane_idx - lane_fill),
            N,
        ).astype(jnp.int32)

        # visibility
        hideish_l = (vclass == VCLASS_HIDE) | (vclass == VCLASS_H_HIDE)
        nxt_same_seg = jnp.concatenate([
            (seg[1:] == seg[:N - 1]) & (seg[:N - 1] >= 0),
            jnp.zeros((1,), bool),
        ])
        nxt_hide = jnp.concatenate([hideish_l[1:], jnp.zeros((1,), bool)])
        kill_in_seg = in_surviving & nxt_same_seg & nxt_hide
        killed = killed_sc[:N] | kill_in_seg

        visible = (
            valid & (rank_lane < N) & (vclass == 0) & ~root_lane & ~killed
        )
        return rank_lane, visible, conflict, overflow


def merge_weave_kernel_v5(hi, lo, cci, vclass, valid, seg,
                          sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
                          sg_len, sg_lane0, sg_dense, sg_tail_special,
                          sg_valid, sg_vsum, u_max: int, k_max: int):
    """The v5 segment-union kernel (see ``_merge_weave_kernel_v5_impl``
    for the phase-by-phase contract), traced under an obs span so a
    trace attributes host TRACE time — where the sort/gather/search
    spans nest — to the kernel build it belongs to. Runs only at trace
    time (the body is jit-staged), so the span cost never touches the
    dispatch path."""
    from ..obs import span as _span

    with _span("weave.trace.v5", n=int(hi.shape[-1]),
               u_max=int(u_max), k_max=int(k_max), stage="FULL"):
        return _merge_weave_kernel_v5_impl(
            hi, lo, cci, vclass, valid, seg,
            sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
            sg_len, sg_lane0, sg_dense, sg_tail_special,
            sg_valid, sg_vsum, u_max=u_max, k_max=k_max)


merge_weave_kernel_v5_jit = jax.jit(
    merge_weave_kernel_v5,
    static_argnames=("u_max", "k_max"),
)


def run_widths(k_max: int) -> tuple:
    """The run-width ladder of a batch with run cap ``k_max``: 128 (one
    lane row), 256, ... while below ``k_max``, then ``k_max`` itself."""
    ks = []
    k = 128
    while k < k_max:
        ks.append(k)
        k *= 2
    return tuple(ks) + (int(k_max),)


def batched_v5(hi, lo, cci, vclass, valid, seg, sg_min_hi, sg_min_lo,
               sg_max_hi, sg_max_lo, sg_len, sg_lane0, sg_dense,
               sg_tail_special, sg_valid, sg_vsum, u_max: int, k_max: int):
    """The batched v5 body (traced inside a caller's jit): every row's
    front, then the run tail of all rows at the smallest width of
    ``run_widths(k_max)`` that holds the batch's largest run count,
    chosen on the device by one ``lax.switch`` outside the row
    ``vmap``, then phase F. Returns ``(rank, visible, conflict,
    overflow, k)``, ``k`` the width the tail ran at (int32 scalar).

    Bit-identical to every row running at ``k_max`` (see
    ``_v5_run_tail``); a batch whose run count passes ``k_max`` runs at
    ``k_max`` and flags ``overflow`` exactly as before."""
    from ..obs import span as _span

    with _span("weave.trace.v5", n=int(hi.shape[-1]),
               u_max=int(u_max), k_max=int(k_max), stage="BATCHED"):
        # the scopes around the row pieces name what XLA keeps of the
        # call boundaries (the pieces name their own ops)
        with jax.named_scope("v5.E"):
            fr = jax.vmap(partial(_front_row, u_max=u_max, k_max=k_max))(
                hi, lo, cci, vclass, valid, seg, sg_min_hi, sg_min_lo,
                sg_max_hi, sg_max_lo, sg_len, sg_lane0, sg_dense,
                sg_tail_special, sg_valid, sg_vsum)
        N = hi.shape[-1]
        ks = run_widths(k_max)

        def tail(k):
            def run(fr):
                delta_u, vict = jax.vmap(partial(_tail_row, k=k))(fr)
                # pad the per-run kills to k_max with the no-kill lane
                return delta_u, jnp.pad(vict, ((0, 0), (0, k_max - k)),
                                        constant_values=N)
            return run

        with jax.named_scope("v5.E"):
            widest = jnp.max(fr["n_runs"])
            i = jnp.sum(jnp.asarray(ks[:-1], jnp.int32) < widest)
            width = jnp.asarray(ks, jnp.int32)[i]
            delta_u, vict_tail = lax.switch(i, [tail(k) for k in ks], fr)
        with jax.named_scope("v5.F"):
            rank, visible, conflict, overflow = jax.vmap(_expand_row)(
                fr, delta_u, vict_tail)
        return rank, visible, conflict, overflow, width


# The row pieces of ``batched_v5``, each under its own jit: programs of
# one row shape (the merge tree's levels, one per batch size) then
# trace each piece once, where tracing the nine tails again for every
# batch size doubled a program's trace time.
@partial(jax.jit, static_argnames=("u_max", "k_max"))
def _front_row(*lanes, u_max: int, k_max: int):
    return _v5_front(*lanes, u_max=u_max, k_max=k_max)


@partial(jax.jit, static_argnames=("k",))
def _tail_row(fr, k: int):
    return _v5_run_tail(fr, k)


@jax.jit
def _expand_row(fr, delta_u, vict_tail):
    return _v5_expand(fr, delta_u, vict_tail)


@partial(jax.jit, static_argnames=("u_max", "k_max", "with_k"))
def batched_merge_weave_v5(hi, lo, cci, vclass, valid, seg,
                           sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
                           sg_len, sg_lane0, sg_dense, sg_tail_special,
                           sg_valid, sg_vsum, u_max: int, k_max: int,
                           with_k: bool = False):
    """Segment-union batch: [B, N] node lanes + [B, S] segment tables
    -> per-replica (rank, visible, conflict, overflow), rank/visible
    indexed by concat lane, plus the run width the batch ran at when
    ``with_k`` (``batched_v5``). ``k_max`` is the run cap, the line
    past which a row overflows; the run tail runs at the smallest
    width of ``run_widths(k_max)`` that holds the batch's runs."""
    out = batched_v5(hi, lo, cci, vclass, valid, seg,
                     sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
                     sg_len, sg_lane0, sg_dense, sg_tail_special,
                     sg_valid, sg_vsum, u_max=u_max, k_max=k_max)
    return out if with_k else out[:4]
