"""The F-phase tile-window fusion: v5's lane expansion in one kernel.

Phase F of the v5 segment-union kernel (jaxw5) expands token-width
results back to the full concat-lane width: per-lane rank fills,
segment coverage, and visibility. The XLA form is delta scatters into
[N]-buffers, three N-width cumsums, and ~10 elementwise N-passes —
every one an HBM round trip, and the scatters serialize. The round-3
chip stage attribution predicts this phase dominates v5 once the
token phases stream (PERF.md "Round 4": the tile-window theorem).

This module computes the same values inside ONE Pallas kernel per
8-row block with zero scatters and zero cumsums, from two facts:

- kept tokens have DISTINCT lanes (each surviving segment contributes
  its head lane once; exploded segments contribute each lane once;
  duplicate-id tokens are dropped before ranking), so a 128-lane tile
  intersects at most 128 tokens. The per-lane fill (the last kept
  token at or before the lane — exactly what the XLA delta-cumsum
  telescopes to) is therefore computable per tile from a FIXED
  128-token window starting at ``searchsorted(token_lanes,
  tile_start)``, via a [window=128, lanes=128] compare-select matrix
  in VMEM, with the single token before the window as the carry.
- covered segments are disjoint contiguous lane runs, so per-lane
  coverage (``in_surviving``) is the same rightmost-start-at-or-
  before query against the sorted coverage table, testing the
  selected segment's end.

Mosaic layout note: every ref keeps the natural [rows, width]
orientation (whole-width blocks satisfy the (8, 128) tiling rule; a
transposed (width, 8) block does not). A window loads as a [1, 128]
lane-oriented slice and is flipped to the [128, 1] sublane orientation
with one tiny MXU dot against the identity (int values here are
< 2^24, so the f32 contraction is exact) — Mosaic has no cheap
relayout, but a 128x128x1 matmul is effectively free. The
compare-select matrices are then [window=128 sublanes, lane=128
lanes] and reduce along sublanes into [1, 128] results that store
straight into the [B, N] outputs. Window-start tables are
precomputed in XLA as tiny [B, T] comparison-matrix searchsorteds
(T = N/128 tiles).

Visibility (the pure elementwise tail: next-lane tombstone checks,
kill flags, value-class masks) runs as a second vectorized pass over
the whole [8, N] block inside the same kernel. The only F-phase work
left in XLA are the U-width kill scatters (duplicate victims are
possible, so they are genuine scatters) and the root-lane bit, both
folded into one input bit-plane.

Replaces the weave linearization of
/root/reference/src/causal/collections/shared.cljc:225-241 at batch
width (same anchor as jaxw5 phase F). ``CAUSE_TPU_FPHASE=pallas``
flips jaxw5 at trace time; bit-exactness vs the XLA form is pinned by
tests/test_fphase.py and the Mosaic lowering by
tests/test_pallas_lowering.py.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .arrays import VCLASS_H_HIDE, VCLASS_HIDE

__all__ = ["fphase_expand"]

_ROWS = 8  # rows per grid block (the Mosaic sublane tiling unit)
_LANE = 128


def _interpret() -> bool:
    """Interpret off-TPU (tests, dryrun); compile via Mosaic on TPU."""
    return jax.default_backend() != "tpu"


def _kernel(lk_ref, tb_ref, cs_ref, ce_ref, tw0_ref, sw0_ref,
            vc_ref, seg_ref, fl_ref, rank_ref, vis_ref):
    """One 8-row block: per-(row, tile) window fills, then the
    vectorized visibility pass over the whole block."""
    R, N = vc_ref.shape
    Up = lk_ref.shape[1]
    Sp = cs_ref.shape[1]
    T = N // _LANE
    i0 = lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)  # window j
    i1 = lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)      # lane pos
    eye = (i0 == lax.broadcasted_iota(
        jnp.int32, (_LANE, _LANE), 1)).astype(jnp.float32)

    def flip(v_row):
        """[1, 128] -> [128, 1] via one MXU dot (exact: |v| < 2^24)."""
        return lax.dot_general(
            eye, v_row.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

    def row(r, _):
        def tile(t, __):
            lane = t * _LANE + i1                         # [1, 128]

            # ---- token fill window --------------------------------
            c0t = tw0_ref[r, t]
            ws = jnp.clip(c0t, 0, Up - _LANE)
            wlk = flip(lk_ref[pl.ds(r, 1), pl.ds(ws, _LANE)])
            wtb = flip(tb_ref[pl.ds(r, 1), pl.ds(ws, _LANE)])
            m = wlk <= lane                               # [128, 128]
            jmax = jnp.max(jnp.where(m, i0, -1), axis=0,
                           keepdims=True)                 # [1, 128]
            found = jmax >= 0
            sel = i0 == jmax
            bsel = jnp.sum(jnp.where(sel, wtb, 0), axis=0,
                           keepdims=True)
            lsel = jnp.sum(jnp.where(sel, wlk, 0), axis=0,
                           keepdims=True)
            ci = jnp.maximum(c0t - 1, 0)
            has_c = c0t > 0
            cb = jnp.where(has_c, tb_ref[r, ci], 0)
            cl = jnp.where(has_c, lk_ref[r, ci], 0)
            base_f = jnp.where(found, bsel, cb)
            lane_f = jnp.where(found, lsel, cl)
            has_tok = found & (lsel == lane)

            # ---- segment coverage window --------------------------
            c0s = sw0_ref[r, t]
            ss = jnp.clip(c0s, 0, Sp - _LANE)
            wcs = flip(cs_ref[pl.ds(r, 1), pl.ds(ss, _LANE)])
            wce = flip(ce_ref[pl.ds(r, 1), pl.ds(ss, _LANE)])
            m2 = wcs <= lane
            j2 = jnp.max(jnp.where(m2, i0, -1), axis=0,
                         keepdims=True)
            f2 = j2 >= 0
            esel = jnp.sum(jnp.where(i0 == j2, wce, 0), axis=0,
                           keepdims=True)
            si = jnp.maximum(c0s - 1, 0)
            ce_carry = jnp.where(c0s > 0, ce_ref[r, si], 0)
            in_surv = jnp.where(f2, esel, ce_carry) > lane

            fl = fl_ref[pl.ds(r, 1), pl.ds(t * _LANE, _LANE)]
            valid = (fl & 1) > 0
            rank_t = jnp.where(
                valid & (in_surv | has_tok),
                base_f + (lane - lane_f), N
            ).astype(jnp.int32)
            rank_ref[pl.ds(r, 1), pl.ds(t * _LANE, _LANE)] = rank_t
            # stash coverage for the visibility pass
            vis_ref[pl.ds(r, 1), pl.ds(t * _LANE, _LANE)] = (
                in_surv.astype(jnp.int32))
            return 0

        lax.fori_loop(0, T, tile, 0)
        return 0

    lax.fori_loop(0, R, row, 0)

    # ---- visibility: one vectorized pass over the block -----------
    rank = rank_ref[:, :]
    in_surv = vis_ref[:, :] > 0
    vc = vc_ref[:, :]
    seg = seg_ref[:, :]
    fl = fl_ref[:, :]
    valid = (fl & 1) > 0
    killed_ext = (fl & 2) > 0  # kill scatters + root lane (from XLA)
    col = lax.broadcasted_iota(jnp.int32, (R, N), 1)
    not_last = col < N - 1
    hide = ((vc == VCLASS_HIDE) | (vc == VCLASS_H_HIDE)).astype(
        jnp.int32)
    nxt_same = (jnp.roll(seg, -1, axis=1) == seg) & (seg >= 0) \
        & not_last
    nxt_hide = (jnp.roll(hide, -1, axis=1) > 0) & not_last
    kill_in = in_surv & nxt_same & nxt_hide
    vis_ref[:, :] = (
        valid & (rank < N) & (vc == 0) & ~killed_ext & ~kill_in
    ).astype(jnp.int32)


@lru_cache(maxsize=None)
def _build():
    def kernel(*refs):
        _kernel(*refs)

    def batch_call(lk, tb, cs, ce, tw0, sw0, vc, seg, fl):
        B, N = vc.shape
        Up = lk.shape[1]
        Sp = cs.shape[1]
        T = tw0.shape[1]
        Bp = -(-B // _ROWS) * _ROWS
        if Bp != B:
            # padded rows: flags 0 => valid False => rank N, vis 0
            pad = ((0, Bp - B), (0, 0))
            lk, tb, cs, ce, tw0, sw0, vc, seg, fl = (
                jnp.pad(x, pad) for x in
                (lk, tb, cs, ce, tw0, sw0, vc, seg, fl))
        def vmem(width):
            # blocks cover the whole width (satisfies the tiling rule
            # for widths that are not 128-multiples, e.g. T) and walk
            # the replica axis in 8-row steps
            shape = (_ROWS, width)
            imap = lambda b: (b, 0)
            return pl.BlockSpec(shape, imap,
                                memory_space=pltpu.VMEM)

        out = pl.pallas_call(
            kernel,
            grid=(Bp // _ROWS,),
            in_specs=[
                vmem(Up), vmem(Up), vmem(Sp), vmem(Sp),
                vmem(T), vmem(T),
                vmem(N), vmem(N), vmem(N),
            ],
            out_specs=[vmem(N)] * 2,
            out_shape=[jax.ShapeDtypeStruct((Bp, N), jnp.int32)] * 2,
            interpret=_interpret(),
        )(lk, tb, cs, ce, tw0, sw0, vc, seg, fl)
        return tuple(x[:B] for x in out)

    @jax.custom_batching.custom_vmap
    def single(lk, tb, cs, ce, tw0, sw0, vc, seg, fl):
        out = batch_call(*(x[None] for x in
                           (lk, tb, cs, ce, tw0, sw0, vc, seg, fl)))
        return tuple(x[0] for x in out)

    @single.def_vmap
    def _single_vmap(axis_size, in_batched, *ops):
        ops = tuple(
            x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(ops, in_batched))
        return batch_call(*ops), (True, True)

    return single, batch_call


def fphase_expand(lk, tb_l, cov_start, cov_end, vclass, seg, flags):
    """Per-lane (rank, visible) for one row, fused in VMEM.

    ``lk``/``tb_l``: lane-sorted kept-token lanes (N sentinel past the
    kept prefix) and their token bases, as phase F's lane sort emits.
    ``cov_start``/``cov_end``: the SORTED surviving-segment coverage
    table (start ascending; sentinel entries start=N, end=0).
    ``vclass``/``seg``: the kernel's per-lane value-class and segment
    ordinals. ``flags``: bit 0 = lane valid, bit 1 = killed-external
    (the U-width kill scatters + the root lane, still XLA-side).

    Requires ``N % 128 == 0`` (the jaxw5 caller falls back to the XLA
    form otherwise). Under ``vmap`` the batch maps onto the Pallas
    grid of 8-row blocks.
    """
    N = vclass.shape[-1]
    assert N % _LANE == 0, N
    # the in-kernel window flips contract through f32 (exact only
    # below 2^24); every windowed value is a lane index or rank < N
    assert N < (1 << 24), N
    T = N // _LANE

    # pad token/coverage tables to >= one window
    fill_lk = jnp.full((1,), N, jnp.int32)
    Up = max(_LANE, lk.shape[-1])
    if lk.shape[-1] < Up:
        pad_n = Up - lk.shape[-1]
        lk = jnp.concatenate(
            [lk, jnp.broadcast_to(fill_lk, (pad_n,))])
        tb_l = jnp.concatenate(
            [tb_l, jnp.zeros((pad_n,), jnp.int32)])
    Sp = max(_LANE, cov_start.shape[-1])
    if cov_start.shape[-1] < Sp:
        pad_n = Sp - cov_start.shape[-1]
        cov_start = jnp.concatenate(
            [cov_start, jnp.full((pad_n,), N, jnp.int32)])
        cov_end = jnp.concatenate(
            [cov_end, jnp.zeros((pad_n,), jnp.int32)])

    # [T] window starts: comparison-matrix searchsorted (tiny)
    starts = (jnp.arange(T, dtype=jnp.int32) * _LANE)
    tw0 = jnp.sum(
        (lk[None, :] < starts[:, None]), axis=1).astype(jnp.int32)
    sw0 = jnp.sum(
        (cov_start[None, :] < starts[:, None]), axis=1
    ).astype(jnp.int32)

    single, _ = _build()
    rank, vis = single(lk, tb_l, cov_start, cov_end, tw0, sw0,
                       vclass, seg, flags)
    return rank, vis > 0
