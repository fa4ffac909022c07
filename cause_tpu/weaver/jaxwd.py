"""Delta-native weave programs: steady-state wave cost ∝ divergence.

Every wave generation so far — v5 included — dispatches at full
document width: the token pipeline scales with divergence, but the
lane phases (F expansion, digest) and the host assembly pay O(doc) per
wave even when 1024 replicas diverge by a handful of ops. PERF.md's
phase profile and the PR-6 ``wave.cost`` stream both show it: cost is
O(doc). This module is the other half of the segment-union design —
when the converged weave is *resident* (FleetSession keeps lanes, and
the last wave's ranks/visibility, on device), a steady-state wave only
needs to reweave the **divergent window** and splice the result back:

- the *window* is a tiny self-contained replica pair: one **anchor**
  lane (the final node of the converged resident weave, playing the
  root) plus each tree's divergent-suffix lanes. Within the delta
  domain (every divergent lane's cause resolves inside the window or
  to the anchor; no tombstone targets the anchor; see
  ``parallel.wave.delta_domain_ok``) the full weave factors exactly::

      weave(union) = weave(converged prefix) ++ weave(window) \\ anchor

  because every divergent node descends from the anchor and the anchor
  is the last element of the prefix weave — so prefix ranks and
  visibility are FROZEN and the window's v5 ranks, offset by the
  anchor's rank ``r0``, ARE the full-weave ranks of the divergent
  lanes. This is a semantic identity of the causal-tree linearization
  (sibling order depends only on ids/specialness, both local to the
  window), not a kernel coincidence; tests/test_delta_weave.py pins it
  against ``merge`` and the full kernel bit-for-bit.
- the *digest* is incremental and EXACT: ``mesh.replica_digest`` is a
  permutation-invariant uint32 wraparound sum of per-lane avalanche
  terms, so ``digest(full) = digest(prefix terms) + digest(window
  terms)`` with window positions offset by ``r0``. The prefix sum is
  computed once per rebuild and rides along as a [B] uint32 input.
- the *splice* is a buffer-donated masked scatter updating the
  resident full-width rank/visibility arrays in place, so on-demand
  host materialization (``WaveResult.merged``) keeps working after
  delta waves.

Budgets: the window kernel runs with ``u_max = k_max = N_w`` (the
window width), which makes token/run overflow structurally impossible
— a window can never mint more tokens than it has lanes. The only
overflow left is the window *capacity* itself (divergence outgrowing
the session's pow2 window budget), which falls back to a full-width
rebuild — the "first contact or budget overflow" policy of ROADMAP
item 1.

Consumers (PR 8): beyond the steady-state ``FleetSession`` wave, the
merge reduction tree (``parallel.tree``) batches each of its
ceil(log2(n)) fleet-convergence levels as ONE ``batched_delta_weave``
dispatch — per pair the two "trees" are pooled subtree sides under
the shared anchor, and the returned digest is each merged subtree's
TOTAL document digest, so per-level convergence evidence costs no
extra dispatch. ``batched_weave_digest`` is the tree's full-width
level (first contact / window-budget bounce), through
``parallel.wave.dispatch_full_rows``; ``bench.py``'s divergence sweep
calls it directly, as the full-width arm it checks the window program
against.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .jaxw5 import batched_v5, merge_weave_kernel_v5

__all__ = [
    "batched_delta_weave",
    "batched_weave_digest",
    "splice_ranks",
]


@partial(jax.jit, static_argnames=("u_max", "k_max", "with_k"))
def batched_weave_digest(hi, lo, cci, vclass, valid, seg,
                         sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
                         sg_len, sg_lane0, sg_dense, sg_tail_special,
                         sg_valid, sg_vsum, u_max: int, k_max: int,
                         with_k: bool = False):
    """The full-width control program: one fused dispatch running the
    batched v5 segment-union kernel (``jaxw5.batched_v5``: ``k_max`` is
    the run cap, the runs rank at the batch's own width) AND the
    per-row convergence digest. Returns ``(rank, visible, digest,
    overflow)``, plus the run width the batch ran at when ``with_k``.
    This is what the divergence sweep times as the full-weave A/B arm
    — kernel + digest in one program, the same shape of work a
    session's full wave performs in two."""
    from ..parallel.mesh import replica_digest

    rank, visible, _conflict, overflow, k = batched_v5(
        hi, lo, cci, vclass, valid, seg,
        sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
        sg_len, sg_lane0, sg_dense, sg_tail_special, sg_valid, sg_vsum,
        u_max=u_max, k_max=k_max)
    digest = jax.vmap(replica_digest)(hi, lo, rank, visible)
    out = (rank, visible, digest, overflow)
    return out + (k,) if with_k else out


@partial(jax.jit, static_argnames=("u_max", "k_max"))
def batched_delta_weave(hi, lo, cci, vclass, valid, seg,
                        sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
                        sg_len, sg_lane0, sg_dense, sg_tail_special,
                        sg_valid, sg_vsum, prefix_digest, r0,
                        u_max: int, k_max: int):
    """The delta wave: v5 segment-union over the divergent WINDOW plus
    the incremental digest, in one dispatch.

    Window lanes are ``[B, 2*wcap]`` — per tree, lane 0 is the anchor
    (the converged weave's final node, presented as the window root)
    followed by that tree's divergent-suffix lanes. ``prefix_digest``
    is the [B] uint32 sum of the resident prefix's avalanche terms
    (frozen ranks/visibility, anchor included); ``r0`` is the [B]
    anchor rank (``shared_prefix_len - 1``).

    Returns ``(rank_w, visible_w, digest, overflow)``: window-local
    ranks (full rank = ``r0 + rank_w``; the splice applies the
    offset), window visibility, the TOTAL document digest — bit
    -identical to what the full-width wave would compute — and the
    per-row overflow flag (structurally False when callers follow the
    ``u_max = k_max = N_w`` budget rule; kept as a safety net).
    """
    from ..parallel.mesh import mix32

    def row(*a):
        return merge_weave_kernel_v5(*a, u_max=u_max, k_max=k_max)

    rank_w, visible_w, _conflict, overflow = jax.vmap(row)(
        hi, lo, cci, vclass, valid, seg,
        sg_min_hi, sg_min_lo, sg_max_hi, sg_max_lo,
        sg_len, sg_lane0, sg_dense, sg_tail_special, sg_valid, sg_vsum)

    with jax.named_scope("wd.digest"):
        B, Nw = hi.shape
        wcap = Nw // 2
        lane = jnp.arange(Nw, dtype=jnp.int32)
        # the anchor lanes (one copy per tree) belong to the PREFIX digest:
        # the kept copy ranks 0 in the window but carries the prefix's own
        # rank/visibility in the full weave; the twin-dropped copy would
        # contribute zero anyway
        is_anchor = (lane == 0) | (lane == wcap)
        kept = (rank_w < Nw) & ~is_anchor[None, :]
        pos = r0[:, None].astype(jnp.uint32) + rank_w.astype(jnp.uint32)
        terms = mix32(hi, lo, jnp.where(kept, pos, 0), visible_w)
        window_sum = jnp.sum(
            jnp.where(kept, terms, jnp.uint32(0)), axis=1)
        digest = prefix_digest.astype(jnp.uint32) + window_sum
    return rank_w, visible_w, digest, overflow


@partial(jax.jit, donate_argnums=(0, 1))
def splice_ranks(rank_full, vis_full, rank_w, vis_w, starts, counts,
                 r0):
    """Splice a delta wave's window ranks/visibility into the resident
    full-width arrays (buffer-donated: updates in place on device).

    ``rank_full``/``vis_full`` are the [B, 2*cap] residents from the
    last wave; ``rank_w``/``vis_w`` the [B, 2*wcap] window outputs;
    ``starts[B, 2]`` each tree's shared-prefix length (the full-lane
    index of its first divergent lane), ``counts[B, 2]`` its divergent
    lane count, ``r0`` the [B] anchor rank. Window lane ``t*wcap+1+j``
    maps to full concat lane ``t*cap + starts[t] + j``; dropped window
    lanes (twin copies across the pair) splice the full-width sentinel
    ``2*cap``."""
    with jax.named_scope("wd.splice"):
        B, N = rank_full.shape
        cap = N // 2
        Nw = rank_w.shape[1]
        wcap = Nw // 2
        off = jnp.arange(wcap - 1, dtype=jnp.int32)

        def one_row(rf, vf, rw, vw, st, ct, r0_row):
            for t in range(2):
                src = t * wcap + 1 + off           # window D lanes
                w_rank = rw[src]
                w_vis = vw[src]
                val = jnp.where(w_rank < Nw,
                                r0_row.astype(jnp.int32) + w_rank,
                                jnp.int32(N))
                idx = t * cap + st[t] + off
                idx = jnp.where(off < ct[t], idx, N)  # beyond count: drop
                rf = rf.at[idx].set(val, mode="drop")
                vf = vf.at[idx].set(w_vis, mode="drop")
            return rf, vf

        return jax.vmap(one_row)(rank_full, vis_full, rank_w,
                                 vis_w, starts, counts, r0)
