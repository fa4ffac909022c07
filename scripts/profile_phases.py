"""Phase-level TPU timing of the north-star merge+weave program.

Times each stage of the batched v2 pipeline in isolation (same shapes
and data as bench.py full size unless --smoke) so optimization work
targets the real bottleneck instead of a guess. Each phase is its own
jitted program whose output reduces to one scalar; the device->host
fetch of that scalar is the sync point.

DEPRECATION NOTE: this script's private timing loop is gone — all
timing routes through the shared obs stage profiler
(``cause_tpu.obs.stages.timed_median``), so with ``CAUSE_TPU_OBS=1``
each phase's warm compile and reps land in the obs JSONL/Perfetto
stream. The v5 stage ladder equivalent is ``python -m cause_tpu.obs
stages``; this script remains for the v2-pipeline phase split only.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import argparse
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from cause_tpu import benchgen
from cause_tpu.benchgen import LANE_KEYS
from cause_tpu.obs.stages import timed_median
from cause_tpu.weaver import jaxw


def timed(name, fn, *args, reps=3):
    # the one timing loop lives in cause_tpu.obs.stages; this keeps
    # only the historical stdout format
    out, p50, ts = timed_median(name, fn, *args, reps=reps)
    print(f"{name:42s} {p50:10.1f} ms   (reps: {[round(t,1) for t in ts]})")
    return out, p50


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    args_ns = ap.parse_args()

    if args_ns.smoke:
        B, n_base, n_div, cap = 8, 800, 100, 1024
    else:
        B, n_base, n_div, cap = 1024, 9_000, 1_000, 10_240

    print(f"platform={jax.devices()[0].platform} B={B} cap={cap}")
    batch = benchgen.batched_pair_lanes(
        n_replicas=B, n_base=n_base, n_div=n_div, capacity=cap, hide_every=8
    )
    k_max = benchgen.pair_run_budget(batch)
    print(f"k_max={k_max}")
    dev = [jax.device_put(batch[k]) for k in LANE_KEYS]
    hi, lo, chi, clo, vc, va = dev
    M = hi.shape[1]
    reps = args_ns.reps

    # ---- phase 1: id lexsort only
    @jax.jit
    def p_sort(hi, lo):
        def row(h, l):
            o = jnp.lexsort((l, h))
            return jnp.sum(o.astype(jnp.float32))
        return jnp.sum(jax.vmap(row)(hi, lo))

    timed("front: id lexsort (2-key, M)", p_sort, hi, lo, reps=reps)

    # ---- phase 2: full front half
    @jax.jit
    def p_front(hi, lo, chi, clo, vc, va):
        def row(h, l, ch, cl, v, m):
            order, (h_s, l_s, ci, v_s, keep, conf) = (
                jaxw._merge_front_half(h, l, ch, cl, v, m))
            return (jnp.sum(ci.astype(jnp.float32))
                    + jnp.sum(order.astype(jnp.float32)))
        return jnp.sum(jax.vmap(row)(hi, lo, chi, clo, vc, va))

    timed("front: full (2 sorts + join)", p_front, *dev, reps=reps)

    # ---- materialize sorted lanes once for the back-half phases
    @jax.jit
    def front_out(hi, lo, chi, clo, vc, va):
        def row(h, l, ch, cl, v, m):
            order, (h_s, l_s, ci, v_s, keep, conf) = (
                jaxw._merge_front_half(h, l, ch, cl, v, m))
            return h_s, l_s, ci, v_s, keep
        return jax.vmap(row)(hi, lo, chi, clo, vc, va)

    h_s, l_s, ci, v_s, keep = [np.asarray(x) for x in front_out(*dev)]
    h_s, l_s, ci, v_s, keep = map(jax.device_put, (h_s, l_s, ci, v_s, keep))

    # ---- phase 3: host jump (while_loop pointer doubling)
    @jax.jit
    def p_host(ci, v_s, keep):
        def row(c, v, k):
            N = c.shape[0]
            idx = jnp.arange(N, dtype=jnp.int32)
            is_root = k & (idx == 0)
            special = k & (v > 0)
            rel = k & ~is_root
            cs = jnp.clip(c, 0, N - 1)
            host = jaxw._host_jump(
                special, cs, rel, max(1, math.ceil(math.log2(N))))
            return jnp.sum(host.astype(jnp.float32))
        return jnp.sum(jax.vmap(row)(ci, v_s, keep))

    timed("back: host jump (while_loop)", p_host, ci, v_s, keep, reps=reps)

    # ---- phase 4: v2 full linearize
    @jax.jit
    def p_lin2(h_s, l_s, ci, v_s, keep):
        def row(h, l, c, v, k):
            rank, vis, ovf = jaxw.linearize_v2(h, l, c, v, k, k_max)
            return (jnp.sum(rank.astype(jnp.float32))
                    + jnp.sum(vis.astype(jnp.float32))
                    + ovf.astype(jnp.float32))
        return jnp.sum(jax.vmap(row)(h_s, l_s, ci, v_s, keep))

    timed("back: linearize_v2 (full)", p_lin2, h_s, l_s, ci, v_s, keep,
          reps=reps)

    # ---- phase 5: v2 contraction only (no euler, no visibility)
    @jax.jit
    def p_contract(h_s, l_s, ci, v_s, keep):
        def row(h, l, c, v, k):
            N = h.shape[0]
            idx = jnp.arange(N, dtype=jnp.int32)
            is_root = k & (idx == 0)
            special = k & (v > 0)
            rel = k & ~is_root
            cs = jnp.clip(c, 0, N - 1)
            host = jaxw._host_jump(
                special, cs, rel, max(1, math.ceil(math.log2(N))))
            parent_t = jnp.where(special, cs, host)
            parent = jnp.where(rel, parent_t, -1)
            kidx = jnp.cumsum(k.astype(jnp.int32)) - 1
            has_parent = parent >= 0
            pc = jnp.clip(parent, 0, N - 1)
            child_count = (
                jnp.zeros(N + 1, jnp.int32)
                .at[jnp.where(has_parent, pc, N)]
                .add(1)[:N]
            )
            only_child = has_parent & (child_count[pc] == 1)
            glued = only_child & (kidx[pc] == kidx - 1)
            run_start = k & ~glued
            run_id = jnp.cumsum(run_start.astype(jnp.int32)) - 1
            return jnp.sum(run_id.astype(jnp.float32))
        return jnp.sum(jax.vmap(row)(h_s, l_s, ci, v_s, keep))

    timed("back: contraction only", p_contract, h_s, l_s, ci, v_s, keep,
          reps=reps)

    # ---- phase 6: visibility only
    @jax.jit
    def p_vis(ci, v_s, keep):
        def row(c, v, k):
            N = c.shape[0]
            idx = jnp.arange(N, dtype=jnp.int32)
            rank = idx  # stand-in rank with the right shape/dtype
            node_at = jaxw._scatter_by_rank(rank, k, N)
            succ = node_at[jnp.clip(rank, 0, N) + 1]
            ss = jnp.clip(succ, 0, N - 1)
            hide = ((succ >= 0) & ((v[ss] == 2) | (v[ss] == 3))
                    & (c[ss] == idx))
            return jnp.sum(hide.astype(jnp.float32))
        return jnp.sum(jax.vmap(row)(ci, v_s, keep))

    timed("back: visibility scatter+gather", p_vis, ci, v_s, keep, reps=reps)

    # ---- whole program for reference
    from cause_tpu.benchgen import merge_wave_scalar

    def whole():
        return merge_wave_scalar(*dev, k_max=k_max)

    timed("WHOLE v2 program", whole, reps=reps)

    def whole_v1():
        return merge_wave_scalar(*dev, k_max=0)

    timed("WHOLE v1 program", whole_v1, reps=reps)


if __name__ == "__main__":
    main()
