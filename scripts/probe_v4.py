"""Isolated-cost probe for the v4 kernel's building blocks at full
north-star size (B=1024, N=20480), plus whole-kernel timings.

Methodology: the v3 phase profile attributed costs by differencing
progressively longer pipeline prefixes, which XLA dead-code
elimination confounds (a prefix that only consumes ``h`` gets a
1-operand sort, so the next stage's delta silently includes the other
operands' sort cost). Here every program is an *isolated* primitive
with all inputs consumed, timed under the scalar-fetch sync; read
costs directly, not by subtraction. Prints incrementally (run with
``python -u``) so a timeout keeps partial results.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from cause_tpu import benchgen
from cause_tpu.benchgen import LANE_KEYS4, merge_wave_scalar


def timed(name, fn, *args, reps=2):
    try:
        out = np.asarray(fn(*args))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = np.asarray(fn(*args))
            ts.append((time.perf_counter() - t0) * 1000.0)
        print(f"{name:48s} {float(np.median(ts)):9.1f} ms", flush=True)
        return out
    except Exception as e:  # noqa: BLE001 - keep probing
        print(f"{name:48s} FAILED {type(e).__name__}: "
              f"{str(e).splitlines()[0][:120]}", flush=True)
        return None


def main():
    from cause_tpu.benchgen import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args_ns = ap.parse_args()
    if args_ns.smoke:
        B, NB, ND, CAP = 8, 800, 100, 1024
    else:
        B, NB, ND, CAP = 1024, 9_000, 1_000, 10_240

    print(f"platform={jax.devices()[0].platform} B={B} cap={CAP}",
          flush=True)
    batch = benchgen.batched_pair_lanes(
        n_replicas=B, n_base=NB, n_div=ND, capacity=CAP, hide_every=8
    )
    k_max = benchgen.pair_run_budget(batch)
    print(f"k_max={k_max}", flush=True)
    dev = {k: jax.device_put(batch[k]) for k in
           dict.fromkeys(benchgen.LANE_KEYS + LANE_KEYS4)}
    N = batch["hi"].shape[1]
    K = k_max
    hi, lo, cci, vc, va = (dev[k] for k in LANE_KEYS4)

    @jax.jit
    def floor_prog(h):
        return h[0, 0] + jnp.float32(0)

    timed("dispatch floor", floor_prog, hi)

    # ---- the sort, in the variants that matter
    @jax.jit
    def sort_keys_only(h, l):
        def row(a, b):
            return lax.sort((a, b), num_keys=2)[0]

        return jnp.sum(jax.vmap(row)(h, l).astype(jnp.float32))

    timed("sort 2 keys, no payload", sort_keys_only, hi, lo)

    @jax.jit
    def sort_v4(h, l, cc, v):
        def row(a, b, c2, v2):
            idx = jnp.arange(a.shape[0], dtype=jnp.int32)
            outs = lax.sort((a, b, idx, v2, c2), num_keys=2)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

        return jnp.sum(jax.vmap(row)(h, l, cc, v))

    timed("sort 2 keys + 3 payloads (v4 front)", sort_v4, hi, lo, cci, vc)

    @jax.jit
    def sort_gather6(*a):
        def row(h, l, ch, cl, v2, va2):
            o = jnp.lexsort((l, h))
            return (jnp.sum(h[o]) + jnp.sum(l[o]) + jnp.sum(ch[o])
                    + jnp.sum(cl[o]) + jnp.sum(v2[o])
                    + jnp.sum(va2[o].astype(jnp.int32))).astype(jnp.float32)

        return jnp.sum(jax.vmap(row)(*a))

    timed("lexsort + 6 perm gathers (v3 front)", sort_gather6,
          *[dev[k] for k in benchgen.LANE_KEYS])

    # ---- full-width scans and elementwise
    @jax.jit
    def one_cumsum(h):
        return jnp.sum(jnp.cumsum(h, axis=1).astype(jnp.float32))

    timed("ONE full-width cumsum", one_cumsum, hi)

    @jax.jit
    def one_cummax(h):
        return jnp.sum(lax.cummax(h, axis=1).astype(jnp.float32))

    timed("ONE full-width cummax", one_cummax, hi)

    @jax.jit
    def eight_scans(h, l):
        acc = jnp.float32(0)
        for i in range(4):
            acc += jnp.sum(jnp.cumsum(h + i, axis=1).astype(jnp.float32))
            acc += jnp.sum(lax.cummax(l - i, axis=1).astype(jnp.float32))
        return acc

    timed("8 full-width scans (4 cumsum + 4 cummax)", eight_scans, hi, lo)

    @jax.jit
    def elementwise30(h, l, cc, v):
        x = h
        for i in range(10):
            x = (x * 3 + l) ^ (cc + i)
            x = jnp.where(v > 0, x, x + 1)
            x = jnp.maximum(x, l)
        return jnp.sum(x.astype(jnp.float32))

    timed("~30 fused elementwise passes", elementwise30, hi, lo, cci, vc)

    # ---- full-width random access (the v4 cause resolution pair)
    order = jnp.argsort(hi, axis=1).astype(jnp.int32)

    @jax.jit
    def inv_scatter(o):
        def row(orow):
            n = orow.shape[0]
            return jnp.zeros(n, jnp.int32).at[orow].set(
                jnp.arange(n, dtype=jnp.int32)
            )

        return jnp.sum(jax.vmap(row)(o).astype(jnp.float32))

    timed("ONE full-width scatter (inverse perm)", inv_scatter, order)

    @jax.jit
    def full_gather(h, cc):
        def row(hrow, crow):
            n = hrow.shape[0]
            return hrow[jnp.clip(crow, 0, n - 1)]

        return jnp.sum(jax.vmap(row)(h, cc).astype(jnp.float32))

    timed("ONE full-width gather (cause_pos)", full_gather, hi, cci)

    # ---- K-width pieces
    targets = jnp.broadcast_to(
        jnp.arange(1, K + 1, dtype=jnp.int32), (B, K)).copy()
    cum = jnp.cumsum(va.astype(jnp.int32), axis=1)

    @jax.jit
    def ss(c, t):
        def row(cr, tr):
            return jnp.searchsorted(cr, tr, side="left").astype(jnp.int32)

        return jnp.sum(jax.vmap(row)(c, t).astype(jnp.float32))

    timed("ONE searchsorted K into N", ss, cum, targets)

    # compaction variants: extract the flagged lanes' indices into K
    # dense slots (ascending). searchsorted is what v3/v4 ship; top_k
    # and sort-prefix are the candidate replacements.
    flag = (dev["vc"] > 0) | ((dev["cci"] % 11) == 0)

    @jax.jit
    def compact_topk(f):
        def row(fr):
            n = fr.shape[0]
            key = jnp.where(fr, -jnp.arange(n, dtype=jnp.int32),
                            jnp.int32(-(1 << 30)))
            top, _ = lax.top_k(key, K)
            return -top

        return jnp.sum(jax.vmap(row)(f).astype(jnp.float32))

    timed("compaction via top_k", compact_topk, flag)

    @jax.jit
    def compact_sort(f):
        def row(fr):
            n = fr.shape[0]
            key = jnp.where(fr, jnp.arange(n, dtype=jnp.int32),
                            jnp.int32(1 << 30))
            return lax.sort(key)[:K]

        return jnp.sum(jax.vmap(row)(f).astype(jnp.float32))

    timed("compaction via full sort prefix", compact_sort, flag)

    qidx = jnp.broadcast_to(
        (jnp.arange(K, dtype=jnp.int32) * 7) % N, (B, K)).copy()

    @jax.jit
    def kg(h, q):
        def row(hr, qr):
            return hr[qr]

        return jnp.sum(jax.vmap(row)(h, q).astype(jnp.float32))

    timed("ONE K-wide gather from N", kg, hi, qidx)

    vals = jnp.ones((B, K), jnp.int32)

    @jax.jit
    def sc(q, v):
        def row(qr, vr):
            return jnp.zeros(N, jnp.int32).at[qr].set(vr, mode="drop")

        return jnp.sum(jax.vmap(row)(q, v).astype(jnp.float32))

    timed("ONE K->N scatter", sc, qidx, vals)

    # pointer doubling at 2K (the euler core), isolated
    nxt = jnp.broadcast_to(
        (jnp.arange(2 * K, dtype=jnp.int32) * 5 + 1) % (2 * K),
        (B, 2 * K)).copy()
    w = jnp.ones((B, 2 * K), jnp.int32)

    @jax.jit
    def pd(nx, ww):
        def row(n, v):
            def body(_, c):
                val, x = c
                return val + val[x], x[x]

            val, _ = lax.fori_loop(0, 13, body, (v, n))
            return val

        return jnp.sum(jax.vmap(row)(nx, ww).astype(jnp.float32))

    timed("pointer doubling 13 rounds at 2K", pd, nxt, w)

    # K-wide lexsort (sibling sort)
    ka = jnp.broadcast_to(
        (jnp.arange(K, dtype=jnp.int32) * 13) % K, (B, K)).copy()

    @jax.jit
    def ksort(a, b):
        def row(x, y):
            return jnp.lexsort((y, x))

        return jnp.sum(jax.vmap(row)(a, b).astype(jnp.float32))

    timed("ONE K-wide lexsort", ksort, ka, qidx)

    # ---- whole kernels
    args4 = [dev[k] for k in LANE_KEYS4]
    args6 = [dev[k] for k in benchgen.LANE_KEYS]

    def whole(kernel, k):
        lanes = args4 if kernel == "v4" else args6

        def run():
            return merge_wave_scalar(*lanes, k_max=k, kernel=kernel)

        return run

    timed("WHOLE v4", whole("v4", k_max))
    timed("WHOLE v3", whole("v3", k_max))


if __name__ == "__main__":
    main()
