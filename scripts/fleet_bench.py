"""Fleet convergence benchmark: K divergent replicas into ONE tree.

Two measurements:

1. **Kernel-level** (device only): ``fleet_lanes`` flattens the whole
   fleet into one [K*cap] lane row; the merge kernel's sort-dedupe
   union is K-ary for free, so one dispatch converges the entire
   fleet. This is the "1024 replicas into one tree" reading of the
   north star.
2. **API-level** (host union + one device reweave):
   ``CausalList.merge_many`` at a smaller K, reporting the host-union
   and reweave split.

Prints one JSON line per measurement.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp


def _timed_with_overflow_doubling(step, budget: int):
    """Shared harness: warm/retry until the budget fits (``step``
    raises OverflowError), then report the 3-run median and the final
    budget actually used."""
    while True:
        try:
            step(budget)
            break
        except OverflowError:
            budget *= 2
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        step(budget)
        ts.append((time.perf_counter() - t0) * 1000)
    return float(np.median(ts)), budget


def kernel_level(K: int, n_base: int, n_div: int, cap: int) -> dict:
    from cause_tpu import benchgen
    from cause_tpu.weaver.jaxw4 import merge_weave_kernel_v4_jit

    lanes = benchgen.fleet_lanes(
        n_replicas=K, n_base=n_base, n_div=n_div, capacity=cap,
        hide_every=8,
    )
    # runs scale with K (each replica contributes its suffix's runs;
    # a pair row counts two suffixes, so half of it per replica), and
    # the overflow loop below corrects any shortfall
    est = benchgen.estimate_pair_runs(
        {k: lanes[k][: 2 * cap] for k in benchgen.LANE_KEYS}
    )
    args = [jax.device_put(jnp.asarray(lanes[k]))
            for k in benchgen.LANE_KEYS4]

    def step(k):
        o, r, v, c, ovf = merge_weave_kernel_v4_jit(*args, k_max=k)
        out = np.asarray(
            jnp.stack([jnp.sum(r.astype(jnp.float32)),
                       ovf.astype(jnp.float32)])
        )
        if out[1]:
            raise OverflowError(k)
        return out

    p50, k_max = _timed_with_overflow_doubling(
        step, max(1024, 1024 + (est * K) // 2)
    )
    return {
        "metric": f"fleet kernel-merge {K} replicas x "
                  f"{1 + n_base + n_div} nodes -> one tree",
        "value": round(p50, 1),
        "unit": "ms",
        "lanes": K * cap,
        "k_max": k_max,
        "platform": jax.devices()[0].platform,
    }


def kernel_level_v5(K: int, n_base: int, n_div: int, cap: int) -> dict:
    """The same fleet convergence through the v5 segment-union kernel:
    all K copies of the shared base dedupe wholesale, so token count is
    ~K * divergence instead of K * document."""
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import merge_weave_kernel_v5_jit

    lanes = benchgen.fleet_lanes(
        n_replicas=K, n_base=n_base, n_div=n_div, capacity=cap,
        hide_every=8,
    )
    t0 = time.perf_counter()
    v5row = benchgen.v5_inputs(lanes, cap)
    marshal_ms = (time.perf_counter() - t0) * 1000
    tokens = benchgen.estimate_tokens(v5row)
    args = [jax.device_put(jnp.asarray(v5row[k])) for k in LANE_KEYS5]

    def step(k):
        rank, vis, c, ovf = merge_weave_kernel_v5_jit(
            *args, u_max=k, k_max=k
        )
        out = np.asarray(
            jnp.stack([jnp.sum(rank.astype(jnp.float32)),
                       ovf.astype(jnp.float32)])
        )
        if out[1]:
            raise OverflowError(k)
        return out

    p50, u_max = _timed_with_overflow_doubling(
        step, benchgen.v5_token_budget(v5row)
    )
    return {
        "metric": f"fleet kernel-merge v5 {K} replicas x "
                  f"{1 + n_base + n_div} nodes -> one tree",
        "value": round(p50, 1),
        "unit": "ms",
        "lanes": K * cap,
        "tokens": int(tokens),
        "u_max": u_max,
        "marshal_ms": round(marshal_ms, 1),
        "platform": jax.devices()[0].platform,
    }


def api_level(K: int, n_nodes: int) -> dict:
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.ids import new_site_id

    base = c.clist(weaver="jax").extend(
        ["x"] * n_nodes
    )
    fleet = []
    for i in range(K):
        r = CausalList(base.ct.evolve(site_id=new_site_id()))
        r = r.extend([f"r{i}-{j}" for j in range(32)])
        fleet.append(r)

    fleet[0].merge_many(fleet[1:])  # warm the jit cache for this tier
    t0 = time.perf_counter()
    merged = fleet[0].merge_many(fleet[1:])
    wall = (time.perf_counter() - t0) * 1000
    assert len(merged.ct.nodes) == len(base.ct.nodes) + K * 32
    return {
        "metric": f"API merge_many {K} replicas x {n_nodes}+32 nodes",
        "value": round(wall, 1),
        "unit": "ms",
        "platform": jax.devices()[0].platform,
    }


def main():
    from cause_tpu.benchgen import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        print(json.dumps(kernel_level_v5(K=8, n_base=800, n_div=100,
                                         cap=1024)))
        print(json.dumps(kernel_level(K=8, n_base=800, n_div=100,
                                      cap=1024)))
        print(json.dumps(api_level(K=8, n_nodes=1000)))
    else:
        print(json.dumps(kernel_level_v5(K=1024, n_base=9000, n_div=1000,
                                         cap=10240)), flush=True)
        print(json.dumps(kernel_level(K=1024, n_base=9000, n_div=1000,
                                      cap=10240)), flush=True)
        print(json.dumps(api_level(K=64, n_nodes=10000)))


if __name__ == "__main__":
    main()
