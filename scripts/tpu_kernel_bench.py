"""Compare the v1 and v2 merge+weave kernels at configurable scales.

Thin wrapper over benchmarks.config5_batched_merge (the one shared
timing harness — checksum-transfer sync, overflow abort). Run with a
small batch first.

Usage: python scripts/tpu_kernel_bench.py [B] [n_base] [n_div] [reps]
Defaults: 64 9000 1000 3  (one-sixteenth of the north-star batch).
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import json
import sys
import time

import jax

from cause_tpu.benchmarks import config5_batched_merge


def main():
    from cause_tpu.benchgen import enable_compile_cache

    enable_compile_cache()
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    n_base = int(sys.argv[2]) if len(sys.argv) > 2 else 9000
    n_div = int(sys.argv[3]) if len(sys.argv) > 3 else 1000
    reps = int(sys.argv[4]) if len(sys.argv) > 4 else 3
    cap = 1 + n_base + n_div + 239
    cap += (-cap) % 256
    print(f"B={B} nodes/tree={1 + n_base + n_div} cap={cap} "
          f"devices={jax.devices()}", flush=True)

    for label, k_max in (("v1", 0), ("v2", None)):
        t0 = time.perf_counter()
        rec = config5_batched_merge(
            n_replicas=B, n_base=n_base, n_div=n_div, cap=cap, reps=reps,
            k_max=k_max,
        )
        wall = time.perf_counter() - t0
        per_pair = rec["value"] / B
        print(f"{label}: {json.dumps(rec)}  "
              f"({per_pair:.3f} ms/pair; x1024 projects to "
              f"{per_pair * 1024:.0f} ms; incl compile {wall:.1f}s)",
              flush=True)


if __name__ == "__main__":
    main()
