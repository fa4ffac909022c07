"""The stage profiler: the jaxw5 ``stage=`` prefix ladder as obs spans.

``scripts/probe_v5_stages.py`` and ``scripts/profile_phases.py`` each
carried a private compile-warm-then-median timing loop whose numbers
lived only in stdout. This module is the ONE timing loop
(``timed_median``) plus the v5 cumulative-prefix ladder
(``run_v5_stage_ladder``), both recording through obs — every warm
compile, every rep, and every stage delta lands in the same
JSONL/Perfetto stream as the bench and wave spans, so a stage
attribution is a trace artifact, not a scrollback line.

Stages (jaxw5 early returns, each checksumming its live outputs so XLA
cannot DCE the prefix): A segment ordering + explode/dedupe; B token
construction; C token sort + dedupe; D cause resolution; E token-width
ranking + kills; FULL adds lane expansion + visibility.

CLI: ``python -m cause_tpu.obs stages [--smoke] [--reps N]
[--allstream] [--shape B,NB,ND,CAP] [--obs-out PATH]`` — enables obs
for the run and streams the ladder's spans to the sidecar.

Unlike the rest of ``cause_tpu.obs`` this module imports jax/numpy
(lazily, inside the entry points): it exists to *run* kernels. It is
deliberately NOT imported by ``cause_tpu.obs.__init__``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Callable, List, Optional, Tuple

from . import core

__all__ = ["STAGES", "timed_median", "run_v5_stage_ladder", "main"]

# the jaxw5 prefix ladder, cumulative; None = the full kernel
STAGES = ("A", "B", "C", "D", "E", None)


def timed_median(name: str, fn: Callable, *args,
                 reps: int = 3) -> Tuple[object, float, List[float]]:
    """The shared timing loop: compile + warm once (under a
    ``stages.warm`` span — on a fresh program this IS the XLA compile
    wall time), then ``reps`` timed executions (``stages.rep`` spans),
    forcing each with a host fetch (``np.asarray``). Returns (last output, median ms, all
    samples). Works identically with obs off — the spans are no-ops
    and the perf_counter numbers remain."""
    import numpy as np

    with core.span("stages.warm", program=name):
        out = np.asarray(fn(*args))
    samples: List[float] = []
    for i in range(max(1, int(reps))):
        with core.span("stages.rep", program=name, rep=i):
            t0 = time.perf_counter()
            out = np.asarray(fn(*args))
            samples.append((time.perf_counter() - t0) * 1000.0)
    p50 = float(np.median(samples))
    core.event("stages.result", program=name, p50_ms=round(p50, 3),
               samples=[round(s, 3) for s in samples])
    return out, p50, samples


_ALLSTREAM_FLIPS = (
    ("CAUSE_TPU_SORT", "bitonic"),  # causelint: disable=TID002 -- probe's own A/B flip, deliberately restated
    ("CAUSE_TPU_GATHER", "rowgather"),  # causelint: disable=TID002 -- probe's own A/B flip, deliberately restated
    ("CAUSE_TPU_SEARCH", "matrix"),  # causelint: disable=TID002 -- probe's own A/B flip, deliberately restated
)


def _apply_allstream() -> dict:
    """The stage probe's deliberate A/B flip of its own config (NOT
    the beststream candidate — the stage probe wants the bitonic sort
    specifically), so the restated names are intentional. Returns the
    prior values so the ladder can restore them: unlike the old probe
    script (bounded by process exit), ``run_v5_stage_ladder`` is a
    library API — leaking the flips would silently re-key every later
    ``merge_wave_scalar`` in the same interpreter."""
    saved = {k: os.environ.get(k) for k, _ in _ALLSTREAM_FLIPS}  # causelint: disable=OBS001 -- saving the probe's own A/B keys for restore; obs-off never reaches the ladder
    for k, v in _ALLSTREAM_FLIPS:
        os.environ[k] = v  # causelint: disable=TID002,OBS001 -- probe flips its own A/B config; obs-off never reaches the ladder
    return saved


def _restore_env(saved: dict) -> None:
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)  # causelint: disable=OBS001 -- restoring the pre-ladder A/B config; obs-off never reaches the ladder
        else:
            os.environ[k] = v  # causelint: disable=TID002,OBS001 -- restoring the pre-ladder A/B config


def run_v5_stage_ladder(smoke: bool = False, reps: int = 3,
                        allstream: bool = False,
                        shape: Optional[Tuple[int, int, int, int]] = None,
                        echo: Callable[[str], None] = None) -> List[dict]:
    """Time the kernel truncated at each stage checkpoint at the
    north-star bench shape (or ``--smoke`` / an explicit ``shape``)
    and report per-stage increments — the measurement the isolated
    re-implementations in probe_v5.py can't give: the *actual*
    compiled prefix cost, gathers, vmap batching and all.

    Every stage lands as a ``stages.prefix`` obs event (stage, p50,
    delta) on top of the per-rep spans from ``timed_median``, keyed to
    the platform and switch snapshot like everything else in the
    stream. Returns the stage dicts in ladder order."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from .. import benchgen
    from ..benchgen import LANE_KEYS5, enable_compile_cache
    from ..weaver.jaxw5 import merge_weave_kernel_v5

    def _echo(line: str) -> None:
        (echo or (lambda s: print(s, flush=True)))(line)

    enable_compile_cache()
    saved_env = _apply_allstream() if allstream else {}
    try:
        if shape is not None:
            B, NB, ND, CAP = shape
        elif smoke:
            B, NB, ND, CAP = 8, 800, 100, 1024
        else:
            B, NB, ND, CAP = 1024, 9_000, 1_000, 10_240

        platform = jax.devices()[0].platform
        core.set_platform(platform)
        _echo(f"platform={platform} B={B} cap={CAP}")
        with core.span("stages.marshal", B=B, cap=CAP):
            batch = benchgen.batched_pair_lanes(
                n_replicas=B, n_base=NB, n_div=ND, capacity=CAP,
                hide_every=8,
            )
            v5 = benchgen.batched_v5_inputs(batch, CAP)
            u = benchgen.v5_token_budget(v5)
        _echo(f"u_budget={u} S={v5['sg_len'].shape[1]} "
              f"N={v5['hi'].shape[1]}")
        dev = {k: jax.device_put(v5[k]) for k in LANE_KEYS5}
        args = [dev[k] for k in LANE_KEYS5]

        progs = {}

        def prog_for(stage):
            if stage not in progs:
                def row(*xs):
                    out = merge_weave_kernel_v5(*xs, u_max=u, k_max=u,
                                                stage=stage)
                    if stage is None:
                        rank, visible, conflict, overflow = out
                        return (jnp.sum(rank.astype(jnp.float32))
                                + jnp.sum(visible.astype(jnp.float32))
                                + conflict.astype(jnp.float32)
                                + overflow.astype(jnp.float32))
                    return out

                progs[stage] = jax.jit(
                    lambda *xs: jnp.sum(jax.vmap(row)(*xs))
                )
            return progs[stage]

        results: List[dict] = []
        prev = 0.0
        for stage in STAGES:
            name = stage or "FULL"
            p = prog_for(stage)
            try:
                _, med, _samples = timed_median(
                    f"stages.prefix.{name}", p, *args, reps=reps)
            except Exception as e:  # noqa: BLE001 - keep probing
                _echo(f"prefix->{name} FAILED "
                      f"{type(e).__name__}: "
                      f"{str(e).splitlines()[0][:120]}")
                core.event("stages.prefix", stage=name,
                           error=str(e)[:200])
                continue
            _echo(f"prefix->{name:4s} {med:9.1f} ms   "
                  f"(+{med - prev:8.1f} ms)")
            core.event("stages.prefix", stage=name,
                       p50_ms=round(med, 3),
                       delta_ms=round(med - prev, 3))
            results.append({"stage": name, "p50_ms": med,
                            "delta_ms": med - prev})
            prev = med
        core.flush()
        return results
    finally:
        _restore_env(saved_env)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m cause_tpu.obs stages",
        description="Cumulative-prefix stage timing of the v5 kernel "
                    "through obs spans (the probe_v5_stages.py ladder "
                    "as a first-class obs artifact).")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--allstream", action="store_true",
                    help="profile the streaming configuration "
                         "(rowgather + bitonic + matrix search)")
    ap.add_argument("--shape", default="",
                    help="explicit B,n_base,n_div,capacity (overrides "
                         "--smoke; e.g. 2,30,6,64 for a tiny run)")
    ap.add_argument("--obs-out", default="",
                    help="stream the run's obs events to this JSONL "
                         "path (default: record into the ring only)")
    a = ap.parse_args(argv)
    shape = None
    if a.shape:
        try:
            parts = tuple(int(x) for x in a.shape.split(","))
            if len(parts) != 4:
                raise ValueError
            shape = parts
        except ValueError:
            ap.error("--shape wants B,n_base,n_div,capacity")
    core.configure(enabled=True,
                   out=a.obs_out if a.obs_out else None)
    results = run_v5_stage_ladder(smoke=a.smoke, reps=a.reps,
                                  allstream=a.allstream, shape=shape)
    if a.obs_out:
        print(f"stages: obs events -> {a.obs_out}", file=sys.stderr)
    return 0 if results else 1


if __name__ == "__main__":
    raise SystemExit(main())
