"""Device-program telemetry: compile spans, XLA cost accounting,
device-memory gauges.

Host wall time includes every dispatch round trip; the *device-side*
cost of a
compiled program (flops, bytes moved, buffer footprint) was invisible
unless someone hand-ran a probe script. This module closes that gap
at the program caches themselves:

- ``profile_program(jitfn, args, **meta)`` — on a program-cache miss
  (``benchgen.merge_wave_scalar``), the first compile is routed
  through jax's AOT path (``lower().compile()``) under a
  ``devprof.compile`` span, and the executable's ``cost_analysis()``
  / ``memory_analysis()`` land ONCE per compiled program as a
  ``devprof.program`` obs event carrying the program identity the
  cache key uses. The returned wrapper serves
  the AOT executable for matching input shapes and falls back to the
  ordinary jit path otherwise — one compile on the served path (an
  AOT executable that *errors* at call time falls back too, which
  re-compiles; that abandonment is recorded, see
  ``_ProfiledProgram``).
- ``sample_device_memory(site)`` — live-array count/bytes (and the
  backend's ``memory_stats`` where it has one) as obs gauges, sampled
  at wave boundaries (``parallel/wave.py`` / ``session.py``) so
  leaks and resident-batch growth render as curves in Perfetto.
- ``arena_footprint(arena, site)`` — host-side lane-arena bytes (the
  marshal cache the waves assemble from, ``weaver/lanecache.py``).

Contract (same as the rest of ``cause_tpu.obs``): importable without
jax — jax is imported lazily inside the enabled paths only. With
``CAUSE_TPU_OBS`` unset every entry point returns immediately:
nothing is recorded, no jax attribute is touched, and program caches
store exactly what they stored
before this module existed (pinned by tests/test_devprof.py). On
traced paths, call sites must sit behind ``obs.enabled()`` guards —
causelint rule OBS003 gates that.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

from . import core

__all__ = [
    "enabled",
    "profile_program",
    "program_cost",
    "sample_device_memory",
    "arena_footprint",
]


def enabled() -> bool:
    """Whether devprof records anything (== ``obs.enabled()``)."""
    return core.enabled()


# ------------------------------------------------------------- programs


def _args_signature(args) -> Tuple:
    """Cheap (shape, dtype) signature of a call's arguments — what the
    AOT executable was compiled for."""
    return tuple(
        (tuple(getattr(a, "shape", ()) or ()),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in args
    )


def program_cost(compiled) -> dict:
    """Normalize a compiled executable's cost/memory analysis into the
    flat metric dict the ledger compares (deterministic for a given
    program + shapes, unlike wall time). Missing analyses are simply
    absent keys — some backends omit them."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-optional API
        ca = None
    if isinstance(ca, dict):
        flops = ca.get("flops")
        if flops is not None:
            out["flops"] = float(flops)
        ba = ca.get("bytes accessed")
        if ba is not None:
            out["bytes_accessed"] = float(ba)
        if ca.get("bytes accessedout{}") is not None:
            out["output_bytes"] = float(ca["bytes accessedout{}"])
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - backend-optional API
        ma = None
    if ma is not None:
        for field, key in (
            ("argument_size_in_bytes", "argument_bytes"),
            ("output_size_in_bytes", "output_buffer_bytes"),
            ("temp_size_in_bytes", "temp_bytes"),
            ("generated_code_size_in_bytes", "code_bytes"),
        ):
            v = getattr(ma, field, None)
            if v is not None:
                out[key] = int(v)
    return out


class _ProfiledProgram:
    """The program-cache value after a profiled compile: dispatches to
    the AOT executable when the input signature matches what it was
    compiled for, otherwise falls back to the ordinary jit path (which
    re-traces for the new shapes exactly as it would have without
    devprof).

    The exception fallback is a safety valve, not a silent one: AOT
    executables can be stricter than the jit path (donation/committed
    -device rules), and the retry re-compiles the
    whole program — so the first abandonment emits a
    ``devprof.aot_abandoned`` event and every retry bumps the
    ``devprof.aot_fallback`` counter, otherwise the run's cost digest
    would describe an executable that never served the traffic."""

    __slots__ = ("jitfn", "compiled", "signature", "cost", "_abandoned")

    def __init__(self, jitfn, compiled, signature, cost):
        self.jitfn = jitfn
        self.compiled = compiled
        self.signature = signature
        self.cost = cost
        self._abandoned = False

    def __call__(self, *args):
        if _args_signature(args) == self.signature:
            try:
                return self.compiled(*args)
            except Exception as e:  # noqa: BLE001 - AOT strictness varies
                if core.enabled():
                    core.counter("devprof.aot_fallback").inc()
                    if not self._abandoned:
                        self._abandoned = True
                        core.event("devprof.aot_abandoned",
                                   error=f"{type(e).__name__}: "
                                         f"{str(e)[:200]}")
                return self.jitfn(*args)
        return self.jitfn(*args)


def profile_program(jitfn, args, **meta) -> Optional[_ProfiledProgram]:
    """AOT-compile ``jitfn`` for ``args`` under a ``devprof.compile``
    span, record its cost analysis once, and return the dispatch
    wrapper — or None (caller keeps the plain jit path) when obs is
    off or anything about the capture fails. ``meta`` is the program
    identity the cache key carries (kernel, budgets)."""
    if not core.enabled():
        return None
    try:
        t0 = time.perf_counter()
        with core.span("devprof.compile", **meta):
            compiled = jitfn.lower(*args).compile()
        cost = program_cost(compiled)
        core.event(
            "devprof.program",
            compile_ms=round((time.perf_counter() - t0) * 1000.0, 3),
            cost=cost,
            **meta,
        )
        for k, v in cost.items():
            core.gauge(f"devprof.program.{k}").set(v)
        return _ProfiledProgram(jitfn, compiled, _args_signature(args),
                                cost)
    except Exception:  # noqa: BLE001 - telemetry must never cost a run
        return None


# ------------------------------------------------------------- memory


def sample_device_memory(site: str) -> dict:
    """Gauge the process's live device arrays (count + bytes) and the
    default device's ``memory_stats`` where available. ``site`` names
    the boundary being sampled (``wave``, ``session`` ...) so each
    boundary renders as its own Perfetto counter track."""
    if not core.enabled():
        return {}
    try:
        import jax
    except Exception:  # noqa: BLE001 - obs stays importable without jax
        return {}
    out: dict = {}
    try:
        arrs = jax.live_arrays()
        out["live_arrays"] = len(arrs)
        out["live_bytes"] = int(sum(
            int(getattr(a, "nbytes", 0) or 0) for a in arrs
        ))
    except Exception:  # noqa: BLE001 - backend-optional API
        pass
    try:
        stats = jax.devices()[0].memory_stats()
        if stats and stats.get("bytes_in_use") is not None:
            out["device_bytes_in_use"] = int(stats["bytes_in_use"])
    except Exception:  # noqa: BLE001 - cpu backends have no stats
        pass
    for k, v in out.items():
        core.gauge(f"devprof.{k}.{site}").set(v)
    return out


def arena_footprint(arena, site: str = "lanecache") -> dict:
    """Gauge one lane arena's host-side footprint (the numpy columns
    the wave marshal reuses across versions). Cheap: ``nbytes`` sums
    over the already-allocated columns, no copies."""
    if not core.enabled():
        return {}
    try:
        cols = ("ts", "site", "tx", "cause_idx", "vclass",
                "cause_hi", "cause_lo")
        nbytes = sum(
            int(getattr(getattr(arena, c), "nbytes", 0) or 0)
            for c in cols
        )
        out = {"arena_bytes": nbytes,
               "arena_lanes": int(arena.committed_n)}
    except Exception:  # noqa: BLE001 - telemetry must never raise
        return {}
    for k, v in out.items():
        core.gauge(f"devprof.{k}.{site}").set(v)
    return out
