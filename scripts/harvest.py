"""Round-5 TPU harvester: the WHOLE measurement ladder in ONE process.

Built when chip time came in rare short windows (round 3 saw one
~6-minute window), where a one-item-per-process queue could land only
a single bench number. This script runs every queued measurement — the v5 phase attribution (VERDICT #1),
the four streaming A/Bs, the fleet shapes, a v4 ladder point and a
bookend repeat of the headline — inside one process, one backend
claim, emitting ONE JSON line per result (flushed immediately) so even
a partial window yields committed evidence.

Design rules (from rounds 2-3):
- One process holds the chip.
- Every timed program reduces to a scalar and the harness forces the
  4-byte device->host fetch (the sync point).
- Trace-time kernel switches (CAUSE_TPU_SORT/GATHER/SEARCH/SCATTER)
  require
  ``jax.clear_caches()`` between configs or the A/B silently re-times
  the cached default program.

State: completed one-shot items are recorded in
``measurements/harvest_state_r5.json`` and skipped on later attempts;
the headline bench (``bench_v5``) is always re-measured — repetition
across windows is the point (VERDICT weak #1).

Round 5 adds an on-chip correctness gate (``verify_beststream``,
ADVICE.md #3): per-row avalanche digests of the full batch under the
pinned XLA baseline vs the beststream config. On MISMATCH it
attributes the culprit by re-digesting one switch at a time, and every
timing item whose config contains a suspect strategy is skipped for
the window (timing a wrong kernel is not evidence) — suspect skips
still count as attempted so the watcher can advance phases.

Usage: python -u scripts/harvest.py  [--smoke] [--allow-cpu]
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import argparse
import json
import os
import sys
import time

import numpy as np

T0 = time.monotonic()
# one id per harvest process = per window attempt; stamped into every
# result so decide_defaults can require same-window comparisons
RUN_ID = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()) + f"-{os.getpid()}"
STATE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "measurements", "harvest_state_r5.json",
)
# bump when an item NAME keeps its meaning but its config/kernel
# changes (round-5 review finding: stale done/results entries from an
# older definition must not certify a config that was never verified)
STATE_VERSION = 2

from cause_tpu import obs  # noqa: E402  (dependency-light, no jax)
from cause_tpu.switches import TRACE_SWITCHES as SWITCHES  # noqa: E402

# Every item pins the FULL switch set explicitly ("xla" = force the
# XLA-default lowering), so the ladder keeps measuring true baselines
# even after chip wins are flipped into switches.TPU_DEFAULTS —
# otherwise single-switch A/Bs would silently become winner-vs-winner
# (round-4 review finding). Module-level so the watcher derives its
# phase-2 env from here instead of restating it (drift trap).
XLA_BASE = {k: "xla" for k in SWITCHES}


def cfg_of(**over):
    out = dict(XLA_BASE)
    out.update(over)
    return out


def flips_of(cfg) -> dict:
    """The non-xla switch subset of a config — the one projection the
    certified-cfg plumbing (verify records, bench records,
    decide_defaults, certified_env) must agree on."""
    return {k: v for k, v in cfg.items() if v != "xla"}


def persisted_suspects(results) -> set:
    """Digest-gate culprits carried by certification records (the
    MATCH-REDUCED path stores the strategies its reduction dropped).
    Re-seeded at attempt start: a reduced certification puts
    verify_beststream in ``done``, so later windows run NO
    re-derivation — an unseeded gate would then time and permanently
    record the contradicted strategy (round-5 session-2 review
    finding). A later full MATCH overwrites the record and clears
    them; a MISMATCH pops the record, and the re-verify that follows
    re-derives suspects fresh."""
    out: set = set()
    for rec in results.values():
        if isinstance(rec, dict):
            out.update(rec.get("suspects", []))
    return out


ALLSTREAM = cfg_of(CAUSE_TPU_SORT="bitonic",
                   CAUSE_TPU_GATHER="rowgather",
                   CAUSE_TPU_SEARCH="matrix")
# The headline candidate CONFIG the watcher/bench ride when certified:
# XLA-ONLY streaming strategies. Round-5 window-1 evidence
# (measurements/harvest_tpu_r5.log): the remote compile helper of the
# time crashed on v5f and fphase, and PR 21's v5e compile rehearsal
# refuses the euler walk, the fused F-phase and befuse K4. Mosaic
# -flavored items therefore sit behind HARVEST_TRY_MOSAIC=1 below, and
# the certifiable beststream contains no Mosaic strategy.
from cause_tpu.switches import BESTSTREAM_FLIPS  # noqa: E402

BESTSTREAM = cfg_of(**BESTSTREAM_FLIPS)
# CAUSE_TPU_SORT=matrix (round-5 session 2): the blocked rank-count
# sort (weaver/matsort.py) — the pure-XLA replacement for the
# comparator sorts phase E's chip profile indicts, now that the
# Mosaic pallas sort is unmeasurable here. If its digest gate fails
# on chip, verify_beststream's reduced-set fallback re-certifies the
# combination without it (the certified cfg rides the state file).
# the aspirational full-Mosaic config (VMEM-resident pallas sort +
# fused F-phase), measurable only where the compile helper supports
# Mosaic — opt in with HARVEST_TRY_MOSAIC=1
MOSAICSTREAM = cfg_of(CAUSE_TPU_SORT="pallas",
                      CAUSE_TPU_GATHER="rowgather",
                      CAUSE_TPU_SEARCH="matrix-table",
                      CAUSE_TPU_SCATTER="hint",
                      CAUSE_TPU_FPHASE="pallas")
# strategy pairs that require a Mosaic kernel compile — a DENYLIST of
# specific values (flip strings), not a restated config: the ladder
# still builds every config from BESTSTREAM_FLIPS/cfg_of
MOSAIC_VALUES = {"CAUSE_TPU_SORT=pallas", "CAUSE_TPU_FPHASE=pallas",  # causelint: disable=TID002 -- denylist of Mosaic values, not a config copy
                 "euler=walk", "kernel=v5f"}
TRY_MOSAIC = os.environ.get("HARVEST_TRY_MOSAIC", "").strip() == "1"


def emit(**obj):
    obj["t"] = round(time.monotonic() - T0, 1)
    obj["utc"] = time.strftime("%H:%M:%S", time.gmtime())
    print(json.dumps(obj), flush=True)
    # every ladder decision doubles as a structured obs event (no-op
    # unless CAUSE_TPU_OBS/--obs-out is on): certify/revoke/skip lines
    # carry the cfg and digests that justified them, so a soak log
    # opens in Perfetto with full provenance instead of raw prints
    obs.event("harvest." + str(obj.get("ev", "emit")),
              **{k: v for k, v in obj.items()
                 if k not in ("ev", "t", "utc")})


def load_state() -> tuple:
    """(done item-name set, per-item results dict). Results accumulate
    across windows; a STATE_VERSION mismatch discards everything (the
    old entries certified item definitions that no longer exist)."""
    try:
        with open(STATE_PATH) as f:
            data = json.load(f)
        if data.get("version") != STATE_VERSION:
            return set(), {}
        done = set(data["done"])
        # shipped defaults must re-certify every window: once the
        # defaults file exists, verify_beststream is never "done"
        # (round-5 review finding: a certification must not outlive
        # its evidence — without this, a post-certification kernel
        # regression would ship wrong results forever)
        if os.path.exists(defaults_file_path()):
            done.discard("verify_beststream")
        results = dict(data.get("results", {}))
        # a certification that did not record WHICH cfg it checked
        # (records from code predating the cfg field) is not
        # actionable: the static BESTSTREAM may have gained strategies
        # since, and timing/shipping them under the old verdict would
        # be certification drift — force a re-verify instead
        if not (results.get("verify_beststream") or {}).get("cfg"):
            done.discard("verify_beststream")
        return done, results
    except Exception:  # noqa: BLE001 - missing/corrupt state = fresh
        return set(), {}


def save_state(done: set, results: dict) -> None:
    os.makedirs(os.path.dirname(STATE_PATH), exist_ok=True)
    with open(STATE_PATH, "w") as f:
        json.dump({"version": STATE_VERSION, "done": sorted(done),
                   "results": results}, f)


def set_config(cfg: dict) -> None:
    """Flip the trace-time kernel switches and drop every cached traced
    program (module-level jit caches key on avals only — see bench.py's
    allstream note). No-op when the switches already match — most
    ladder transitions are default->default, and a needless
    clear_caches would recompile identical programs mid-window."""
    import jax

    current = {k: os.environ[k] for k in SWITCHES if k in os.environ}
    if current == cfg:
        return
    for k in SWITCHES:
        os.environ.pop(k, None)
    os.environ.update(cfg)
    jax.clear_caches()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run the ladder on the CPU backend (rehearsal)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--obs-out", default="",
                    help="stream structured obs events (JSONL) to this"
                         " path — future soak logs over raw prints")
    a = ap.parse_args()
    if a.obs_out:
        obs.configure(enabled=True, out=a.obs_out)

    # defend against stale switches inherited from a caller's env: every
    # measurement here names its config explicitly
    for k in SWITCHES:
        os.environ.pop(k, None)

    # ---- host marshal first: ~60-90 s of pure numpy
    from cause_tpu import benchgen
    from cause_tpu.benchgen import (
        LANE_KEYS4,
        LANE_KEYS5,
        enable_compile_cache,
        merge_wave_scalar,
    )

    if a.smoke:
        B, NB, ND, CAP = 8, 800, 100, 1024
    else:
        B, NB, ND, CAP = 1024, 9_000, 1_000, 10_240

    t0 = time.monotonic()
    batch = benchgen.batched_pair_lanes(
        n_replicas=B, n_base=NB, n_div=ND, capacity=CAP, hide_every=8
    )
    v5batch = benchgen.batched_v5_inputs(batch, CAP)
    u_budget = benchgen.v5_token_budget(v5batch)
    budget = benchgen.pair_run_budget(batch)
    emit(ev="marshal", ms=round((time.monotonic() - t0) * 1000, 1))

    # delta-native arms (PR 7): window-only marshals
    # for the low/high bench_delta timing items (same document shape,
    # divergence-sized windows) plus a B=64 full+window subset for the
    # verify_delta digest gate — small enough that the gate's full
    # -kernel control compiles in a fraction of the headline compile
    t0 = time.monotonic()
    ND_LOW = max(1, min(25, ND))
    dsw_low = benchgen.delta_sweep_inputs(
        B, NB + ND - ND_LOW, ND_LOW, CAP, hide_every=8,
        include_full=False)
    dsw_high = benchgen.delta_sweep_inputs(
        B, NB, ND, CAP, hide_every=8, include_full=False)
    dsw_verify = benchgen.delta_sweep_inputs(
        min(64, B), NB, ND, CAP, hide_every=8)
    emit(ev="marshal_delta",
         ms=round((time.monotonic() - t0) * 1000, 1),
         wcap_low=dsw_low["wcap"], wcap_high=dsw_high["wcap"])

    # merge-tree fleet (PR 8): REAL replica handles (the flat-fold
    # baseline must materialize through them) built jax-free —
    # tree_fleet_handles weaves the shared base with the PURE host
    # weaver. ~10 s of host Python, so a resumed run whose tree items
    # are already done skips it (the lazy fallback below covers any
    # state drift).
    if a.smoke:
        TREE_N, TREE_NB, TREE_ND = 8, 400, 6
    else:
        TREE_N, TREE_NB, TREE_ND = 64, 10_000, 24
    _tree_fleet_cache: list = []

    def tree_fleet():
        if not _tree_fleet_cache:
            t0 = time.monotonic()
            _tree_fleet_cache.append(benchgen.tree_fleet_handles(
                TREE_N, TREE_NB, TREE_ND, hide_every=8))
            emit(ev="marshal_tree",
                 ms=round((time.monotonic() - t0) * 1000, 1),
                 replicas=TREE_N, doc=1 + TREE_NB + TREE_ND)
        return _tree_fleet_cache[0]

    _done_preview, _ = load_state()
    if not {"verify_tree", "bench_tree"} <= _done_preview:
        tree_fleet()

    import jax
    import jax.numpy as jnp

    enable_compile_cache()
    # ---- backend confirm ----
    plat = jax.devices()[0].platform
    obs.set_platform(plat)
    emit(ev="backend", platform=plat)
    if plat == "cpu" and not a.allow_cpu:
        emit(ev="abort", reason="cpu backend without --allow-cpu")
        sys.exit(2)
    np.asarray(jax.jit(lambda x: x + 1)(jnp.ones(8)))
    emit(ev="alive", platform=plat)

    done, results = load_state()
    reps = a.reps
    # a CPU rehearsal or a smoke-shape run must not mark ladder items
    # done: the state file gates what a real full-size window measures
    record_state = plat != "cpu" and not a.smoke

    # ---- one upload serving every full-size item --------------------
    t0 = time.monotonic()
    dev = {k: jax.device_put(batch[k])
           for k in dict.fromkeys(LANE_KEYS4)}
    for k in LANE_KEYS5:
        if k not in dev:
            dev[k] = jax.device_put(v5batch[k])
    for v in dev.values():
        v.block_until_ready()  # best effort; the sync below is real
    np.asarray(jnp.sum(dev["hi"][0, :8]))  # real sync: upload done
    emit(ev="upload", ms=round((time.monotonic() - t0) * 1000, 1),
         u_budget=int(u_budget), run_budget=int(budget))

    class _Overflow(RuntimeError):
        pass

    # budgets validated against the overflow flag by a completed
    # bench_item at this shape (overflow is data-dependent only — the
    # trace-time switches never change token/run counts — so one
    # validation per kernel family covers every config)
    validated_k: dict = {}
    # strategies that failed the on-chip digest gate this attempt,
    # keyed as "SWITCH=value" pairs ("euler=walk" for the v5w/v4w
    # kernels) — bare values would collide ("pallas" names both the
    # sort and the fphase strategy) and wrongly quarantine the other;
    # items whose config uses a suspect pair are skipped-as-attempted
    suspect_values: set = persisted_suspects(results)
    skipped_suspect: set = set()

    def effective_values(kernel, cfg) -> set:
        """The strategy pairs an item actually runs with: the explicit
        cfg, plus — for switches the cfg leaves unset (shipped-default
        items use cfg={}) — the backend defaults switches.resolve()
        would apply on TPU. Without the union, the headline/fleet items
        would bypass the suspect gate the moment a win is promoted
        into TPU_DEFAULTS."""
        from cause_tpu.switches import TPU_DEFAULTS

        vals = set()
        for k_ in SWITCHES:
            v = cfg.get(k_, "")
            if not v and plat == "tpu":
                v = TPU_DEFAULTS.get(k_, "")
            if v and v != "xla":
                vals.add(f"{k_}={v}")
        if kernel in ("v5w", "v4w"):
            vals.add("euler=walk")
        if kernel == "v5f":
            vals.add("kernel=v5f")
        return vals

    def suspect_gate(name, kernel, cfg) -> bool:
        """True (and emits the skip) when the item's effective config
        contains a strategy the digest gate flagged this attempt."""
        bad = effective_values(kernel, cfg) & suspect_values
        if bad:
            emit(ev="skip", item=name,
                 reason=f"config uses digest-mismatching strategies "
                        f"{sorted(bad)}; not timing a wrong kernel")
            skipped_suspect.add(name)
            return True
        return False

    def mosaic_gate(name, kernel, cfg) -> bool:
        """True (and emits the skip) for items needing a Mosaic kernel
        compile, unless HARVEST_TRY_MOSAIC=1. Round-5 window-1
        evidence: the remote compile helper of the time crashed (HTTP
        500) or hung on Mosaic programs, and PR 21's v5e compile
        rehearsal refuses the euler walk, fused F-phase and befuse K4.
        Gated items count as attempted so the watcher advances."""
        if TRY_MOSAIC:
            return False
        need = effective_values(kernel, cfg) & MOSAIC_VALUES
        if need:
            emit(ev="skip", item=name,
                 reason=f"needs Mosaic compile {sorted(need)}; the "
                        "v5e compiler refuses some Mosaic kernels "
                        "(set HARVEST_TRY_MOSAIC=1 to retry)")
            skipped_suspect.add(name)
            return True
        return False

    def dispatch(kernel, k):
        lanes = (LANE_KEYS5 if kernel in ("v5", "v5w", "v5f")
                 else LANE_KEYS4)
        args = [dev[name] for name in lanes]
        return merge_wave_scalar(
            *args, k_max=k, kernel=kernel,
            u_max=k if kernel in ("v5", "v5w", "v5f") else 0,
        )

    def bench_item(name, kernel, cfg, burst_n=8, record=True):
        """bench.py-methodology measurement of one kernel+config:
        single-dispatch p50 and amortized-burst p50, reps each."""
        if mosaic_gate(name, kernel, cfg) or suspect_gate(
                name, kernel, cfg):
            return
        set_config(cfg)
        k = u_budget if kernel in ("v5", "v5w", "v5f") else budget
        try:
            for _ in range(3):  # compile + warm + overflow ladder
                out = np.asarray(dispatch(kernel, k))
                if out[1]:
                    emit(ev="overflow", item=name, k=int(k))
                    k *= 2
                    continue
                break
            else:
                raise _Overflow(name)
            singles, bursts = [], []
            for _ in range(reps):
                if obs.enabled():
                    # each timed single is one wave: land its
                    # wave.cost record (dispatch accounting + the
                    # generator's KNOWN divergence of 2*ND suffix ops
                    # per pair) so harvest sidecars feed the gap
                    # report's cost-vs-divergence join
                    from cause_tpu.obs import costmodel as _cm

                    _cm.wave_begin("harvest")
                t0 = time.perf_counter()
                np.asarray(dispatch(kernel, k))
                singles.append((time.perf_counter() - t0) * 1000)
                if obs.enabled():
                    from cause_tpu.obs import costmodel as _cm

                    v5_family = kernel in ("v5", "v5w", "v5f")
                    _cm.wave_cost(
                        uuid=f"harvest:{name}", pairs=B,
                        lanes=2 * CAP * B,
                        tokens=k * B if v5_family else None,
                        token_budget=k * B if v5_family else 0,
                        delta_ops=2 * ND * B, path="full")
            # bench.py's adaptive-burst rule (chip time, and the
            # window-2 lesson — a slow kernel's 3 bursts are ~90 s of
            # window for nothing): when single > 1 s the ~64-70 ms
            # dispatch floor is noise, amortized ~= single, and one
            # burst suffices
            burst_reps = (reps if float(np.median(singles)) < 1000.0
                          else 1)
            for _ in range(burst_reps):
                t0 = time.perf_counter()
                o = None
                for _ in range(burst_n):
                    o = dispatch(kernel, k)
                np.asarray(o)
                bursts.append((time.perf_counter() - t0) * 1000 / burst_n)
            label = "+".join(
                f"{k_.split('_')[-1].lower()}={v}"
                for k_, v in sorted(cfg.items()) if v != "xla")
            rec = dict(
                item=name, kernel=kernel,
                config=label or ("xla-baseline" if cfg
                                 else "shipped-default"),
                # the non-xla switch dict, verbatim: decide_defaults
                # flips exactly what was timed, not a constant that
                # may have drifted (reduced-certification support)
                cfg=flips_of(cfg),
                p50_single_ms=round(float(np.median(singles)), 1),
                p50_amortized_ms=round(float(np.median(bursts)), 1),
                singles_ms=[round(x, 1) for x in singles],
                bursts_ms=[round(x, 1) for x in bursts],
                k_max=int(k), platform=plat, shape=f"{B}x{1+NB+ND}",
                run=RUN_ID)
            emit(ev="result", **rec)
            validated_k[kernel] = k
            if record_state:
                # results persist for decide_defaults even for the
                # always-re-measured headline items (latest wins)
                results[name] = rec
                if record:
                    done.add(name)
                save_state(done, results)
        except _Overflow:
            emit(ev="error", item=name, error="overflow at max budget")
        finally:
            set_config({})

    def xla_base_item(name):
        """The A/B anchor. With TPU_DEFAULTS empty, the shipped
        default traces the IDENTICAL program as the pinned XLA base
        (env unset resolves to "" — window 1 measured them equal to
        0.1 ms), so the headline's fresh result is copied instead of
        re-timing the same compiled program for ~2.5 min of window.
        With defaults flipped, the baseline is a different program and
        measures normally."""
        from cause_tpu.switches import TPU_DEFAULTS

        head = results.get("bench_v5", {})
        if not TPU_DEFAULTS and head.get("run") == RUN_ID:
            rec = dict(head, item=name, config="xla-baseline",
                       note="defaults empty: shipped default IS the "
                            "xla baseline; copied from bench_v5 "
                            "(same compiled program)")
            emit(ev="result", **rec)
            if record_state:
                results[name] = rec
                save_state(done, results)
            return
        bench_item(name, "v5", XLA_BASE, 8, False)

    def beststream_bench_item(name):
        """Time the config the digest gate actually certified — the
        full BESTSTREAM on MATCH, or the reduced combination on
        MATCH-REDUCED (the state file carries it across windows). A
        decide_defaults flip then ships exactly the timed cfg."""
        stored = (results.get("verify_beststream") or {}).get("cfg")
        cfg = cfg_of(**stored) if stored else dict(BESTSTREAM)
        bench_item(name, "v5", cfg, 8, False)

    def verify_item(name, cfg_a, kernel_b, cfg_b):
        """On-chip correctness gate (round-4 advisor finding): the
        streaming strategies and the Mosaic-compiled pallas kernels are
        parity-validated only in interpret/CPU mode — a wrong scatter
        hint or Mosaic lowering on real TPU would produce silently
        wrong results that the timing ladder would happily measure.
        Before any config A/B is trusted, compare the v5 family's
        scalar — which IS an exact order-independent avalanche digest
        of (rank, visibility, lane, conflict) per benchgen
        .merge_wave_scalar (a plain linear weighted sum was observed
        cancelling compensating errors into collisions) — of the FULL
        batch under the pinned XLA-baseline ``cfg_a`` (NOT the shipped
        default, which becomes suspect-vs-suspect the moment a win
        lands in switches.TPU_DEFAULTS) against ``cfg_b``. Riding the
        SAME compiled program as the timing items is the round-5
        chip-time fix: the previous separate per-row digest
        program cost two fresh compiles and ate two whole windows
        mid-compile; now the baseline digest is a dispatch of an
        already-compiled program and the candidate digest shares its
        compile with the candidate's own bench item. Requires a
        bench-validated v5 budget (truncated programs clamp
        identically and would certify a false MATCH); done only on
        MATCH with zero overflow on both sides."""
        if mosaic_gate(name, kernel_b, cfg_b):
            return
        if "v5" not in validated_k:
            emit(ev="error", item=name,
                 error="no bench-validated v5 budget this attempt; "
                       "skipping verify rather than digest a possibly "
                       "truncated program")
            return
        k = validated_k["v5"]

        def digests(kernel, cfg):
            set_config(cfg)
            out = np.asarray(dispatch(kernel, k))
            return int(out[0]), int(out[1])

        try:
            da, ova = digests("v5", cfg_a)
            db, ovb = digests(kernel_b, cfg_b)
            ok = da == db and ova == 0 and ovb == 0
            emit(ev="result", item=name,
                 digest_a=da, digest_b=db,
                 overflow_a=int(ova), overflow_b=int(ovb),
                 platform=plat,
                 verdict="MATCH" if ok else "MISMATCH")
            if ok:
                if record_state:
                    # the certified cfg rides the state so the timing
                    # item, decide_defaults and the watcher's phase-2
                    # env all run EXACTLY what the digest gate checked;
                    # the matched digest rides along so the provenance
                    # of every later certify/ship decision is auditable
                    results[name] = dict(
                        item=name, verdict="MATCH",
                        cfg=flips_of(cfg_b), digest=int(da),
                        run=RUN_ID, platform=plat)
                    done.add(name)
                    save_state(done, results)
                return
            # a MISMATCH revokes any certification record a previous
            # window left: certified_env()/the watcher/phase-2 must
            # never keep shipping a cfg the digest gate just
            # contradicted (same rule as decide_defaults' revocation
            # of the defaults file). A reduced re-certification below
            # writes a fresh record.
            if record_state and results.pop(name, None) is not None:
                save_state(done, results)
            # attribute the culprit: one switch (or the euler walk)
            # at a time against the same baseline digests. Snapshot
            # the suspect set first — with two verify items in the
            # ladder, suspects left by an earlier one must not
            # suppress THIS item's combination-only fallback.
            pre_suspects = set(suspect_values)
            singles = [("v5", dict(cfg_a, **{k_: v}), f"{k_}={v}")
                       for k_, v in cfg_b.items() if v != "xla"]
            if kernel_b in ("v5w", "v4w"):
                singles.append(("v5w", dict(cfg_a), "euler=walk"))
            if kernel_b == "v5f":
                singles.append(("v5f", dict(cfg_a), "kernel=v5f"))
            for kern, cfg1, val in singles:
                d1, ov1 = digests(kern, cfg1)
                m1 = int(da != d1)
                if m1 or ov1 != ova:
                    suspect_values.add(val)
                emit(ev="verify_attr", item=name, strategy=val,
                     mismatch=m1, overflow=int(ov1),
                     platform=plat)
            if not (suspect_values - pre_suspects):
                # combination-only defect: no single strategy
                # reproduces it, so every strategy in the failing
                # config is suspect — better to skip them all than to
                # time and permanently record a known-wrong config
                suspect_values.update(
                    f"{k_}={v}" for k_, v in cfg_b.items()
                    if v != "xla")
                if kernel_b in ("v5w", "v4w"):
                    suspect_values.add("euler=walk")
                if kernel_b == "v5f":
                    suspect_values.add("kernel=v5f")
                emit(ev="verify_attr", item=name,
                     strategy="combination-only",
                     note="no single culprit; all strategies of the "
                          "failing config marked suspect")
            elif name == "verify_beststream" and kernel_b == "v5":
                # reduced-set fallback: one bad strategy must not cost
                # the window its certification — re-gate the
                # combination minus the attributed culprits and
                # certify THAT (the reduced cfg rides the state file
                # to bench_beststream / decide_defaults / the watcher)
                reduced = {
                    k_: ("xla" if f"{k_}={v}" in suspect_values else v)
                    for k_, v in cfg_b.items()
                }
                if (reduced != cfg_b
                        and any(v != "xla" for v in reduced.values())):
                    dr, ovr = digests("v5", reduced)
                    okr = da == dr and ova == 0 and ovr == 0
                    emit(ev="result", item=name,
                         digest_a=da, digest_b=dr,
                         overflow_a=int(ova), overflow_b=int(ovr),
                         platform=plat,
                         verdict=("MATCH-REDUCED" if okr
                                  else "MISMATCH-REDUCED"),
                         cfg=flips_of(reduced))
                    if okr and record_state:
                        results[name] = dict(
                            item=name, verdict="MATCH-REDUCED",
                            cfg=flips_of(reduced), digest=int(dr),
                            # the strategies the reduction dropped,
                            # persisted so later windows re-seed the
                            # suspect gate (see persisted_suspects)
                            suspects=sorted(
                                set(f"{k_}={v}" for k_, v
                                    in flips_of(cfg_b).items())
                                & suspect_values),
                            run=RUN_ID, platform=plat)
                        done.add(name)
                        save_state(done, results)
            emit(ev="suspects", item=name,
                 suspects=sorted(suspect_values))
        finally:
            set_config({})

    def micro_item(name):
        """Primitive-strategy A/Bs at exact kernel shapes (shares this
        process's backend; scripts/tpu_microbench.py cases)."""
        if a.smoke:
            emit(ev="skip", item=name,
                 reason="microbench cases are full-size only")
            return
        import tpu_microbench as mb

        ok = True
        mosaic_skipped = False
        for case in mb.TOK_CASES:
            if not TRY_MOSAIC and case == "tokpallas":
                emit(ev="skip", item=name, case=case,
                     reason="Mosaic compile; see mosaic_gate")
                mosaic_skipped = True
                continue
            try:
                per_op, once = mb.ALL[case]()
                emit(ev="micro", item=name, case=case,
                     per_op_ms=round(per_op, 2),
                     single_dispatch_ms=round(once, 1), platform=plat)
            except Exception as e:  # noqa: BLE001 - keep measuring
                ok = False
                emit(ev="error", item=name, case=case,
                     error=f"{type(e).__name__}: {str(e)[:200]}")
        if ok and record_state:
            if mosaic_skipped:
                # attempted for THIS window's completeness, but not
                # done: a later HARVEST_TRY_MOSAIC=1 window must still
                # be able to measure the gated case
                skipped_suspect.add(name)
            else:
                done.add(name)
                save_state(done, results)

    def fleet_item(name, K, nb, nd, cap):
        from cause_tpu.weaver.jaxw5 import merge_weave_kernel_v5_jit

        try:
            lanes = benchgen.fleet_lanes(
                n_replicas=K, n_base=nb, n_div=nd, capacity=cap,
                hide_every=8,
            )
            t0 = time.monotonic()
            v5row = benchgen.v5_inputs(lanes, cap)
            marshal_ms = (time.monotonic() - t0) * 1000
            fargs = [jax.device_put(jnp.asarray(v5row[k]))
                     for k in LANE_KEYS5]
            k = benchgen.v5_token_budget(v5row)

            def step(kk):
                rank, vis, c, ovf = merge_weave_kernel_v5_jit(
                    *fargs, u_max=kk, k_max=kk
                )
                out = np.asarray(
                    jnp.stack([jnp.sum(rank.astype(jnp.float32)),
                               ovf.astype(jnp.float32)])
                )
                if out[1]:
                    raise _Overflow(kk)
                return out

            for _ in range(3):
                try:
                    step(k)
                    break
                except _Overflow:
                    emit(ev="overflow", item=name, k=int(k))
                    k *= 2
            else:
                raise RuntimeError("overflow at max fleet budget")
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                step(k)
                ts.append((time.perf_counter() - t0) * 1000)
            rec = dict(item=name,
                       metric=f"fleet v5 {K}x{1+nb+nd} -> one tree",
                       p50_ms=round(float(np.median(ts)), 1),
                       reps_ms=[round(x, 1) for x in ts],
                       lanes=K * cap, u_max=int(k),
                       marshal_ms=round(marshal_ms, 1), platform=plat,
                       run=RUN_ID)
            emit(ev="result", **rec)
            if record_state:
                results[name] = rec
                done.add(name)
                save_state(done, results)
        except Exception as e:  # noqa: BLE001 - keep harvesting
            emit(ev="error", item=name,
                 error=f"{type(e).__name__}: {str(e)[:200]}")

    def verify_delta_item(name):
        """On-chip digest gate for the delta-native weave: per-row
        convergence digests of the full v5 kernel vs the delta window
        program (prefix digest + window weave) on a B=64 subset of the
        headline shape restricted to the delta domain. MATCH means
        bit-identical uint32 digests on every row — the same gate the
        CPU equality suite pins, re-proven on the chip's own lowering.
        Deliberately NOT part of BESTSTREAM: the delta path ships into
        defaults only after this gate has certified it on hardware."""
        from cause_tpu.weaver import jaxwd
        from cause_tpu.weaver.arrays import next_pow2

        full_args = [jax.device_put(jnp.asarray(dsw_verify["full"][k]))
                     for k in LANE_KEYS5]
        u = next_pow2(benchgen.v5_token_budget(dsw_verify["full"]))
        _r, _v, dig_full, ovf = jaxwd.batched_weave_digest(
            *full_args, u_max=int(u), k_max=int(u))
        dig_full = np.asarray(dig_full)
        ov_full = int(np.asarray(ovf).sum())
        nw = 2 * dsw_verify["wcap"]
        win_args = [jax.device_put(jnp.asarray(dsw_verify["window"][k]))
                    for k in LANE_KEYS5]
        _rw, _vw, dig_d, ovw = jaxwd.batched_delta_weave(
            *win_args, jax.device_put(dsw_verify["prefix_digest"]),
            jax.device_put(dsw_verify["r0"]),
            u_max=int(nw), k_max=int(nw))
        dig_d = np.asarray(dig_d)
        ov_d = int(np.asarray(ovw).sum())
        ok = (ov_full == 0 and ov_d == 0
              and bool(np.array_equal(dig_full, dig_d)))
        rec = dict(item=name, verdict="MATCH" if ok else "MISMATCH",
                   rows=int(dig_full.shape[0]),
                   overflow_full=ov_full, overflow_delta=ov_d,
                   wcap=dsw_verify["wcap"],
                   # the gate runs on its own row subset — the shape
                   # label must say so, not claim the headline batch
                   shape=f"{int(dig_full.shape[0])}x{1+NB+ND}",
                   platform=plat, run=RUN_ID)
        emit(ev="result", **rec)
        if record_state:
            results[name] = rec
            if ok:
                done.add(name)
            save_state(done, results)

    def delta_bench_item(name, dsw, n_div_side):
        """bench.py-methodology timing of the delta-native wave
        program — window weave + incremental digest + resident splice
        — at the headline batch size and document shape, with the
        window sized to the item's divergence. Residents are device
        -allocated placeholders (the splice's cost is content
        -independent); correctness is verify_delta's gate, this item
        is the wall-clock arm of the one-claim A/B vs bench_v5."""
        from cause_tpu.weaver import jaxwd

        nw = 2 * dsw["wcap"]
        win = [jax.device_put(jnp.asarray(dsw["window"][k]))
               for k in LANE_KEYS5]
        pd = jax.device_put(dsw["prefix_digest"])
        r0v = jax.device_put(dsw["r0"])
        st = jax.device_put(dsw["starts"])
        ct = jax.device_put(dsw["counts"])
        res = [jnp.zeros((B, 2 * CAP), jnp.int32),
               jnp.zeros((B, 2 * CAP), bool)]

        def dispatch():
            rw, vw, dig, _ovf = jaxwd.batched_delta_weave(
                *win, pd, r0v, u_max=int(nw), k_max=int(nw))
            res[0], res[1] = jaxwd.splice_ranks(
                res[0], res[1], rw, vw, st, ct, r0v)
            if obs.enabled():
                from cause_tpu.obs import costmodel as _cm

                _cm.record_dispatch(f"harvest:delta:w{dsw['wcap']}",
                                    site="harvest")
                _cm.record_dispatch("harvest:delta_splice",
                                    site="harvest")
            # sync value depends on BOTH programs: fetching the digest
            # alone would let the O(doc) splice run past the timer
            return jnp.concatenate(
                [dig, res[0][:, 0].astype(jnp.uint32)])

        np.asarray(dispatch())  # compile + warm

        def _begin():
            if obs.enabled():
                from cause_tpu.obs import costmodel as _cm

                _cm.wave_begin("harvest")

        def _end():
            if obs.enabled():
                from cause_tpu.obs import costmodel as _cm

                _cm.wave_cost(
                    uuid=f"harvest:{name}", pairs=B,
                    lanes=2 * CAP * B,
                    tokens=2 * (n_div_side + 1) * B,
                    token_budget=int(nw) * B,
                    delta_ops=2 * n_div_side * B, path="delta")

        singles, bursts = benchgen.time_dispatch(
            dispatch, reps, 8, begin=_begin, end=_end)
        rec = dict(
            item=name, kernel="v5d", config="delta-native",
            cfg={},
            p50_single_ms=round(float(np.median(singles)), 2),
            p50_amortized_ms=round(float(np.median(bursts)), 2),
            singles_ms=[round(x, 2) for x in singles],
            bursts_ms=[round(x, 2) for x in bursts],
            k_max=int(nw), wcap=dsw["wcap"],
            divergence_ops=2 * n_div_side,
            platform=plat, shape=f"{B}x{1+NB+ND}", run=RUN_ID)
        emit(ev="result", **rec)
        if record_state:
            results[name] = rec
            done.add(name)
            save_state(done, results)

    def verify_tree_item(name):
        """Bit-identity gate for the merge reduction tree (PR 8): the
        TREE_N-replica fleet converged through ``parallel.tree``
        (ceil(log2(n)) batched device rounds) must equal the flat
        sequential pairwise fold bit-for-bit — weave AND node store —
        with the round count the tree promises. Both arms run on this
        chip's own lowering; the wall times ride the record so
        bench_tree can reuse the fold arm instead of paying the n-1
        sequential waves a second time (window economy)."""
        from cause_tpu.parallel import tree as tree_mod

        fleet = tree_fleet()
        t0 = time.perf_counter()
        root, rep = tree_mod.merge_tree_report(fleet)
        tree_ms = (time.perf_counter() - t0) * 1000
        t1 = time.perf_counter()
        fold = tree_mod.flat_fold(fleet)
        fold_ms = (time.perf_counter() - t1) * 1000
        rounds_expected = tree_mod.tree_rounds(len(fleet))
        rounds_ok = len(rep["levels"]) == rounds_expected
        ok = (rounds_ok and root.ct.weave == fold.ct.weave
              and root.ct.nodes == fold.ct.nodes)
        rec = dict(item=name, verdict="MATCH" if ok else "MISMATCH",
                   replicas=len(fleet),
                   rounds=len(rep["levels"]),
                   rounds_expected=rounds_expected,
                   paths=[lv["path"] for lv in rep["levels"]],
                   tree_ms=round(tree_ms, 1), fold_ms=round(fold_ms, 1),
                   shape=f"{TREE_N}x{1 + TREE_NB + TREE_ND}",
                   platform=plat, run=RUN_ID)
        emit(ev="result", **rec)
        # in-memory always (bench_tree reads the same-window verdict
        # even on CPU/smoke rehearsals); persisted only for real
        # full-size windows like every other item
        results[name] = rec
        if record_state:
            if ok:
                done.add(name)
            save_state(done, results)

    def bench_tree_item(name):
        """bench.py-methodology timing of merge-tree fleet convergence
        vs the flat fold. The tree arm re-measures (reps); the fold arm
        — n-1 SEQUENTIAL full-width waves, minutes of window — reuses
        verify_tree's same-window measurement when one exists and runs
        once otherwise."""
        from cause_tpu.parallel import tree as tree_mod

        vrec = results.get("verify_tree") or {}
        if vrec.get("verdict") != "MATCH":
            emit(ev="skip", item=name,
                 reason="no MATCH verify_tree on record; not timing an "
                        "unverified reduction")
            return
        fleet = tree_fleet()
        singles = []
        for _ in range(reps):
            t0 = time.perf_counter()
            tree_mod.merge_tree(fleet)
            singles.append((time.perf_counter() - t0) * 1000)
        if vrec.get("run") == RUN_ID and vrec.get("fold_ms"):
            fold_ms = float(vrec["fold_ms"])
            fold_src = "verify_tree (same window)"
        else:
            t0 = time.perf_counter()
            tree_mod.flat_fold(fleet)
            fold_ms = (time.perf_counter() - t0) * 1000
            fold_src = "measured"
        p50 = float(np.median(singles))
        rec = dict(item=name, kernel="v5t", config="merge-tree",
                   cfg={},
                   p50_tree_ms=round(p50, 1),
                   singles_ms=[round(x, 1) for x in singles],
                   fold_ms=round(fold_ms, 1), fold_source=fold_src,
                   tree_over_fold=round(p50 / max(fold_ms, 1e-9), 4),
                   rounds=vrec.get("rounds"),
                   replicas=len(fleet), platform=plat,
                   shape=f"{TREE_N}x{1 + TREE_NB + TREE_ND}",
                   run=RUN_ID)
        emit(ev="result", **rec)
        if record_state:
            results[name] = rec
            done.add(name)
            save_state(done, results)

    # ---- the ladder, highest information value per second first -----
    # Round-5 order after window 1: the XLA-only streaming family is
    # the only measurable candidate (Mosaic kernels are refused —
    # see mosaic_gate), so its digest
    # gate + timing lead, then the baseline, then the single-switch
    # attribution A/Bs. The Mosaic items stay listed (gated) so a
    # repaired kernel is measured via
    # HARVEST_TRY_MOSAIC=1 without a code change.
    ladder: list[tuple[str, object, tuple]] = [
        ("bench_v5", bench_item, ("bench_v5", "v5", {}, 8, False)),
        # re-derived EVERY window so decide_defaults always has a
        # same-window (same run id) anchor — a cross-window 2% margin
        # would certify day-to-day load drift (round-5 review finding)
        ("bench_xla_base", xla_base_item, ("bench_xla_base",)),
        ("verify_beststream", verify_item,
         ("verify_beststream", XLA_BASE, "v5", BESTSTREAM)),
        # record=False like the baseline: the candidate must re
        # -measure in the same window as its anchor or the same-run
        # rule could never (re-)certify after window 1
        ("bench_beststream", beststream_bench_item,
         ("bench_beststream",)),
        # delta-native weave (PR 7): the digest gate plus low/high
        # -divergence timing arms, so the FIRST window A/Bs
        # delta-native vs full weave (bench_v5 above) in one claim.
        # Not in BESTSTREAM: the delta path only ships as a default
        # once verify_delta has certified it on hardware.
        ("verify_delta", verify_delta_item, ("verify_delta",)),
        ("bench_delta_high", delta_bench_item,
         ("bench_delta_high", dsw_high, ND)),
        ("bench_delta_low", delta_bench_item,
         ("bench_delta_low", dsw_low, ND_LOW)),
        # merge reduction tree (PR 8), right after the delta items so
        # the FIRST chip run certifies the still-pending delta
        # weave AND the O(log n) tree in one claim: the bit-identity
        # gate (tree vs flat fold at B=64), then the timing A/B
        ("verify_tree", verify_tree_item, ("verify_tree",)),
        ("bench_tree", bench_tree_item, ("bench_tree",)),
        ("bench_rowgather", bench_item,
         ("bench_rowgather", "v5", cfg_of(CAUSE_TPU_GATHER="rowgather"))),
        ("bench_matrix", bench_item,
         ("bench_matrix", "v5", cfg_of(CAUSE_TPU_SEARCH="matrix"))),
        ("bench_mtable", bench_item,
         ("bench_mtable", "v5",
          cfg_of(CAUSE_TPU_SEARCH="matrix-table"))),
        ("bench_schint", bench_item,
         ("bench_schint", "v5", cfg_of(CAUSE_TPU_SCATTER="hint"))),
        ("bench_sortmatrix", bench_item,
         ("bench_sortmatrix", "v5", cfg_of(CAUSE_TPU_SORT="matrix"))),
        ("bench_allstream", bench_item,
         ("bench_allstream", "v5", ALLSTREAM)),
        ("bench_bitonic", bench_item,
         ("bench_bitonic", "v5", cfg_of(CAUSE_TPU_SORT="bitonic"))),
        ("microbench", micro_item, ("microbench",)),
        ("fleet64", fleet_item, ("fleet64", 64, 2_000, 200, 2_560)),
        ("fleet256", fleet_item, ("fleet256", 256, 500, 64, 1_024)),
        ("bench_v4", bench_item, ("bench_v4", "v4", XLA_BASE)),
        # Mosaic-needing items (all skip-as-attempted unless
        # HARVEST_TRY_MOSAIC=1; see module comment)
        ("verify_v5f", verify_item,
         ("verify_v5f", XLA_BASE, "v5f", MOSAICSTREAM)),
        ("bench_v5f", bench_item,
         ("bench_v5f", "v5f", MOSAICSTREAM)),
        ("bench_v5f_xla", bench_item,
         ("bench_v5f_xla", "v5f", XLA_BASE)),
        ("verify_mosaicstream", verify_item,
         ("verify_mosaicstream", XLA_BASE, "v5w", MOSAICSTREAM)),
        ("bench_mosaicstream", bench_item,
         ("bench_mosaicstream", "v5w", MOSAICSTREAM)),
        ("bench_psort", bench_item,
         ("bench_psort", "v5", cfg_of(CAUSE_TPU_SORT="pallas"))),
        ("bench_v5w", bench_item, ("bench_v5w", "v5w", XLA_BASE)),
        ("bench_fphase", bench_item,
         ("bench_fphase", "v5", cfg_of(CAUSE_TPU_FPHASE="pallas"))),
        # bookend repeat of the headline (cross-window repetition)
        ("bench_v5_bookend", bench_item,
         ("bench_v5_bookend", "v5", {}, 8, False)),
    ]

    for name, fn, args in ladder:
        if name in done:
            emit(ev="skip", item=name)
            continue
        emit(ev="start", item=name)
        # wedge-triage heartbeat (PR 10): one record at every ladder
        # -item boundary, so `obs watch` over the harvest sidecar can
        # tell WHICH item a wedged round died inside (and how long it
        # had been running) without ssh archaeology — `obs watch`
        # reads exactly these
        obs.event("run.heartbeat", item=name, stage="start",
                  elapsed=round(time.monotonic() - T0, 1))
        try:
            with obs.span("harvest.item", item=name):
                fn(*args)
            obs.event("run.heartbeat", item=name, stage="done",
                      elapsed=round(time.monotonic() - T0, 1))
        except Exception as e:  # noqa: BLE001 - emit + try next item
            obs.event("run.heartbeat", item=name, stage="error",
                      elapsed=round(time.monotonic() - T0, 1))
            emit(ev="error", item=name,
                 error=f"{type(e).__name__}: {str(e)[:300]}")

    # suspect skips count as attempted (re-measuring a digest
    # -mismatching config in a later window yields the same skip; the
    # watcher must be able to advance to phases 2-3); verify itself
    # also counts as attempted on MISMATCH — it re-runs next window
    # anyway because it is not in ``done``
    attempted = done | skipped_suspect
    if suspect_values:
        attempted.add("verify_beststream")
        attempted.add("verify_v5f")
        attempted.add("verify_mosaicstream")
    complete = all(
        name in attempted for name, _, _ in ladder
        if name not in ("bench_v5", "bench_xla_base",
                        "bench_beststream", "bench_v5_bookend")
    )

    # ---- flip shipped defaults from certified wins (VERDICT r4 weak
    # #4 / next #3): the moment a window certifies the streaming
    # config (digest-gate MATCH => "verify_beststream" in done) AND
    # measures it faster than the same-window XLA baseline, write it
    # to cause_tpu/_tpu_defaults.json — switches.TPU_DEFAULTS loads it
    # at import, so every later process (bench.py's default path, API
    # waves, user code) ships the winner with no human in the loop.
    if record_state:
        decide_defaults(done, results, plat, suspects=suspect_values)
    obs.event("run.heartbeat", item="ladder", stage="done",
              elapsed=round(time.monotonic() - T0, 1))
    emit(ev="done", complete=complete, platform=plat)
    obs.flush()


def certified_env() -> str:
    """Space-separated ``K=V`` pairs for the watcher's phase-2 wave
    run: the cfg the digest gate certified (full or reduced, from the
    state file). Import-light on purpose — the watcher calls this
    under JAX_PLATFORMS=cpu.

    Cfgless-certification guard (ADVICE r5 medium): when the RAW state
    file claims verify_beststream (the watcher's grep on it is what
    routed us here) but the record carries no cfg — a pre-migration
    file, or a version-mismatched one load_state() discarded — return
    the shipped-default sentinel (empty string) so the watcher takes
    its shipped-default branch, mirroring load_state()'s cfgless
    -record re-verify rule. The static BESTSTREAM flips (which now
    include the never-before-certified matrix sort) are the fallback
    ONLY when the state carries no verify_beststream claim at all."""
    _, results = load_state()
    stored = (results.get("verify_beststream") or {}).get("cfg")
    if stored:
        return " ".join(f"{k}={v}" for k, v in sorted(stored.items()))
    try:
        with open(STATE_PATH) as f:
            raw = json.load(f)
        claimed = ("verify_beststream" in (raw.get("done") or ())
                   or "verify_beststream" in (raw.get("results") or {}))
    except Exception:  # noqa: BLE001 - missing/corrupt = no claim
        claimed = False
    if claimed:
        return ""  # shipped-default sentinel: never ship uncertified
    flips = flips_of(BESTSTREAM)
    return " ".join(f"{k}={v}" for k, v in sorted(flips.items()))


def defaults_file_path() -> str:
    # delegate to the consumer side: writer, revoker, re-certify check
    # and every reader must act on the SAME file, including under the
    # CAUSE_TPU_DEFAULTS_FILE override
    from cause_tpu.switches import _defaults_path

    return _defaults_path()


def decide_defaults(done: set, results: dict, plat: str,
                    path: str = "", suspects=frozenset()) -> None:
    """Write (or revoke) chip-certified switch defaults.

    Rules (each closes a round-5 review finding):
    - Flip ONLY the whole v5-certified combination
      (verify_beststream + bench_beststream, kernel v5): the global
      switch defaults apply to EVERY kernel a user's wave runs —
      which is v5 — so a combination certified under v5w/v5f
      (MOSAICSTREAM) must not leak into v5 paths it was never
      digest-checked against. Mosaic wins are reported (ev=defaults,
      informational) but never shipped globally; shipping them needs
      a v5-paired digest gate first.
    - Same-window comparison: the candidate and the xla baseline must
      carry the same ``run`` id. PERF.md records ~14% cross-day drift
      (4,300 -> 3,750 ms at identical code+shape); a 2% margin across
      windows would certify pure load noise. Within one window the
      measured spread is <2%, so the margin is meaningful.
    - Revocation: if the currently-shipped defaults intersect this
      attempt's digest-MISMATCH suspects, the file is deleted — a
      certification must not outlive its evidence."""
    path = path or defaults_file_path()

    # revoke first: shipped defaults contradicted by this attempt's
    # digest gate must go regardless of what else measured
    if suspects and os.path.exists(path):
        try:
            with open(path) as f:
                shipped = json.load(f).get("switches", {})
        except Exception:  # noqa: BLE001 - corrupt file: revoke it
            shipped = {"corrupt": "file"}
        shipped_vals = {f"{k}={v}" for k, v in shipped.items()}
        if (shipped_vals & set(suspects)) or "corrupt" in shipped:
            os.remove(path)
            emit(ev="defaults", flipped=False, revoked=True,
                 reason=f"shipped defaults intersect digest suspects "
                        f"{sorted(shipped_vals & set(suspects))}")
            return

    base_rec = results.get("bench_xla_base", {})
    base = base_rec.get("p50_amortized_ms")
    if not base:
        emit(ev="defaults", flipped=False,
             reason="no xla baseline measured; flip logic cannot rule")
        return
    cand = results.get("bench_beststream", {})
    p50 = cand.get("p50_amortized_ms")
    same_window = (cand.get("run") and
                   cand.get("run") == base_rec.get("run"))
    # informational only: Mosaic-combination wins (never shipped, see
    # docstring) — same-window rule applies to the report too
    for verify, bench in (("verify_mosaicstream", "bench_mosaicstream"),
                          ("verify_v5f", "bench_v5f")):
        mrec = results.get(bench, {})
        m = mrec.get("p50_amortized_ms")
        if (verify in done and m and m < base
                and mrec.get("run") == base_rec.get("run")):
            emit(ev="defaults", flipped=False, informational=True,
                 reason=f"{bench} ({m} ms) beats base ({base} ms) but "
                        "is certified under its own kernel only; a "
                        "v5-paired digest gate is required before "
                        "shipping its switches globally")
    if not ("verify_beststream" in done and p50
            and same_window and p50 < 0.98 * base):
        emit(ev="defaults", flipped=False,
             reason="no v5-certified same-window config beat the xla "
                    f"baseline by >2% (base {base} ms, "
                    f"beststream {p50} ms, same_window={same_window})")
        return
    # the timed cfg and the digest-certified cfg must be the SAME
    # program (reduced-certification coherence: a bench record from
    # before a reduction, or any future ladder reorder, must not ship
    # switches the gate never checked)
    vrec = results.get("verify_beststream") or {}
    vcfg = vrec.get("cfg")
    if vcfg is not None and dict(vcfg) != dict(cand.get("cfg") or vcfg):
        emit(ev="defaults", flipped=False,
             reason=f"timed cfg {cand.get('cfg')} != certified cfg "
                    f"{vcfg}; not shipping an uncertified combination")
        return
    # flip exactly what was timed: the bench record carries its own
    # cfg (reduced-certification support). For records predating the
    # cfg field the fallback is the CERTIFIED vcfg — not the static
    # BESTSTREAM flips, which can differ from a reduced certification
    # and would ship exactly the drift the coherence check above
    # exists to prevent (ADVICE r5 low); the constant is the last
    # resort only when neither record carries a cfg
    flips = dict(cand.get("cfg") or vcfg or flips_of(BESTSTREAM))
    rec = {
        # committed on purpose: the framework targets exactly this
        # chip (one v5e), and VERDICT r4 asks for
        # shipped defaults to come from measured winners; CPU and
        # other backends ignore these (switches.resolve backend guard)
        "switches": flips,
        "kernel": "v5",
        "evidence": {
            "p50_amortized_ms": p50,
            "xla_base_ms": base,
            "run": cand.get("run"),
            "platform": plat,
            # the digest the certification matched (None for records
            # predating the field): the flip's provenance is auditable
            # from the defaults file alone
            "digest": vrec.get("digest"),
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
    }
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    emit(ev="defaults", flipped=True, p50_ms=p50, xla_base_ms=base,
         kernel="v5", switches=flips, cfg=flips,
         digest=vrec.get("digest"), path=path)


if __name__ == "__main__":
    main()
