"""The registry of the ``CAUSE_TPU_*`` environment knobs.

None of them reaches a traced program: they steer observability,
host-side sampling, file locations, fault injection and durability, so
the shipped programs are a function of their inputs alone. Every
``CAUSE_TPU_*`` read in the tree must name a member — causelint (rule
TID001, ``cause_tpu.analysis``) fails CI on a read of an unregistered
name, so a typo'd knob cannot silently become a config axis.
Dependency-free on purpose: causelint and bench.py's parent process
read it without importing jax.
"""

KNOWN_ENV_KNOBS = (
    "CAUSE_TPU_OBS",
    "CAUSE_TPU_OBS_OUT",
    "CAUSE_TPU_OBS_RING",
    "CAUSE_TPU_NATIVE_CACHE",
    "CAUSE_TPU_BODY_SAMPLE",
    "CAUSE_TPU_LEDGER",
    "CAUSE_TPU_LAG_SLO_MS",
    "CAUSE_TPU_CHAOS",
    "CAUSE_TPU_WAL_FSYNC",
    "CAUSE_TPU_OBS_SHIP",
)
