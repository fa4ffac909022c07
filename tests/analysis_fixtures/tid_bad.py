"""Known-bad TID fixture: reads of unregistered CAUSE_TPU_* knobs.

Never imported at runtime — causelint parses it (tests/test_analysis.py
pins the findings and a non-zero CLI exit).
"""

import os

import jax


@jax.jit
def traced_reads_unregistered(x):
    # TID001: CAUSE_TPU_TYPO is not in KNOWN_ENV_KNOBS, read from
    # jit-reachable code
    if os.environ.get("CAUSE_TPU_TYPO"):
        return x + 1
    return x


def host_reads_retired():
    # TID001: a retired strategy switch read from host code — no knob
    # may steer the program unregistered, traced or not
    return os.environ.get("CAUSE_TPU_SORT", "")
