"""Known-good TID fixture: reads of registered knobs stay silent."""

import os

from cause_tpu.switches import KNOWN_ENV_KNOBS


def obs_on():
    return os.environ.get("CAUSE_TPU_OBS", "") == "1"


def knob_snapshot():
    # iterating the imported registry is the blessed pattern
    return {k: os.environ.get(k, "") for k in KNOWN_ENV_KNOBS}
