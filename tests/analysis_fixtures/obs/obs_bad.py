"""Known-bad OBS fixture (lives under an ``obs/`` directory, so the
obs-package rules apply): obs reading config it does not own breaks
the obs-off invariance contract."""

import os


def snapshot():
    bad = os.environ.get("CAUSE_TPU_SORT", "")      # OBS001 (literal)
    key = "CAUSE_TPU" + "_GATHER"
    worse = os.environ.get(key, "")                  # OBS001 (opaque)
    return bad, worse
