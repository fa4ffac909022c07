"""Suppression fixture: both syntaxes neutralize a real finding."""

import os

SAME_LINE = os.environ.get("CAUSE_TPU_SORT")  # causelint: disable=TID001 -- fixture: same-line suppression
# causelint: disable-next-line=TID -- fixture: family token on next line
NEXT_LINE = os.environ.get("CAUSE_TPU_GATHER")
NOT_SUPPRESSED = os.environ.get("CAUSE_TPU_SEARCH")  # causelint: disable=JPH001 -- wrong family: must NOT suppress
