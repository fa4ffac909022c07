"""The yardstick's arithmetic: the chip's peaks, and the least bytes a
merge has to move, whatever implements it."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
ID_BYTES = 8        # one id, packed
CAUSE_BYTES = 8     # one cause id, packed
OUT_BYTES = 5       # per merged node: rank (4) and visibility (1)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS} (have {sorted(table)})")
    return table[device_kind]


def merge_least_bytes(node_counts) -> int:
    """Bytes one merge of the fleet must move at the least: each
    replica's nodes read once (id and cause), each merged node's rank
    and visibility written once. ``node_counts``: per pair, (nodes of
    a, nodes of b, nodes of the merge)."""
    return sum((na + nb) * (ID_BYTES + CAUSE_BYTES) + nm * OUT_BYTES
               for na, nb, nm in node_counts)
