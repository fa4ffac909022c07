"""A throwaway benchmark root at a size the CPU holds: BENCHMARK.json and
benchmark/ copied into a temp directory, the cells' configurations and
mixes shrunk, nothing else changed."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIGS = {
    "fleet1024x10k": {"pairs": 4, "doc_nodes": 61},
    "serve64x10k": {"tenants": 4, "doc_values": 60, "residency": 4},
}
TINY_MIXES = {
    "div1000": {"shared_values": 50, "divergent_values": 10},
    "div8": {"shared_values": 52, "divergent_values": 8},
    "edit-b4": {"rate_ops_per_s": 30.0, "warm_seconds": 0.5, "drain_s": 30,
                "warm_ladder": [4], "warm_ahead": 2},
    "append-b1": {"rate_ops_per_s": 30.0, "warm_seconds": 0.5,
                  "drain_s": 30, "warm_ladder": [1, 2, 4]},
}


def _patch(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)


def make_root(tmp) -> str:
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name, changes in TINY_CONFIGS.items():
        _patch(os.path.join(root, "benchmark", "configs", name + ".json"),
               changes)
    for name, changes in TINY_MIXES.items():
        _patch(os.path.join(root, "benchmark", "mixes", name + ".json"),
               changes)
    return root
