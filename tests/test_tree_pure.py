"""The merge reduction tree against the pure weaver: the tree cells'
editors (``benchmark/gen/editors.py``) at test size, under the append
and edit mixes."""

import pytest

from cause_tpu.parallel.tree import merge_tree_report
from gen_replicas import editor_replicas, pure_merge, woven

SEEDS = range(2700500, 2700506)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix_name", ["append64-b1", "edit64-b4"])
def test_merge_tree_weaves_as_pure(mix_name, seed):
    reps = editor_replicas(seed, 6, 150, 12, mix_name)
    root, report = merge_tree_report(reps)
    assert woven(root) == pure_merge(*reps)
    paths = report["path_counts"]
    if mix_name == "append64-b1":
        assert paths == {"full": 1, "delta": 2}
    else:
        assert paths == {"full": 3, "delta": 0}
