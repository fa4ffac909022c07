"""merge_wave against the pure weaver on every row: the serve cells'
tenants (``benchmark/gen/edits.py``) at capacity 256, after runs of
the ``edit-b4`` mix (typed anywhere, inserts and hides) or the
``append-b1`` mix (appends at each site's tail)."""

import pytest

from gen_replicas import assert_wave_rows, typed_pairs

SEEDS = range(2700300, 2700310)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix_name", ["edit-b4", "append-b1"])
def test_every_row_weaves_as_pure(mix_name, seed):
    pairs, _prod = typed_pairs(seed, 4, 200, 40, mix_name)
    assert_wave_rows(pairs)
