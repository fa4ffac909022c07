"""merge_wave against the pure weaver on every row: the merge cells'
fleets (``benchmark/gen/fleet.py``) at capacity 256, with the shared
document taking all but 0, 1 or 8 values a side."""

import pytest

from gen_replicas import assert_wave_rows, divergent_pairs

SEEDS = range(2700100, 2700110)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("divergent", [0, 1, 8])
def test_every_row_weaves_as_pure(divergent, seed):
    assert_wave_rows(divergent_pairs(seed, 4, 256, divergent))
