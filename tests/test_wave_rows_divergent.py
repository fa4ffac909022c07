"""merge_wave against the pure weaver on every row: the merge cells'
fleets (``benchmark/gen/fleet.py``) at capacity 256, with 64 or 200
divergent values a side, every 8th a hide."""

import pytest

from gen_replicas import assert_wave_rows, divergent_pairs

SEEDS = range(2700200, 2700210)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("divergent", [64, 200])
def test_every_row_weaves_as_pure(divergent, seed):
    assert_wave_rows(divergent_pairs(seed, 4, 256, divergent))
