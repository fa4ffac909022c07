"""The batched v5 kernel's widest run-width branch against the pure
weaver: ``test_jax_v5.test_batched_runs_at_the_batch_width`` covers
the 128 and 256 branches of the 128/256/512 ladder; these batches hold
more than 256 runs in a row, so the tail runs at ``k_max`` = 512."""

import random

import pytest

from test_jax_v5 import (_check_ladder, _doc, _star, _tail_pastes,
                         _typed_anywhere)


@pytest.mark.parametrize("case", [
    "two_stars", "fullest_stars", "wide_row_in_mixed_batch",
    "every_row_wide"])
def test_batched_runs_at_the_cap(case):
    one = _doc(1)
    if case == "two_stars":                 # 401 runs
        pairs = [(_star(one, 200), _star(one, 200))]
    elif case == "fullest_stars":           # 509 runs
        pairs = [(_star(one, 254), _star(one, 254))]
    elif case == "wide_row_in_mixed_batch":
        base = _doc(120)
        rng = random.Random(case)
        pairs = [(_tail_pastes(base, 8), _tail_pastes(base, 9)),
                 (_star(one, 150), _star(one, 130)),
                 (_typed_anywhere(base, 12, rng),
                  _typed_anywhere(base, 9, rng))]
    else:
        pairs = [(_star(one, 130 + 10 * i), _star(one, 140 + 10 * i))
                 for i in range(3)]
    assert not _check_ladder(pairs, 512).any()
