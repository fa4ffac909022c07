"""The v5 kernel's sort, gather and sorted-search primitives against
numpy, on the edge cases the kernel feeds them: the ``I32_MAX``
invalid-lane sentinel, the full int32 range, negative and duplicate
keys, stability, widths that are not powers of two, rows under
``vmap``, and empty or all-equal search tables."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cause_tpu.weaver.gatherops import (searchsorted_iota_right,
                                        searchsorted_targets_left, take1d)
from cause_tpu.weaver.jaxw5 import _pair_search_le, _sort

I32 = np.iinfo(np.int32)


def _np_sort(keys, payload):
    """Stable ascending lexicographic sort over ``keys`` (first key
    most significant), ``payload`` riding along."""
    order = np.lexsort(tuple(reversed(keys)), axis=-1)
    return [np.take_along_axis(k, order, -1) for k in keys] + [
        np.take_along_axis(payload, order, -1)]


def _keys(case, rng):
    if case == "sentinels":
        hi = rng.randint(0, 50, 100).astype(np.int32)
        hi[rng.rand(100) < 0.4] = I32.max
        return [hi, np.where(hi == I32.max, I32.max,
                             rng.randint(0, 9, 100)).astype(np.int32)]
    if case == "full_range":
        return [rng.randint(I32.min, I32.max, 300, dtype=np.int64)
                .astype(np.int32)]
    if case == "extremes":
        return [np.array([I32.max, I32.min, 0, -1, I32.max, I32.min, 1],
                         np.int32)]
    if case == "negative_duplicates":
        return [rng.randint(-4, 3, 200).astype(np.int32)]
    if case == "all_equal":
        return [np.full(129, 7, np.int32), np.full(129, -7, np.int32)]
    if case == "two_keys_ties":
        return [rng.randint(0, 3, 257).astype(np.int32),
                rng.randint(0, 3, 257).astype(np.int32)]
    if case == "width_one":
        return [np.array([5], np.int32)]
    if case == "width_three":
        return [np.array([2, 2, 1], np.int32)]
    assert case == "descending_run"
    return [np.arange(1000, 0, -1).astype(np.int32)]


@pytest.mark.parametrize("case", [
    "sentinels", "full_range", "extremes", "negative_duplicates",
    "all_equal", "two_keys_ties", "width_one", "width_three",
    "descending_run"])
def test_sort_is_stable_lexicographic(case):
    rng = np.random.RandomState(len(case))
    keys = _keys(case, rng)
    payload = np.arange(keys[0].shape[-1], dtype=np.int32)
    got = jax.jit(lambda *o: _sort(o, num_keys=len(keys)))(
        *keys, payload)
    for g, w in zip(got, _np_sort(keys, payload)):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_sort_rows_under_vmap():
    rng = np.random.RandomState(11)
    hi = rng.randint(-3, 4, (5, 77)).astype(np.int32)
    hi[:, 60:] = I32.max
    lo = rng.randint(-3, 4, (5, 77)).astype(np.int32)
    src = np.tile(np.arange(77, dtype=np.int32), (5, 1))
    got = jax.jit(jax.vmap(lambda a, b, c: _sort((a, b, c), num_keys=2)))(
        hi, lo, src)
    for g, w in zip(got, _np_sort([hi, lo], src)):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("width", [1, 100, 1000])
def test_take1d_is_the_plain_gather(width):
    rng = np.random.RandomState(width)
    tab = rng.randint(I32.min, I32.max, width, dtype=np.int64).astype(
        np.int32)
    idx = rng.randint(0, width, 333).astype(np.int32)
    got = jax.jit(take1d)(tab, idx)
    np.testing.assert_array_equal(np.asarray(got), tab[idx])


def test_take1d_rows_under_vmap():
    rng = np.random.RandomState(3)
    tab = rng.randint(-9, 9, (3, 130)).astype(np.int32)
    idx = rng.randint(0, 130, (3, 57)).astype(np.int32)
    got = jax.jit(jax.vmap(take1d))(tab, idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.take_along_axis(tab, idx, -1))


def _nondecreasing(case, rng, n):
    if case == "random":
        return np.sort(rng.randint(0, 60, n)).astype(np.int32)
    if case == "all_equal":
        return np.full(n, 17, np.int32)
    if case == "past_q":
        return np.sort(rng.randint(0, 400, n)).astype(np.int32)
    if case == "negative":
        return np.sort(rng.randint(-30, 30, n)).astype(np.int32)
    assert case == "empty"
    return np.zeros(0, np.int32)


@pytest.mark.parametrize("case", [
    "random", "all_equal", "past_q", "negative", "empty"])
def test_searchsorted_iota_right(case):
    rng = np.random.RandomState(len(case))
    keys = _nondecreasing(case, rng, 0 if case == "empty" else 90)
    q = 64
    got = jax.jit(searchsorted_iota_right, static_argnums=1)(keys, q)
    want = np.searchsorted(keys, np.arange(q), side="right")
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("case", ["random", "all_equal"])
def test_searchsorted_targets_left(case):
    rng = np.random.RandomState(7)
    keys = _nondecreasing(case, rng, 90)
    k = 50
    got = jax.jit(searchsorted_targets_left, static_argnums=1)(keys, k)
    want = np.searchsorted(keys, np.arange(1, k + 1), side="left")
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("case", [
    "random", "duplicates", "below_all", "sentinels"])
def test_pair_search_le(case):
    """The rightmost (hi, lo) key at or below each query, -1 if none."""
    rng = np.random.RandomState(len(case) + 40)
    size = 37
    kh = rng.randint(0, 20, size).astype(np.int32)
    kl = rng.randint(-5, 5, size).astype(np.int32)
    if case == "duplicates":
        kh[:] = 4
    if case == "sentinels":
        kh[25:] = kl[25:] = I32.max
    order = np.lexsort((kl, kh))
    kh, kl = kh[order], kl[order]
    qh = rng.randint(-2, 22, 50).astype(np.int32)
    ql = rng.randint(-6, 6, 50).astype(np.int32)
    if case == "below_all":
        qh[:] = -1
    got = jax.jit(_pair_search_le, static_argnums=4)(kh, kl, qh, ql, size)
    keys = list(zip(kh.tolist(), kl.tolist()))
    want = [max([i for i, k in enumerate(keys) if k <= (h, l)],
                default=-1) for h, l in zip(qh.tolist(), ql.tolist())]
    np.testing.assert_array_equal(np.asarray(got), want)
