"""pallas_bitonic_sort must reproduce stable lax.sort exactly — the
same contract tests as the XLA-level bitonic network, plus vmap (the
kernels' calling convention) and Mosaic-lowering export guards
(interpret mode accepts programs Mosaic rejects; see
tests/test_pallas_lowering.py for the precedent)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from cause_tpu.weaver.pallas_sort import pallas_bitonic_sort


I32_MAX = np.iinfo(np.int32).max


@pytest.mark.parametrize("n", [1, 2, 3, 7, 64, 100, 257])
@pytest.mark.parametrize("num_keys", [1, 2])
def test_matches_stable_lax_sort(n, num_keys):
    rng = np.random.RandomState(n * 10 + num_keys)
    ops = tuple(
        jnp.asarray(rng.randint(0, 7, size=n).astype(np.int32))
        for _ in range(num_keys)
    ) + (jnp.arange(n, dtype=jnp.int32) * 3,)
    want = lax.sort(ops, num_keys=num_keys, is_stable=True)
    got = pallas_bitonic_sort(ops, num_keys=num_keys)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


def test_batched_direct_and_sentinels():
    rng = np.random.RandomState(0)
    hi = rng.randint(0, 50, size=(12, 100)).astype(np.int32)
    hi[:, 40:] = I32_MAX  # invalid-lane sentinel region
    lo = rng.randint(-5, 50, size=(12, 100)).astype(np.int32)
    src = np.tile(np.arange(100, dtype=np.int32), (12, 1))
    ops = (jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(src))
    want = lax.sort(ops, num_keys=2, is_stable=True)
    got = pallas_bitonic_sort(ops, num_keys=2)  # 12 rows: pads to 16
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


def test_under_vmap_matches():
    """The kernels call sort inside a vmapped row function — the
    custom_vmap rule must swap in the gridded batch kernel."""
    rng = np.random.RandomState(1)
    B, n = 11, 300
    a = jnp.asarray(rng.randint(-9, 9, size=(B, n)).astype(np.int32))
    b = jnp.asarray(rng.randint(0, 5, size=(B, n)).astype(np.int32))

    def row(x, y):
        return pallas_bitonic_sort((x, y), num_keys=1)

    got = jax.vmap(row)(a, b)
    want = lax.sort((a, b), num_keys=1, is_stable=True)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


def test_negative_keys_and_duplicates():
    rng = np.random.RandomState(2)
    n = 1000
    key = jnp.asarray(rng.randint(-3, 3, size=n).astype(np.int32))
    pay = jnp.asarray(rng.randint(-100, 100, size=n).astype(np.int32))
    want = lax.sort((key, pay), num_keys=1, is_stable=True)
    got = pallas_bitonic_sort((key, pay), num_keys=1)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


def test_rejects_non_int32():
    with pytest.raises(TypeError):
        pallas_bitonic_sort((jnp.zeros(8, jnp.float32),), num_keys=1)


def test_exports_for_tpu(monkeypatch):
    from cause_tpu.weaver import pallas_sort

    monkeypatch.setattr(pallas_sort, "_interpret", lambda: False)
    a = jnp.arange(300, dtype=jnp.int32)[::-1]
    b = jnp.arange(300, dtype=jnp.int32)

    def f(x, y):
        return pallas_bitonic_sort((x, y), num_keys=1)

    jax.export.export(jax.jit(f), platforms=["tpu"])(a, b)


def test_exports_for_tpu_vmapped(monkeypatch):
    from cause_tpu.weaver import pallas_sort

    monkeypatch.setattr(pallas_sort, "_interpret", lambda: False)
    a = jnp.tile(jnp.arange(300, dtype=jnp.int32)[::-1], (12, 1))
    b = jnp.tile(jnp.arange(300, dtype=jnp.int32), (12, 1))

    def f(x, y):
        return jax.vmap(
            lambda u, v: pallas_bitonic_sort((u, v), num_keys=1)
        )(x, y)

    jax.export.export(jax.jit(f), platforms=["tpu"])(a, b)


def test_v5_kernel_with_pallas_sort_exports_for_tpu(monkeypatch):
    """The full v5 kernel under CAUSE_TPU_SORT=pallas must lower for
    TPU — the exact program the harvest A/B dispatches."""
    from cause_tpu.weaver import pallas_sort

    monkeypatch.setattr(pallas_sort, "_interpret", lambda: False)
    monkeypatch.setenv("CAUSE_TPU_SORT", "pallas")
    from cause_tpu import benchgen
    from cause_tpu.benchgen import LANE_KEYS5
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    batch = benchgen.batched_pair_lanes(
        n_replicas=4, n_base=120, n_div=40, capacity=256, hide_every=8
    )
    v5 = benchgen.batched_v5_inputs(batch, 256)
    u = benchgen.v5_token_budget(v5)
    args = [jnp.asarray(v5[k]) for k in LANE_KEYS5]

    def f(*a):
        return batched_merge_weave_v5(*a, u_max=u, k_max=u)

    batched_merge_weave_v5.clear_cache()
    try:
        jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    finally:
        batched_merge_weave_v5.clear_cache()
