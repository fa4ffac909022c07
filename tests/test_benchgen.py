"""The synthetic bench generator must describe *real* trees: kernel
output on benchgen lanes == pure host merge of the equivalent trees
built through the public API."""

import numpy as np

import cause_tpu as c
from cause_tpu import benchgen as bg
from cause_tpu.collections import clist as c_list
from cause_tpu.collections import shared as s
from cause_tpu.weaver import jaxw

# site-id strings whose sorted order matches the synthetic ranks
# (root "0" < base < A < B)
SITE_STRS = {bg.SITE_BASE: "site1base____", bg.SITE_A: "site2a_______",
             bg.SITE_B: "site3b_______"}


def build_real_pair(n_base, n_div, hide_every=0):
    """The trees benchgen's lanes claim to describe, via the host API."""
    base = c_list.CausalList(
        c_list.new_causal_tree().evolve(site_id=SITE_STRS[bg.SITE_BASE])
    )
    for i in range(1, n_base + 1):
        cause = c.root_id if i == 1 else (i - 1, SITE_STRS[bg.SITE_BASE], 0)
        base = base.insert(((i, SITE_STRS[bg.SITE_BASE], 0), cause, f"b{i}"))

    def suffixed(site_rank):
        site = SITE_STRS[site_rank]
        t = c_list.CausalList(base.ct.evolve(site_id=site))
        prev = (
            (n_base, SITE_STRS[bg.SITE_BASE], 0) if n_base else c.root_id
        )
        for j in range(1, n_div + 1):
            ts = n_base + j
            val = c.hide if (hide_every and j % hide_every == 0) else f"v{j}"
            t = t.insert(((ts, site, 0), prev, val))
            prev = (ts, site, 0)
        return t

    return suffixed(bg.SITE_A), suffixed(bg.SITE_B)


def kernel_weave(lanes, cap, a_ct, b_ct):
    """Decode merge_weave_kernel output back to a host node weave."""
    from test_jax_weaver import decode_device_weave, pair_lane_nodes

    order, rank, visible, conflict = jaxw.merge_weave_kernel(
        *(lanes[k] for k in ("hi", "lo", "chi", "clo", "vc", "valid"))
    )
    order, rank = np.asarray(order), np.asarray(rank)
    assert not bool(conflict)
    weave, _ = decode_device_weave(order, rank, pair_lane_nodes(a_ct, b_ct, cap))
    return weave


def check_config(n_base, n_div, hide_every, cap):
    lanes = bg.divergent_pair_lanes(n_base, n_div, cap, hide_every)
    a, b = build_real_pair(n_base, n_div, hide_every)
    got = kernel_weave(lanes, cap, a.ct, b.ct)
    expect = s.merge_trees(c_list.weave, a.ct, b.ct).weave
    assert got == expect


def test_parity_append_only():
    check_config(n_base=6, n_div=4, hide_every=0, cap=16)


def test_parity_with_tombstones():
    check_config(n_base=5, n_div=6, hide_every=3, cap=16)


def test_parity_no_base():
    check_config(n_base=0, n_div=5, hide_every=2, cap=8)


def test_batched_shape():
    batch = bg.batched_pair_lanes(4, 3, 2, 8, hide_every=0)
    assert batch["hi"].shape == (4, 16)
    assert all(v.shape[0] == 4 for v in batch.values())


def test_scalar_program_cache_hit_is_backend_init_free(monkeypatch):
    """The merge_wave_scalar program-cache lookup runs on host paths
    (bench.py's parent process, wave assembly) that must never trigger
    jax backend init: with the backend queries booby-trapped, a cache
    hit must still be served. The key is the program's own static
    arguments, ``(k_max, kernel, u_max)``."""
    import jax

    from cause_tpu import benchgen as bg_mod

    def boom(*a, **k):  # pragma: no cover - the assertion IS the test
        raise AssertionError("backend queried on the program-cache path")

    monkeypatch.setattr(jax, "default_backend", boom)
    monkeypatch.setattr(jax, "devices", boom)
    seen = []
    sentinel = object()

    def fake_program(*a):
        seen.append(a)
        return sentinel

    monkeypatch.setitem(bg_mod._scalar_programs, (7, "v5", 7), fake_program)
    out = bg_mod.merge_wave_scalar(1, 2, k_max=7, kernel="v5", u_max=7)
    assert out is sentinel
    assert seen == [(1, 2)]


RETIRED = ("CAUSE_TPU_SORT", "CAUSE_TPU_GATHER", "CAUSE_TPU_SEARCH",
           "CAUSE_TPU_SCATTER", "CAUSE_TPU_FPHASE")


def test_scalar_program_cache_key_xla_collapse(monkeypatch):
    """Every value of the retired strategy variables, "xla" included,
    collapses onto the one key: the environment is no part of a
    program's identity."""
    from cause_tpu import benchgen as bg_mod

    hits = []

    def fake_program(*a):
        hits.append(a)
        return "sentinel"

    monkeypatch.setitem(bg_mod._scalar_programs, (7, "v5", 7), fake_program)
    for value in ("", "xla", "matrix", "pallas"):
        for name in RETIRED:
            monkeypatch.setenv(name, value)
        assert bg_mod.merge_wave_scalar(
            1, k_max=7, kernel="v5", u_max=7) == "sentinel"
    assert len(hits) == 4
    assert set(bg_mod._scalar_programs) >= {(7, "v5", 7)}
    assert all(len(k) == 3 for k in bg_mod._scalar_programs)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The cache follows JAX_COMPILATION_CACHE_DIR when set (JAX reads
    it; no other directory is set in code), else the checkout's fixed
    ``.jax_cache``; the CPU is skipped."""
    import os

    import jax

    assert bg.enable_compile_cache() is None  # the suite runs on CPU
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_compilation_cache_dir)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert bg.enable_compile_cache() == bg.CACHE_DIR
        assert bg.CACHE_DIR == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(
                bg.__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert bg.enable_compile_cache() == str(tmp_path)
    finally:
        jax.config.update("jax_enable_compilation_cache", before[0])
        jax.config.update("jax_compilation_cache_dir", before[1])
