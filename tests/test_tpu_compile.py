"""The chip's own compiler on the main path's programs, without a chip.

A described v5e (``v5e:2x2``) takes the place of an attached one: the
TPU compiler is installed here and compiles for it, refusing what the
chip would refuse — VMEM overuse, tiling the Mosaic compiler cannot
prove, programs that do not fit. Interpret mode and ``jax.export``
lowering (tests/test_pallas_lowering.py) cannot see that class of
failure. Only kernels the compiler accepts are pinned here; the ones it
refuses are listed in CHANGES.md (PR 21) and stay off the default
route.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the test
workers all import this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from cause_tpu.benchgen import LANE_KEYS5

# north-star row width: 1024 pairs x 10,001-node lists use 20,480
# concat lanes and 512 segment slots per row; the batch stays small
N_LANES, N_SEGS, U_MAX, BATCH = 20_480, 512, 1024, 8
_BOOL = {"valid", "sg_dense", "sg_tail_special", "sg_valid"}
_NODE = {"hi", "lo", "cci", "vc", "valid", "seg"}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Pallas kernels compile through Mosaic, not the interpreter."""
    from cause_tpu.weaver import pallas_befuse, pallas_sort

    for mod in (pallas_befuse, pallas_sort):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _v5_specs(sharding):
    return [_spec((BATCH, N_LANES if k in _NODE else N_SEGS), sharding,
                  jnp.bool_ if k in _BOOL else jnp.int32)
            for k in LANE_KEYS5]


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_v5_wave_program_compiles_for_v5e(one_chip):
    """The shipped default: merge_wave's v5 kernel and its digest."""
    from cause_tpu.parallel.wave import _digest_fn
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    compiled = _compile(
        lambda *a: batched_merge_weave_v5(*a, u_max=U_MAX, k_max=U_MAX),
        *_v5_specs(one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    _compile(lambda h, l, r, v: _digest_fn()(h, l, r, v),
             *(_spec((BATCH, N_LANES), one_chip) for _ in range(3)),
             _spec((BATCH, N_LANES), one_chip, jnp.bool_))


def test_pallas_bitonic_sort_compiles_for_v5e(one_chip, compiled_kernels):
    from cause_tpu.weaver.pallas_sort import pallas_bitonic_sort

    compiled = _compile(
        lambda k, v: jax.vmap(
            lambda x, y: pallas_bitonic_sort((x, y), num_keys=1))(k, v),
        _spec((BATCH, 1024), one_chip), _spec((BATCH, 1024), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["k1_sort_redirect", "k2_runs"])
def test_befuse_kernel_compiles_for_v5e(one_chip, compiled_kernels,
                                        kernel):
    """The fused token-pipeline kernels the compiler accepts, at the
    shapes jaxw5f hands them (recorded from a trace of the pipeline)."""
    from cause_tpu.weaver import jaxw5f, pallas_befuse

    got = {}

    def record(*args, **kw):
        got["args"] = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in args]
        got["kw"] = kw
        raise _Recorded

    u = 256
    specs = [jax.ShapeDtypeStruct(s.shape[1:], s.dtype)
             for s in _v5_specs(None)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jaxw5f, kernel, record)
        with pytest.raises(_Recorded):
            jax.make_jaxpr(lambda *a: jaxw5f.merge_weave_kernel_v5f(
                *a, u_max=u, k_max=u))(*specs)
    fn = getattr(pallas_befuse, kernel)
    compiled = _compile(lambda *a: fn(*a, **got["kw"]),
                        *(_spec(a.shape, one_chip, a.dtype)
                          for a in got["args"]))
    assert "tpu_custom_call" in compiled.as_text()


class _Recorded(Exception):
    pass


def test_refused_kernels_are_off_the_default_route(monkeypatch):
    """What the v5e compiler refuses today (the Pallas euler walk, the
    fused F-phase, befuse K4 — CHANGES.md, PR 21) is reachable only by
    an explicit switch: with none set, every strategy is the XLA one
    and no certified-defaults record re-routes it."""
    from cause_tpu import switches

    for name in switches.TRACE_SWITCHES:
        monkeypatch.delenv(name, raising=False)
        assert switches.resolve(name) == ""
    assert switches.TPU_DEFAULTS == {}
    assert switches.measured_kernel() == ""
    assert "pallas" not in switches.BESTSTREAM_FLIPS.values()
