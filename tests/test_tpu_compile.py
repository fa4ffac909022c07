"""The chip's own compiler on the main path's programs, without a chip.

A described v5e (``v5e:2x2``) takes the place of an attached one: the
TPU compiler is installed here and compiles for it, refusing what the
chip would refuse — programs that do not fit, shapes it cannot tile.
``jax.export`` lowering (tests/test_pallas_lowering.py) cannot see that
class of failure.

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


