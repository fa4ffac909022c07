"""TPU lowering guard for the shipped programs.

``jax.export`` with platforms=["tpu"] lowers a program for the TPU from
any backend, so a jnp construct with no TPU lowering fails here, not on
the chip. Lowering is not compiling: the chip's compiler runs in
tests/test_tpu_compile.py."""

import pytest

import jax
import jax.numpy as jnp


def test_v5_kernel_exports_for_tpu():
    """The default v5 program (pure XLA) lowers for TPU too — guards
    against a jnp construct with no TPU lowering sneaking in."""
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

    jax.export.export(jax.jit(f), platforms=["tpu"])(*args)




_BOOL = {"valid", "sg_dense", "sg_tail_special", "sg_valid"}
_NODE = {"hi", "lo", "cci", "vc", "valid", "seg"}


def _specs(B, N, S):
    from cause_tpu.benchgen import LANE_KEYS5

    lead = () if B is None else (B,)
    return [jax.ShapeDtypeStruct(lead + ((N,) if k in _NODE else (S,)),
                                 jnp.bool_ if k in _BOOL else jnp.int32)
            for k in LANE_KEYS5]


def _i32(*shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _shipped(name):
    """``[(jitted program, args, static kwargs)]`` of one shipped
    program at a test shape."""
    from cause_tpu.parallel import mesh
    from cause_tpu.parallel.session import _apply_deltas
    from cause_tpu.parallel.wave import _digest_fn
    from cause_tpu.weaver import jaxw5, jaxwd

    bm5 = jaxw5.batched_merge_weave_v5
    window = _specs(4, 64, 16) + [_i32(4, dtype=jnp.uint32), _i32(4)]
    if name == "merge_wave":
        return [(bm5, _specs(4, 512, 64),
                 dict(u_max=256, k_max=256, with_k=True)),
                (_digest_fn(), [_i32(4, 512)] * 3
                 + [_i32(4, 512, dtype=jnp.bool_)], {})]
    if name == "delta_wave":
        return [(jaxwd.batched_delta_weave, window,
                 dict(u_max=64, k_max=64))]
    if name == "tree_full_level":
        return [(jaxwd.batched_weave_digest, _specs(3, 512, 64),
                 dict(u_max=512, k_max=512, with_k=True))]
    if name == "tree_delta_window":
        return [(jaxwd.batched_delta_weave,
                 _specs(2, 128, 32) + [_i32(2, dtype=jnp.uint32), _i32(2)],
                 dict(u_max=128, k_max=128))]
    if name == "session_wave":
        cols = ("hi", "lo", "cci", "vc", "valid", "seg")
        dev = {k: _i32(2, 256, dtype=jnp.bool_ if k == "valid"
                       else jnp.int32) for k in cols}
        dl = {k: _i32(2, 2, 8, dtype=jnp.bool_ if k == "valid"
                      else jnp.int32) for k in cols}
        return [(bm5, _specs(2, 256, 32),
                 dict(u_max=128, k_max=128, with_k=True)),
                (_apply_deltas, [dev, dl, _i32(2, 2), _i32(2, 2), _i32(2),
                                 _i32(2)], {}),
                (jaxwd.splice_ranks,
                 [_i32(2, 256), _i32(2, 256, dtype=jnp.bool_), _i32(2, 64),
                  _i32(2, 64, dtype=jnp.bool_), _i32(2, 2), _i32(2, 2),
                  _i32(2)], {})]
    if name == "weave_arrays_v5":
        return [(jaxw5.merge_weave_kernel_v5_jit, _specs(None, 256, 64),
                 dict(u_max=72, k_max=72))]
    if name == "sharded_v5_step":
        step = mesh._sharded_step_v5(mesh.make_mesh(4), 128, 128)
        return [(step, _specs(8, 256, 32), {})]
    assert name == "map_wave_v5"
    return [(bm5, _specs(4, 128, 32), dict(u_max=64, k_max=64))]


@pytest.mark.parametrize("name", [
    "merge_wave", "delta_wave", "tree_full_level", "tree_delta_window",
    "session_wave", "weave_arrays_v5", "sharded_v5_step", "map_wave_v5"])
def test_shipped_program_exports_for_tpu(name):
    """Each shipped program lowers for the TPU as plain XLA: no Mosaic
    custom call anywhere in it."""
    for fn, args, kw in _shipped(name):
        text = jax.export.export(fn, platforms=["tpu"])(
            *args, **kw).mlir_module()
        assert "tpu_custom_call" not in text
        assert "custom_call @tpu" not in text
