"""Empirical probe of the Pallas/Mosaic capabilities the v4 kernel
design depends on, run against a real TPU:

1. basic elementwise kernel + grid + VMEM blocks
2. per-row SMEM carry across grid steps (sequential chunk scan)
3. vectorized dynamic gather within VMEM (jnp.take_along_axis / x[idx])
4. masked store at a dynamic offset (pl.ds + pltpu.store)
5. scalar fori_loop throughput (cycles/iter estimate)
6. int32 one-hot matmul on the MXU (gather/scatter-as-matmul)

Each probe prints PASS/FAIL (+ timing where relevant) and the script
keeps going on failure — the point is the capability map, not a green
exit code.
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path for checkout runs)

import os
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe(name):
    def deco(fn):
        def run():
            try:
                t0 = time.perf_counter()
                out = fn()
                dt = (time.perf_counter() - t0) * 1e3
                print(f"PASS {name:40s} {dt:8.1f} ms  {out}")
            except Exception as e:  # noqa: BLE001 - capability map
                print(f"FAIL {name:40s} {type(e).__name__}: "
                      f"{str(e).splitlines()[0][:160]}")
                if os.environ.get("PROBE_TRACE"):
                    traceback.print_exc()
        return run
    return deco


@probe("basic elementwise + grid + VMEM")
def p_basic():
    def kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2 + 1

    x = jnp.arange(8 * 1024, dtype=jnp.int32).reshape(8, 1024)
    out = pl.pallas_call(
        kernel,
        grid=(8,),
        in_specs=[pl.BlockSpec((1, 1024), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, 1024), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, 1024), jnp.int32),
    )(x)
    ok = bool(jnp.all(out == x * 2 + 1))
    return f"ok={ok}"


@probe("SMEM carry across grid steps")
def p_carry():
    # cumulative chunk sums: carry lives in SMEM scratch across the grid
    def kernel(x_ref, o_ref, carry_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carry_ref[0] = 0

        s = jnp.sum(x_ref[:])
        o_ref[0, 0] = carry_ref[0] + s
        carry_ref[0] = carry_ref[0] + s

    x = jnp.ones((16, 512), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid=(16,),
        in_specs=[pl.BlockSpec((1, 512), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, 1), lambda i: (i, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((16, 1), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )(x)
    want = 512 * np.arange(1, 17)
    return f"ok={bool(jnp.all(out[:, 0] == want))}"


@probe("vector dynamic gather in VMEM (take_along_axis)")
def p_gather():
    def kernel(x_ref, idx_ref, o_ref):
        o_ref[:] = jnp.take_along_axis(x_ref[:], idx_ref[:], axis=1)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 1 << 20, (1, 2048), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, 2048, (1, 2048), dtype=np.int32))
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, 2048), jnp.int32),
    )(x, idx)
    want = np.asarray(x)[0][np.asarray(idx)[0]]
    return f"ok={bool(jnp.all(out[0] == want))}"


@probe("one-hot int32 matmul on MXU (gather-as-matmul)")
def p_onehot():
    # gather 256 values from a 2048 table via f32 one-hot matmul
    def kernel(x_ref, idx_ref, o_ref):
        tbl = x_ref[:].astype(jnp.float32)          # [1, 2048]
        q = idx_ref[:]                               # [1, 256]
        cols = lax.broadcasted_iota(jnp.int32, (256, 2048), 1)
        onehot = (q.reshape(256, 1) == cols).astype(jnp.float32)
        got = jax.lax.dot_general(
            onehot, tbl.reshape(2048, 1),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[:] = got.reshape(1, 256).astype(jnp.int32)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 1 << 20, (1, 2048), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, 2048, (1, 256), dtype=np.int32))
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, 256), jnp.int32),
    )(x, idx)
    want = np.asarray(x)[0][np.asarray(idx)[0]]
    return f"ok={bool(jnp.all(out[0] == want))}"


@probe("masked store at dynamic offset")
def p_store():
    def kernel(x_ref, off_ref, o_ref):
        o_ref[:] = jnp.zeros_like(o_ref)
        off = off_ref[0]
        vals = x_ref[0, :]
        o_ref[0, pl.ds(off, 128)] = vals

    x = jnp.arange(128, dtype=jnp.int32).reshape(1, 128)
    off = jnp.array([37], jnp.int32)
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, 512), jnp.int32),
    )(x, off)
    ok = bool(jnp.all(out[0, 37:37 + 128] == jnp.arange(128)))
    return f"ok={ok}"


@probe("scalar fori_loop throughput (SMEM)")
def p_scalar():
    # 100k dependent scalar iterations; report per-iter cost
    ITER = 100_000

    def kernel(x_ref, o_ref, acc_ref):
        def body(i, s):
            return s + x_ref[0, i % 512]

        acc_ref[0] = 0
        o_ref[0, 0] = lax.fori_loop(0, ITER, body, jnp.int32(0))

    x = jnp.ones((1, 512), jnp.int32)
    prog = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    out = prog(x)
    assert int(out[0, 0]) == ITER
    t0 = time.perf_counter()
    out = prog(x)
    int(out[0, 0])
    dt = time.perf_counter() - t0
    return f"{dt / ITER * 1e9:.1f} ns/iter (incl dispatch floor)"


@probe("local cumsum via triangular matmul")
def p_tri():
    def kernel(x_ref, o_ref):
        x = x_ref[:].astype(jnp.float32)             # [128, 128]
        r = lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        c = lax.broadcasted_iota(jnp.int32, (128, 128), 1)
        tri = (c <= r).astype(jnp.float32)           # lower triangular
        o_ref[:] = jax.lax.dot_general(
            tri, x, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

    x = jnp.ones((128, 128), jnp.int32)
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((128, 128), jnp.int32),
    )(x)
    want = np.cumsum(np.ones((128, 128)), axis=0)
    return f"ok={bool(jnp.all(out == want))}"


if __name__ == "__main__":
    print(f"platform={jax.devices()[0].platform} jax={jax.__version__}")
    for p in (p_basic, p_carry, p_gather, p_onehot, p_store, p_scalar,
              p_tri):
        p()
