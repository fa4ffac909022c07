"""The kernels' phase scopes reach the optimized HLO.

``weaver/jaxw5.py`` runs each phase A-F under ``jax.named_scope("v5.<
phase>")`` and ``weaver/jaxwd.py`` its window digest and splice under
``wd.digest`` and ``wd.splice``, so a device trace's ops can be named
by phase. These tests keep the names from decaying: in the compiled
programs at a small shape, every ``while``, ``fusion``, ``sort`` and
``scatter`` instruction that carries an ``op_name`` carries a scope.
(XLA's own instructions, such as a broadcast of a constant it wrapped
in a fusion, carry no ``op_name`` at all.)
"""

import re

import jax
import jax.numpy as jnp

from cause_tpu.benchgen import LANE_KEYS5

B, N, S, U = 2, 64, 16, 32
_BOOL = {"valid", "sg_dense", "sg_tail_special", "sg_valid"}
_NODE = {"hi", "lo", "cci", "vc", "valid", "seg"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
KINDS = ("while", "fusion", "sort", "scatter")


def _lanes(n=N):
    return [jax.ShapeDtypeStruct((B, n if k in _NODE else S),
                                 jnp.bool_ if k in _BOOL else jnp.int32)
            for k in LANE_KEYS5]


def _scoped(text):
    """{kind: [scope of each instruction with an op_name]}."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in KINDS:
            continue
        on = _OP_NAME.search(line)
        if on:
            found = re.findall(r"v5\.[A-F]|wd\.digest|wd\.splice",
                               on.group(1))
            out.setdefault(m.group(2), []).append(
                found[-1] if found else None)
    return out


def test_every_v5_kernel_instruction_is_under_a_phase():
    from cause_tpu.weaver.jaxw5 import batched_merge_weave_v5

    text = batched_merge_weave_v5.lower(
        *_lanes(), u_max=U, k_max=U).compile().as_text()
    got = _scoped(text)
    assert got.get("fusion") and got.get("scatter")
    for kind, scopes in got.items():
        assert None not in scopes, kind
        assert all(s.startswith("v5.") for s in scopes), kind
    seen = {s for scopes in got.values() for s in scopes}
    assert seen == {f"v5.{p}" for p in "ABCDEF"}


def test_delta_wave_carries_kernel_and_digest_scopes():
    from cause_tpu.weaver.jaxwd import batched_delta_weave

    text = batched_delta_weave.lower(
        *_lanes(), jax.ShapeDtypeStruct((B,), jnp.uint32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        u_max=N, k_max=N).compile().as_text()
    got = _scoped(text)
    seen = {s for scopes in got.values() for s in scopes}
    assert None not in seen
    assert "wd.digest" in seen and {"v5.A", "v5.E", "v5.F"} <= seen


def test_splice_is_under_its_scope():
    from cause_tpu.weaver.jaxwd import splice_ranks

    full = jax.ShapeDtypeStruct((B, 2 * N), jnp.int32)
    win = jax.ShapeDtypeStruct((B, N), jnp.int32)
    text = splice_ranks.lower(
        full, jax.ShapeDtypeStruct((B, 2 * N), jnp.bool_), win,
        jax.ShapeDtypeStruct((B, N), jnp.bool_),
        jax.ShapeDtypeStruct((B, 2), jnp.int32),
        jax.ShapeDtypeStruct((B, 2), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).compile().as_text()
    got = _scoped(text)
    assert got, "no scoped instruction in the splice program"
    assert {s for scopes in got.values() for s in scopes} == {"wd.splice"}


def test_scoped_kernel_weaves_as_merge_does():
    """named_scope changes metadata only: the scoped program that runs
    still weaves a divergent pair as the host merge does."""
    import cause_tpu as c
    from cause_tpu.collections.clist import CausalList
    from cause_tpu.ids import new_site_id
    from cause_tpu.parallel import merge_wave

    base = c.clist(*range(30), weaver="jax")
    a, b = (CausalList(base.ct.evolve(site_id=new_site_id()))
            for _ in range(2))
    a, b = a.conj("x"), b.conj("y").conj("z")
    res = merge_wave([(a, b)])
    assert res.fallback == [] and res.poisoned == []
    want = [n[0] for n in a.merge(b).ct.weave]
    assert [n[0] for n in res.merged(0).ct.weave] == want
