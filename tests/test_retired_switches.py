"""The retired strategy variables are inert: with each one set, the
program merge_wave dispatches lowers to the same StableHLO text as with
none set. Each lowering starts from empty jit caches, so the kernel's
Python runs again under the variable. (The retired defaults file only
ever acted on a TPU backend, so on this CPU host its case pins the
registry module instead: reloaded under the variable, it holds
``KNOWN_ENV_KNOBS`` alone.)"""

import importlib

import pytest

import jax

from cause_tpu.parallel import merge_wave
from cause_tpu.parallel import wave
from gen_replicas import divergent_pairs

RETIRED = {
    "CAUSE_TPU_SORT": "matrix",
    "CAUSE_TPU_GATHER": "rowgather",
    "CAUSE_TPU_SEARCH": "matrix-table",
    "CAUSE_TPU_SCATTER": "hint",
    "CAUSE_TPU_FPHASE": "pallas",
    "BENCH_KERNEL": "v5f",
    "CAUSE_TPU_DEFAULTS_FILE": None,      # a file naming flips, below
}


def _lowered(spec) -> str:
    """The lowered text of a recorded dispatch, traced afresh."""
    jax.clear_caches()
    return spec["fn"].lower(*spec["args"], **spec["kw"]).as_text()


def _dispatched(pairs) -> dict:
    """The call merge_wave dispatches for ``pairs``, recorded."""
    jax.clear_caches()
    wave._LAST_KERNEL.clear()
    merge_wave(pairs)
    return dict(wave._LAST_KERNEL)


@pytest.fixture(scope="module")
def unset():
    with pytest.MonkeyPatch.context() as mp:
        for name in RETIRED:
            mp.delenv(name, raising=False)
        pairs = divergent_pairs(2700600, 2, 64, 12)
        spec = _dispatched(pairs)
        return pairs, spec, _lowered(spec)


@pytest.mark.parametrize("name", list(RETIRED))
def test_retired_variable_is_inert(monkeypatch, tmp_path, unset, name):
    from cause_tpu import switches

    pairs, spec, want = unset
    value = RETIRED[name]
    if value is None:
        flips = tmp_path / "defaults.json"
        flips.write_text(
            '{"switches": {"CAUSE_TPU_SORT": "matrix", '
            '"CAUSE_TPU_GATHER": "rowgather", "CAUSE_TPU_SEARCH": '
            '"matrix-table", "CAUSE_TPU_SCATTER": "hint"}, '
            '"kernel": "v5f"}')
        value = str(flips)
    monkeypatch.setenv(name, value)
    importlib.reload(switches)
    assert [n for n in vars(switches) if not n.startswith("_")] == [
        "KNOWN_ENV_KNOBS"]
    assert name not in switches.KNOWN_ENV_KNOBS
    if name == "BENCH_KERNEL":        # read where merge_wave dispatches
        spec = _dispatched(pairs)
    assert _lowered(spec) == want
