"""The delta wave (``jaxwd``, through ``FleetSession``) against the pure
weaver: the serve cells' tenants (``benchmark/gen/edits.py``) at test
size, waved after runs of the append and edit mixes."""

import pytest

from cause_tpu.parallel.session import FleetSession
from cause_tpu.weaver import jaxwd
from gen_replicas import more_runs, pure_merge, typed_pairs, woven

SEEDS = range(2700400, 2700406)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix_name", ["append-b1", "edit-b4"])
def test_session_waves_weave_as_pure(monkeypatch, mix_name, seed):
    calls = []
    delta = jaxwd.batched_delta_weave

    def counted(*a, **k):
        calls.append(k["u_max"])
        return delta(*a, **k)

    monkeypatch.setattr(jaxwd, "batched_delta_weave", counted)
    pairs, prod = typed_pairs(seed, 4, 200, 16, mix_name)
    sess = FleetSession(pairs)
    sess.wave()
    for _ in range(2):
        pairs = more_runs(pairs, prod, 8)
        sess.update(pairs)
        sess.wave()
        for i, (a, b) in enumerate(pairs):
            assert woven(sess.merged(i)) == pure_merge(a, b), i
    if mix_name == "append-b1":
        # tail appends stay in the delta domain: the steady waves
        # ride the window program
        assert len(calls) == 2
