"""The device contract of the two entry points that measure the chip:
``bench.py`` and ``chip_smoke.py`` run on a TPU or exit non-zero, and
a CPU run happens only when asked for and never claims the target.
Real subprocess tests, since the exit code is the contract."""

import json
import os
import subprocess
import sys
import time

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra_env):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("BENCH_", "CAUSE_TPU_"))}
    env.update(JAX_PLATFORMS="cpu", **extra_env)
    return env


def _run(script, extra_env, timeout):
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        capture_output=True, text=True, timeout=timeout,
        env=_env(extra_env), cwd=_ROOT,
    )
    return out, time.monotonic() - t0


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_no_tpu_exits_nonzero_fast(script):
    """Without a TPU both entry points refuse at once: non-zero exit,
    no result line, no fallback row."""
    out, wall = _run(script, {}, timeout=120)
    assert out.returncode != 0, out.stdout[-800:]
    assert '"ok": true' not in out.stdout
    assert "cpu-fallback" not in out.stdout
    assert not [ln for ln in out.stdout.splitlines()
                if ln.lstrip().startswith("{")], out.stdout[-800:]
    assert wall < 60, f"{script} took {wall:.0f}s to refuse"


def _bench_cpu(extra_env):
    out, _wall = _run("bench.py", dict({"BENCH_FORCE_CPU": "1",
                                        "BENCH_SMOKE": "1"}, **extra_env),
                      timeout=900)
    assert out.returncode == 0, out.stderr[-1500:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert lines, out.stderr[-1500:]
    rec = json.loads(lines[-1])
    for key in ("metric", "value", "unit", "vs_baseline", "vs_target",
                "target_ms", "platform", "kernel", "config",
                "schema_version"):
        assert key in rec, (key, rec)
    assert rec["value"] and rec["value"] > 0
    assert rec["unit"] == "ms"
    # vs_baseline is kept for driver compatibility; vs_target is the
    # honest name — the two must always agree, and a CPU run never
    # claims the TPU-defined target
    assert rec["vs_target"] == rec["vs_baseline"] == 0.0
    assert rec["target_ms"] == 100.0
    assert rec["platform"] == "cpu"
    assert not {"fallback", "error", "sentinel"} & set(rec)
    return rec


@pytest.mark.slow
def test_explicit_cpu_rehearsal_contract():
    rec = _bench_cpu({})
    assert "smoke size" in rec["metric"]
    # the headline is a median, and the artifact says over how many
    assert rec["reps"] >= 3
    assert rec["burst_reps"] >= 1


