"""Without a TPU the benchmark exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

import bench_tiny


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=bench_tiny.REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_run_exits_nonzero_without_a_tpu():
    p = _run("benchmark/run.py", "--workload", "merge.fleet1024x10k.div1000",
             "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert "[merge" not in p.stdout     # no data was built
    _no_result(p.stdout)


def test_sweep_exits_nonzero_without_a_tpu():
    p = _run("benchmark/sweep.py", "--workload", "serve.t64x10k.append",
             "--seed", "3", "--rates", "10")
    assert p.returncode != 0
    _no_result(p.stdout)
