"""The control of a cell's comparison, on the chip at the cell's size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as ``run.py`` makes it, except that
what the comparison reads comes from the plain reference with concurrent
siblings in ascending order (the convergence guarantee broken) in the
program's place. Every such run has to come out not correct; the
numbers compared are the upper readings the limits are set against
(PERF.md). The benchmark's own runs never run this. Each seed runs in a
process of its own, one after another, so that one process holds the
chip at a time.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", help="comma-separated; one process each")
    ap.add_argument("--seed", type=int, help="one run in this process")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    if args.seed is not None:
        return harness.run_cell(args.workload, args.seed, args.seconds,
                                False, T_PROCESS, control=True)
    worst = 0
    for seed in args.seeds.split(","):
        # the parent never touches JAX: each child holds the chip alone
        p = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--workload", args.workload, "--seed", seed,
                            "--seconds", str(args.seconds)], cwd=ROOT)
        worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
