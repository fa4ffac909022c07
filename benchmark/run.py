"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Checks the device first (no TPU, or fewer chips than the cell asks for:
exit 2, no result), builds the cell's inputs from the seed, warms every
shape, measures for ``--seconds``, compares what the window produced
with the plain reference, and prints one JSON result as the last line
of standard output (see ``harness.run_cell``).
"""

import time

T_PROCESS = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the compared numbers stay the last lines of standard error: skip
    # the interpreter's teardown, which can print the runtime's notices
    os._exit(code)
