"""One traced run of one cell, reduced by the program's own spans and
the kernel's phase scopes (``benchmark/trace_spans.py``).

    python3 benchmark/breakdown.py --workload <cell> --seed <n> --seconds <s> [--keep DIR]

Makes the run ``run.py --trace 1`` makes (its result line is printed as
usual), keeps the profiler trace, and prints one more JSON line,
``{"breakdown": ...}``: the program spans that started in the window,
idle gaps by the innermost span covering them, and, in the merge
cells, device ops and device time by ``v5.*`` phase. The phase map is
the optimized HLO of the last kernel dispatch
(``parallel.wave.last_kernel_hlo``), looked up after the window. With
``--keep`` the ``.xplane.pb`` is copied there.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, spans, trace_reduce, trace_spans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    kept = {}
    start, stop = harness._start_trace, harness._stop_trace

    def start_and_note(tmp):
        kept["dir"] = start(tmp)
        return kept["dir"]

    def stop_and_keep():
        stop()
        src = trace_reduce.find_xplane(kept["dir"])
        kept["path"] = os.path.join(args.keep or tempfile.mkdtemp(),
                                    f"{args.workload}.{args.seed}.xplane.pb")
        os.makedirs(os.path.dirname(kept["path"]), exist_ok=True)
        shutil.copy(src, kept["path"])

    harness._start_trace, harness._stop_trace = start_and_note, stop_and_keep
    code = harness.run_cell(args.workload, args.seed, args.seconds, True,
                            T_PROCESS)
    if code or "path" not in kept:
        return code or 1
    hlo = ""
    if args.workload.startswith("merge."):
        from cause_tpu.parallel import wave

        t0 = time.perf_counter()
        hlo = wave.last_kernel_hlo()
        print(f"[breakdown] kernel_hlo_s={time.perf_counter() - t0:.3f}",
              flush=True)
    red = trace_spans.reduce_file(kept["path"], harness.WINDOW,
                                  harness.ANNOTATIONS, spans.PROGRAM_SPANS,
                                  trace_spans.scope_map([hlo] if hlo else []))
    print(json.dumps({"breakdown": red}), flush=True)
    if not args.keep:
        os.remove(kept["path"])
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
