"""The knee sweep for a serve cell: one process, one set-up, then the
cell's open loop at each offered rate in turn, drained between rates.

    python3 benchmark/sweep.py --workload serve.t64x10k.append --seed 7 \
        --seconds 10 --rates 40,80,160,320 [--out chiprun_out/sweep.jsonl]

The knee is the highest offered rate at which the queue's depth at the
end of the rate's window stays within one drain (``d_max``) of its depth
at the start, with nothing shed, and the ops converged inside the window
come to at least 95% of those offered (a short window can end before
the backlog shows in the depth). Prints one JSON line per rate and a
last line naming the knee; the mix file records the knee with the sweep
that found it, and the cell runs at about 0.8 of it. The documents grow
from rate to rate, as they would in a longer run.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.gen import edits  # noqa: E402


def sweep(workload, seed, seconds, rates, out=None, root=ROOT,
          check_device=True):
    cell = harness.Cell(root, workload)
    info = harness.device_info(cell.chips, check_device)
    if info is None:
        return 2
    if check_device:
        harness.enable_compile_cache(root)
    drv = cell.driver()
    ctx = harness.Ctx(cell, seed, seconds, False)
    import tempfile

    ctx.tmp = tempfile.mkdtemp(prefix="bench_sweep_")
    state = drv.setup(ctx)
    drv.warmup(state, ctx)
    d_max = int(cell.config["d_max"])
    counter = harness.CompileCounter()
    rows = []
    for i, rate in enumerate(rates):
        prod = edits.Producer(seed + 100 + i, cell.mix, state["tenants"],
                              rate)
        loop = drv.Loop(state, prod, seconds)
        counter.counts = dict.fromkeys(counter.counts, 0)
        counter.armed = True
        loop.run()
        counter.armed = False
        loop.drain(float(cell.mix["drain_s"]))
        converged = sum(b[1] for b in loop.batches if b[3]) / seconds
        lags = sorted(b[2] for b in loop.batches if b[2] is not None)
        row = {"rate_ops_per_s": rate, "offered": loop.offered,
               "refused": loop.refused,
               "depth_start": loop.depth_start, "depth_end": loop.depth_end,
               "converged_ops_per_s": converged,
               "lag_p50_ms": 1e3 * lags[len(lags) // 2] if lags else None,
               "lag_p95_ms": 1e3 * harness.percentile(lags, 95)
               if lags else None,
               "ticks": loop.ticks,
               "tick_ms_mean": 1e3 * sum(loop.tick_s) / max(1, len(loop.tick_s)),
               "window_compiles": counter.compiled,
               "holds": (loop.refused == 0
                         and loop.depth_end - loop.depth_start <= d_max
                         and converged >= 0.95 * rate)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if out:
            with open(out, "a") as f:
                f.write(json.dumps(dict(row, workload=workload, seed=seed,
                                        seconds=seconds, device=info)) + "\n")
        if not row["holds"]:
            break          # past the knee: higher rates only queue more
    held = [r["rate_ops_per_s"] for r in rows if r["holds"]]
    print(json.dumps({"workload": workload, "knee_ops_per_s":
                      max(held) if held else None, "device": info}),
          flush=True)
    drv.close(state, ctx)
    import shutil

    shutil.rmtree(ctx.tmp, ignore_errors=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, ops/s")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    return sweep(args.workload, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")], args.out)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
