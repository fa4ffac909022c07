"""The benchmark's harness: discovery by name, one run of one cell, and
the result line.

Everything that belongs to one configuration, traffic mix, driver or
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``benchmark/configs/<config>.json``: the deployment (sizes, guarantees,
  ``driver``);
- ``benchmark/mixes/<traffic>.json``: the traffic mix's parameters;
- ``benchmark/drivers/<driver>.py``: the entry the window drives, with
  ``setup``, ``warmup``, ``window``, ``check`` and ``close``;
- ``benchmark/metrics/<metric>.py``: ``read(ctx)``, returning a number or
  None when it finds nothing to read.

A new cell, mix, configuration or metric is new files plus entries in
``BENCHMARK.json``; nothing here changes.

This module imports no JAX at import time, so the tests can read it on
a machine without the chip.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW = "bench.window"    # the TraceAnnotation around the measured window
ANNOTATIONS = ("merge_wave", "offer", "tick", "wait")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = os.path.join(root, "benchmark")
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.workload = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(root,
                                             self.config_entry["file"]))
        self.mix = load_json(os.path.join(
            self.bench, "mixes", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])

    def driver(self):
        name = self.config["driver"]
        return load_module(os.path.join(self.bench, "drivers", name + ".py"),
                           f"benchmark_driver_{name}")

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports.
        An entry with a ``workloads`` key lists its cells. Without it,
        as the contract allows, an end-to-end metric is every cell's,
        and a per-layer one goes with the end-to-end metric it moves."""
        e2e = {m["name"]: m for m in self.spec["end_to_end"]}

        def reports(m):
            if "workloads" in m:
                return self.name in m["workloads"]
            return "moves" not in m or reports(e2e[m["moves"]])

        return [m for m in self.spec[kind] if reports(m)]

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench, "metrics", metric + ".py"),
                           "benchmark_metric_" + metric.replace(".", "_"))


class Ctx:
    """What a driver gets and what the metric readers read: the cell,
    the seed, the run's length, and ``data``, the driver's records."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.data: dict = {}
        self.device: dict = {}
        self.tmp = None

    def note(self, **facts) -> None:
        """One line of facts on standard output, before the result."""
        print("[" + self.cell.name + "] " + " ".join(
            f"{k}={v}" for k, v in facts.items()), flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return xs[k]


class GcWatch:
    """Counts, while armed, the full (generation 2) collections of the
    cyclic garbage collector and the seconds they paused the process."""

    def __init__(self):
        self.armed = False
        self.full = 0
        self.pause_s = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.armed or info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.full += 1
            self.pause_s += time.perf_counter() - self._t0


class CompileCounter:
    """Counts, while armed, the programs JAX lowers, the programs it
    compiles or loads, and the loads that hit the persistent cache."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}

        def listen(event, *_a, **_kw):
            if self.armed and event in self.counts:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(listen)

    @property
    def lowered(self) -> int:
        return self.counts[self.EVENTS[0]]

    @property
    def compiled(self) -> int:
        return self.counts[self.EVENTS[1]]

    @property
    def cache_loads(self) -> int:
        return self.counts[self.EVENTS[2]]


def device_info(chips: int, check_device: bool):
    """The platform, kind and count JAX reports. Returns None (after
    saying why on standard error) when there is no TPU or too few."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if check_device and info["platform"] != "tpu":
        print(f"benchmark: platform is {info['platform']!r}, not 'tpu'; "
              "the benchmark has no CPU path", file=sys.stderr)
        return None
    if check_device and info["count"] < chips:
        print(f"benchmark: {info['count']} device(s), the cell asks for "
              f"{chips}", file=sys.stderr)
        return None
    return info


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins when it is set; the
    program's own ``benchgen.enable_compile_cache`` then takes the same
    directory). Every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, root: str = ROOT, check_device: bool = True,
             control: bool = False, driver=None) -> int:
    """One run of one cell. Returns the exit code; prints the result
    line last on standard output unless the run cannot be made.
    ``control`` puts the control in the program's place (control.py).
    ``check_device=False`` and ``driver`` are for the tests: they skip
    the look for a chip and put a broken driver underneath."""
    cell = Cell(root, workload)
    info = device_info(cell.chips, check_device)
    if info is None:
        return 2
    if check_device:
        cache = enable_compile_cache(root)
        print(f"[setup] compile_cache={cache}", flush=True)
    ctx = Ctx(cell, seed, seconds, trace)
    ctx.device = info
    drv = driver or cell.driver()
    counter = CompileCounter()
    gcw = GcWatch()
    ctx.tmp = tempfile.mkdtemp(prefix="bench_")
    state = None
    try:
        state = drv.setup(ctx)
        drv.warmup(state, ctx)
        gc.collect()      # every run's window starts from the same heap state
        trace_dir = _start_trace(ctx.tmp) if trace else None
        counter.armed = gcw.armed = True
        ctx.data["setup_s"] = time.perf_counter() - t_process
        # a fault under the timed path that raises makes a run that is
        # not correct: the traceback goes to standard error, and the
        # phase's error is the number compared
        compared = None
        try:
            drv.window(state, ctx)
        except Exception:
            traceback.print_exc()
            compared = {"window_error": (1, 0)}
        counter.armed = gcw.armed = False
        if trace:
            _stop_trace()
        ctx.note(window_programs_lowered=counter.lowered,
                 window_compiles=counter.compiled,
                 window_cache_loads=counter.cache_loads,
                 window_full_gcs=gcw.full,
                 window_full_gc_s=f"{gcw.pause_s:.3f}")
        info["memory_peak_bytes"] = memory_peak_bytes()
        if compared is None and trace:
            from benchmark import trace_reduce

            t0 = time.perf_counter()
            red = trace_reduce.reduce_dir(trace_dir, WINDOW, ANNOTATIONS)
            ctx.data["trace"] = red
            info["busy_s"] = red["busy_s"]
            info["window_s"] = red["window_s"]
            ctx.note(trace_read_s=f"{time.perf_counter() - t0:.3f}",
                     device_planes=red["n_devices"],
                     device_events=red["n_events"])
        if compared is None:
            try:
                compared = drv.check(state, ctx, control=control)
            except Exception:
                traceback.print_exc()
                compared = {"check_error": (1, 0)}
    finally:
        if state is not None:
            drv.close(state, ctx)
        import shutil

        shutil.rmtree(ctx.tmp, ignore_errors=True)
    ctx.data.setdefault("attempted", 0)
    ctx.data.setdefault("failed", max(1, ctx.data["attempted"]))
    correct = all(v <= lim for v, lim in compared.values())
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct,
              "attempted": int(ctx.data["attempted"]),
              "failed": int(ctx.data["failed"]),
              "metrics": metrics, "device": info}
    if "trace" in ctx.data:
        result["breakdown"] = ctx.data["trace"]["breakdown"]
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}={v} limit={lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _start_trace(tmp: str) -> str:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    path = os.path.join(tmp, "trace")
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def _stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()
