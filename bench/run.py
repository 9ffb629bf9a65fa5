"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name: ``BENCHMARK.json`` names its configuration and
traffic, ``bench/cells/<cell>.json`` its entry point (a module under
``bench/entries/``), ``bench/configs/<config>.json`` the configuration and
``bench/traffic/<traffic>.json`` the traffic's parameters.  Per-layer
metrics are modules ``bench/metrics/<metric>.py`` with ``read(run)``.

A run sets up the system under test (``src/repro``) from ``--seed``, warms
up every shape the window uses, measures for ``--seconds``, then compares
what the timed path produced with the plain reference under
``bench/reference/``.  With ``--trace 0`` the last line of standard output
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the line carries the per-layer metrics read from
the trace.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so that only a cell's first run in a checkout compiles what its seed
#: does not change.
CACHE_DIR = os.path.join(ROOT, "results", ".xla_cache", "xla")
TRACE_DIR = os.path.join(ROOT, "results", "bench_trace")


class NoChip(SystemExit):
    """The machine lacks what the cell needs; no result is printed."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, root: str = ROOT) -> SimpleNamespace:
    """Everything the benchmark files say about one cell."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}: {sorted(cells)}")
    w = cells[workload]
    cell = load_json(os.path.join(root, "bench", "cells", workload + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != w[key]:
            raise SystemExit(f"bench/cells/{workload}.json has {key}="
                             f"{cell[key]!r}, BENCHMARK.json {w[key]!r}")
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    reported = {m["name"]: m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
    per_layer = {m["name"]: m for m in bench["per_layer"]
                 if m["moves"] in reported
                 and workload in m.get("workloads", [workload])}
    return SimpleNamespace(name=workload, chips=w["chips"], cell=cell,
                           config=config, traffic=traffic,
                           end_to_end=reported, per_layer=per_layer)


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits through
    ``jax.monitoring``; a request that no cache hit served is a backend
    compilation."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.requests = 0
        self.hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **kw):
            if name == event:
                self.requests += 1

        def on_event(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        self._jax, self._listeners = jax, (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.requests, self.hits

    def close(self) -> None:
        on_duration, on_event = self._listeners
        self._jax.monitoring.unregister_event_duration_listener(on_duration)
        self._jax.monitoring.unregister_event_listener(on_event)


class MemoryLog:
    """``memory_analysis()`` of every program compiled ahead of time
    (``jax.stages.Lowered.compile``), recorded as sizes only."""

    FIELDS = ("peak_memory_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "generated_code_size_in_bytes")

    def __init__(self, jax):
        self.programs = []
        self._jax = jax
        self._original = original = jax.stages.Lowered.compile
        log = self.programs

        def compile_and_log(lowered, *a, **kw):
            compiled = original(lowered, *a, **kw)
            mem = compiled.memory_analysis()
            if mem is not None:
                log.append({f: int(getattr(mem, f, 0)) for f in
                            MemoryLog.FIELDS})
            return compiled

        jax.stages.Lowered.compile = compile_and_log

    def close(self) -> None:
        self._jax.stages.Lowered.compile = self._original


class Run:
    """The harness's side of one run: the window, its spans, the compile
    counts inside it and, with ``--trace 1``, the profiler."""

    def __init__(self, spec, seed: int, seconds: float, trace: bool, jax):
        self.spec, self.seed, self.seconds, self.tracing = (
            spec, seed, seconds, trace)
        self.config, self.traffic = spec.config, spec.traffic
        self.jax = jax
        self.devices = jax.devices()[:spec.chips]
        self.compiles = CompileCounter(jax)
        self.memory = MemoryLog(jax)
        self.t_open = self.t_close = None
        self.trace = None
        self.notes = {}
        self.counts = {}
        self.t_start_process = T_START
        self._window_span = None
        self._trace_dir = os.path.join(TRACE_DIR, spec.name)

    def span(self, name: str):
        """A host span on the profiler's clock around a call into a layer."""
        return self.jax.profiler.TraceAnnotation("bench." + name)

    def open_window(self) -> None:
        # collect set-up's garbage now, so that no full collection of it
        # falls into the window
        gc.collect()
        if self.tracing:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            os.makedirs(self._trace_dir)
            opts = self.jax.profiler.ProfileOptions()
            # host spans and device ops only: tracing every Python call
            # would slow the host-bound cells by several times
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(self._trace_dir,
                                          profiler_options=opts)
            self._window_span = self.span("window")
            self._window_span.__enter__()
        self.compiles_open = self.compiles.snapshot()
        self.t_open = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    def close_window(self) -> None:
        self.t_close = time.perf_counter()
        self.compiles_close = self.compiles.snapshot()
        if self.tracing:
            self._window_span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
        self.memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices)

    @property
    def setup_s(self) -> float:
        return self.t_open - T_START

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def close(self) -> None:
        self.compiles.close()
        self.memory.close()

    def read_trace(self) -> None:
        import trace_reader

        self.trace = trace_reader.Trace.from_dir(self._trace_dir)
        shutil.rmtree(self._trace_dir, ignore_errors=True)


def per_layer_metrics(run, spec) -> dict:
    import flops
    import peaks

    run.flops = flops
    run.peaks = peaks.peaks(run.devices[0].device_kind)
    out = {}
    for name, meta in spec.per_layer.items():
        mod = load_module(os.path.join(BENCH, "metrics", name + ".py"),
                          "bench_metric_" + name.replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[name] = {"value": value, "unit": meta["unit"]}
    return out


def look_for_chips(jax, spec) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"[bench] no TPU: JAX reports {devices[0].platform!r}")
    if len(devices) < spec.chips:
        raise NoChip(f"[bench] {spec.name} needs {spec.chips} chips, "
                     f"JAX reports {len(devices)}")


def execute(spec, seed: int, seconds: float, trace: bool, jax) -> tuple:
    """Set up, measure and check one run; return the result line and every
    number the comparison computed (limits or not)."""
    from repro.sweep import cache as cache_lib

    cache_lib.enable_xla_cache(CACHE_DIR)
    run = Run(spec, seed, seconds, trace, jax)
    try:
        return _measure_and_check(run, spec, jax)
    finally:
        run.close()


def _measure_and_check(run, spec, jax) -> tuple:
    entry = load_module(os.path.join(BENCH, "entries",
                                     spec.cell["entry"] + ".py"),
                        "bench_entry_" + spec.cell["entry"])
    result = entry.run(run)
    gc.collect()
    if run.tracing:
        run.read_trace()
        metrics = per_layer_metrics(run, spec)
    else:
        metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
        for name, value in result["metrics"].items():
            metrics[name] = {"value": value,
                             "unit": spec.end_to_end[name]["unit"]}
    numbers = entry.check(run, result)
    checks = {k: c for k, c in numbers.items() if c["limit"] is not None}
    correct = (result["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    requests = run.compiles_close[0] - run.compiles_open[0]
    hits = run.compiles_close[1] - run.compiles_open[1]
    say(f"in the window: {requests} compile requests, {hits} served by the "
        f"persistent cache, {requests - hits} backend compilations")
    seen = {}
    for mem in run.memory.programs:
        key = tuple(sorted(mem.items()))
        seen[key] = seen.get(key, 0) + 1
    for key, times in seen.items():
        say(f"memory_analysis, compiled {times}x: {dict(key)}")
    for key, value in run.notes.items():
        say(f"{key}: {value}")
    for name, c in numbers.items():
        if c["limit"] is None:
            say(f"reading {name}: {c['value']!r} (not compared)")

    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if run.tracing:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return line, {k: c["value"] for k, c in numbers.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no system under test at {src}")
    sys.path.insert(0, src)
    sys.path.insert(0, BENCH)
    import jax

    look_for_chips(jax, spec)
    line, _ = execute(spec, args.seed, args.seconds, bool(args.trace), jax)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
