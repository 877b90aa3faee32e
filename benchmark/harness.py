"""harness.py - what every cell shares: the device check, the compile cache,
the compile meter, spans, the measured window, the traced window, the
readers and the result line.

A cell is data: `BENCHMARK.json` names a configuration file and a traffic
mix; the mix's file names its driver (`drivers/<driver>.py`, found by file
name); each per-layer metric has `metrics/<metric>.json`, which names its
reader (`readers/<reader>.py`) and that reader's parameters. Adding a cell,
a configuration, a mix, a metric, a reader or a driver is adding files and
manifest entries; nothing here lists them.
"""
import collections
import contextlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot give a result; the process exits non-zero."""


def load_module(kind, name, bench_dir=HERE):
    """`<bench_dir>/<kind>/<name>.py`, imported by its file name."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


class CompileMeter:
    """JAX's own compile events (chip_smoke.CompileMeter, copied): backend
    compile seconds (a persistent-cache fetch included), compilations,
    persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self.cache_misses += event == "/jax/compilation_cache/cache_misses"

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def peaks_for(device_kind, bench_dir=HERE):
    """The row of peaks.json for this chip; an unknown kind is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    kind = device_kind.lower()
    for row in table["chips"]:
        if any(s in kind for s in row["device_kind_contains"]):
            return row
    raise BenchError(f"peaks.json has no chip for device_kind "
                     f"{device_kind!r}")


class Bench:
    """One run of one cell. A driver's `run(bench)` sets up, calls
    `setup_done()`, measures until `open()` turns false, optionally traces a
    short part inside `traced()`, and returns its result dict."""

    def __init__(self, manifest, workload, seed, seconds, trace, t_start,
                 root, bench_dir=HERE, require_tpu=True):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise BenchError(f"no cell {workload!r} in the manifest")
        self.manifest, self.cell = manifest, cells[workload]
        self.root, self.bench_dir = root, bench_dir
        config = next(c for c in manifest["configs"]
                      if c["name"] == self.cell["config"])
        self.cfg = load_json(os.path.join(root, config["file"]))
        self.mix = load_json(os.path.join(
            bench_dir, "traffic", self.cell["traffic"] + ".json"))
        self.seed, self.seconds, self.trace_on = seed, seconds, bool(trace)
        self.t_start = t_start
        self.chips = self.cell["chips"]
        self.spans = collections.defaultdict(list)
        self.trace = None
        self.setup_s = None
        self._t_window = None
        self.device = self._devices(require_tpu)
        self.peaks = (peaks_for(self.device["kind"], bench_dir)
                      if self.device["platform"] == "tpu" else None)
        self.meter = CompileMeter()
        self._compiles_at_setup = None
        self._program_peak = self.counter_peak = 0

    # ------------------------------------------------------------ device
    def _devices(self, require_tpu):
        import jax
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache:
            # fixed path inside the checkout: the path is part of the key
            cache = os.path.join(self.bench_dir, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        print(f"benchmark: jax {jax.__version__} platform="
              f"{device['platform']} device_kind={device['kind']!r} "
              f"count={device['count']} compile_cache={cache}", flush=True)
        if require_tpu and device["platform"] != "tpu":
            raise BenchError(f"JAX found platform {device['platform']!r}, "
                             f"not a TPU: nothing is measured on it")
        if device["count"] != self.chips:
            raise BenchError(f"cell {self.cell['name']} asks for "
                             f"{self.chips} chip(s), JAX found "
                             f"{device['count']}")
        return device

    def jax_key(self):
        """A PRNG key from --seed, which may pass 2**31."""
        import jax
        return jax.random.fold_in(
            jax.random.PRNGKey(self.seed & 0x7FFFFFFF), self.seed >> 31)

    # ------------------------------------------------------------ clocks
    @contextlib.contextmanager
    def span(self, name):
        """One of the benchmark's own spans: host clock, and an annotation
        the profiler's trace carries on the device's clock."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans[name].append(time.perf_counter() - t0)

    def setup_done(self):
        """Set-up ends here: everything since the process started."""
        self.setup_s = time.perf_counter() - self.t_start
        self._compiles_at_setup = self.meter.compiles
        self._t_window = time.perf_counter()
        return self._t_window

    def open(self):
        return time.perf_counter() - self._t_window < self.seconds

    def end_window(self):
        self.compiles_in_window = self.meter.compiles - self._compiles_at_setup

    @contextlib.contextmanager
    def traced(self):
        """Trace what runs inside: the profiler with the Python tracer off
        (it makes the file large and slows the host), a `bench.window`
        annotation round it, then the reduction."""
        import jax
        from xplane import Trace, find_xplane
        out = os.path.join(self.bench_dir, ".traces", self.cell["name"])
        shutil.rmtree(out, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        self.trace = Trace.from_file(find_xplane(out))
        shutil.rmtree(out, ignore_errors=True)

    # ------------------------------------------------------------ result
    def note_program_memory(self, analysis):
        """A compiled program's `memory_analysis()`: its peak, arguments and
        temporaries together, as the chip's compiler counts them."""
        peak = getattr(analysis, "peak_memory_in_bytes", 0) or (
            analysis.temp_size_in_bytes + analysis.argument_size_in_bytes
            + analysis.output_size_in_bytes - analysis.alias_size_in_bytes)
        self._program_peak = max(self._program_peak, int(peak))

    def memory_peak_bytes(self):
        """Peak bytes on the fullest chip. The runtime's counter
        (`peak_bytes_in_use`) counts live arrays and not a running program's
        temporaries (PR 25: it reads 4.28 GB for an LM step whose program
        needs 15.2 GB), so a driver asks the compiler for the peak of its
        largest program and the larger of the two is reported."""
        import jax
        stats = [d.memory_stats() for d in jax.devices()]
        peaks = [s["peak_bytes_in_use"] for s in stats
                 if s and "peak_bytes_in_use" in s]
        self.counter_peak = max(peaks) if peaks else 0
        return max(self.counter_peak, self._program_peak)

    def _names(self, result):
        """What a `derived` expression may name, before the metrics."""
        import work
        names = {}
        for group, prefix in ((self.cfg, "cfg_"), (self.mix, "mix_"),
                              (self.peaks or {}, "peak_")):
            names.update({prefix + k: v for k, v in group.items()
                          if isinstance(v, (int, float))
                          and not isinstance(v, bool)})
        names.update({"span_" + k: statistics.median(v)
                      for k, v in self.spans.items() if v})
        names.update(work.quantities(self.cfg, self.mix))
        names.update(result.get("facts", {}))
        if self.trace is not None:
            names["trace_busy_s"] = self.trace.busy_s()
            names["trace_window_s"] = self.trace.window_s()
        return names

    def per_layer(self, result, e2e_values):
        """Read this cell's per-layer metrics, each by its own file. A
        reader that finds nothing returns None and the metric is left out;
        `derived` metrics wait for the values they use."""
        mine = [m for m in self.manifest["per_layer"]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]
        ctx = {"trace": self.trace, "spans": self.spans,
               "program": result.get("program", {}),
               "facts": result.get("facts", {})}
        names = self._names(result)
        values, pending = {}, mine
        while pending:
            waiting = []
            for m in pending:
                spec = load_json(os.path.join(
                    self.bench_dir, "metrics", m["name"] + ".json"))
                reader = load_module("readers", spec["reader"],
                                     self.bench_dir)
                ctx["names"] = {**names, **e2e_values, **values}
                value = reader.read(spec, ctx)
                if value is None:
                    waiting.append(m)
                else:
                    values[m["name"]] = value
            if len(waiting) == len(pending):
                break           # nothing more can be read: left out
            pending = waiting
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in mine if m["name"] in values}

    def line(self, result):
        """The run's last line of standard output."""
        e2e_units = {m["name"]: m["unit"] for m in self.manifest["end_to_end"]
                     if self.cell["name"] in m.get("workloads",
                                                   [self.cell["name"]])}
        e2e = dict(result["metrics"], setup_s=self.setup_s)
        missing = set(e2e_units) - set(e2e)
        if missing:
            raise BenchError(f"the driver reported no {sorted(missing)}")
        problems = list(result.get("problems", []))
        if self.compiles_in_window:
            problems.append(f"{self.compiles_in_window} programs compiled "
                            f"inside the measured window")
        device = dict(self.device,
                      memory_peak_bytes=self.memory_peak_bytes())
        out = {"correct": not problems, "attempted": result["attempted"],
               "failed": result["failed"]}
        if self.trace_on:
            if self.trace is None:
                raise BenchError("--trace 1 and the driver traced nothing")
            out["metrics"] = self.per_layer(result, e2e)
            device.update(busy_s=self.trace.busy_s(),
                          window_s=self.trace.window_s())
            if self.chips > 1:
                device["busy_s_chip0"] = self.trace.busy_s(0)
            out["breakdown"] = self.trace.breakdown()
        else:
            out["metrics"] = {k: {"value": e2e[k], "unit": u}
                              for k, u in e2e_units.items()}
        out["device"] = device
        out["problems"] = problems
        out["notes"] = dict(result.get("notes", {}), **self.meter.snapshot(),
                            compiles_in_window=self.compiles_in_window,
                            setup_s=self.setup_s,
                            memory_counter_peak_bytes=self.counter_peak)
        return out


def run_cell(manifest_path, workload, seed, seconds, trace, t_start=None,
             bench_dir=HERE, require_tpu=True):
    """Run one cell and return its result line as a dict. `run.py` prints
    it; the tests call this with a toy manifest and `require_tpu=False`."""
    t_start = time.perf_counter() if t_start is None else t_start
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    root = os.path.dirname(os.path.abspath(manifest_path))
    if root not in sys.path:
        sys.path.insert(0, root)        # the system under test
    import check_manifest
    manifest = load_json(manifest_path)
    faults = check_manifest.check(manifest, root)
    if faults:
        raise BenchError("the manifest is invalid: " + "; ".join(faults[:5]))
    bench = Bench(manifest, workload, seed, seconds, trace, t_start, root,
                  bench_dir, require_tpu)
    driver = load_module("drivers", bench.mix["driver"], bench_dir)
    result = driver.run(bench)
    return bench.line(result)
