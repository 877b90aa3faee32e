"""Performance observability: compile/cost telemetry, resource gauges,
and the burn-triggered flight recorder.

PR 7's windowed/SLO tier can say *that* a latency objective is burning;
nothing in the tree could say *why*: XLA compiles, executable cost and
memory footprints, and device/host memory pressure were uninstrumented,
and the moment of distress left no durable artifact. This module closes
those gaps (docs/observability.md "Performance observability"):

- **Compile log** (`CompileLog` / `record_plan_compile`): every serving
  plan build and AOT jit compile records a `plan.compile` span +
  histogram and per-(pipeline fingerprint, shape bucket) compile
  counts/seconds in a bounded LRU map. A key compiled MORE than once is
  a *recompile* (`plan.recompiles`) — the signal the shape-bucket design
  exists to pin at zero on the steady-state serving path, and the plan
  cache's LRU eviction pressure made visible. This per-key compile data
  is the training signal ROADMAP item 4's learned cost model needs
  (*A Learned Performance Model for TPUs*, PAPERS.md).
- **Executable analysis** (`executable_analysis` /
  `compile_with_analysis`): captures `cost_analysis()` (flops, bytes
  accessed) and `memory_analysis()` (generated-code/argument/output/temp
  bytes) from a compiled XLA executable, degrading field-by-field where
  a backend omits them (the CPU backend reports cost but not
  `memory_stats`; TPU reports both).
- **Collective traffic** (`collective_traffic` and the `collectives`
  field of `executable_analysis`): per-executable collective ops/bytes
  (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all) parsed from the COMPILED module's HLO — the COMM_TRAFFIC
  account promoted from the bench-only `__graft_entry__` harness into
  the compile log, so the numbers ride every recorded fit and merge
  fleet-wide through the `plan.collective_{ops,bytes}` counters.
- **AotCache**: a per-shape AOT jit cache for training-loop executables
  (the distributed GBDT tree/chunk steps): the FIRST call per shape
  signature lowers and compiles through the compile log — cost analysis
  and collective traffic recorded on the executable actually used, no
  double compile — and later calls dispatch to the cached executable.
- **Resource gauges** (`sample_resource_gauges`): per-device
  `memory_stats()` bytes-in-use/peak and host RSS into gauges, sampled
  on every exposition scrape — fleet scrapes carry memory headroom next
  to latency, and `TelemetryPoller` retains the series. jax is only
  touched if the process already imported it (a scrape must never pay a
  cold jax import on the ingress loop thread).
- **Flight recorder** (`FlightRecorder`): when an SLO verdict
  TRANSITIONS to burning (or on demand via `GET /debug/bundle`), dump a
  bounded, rate-limited debug bundle — span ring JSONL, pending tail
  traces, windowed + cumulative metric snapshots, the SLO verdict,
  recent compile records, device/host memory — to a directory. Rich
  diagnostics captured at the moment of tail-latency distress rather
  than continuously (*CTA-Pipelining*, PAPERS.md). Disabled unless a
  bundle dir is configured (env ``MMLSPARK_TPU_BUNDLE_DIR`` or
  `configure_flight_recorder(bundle_dir=...)`).

`hbm_utilization` also lives here: achieved bytes/s over measured copy
bandwidth, in one place so every harness computes it the same way.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import sys
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Optional

from ..reliability.metrics import reliability_metrics
from . import names as tnames
from .spans import get_tracer, wall_now

BUNDLE_DIR_ENV = "MMLSPARK_TPU_BUNDLE_DIR"

_REASON_RE = re.compile(r"[^a-zA-Z0-9_-]+")


# --------------------------------------------------------- compile telemetry
class CompileLog:
    """Bounded per-(fingerprint, shape-bucket) compile bookkeeping.

    `record()` is the single entry point: it feeds the aggregate
    `plan.compiles`/`plan.recompiles` counters and the `plan.compile`
    histogram on the given registry (mergeable fleet-wide: counters sum),
    emits a post-hoc `plan.compile` span (joins the ambient request trace
    when one is sampled), and keeps two bounded stores — an LRU map of
    per-key count/seconds and a deque of the most recent full records
    (what the flight recorder dumps). A key seen again IS a recompile:
    either the plan cache evicted it (pressure) or shape bucketing
    failed (a bug the zero-recompile tests exist to catch)."""

    def __init__(self, max_keys: int = 512, max_records: int = 256,
                 registry=None, tracer=None):
        self._lock = threading.Lock()
        self._keys: OrderedDict = OrderedDict()
        self._records: deque = deque(maxlen=max(int(max_records), 1))
        self._max_keys = max(int(max_keys), 1)
        self._registry = registry
        self._tracer = tracer
        self._compiles = 0
        self._recompiles = 0
        self._seconds = 0.0

    def record(self, fingerprint, bucket, seconds: float,
               analysis: Optional[dict] = None,
               label: Optional[str] = None, registry=None,
               region: Optional[str] = None) -> dict:
        key = (str(fingerprint), bucket)
        if region is None:
            # a compile performed inside a utils.tracing.annotate region
            # tags itself with it — the RooflineLedger's exact join key
            # (never a guessed prefix match)
            try:
                from .profiler import current_region
                region = current_region()
            except Exception:  # noqa: BLE001 - a record without a region
                region = None
        with self._lock:
            ent = self._keys.get(key)
            recompile = ent is not None
            if ent is None:
                if len(self._keys) >= self._max_keys:
                    self._keys.popitem(last=False)
                ent = self._keys[key] = {"count": 0, "seconds": 0.0}
            else:
                self._keys.move_to_end(key)
            ent["count"] += 1
            ent["seconds"] += float(seconds)
            self._compiles += 1
            self._seconds += float(seconds)
            if recompile:
                self._recompiles += 1
            rec = {"fingerprint": str(fingerprint), "bucket": bucket,
                   "seconds": float(seconds), "count": ent["count"],
                   "recompile": recompile, "t": wall_now(),
                   "label": label, "region": region,
                   "analysis": analysis or None}
            self._records.append(rec)
        if registry is None:
            registry = self._registry
        reg = registry if registry is not None else reliability_metrics
        reg.inc(tnames.PLAN_COMPILES)
        if recompile:
            reg.inc(tnames.PLAN_RECOMPILES)
        colls = (analysis or {}).get("collectives") or {}
        if colls:
            # COMM_TRAFFIC-style account rides the fleet-mergeable
            # counters (sums across workers); per-kind detail stays on
            # the record itself
            reg.inc(tnames.PLAN_COLLECTIVE_OPS,
                    sum(int(v.get("ops", 0)) for v in colls.values()))
            reg.inc(tnames.PLAN_COLLECTIVE_BYTES,
                    sum(int(v.get("bytes", 0)) for v in colls.values()))
        reg.observe_ms(tnames.PLAN_COMPILE, float(seconds) * 1000.0)
        tracer = self._tracer if self._tracer is not None else get_tracer()
        tracer.record(tnames.PLAN_COMPILE_SPAN,
                      duration_ms=float(seconds) * 1000.0,
                      attrs={"fingerprint": str(fingerprint)[:16],
                             "bucket": str(bucket),
                             "recompile": recompile})
        return rec

    def per_key(self) -> dict:
        """{"<fingerprint>@<bucket>": {"count", "seconds"}} — the
        autotuner's per-key training rows."""
        with self._lock:
            return {f"{fp}@{bucket}": dict(v)
                    for (fp, bucket), v in self._keys.items()}

    def records(self) -> list:
        """Most recent full records, oldest first (bounded)."""
        with self._lock:
            return list(self._records)

    def stats(self) -> dict:
        with self._lock:
            return {"compiles": self._compiles,
                    "recompiles": self._recompiles,
                    "seconds": self._seconds,
                    "keys": len(self._keys)}

    def clear(self) -> None:
        with self._lock:
            self._keys.clear()
            self._records.clear()
            self._compiles = 0
            self._recompiles = 0
            self._seconds = 0.0


_default_log = CompileLog()


def get_compile_log() -> CompileLog:
    return _default_log


def record_plan_compile(fingerprint, bucket, seconds: float,
                        analysis: Optional[dict] = None,
                        label: Optional[str] = None,
                        registry=None) -> dict:
    """Record one plan build / jit compile into the process-default
    CompileLog (io/plan.py's builder calls this). `registry` routes the
    counters/histogram to a private registry (a ServingTransform built
    with `metrics=`); the recompile bookkeeping stays in the shared log
    either way."""
    return _default_log.record(fingerprint, bucket, seconds,
                               analysis=analysis, label=label,
                               registry=registry)


def compile_stats() -> dict:
    """Aggregate compile counters of the process-default log (bench rides
    this into every BENCH output line)."""
    return _default_log.stats()


# ------------------------------------------------------- collective traffic
_HLO_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
              "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
              "pred": 1}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\w+\[[^\]]*\][^ ]*|\([^)]*\)))\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def collective_traffic(hlo_text: str) -> dict:
    """Count collective ops and their payload bytes in compiled HLO:
    {kind: {"ops": n, "bytes": b}}. Bytes are per-device
    per-instruction-execution (instructions inside loops count once —
    pair with analytic per-step formulas where a loop trip count
    matters). Promoted from the bench-only `__graft_entry__` harness so
    every recorded executable carries the COMM_TRAFFIC account."""
    out: dict = {}
    for shapes, kind in _COLLECTIVE_RE.findall(hlo_text):
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes):
            if dt not in _HLO_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _HLO_BYTES[dt]
        ent = out.setdefault(kind, {"ops": 0, "bytes": 0})
        ent["ops"] += 1
        ent["bytes"] += nbytes
    return out


# ----------------------------------------------------------------- scope map
# `jax.named_scope` lands in the compiled instructions' metadata
# (`metadata={op_name="jit(f)/.../vmap(lm.mlp)/dot_general"}`), fusions
# included, and a device trace names each event by its instruction's text
# (`%fusion.123 = ...`): the instruction name is the join key between a
# capture and the regions of telemetry/names.py.
_SCOPED_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name=\"([^\"]*)\"", re.M)
FWD, BWD, REMAT = "fwd", "bwd", "remat"


def region_of(op_name: str, regions=None) -> Optional[str]:
    """The region an `op_name` path lies in: scopes nest, so the innermost
    token that occurs, and where one name starts another at the same place
    the longer (`lm.attn.flash` over `lm.attn`); None when the path names
    no region."""
    best, best_at = None, -1
    for region in (tnames.DEVICE_REGIONS if regions is None else regions):
        at = op_name.rfind(region)
        if at >= 0 and (at, len(region)) > (best_at, len(best or "")):
            best, best_at = region, at
    return best


def scope_map(hlo_text: str, regions=None) -> dict:
    """{instruction name: (region, "fwd" | "bwd" | "remat")} of an
    optimized HLO module. `transpose(` in the path marks the backward pass
    and `rematted_computation` the forward that `jax.checkpoint` runs
    again inside it; an instruction whose metadata names no region (or
    that has none) maps to nothing."""
    out: dict = {}
    for name, op_name in _SCOPED_INSTRUCTION_RE.findall(hlo_text):
        region = region_of(op_name, regions)
        if region is not None:
            out[name] = (region,
                         REMAT if "rematted_computation" in op_name
                         else BWD if "transpose(" in op_name else FWD)
    return out


def region_instruction_counts(scopes: dict) -> dict:
    """{region: instructions} of a scope map: what a compile record keeps,
    so a program whose scopes were lost shows in the compile log."""
    out: dict = {}
    for region, _direction in scopes.values():
        out[region] = out.get(region, 0) + 1
    return out


_MAX_PROGRAMS = 16
_programs: OrderedDict = OrderedDict()   # label -> [thunk or weak ref, map]
_programs_lock = threading.Lock()


def register_program(label: str, thunk) -> None:
    """Name a compiled program that a reader of captures may ask about.
    `thunk()` returns its optimized HLO text, a compiled executable
    (`as_text()`), a ready scope map, or None once the program is gone;
    it is not called until `scope_maps()` is, so registering costs one
    dict insert. A bound method is held weakly: the registry keeps no
    trainer or cache alive. Registering a label again replaces it; the
    registry keeps the newest `_MAX_PROGRAMS`."""
    ref = (weakref.WeakMethod(thunk) if hasattr(thunk, "__func__")
           else (lambda: thunk))
    with _programs_lock:
        _programs.pop(label, None)
        _programs[label] = [ref, None]
        while len(_programs) > _MAX_PROGRAMS:
            _programs.popitem(last=False)


def scope_maps() -> dict:
    """{label: scope map} of the registered programs that are still alive.
    The first call per program lowers, compiles (with a persistent cache:
    fetches) and parses; the map is kept. A thunk that fails or whose
    owner is gone drops out; this never raises."""
    with _programs_lock:
        entries = list(_programs.items())
    out = {}
    for label, entry in entries:
        if entry[1] is None:
            thunk = entry[0]()
            try:
                got = thunk() if thunk is not None else None
                if got is not None and not isinstance(got, (str, dict)):
                    got = got.as_text()
                entry[1] = scope_map(got) if isinstance(got, str) else got
            except Exception:  # noqa: BLE001 - a program that cannot be
                entry[1] = None  # lowered again has no map
            if entry[1] is None:
                with _programs_lock:
                    if _programs.get(label) is entry:
                        del _programs[label]
                continue
        out[label] = entry[1]
    return out


def merged_scope_map(maps: Optional[dict] = None) -> tuple:
    """(one {instruction: (region, direction)} over every program,
    the instruction names that two programs map differently). Those are
    left out of the map: a capture cannot say which program ran them."""
    merged: dict = {}
    conflicts = set()
    for scopes in (scope_maps() if maps is None else maps).values():
        for name, where in scopes.items():
            where = tuple(where)
            if merged.setdefault(name, where) != where:
                conflicts.add(name)
    for name in conflicts:
        del merged[name]
    return merged, sorted(conflicts)


_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,")
_MODULE_NAME_RE = re.compile(r"^HloModule [^,\n]*")


def donation_aliases(hlo_text: str) -> tuple:
    """Flattened parameter numbers donated to outputs, parsed from the
    compiled module header's `input_output_alias={ {0}: (1, {}, ...) }`
    (each entry is `{output}: (param, {param_index}[, kind])`). Returns
    () when the module has no aliasing — shared by the perf ledger and
    the semantic analyzer's donation checker."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return ()
    i = start + len("input_output_alias={")
    depth, j = 1, i
    while j < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[j], 0)
        j += 1
    return tuple(sorted({int(m) for m in
                         _ALIAS_PARAM_RE.findall(hlo_text[i:j - 1])}))


def hlo_fingerprint(hlo_text: str) -> str:
    """Content hash of an HLO/StableHLO module with the (arbitrary)
    module name normalized away — two lowerings are THE SAME executable
    iff their fingerprints match (the semantic executable-identity
    checker's unit of comparison)."""
    return hashlib.sha1(
        _MODULE_NAME_RE.sub("HloModule m", hlo_text,
                            count=1).encode()).hexdigest()


# ------------------------------------------------------ executable analysis
_COST_FIELDS = (("flops", "flops"),
                ("bytes accessed", "bytes_accessed"),
                ("transcendentals", "transcendentals"),
                ("optimal_seconds", "optimal_seconds"))
_MEM_FIELDS = (("generated_code_size_in_bytes", "generated_code_bytes"),
               ("argument_size_in_bytes", "argument_bytes"),
               ("output_size_in_bytes", "output_bytes"),
               ("alias_size_in_bytes", "alias_bytes"),
               ("temp_size_in_bytes", "temp_bytes"))


def executable_analysis(compiled, collectives: bool = True) -> dict:
    """Cost/memory footprint of a compiled XLA executable, field by
    field, skipping anything the backend omits (the contract: NEVER
    raise, possibly return {}). `peak_bytes` is derived as the sum of
    the reported argument/output/temp/code components — a lower bound
    on live bytes, labeled by construction rather than guessed.
    `collectives` (default on) also parses the optimized HLO for the
    per-kind collective ops/bytes account (`collectives` key, only
    present when the module actually contains collectives) and for the
    instructions per region (`regions` key, `scope_map`; absent when no
    instruction carries a region, which is how a program whose scopes
    were lost shows in the compile log)."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend may not implement it
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        for src, dst in _COST_FIELDS:
            v = ca.get(src)
            if isinstance(v, (int, float)):
                out[dst] = float(v)
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        ma = None
    if ma is not None:
        peak = 0.0
        have_peak = False
        for src, dst in _MEM_FIELDS:
            v = getattr(ma, src, None)
            if isinstance(v, (int, float)):
                out[dst] = float(v)
                if dst != "alias_bytes":
                    peak += float(v)
                    have_peak = True
        if have_peak:
            out["peak_bytes"] = peak
    if collectives:
        try:
            text = compiled.as_text()
        except Exception:  # noqa: BLE001 - a backend without HLO text
            text = ""
        traffic = collective_traffic(text)
        if traffic:
            out["collectives"] = traffic
        regions = region_instruction_counts(scope_map(text))
        if regions:
            out["regions"] = regions
    return out


def compile_with_analysis(fn, *args, label: Optional[str] = None,
                          fingerprint: Optional[str] = None,
                          bucket=None, log: Optional[CompileLog] = None,
                          **jit_kwargs):
    """AOT-compile `fn` for `args` (jit -> lower -> compile), timing the
    compile and recording it — with the executable's cost/memory
    analysis — into the compile log. Returns the compiled executable
    (callable with same-shaped args). This is the module-level-jit
    analog of the serving plan build: one call site gives a kernel a
    `plan.compile` span, per-(fingerprint, bucket) counters, and cost
    data the autotuner can learn from."""
    import jax
    t0 = time.perf_counter()
    lowered = jax.jit(fn, **jit_kwargs).lower(*args)
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    if bucket is None:
        shapes = []
        for a in args:
            shape = getattr(a, "shape", None)
            shapes.append("x".join(str(d) for d in shape)
                          if shape is not None else type(a).__name__)
        bucket = ",".join(shapes) or "scalar"
    fp = fingerprint or label or getattr(fn, "__qualname__", None) or "jit"
    analysis = executable_analysis(compiled)
    (log if log is not None else _default_log).record(
        fp, bucket, seconds, analysis=analysis, label=label or fp)
    return compiled


class AotCache:
    """Per-shape AOT jit cache that records every compile it performs.

    The serving plan cache gave inference zero-recompile telemetry; the
    training loops still compiled through bare `jax.jit`, invisible to
    the compile log. Wrapping a step function in an AotCache keeps ONE
    compile per (shape, dtype, sharding) signature — the first call per
    signature lowers and compiles (jit -> lower -> compile), records the
    executable's cost analysis AND collective traffic into the compile
    log, and every later call dispatches straight to the cached
    executable. A signature compiled twice (cache pressure, a renamed
    fingerprint) counts `plan.recompiles`, same discipline as serving.

        step = AotCache(train_step_fn, label="gbdt.tree.data_parallel")
        tree, delta = step(bins, grad, hess, fmask, count_w)
    """

    def __init__(self, fn, label: str, fingerprint: Optional[str] = None,
                 log: Optional["CompileLog"] = None, registry=None,
                 max_entries: int = 32, **jit_kwargs):
        self._fn = fn
        self.label = label
        self.fingerprint = fingerprint or label
        self._log = log
        self._registry = registry
        self._jit_kwargs = jit_kwargs
        self._max = max(int(max_entries), 1)
        self._lock = threading.Lock()
        self._compiled: OrderedDict = OrderedDict()
        self._jitted = None

    @property
    def fn(self):
        """The wrapped (un-jitted) step function — the semantic analyzer
        lowers the SAME callable the cache compiles, so its contract
        checks cover the executable that actually runs."""
        return self._fn

    @staticmethod
    def _sig(args) -> tuple:
        sig = []
        for a in args:
            shape = getattr(a, "shape", None)
            if shape is None:
                sig.append(("py", type(a).__name__))
                continue
            sig.append((tuple(shape), str(getattr(a, "dtype", "?")),
                        getattr(a, "sharding", None)))
        return tuple(sig)

    @staticmethod
    def _bucket(args) -> str:
        shapes = []
        for a in args:
            shape = getattr(a, "shape", None)
            shapes.append("x".join(str(d) for d in shape)
                          if shape is not None else type(a).__name__)
        return ",".join(shapes) or "scalar"

    def __call__(self, *args):
        key = self._sig(args)
        with self._lock:
            compiled = self._compiled.get(key)
            if compiled is not None:
                self._compiled.move_to_end(key)
        if compiled is None:
            compiled = self._compile(key, args)
        return compiled(*args)

    def _compile(self, key, args):
        import jax
        with self._lock:
            if self._jitted is None:
                self._jitted = jax.jit(self._fn, **self._jit_kwargs)
            jitted = self._jitted
        # compile OUTSIDE the lock (minutes-long XLA runs must not
        # serialize an unrelated shape's dispatch); two threads racing
        # the same key cost one duplicate compile, last one wins
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        seconds = time.perf_counter() - t0
        analysis = executable_analysis(compiled)
        log = self._log if self._log is not None else _default_log
        log.record(self.fingerprint, self._bucket(args), seconds,
                   analysis=analysis, label=self.label,
                   registry=self._registry)
        with self._lock:
            self._compiled[key] = compiled
            while len(self._compiled) > self._max:
                self._compiled.popitem(last=False)
        # what the cache already holds, by a weak reference to the cache:
        # an evicted or collected executable reads as gone
        cache = weakref.ref(self)

        def text():
            held = cache()
            held = held._compiled.get(key) if held is not None else None
            return held.as_text() if held is not None else None
        register_program(f"{self.label}[{self._bucket(args)}]", text)
        return compiled


# -------------------------------------------------------------- bench math
def hbm_utilization(bytes_per_sec: float, copy_gbps: float) -> float:
    """Achieved memory traffic over MEASURED copy bandwidth — the bench
    honesty metric (a throughput claim without it can hide a 50x
    memory-bound gap). 0.0 when bandwidth wasn't measured."""
    if copy_gbps is None or copy_gbps <= 0.0:
        return 0.0
    return float(bytes_per_sec) / (float(copy_gbps) * 1e9)


# ---------------------------------------------------------- resource gauges
def _host_rss_bytes() -> int:
    """Current resident set size. /proc on Linux; getrusage peak as the
    portable fallback (labeled the same — headroom math wants 'at least
    this much is held')."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        try:
            import resource
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:  # noqa: BLE001
            return 0


def sample_resource_stats() -> dict:
    """Raw device/host memory snapshot (what memory.json in a flight
    bundle holds). Devices are only enumerated when jax is ALREADY
    imported — sampling must never trigger a cold jax import on the
    serving ingress thread — and `memory_stats()` may be None per device
    (the CPU backend); both degrade to an empty/partial report."""
    out = {"t": wall_now(), "host_rss_bytes": _host_rss_bytes(),
           "devices": []}
    if "jax" in sys.modules:
        try:
            import jax
            for i, d in enumerate(jax.local_devices()):
                try:
                    stats = d.memory_stats()
                except Exception:  # noqa: BLE001
                    stats = None
                out["devices"].append(
                    {"ordinal": i,
                     "platform": getattr(d, "platform", "unknown"),
                     "stats": dict(stats) if stats else None})
        except Exception:  # noqa: BLE001 - a broken backend loses gauges,
            pass           # never a scrape
    return out


def sample_resource_gauges(registry=None) -> dict:
    """Sample device/host memory into gauges on `registry` (default: the
    process registry). Called on every exposition scrape, so
    `scrape_cluster` and the TelemetryPoller carry memory headroom next
    to latency; gauges merge with MAX across workers (worst headroom
    wins, same discipline as queue depth)."""
    reg = registry if registry is not None else reliability_metrics
    stats = sample_resource_stats()
    reg.set_gauge(tnames.HOST_RSS_BYTES, stats["host_rss_bytes"])
    total_use = 0.0
    total_peak = 0.0
    have = False
    for dev in stats["devices"]:
        ms = dev["stats"]
        if not ms:
            continue
        use = ms.get("bytes_in_use")
        peak = ms.get("peak_bytes_in_use")
        if isinstance(use, (int, float)):
            reg.set_gauge(tnames.device_mem_in_use(dev["ordinal"]), use)
            total_use += use
            have = True
        if isinstance(peak, (int, float)):
            reg.set_gauge(tnames.device_mem_peak(dev["ordinal"]), peak)
            total_peak += peak
            have = True
    if have:
        reg.set_gauge(tnames.DEVICE_MEM_BYTES_IN_USE, total_use)
        reg.set_gauge(tnames.DEVICE_MEM_PEAK_BYTES, total_peak)
    return stats


# ---------------------------------------------------------- flight recorder
class FlightRecorder:
    """Bounded, rate-limited debug-bundle dumper.

    Triggers: `SLOEngine.verdict()` notifies `on_verdict` — a verdict
    TRANSITIONING to burning dumps once (staying burning does not; the
    next transition re-arms after it clears); `GET /debug/bundle` calls
    `dump("on-demand")` directly. Both share one rate limit
    (`min_interval_s`, default 60 s) counted under
    `telemetry.bundle.suppressed`, and at most `max_bundles` bundle
    directories are kept (oldest pruned by mtime).

    The dump itself is synchronous and bounded — a span ring, pending
    tail traces, two metric snapshots, the verdict, recent compile
    records, and a memory sample; a few MB of local JSON, written with
    no lock held — deliberately simple enough to run from the /slo or
    /debug handler without a worker thread, so the burn->bundle path is
    deterministic under a seeded fault schedule.

    Disabled (every call a cheap no-op) until a bundle dir is set via
    env ``MMLSPARK_TPU_BUNDLE_DIR`` or `configure(bundle_dir=...)`."""

    def __init__(self, bundle_dir: Optional[str] = None,
                 min_interval_s: float = 60.0, max_bundles: int = 8,
                 window_s: float = 60.0, registry=None, tracer=None,
                 compile_log: Optional[CompileLog] = None,
                 profile_on_burn: bool = False):
        if bundle_dir is None:
            bundle_dir = os.environ.get(BUNDLE_DIR_ENV) or None
        self.bundle_dir = bundle_dir
        self.min_interval_s = float(min_interval_s)
        self.max_bundles = max(int(max_bundles), 1)
        self.window_s = float(window_s)
        # arm a device-profile capture on the same burn transition that
        # dumped the bundle (telemetry/profiler.py; a no-op until a
        # profile dir is configured, absorbed on failure — the bundle
        # outranks the profile)
        self.profile_on_burn = bool(profile_on_burn)
        self._registry = registry
        self._tracer = tracer
        self._compile_log = compile_log
        self._lock = threading.Lock()
        self._seq = 0
        self._last_dump: Optional[float] = None
        # per-trigger-source burn latches ("local" for the process SLO
        # engine, "fleet" for the poller's merged verdict): a burn is one
        # incident per source, and the sources must not mask each other
        self._burn_state: dict = {}

    @property
    def enabled(self) -> bool:
        return self.bundle_dir is not None

    def configure(self, bundle_dir=None, min_interval_s: Optional[float]
                  = None, max_bundles: Optional[int] = None,
                  window_s: Optional[float] = None,
                  profile_on_burn: Optional[bool] = None
                  ) -> "FlightRecorder":
        """Reconfigure in place (None leaves a knob untouched; pass
        bundle_dir="" to disable)."""
        with self._lock:
            if bundle_dir is not None:
                self.bundle_dir = bundle_dir or None
            if min_interval_s is not None:
                self.min_interval_s = float(min_interval_s)
            if max_bundles is not None:
                self.max_bundles = max(int(max_bundles), 1)
            if window_s is not None:
                self.window_s = float(window_s)
            if profile_on_burn is not None:
                self.profile_on_burn = bool(profile_on_burn)
        return self

    # -- triggers ------------------------------------------------------------
    def on_verdict(self, verdict: dict, reason: str = "slo-burn",
                   source: str = "local") -> Optional[dict]:
        """SLO hook: dump once per ok->burning transition, per trigger
        `source` (the process engine and the poller's fleet verdict each
        get their own latch). The latch only engages on a SUCCESSFUL
        dump — a transition whose dump was rate-limit-suppressed or
        failed is retried on the next burning verdict, so the one bundle
        the feature exists for is not silently lost to an earlier
        on-demand dump's rate-limit slot. Never raises."""
        if not self.enabled or not isinstance(verdict, dict):
            return None
        burning = bool(verdict.get("burning"))
        with self._lock:
            fire = burning and not self._burn_state.get(source, False)
            if not burning:
                self._burn_state[source] = False   # incident over: re-arm
        if not fire:
            return None
        manifest = None
        try:
            manifest = self.dump(reason, verdict=verdict)
        except Exception:  # noqa: BLE001 - verdict readers must survive
            manifest = None
        if manifest is not None:
            with self._lock:
                self._burn_state[source] = True
            if self.profile_on_burn:
                # the burn latch also arms ONE device-profile capture:
                # the bundle says WHAT burned, the profile says which op
                # burned it. Rate-limited by the profile session's own
                # slot; absorbed — the successful bundle already latched.
                try:
                    from .profiler import get_profile_session
                    get_profile_session().capture(reason=str(reason))
                except Exception:  # noqa: BLE001 - bundle outranks profile
                    pass
        return manifest

    # -- the dump ------------------------------------------------------------
    def dump(self, reason: str, verdict: Optional[dict] = None
             ) -> Optional[dict]:
        """Write one bundle; returns the manifest dict, or None when the
        recorder is disabled or the rate limit suppressed the dump.
        Raises on a failed write (OSError for an unwritable dir,
        TypeError for unserializable content) — with the rate-limit slot
        ROLLED BACK and the partial bundle dir removed, so a failed dump
        never shadows the next trigger for min_interval_s."""
        if not self.enabled:
            return None
        reg = self._registry if self._registry is not None \
            else reliability_metrics
        now = time.monotonic()
        with self._lock:
            if (self._last_dump is not None
                    and now - self._last_dump < self.min_interval_s):
                suppressed = True
            else:
                suppressed = False
                prev_last = self._last_dump
                self._last_dump = now
                seq = self._seq
                self._seq += 1
        if suppressed:
            reg.inc(tnames.TELEMETRY_BUNDLE_SUPPRESSED)
            return None
        # everything below runs with NO lock held: file I/O must never
        # serialize verdict evaluation or a second trigger's check
        tracer = self._tracer if self._tracer is not None else get_tracer()
        log = self._compile_log if self._compile_log is not None \
            else _default_log
        tag = _REASON_RE.sub("-", str(reason))[:48] or "bundle"
        path = os.path.join(self.bundle_dir,
                            f"bundle-{os.getpid()}-{seq:04d}-{tag}")
        if verdict is None:
            try:
                from .slo import get_engine
                # notify=False: capturing the verdict for the bundle must
                # not re-trigger the recorder mid-dump
                verdict = get_engine().verdict(notify=False)
            except Exception:  # noqa: BLE001 - bundle without a verdict
                verdict = None
        files = []

        def _jsonl(name: str, rows: list) -> None:
            with open(os.path.join(path, name), "w") as f:
                for row in rows:
                    f.write(json.dumps(row) + "\n")
            files.append(name)

        def _json(name: str, obj) -> None:
            with open(os.path.join(path, name), "w") as f:
                json.dump(obj, f, indent=1)
            files.append(name)

        try:
            os.makedirs(path, exist_ok=True)
            _jsonl("spans.jsonl", tracer.finished())
            _jsonl("pending.jsonl", tracer.pending_tail())
            _json("metrics.json", reg.export_state())
            _json("metrics_window.json",
                  reg.export_state(window_s=self.window_s))
            _json("slo.json", verdict)
            _json("compiles.json", {"stats": log.stats(),
                                    "per_key": log.per_key(),
                                    "records": log.records()})
            _json("memory.json", sample_resource_stats())
            # the training-side step-phase breakdown (empty {} on pure
            # serving processes): a burning TRAINING run's bundle then
            # says where its steps' time went
            from .goodput import default_snapshot
            _json("goodput.json", default_snapshot())
            # per-region roofline rows (telemetry/profiler.py): measured
            # region time joined with compile-log cost against peaks —
            # the bundle answers "where does the headroom live" per
            # kernel, not just whole-fit ({} until anything was noted)
            from .profiler import roofline_export
            _json("roofline.json", roofline_export())
            # model-quality state (telemetry/quality.py): per-feature
            # drift rows + streaming-eval state, so a burning bundle
            # says whether the fleet is also still PREDICTING well
            # ({"active": false} on processes without a reference)
            from .quality import export_quality
            _json("quality.json", export_quality())
            # deployment state (telemetry/lineage.py): which model
            # versions this process serves, their roles, per-version
            # metric splits, and the canary readout — a bundle tripped
            # by a canary watch rule NAMES the candidate it indicts
            # ({"versions": [], ...} on processes that never served)
            from .lineage import export_versions
            _json("versions.json", export_versions())
            manifest = {"reason": str(reason), "tag": tag, "seq": seq,
                        "pid": os.getpid(), "t": wall_now(), "path": path,
                        "files": files, "tracer": tracer.stats(),
                        "burning": (verdict or {}).get("burning")}
            _json("manifest.json", manifest)
        except Exception:
            # ANY failed dump — unwritable dir, a non-JSON-serializable
            # span attr or verdict value — gives the rate-limit slot back
            # (a failed dump must not shadow the next trigger) and clears
            # its partial bundle dir, then lets the caller report it
            # (on_verdict absorbs, /debug/bundle 500s)
            with self._lock:
                if self._last_dump == now:
                    self._last_dump = prev_last
            shutil.rmtree(path, ignore_errors=True)
            raise
        self._prune()
        reg.inc(tnames.TELEMETRY_BUNDLE_DUMPS)
        tracer.event(tnames.TELEMETRY_BUNDLE_EVENT, reason=str(reason),
                     path=path)
        return manifest

    def _prune(self) -> None:
        """Keep the newest `max_bundles` bundle dirs (mtime order);
        best-effort — a concurrent prune losing a race is harmless."""
        try:
            entries = [os.path.join(self.bundle_dir, e)
                       for e in os.listdir(self.bundle_dir)
                       if e.startswith("bundle-")]
            entries.sort(key=lambda p: (os.path.getmtime(p), p))
            for stale in entries[:-self.max_bundles]:
                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_flight_recorder() -> FlightRecorder:
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            _recorder = FlightRecorder()
        return _recorder


def configure_flight_recorder(**kwargs) -> FlightRecorder:
    """Configure the process-default flight recorder (see
    `FlightRecorder.configure`)."""
    return get_flight_recorder().configure(**kwargs)
