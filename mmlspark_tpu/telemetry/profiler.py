"""Device-profile observability: triggered on-device capture, per-op
parse, and per-region roofline attribution.

ROADMAP item 1 made `hbm_utilization` the honesty metric of the
histogram roofline chase, but the tree could only compute it for the
WHOLE fit — a percent or two, with nothing able to say which op burns
the rest. This module is the fourth observability tier
(docs/observability.md "Device profiling & roofline"): the sensors that
turn "the fit is memory-idle" into "gbdt.hist achieves X% of peak HBM
and gbdt.route none of it" — the per-op (cost-analysis, measured-time)
pairs *A Learned Performance Model for TPUs* (PAPERS.md) trains on and
the ROADMAP item-4 autotuner's measured rows.

- **ProfileSession**: programmatic `jax.profiler` start/stop with the
  flight-recorder discipline — disabled until a profile dir is
  configured (env ``MMLSPARK_TPU_PROFILE_DIR``), min-interval rate
  limiting (`telemetry.profile.suppressed`), bounded retention (oldest
  capture dirs pruned), and failure ROLLBACK (a failed capture gives the
  rate-limit slot back and removes its partial dir, so it can never
  shadow the next trigger). Triggers: `GET /debug/profile?ms=N` (same
  429/503/500 contract as `/debug/bundle`), a `StragglerDetector` flag
  transition on the flagged host, an SLO burn via the recorder latch
  (`FlightRecorder(profile_on_burn=True)`), and `utils.tracing.trace`
  (the explicit block-capture API, rebased on `session()`).
- **parse_trace**: the captured trace (the ``.xplane.pb`` under
  ``plugins/profile/*/``, read through `jax.profiler.ProfileData`) parsed
  into per-op records ``{op, region, direction, occurrences,
  self_time_us}`` from the DEVICE planes. Graceful degradation, mirroring
  `executable_analysis`'s never-raise contract: on the CPU backend device
  planes are absent and the table is empty — capture still succeeds,
  regions still carry their host-noted walls. A device event is named by
  its HLO instruction's text and carries nothing of a `jax.named_scope`
  (v5e traces, PR 25), so `region` resolves by instruction name through
  the scope maps of the registered programs
  (`telemetry.perf.scope_maps`): the LM step's `lm.*` regions, the GBDT
  tree build's `gbdt.*`. What no map places is `UNSCOPED` and reported as
  the `telemetry.profile.unscoped_share` gauge.
- **RooflineLedger**: joins per-region measured time (device-plane
  self-time when a parse provided it, host-noted wall otherwise) with
  `CompileLog` cost analysis into achieved FLOP/s and HBM bytes/s
  against peak (env/chip table, `resolve_peaks`). Exported as
  `op.<region>.{hbm_util,flops_util}` gauges and the `roofline.json`
  section of every flight bundle. A side that is unknown (no peak declared, no cost
  analysis for the region) leaves its gauge ABSENT — never guessed,
  same contract as MFU.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import glob
import json
import os
import re
import shutil
import statistics
import sys
import threading
import time
from typing import NamedTuple, Optional

try:
    import resource
    _RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
except ImportError:      # no getrusage on this platform: the counts read 0
    resource = None

from ..reliability.metrics import reliability_metrics
from . import names as tnames
from .spans import get_tracer, wall_now

PROFILE_DIR_ENV = "MMLSPARK_TPU_PROFILE_DIR"
# default capture window for TRIGGERED captures (ms); explicit callers
# and ?ms=N override
PROFILE_MS_ENV = "MMLSPARK_TPU_PROFILE_MS"
PEAK_HBM_ENV = "MMLSPARK_TPU_PEAK_HBM_GBPS"

# The region vocabulary (names and meanings in telemetry/names.py): what
# the parser attributes per-op device time to, and the keys of the roofline
# ledger / the op.<region>.* gauges. Device code stamps a region with
# jax.named_scope (trace-time only: the name lands in the compiled
# instructions' `op_name` metadata, which `telemetry.perf.scope_map` joins
# to a capture's events by instruction name; the events themselves carry
# the instruction's text and nothing of the scope). Host-side layer
# boundaries stamp utils.tracing.annotate (TraceAnnotation on the
# profiler's clock + host wall note).
REGIONS = (*tnames.DEVICE_REGIONS, *tnames.HOST_REGIONS,
           tnames.SERVING_PLAN_RUN_SPAN, tnames.TRAIN_STEP_SPAN)
RING = 256     # durations kept per region

# per-chip peaks (bf16 TFLOP/s, HBM GB/s) keyed on device_kind
# substrings — the StepClock-style fallback when no env override is set.
# Spec-sheet numbers, labeled as such in resolve_peaks()["source"].
CHIP_PEAKS = (
    ("v6e", 918.0, 1640.0),
    ("v5p", 459.0, 2765.0),
    ("v5e", 197.0, 819.0),
    ("v5 lite", 197.0, 819.0),
    ("v4", 275.0, 1228.0),
)

_REASON_RE = re.compile(r"[^a-zA-Z0-9_-]+")

# active region (utils.tracing.annotate sets it): CompileLog.record reads
# it so a compile performed inside a region lands with an exact join key
_region_var: contextvars.ContextVar = contextvars.ContextVar(
    "mmlspark_tpu_region", default=None)


def current_region() -> Optional[str]:
    """The innermost active `utils.tracing.annotate` region, or None."""
    return _region_var.get()


# ---------------------------------------------------------------- peaks
def peak_hbm_from_env() -> Optional[float]:
    """Peak HBM bytes/s from ``MMLSPARK_TPU_PEAK_HBM_GBPS`` (GB/s), or
    None — the documented degrade on hosts that never declared one."""
    raw = os.environ.get(PEAK_HBM_ENV)
    if not raw:
        return None
    try:
        gbps = float(raw)
    except ValueError:
        return None
    return gbps * 1e9 if gbps > 0 else None


def chip_peaks() -> Optional[tuple]:
    """(flops_per_s, hbm_bytes_per_s, kind) from the local device kind —
    only consulted when jax is ALREADY imported (a passive read must
    never pay a cold jax import), and only for kinds in CHIP_PEAKS."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        kind = str(getattr(jax.devices()[0], "device_kind", ""))
    except Exception:  # noqa: BLE001 - no backend: no chip peaks
        return None
    low = kind.lower()
    for token, tflops, gbps in CHIP_PEAKS:
        if token in low:
            return tflops * 1e12, gbps * 1e9, kind
    return None


def resolve_peaks(peaks: Optional[dict] = None) -> dict:
    """{"flops_per_s", "hbm_bytes_per_s", "source"} with explicit args
    > env (``MMLSPARK_TPU_PEAK_TFLOPS`` / ``MMLSPARK_TPU_PEAK_HBM_GBPS``)
    > chip table. A side nobody declared stays None — downstream
    utilization gauges are then absent, never guessed."""
    out = {"flops_per_s": None, "hbm_bytes_per_s": None, "source": None}
    if peaks:
        out["flops_per_s"] = peaks.get("flops_per_s")
        out["hbm_bytes_per_s"] = peaks.get("hbm_bytes_per_s")
        out["source"] = peaks.get("source", "explicit")
        if (out["flops_per_s"] is not None
                and out["hbm_bytes_per_s"] is not None):
            return out
    from .goodput import peak_flops_from_env
    env_flops = peak_flops_from_env()
    env_hbm = peak_hbm_from_env()
    if out["flops_per_s"] is None and env_flops is not None:
        out["flops_per_s"] = env_flops
        out["source"] = out["source"] or "env"
    if out["hbm_bytes_per_s"] is None and env_hbm is not None:
        out["hbm_bytes_per_s"] = env_hbm
        out["source"] = out["source"] or "env"
    if out["flops_per_s"] is None or out["hbm_bytes_per_s"] is None:
        chip = chip_peaks()
        if chip is not None:
            if out["flops_per_s"] is None:
                out["flops_per_s"] = chip[0]
            if out["hbm_bytes_per_s"] is None:
                out["hbm_bytes_per_s"] = chip[1]
            out["source"] = out["source"] or f"chip-table:{chip[2]}"
    return out


# ----------------------------------------------------------- trace parse
_MAX_OP_RECORDS = 512
# what `parse_trace` calls device time that no scope map puts in a region;
# it is reported (the `telemetry.profile.unscoped_share` gauge), never
# folded into a region, and the ledger does not ingest it
UNSCOPED = "unscoped"
_OPS_LINE = "XLA Ops"
_INSTRUCTION_RE = re.compile(r"^%?([^\s=]+) = ")


def _xplane_files(log_dir: str) -> list:
    """The capture's ``*.xplane.pb`` files, newest profile run first (jax
    writes ``plugins/profile/<timestamp>/<host>.xplane.pb``)."""
    if os.path.isfile(log_dir):      # one recorded file, handed in itself
        return [log_dir]
    runs = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*")), reverse=True)
    for run in runs:
        files = sorted(glob.glob(os.path.join(run, "*.xplane.pb")))
        if files:
            return files
    return []


def instruction_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`: a device
    event is named by its HLO instruction's text, and the instruction's
    name is the key of `telemetry.perf.scope_map`."""
    m = _INSTRUCTION_RE.match(event_name)
    return m.group(1) if m else event_name


def self_times(events) -> dict:
    """{event name: [self ns, occurrences]} of one device line's events
    (`(name, start_ns, duration_ns)`): a `while` or `conditional` event
    spans its body's events, so an event's own time is its duration less
    its children's, and the self times add up to the line's busy time."""
    out: dict = {}
    stack: list = []     # [end_ns, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            ent = out.setdefault(name, [0, 0])
            ent[0] += max(own, 0)
            ent[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    close(float("inf"))
    return out


def parse_trace(log_dir: str, scopes: Optional[dict] = None,
                registry=None, limit: Optional[int] = _MAX_OP_RECORDS
                ) -> list:
    """Per-op records from a captured profile's DEVICE planes (the
    ``.xplane.pb`` the profiler writes, line `XLA Ops`):
    ``[{op, region, direction, occurrences, self_time_us}]``, largest
    self time first, the first `limit`. `op` is the HLO instruction's name; `region`
    and `direction` come from the scope maps of the registered programs
    (`telemetry.perf.scope_maps()`, or `scopes`, a `{label: scope map}`
    kept from the process that ran, to read a capture elsewhere); an
    instruction that no map places, or that two programs place
    differently, is `UNSCOPED`, and its share of the device self time is
    the `telemetry.profile.unscoped_share` gauge. NEVER raises (the
    `executable_analysis` contract): a missing or torn file, an
    unexpected schema, or a backend with no device planes (CPU) all
    degrade to an empty table."""
    from .perf import merged_scope_map
    ops: dict = {}
    for path in _xplane_files(log_dir):
        try:
            import jax
            planes = jax.profiler.ProfileData.from_file(path).planes
            lines = [[(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
                     for plane in planes if plane.name.startswith("/device:")
                     for line in plane.lines if line.name == _OPS_LINE]
        except Exception:  # noqa: BLE001 - torn capture: skip the file
            continue
        for events in lines:
            for name, (own, n) in self_times(events).items():
                ent = ops.setdefault(instruction_name(name), [0, 0])
                ent[0] += own
                ent[1] += n
    if not ops:
        return []
    try:
        placed, _conflicts = merged_scope_map(scopes)
    except Exception:  # noqa: BLE001 - a capture without maps still parses
        placed = {}
    records = []
    total = unscoped = 0
    for op, (own, n) in ops.items():
        region, direction = placed.get(op, (UNSCOPED, None))
        total += own
        unscoped += own if region == UNSCOPED else 0
        records.append({"op": op, "region": region, "direction": direction,
                        "occurrences": n,
                        "self_time_us": round(own / 1e3, 3)})
    if total:
        (registry if registry is not None else reliability_metrics
         ).set_gauge(tnames.TELEMETRY_PROFILE_UNSCOPED_SHARE,
                     unscoped / total)
    records.sort(key=lambda r: (-r["self_time_us"], r["op"]))
    return records[:limit]


def region_totals(records: list) -> dict:
    """{region: {"self_time_us", "occurrences"}} rollup of a per-op
    table (what the ledger ingests after a capture)."""
    out: dict = {}
    for r in records:
        ent = out.setdefault(r.get("region", UNSCOPED),
                             {"self_time_us": 0.0, "occurrences": 0})
        ent["self_time_us"] += float(r.get("self_time_us", 0.0))
        ent["occurrences"] += int(r.get("occurrences", 0))
    return out


_MOVERS = ("sort", "gather", "scatter")


def by_instruction(log_dir: str, scopes: Optional[dict] = None,
                   steps: int = 1, top: int = 40) -> list:
    """A capture read by HLO instruction, as lines to print: the `top`
    largest instructions with region and direction, every `sort` /
    `gather` / `scatter` (a scalar one costs a millisecond on a v5e
    whatever its size: PERF.md section 7), and the regions' totals by
    direction; ms a step over `steps` traced steps. `log_dir` is a capture
    directory of `utils.tracing.trace` (or one `.xplane.pb`); `scopes` as
    for `parse_trace`: None in the process that ran the steps, else the
    `{label: scope map}` it kept (`telemetry.perf.scope_maps()`)."""
    records = parse_trace(log_dir, scopes=scopes, limit=None)
    per = 1e3 * max(int(steps), 1)

    def show(r):
        return (f"{r['self_time_us'] / per:9.3f} ms/step  "
                f"x{r['occurrences'] / max(int(steps), 1):<6g} "
                f"{r['region']:<16} {str(r['direction']):<6} {r['op']}")

    lines = [f"--- {min(top, len(records))} largest of {len(records)} "
             f"instructions"]
    lines += [show(r) for r in records[:top]]
    lines.append("--- every " + " / ".join(_MOVERS))
    lines += [show(r) for r in records
              if any(m in r["op"] for m in _MOVERS)]
    totals: dict = {}
    for r in records:
        key = (r["region"], str(r["direction"]))
        totals[key] = totals.get(key, 0.0) + r["self_time_us"] / per
    lines.append("--- regions by direction")
    lines += [f"{ms:9.3f} ms/step  {region:<16} {direction}"
              for (region, direction), ms in
              sorted(totals.items(), key=lambda kv: -kv[1])]
    lines.append(f"{sum(totals.values()):9.3f} ms/step  busy")
    return lines


def main(argv=None) -> int:
    """python -m mmlspark_tpu.telemetry.profiler <capture> [--scopes
    <json>] [--steps N] [--top N]: `by_instruction` printed."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m mmlspark_tpu.telemetry.profiler",
        description="a device capture by HLO instruction")
    ap.add_argument("capture", help="capture directory of "
                    "utils.tracing.trace, or one .xplane.pb")
    ap.add_argument("--scopes", help="JSON {label: scope map} kept from "
                    "the process that ran (telemetry.perf.scope_maps())")
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    scopes = None
    if args.scopes:
        with open(args.scopes) as f:
            scopes = json.load(f)
    print("\n".join(by_instruction(args.capture, scopes, args.steps,
                                   args.top)))
    return 0


# -------------------------------------------------------- roofline ledger
class RooflineLedger:
    """Per-region achieved-vs-peak accounting (module docstring).

    Two measurement sources feed it: `note_region` (host wall from
    `utils.tracing.annotate` — exists on every backend) and `ingest_ops`
    (device-plane self time from a parsed capture — overrides the host
    wall for regions it covers, labeled ``source: device``). Costs join
    per region from the CompileLog (records whose ``region`` tag or
    label matches) or explicitly via `set_cost` (bench's analytic
    traffic). All state is bounded: regions are a handful of names, ops
    keep the last parse only."""

    def __init__(self, registry=None, compile_log=None,
                 peaks: Optional[dict] = None):
        self._registry = registry
        self._compile_log = compile_log
        self._peaks = peaks
        self._lock = threading.Lock()
        self._host: dict = {}     # region -> [seconds, occurrences, source]
        self._rings: dict = {}    # region -> the last RING single durations
        self._steps: dict = {}    # name -> the last RING step records
        self._device: dict = {}   # region -> {"self_time_us", "occurrences"}
        self._ops: list = []      # last parsed per-op table (bounded)
        self._costs: dict = {}    # region -> {"flops", "bytes_accessed"}

    # -- measurement feeds ---------------------------------------------------
    def note_region(self, region: str, seconds: float,
                    occurrences: int = 1, source: str = "host") -> None:
        """Accumulate wall-clock region time measured OUTSIDE a device
        plane. `source` labels the provenance honestly ("host" for
        annotate walls, bench passes "bench-phase" for its in-graph
        phase programs); device-plane self time from a parse overrides
        these rows entirely."""
        s = max(float(seconds), 0.0)
        with self._lock:
            ent = self._host.setdefault(region, [0.0, 0, str(source)])
            ent[0] += s
            ent[1] += int(occurrences)
            ent[2] = str(source)
            if occurrences == 1:
                ring = self._rings.get(region)
                if ring is None:
                    ring = self._rings[region] = collections.deque(
                        maxlen=RING)
                ring.append(s)

    def durations(self, region: str) -> list:
        """The last `RING` single durations noted for `region`, oldest
        first."""
        with self._lock:
            return list(self._rings.get(region, ()))

    def note_step(self, name: str, record: "StepRecord") -> None:
        """Keep one step's record in `name`'s ring."""
        with self._lock:
            ring = self._steps.get(name)
            if ring is None:
                ring = self._steps[name] = collections.deque(maxlen=RING)
            ring.append(record)

    def step_records(self, name: str) -> list:
        """The last `RING` step records kept under `name`, oldest first."""
        with self._lock:
            return list(self._steps.get(name, ()))

    def region_stats(self, region: str) -> Optional[dict]:
        """{"count", "seconds", "median", "p95"} of a region's host notes
        (count and seconds over the ledger's life, the quantiles over the
        ring: a median and a p95 without a sampled Tracer), or None for a
        region never noted."""
        with self._lock:
            ring = sorted(self._rings.get(region, ()))
            if not ring:
                return None
            seconds, count, _source = self._host[region]
        return {"count": count, "seconds": seconds,
                "median": statistics.median(ring),
                "p95": ring[min(len(ring) - 1, int(0.95 * len(ring)))]}

    def ingest_ops(self, records: list) -> None:
        """Adopt a parsed per-op table: device-plane region totals
        REPLACE earlier device totals (a capture is a fresh window, not
        a cumulative series)."""
        totals = region_totals(records)
        totals.pop(UNSCOPED, None)
        with self._lock:
            self._ops = list(records)
            if totals:
                self._device = totals

    def set_cost(self, region: str, flops: Optional[float] = None,
                 bytes_accessed: Optional[float] = None) -> None:
        """Declare a region's PER-OCCURRENCE cost explicitly (bench's
        analytic histogram traffic; a caller that knows its executable's
        cost analysis). None leaves that side unknown."""
        with self._lock:
            ent = self._costs.setdefault(region, {})
            if flops is not None:
                ent["flops"] = float(flops)
            if bytes_accessed is not None:
                ent["bytes_accessed"] = float(bytes_accessed)

    def clear(self) -> None:
        with self._lock:
            self._host.clear()
            self._rings.clear()
            self._steps.clear()
            self._device.clear()
            self._costs.clear()
            self._ops = []

    # -- the join ------------------------------------------------------------
    def _cost_of(self, region: str) -> Optional[dict]:
        # explicit declarations win; else the newest compile record
        # tagged with (or labeled as) the region — an exact join key,
        # not a guessed prefix match
        cost = self._costs.get(region)
        if cost:
            return dict(cost)
        log = self._compile_log
        if log is None:
            from .perf import get_compile_log
            log = get_compile_log()
        for rec in reversed(log.records()):
            if rec.get("region") != region and rec.get("label") != region:
                continue
            analysis = rec.get("analysis") or {}
            out = {}
            for field in ("flops", "bytes_accessed"):
                v = analysis.get(field)
                if isinstance(v, (int, float)) and v > 0:
                    out[field] = float(v)
            if out:
                return out
        return None

    def rows(self, peaks: Optional[dict] = None) -> dict:
        """{region: row} with measured seconds/occurrences (+source),
        per-occurrence cost when known, achieved FLOP/s and HBM bytes/s,
        and utilizations when the matching peak is known. Absent keys ARE
        the degrade — a consumer must not find a guessed 0.0."""
        resolved = resolve_peaks(peaks if peaks is not None else self._peaks)
        with self._lock:
            host = {k: list(v) for k, v in self._host.items()}
            device = {k: dict(v) for k, v in self._device.items()}
            costs_known = set(self._costs)
        out: dict = {}
        for region in sorted(set(host) | set(device) | costs_known):
            if region in device:
                seconds = device[region]["self_time_us"] / 1e6
                occurrences = device[region]["occurrences"]
                source = "device"
            elif region in host:
                seconds, occurrences, source = host[region]
            else:
                continue   # a cost with no measurement yet: nothing to say
            row = {"seconds": round(seconds, 6),
                   "occurrences": int(occurrences), "source": source}
            cost = self._cost_of(region)
            if cost and seconds > 0.0:
                for field, achieved_key, peak_key, util_key in (
                        ("flops", "achieved_flops_per_s", "flops_per_s",
                         "flops_util"),
                        ("bytes_accessed", "achieved_hbm_bytes_per_s",
                         "hbm_bytes_per_s", "hbm_util")):
                    per_occ = cost.get(field)
                    if per_occ is None:
                        continue
                    row[field] = per_occ
                    achieved = per_occ * occurrences / seconds
                    row[achieved_key] = round(achieved, 1)
                    peak = resolved.get(peak_key)
                    if peak:
                        # 9 decimals: a genuinely tiny utilization (a
                        # long host wall over a fast chip, ~1e-8) must
                        # not round to a 0.0 that reads as guessed
                        row[util_key] = round(achieved / peak, 9)
            out[region] = row
        return out

    def publish(self, registry=None) -> dict:
        """Set the `op.<region>.{hbm_util,flops_util}` gauges for every
        region whose utilization is computable; absent sides set
        nothing. Returns the rows it published from."""
        reg = registry if registry is not None else (
            self._registry if self._registry is not None
            else reliability_metrics)
        rows = self.rows()
        for region, row in rows.items():
            if "hbm_util" in row:
                reg.set_gauge(tnames.op_hbm_util(region), row["hbm_util"])
            if "flops_util" in row:
                reg.set_gauge(tnames.op_flops_util(region),
                              row["flops_util"])
        return rows

    def export(self) -> dict:
        """The roofline.json body: peaks (with provenance), per-region
        rows, and the last parsed per-op table."""
        with self._lock:
            ops = list(self._ops)
        return {"t": wall_now(),
                "peaks": resolve_peaks(self._peaks),
                "regions": self.rows(),
                "ops": ops}


_default_ledger = RooflineLedger()


def get_roofline() -> RooflineLedger:
    return _default_ledger


def note_region(region: str, seconds: float) -> None:
    """Host-wall region note into the process-default ledger
    (`utils.tracing.annotate` calls this on every region exit): count,
    total seconds and the ring of the last `RING` durations. A region
    that is also a timing label (`names.HOST_REGIONS`) lands in
    `reliability_metrics` too, where `/metrics` and a benchmark driver's
    difference over its window find it."""
    _default_ledger.note_region(region, seconds)
    if region in tnames.HOST_REGIONS:
        reliability_metrics.observe(region, seconds)


def region_stats(region: str) -> Optional[dict]:
    """`RooflineLedger.region_stats` of the process-default ledger."""
    return _default_ledger.region_stats(region)


# ------------------------------------------------------------ step records
PHASES = ("gap", "h2d", "dispatch", "wait")
SLOW_RATIO = 1.03     # a step is slow over this much of the median period
SLOW_MIN_STEADY = 8   # non-compiling records a median wants before it judges


class StepRecord(NamedTuple):
    """One step as its host saw it. The four phases are seconds between
    five clock readings, so they add up to the step's period with nothing
    outside them; `gap` is None where there was no step before (or, for a
    reader, where the time before the step is not the loop's: the first
    step of a window). What stood beside the step is read once, at the
    record's end, as the difference since the record before it."""
    gap: Optional[float]
    h2d: float
    dispatch: float
    wait: float
    compiled: int        # programs the step compiled: never a slow step
    preemptions: int     # involuntary context switches of the stepping
    #                      thread (ru_nivcsw): the host ran something else
    faults: int          # its major page faults (ru_majflt)
    gc_s: float          # seconds inside generation-2 garbage collections

    @property
    def period(self) -> float:
        return (self.gap or 0.0) + self.h2d + self.dispatch + self.wait


def slow_steps(records) -> list:
    """THE slow-step rule (PR 35's `slow_steps`, and the only place it
    lives): of `records` (oldest first), those that compiled nothing and
    whose period is over `SLOW_RATIO` of the median period of the
    non-compiling records given. Each as ``{"index", "record", "period",
    "median", "loss", "lost"}``: `loss` is the period less that median,
    and `lost` puts it down to the phases by each phase's excess over its
    own median (the parts add up to the loss). A record without a gap is
    given the median one, so it can be slow in its other phases only.
    Fewer than `SLOW_MIN_STEADY` non-compiling records judge nothing."""
    steady = [r for r in records if not r.compiled]
    if len(steady) < SLOW_MIN_STEADY:
        return []
    typical = []             # the phases are a record's first fields
    for i in range(len(PHASES)):
        seen = [r[i] for r in steady if r[i] is not None]
        typical.append(statistics.median(seen) if seen else 0.0)
    took = [[m if t is None else t for t, m in zip(r, typical)]
            for r in records]
    periods = [sum(t) for t in took]
    median = statistics.median(
        p for p, r in zip(periods, records) if not r.compiled)
    out = []
    for index, r in enumerate(records):
        if r.compiled or periods[index] <= SLOW_RATIO * median:
            continue
        loss = periods[index] - median
        excess = [max(t - m, 0.0) for t, m in zip(took[index], typical)]
        scale = loss / (sum(excess) or 1.0)
        out.append({"index": index, "record": r, "period": periods[index],
                    "median": median, "loss": loss,
                    "lost": {p: e * scale for p, e in zip(PHASES, excess)}})
    return out


_gc_seconds = 0.0      # inside generation-2 collections, this process
_gc_started = None


def _on_gc(phase, info):
    """`gc.callbacks` entry: time generation-2 collections (the ones that
    walk every tracked object: tens of ms to seconds on a host that holds
    a model's arrays); younger generations return at once."""
    global _gc_seconds, _gc_started
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_started = time.perf_counter()
    elif _gc_started is not None:
        _gc_seconds += time.perf_counter() - _gc_started
        _gc_started = None


def _beside():
    """(involuntary context switches, major faults) of the calling thread
    so far, and the process's generation-2 collection seconds."""
    if resource is None:
        return 0, 0, _gc_seconds
    use = resource.getrusage(_RUSAGE_WHO)
    return use.ru_nivcsw, use.ru_majflt, _gc_seconds


class StepRecorder:
    """What `PipelinedLMTrainer.step` keeps of itself: a `StepRecord` a
    call in the default ledger's ring under "lm.step", the `lm.step.gap`
    span from the second call on, and the `lm.step.slow` /
    `lm.step.lost_seconds` counters when `slow_steps` calls the step just
    recorded slow against the ring as it then stands. `start()` at the
    step's entry, `mark()` at the two boundaries between its spans,
    `stop(compiled)` at its end: four clock readings, one `getrusage`,
    one tuple and one append a step. Always on; `clock` is an attribute
    for tests to replace."""

    def __init__(self):
        self.clock = time.perf_counter
        self._marks: list = []
        self._gap = None
        self._end = None         # clock at the last stop()
        self._was = None         # _beside() at the last stop()
        self._periods = collections.deque(maxlen=RING)

    def start(self) -> None:
        now = self.clock()
        if self._end is None:
            if _on_gc not in gc.callbacks:
                gc.callbacks.append(_on_gc)
            self._was = _beside()
        else:
            self._gap = now - self._end
            note_region(tnames.LM_STEP_GAP, self._gap)
        self._marks = [now]

    def mark(self) -> None:
        self._marks.append(self.clock())

    def stop(self, compiled: int = 0) -> StepRecord:
        t0, t1, t2 = self._marks
        self._end = now = self.clock()
        beside = _beside()
        was, self._was = self._was, beside
        record = StepRecord(self._gap, t1 - t0, t2 - t1, now - t2,
                            int(compiled), beside[0] - was[0],
                            beside[1] - was[1], beside[2] - was[2])
        _default_ledger.note_step(tnames.LM_STEP, record)
        if compiled:
            return record
        # a cheap gate (one sort of floats) before the rule, which decides
        self._periods.append(record.period)
        if record.period > SLOW_RATIO * statistics.median(self._periods):
            slow = slow_steps(step_records(tnames.LM_STEP))
            if slow and slow[-1]["record"] is record:
                reliability_metrics.inc(tnames.LM_STEP_SLOW)
                reliability_metrics.inc(tnames.LM_STEP_LOST_SECONDS,
                                        slow[-1]["loss"])
        return record


def step_records(name: str = tnames.LM_STEP) -> list:
    """The process-default ledger's last `RING` step records under `name`
    (`PipelinedLMTrainer.step` keeps its own under "lm.step"), oldest
    first."""
    return _default_ledger.step_records(name)


@contextlib.contextmanager
def region(name: str):
    """Activate `name` as the current region for the block (compile
    records made inside tag themselves with it) — the contextvar half of
    `utils.tracing.annotate`, split out so the profiler owns the key."""
    token = _region_var.set(name)
    try:
        yield
    finally:
        _region_var.reset(token)


def roofline_export() -> dict:
    """The default ledger's export — what FlightRecorder.dump writes as
    roofline.json. Never raises (a bundle without roofline beats no
    bundle)."""
    try:
        return _default_ledger.export()
    except Exception:  # noqa: BLE001
        return {}


def _stamp_context(log_dir: str, ctx, registry=None) -> bool:
    """Stamp a profile dir with the active trace id
    (`trace_context.json`) so the on-disk artifact and the span log
    cross-reference each other. The capture outranks the stamp — but the
    old silent `pass` on failure hid real breakage, so a failed stamp is
    counted under `telemetry.profile.stamp_errors`."""
    reg = registry if registry is not None else reliability_metrics
    try:
        with open(os.path.join(log_dir, "trace_context.json"), "w") as f:
            json.dump({"trace_id": ctx.trace_id,
                       "span_id": ctx.span_id}, f)
        return True
    except OSError:
        reg.inc(tnames.TELEMETRY_PROFILE_STAMP_ERRORS)
        return False


# --------------------------------------------------------- ProfileSession
class ProfileSession:
    """Rate-limited, bounded, rollback-safe device-profile capture
    (module docstring). Disabled (every trigger a cheap no-op / 503)
    until a profile dir is configured via env ``MMLSPARK_TPU_PROFILE_DIR``
    or `configure(profile_dir=...)`; `utils.tracing.trace` passes an
    explicit log_dir + force=True and works regardless."""

    def __init__(self, profile_dir: Optional[str] = None,
                 min_interval_s: float = 60.0, max_profiles: int = 4,
                 max_ms: float = 10_000.0, registry=None, tracer=None,
                 ledger: Optional[RooflineLedger] = None):
        if profile_dir is None:
            profile_dir = os.environ.get(PROFILE_DIR_ENV) or None
        self.profile_dir = profile_dir
        self.min_interval_s = float(min_interval_s)
        self.max_profiles = max(int(max_profiles), 1)
        self.max_ms = float(max_ms)
        self._registry = registry
        self._tracer = tracer
        self._ledger = ledger
        self._lock = threading.Lock()
        self._seq = 0
        self._last: Optional[float] = None

    @property
    def enabled(self) -> bool:
        return self.profile_dir is not None

    def configure(self, profile_dir=None,
                  min_interval_s: Optional[float] = None,
                  max_profiles: Optional[int] = None,
                  max_ms: Optional[float] = None) -> "ProfileSession":
        """Reconfigure in place (None leaves a knob untouched; pass
        profile_dir="" to disable)."""
        with self._lock:
            if profile_dir is not None:
                self.profile_dir = profile_dir or None
            if min_interval_s is not None:
                self.min_interval_s = float(min_interval_s)
            if max_profiles is not None:
                self.max_profiles = max(int(max_profiles), 1)
            if max_ms is not None:
                self.max_ms = float(max_ms)
        return self

    def default_ms(self) -> float:
        """Capture window for triggered captures (straggler flags, burn
        latches): env ``MMLSPARK_TPU_PROFILE_MS``, default 200, clamped
        to max_ms."""
        raw = os.environ.get(PROFILE_MS_ENV)
        try:
            ms = float(raw) if raw else 200.0
        except ValueError:
            ms = 200.0
        return min(max(ms, 1.0), self.max_ms)

    # -- the capture primitive -----------------------------------------------
    @contextlib.contextmanager
    def session(self, reason: str = "trace",
                log_dir: Optional[str] = None, force: bool = False,
                create_perfetto_link: bool = False):
        """Capture a device profile around the enclosed block; yields an
        info dict that gains ``ops``/``regions``/``path`` at exit.

        One capture path for every entry point: rate-limit gate (skipped
        with force=True — the explicit `utils.tracing.trace` API keeps
        its unconditional behavior), `device.profile` span, the
        trace-context stamp (`trace_context.json`, stamp failures
        counted under `telemetry.profile.stamp_errors`), per-op parse,
        ledger feed, retention pruning. A suppressed capture yields
        ``{"suppressed": True}`` and runs the block unprofiled; a FAILED
        capture rolls the rate-limit slot back, removes the partial
        capture dir (never a caller-owned log_dir), and raises."""
        reg = self._registry if self._registry is not None \
            else reliability_metrics
        own_dir = log_dir is None
        if own_dir and not self.enabled:
            raise RuntimeError(
                "ProfileSession disabled — set MMLSPARK_TPU_PROFILE_DIR "
                "or configure(profile_dir=...)")
        now = time.monotonic()
        with self._lock:
            if (not force and self._last is not None
                    and now - self._last < self.min_interval_s):
                suppressed = True
                prev_last = seq = None
            else:
                suppressed = False
                prev_last = self._last
                self._last = now
                seq = self._seq
                self._seq += 1
        if suppressed:
            reg.inc(tnames.TELEMETRY_PROFILE_SUPPRESSED)
            yield {"suppressed": True}
            return
        tag = _REASON_RE.sub("-", str(reason))[:48] or "profile"
        if own_dir:
            log_dir = os.path.join(self.profile_dir,
                                   f"profile-{os.getpid()}-{seq:04d}-{tag}")
        tracer = self._tracer if self._tracer is not None else get_tracer()
        info = {"path": log_dir, "reason": str(reason), "tag": tag,
                "t": wall_now()}
        started = False
        span = None

        def _rollback():
            # a failed capture must not shadow the next trigger for
            # min_interval_s, keep a partial dir in the retention
            # budget, or leak an unfinished span — same contract on the
            # block path AND the finalization path (stop_trace can fail
            # on a full disk)
            if span is not None:
                span.finish(error="capture-failed")
            with self._lock:
                if self._last == now:
                    self._last = prev_last
            if own_dir:
                shutil.rmtree(log_dir, ignore_errors=True)

        try:
            import jax
            os.makedirs(log_dir, exist_ok=True)
            span = tracer.start_span(tnames.DEVICE_PROFILE_SPAN,
                                     attrs={"log_dir": log_dir})
            jax.profiler.start_trace(
                log_dir, create_perfetto_link=create_perfetto_link)
            started = True
            yield info
        except BaseException:
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001 - already torn down
                    pass
            _rollback()
            raise
        try:
            jax.profiler.stop_trace()
            ctx = span.context if span is not None else tracer.current()
            if ctx is not None:
                _stamp_context(log_dir, ctx, reg)
            ops = parse_trace(log_dir, registry=reg)
            info["ops"] = ops
            info["regions"] = region_totals(ops)
            ledger = self._ledger if self._ledger is not None \
                else _default_ledger
            ledger.ingest_ops(ops)
            ledger.publish(registry=reg)
        except BaseException:
            _rollback()
            raise
        if span is not None:
            span.finish(ops=len(ops))
        if own_dir:
            self._prune()
        reg.inc(tnames.TELEMETRY_PROFILE_CAPTURES)
        tracer.event(tnames.TELEMETRY_PROFILE_EVENT, reason=str(reason),
                     path=log_dir, ops=len(ops))

    def capture(self, ms: Optional[float] = None,
                reason: str = "on-demand",
                force: bool = False) -> Optional[dict]:
        """Timed capture: profile for `ms` (clamped to max_ms) and return
        the manifest, or None when the rate limit suppressed it. Same
        trigger contract as `FlightRecorder.dump`: /debug/profile maps
        None to 429, disabled to 503, and a raised failure to 500."""
        if not self.enabled:
            return None
        if ms is None:
            ms = self.default_ms()
        ms = min(max(float(ms), 1.0), self.max_ms)
        with self.session(reason=reason, force=force) as info:
            if info.get("suppressed"):
                return None
            time.sleep(ms / 1000.0)
        info["ms"] = ms
        return info

    def _prune(self) -> None:
        """Keep the newest `max_profiles` capture dirs (mtime order);
        best-effort — losing a race to a concurrent prune is harmless."""
        try:
            entries = [os.path.join(self.profile_dir, e)
                       for e in os.listdir(self.profile_dir)
                       if e.startswith("profile-")]
            entries.sort(key=lambda p: (os.path.getmtime(p), p))
            for stale in entries[:-self.max_profiles]:
                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass


_session: Optional[ProfileSession] = None
_session_lock = threading.Lock()


def get_profile_session() -> ProfileSession:
    global _session
    with _session_lock:
        if _session is None:
            _session = ProfileSession()
        return _session


def configure_profile_session(**kwargs) -> ProfileSession:
    """Configure the process-default profile session (see
    `ProfileSession.configure`)."""
    return get_profile_session().configure(**kwargs)


def capture_profile(ms: Optional[float] = None, reason: str = "manual",
                    force: bool = False) -> Optional[dict]:
    """One-liner timed capture on the process-default session (the
    public application API; triggers use the same path)."""
    return get_profile_session().capture(ms=ms, reason=reason, force=force)


if __name__ == "__main__":
    sys.exit(main())
