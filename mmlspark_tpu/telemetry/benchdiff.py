"""Bench-trajectory differ: per-metric deltas across BENCH round files.

The driver records one ``BENCH_rNN.json`` per round (a wrapper object
whose ``parsed`` field holds the headline JSON line and whose ``tail``
holds every JSON line the bench printed), but nothing in the tree ever
*compared* rounds — a 20% regression between r4 and r5 was only visible
to a human reading two files. This is the missing tool:

    python -m mmlspark_tpu.telemetry.benchdiff BENCH_r*.json
    python -m mmlspark_tpu.telemetry.benchdiff --threshold 0.15 BENCH_r*.json

prints, per metric, the value trajectory across rounds and the
last-vs-previous delta, and — with ``--threshold`` set — exits nonzero
when any metric regressed by more than that fraction (higher-is-better
by default; flag lower-is-better metrics with ``--lower-better``, e.g.
elapsed-seconds metrics). Accepts the driver wrapper format, raw bench
JSONL (one ``{"metric": ...}`` object per line), or a single JSON
object; rounds order by the wrapper's ``n`` when present, else by
filename.

GBDT regression gates (round 6): every ``gbdt_train_rows_iters_per_sec``
record additionally synthesizes per-shape derived records
``gbdt.<shape>.vs_baseline`` and ``gbdt.<shape>.hbm_utilization`` (both
higher-is-better), so the headline's baseline ratio and the honesty
metric gate across rounds exactly like the MULTICHIP bubble/traffic
records — a kernel "win" that tanked either fails the diff:

    python -m mmlspark_tpu.telemetry.benchdiff --threshold 0.1 BENCH_r*.json

Fleet control-loop gates (round 16): every ``fleet_req_per_sec`` record
(BENCH_MODE=fleet — loadgen through the weighted router with a poison
candidate auto-rolled-back mid-run) additionally synthesizes
``fleet.rollback_window_p99_ms`` and ``fleet.requests_dropped``, both
born ``lower_better`` — a round that stretched the chaos-window tail or
dropped even one request during rollback fails the diff regardless of
throughput.

Online-learning gates (round 17): every ``online_sparse_req_per_sec``
record (BENCH_MODE=online — the sparse-pair serving fast path with the
continuous-learning loop driven through a seeded covariate shift)
additionally synthesizes ``online.updates_per_sec`` (higher-is-better:
the fixed-bucket `partial_fit` throughput) plus ``online.adapt_latency_s``
and ``online.requests_dropped`` (both born ``lower_better`` — the
shift-to-promoted window must not stretch, and a drop during the swap is
a regression even if raw req/s improved).

Backend gating (round 11): records carry a ``backend`` annotation (from
the record itself, or a round file's top-level ``backend`` declaration —
bench.py stamps the device's platform); records measured on a
non-TPU backend are excluded from both trajectories and gates and
reported as excluded — a CPU run must not read as a perf datapoint.
Extras-style artifacts (a JSON object whose top-level values are whole
records, or lists of them) are harvested too.

It also reads the multichip wrapper format (a driver ``{"n", "tail"}``
object whose ``tail`` holds ``GPIPE_MSWEEP {json}`` / ``TRAFFIC
{json}`` lines): the GPipe microbatch sweep becomes
``gpipe_m<M>_{s_per_step,bubble_fraction}`` records and the collective
account becomes ``comm.<program>.<kind>.{ops,bytes}`` records — all
marked lower-is-better on the record itself (``"lower_better": true``),
so bubble-fraction and collective-bytes trajectories gate exactly like
headline metrics:

    python -m mmlspark_tpu.telemetry.benchdiff --threshold 0.1 \\
        MULTICHIP_r*.json
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from typing import List, Optional, Tuple

_DIGITS = re.compile(r"(\d+)")
# MULTICHIP tail lines: an UPPERCASE tag followed by one JSON object
# (the dryrun prints "GPIPE_MSWEEP {...}" and "TRAFFIC {...}")
_TAGGED = re.compile(r"^([A-Z][A-Z0-9_]*)\s+(\{.*)$")


def _natural_key(path: str) -> tuple:
    """Filename sort key with digit runs compared numerically, so
    BENCH_r10 orders after BENCH_r2 (lexicographic sorting would put it
    first and make last-vs-prev compare the wrong rounds)."""
    return tuple(int(part) if part.isdigit() else part
                 for part in _DIGITS.split(path))


def _sweep_records(sweep: dict) -> list:
    """GPIPE_MSWEEP -> per-M records. Both step time and bubble fraction
    regress by GROWING, so they are born lower-is-better."""
    records = []
    for m in sorted(sweep, key=str):
        entry = sweep[m]
        if not isinstance(entry, dict):
            continue
        for field in ("s_per_step", "bubble_fraction"):
            v = entry.get(field)
            if isinstance(v, (int, float)):
                records.append({"metric": f"gpipe_m{m}_{field}",
                                "value": float(v), "lower_better": True})
    return records


def _traffic_records(table: dict) -> list:
    """TRAFFIC -> per-(program, collective-kind) records. Growing
    collective volume is the regression the voting/bucketing designs
    exist to prevent, so ops and bytes are lower-is-better."""
    records = []
    for prog in sorted(table):
        kinds = table[prog]
        if not isinstance(kinds, dict):
            continue
        for kind in sorted(kinds):
            ent = kinds[kind]
            if not isinstance(ent, dict):
                continue
            for field in ("ops", "bytes"):
                v = ent.get(field)
                if isinstance(v, (int, float)):
                    records.append(
                        {"metric": f"comm.{prog}.{kind}.{field}",
                         "value": float(v), "lower_better": True})
    return records


def _tagged_records(tag: str, obj: dict) -> list:
    """Records synthesized from one tagged tail line (MULTICHIP rounds)."""
    if tag == "GPIPE_MSWEEP" and isinstance(obj.get("sweep"), dict):
        return _sweep_records(obj["sweep"])
    if tag == "TRAFFIC":
        return _traffic_records(obj)
    return []


# extra numeric fields of the GBDT headline record that gate like
# first-class metrics (higher is better for both: vs_baseline IS the
# headline ratio, hbm_utilization is the honesty metric a fake win tanks)
_GBDT_METRIC = "gbdt_train_rows_iters_per_sec"
_GBDT_GATED_FIELDS = ("vs_baseline", "hbm_utilization")


def _gbdt_records(rec: dict) -> list:
    """Derived per-shape gate records from one GBDT headline record. The
    shape rides in the metric name so the wide rows (same metric string,
    earlier tail lines) gate independently of the canonical 8M headline
    instead of being last-line-overwritten. The parent's backend
    annotation rides along — a CPU-only round's derived gates are
    excluded exactly like its headline."""
    if rec.get("metric") != _GBDT_METRIC:
        return []
    tag = str(rec.get("shape", "headline")).replace(" ", "_") or "headline"
    out = []
    for field in _GBDT_GATED_FIELDS:
        v = rec.get(field)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = {"metric": f"gbdt.{tag}.{field}", "value": float(v)}
            if rec.get("backend") is not None:
                d["backend"] = rec["backend"]
            out.append(d)
    return out


# fields of the BENCH_MODE=fleet headline record that gate as first-class
# LOWER-IS-BETTER metrics: the chaos window's tail latency and the
# zero-drop acceptance count (any value above 0 is a regression, and a
# round that drops requests must fail the diff even if req/s improved)
_FLEET_METRIC = "fleet_req_per_sec"
_FLEET_LOWER_FIELDS = ("rollback_window_p99_ms", "requests_dropped")


def _fleet_records(rec: dict) -> list:
    """Derived gate records from one fleet-bench headline record (born
    ``lower_better``); the parent's backend annotation rides along."""
    if rec.get("metric") != _FLEET_METRIC:
        return []
    out = []
    for field in _FLEET_LOWER_FIELDS:
        v = rec.get(field)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = {"metric": f"fleet.{field}", "value": float(v),
                 "lower_better": True}
            if rec.get("backend") is not None:
                d["backend"] = rec["backend"]
            out.append(d)
    return out


# fields of the BENCH_MODE=elastic headline record (kill-one-host run)
# that gate as first-class LOWER-IS-BETTER metrics: how long the
# survivors take to resume after the death verdict, and the fraction of
# finished boosting work the committed fleet manifest failed to preserve
_ELASTIC_METRIC = "elastic_detect_s"
_ELASTIC_LOWER_FIELDS = ("resume_s", "lost_work_fraction")


def _elastic_records(rec: dict) -> list:
    """Derived gate records from one elastic-bench headline record (born
    ``lower_better``); the parent's backend annotation rides along."""
    if rec.get("metric") != _ELASTIC_METRIC:
        return []
    out = []
    for field in _ELASTIC_LOWER_FIELDS:
        v = rec.get(field)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = {"metric": f"elastic.{field}", "value": float(v),
                 "lower_better": True}
            if rec.get("backend") is not None:
                d["backend"] = rec["backend"]
            out.append(d)
    return out


# fields of the BENCH_MODE=workloads headline (iforest + SAR closed-loop
# serving A/B) that gate as first-class per-workload metrics: compiled-path
# throughput (higher better) and its tail latency (born lower-is-better)
_WORKLOADS_METRIC = "workloads_req_per_sec"
_WORKLOADS_HIGHER_FIELDS = ("iforest_req_per_sec", "sar_req_per_sec")
_WORKLOADS_LOWER_FIELDS = ("iforest_p99_ms", "sar_p99_ms")


def _workloads_records(rec: dict) -> list:
    """Derived gate records from one workloads-bench headline record —
    ``workloads.iforest.*`` / ``workloads.sar.*`` so each workload's
    throughput and tail gate independently of the combined headline; the
    parent's backend annotation rides along."""
    if rec.get("metric") != _WORKLOADS_METRIC:
        return []
    out = []
    for field, lower in ([(f, False) for f in _WORKLOADS_HIGHER_FIELDS]
                         + [(f, True) for f in _WORKLOADS_LOWER_FIELDS]):
        v = rec.get(field)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            workload, metric = field.split("_", 1)
            d = {"metric": f"workloads.{workload}.{metric}",
                 "value": float(v)}
            if lower:
                d["lower_better"] = True
            if rec.get("backend") is not None:
                d["backend"] = rec["backend"]
            out.append(d)
    return out


# fields of the BENCH_MODE=online headline that gate as first-class
# metrics: partial_fit throughput (higher better) and the self-healing
# window + zero-drop acceptance (born lower-is-better)
_ONLINE_METRIC = "online_sparse_req_per_sec"
_ONLINE_HIGHER_FIELDS = ("online_updates_per_sec",)
_ONLINE_LOWER_FIELDS = ("adapt_latency_s", "requests_dropped")


def _online_records(rec: dict) -> list:
    """Derived gate records from one online-bench headline record; the
    parent's backend annotation rides along."""
    if rec.get("metric") != _ONLINE_METRIC:
        return []
    out = []
    for field, lower in ([(f, False) for f in _ONLINE_HIGHER_FIELDS]
                         + [(f, True) for f in _ONLINE_LOWER_FIELDS]):
        v = rec.get(field)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            d = {"metric": f"online.{field.removeprefix('online_')}",
                 "value": float(v)}
            if lower:
                d["lower_better"] = True
            if rec.get("backend") is not None:
                d["backend"] = rec["backend"]
            out.append(d)
    return out


def _with_derived(records: list) -> list:
    return records + [d for r in records
                      for d in (_gbdt_records(r) + _fleet_records(r)
                                + _online_records(r)
                                + _elastic_records(r)
                                + _workloads_records(r))]


def _records_from_text(text: str) -> list:
    """Every JSON object with a "metric" key found in `text` (whole-file
    object, wrapper with parsed/tail, or JSONL), plus records synthesized
    from MULTICHIP-style tagged tail lines."""
    text = text.strip()
    if not text:
        return []
    records: list = []
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        if "metric" in obj:
            return _with_derived([obj])
        # driver wrapper: {"n": ..., "parsed": {...}, "tail": "..."} —
        # harvest every bench line from the tail (multi-mode runs print
        # several), with `parsed` as the authoritative headline. The
        # MULTICHIP wrapper's tail carries TAGGED lines instead.
        # BENCH_EXTRA-style artifacts nest whole records as top-level
        # values (and declare the round's backend at top level) — harvest
        # those too so an auto-emitted CPU round is SEEN and then
        # excluded from gating by its backend, rather than invisible.
        for v in obj.values():
            if isinstance(v, dict) and "metric" in v:
                records.append(dict(v))
            elif isinstance(v, list):
                records.extend(dict(e) for e in v
                               if isinstance(e, dict) and "metric" in e)
        for line in str(obj.get("tail", "")).splitlines():
            line = line.strip()
            tagged = _TAGGED.match(line)
            if tagged:
                try:
                    payload = json.loads(tagged.group(2))
                except ValueError:
                    continue
                if isinstance(payload, dict):
                    records.extend(_tagged_records(tagged.group(1),
                                                   payload))
                continue
            if line.startswith("{"):
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "metric" in rec:
                    records.append(rec)
        # a round-level backend declaration annotates every record that
        # didn't carry its own (newer bench records do) — the per-record
        # field is what gating reads. Annotation runs BEFORE derivation
        # (derived gate records inherit from their parent) and applies
        # to the authoritative `parsed` headline too — the re-added
        # parsed copy below would otherwise gate as TPU.
        file_backend = obj.get("backend")

        def _annotated(rs: list) -> list:
            if isinstance(file_backend, str):
                for r in rs:
                    r.setdefault("backend", file_backend)
            return rs

        # derive BEFORE the parsed-headline dedup: the wide GBDT rows
        # share the headline's metric string and would be dropped by it,
        # but their per-shape derived gate records must survive
        records = _with_derived(_annotated(records))
        parsed = obj.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            records = [r for r in records
                       if r.get("metric") != parsed["metric"]]
            records.extend(_with_derived(_annotated([dict(parsed)])))
        return records
    # JSONL fallback
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            records.append(rec)
    return _with_derived(records)


def load_round(path: str) -> Tuple[object, dict]:
    """(sort_key, {metric: record}) for one round file."""
    with open(path) as f:
        text = f.read()
    sort_key: object = path
    try:
        obj = json.loads(text)
        if isinstance(obj, dict) and isinstance(obj.get("n"), int):
            sort_key = obj["n"]
    except ValueError:
        pass
    by_metric = {}
    for rec in _records_from_text(text):
        by_metric[rec["metric"]] = rec   # last line wins, like the driver
    return sort_key, by_metric


def _perf_backend(rec: dict) -> bool:
    """Is this record a perf-trajectory datapoint? Records ANNOTATED with
    a non-TPU backend (bench.py stamps `jax.default_backend()`; wrapper
    files may declare it round-wide) are real measurements of the wrong
    hardware — a CPU fallback round reading as a 99.9% regression, or a
    CPU round "recovering" to TPU reading as a win, would both poison
    the gate. Unannotated records (historic rounds) gate as before."""
    backend = rec.get("backend")
    return backend is None or str(backend).lower() == "tpu"


def diff_rounds(rounds: List[Tuple[str, dict]], key: str = "value",
                threshold: Optional[float] = None,
                lower_better: Tuple[str, ...] = ()) -> Tuple[list, list]:
    """(report_lines, regressions) across rounds (already ordered).
    A regression compares the LAST round's value against the most recent
    earlier round that carries the metric. A record born with
    ``"lower_better": true`` (MULTICHIP bubble/traffic synthesis) gates
    as lower-is-better without a CLI flag. Records whose ``backend``
    annotation is non-TPU are EXCLUDED from both the trajectory and the
    gate (reported as excluded, so the omission is visible). A record's
    ``model_version`` stamp (the serving bench carries the fitted
    model's content-addressed id, telemetry/lineage.py) rides the
    trajectory as ``label:value@version`` and annotates any regression
    whose two compared rounds measured DIFFERENT versions — a model
    swap and a perf regression must not read the same."""
    order: dict = {}   # metric -> [(label, value, version)] — insertion order
    born_lower: set = set()
    excluded: list = []
    for label, by_metric in rounds:
        for metric, rec in by_metric.items():
            v = rec.get(key)
            if not isinstance(v, (int, float)):
                continue
            if not _perf_backend(rec):
                excluded.append(f"{label} {metric} "
                                f"(backend={rec.get('backend')})")
                continue
            order.setdefault(metric, []).append(
                (label, float(v), rec.get("model_version")))
            if rec.get("lower_better"):
                born_lower.add(metric)
    lines: list = []
    regressions: list = []
    for metric, series in order.items():
        traj = " -> ".join(
            f"{label}:{value:g}" + (f"@{ver}" if ver else "")
            for label, value, ver in series)
        if len(series) < 2:
            lines.append(f"{metric} [{key}]: {traj}  (single round)")
            continue
        (_, prev, pver), (_, last, lver) = series[-2], series[-1]
        if last == prev:
            delta = 0.0   # unchanged is unchanged, even from a 0 baseline
        elif prev:
            delta = (last - prev) / abs(prev)
        else:
            delta = float("inf")
        lines.append(f"{metric} [{key}]: {traj}  last-vs-prev "
                     f"{delta:+.1%}")
        if threshold is not None:
            lb = metric in lower_better or metric in born_lower
            drop = delta if lb else -delta
            if drop > threshold:
                swap = (f", model_version {pver} -> {lver}"
                        if pver and lver and pver != lver else "")
                regressions.append(
                    f"{metric}: {prev:g} -> {last:g} "
                    f"({delta:+.1%}, threshold {threshold:.0%}"
                    f"{', lower-better' if lb else ''}{swap})")
    for note in excluded:
        lines.append(f"excluded from perf gates (non-TPU backend): {note}")
    return lines, regressions


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m mmlspark_tpu.telemetry.benchdiff",
        description="Per-metric deltas across bench round files; "
                    "nonzero exit on regression beyond --threshold.")
    parser.add_argument("files", nargs="+", help="BENCH_r*.json files")
    parser.add_argument("--key", default="value",
                        help="numeric field to diff (default: value)")
    parser.add_argument("--threshold", type=float, default=None,
                        help="fail when a metric regresses by more than "
                             "this fraction (e.g. 0.15 = 15%%)")
    parser.add_argument("--lower-better", action="append", default=[],
                        metavar="METRIC",
                        help="metric where a DROP is an improvement "
                             "(repeatable)")
    args = parser.parse_args(argv)
    rounds = []
    for path in args.files:
        try:
            sort_key, by_metric = load_round(path)
        except (OSError, ValueError) as e:
            # ValueError covers UnicodeDecodeError: a stray binary file
            # in the glob is "unreadable input" (exit 2), not a crash
            print(f"benchdiff: cannot read {path}: {e}", file=sys.stderr)
            return 2
        rounds.append((sort_key, path, by_metric))
    # wrapper `n` orders rounds when every file has one; natural
    # filename order otherwise (mixed keys are not comparable in py3)
    if all(isinstance(k, int) for k, _, _ in rounds):
        rounds.sort(key=lambda r: r[0])
    else:
        rounds.sort(key=lambda r: _natural_key(r[1]))
    labeled = [(f"r{k:02d}" if isinstance(k, int) else path, by)
               for k, path, by in rounds]
    lines, regressions = diff_rounds(
        labeled, key=args.key, threshold=args.threshold,
        lower_better=tuple(args.lower_better))
    for line in lines:
        print(line)
    if not lines:
        print("benchdiff: no numeric records found", file=sys.stderr)
        return 2
    if regressions:
        print(f"\nREGRESSIONS ({len(regressions)}):", file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
