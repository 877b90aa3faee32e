"""Benchmarks-as-regression-tests harness, modeled on the reference's
core/test/benchmarks/Benchmarks.scala:16-130: golden metric CSVs checked into
tests/resources/benchmarks/, `add_benchmark(name, value, precision)` compares
each run against the stored golden (creating it on first run).

Also home of `measure_quiet` — the tier-1 deflake helper for wall-clock
capability floors (the PR-9 quiet-host-retry pattern): a throughput/latency FLOOR proves a capability, so one
quiet pass suffices; host contention can only push the measurement the
failing way. Retry with a settle pause before letting a single noisy run
fail the suite.
"""
import csv
import os
import time

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "resources", "benchmarks")


def measure_quiet(measure, ok, attempts: int = 3, settle_s: float = 1.5):
    """Run a wall-clock-sensitive measurement up to `attempts` times and
    return the first result satisfying `ok` (or the last attempt, so the
    caller's assertion still fails — with the real numbers — on a build
    that is genuinely too slow). Between attempts, sleep `settle_s` so a
    transient load spike (a parallel suite, a review subagent) passes.

    Use ONLY for capability floors ("sustains > N req/s", "p50 under X
    ms"), never for regression *equality* checks: retrying those would
    hide real drift."""
    result = None
    for attempt in range(attempts):
        result = measure()
        if ok(result):
            return result
        if attempt + 1 < attempts:
            time.sleep(settle_s)
    return result


class Benchmarks:
    def __init__(self, suite_name: str):
        self.suite = suite_name
        self.path = os.path.join(GOLDEN_DIR, f"benchmarks_{suite_name}.csv")
        self.golden = {}
        if os.path.exists(self.path):
            with open(self.path) as f:
                for row in csv.reader(f):
                    if row and row[0] != "name":
                        self.golden[row[0]] = float(row[1])
        self.new_rows = {}

    def add(self, name: str, value: float, precision: float):
        self.new_rows[name] = (value, precision)
        if name in self.golden:
            g = self.golden[name]
            assert abs(g - value) <= precision, (
                f"benchmark {self.suite}/{name}: value {value:.6f} drifted from "
                f"golden {g:.6f} (tolerance {precision})")

    def flush(self):
        """Write goldens for any new entries (first run records them)."""
        missing = [n for n in self.new_rows if n not in self.golden]
        if not missing:
            return
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        rows = dict(self.golden)
        rows.update({n: self.new_rows[n][0] for n in missing})
        with open(self.path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["name", "value"])
            for n, v in sorted(rows.items()):
                w.writerow([n, f"{v:.6f}"])


def auc(y_true, scores):
    import numpy as np
    y_true = np.asarray(y_true)
    scores = np.asarray(scores)
    order = np.argsort(scores, kind="stable")
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    npos = y_true.sum()
    nneg = len(y_true) - npos
    return (ranks[y_true == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)
