"""Device-profile observability (ISSUE 11): triggered on-device capture,
per-op roofline attribution, and the live telemetry regression watcher.

Pins the new contracts: ProfileSession captures succeed on the CPU
backend with an EMPTY per-op table (device planes absent — the
documented degrade, never a raise); the parse attributes device-plane
self time to the registered regions; `GET /debug/profile` answers the
/debug/bundle 400/429/503/500 contract on both serving transports and
the trainer scrape surface; failed captures roll the rate-limit slot
back; `utils.tracing.trace` (rebased on the session) still stamps
`trace_context.json` and the `device.profile` span, with stamp failures
COUNTED; the RooflineLedger joins measured region time with
region-tagged compile costs and publishes `op.<region>.*` gauges only
when both sides are known; the watcher's threshold and median-shift
detection is a pure function of the series (transition-once, recovery
re-arms, recorder latch per rule); the poller's JSONL sink rotates
oldest-first under a byte bound; benchdiff excludes non-TPU rounds from
perf gates; and the seeded delay-fault acceptance drives
straggler-flag -> triggered capture -> bundle with roofline.json, with
the watch-trip and capture events causally ordered in the span log."""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.reliability import TrainingSupervisor
from mmlspark_tpu.reliability.faults import FaultInjector
from mmlspark_tpu.reliability.metrics import (MetricsRegistry,
                                              reliability_metrics)
from mmlspark_tpu.telemetry import benchdiff
from mmlspark_tpu.telemetry import names as tnames
from mmlspark_tpu.telemetry import perf as tperf
from mmlspark_tpu.telemetry import profiler as tprof
from mmlspark_tpu.telemetry import slo as tslo
from mmlspark_tpu.telemetry.goodput import StepClock
from mmlspark_tpu.telemetry.watch import (TelemetryWatcher, WatchRule,
                                          evaluate_rule)
from mmlspark_tpu.utils import tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _fresh_executable_registry():
    """XLA's debug-info manager serializes an hlo_proto for EVERY live
    compiled executable into each profiler dump: after a few hundred
    suite tests the cached fits make a 20ms capture write tens of MB of
    xplane.pb and the /debug/profile handlers blow their client
    timeouts. Captures here measure THIS module's work, not the suite's
    leftovers — drop cached executables so dump size stays proportional
    to what these tests actually run. jax.clear_caches() alone is not
    enough: the distributed-GBDT AotCaches live in process-global
    lru_caches and keep their AOT executables alive (and tracked by the
    debug-info manager) until explicitly dropped."""
    import gc

    import jax

    from mmlspark_tpu.models.gbdt import distributed as gbdt_distributed
    gbdt_distributed._compiled_tree_fn.cache_clear()
    gbdt_distributed._compiled_chunk_fn.cache_clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def clean_profiler_state():
    """The profiler tier is process-global (session, ledger, compile
    log, counters): give every test a clean slate and disable after."""
    reliability_metrics.reset()
    tprof.get_roofline().clear()
    tperf.get_compile_log().clear()
    session = tprof.get_profile_session()
    session.configure(profile_dir="", min_interval_s=0.0, max_profiles=4)
    session._last = None
    yield
    session.configure(profile_dir="", min_interval_s=60.0, max_profiles=4)
    session._last = None
    tprof.get_roofline().clear()
    tperf.get_compile_log().clear()
    reliability_metrics.reset()


@pytest.fixture
def profile_dir(tmp_path):
    d = tmp_path / "profiles"
    d.mkdir()
    tprof.configure_profile_session(profile_dir=str(d), min_interval_s=0.0)
    return d


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(tperf, "_recorder", None)   # fresh burn latches
    bundles = tmp_path / "bundles"
    tperf.configure_flight_recorder(bundle_dir=str(bundles),
                                    min_interval_s=0.0, max_bundles=8)
    yield bundles
    tperf.configure_flight_recorder(bundle_dir="")
    monkeypatch.setattr(tperf, "_recorder", None)


def _get_json(url, timeout=15):
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


_RECORDED = os.path.join(_REPO, "benchmark", "tests", "data")


def _capture_dir(tmp_path, kind):
    """A capture directory of each kind `parse_trace` has to take."""
    d = tmp_path / kind
    run = d / "plugins" / "profile" / "run1"
    if kind == "missing-dir":
        return d
    run.mkdir(parents=True)
    if kind == "torn-file":
        (run / "vm.xplane.pb").write_bytes(b"not a protobuf at all")
    elif kind == "no-device-plane":
        # what the CPU backend writes: host planes only
        import jax
        import jax.numpy as jnp
        jax.profiler.start_trace(str(d))
        float(jnp.ones((8, 8)).sum())
        jax.profiler.stop_trace()
    else:
        # two steps of the toy LM on a v5e chip (PR 26,
        # benchmark/tests/record_lm_scoped.py)
        import shutil
        shutil.copy(os.path.join(_RECORDED, "lm_scoped.xplane.pb"),
                    run / "vm.xplane.pb")
    return d


# ------------------------------------------------------------- trace parse
@pytest.mark.parametrize("kind", ["missing-dir", "torn-file",
                                  "no-device-plane", "recorded-v5e"])
def test_parse_trace_reads_the_xplane_and_never_raises(tmp_path, kind):
    """The xplane path, one parser: a missing or torn capture and a backend
    without device planes degrade to an empty table; a capture from the
    chip is attributed by the step program's scope map, by instruction
    name, and what no map places is reported, not hidden."""
    with open(os.path.join(_RECORDED, "lm_scoped_scopes.json")) as f:
        scopes = json.load(f)
    reg = MetricsRegistry()
    records = tprof.parse_trace(str(_capture_dir(tmp_path, kind)),
                                scopes=scopes, registry=reg)
    share = reg.peek_gauge(tnames.TELEMETRY_PROFILE_UNSCOPED_SHARE)
    if kind != "recorded-v5e":
        assert records == [] and share is None
        return
    assert records == sorted(records, key=lambda r: (-r["self_time_us"],
                                                     r["op"]))
    totals = tprof.region_totals(records)
    assert {tnames.LM_MLP, tnames.LM_ATTN, tnames.LM_ATTN_FLASH,
            tnames.LM_HEAD, tnames.LM_OPT, tnames.LM_EMBED,
            tnames.LM_CAST} <= set(totals)
    by_op = {r["op"]: r for r in records}
    # the kernels carry their own names into the trace
    flash = {op.split(".")[0] for op, r in by_op.items()
             if r["region"] == tnames.LM_ATTN_FLASH}
    assert {"flash_fwd", "flash_dq", "flash_dkv"} <= flash
    # 2 layers x 2 steps of each kernel
    assert sum(r["occurrences"] for op, r in by_op.items()
               if op.startswith("flash_dq")) == 4
    # the feed-forward forward runs again inside the backward
    assert {r["direction"] for r in records
            if r["region"] == tnames.LM_MLP} == {"fwd", "bwd", "remat"}
    # self times add up to the device's busy time, unscoped included
    total = sum(v["self_time_us"] for v in totals.values())
    unscoped = totals.get(tprof.UNSCOPED, {"self_time_us": 0.0})
    assert share == pytest.approx(unscoped["self_time_us"] / total,
                                  rel=1e-3)
    assert 0.0 <= share < 0.5
    # the ledger takes the regions and leaves the unscoped rest out
    led = tprof.RooflineLedger(registry=reg)
    led.ingest_ops(records)
    rows = led.rows(peaks={"flops_per_s": None, "hbm_bytes_per_s": None})
    assert rows[tnames.LM_MLP]["source"] == "device"
    assert tprof.UNSCOPED not in rows
    # without a map everything is unscoped, and says so
    bare = tprof.parse_trace(str(tmp_path / kind), scopes={}, registry=reg)
    assert {r["region"] for r in bare} == {tprof.UNSCOPED}
    assert reg.peek_gauge(tnames.TELEMETRY_PROFILE_UNSCOPED_SHARE) == 1.0


def test_self_times_take_children_out_of_their_parents():
    events = [("%while.1 = while", 0, 100), ("%fusion.2 = fusion", 10, 30),
              ("%fusion.3 = fusion", 50, 40), ("%copy.4 = copy", 120, 5),
              ("%fusion.2 = fusion", 130, 30)]
    own = tprof.self_times(events)
    assert own == {"%while.1 = while": [30, 1], "%fusion.2 = fusion": [60, 2],
                   "%fusion.3 = fusion": [40, 1], "%copy.4 = copy": [5, 1]}
    assert tprof.instruction_name("%fusion.2 = bf16[8] fusion(%a)") == \
        "fusion.2"
    assert tprof.instruction_name("no instruction") == "no instruction"


# ---------------------------------------------------------- ProfileSession
def test_capture_on_cpu_backend_degrades_to_empty_op_table(profile_dir):
    """THE degrade contract: on the CPU backend the capture itself
    succeeds (trace artifacts on disk, counter, event) while the per-op
    table is empty because no device plane exists — no raise anywhere."""
    import jax.numpy as jnp
    tracer = telemetry.get_tracer()
    tracer.configure(sample=1.0)
    tracer.clear()
    try:
        with tprof.get_profile_session().session(reason="degrade") as info:
            float(jnp.ones((64, 64)).sum())
        assert info["ops"] == [] and info["regions"] == {}
        assert os.path.isdir(info["path"])
        found = []
        for root, _, files in os.walk(info["path"]):
            found += [f for f in files if f.endswith(".json.gz")]
        assert found, "capture produced no trace artifacts"
        assert reliability_metrics.get(
            tnames.TELEMETRY_PROFILE_CAPTURES) == 1
        events = tracer.finished(tnames.TELEMETRY_PROFILE_EVENT)
        assert len(events) == 1 and events[0]["attrs"]["ops"] == 0
        spans = tracer.finished(tnames.DEVICE_PROFILE_SPAN)
        assert len(spans) == 1
    finally:
        tracer.configure(sample=0.0)
        tracer.clear()


def test_capture_rate_limit_and_bounded_retention(profile_dir):
    session = tprof.get_profile_session()
    assert session.capture(ms=5, reason="one") is not None
    session.configure(min_interval_s=3600.0)
    assert session.capture(ms=5, reason="two") is None
    assert reliability_metrics.get(
        tnames.TELEMETRY_PROFILE_SUPPRESSED) == 1
    # force bypasses the limit (the explicit tracing.trace API)
    assert session.capture(ms=5, reason="forced", force=True) is not None
    # retention: oldest capture dirs pruned by mtime
    session.configure(min_interval_s=0.0, max_profiles=2)
    for i in range(3):
        assert session.capture(ms=5, reason=f"r{i}") is not None
    kept = sorted(p.name for p in profile_dir.iterdir()
                  if p.name.startswith("profile-"))
    assert len(kept) == 2
    assert [p.rsplit("-", 1)[-1] for p in kept] == ["r1", "r2"]


def test_failed_capture_rolls_back_rate_limit_slot(tmp_path):
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    session = tprof.get_profile_session()
    session.configure(profile_dir=str(blocker / "sub"),
                      min_interval_s=3600.0)
    with pytest.raises(OSError):
        session.capture(ms=5, reason="broken")
    # slot rolled back: a capture against a good dir succeeds NOW
    good = tmp_path / "good"
    good.mkdir()
    session.configure(profile_dir=str(good))
    assert session.capture(ms=5, reason="after") is not None
    # and no partial dir of the failed capture survived anywhere
    assert not (tmp_path / "blocker" / "sub").exists()


def test_capture_disabled_is_none_and_session_raises():
    session = tprof.get_profile_session()
    assert not session.enabled
    assert session.capture(ms=5) is None
    with pytest.raises(RuntimeError, match="disabled"):
        with session.session(reason="x"):
            pass


# ------------------------------------------ utils.tracing.trace (rebased)
def test_trace_rebased_stamps_context_and_device_profile_span(tmp_path):
    """The satellite contract: ONE capture path. trace() still writes
    trace_context.json with the ACTIVE trace id and records the
    device.profile span — and works with the session disabled (explicit
    log_dir, force)."""
    import jax.numpy as jnp
    tracer = telemetry.get_tracer()
    tracer.configure(sample=1.0)
    tracer.clear()
    d = str(tmp_path / "trace")
    try:
        with tracer.span("outer") as outer:
            with tracing.trace(d):
                float(jnp.ones((32, 32)).sum())
            outer_trace = outer.trace_id
        stamped = json.loads(
            open(os.path.join(d, "trace_context.json")).read())
        assert stamped["trace_id"] == outer_trace
        spans = tracer.finished(tnames.DEVICE_PROFILE_SPAN)
        assert len(spans) == 1
        assert spans[0]["attrs"]["log_dir"] == d
        assert stamped["span_id"] == spans[0]["span_id"]
        # caller-owned dir: never pruned, artifacts on disk
        assert os.path.isdir(os.path.join(d, "plugins"))
    finally:
        tracer.configure(sample=0.0)
        tracer.clear()


def test_stamp_failure_is_counted_not_silent():
    from mmlspark_tpu.telemetry.spans import SpanContext
    reg = MetricsRegistry()
    ctx = SpanContext("t" * 16, "s" * 16, True)
    ok = tprof._stamp_context("/nonexistent/dir/for/stamp", ctx, reg)
    assert ok is False
    assert reg.get(tnames.TELEMETRY_PROFILE_STAMP_ERRORS) == 1


# --------------------------------------------------------- roofline ledger
def test_annotate_notes_region_and_tags_compiles():
    led = tprof.get_roofline()
    with tracing.annotate("train.step"):
        time.sleep(0.01)
        rec = tperf.record_plan_compile(
            "fp-train", "8x4", 0.01,
            analysis={"flops": 2.0e9, "bytes_accessed": 1.0e8})
    assert rec["region"] == "train.step"
    rows = led.rows(peaks={"flops_per_s": 1.0e12,
                           "hbm_bytes_per_s": 1.0e11})
    row = rows["train.step"]
    assert row["source"] == "host" and row["seconds"] >= 0.01
    # cost joined from the region-tagged compile record
    assert row["flops"] == 2.0e9
    # (row seconds are rounded for export; achieved uses the raw wall)
    assert row["achieved_flops_per_s"] == pytest.approx(
        2.0e9 / row["seconds"], rel=1e-3)
    assert 0.0 < row["flops_util"] < 1.0
    assert 0.0 < row["hbm_util"] < 1.0


def test_roofline_absent_sides_never_guessed():
    reg = MetricsRegistry()
    led = tprof.RooflineLedger(registry=reg)
    led.note_region("gbdt.route", 0.5, occurrences=10)
    rows = led.rows(peaks={"flops_per_s": None, "hbm_bytes_per_s": None})
    # measured time only: no cost -> no achieved/util keys at all
    assert set(rows["gbdt.route"]) == {"seconds", "occurrences", "source"}
    # cost known but NO peak: achieved present, utilization absent
    led.set_cost("gbdt.route", bytes_accessed=1.0e6)
    row = led.rows(peaks={"flops_per_s": None,
                          "hbm_bytes_per_s": None})["gbdt.route"]
    assert "achieved_hbm_bytes_per_s" in row and "hbm_util" not in row
    led.publish()
    assert reg.peek_gauge(tnames.op_hbm_util("gbdt.route")) is None
    # with a declared peak the gauge appears
    led._peaks = {"hbm_bytes_per_s": 1.0e12}
    led.publish()
    assert reg.peek_gauge(tnames.op_hbm_util("gbdt.route")) is not None
    assert reg.peek_gauge(tnames.op_flops_util("gbdt.route")) is None


def test_roofline_device_records_override_host_walls():
    led = tprof.RooflineLedger()
    led.note_region("gbdt.hist", 5.0, occurrences=3)
    led.ingest_ops([{"op": "fusion.1", "region": "gbdt.hist",
                     "occurrences": 7, "self_time_us": 2_000_000.0},
                    {"op": "copy.2", "region": tprof.UNSCOPED,
                     "occurrences": 1, "self_time_us": 1.0}])
    row = led.rows(peaks={})["gbdt.hist"]
    assert row["source"] == "device"
    assert row["seconds"] == pytest.approx(2.0)
    assert row["occurrences"] == 7
    export = led.export()
    assert [o["op"] for o in export["ops"]][0] == "fusion.1"
    assert set(export["regions"]) == {"gbdt.hist"}


def test_resolve_peaks_env_order(monkeypatch):
    monkeypatch.delenv("MMLSPARK_TPU_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv(tprof.PEAK_HBM_ENV, raising=False)
    explicit = tprof.resolve_peaks({"flops_per_s": 1.0,
                                    "hbm_bytes_per_s": 2.0})
    assert (explicit["flops_per_s"], explicit["hbm_bytes_per_s"]) == (1., 2.)
    monkeypatch.setenv("MMLSPARK_TPU_PEAK_TFLOPS", "197")
    monkeypatch.setenv(tprof.PEAK_HBM_ENV, "819")
    env = tprof.resolve_peaks()
    assert env["flops_per_s"] == pytest.approx(197e12)
    assert env["hbm_bytes_per_s"] == pytest.approx(819e9)
    # malformed env degrades to absent, not a crash or a guess (the CPU
    # chip kind is not in the chip table, so both sides stay None)
    monkeypatch.setenv("MMLSPARK_TPU_PEAK_TFLOPS", "lots")
    monkeypatch.setenv(tprof.PEAK_HBM_ENV, "-3")
    none = tprof.resolve_peaks()
    assert none["flops_per_s"] is None and none["hbm_bytes_per_s"] is None


# ------------------------------------------------- /debug/profile contract
@pytest.mark.parametrize("transport", ["selector", "threading"])
def test_debug_profile_contract_on_both_transports(
        transport, tmp_path, profile_dir):
    from mmlspark_tpu.io.serving import ServingQuery, ServingServer
    server = ServingServer(num_partitions=1, transport=transport).start()
    query = ServingQuery(server, lambda bodies: [{"ok": 1}] * len(bodies),
                         mode="continuous").start()
    session = tprof.get_profile_session()
    try:
        # 200: manifest with parsed (empty on CPU) op table
        manifest = _get_json(server.address + "/debug/profile?ms=20")
        assert manifest["ops"] == [] and manifest["ms"] == 20.0
        # 429 under the rate limit
        session.configure(min_interval_s=3600.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                server.address + "/debug/profile?ms=20", timeout=15)
        assert ei.value.code == 429
        # 400 on malformed ms (NaN included)
        for bad in ("abc", "nan", "-5"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    server.address + f"/debug/profile?ms={bad}", timeout=15)
            assert ei.value.code == 400, bad
        # 500 on a failed capture (unwritable profile dir), slot rolled back
        blocker = tmp_path / f"blk-{transport}"
        blocker.write_text("file")
        session.configure(profile_dir=str(blocker / "x"),
                          min_interval_s=0.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                server.address + "/debug/profile?ms=20", timeout=15)
        assert ei.value.code == 500
        # 503 when disabled
        session.configure(profile_dir="")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                server.address + "/debug/profile?ms=20", timeout=15)
        assert ei.value.code == 503
    finally:
        query.stop()
        server.stop()


def test_debug_profile_on_trainer_surface_and_registry(profile_dir):
    """The EXPOSITION_PATHS mount reaches the trainer ExpositionServer
    and the ServiceRegistry leader (shared handler body)."""
    from mmlspark_tpu.io import ServiceRegistry
    from mmlspark_tpu.telemetry.exposition import ExpositionServer
    server = ExpositionServer().start()
    try:
        manifest = _get_json(server.address + "/debug/profile?ms=10")
        assert manifest["reason"] == "on-demand"
        assert os.path.isdir(manifest["path"])
    finally:
        server.stop()
    reg = ServiceRegistry().start()
    try:
        manifest = _get_json(reg.address + "/debug/profile?ms=10")
        assert manifest["reason"] == "on-demand"
    finally:
        reg.stop()


# ------------------------------------------------------------------ watcher
def test_evaluate_rule_is_deterministic_pure_function():
    rule = WatchRule(key="k", max_value=10.0)
    quiet = [(float(i), 5.0) for i in range(6)]
    assert evaluate_rule(rule, quiet) is None
    breach = quiet + [(9.0, 11.0)]
    out1 = evaluate_rule(rule, breach)
    assert out1 == evaluate_rule(rule, breach)   # same series, same verdict
    assert out1["kind"] == "threshold" and out1["value"] == 11.0
    # below min_samples the rule stays quiet even on a breach
    assert evaluate_rule(WatchRule(key="k", max_value=10.0, min_samples=9),
                         breach) is None
    # median shift: a single spike does NOT trip (medians, not means)
    shift = WatchRule(key="k", shift=1.5, window=4, direction="up")
    spiky = [(float(i), 10.0) for i in range(7)] + [(8.0, 100.0)]
    assert evaluate_rule(shift, spiky) is None
    shifted = ([(float(i), 10.0) for i in range(4)]
               + [(float(i), 40.0) for i in range(4, 8)])
    out = evaluate_rule(shift, shifted)
    assert out["kind"] == "shift" and out["direction"] == "up"
    assert out["baseline"] == 10.0 and out["value"] == 40.0
    # down direction
    down = WatchRule(key="k", shift=1.5, window=4, direction="down")
    dropped = ([(float(i), 100.0) for i in range(4)]
               + [(float(i), 40.0) for i in range(4, 8)])
    assert evaluate_rule(down, dropped)["direction"] == "down"
    assert evaluate_rule(down, shifted) is None   # wrong direction


def test_watcher_transitions_events_and_gauge():
    reg = MetricsRegistry()
    tr = telemetry.Tracer(sample=1.0)
    w = TelemetryWatcher(
        rules=[WatchRule(key="p99", max_value=10.0)],
        registry=reg, tracer=tr, recorder=_NullRecorder())
    s = {"p99": [(float(i), 5.0) for i in range(5)]}
    assert w.check(s) == []
    s["p99"].append((9.0, 20.0))
    assert len(w.check(s)) == 1
    assert w.check(s) == []                     # staying tripped: no re-fire
    assert reg.get(tnames.TELEMETRY_WATCH_TRIPS) == 1
    assert reg.gauge(tnames.TELEMETRY_WATCH_TRIPPED) == 1
    assert len(tr.finished(tnames.TELEMETRY_WATCH_TRIP_EVENT)) == 1
    s["p99"] = [(float(i), 5.0) for i in range(6)]
    assert w.check(s) == []                     # recovery
    assert reg.gauge(tnames.TELEMETRY_WATCH_TRIPPED) == 0
    s["p99"].append((9.0, 30.0))
    assert len(w.check(s)) == 1                 # re-trips after recovery
    assert reg.get(tnames.TELEMETRY_WATCH_TRIPS) == 2
    assert w.stats()["trips_total"] == 2
    # a rule with no detector is a config error, loudly
    with pytest.raises(ValueError):
        TelemetryWatcher(rules=[WatchRule(key="x")])


class _NullRecorder:
    def on_verdict(self, verdict, reason="", source=""):
        return None


def test_watcher_is_a_flight_recorder_source(flight_dir):
    """A trip transition dumps a bundle through the recorder's per-source
    latch; recovery re-arms it for the next incident."""
    reg = MetricsRegistry()
    w = TelemetryWatcher(rules=[WatchRule(key="goodput", min_value=0.8)],
                         registry=reg, tracer=telemetry.Tracer(sample=0.0))
    healthy = {"goodput": [(float(i), 0.95) for i in range(5)]}
    burned = {"goodput": healthy["goodput"] + [(9.0, 0.3)]}
    w.check(healthy)
    assert not flight_dir.exists() or not list(flight_dir.iterdir())
    assert len(w.check(burned)) == 1
    bundles = [p for p in flight_dir.iterdir()
               if p.name.startswith("bundle-")]
    assert len(bundles) == 1 and "watch-goodput" in bundles[0].name
    manifest = json.loads((bundles[0] / "manifest.json").read_text())
    assert "roofline.json" in manifest["files"]
    w.check(burned)                              # latched: no second bundle
    assert len(list(flight_dir.iterdir())) == 1
    w.check(healthy)                             # recovery re-arms
    w.check(burned)
    assert len(list(flight_dir.iterdir())) == 2


# ------------------------------------------------------- poller JSONL sink
def test_poller_jsonl_sink_rotates_oldest_first(tmp_path, monkeypatch):
    from mmlspark_tpu.telemetry import poller as tpoller
    t = [1000.0]
    n = [0]

    class _Snap:
        def __init__(self, i):
            self.merged = {"telemetry.scrape.workers": 1, "x.p99": float(i)}
            self.slo = None

    monkeypatch.setattr(tpoller, "scrape_cluster",
                        lambda *a, **kw: _Snap(n[0]))
    path = tmp_path / "sink.jsonl"
    poller = tpoller.TelemetryPoller(
        "http://unused", jsonl_path=str(path), jsonl_max_bytes=1200,
        clock=lambda: t[0], history=64)
    for i in range(30):
        n[0] = i
        t[0] = 1000.0 + i
        poller.poll_once()
    assert path.stat().st_size <= 1200
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines, "rotation must keep the newest lines"
    # oldest-first eviction: the tail of the series survives, in order
    assert lines[-1]["metrics"]["x.p99"] == 29.0
    assert [ln["t"] for ln in lines] == sorted(ln["t"] for ln in lines)
    assert len(lines) < 30
    # in-memory series intact regardless of rotation
    assert len(poller.series("x.p99")) == 30
    # bounded offline export: oldest dropped first, newest always kept
    out = tmp_path / "export.jsonl"
    kept = poller.export_jsonl(str(out), max_bytes=500)
    exported = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert len(exported) == kept < 30
    assert exported[-1]["metrics"]["x.p99"] == 29.0
    assert out.stat().st_size <= 500


# ------------------------------------------------------- benchdiff backend
def test_benchdiff_excludes_non_tpu_rounds_from_gates(tmp_path, capsys):
    r1 = tmp_path / "B_r01.json"
    r1.write_text(json.dumps(
        {"n": 1, "parsed": {"metric": "m", "value": 100.0,
                            "backend": "tpu"}, "tail": ""}))
    # a CPU fallback round: 99% "regression" that must NOT gate
    r2 = tmp_path / "B_r02.json"
    r2.write_text(json.dumps(
        {"n": 2, "parsed": {"metric": "m", "value": 1.0,
                            "backend": "cpu"}, "tail": ""}))
    rc = benchdiff.main([str(r1), str(r2), "--threshold", "0.1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "excluded from perf gates (non-TPU backend)" in out
    # round-level backend declaration annotates records without one, and
    # BENCH_EXTRA-style nested records are harvested
    r3 = tmp_path / "B_r03.json"
    r3.write_text(json.dumps(
        {"backend": "cpu",
         "nested_headline": {"metric": "m", "value": 2.0},
         "wide_shapes": [{"metric": "m2", "value": 3.0}]}))
    rc = benchdiff.main([str(r1), str(r3), "--threshold", "0.1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert out.count("excluded from perf gates") == 2
    # the authoritative PARSED headline inherits a round-level backend
    # too (it is re-added after the dedup and must not gate as TPU)
    r5 = tmp_path / "B_r05.json"
    r5.write_text(json.dumps(
        {"n": 5, "backend": "cpu",
         "parsed": {"metric": "m", "value": 1.0}, "tail": ""}))
    rc = benchdiff.main([str(r1), str(r5), "--threshold", "0.1"])
    out = capsys.readouterr().out
    assert rc == 0 and "excluded from perf gates" in out
    # and a genuine TPU regression still fails
    r4 = tmp_path / "B_r04.json"
    r4.write_text(json.dumps(
        {"n": 4, "parsed": {"metric": "m", "value": 10.0,
                            "backend": "tpu"}, "tail": ""}))
    assert benchdiff.main([str(r1), str(r4), "--threshold", "0.1"]) == 1
    capsys.readouterr()


def test_benchdiff_rounds_with_cpu_extra_excluded(bench_rounds, capsys):
    """Over driver-format rounds: an extras file from a CPU run
    (backend=cpu) is harvested, visibly excluded, and contributes nothing
    to trajectories or gates — r01->r05 gate exactly as without it
    (including the r04->r05 hbm_utilization dip)."""
    files, extra = bench_rounds
    rc = benchdiff.main(files + [extra, "--threshold", "0.1"])
    out = capsys.readouterr().out
    assert "excluded from perf gates (non-TPU backend)" in out
    assert "backend=cpu" in out
    # the CPU headline value (tiny) must not appear in any trajectory
    for line in out.splitlines():
        if line.startswith("gbdt_train_rows_iters_per_sec"):
            assert "48931" not in line
    # gates identical to the r01->r05 run (the hbm dip still fires)
    rc_without = benchdiff.main(files + ["--threshold", "0.1"])
    capsys.readouterr()
    assert rc == rc_without == 1


# -------------------- acceptance: delay fault -> flag -> capture -> bundle
def _toy_supervisor(directory, reg, clock, faults=None, step_s=0.004, **kw):
    state = {"x": np.zeros(3, np.float64)}
    sup = TrainingSupervisor(
        directory, lambda: {"x": state["x"].copy()},
        lambda p: state.update(x=np.asarray(p["x"]).copy()),
        metrics=reg, faults=faults, step_clock=clock,
        handle_signals=False, **kw)

    def step(k):
        time.sleep(step_s)
        state["x"] = state["x"] + (k + 1)
        return float(state["x"][0])

    return sup, step


@pytest.mark.chaos
def test_delay_fault_straggler_triggers_profile_and_roofline_bundle(
        tmp_path, monkeypatch, flight_dir):
    """THE acceptance path on the CPU backend, seed-deterministic:
    a delay fault on host 1 of a two-host (heartbeat-file) run flags it
    as a straggler, the flag transition triggers a ProfileSession
    capture ON that host (capture succeeds, per-op table empty — no
    device planes on CPU), the goodput burn dumps a flight bundle whose
    roofline.json carries per-region records (train.step host walls),
    and the watcher trips on the goodput series — with straggler-flag,
    capture, and watch-trip events causally ordered in the span log."""
    from mmlspark_tpu.parallel.cluster import Heartbeat
    monkeypatch.setenv(tprof.PROFILE_MS_ENV, "25")
    profiles = tmp_path / "profiles"
    profiles.mkdir()
    tprof.configure_profile_session(profile_dir=str(profiles),
                                    min_interval_s=0.0)
    tracer = telemetry.get_tracer()
    tracer.configure(sample=1.0)
    tracer.clear()
    hb_dir = str(tmp_path / "hb")
    try:
        # host 0: healthy
        reg0 = MetricsRegistry()
        clock0 = StepClock(registry=reg0, install=False)
        hb0 = Heartbeat(hb_dir, process_id=0)
        sup0, step0 = _toy_supervisor(str(tmp_path / "ck0"), reg0, clock0,
                                      heartbeat=hb0, checkpoint_every=2,
                                      step_s=0.012)
        sup0.run(step0, 6)
        sup0.close()
        hb0.beat(6, stats=clock0.beat_stats())

        # host 1: every step pays a seeded 150ms injected stall
        reg1 = MetricsRegistry()
        clock1 = StepClock(registry=reg1)   # installed: bundle reads it
        hb1 = Heartbeat(hb_dir, process_id=1)
        inj = FaultInjector(seed=7, rules=[
            {"site": "train.step*", "kind": "delay", "param": 0.15,
             "prob": 1.0}])
        sup1, step1 = _toy_supervisor(str(tmp_path / "ck1"), reg1, clock1,
                                      heartbeat=hb1, faults=inj,
                                      checkpoint_every=1, step_s=0.002)
        goodput_series = []
        base_t = telemetry.wall_now()
        sup1.run(step1, 6)
        sup1.close()

        # 1) straggler flagged on host 1's own beat
        straggler_events = tracer.finished(tnames.TRAIN_STRAGGLER_EVENT)
        assert straggler_events
        assert straggler_events[-1]["attrs"]["host"] == 1
        # 2) the flag TRANSITION captured a profile on the flagged host:
        # capture succeeded, per-op table empty (CPU degrade), and the
        # capture event follows the straggler event causally (seq order)
        profile_events = tracer.finished(tnames.TELEMETRY_PROFILE_EVENT)
        assert len(profile_events) == 1
        assert profile_events[0]["attrs"]["reason"] == "straggler"
        assert profile_events[0]["attrs"]["ops"] == 0
        assert profile_events[0]["seq"] > straggler_events[0]["seq"]
        captured = [p for p in profiles.iterdir()
                    if p.name.startswith("profile-")]
        assert len(captured) == 1 and "straggler" in captured[0].name
        assert reliability_metrics.get(
            tnames.TELEMETRY_PROFILE_CAPTURES) == 1
        # 3) goodput burn -> flight bundle with per-region roofline.json
        engine = tslo.SLOEngine(
            objectives=tslo.trainer_objectives(goodput_floor=0.9),
            registry=reg1)
        verdict = engine.verdict()
        assert verdict["burning"]
        bundles = [p for p in flight_dir.iterdir()
                   if p.name.startswith("bundle-")]
        assert bundles, "burning verdict did not dump a bundle"
        roofline = json.loads(
            (bundles[-1] / "roofline.json").read_text())
        assert "train.step" in roofline["regions"]
        row = roofline["regions"]["train.step"]
        # both hosts' steps noted into the process ledger (6 + 6); the
        # injected stalls fire BEFORE the annotated region and land in
        # the goodput account as lost time, not in the step region wall
        assert row["source"] == "host" and row["occurrences"] >= 12
        assert row["seconds"] > 0.05
        # CPU degrade inside the bundle too: no utilization was guessed
        assert "hbm_util" not in row and "flops_util" not in row
        # 4) the watcher trips on the live goodput series and its trip
        # event lands AFTER the capture in the same causal span log
        goodput_series = [(base_t + i, 0.97) for i in range(5)]
        goodput_series.append(
            (base_t + 5, reg1.gauge(tnames.TRAIN_GOODPUT)))
        watcher = TelemetryWatcher(
            rules=[WatchRule(key=tnames.TRAIN_GOODPUT, min_value=0.8)],
            registry=reg1, tracer=tracer, recorder=_NullRecorder())
        trips = watcher.check({tnames.TRAIN_GOODPUT: goodput_series})
        assert len(trips) == 1 and trips[0]["value"] < 0.8
        trip_events = tracer.finished(tnames.TELEMETRY_WATCH_TRIP_EVENT)
        assert len(trip_events) == 1
        assert trip_events[0]["seq"] > profile_events[0]["seq"]
    finally:
        tracer.configure(sample=0.0)
        tracer.clear()
