"""Performance observability (ISSUE 8): compile/cost telemetry, trace
exemplars, resource gauges, the bounded LRU plan cache, and the
burn-triggered flight recorder.

Pins the new contracts: a seeded FaultInjector delay fault drives an SLO
burn whose verdict transition produces a flight-recorder bundle with
asserted contents (spans, verdict, compile records, memory);
`plan.recompiles` stays zero across repeated same-bucket serving batches
while LRU eviction pressure makes rebuilds countable; histogram
exemplars stay bounded under racing writers and render in OpenMetrics
syntax on /metrics and raw on /metrics.json; memory/compile metrics
merge fleet-wide with the documented semantics (gauges max, counters
sum); and the benchdiff CLI flags trajectory regressions."""
import json
import os
import threading
import time
import urllib.error
import urllib.request
from bisect import bisect_right

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.core import Table
from mmlspark_tpu.io.plan import compile_serving_transform
from mmlspark_tpu.reliability.faults import FaultInjector
from mmlspark_tpu.reliability.metrics import (Histogram,
                                              histogram_bounds_ms,
                                              reliability_metrics)
from mmlspark_tpu.telemetry import benchdiff
from mmlspark_tpu.telemetry import names as tnames
from mmlspark_tpu.telemetry import perf
from mmlspark_tpu.telemetry import slo as tslo
from mmlspark_tpu.telemetry.exposition import (merge_states,
                                               render_prometheus,
                                               scrape_cluster)
from mmlspark_tpu.telemetry.slo import Objective


@pytest.fixture
def perf_state():
    """Clean process registry (fast windows) + clean compile log; restore
    defaults after."""
    reliability_metrics.reset()
    perf.get_compile_log().clear()
    reliability_metrics.configure_windows(0.25, 40)   # 9.75 s span
    yield reliability_metrics
    reliability_metrics.reset()
    reliability_metrics.configure_windows(10.0, 31)


@pytest.fixture
def flight_dir(tmp_path):
    """Enable the process-default flight recorder into a tmp dir; fully
    disable and re-arm it after."""
    rec = perf.get_flight_recorder()
    rec.configure(bundle_dir=str(tmp_path), min_interval_s=0.0,
                  max_bundles=8, window_s=8.0)
    rec._burn_state.clear()
    rec._last_dump = None
    yield tmp_path
    rec.configure(bundle_dir="")
    rec._burn_state.clear()
    rec._last_dump = None


def _fit_gbdt(n=800, f=8, **kw):
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    kw.setdefault("num_iterations", 4)
    kw.setdefault("max_depth", 3)
    return GBDTClassifier(**kw).fit(Table({"features": x, "label": y}))


def _post(url, payload, timeout=15):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp, json.loads(resp.read())


def _get_json(url, timeout=15):
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


def _bundles(tmp_path, tag=None):
    out = sorted(p for p in tmp_path.iterdir()
                 if p.name.startswith("bundle-"))
    if tag is not None:
        out = [p for p in out if p.name.endswith(tag)]
    return out


# ------------------------------------------------------- compile telemetry
def test_compile_with_analysis_captures_cost_and_memory(perf_state):
    import jax.numpy as jnp
    a = jnp.ones((16, 16), jnp.float32)
    compiled = perf.compile_with_analysis(lambda v: v @ v, a,
                                          label="perftest.matmul")
    out = np.asarray(compiled(a))
    assert out.shape == (16, 16)
    rec = perf.get_compile_log().records()[-1]
    assert rec["label"] == "perftest.matmul"
    assert rec["seconds"] > 0.0 and rec["recompile"] is False
    # the CPU backend reports cost analysis; memory_analysis fields ride
    # along where present — both captured, neither required (graceful
    # degradation is the contract, asserted via the never-raise path)
    analysis = rec["analysis"]
    assert analysis, analysis
    assert analysis.get("flops", 0) > 0
    assert analysis.get("bytes_accessed", 0) > 0
    snap = reliability_metrics.snapshot()
    assert snap[tnames.PLAN_COMPILES] == 1
    assert snap.get(tnames.PLAN_RECOMPILES, 0) == 0
    assert snap["plan.compile.count"] == 1


def test_executable_analysis_degrades_to_empty():
    class Opaque:
        pass   # no cost_analysis / memory_analysis at all
    assert perf.executable_analysis(Opaque()) == {}

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            raise RuntimeError("backend says no")
    assert perf.executable_analysis(Broken()) == {}


def test_plan_recompiles_pinned_zero_on_repeated_same_bucket(perf_state):
    """Acceptance: >= 3 repeated same-bucket serving batches are pure
    cache hits — ONE plan.compile, zero plan.recompiles. A second bucket
    costs one more compile, still zero recompiles."""
    model = _fit_gbdt(num_iterations=5)
    # on a multi-device host the FIT itself compiles through the
    # distributed AotCache and is recorded too (ISSUE 9: collective
    # accounting rides every fit); this test pins the SERVING plan path,
    # so the count starts after the fit
    reliability_metrics.reset(prefix="plan.")
    perf.get_compile_log().clear()
    transform = compile_serving_transform(model, ["features"])
    body = json.dumps({"features": [0.1] * 8}).encode()
    for _ in range(4):
        replies = transform([body] * 3)           # bucket 4 every time
        assert all(r.status == 200 for r in replies)
    assert reliability_metrics.get(tnames.PLAN_COMPILES) == 1
    assert reliability_metrics.get(tnames.PLAN_RECOMPILES) == 0
    transform([body] * 7)                          # bucket 8: new compile
    assert reliability_metrics.get(tnames.PLAN_COMPILES) == 2
    assert reliability_metrics.get(tnames.PLAN_RECOMPILES) == 0
    # per-key compile seconds recorded for the autotuner
    per_key = perf.get_compile_log().per_key()
    key4 = f"{transform.fingerprint}@4"
    assert per_key[key4]["count"] == 1
    assert per_key[key4]["seconds"] >= 0.0


def test_plan_cache_lru_eviction_drains_not_invalidates(perf_state):
    """Cap 2, three buckets: the oldest evicts (counted), a HELD evicted
    plan keeps working (drain semantics — groundwork for hot-swap), and
    re-using the evicted bucket rebuilds, which the recompile detector
    counts."""
    model = _fit_gbdt(num_iterations=6)
    transform = compile_serving_transform(model, ["features"], max_plans=2)
    body = json.dumps({"features": [0.2] * 8}).encode()
    transform([body] * 3)                          # bucket 4
    held = transform._plan_for(3)                  # hold bucket-4 plan
    transform([body] * 7)                          # bucket 8
    transform([body] * 17)                         # bucket 32 -> evict 4
    stats = transform.stats()
    assert stats["evictions"] == 1 and stats["buckets"] == 2
    assert stats["capacity"] == 2
    assert reliability_metrics.get(tnames.SERVING_PLAN_EVICTIONS) == 1
    # the evicted plan object still scores (drained, not invalidated)
    assemble, run = held
    vals = np.asarray(run(assemble([json.loads(body)] * 3)))
    assert vals.shape[0] == 3
    # re-entering the evicted bucket is a REBUILD: recompile counted
    before = reliability_metrics.get(tnames.PLAN_RECOMPILES)
    transform([body] * 3)
    assert reliability_metrics.get(tnames.PLAN_RECOMPILES) == before + 1


# ------------------------------------------------------------- exemplars
def test_exemplars_bounded_and_consistent_under_racing_writers():
    h = Histogram("race.lat")
    bounds = histogram_bounds_ms()
    written = set()
    errs = []

    def writer(w):
        try:
            for i in range(300):
                ms = 0.5 if i % 2 else 400.0
                tid = f"w{w}-{i}"
                written.add(tid)
                h.observe_ms(ms, trace_id=tid)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    ex = h.exemplars()
    # bounded by construction: one slot per bucket
    assert 0 < len(ex) <= len(bounds) + 1
    for idx, (tid, ms, ts) in ex.items():
        assert tid in written                     # a real writer's id
        assert bisect_right(bounds, ms) == idx    # slot matches its value
        assert ts > 0.0
    assert h.count == 1800                        # no observation lost


def test_exemplars_absent_without_trace_id():
    h = Histogram("plain.lat")
    for _ in range(10):
        h.observe_ms(1.0)
    assert h.exemplars() == {}
    assert "exemplars" not in h.state()


def test_exemplar_exposition_prometheus_and_json(perf_state):
    """A served request's id (== trace id) surfaces as its latency
    bucket's exemplar in OpenMetrics syntax on /metrics and raw on
    /metrics.json."""
    from mmlspark_tpu.io.serving import ServingQuery, ServingServer
    server = ServingServer(num_partitions=1).start()
    query = ServingQuery(
        server, lambda bodies: [{"echo": json.loads(b)["x"]}
                                for b in bodies],
        mode="continuous").start()
    try:
        resp, _ = _post(server.address, {"x": 1})
        rid = resp.headers["X-Request-Id"]
        e2e = reliability_metrics.histogram(tnames.SERVING_REQUEST_E2E)
        deadline = time.monotonic() + 5.0
        while e2e.count < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        state = _get_json(server.address + "/metrics.json")
        exemplars = state["hists"][tnames.SERVING_REQUEST_E2E]["exemplars"]
        assert any(e[0] == rid for e in exemplars.values()), exemplars
        # the DEFAULT /metrics stays clean 0.0.4: exemplar syntax would
        # make a stock Prometheus parser reject the whole scrape
        resp = urllib.request.urlopen(server.address + "/metrics",
                                      timeout=15)
        assert "0.0.4" in resp.headers["Content-Type"]
        assert "trace_id=" not in resp.read().decode()
        # ?exemplars=1 opts into OpenMetrics: exemplar suffixes on
        # bucket lines, the OpenMetrics content type, and an EOF trailer
        resp = urllib.request.urlopen(
            server.address + "/metrics?exemplars=1", timeout=15)
        assert "openmetrics-text" in resp.headers["Content-Type"]
        text = resp.read().decode()
        assert text.endswith("# EOF\n")
        assert f'# {{trace_id="{rid}"}}' in text
        # exemplar lines live on bucket samples of the e2e histogram
        line = [ln for ln in text.splitlines()
                if f'trace_id="{rid}"' in ln][0]
        assert line.startswith("serving_request_e2e_seconds_bucket{le=")
    finally:
        query.stop()
        server.stop()


def test_both_exposition_formats_parse_under_official_parsers(perf_state):
    """The default /metrics must parse as Prometheus 0.0.4 and the
    ?exemplars=1 variant as STRICT OpenMetrics (family names without
    _total, exemplar syntax, # EOF) — validated against the official
    prometheus_client parsers when available."""
    prometheus_client = pytest.importorskip("prometheus_client")
    from mmlspark_tpu.telemetry import metrics_http_response
    reliability_metrics.inc(tnames.SERVING_SHED_REQUESTS, 3)
    reliability_metrics.observe("data.fit_bins", 0.5)
    reliability_metrics.observe_ms(tnames.SERVING_REQUEST_E2E, 123.0,
                                   trace_id="tid42")
    status, payload, ctype = metrics_http_response("/metrics?exemplars=1")
    assert "openmetrics-text" in ctype
    from prometheus_client.openmetrics.parser import (
        text_string_to_metric_families)
    fams = {f.name: f for f in
            text_string_to_metric_families(payload.decode())}
    assert "serving_shed_requests" in fams          # family w/o _total
    exemplar_samples = [s for f in fams.values() for s in f.samples
                        if s.exemplar]
    assert exemplar_samples
    ex = exemplar_samples[0].exemplar
    assert ex.labels == {"trace_id": "tid42"}
    assert ex.timestamp is not None                 # ms-precision ts kept
    status, payload, ctype = metrics_http_response("/metrics")
    assert "0.0.4" in ctype
    from prometheus_client.parser import (
        text_string_to_metric_families as parse_004)
    assert list(parse_004(payload.decode()))        # parses clean
    assert "trace_id" not in payload.decode()


def test_windowed_state_carries_no_exemplars(perf_state):
    reliability_metrics.observe_ms(tnames.SERVING_REQUEST_E2E, 5.0,
                                   trace_id="win-1")
    st = reliability_metrics.export_state(window_s=8.0)
    assert "exemplars" not in st["hists"][tnames.SERVING_REQUEST_E2E]
    cum = reliability_metrics.export_state()
    assert "exemplars" in cum["hists"][tnames.SERVING_REQUEST_E2E]


# ----------------------------------------------------- resource gauges
def test_resource_gauges_sampled_on_scrape(perf_state):
    from mmlspark_tpu.io.serving import ServingServer
    server = ServingServer(num_partitions=1).start()
    try:
        state = _get_json(server.address + "/metrics.json")
        assert state["gauges"][tnames.HOST_RSS_BYTES] > 0
        # device gauges appear only where memory_stats() does (TPU yes,
        # CPU backend None) — presence is optional, absence is graceful
        stats = perf.sample_resource_stats()
        if any(d["stats"] for d in stats["devices"]):
            assert state["gauges"][tnames.DEVICE_MEM_BYTES_IN_USE] > 0
    finally:
        server.stop()


def test_memory_and_compile_merge_semantics(perf_state):
    """Fleet merge discipline for the new series: compile counters SUM,
    memory gauges keep MAX (worst headroom wins), exemplars keep the
    newest per bucket."""
    hist_a = Histogram("m.lat")
    hist_a.observe_ms(3.0, trace_id="old")
    sa = hist_a.state()
    sa["exemplars"] = {k: [v[0], v[1], 1000.0]
                       for k, v in sa["exemplars"].items()}
    hist_b = Histogram("m.lat")
    hist_b.observe_ms(3.0, trace_id="new")
    sb = hist_b.state()
    sb["exemplars"] = {k: [v[0], v[1], 2000.0]
                       for k, v in sb["exemplars"].items()}
    merged = merge_states([
        {"counters": {tnames.PLAN_COMPILES: 3, tnames.PLAN_RECOMPILES: 1},
         "gauges": {tnames.HOST_RSS_BYTES: 100.0,
                    tnames.DEVICE_MEM_BYTES_IN_USE: 7.0},
         "timings": {}, "hists": {"m.lat": sa}},
        {"counters": {tnames.PLAN_COMPILES: 4},
         "gauges": {tnames.HOST_RSS_BYTES: 250.0},
         "timings": {}, "hists": {"m.lat": sb}}])
    assert merged["counters"][tnames.PLAN_COMPILES] == 7      # sum
    assert merged["counters"][tnames.PLAN_RECOMPILES] == 1
    assert merged["gauges"][tnames.HOST_RSS_BYTES] == 250.0   # max
    assert merged["gauges"][tnames.DEVICE_MEM_BYTES_IN_USE] == 7.0
    (ex,) = merged["hists"]["m.lat"]["exemplars"].values()
    assert ex[0] == "new" and ex[2] == 2000.0                 # newest wins
    # the same rows render fine as Prometheus text
    text = render_prometheus(state=merged)
    assert "plan_compiles_total 7" in text
    assert "host_rss_bytes 250" in text


def test_scrape_cluster_carries_memory_next_to_latency(perf_state):
    from mmlspark_tpu.io import ServiceRegistry, report_server_to_registry
    from mmlspark_tpu.io.serving import ServingServer
    reg = ServiceRegistry().start()
    server = ServingServer(num_partitions=1).start()
    try:
        host, port = server._httpd.server_address[:2]
        report_server_to_registry(reg.address, "memscrape", host, port)
        snap = scrape_cluster(reg.address)
        assert snap.merged[tnames.HOST_RSS_BYTES] > 0
    finally:
        server.stop()
        reg.stop()


# -------------------------------------------------------- flight recorder
def test_delay_fault_burn_produces_flight_bundle(perf_state, flight_dir):
    """THE acceptance path: a seeded FaultInjector delay fault pushes
    every served request over the latency objective; the SLO verdict
    transition to burning dumps exactly one bundle whose spans, verdict,
    compile records, metrics, and memory sample are all asserted. The
    on-demand GET /debug/bundle and its rate limit ride the same test
    server."""
    from mmlspark_tpu.io.serving import ServingQuery, ServingServer
    model = _fit_gbdt(num_iterations=7)
    transform = compile_serving_transform(model, ["features"])
    inj = FaultInjector(seed=11, rules=[
        {"site": "serving.worker", "kind": "delay",
         "param": 0.05, "prob": 1.0}])
    server = ServingServer(num_partitions=1, faults=inj).start()
    query = ServingQuery(server, transform, mode="continuous").start()
    objectives = [Objective(name="serving.e2e.p99", kind=tslo.LATENCY,
                            metric=tnames.SERVING_REQUEST_E2E,
                            threshold_ms=20.0, quantile=99.0,
                            window_s=8.0)]
    tslo.configure(objectives)
    telemetry.configure(sample=1.0)
    try:
        for i in range(6):
            _post(server.address, {"features": [0.1 * i] * 8})
        e2e = reliability_metrics.histogram(tnames.SERVING_REQUEST_E2E)
        deadline = time.monotonic() + 5.0
        while e2e.count < 6 and time.monotonic() < deadline:
            time.sleep(0.01)
        verdict = _get_json(server.address + "/slo")
        assert verdict["burning"], verdict

        bundles = _bundles(flight_dir, "slo-burn")
        assert len(bundles) == 1, bundles
        bundle = bundles[0]
        manifest = json.loads((bundle / "manifest.json").read_text())
        assert manifest["reason"] == "slo-burn"
        assert manifest["burning"] is True
        slo_dump = json.loads((bundle / "slo.json").read_text())
        assert slo_dump["burning"] is True
        w = slo_dump["objectives"][0]["windows"][0]
        assert w["violations"] == w["count"] == 6
        spans = [json.loads(ln) for ln
                 in (bundle / "spans.jsonl").read_text().splitlines()]
        names = {s["name"] for s in spans}
        assert tnames.SERVING_REQUEST_SPAN in names
        assert tnames.PLAN_COMPILE_SPAN in names
        compiles = json.loads((bundle / "compiles.json").read_text())
        assert any(r["fingerprint"] == transform.fingerprint
                   for r in compiles["records"])
        assert compiles["stats"]["recompiles"] == 0
        metrics = json.loads((bundle / "metrics.json").read_text())
        assert tnames.SERVING_REQUEST_E2E in metrics["hists"]
        windowed = json.loads(
            (bundle / "metrics_window.json").read_text())
        assert windowed["window_s"] > 0.0
        memory = json.loads((bundle / "memory.json").read_text())
        assert memory["host_rss_bytes"] > 0
        assert (bundle / "pending.jsonl").exists()

        # STAYING burning is not a transition: no second slo-burn bundle
        verdict2 = _get_json(server.address + "/slo")
        assert verdict2["burning"]
        assert len(_bundles(flight_dir, "slo-burn")) == 1

        # on-demand dump via the debug endpoint
        manifest2 = _get_json(server.address + "/debug/bundle")
        assert manifest2["reason"] == "on-demand"
        assert len(_bundles(flight_dir)) == 2

        # rate limit: a tight scrape loop gets 429 + a suppressed count
        perf.configure_flight_recorder(min_interval_s=3600.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.address + "/debug/bundle",
                                   timeout=15)
        assert ei.value.code == 429
        assert reliability_metrics.get(
            tnames.TELEMETRY_BUNDLE_SUPPRESSED) >= 1
        assert reliability_metrics.get(tnames.TELEMETRY_BUNDLE_DUMPS) == 2
    finally:
        telemetry.configure(sample=0.0)
        tslo.configure(None)
        query.stop()
        server.stop()


def test_debug_bundle_disabled_answers_503(perf_state):
    from mmlspark_tpu.io.serving import ServingServer
    server = ServingServer(num_partitions=1).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.address + "/debug/bundle",
                                   timeout=15)
        assert ei.value.code == 503
    finally:
        server.stop()


def test_bundle_retention_is_bounded(perf_state, flight_dir):
    rec = perf.get_flight_recorder()
    rec.configure(max_bundles=3)
    for i in range(6):
        assert rec.dump(f"r{i}") is not None
    kept = _bundles(flight_dir)
    assert len(kept) == 3
    assert [p.name.rsplit("-", 1)[-1] for p in kept] == ["r3", "r4", "r5"]


def test_suppressed_burn_transition_retries(perf_state, flight_dir):
    """A burn transition whose dump was rate-limit-suppressed must NOT
    latch: the next burning verdict retries, so an earlier on-demand
    dump's rate-limit slot cannot swallow the incident's bundle. Once a
    dump SUCCEEDS the latch holds until the burn clears."""
    rec = perf.get_flight_recorder()
    rec.configure(min_interval_s=3600.0)
    assert rec.dump("warm") is not None          # consumes the slot
    assert rec.on_verdict({"burning": True}) is None     # suppressed
    assert reliability_metrics.get(
        tnames.TELEMETRY_BUNDLE_SUPPRESSED) >= 1
    rec.configure(min_interval_s=0.0)
    assert rec.on_verdict({"burning": True}) is not None  # retried
    assert rec.on_verdict({"burning": True}) is None      # latched
    rec.on_verdict({"burning": False})                    # incident over
    assert rec.on_verdict({"burning": True}) is not None  # re-armed


def test_failed_dump_rolls_back_rate_limit_and_answers_500(
        perf_state, flight_dir):
    """An unwritable bundle dir raises OSError with the rate-limit slot
    given back (a failed dump must not shadow the next trigger), and the
    debug endpoint turns it into a 500 instead of dropping the
    connection."""
    from mmlspark_tpu.telemetry.exposition import metrics_http_response
    rec = perf.get_flight_recorder()
    blocker = flight_dir / "blocker"
    blocker.write_text("not a directory")
    rec.configure(bundle_dir=str(blocker), min_interval_s=3600.0)
    with pytest.raises(OSError):
        rec.dump("broken")
    status, payload, _ = metrics_http_response("/debug/bundle")
    assert status == 500 and b"bundle write failed" in payload
    # slot rolled back: a dump against a good dir succeeds IMMEDIATELY
    rec.configure(bundle_dir=str(flight_dir))
    assert rec.dump("after-failure") is not None
    # non-OSError failures (unserializable verdict) roll back too, and
    # the partial bundle dir is cleaned up
    rec.configure(min_interval_s=3600.0)
    rec._last_dump = None
    with pytest.raises(TypeError):
        rec.dump("bad-verdict", verdict={"burning": object()})
    assert _bundles(flight_dir, "bad-verdict") == []
    assert rec.dump("recovered") is not None


def test_poller_fleet_burn_triggers_bundle(perf_state, flight_dir):
    """The fleet-side trigger: the poller's MERGED verdict transitioning
    to burning dumps a local bundle tagged fleet-slo-burn."""
    from mmlspark_tpu.io import ServiceRegistry, report_server_to_registry
    from mmlspark_tpu.io.serving import ServingServer
    from mmlspark_tpu.telemetry import TelemetryPoller
    reg = ServiceRegistry().start()
    server = ServingServer(num_partitions=1).start()
    tslo.configure([Objective(name="serving.e2e.p99", kind=tslo.LATENCY,
                              metric=tnames.SERVING_REQUEST_E2E,
                              threshold_ms=20.0, quantile=99.0,
                              window_s=8.0)])
    try:
        host, port = server._httpd.server_address[:2]
        report_server_to_registry(reg.address, "burnpoll", host, port)
        for _ in range(10):
            reliability_metrics.observe_ms(tnames.SERVING_REQUEST_E2E,
                                           60_000.0)
        poller = TelemetryPoller(reg.address, interval_s=5.0, window_s=8.0,
                                 flight_on_burn=True)
        sample = poller.poll_once()
        assert sample["slo"]["burning"]
        assert len(_bundles(flight_dir, "fleet-slo-burn")) == 1
        poller.poll_once()   # still burning: no second fleet bundle
        assert len(_bundles(flight_dir, "fleet-slo-burn")) == 1
    finally:
        tslo.configure(None)
        server.stop()
        reg.stop()


# ------------------------------------------------------------- benchdiff
def _write_round(path, n, records):
    tail = "\n".join(json.dumps(r) for r in records)
    path.write_text(json.dumps(
        {"n": n, "rc": 0, "tail": tail, "parsed": records[-1]}))


def test_benchdiff_reports_deltas_and_flags_regression(tmp_path, capsys):
    r1 = tmp_path / "BENCH_r01.json"
    r2 = tmp_path / "BENCH_r02.json"
    _write_round(r1, 1, [
        {"metric": "serving_fast_req_per_sec", "value": 5000.0},
        {"metric": "gbdt_train_rows_iters_per_sec", "value": 100.0}])
    _write_round(r2, 2, [
        {"metric": "serving_fast_req_per_sec", "value": 5100.0},
        {"metric": "gbdt_train_rows_iters_per_sec", "value": 50.0}])
    files = [str(r2), str(r1)]   # out of order: the n key must sort them

    # informational run: no threshold, exit 0, every metric reported
    assert benchdiff.main(files) == 0
    out = capsys.readouterr().out
    assert "gbdt_train_rows_iters_per_sec" in out
    assert "r01:100 -> r02:50" in out
    assert "-50.0%" in out

    # threshold run: the 50% drop fails, the 2% gain does not
    assert benchdiff.main(["--threshold", "0.15"] + files) == 1
    err = capsys.readouterr().err
    assert "REGRESSIONS" in err and "gbdt_train" in err

    # a lower-is-better metric regresses on the way UP
    _write_round(r1, 1, [{"metric": "gbdt_e2e_fit_8m_32f", "value": 10.0}])
    _write_round(r2, 2, [{"metric": "gbdt_e2e_fit_8m_32f", "value": 14.0}])
    assert benchdiff.main(["--threshold", "0.15", "--lower-better",
                           "gbdt_e2e_fit_8m_32f"] + files) == 1
    assert benchdiff.main(["--threshold", "0.5", "--lower-better",
                           "gbdt_e2e_fit_8m_32f"] + files) == 0
    capsys.readouterr()


def test_benchdiff_gbdt_gates(tmp_path, capsys):
    """Round-6 GBDT regression gates: the headline record's vs_baseline
    and hbm_utilization synthesize per-shape derived records (higher is
    better) that gate like MULTICHIP bubble/traffic — a throughput 'win'
    that tanked the honesty metric fails the diff, and the wide shape's
    record gates independently of the canonical 8M headline even though
    both share one metric string."""
    r1 = tmp_path / "BENCH_r01.json"
    r2 = tmp_path / "BENCH_r02.json"

    def rec(shape, vsb, hbm, value=100.0):
        return {"metric": "gbdt_train_rows_iters_per_sec", "value": value,
                "shape": shape, "vs_baseline": vsb, "hbm_utilization": hbm}

    _write_round(r1, 1, [rec("1000000x128x255bins x10it", 0.9, 0.05),
                         rec("8000000x32x64bins x20it", 4.4, 0.02)])
    # headline value/ratio improves but hbm_utilization halves -> gated
    _write_round(r2, 2, [rec("1000000x128x255bins x10it", 1.1, 0.05),
                         rec("8000000x32x64bins x20it", 5.0, 0.01,
                             value=120.0)])
    files = [str(r1), str(r2)]
    assert benchdiff.main(["--threshold", "0.15"] + files) == 1
    err = capsys.readouterr().err
    assert "gbdt.8000000x32x64bins_x20it.hbm_utilization" in err
    assert "vs_baseline" not in err          # the ratio itself improved

    # a vs_baseline drop on the WIDE shape alone is also caught
    _write_round(r2, 2, [rec("1000000x128x255bins x10it", 0.5, 0.05),
                         rec("8000000x32x64bins x20it", 4.4, 0.02)])
    assert benchdiff.main(["--threshold", "0.15"] + files) == 1
    err = capsys.readouterr().err
    assert "gbdt.1000000x128x255bins_x10it.vs_baseline" in err

    # unchanged rounds gate clean
    _write_round(r2, 2, [rec("1000000x128x255bins x10it", 0.9, 0.05),
                         rec("8000000x32x64bins x20it", 4.4, 0.02)])
    assert benchdiff.main(["--threshold", "0.15"] + files) == 0
    capsys.readouterr()


def test_benchdiff_fleet_gates(tmp_path, capsys):
    """Round-16 fleet gates: the fleet headline record synthesizes
    fleet.rollback_window_p99_ms and fleet.requests_dropped as born
    lower-is-better — a round that stretched the chaos-window tail or
    dropped even one request during rollback fails the diff even though
    fleet req/s improved."""
    r1 = tmp_path / "BENCH_r01.json"
    r2 = tmp_path / "BENCH_r02.json"

    def rec(value, p99, dropped):
        return {"metric": "fleet_req_per_sec", "value": value,
                "rollback_window_p99_ms": p99,
                "requests_dropped": dropped}

    _write_round(r1, 1, [rec(900.0, 40.0, 0)])
    # req/s up, but the rollback-window tail doubled -> gated
    _write_round(r2, 2, [rec(1100.0, 85.0, 0)])
    files = [str(r1), str(r2)]
    assert benchdiff.main(["--threshold", "0.15"] + files) == 1
    err = capsys.readouterr().err
    assert "fleet.rollback_window_p99_ms" in err

    # a single dropped request gates (0 -> 1 is an infinite regression)
    _write_round(r2, 2, [rec(1100.0, 40.0, 1)])
    assert benchdiff.main(["--threshold", "0.15"] + files) == 1
    err = capsys.readouterr().err
    assert "fleet.requests_dropped" in err

    # clean round: faster, same tail, still zero drops
    _write_round(r2, 2, [rec(1100.0, 38.0, 0)])
    assert benchdiff.main(["--threshold", "0.15"] + files) == 0
    capsys.readouterr()


def test_benchdiff_gbdt_gates_on_driver_rounds(bench_rounds):
    """Driver-format headline rounds must parse and synthesize the derived
    per-shape gate records without error (threshold-free informational
    run)."""
    files, _ = bench_rounds
    rounds = [benchdiff.load_round(f) for f in files]
    labeled = [(f"r{i}", by) for i, (_, by) in enumerate(rounds)]
    lines, _ = benchdiff.diff_rounds(labeled)
    assert any("gbdt." in ln and ".vs_baseline" in ln for ln in lines)


def test_benchdiff_natural_order_and_unreadable_input(tmp_path, capsys):
    """Filename fallback (no wrapper `n`) orders r2 before r10 — a
    lexicographic sort would compare the wrong last-vs-prev pair — and a
    binary file in the glob is 'unreadable input' (exit 2), not a
    traceback."""
    r2 = tmp_path / "BENCH_r2.json"
    r10 = tmp_path / "BENCH_r10.json"
    r2.write_text(json.dumps({"metric": "m", "value": 100.0}))
    r10.write_text(json.dumps({"metric": "m", "value": 90.0}))
    assert benchdiff.main([str(r10), str(r2)]) == 0
    out = capsys.readouterr().out
    assert out.index("r2.json:100") < out.index("r10.json:90")
    # last-vs-prev is r10 vs r2: a 10% drop, flagged at a 5% threshold
    assert benchdiff.main(["--threshold", "0.05",
                           str(r10), str(r2)]) == 1
    capsys.readouterr()
    bad = tmp_path / "binary.json"
    bad.write_bytes(b"\xff\xfe\x00\x01")
    assert benchdiff.main([str(bad)]) == 2
    assert "cannot read" in capsys.readouterr().err
    # a zero baseline that STAYS zero is unchanged, not an inf-percent
    # regression (error counts are naturally 0 -> 0 under lower-better)
    r2.write_text(json.dumps({"metric": "errs", "value": 0.0}))
    r10.write_text(json.dumps({"metric": "errs", "value": 0.0}))
    assert benchdiff.main(["--threshold", "0.1", "--lower-better", "errs",
                           str(r2), str(r10)]) == 0
    capsys.readouterr()


def test_benchdiff_cli_subprocess(tmp_path):
    import subprocess
    import sys
    r1 = tmp_path / "BENCH_r01.json"
    _write_round(r1, 1, [{"metric": "m", "value": 1.0}])
    proc = subprocess.run(
        [sys.executable, "-m", "mmlspark_tpu.telemetry.benchdiff",
         str(r1)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "single round" in proc.stdout


# ------------------------------------------------------------- bench math
def test_hbm_utilization_helper():
    assert perf.hbm_utilization(2e9, 10.0) == pytest.approx(0.2)
    assert perf.hbm_utilization(2e9, 0.0) == 0.0
    assert perf.hbm_utilization(2e9, None) == 0.0
