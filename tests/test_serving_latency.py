"""Serving hot-path contracts: adaptive micro-batching, the compiled-plan
cache, and latency-percentile observability.

Per the round-5 advisor flake finding (GPipe M-sweep): tier-1 asserts
ORDERING / MONOTONIC invariants and metric PRESENCE only — never absolute
wall-clock thresholds.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core import Table
from mmlspark_tpu.io.plan import compile_serving_transform, pipeline_fingerprint
from mmlspark_tpu.io.serving import Reply, ServingQuery, ServingServer, serve_pipeline
from mmlspark_tpu.reliability.metrics import reliability_metrics


def _fit_gbdt(n=2000, f=8, **kw):
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    kw.setdefault("num_iterations", 5)
    kw.setdefault("max_depth", 3)
    return GBDTClassifier(**kw).fit(Table({"features": x, "label": y}))


def _post(url, obj, timeout=10):
    req = urllib.request.Request(url, data=json.dumps(obj).encode(),
                                 headers={"Content-Type": "application/json"},
                                 method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


# ------------------------------------------------------------- plan cache
def test_plan_cache_zero_recompiles_same_bucket():
    """Repeated same-bucket batches must be pure cache HITS: exactly one
    miss per distinct (fingerprint, bucket) key — the zero-recompile
    invariant the shape buckets exist for."""
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    body = json.dumps({"features": [0.1] * 8}).encode()
    for _ in range(10):
        replies = transform([body] * 3)       # bucket 4 every time
        assert all(isinstance(r, Reply) and r.status == 200 for r in replies)
    stats = transform.stats()
    assert stats["hits"] == 9 and stats["misses"] == 1, stats
    assert stats["buckets"] == 1 and stats["evictions"] == 0, stats
    # a second bucket costs exactly one more miss, then hits again
    transform([body] * 7)                     # bucket 8
    transform([body] * 5)                     # bucket 8 again -> hit
    stats = transform.stats()
    assert stats["misses"] == 2 and stats["buckets"] == 2, stats


def test_plan_cache_counters_in_metrics():
    reliability_metrics.reset("serving.")
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    body = json.dumps({"features": [0.2] * 8}).encode()
    for _ in range(4):
        transform([body])
    snap = reliability_metrics.snapshot()
    assert snap.get("serving.plan.misses") == 1, snap
    assert snap.get("serving.plan.hits") == 3, snap


def test_plan_cache_miss_stampede_single_flight():
    """Two workers missing the same (fingerprint, bucket) CONCURRENTLY
    must produce exactly ONE compile: the second misser blocks on the
    builder and receives the same plan object — `serving.plan.misses`
    stays pinned at 1 however many partitions race a cold cache."""
    reliability_metrics.reset("serving.")
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    builds = []
    in_build = threading.Event()
    release = threading.Event()
    real_build = transform._build_plan

    def slow_build(bucket, handle=None):
        builds.append(bucket)
        in_build.set()
        assert release.wait(10), "test orchestration stalled"
        return real_build(bucket, handle)

    transform._build_plan = slow_build
    plans = []
    threads = [threading.Thread(
        target=lambda: plans.append(transform._plan_for(3)))
        for _ in range(2)]
    threads[0].start()
    assert in_build.wait(10)         # first thread is inside the compile
    threads[1].start()               # second thread misses the same key
    time.sleep(0.05)                 # give it time to reach the wait path
    release.set()
    for th in threads:
        th.join(timeout=10)
    assert len(plans) == 2
    assert plans[0] is plans[1]      # both got THE plan, not copies
    assert builds == [4]             # exactly one compile (bucket 4)
    stats = transform.stats()
    assert stats["misses"] == 1 and stats["hits"] == 1, stats
    assert reliability_metrics.get("serving.plan.misses") == 1
    assert reliability_metrics.get("serving.plan.hits") == 1


def test_plan_build_failure_not_cached():
    """A builder that raises must not poison the cache: waiters (and the
    next caller) retry the build instead of inheriting the failure."""
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    real_build = transform._build_plan
    calls = []

    def failing_once(bucket, handle=None):
        calls.append(bucket)
        if len(calls) == 1:
            raise RuntimeError("transient build failure")
        return real_build(bucket, handle)

    transform._build_plan = failing_once
    with pytest.raises(RuntimeError, match="transient"):
        transform._plan_for(3)
    plan = transform._plan_for(3)    # retried, cached
    assert plan is transform._plan_for(3)
    assert len(calls) == 2
    assert not transform._building   # no leaked single-flight events


def test_fingerprint_distinguishes_models():
    a, b = _fit_gbdt(num_iterations=5), _fit_gbdt(num_iterations=6)
    assert pipeline_fingerprint(a) != pipeline_fingerprint(b)
    assert pipeline_fingerprint(a) == pipeline_fingerprint(a)


def test_serving_kernel_matches_transform():
    """The fast path's prebuilt kernel must agree with the Table transform
    it replaces — prediction values bit-equal (threshold/argmax outputs)."""
    model = _fit_gbdt(num_iterations=10, max_depth=4)
    kern = model._serving_kernel("prediction")
    assert kern is not None
    x = np.random.default_rng(1).normal(size=(33, 8)).astype(np.float32)
    via_table = np.asarray(model.transform(
        Table({"features": x}))["prediction"])
    assert np.array_equal(kern(x), via_table)


def test_generic_plan_pads_and_slices():
    """A model WITHOUT a serving kernel takes the bucketed generic path:
    outputs for n rows must match an unpadded transform exactly even when
    n is not a bucket size (padding rows must never leak into replies)."""
    from mmlspark_tpu.models.linear import LogisticRegression
    rng = np.random.default_rng(0)
    x = rng.normal(size=(200, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    model = LogisticRegression(max_iter=50).fit(
        Table({"features": x, "label": y}))
    transform = compile_serving_transform(model, ["features"])
    rows = [{"features": [float(v), 0.0, 0.0, 0.0]}
            for v in (-2.0, -1.0, 1.0, 2.0, 3.0)]     # n=5 -> bucket 8
    replies = transform([json.dumps(r).encode() for r in rows])
    got = [json.loads(r.data)["prediction"] for r in replies]
    assert got == [0.0, 0.0, 1.0, 1.0, 1.0]


# --------------------------------------------------- per-row 400 isolation
def test_bad_value_row_isolated_without_replay():
    """A PARSEABLE body whose value breaks columnar assembly (wrong type /
    ragged vector) must 400 alone in the same pass — batch-mates answer
    200 without riding the MAX_REPLAYS machinery."""
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    good = json.dumps({"features": [0.5] * 8}).encode()
    replies = transform([good,
                         json.dumps({"features": "hello"}).encode(),
                         json.dumps({"features": [1.0, 2.0]}).encode(),
                         good])
    assert replies[0].status == 200 and replies[3].status == 200
    assert replies[1].status == 400
    assert replies[2].status == 400


def test_mutually_ragged_rows_isolated_without_replay():
    """Rows that are each valid ALONE but mutually incompatible (two
    different vector widths) must not escape the transform and ride the
    MAX_REPLAYS machinery: each row scores in its own batch, in the same
    pass."""

    class WidthAgnostic:
        """Generic-path model (no _serving_kernel) that accepts any
        feature width — the shape a real ragged-tolerant stage has."""

        def transform(self, t):
            x = np.asarray(t["features"])
            return Table({"prediction": x.sum(axis=1)})

    transform = compile_serving_transform(WidthAgnostic(), ["features"])
    replies = transform([json.dumps({"features": [1.0, 2.0]}).encode(),
                         json.dumps({"features": [1.0, 2.0, 3.0]}).encode()])
    assert [r.status for r in replies] == [200, 200]
    assert json.loads(replies[0].data)["prediction"] == 3.0
    assert json.loads(replies[1].data)["prediction"] == 6.0


def test_nonfinite_prediction_encodes_like_legacy():
    """Non-finite floats must serialize as json.dumps' NaN/Infinity tokens
    (what the legacy path emitted and json.loads accepts) — never Python's
    repr 'nan'/'inf', which nothing parses."""
    from mmlspark_tpu.models.linear import LinearRegression
    rng = np.random.default_rng(0)
    x = rng.normal(size=(100, 2)).astype(np.float32)
    y = x[:, 0] * 2.0
    model = LinearRegression().fit(Table({"features": x, "label": y}))
    transform = compile_serving_transform(model, ["features"])
    replies = transform([json.dumps({"features": [float("nan"), 0.0]}).encode(),
                        json.dumps({"features": [1.0, 0.0]}).encode()])
    out = json.loads(replies[0].data)          # parseable, not b'... nan}'
    assert out["prediction"] != out["prediction"]   # NaN round-trips
    assert json.loads(replies[1].data)["prediction"] == pytest.approx(
        2.0, abs=0.2)


def test_server_fault_is_not_blamed_on_client():
    """A SERVER misconfiguration (e.g. an output column the pipeline never
    produces) must propagate to the replay/502 machinery — never be
    answered 400 as if the request were bad."""
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"],
                                          output_col="no_such_col")
    good = json.dumps({"features": [0.5] * 8}).encode()
    with pytest.raises(KeyError):
        transform([good])


def test_malformed_json_row_gets_400_alone():
    """Satellite: a malformed body answers 400 immediately — no
    MAX_REPLAYS poison-batch machinery — and its batch-mates stay 200."""
    model = _fit_gbdt()
    transform = compile_serving_transform(model, ["features"])
    good = json.dumps({"features": [0.5] * 8}).encode()
    replies = transform([good, b"{not json", good,
                         json.dumps({"wrong": 1}).encode()])
    assert replies[0].status == 200 and replies[2].status == 200
    assert replies[1].status == 400
    assert replies[3].status == 400
    assert "features" in replies[3].data["error"]


def test_malformed_json_400_over_http_batchmates_unaffected():
    model = _fit_gbdt()
    server, q = serve_pipeline(model, input_cols=["features"])
    results = {}

    def send(key, payload: bytes):
        req = urllib.request.Request(server.address, data=payload,
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=15) as r:
                results[key] = ("ok", r.status, json.loads(r.read()))
        except urllib.error.HTTPError as e:
            results[key] = ("err", e.code, json.loads(e.read()))

    threads = [threading.Thread(target=send, args=(k, p)) for k, p in [
        ("a", json.dumps({"features": [1.0] * 8}).encode()),
        ("bad", b"][ definitely not json"),
        ("b", json.dumps({"features": [-1.0] * 8}).encode())]]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert results["a"][0] == "ok" and results["a"][1] == 200
        assert results["b"][0] == "ok" and results["b"][1] == 200
        assert results["bad"][0] == "err" and results["bad"][1] == 400
        assert "bad request" in results["bad"][2]["error"]
    finally:
        q.stop()
        server.stop()


# ------------------------------------------------- adaptive micro-batching
def test_continuous_mode_batches_of_one():
    server = ServingServer(num_partitions=1).start()
    sizes = []

    def transform(bodies):
        sizes.append(len(bodies))
        return [{"ok": 1}] * len(bodies)

    q = ServingQuery(server, transform, mode="continuous",
                     poll_timeout=0.005).start()
    try:
        for i in range(5):
            assert _post(server.address, {"x": i}) == {"ok": 1}
        assert sizes and all(s == 1 for s in sizes), sizes
    finally:
        q.stop()
        server.stop()


def test_linger_coalesces_concurrent_requests():
    """With a generous linger budget and max_batch == the request count,
    concurrent requests coalesce into few batches (the drain returns as
    soon as max_batch fills — the budget is a ceiling, not a sleep)."""
    server = ServingServer(num_partitions=1).start()
    sizes = []

    def transform(bodies):
        sizes.append(len(bodies))
        return [{"ok": 1}] * len(bodies)

    q = ServingQuery(server, transform, mode="microbatch", max_batch=4,
                     batch_linger_ms=2000.0, poll_timeout=0.005).start()
    results = []

    def client(i):
        results.append(_post(server.address, {"x": i}, timeout=20))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(results) == 4
        assert sum(sizes) == 4
        # coalesced: strictly fewer batches than requests (a scheduler
        # stall can split one straggler off; four singletons would mean
        # the linger never coalesced anything)
        assert len(sizes) <= 2, sizes
    finally:
        q.stop()
        server.stop()


def test_linger_zero_drains_only_whats_queued():
    """linger=0 keeps drain-available semantics: requests enqueued before
    the query starts land in ONE batch (no per-request dispatch)."""
    server = ServingServer(num_partitions=1).start()
    sizes = []

    def transform(bodies):
        sizes.append(len(bodies))
        return [{"ok": 1}] * len(bodies)

    q = ServingQuery(server, transform, mode="microbatch", max_batch=8,
                     poll_timeout=0.05)
    results = []
    threads = [threading.Thread(
        target=lambda i=i: results.append(_post(server.address, {"x": i},
                                                timeout=20)))
        for i in range(3)]
    try:
        for th in threads:
            th.start()
        time.sleep(0.3)   # all three enqueue before the workers exist
        q.start()
        for th in threads:
            th.join()
        assert len(results) == 3
        assert sizes[0] == 3, sizes
    finally:
        q.stop()
        server.stop()


def test_continuous_rejects_linger():
    server = ServingServer(num_partitions=1).start()
    try:
        q = ServingQuery(server, lambda b: b, mode="continuous",
                         batch_linger_ms=50.0)
        assert q.batch_linger_ms == 0.0   # continuous never lingers
        with pytest.raises(ValueError):
            ServingQuery(server, lambda b: b, batch_linger_ms=-1.0)
    finally:
        server.stop(drain=False)


# ------------------------------------------------- percentile observability
def test_serving_request_metrics_present_and_monotonic():
    """snapshot() must expose serving.request.* percentiles after traffic,
    with p50 <= p95 <= p99 (ordering invariant — no wall-clock bounds),
    e2e covering every answered request, and the queue-depth /
    batch-occupancy gauges recorded."""
    reliability_metrics.reset("serving.")
    model = _fit_gbdt()
    server, q = serve_pipeline(model, input_cols=["features"])
    try:
        n = 12
        for i in range(n):
            _post(server.address, {"features": [0.1 * i] * 8})
    finally:
        q.stop()
        server.stop()
    snap = reliability_metrics.snapshot()
    for stage in ("queue", "transform", "reply", "e2e"):
        count = snap.get(f"serving.request.{stage}.count", 0)
        assert count > 0, (stage, snap)
        p50 = snap[f"serving.request.{stage}.p50"]
        p95 = snap[f"serving.request.{stage}.p95"]
        p99 = snap[f"serving.request.{stage}.p99"]
        assert 0.0 <= p50 <= p95 <= p99, (stage, p50, p95, p99)
    assert snap["serving.request.e2e.count"] == n
    assert "serving.queue_depth" in snap
    assert "serving.batch.occupancy" in snap
    # stage ordering: a request's end-to-end time includes its queue wait
    # and its batch's transform time
    assert snap["serving.request.e2e.p50"] >= 0.0
    assert reliability_metrics.percentile("serving.request.e2e", 50.0) \
        == snap["serving.request.e2e.p50"]


def test_epoch_replay_preserved_on_fast_path():
    """The batching/plan overhaul must not touch the replay contract: a
    worker killed between read and commit redelivers the in-flight batch
    (same assertion as test_serving_fault_tolerance, on the fast path)."""
    model = _fit_gbdt()
    server, q = serve_pipeline(model, input_cols=["features"])
    q.inject_fault(0)
    try:
        out = _post(server.address, {"features": [1.0] * 8}, timeout=20)
        assert out == {"prediction": 1.0}
        assert q._recoveries >= 1
    finally:
        q.stop()
        server.stop()
