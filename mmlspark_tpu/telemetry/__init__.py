"""Telemetry subsystem: request-scoped span tracing, cross-process metrics
exposition, windowed aggregation, SLO burn rates, tail-based trace
capture, and profiling hooks (docs/observability.md).

Pillars:

- **Spans** (`telemetry.spans`): `Tracer`/`Span` with contextvar parent
  linkage, deterministic head sampling, a bounded ring buffer, JSONL
  export, and `X-Trace-Id` propagation — one id follows a request from
  serving ingress through the partition queue and compiled-plan transform
  to the reply, and from `RegistryClient` posts into the registry.
- **Exposition** (`telemetry.exposition`): Prometheus text + JSON
  rendering of `reliability.metrics.MetricsRegistry`, mounted as
  `/metrics` / `/metrics.json` on `ServingServer` and `ServiceRegistry`,
  plus `scrape_cluster()` which pulls and exactly merges every registered
  worker's snapshot (bucket-level histogram merge, not percentile
  averaging).
- **Windows** (`telemetry.window`): a ring of per-interval shards under
  every counter/histogram — `/metrics.json?window=60` and
  `MetricsRegistry.window_snapshot()` answer with percentiles over the
  LAST N seconds (bounded memory, shard-merged, never averaged).
- **SLOs** (`telemetry.slo`): declared objectives (latency quantile
  bounds, error-rate budgets) evaluated as multi-window burn rates over
  the windowed shards; `GET /slo` per worker, merged fleet-wide by
  `scrape_cluster(slo=True)`.
- **Tail capture** (`telemetry.spans`): a second sampling stage that
  retroactively keeps the full span tree of any trace whose root
  finished slow, errored, or 5xx — coexists with the deterministic 1%
  head sample.
- **Retention** (`telemetry.poller`): `TelemetryPoller` polls the fleet
  on an interval and keeps a bounded JSONL-exportable series — the
  autotuner/control-plane data substrate.
- **Performance** (`telemetry.perf`): compile/cost telemetry with a
  recompile detector, device/host memory gauges sampled on every
  scrape, per-bucket trace exemplars on histograms, and the
  burn-triggered flight recorder (`GET /debug/bundle`).
- **Device profiles** (`telemetry.profiler`): triggered on-device
  capture (`GET /debug/profile`, straggler flags, burn latches) parsed
  into per-op records and joined with compile-log cost into the
  per-region roofline ledger (`op.<region>.*` gauges, roofline.json).
- **Watch** (`telemetry.watch`): threshold + median-shift change-point
  detection over poller series — live regressions trip events and
  flight bundles instead of waiting for the next offline benchdiff.
- **Quality** (`telemetry.quality`): mergeable streaming distribution
  sketches on the serving stream, PSI/JS drift against the fit-time
  reference profile (`quality.drift.*` gauges, `GET /quality`,
  `scrape_cluster(quality=True)`), and a delayed-label join feeding
  streaming evaluation through the batch `ComputeModelStatistics`
  metric kernels — the semantic tier over the systems telemetry.
- **Lineage** (`telemetry.lineage`): content-addressed model versions
  (structural + fitted-array digests) with fit-time provenance, the
  bounded per-version metric splits behind `GET /versions`, the
  candidate-vs-incumbent canary gauges (`canary.*`), rollout-skew from
  `scrape_cluster(versions=True)`, and the append-only `RunLedger` —
  deployment observability over the serving hot-swap
  (`ServingTransform.install_model`).
- **Hooks**: serving request path, `data.DevicePrefetcher`,
  `TrainingSupervisor` step/checkpoint lifecycle, `fit_booster`
  iterations, `utils.tracing.trace` device profiles (stamped with the
  active trace id), and structured events for supervisor
  restarts/preemptions and `FaultInjector` firings — chaos runs read as
  one causally-ordered event log.

Sampling defaults OFF (env `MMLSPARK_TPU_TRACE_SAMPLE`, or
`telemetry.configure(sample=...)`): at 0% the hot-path cost is a single
compare per site.
"""
from .spans import (CAPACITY_ENV, REQUEST_ID_HEADER, SAMPLE_ENV, Span,
                    SpanContext, TAIL_ENV, TRACE_HEADER, Tracer, configure,
                    get_tracer, head_sampled, new_id, parse_trace_header,
                    read_jsonl, wall_now)

# exposition/window/slo/poller re-exports are LAZY: spans.py is the
# stdlib-only layer every subsystem imports
# (`from ..telemetry.spans import get_tracer`), and that import executes
# this __init__ — an eager import here would pull reliability.metrics into
# every low layer and re-open the circular-import door spans.py exists to
# close.
_LAZY_NAMES = {
    "ClusterSnapshot": "exposition", "PROM_CONTENT_TYPE": "exposition",
    "merge_states": "exposition", "metrics_http_response": "exposition",
    "render_prometheus": "exposition", "scrape_cluster": "exposition",
    "state_snapshot": "exposition",
    "ExpositionServer": "exposition", "expose_trainer": "exposition",
    "WindowedCounter": "window", "WindowedHistogram": "window",
    "Objective": "slo", "SLOEngine": "slo", "default_objectives": "slo",
    "merge_verdicts": "slo", "trainer_objectives": "slo",
    "quality_objectives": "slo", "canary_objectives": "slo",
    "TelemetryPoller": "poller",
    "ModelVersion": "lineage", "RunLedger": "lineage",
    "model_version": "lineage", "configure_run_ledger": "lineage",
    "get_run_ledger": "lineage",
    "get_version_registry": "lineage", "reset_version_registry": "lineage",
    "export_versions": "lineage", "merge_version_exports": "lineage",
    "refresh_canary_gauges": "lineage", "rollout_skew": "lineage",
    "canary_watch_rules": "lineage",
    "QualityMonitor": "quality", "DatasetProfile": "quality",
    "FeatureSketch": "quality", "StreamingEvaluator": "quality",
    "get_monitor": "quality", "reset_monitor": "quality",
    "configure_quality": "quality", "export_quality": "quality",
    "refresh_quality_gauges": "quality",
    "merge_quality_exports": "quality", "drift_scores": "quality",
    "psi": "quality", "js_divergence": "quality",
    "quality_watch_rules": "quality", "record_label": "quality",
    "StepClock": "goodput", "StragglerDetector": "goodput",
    "ProfileSession": "profiler", "RooflineLedger": "profiler",
    "get_profile_session": "profiler",
    "configure_profile_session": "profiler",
    "capture_profile": "profiler", "parse_trace": "profiler",
    "get_roofline": "profiler", "resolve_peaks": "profiler",
    "region_stats": "profiler", "step_records": "profiler",
    "slow_steps": "profiler", "by_instruction": "profiler",
    "WatchRule": "watch", "TelemetryWatcher": "watch",
    "CompileLog": "perf", "FlightRecorder": "perf", "AotCache": "perf",
    "collective_traffic": "perf", "scope_map": "perf",
    "register_program": "perf", "scope_maps": "perf",
    "compile_with_analysis": "perf", "executable_analysis": "perf",
    "record_plan_compile": "perf", "get_compile_log": "perf",
    "compile_stats": "perf", "hbm_utilization": "perf",
    "sample_resource_gauges": "perf", "sample_resource_stats": "perf",
    "get_flight_recorder": "perf", "configure_flight_recorder": "perf",
}


def __getattr__(name):
    mod = _LAZY_NAMES.get(name)
    if mod is not None:
        import importlib
        return getattr(importlib.import_module(f".{mod}", __name__), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = ["Tracer", "Span", "SpanContext", "get_tracer", "configure",
           "head_sampled", "new_id", "parse_trace_header", "read_jsonl",
           "wall_now",
           "TRACE_HEADER", "REQUEST_ID_HEADER", "SAMPLE_ENV", "CAPACITY_ENV",
           "TAIL_ENV",
           "render_prometheus", "metrics_http_response", "merge_states",
           "state_snapshot", "scrape_cluster", "ClusterSnapshot",
           "PROM_CONTENT_TYPE", "ExpositionServer", "expose_trainer",
           "WindowedHistogram", "WindowedCounter",
           "Objective", "SLOEngine", "default_objectives", "merge_verdicts",
           "trainer_objectives", "quality_objectives", "canary_objectives",
           "TelemetryPoller",
           "ModelVersion", "RunLedger", "model_version",
           "configure_run_ledger", "get_run_ledger",
           "get_version_registry", "reset_version_registry",
           "export_versions", "merge_version_exports",
           "refresh_canary_gauges", "rollout_skew", "canary_watch_rules",
           "QualityMonitor", "DatasetProfile", "FeatureSketch",
           "StreamingEvaluator", "get_monitor", "reset_monitor",
           "configure_quality", "export_quality", "refresh_quality_gauges",
           "merge_quality_exports", "drift_scores", "psi", "js_divergence",
           "quality_watch_rules", "record_label",
           "StepClock", "StragglerDetector",
           "CompileLog", "FlightRecorder", "AotCache", "collective_traffic",
           "scope_map", "register_program", "scope_maps",
           "compile_with_analysis",
           "executable_analysis", "record_plan_compile", "get_compile_log",
           "compile_stats", "hbm_utilization", "sample_resource_gauges",
           "sample_resource_stats", "get_flight_recorder",
           "configure_flight_recorder",
           "ProfileSession", "RooflineLedger", "get_profile_session",
           "configure_profile_session", "capture_profile", "parse_trace",
           "get_roofline", "resolve_peaks", "region_stats", "step_records",
           "slow_steps", "by_instruction",
           "WatchRule", "TelemetryWatcher"]
