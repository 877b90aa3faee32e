"""program_span reader: one of the program's own host spans at a layer
boundary (`utils.tracing.annotate`, e.g. `lm.step.dispatch`), read from the
ring of its last durations that the program keeps
(`mmlspark_tpu.telemetry.profiler.region_stats`), times a scale.

params: {"region": "<span name>", "stat": "median" | "p95", "scale": 1000}.
A program from before the ring, or a span that never ran, makes the metric
absent (returns None)."""


def read(params, ctx):
    try:
        from mmlspark_tpu.telemetry.profiler import region_stats
    except ImportError:
        return None
    stats = region_stats(params["region"])
    if stats is None:
        return None
    return stats[params.get("stat", "median")] * params.get("scale", 1.0)
