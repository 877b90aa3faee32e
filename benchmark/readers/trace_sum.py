"""trace_sum reader: summed device durations of the events whose name matches
a pattern, on one chip, over a divisor the driver counted (iterations or
steps inside the traced window), times a scale.

params: {"pattern": regex, "per": "<fact name>", "scale": 1000, "chip": 0}.
A pattern that matches no event is an error: it never reads as zero."""


def read(params, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    seconds, n = trace.sum_matching(params["pattern"], params.get("chip", 0))
    if n == 0:
        raise LookupError(
            f"no device event matches {params['pattern']!r} in the traced "
            f"window")
    return seconds * params.get("scale", 1.0) / ctx["facts"][params["per"]]
