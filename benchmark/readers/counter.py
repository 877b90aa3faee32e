"""counter reader: a count or gauge of the program that the driver sampled
while the traced steps ran (`result["program"][<name>]`, e.g. the mean of
`moe.load.max_over_mean` over the traced steps), times a scale.

params: {"counter": "<program name>", "scale": 1}. A program that has no
such counter, or a run without a traced window, makes the metric absent
(returns None)."""


def read(params, ctx):
    if ctx.get("trace") is None:
        return None
    value = ctx["program"].get(params["counter"])
    return None if value is None else value * params.get("scale", 1.0)
