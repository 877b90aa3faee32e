"""timer reader: a timer of the program (`reliability_metrics`, seconds per
call over the measured window) or one of the benchmark's own spans (median
seconds), by name.

params: {"timer": "<program timer>"} or {"span": "<benchmark span>"}."""
import statistics


def read(params, ctx):
    if "timer" in params:
        seconds = ctx["program"].get(params["timer"] + ".seconds")
        count = ctx["program"].get(params["timer"] + ".count")
        return seconds / count if seconds is not None and count else None
    values = ctx["spans"].get(params["span"])
    return statistics.median(values) if values else None
