"""step_record reader: the measured window as the program's own step records
tell it (`mmlspark_tpu.telemetry.profiler.step_records("lm.step")`: one
record a `PipelinedLMTrainer.step` call, the four host phases gap / h2d /
dispatch / wait with what the host did meanwhile) and the program's own
slow-step rule over them (`slow_steps`), times a scale.

The window's records are the last n the program kept, n the count of the
benchmark's own `lm_step` spans (window steps + traced steps), less the last
`facts["traced_steps"]` (the profiler's start lands in the first traced
step's gap). The window's first record is judged without its gap, which
holds the end of set-up. The program keeps 256 records: a window of more
steps is read by its last ones.

params: {"stat": "median" | "slow" | "lost" | "beside",
         "of": a phase ("gap", "h2d", "dispatch", "wait") for "median" and
               (optional: the whole loss without) for "lost"; a record field
               ("gc_s", "preemptions", "faults") for "beside",
         "scale": 1000}.
"median": the phase's median over the window. "slow": the slow steps.
"lost": their summed loss, or the part of it in one phase. "beside": a
field summed over the slow steps. Every one reads 0 where nothing stalled.
A program from before the records, or one that kept none, makes the metric
absent (returns None)."""
import statistics


def window_records(ctx):
    """The measured window's step records, oldest first, or None."""
    try:
        from mmlspark_tpu.telemetry.profiler import step_records
    except ImportError:
        return None
    n = len(ctx["spans"].get("lm_step", ()))
    records = step_records("lm.step")[-n:] if n else []
    traced = ctx["facts"].get("traced_steps", 0)
    records = records[:len(records) - traced]
    if not records:
        return None
    return [records[0]._replace(gap=None)] + records[1:]


def read(params, ctx):
    records = window_records(ctx)
    if records is None:
        return None
    from mmlspark_tpu.telemetry.profiler import slow_steps
    stat, of = params["stat"], params.get("of")
    if stat == "median":
        seen = [getattr(r, of) for r in records
                if getattr(r, of) is not None]
        value = statistics.median(seen) if seen else 0.0
    else:
        slow = slow_steps(records)
        if stat == "slow":
            value = len(slow)
        elif stat == "lost":
            value = sum(s["loss"] if of is None else s["lost"][of]
                        for s in slow)
        elif stat == "beside":
            value = sum(getattr(s["record"], of) for s in slow)
        else:
            raise ValueError(f"step_record: no stat {stat!r}")
    return value * params.get("scale", 1.0)
