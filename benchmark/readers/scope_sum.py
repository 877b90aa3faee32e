"""scope_sum reader: device self time, on one chip, of the events that the
program's own scope maps put in a region, over a divisor the driver counted,
times a scale.

A device event is named by its HLO instruction's text (`%fusion.12 = ...`)
and carries nothing of the `jax.named_scope` it was traced under; the
program keeps, for each of its registered programs, a map from instruction
name to (region, direction) read from the compiled module's `op_name`
metadata (`mmlspark_tpu.telemetry.perf.scope_maps`). This reader joins the
two by instruction name. Self time is an event's duration less its
children's (a `while` spans its body), so the regions, the kernels and what
no region claims add up to the chip's busy time.

params: {"region": "<name>" | ["<name>", ...] | "any" | null,
         "direction": "fwd" | "bwd" | "remat" (optional),
         "pattern": regex the event's text must match (optional),
         "exclude": regex the event's text must not match (optional),
         "per": "<fact name>" (optional), "scale": 1000, "chip": 0}.
`"region": null` sums what no region claims, instructions that two
registered programs place differently included. A program from before the
scope map makes the metric absent (returns None). With the map there, a
named region that matches no event is an error: it never reads as zero."""
import collections
import re

_INSTRUCTION = re.compile(r"^%?([^\s=]+) = ")


def self_times(trace, chip=0):
    """{event text: self ns} of the chip's events inside the window."""
    t0, t1 = trace.window()
    own = collections.Counter()
    stack = []      # [end_ns, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, ns = stack.pop()
            own[name] += max(ns, 0)

    for e in trace.devices.get(chip, ()):
        if not t0 <= e.start_ns < t1:
            continue
        close(e.start_ns)
        if stack:
            stack[-1][2] -= e.dur_ns
        stack.append([e.start_ns + e.dur_ns, e.name, e.dur_ns])
    close(float("inf"))
    return own


def instruction_name(event_text):
    m = _INSTRUCTION.match(event_text)
    return m.group(1) if m else event_text


def sum_scoped(own, placed, params):
    """(ns, events) of the self times `own` that `params` selects, by the
    merged scope map `placed`."""
    want = params.get("region")
    regions = None if want in (None, "any") else (
        {want} if isinstance(want, str) else set(want))
    direction = params.get("direction")
    include = re.compile(params["pattern"]) if "pattern" in params else None
    exclude = re.compile(params["exclude"]) if "exclude" in params else None
    total = n = 0
    for text, ns in own.items():
        region, way = placed.get(instruction_name(text), (None, None))
        if want is None:
            if region is not None:
                continue
        elif region is None or (regions is not None
                                and region not in regions):
            continue
        if direction is not None and way != direction:
            continue
        if include is not None and not include.search(text):
            continue
        if exclude is not None and exclude.search(text):
            continue
        total += ns
        n += 1
    return total, n


def read(params, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    try:
        from mmlspark_tpu.telemetry.perf import merged_scope_map
    except ImportError:
        return None
    placed, conflicts = merged_scope_map()
    total, n = sum_scoped(self_times(trace, params.get("chip", 0)), placed,
                          params)
    if n == 0 and params.get("region") is not None:
        raise LookupError(
            f"no device event of the traced window lies in region "
            f"{params['region']!r} (direction {params.get('direction')!r}, "
            f"pattern {params.get('pattern')!r}); the program's scope maps "
            f"place {len(placed)} instructions and leave out "
            f"{len(conflicts)} that two programs place differently")
    per = ctx["facts"][params["per"]] if "per" in params else 1
    return total / 1e9 * params.get("scale", 1.0) / per
