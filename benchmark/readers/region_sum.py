"""region_sum reader: `scope_sum` for a region that a program may not have
yet. `scope_sum` makes a named region that matches no event an error, which
is right for a program that declares the region and wrong for one from
before it: there the metric is absent (returns None), as a reader's contract
for a program that lacks a span or a counter asks. Where the program
declares every region named (`mmlspark_tpu.telemetry.names.DEVICE_REGIONS`)
this IS `scope_sum`, error included: a declared region never reads as zero.

params: those of `scope_sum` (readers/scope_sum.py), "region" a name or a
list of names."""
from harness import load_module


def read(params, ctx):
    try:
        from mmlspark_tpu.telemetry.names import DEVICE_REGIONS
    except ImportError:
        return None
    want = params["region"]
    if any(r not in DEVICE_REGIONS
           for r in ([want] if isinstance(want, str) else want)):
        return None
    return load_module("readers", "scope_sum").read(params, ctx)
