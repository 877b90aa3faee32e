"""Device tracing/profiling hooks (SURVEY.md §5: tracing/profiling aux
subsystem; pairs with the Timer stage for wall-clock and utils.stopwatch for
code blocks).

`trace(dir)` wraps device-profile capture — the resulting trace opens in
TensorBoard/Perfetto and shows per-op device time, the ground truth for the
fusion/HBM questions this framework's perf work keeps asking. `annotate()`
marks named regions inside a trace.

Telemetry integration (docs/observability.md): `trace()` is rebased on
`telemetry.profiler.ProfileSession` — ONE capture path shared with the
triggered captures (`GET /debug/profile`, straggler flags, burn latches),
so every capture gets the same `device.profile` span, the same
`trace_context.json` trace-id stamp (stamp failures counted under
`telemetry.profile.stamp_errors` instead of silently passed), and the same
per-op parse feeding the roofline ledger. `annotate(name)` additionally
notes the region's host wall into that ledger and activates the region for
compile-record tagging, so per-region rows exist even on backends whose
profiles carry no device planes (CPU). `wall_clock(..., tracer=...)`
routes a timed block into the telemetry tracer as a span instead of
printing.
"""
from __future__ import annotations

import contextlib
import sys
import time


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Capture a device trace for the enclosed block:

        with tracing.trace("/tmp/trace"):
            model.fit(table)

    Rebased on `telemetry.profiler.ProfileSession.session` (force=True:
    the explicit API is never rate-limited, and the caller owns
    `log_dir` — no retention pruning). The `device.profile` span and the
    `trace_context.json` stamp are unchanged from the pre-session
    behavior."""
    from ..telemetry.profiler import get_profile_session
    with get_profile_session().session(
            reason="trace", log_dir=log_dir, force=True,
            create_perfetto_link=create_perfetto_link):
        yield log_dir


_prof = None     # telemetry.profiler, imported at the first annotate


class annotate:
    """Named region inside a trace (jax.profiler.TraceAnnotation on the
    host timeline, so the span lies on the device trace's clock in any
    capture, at the cost of a flag test when none runs; `attrs` ride the
    annotation) that ALSO feeds the roofline ledger: the region's host
    wall is noted on exit (`telemetry.profiler.note_region`: count, total
    and a ring of the last durations) and any compile recorded inside
    tags itself with the region — so `roofline.json` carries per-region
    rows on every backend, refined to device-plane self time where a
    parse provided it. jax is only touched when already imported
    (annotating must never pay a cold jax import on a hot path). A class
    and not a generator: three of these sit on every LM step."""

    __slots__ = ("name", "_attrs", "_cm", "_token", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self._attrs = attrs

    def __enter__(self):
        global _prof
        if _prof is None:
            from ..telemetry import profiler as _prof
        self._cm = None
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                self._cm = jax.profiler.TraceAnnotation(self.name,
                                                        **self._attrs)
                self._cm.__enter__()
            except Exception:  # noqa: BLE001 - a backend without profiler
                self._cm = None
        self._token = _prof._region_var.set(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        _prof._region_var.reset(self._token)
        try:
            if self._cm is not None:
                self._cm.__exit__(*exc)
        finally:
            _prof.note_region(self.name, seconds)
        return False


@contextlib.contextmanager
def wall_clock(label: str, sink=None, tracer=None):
    """Host-side wall-clock for a block; `sink(label, seconds)` or print.

    `tracer` routes the timing into the telemetry span log instead of the
    console: pass a `telemetry.Tracer` (or `True` for the process default)
    and the block lands as a span named `label` — the Timer stage's
    telemetry mode and ad-hoc pipeline timings share this path."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        recorded = None
        if tracer is not None:
            if tracer is True:
                from ..telemetry.spans import get_tracer
                tracer = get_tracer()
            recorded = tracer.observe(label, dt)
        if sink is not None:
            sink(label, dt)
        elif tracer is None or recorded is None:
            # an unsampled span records nothing — a timing the caller
            # asked for must not vanish, so fall back to the print
            print(f"{label}: {dt:.4f}s")
