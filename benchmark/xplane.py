"""xplane.py - the reduction from a profiler trace to numbers.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but JAX
(`jax.profiler.ProfileData`). What one v5e trace holds (looked at by hand,
PR 25; `tests/data/` keeps two small ones):

- one plane per chip, `/device:TPU:<n>`, with the lines `XLA Modules` (one
  event per executed program), `XLA Ops` (one event per HLO instruction,
  named by the instruction's whole text, `%name = type op(...)`; a `while`
  or `conditional` event spans its body's events, so events nest) and
  `Async XLA Ops`;
- the host plane `/host:CPU`, whose lines carry the benchmark's own
  `jax.profiler.TraceAnnotation` spans (named `bench.<span>`), on the same
  clock as the device lines.

Busy time is the union of the `XLA Ops` intervals, clipped to the traced
window (`bench.window`). A kernel's time is the sum of the durations of the
events whose name matches its pattern: patterns are written against leaf
instructions (a Pallas call is a `custom-call`), so nesting does not count
them twice. The breakdown's device operations are self times: an event's
duration minus its children's.
"""
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Event = collections.namedtuple("Event", "name start_ns dur_ns")


class TraceError(RuntimeError):
    """The trace cannot give what was asked of it."""


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(hlo_text):
    """`%pallas_hist.24 = (...) custom-call(...)` -> `pallas_hist custom-call`:
    the instruction's name without its number, its opcode and, for a
    fusion, its kind."""
    m = re.match(r"^%?([^\s=]+?)(?:\.\d+)* = (.*)$", hlo_text)
    if not m:
        return hlo_text[:80]
    rest = m.group(2)
    depth, i = 0, 0
    if rest.startswith("("):        # a tuple type: skip to its end
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest)
    kind = re.search(r", kind=(k\w+)", rest)
    return (f"{m.group(1)} {op.group(1)}" + (f" {kind.group(1)}" if kind else "")
            if op else m.group(1))


class Trace:
    """Device op events per chip and the benchmark's host spans."""

    def __init__(self, devices, spans):
        self.devices = devices      # {chip index: [Event] sorted by start}
        self.spans = spans          # [Event] named bench.*
        self._busy = {}             # chip -> merged busy intervals
        if not devices or not any(devices.values()):
            raise TraceError(
                "the trace has no device plane with operations "
                "(/device:TPU:<n>, line 'XLA Ops'): nothing ran on a chip "
                "inside the traced window")

    @classmethod
    def from_file(cls, path):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        devices, spans = {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[int(m.group(1))] = sorted(
                            (Event(e.name, int(e.start_ns),
                                   int(e.duration_ns))
                             for e in line.events),
                            key=lambda e: (e.start_ns, -e.dur_ns))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [Event(e.name, int(e.start_ns),
                                    int(e.duration_ns))
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls(devices, sorted(spans, key=lambda e: e.start_ns))

    # ------------------------------------------------------------ window
    def window(self):
        """(start_ns, end_ns) of the traced window: the `bench.window` span,
        or, in a trace without one, first to last device event."""
        for s in self.spans:
            if s.name == WINDOW_SPAN:
                return s.start_ns, s.start_ns + s.dur_ns
        evs = [e for d in self.devices.values() for e in d]
        return (min(e.start_ns for e in evs),
                max(e.start_ns + e.dur_ns for e in evs))

    def window_s(self):
        t0, t1 = self.window()
        return (t1 - t0) / 1e9

    def _busy_intervals(self, chip):
        if chip in self._busy:
            return self._busy[chip]
        t0, t1 = self.window()
        out = self._busy[chip] = []
        for e in self.devices[chip]:
            a, b = max(e.start_ns, t0), min(e.start_ns + e.dur_ns, t1)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def busy_s(self, chip=None):
        """Seconds in which an operation ran: one chip's, or the mean."""
        chips = [chip] if chip is not None else sorted(self.devices)
        per = [sum(b - a for a, b in self._busy_intervals(c)) / 1e9
               for c in chips]
        return sum(per) / len(per)

    # ------------------------------------------------------------ kernels
    def sum_matching(self, pattern, chip=0):
        """(seconds, events) of the device events on `chip` whose name
        matches `pattern`, inside the window."""
        rx = re.compile(pattern)
        t0, t1 = self.window()
        total, n = 0, 0
        for e in self.devices.get(chip, ()):
            if t0 <= e.start_ns < t1 and rx.search(e.name):
                total += e.dur_ns
                n += 1
        return total / 1e9, n

    def device_ops(self, chip=0, top=10):
        """[[short name, self seconds]]: the operations that took most
        device time, children's time taken out of their parents'."""
        t0, t1 = self.window()
        self_ns = collections.Counter()
        stack = []      # [end_ns, name, self_ns]

        def close(upto):
            while stack and stack[-1][0] <= upto:
                _end, name, own = stack.pop()
                self_ns[short_name(name)] += max(own, 0)

        for e in self.devices.get(chip, ()):
            if not t0 <= e.start_ns < t1:
                continue
            close(e.start_ns)
            if stack:
                stack[-1][2] -= e.dur_ns
            stack.append([e.start_ns + e.dur_ns, e.name, e.dur_ns])
        close(float("inf"))
        return [[n, ns / 1e9] for n, ns in self_ns.most_common(top)]

    # ------------------------------------------------------------ gaps
    def idle_gaps(self, chip=0, top=10):
        """[[span name, idle seconds]]: the chip's idle time inside the
        window by what the host was doing, i.e. the innermost `bench.*`
        span that holds the middle of each gap."""
        t0, t1 = self.window()
        busy = self._busy_intervals(chip)
        edges = [t0] + [t for iv in busy for t in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [s for s in self.spans if s.name != WINDOW_SPAN]
        idle = collections.Counter()
        for a, b in gaps:
            mid = (a + b) / 2
            holding = [s for s in spans
                       if s.start_ns <= mid < s.start_ns + s.dur_ns]
            label = (min(holding, key=lambda s: s.dur_ns).name[
                len(SPAN_PREFIX):] if holding else "outside_spans")
            idle[label] += b - a
        return [[n, ns / 1e9] for n, ns in idle.most_common(top)]

    def breakdown(self):
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}
