"""Reduction of a profiler trace to what the per-layer metrics read.

`Trace.from_file` reads an `.xplane.pb` with `jax.profiler.ProfileData`
and keeps, per device plane (`/device:TPU:<n>`), the events of its program
line ("XLA Modules": one event per execution of a compiled program) and of
its operation line ("XLA Ops": one event per HLO operation, nested: a
while loop's event holds its body's events), each under the operation's
short name (`fusion.12`, not the whole HLO text); and every event of the
host plane (`/host:CPU`), where `jax.profiler.TraceAnnotation` spans land
on the same clock.

* busy: the union of a device's program executions inside the window,
  averaged over the device planes.  A program runs on the device from its
  start to its end without waiting for the host, and the TPU may drop
  operation events when its trace buffers fill, never program events;
* idle gaps: the complement of that union inside the window, each named by
  the innermost host event that covers the gap's middle;
* self time per device operation (its duration less that of the
  operations nested in it), and the events whose name starts with a
  pattern (one kernel's calls).

The window is the span from the first `bench.call` host span's start to the
last one's end.  On a TPU v5e the device's timestamps read up to about
1 ms earlier than the host's (bench/traces/small.xplane.pb), which is
nothing against a window of seconds; an operation counts where it overlaps
the window.
"""

from __future__ import annotations

from dataclasses import dataclass, field

OPS, MODULES = "XLA Ops", "XLA Modules"


def short(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Sorted disjoint (start, end) cover of `intervals`."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class Trace:
    devices: dict          # plane -> {line: [(name, start_ns, end_ns)]}
    host: list             # [(name, start_ns, end_ns, line)]

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        devices, host = {}, []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {}
                for line in plane.lines:
                    if line.name in (OPS, MODULES):
                        lines[line.name] = [(short(e.name), e.start_ns,
                                             e.end_ns) for e in line.events]
                devices[plane.name] = lines
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.end_ns, line.name)
                                for e in line.events)
        return cls(devices, host)

    def window(self, span: str = "bench.call"):
        marks = [(s, e) for name, s, e, _ in self.host if name == span]
        if not marks:
            return None
        return min(s for s, _ in marks), max(e for _, e in marks)

    def _events(self, plane: str):
        lines = self.devices[plane]
        return lines.get(MODULES) or lines.get(OPS) or []

    def busy_ns(self, lo, hi) -> float:
        """Union of device event time inside [lo, hi], mean over devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for plane in self.devices:
            iv = union(clip([(s, e) for _, s, e in self._events(plane)], lo, hi))
            total += sum(e - s for s, e in iv)
        return total / len(self.devices)

    def gaps(self, lo, hi):
        """Idle intervals of the first device inside [lo, hi]."""
        plane = sorted(self.devices)[0]
        iv = union(clip([(s, e) for _, s, e in self._events(plane)], lo, hi))
        out, t = [], lo
        for s, e in iv:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def name_gap(self, s, e) -> str:
        """The innermost host event covering the gap's middle."""
        mid = 0.5 * (s + e)
        best = None
        for name, hs, he, _ in self.host:
            if hs <= mid <= he and (best is None or he - hs < best[1]):
                best = (name, he - hs)
        return best[0] if best else "no host span"

    def op_seconds(self, lo, hi) -> dict:
        """Device self seconds per operation name, of the operations that
        overlap [lo, hi], summed over the device planes and divided by
        their number."""
        out = {}
        for plane in self.devices:
            events = sorted((ev for ev in self.devices[plane].get(OPS, [])
                             if ev[2] > lo and ev[1] < hi),
                            key=lambda ev: (ev[1], -ev[2]))
            stack = []                  # [end, name, self time] of parents
            for name, s, e in events:
                while stack and stack[-1][0] <= s:
                    _, pname, own = stack.pop()
                    out[pname] = out.get(pname, 0.0) + own
                if stack:
                    stack[-1][2] -= min(e, stack[-1][0]) - s
                stack.append([e, name, e - s])
            for _, pname, own in stack:
                out[pname] = out.get(pname, 0.0) + own
        n = max(len(self.devices), 1)
        return {k: v / n / 1e9 for k, v in out.items()}

    def matching(self, pattern: str, lo, hi):
        """Durations (s) of device operation events whose name starts with
        `pattern` and that overlap [lo, hi]."""
        out = []
        for plane in self.devices:
            for name, s, e in self.devices[plane].get(OPS, []):
                if name.startswith(pattern) and e > lo and s < hi:
                    out.append((e - s) / 1e9)
        return out


@dataclass
class RunContext:
    """What a metric reader sees of one traced run."""

    busy_s: float
    window_s: float
    slots: int
    window_compile_s: float
    facts: dict
    peak: dict | None
    kernel_events: list
    breakdown: dict = field(default_factory=dict)


def reduce_run(trace: Trace, stats: dict, entry, peak, top: int = 10):
    facts = entry.facts()
    lo, hi = trace.window() or (0, 0)
    busy = trace.busy_ns(lo, hi) / 1e9
    ops = sorted(trace.op_seconds(lo, hi).items(), key=lambda kv: -kv[1])
    gaps = sorted(trace.gaps(lo, hi), key=lambda g: g[0] - g[1])[:top]
    kernel = trace.matching(facts["kernel"], lo, hi) if "kernel" in facts \
        else []
    return RunContext(
        busy_s=busy, window_s=(hi - lo) / 1e9, slots=stats["slots"],
        window_compile_s=stats["compile_s"], facts=facts, peak=peak,
        kernel_events=kernel,
        breakdown={"device_ops": [[n, s] for n, s in ops[:top]],
                   "idle_gaps": [[trace.name_gap(s, e), (e - s) / 1e9]
                                 for s, e in gaps]})
