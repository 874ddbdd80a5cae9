"""Reduction of a `jax.profiler` trace to the numbers the per-layer
metrics read.

The trace is the ``.xplane.pb`` that `jax.profiler.trace` writes. Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction text (a Mosaic
kernel's carries ``custom_call_target="tpu_custom_call"``); a loop's
event encloses those of its body. Each is attributed to the program
(``XLA Modules`` event) it ran in. Host spans are the events of the
``/host:CPU`` plane, among them the benchmark's own `TraceAnnotation`s
and, with the Python tracer on, one event per Python call. All
timestamps share one clock.

Everything here is a pure function of the events, so the tests check it
on a synthetic trace.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""          # the HLO module of a device operation
    thread: str = ""          # the host line (thread) of a host span

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The HLO instruction's name, without its text."""
        return self.name.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    """Device operations per chip and host spans of one traced window."""
    device_ops: dict[int, list[Event]]
    host: list[Event]

    def window(self) -> tuple[float, float]:
        """The benchmark's window span: (start, end) in ns."""
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        w = max(spans, key=lambda e: e.dur_ns)
        return w.start_ns, w.end_ns


def load(log_dir: str) -> Trace:
    """The trace `jax.profiler.trace(log_dir)` wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    device_ops: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get(MODULES_LINE, []))
            starts = [mod[0] for mod in modules]
            ops = device_ops.setdefault(int(m.group(1)), [])
            for ev in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                module = (modules[i][2] if i >= 0
                          and ev.start_ns < modules[i][1] else "")
                ops.append(Event(ev.name, ev.start_ns, ev.duration_ns,
                                 module=module))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(ev.name, ev.start_ns, ev.duration_ns,
                                      thread=line.name))
    return Trace(device_ops, host)


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The events' intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def matches(event: Event, patterns) -> bool:
    """True when any regex of ``patterns`` matches the event's name or
    its module."""
    return any(re.search(p, event.name) or re.search(p, event.module)
               for p in patterns)


@dataclasses.dataclass(frozen=True)
class Summary:
    """Busy time and breakdown of the window, in seconds."""
    window_s: float
    busy_s: float                 # union of device operations, mean over chips
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]


def kernel_s(trace: Trace, patterns) -> float:
    """Seconds of the device operations matching ``patterns`` inside the
    window span (their union), averaged over the chips that ran anything."""
    lo, hi = trace.window()
    chips = [ops for ops in trace.device_ops.values() if ops] or [[]]
    return sum(length(union(clip([e for e in ops if matches(e, patterns)],
                                 lo, hi))) for ops in chips) * 1e-9 / len(chips)


def summarize(trace: Trace, top: int = 10) -> Summary:
    """Busy time and the breakdown of device operations (by their own
    time) and idle gaps, all inside the window span and averaged over the
    chips that ran anything."""
    lo, hi = trace.window()
    chips = [ops for ops in trace.device_ops.values() if ops] or [[]]
    busy = 0.0
    by_op: collections.Counter = collections.Counter()
    gaps: collections.Counter = collections.Counter()
    for ops in chips:
        busy_iv = union(clip(ops, lo, hi))
        busy += length(busy_iv)
        for e, d in self_times(ops, lo, hi):
            by_op[f"{e.short} ({e.module})" if e.module else e.short] += d
        chip_gaps = idle(busy_iv, lo, hi)
        labels = host_labels(trace.host, chip_gaps)
        for (s, t), label in zip(chip_gaps, labels):
            gaps[label] += t - s
    n = len(chips)
    ns = 1e-9 / n
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * ns,
        device_ops=[(k, v * ns) for k, v in by_op.most_common(top)],
        idle_gaps=[(k, v * ns) for k, v in gaps.most_common(top)])


def self_times(ops, lo: float, hi: float):
    """(event, seconds in [lo, hi] not covered by events nested in it):
    a loop's own time, without the operations of its body."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack = [], []          # stack: [event, own time]
    for e in ops:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        own = length(clip([e], lo, hi))
        if stack:
            stack[-1][1] -= own
        stack.append([e, own])
    out.extend(tuple(x) for x in stack)
    return [(e, d) for e, d in out if d > 0]


def idle(busy_iv, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps in [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for s, e in busy_iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_labels(host, gaps) -> list[str]:
    """What the host was doing in each of the gaps, sorted by start: the
    innermost host span (the shortest) that overlaps at least half of the
    gap, other than the window span itself. One sweep over the spans."""
    spans = sorted((e for e in host if e.name != WINDOW_SPAN),
                   key=lambda e: e.start_ns)
    out, active, i = [], [], 0
    for s, t in gaps:
        while i < len(spans) and spans[i].start_ns < t:
            active.append(spans[i])
            i += 1
        active = [e for e in active if e.end_ns > s]
        half = [e for e in active
                if 2 * (min(e.end_ns, t) - max(e.start_ns, s)) >= t - s]
        out.append(min(half, key=lambda e: e.dur_ns).name if half
                   else "(no host span)")
    return out


def idle_share(run, driver: str):
    """Share of the traced window, in %, in which no operation ran on the
    device: 1 - (union of device-busy intervals) / window. None for a run
    of another driver or without a trace."""
    if run.driver != driver or run.summary is None:
        return None
    return 100.0 * (1.0 - run.summary.busy_s / run.summary.window_s)


def dense_s(run, driver: str, kernels):
    """Device seconds per step outside the ``kernels`` (regexes of their
    events): busy time per step less the kernels' time per step. None for
    a run of another driver or without a trace."""
    if run.driver != driver or run.summary is None:
        return None
    return run.summary.busy_s / run.steps - (run.kernel_s(kernels) or 0.0)
