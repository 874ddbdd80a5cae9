"""The program's own spans in a traced window: the device's idle time
inside them, and how many there are.

The program names its spans (`repro.core.telemetry`); they are host
events of the same trace as the device's operations, on the same clock.
Pure functions of a `trace.Trace`, so the tests check them on a
synthetic one.
"""
from __future__ import annotations

from bench import trace


def _intersect(a, b) -> list[tuple[float, float]]:
    """The overlap of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def named(tr: trace.Trace, name: str) -> list[tuple[float, float]]:
    """The intervals of the host spans ``name``, cut to the window."""
    lo, hi = tr.window()
    return trace.clip([e for e in tr.host if e.name == name], lo, hi)


def idle_in(tr: trace.Trace, name: str) -> float | None:
    """Seconds of the window in which the device ran nothing and a host
    span ``name`` was open, averaged over the chips that ran anything;
    None when the window holds no such span."""
    inside = trace.union(named(tr, name))
    if not inside:
        return None
    lo, hi = tr.window()
    chips = [ops for ops in tr.device_ops.values() if ops] or [[]]
    idle_ns = sum(
        trace.length(_intersect(
            trace.idle(trace.union(trace.clip(ops, lo, hi)), lo, hi),
            inside))
        for ops in chips)
    return idle_ns * 1e-9 / len(chips)


def idle_per_step(run, driver: str, name: str) -> float | None:
    """`idle_in` per step of the window; None for a run of another
    driver, without a trace, or without the span."""
    if run.driver != driver or run.trace is None:
        return None
    s = idle_in(run.trace, name)
    return None if s is None else s / run.steps
