"""Program spans and trace counters.

A span is a `jax.profiler.TraceAnnotation`: it records only while a
profiler session is active (`jax.profiler.trace`), and then lands on the
``/host:CPU`` plane of the same trace as the device's operations, on the
same clock. Its keyword arguments become the event's stats. Off, a span
costs about a microsecond of host time and records nothing.

A trace counter counts how often JAX traced a program body: the body
calls `traced`, which runs only while JAX traces it (a jitted call that
hits its cache never enters the Python body). `counts` returns every
counter; callers compare two readings. `docs/tracing.md` names each span
and counter and the question it answers.
"""
from __future__ import annotations

import collections
import contextlib
import threading

import jax

_COUNTS: collections.Counter = collections.Counter()
# Serving drivers trace from worker threads.
_LOCK = threading.Lock()


def span(name: str, **args):
    """A host span ``name`` over a ``with`` block, ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def traced(name: str, **args):
    """Inside a body that JAX traces: add 1 to the trace counter ``name``
    and open span ``name`` for the length of the trace. A ``with`` block,
    or a decorator of the traced function."""
    with _LOCK:
        _COUNTS[name] += 1
    with span(name, **args):
        yield


def counts() -> dict[str, int]:
    """A copy of every trace counter; they only grow."""
    with _LOCK:
        return dict(_COUNTS)
