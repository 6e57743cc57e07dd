"""The benchmark's own spans, around its calls into each layer.

A span is recorded on the host's clocks into a list: the monotonic clock for
durations (read by the per-layer metrics over the whole window) and the wall
clock for its place on the profiler's timeline.  The profiler stamps its
events in wall-clock nanoseconds since the start of its session
(``profile_start_time``), so ``wall_ns - profile_start_time`` puts a span
beside the device's operations to within microseconds (checked on the CPU
against a ``TraceAnnotation``: 3-6 us), and ``trace_reduce`` can say what the
host was doing in a device gap.

The spans are not written into the trace itself (``TraceAnnotation``),
because the host half of the profiler cannot stay on: with it on at any
level the TPU runtime logs every tile it transposes while staging a batch
(4.3 M events for one 154 MB image batch), which slowed that cell's traced
slice tenfold.  Spans inside the program are the tracing issue's; these wrap
it from outside.
"""
from __future__ import annotations

import time


class Spans:
    def __init__(self):
        self.records = []            # (name, wall_ns at start, seconds)

    def span(self, name):
        return _Span(self, name)

    def on_timeline(self, session_start_ns):
        """``(name, start, end)`` in seconds since the profiler's session
        started."""
        return [(name, (wall - session_start_ns) * 1e-9,
                 (wall - session_start_ns) * 1e-9 + seconds)
                for name, wall, seconds in self.records]


class _Span:
    __slots__ = ("_owner", "_name", "_wall", "_t0", "seconds")

    def __init__(self, owner, name):
        self._owner = owner
        self._name = name
        self.seconds = 0.0

    def __enter__(self):
        self._wall = time.time_ns()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._owner.records.append((self._name, self._wall, self.seconds))
        return False
