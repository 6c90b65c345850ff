"""Host spans from inside the program, for whoever installs a sink.

The control plane (``service.FleetService``) and the fleet primitives it
calls (``sim.HartState.boot*``, ``sim.Fleet.replace_hart``) wrap their
phases in :func:`span`.  With no sink installed a span is one shared no-op
context and costs one global read; with one installed, ``span(name)``
returns ``sink(name)``.

A sink is any callable ``sink(name)`` returning a context manager.
:class:`Recorder` is the one the program ships: it writes each span into
the profiler's trace (``jax.profiler.TraceAnnotation``), so a device trace
names its idle gaps after the phase the host was in, and keeps
``(name, start, end)`` on the host clock (``time.perf_counter``)::

    rec = telemetry.Recorder()
    telemetry.install(rec)
    try:
        svc.step()
    finally:
        telemetry.install(None)
    rec.dump("spans.json")
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, ContextManager, List, Optional, Tuple

import jax

__all__ = ["span", "install", "Recorder"]

_NOOP = contextlib.nullcontext()
_sink: Optional[Callable[[str], ContextManager]] = None


def install(sink: Optional[Callable[[str], ContextManager]]) -> None:
    """Route every later :func:`span` to ``sink``; ``None`` turns spans
    off again."""
    global _sink
    _sink = sink


def span(name: str) -> ContextManager:
    """The installed sink's span ``name``, or the shared no-op."""
    sink = _sink
    return _NOOP if sink is None else sink(name)


class Recorder:
    """Spans on the host clock, each also written to the profiler's trace
    so that a trace puts it on the device's clock."""

    def __init__(self):
        self.items: List[Tuple[str, float, float]] = []  # perf_counter s

    @contextlib.contextmanager
    def __call__(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float, hi: float) -> float:
        """Seconds of ``name`` spans that start inside ``[lo, hi)``."""
        return sum(b - a for n, a, b in self.items
                   if n == name and lo <= a < hi)

    def dump(self, path: str) -> None:
        """Write the spans as JSON: ``[[name, start_s, end_s], ...]``."""
        with open(path, "w") as fh:
            json.dump([list(it) for it in self.items], fh)
