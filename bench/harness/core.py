"""What every cell shares: the device check, host spans, compile counting,
the engine wrapper that times each engine run, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time

import jax
import numpy as np

from repro.core.hext.bits import x64

# The benchmark measures the chip and nothing else.  Tests on the CPU steer
# this from inside the test.
PLATFORM = "tpu"

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
MASK64 = (1 << 64) - 1


class NoDevice(SystemExit):
    """JAX found no accelerator of the benchmark's platform, or too few."""


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises before any set-up unless JAX
    holds at least ``chips`` devices of ``PLATFORM``."""
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise NoDevice(f"bench needs a {PLATFORM} device; JAX found "
                       f"platform {devs[0].platform!r} ({len(devs)} "
                       f"device(s))")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} {PLATFORM} devices; JAX "
                       f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def covered(spans) -> float:
    """Length covered by the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class CompileMeter:
    """JAX's own compile events (tracing, lowering, backend compile or
    persistent-cache load), merged where nested, plus persistent-cache hits
    and misses and the number of backend compiles.  Copied from
    ``chip_smoke.CompileMeter``; the backend-compile count is what tells a
    compile inside the measured window."""

    def __init__(self):
        self.spans = []
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def seconds(self) -> float:
        return covered(self.spans)


class Spans:
    """Host spans on the host clock, each also written to the profiler's
    trace (``TraceAnnotation``) so a trace puts it on the device's clock."""

    def __init__(self):
        self.items = []            # (name, start, end), perf_counter s

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


class TimedEngine:
    """Passed to the program as ``engine=``: times each engine run (the
    program's engines end in ``block_until_ready``) and counts the loop
    ticks it executed, from the harts' tick counters before and after.

    The loop runs whole chunks until every hart is done, so its ticks are
    the longest-running hart's advance, rounded up to the chunk."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self.name = getattr(inner, "name", "custom")
        self.spans = spans
        self.runs = []             # (start, end, loop ticks)

    def run(self, state, max_ticks: int, chunk: int = 4096):
        with x64():
            before = np.asarray(state.counters.ticks)
        t0 = time.perf_counter()
        with self.spans("engine.run"):
            out = self.inner.run(state, max_ticks, chunk=chunk)
        t1 = time.perf_counter()
        with x64():
            after = np.asarray(out.counters.ticks)
        advance = int(np.max(after - before)) if after.size else 0
        self.runs.append((t0, t1, -(-advance // int(chunk)) * int(chunk)))
        return out

    def in_window(self, lo: float, hi: float):
        return [r for r in self.runs if lo <= r[0] < hi]


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, dtype=float), q))


def emit(result: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
