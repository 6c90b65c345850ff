"""From a profiler trace to the device's busy time, its top operations and
its idle gaps, each gap named after the host span that was open then.

A run with ``--trace 1`` records the profiler over a short part of its
window, inside a host annotation named ``WINDOW``.  :func:`load` reads the
``.xplane.pb`` file into plain lists: each TPU's operations (the ``XLA
Ops`` line of a ``/device:TPU:<n>`` plane, named by HLO instruction kind)
and the window.  The benchmark's own host spans come from its host clock,
moved onto the trace's clock by the window's start.  :func:`reduce` then
works on those lists alone, so a test can check it on a small recorded
trace.

A TPU records every operation it runs, some 350 per simulated tick, and a
trace costs host memory and time for each: a traced window is therefore a
batch boundary or a few rounds, never a whole batch.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import threading
import time

import jax
import numpy as np

WINDOW = "traced_window"
NO_SPAN = "no_span"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")


def op_kind(name: str) -> str:
    """``%fusion.12 = u32[...] fusion(...)`` -> ``%fusion``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0])


def load(path: str) -> dict:
    """``{"devices": {plane: {"names", "start_ns", "end_ns"}}, "window":
    [start_ns, end_ns]}`` from one ``.xplane.pb`` file."""
    pd = jax.profiler.ProfileData.from_file(path)
    devices, window = {}, None
    kinds = {}                 # an op's full name -> its kind, computed once
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                names, start, end = [], [], []
                for ev in line.events:
                    kind = kinds.get(ev.name)
                    if kind is None:
                        kind = kinds[ev.name] = op_kind(ev.name)
                    names.append(kind)
                    start.append(ev.start_ns)
                    end.append(ev.end_ns)
                devices[plane.name] = {"names": names, "start_ns": start,
                                       "end_ns": end}
        elif window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = [ev.start_ns, ev.end_ns]
    return {"devices": devices, "window": window}


def _innermost(host):
    """The name of the innermost host span (the latest to start among those
    open) at each of a list of times."""
    spans = sorted((s, e, n) for n, s, e in host)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [(s, n) for s, e, n in spans if s <= mid < e]
        labels.append(max(open_)[1] if open_ else NO_SPAN)
    cuts = np.asarray(cuts, dtype=float)

    def name_of(t):
        i = np.searchsorted(cuts, t, side="right") - 1
        inside = (i >= 0) & (i < len(labels))
        return [labels[k] if ok else NO_SPAN for k, ok in zip(i, inside)]
    return name_of


def _self_times(names, start, end) -> dict:
    """Seconds each op kind ran with no op nested inside it running: a
    control-flow op (a while loop, a conditional) spans its body's ops."""
    order = sorted(range(len(start)), key=lambda i: (start[i], -end[i]))
    out = {}
    stack = []                 # [index, end, time its children cover]

    def retire(j, child):
        out[names[j]] = out.get(names[j], 0.0) + end[j] - start[j] - child
    for i in order:
        while stack and stack[-1][1] <= start[i]:
            retire(stack[-1][0], stack.pop()[2])
        if stack:
            stack[-1][2] += min(end[i], stack[-1][1]) - start[i]
        stack.append([i, end[i], 0.0])
    for j, _, child in stack:
        retire(j, child)
    return out


def reduce(trace: dict, top: int = 10) -> dict | None:
    """Busy seconds (the union of operation intervals inside the traced
    window, averaged over the devices), the window's length, the ``top`` op
    kinds by self time and the ``top`` host spans by the idle device
    seconds they were open for, both averaged over the devices.  ``trace``
    holds ``devices``, ``window`` and ``host``: ``[[name, start_ns,
    end_ns], ...]``.  None where the trace holds no window or no device."""
    if not trace.get("window") or not trace["devices"]:
        return None
    w0, w1 = trace["window"]
    name_of = _innermost(trace["host"])
    n_dev = len(trace["devices"])
    busy = 0.0
    ops, gaps = {}, {}
    for dev in trace["devices"].values():
        start = np.clip(np.asarray(dev["start_ns"], dtype=float), w0, w1)
        end = np.clip(np.asarray(dev["end_ns"], dtype=float), w0, w1)
        live = end > start
        names = [n for n, ok in zip(dev["names"], live) if ok]
        start, end = start[live], end[live]
        for n, d in _self_times(names, start.tolist(), end.tolist()).items():
            ops[n] = ops.get(n, 0.0) + d / 1e9 / n_dev
        order = np.argsort(start, kind="stable")
        start, end = start[order], end[order]
        # a gap opens where an op starts after every earlier op has ended;
        # the last one runs from the latest end to the window's close
        reach = np.maximum.accumulate(np.concatenate(([w0], end)))
        opens = start > reach[:-1]
        gap_lo = np.concatenate((reach[:-1][opens], [reach[-1]]))
        gap_hi = np.concatenate((start[opens], [w1]))
        idle = gap_hi - gap_lo
        keep = idle > 0
        gap_lo, idle = gap_lo[keep], idle[keep]
        busy += (w1 - w0 - idle.sum()) / 1e9
        for n, d in zip(name_of(gap_lo + idle / 2), idle):
            gaps[n] = gaps.get(n, 0.0) + d / 1e9 / n_dev

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"busy_s": busy / n_dev, "window_s": (w1 - w0) / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}


class Tracer:
    """Records the profiler into a directory of its own under the temporary
    directory, inside a host annotation named ``WINDOW``: either between
    :meth:`start` and :meth:`stop` on the caller's thread, or over a slice
    of fixed length on a thread of its own (:meth:`slice`), which lets it
    open and close while the caller waits on the device.  Only the first
    recording of a run counts; :meth:`result` reduces it and deletes it."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = False
        self._dir = None
        self._ann = None
        self._t0 = None              # host clock at the window's start
        self._thread = None

    @property
    def started(self) -> bool:
        return self._dir is not None

    def start(self) -> None:
        if not self.enabled or self.started:
            return
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host annotations, not calls
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def slice(self, delay_s: float, length_s: float) -> None:
        """Record from ``delay_s`` to ``delay_s + length_s`` from now."""
        if not self.enabled or self._thread is not None:
            return

        def record():
            time.sleep(max(0.0, delay_s))
            self.start()
            time.sleep(length_s)
            self.stop()
        self._thread = threading.Thread(target=record, daemon=True)
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
        self.stop()

    def result(self, spans) -> dict | None:
        """The reduction, with the host spans ``[(name, start, end), ...]``
        (host clock, seconds) moved onto the trace's clock."""
        if self._dir is None:
            return None
        try:
            files = glob.glob(os.path.join(self._dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not files:
                return None
            tr = load(files[0])
            if tr["window"] is None:
                return None
            shift = tr["window"][0] - self._t0 * 1e9
            tr["host"] = [[n, a * 1e9 + shift, b * 1e9 + shift]
                          for n, a, b in spans]
            return reduce(tr)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
