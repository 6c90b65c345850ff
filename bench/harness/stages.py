"""Device time by stage of the tick pipeline, from a profiler trace.

``machine.step_batched`` runs each stage under a ``jax.named_scope``, so
the stage is part of each HLO operation's ``op_name``.  A TPU trace keeps
that path in its operations' metadata, as the stat ``tf_op`` (for example
``jit(_run_impl)/while/body/closed_call/fetch/walk/cond/...:``), which
``jax.profiler.ProfileData`` does not expose: :func:`op_paths` reads it
from the ``.xplane.pb`` file's protobuf encoding, and skips the events.

:func:`load` reads a trace as ``trace.load`` does and adds, per device, the
stage of each operation: the innermost stage scope on its path, or
``unscoped`` (loop control, carry copies, and every program other than
the tick loop).  :func:`reduce` gives each stage's device self time in the
traced window, averaged over the devices; the stages sum to the busy time.
"""
from __future__ import annotations

import mmap

import jax

from harness import trace

STAGES = ("timers", "fetch", "walk", "decode", "execute", "system",
          "retire", "trap")
UNSCOPED = "unscoped"
SCOPE_STAT = "tf_op"

# protobuf field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_ENTRY_VALUE = 2                 # of a map entry
_META_NAME, _META_STATS = 2, 5   # XEventMetadata; XStatMetadata's name is 2
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def stage_of(path: str) -> str:
    """The innermost stage scope of an op's ``tf_op`` path."""
    for part in reversed(path.split(":", 1)[0].split("/")):
        if part in STAGES:
            return part
    return UNSCOPED


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field number, value)`` of the message in ``buf[lo:hi]``: an int
    for a varint, ``(start, end)`` for a length-delimited field, None for
    a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _string(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, spans):
    for lo, hi in spans:
        for num, value in _fields(buf, lo, hi):
            if num == _ENTRY_VALUE:
                yield value


def op_paths(path: str) -> dict:
    """``{op name: tf_op path}`` from the event metadata of every TPU plane
    of an ``.xplane.pb`` file.  An op whose name two programs share keeps
    the first program's path."""
    out = {}
    with open(path, "rb") as fh, \
            mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for num, plane in _fields(buf, 0, len(buf)):
            if num != _SPACE_PLANES:
                continue
            name, events, stats = "", [], []
            for pnum, value in _fields(buf, *plane):
                if pnum == _PLANE_NAME:
                    name = _string(buf, value)
                elif pnum == _PLANE_EVENT_META:
                    events.append(value)
                elif pnum == _PLANE_STAT_META:
                    stats.append(value)
            if not trace.DEVICE_PLANE.match(name):
                continue
            stat_names = {}
            for lo, hi in _map_values(buf, stats):
                f = dict(_fields(buf, lo, hi))
                stat_names[f.get(1)] = _string(buf, f.get(2, (0, 0)))
            scope_id = next((k for k, v in stat_names.items()
                             if v == SCOPE_STAT), None)
            for lo, hi in _map_values(buf, events):
                op, scope = None, None
                for mnum, value in _fields(buf, lo, hi):
                    if mnum == _META_NAME:
                        op = _string(buf, value)
                    elif mnum == _META_STATS:
                        s = dict(_fields(buf, *value))
                        if s.get(_STAT_ID) != scope_id:
                            continue
                        scope = _string(buf, s[_STAT_STR]) \
                            if _STAT_STR in s else \
                            stat_names.get(s.get(_STAT_REF), "")
                if op is not None and scope is not None:
                    out.setdefault(op, scope)
    return out


def load(path: str) -> dict:
    """``trace.load``'s lists, each device's with ``stages``: the stage of
    each operation, computed once per distinct operation."""
    paths = op_paths(path)
    pd = jax.profiler.ProfileData.from_file(path)
    devices, window = {}, None
    seen = {}                  # an op's full name -> (kind, stage)
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                dev = {"names": [], "stages": [], "start_ns": [],
                       "end_ns": []}
                for ev in line.events:
                    got = seen.get(ev.name)
                    if got is None:
                        got = seen[ev.name] = (
                            trace.op_kind(ev.name),
                            stage_of(paths.get(ev.name, "")))
                    dev["names"].append(got[0])
                    dev["stages"].append(got[1])
                    dev["start_ns"].append(ev.start_ns)
                    dev["end_ns"].append(ev.end_ns)
                devices[plane.name] = dev
        elif window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW:
                        window = [ev.start_ns, ev.end_ns]
    return {"devices": devices, "window": window}


def reduce(tr: dict) -> dict | None:
    """Device self seconds of each stage (and ``unscoped``) inside the
    traced window, averaged over the devices; None where ``trace.reduce``
    reads nothing."""
    by_stage = {"window": tr["window"], "host": [], "devices": {
        p: {"names": d["stages"], "start_ns": d["start_ns"],
            "end_ns": d["end_ns"]} for p, d in tr["devices"].items()}}
    r = trace.reduce(by_stage, top=len(STAGES) + 1)
    if r is None:
        return None
    out = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    out.update(r["device_ops"])
    return out
