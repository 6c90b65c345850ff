"""The reduction from a profiler trace to busy time, top operations and
named idle gaps: on a hand-made trace with known answers, and on a small
trace recorded on a TPU v5e, against a plain interval walk."""
import json
import pathlib

import pytest

from harness import core, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _dev(*ops):
    return {"names": [o[0] for o in ops], "start_ns": [o[1] for o in ops],
            "end_ns": [o[2] for o in ops]}


HAND = {
    "window": [0, 100],
    "host": [["boot", 0, 10], ["engine.run", 10, 80], ["readback", 80, 95]],
    "devices": {"/device:TPU:0": _dev(("a", 12, 30), ("b", 30, 40),
                                      ("a", 50, 70), ("c", 85, 86),
                                      ("d", -5, 3))},
}


def test_hand_made_trace():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [0,3] [12,40] [50,70] [85,86]
    assert r["busy_s"] == pytest.approx(52e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a": 38e-9, "b": 10e-9, "d": 3e-9, "c": 1e-9})
    # gaps [3,12] [40,50] [70,85] [86,100], each named by the innermost
    # host span open at its midpoint
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"engine.run": 25e-9, "readback": 14e-9, "boot": 9e-9})
    assert [k for k, _ in r["idle_gaps"]] == ["engine.run", "readback",
                                              "boot"]


def test_two_devices_are_averaged_and_nested_spans_name_the_inner():
    t = {"window": [0, 100],
         "host": [["control_round", 0, 100], ["engine.run", 40, 60]],
         "devices": {"/device:TPU:0": _dev(("x", 0, 45), ("y", 55, 100)),
                     "/device:TPU:1": _dev(("x", 0, 100))}}
    r = trace.reduce(t)
    assert r["busy_s"] == pytest.approx(95e-9)
    # device 0 idles from 45 to 55, inside engine.run inside control_round
    assert dict(r["idle_gaps"]) == pytest.approx({"engine.run": 5e-9})
    assert dict(r["device_ops"]) == pytest.approx({"x": 72.5e-9,
                                                   "y": 22.5e-9})


def test_ops_are_ranked_by_self_time():
    """A while loop spans its body's ops; only its own time counts."""
    t = {"window": [0, 100], "host": [],
         "devices": {"/device:TPU:0": _dev(("%while", 0, 100),
                                           ("%fusion", 10, 40),
                                           ("%conditional", 50, 90),
                                           ("%copy", 60, 70))}}
    r = trace.reduce(t)
    assert dict(r["device_ops"]) == pytest.approx(
        {"%fusion": 30e-9, "%conditional": 30e-9, "%while": 30e-9,
         "%copy": 10e-9})
    assert r["busy_s"] == pytest.approx(100e-9)
    assert trace.op_kind("%broadcast.631.clone = u32[18] broadcast(x)") == \
        "%broadcast"


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce({"window": None, "host": [],
                         "devices": HAND["devices"]}) is None
    assert trace.reduce({"window": [0, 100], "host": HAND["host"],
                         "devices": {}}) is None


def _walk(t):
    """A plain walk: per device, merge the sorted intervals one by one."""
    w0, w1 = t["window"]
    busy = []
    for d in t["devices"].values():
        iv = sorted((max(s, w0), min(e, w1))
                    for s, e in zip(d["start_ns"], d["end_ns"]))
        total, reach = 0.0, w0
        for s, e in iv:
            if e <= s:
                continue
            if e > reach:
                total += e - max(s, reach)
                reach = e
        busy.append(total)
    return sum(busy) / len(busy) / 1e9, (w1 - w0) / 1e9


def test_recorded_chip_trace_against_a_plain_walk():
    with open(DATA / "trace_v5e.json") as fh:
        t = json.load(fh)
    r = trace.reduce(t)
    busy, window = _walk(t)
    assert r["window_s"] == pytest.approx(window)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    ops = [v for _, v in r["device_ops"]]
    assert ops == sorted(ops, reverse=True)


@pytest.mark.parametrize("spans,want", [
    ([], 0.0),
    ([(0.0, 2.0), (0.5, 1.0)], 2.0),
    ([(3.0, 4.0), (0.0, 1.0), (0.5, 1.5)], 2.5),
])
def test_compile_spans_are_merged(spans, want):
    assert core.covered(spans) == want
