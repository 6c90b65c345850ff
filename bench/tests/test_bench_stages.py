"""Device time by tick-pipeline stage: the scope path read from a trace's
operation metadata, on hand-encoded protobuf bytes and on a small trace
recorded on a TPU v5e, and the per-stage self times against busy time."""
import pathlib

import pytest

from harness import stages, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
# The first 1,500 operations of the 18-hart paper matrix's engine run
# (1,024 ticks, chunk 512) on a TPU v5 lite, with each operation's metadata
# cut to its ``tf_op`` stat and the traced window set around them.
SCOPED = DATA / "trace_v5e_scopes.xplane.pb"


@pytest.mark.parametrize("path,want", [
    ("jit(_run_impl)/while/body/while/body/closed_call/fetch/walk/cond/"
     "branch_1_fun/vmap()/add:", "walk"),
    ("jit(_run_impl)/while/body/while/body/closed_call/execute/decode/"
     "vmap(jit(_take))/gather:", "decode"),
    ("jit(_run_impl)/while/body/while/body/closed_call/execute/system/cond:",
     "system"),
    ("jit(_run_impl)/while/body/while/body/closed_call/retire/trap/cond:",
     "trap"),
    ("jit(_run_impl)/while/body/while/body/closed_call/timers/add:",
     "timers"),
    ("jit(_run_impl)/while/body/while/body/closed_call:", "unscoped"),
    ("jit(_run_impl)/while/cond/reduce_and:", "unscoped"),
    ("", "unscoped"),
])
def test_stage_is_the_innermost_stage_scope(path, want):
    assert stages.stage_of(path) == want


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _entry(key, value):
    return _msg((1, key), (2, value))


def test_op_paths_read_strings_and_references(tmp_path):
    """``tf_op`` given inline, given by reference to a stat's name, absent;
    a plane that is not a TPU's is skipped."""
    device = _msg(
        (1, 7), (2, "/device:TPU:0"),
        (3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 5), (3, 9))))),
        (4, _entry(1, _msg((1, 1), (2, "%fusion.1 = f()"),
                           (5, _msg((1, 3), (4, 64))),
                           (5, _msg((1, 2), (5, "jit(f)/fetch/add:")))))),
        (4, _entry(2, _msg((1, 2), (2, "%copy.2 = copy()"),
                           (5, _msg((1, 2), (7, 4)))))),
        (4, _entry(3, _msg((1, 3), (2, "%copy.3 = copy()")))),
        (5, _entry(2, _msg((1, 2), (2, "tf_op")))),
        (5, _entry(3, _msg((1, 3), (2, "flops")))),
        (5, _entry(4, _msg((1, 4), (2, "jit(f)/retire/trap/cond:")))))
    host = _msg((2, "/host:CPU"),
                (4, _entry(1, _msg((1, 1), (2, "%fusion.9 = g()"),
                                   (5, _msg((1, 1), (5, "x")))))),
                (5, _entry(1, _msg((1, 1), (2, "tf_op")))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device), (2, "an error")))
    assert stages.op_paths(str(path)) == {
        "%fusion.1 = f()": "jit(f)/fetch/add:",
        "%copy.2 = copy()": "jit(f)/retire/trap/cond:"}


def _dev(*ops):
    return {"names": [o[0] for o in ops], "stages": [o[1] for o in ops],
            "start_ns": [o[2] for o in ops], "end_ns": [o[3] for o in ops]}


def test_hand_made_stages_sum_to_busy_time():
    t = {"window": [0, 100],
         "devices": {"/device:TPU:0": _dev(("%while", "unscoped", 0, 90),
                                           ("%fusion", "fetch", 5, 20),
                                           ("%conditional", "walk", 20, 50),
                                           ("%fusion", "walk", 25, 45),
                                           ("%copy", "retire", 60, 70),
                                           ("%fusion", "trap", 95, 120))}}
    got = stages.reduce(t)
    assert set(got) == set(stages.STAGES) | {stages.UNSCOPED}
    assert got == pytest.approx(
        {"timers": 0.0, "fetch": 15e-9, "walk": 30e-9, "decode": 0.0,
         "execute": 0.0, "system": 0.0, "retire": 10e-9, "trap": 5e-9,
         "unscoped": 35e-9})
    busy = trace.reduce({**t, "host": []})["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_recorded_chip_trace_stages_sum_to_busy_time():
    tr = stages.load(str(SCOPED))
    (dev,) = tr["devices"].values()
    assert len(dev["stages"]) == len(dev["names"]) == len(dev["start_ns"]) \
        == 1500
    got = stages.reduce(tr)
    busy = trace.reduce({**tr, "host": []})["busy_s"]
    assert 0 < busy and sum(got.values()) == pytest.approx(busy, rel=1e-9)
    # every stage of a tick runs in four ticks of the paper matrix
    assert all(got[s] > 0 for s in stages.STAGES)
    # the rest is trace.load's
    plain = trace.load(str(SCOPED))
    assert plain["window"] == tr["window"]
    (want,) = plain["devices"].values()
    assert {k: dev[k] for k in want} == want


def test_no_window_reads_nothing():
    assert stages.reduce({"window": None, "devices": {}}) is None
