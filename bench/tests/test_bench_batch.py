"""The batch cells at a tiny size on the CPU: the result line's shape, the
per-layer metrics of a traced run, and ``correct`` turning false for a
counter that differs from the reference and for each fault the cell can
have."""
import json

import pytest

import control

CELLS = ("matrix.batch",)
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _shape(res, names):
    assert list(res)[-1] == "checks"
    assert set(KEYS) <= set(res)
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_batch_cell_result_line(tiny_root, run_cell, cell):
    res, out = run_cell(tiny_root, cell, seed=2 ** 31 + 11, seconds=1.0)
    _shape(res, {"hart_ticks_per_s", "setup_s"})
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 8 == 0 and res["attempted"] > 0
    assert res["checks"]["counter_mismatches"] == {"value": 0, "limit": 0}
    assert "compiles inside the window: 0" in out.out
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_batch_cell_traced_run(tiny_root, run_cell):
    res, _ = run_cell(tiny_root, "matrix.batch", seconds=1.0, trace=1)
    # the CPU has no TPU plane to trace, so the device's share is absent
    _shape(res, {"host_share.batch", "loop_tick_us.batch",
                 "live_share.batch"})
    # sha and fft, native and guest: 1320 + 1649 + 1159 + 1544 live ticks
    # of every 4 harts x 2048 loop ticks
    assert res["metrics"]["live_share.batch"]["value"] == \
        pytest.approx(100 * 5672 / (4 * 2048))
    assert res["correct"] is True


def test_a_mismatched_counter_is_not_correct(tiny_root, run_cell):
    path = tiny_root / "bench" / "reference" / "mibench-goldens.json"
    ref = json.loads(path.read_text())
    ref["workloads"]["sha"]["guest"]["walks"] += 1
    path.write_text(json.dumps(ref))
    res, out = run_cell(tiny_root, "matrix.batch", seconds=1.0)
    assert res["correct"] is False
    # a quarter of the harts run sha as a guest
    assert res["checks"]["counter_mismatches"]["value"] == \
        res["attempted"] // 4 == res["failed"]
    assert res["checks"]["wrong_checksums"]["value"] == 0
    assert "check counter_mismatches" in out.err


@pytest.mark.parametrize("fault", ["altered", "unchanged", "half"])
def test_each_fault_is_not_correct(tiny_root, run_cell, fault):
    wrap = control.fault(fault, {"kind": "fleet_batch"})
    res, _ = run_cell(tiny_root, "matrix.batch", seconds=0.5, wrap=wrap)
    assert res["correct"] is False
    assert res["checks"]["counter_mismatches"]["value"] > 0
