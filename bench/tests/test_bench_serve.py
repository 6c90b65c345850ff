"""The served pod cell at a tiny size on the CPU: the result line's shape,
and ``correct`` turning false when answers are altered where they are
produced or never come."""
import json

import pytest

import control

CELL = "pod4.full"


def test_pod_cell_result_line(tiny_root, run_cell):
    res, out = run_cell(tiny_root, CELL, seed=2 ** 31 + 5, seconds=3.0)
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"jobs_per_s", "ttr_p95_s", "setup_s"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["checks"] == {"wrong_checksums": {"value": 0, "limit": 0},
                             "missing_results": {"value": 0, "limit": 0}}
    assert "compiles inside the window: 0" in out.out
    # a closed loop of one client per guest slot: 2 lanes x 4 guests
    assert "clients=8," in out.out and "rejected=0," in out.out


def test_pod_cell_traced_run(tiny_root, run_cell):
    res, _ = run_cell(tiny_root, CELL, seconds=3.0, trace=1)
    assert set(res["metrics"]) == {"control_share.serve",
                                   "engine_tick_us.serve"}
    assert res["correct"] is True


@pytest.mark.parametrize("fault,check", [("altered", "wrong_checksums"),
                                         ("unchanged", "missing_results"),
                                         ("half", "missing_results")])
def test_each_fault_is_not_correct(tiny_root, run_cell, fault, check):
    if fault != "altered":
        # answers that never come are waited for no longer than this
        path = tiny_root / "bench" / "traffic" / "consolidation.json"
        mix = json.loads(path.read_text())
        mix["drain_s"] = 2.0
        path.write_text(json.dumps(mix))
    wrap = control.fault(fault, {"kind": "fleet_service",
                                 "guests_per_hart": 4})
    res, _ = run_cell(tiny_root, CELL, seconds=3.0, wrap=wrap)
    assert res["correct"] is False
    assert res["checks"][check]["value"] > 0
