"""One run of one cell: check the device, set up, measure, compare, print.

The cell's configuration names the module that drives it (``kind``: a
module of this package with a ``drive(run)`` function), which fills a
record; each metric's reader turns the record into one number.
"""
from __future__ import annotations

import argparse
import importlib
import time

import jax

from repro.core.hext import engine as hext_engine

from harness import core, spec
from harness.trace import Tracer


class Run:
    """What a ``drive`` function is handed: the cell's files, the seed and the window's
    length, the host spans, and the calls that open and close the window."""

    def __init__(self, bench, cell, seed, seconds, trace, t_start,
                 wrap=None):
        self.cfg = bench.config(cell["config"])
        self.mix = bench.traffic(cell["traffic"])
        self.reference = bench.reference(self.cfg)
        self.seed = seed
        self.seconds = float(seconds)
        self.spans = core.Spans()
        self.meter = core.CompileMeter()
        self.tracer = Tracer(trace)
        self.devices = jax.devices()[:int(cell["chips"])]
        self.wrap = wrap or (lambda engine: engine)
        self.t_start = t_start
        self.t_open = None
        self.setup_s = None
        self.compiles_at_open = None
        self.compiles_in_window = None
        self.setup_compile_s = None

    def open_window(self) -> float:
        now = self.t_open = time.perf_counter()
        self.setup_s = now - self.t_start
        self.setup_compile_s = self.meter.seconds
        self.compiles_at_open = self.meter.compiles
        return now

    def close_window(self) -> None:
        self.tracer.join()
        self.compiles_in_window = self.meter.compiles - self.compiles_at_open


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(root: str, args, t_start: float, wrap=None) -> dict:
    """Run the cell and return the result line's object."""
    bench = spec.Bench(root)
    cell = bench.cell(args.workload)
    device = core.check_device(int(cell["chips"]))
    cache = hext_engine.use_compile_cache()
    # the control plane compiles small programs (one per lane index) that
    # JAX would otherwise leave out of the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(bench, cell, args.seed, args.seconds, bool(args.trace),
              t_start, wrap)
    try:
        kind = importlib.import_module(f"harness.{run.cfg['kind']}")
        rec = kind.drive(run)
    finally:
        run.meter.close()
    rec["setup_s"] = run.setup_s
    trace = run.tracer.result(run.spans.items)
    rec["trace"] = trace
    device["memory_peak_bytes"] = core.memory_peak_bytes(run.devices)
    print(f"set-up {run.setup_s:.3f} s: compile {run.setup_compile_s:.3f} s "
          f"(persistent cache {run.meter.hits} hits, {run.meter.misses} "
          f"misses, cache {cache}); compiles inside the window: "
          f"{run.compiles_in_window}; peak_bytes_in_use "
          f"{device['memory_peak_bytes']}", flush=True)
    print("record: " + ", ".join(
        f"{k}={v}" for k, v in rec.items()
        if k not in ("checks", "trace", "ttr_s", "done_s")),
        flush=True)
    metrics = {}
    for m in bench.metrics(cell["name"], bool(args.trace)):
        value = bench.reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in rec["checks"].values()),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = rec["checks"]
    return result


def main(root: str, argv=None, t_start: float | None = None,
         wrap=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    core.emit(execute(root, args, t_start, wrap))
    return 0
