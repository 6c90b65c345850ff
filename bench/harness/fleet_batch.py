"""A closed loop of batches through ``Fleet``: the paper's matrix of jobs,
repeated to fill the configuration's harts in a seed-drawn order, each
batch run to completion and its counters read back.

The window opens after a warm-up that compiles every program a batch
uses, and ends at the first batch boundary after ``--seconds``.  After
the window every hart of every batch is compared with the reference
counters and checksum of its (kernel, mode).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hext import engine as hext_engine
from repro.core.hext import programs
from repro.core.hext.bits import x64
from repro.core.hext.sim import Fleet

from harness import traffic
from harness.core import MASK64, TimedEngine

# A traced run records one batch boundary: the last TRACE_LEAD_S of one
# batch's engine run, its read-back, the next batch's boot and the first
# TRACE_LAG_S of its run.  The device runs some 350 operations per tick
# and the profiler keeps each, so a whole batch would not fit the host's
# memory.
TRACE_LEAD_S = 0.3
TRACE_LAG_S = 0.3
SCALARS = ("done", "exit_code", "instret", "instret_virt", "ticks",
           "pagefaults", "walks", "timer_irqs", "ctx_switches")
VECTORS = ("exc_by_level", "int_by_level")


def _engine(cfg: dict):
    if cfg["engine"] == "jit":
        return hext_engine.JitEngine()
    raise ValueError(f"unknown engine {cfg['engine']!r}")


@jax.jit
def _gather(state, idx):
    return jax.tree.map(lambda x: x[idx], state)


def _expected(jobs, ref: dict) -> dict:
    """Per job index, each reference counter as a numpy array."""
    rows = [ref["workloads"][name][mode] for name, mode in jobs]
    out = {k: np.array([int(r[k]) & MASK64 if k == "exit_code" else r[k]
                        for r in rows],
                       dtype=np.uint64 if k == "exit_code" else None)
           for k in SCALARS + VECTORS}
    out["checksum"] = np.array([ref["workloads"][name]["checksum"] & MASK64
                                for name, _ in jobs], dtype=np.uint64)
    return out


def compare(batches, jobs, ref: dict) -> tuple:
    """(harts whose counters differ from the reference in any field, harts
    whose exit checksum differs from the kernel's reference checksum)."""
    want = _expected(jobs, ref)
    counters = checksums = 0
    for idx, c in batches:
        bad = np.zeros(len(idx), dtype=bool)
        for k in SCALARS:
            got = np.asarray(c[k])
            if k == "exit_code":
                got = got.astype(np.uint64)
            bad |= got != want[k][idx]
        for k in VECTORS:
            bad |= np.any(np.asarray(c[k]) != want[k][idx], axis=1)
        counters += int(bad.sum())
        checksums += int(np.sum(np.asarray(c["exit_code"]).astype(np.uint64)
                                != want["checksum"][idx]))
    return counters, checksums


def drive(run) -> dict:
    cfg = run.cfg
    by = {w.name: w for w in programs.WORKLOADS}
    jobs = [(name, mode) for mode in cfg["modes"] for name in cfg["workloads"]]
    engine = TimedEngine(run.wrap(_engine(cfg)), run.spans)
    chunk, max_ticks = int(cfg["chunk"]), int(cfg["max_ticks"])
    with run.spans("boot"):
        base = Fleet.boot([by[name] for name, _ in jobs],
                          guest=[mode == "guest" for _, mode in jobs])
        base_state = base.harts.unwrap()
    specs = base.specs
    orders = traffic.batch_orders(run.mix, run.seed, len(jobs),
                                  int(cfg["harts"]))

    def batch(idx, ticks):
        with run.spans("boot"):
            with x64():
                state = jax.block_until_ready(
                    _gather(base_state, jnp.asarray(idx)))
            fleet = Fleet(state, [specs[j] for j in idx], engine=engine)
        fleet.run(ticks, chunk=chunk)
        with run.spans("readback"):
            with x64():
                c = jax.device_get(fleet.harts.unwrap().counters)
        return {k: getattr(c, k) for k in SCALARS + VECTORS}

    # warm-up: the gather, the run loop at this fleet's shape (given no
    # ticks, it compiles and returns) and the read-back
    batch(next(orders), 0)
    engine.runs.clear()

    t_open = run.open_window()
    batches = []
    ends = [t_open]
    while True:
        idx = next(orders)
        batches.append((idx, batch(idx, max_ticks)))
        now = time.perf_counter()
        ends.append(now)
        if now - t_open >= run.seconds:
            break
        if len(batches) == 1:
            # the boundary after the next batch, timed from this one
            t_run, t_ran, _ = engine.runs[-1]
            host_s = (t_run - ends[-2]) + (now - t_ran)
            run.tracer.slice(t_ran - ends[-2] - TRACE_LEAD_S,
                             TRACE_LEAD_S + host_s + TRACE_LAG_S)
    run.close_window()

    mism, wrong = compare(batches, jobs, run.reference)
    runs = engine.in_window(t_open, now)
    hart_ticks = sum(int(np.sum(c["ticks"])) for _, c in batches)
    loop_ticks = sum(r[2] for r in runs)
    harts = int(cfg["harts"])
    return {
        "window_s": now - t_open,
        "batches": len(batches),
        "batch_s": [b - a for a, b in zip(ends, ends[1:])],
        "hart_ticks": hart_ticks,
        "engine_s": sum(r[1] - r[0] for r in runs),
        "loop_ticks": loop_ticks,
        "hart_loop_ticks": harts * loop_ticks,
        "attempted": harts * len(batches),
        "failed": mism,
        "checks": {"counter_mismatches": {"value": mism, "limit": 0},
                   "wrong_checksums": {"value": wrong, "limit": 0}},
    }
