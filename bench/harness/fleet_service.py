"""A closed loop of guest jobs served by ``FleetService``: one client per
guest slot of the pod, each submitting its next job as soon as its last
is harvested, one control round and one engine slice after another.

Set-up compiles what the pool runs, then serves the same traffic for the
mix's ``warmup_s`` so the pool is in steady state when the window opens.
The window ends at the first round boundary after ``--seconds``; a traced
run goes on to the end of the next round that snapshots every lane, and
records that round.  Then the clients stop and the pool runs on for at
most ``drain_s``, until every job that was in flight when the window
opened has its answer.  Every answer harvested, in set-up, window or
drain, is compared with the reference checksum of its kernel.
"""
from __future__ import annotations

import shutil
import tempfile
import time

from repro.core.hext import engine as hext_engine
from repro.core.hext import programs
from repro.core.hext.policies import BinPackPolicy
from repro.core.hext.service import DONE, REJECTED, FleetService
from repro.core.hext.sim import Fleet, HartState

from harness import traffic
from harness.core import MASK64, TimedEngine, percentile


def _warm(cfg: dict, cohort) -> None:
    """Compile what the pool runs before the traffic starts: its engine
    loop at the pool's shape, and the splice of a provisioned cohort into a
    lane and the read of one lane, which the control plane does eagerly."""
    pool = Fleet.from_states([cohort] * int(cfg["pod_lanes"]),
                             engine=hext_engine.JitEngine())
    pool.replace_hart(0, cohort)
    pool[0]
    pool.run(int(cfg["slice_ticks"]), chunk=int(cfg["chunk"]))


def drive(run) -> dict:
    cfg, mix = run.cfg, run.mix
    by = {w.name: w for w in programs.WORKLOADS}
    n = int(cfg["guests_per_hart"])
    ts = int(cfg["timeslice"])
    kernels = mix["kernels"]
    _warm(cfg, HartState.boot_preemptive(
        *[by[kernels[i % len(kernels)]] for i in range(n)], timeslice=ts))
    engine = TimedEngine(run.wrap(hext_engine.JitEngine()), run.spans)
    snapshots = tempfile.mkdtemp(prefix="bench-pod-")
    try:
        svc = FleetService(
            n_harts=int(cfg["pod_lanes"]), guests_per_hart=n, n_solo=0,
            timeslice=ts, slice_ticks=int(cfg["slice_ticks"]),
            chunk=int(cfg["chunk"]), engine=engine,
            policy=BinPackPolicy(**cfg["policy"]), snapshot_dir=snapshots)
        return _serve(run, svc, engine, by)
    finally:
        shutil.rmtree(snapshots, ignore_errors=True)


def _serve(run, svc, engine, by) -> dict:
    cfg, mix = run.cfg, run.mix
    clients = int(cfg["pod_lanes"]) * int(cfg["guests_per_hart"]) * \
        int(mix["clients_per_slot"])
    stream = traffic.closed_jobs(mix, run.seed)
    submitted = {}                   # job id -> submit time (perf_counter s)
    rounds = []                      # start of each round, by slice
    open_ = set()                    # submitted jobs not yet terminal
    t0 = time.perf_counter()
    t_open = None
    while True:
        with run.spans("submit"):
            while len(open_) < clients:
                jid = svc.submit(by[next(stream)], tenant=len(submitted),
                                 mode=mix["mode"])
                submitted[jid] = time.perf_counter()
                open_.add(jid)
        now = time.perf_counter()
        if t_open is None and now - t0 >= float(mix["warmup_s"]):
            t_open, first = run.open_window(), svc.slices
            at_open = set(open_)
        if t_open is not None and now - t_open >= run.seconds:
            # a traced run goes on to the next round that snapshots every
            # lane, records that round alone and closes after it: a trace
            # of four rounds, the whole snapshot period, took 30 GB of host
            # memory and 110 s to write on a v5e host
            if not run.tracer.enabled or run.tracer.started:
                break
            if svc.slices % svc.snapshot_every == 0:
                run.tracer.start()
        rounds.append(time.perf_counter())
        with run.spans("control_round"):
            svc.step()
        open_ = {j for j in open_ if not svc.job(j).terminal}
    t_close = now
    last = svc.slices - 1
    run.close_window()

    # drain: the clients stop; every job in flight at the window's opening
    # gets its answer or runs out of time
    deadline = time.perf_counter() + float(mix["drain_s"])
    while any(not svc.job(j).terminal for j in at_open) and \
            time.perf_counter() < deadline:
        rounds.append(time.perf_counter())
        svc.step()
    drained = time.perf_counter() - t_close

    ref = run.reference["workloads"]
    jobs = [svc.job(j) for j in submitted]
    wrong = {j.job_id for j in jobs if j.state == DONE and
             (j.checksum & MASK64) != (ref[j.name]["checksum"] & MASK64)}
    missing = [j for j in at_open if svc.job(j).state != DONE]
    in_window = sorted((j for j in jobs if j.state == DONE
                        and first <= j.done_slice <= last),
                       key=lambda j: (j.done_slice, j.job_id))
    ttr = [rounds[j.done_slice] - submitted[j.job_id] for j in in_window]
    due = at_open | {j.job_id for j in in_window}
    failed = [j for j in due if svc.job(j).state != DONE or j in wrong]
    runs = engine.in_window(t_open, t_close)
    engine_s = sum(r[1] - r[0] for r in runs)
    rec = {
        "window_s": t_close - t_open,
        "jobs_ok": sum(j.job_id not in wrong for j in in_window),
        "ttr_s": ttr,
        "done_s": [rounds[j.done_slice] - t_open for j in in_window],
        "engine_s": engine_s,
        "loop_ticks": sum(r[2] for r in runs),
        "control_s": run.spans.total("control_round", t_open, t_close)
        - engine_s,
        "rounds": last - first + 1,
        "clients": clients,
        "submitted": len(submitted),
        "rejected": sum(j.state == REJECTED for j in jobs),
        "in_flight_at_open": len(at_open),
        "drain_s": drained,
        "attempted": len(due),
        "failed": len(failed),
        "checks": {"wrong_checksums": {"value": len(wrong), "limit": 0},
                   "missing_results": {"value": len(missing), "limit": 0}},
    }
    if ttr:
        rec["ttr_p50_s"] = percentile(ttr, 50)
        rec["ttr_p95_s"] = percentile(ttr, 95)
    return rec
