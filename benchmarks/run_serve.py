"""Serve benchmark: drive the fleet-as-a-service control plane with a
seeded open-loop arrival process and report serving metrics.

A Poisson-ish trace (seeded exponential inter-arrival times, measured in
control slices) draws workloads uniformly from the 9-workload registry
across eight tenants and three modes (``vm`` scheduler guests on the pod
pool, ``native``/``guest`` on solo lanes).  The daemon admits, bin-packs,
sheds, evicts, and recovers exactly as in production; the report records

* **sustained guests/sec** — completed jobs over wall-clock drain time,
* **p50/p99 time-to-result** — in control slices and simulated ticks,
* control-plane event totals (migrations, parks, resumes, recoveries),
* a correctness bit: every completed checksum matched its registry
  golden (the daemon-vs-direct invariant, enforced per job).

``--smoke`` runs the 16-submission CI gate instead: a fixed-seed trace
with forced geometry — a full N=3 cohort plus a later long-running
tenant (so the policy must shed), sustained queue pressure (so a victim
is parked and later resumed), and one injected hart failure (so recovery
restores a snapshot).  The smoke asserts all of admission, >=1
migration, >=1 park, and >=1 recovery happened and every checksum hit
its golden; any violation exits non-zero.

Usage: PYTHONPATH=src python -m benchmarks.run_serve [--out PATH]
           [--submissions 64] [--seed 1234] [--rate 1.5]
           [--harts 4] [--guests 2] [--solo 2] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.hext import programs
from repro.core.hext.engine import use_compile_cache
from repro.core.hext.policies import BinPackPolicy
from repro.core.hext.service import DONE, FleetService

MODE_MIX = ("vm", "vm", "vm", "vm", "vm", "vm", "native", "guest")


def _drain_trace(svc: FleetService, arrivals, picks, fail_at=None,
                 max_slices=20000) -> float:
    """Feed the arrival trace into the daemon and drain it; returns the
    wall-clock seconds spent stepping (placement through completion)."""
    k = 0
    failed = False
    t0 = time.perf_counter()
    while k < len(arrivals) or any(not j.terminal for j in svc.jobs()):
        while k < len(arrivals) and arrivals[k] <= svc.slices:
            wl, tenant, mode = picks[k]
            svc.submit(wl, tenant=tenant, mode=mode)
            k += 1
        if fail_at is not None and not failed and svc.slices >= fail_at:
            lanes = [i for i, l in enumerate(svc._pod_lanes) if l.active]
            if lanes:
                svc.inject_hart_failure(lanes[-1], pool="pod")
                failed = True
        svc.step()
        if svc.slices >= max_slices:
            raise RuntimeError(f"trace failed to drain in {max_slices} "
                               f"slices (queued={len(svc._queue)})")
    return time.perf_counter() - t0


def _trace(n, seed, rate, registry):
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(rate, size=n)).astype(int)
    picks = [(registry[int(rng.integers(len(registry)))],
              int(rng.integers(8)),
              MODE_MIX[int(rng.integers(len(MODE_MIX)))])
             for _ in range(n)]
    return arrivals, picks


def run_soak(args) -> dict:
    registry = list(programs.WORKLOADS)
    svc = FleetService(
        n_harts=args.harts, guests_per_hart=args.guests, n_solo=args.solo,
        timeslice=args.timeslice, slice_ticks=args.slice_ticks,
        chunk=args.chunk, snapshot_every=3,
        policy=BinPackPolicy(max_queue=args.submissions,
                             partial_after=2))
    arrivals, picks = _trace(args.submissions, args.seed, args.rate,
                             registry)
    wall = _drain_trace(svc, arrivals, picks,
                        fail_at=args.fail_at if args.fail else None)
    done = [j for j in svc.jobs() if j.state == DONE]
    bad = [j.job_id for j in done if not j.ok]
    m = svc.metrics()
    report = {
        "submissions": args.submissions,
        "seed": args.seed,
        "rate_slices": args.rate,
        "pool": {"harts": args.harts, "guests_per_hart": args.guests,
                 "solo": args.solo, "timeslice": args.timeslice,
                 "slice_ticks": args.slice_ticks},
        "wall_seconds": round(wall, 3),
        "sustained_guests_per_sec": round(len(done) / wall, 3),
        "all_goldens_ok": not bad,
        "mismatched_jobs": bad,
        "metrics": m,
    }
    return report


def run_smoke(args) -> dict:
    """Fixed-seed 16-submission gate: forces one shed, one park/resume
    cycle, and one recovery, then checks every golden."""
    by = {w.name: w for w in programs.WORKLOADS}
    svc = FleetService(
        n_harts=2, guests_per_hart=3, n_solo=1, timeslice=args.timeslice,
        slice_ticks=args.slice_ticks, chunk=args.chunk, snapshot_every=3,
        fail_after=2,
        policy=BinPackPolicy(max_queue=16, partial_after=1, shed_margin=2))
    # forced geometry: a full N=3 cohort of long guests at slice 0, a
    # long 4th tenant a little later (partial cohort -> shed window),
    # then a burst of short jobs to hold queue pressure (evict), one
    # native solo job, and a mid-run hart failure (recover)
    names = (["susan", "dijkstra", "bitcount"] + ["qsort"] +
             ["sha", "crc32", "stringsearch", "fft", "sha", "crc32",
              "stringsearch", "fft", "sha", "crc32", "basicmath"])
    # the burst waits until slice 6 so the qsort lane boots under-packed
    # (live 3-vs-1 imbalance) and the shed window opens before the queue
    # pressure starts forcing evictions
    arrivals = np.array([0, 0, 0, 2] + [6] * 11)
    picks = [(by[n], t % 8, "vm") for t, n in enumerate(names)]
    picks.append((by["dijkstra"], 7, "native"))
    arrivals = np.append(arrivals, 6)
    wall = _drain_trace(svc, arrivals, picks, fail_at=10, max_slices=2000)
    done = [j for j in svc.jobs() if j.state == DONE]
    bad = [j.job_id for j in done if not j.ok]
    checks = {
        "all_goldens_ok": not bad and len(done) == 16,
        "shed_happened": svc.stats["migrations"] >= 1,
        "park_happened": svc.stats["parks"] >= 1,
        "recovery_happened": svc.stats["recoveries"] >= 1,
    }
    report = {
        "mode": "smoke", "submissions": 16,
        "wall_seconds": round(wall, 3),
        "sustained_guests_per_sec": round(len(done) / wall, 3),
        "checks": checks, "mismatched_jobs": bad,
        "metrics": svc.metrics(),
    }
    report["ok"] = all(checks.values())
    return report


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "results", "serve_runs.json"))
    ap.add_argument("--submissions", type=int, default=64)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--rate", type=float, default=1.5,
                    help="mean inter-arrival time in control slices")
    ap.add_argument("--harts", type=int, default=4)
    ap.add_argument("--guests", type=int, default=2)
    ap.add_argument("--solo", type=int, default=2)
    ap.add_argument("--timeslice", type=int, default=300)
    ap.add_argument("--slice-ticks", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--fail", action="store_true", default=True,
                    help="inject one hart failure mid-trace (default)")
    ap.add_argument("--no-fail", dest="fail", action="store_false")
    ap.add_argument("--fail-at", type=int, default=40,
                    help="slice at which the failure is injected")
    ap.add_argument("--smoke", action="store_true",
                    help="run the fixed 16-submission CI gate instead")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    report = run_smoke(args) if args.smoke else run_soak(args)
    report["generated_by"] = "benchmarks/run_serve.py"
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps(report, indent=2, sort_keys=True))
    print(f"\nwrote {args.out}")
    if args.smoke and not report["ok"]:
        print("SMOKE FAILED", file=sys.stderr)
        return 1
    if not args.smoke and not report["all_goldens_ok"]:
        print("GOLDEN MISMATCH", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
