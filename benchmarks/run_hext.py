"""Run all MiBench-like workloads native + guest through the hext simulator
(one `Fleet` — the TPU-native 'many VMs in lockstep' mode) and dump the
per-workload counters that reproduce paper Figures 4-7.

On top of the native/guest pair, one ``{n}guest-preempt`` column per
requested tenant count boots every workload N times per hart under the
preemptive HS scheduler (timer-sliced round-robin, DESIGN.md §2c) and
reports the **consolidation-overhead curve**: how virtualization overhead
grows with tenants per hart — ``instret / (N × single-guest instret)`` for
N ∈ {1, 2, 4} by default (the cloud-density measurement the paper's
scenario motivates; add 8 with ``--guests``).

An **engine column** additionally times the same matrix on the pluggable
backends (``jit`` vs ``sharded`` ticks/s, DESIGN.md §3) after verifying
both are bit-identical to the counter-producing reference run, so the
committed counter goldens can never be perturbed by an engine swap.

Usage: PYTHONPATH=src python -m benchmarks.run_hext [--out PATH]
                                                    [--timeslice N]
                                                    [--guests 1 2 4 ...]
                                                    [--no-preempt]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.core.hext import engine as hext_engine
from repro.core.hext import programs
from repro.core.hext.sim import Fleet, MASK64

DEFAULT_GUEST_COUNTS = (1, 2, 4)


def _engine_column(wls, max_ticks: int, chunk: int, ref_fleet) -> dict:
    """jit-vs-sharded throughput on the same native/guest matrix.

    Each engine gets one untimed warmup pass over a throwaway fleet
    before its timed run.  Compilation is already shared across engines
    (the executable is cached per chunk shape), but the *first* timed
    run used to also pay one-off allocator growth and donation-buffer
    churn — which made whichever engine ran first (jit) look ~30%
    slower than the second (sharded's single-device jit fallback), a
    pure measurement-order artifact (DESIGN.md §7d).  With the warmup,
    both rates are steady-state and converge on one device.

    Results are checked bit-identical against the reference fleet the
    counter columns came from, and ticks/s is aggregate simulated ticks
    over wall time.  On a single-device host the sharded engine falls
    back to jit (recorded in the column)."""
    flags = [False] * len(wls) + [True] * len(wls)
    ref = ref_fleet.counters()
    total_ticks = sum(int(c.ticks) for c in ref)
    out = {}
    for name in ("jit", "sharded"):
        warm = Fleet.boot(wls + wls, guest=flags, engine=name)
        t0 = time.time()
        warm.run(max_ticks, chunk=chunk)
        warmup_wall = time.time() - t0
        fleet = Fleet.boot(wls + wls, guest=flags, engine=name)
        t0 = time.time()
        fleet.run(max_ticks, chunk=chunk)
        wall = time.time() - t0
        for i in range(len(fleet)):
            d = hext_engine.diff_states(fleet[i], ref_fleet[i])
            if d:
                raise RuntimeError(
                    f"engine {name} drifted from the reference on hart "
                    f"{i}: {d[:3]}")
        out[name] = {
            "wall_seconds": wall,
            "warmup_wall_seconds": warmup_wall,
            "ticks_per_sec": total_ticks / max(wall, 1e-9),
        }
    out["sharded"]["devices"] = len(jax.devices())
    out["sharded"]["fallback_to_jit"] = len(jax.devices()) < 2
    return out


def main(out_path: str = "benchmarks/results/hext_runs.json",
         max_ticks: int = 120000, chunk: int = 8192,
         timeslice: int | None = None, preempt: bool = True,
         guest_counts=DEFAULT_GUEST_COUNTS):
    wls = programs.WORKLOADS
    t_start = time.time()
    # the batch: [native×9 ; guest×9]
    fleet = Fleet.boot(wls + wls,
                       guest=[False] * len(wls) + [True] * len(wls))
    t0 = time.time()
    fleet.run(max_ticks, chunk=chunk)
    wall = time.time() - t0
    counters = fleet.counters()

    # engine column: jit vs sharded throughput on the same matrix, with a
    # bit-identity check against the counter-producing reference fleet so
    # the published goldens cannot be perturbed by an engine bug
    engines = _engine_column(wls, max_ticks, chunk, fleet)

    # consolidation columns: each workload × N tenants per hart, timer
    # round-robin (every N is its own fleet — image sizes differ with N)
    preempt_reports: dict = {}
    wall_preempt: dict = {}
    counts = tuple(guest_counts) if preempt else ()
    ts = programs.DEFAULT_TIMESLICE if timeslice is None else int(timeslice)
    for n in counts:
        pfleet = Fleet.boot(wls, guests_per_hart=n, timeslice=ts)
        t1 = time.time()
        pfleet.run(max_ticks * n, chunk=chunk)
        wall_preempt[n] = time.time() - t1
        preempt_reports[n] = pfleet.report()

    results = {}
    curve: dict = {n: [] for n in counts}
    for i, w in enumerate(wls):
        g = w.golden()
        entry = {
            "golden": int(g) & MASK64,
            "native": counters[i].to_dict(g),
            "guest": counters[i + len(wls)].to_dict(g),
        }
        for n in counts:
            label = "+".join([w.name] * n) + f"/{n}guest-preempt"
            p = preempt_reports[n].get(label)
            if p is None:
                continue
            # overhead vs running the N tenants back-to-back without
            # preemption: hart instret / (N × single-guest instret)
            ovh = p["instret"] / max(n * entry["guest"]["instret"], 1)
            p["overhead_vs_nx_guest"] = ovh
            if n == 2:                        # legacy key, same number
                p["overhead_vs_2x_guest"] = ovh
            if p["ok"]:
                curve[n].append(ovh)
            else:
                # an unfinished/failed hart has a truncated instret — keep
                # the column but keep it out of the published curve
                print(f"WARNING: {label} not ok — excluded from the "
                      f"consolidation curve")
            entry[f"{n}guest-preempt"] = p
        results[w.name] = entry
    consolidation = {
        str(n): {
            "mean_overhead": sum(v) / len(v) if v else None,
            "max_overhead": max(v) if v else None,
        } for n, v in curve.items()
    }
    out = {
        "wall_seconds_batched": wall,
        "wall_seconds_preempt": sum(wall_preempt.values()),
        "wall_seconds_preempt_by_n": {str(n): wall_preempt[n]
                                      for n in counts},
        "setup_seconds": t0 - t_start,
        "timeslice": ts,
        "engines": engines,
        "consolidation_overhead": consolidation,
        "workloads": results,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for name, r in results.items():
        n_, gg = r["native"], r["guest"]
        ratio = gg["instret"] / max(n_["instret"], 1)
        line = (f"{name:14s} ok={n_['ok']}/{gg['ok']} instret "
                f"{n_['instret']}→{gg['instret']} ({ratio:.2f}x) exc "
                f"{n_['exc_by_level']}→{gg['exc_by_level']} "
                f"pf {n_['pagefaults']}→{gg['pagefaults']}")
        ovhs = []
        for n in counts:
            p = r.get(f"{n}guest-preempt")
            if p is not None:
                ovhs.append(f"N={n}:{p['overhead_vs_nx_guest']:.2f}x")
        if ovhs:
            line += " | consolidation " + " ".join(ovhs)
        print(line)
    if consolidation:
        print("consolidation-overhead curve (mean over workloads): " +
              "  ".join(f"N={n}: {c['mean_overhead']:.3f}x"
                        for n, c in consolidation.items()
                        if c["mean_overhead"]))
    print("engine column: " +
          "  ".join(f"{n}: {e['ticks_per_sec']:,.0f} ticks/s"
                    for n, e in engines.items()) +
          (f"  (sharded fell back to jit on "
           f"{engines['sharded']['devices']} device)"
           if engines["sharded"]["fallback_to_jit"] else
           f"  ({engines['sharded']['devices']} devices)"))
    return out


if __name__ == "__main__":
    hext_engine.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/results/hext_runs.json")
    ap.add_argument("--max-ticks", type=int, default=120000)
    ap.add_argument("--timeslice", type=int, default=None,
                    help="preemption interval in ticks "
                         f"(default {programs.DEFAULT_TIMESLICE})")
    ap.add_argument("--guests", type=int, nargs="+",
                    default=list(DEFAULT_GUEST_COUNTS),
                    help="tenant counts for the consolidation columns")
    ap.add_argument("--no-preempt", action="store_true",
                    help="skip the consolidation columns")
    a = ap.parse_args()
    main(a.out, a.max_ticks, timeslice=a.timeslice,
         preempt=not a.no_preempt, guest_counts=tuple(a.guests))
