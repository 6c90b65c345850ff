"""Fuzz-throughput benchmark: scenarios/sec batched vs serial vs oracle.

The torture harness's design claim (ROADMAP north star: batch everything)
is that running the whole randomized corpus as ONE vmapped Fleet beats
per-scenario host loops.  This benchmark measures all three executors on
the same fixed-seed corpus:

* **batched** — the full corpus as one ``Fleet.from_corpus`` run (one XLA
  executable, all scenarios in lockstep);
* **serial**  — one single-hart Fleet per scenario (one compile for the
  (1, mem) shape, then per-scenario dispatch + host sync), measured on a
  subsample and reported per-scenario;
* **oracle**  — the pure-Python reference model.

Results land in ``benchmarks/results/torture_fuzz.json`` — a separate
file from ``hext_runs.json``, whose counter columns are a bit-identical
regression oracle and must never be perturbed by a fuzz run.

Usage: PYTHONPATH=src python -m benchmarks.run_torture [--count N]
                                                       [--serial-sample K]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core.hext import torture
from repro.core.hext.engine import use_compile_cache
from repro.core.hext.sim import Fleet


def main(out_path: str = "benchmarks/results/torture_fuzz.json",
         seed: int = torture.DEFAULT_SEED, count: int = 256,
         serial_sample: int = 16, max_ticks: int = torture.MAX_TICKS):
    t0 = time.time()
    corpus = torture.generate(seed, count)
    wall_gen = time.time() - t0
    # throughput legs use the fuzz family only: sched-family images are
    # bigger than T_MEM_WORDS, and mixing shapes would split the single
    # XLA executable the benchmark is about
    scenarios = [s for s in corpus if s.family == "fuzz"]
    n_fuzz = len(scenarios)

    # batched cold: the whole corpus as one Fleet, including the one-time
    # XLA compile for the (count, mem) shape
    t0 = time.time()
    fleet = Fleet.from_corpus([s.image for s in scenarios],
                              mem_words=torture.T_MEM_WORDS)
    fleet.run(max_ticks, chunk=torture.CHUNK)
    wall_batched_cold = time.time() - t0
    n_done = sum(1 for c in fleet.counters() if bool(c.done))
    # batched warm: a fresh Fleet of the same shape reuses the executable —
    # the steady-state rate a nightly corpus sweep actually sees
    t0 = time.time()
    Fleet.from_corpus([s.image for s in scenarios],
                      mem_words=torture.T_MEM_WORDS).run(
        max_ticks, chunk=torture.CHUNK)
    wall_batched = time.time() - t0

    # serial: per-scenario single-hart Fleets (subsample, steady-state —
    # the first run pays the (1, mem) compile, so time runs 2..K+1)
    sub = scenarios[:serial_sample + 1]
    Fleet.from_corpus([sub[0].image],
                      mem_words=torture.T_MEM_WORDS).run(
        max_ticks, chunk=torture.CHUNK)             # warm the compile cache
    t0 = time.time()
    for s in sub[1:]:
        Fleet.from_corpus([s.image],
                          mem_words=torture.T_MEM_WORDS).run(
            max_ticks, chunk=torture.CHUNK)
    wall_serial_each = (time.time() - t0) / max(len(sub) - 1, 1)

    # oracle throughput (the host-side reference cost per scenario),
    # measured through the first-class OracleEngine fleet path — the same
    # leg run_corpus diffs against (DESIGN.md §3/§5)
    t0 = time.time()
    Fleet.from_corpus([s.image for s in scenarios],
                      mem_words=torture.T_MEM_WORDS,
                      engine="oracle").run(max_ticks, chunk=torture.CHUNK)
    wall_oracle = time.time() - t0

    batched_rate = n_fuzz / wall_batched
    serial_rate = 1.0 / wall_serial_each
    # coverage column: the static shape buckets the coverage-guided
    # generator steered into over the WHOLE corpus (sched included) —
    # the dynamic-event buckets on top of these are the nightly
    # `--coverage-out` artifact's job, since they need an oracle pass
    static_hist = torture.coverage_map(corpus, {})
    out = {
        "seed": seed, "count": count, "max_ticks": max_ticks,
        "fuzz_scenarios": n_fuzz,
        "sched_scenarios": count - n_fuzz,
        "scenarios_done": n_done,
        "wall_gen_seconds": wall_gen,
        "coverage_buckets_static": len(static_hist),
        "fuzz_throughput": {
            "batched_scenarios_per_sec": batched_rate,
            "batched_cold_scenarios_per_sec": n_fuzz / wall_batched_cold,
            "serial_scenarios_per_sec": serial_rate,
            "oracle_scenarios_per_sec": n_fuzz / wall_oracle,
            "batched_speedup_vs_serial": batched_rate / serial_rate,
            "serial_sample": serial_sample,
        },
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    ft = out["fuzz_throughput"]
    print(f"{n_fuzz}/{count} fuzz scenarios ({n_done} done, "
          f"{len(static_hist)} static coverage buckets): "
          f"batched {ft['batched_scenarios_per_sec']:.2f}/s, "
          f"serial {ft['serial_scenarios_per_sec']:.2f}/s "
          f"({ft['batched_speedup_vs_serial']:.1f}x), "
          f"oracle {ft['oracle_scenarios_per_sec']:.1f}/s")
    return out


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/results/torture_fuzz.json")
    ap.add_argument("--seed", type=int, default=torture.DEFAULT_SEED)
    ap.add_argument("--count", type=int, default=256)
    ap.add_argument("--serial-sample", type=int, default=16)
    a = ap.parse_args()
    main(a.out, a.seed, a.count, serial_sample=a.serial_sample)
