"""CI push-gate perf smoke: fixed workload matrix, counter-drift gate.

Boots the same native+guest workload matrix the committed
``benchmarks/results/hext_runs.json`` goldens came from, runs it to
completion, and

* **fails (exit 1)** if any counter column drifts from the committed
  per-workload goldens — the bit-identity contract behind every perf
  change (DESIGN.md §7);
* **appends** the measured aggregate ticks/s to a
  ``perf_smoke_history`` list inside ``hext_runs.json`` so successive
  runs leave a throughput trail next to the goldens they were gated on.

Throughput is recorded, not gated — CI hosts vary too much for a wall
-clock threshold, while counters must never move.  The timed pass runs
after one untimed warmup pass so the number is steady-state (same
rationale as ``run_hext._engine_column``).

Usage: PYTHONPATH=src python -m benchmarks.perf_smoke [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.core.hext import programs
from repro.core.hext.engine import use_compile_cache
from repro.core.hext.sim import Fleet

GOLDEN_PATH = "benchmarks/results/hext_runs.json"
MAX_TICKS = 120000
CHUNK = 8192


def _boot():
    wls = programs.WORKLOADS
    return wls, Fleet.boot(wls + wls,
                           guest=[False] * len(wls) + [True] * len(wls))


def main(out_path: str = GOLDEN_PATH) -> int:
    with open(out_path) as f:
        committed = json.load(f)
    golden_wl = committed["workloads"]

    # warmup pass (compile + allocator steady state), then the timed pass
    wls, fleet = _boot()
    fleet.run(MAX_TICKS, chunk=CHUNK)
    wls, fleet = _boot()
    t0 = time.time()
    fleet.run(MAX_TICKS, chunk=CHUNK)
    wall = time.time() - t0
    counters = fleet.counters()
    total_ticks = sum(int(c.ticks) for c in counters)
    rate = total_ticks / max(wall, 1e-9)

    drifted = []
    for i, w in enumerate(wls):
        g = w.golden()
        got = {"native": counters[i].to_dict(g),
               "guest": counters[i + len(wls)].to_dict(g)}
        for col in ("native", "guest"):
            want = golden_wl[w.name][col]
            for k, v in want.items():
                have = got[col].get(k)
                # json round-trip normalizes tuples → lists
                if isinstance(have, tuple):
                    have = list(have)
                if have != v:
                    drifted.append(f"{w.name}/{col}.{k}: "
                                   f"committed={v} measured={have}")
    if drifted:
        print(f"FAIL: {len(drifted)} counter column(s) drifted from the "
              f"committed goldens in {out_path}:")
        for line in drifted[:20]:
            print("  " + line)
        return 1

    entry = {"ticks_per_sec": rate, "wall_seconds": wall,
             "total_ticks": total_ticks}
    committed.setdefault("perf_smoke_history", []).append(entry)
    with open(out_path, "w") as f:
        json.dump(committed, f, indent=2)
    base = committed.get("engines", {}).get("jit", {}).get("ticks_per_sec")
    vs = f" ({rate / base:.2f}x committed jit column)" if base else ""
    print(f"OK: all counter columns bit-identical to committed goldens; "
          f"{rate:,.0f} ticks/s over {total_ticks} ticks{vs}")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=GOLDEN_PATH)
    sys.exit(main(ap.parse_args().out))
