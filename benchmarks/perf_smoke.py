"""CI push-gate smoke: fixed workload matrix, counter-drift gate.

Boots the same native+guest workload matrix the committed
``benchmarks/results/hext_runs.json`` goldens came from, runs it to
completion, and **fails (exit 1)** if any counter column drifts from the
committed per-workload goldens — the bit-identity contract behind every
perf change (DESIGN.md §7).  It reads the goldens and writes nothing;
speed is measured on the chip by ``bench/run.py``, never here.

Usage: PYTHONPATH=src python -m benchmarks.perf_smoke [--goldens PATH]
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.core.hext import programs
from repro.core.hext.engine import use_compile_cache
from repro.core.hext.sim import Fleet

GOLDEN_PATH = "benchmarks/results/hext_runs.json"
MAX_TICKS = 120000
CHUNK = 8192


def main(golden_path: str = GOLDEN_PATH) -> int:
    with open(golden_path) as f:
        golden_wl = json.load(f)["workloads"]

    wls = programs.WORKLOADS
    fleet = Fleet.boot(wls + wls,
                       guest=[False] * len(wls) + [True] * len(wls))
    fleet.run(MAX_TICKS, chunk=CHUNK)
    counters = fleet.counters()

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
              f"committed goldens in {golden_path}:")
        for line in drifted[:20]:
            print("  " + line)
        return 1

    total_ticks = sum(int(c.ticks) for c in counters)
    print(f"OK: all counter columns bit-identical to committed goldens "
          f"({total_ticks} ticks)")
    return 0


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--goldens", default=GOLDEN_PATH)
    sys.exit(main(ap.parse_args().goldens))
