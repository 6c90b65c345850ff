"""Quickstart: boot a guest VM under the xvisor-lite hypervisor and compare
it against native execution — the paper's experiment in 30 lines.

The optional second argument picks the execution backend (DESIGN.md §3):
``jit`` (default), ``sharded`` (pmap over jax.devices()), or ``oracle``
(the pure-Python reference model — slow, but great for differential
debugging: every counter, `walks` included, matches the device engines
bit-for-bit).

Run with the package on the path (see DESIGN.md §6):

    PYTHONPATH=src python examples/quickstart.py [workload] [engine]
"""
import sys
import time

from repro.core.hext import programs
from repro.core.hext.engine import ENGINES, use_compile_cache
from repro.core.hext.sim import Fleet


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "crc32"
    engine = sys.argv[2] if len(sys.argv) > 2 else "jit"
    by_name = {w.name: w for w in programs.WORKLOADS}
    if name not in by_name:
        sys.exit(f"unknown workload {name!r}; "
                 f"choose from: {', '.join(sorted(by_name))}")
    if engine not in ENGINES:
        sys.exit(f"unknown engine {engine!r}; "
                 f"choose from: {', '.join(sorted(ENGINES))}")
    wl = by_name[name]
    print(f"workload: {wl.name}   golden checksum: {wl.golden()}   "
          f"engine: {engine}")
    fleet = Fleet.boot([wl, wl], guest=[False, True], engine=engine)
    t0 = time.time()
    fleet.run(max_ticks=120000, chunk=8192)
    wall = time.time() - t0
    for spec, c in zip(fleet.specs, fleet.counters()):
        label = ("guest (two-stage, xvisor-lite)" if spec.guest else "native")
        print(f"{label:34s} checksum_ok={c.ok(wl.golden())}  "
              f"instret={int(c.instret)}  "
              f"exceptions M/HS/VS={c.exc_by_level.tolist()}  "
              f"pagefaults={int(c.pagefaults)}")
    print(f"fleet wall={wall:.1f}s (both machines in one lockstep run)")


if __name__ == "__main__":
    use_compile_cache()
    main()
