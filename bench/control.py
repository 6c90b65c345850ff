"""Run a cell with its timed path broken underneath, to show that the
comparison which decides ``correct`` catches the break.

    python3 bench/control.py --workload <cell> --fault <fault> \\
        --seeds <n> [<n> ...] --seconds <s>

Each fault wraps the engine the program is given:

* ``altered``   -- an answer altered where it is produced: after each
  engine run, the low bit of hart 0's exit checksum (batch cells) or of
  every guest's result mailbox (served cells) is flipped.  This is the
  control: it breaks the configuration's guarantee of exact answers.
* ``unchanged`` -- a step that returns its state unchanged.
* ``half``      -- half of the batch left out: only the first half of
  the harts runs; the rest keep their state.

Every seed runs in this one process, one after another; each prints the
result line that ``bench/run.py`` would.  The benchmark's own runs never
use this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.hext import programs  # noqa: E402
from repro.core.hext.bits import x64  # noqa: E402

from harness import cli, core, spec  # noqa: E402


class _Fault:
    def __init__(self, inner):
        self.inner = inner
        self.name = getattr(inner, "name", "custom")


class Unchanged(_Fault):
    def run(self, state, max_ticks, chunk=4096):
        return state


class Half(_Fault):
    def run(self, state, max_ticks, chunk=4096):
        with x64():
            h = int(state.counters.done.shape[0]) // 2
            head = jax.tree.map(lambda x: x[:h], state)
            tail = jax.tree.map(lambda x: x[h:], state)
            out = self.inner.run(head, max_ticks, chunk=chunk)
            return jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                out, tail)


class AlteredExit(_Fault):
    def run(self, state, max_ticks, chunk=4096):
        out = self.inner.run(state, max_ticks, chunk=chunk)
        with x64():
            c = out.counters
            flipped = c.exit_code.at[0].set(c.exit_code[0] ^ jnp.uint64(1))
            return out.replace(counters=dataclasses.replace(
                c, exit_code=flipped))


class AlteredMailboxes(_Fault):
    def __init__(self, inner, guests: int):
        super().__init__(inner)
        lay = programs.sched_layout(guests)
        self.words = np.array([(lay.guest_res + 8 * s) >> 3
                               for s in range(guests)])

    def run(self, state, max_ticks, chunk=4096):
        out = self.inner.run(state, max_ticks, chunk=chunk)
        with x64():
            mem = out.mem.at[:, self.words].set(
                out.mem[:, self.words] ^ jnp.uint64(1))
            return out.replace(mem=mem)


def fault(name: str, cfg: dict):
    """The engine wrapper that plants fault ``name`` in a cell of ``cfg``."""
    if name == "unchanged":
        return Unchanged
    if name == "half":
        return Half
    if name == "altered":
        if cfg["kind"] == "fleet_service":
            return lambda inner: AlteredMailboxes(
                inner, int(cfg["guests_per_hart"]))
        return AlteredExit
    raise ValueError(f"unknown fault {name!r}")


FAULTS = ("altered", "unchanged", "half")


def main(root: str, argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = spec.Bench(root)
    cell = bench.cell(args.workload)
    wrap = fault(args.fault, bench.config(cell["config"]))
    t_start = T_START
    for seed in args.seeds:
        run_args = cli.parse_args(["--workload", args.workload, "--seed",
                                   str(seed), "--seconds",
                                   str(args.seconds), "--trace", "0"])
        print(f"fault {args.fault}, seed {seed}", flush=True)
        core.emit(cli.execute(root, run_args, t_start, wrap))
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main(ROOT))
