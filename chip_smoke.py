"""Run the hext fleet's main path once on a TPU and check every result.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # ShardedEngine over 4 chips vs jit

Each phase compares what it computes against a reference and exits
non-zero on any mismatch:

* ``matrix``        — the paper's 9 workloads × {native, guest} on the jit
  engine; every counter column bit-identical to
  ``benchmarks/results/hext_runs.json`` (opened read-only).
* ``consolidation`` — the 2- and 4-guest preemptive columns vs their goldens.
* ``width``         — the 18-hart matrix tiled 64 times (1,152 harts, about
  300 MB of hart state); every copy vs its golden.
* ``torture``       — the fixed-seed 64-case corpus as one batched fleet vs
  the pure-Python oracle: 0 mismatches.
* ``serve``         — the 16-submission ``FleetService`` smoke of
  ``benchmarks/run_serve.py``: 16/16 goldens, >=1 shed, park and recovery.
* ``kernel``        — the Pallas two-stage walker vs its jnp reference.

``--four-chips`` runs only the width phase's fleet (1,152 harts, 288 per
chip) on ``engine="sharded"`` over exactly 4 chips, then on the jit engine
on the first chip, and requires the two to be bit-identical, to match the
goldens, and the sharded result to live on all 4 devices.

Every phase prints its set-up, compile and run seconds.  The last line of
standard output is one JSON object naming the device.  Timings here are a
first chip reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.hext import engine as hext_engine  # noqa: E402
from repro.core.hext import programs, torture  # noqa: E402
from repro.core.hext.bits import x64  # noqa: E402
from repro.core.hext.sim import Fleet  # noqa: E402

PLATFORM = "tpu"
GOLDEN_PATH = os.path.join(ROOT, "benchmarks", "results", "hext_runs.json")
MAX_TICKS = 120000          # the perf_smoke / run_hext matrix budget
CHUNK = 8192
COPIES = 64                 # 64 × 18 = 1,152 harts: width and --four-chips
TORTURE_SEED, TORTURE_COUNT = 2026, 64     # the CI push-gate corpus
# keys run_hext derives after the run; not part of a fleet report
DERIVED_KEYS = ("overhead_vs_nx_guest", "overhead_vs_2x_guest")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(SystemExit):
    """A phase's result disagrees with its reference (exit status 1)."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def _covered(spans) -> float:
    """Seconds covered by the union of ``(start, end)`` spans."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class CompileMeter:
    """Times JAX's own compile events (tracing, lowering, backend compile
    or persistent-cache load) and counts persistent-cache hits and misses,
    so a phase's compile time can be told apart from its run.  A nested
    jit's or a Pallas kernel's events fall inside their parent's, so the
    spans are merged, not summed."""

    def __init__(self):
        self.spans = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def seconds(self) -> float:
        return _covered(self.spans)

    def timed(self, fn):
        """(result, wall seconds, compile seconds within them)."""
        n, t0 = len(self.spans), time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0, _covered(self.spans[n:])


def device_info() -> dict:
    """The device as JAX reports it; fails unless it is a ``PLATFORM``."""
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        raise SystemExit(
            f"chip_smoke.py needs a {PLATFORM} device; JAX found platform "
            f"{devs[0].platform!r} ({len(devs)} device(s))")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_goldens() -> dict:
    with open(GOLDEN_PATH) as fh:
        return json.load(fh)


def _report(phase: str, setup, wall: float, compile_s: float,
            verdict: str) -> None:
    """One line per phase; ``setup=None`` where set-up is inside the run."""
    setup = "in run" if setup is None else f"{setup:.3f} s"
    print(f"[{phase}] setup {setup} | compile {compile_s:.3f} s | "
          f"run {wall - compile_s:.3f} s | {verdict}", flush=True)


def _mismatches(label: str, got: dict, want: dict, skip=()) -> list:
    want = {k: v for k, v in want.items() if k not in skip}
    got = json.loads(json.dumps(got))          # tuples → lists, as stored
    bad = [f"{label}: keys differ: {sorted(set(got) ^ set(want))}"] \
        if set(got) != set(want) else []
    return bad + [f"{label}.{k}: golden={want[k]} got={got[k]}"
                  for k in sorted(set(got) & set(want)) if got[k] != want[k]]


def _fail_on(bad: list, phase: str) -> None:
    """Fail with the first mismatches and a count per column and field
    (hart numbers dropped), so a wide fleet's failure stays readable."""
    if bad:
        per = collections.Counter(re.sub(r"^hart \d+ ", "", b).split(":")[0]
                                  for b in bad)
        raise SmokeFailure(f"{phase}: {len(bad)} mismatch(es): "
                           + "; ".join(bad[:8]) + f" | by field: {dict(per)}")


def _matrix_boot(wls, copies: int = 1, engine=None) -> Fleet:
    """``wls × {native, guest}``, tiled ``copies`` times on the device."""
    n = len(wls)
    base = Fleet.boot(wls + wls, guest=[False] * n + [True] * n,
                      engine=engine)
    if copies == 1:
        return base
    with x64():
        tiled = jax.tree.map(
            lambda x: jnp.tile(x, (copies,) + (1,) * (x.ndim - 1)),
            base.harts.unwrap())
    return Fleet(tiled, base.specs * copies, engine=engine)


def _host_counters(fleet: Fleet) -> list:
    """Per-hart ``Counters`` with host leaves: one device→host copy."""
    with x64():
        c = jax.device_get(fleet.harts.unwrap().counters)
    return [jax.tree.map(lambda x, i=i: x[i], c) for i in range(len(fleet))]


def _matrix_mismatches(fleet: Fleet, wls, goldens: dict) -> list:
    """Hart i of a (tiled) matrix fleet vs its native/guest golden."""
    n = len(wls)
    gold = [w.golden() for w in wls]
    bad = []
    for i, c in enumerate(_host_counters(fleet)):
        j = i % (2 * n)
        col = "native" if j < n else "guest"
        w = wls[j % n]
        bad += _mismatches(f"hart {i} {w.name}/{col}", c.to_dict(gold[j % n]),
                           goldens["workloads"][w.name][col])
    return bad


def phase_matrix(meter: CompileMeter, goldens: dict, wls=None) -> None:
    wls = list(programs.WORKLOADS if wls is None else wls)
    fleet, setup, _ = meter.timed(lambda: _matrix_boot(wls))
    _, wall, comp = meter.timed(lambda: fleet.run(MAX_TICKS, chunk=CHUNK))
    _fail_on(_matrix_mismatches(fleet, wls, goldens), "matrix")
    _report("matrix", setup, wall, comp,
            f"PASS: {len(fleet)} harts bit-identical to the goldens, all ok")


def phase_consolidation(meter: CompileMeter, goldens: dict, wls=None,
                        guests=(2, 4)) -> None:
    wls = list(programs.WORKLOADS if wls is None else wls)
    ts = int(goldens["timeslice"])
    for n in guests:
        fleet, setup, _ = meter.timed(
            lambda: Fleet.boot(wls, guests_per_hart=n, timeslice=ts))
        _, wall, comp = meter.timed(
            lambda: fleet.run(MAX_TICKS * n, chunk=CHUNK))
        rep = fleet.report()
        bad = []
        for w in wls:
            label = "+".join([w.name] * n) + f"/{n}guest-preempt"
            bad += _mismatches(label, rep[label],
                               goldens["workloads"][w.name]
                               [f"{n}guest-preempt"], skip=DERIVED_KEYS)
        _fail_on(bad, f"consolidation N={n}")
        _report(f"consolidation N={n}", setup, wall, comp,
                f"PASS: {len(fleet)} harts bit-identical to the goldens")


def phase_width(meter: CompileMeter, goldens: dict, wls=None,
                copies: int = COPIES) -> None:
    wls = list(programs.WORKLOADS if wls is None else wls)
    fleet, setup, _ = meter.timed(lambda: _matrix_boot(wls, copies))
    with x64():
        state_bytes = sum(x.nbytes for x in
                          jax.tree.leaves(fleet.harts.unwrap()))
    _, wall, comp = meter.timed(lambda: fleet.run(MAX_TICKS, chunk=CHUNK))
    ticks = sum(int(c.ticks) for c in _host_counters(fleet))
    _fail_on(_matrix_mismatches(fleet, wls, goldens), "width")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[width] first chip reading, not a benchmark: {len(fleet)} harts, "
          f"{state_bytes} bytes of hart state, {ticks} simulated hart-ticks "
          f"in {wall - comp:.3f} s = {ticks / (wall - comp):.1f} "
          f"hart-ticks/s; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    _report("width", setup, wall, comp,
            f"PASS: all {len(fleet)} harts bit-identical to the goldens")


def phase_torture(meter: CompileMeter, seed: int = TORTURE_SEED,
                  count: int = TORTURE_COUNT) -> None:
    rep, wall, comp = meter.timed(lambda: torture.run_corpus(seed, count))
    if rep["failures"]:
        f = rep["failures"][0]
        raise SmokeFailure(
            f"torture: {len(rep['failures'])} of {count} cases differ from "
            f"the oracle; first: case {f['case']}: {f['diff'][:3]} "
            f"(repro: {f['repro']})")
    _report("torture", rep["wall_gen"], wall - rep["wall_gen"], comp,
            f"PASS: seed {seed}, {count} cases, 0 mismatches vs the oracle "
            f"(machine {rep['wall_machine']:.3f} s, oracle "
            f"{rep['wall_oracle']:.3f} s, {rep['coverage']['buckets']} "
            f"coverage buckets)")


def phase_serve(meter: CompileMeter) -> None:
    from benchmarks import run_serve
    args = run_serve.parse_args(["--smoke"])
    rep, wall, comp = meter.timed(lambda: run_serve.run_smoke(args))
    if not rep["ok"]:
        raise SmokeFailure(f"serve: checks {rep['checks']}, mismatched "
                           f"jobs {rep['mismatched_jobs']}")
    m = rep["metrics"]
    _report("serve", None, wall, comp,
            f"PASS: 16/16 goldens, checks {rep['checks']}, "
            f"{rep['sustained_guests_per_sec']} guests/s, metrics "
            f"{json.dumps(m, sort_keys=True)}")


def phase_kernel(meter: CompileMeter, shape=(8, 64, 512, 512, 4096),
                 force: str = "kernel") -> None:
    """The Pallas walker (``force="kernel"``, as ``auto`` picks on a TPU)
    vs the jnp reference on seeded tables with wide and negative entries."""
    from repro.kernels.pagewalk.ops import two_stage_translate
    T, R, P, G, B = shape
    rng = np.random.default_rng(2026)
    # entries past the stage-2 table and coordinates out of range on both
    # sides, which both paths treat as jnp indexing does
    vs = rng.integers(-1, G + 8, size=(T, R, P), dtype=np.int32)
    perm = rng.integers(0, 4, size=(T, R, P), dtype=np.int32)
    g = rng.integers(-1, 1 << 30, size=(T, G), dtype=np.int32)
    q = [rng.integers(-n - 2, n + 2, size=B, dtype=np.int32)
         for n in (T, R, P)]
    w = rng.integers(0, 2, size=B).astype(bool)
    ref, setup, _ = meter.timed(lambda: jax.block_until_ready(
        two_stage_translate(vs, perm, g, *q, w, force="ref")))
    got, wall, comp = meter.timed(lambda: jax.block_until_ready(
        two_stage_translate(vs, perm, g, *q, w, force=force)))
    bad = [name for name, a, b in zip(("slot", "fault", "stage"), got, ref)
           if not np.array_equal(np.asarray(a), np.asarray(b))]
    if bad:
        raise SmokeFailure(f"kernel: {bad} differ from the jnp reference")
    _report("kernel", setup, wall, comp,
            f"PASS: {B} queries over [{T},{R},{P}]/[{T},{G}] tables "
            f"match the reference")


def phase_four_chips(meter: CompileMeter, goldens: dict, wls=None,
                     copies: int = COPIES, devices=None) -> None:
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) != 4:
        raise SmokeFailure(f"four-chips: needs exactly 4 devices, found "
                           f"{len(devs)}")
    wls = list(programs.WORKLOADS if wls is None else wls)
    sharded, setup, _ = meter.timed(lambda: _matrix_boot(
        wls, copies, engine=hext_engine.ShardedEngine(devices=devs)))
    _, wall, comp = meter.timed(lambda: sharded.run(MAX_TICKS, chunk=CHUNK))
    with x64():
        mem = sharded.harts.unwrap().mem
        held = sorted({s.device.id for s in mem.addressable_shards
                       if s.data.size})
    if held != sorted(d.id for d in devs):
        raise SmokeFailure(f"four-chips: sharded hart state lives on "
                           f"devices {held}, not on all of "
                           f"{sorted(d.id for d in devs)}")
    _report("four-chips sharded", setup, wall, comp,
            f"hart state on devices {held}")
    # the reference: the same fleet on the jit engine on the default
    # device, jax.devices()[0]
    single, setup1, _ = meter.timed(
        lambda: _matrix_boot(wls, copies, engine="jit"))
    _, wall1, comp1 = meter.timed(lambda: single.run(MAX_TICKS, chunk=CHUNK))
    a = hext_engine.state_arrays(sharded.harts.unwrap())
    b = hext_engine.state_arrays(single.harts.unwrap())
    differ = [k for k in a if not np.array_equal(a[k], b[k])]
    if differ:
        raise SmokeFailure(f"four-chips: sharded and single-chip jit states "
                           f"differ in {differ}")
    _fail_on(_matrix_mismatches(sharded, wls, goldens), "four-chips")
    _report("four-chips jit on device 0", setup1, wall1, comp1,
            f"PASS: {len(sharded)} harts, sharded == jit bit for bit "
            f"({len(a)} fields), both match the goldens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip ShardedEngine phase")
    args = ap.parse_args(argv)
    cache = hext_engine.use_compile_cache()
    info = device_info()
    print(f"device: {info['kind']} x{info['count']} ({info['platform']}), "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    # a declined donation would copy all hart state on every run
    warnings.filterwarnings("error", message=".*[Dd]onat.*")
    meter = CompileMeter()
    goldens = load_goldens()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(meter, goldens)
    else:
        phase_matrix(meter, goldens)
        phase_consolidation(meter, goldens)
        phase_width(meter, goldens)
        phase_torture(meter)
        phase_serve(meter)
        phase_kernel(meter)
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s; "
          f"compile {meter.seconds:.3f} s; persistent compile cache "
          f"{meter.hits} hits, {meter.misses} misses", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
