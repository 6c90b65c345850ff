"""The closed loop of ``fleet_batch`` over a mesh of chips: each batch is
gathered from the jobs' boot images straight onto the mesh, one shard of
harts per chip, run to completion by ``ShardedEngine`` through ``Fleet``,
and only its counters are read back.

The window, the warm-up and the comparison with the reference are
``fleet_batch``'s.  So is the traced slice, a batch boundary, but cut by
the number of chips: the profiler keeps every operation of every chip,
and reducing the trace costs the host time for each (a traced run with
``fleet_batch``'s slice over four v5e chips had not ended after five
minutes), so the trace holds about as many operations as one chip's
does in ``fleet_batch``.

The program's own spans (``engine.place``) land in the record beside
the harness's, and the engine's ``stats`` give each shard's chunk-scans;
a program whose ``ShardedEngine`` has neither a ``sharding`` nor
``stats`` gets its batches on one device and leaves the metrics that
read them out.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.hext import engine as hext_engine
from repro.core.hext import programs, telemetry
from repro.core.hext.bits import x64
from repro.core.hext.sim import Fleet

from harness import traffic
from harness.core import TimedEngine
from harness import fleet_batch
from harness.fleet_batch import SCALARS, VECTORS, compare


def _gather(sharding):
    """A jitted gather of hart rows whose result lies on ``sharding``, or
    where JAX places it when that is None."""
    def gather(state, idx):
        return jax.tree.map(lambda x: x[idx], state)
    if sharding is None:
        return jax.jit(gather)
    return jax.jit(gather, out_shardings=sharding)


def _shard_chunks(sharded) -> list | None:
    stats = getattr(sharded, "stats", None)
    return None if stats is None else list(stats["shard_chunks"])


def drive(run) -> dict:
    telemetry.install(run.spans)
    try:
        return _drive(run)
    finally:
        telemetry.install(None)


def _drive(run) -> dict:
    cfg = run.cfg
    by = {w.name: w for w in programs.WORKLOADS}
    jobs = [(name, mode) for mode in cfg["modes"] for name in cfg["workloads"]]
    sharded = hext_engine.ShardedEngine(devices=run.devices)
    engine = TimedEngine(run.wrap(sharded), run.spans)
    chunk, max_ticks = int(cfg["chunk"]), int(cfg["max_ticks"])
    lead_s = fleet_batch.TRACE_LEAD_S / len(run.devices)
    lag_s = fleet_batch.TRACE_LAG_S / len(run.devices)
    sharding = getattr(sharded, "sharding", None)
    with run.spans("boot"):
        base = Fleet.boot([by[name] for name, _ in jobs],
                          guest=[mode == "guest" for _, mode in jobs])
        with x64():
            base_state = base.harts.unwrap()
            if sharding is not None:
                # every chip holds the boot images, so each gathers its
                # own shard of a batch
                base_state = jax.device_put(base_state, NamedSharding(
                    sharding.mesh, PartitionSpec()))
    gather = _gather(sharding)
    specs = base.specs
    orders = traffic.batch_orders(run.mix, run.seed, len(jobs),
                                  int(cfg["harts"]))

    def batch(idx, ticks):
        with run.spans("boot"):
            with x64():
                state = jax.block_until_ready(
                    gather(base_state, jnp.asarray(idx)))
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
    place_bytes = getattr(sharded, "stats", {}).get("place_bytes")
    chunks = [_shard_chunks(sharded)]

    t_open = run.open_window()
    batches = []
    ends = [t_open]
    while True:
        idx = next(orders)
        batches.append((idx, batch(idx, max_ticks)))
        chunks.append(_shard_chunks(sharded))
        now = time.perf_counter()
        ends.append(now)
        if now - t_open >= run.seconds:
            break
        if len(batches) == 1:
            # the boundary after the next batch, timed from this one
            t_run, t_ran, _ = engine.runs[-1]
            host_s = (t_run - ends[-2]) + (now - t_ran)
            run.tracer.slice(t_ran - ends[-2] - lead_s,
                             lead_s + host_s + lag_s)
    run.close_window()

    mism, wrong = compare(batches, jobs, run.reference)
    runs = engine.in_window(t_open, now)
    hart_ticks = sum(int(np.sum(c["ticks"])) for _, c in batches)
    loop_ticks = sum(r[2] for r in runs)
    harts = int(cfg["harts"])
    rec = {
        "shards": len(run.devices),
        "window_s": now - t_open,
        "batches": len(batches),
        "batch_s": [b - a for a, b in zip(ends, ends[1:])],
        "hart_ticks": hart_ticks,
        "engine_s": sum(r[1] - r[0] for r in runs),
        "loop_ticks": loop_ticks,
        "hart_loop_ticks": harts * loop_ticks,
        # the program's own layout span and counters; None where the
        # program has no such span or counter
        "place_s": None,
        "place_bytes": None,
        "shard_chunks": None,
        "attempted": harts * len(batches),
        "failed": mism,
        "checks": {"counter_mismatches": {"value": mism, "limit": 0},
                   "wrong_checksums": {"value": wrong, "limit": 0}},
    }
    if chunks[0] is not None:
        rec["place_s"] = run.spans.total("engine.place", t_open, now)
        rec["place_bytes"] = sharded.stats["place_bytes"] - place_bytes
        rec["shard_chunks"] = [[b - a for a, b in zip(lo, hi)]
                               for lo, hi in zip(chunks, chunks[1:])]
    return rec
