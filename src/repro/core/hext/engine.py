"""Pluggable execution backends for the hext simulator (DESIGN.md §3).

gem5 exposes swappable CPU models behind one plug point; this module is
the same seam for the hext fleet.  An :class:`Engine` advances a (possibly
batched) ``HartState`` by up to ``max_ticks`` ticks and returns the final
state — everything else about *how* (one jitted while-loop, a shard_map
over a mesh of devices, a pure-Python interpreter) is backend-private.
Three backends are registered:

* ``"jit"`` — :class:`JitEngine`, the donated on-device ``lax.while_loop``
  over chunked scans (the engine ``Fleet`` always used; extracted here
  from ``sim.run_on_device``).
* ``"sharded"`` — :class:`ShardedEngine`, one ``jax.jit`` of
  ``jax.shard_map`` over a 1-D ``Mesh`` of ``jax.devices()`` (or the
  devices given), the state donated.  Each device runs the same
  while-loop on its shard and exits it on its own, so per-hart results
  are bit-identical to ``"jit"``.  A batch built on the mesh
  (``ShardedEngine.sharding``) runs where it lies; any other is padded to
  a device multiple and laid out once.  On a single device it falls back
  to :class:`JitEngine`.
* ``"oracle"`` — :class:`OracleEngine`, the pure-Python architectural
  oracle (``repro.core.hext.oracle``) behind the same typed interface.
  This makes differential runs first-class: boot the same workloads twice
  (``engine="jit"`` / ``engine="oracle"``) and :func:`diff_states` the
  results — the torture harness (DESIGN.md §5) is now just a user of this
  path.  The oracle models the software TLB (scoped fences included) and
  the ``walks`` counter bit-exactly, so the diff exclusion list is empty.

Engines are resolved by name through the registry (``resolve``); any
object with a ``run(state, max_ticks, chunk=...)`` method is accepted
directly, so downstream experiments (async streams, multi-host, caching)
plug in without touching ``Fleet``.

All entry points own the x64 context, like the facade they serve.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import warnings
from typing import Any, Callable, Dict, List, Optional, Protocol, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hext import csr as C
from repro.core.hext import machine as _machine
from repro.core.hext import oracle as _oracle
from repro.core.hext import telemetry
from repro.core.hext.bits import x64

U64 = jnp.uint64
MASK64 = (1 << 64) - 1

__all__ = ["Engine", "JitEngine", "ShardedEngine", "OracleEngine",
           "ENGINES", "register_engine", "resolve", "diff_states",
           "diff_arrays", "state_arrays", "DIFF_SCALARS",
           "DIFF_COUNTERS", "use_compile_cache"]

# The single definition of the differential comparison scope, shared by
# `diff_states` and the torture harness's array-based diff so the two
# paths can never silently drift apart.  The oracle models the software
# TLB, so `walks` is compared exactly; the exclusion list is empty.
DIFF_SCALARS = ("pc", "priv", "virt", "halted", "done", "exit_code",
                "console")
DIFF_COUNTERS = ("instret", "instret_virt", "pagefaults", "walks",
                 "ticks", "timer_irqs", "ctx_switches")


def use_compile_cache() -> str:
    """Give JAX's persistent compile cache a fixed home; returns its path.

    Entry points (benchmarks, examples, the torture CLI, ``chip_smoke.py``)
    call this at start-up; importing ``repro`` sets nothing.  A cache placed
    from outside through ``JAX_COMPILATION_CACHE_DIR`` is left where it is.
    Otherwise it goes to ``<checkout>/.jax_cache``, a fixed path, so a later
    run in the same checkout finds what an earlier one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(pathlib.Path(__file__).resolve().parents[4] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _n_chunks(max_ticks: int, chunk: int) -> int:
    """Tick budgets round UP to whole chunk-scans (legacy loop semantics)."""
    return -(-int(max_ticks) // int(chunk))


def _is_batched(state) -> bool:
    return state.counters.done.ndim == 1


# ---------------------------------------------------------------------------
# the shared on-device run loop (used by JitEngine and, per shard, by
# ShardedEngine): while_loop over chunked scans, gated on all(done)
# ---------------------------------------------------------------------------

def _run_loop(state, n_chunks, chunk: int, ips: int = 1):
    """``(state, chunk-scans run)``: `n_chunks` chunk-scans max, early exit
    once every hart reports done (no per-chunk host sync).  Only
    `chunk`/`ips` are static — different tick budgets reuse the same
    executable.

    A batched state runs ``machine.step_batched`` directly: the pipeline's
    batch-level ``lax.cond`` fast paths (walk skip, SYSTEM skip, trap
    skip) survive only as real HLO conditionals — wrapping the scalar step
    in ``vmap`` would lower every cond to compute-both-branches and give
    back the cost the pipeline removed.

    ``ips`` (instrs_per_step) unrolls that many architectural ticks into
    one scan element, shrinking the scan to ``chunk // ips`` elements —
    less per-element scan/dispatch overhead at the price of a bigger
    step graph.  Tick semantics are unchanged (each chunk-scan still
    advances exactly ``chunk`` ticks); results are bit-identical by
    construction because the unrolled body is the same step composed."""
    batched = _is_batched(state)
    if batched:
        def step_fn(s):
            return type(s).from_raw(_machine.step_batched(s.to_raw()))
    else:
        def step_fn(s):
            return s.step()

    def scan_body(s, _):
        for _ in range(ips):
            s = step_fn(s)
        return s, None

    def cond(carry):
        s, i = carry
        return (i < n_chunks) & ~jnp.all(s.counters.done)

    def body(carry):
        s, i = carry
        s = jax.lax.scan(scan_body, s, None, length=chunk // ips)[0]
        return s, i + jnp.ones((), jnp.int32)

    return jax.lax.while_loop(cond, body, (state, jnp.zeros((), jnp.int32)))


def _run_impl(state, n_chunks, chunk: int, ips: int = 1):
    """The run loop's final state (:func:`_run_loop` without its count of
    chunk-scans run)."""
    return _run_loop(state, n_chunks, chunk, ips)[0]


def _check_ips(chunk: int, ips: int) -> int:
    ips = int(ips)
    if ips < 1 or int(chunk) % ips != 0:
        raise ValueError(
            f"instrs_per_step must divide chunk: chunk={chunk} ips={ips}")
    return ips


_run_jit_donating = jax.jit(_run_impl, static_argnums=(2, 3),
                            donate_argnums=(0,))
_run_jit = jax.jit(_run_impl, static_argnums=(2, 3))


# ---------------------------------------------------------------------------
# Engine protocol + registry
# ---------------------------------------------------------------------------

@runtime_checkable
class Engine(Protocol):
    """An execution backend: advance `state` by up to `max_ticks` ticks.

    Must return a state of the same pytree structure; whether the input
    buffers are donated/invalidated is backend-private (``Fleet`` treats
    them as invalidated either way — see the run-generation guard)."""

    name: str

    def run(self, state, max_ticks: int, chunk: int = 4096):
        ...


ENGINES: Dict[str, Callable[[], "Engine"]] = {}


def register_engine(name: str, factory: Callable[[], "Engine"]) -> None:
    """Register a backend under `name` (`Fleet.boot(..., engine=name)`)."""
    ENGINES[name] = factory


def resolve(engine: Any) -> "Engine":
    """None → the default JitEngine; str → registry lookup; any object
    with a ``run`` method is taken as an engine instance."""
    if engine is None:
        return JitEngine()
    if isinstance(engine, str):
        if engine not in ENGINES:
            raise ValueError(
                f"unknown engine {engine!r}; registered: "
                f"{sorted(ENGINES)}")
        return ENGINES[engine]()
    if callable(getattr(engine, "run", None)):
        return engine
    raise TypeError(f"engine must be None, a registered name, or an "
                    f"object with .run(state, max_ticks); got {engine!r}")


# ---------------------------------------------------------------------------
# JitEngine — the donated single-executable while-loop
# ---------------------------------------------------------------------------

class JitEngine:
    """The default backend: one jitted on-device while-loop.

    With ``donate`` (Fleet's mode) the input buffers are donated and
    updated in place, so the input state must not be reused after `run`;
    ``donate=False`` serves callers that keep a reference to the input
    (the `run_on_device` compat wrapper exposes this)."""

    name = "jit"

    def __init__(self, donate: bool = True, instrs_per_step: int = 1):
        self._donate = donate
        self._ips = int(instrs_per_step)

    def run(self, state, max_ticks: int, chunk: int = 4096):
        ips = _check_ips(chunk, self._ips)
        fn = _run_jit_donating if self._donate else _run_jit
        with x64(), warnings.catch_warnings():
            # the CPU backend may decline donation; anywhere else a declined
            # donation copies all hart state every run, so it stays loud
            if jax.default_backend() == "cpu":
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onat.*", category=UserWarning)
            out = fn(state, jnp.asarray(_n_chunks(max_ticks, chunk),
                                        jnp.int32), int(chunk), ips)
            return jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# ShardedEngine — shard_map over a 1-D mesh of harts
# ---------------------------------------------------------------------------

HARTS = "harts"


def _run_sharded_impl(state, n_chunks, mesh: Mesh, chunk: int, ips: int):
    """The run loop on each shard of ``mesh``: each shard exits its own
    while-loop once its harts are done (no collective per chunk), and
    returns its loop counter beside its state."""
    def shard(s, n):
        s, i = _run_loop(s, n, chunk, ips)
        return s, i[None]
    return jax.shard_map(shard, mesh=mesh, in_specs=(P(HARTS), P()),
                         out_specs=(P(HARTS), P(HARTS)),
                         check_vma=False)(state, n_chunks)


_run_sharded = jax.jit(_run_sharded_impl, static_argnums=(2, 3, 4),
                       donate_argnums=(0,))


class ShardedEngine:
    """Data-parallel backend: shard the hart batch over a 1-D ``Mesh`` of
    devices (axis ``"harts"``) and run the same while-loop on each shard
    under ``jax.shard_map``, the state donated as :class:`JitEngine`
    donates it.  Harts are fully independent, so counters are
    bit-identical to :class:`JitEngine`, hart for hart.

    A state that already lies on the mesh with :attr:`sharding` and
    divides evenly goes straight in and comes out on the mesh.  Any other
    is laid out once, inside an ``engine.place`` span: padded up to a
    device multiple by repeating harts with ``done=True`` (frozen by
    ``machine.step``, and invisible to each shard's ``all(done)`` early
    exit) and moved onto the mesh; the padding is sliced off the result.

    ``stats["place_bytes"]`` counts the bytes laid out so;
    ``stats["shard_chunks"]`` the chunk-scans each shard ran, summed over
    runs.  On a single device (or an unbatched state) this falls back to
    :class:`JitEngine`."""

    name = "sharded"

    def __init__(self, devices: Optional[list] = None,
                 instrs_per_step: int = 1):
        devs = list(jax.devices() if devices is None else devices)
        self._ips = int(instrs_per_step)
        self.mesh = Mesh(np.array(devs), (HARTS,))
        self.sharding = NamedSharding(self.mesh, P(HARTS))
        self.stats = {"place_bytes": 0, "shard_chunks": [0] * len(devs)}

    def _place(self, state):
        """``state`` padded to a device multiple and laid out on the mesh,
        or as it is where it lies there already."""
        b = int(state.counters.done.shape[0])
        bp = -(-b // self.mesh.size) * self.mesh.size
        if bp == b and all(
                getattr(x, "sharding", None) is not None and
                x.sharding.is_equivalent_to(self.sharding, x.ndim)
                for x in jax.tree.leaves(state)):
            return state
        with telemetry.span("engine.place"):
            if bp != b:
                idx = np.arange(bp) % b               # repeat to pad
                state = jax.tree.map(lambda x: x[idx], state)
                done = state.counters.done.at[b:].set(True)
                state = state.replace(counters=dataclasses.replace(
                    state.counters, done=done))
            self.stats["place_bytes"] += sum(
                int(x.nbytes) for x in jax.tree.leaves(state))
            return jax.device_put(state, self.sharding)

    def run(self, state, max_ticks: int, chunk: int = 4096):
        ips = _check_ips(chunk, self._ips)
        if not _is_batched(state) or self.mesh.size < 2:
            return JitEngine(instrs_per_step=ips).run(state, max_ticks,
                                                      chunk)
        with x64(), warnings.catch_warnings():
            if jax.default_backend() == "cpu":
                warnings.filterwarnings(
                    "ignore", message=".*[Dd]onat.*", category=UserWarning)
            b = int(state.counters.done.shape[0])
            out, chunks = _run_sharded(
                self._place(state), jnp.asarray(_n_chunks(max_ticks, chunk),
                                                jnp.int32),
                self.mesh, int(chunk), ips)
            if int(out.counters.done.shape[0]) != b:
                out = jax.tree.map(lambda x: x[:b], out)
            self.stats["shard_chunks"] = [
                a + int(c) for a, c in zip(self.stats["shard_chunks"],
                                           np.asarray(chunks))]
            return jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# OracleEngine — the pure-Python reference model as a backend
# ---------------------------------------------------------------------------

def _snapshot_row(row) -> Dict[str, Any]:
    """Host-side plain-python snapshot of one hart (oracle state shape)."""
    c = row.counters
    t = row.tlb
    return {
        "pc": int(row.pc), "priv": int(row.priv),
        "virt": bool(row.virt), "halted": bool(row.halted),
        "regs": np.asarray(row.regs).tolist(),
        "csrs": np.asarray(row.csrs).tolist(),
        "mem": np.asarray(row.mem).tolist(),
        "tlb": {k: (int(v) if np.ndim(v) == 0 else
                    np.asarray(v).tolist()) for k, v in t.items()},
        "console": int(row.console),
        "done": bool(c.done), "exit_code": int(c.exit_code),
        "instret": int(c.instret), "instret_virt": int(c.instret_virt),
        "exc_by_level": np.asarray(c.exc_by_level).tolist(),
        "int_by_level": np.asarray(c.int_by_level).tolist(),
        "pagefaults": int(c.pagefaults), "walks": int(c.walks),
        "ticks": int(c.ticks),
        "timer_irqs": int(c.timer_irqs),
        "ctx_switches": int(c.ctx_switches),
    }


def _adopt_row(ost: Dict, template):
    """Oracle final state → HartState, reusing the template's dtypes.

    The oracle models the TLB and ``walks`` too, so every leaf — the TLB
    sub-pytree included — is adopted from the oracle's final state."""
    def u64a(x):
        return jnp.asarray(np.asarray(x, dtype=np.uint64))

    def i64(x):
        return jnp.asarray(int(x), jnp.int64)

    def i32a(x):
        return jnp.asarray(np.asarray(x, dtype=np.int32))

    def ba(x):
        return jnp.asarray(np.asarray(x, dtype=bool))

    ot = ost["tlb"]
    tlb = {
        "vpn": u64a(ot["vpn"]), "ppn": u64a(ot["ppn"]),
        "level": i32a(ot["level"]), "perm": i32a(ot["perm"]),
        "guest": ba(ot["guest"]), "priv": i32a(ot["priv"]),
        "sum": ba(ot["sum"]), "mxr": ba(ot["mxr"]),
        "valid": ba(ot["valid"]),
        "ptr": jnp.asarray(int(ot["ptr"]), jnp.int32),
    }
    counters = dataclasses.replace(
        template.counters,
        done=jnp.asarray(bool(ost["done"]), bool),
        exit_code=u64a(ost["exit_code"]),
        instret=i64(ost["instret"]),
        instret_virt=i64(ost["instret_virt"]),
        exc_by_level=jnp.asarray(
            np.asarray(ost["exc_by_level"], dtype=np.int64)),
        int_by_level=jnp.asarray(
            np.asarray(ost["int_by_level"], dtype=np.int64)),
        pagefaults=i64(ost["pagefaults"]),
        walks=i64(ost["walks"]),
        ticks=i64(ost["ticks"]),
        timer_irqs=i64(ost["timer_irqs"]),
        ctx_switches=i64(ost["ctx_switches"]),
    )
    return template.replace(
        pc=u64a(ost["pc"]),
        regs=u64a(ost["regs"]),
        csrs=u64a(ost["csrs"]),
        priv=jnp.asarray(int(ost["priv"]), jnp.int32),
        virt=jnp.asarray(bool(ost["virt"]), bool),
        mem=u64a(ost["mem"]),
        tlb=tlb,
        halted=jnp.asarray(bool(ost["halted"]), bool),
        console=i64(ost["console"]),
        counters=counters,
    )


class OracleEngine:
    """The pure-Python architectural oracle behind the Engine interface.

    Each hart is lifted off device, stepped by ``oracle.step`` for the
    same rounded-up tick budget the device engines use (per-hart early
    exit on ``done``), and lowered back with the template's dtypes.  The
    oracle models the software TLB and ``walks`` bit-exactly (DESIGN.md
    §5), so every leaf is diffable.

    After :meth:`run`, ``last_events`` holds one frozenset of
    architectural-event tuples per hart (trap / fence / atp / wfi
    signatures the oracle recorded) — the torture harness hashes these
    into coverage buckets.  Events are observational only and are never
    part of the differential comparison."""

    name = "oracle"

    def __init__(self):
        self.last_events: List[frozenset] = []

    def run(self, state, max_ticks: int, chunk: int = 4096):
        total = _n_chunks(max_ticks, chunk) * int(chunk)
        self.last_events = []
        with x64():
            if not _is_batched(state):
                return self._run_row(state, total)
            rows = [jax.tree.map(lambda x, i=i: x[i], state)
                    for i in range(int(state.counters.done.shape[0]))]
            outs = [self._run_row(r, total) for r in rows]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    def _run_row(self, row, total: int):
        ost = _oracle.resume_state(_snapshot_row(row))
        for _ in range(total):
            if ost["done"]:
                break
            _oracle.step(ost)
        self.last_events.append(frozenset(ost.get("events", ())))
        return _adopt_row(ost, row)


register_engine("jit", JitEngine)
register_engine("sharded", ShardedEngine)
register_engine("oracle", OracleEngine)


# ---------------------------------------------------------------------------
# first-class differential compare (ONE core, shared with the torture
# harness so the two diff paths cannot drift apart)
# ---------------------------------------------------------------------------

def state_arrays(state) -> Dict[str, np.ndarray]:
    """Host-array extraction of a (scalar or batched) ``HartState``,
    shaped for :func:`diff_arrays` — one batched device→host copy per
    field, leading batch dim always present."""
    with x64():
        batched = _is_batched(state)

        def arr(x):
            a = np.asarray(x)
            return a if batched else a[None]

        c = state.counters
        out = {
            "pc": arr(state.pc), "regs": arr(state.regs),
            "csrs": arr(state.csrs), "priv": arr(state.priv),
            "virt": arr(state.virt), "halted": arr(state.halted),
            "mem": arr(state.mem), "console": arr(state.console),
            "done": arr(c.done), "exit_code": arr(c.exit_code),
            "exc_by_level": arr(c.exc_by_level),
            "int_by_level": arr(c.int_by_level),
        }
        for k in DIFF_COUNTERS:
            out[k] = arr(getattr(c, k))
        return out


def diff_arrays(a: Dict[str, np.ndarray], i: int,
                b: Dict[str, np.ndarray], j: int,
                compare_mem: bool = True) -> List[str]:
    """Field-by-field architectural diff of hart `i` of array-dict `a`
    against hart `j` of `b` — the single comparison core under both
    :func:`diff_states` and the torture harness's batched diff."""
    d: List[str] = []

    def chk(name, x, y):
        if int(x) != int(y):
            d.append(f"{name}: a={int(x):#x} b={int(y):#x}")

    for k in DIFF_SCALARS + DIFF_COUNTERS:
        chk(k, a[k][i], b[k][j])
    for r in range(1, 32):
        chk(f"x{r}", a["regs"][i, r], b["regs"][j, r])
    for idx in range(C.N_CSR):
        chk(f"csr[{idx}]", a["csrs"][i, idx], b["csrs"][j, idx])
    for lvl, nm in enumerate(("M", "HS", "VS")):
        chk(f"exc@{nm}", a["exc_by_level"][i, lvl],
            b["exc_by_level"][j, lvl])
        chk(f"int@{nm}", a["int_by_level"][i, lvl],
            b["int_by_level"][j, lvl])
    if compare_mem:
        ma, mb = a["mem"][i], b["mem"][j]
        bad = np.nonzero(ma != mb)[0]
        if bad.size:
            w = int(bad[0])
            d.append(f"mem[{w * 8:#x}]: a={int(ma[w]):#x} "
                     f"b={int(mb[w]):#x} (+{bad.size - 1} more words)")
    return d


def diff_states(a, b, compare_mem: bool = True) -> List[str]:
    """Field-by-field architectural diff of two scalar ``HartState`` s.

    Compares pc / x1..x31 / the full CSR file / priv / virt / halted /
    done / exit_code / console / memory / ALL counters, ``walks``
    included (the oracle models the software TLB, so the exclusion list
    is empty) — exactly the torture harness's comparison scope, now
    usable on any pair of runs (e.g. ``engine="jit"`` vs
    ``engine="oracle"`` of the same fleet)."""
    return diff_arrays(state_arrays(a), 0, state_arrays(b), 0,
                       compare_mem=compare_mem)
