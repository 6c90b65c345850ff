"""Typed simulation API: `HartState` pytree + `Fleet` facade (DESIGN.md §3).

This module is the single public surface for running hext simulations.  It
replaces the raw-dict plumbing that every consumer used to hand-roll
(`make_state` → manual `jnp.stack` batching → chunked host-loop
`run_until_done` → stringly-typed counter reads) with two first-class
objects:

* ``HartState`` — a frozen, registered-pytree dataclass with typed fields
  for pc/regs/csrs/mem/tlb and a nested ``Counters`` record.  It is a
  drop-in pytree: ``jax.jit``/``jax.vmap``/``jax.lax.scan`` all traverse
  it, and ``to_raw``/``from_raw`` bridge to the legacy dict layout used by
  the branchless ISA core (a purely structural conversion — free under
  ``jit``).

* ``Fleet`` — the simulation facade, in the spirit of riescue's
  ``Hypervisor`` runtime object: ``Fleet.boot(workloads, guest=...)``
  assembles system images and batches them, ``fleet.run(max_ticks)``
  advances every machine in lockstep, ``fleet.counters()`` /
  ``fleet.report()`` read the architectural counters back out.

Execution is delegated to a pluggable :mod:`repro.core.hext.engine`
backend (``Fleet.boot(..., engine="jit"|"sharded"|"oracle")``): the
default ``JitEngine`` runs the donated on-device ``lax.while_loop`` over
chunked scans, ``ShardedEngine`` runs the same loop on each shard of the
batch under ``jax.shard_map`` over a mesh of ``jax.devices()``, and ``OracleEngine`` drives the pure-Python reference model behind the
same typed interface.  On top of the unified state path the fleet offers
gem5-style checkpointing (``Fleet.snapshot`` / ``Fleet.restore``, a
versioned ``.npz`` with a schema-hash guard — see
:mod:`repro.core.hext.checkpoint`) and live guest migration between harts
(``Fleet.migrate_guest``).  The x64 requirement is owned by the facade and
the engines, which enter the one scoped ``bits.x64()`` context at their
entry points instead of sprinkling per-call wrappers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hext import engine as _engine
from repro.core.hext import machine as _machine
from repro.core.hext import telemetry
from repro.core.hext.bits import x64

U64 = jnp.uint64
MASK64 = (1 << 64) - 1

__all__ = ["Counters", "HartState", "Fleet", "HartSpec", "checksum_ok",
           "run_on_device", "StaleHartsError", "MigrationError"]


def checksum_ok(exit_code, golden: int) -> bool:
    """Canonical result check: compare exit code and golden mod 2**64.

    Workload checksums are uint64 values; Python goldens may carry the top
    bit.  Both sides are reduced mod 2**64 so signedness can never skew the
    comparison (previously one call site masked with ``(1 << 63) - 1`` and
    another compared raw ints).
    """
    return (int(exit_code) & MASK64) == (int(golden) & MASK64)


# ---------------------------------------------------------------------------
# Counters — the per-hart measurement record (paper Figures 4-7)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["done", "exit_code", "instret", "instret_virt",
                 "exc_by_level", "int_by_level", "pagefaults", "walks",
                 "ticks", "timer_irqs", "ctx_switches"],
    meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Counters:
    """Architectural counters + run outcome for one hart (or a batch).

    instret / instret_virt — Fig 5 (instructions w/ and w/o VM)
    exc_by_level[3] / int_by_level[3] — Figs 6/7 (M, HS, VS)
    pagefaults, walks — translation activity; ticks — Fig 4 time proxy
    timer_irqs / ctx_switches — preemption activity (DESIGN.md §2c)
    done / exit_code — run outcome (checksum mailbox)
    """

    done: jax.Array
    exit_code: jax.Array
    instret: jax.Array
    instret_virt: jax.Array
    exc_by_level: jax.Array
    int_by_level: jax.Array
    pagefaults: jax.Array
    walks: jax.Array
    ticks: jax.Array
    timer_irqs: jax.Array
    ctx_switches: jax.Array

    @classmethod
    def zero(cls) -> "Counters":
        return cls(
            done=jnp.zeros((), bool),
            exit_code=jnp.zeros((), U64),
            instret=jnp.zeros((), jnp.int64),
            instret_virt=jnp.zeros((), jnp.int64),
            exc_by_level=jnp.zeros((3,), jnp.int64),
            int_by_level=jnp.zeros((3,), jnp.int64),
            pagefaults=jnp.zeros((), jnp.int64),
            walks=jnp.zeros((), jnp.int64),
            ticks=jnp.zeros((), jnp.int64),
            timer_irqs=jnp.zeros((), jnp.int64),
            ctx_switches=jnp.zeros((), jnp.int64),
        )

    def ok(self, golden: int) -> bool:
        """One canonical uint64 comparison for every call site."""
        return checksum_ok(self.exit_code, golden)

    def to_dict(self, golden: Optional[int] = None) -> Dict[str, Any]:
        """Host-side dict (JSON-safe) — the legacy benchmark record shape."""
        with x64():
            out = {
                "done": bool(self.done),
                # masked to uint64 so a report entry can reproduce the
                # exact checksum its `ok` was computed from
                "exit_code": int(self.exit_code) & MASK64,
                "instret": int(self.instret),
                "instret_virt": int(self.instret_virt),
                "ticks": int(self.ticks),
                "exc_by_level": [int(x) for x in self.exc_by_level],
                "int_by_level": [int(x) for x in self.int_by_level],
                "pagefaults": int(self.pagefaults),
                "walks": int(self.walks),
                "timer_irqs": int(self.timer_irqs),
                "ctx_switches": int(self.ctx_switches),
            }
            if golden is not None:
                out["ok"] = self.ok(golden)
            return out


_COUNTER_KEYS = ("done", "exit_code", "instret", "instret_virt",
                 "exc_by_level", "int_by_level", "pagefaults", "walks",
                 "ticks", "timer_irqs", "ctx_switches")


# ---------------------------------------------------------------------------
# HartState — the typed machine state pytree
# ---------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["pc", "regs", "csrs", "priv", "virt", "mem", "tlb",
                 "halted", "console", "counters"],
    meta_fields=[])
@dataclasses.dataclass(frozen=True)
class HartState:
    """Full architectural state of one hart (or a leading-dim batch).

    ``tlb`` is the software-TLB sub-pytree (see ``tlb.init_tlb``);
    ``counters`` is the nested :class:`Counters` record.  The class is a
    registered pytree, so it composes with jit/vmap/scan directly.
    """

    pc: jax.Array
    regs: jax.Array
    csrs: jax.Array
    priv: jax.Array
    virt: jax.Array
    mem: jax.Array
    tlb: Dict[str, jax.Array]
    halted: jax.Array
    console: jax.Array
    counters: Counters

    # -- construction -------------------------------------------------------
    @classmethod
    def fresh(cls, mem_words: int = _machine.DEFAULT_MEM_WORDS) -> "HartState":
        """Power-on state: pc=0, M mode, zeroed memory and counters."""
        with x64():
            return cls.from_raw(_machine._make_state(mem_words))

    @classmethod
    def boot(cls, workload, guest: bool = False) -> "HartState":
        """State with a full bootable system image for `workload` loaded
        (native M→S stack, or M→HS xvisor-lite→VS when ``guest``)."""
        from repro.core.hext import programs
        with telemetry.span("image.build"):
            image = programs.build_image(workload, guest)
            with x64():
                st = cls.fresh(programs.MEM_WORDS)
                return st.with_mem(jnp.asarray(image))

    @classmethod
    def boot_preemptive(cls, *workloads,
                        timeslice: Optional[int] = None) -> "HartState":
        """State with an N-guest preemptive system image loaded: M firmware
        → HS scheduler-hypervisor → N VS guests round-robined on timer
        interrupts every `timeslice` ticks (DESIGN.md §2c).  Memory is
        sized per N (`programs.sched_layout`)."""
        from repro.core.hext import programs
        ts = programs.DEFAULT_TIMESLICE if timeslice is None else \
            int(timeslice)
        with telemetry.span("image.build"):
            image = programs.build_image_nguest(workloads, timeslice=ts)
            with x64():
                st = cls.fresh(int(image.shape[0]))
                return st.with_mem(jnp.asarray(image))

    # -- raw-dict bridge (legacy ISA-core layout) ---------------------------
    @classmethod
    def from_raw(cls, raw) -> "HartState":
        """Wrap the flat dict layout the branchless ISA core computes on.

        A `HartState` passes through unchanged, so compat shims accept
        either representation."""
        if isinstance(raw, cls):
            return raw
        return cls(
            pc=raw["pc"], regs=raw["regs"], csrs=raw["csrs"],
            priv=raw["priv"], virt=raw["virt"], mem=raw["mem"],
            tlb=raw["tlb"], halted=raw["halted"], console=raw["console"],
            counters=Counters(**{k: raw[k] for k in _COUNTER_KEYS}),
        )

    def to_raw(self) -> Dict[str, Any]:
        """Flat dict layout (inverse of :meth:`from_raw`; structural only)."""
        raw = {
            "pc": self.pc, "regs": self.regs, "csrs": self.csrs,
            "priv": self.priv, "virt": self.virt, "mem": self.mem,
            "tlb": self.tlb, "halted": self.halted, "console": self.console,
        }
        raw.update({k: getattr(self.counters, k) for k in _COUNTER_KEYS})
        return raw

    # -- functional updates -------------------------------------------------
    def replace(self, **kw) -> "HartState":
        return dataclasses.replace(self, **kw)

    def with_mem(self, mem) -> "HartState":
        with x64():
            return self.replace(mem=jnp.asarray(mem, U64))

    def or_image(self, image, base: int = 0) -> "HartState":
        """OR a uint64-word image into memory at byte address `base`.

        Note: unlike ``machine.load_image`` (which overwrites), this merges
        — the semantics test harnesses want when layering fragments onto a
        fresh (zeroed) machine.  Use :meth:`with_mem` to replace memory."""
        with x64():
            w = base >> 3
            img = jnp.asarray(image, U64)
            mem = self.mem.at[w:w + img.shape[0]].set(
                self.mem[w:w + img.shape[0]] | img)
            return self.replace(mem=mem)

    # -- stepping -----------------------------------------------------------
    def step(self) -> "HartState":
        """One tick (CheckInterrupts → fetch → execute → trap), typed."""
        return HartState.from_raw(_machine.step(self.to_raw()))


# ---------------------------------------------------------------------------
# run_on_device — thin compat wrapper over the default JitEngine backend
# ---------------------------------------------------------------------------

def run_on_device(state: HartState, max_ticks: int, chunk: int = 4096,
                  donate: bool = True) -> HartState:
    """Run until every hart is done or `max_ticks` elapse — one jitted call.

    Compat wrapper over ``engine.JitEngine`` (the while-loop over chunked
    scans now lives in :mod:`repro.core.hext.engine`).  The tick budget
    rounds up to whole chunks: `ceil(max_ticks / chunk)` scans.  With
    ``donate`` (the default) the `state` buffers are donated and updated
    in place, so `state` must not be reused after this call; pass
    ``donate=False`` when the caller keeps a reference to the input.
    """
    return _engine.JitEngine(donate=donate).run(state, max_ticks, chunk)


# ---------------------------------------------------------------------------
# Fleet — the simulation facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HartSpec:
    """What one fleet slot is running (for labels and golden checks).

    A preemptive slot carries the full guest tuple in ``guests`` (N ≥ 1;
    ``workload`` aliases guest 0) and the scheduler timeslice."""
    workload: Optional[Any]
    guest: bool
    name: str
    guests: Optional[tuple] = None
    timeslice: int = 0

    @property
    def preemptive(self) -> bool:
        return self.guests is not None

    @property
    def label(self) -> str:
        if self.preemptive:
            return f"{self.name}/{len(self.guests)}guest-preempt"
        return f"{self.name}/{'guest' if self.guest else 'native'}"


class StaleHartsError(RuntimeError):
    """A ``fleet.harts`` reference was used after a later ``fleet.run``
    (or ``migrate_guest``) invalidated it (donated buffers)."""


class MigrationError(RuntimeError):
    """A ``Fleet.migrate_guest`` precondition does not hold (wrong slot
    kind, guest currently scheduled, hart already exited, …)."""


class _HartsView:
    """Generation-checked view of the fleet's batched ``HartState``.

    ``fleet.run`` donates the fleet buffers, so a reference taken before
    a run points at invalidated memory on backends that honor donation —
    and at silently *stale* memory on those that don't (CPU).  The view
    forwards attribute access to the live state while its generation
    matches, and raises :class:`StaleHartsError` afterwards."""

    __slots__ = ("_fleet", "_gen")

    def __init__(self, fleet: "Fleet", gen: int):
        object.__setattr__(self, "_fleet", fleet)
        object.__setattr__(self, "_gen", gen)

    def _live(self) -> HartState:
        if self._fleet._generation != self._gen:
            raise StaleHartsError(
                f"this fleet.harts reference is stale: it was taken at "
                f"run-generation {self._gen} but the fleet is now at "
                f"generation {self._fleet._generation} (fleet.run donates "
                f"its buffers) — re-read fleet.harts after each run")
        return self._fleet._harts

    def unwrap(self) -> HartState:
        """The underlying ``HartState`` pytree (generation-checked)."""
        return self._live()

    def __getattr__(self, name):
        return getattr(self._live(), name)

    def __repr__(self):
        return f"<harts view gen={self._gen} of {self._fleet!r}>"


class Fleet:
    """A batch of harts simulated in lockstep — the 'gem5 pod'.

    >>> fleet = Fleet.boot(programs.WORKLOADS, guest=False)
    >>> fleet.run(120_000)
    >>> fleet.report()["crc32/native"]["ok"]
    True

    The fleet owns the x64 context, the batched ``HartState``, and a
    pluggable execution backend (``engine=`` — ``"jit"``, ``"sharded"``,
    ``"oracle"``, or any object with ``run(state, max_ticks, chunk)``);
    consumers never touch raw dicts, ``jnp.stack`` trees, or per-chunk
    host syncs.
    """

    def __init__(self, harts: HartState, specs: Sequence[HartSpec],
                 engine: Any = None):
        self._harts = harts
        self._specs = list(specs)
        self._engine = _engine.resolve(engine)
        self._generation = 0

    # -- construction -------------------------------------------------------
    @classmethod
    def boot(cls, workloads, guest: Union[bool, Sequence[bool]] = False,
             guests_per_hart: int = 1,
             timeslice: Optional[int] = None,
             engine: Any = None) -> "Fleet":
        """Assemble + batch bootable machines, one per workload.

        ``workloads`` is a Workload or a sequence of them; ``guest`` is a
        bool applied fleet-wide or a per-slot sequence (e.g.
        ``Fleet.boot(wls * 2, guest=[False] * 9 + [True] * 9)`` is the
        paper's native-vs-VM matrix).

        ``guests_per_hart=N`` (N ≥ 2, or N=1 with an explicit
        ``timeslice``) boots the preemptive multi-guest images instead:
        each slot runs N guest VMs under the HS scheduler, round-robin
        every ``timeslice`` ticks.  A slot entry may be a single workload
        (all N guests run it) or a length-N tuple of workloads
        (heterogeneous tenants).

        ``engine`` selects the execution backend (DESIGN.md §3): a
        registered name (``"jit"`` default, ``"sharded"``, ``"oracle"``)
        or an :class:`repro.core.hext.engine.Engine` instance.
        """
        wls = list(workloads) if isinstance(workloads, (list, tuple)) \
            else [workloads]
        n = int(guests_per_hart)
        if n < 1:
            raise ValueError(f"guests_per_hart must be >= 1, got {n}")
        if n >= 2 or timeslice is not None:
            if guest is not False:
                raise ValueError(
                    "guest= does not apply with a preemptive boot "
                    "(every slot runs VS guests under the scheduler)")
            from repro.core.hext import programs
            ts = programs.DEFAULT_TIMESLICE if timeslice is None else \
                int(timeslice)
            groups = []
            for i, w in enumerate(wls):
                grp = tuple(w) if isinstance(w, (tuple, list)) else (w,) * n
                if len(grp) != n:
                    raise ValueError(
                        f"slot {i}: expected a workload or a length-{n} "
                        f"tuple, got {len(grp)} entries")
                groups.append(grp)
            # a None guest entry is a reserved slot: it boots parked
            # (ginfo.done=1) and can later be filled via resume_guest
            specs = [HartSpec(g[0], True,
                              "+".join(w.name if w is not None else "~"
                                       for w in g),
                              guests=g, timeslice=ts) for g in groups]
            states = [HartState.boot_preemptive(*g, timeslice=ts)
                      for g in groups]
            return cls(cls._stack(states), specs, engine=engine)
        guests = list(guest) if isinstance(guest, (list, tuple)) \
            else [bool(guest)] * len(wls)
        if len(guests) != len(wls):
            raise ValueError(
                f"guest has {len(guests)} entries for {len(wls)} workloads")
        specs = [HartSpec(w, g, w.name) for w, g in zip(wls, guests)]
        states = [HartState.boot(w, guest=g) for w, g in zip(wls, guests)]
        return cls(cls._stack(states), specs, engine=engine)

    @classmethod
    def from_states(cls, states: Sequence[HartState],
                    specs: Optional[Sequence[HartSpec]] = None,
                    engine: Any = None) -> "Fleet":
        """Fleet over pre-built states (e.g. hand-assembled test images)."""
        states = list(states)
        if specs is None:
            specs = [HartSpec(None, False, f"hart{i}")
                     for i in range(len(states))]
        return cls(cls._stack(states), specs, engine=engine)

    @classmethod
    def from_images(cls, images: Sequence[Any],
                    mem_words: int = _machine.DEFAULT_MEM_WORDS,
                    names: Optional[Sequence[str]] = None,
                    engine: Any = None) -> "Fleet":
        """Fleet of fresh harts, each booted from a raw uint64-word image
        (shorter images are zero-padded; an oversized one is an error)."""
        with x64():
            imgs = [jnp.asarray(im, U64) for im in images]
            for i, im in enumerate(imgs):
                if int(im.shape[0]) > mem_words:
                    raise ValueError(
                        f"image {i} has {int(im.shape[0])} words > "
                        f"mem_words={mem_words}")
            states = [HartState.fresh(mem_words).or_image(im)
                      for im in imgs]
        specs = None if names is None else \
            [HartSpec(None, False, str(n)) for n in names]
        return cls.from_states(states, specs, engine=engine)

    @classmethod
    def from_corpus(cls, images: Sequence[Any],
                    names: Optional[Sequence[str]] = None,
                    mem_words: Optional[int] = None,
                    engine: Any = None) -> "Fleet":
        """Batch a scenario corpus (possibly differently-sized images) as
        ONE fleet: every image is zero-padded to a common word count so the
        whole corpus traces to a single XLA executable — the batched-fuzz
        mode of the torture harness (DESIGN.md §5).  ``mem_words`` defaults
        to the largest image rounded up to a power of two, so corpora of
        similar size reuse the compile cache across runs."""
        if not len(images):
            raise ValueError("from_corpus needs at least one image")
        if mem_words is None:
            m = max(len(im) for im in images)
            mem_words = 1 << max(m - 1, 1).bit_length()
        if names is None:
            names = [f"case{i}" for i in range(len(images))]
        return cls.from_images(images, mem_words, names=names,
                               engine=engine)

    @staticmethod
    def _stack(states: Sequence[HartState]) -> HartState:
        if not states:
            raise ValueError("Fleet needs at least one hart")
        with x64():
            return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    # -- running ------------------------------------------------------------
    def run(self, max_ticks: int, chunk: int = 4096) -> "Fleet":
        """Advance the whole fleet through the selected engine backend.

        Bumps the run generation: every previously handed-out
        ``fleet.harts`` view is invalidated (the default engine donates
        the fleet buffers) and raises :class:`StaleHartsError` on access.
        """
        self._harts = self._engine.run(self._harts, max_ticks, chunk=chunk)
        self._generation += 1
        return self

    # -- gem5-style checkpoint / restore ------------------------------------
    def snapshot(self, path) -> str:
        """Persist the full fleet state as a versioned ``.npz`` checkpoint
        (every ``HartState`` leaf + ``HartSpec`` metadata + a schema-hash
        guard — :mod:`repro.core.hext.checkpoint`).  A restored fleet
        resumes bit-identically to an uninterrupted run."""
        from repro.core.hext import checkpoint
        return checkpoint.save(
            str(path), self._harts, self._specs,
            engine_name=getattr(self._engine, "name", "custom"))

    @classmethod
    def restore(cls, path, specs: Optional[Sequence[HartSpec]] = None,
                engine: Any = None) -> "Fleet":
        """Rebuild a fleet from a :meth:`snapshot` checkpoint.

        Specs are restored by workload *name* via the standard registry;
        pass ``specs=`` explicitly when the snapshot ran custom workload
        objects the registry cannot resolve.  Raises
        :class:`repro.core.hext.checkpoint.CheckpointError` on corrupted
        or schema-incompatible files."""
        from repro.core.hext import checkpoint
        harts, saved_specs = checkpoint.load(str(path),
                                             decode_specs=specs is None)
        if specs is None:
            specs = saved_specs
        specs = list(specs)
        n = int(harts.counters.done.shape[0])
        if len(specs) != n:
            raise ValueError(f"{len(specs)} specs for {n} restored harts")
        return cls(harts, specs, engine=engine)

    # -- live guest migration (the gem5 'switch CPU / move work' demo) ------
    def migrate_guest(self, src: int, dst: int, guest: int = 0) -> "Fleet":
        """Move a descheduled guest VM from hart `src` to hart `dst`.

        Lifts guest slot ``guest``'s entire migratable state out of the
        source hart's memory — saved context (GPRs + sepc + the VS CSR
        bank + the frozen virtual clock), private G-stage table block,
        64 KiB physical window (kernel + workload + VS tables + data),
        result mailbox, and scheduler info block — and injects it at the
        same addresses in the destination hart (`programs.guest_regions`).
        The destination's scheduler picks the guest up at its next switch
        and resumes it mid-flight; the context's frozen virtual time
        rebuilds ``htimedelta`` against the destination's own ``mtime``,
        so the guest's clock survives the move.  On the source the slot is
        marked done with a zeroed mailbox (migrated away), and both specs
        are updated so ``report()`` checks the guest's golden on its new
        hart.

        The destination slot's own tenant is **discarded**: its context,
        window, tables, and mailbox are overwritten and its spec entry is
        replaced by the migrated workload (the evacuation semantics the
        demo wants — migrate into a slot whose tenant has finished, or
        accept losing it).

        Preconditions (else :class:`MigrationError`): both slots are
        preemptive, neither hart has exited, both harts are paused while
        *executing guest code* (V=1 — a hart paused inside the HS
        scheduler may have a context switch in flight, making both
        ``SCHED_CUR`` and the context slots non-authoritative), and the
        guest is live and not currently scheduled on either hart.
        """
        from repro.core.hext import programs
        if src == dst:
            raise MigrationError("src and dst must be different harts")
        for i in (src, dst):
            if not (0 <= i < len(self._specs)):
                raise MigrationError(f"hart {i} out of range")
            if not self._specs[i].preemptive:
                raise MigrationError(
                    f"hart {i} ({self._specs[i].label}) is not a "
                    f"preemptive multi-guest slot")
        s_spec, d_spec = self._specs[src], self._specs[dst]
        n = len(s_spec.guests)
        if not 0 <= guest < n:
            raise MigrationError(f"guest {guest} out of range for N={n}")
        if s_spec.guests[guest] is None:
            raise MigrationError(
                f"hart {src} guest {guest} was already migrated away")
        lay = programs.sched_layout(n)
        with x64():
            mem = np.array(self._harts.mem)       # writable host copy
            done = np.asarray(self._harts.counters.done)
            virt = np.asarray(self._harts.virt)
            for i in (src, dst):
                # paused in M firmware or inside the HS scheduler: a
                # context switch may be in flight (target chosen but
                # SCHED_CUR not yet updated), so neither SCHED_CUR
                # nor the context slots are authoritative
                self._check_guest_op(mem, done, virt, i, guest, "migrate")
            gi_done_w = (lay.ginfo0 + guest * programs.GINFO_SIZE + 24) >> 3
            if int(mem[src, gi_done_w]) != 0:
                raise MigrationError(
                    f"hart {src} guest {guest} already finished — "
                    f"nothing to migrate")
            for base, size in programs.guest_regions(lay, guest):
                w0, w1 = base >> 3, (base + size) >> 3
                mem[dst, w0:w1] = mem[src, w0:w1]
            # source: slot is gone — mark done, zero the mailbox so the
            # hart's combined exit checksum covers only remaining guests
            mem[src, gi_done_w] = 1
            mem[src, (lay.guest_res + 8 * guest) >> 3] = 0
            self._harts = self._harts.replace(mem=jnp.asarray(mem, U64))
        self._generation += 1          # invalidate handed-out views

        moved = s_spec.guests[guest]
        s_guests = tuple(None if k == guest else w
                         for k, w in enumerate(s_spec.guests))
        d_guests = tuple(moved if k == guest else w
                         for k, w in enumerate(d_spec.guests))
        self._respec_slot(src, s_guests)
        self._respec_slot(dst, d_guests)
        return self

    def _respec_slot(self, i: int, new_guests: tuple,
                     hole: str = "moved") -> None:
        """Rewrite slot i's spec after a guest-level mutation; ``hole``
        names empty (None) guest entries in the label."""
        spec = self._specs[i]
        name = "+".join(w.name if w is not None else hole
                        for w in new_guests)
        self._specs[i] = dataclasses.replace(
            spec, guests=new_guests, workload=new_guests[0], name=name)

    def _check_guest_op(self, mem, done, virt, hart: int, guest: int,
                        verb: str) -> None:
        """Shared park/resume precondition: the hart is paused while
        executing guest code and slot `guest` is not currently scheduled
        (same reasoning as :meth:`migrate_guest`)."""
        from repro.core.hext import programs
        if done[hart]:
            raise MigrationError(f"hart {hart} has already exited")
        if not bool(virt[hart]):
            raise MigrationError(
                f"hart {hart} is not executing guest code (V=0 — "
                f"possibly mid context-switch); run a little longer "
                f"and retry")
        if int(mem[hart, programs.SCHED_CUR >> 3]) == guest:
            raise MigrationError(
                f"guest {guest} is currently scheduled on hart {hart}; "
                f"{verb} only descheduled guests (run a little longer "
                f"and retry)")

    # -- guest park / resume (the control plane's evict + re-admit) ---------
    def park_guest(self, hart: int, guest: int, path) -> str:
        """Evict a descheduled guest VM to a per-guest checkpoint file.

        Lifts the same migratable region set :meth:`migrate_guest` moves —
        saved context, G-stage table block, 64 KiB window, result mailbox,
        and scheduler info block — out of the hart's memory into a
        versioned ``.npz`` (:func:`repro.core.hext.checkpoint.save_guest`):
        a migration whose destination is a file.  The slot is then marked
        done with a zeroed mailbox (parked away) and its spec entry
        cleared, exactly like a migration source.  :meth:`resume_guest`
        later splices the file into slot ``guest`` of any same-layout hart
        (the region addresses are slot-determined, so a parked guest must
        resume into the same slot index).

        Preconditions mirror :meth:`migrate_guest` (else
        :class:`MigrationError`): preemptive slot, hart not exited, hart
        paused while executing guest code (V=1), guest live and not
        currently scheduled.
        """
        from repro.core.hext import checkpoint, programs
        if not (0 <= hart < len(self._specs)):
            raise MigrationError(f"hart {hart} out of range")
        spec = self._specs[hart]
        if not spec.preemptive:
            raise MigrationError(
                f"hart {hart} ({spec.label}) is not a preemptive "
                f"multi-guest slot")
        n = len(spec.guests)
        if not 0 <= guest < n:
            raise MigrationError(f"guest {guest} out of range for N={n}")
        if spec.guests[guest] is None:
            raise MigrationError(f"hart {hart} guest {guest} is an "
                                 f"empty slot — nothing to park")
        lay = programs.sched_layout(n)
        with x64():
            mem = np.array(self._harts.mem)       # writable host copy
            done = np.asarray(self._harts.counters.done)
            virt = np.asarray(self._harts.virt)
            self._check_guest_op(mem, done, virt, hart, guest, "park")
            gi_done_w = (lay.ginfo0 + guest * programs.GINFO_SIZE + 24) >> 3
            if int(mem[hart, gi_done_w]) != 0:
                raise MigrationError(
                    f"hart {hart} guest {guest} already finished — "
                    f"nothing to park")
            # the saved ginfo block carries done=0, so the region splice
            # alone revives the guest on resume
            regions = {
                name: mem[hart, base >> 3:(base + size) >> 3].copy()
                for name, (base, size) in zip(
                    checkpoint.GUEST_REGIONS,
                    programs.guest_regions(lay, guest))}
            out = checkpoint.save_guest(
                str(path), regions, n=n, slot=guest,
                timeslice=spec.timeslice,
                workload=getattr(spec.guests[guest], "name", None))
            mem[hart, gi_done_w] = 1
            mem[hart, (lay.guest_res + 8 * guest) >> 3] = 0
            self._harts = self._harts.replace(mem=jnp.asarray(mem, U64))
        self._generation += 1
        self._respec_slot(hart, tuple(
            None if k == guest else w
            for k, w in enumerate(spec.guests)), hole="parked")
        return out

    def resume_guest(self, hart: int, path,
                     workload: Optional[Any] = None) -> "Fleet":
        """Splice a parked guest checkpoint into its slot on hart `hart`.

        The checkpoint's region set is written at the slot-determined
        addresses (slot index comes from the file); the restored info
        block carries ``done=0``, so the destination scheduler picks the
        guest up at its next timer tick and resumes it mid-flight — the
        context's frozen virtual time rebuilds ``htimedelta`` against the
        destination's own clock, like :meth:`migrate_guest`.

        The destination slot must not be live: either a ``None`` entry
        (boot-time reservation, or a tenant that migrated/parked away) or
        a finished tenant — in the latter case the tenant's recorded
        mailbox result is overwritten, so harvest it first.  ``workload``
        sets the spec entry for golden checks; by default the stored
        workload name is resolved via the standard registry.

        Preconditions (else :class:`MigrationError`): preemptive slot
        with the checkpoint's layout (same N), hart not exited, hart
        paused while executing guest code (V=1), destination slot not
        live.
        """
        from repro.core.hext import checkpoint, programs
        regions, meta = checkpoint.load_guest(str(path))
        if not (0 <= hart < len(self._specs)):
            raise MigrationError(f"hart {hart} out of range")
        spec = self._specs[hart]
        if not spec.preemptive:
            raise MigrationError(
                f"hart {hart} ({spec.label}) is not a preemptive "
                f"multi-guest slot")
        n = len(spec.guests)
        if n != int(meta["n"]):
            raise MigrationError(
                f"guest checkpoint has an N={meta['n']} layout but hart "
                f"{hart} runs N={n}")
        guest = int(meta["slot"])
        if workload is None and meta.get("workload"):
            workload = checkpoint.workload_registry().get(meta["workload"])
        if workload is None:
            raise MigrationError(
                f"cannot resolve workload {meta.get('workload')!r} from "
                f"the guest checkpoint — pass workload= explicitly")
        lay = programs.sched_layout(n)
        with x64():
            mem = np.array(self._harts.mem)       # writable host copy
            done = np.asarray(self._harts.counters.done)
            virt = np.asarray(self._harts.virt)
            self._check_guest_op(mem, done, virt, hart, guest, "resume")
            gi_done_w = (lay.ginfo0 + guest * programs.GINFO_SIZE + 24) >> 3
            if spec.guests[guest] is not None and \
                    int(mem[hart, gi_done_w]) == 0:
                raise MigrationError(
                    f"hart {hart} guest slot {guest} is still live — "
                    f"park or migrate it first")
            for name, (base, size) in zip(checkpoint.GUEST_REGIONS,
                                          programs.guest_regions(lay,
                                                                 guest)):
                mem[hart, base >> 3:(base + size) >> 3] = regions[name]
            self._harts = self._harts.replace(mem=jnp.asarray(mem, U64))
        self._generation += 1
        self._respec_slot(hart, tuple(
            workload if k == guest else w
            for k, w in enumerate(spec.guests)))
        return self

    def replace_hart(self, i: int, state: HartState,
                     spec: Optional[HartSpec] = None) -> "Fleet":
        """Splice one hart's full state (and optionally its spec) into the
        batch in place — the control plane's provision/recover primitive:
        lanes keep the fleet's compiled shapes (same batch size, same
        mem_words) while tenants come and go.  ``state`` must carry
        scalar (unbatched) leaves matching the fleet's per-hart shapes.
        """
        if not (0 <= i < len(self._specs)):
            raise ValueError(f"hart {i} out of range")
        with telemetry.span("fleet.splice"), x64():
            want = tuple(self._harts.mem.shape[1:])
            got = tuple(jnp.shape(state.mem))
            if got != want:
                raise ValueError(
                    f"hart {i}: state.mem shape {got} != fleet per-hart "
                    f"shape {want} (lanes must keep the compiled shape)")
            self._harts = jax.tree.map(
                lambda b, s: b.at[i].set(jnp.asarray(s, b.dtype)),
                self._harts, state)
        if spec is not None:
            self._specs[i] = spec
        self._generation += 1
        return self

    # -- inspection ---------------------------------------------------------
    @property
    def engine(self) -> Any:
        """The resolved execution backend this fleet runs on."""
        return self._engine

    @property
    def harts(self) -> "_HartsView":
        """Generation-checked view of the batched state (leading dim =
        fleet size).  ``fleet.run`` donates the underlying buffers, so a
        view taken *before* a run raises :class:`StaleHartsError` after
        it instead of silently reading stale (or freed) memory — re-read
        ``fleet.harts`` after each run.  Use ``.unwrap()`` (or
        ``fleet[i]``) when the raw pytree is needed."""
        return _HartsView(self, self._generation)

    @property
    def specs(self) -> List[HartSpec]:
        return list(self._specs)

    @property
    def all_done(self) -> bool:
        with x64():
            return bool(jnp.all(self._harts.counters.done))

    def __len__(self) -> int:
        return len(self._specs)

    def __getitem__(self, i: int) -> HartState:
        """Per-hart view (scalar leaves) of slot `i`."""
        with x64():
            return jax.tree.map(lambda x: x[i], self._harts)

    def counters(self) -> List[Counters]:
        """Per-hart :class:`Counters`, in fleet order."""
        with x64():
            return [jax.tree.map(lambda x: x[i], self._harts.counters)
                    for i in range(len(self))]

    def _preempt_entry(self, i: int, spec: HartSpec,
                       c: Counters) -> Dict[str, Any]:
        """Report entry for an N-guest slot: per-guest checksum mailboxes
        are read straight from the hart's memory (the HS scheduler records
        each guest's result before combining them into the exit code).

        A ``None`` guest entry is a slot whose VM was migrated away
        (:meth:`migrate_guest`): its mailbox was zeroed, it contributes
        nothing to the expected combined checksum, and its ``ok_guests``
        entry reports ``None`` (not checked here — the VM's golden is
        checked on its destination hart)."""
        from repro.core.hext import programs
        n = len(spec.guests)
        lay = programs.sched_layout(n)
        with x64():
            res_w = lay.guest_res // 8
            cks = [int(self._harts.mem[i, res_w + k]) & MASK64
                   for k in range(n)]
        goldens = [None if w is None else int(w.golden()) & MASK64
                   for w in spec.guests]
        oks = [None if g is None else ck == g
               for ck, g in zip(cks, goldens)]
        total = sum(g for g in goldens if g is not None) & MASK64
        entry = c.to_dict()
        entry.update({
            "golden": total,
            "guests": n,
            "checksums": cks,
            "ok_guests": oks,
            "ok": bool(c.done) and all(o for o in oks if o is not None)
            and c.ok(total),
            "timeslice": spec.timeslice,
        })
        if n == 2:       # legacy 2-guest report keys
            entry.update({"checksum_a": cks[0], "checksum_b": cks[1],
                          "ok_a": oks[0], "ok_b": oks[1]})
        return entry

    def report(self) -> Dict[str, Dict[str, Any]]:
        """``{label: counter-dict}`` with golden checks where known.

        Duplicate (workload, guest) slots get a ``#<slot>`` suffix so no
        hart's counters are silently dropped."""
        out: Dict[str, Dict[str, Any]] = {}
        for i, (spec, c) in enumerate(zip(self._specs, self.counters())):
            if spec.preemptive:
                entry = self._preempt_entry(i, spec, c)
            else:
                golden = spec.workload.golden() if spec.workload is not None \
                    else None
                entry = c.to_dict(golden)
                if golden is not None:
                    entry["golden"] = int(golden) & MASK64
            label = spec.label
            if label in out:
                label = f"{label}#{i}"
            out[label] = entry
        return out
