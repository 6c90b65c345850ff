"""Batched hart state machine — gem5's tick loop, vectorized.

The per-tick pipeline is staged (DESIGN.md §7):

  ``fetch``  — TLB probe for every hart; the two-stage walk graph is only
               materialized under a batch-level ``lax.cond`` when some
               *running* hart actually misses (paper Fig 3: the walk is
               the dominant cost, and warm phases never pay it);
  ``decode`` — table-driven expansion to a :class:`decode.MicroOp`;
  ``execute``— uniform opclass contributors (``isa.execute_uop``), with
               the data-side walk and the SYSTEM/CSR contributor each
               behind their own batch-level cond;
  ``retire`` — per-field commit under the batch outcome masks (frozen /
               interrupt / idle / fault / ok); stores and register
               writebacks are single conditional scatters, never
               full-array selects.

All four stages are pure functions of the raw dict state; ``step_batched``
is the fused pipeline over a leading hart axis and ``step`` the
single-hart wrapper (a B=1 batch).  The batch-level conds are the whole
point of the layout: inside ``vmap`` a ``lax.cond`` degenerates to
computing both branches, so the engine runs ``step_batched`` directly —
*never* ``vmap(step)``.

Counters (per hart) mirror the paper's Figures:
  instret              — Fig 5 (executed instructions w/ and w/o VM)
  exc_by_level[3]      — Figs 6/7 (exceptions handled at M / HS / VS)
  int_by_level[3]      — interrupts handled per level
  pagefaults           — page-fault subset of exceptions
  walks                — page-table walks performed (fetch TLB misses)
  ticks                — Fig 4 (simulation time proxy; deterministic)
  timer_irqs           — taken timer interrupts (MTI/STI/VSTI)
  ctx_switches         — guest context switches (hypervisor MMIO pokes)

``step_batched`` also advances the virtual CLINT each tick
(``_advance_timers``): mtime increments, and each *armed* comparator
(mtimecmp, and the Sstc-style stimecmp/vstimecmp CSRs) drives its mip
bit.  Comparators boot disarmed (2^64-1), so workloads that never arm
one see identical behavior.

64-bit integer state requires x64; call sites must run under
``with bits.x64():`` — ``run``/``batched_run`` do this internally around
trace+execute.

NOTE: this module is the raw-dict ISA-core layer.  The public simulation
API is ``repro.core.hext.sim`` (typed ``HartState`` pytree + ``Fleet``
facade, DESIGN.md §3) and the run loops live behind the pluggable
``repro.core.hext.engine`` backends.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.hext import csr as C
from repro.core.hext import decode as D
from repro.core.hext import isa
from repro.core.hext import tlb as TLB
from repro.core.hext import translate as X
from repro.core.hext import trap as TR
from repro.core.hext.bits import u64 as _u
from repro.core.hext.bits import x64

U64 = jnp.uint64


DEFAULT_MEM_WORDS = 1 << 15          # 256 KiB per hart


def _make_state(mem_words: int) -> Dict:
    """Power-on raw-dict state (private: the typed ``sim.HartState.fresh``
    is the public constructor and owns the x64 context)."""
    return {
        "pc": _u(0),
        "regs": jnp.zeros((32,), U64),
        "csrs": C.init_csrs(),
        "priv": jnp.asarray(3, jnp.int32),     # boot in M
        "virt": jnp.zeros((), bool),
        "mem": jnp.zeros((mem_words,), U64),
        "tlb": TLB.init_tlb(),
        "halted": jnp.zeros((), bool),
        "done": jnp.zeros((), bool),
        "exit_code": _u(0),
        "console": jnp.zeros((), jnp.int64),
        # counters
        "instret": jnp.zeros((), jnp.int64),
        "instret_virt": jnp.zeros((), jnp.int64),
        "exc_by_level": jnp.zeros((3,), jnp.int64),   # M, HS, VS
        "int_by_level": jnp.zeros((3,), jnp.int64),
        "pagefaults": jnp.zeros((), jnp.int64),
        "walks": jnp.zeros((), jnp.int64),
        "ticks": jnp.zeros((), jnp.int64),
        "timer_irqs": jnp.zeros((), jnp.int64),
        "ctx_switches": jnp.zeros((), jnp.int64),
    }


def load_image(state: Dict, image, base: int = 0) -> Dict:
    """Write a uint64-word image into memory at byte address `base`."""
    with x64():
        w = base >> 3
        mem = state["mem"].at[w:w + image.shape[0]].set(image.astype(U64))
        return {**state, "mem": mem}


def _advance_timers(csrs):
    """CLINT-style virtual time source: mtime advances once per tick; each
    *armed* comparator (mtimecmp / stimecmp / vstimecmp, Sstc-style) drives
    its mip bit from the comparison.  Disarmed comparators (the boot value,
    2^64-1) leave their mip bit fully software-owned — hvip injection and
    direct mip writes behave exactly as before the timer existed.

    The VS comparator sees the *guest's* time base: vstimecmp compares
    against mtime + htimedelta, so a hypervisor that maintains per-guest
    htimedelta across context switches gives each guest timer interrupts in
    its own virtual time."""
    mtime = csrs[C.R_MTIME] + _u(1)
    csrs = csrs.at[C.R_MTIME].set(mtime)
    mip = csrs[C.R_MIP]
    vs_time = mtime + csrs[C.R_HTIMEDELTA]
    for cmp_idx, bit, now in ((C.R_MTIMECMP, C.IP_MTIP, mtime),
                              (C.R_STIMECMP, C.IP_STIP, mtime),
                              (C.R_VSTIMECMP, C.IP_VSTIP, vs_time)):
        cmpv = csrs[cmp_idx]
        armed = cmpv != _u(C.TIMER_DISARMED)
        fired = mip | _u(bit)
        idle = mip & ~_u(bit)
        mip = jnp.where(armed, jnp.where(now >= cmpv, fired, idle), mip)
    return csrs.at[C.R_MIP].set(mip)


def _sel_tree(cond, a, b):
    """Per-hart tree select: cond is (B,); leaves may carry trailing dims."""
    def sel(x, y):
        c = cond.reshape(cond.shape + (1,) * (x.ndim - cond.ndim))
        return jnp.where(c, x, y)
    return jax.tree.map(sel, a, b)


def _zero_xr(batch: int) -> X.XResult:
    """Neutral XResult for the cond branch that skips the walk.  Safe
    because every consumer of a walk-only field is gated on ``walked`` /
    ``xr.fault`` (both forced false on the TLB fast path)."""
    z64 = jnp.zeros((batch,), U64)
    zb = jnp.zeros((batch,), bool)
    zi = jnp.zeros((batch,), jnp.int32)
    return X.XResult(pa=z64, fault=zb, cause=z64, tval=z64, tval2=z64,
                     gva=zb, implicit=zb, leaf_pte=z64, g_leaf_pte=z64,
                     level=zi)


def _neutral_sys(csrs) -> isa.SysOut:
    """All-gates-closed SysOut — exact for every non-SYSTEM micro-op
    (``exec_sys`` internally gates all its effects on the SYSTEM
    predicates, so the neutral record equals its output there)."""
    batch = csrs.shape[0]
    z64 = jnp.zeros((batch,), U64)
    zb = jnp.zeros((batch,), bool)
    zi = jnp.zeros((batch,), jnp.int32)
    fz = isa.Fault(zb, z64, z64, z64, zb, z64)
    return isa.SysOut(fault=fz, wb=z64, do_wb=zb, csrs=csrs, csrs_set=zb,
                      pc=z64, pc_set=zb, priv=zi, virt=zb, pv_set=zb,
                      halt=zb, flush_guest=zb, flush_native=zb,
                      flush_guest_addr=zb, flush_native_addr=zb,
                      flush_va=z64)


def _gather(arr2d, idx):
    """Per-hart dynamic gather: arr2d (B, N), idx (B,) → (B,)."""
    return jax.vmap(lambda a, i: a[i])(arr2d, idx)


def fetch(state: Dict, csrs1, m_run):
    """Stage 1: translate PC (TLB fast path, cond-gated walk) and gather
    the instruction word.  Returns (instr, fetch_fault, f_fetch, tlb1,
    walked) where tlb1 carries the fetch-side TLB fill."""
    pc0, priv0, virt0 = state["pc"], state["priv"], state["virt"]
    batch = pc0.shape[0]
    sum_f, mxr_f = jax.vmap(X.eff_ctx)(csrs1, virt0)
    tv = jax.vmap(TLB.lookup, in_axes=(0, 0, 0, None, 0, 0, 0))(
        state["tlb"], pc0, virt0, _u(X.ACC_X), priv0, sum_f, mxr_f)
    use_f = tv.hit & tv.perm_ok
    walked = ~use_f
    need = m_run & walked

    def walk():
        return jax.vmap(
            lambda m, c, p, v, va: X.translate(m, c, p, v, va, X.ACC_X))(
            state["mem"], csrs1, priv0, virt0, pc0)

    with jax.named_scope("walk"):
        xrw = jax.lax.cond(jnp.any(need), walk, lambda: _zero_xr(batch))
    pa = jnp.where(use_f, tv.pa, xrw.pa)
    fault_w = ~use_f & xrw.fault
    xr = xrw._replace(pa=pa, fault=fault_w)
    # fetching from a PA beyond memory (MMIO included — nothing up there is
    # executable) is an instruction access fault, not a wrap into RAM
    fetch_oob = ~xr.fault & (pa >= _u(state["mem"].shape[1] * 8))
    fetch_fault = xr.fault | fetch_oob
    # fetch guest-page-fault tinst is always 0
    f_fetch = isa.Fault(
        fetch_fault,
        jnp.where(xr.fault, xr.cause, _u(C.EXC_IACCESS)),
        jnp.where(xr.fault, xr.tval, pc0),
        jnp.where(xr.fault, xr.tval2, _u(0)),
        jnp.where(xr.fault, xr.gva, virt0),
        jnp.zeros((batch,), U64))
    word = _gather(state["mem"],
                   (pa >> _u(3)).astype(jnp.int32) % state["mem"].shape[1])
    instr = jnp.where((pa & _u(4)) != 0, word >> _u(32),
                      word & _u(0xFFFFFFFF))

    def fill_one(tlb, c, p, v, va, x):
        return isa.tlb_fill({"tlb": tlb, "csrs": c, "priv": p, "virt": v},
                            va, x)

    fill = m_run & ~fetch_fault & walked
    tlb1 = _sel_tree(fill,
                     jax.vmap(fill_one)(state["tlb"], csrs1, priv0, virt0,
                                        pc0, xr),
                     state["tlb"])
    return instr, fetch_fault, f_fetch, tlb1, walked


def execute(state: Dict, csrs1, tlb1, instr, m_exec):
    """Stages 2+3: decode to micro-ops, translate the data access (TLB
    fast path, cond-gated walk), run the cond-gated SYSTEM contributor,
    and merge everything through ``isa.execute_uop``.  ``m_exec`` masks
    the harts whose execution will actually commit (running, fetch OK) —
    it gates the batch-level conds only; the per-hart outputs are wrong
    outside the mask and the retire stage discards them."""
    pc0, priv0, virt0 = state["pc"], state["priv"], state["virt"]

    # ---- decode ------------------------------------------------------------
    with jax.named_scope("decode"):
        uop = jax.vmap(D.decode)(instr)
        rv1 = _gather(state["regs"], uop.rs1)
        rv2 = _gather(state["regs"], uop.rs2)

    # ---- data translation (TLB fast path + cond-gated walk) ----------------
    q = jax.vmap(isa.mem_query)(csrs1, priv0, virt0, uop, rv1)
    virt_d = virt0 | q.force_virt
    sum_d, mxr_d = jax.vmap(X.eff_ctx)(csrs1, virt_d)
    tv = jax.vmap(TLB.lookup)(tlb1, q.addr, virt_d, q.macc, priv0,
                              sum_d, mxr_d)
    use_d = tv.hit & tv.perm_ok & ~q.hlvx
    walked_d = ~use_d
    need_d = m_exec & q.mem_op & ~q.misaligned & walked_d

    def walk():
        return jax.vmap(
            lambda m, c, p, v, va, a, fv, hx: X.translate(
                m, c, p, v, va, a, force_virt=fv, hlvx=hx))(
            state["mem"], csrs1, priv0, virt0, q.addr, q.macc,
            q.force_virt, q.hlvx)

    with jax.named_scope("walk"):
        xrw = jax.lax.cond(jnp.any(need_d), walk,
                           lambda: _zero_xr(pc0.shape[0]))
    pa = jnp.where(use_d, tv.pa, xrw.pa)
    fault_w = ~use_d & xrw.fault
    xr = xrw._replace(pa=pa, fault=fault_w)

    # ---- SYSTEM contributor (cond-gated: CSR where-chains are heavy) -------
    sys_need = m_exec & (uop.cls == D.CLS_SYSTEM) & (uop.f3 != _u(4))
    with jax.named_scope("system"):
        sys = jax.lax.cond(
            jnp.any(sys_need),
            lambda: jax.vmap(isa.exec_sys)(csrs1, priv0, virt0, pc0, rv1,
                                           uop),
            lambda: _neutral_sys(csrs1))

    # ---- merge contributors -------------------------------------------------
    st = dict(state)
    st["csrs"] = csrs1
    st["tlb"] = tlb1
    eo = jax.vmap(isa.execute_uop)(st, uop, rv1, rv2, q, xr, walked_d, sys)
    return eo, virt0


def retire(state: Dict, csrs1, tlb1, eo: isa.ExecOut, f_fetch, fetch_fault,
           walked_f, masks):
    """Stage 4: apply outcome-class commit masks per field.  Register
    writeback and the store are single conditional scatters."""
    frozen, take, icause, m_run, m_int = masks
    pc0, priv0, virt0 = state["pc"], state["priv"], state["virt"]
    batch = pc0.shape[0]

    fault = isa.merge_fault(f_fetch, eo.fault)
    m_fault = m_run & fault.fault
    m_ok = m_run & ~fault.fault
    m_trap = m_int | m_fault

    # ---- trap invoke (one cond-gated take_trap for interrupts + faults) ----
    t_cause = jnp.where(take, icause, fault.cause)
    t_tval = jnp.where(take, _u(0), fault.tval)
    t_tval2 = jnp.where(take, _u(0), fault.tval2)
    t_gva = jnp.where(take, False, fault.gva)
    t_tinst = jnp.where(take, _u(0), fault.tinst)

    def trap():
        return jax.vmap(TR.take_trap)(csrs1, priv0, virt0, pc0, t_cause,
                                      take, t_tval, t_tval2, t_gva, t_tinst)

    with jax.named_scope("trap"):
        trap_csrs, trap_pc, trap_priv, trap_virt, handled = jax.lax.cond(
            jnp.any(m_trap), trap,
            lambda: (csrs1, jnp.zeros((batch,), U64),
                     jnp.zeros((batch,), jnp.int32),
                     jnp.zeros((batch,), bool),
                     jnp.zeros((batch,), jnp.int32)))

    out = dict(state)
    out["pc"] = jnp.where(m_trap, trap_pc,
                          jnp.where(m_ok, eo.new_pc, pc0))
    out["csrs"] = jnp.where(frozen[:, None], state["csrs"],
                  jnp.where(m_trap[:, None], trap_csrs,
                  jnp.where(m_ok[:, None], eo.csrs, csrs1)))
    out["priv"] = jnp.where(m_trap, trap_priv,
                            jnp.where(m_ok, eo.priv, priv0))
    out["virt"] = jnp.where(m_trap, trap_virt,
                            jnp.where(m_ok, eo.virt, virt0))
    out["halted"] = jnp.where(m_trap, False,
                              jnp.where(m_ok, eo.halt, state["halted"]))
    # delta retire: one conditional scatter each for regs and memory
    wb_go = m_ok & eo.do_wb & (eo.rd != 0)
    out["regs"] = jax.vmap(
        lambda r, i, c, w: r.at[i].set(jnp.where(c, w, r[i])))(
        state["regs"], eo.rd, wb_go, eo.wb)
    st_go = m_ok & eo.mem_commit
    out["mem"] = jax.vmap(
        lambda m, i, c, w: m.at[i].set(jnp.where(c, w, m[i])))(
        state["mem"], eo.mem_idx, st_go, eo.mem_word)
    out["tlb"] = _sel_tree(m_ok, eo.tlb, tlb1)

    out["console"] = state["console"] + \
        (m_ok & eo.console_inc).astype(jnp.int64)
    out["done"] = state["done"] | (m_ok & eo.done_set)
    out["exit_code"] = jnp.where(m_ok & eo.done_set, eo.exit_code,
                                 state["exit_code"])
    out["ctx_switches"] = state["ctx_switches"] + \
        (m_ok & eo.ctxsw_inc).astype(jnp.int64)

    # ---- counters ----------------------------------------------------------
    out["instret"] = state["instret"] + m_ok.astype(jnp.int64)
    out["instret_virt"] = state["instret_virt"] + \
        (m_ok & virt0).astype(jnp.int64)
    out["walks"] = state["walks"] + (m_run & walked_f).astype(jnp.int64)
    out["ticks"] = state["ticks"] + (~frozen).astype(jnp.int64)
    is_pf = ((fault.cause == _u(C.EXC_IPAGE_FAULT)) |
             (fault.cause == _u(C.EXC_LPAGE_FAULT)) |
             (fault.cause == _u(C.EXC_SPAGE_FAULT)) |
             (fault.cause == _u(C.EXC_IGUEST_PAGE_FAULT)) |
             (fault.cause == _u(C.EXC_LGUEST_PAGE_FAULT)) |
             (fault.cause == _u(C.EXC_SGUEST_PAGE_FAULT)))
    out["pagefaults"] = state["pagefaults"] + \
        (m_fault & is_pf).astype(jnp.int64)
    is_timer = (icause == _u(5)) | (icause == _u(6)) | (icause == _u(7))
    out["timer_irqs"] = state["timer_irqs"] + \
        (m_int & is_timer).astype(jnp.int64)
    bump = jax.vmap(lambda a, i, c: a.at[i].add(c.astype(jnp.int64)))
    out["int_by_level"] = bump(state["int_by_level"], handled, m_int)
    out["exc_by_level"] = bump(state["exc_by_level"], handled, m_fault)
    return out


def step_batched(state: Dict) -> Dict:
    """One architectural tick for a (B, ...) hart batch — the fused
    fetch → decode → execute → retire pipeline.

    Each stage runs under a ``jax.named_scope`` (``timers``, ``fetch``,
    ``decode``, ``execute``, ``retire``; ``walk``, ``system`` and ``trap``
    around the conds nested in them), which names the stage in every HLO
    op's metadata and so in a device trace; the compiled program is the
    same without them."""
    frozen = state["done"]

    with jax.named_scope("timers"):
        # ---- 0. virtual CLINT tick (frozen harts keep their old csrs) ------
        csrs1 = jax.vmap(_advance_timers)(state["csrs"])

        # ---- 1. CheckInterrupts (paper Fig 2) ------------------------------
        take, icause = jax.vmap(TR.pending_interrupt)(csrs1, state["priv"],
                                                      state["virt"])
        # halted harts wake on any pending+locally-enabled interrupt — the
        # spec says WFI resumes on (mip & mie) != 0 regardless of
        # mstatus.MIE/SIE global gating; `take` additionally routes through
        # the trap path when the interrupt is actually deliverable at the
        # current privilege.
        wake = (csrs1[:, C.R_MIP] & csrs1[:, C.R_MIE]) != _u(0)
        idle = state["halted"] & ~take & ~wake
        m_run = ~frozen & ~take & ~idle
        m_int = ~frozen & take

    # ---- 2..4. fetch → decode+execute → retire -----------------------------
    with jax.named_scope("fetch"):
        instr, fetch_fault, f_fetch, tlb1, walked_f = fetch(state, csrs1,
                                                            m_run)
    with jax.named_scope("execute"):        # decode is nested inside
        eo, _ = execute(state, csrs1, tlb1, instr, m_run & ~fetch_fault)
    with jax.named_scope("retire"):
        return retire(state, csrs1, tlb1, eo, f_fetch, fetch_fault,
                      walked_f, (frozen, take, icause, m_run, m_int))


def step(state: Dict) -> Dict:
    """Single-hart tick: a B=1 ride through the batched pipeline.  Fine
    under ``scan``/``jit``; do NOT ``vmap`` this (use ``step_batched``) —
    vmap collapses the batch-level conds into always-both-branches."""
    b = jax.tree.map(lambda x: x[None], state)
    return jax.tree.map(lambda x: x[0], step_batched(b))


def run(state: Dict, n_ticks: int, unroll: int = 1) -> Dict:
    """Scan `n_ticks` steps (compiled once)."""
    with x64():
        def body(s, _):
            return step_batched(s), None
        fn = jax.jit(lambda s: jax.lax.scan(body, s, None, length=n_ticks,
                                            unroll=unroll)[0])
        b = jax.tree.map(lambda x: x[None], state)
        return jax.tree.map(lambda x: x[0], fn(b))


def batched_run(states: Dict, n_ticks: int) -> Dict:
    """Run a hart batch — many VMs simulated in lockstep.  Scans the
    batched pipeline directly (batch-level conds stay real conditionals;
    a vmap-of-scalar-step would compute both branches everywhere)."""
    with x64():
        def body(s, _):
            return step_batched(s), None
        return jax.jit(lambda s: jax.lax.scan(body, s, None,
                                              length=n_ticks)[0])(states)


# The deprecated raw-dict shims (`make_state`, `run_until_done`,
# `batched_run_until_done`) were removed: `sim.HartState.fresh` builds
# power-on state, and runs go through `sim.Fleet` / the pluggable
# `engine` backends (`engine.JitEngine(donate=False)` is the drop-in for
# the old non-donating host loop).
