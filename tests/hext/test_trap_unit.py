"""Direct unit tests for the interrupt/trap plumbing (ISSUE 2 satellites):

* ``trap.pending_interrupt`` priority order and per-level enable gating,
* ``trap.route`` delegation matrix (M → HS → VS),
* ``machine._advance_timers`` CLINT semantics (armed vs disarmed),
* TLB privilege-context tagging (a U-mode access must not reuse an
  S-mode entry's permission verdict),
* reserved PTE encodings (W=1,R=0) page-faulting at both stages,
* HLVX carrying its execute-permission override through the G-stage.

Plus the ISSUE 3 conformance satellites:

* out-of-range physical addresses raising access faults (walk PTE
  fetches and final accesses) instead of wrapping back into RAM,
* ``htimedelta`` shifting the guest's ``time`` view and the vstimecmp
  comparison,
* the counter-enable (TM bit) trap matrix for ``time`` reads,
* the N-guest scheduler memory layout invariants.

These paths were previously exercised only indirectly through workloads.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hext import csr as C
from repro.core.hext import machine
from repro.core.hext import programs
from repro.core.hext import tlb as TLB
from repro.core.hext import translate as X
from repro.core.hext import trap as TR
from repro.core.hext.bits import x64
from tests.hext.conftest import (build_vs_identity, exit_with,
                                 m_handler_capture, prologue, result, run_asm)


def _csrs(**kw):
    """init_csrs with named register overrides (R_* suffix keys)."""
    c = C.init_csrs()
    for name, val in kw.items():
        c = c.at[getattr(C, f"R_{name.upper()}")].set(jnp.uint64(val))
    return c


def _pending(csrs, priv=3, virt=False):
    take, cause = TR.pending_interrupt(
        csrs, jnp.asarray(priv, jnp.int32), jnp.asarray(virt, bool))
    return bool(take), int(cause)


def _route(csrs, priv, virt, cause, is_int):
    tgt = TR.route(csrs, jnp.asarray(priv, jnp.int32),
                   jnp.asarray(virt, bool), jnp.uint64(cause),
                   jnp.asarray(is_int, bool))
    return int(tgt.priv), bool(tgt.virt)


# ---------------------------------------------------------------------------
# pending_interrupt: priority order MEI > MSI > MTI > SEI > SSI > STI > ...
# ---------------------------------------------------------------------------

class TestPendingPriority:
    def test_mei_beats_msi_beats_mti(self):
        with x64():
            allm = C.IP_MEIP | C.IP_MSIP | C.IP_MTIP
            c = _csrs(mip=allm, mie=allm, mstatus=C.MSTATUS_MIE)
            assert _pending(c) == (True, 11)
            c = _csrs(mip=C.IP_MSIP | C.IP_MTIP, mie=allm,
                      mstatus=C.MSTATUS_MIE)
            assert _pending(c) == (True, 3)
            c = _csrs(mip=C.IP_MTIP, mie=allm, mstatus=C.MSTATUS_MIE)
            assert _pending(c) == (True, 7)

    def test_m_interrupts_beat_s_interrupts(self):
        with x64():
            c = _csrs(mip=C.IP_MTIP | C.IP_SEIP,
                      mie=C.IP_MTIP | C.IP_SEIP,
                      mideleg=C.MIDELEG_FORCED | C.IP_SEIP,
                      mstatus=C.MSTATUS_MIE | C.MSTATUS_SIE)
            # both deliverable at priv=S: M-level wins
            assert _pending(c, priv=1) == (True, 7)

    def test_s_priority_sei_ssi_sti(self):
        with x64():
            alls = C.IP_SEIP | C.IP_SSIP | C.IP_STIP
            c = _csrs(mip=alls, mie=alls,
                      mideleg=C.MIDELEG_FORCED | alls,
                      mstatus=C.MSTATUS_SIE)
            assert _pending(c, priv=1) == (True, 9)
            c = _csrs(mip=C.IP_SSIP | C.IP_STIP, mie=alls,
                      mideleg=C.MIDELEG_FORCED | alls,
                      mstatus=C.MSTATUS_SIE)
            assert _pending(c, priv=1) == (True, 1)
            c = _csrs(mip=C.IP_STIP, mie=alls,
                      mideleg=C.MIDELEG_FORCED | alls,
                      mstatus=C.MSTATUS_SIE)
            assert _pending(c, priv=1) == (True, 5)


class TestPendingEnables:
    def test_m_gated_by_mie_at_m_only(self):
        with x64():
            c = _csrs(mip=C.IP_MSIP, mie=C.IP_MSIP)   # mstatus.MIE = 0
            assert _pending(c, priv=3) == (False, 0)
            # from lower privilege, M interrupts always fire
            assert _pending(c, priv=1)[0]
            assert _pending(c, priv=0)[0]

    def test_hs_gated_by_sie_at_hs_only(self):
        with x64():
            c = _csrs(mip=C.IP_SSIP, mie=C.IP_SSIP,
                      mideleg=C.MIDELEG_FORCED | C.IP_SSIP)
            assert _pending(c, priv=1) == (False, 0)  # SIE=0 at HS
            assert _pending(c, priv=0)[0]             # U always interruptible
            c = _csrs(mip=C.IP_SSIP, mie=C.IP_SSIP,
                      mideleg=C.MIDELEG_FORCED | C.IP_SSIP,
                      mstatus=C.MSTATUS_SIE)
            assert _pending(c, priv=1) == (True, 1)

    def test_hs_interrupt_preempts_vs_regardless_of_guest_sie(self):
        """The scheduler relies on this: STI delegated to HS fires while a
        guest runs in VS even with all guest enables clear."""
        with x64():
            c = _csrs(mip=C.IP_STIP, mie=C.IP_STIP,
                      mideleg=C.MIDELEG_FORCED | C.IP_STIP)
            assert _pending(c, priv=1, virt=True) == (True, 5)

    def test_vs_interrupt_gated_by_vsstatus_sie(self):
        with x64():
            base = dict(mip=C.IP_VSSIP, mie=C.IP_VSSIP,
                        hideleg=C.IP_VSSIP)
            c = _csrs(**base)
            assert _pending(c, priv=1, virt=True) == (False, 0)
            c = _csrs(vsstatus=C.MSTATUS_SIE, **base)
            assert _pending(c, priv=1, virt=True) == (True, 2)
            # VU mode: always interruptible for VS-level interrupts
            c = _csrs(**base)
            assert _pending(c, priv=0, virt=True) == (True, 2)

    def test_vs_interrupt_not_deliverable_without_virt(self):
        """hideleg'd VS interrupt targets VS — with V=0 it must not fire as
        a VS-level interrupt."""
        with x64():
            c = _csrs(mip=C.IP_VSSIP, mie=C.IP_VSSIP, hideleg=C.IP_VSSIP,
                      vsstatus=C.MSTATUS_SIE)
            assert _pending(c, priv=1, virt=False) == (False, 0)


# ---------------------------------------------------------------------------
# route: the M → HS → VS delegation matrix
# ---------------------------------------------------------------------------

class TestRouteMatrix:
    def test_exception_default_to_m(self):
        with x64():
            c = _csrs()
            assert _route(c, 1, False, C.EXC_LPAGE_FAULT, False) == (3, False)

    def test_exception_medeleg_to_hs(self):
        with x64():
            c = _csrs(medeleg=1 << C.EXC_LPAGE_FAULT)
            assert _route(c, 1, False, C.EXC_LPAGE_FAULT, False) == (1, False)
            # HS faults never route to VS even with hedeleg set
            c = _csrs(medeleg=1 << C.EXC_LPAGE_FAULT,
                      hedeleg=1 << C.EXC_LPAGE_FAULT)
            assert _route(c, 1, False, C.EXC_LPAGE_FAULT, False) == (1, False)

    def test_exception_hedeleg_to_vs_only_when_virt(self):
        with x64():
            c = _csrs(medeleg=1 << C.EXC_LPAGE_FAULT,
                      hedeleg=1 << C.EXC_LPAGE_FAULT)
            assert _route(c, 1, True, C.EXC_LPAGE_FAULT, False) == (1, True)
            # medeleg'd but not hedeleg'd: guest fault lands at HS
            c = _csrs(medeleg=1 << C.EXC_LPAGE_FAULT)
            assert _route(c, 1, True, C.EXC_LPAGE_FAULT, False) == (1, False)

    def test_traps_from_m_never_delegate(self):
        with x64():
            c = _csrs(medeleg=0xFFFF, mideleg=0xFFFF, hedeleg=0xFFFF)
            assert _route(c, 3, False, C.EXC_LPAGE_FAULT, False) == (3, False)
            assert _route(c, 3, False, 3, True) == (3, False)

    def test_interrupt_mideleg_hideleg_chain(self):
        with x64():
            # VSSI: mideleg VS bits are forced-one; hideleg decides HS vs VS
            c = _csrs(hideleg=C.IP_VSSIP)
            assert _route(c, 1, True, 2, True) == (1, True)    # → VS
            c = _csrs()
            assert _route(c, 1, True, 2, True) == (1, False)   # → HS
            # STI: mideleg clear → M; set → HS (never VS: hideleg WARL-0)
            c = _csrs()
            assert _route(c, 1, True, 5, True) == (3, False)
            c = _csrs(mideleg=C.MIDELEG_FORCED | C.IP_STIP)
            assert _route(c, 1, True, 5, True) == (1, False)


# ---------------------------------------------------------------------------
# the virtual CLINT: armed comparators drive mip, disarmed leave it alone
# ---------------------------------------------------------------------------

class TestAdvanceTimers:
    def test_disarmed_never_touches_mip(self):
        with x64():
            c = _csrs(mip=C.IP_SSIP)              # software-injected bit
            for _ in range(3):
                c = machine._advance_timers(c)
            assert int(c[C.R_MTIME]) == 3
            assert int(c[C.R_MIP]) == C.IP_SSIP   # untouched

    def test_armed_mtimecmp_sets_then_clears_mtip(self):
        with x64():
            c = _csrs(mtimecmp=2)
            c = machine._advance_timers(c)        # mtime=1 < 2
            assert int(c[C.R_MIP]) & C.IP_MTIP == 0
            c = machine._advance_timers(c)        # mtime=2 >= 2
            assert int(c[C.R_MIP]) & C.IP_MTIP
            # re-arming into the future clears the pending bit
            c = c.at[C.R_MTIMECMP].set(jnp.uint64(100))
            c = machine._advance_timers(c)
            assert int(c[C.R_MIP]) & C.IP_MTIP == 0

    def test_stimecmp_and_vstimecmp_drive_their_bits(self):
        with x64():
            c = _csrs(stimecmp=1, vstimecmp=2)
            c = machine._advance_timers(c)
            assert int(c[C.R_MIP]) & C.IP_STIP
            assert int(c[C.R_MIP]) & C.IP_VSTIP == 0
            c = machine._advance_timers(c)
            assert int(c[C.R_MIP]) & C.IP_VSTIP


# ---------------------------------------------------------------------------
# TLB privilege-context tags
# ---------------------------------------------------------------------------

class TestTlbPrivTags:
    def _mk(self, priv, sum_bit=False, mxr=False):
        return (jnp.asarray(priv, jnp.int32), jnp.asarray(sum_bit, bool),
                jnp.asarray(mxr, bool))

    def test_cross_priv_lookup_misses(self):
        with x64():
            t = TLB.init_tlb()
            virt = jnp.asarray(False, bool)
            p1 = self._mk(1)
            t = TLB.insert(t, jnp.uint64(0x5000), jnp.uint64(0x5000),
                           jnp.asarray(0, jnp.int32),
                           jnp.asarray(TLB.PERM_R, jnp.int32), virt, *p1)
            hit, _, ok = TLB.lookup(t, jnp.uint64(0x5000), virt,
                                    jnp.uint64(X.ACC_R), *p1)
            assert bool(hit) and bool(ok)
            # U-mode must not reuse the S-mode verdict
            hit, _, _ = TLB.lookup(t, jnp.uint64(0x5000), virt,
                                   jnp.uint64(X.ACC_R), *self._mk(0))
            assert not bool(hit)

    def test_sum_and_mxr_context_mismatch_misses(self):
        with x64():
            t = TLB.init_tlb()
            virt = jnp.asarray(False, bool)
            ctx = self._mk(1, sum_bit=True)
            t = TLB.insert(t, jnp.uint64(0x6000), jnp.uint64(0x6000),
                           jnp.asarray(0, jnp.int32),
                           jnp.asarray(TLB.PERM_R, jnp.int32), virt, *ctx)
            hit, _, _ = TLB.lookup(t, jnp.uint64(0x6000), virt,
                                   jnp.uint64(X.ACC_R), *self._mk(1))
            assert not bool(hit)                      # SUM flipped off
            hit, _, _ = TLB.lookup(t, jnp.uint64(0x6000), virt,
                                   jnp.uint64(X.ACC_R),
                                   *self._mk(1, sum_bit=True, mxr=True))
            assert not bool(hit)                      # MXR differs


# ---------------------------------------------------------------------------
# reserved PTE encodings + HLVX G-stage override (direct walker tests)
# ---------------------------------------------------------------------------

SV39 = C.ATP_MODE_SV39 << C.ATP_MODE_SHIFT


def _mem_with(entries):
    """Flat uint64 memory with {byte_addr: value} poked in."""
    mem = np.zeros((1 << 12,), dtype=np.uint64)   # 32 KiB
    for addr, val in entries.items():
        mem[addr // 8] = np.uint64(val & ((1 << 64) - 1))
    return jnp.asarray(mem)


def _pte(pa, perms):
    return ((pa >> 12) << 10) | perms


class TestReservedPte:
    def test_w_only_pte_faults_first_stage(self):
        """W=1,R=0 is reserved — previously walked through as a pointer."""
        with x64():
            P = X.PTE_V | X.PTE_W | X.PTE_A | X.PTE_D
            mem = _mem_with({
                0x1000: _pte(0x2000, X.PTE_V),            # L2 → L1
                0x2000: _pte(0x3000, X.PTE_V),            # L1 → L0
                0x3000 + 5 * 8: _pte(0x5000, P),          # reserved leaf
            })
            csrs = _csrs(satp=SV39 | (0x1000 >> 12))
            xr = X.translate(mem, csrs, jnp.asarray(1, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R)
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_LPAGE_FAULT

    def test_w_only_nonleaf_position_faults(self):
        """A reserved encoding in a *non-leaf* slot must fault too, not be
        dereferenced as a next-level pointer."""
        with x64():
            mem = _mem_with({
                0x1000: _pte(0x2000, X.PTE_V | X.PTE_W),  # reserved pointer
                0x2000: _pte(0x3000, X.PTE_V),
                0x3000 + 5 * 8: _pte(0x5000, X.ALL_PERM_PTE),
            })
            csrs = _csrs(satp=SV39 | (0x1000 >> 12))
            xr = X.translate(mem, csrs, jnp.asarray(1, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_X)
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_IPAGE_FAULT

    def test_w_only_pte_faults_g_stage(self):
        with x64():
            P = X.PTE_V | X.PTE_W | X.PTE_U | X.PTE_A | X.PTE_D
            mem = _mem_with({
                0x1000: _pte(0x2000, X.PTE_V),
                0x2000: _pte(0x3000, X.PTE_V),
                0x3000 + 5 * 8: _pte(0x5000, P),
            })
            hgatp = jnp.uint64(SV39 | (0x1000 >> 12))
            xr = X.g_translate(mem, hgatp, jnp.uint64(0x5000),
                               jnp.uint64(X.ACC_R), jnp.asarray(False, bool))
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_LGUEST_PAGE_FAULT


class TestHlvxGStage:
    def _setup(self, g_perms):
        """vsatp BARE, hgatp maps GPA 0x5000 with `g_perms`."""
        mem = _mem_with({
            0x1000: _pte(0x2000, X.PTE_V),
            0x2000: _pte(0x3000, X.PTE_V),
            0x3000 + 5 * 8: _pte(0x5000, g_perms),
            0x5000: 0xCAFE,
        })
        csrs = _csrs(hgatp=SV39 | (0x1000 >> 12))
        return mem, csrs

    def test_hlvx_reads_x_only_g_stage_page(self):
        """HLVX requires execute permission INSTEAD of read — at both
        stages.  An X-only G-stage page must satisfy it."""
        with x64():
            xonly = X.PTE_V | X.PTE_X | X.PTE_U | X.PTE_A | X.PTE_D
            mem, csrs = self._setup(xonly)
            xr = X.translate(mem, csrs, jnp.asarray(3, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R, force_virt=True, hlvx=True)
            assert not bool(xr.fault)
            assert int(xr.pa) == 0x5000
            # while a plain hlv load of the same page still faults …
            xr = X.translate(mem, csrs, jnp.asarray(3, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R, force_virt=True, hlvx=False)
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_LGUEST_PAGE_FAULT

    def test_hlvx_faults_on_r_only_g_stage_page(self):
        with x64():
            ronly = X.PTE_V | X.PTE_R | X.PTE_U | X.PTE_A | X.PTE_D
            mem, csrs = self._setup(ronly)
            xr = X.translate(mem, csrs, jnp.asarray(3, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R, force_virt=True, hlvx=True)
            assert bool(xr.fault)
            # …reported with the original (load) access type
            assert int(xr.cause) == C.EXC_LGUEST_PAGE_FAULT

    def test_hlvx_implicit_walk_fault_reports_load_cause(self):
        """An hlvx whose VS-stage PTE *fetch* guest-faults must report the
        original (load) access type, not the execute override."""
        with x64():
            mem = np.zeros((1 << 13,), dtype=np.uint64)   # 64 KiB

            def poke(addr, val):
                mem[addr // 8] = np.uint64(val & ((1 << 64) - 1))
            # VS-stage tables at GPA 0x1000/0x2000/0x3000 → VA 0x5000
            poke(0x1000, _pte(0x2000, X.PTE_V))
            poke(0x2000, _pte(0x3000, X.PTE_V))
            poke(0x3000 + 5 * 8, _pte(0x5000, X.ALL_PERM_PTE))
            # G-stage (root 0x8000, Sv39x4) maps GPA 0x5000 but NOT the VS
            # page-table pages → the implicit PTE fetch guest-faults
            gp = X.PTE_V | X.PTE_R | X.PTE_W | X.PTE_X | X.PTE_U | \
                X.PTE_A | X.PTE_D
            poke(0x8000, _pte(0xC000, X.PTE_V))
            poke(0xC000, _pte(0xD000, X.PTE_V))
            poke(0xD000 + 5 * 8, _pte(0x5000, gp))
            csrs = _csrs(vsatp=SV39 | (0x1000 >> 12),
                         hgatp=SV39 | (0x8000 >> 12))
            xr = X.translate(jnp.asarray(mem), csrs,
                             jnp.asarray(3, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R, force_virt=True, hlvx=True)
            assert bool(xr.fault) and bool(xr.implicit)
            assert int(xr.cause) == C.EXC_LGUEST_PAGE_FAULT   # not I-GPF


# ---------------------------------------------------------------------------
# out-of-range physical addresses: access faults, not modulo wrap-around
# ---------------------------------------------------------------------------

class TestOobPaAccessFault:
    """A PA beyond physical memory previously aliased back into RAM via
    `% mem.shape[0]`; it must raise the access fault of the original
    access type instead — during walks and on the final access."""

    def test_walk_pte_beyond_memory_faults_per_access_type(self):
        with x64():
            mem = jnp.zeros((1 << 12,), jnp.uint64)       # 32 KiB
            # satp root far beyond memory: the level-2 PTE fetch is OOB
            csrs = _csrs(satp=SV39 | ((1 << 20) >> 12))
            for acc, cause in ((X.ACC_R, C.EXC_LACCESS),
                               (X.ACC_W, C.EXC_SACCESS),
                               (X.ACC_X, C.EXC_IACCESS)):
                xr = X.translate(mem, csrs, jnp.asarray(1, jnp.int32),
                                 jnp.asarray(False, bool),
                                 jnp.uint64(0x5000), acc)
                assert bool(xr.fault)
                assert int(xr.cause) == cause

    def test_walk_inner_pte_beyond_memory_faults(self):
        """An in-range root whose next-level pointer leaves memory must
        fault at that level, not wrap and keep walking."""
        with x64():
            mem = _mem_with({0x1000: _pte(1 << 21, X.PTE_V)})  # L2 → OOB L1
            csrs = _csrs(satp=SV39 | (0x1000 >> 12))
            xr = X.translate(jnp.asarray(mem), csrs,
                             jnp.asarray(1, jnp.int32),
                             jnp.asarray(False, bool), jnp.uint64(0x5000),
                             X.ACC_R)
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_LACCESS

    def test_gstage_walk_pte_beyond_memory_faults(self):
        """G-stage PTE fetches are bounds-checked too — and report the
        access-fault cause, not a guest-page-fault."""
        with x64():
            mem = jnp.zeros((1 << 12,), jnp.uint64)
            hgatp = jnp.uint64(SV39 | ((1 << 20) >> 12))
            xr = X.g_translate(mem, hgatp, jnp.uint64(0x5000),
                               jnp.uint64(X.ACC_R), jnp.asarray(False, bool))
            assert bool(xr.fault)
            assert int(xr.cause) == C.EXC_LACCESS

    def test_final_load_store_beyond_memory_fault_e2e(self):
        """M-mode load/store of a PA past RAM (and not a decoded MMIO
        register) raises the load/store access fault."""
        OOB = programs.MEM_WORDS * 8 + 0x8000

        def build_load(a, img):
            prologue(a)
            a.li("t0", OOB)
            a.ld("a0", 0, "t0")
            a.nop()
            m_handler_capture(a)

        st = run_asm(build_load, ticks=200)
        assert result(st) == C.EXC_LACCESS
        assert csr_of_mtval(st) == OOB

        def build_store(a, img):
            prologue(a)
            a.li("t0", OOB)
            a.sd("t0", 0, "t0")
            a.nop()
            m_handler_capture(a)

        st = run_asm(build_store, ticks=200)
        assert result(st) == C.EXC_SACCESS

    def test_load_from_write_only_mmio_faults_e2e(self):
        """The console/done/ctxsw MMIO registers have no read decode — a
        load from them must access-fault, not wrap into RAM (the CLINT
        mtime/mtimecmp pair stays readable)."""
        from repro.core.hext import isa

        def build(a, img):
            prologue(a)
            a.li("t0", isa.MMIO_CONSOLE)
            a.ld("a0", 0, "t0")
            a.nop()
            m_handler_capture(a)

        st = run_asm(build, ticks=200)
        assert result(st) == C.EXC_LACCESS

        def build_ok(a, img):
            prologue(a)
            a.li("t0", isa.MMIO_MTIME)
            a.ld("a0", 0, "t0")              # readable: raw mtime
            exit_with(a, "a0")
            m_handler_capture(a)

        st = run_asm(build_ok, ticks=200)
        assert st.counters.exc_by_level.tolist() == [0, 0, 0]   # no trap
        assert result(st) > 0                                   # raw mtime

    def test_final_fetch_beyond_memory_faults_e2e(self):
        OOB = programs.MEM_WORDS * 8 + 0x8000

        def build(a, img):
            prologue(a)
            a.li("t0", OOB)
            a.jalr("zero", 0, "t0")
            m_handler_capture(a)

        st = run_asm(build, ticks=200)
        assert result(st) == C.EXC_IACCESS
        assert csr_of_mtval(st) == OOB        # tval = faulting fetch address
        assert int(st.csrs[C.R_MEPC]) == OOB

    def test_translated_load_to_oob_pa_faults_e2e(self):
        """S-mode VA whose leaf PTE points past RAM: translation succeeds,
        the final access faults (previously it wrapped into RAM)."""
        def build(a, img):
            prologue(a)
            build_vs_identity(img)
            # VA 0x5000 → PA 1 MiB (beyond the 256 KiB image)
            img.map_page(programs.S_L0, 0x5000, 1 << 20, programs.P_KERN)
            a.li("t0", 1 << 11)
            a.csrrs(0, 0x300, "t0")           # MPP=S
            a.li("t0", 0x400)
            a.csrw(0x341, "t0")
            a.mret()
            while a.pc < 0x400:
                a.nop()
            a.li("t0", (8 << 60) | (programs.S_L2 >> 12))
            a.csrw(0x180, "t0")               # satp
            a.sfence_vma()
            a.li("t1", 0x5000)
            a.ld("a0", 0, "t1")
            a.nop()
            m_handler_capture(a)

        st = run_asm(build, ticks=400)
        assert result(st) == C.EXC_LACCESS
        assert csr_of_mtval(st) == 0x5000     # tval = faulting VA


def csr_of_mtval(st):
    return int(st.csrs[C.R_MTVAL])


# ---------------------------------------------------------------------------
# htimedelta: the guest time base (CSR 0x605)
# ---------------------------------------------------------------------------

class TestHtimedelta:
    M64 = (1 << 64) - 1

    def _open_counters(self, c):
        return c.at[C.R_MCOUNTEREN].set(jnp.uint64(7)).at[
            C.R_HCOUNTEREN].set(jnp.uint64(7)).at[
            C.R_SCOUNTEREN].set(jnp.uint64(7))

    def _time(self, c, priv, virt):
        with x64():
            v, ok, vinst = C.csr_read(c, jnp.asarray(0xC01, jnp.int32),
                                      jnp.asarray(priv, jnp.int32),
                                      jnp.asarray(virt, bool))
            return int(v), bool(ok), bool(vinst)

    def test_time_shifted_under_v1_only(self):
        with x64():
            c = self._open_counters(_csrs(mtime=1000))
            c = c.at[C.R_HTIMEDELTA].set(jnp.uint64(self.M64 - 99))  # -100
            assert self._time(c, 1, False)[0] == 1000    # HS: raw mtime
            assert self._time(c, 1, True)[0] == 900      # VS: mtime + delta
            assert self._time(c, 0, True)[0] == 900      # VU too

    def test_write_preserved_from_hs_vinst_from_vs(self):
        with x64():
            c = _csrs()
            new, ok, vinst = C.csr_write(
                c, jnp.asarray(0x605, jnp.int32), jnp.uint64(0x1234),
                jnp.asarray(1, jnp.int32), jnp.asarray(False, bool))
            assert bool(ok) and not bool(vinst)
            assert int(new[C.R_HTIMEDELTA]) == 0x1234
            rd, ok, _ = (lambda t: (int(t[0]), bool(t[1]), bool(t[2])))(
                C.csr_read(new, jnp.asarray(0x605, jnp.int32),
                           jnp.asarray(1, jnp.int32),
                           jnp.asarray(False, bool)))
            assert ok and rd == 0x1234
            # VS access to the H-level CSR → virtual instruction
            _, ok, vinst = C.csr_write(
                c, jnp.asarray(0x605, jnp.int32), jnp.uint64(1),
                jnp.asarray(1, jnp.int32), jnp.asarray(True, bool))
            assert not bool(ok) and bool(vinst)

    def test_vstimecmp_compares_guest_time(self):
        """VSTIP must arm on mtime + htimedelta: with delta = -30 and
        vstimecmp = 50, the comparator fires at mtime 80, not 50."""
        with x64():
            c = _csrs(vstimecmp=50, mtime=49)
            c = c.at[C.R_HTIMEDELTA].set(jnp.uint64(self.M64 - 29))  # -30
            c = machine._advance_timers(c)               # mtime 50: vs 20
            assert int(c[C.R_MIP]) & C.IP_VSTIP == 0
            c = c.at[C.R_MTIME].set(jnp.uint64(79))
            c = machine._advance_timers(c)               # mtime 80: vs 50
            assert int(c[C.R_MIP]) & C.IP_VSTIP


# ---------------------------------------------------------------------------
# counter-enable (TM) gating of `time` reads
# ---------------------------------------------------------------------------

class TestTimeCounterEnable:
    def _rd(self, c, priv, virt):
        with x64():
            _, ok, vinst = C.csr_read(c, jnp.asarray(0xC01, jnp.int32),
                                      jnp.asarray(priv, jnp.int32),
                                      jnp.asarray(virt, bool))
            return bool(ok), bool(vinst)

    def _c(self, m=0, h=0, s=0):
        with x64():
            c = C.init_csrs()
            return c.at[C.R_MCOUNTEREN].set(jnp.uint64(m)).at[
                C.R_HCOUNTEREN].set(jnp.uint64(h)).at[
                C.R_SCOUNTEREN].set(jnp.uint64(s))

    TM = C.COUNTEREN_TM

    def test_m_mode_always_reads(self):
        assert self._rd(self._c(), 3, False) == (True, False)

    def test_s_mode_gated_by_mcounteren(self):
        assert self._rd(self._c(), 1, False) == (False, False)   # illegal
        assert self._rd(self._c(m=self.TM), 1, False) == (True, False)

    def test_u_mode_needs_mcounteren_and_scounteren(self):
        assert self._rd(self._c(m=self.TM), 0, False) == (False, False)
        assert self._rd(self._c(m=self.TM, s=self.TM), 0, False) == \
            (True, False)

    def test_vs_matrix(self):
        # mcounteren clear → illegal even under V=1
        assert self._rd(self._c(), 1, True) == (False, False)
        # mcounteren set, hcounteren clear → virtual instruction
        assert self._rd(self._c(m=self.TM), 1, True) == (False, True)
        assert self._rd(self._c(m=self.TM, h=self.TM), 1, True) == \
            (True, False)

    def test_vu_additionally_needs_scounteren(self):
        assert self._rd(self._c(m=self.TM, h=self.TM), 0, True) == \
            (False, True)
        assert self._rd(self._c(m=self.TM, h=self.TM, s=self.TM),
                        0, True) == (True, False)


# ---------------------------------------------------------------------------
# N-guest scheduler layout invariants
# ---------------------------------------------------------------------------

class TestSchedLayout:
    def test_n2_layout_is_the_legacy_layout(self):
        lay = programs.sched_layout(2)
        assert lay.g_l2 == programs.G2_L2
        assert lay.g_l1 == programs.G2_L1
        assert lay.g_l0 == programs.G2_L0
        assert lay.win == programs.PB
        assert lay.guest_res == programs.GUEST_RES
        assert lay.ctx0 == programs.CTX0
        assert lay.mem_words == programs.MEM_WORDS

    @pytest.mark.parametrize("n", range(1, programs.MAX_GUESTS + 1))
    def test_layout_invariants_all_n(self, n):
        lay = programs.sched_layout(n)
        # Sv39x4 roots are 16K-aligned, 16 KiB wide, non-overlapping
        for l2, l1, l0 in zip(lay.g_l2, lay.g_l1, lay.g_l0):
            assert l2 % 0x4000 == 0
            assert l1 == l2 + 0x4000 and l0 == l2 + 0x5000
        # scheduler state fits below the G-stage tables
        assert lay.ctx0 + n * programs.CTX_SIZE <= lay.g_l2[0]
        assert lay.guest_res + 8 * n <= lay.ctx0
        assert lay.ginfo0 + n * programs.GINFO_SIZE <= lay.guest_res
        # windows sit above every table block and tile contiguously
        tab_end = lay.g_l2[-1] + programs.GTAB_STRIDE
        assert lay.win[0] >= tab_end
        for i, w in enumerate(lay.win):
            assert w == lay.win[0] + i * programs.GUEST_WIN
        assert lay.mem_words * 8 == lay.win[-1] + programs.GUEST_WIN

    @pytest.mark.parametrize("n", range(1, programs.MAX_GUESTS + 1))
    def test_region_disjointness_all_n(self, n):
        """Every layout region — scheduler state blocks, per-guest table
        blocks, per-guest windows — must be pairwise disjoint and inside
        the image, for EVERY n (an overlap at an untested n would mean one
        guest silently corrupting a sibling's tables or context)."""
        lay = programs.sched_layout(n)
        regions = [("ginfo", lay.ginfo0, lay.ginfo0 +
                    n * programs.GINFO_SIZE),
                   ("res", lay.guest_res, lay.guest_res + 8 * n)]
        regions += [(f"ctx{i}", lay.ctx0 + i * programs.CTX_SIZE,
                     lay.ctx0 + (i + 1) * programs.CTX_SIZE)
                    for i in range(n)]
        regions += [(f"gtab{i}", l2, l2 + programs.GTAB_STRIDE)
                    for i, l2 in enumerate(lay.g_l2)]
        regions += [(f"win{i}", w, w + programs.GUEST_WIN)
                    for i, w in enumerate(lay.win)]
        for i, (na, sa, ea) in enumerate(regions):
            assert sa < ea <= lay.mem_words * 8, (na, n)
            assert sa % 8 == 0, (na, n)
            for nb, sb, eb in regions[i + 1:]:
                assert ea <= sb or eb <= sa, \
                    f"n={n}: {na} [{sa:#x},{ea:#x}) overlaps " \
                    f"{nb} [{sb:#x},{eb:#x})"
        # context-slot count: exactly n slots fit between ctx0 and the
        # first table block, each holding GPRs + the VS CSR bank + vtime
        assert programs.CTX_VTIME + 8 < programs.CTX_SIZE
        assert lay.ctx0 >= lay.guest_res + 8 * n
        # scheduler code/data regions below the dynamic area are fixed
        assert lay.ginfo0 == programs.GINFO0 >= programs.SCHED_CUR + 0x20

    @pytest.mark.parametrize("n", (0, -1, programs.MAX_GUESTS + 1,
                                   programs.MAX_GUESTS + 100))
    def test_out_of_range_n_rejected(self, n):
        with pytest.raises(ValueError):
            programs.sched_layout(n)

    @pytest.mark.parametrize("n", (0, 9))
    def test_nguest_builders_reject_bad_n(self, n):
        """The image builder and the Fleet facade both surface the
        layout's ValueError instead of building a corrupt image."""
        wls = [programs.SHA()] * n
        with pytest.raises(ValueError):
            programs.build_image_nguest(wls)
        from repro.core.hext.sim import Fleet
        if n > 0:
            with pytest.raises(ValueError):
                Fleet.boot([tuple(wls)], guests_per_hart=n)

    @pytest.mark.parametrize("n", range(1, programs.MAX_GUESTS + 1))
    def test_image_sized_by_layout_all_n(self, n):
        img = programs.build_image_nguest([programs.SHA()] * n)
        assert img.shape[0] == programs.sched_layout(n).mem_words

    def test_scheduler_assembles_for_all_n(self):
        """Boot code must fit below HS2_HANDLER and the handler below
        SCHED_CUR for every supported N (the asserts fire at build time)."""
        for n in range(1, programs.MAX_GUESTS + 1):
            programs._scheduler_hypervisor(500, n=n).assemble()

    def test_max_guests_image_builds(self):
        img = programs.build_image_nguest(
            [programs.SHA()] * programs.MAX_GUESTS)
        assert img.shape[0] == programs.sched_layout(
            programs.MAX_GUESTS).mem_words
