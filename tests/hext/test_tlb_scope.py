"""Scoped fence semantics (DESIGN.md §5): `sfence.vma` / `hfence.vvma`
with rs1 ≠ x0 must drop only the entries covering that VA page, in both
the machine's software TLB and the oracle's mirror of it.  rs1 = x0
stays the conservative full-class flush; superpage entries match (and
are dropped) by their level mask.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hext import oracle
from repro.core.hext import tlb as TLB
from repro.core.hext.bits import x64


def _count_valid(t):
    return int(np.sum(np.asarray(t["valid"])))


def _mk_machine_tlb():
    t = TLB.init_tlb()
    # two native 4K pages, one guest 4K page, one native 2M superpage
    t = TLB.insert(t, 0x3000, 0x3000, 0, 7, False, 1, False, False)
    t = TLB.insert(t, 0x4000, 0x4000, 0, 7, False, 1, False, False)
    t = TLB.insert(t, 0x3000, 0x8000, 0, 7, True, 1, False, False)
    t = TLB.insert(t, 0x200000, 0x400000, 1, 7, False, 1, False, False)
    return t


def test_machine_flush_va_scoped_native():
    with x64():
        t = _mk_machine_tlb()
        out = TLB.flush(t, native_only=True, va=0x3000)
        # only the native 0x3000 entry drops: guest 0x3000 and native
        # 0x4000 and the superpage all survive
        assert _count_valid(out) == 3
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [False, True, True, True]


def test_machine_flush_va_matches_superpage_by_level():
    with x64():
        t = _mk_machine_tlb()
        # any VA inside the 2M superpage selects it via the level mask
        out = TLB.flush(t, native_only=True, va=0x200000 + 0x5A000)
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [True, True, True, False]


def test_machine_flush_full_class_without_va():
    with x64():
        t = _mk_machine_tlb()
        out = TLB.flush(t, native_only=True)
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [False, False, True, False]
        out = TLB.flush(t, guest_only=True)
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [True, True, False, True]


def test_machine_flush_where_addr_conditions():
    with x64():
        t = _mk_machine_tlb()
        zb = jnp.asarray(False)
        tb = jnp.asarray(True)
        # scoped guest-class flush of VA 0x3000: only the guest entry
        out = TLB.flush_where(t, zb, zb, cond_guest_addr=tb,
                              cond_native_addr=zb, va=jnp.asarray(0x3000))
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [True, True, False, True]
        # scoped native-class flush of the same VA: only the native one
        out = TLB.flush_where(t, zb, zb, cond_guest_addr=zb,
                              cond_native_addr=tb, va=jnp.asarray(0x3000))
        v = np.asarray(out["valid"])[:4]
        assert list(v) == [False, True, True, True]
        # full-class conditions ignore the VA
        out = TLB.flush_where(t, tb, tb)
        assert _count_valid(out) == 0


def _mk_oracle_tlb():
    t = oracle.init_tlb()
    for i, (vpn, guest, level) in enumerate(
            ((0x3, False, 0), (0x4, False, 0), (0x3, True, 0),
             (0x200, False, 1))):
        t["vpn"][i] = vpn
        t["ppn"][i] = vpn + 0x10
        t["level"][i] = level
        t["perm"][i] = 7
        t["guest"][i] = guest
        t["priv"][i] = 1
        t["valid"][i] = True
    t["ptr"] = 4
    return t


def test_oracle_flush_mirrors_machine_scoping():
    t = _mk_oracle_tlb()
    oracle.tlb_flush(t, native=True, va=0x3000)
    assert t["valid"][:4] == [False, True, True, True]
    t = _mk_oracle_tlb()
    oracle.tlb_flush(t, guest=True, va=0x3000)
    assert t["valid"][:4] == [True, True, False, True]
    t = _mk_oracle_tlb()
    # superpage match by level mask (VA inside the 2M region)
    oracle.tlb_flush(t, native=True, va=0x200000 + 0x1F000)
    assert t["valid"][:4] == [True, True, True, False]
    t = _mk_oracle_tlb()
    oracle.tlb_flush(t, guest=True, native=True)
    assert t["valid"][:4] == [False, False, False, False]


def test_oracle_lookup_respects_context_tags():
    t = _mk_oracle_tlb()
    hit, pa, ok = oracle.tlb_lookup(t, 0x3008, False, oracle.ACC_R, 1,
                                    False, False)
    assert hit and ok and pa == 0x13008
    # virt mismatch → miss the native entry, hit the guest one
    hit, pa, ok = oracle.tlb_lookup(t, 0x3008, True, oracle.ACC_R, 1,
                                    False, False)
    assert hit and pa == 0x13008
    # priv mismatch → miss entirely
    hit, _, _ = oracle.tlb_lookup(t, 0x3008, False, oracle.ACC_R, 0,
                                  False, False)
    assert not hit


def _run_pte_swap(fence_va, engine="oracle"):
    """S-mode Sv39 program: warm VA 0x3000 (reads 0xBBBB), rewrite its
    live L0 PTE to alias PA 0x2000 (holds 0xAAAA), sfence.vma scoped to
    `fence_va`, reload, ecall to M which exits with the loaded value.

    The exit code is the architectural observable: a fence that covers
    0x3000 forces a fresh walk (0xAAAA); a fence scoped to a different
    page must leave the warm entry alone (stale 0xBBBB)."""
    from repro.core.hext.programs import (Asm, Image, MEM_WORDS, P_KERN,
                                          S_L0, S_L1, S_L2, SATP_SV39)
    from repro.core.hext.sim import Fleet

    a = Asm(0)
    a.li("t0", 0x100)
    a.csrw(0x305, "t0")                      # mtvec → exit handler
    a.li("t0", SATP_SV39 | (S_L2 >> 12))
    a.csrw(0x180, "t0")                      # satp (inert in M)
    a.li("t0", 1 << 11)                      # MPP = S
    a.csrrs(0, 0x300, "t0")
    a.li("t0", 0x200)
    a.csrw(0x341, "t0")
    a.mret()
    a.pad_to(0x100)
    a.li("t6", 0x10000008)                   # M handler: exit with t3
    a.sd("t3", 0, "t6")
    a.label("spin")
    a.j("spin")
    a.pad_to(0x200)
    a.li("t2", 0x3000)
    a.ld("t3", 0, "t2")                      # warm walk: t3 = 0xBBBB
    a.li("t0", S_L0 + 3 * 8)                 # live L0 PTE for VA 0x3000
    a.li("t1", ((0x2000 >> 12) << 10) | P_KERN)
    a.sd("t1", 0, "t0")                      # now maps to PA 0x2000
    a.li("t5", fence_va)
    a.sfence_vma(rs1="t5")
    a.ld("t3", 0, "t2")                      # stale hit or fresh walk
    a.ecall()

    img = Image(MEM_WORDS)
    img.place_code(0, a.assemble())
    img.link(S_L2, 0, S_L1)
    img.link(S_L1, 0, S_L0)
    for page in range(0, 0xB000, 0x1000):    # code+data+table pages
        img.map_page(S_L0, page, page, P_KERN)
    img.store64(0x2000, 0xAAAA)
    img.store64(0x3000, 0xBBBB)

    if engine == "oracle":
        st = oracle.run(img.mem, 512)
        assert st["done"]
        return int(st["exit_code"])
    fleet = Fleet.from_images([img.mem], mem_words=MEM_WORDS)
    fleet.run(512, chunk=512)
    st = fleet[0]
    assert bool(st.counters.done)
    return int(st.counters.exit_code)


@pytest.mark.parametrize("engine", ["oracle", "machine"])
def test_scoped_fence_preserves_sibling_entries_end_to_end(engine):
    # fence scoped to a *different* page: warm entry survives → stale pa
    assert _run_pte_swap(0x2000, engine) == 0xBBBB
    # fence scoped to the rewritten page: fresh walk sees the new PTE
    assert _run_pte_swap(0x3000, engine) == 0xAAAA


@pytest.mark.parametrize("case", [5, 23])
def test_scoped_fence_machine_matches_oracle(case):
    """The corpus path exercises scoped fences randomly; this pins one
    fuzz case and one sched case through both models as a cheap
    deterministic anchor."""
    from repro.core.hext import torture
    s = torture.gen_scenario(torture.DEFAULT_SEED, case)
    mw = torture._fleet_words(s.image)
    mach = torture._run_corpus_fleet([s], s.max_ticks, torture.CHUNK,
                                     mem_words=mw)
    ost = oracle.run(torture._pad_image(s.image, mw), s.max_ticks)
    assert torture.diff_case(mach, 0, ost) == []
