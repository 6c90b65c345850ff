"""Randomized property tests on the CSR file invariants (paper §3.1):
WARL write masks, read-only fields, aliasing coherence, VS swapping.

Seeded ``numpy.random.Generator`` + ``pytest.mark.parametrize`` instead of
hypothesis (absent from the CI container, which used to skip this file
silently).  Case counts are kept small for the push gate; the values are
deterministic, so a failure's ``case`` index is directly reproducible.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hext import csr as C
from repro.core.hext.bits import x64

N_CASES = 16


def _vals(test_tag: str, n: int = N_CASES):
    """Deterministic per-test stream of u64 values (seeded by the test
    name so adding a test never reshuffles another's cases)."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([0xC54] + list(test_tag.encode()))))
    vals = rng.integers(0, 1 << 64, size=n, dtype=np.uint64)
    # always include the classic corner values
    vals[0], vals[1] = 0, (1 << 64) - 1
    return [int(v) for v in vals]


def _csrs():
    with x64():
        return C.init_csrs()


def _rw(csrs, addr, value, priv=3, virt=False):
    with x64():
        new, ok, vinst = C.csr_write(
            csrs, jnp.asarray(addr, jnp.int32),
            jnp.asarray(value, jnp.uint64),
            jnp.asarray(priv, jnp.int32), jnp.asarray(virt, bool))
        return new, bool(ok), bool(vinst)


def _rd(csrs, addr, priv=3, virt=False):
    with x64():
        val, ok, vinst = C.csr_read(
            csrs, jnp.asarray(addr, jnp.int32),
            jnp.asarray(priv, jnp.int32), jnp.asarray(virt, bool))
        return int(val), bool(ok), bool(vinst)


@pytest.mark.parametrize("v", _vals("mideleg"))
def test_mideleg_vs_bits_forced_one(v):
    """Paper: 'new read-only 1-bit fields for VS and guest external
    interrupts' — writes can never clear them."""
    new, ok, _ = _rw(_csrs(), 0x303, v)
    got = int(new[C.R_MIDELEG])
    assert got & C.HS_INTERRUPTS == C.HS_INTERRUPTS
    # and only S-interrupt bits are writable
    assert got & ~(C.HS_INTERRUPTS | C.S_INTERRUPTS) == 0


@pytest.mark.parametrize("v", _vals("hvip"))
def test_hvip_writes_only_vs_bits_and_alias_mip(v):
    new, ok, _ = _rw(_csrs(), 0x645, v)
    mip = int(new[C.R_MIP])
    # only the VS bits can have changed, and hvip reads back == those bits
    assert mip & ~C.VS_INTERRUPTS == 0
    rd, _, _ = _rd(new, 0x645)
    assert rd == mip & C.VS_INTERRUPTS


@pytest.mark.parametrize("v", _vals("hedeleg"))
def test_hedeleg_cannot_delegate_guest_faults(v):
    """hedeleg must never delegate guest-page-faults / ecall-VS to VS."""
    new, _, _ = _rw(_csrs(), 0x602, v)
    got = int(new[C.R_HEDELEG])
    for bit in (C.EXC_IGUEST_PAGE_FAULT, C.EXC_LGUEST_PAGE_FAULT,
                C.EXC_SGUEST_PAGE_FAULT, C.EXC_VIRTUAL_INSTRUCTION,
                C.EXC_ECALL_VS, C.EXC_ECALL_M, C.EXC_ECALL_S):
        assert not (got >> bit) & 1


@pytest.mark.parametrize("v", _vals("vs_swap", 12))
def test_vs_swap_sstatus_redirects(v):
    """With V=1, sstatus writes hit vsstatus; mstatus untouched."""
    base = _csrs()
    m_before = int(base[C.R_MSTATUS])
    new, ok, vinst = _rw(base, 0x100, v, priv=1, virt=True)
    assert not vinst and ok
    assert int(new[C.R_MSTATUS]) == m_before
    assert int(new[C.R_VSSTATUS]) & ~C.SSTATUS_MASK == 0


@pytest.mark.parametrize("v", _vals("vsip", 12))
def test_vsip_shifted_alias_roundtrip(v):
    """vsip.SSIP ↔ mip.VSSIP (shifted-by-1 alias), gated by hideleg."""
    base, _, _ = _rw(_csrs(), 0x603, C.VS_INTERRUPTS)   # hideleg all VS
    new, ok, _ = _rw(base, 0x244, v, priv=1, virt=False)
    mip = int(new[C.R_MIP])
    want_vssip = bool(v & C.IP_SSIP)
    assert bool(mip & C.IP_VSSIP) == want_vssip
    rd, _, _ = _rd(new, 0x244)
    assert bool(rd & C.IP_SSIP) == want_vssip


def test_h_csrs_fault_virtual_from_vs():
    for addr in (0x600, 0x602, 0x603, 0x645, 0x680, 0xE12, 0x200, 0x280):
        _, ok, vinst = _rd(_csrs(), addr, priv=1, virt=True)
        assert vinst, hex(addr)
    # and are fine from HS
    for addr in (0x600, 0x602, 0x603, 0x645, 0x680):
        _, ok, vinst = _rd(_csrs(), addr, priv=1, virt=False)
        assert ok and not vinst, hex(addr)


def test_mepc_low_bit_warl():
    new, _, _ = _rw(_csrs(), 0x341, 0x1003)
    assert int(new[C.R_MEPC]) == 0x1002       # bit 0 forced clear


@pytest.mark.parametrize("v", _vals("plain_rw", 8))
def test_plain_csr_write_read_roundtrip(v):
    for addr, idx in ((0x305, C.R_MTVEC), (0x340, C.R_MSCRATCH),
                      (0x643, C.R_HTVAL), (0x680, C.R_HGATP)):
        new, ok, _ = _rw(_csrs(), addr, v)
        assert ok
        rd, ok2, _ = _rd(new, addr)
        assert ok2 and rd == int(new[idx])


@pytest.mark.parametrize("v", _vals("oracle_csr", 12))
def test_csr_file_matches_oracle(v):
    """Differential micro-check: the pure-Python oracle CSR file (DESIGN.md
    §5) agrees with the JAX one on random writes + reads across modes."""
    from repro.core.hext import oracle
    for addr in (0x300, 0x100, 0x104, 0x144, 0x303, 0x602, 0x645, 0x14D,
                 0x605, 0x680):
        for priv, virt in ((3, False), (1, False), (1, True), (0, False)):
            jnew, jok, jvi = _rw(_csrs(), addr, v, priv, virt)
            onew, ook, ovi = oracle.csr_write(
                oracle.init_csrs(), addr, v, priv, virt)
            assert (jok, jvi) == (ook, ovi), (hex(addr), priv, virt)
            with x64():   # u64 host reads need x64
                jlist = [int(x) for x in np.asarray(jnew)]
            assert jlist == onew, (hex(addr), priv, virt)
            jv, jok, jvi = _rd(jnew, addr, priv, virt)
            ov, ook, ovi = oracle.csr_read(onew, addr, priv, virt)
            assert (jv, jok, jvi) == (ov, ook, ovi), (hex(addr), priv, virt)
