"""Randomized property tests: RV64 arithmetic helper semantics vs Python
golden models (division/remainder/mulh corner cases are classic simulator
bugs).

Seeded ``numpy.random.Generator`` + ``pytest.mark.parametrize`` instead of
hypothesis (absent from the CI container, which used to skip this file
silently).  Every parametrized stream always includes the architectural
corner values (0, ±1, INT_MIN, all-ones) alongside the random draws.
"""
import jax
import jax.numpy as jnp
import pytest

import numpy as np

from repro.core.hext import isa
from repro.core.hext.bits import x64

I64_MIN = -(1 << 63)
U64_MAX = (1 << 64) - 1
N_CASES = 24


def _pairs(tag: str, signed: bool, n: int = N_CASES):
    """Deterministic (a, b) operand pairs, corner cases first."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([0x15A] + list(tag.encode()))))
    if signed:
        corners = [(0, 0), (I64_MIN, -1), (I64_MIN, 1), (-1, -1),
                   ((1 << 63) - 1, -1), (7, 0), (-7, 0), (I64_MIN, 0)]
        rand = rng.integers(I64_MIN, 1 << 63, size=(n, 2), dtype=np.int64)
    else:
        corners = [(0, 0), (U64_MAX, U64_MAX), (U64_MAX, 1), (1, U64_MAX),
                   (0, U64_MAX), (1 << 63, 2), (U64_MAX, 0)]
        rand = rng.integers(0, 1 << 64, size=(n, 2), dtype=np.uint64)
    return corners + [(int(a), int(b)) for a, b in rand]


def _u(x):
    with x64():
        return jnp.asarray(x % (1 << 64), jnp.uint64)


def _as_i64(u):
    u = int(u) & U64_MAX
    return u - (1 << 64) if u >= (1 << 63) else u


def _as_u64(i):
    return i & U64_MAX


@pytest.mark.parametrize("a,b", _pairs("divs", signed=True))
def test_divs_matches_riscv_semantics(a, b):
    with x64():
        got = _as_i64(isa.divs(_u(a), _u(b)))
    if b == 0:
        want = -1
    elif a == I64_MIN and b == -1:
        want = I64_MIN
    else:
        want = int(abs(a) // abs(b))
        if (a < 0) != (b < 0):
            want = -want
    assert got == want, (a, b)


@pytest.mark.parametrize("a,b", _pairs("rems", signed=True))
def test_rems_matches_riscv_semantics(a, b):
    with x64():
        got = _as_i64(isa.rems(_u(a), _u(b)))
    if b == 0:
        want = a
    elif a == I64_MIN and b == -1:
        want = 0
    else:
        want = int(abs(a) % abs(b))
        if a < 0:
            want = -want
    assert got == want, (a, b)


@pytest.mark.parametrize("a,b", _pairs("mulhu", signed=False))
def test_mulhu_matches_python(a, b):
    with x64():
        got = int(isa.mulhu(_u(a), _u(b)))
    assert got == (a * b) >> 64


@pytest.mark.parametrize("a,b", _pairs("mulh", signed=True))
def test_mulh_matches_python(a, b):
    with x64():
        got = _as_i64(isa.mulh(_u(_as_u64(a)), _u(_as_u64(b))))
    assert got == (a * b) >> 64


@pytest.mark.parametrize("a,b", _pairs("mulhsu", signed=True))
def test_mulhsu_matches_python(a, b):
    b = _as_u64(b)                       # rs2 is unsigned for mulhsu
    with x64():
        got = _as_i64(isa.mulhsu(_u(_as_u64(a)), _u(b)))
    assert got == (a * b) >> 64


@pytest.mark.parametrize("bits", [8, 12, 16, 32])
def test_sext_matches_python(bits):
    for v, _ in _pairs(f"sext{bits}", signed=False, n=8):
        with x64():
            got = _as_i64(isa.sext(_u(v), bits))
        low = v & ((1 << bits) - 1)
        want = low - (1 << bits) if low >= (1 << (bits - 1)) else low
        assert got == want, v


@pytest.mark.parametrize("size", [0, 1, 2, 3])
def test_mem_write_read_roundtrip(size):
    nbytes = 1 << size
    for val, off in _pairs(f"mem{size}", signed=False, n=6):
        off = (off % 8 // nbytes) * nbytes        # naturally aligned
        with x64():
            mem = jnp.zeros((4,), jnp.uint64)
            mem = isa.mem_write(mem, _u(8 + off), _u(val), size)
            rd = int(isa.mem_read(mem, _u(8 + off), size,
                                  jnp.asarray(True)))  # unsigned read
        assert rd == val & ((1 << (8 * nbytes)) - 1)


@pytest.mark.parametrize("a,b", _pairs("oracle_alu", signed=True, n=12))
def test_alu_helpers_match_oracle(a, b):
    """Differential micro-check vs the pure-Python oracle (DESIGN.md §5):
    the two independent div/rem/mulh implementations must agree."""
    from repro.core.hext import oracle
    au, bu = _as_u64(a), _as_u64(b)
    with x64():
        assert int(isa.divs(_u(au), _u(bu))) == oracle._divs(au, bu)
        assert int(isa.rems(_u(au), _u(bu))) == oracle._rems(au, bu)
        assert int(isa.mulhu(_u(au), _u(bu))) == oracle._mulhu(au, bu)
        assert int(isa.sext(_u(au), 32)) == oracle.sext(au, 32)


def test_assembler_encodings_golden():
    """Spot-check assembler encodings against known-good golden words."""
    from repro.core.hext.programs import Asm
    a = Asm(0)
    a.addi("a0", "zero", 5)       # 00500513
    a.add("a1", "a0", "a0")       # 00a505b3
    a.ld("t0", 8, "sp")           # 00813283
    a.sd("t0", 16, "sp")          # 00513823
    a.ecall()                     # 00000073
    a.sret()                      # 10200073
    a.mret()                      # 30200073
    a.wfi()                       # 10500073
    a.hfence_gvma()               # 62000073
    words = [hex(w) for w in a.assemble()]
    assert words == ['0x500513', '0xa505b3', '0x813283', '0x513823',
                     '0x73', '0x10200073', '0x30200073', '0x10500073',
                     '0x62000073']


# ---------------------------------------------------------------------------
# decode-table sweep: table-driven decode vs the oracle's independent
# bit-slicing decoder (no shared tables), plus traced-vs-host identity
# ---------------------------------------------------------------------------

_KNOWN_OPS = (0x33, 0x13, 0x3B, 0x1B, 0x37, 0x17, 0x6F, 0x67, 0x63,
              0x03, 0x23, 0x73, 0x0F)


def _decode_words(n: int = 256):
    """Deterministic instruction-word sweep: fixed architectural
    encodings, then random words biased onto the known major opcodes (so
    every opclass and immediate format is exercised), then fully random
    words (mostly illegal — the table's default row)."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([0x15A] + list(b"decode"))))
    fixed = [0x00000000, 0xFFFFFFFF,
             0x02A00093,              # addi x1, x0, 42
             0x40C5D533,              # sra a0, a1, a2
             0x02C5C533,              # div a0, a1, a2
             0x0015051B,              # addiw a0, a0, 1
             0x12345037, 0x12345017,  # lui / auipc
             0x0040006F, 0x00008067,  # jal / jalr
             0xFE550AE3,              # branch (negative B-imm)
             0x00853083, 0x00853023,  # ld / sd
             0x00000073, 0x10200073,  # ecall / sret
             0x30200073, 0x10500073,  # mret / wfi
             0x62000073,              # hfence.gvma
             0x0000000F, 0x0000100F]  # fence / fence.i
    rand = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
    ops = rng.choice(np.asarray(_KNOWN_OPS, np.uint32), size=n // 2)
    biased = (rand[: n // 2] & ~np.uint32(0x7F)) | ops
    return fixed + [int(w) for w in biased] + \
        [int(w) for w in rand[n // 2:]]


def test_decode_word_matches_independent_decoder():
    """Host table decode vs the oracle's if/elif decoder, field by field
    (a mis-built table row or a wrong immediate mux fails by name)."""
    from repro.core.hext import decode as D
    from repro.core.hext import oracle
    for w in _decode_words():
        got = D.decode_word(w)
        ref = oracle.decode_fields(w)
        assert D.CLS_NAMES[got["cls"]] == ref["cls"], hex(w)
        for k in ("rd", "rs1", "rs2", "f3", "f7", "imm", "alu_imm",
                  "instr"):
            assert got[k] == ref[k], (hex(w), k)


def test_traced_decode_matches_decode_word():
    """The jnp.take-gather decode must agree with the host-side decoder
    over the same tables for every sweep word (one vmapped trace)."""
    from repro.core.hext import decode as D
    words = _decode_words()
    with x64():
        uops = jax.jit(jax.vmap(D.decode))(jnp.asarray(words, jnp.uint64))
        uops = jax.tree.map(np.asarray, uops)
    for i, w in enumerate(words):
        ref = D.decode_word(w)
        got = {
            "cls": int(uops.cls[i]), "rd": int(uops.rd[i]),
            "rs1": int(uops.rs1[i]), "rs2": int(uops.rs2[i]),
            "f3": int(uops.f3[i]), "f7": int(uops.f7[i]),
            "imm": int(uops.imm[i]), "alu_imm": bool(uops.alu_imm[i]),
            "instr": int(uops.instr[i]),
        }
        assert got == ref, hex(w)
