"""Shared 64-bit helpers for the hext core.

One definition of the uint64/int64 casts, sign extension, word-granular
memory access, and sub-word extract/deposit that used to be copy-pasted
across ``isa.py`` / ``machine.py`` / ``translate.py`` / ``tlb.py``
(each module had its own ``_u``).  Everything is branchless jnp so it
traces into fixed graphs and vmaps over harts.

64-bit integer semantics require x64 mode; call sites run under the
scoped :func:`x64` context (the sim facade and engines do this at their
entry points).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

U64 = jnp.uint64
I64 = jnp.int64
MASK64 = (1 << 64) - 1


def x64():
    """The one x64 context of the hext core: ``with x64(): ...``.

    Scoped, not process-wide, so code outside it (the LLM stack sharing
    the process) keeps JAX's default 32-bit dtypes."""
    return jax.enable_x64(True)


def u64(x) -> jnp.ndarray:
    """Cast to uint64 (the architectural register width)."""
    return jnp.asarray(x, U64)


def i64(x) -> jnp.ndarray:
    """Cast to int64 (for signed compares/shifts)."""
    return jnp.asarray(x, I64)


def sext(x, bits: int):
    """Sign-extend the low `bits` of uint64 x (upper bits ignored)."""
    x = u64(x) & u64((1 << bits) - 1)
    m = u64(1 << (bits - 1))
    return (x ^ m) - m


def read64(mem, pa):
    """Aligned 64-bit word read at physical byte address `pa`.

    NOTE: the wrapped index is only a safe-indexing device for traced
    code; a PA beyond memory raises an access fault in the walker and at
    the final access, so the wrapped value is never architecturally
    visible.
    """
    return mem[(u64(pa) >> u64(3)).astype(jnp.int32) % mem.shape[0]]


def word_extract(word, pa, size_log2, unsigned):
    """Read 1/2/4/8 bytes out of an aligned 64-bit word (shared by RAM and
    the CLINT MMIO registers)."""
    off = (u64(pa) & u64(7)) << u64(3)           # bit offset
    v = word >> off
    nbits = u64(8) << u64(size_log2)
    mask = jnp.where(nbits >= u64(64), ~u64(0), (u64(1) << nbits) - u64(1))
    v = v & mask
    shift = u64(64) - nbits                      # dynamic sign extension
    sv = u64(i64(v << shift) >> shift.astype(I64))
    return jnp.where(unsigned, v, sv)


def word_deposit(word, pa, val, size_log2):
    """Merge a 1/2/4/8-byte store into an aligned 64-bit word."""
    off = (u64(pa) & u64(7)) << u64(3)
    nbits = u64(8) << u64(size_log2)
    mask = jnp.where(nbits >= 64, ~u64(0), (u64(1) << nbits) - u64(1))
    return (word & ~(mask << off)) | ((u64(val) & mask) << off)
