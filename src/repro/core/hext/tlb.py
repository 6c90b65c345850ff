"""Two-stage-aware TLB (paper §3.5 challenge (3)).

Each entry caches a *composed* translation (VPN → host PFN) plus the
permission bits derived from BOTH the guest (VS-stage) leaf PTE and the host
(G-stage) leaf PTE — the paper's observation that the guest's PFN may carry
different permissions than the supervisor's PFN. Entries created in
virtualization mode are tagged ``guest`` so that ``hfence.{vvma,gvma}``
invalidates only them while ``sfence.vma`` touches only native entries.
Megapage/gigapage leaves insert with their level so neighbours hit too.

Entries additionally carry the privilege context (priv/SUM/MXR) their
permission bits were composed under; a lookup from a different context
misses instead of reusing a stale permission verdict (e.g. a U-mode access
hitting an S-mode entry).

``lookup`` returns a :class:`TlbVerdict` — a complete (hit, pa, perm_ok)
record.  ``verdict.use`` is the machine's fast-path predicate: a usable
hit never needs the two-stage walk graph at all (machine.step only
materializes the walk when some hart in the batch misses — DESIGN.md §7).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.hext import translate as X
from repro.core.hext.bits import u64 as _u

U64 = jnp.uint64
N_TLB = 16

PERM_R, PERM_W, PERM_X = 1, 2, 4


class TlbVerdict(NamedTuple):
    """Complete TLB lookup outcome for one access.

    ``hit``: an entry matched (VPN + guest tag + privilege context);
    ``pa``: the composed host-physical address of the matched entry
    (garbage when ``hit`` is false — gate on ``hit``);
    ``perm_ok``: the cached composed permissions allow this access.

    ``use`` is the short-circuit predicate: the translation is fully
    resolved by the TLB and the walk can be skipped.  A hit with bad
    permissions still walks — the walk, not the TLB, determines the
    architectural fault cause.
    """

    hit: jnp.ndarray
    pa: jnp.ndarray
    perm_ok: jnp.ndarray

    @property
    def use(self):
        return self.hit & self.perm_ok


def init_tlb():
    return {
        "vpn": jnp.zeros((N_TLB,), U64),
        "ppn": jnp.zeros((N_TLB,), U64),
        "level": jnp.zeros((N_TLB,), jnp.int32),
        "perm": jnp.zeros((N_TLB,), jnp.int32),
        "guest": jnp.zeros((N_TLB,), bool),
        # privilege context the cached perms were composed under — a lookup
        # from a different (priv, SUM, MXR) must miss, otherwise e.g. a
        # U-mode access could reuse an S-mode entry's permission verdict
        "priv": jnp.zeros((N_TLB,), jnp.int32),
        "sum": jnp.zeros((N_TLB,), bool),
        "mxr": jnp.zeros((N_TLB,), bool),
        "valid": jnp.zeros((N_TLB,), bool),
        "ptr": jnp.zeros((), jnp.int32),
    }


def _vpn_mask(level):
    """VPN bits that must match for an entry of this level."""
    return ~((_u(1) << (level.astype(U64) * _u(9))) - _u(1))


def lookup(tlb, va, virt, acc, priv, sum_bit, mxr) -> TlbVerdict:
    """→ :class:`TlbVerdict` (unpacks as the legacy ``(hit, pa, perm_ok)``
    triple).  Matches only entries whose cached permission context
    (priv/SUM/MXR at insert time) equals the current access's."""
    vpn = jnp.asarray(va, U64) >> _u(12)
    lm = _vpn_mask(tlb["level"])
    match = tlb["valid"] & (tlb["guest"] == virt) & \
        (tlb["priv"] == priv) & (tlb["sum"] == sum_bit) & \
        (tlb["mxr"] == mxr) & \
        ((vpn & lm) == (tlb["vpn"] & lm))
    hit = jnp.any(match)
    idx = jnp.argmax(match)
    level = tlb["level"][idx]
    in_page = jnp.asarray(va, U64) & ((_u(1) << (_u(12) +
                                       level.astype(U64) * _u(9)))
                                      - _u(1))
    base = tlb["ppn"][idx] << _u(12)
    base = base & ~((_u(1) << (_u(12) + level.astype(U64) *
                                     _u(9))) - _u(1))
    pa = base | in_page
    want = jnp.where(acc == X.ACC_R, PERM_R,
                     jnp.where(acc == X.ACC_W, PERM_W, PERM_X))
    perm_ok = (tlb["perm"][idx] & want) != 0
    return TlbVerdict(hit=hit, pa=pa, perm_ok=perm_ok)


def compose_perms(vs_pte, g_pte, priv, sum_bit, mxr):
    """Permission bits of the composed entry — guest PTE perms AND host PTE
    perms (paper: store guest PTE permission bits alongside the host's)."""
    bits = jnp.zeros((), jnp.int32)
    for acc, bit in ((X.ACC_R, PERM_R), (X.ACC_W, PERM_W), (X.ACC_X, PERM_X)):
        a = jnp.asarray(acc, U64)
        ok1 = X._leaf_ok(vs_pte, a, priv, sum_bit, mxr, jnp.zeros((), bool))
        ok2 = X._leaf_ok(g_pte, a, jnp.zeros((), jnp.int32),
                         jnp.zeros((), bool), mxr, jnp.ones((), bool))
        bits = bits | jnp.where(ok1 & ok2, bit, 0)
    return bits


def insert(tlb, va, pa, level, perm, virt, priv, sum_bit, mxr):
    # a one-hot select, not a scatter: with nine single-entry scatters here
    # a 1,152-hart fleet on a TPU v5e miscounted `walks` (guest harts only)
    # and with the select it matches the goldens; scatter_probe.py checks
    # the scatter and the select alone on a chip
    slot = jnp.arange(N_TLB) == tlb["ptr"] % N_TLB
    new = {"vpn": jnp.asarray(va, U64) >> _u(12),
           "ppn": jnp.asarray(pa, U64) >> _u(12),
           "level": level, "perm": perm, "guest": virt, "priv": priv,
           "sum": sum_bit, "mxr": mxr, "valid": True}
    t = {k: jnp.where(slot, jnp.asarray(v, tlb[k].dtype), tlb[k])
         for k, v in new.items()}
    t["ptr"] = tlb["ptr"] + 1
    return t


def _va_match(tlb, va):
    """Entries whose cached translation covers `va` (superpage-aware:
    an entry invalidates if the fence VA falls anywhere in its reach)."""
    vpn = jnp.asarray(va, U64) >> _u(12)
    lm = _vpn_mask(tlb["level"])
    return (vpn & lm) == (tlb["vpn"] & lm)


def flush(tlb, guest_only=False, native_only=False, va=None):
    """Host-python flush: full-scope per tag class, or — with ``va`` —
    only the entries of that class that translate the given VA page
    (the rs1≠x0 form of sfence.vma / hfence.vvma)."""
    keep = jnp.zeros((N_TLB,), bool)
    if guest_only:
        keep = ~tlb["guest"]       # hfence: drop guest entries only
    if native_only:
        keep = tlb["guest"]        # sfence: drop native entries only
    if va is not None:
        keep = keep | ~_va_match(tlb, va)
    t = dict(tlb)
    t["valid"] = tlb["valid"] & keep
    return t


def flush_where(tlb, cond_guest, cond_native,
                cond_guest_addr=None, cond_native_addr=None, va=None):
    """Traced flush; all conditions are traced bools.

    ``cond_guest``/``cond_native`` are the full-scope flushes (rs1=x0,
    atp writes).  ``cond_guest_addr``/``cond_native_addr`` are the
    address-targeted forms (rs1≠x0): only entries of that tag class
    whose cached translation covers the ``va`` page are dropped, so a
    guest flushing one page no longer nukes every warm entry."""
    drop = (tlb["guest"] & cond_guest) | (~tlb["guest"] & cond_native)
    if cond_guest_addr is not None:
        vm = _va_match(tlb, va)
        drop = drop | (tlb["guest"] & cond_guest_addr & vm) | \
            (~tlb["guest"] & cond_native_addr & vm)
    t = dict(tlb)
    t["valid"] = tlb["valid"] & ~drop
    return t
