"""Pure-jnp oracle for the batched two-stage table walk.

Semantics == repro.core.vmem.page_table.translate (without the fused cache):
stage 1: (tenant, req, page) → tenant_page  (perm-checked)
stage 2: (tenant, tenant_page) → host slot
"""
from __future__ import annotations

import jax.numpy as jnp

PERM_R, PERM_W = 1, 2


def two_stage_translate_ref(vs_table, vs_perm, g_table, tenant, req, page,
                            want_write):
    """vs_table [T,R,P] int32; g_table [T,G] int32; coords [B] int32;
    want_write [B] bool → (slot [B] int32, fault [B] bool, stage [B] int32).
    """
    # jnp arrays, so that out-of-range coordinates follow jnp indexing
    # (from the end when negative, then clamped) for numpy inputs too
    vs_table, vs_perm, g_table = map(jnp.asarray,
                                     (vs_table, vs_perm, g_table))
    tp = vs_table[tenant, req, page]
    perm = vs_perm[tenant, req, page]
    want = jnp.where(want_write, PERM_W, PERM_R)
    s1_fault = (tp < 0) | ((perm & want) == 0)
    slot = g_table[tenant, jnp.maximum(tp, 0)]
    s2_fault = ~s1_fault & (slot < 0)
    fault = s1_fault | s2_fault
    out = jnp.where(fault, -1, slot)
    stage = jnp.where(s1_fault, 1, jnp.where(s2_fault, 2, 0))
    return out.astype(jnp.int32), fault, stage.astype(jnp.int32)
