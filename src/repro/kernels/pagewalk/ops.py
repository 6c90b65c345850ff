"""Dispatching wrapper: TPU → Pallas kernel, CPU → jnp ref (identical
semantics, out-of-range coordinates included; the dry-run lowers this
path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.pagewalk import ref
from repro.kernels.pagewalk.kernel import two_stage_translate_kernel


def two_stage_translate(vs_table, vs_perm, g_table, tenant, req, page,
                        want_write=None, force: str = "auto"):
    """force: auto | ref | kernel | interpret."""
    if want_write is None:
        want_write = jnp.zeros(tenant.shape, bool)
    on_tpu = jax.default_backend() == "tpu"
    if force == "kernel" or (force == "auto" and on_tpu):
        return two_stage_translate_kernel(vs_table, vs_perm, g_table, tenant,
                                          req, page, want_write)
    if force == "interpret":
        return two_stage_translate_kernel(vs_table, vs_perm, g_table, tenant,
                                          req, page, want_write,
                                          interpret=True)
    return ref.two_stage_translate_ref(vs_table, vs_perm, g_table, tenant,
                                       req, page, want_write)
