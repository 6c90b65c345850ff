"""Pallas TPU kernel: batched two-stage table walk.

TPU adaptation of gem5's pointer-chasing ``stepWalk()``: both table stages
are VMEM-resident (they are small: stage-1 [T,R,P] and stage-2 [T,G] int32),
and a *vector* of (tenant, req, page) queries is translated per grid step.

Mosaic has no general vector gather, so each lookup is a one-hot matmul on
the MXU.  A flat table index ``f`` splits into a row ``f // 128`` and a
lane ``f % 128``: ``onehot(row) @ table`` picks the row, and a masked lane
sum picks the entry.  To keep the matmul exact for any int32 entry, a
table is stored as its four bytes side by side in bf16 (every byte value
is exact in bf16, and each output sums one nonzero product in f32).

Block layout:
  queries are blocked along the batch dim (BLOCK_B at a time) as (B, 1)
  columns; both byte-plane tables are broadcast (whole-table blocks) —
  they fit VMEM easily (e.g. 8 tenants × 64 reqs × 512 pages = 2048 rows
  × 1024 bf16 lanes = 4 MiB for stage 1, entries and permissions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PERM_R, PERM_W = 1, 2
BLOCK_B = 512
LANE_BITS = 7
LANES = 1 << LANE_BITS
BYTES = 4          # byte planes per int32 table
ROW_ALIGN = 128    # table rows are the matmul's contraction dim


def _byte_planes(*tables):
    """int32 tables of one size → bf16 [rows, len(tables) * BYTES * LANES]:
    each table's flat entries, zero-padded to whole aligned 128-lane rows,
    split into byte planes laid side by side."""
    n = tables[0].size
    rows = -(-n // (LANES * ROW_ALIGN)) * ROW_ALIGN
    planes = []
    for t in tables:
        u = jax.lax.bitcast_convert_type(t.reshape(-1), jnp.uint32)
        u = jnp.pad(u, (0, rows * LANES - n)).reshape(rows, LANES)
        planes += [(u >> (8 * k)) & 0xFF for k in range(BYTES)]
    return jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)


def _gather(planes, f, n_tables: int):
    """Entry ``f`` ((bb, 1) int32, in range) of each of the ``n_tables``
    int32 tables packed in ``planes`` → list of (bb, 1) int32."""
    bb = f.shape[0]
    rows = planes.shape[0]
    hot = (f >> LANE_BITS) == jax.lax.broadcasted_iota(
        jnp.int32, (bb, rows), 1)
    picked = jnp.dot(hot.astype(jnp.bfloat16), planes,
                     preferred_element_type=jnp.float32)
    lane = (f & (LANES - 1)) == jax.lax.broadcasted_iota(
        jnp.int32, (bb, LANES), 1)
    out = []
    for t in range(n_tables):
        v = jnp.zeros((bb, 1), jnp.int32)
        for k in range(BYTES):
            c = (t * BYTES + k) * LANES
            byte = jnp.sum(jnp.where(lane, picked[:, c:c + LANES], 0.0),
                           axis=1, keepdims=True)
            v = v | (byte.astype(jnp.int32) << (8 * k))
        out.append(v)
    return out


def _kernel(s1_ref, g_ref, tenant_ref, req_ref, page_ref, w_ref,
            slot_out, fault_out, stage_out, *, R, P, G):
    t = tenant_ref[...]
    r = req_ref[...]
    p = page_ref[...]
    ww = w_ref[...]
    # stage 1: (tenant, req, page) → tenant page + permission bits
    tp, perm = _gather(s1_ref[...], (t * R + r) * P + p, 2)
    want = jnp.where(ww != 0, PERM_W, PERM_R)
    s1_fault = (tp < 0) | ((perm & want) == 0)
    # stage 2: (tenant, tenant page) → host slot
    (slot,) = _gather(g_ref[...], t * G + jnp.clip(tp, 0, G - 1), 1)
    s2_fault = ~s1_fault & (slot < 0)
    fault = s1_fault | s2_fault
    slot_out[...] = jnp.where(fault, -1, slot).astype(jnp.int32)
    fault_out[...] = fault.astype(jnp.int32)
    stage_out[...] = jnp.where(s1_fault, 1,
                               jnp.where(s2_fault, 2, 0)).astype(jnp.int32)


def _index(x, n):
    """jnp indexing's rule for one coordinate of an axis of size ``n``: a
    negative index counts from the end, then it is clamped into range."""
    x = x.astype(jnp.int32)
    return jnp.clip(jnp.where(x < 0, x + n, x), 0, n - 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def two_stage_translate_kernel(vs_table, vs_perm, g_table, tenant, req, page,
                               want_write, interpret: bool = False):
    """Same contract as ``ref.two_stage_translate_ref``, out-of-range
    coordinates and stage-1 entries included: they are brought into range
    as jnp indexing does, since the one-hot lookup itself would read 0 or
    the next row."""
    T, R, P = vs_table.shape
    G = g_table.shape[1]
    B = tenant.shape[0]
    bb = min(BLOCK_B, B)
    s1 = _byte_planes(vs_table.astype(jnp.int32), vs_perm.astype(jnp.int32))
    g = _byte_planes(g_table.astype(jnp.int32))
    col = lambda x: x.astype(jnp.int32).reshape(B, 1)
    qspec = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    slot, fault, stage = pl.pallas_call(
        functools.partial(_kernel, R=R, P=P, G=G),
        grid=(pl.cdiv(B, bb),),
        in_specs=[full(s1), full(g), qspec, qspec, qspec, qspec],
        out_specs=[qspec, qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3,
        interpret=interpret,
    )(s1, g, col(_index(tenant, T)), col(_index(req, R)),
      col(_index(page, P)), col(want_write))
    return slot[:, 0], fault[:, 0].astype(bool), stage[:, 0]
