"""Benchmark aggregator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
  fig4_sim_time      — native vs guest simulation time (paper Fig 4)
  fig5_instructions  — executed instructions w/ and w/o VM (paper Fig 5)
  fig6_native_exc    — exceptions per privilege level, native (paper Fig 6)
  fig7_guest_exc     — exceptions per privilege level, guest (paper Fig 7)
  vmem_*             — beyond-paper: two-stage paged-KV data/control plane
  kernel_*           — kernel ref-path micro-benches
  roofline_*         — condensed §Roofline rows from the dry-run artifacts

Heavy simulator runs are cached in benchmarks/results/hext_runs.json
(regenerate with ``python -m benchmarks.run_hext``).
"""
from __future__ import annotations

import json
import os
import time

ROOT = os.path.dirname(__file__)
HEXT_RESULTS = os.path.join(ROOT, "results", "hext_runs.json")


def _row(name, us, derived):
    print(f"{name},{us:.3f},{derived}")


def _hext_data():
    if not os.path.exists(HEXT_RESULTS):
        from benchmarks import run_hext
        run_hext.main(HEXT_RESULTS)
    with open(HEXT_RESULTS) as f:
        return json.load(f)


def fig4_sim_time():
    """Sim-time proxy: deterministic ticks native vs guest (+ the measured
    batched-run wall time). The paper's Fig 4 measures gem5 host seconds —
    our batched lockstep simulator has constant per-tick cost, so tick
    ratios are the faithful analogue (DESIGN.md §6)."""
    d = _hext_data()
    for name, r in d["workloads"].items():
        n, g = r["native"], r["guest"]
        slow = g["ticks"] / max(n["ticks"], 1)
        _row(f"fig4_sim_time_{name}", 0.0,
             f"native_ticks={n['ticks']};guest_ticks={g['ticks']};"
             f"slowdown={slow:.3f}")
    _row("fig4_batched_wall", d["wall_seconds_batched"] * 1e6,
         "18 machines (9 workloads x native+guest) in one vmapped run")


def fig5_instructions():
    d = _hext_data()
    for name, r in d["workloads"].items():
        n, g = r["native"], r["guest"]
        _row(f"fig5_instret_{name}", 0.0,
             f"wo_vm={n['instret']};w_vm={g['instret']};"
             f"overhead={g['instret']/max(n['instret'],1):.3f};"
             f"ok={n['ok'] and g['ok']}")


def fig6_native_exceptions():
    d = _hext_data()
    for name, r in d["workloads"].items():
        e = r["native"]["exc_by_level"]
        _row(f"fig6_native_exc_{name}", 0.0,
             f"M={e[0]};S={e[1]};pagefaults={r['native']['pagefaults']}")


def fig7_guest_exceptions():
    d = _hext_data()
    for name, r in d["workloads"].items():
        e = r["guest"]["exc_by_level"]
        _row(f"fig7_guest_exc_{name}", 0.0,
             f"M={e[0]};HS={e[1]};VS={e[2]};"
             f"pagefaults={r['guest']['pagefaults']}")


def vmem_bench():
    import jax
    import jax.numpy as jnp
    from repro.core.vmem import kvcache as KC
    from repro.core.vmem import page_table as PT

    kv = KC.PagedKVCache.create(
        n_slots=512, page_size=16, n_kv_heads=8, head_dim=128, n_tenants=8,
        reqs_per_tenant=8, logical_pages=64, tenant_pages=256)
    for p in range(64):
        kv, ok = KC.ensure_mapped(kv, 0, 0, p)

    t_ids = jnp.zeros((1024,), jnp.int32)
    r_ids = jnp.zeros((1024,), jnp.int32)
    pages = jnp.arange(1024, dtype=jnp.int32) % 64
    f = jax.jit(lambda t, r, p: PT.translate(kv.tables, t, r, p))
    f(t_ids, r_ids, pages)  # compile
    t0 = time.time()
    N = 100
    for _ in range(N):
        out = f(t_ids, r_ids, pages)
    jax.block_until_ready(out.slot)
    us = (time.time() - t0) / N * 1e6
    _row("vmem_translate_1024", us, "two-stage translate (fused-cache path)")

    t0 = time.time()
    KC.evict_tenant(kv, 0)
    _row("vmem_evict_tenant", (time.time() - t0) * 1e6,
         "O(tenant pages) teardown — the paper's two-stage win")


def kernel_bench():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention

    rng = np.random.RandomState(0)
    B, S, H, KV, hd = 1, 256, 8, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, 0.125, force="ref"))
    f(q, k, v)
    t0 = time.time()
    for _ in range(10):
        out = f(q, k, v)
    jax.block_until_ready(out)
    _row("flash_attention_ref", (time.time() - t0) / 10 * 1e6,
         f"B{B} S{S} H{H} hd{hd} (TPU path = Pallas kernel)")


def roofline_summary():
    """Condensed §Roofline rows from the dry-run JSONs (if present)."""
    d = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(d):
        _row("roofline", 0.0, "no dryrun results yet")
        return
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(d, fn)) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        _row(f"roofline_{rec['arch']}_{rec['shape']}"
             f"_{'mp' if rec['multi_pod'] else 'sp'}", 0.0,
             f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
             f"tc={r['t_compute_s']:.2e};tm={r['t_memory_s']:.2e};"
             f"tx={r['t_collective_s']:.2e}")


def main() -> None:
    print("name,us_per_call,derived")
    fig4_sim_time()
    fig5_instructions()
    fig6_native_exceptions()
    fig7_guest_exceptions()
    vmem_bench()
    kernel_bench()
    roofline_summary()


if __name__ == "__main__":
    from repro.core.hext.engine import use_compile_cache
    use_compile_cache()
    main()
