"""Do the hext tick's per-hart single-entry writes keep every write?

    python scatter_probe.py                     # widths 18 1152 2048 4096
    python scatter_probe.py --widths 18 64 --steps 32

The tick writes one entry per hart in three places: the TLB fill
(``tlb.insert``: 16 entries of nine fields, four of them bool), the
register retire (32 uint64) and the memory retire (``programs.MEM_WORDS``
uint64) of ``machine.step_batched``.  For each place this runs the write
both as a vmapped ``.at[i].set`` scatter (the TLB's form before it became
a select, and the form the retire still uses) and as a one-hot select,
``--steps`` times in one on-device loop with a random per-hart write mask,
at each fleet width.  After every step it reads the written entry back
and counts those that differ from what was written; at the end it
compares the whole arrays with a numpy model.

One line per (place, form, width) gives both counts.  The exit status is
1 if any count is not 0.  Runs on any backend; on a TPU it is the chip
check for the cause of the TLB ``walks`` drift seen at 1,152 harts.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.hext import programs  # noqa: E402
from repro.core.hext import tlb as TLB  # noqa: E402
from repro.core.hext.bits import x64  # noqa: E402

U64 = jnp.uint64
N = TLB.N_TLB
# the TLB entry fields as (name, dtype, high bound of random values)
TLB_FIELDS = (("vpn", np.uint64, 1 << 40), ("ppn", np.uint64, 1 << 40),
              ("level", np.int32, 3), ("perm", np.int32, 8),
              ("guest", bool, 2), ("priv", np.int32, 4),
              ("sum", bool, 2), ("mxr", bool, 2), ("valid", bool, 2))
# insert's arguments, in order, and the entry field each one sets
TLB_ARGS = (("va", "vpn"), ("pa", "ppn"), ("level", "level"),
            ("perm", "perm"), ("virt", "guest"), ("priv", "priv"),
            ("sum_bit", "sum"), ("mxr", "mxr"))


def _tlb_scatter(tlb, va, pa, level, perm, virt, priv, sum_bit, mxr):
    """``tlb.insert`` as nine single-entry scatters (its earlier form)."""
    i = tlb["ptr"] % N
    new = {"vpn": va >> jnp.uint64(12), "ppn": pa >> jnp.uint64(12),
           "level": level, "perm": perm, "guest": virt, "priv": priv,
           "sum": sum_bit, "mxr": mxr, "valid": True}
    t = {k: tlb[k].at[i].set(v) for k, v in new.items()}
    t["ptr"] = tlb["ptr"] + 1
    return t


def _entry_scatter(arr, i, c, w):
    """The retire's form (``machine.step_batched``), vmapped over harts."""
    return jax.vmap(lambda r, i, c, w: r.at[i].set(jnp.where(c, w, r[i])))(
        arr, i, c, w)


def _entry_select(arr, i, c, w):
    hot = (jnp.arange(arr.shape[1])[None, :] == i[:, None]) & c[:, None]
    return jnp.where(hot, w[:, None], arr)


def _at(arr, i):
    return jnp.take_along_axis(arr, i[:, None], axis=1)[:, 0]


def _tlb_step(insert):
    def step(tlb, x):
        new = jax.vmap(insert)(tlb, *(x[a] for a, _ in TLB_ARGS))
        fill = x["fill"]
        out = jax.tree.map(
            lambda n, o: jnp.where(
                fill.reshape(fill.shape + (1,) * (n.ndim - 1)), n, o),
            new, tlb)
        slot = tlb["ptr"] % N
        want = {f: x[a] for a, f in TLB_ARGS}
        want["vpn"] = want["vpn"] >> jnp.uint64(12)
        want["ppn"] = want["ppn"] >> jnp.uint64(12)
        want["valid"] = jnp.ones_like(fill)
        wrong = sum(jnp.sum(_at(out[f], slot) !=
                            jnp.where(fill, want[f], _at(tlb[f], slot)))
                    for f in want)
        return out, wrong
    return step


def _entry_step(write):
    def step(arr, x):
        out = write(arr, x["i"], x["c"], x["w"])
        want = jnp.where(x["c"], x["w"], _at(arr, x["i"]))
        return out, jnp.sum(_at(out, x["i"]) != want)
    return step


def _loop(step, state, xs):
    """``step`` once per leading index of ``xs`` in one jitted loop;
    returns the final state and the summed read-back mismatches."""
    def body(k, carry):
        st, wrong = carry
        st, w = step(st, jax.tree.map(lambda a: a[k], xs))
        return st, wrong + w.astype(jnp.int32)
    steps = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.fori_loop(0, steps, body, (state, jnp.int32(0)))


_run = jax.jit(_loop, static_argnums=0, donate_argnums=1)


def _tlb_case(rng, B, steps):
    state = {f: rng.integers(0, hi, size=(B, N)).astype(dt)
             for f, dt, hi in TLB_FIELDS}
    state["ptr"] = rng.integers(0, 1 << 20, size=B).astype(np.int32)
    dtypes = {f: (dt, hi) for f, dt, hi in TLB_FIELDS}
    xs = {a: rng.integers(0, dtypes[f][1], size=(steps, B))
          .astype(dtypes[f][0]) for a, f in TLB_ARGS}
    xs["va"] = xs["va"] << np.uint64(12)
    xs["pa"] = xs["pa"] << np.uint64(12)
    xs["fill"] = rng.random((steps, B)) < 0.5
    want = {k: v.copy() for k, v in state.items()}
    for k in range(steps):
        rows = np.nonzero(xs["fill"][k])[0]
        slot = want["ptr"][rows] % N
        for a, f in TLB_ARGS:
            v = xs[a][k, rows]
            want[f][rows, slot] = v >> np.uint64(12) if f in ("vpn", "ppn") \
                else v
        want["valid"][rows, slot] = True
        want["ptr"][rows] += 1
    return state, xs, want


def _entry_case(rng, B, steps, n):
    state = rng.integers(0, 1 << 63, size=(B, n), dtype=np.uint64)
    xs = {"i": rng.integers(0, n, size=(steps, B)).astype(np.int32),
          "c": rng.random((steps, B)) < 0.5,
          "w": rng.integers(0, 1 << 63, size=(steps, B), dtype=np.uint64)}
    want = state.copy()
    for k in range(steps):
        rows = np.nonzero(xs["c"][k])[0]
        want[rows, xs["i"][k, rows]] = xs["w"][k, rows]
    return state, xs, want


def probe(widths, steps: int, seed: int = 2026,
          mem_words: int = programs.MEM_WORDS) -> int:
    """Print one line per (place, form, width); return the total count of
    wrong entries."""
    rng = np.random.default_rng(seed)
    places = (
        ("tlb", lambda B: _tlb_case(rng, B, steps),
         (("scatter", _tlb_step(_tlb_scatter)),
          ("select", _tlb_step(TLB.insert)))),
        ("regs", lambda B: _entry_case(rng, B, steps, 32),
         (("scatter", _entry_step(_entry_scatter)),
          ("select", _entry_step(_entry_select)))),
        ("mem", lambda B: _entry_case(rng, B, steps, mem_words),
         (("scatter", _entry_step(_entry_scatter)),
          ("select", _entry_step(_entry_select)))),
    )
    total = 0
    with x64():
        for place, make, forms in places:
            for B in widths:
                state, xs, want = make(B)
                for form, step in forms:
                    t0 = time.perf_counter()
                    out, back = jax.device_get(
                        _run(step, jax.device_put(state), xs))
                    final = sum(int(np.sum(np.asarray(o) != w)) for o, w in
                                zip(jax.tree.leaves(out),
                                    jax.tree.leaves(want)))
                    total += int(back) + final
                    print(f"{place:4s} {form:7s} B={B:5d}: read-back wrong "
                          f"{int(back)}, final wrong {final} "
                          f"({time.perf_counter() - t0:.3f} s with compile)",
                          flush=True)
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--widths", type=int, nargs="+",
                    default=[18, 1152, 2048, 4096])
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--seed", type=int, default=2026)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform}), steps {args.steps}",
          flush=True)
    wrong = probe(args.widths, args.steps, args.seed)
    print(f"{wrong} wrong entries in all")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
