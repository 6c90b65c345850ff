"""Smoke test for the typed simulation API (DESIGN.md §3).

`Fleet.boot` + `fleet.run` (on-device `lax.while_loop` early exit) must
reproduce, counter-for-counter, what the legacy host-sync chunk loop
computed over hand-stacked raw dicts — same `instret`, same
`exc_by_level`, same exit codes — on ≥2 workloads, native and guest.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.hext import machine, programs
from repro.core.hext.bits import x64
from repro.core.hext.sim import Counters, Fleet, HartState, checksum_ok

MAX_TICKS = 30000
CHUNK = 2048


def _legacy_host_loop(raw_batch, max_ticks, chunk):
    """The pre-Fleet algorithm: jitted vmapped chunk scan with a per-chunk
    `bool(jnp.all(...))` host sync — the reference for counter parity."""
    with x64():
        def body(s, _):
            return machine.step(s), None
        one = lambda s: jax.lax.scan(body, s, None, length=chunk)[0]
        chunk_fn = jax.jit(jax.vmap(one))
        t = 0
        while t < max_ticks:
            raw_batch = chunk_fn(raw_batch)
            t += chunk
            if bool(jnp.all(raw_batch["done"])):
                break
        return raw_batch


@pytest.fixture(scope="module")
def fleet_and_legacy():
    wls = [programs.BitCount(), programs.SHA()]
    guests = [False, False, True, True]
    pairs = list(zip(wls + wls, guests))

    fleet = Fleet.boot([w for w, _ in pairs], guest=guests)
    fleet.run(MAX_TICKS, chunk=CHUNK)

    with x64():
        states = [HartState.boot(w, guest=g).to_raw() for w, g in pairs]
        raw = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    raw = _legacy_host_loop(raw, MAX_TICKS, CHUNK)
    return pairs, fleet, raw


def test_fleet_matches_legacy_counters(fleet_and_legacy):
    pairs, fleet, raw = fleet_and_legacy
    for i, c in enumerate(fleet.counters()):
        assert bool(c.done), pairs[i]
        assert int(c.instret) == int(raw["instret"][i]), pairs[i]
        assert int(c.instret_virt) == int(raw["instret_virt"][i]), pairs[i]
        assert int(c.ticks) == int(raw["ticks"][i]), pairs[i]
        assert c.exc_by_level.tolist() == raw["exc_by_level"][i].tolist()
        assert c.int_by_level.tolist() == raw["int_by_level"][i].tolist()
        assert int(c.pagefaults) == int(raw["pagefaults"][i]), pairs[i]
        assert int(c.walks) == int(raw["walks"][i]), pairs[i]
        assert int(c.exit_code) == int(raw["exit_code"][i]), pairs[i]


def test_fleet_golden_checks(fleet_and_legacy):
    pairs, fleet, _ = fleet_and_legacy
    for (w, _), c in zip(pairs, fleet.counters()):
        assert c.ok(w.golden()), w.name
    report = fleet.report()
    assert set(report) == {"bitcount/native", "sha/native",
                           "bitcount/guest", "sha/guest"}
    for entry in report.values():
        assert entry["ok"] and entry["done"]


def test_to_dict_exit_code_reproduces_checksum(fleet_and_legacy):
    """A report entry must carry the exact uint64 checksum its `ok` was
    computed from (`exit_code`), so the committed benchmark records are
    self-verifying: checksum_ok(entry['exit_code'], entry['golden'])."""
    pairs, fleet, _ = fleet_and_legacy
    for (w, _), c in zip(pairs, fleet.counters()):
        d = c.to_dict(w.golden())
        assert d["exit_code"] == int(c.exit_code) & ((1 << 64) - 1)
        assert checksum_ok(d["exit_code"], w.golden()) == d["ok"]
    for entry in fleet.report().values():
        assert "exit_code" in entry
        assert checksum_ok(entry["exit_code"], entry["golden"])


def test_counters_ok_is_mod_2_64():
    # one canonical uint64 comparison: both sides reduced mod 2**64
    assert checksum_ok(0, 1 << 64)
    assert not checksum_ok(1, 1 + (1 << 63))
    # top-bit-set goldens must not be truncated by a signed/63-bit mask
    top = (1 << 63) | 5
    assert checksum_ok(top, top)
    assert not checksum_ok(top & ((1 << 63) - 1), top)
    with x64():
        z = Counters.zero()
        assert z.ok(0) and not z.ok(top)


def test_hartstate_raw_round_trip():
    st = HartState.fresh(1 << 10)
    st2 = HartState.from_raw(st.to_raw())
    leaves1 = jax.tree_util.tree_leaves(st)
    leaves2 = jax.tree_util.tree_leaves(st2)
    assert len(leaves1) == len(leaves2)
    for a, b in zip(leaves1, leaves2):
        assert a.shape == b.shape and a.dtype == b.dtype


# ---------------------------------------------------------------------------
# N-guest VMM smoke (quick CI): three tiny tenants under the scheduler
# ---------------------------------------------------------------------------

class _Const(programs.Workload):
    """Trivial tenant returning a constant — boots the full VS kernel
    (paging + demand faults) but finishes within a couple of timeslices,
    keeping this in the quick (not slow) suite."""

    def __init__(self, name, val):
        self.name, self.val = name, val

    def asm(self, a):
        a.label("workload_entry")
        a.li("a0", self.val)
        a.ret()

    def golden(self):
        return self.val


def test_three_guest_smoke():
    trio = tuple(_Const(f"c{i}", 100 + i) for i in range(3))
    fleet = Fleet.boot([trio], guests_per_hart=3, timeslice=100)
    fleet.run(20000, chunk=512)
    rep = fleet.report()["c0+c1+c2/3guest-preempt"]
    assert rep["done"] and rep["ok"]
    assert rep["guests"] == 3 and all(rep["ok_guests"])
    assert rep["checksums"] == [100, 101, 102]   # per-guest mailboxes
    assert rep["ctx_switches"] >= 2              # every tenant got the CPU
    assert rep["int_by_level"][1] == rep["timer_irqs"]


def test_preemptive_boot_rejects_mismatched_tuple_and_guest_flag():
    trio = tuple(_Const(f"c{i}", i) for i in range(3))
    with pytest.raises(ValueError):
        Fleet.boot([trio], guests_per_hart=2)    # length-3 tuple for N=2
    with pytest.raises(ValueError):
        Fleet.boot([trio[0]], guests_per_hart=3, guest=True)
    with pytest.raises(ValueError):
        Fleet.boot([trio[0]], guests_per_hart=0)
