"""Fleet-as-a-service control plane (ISSUE 10, DESIGN.md §8).

Quick tests (CI push gate, ``-m serve`` selects the family):

* policy unit tests — admission capacity, FFD bin-packing with tenant
  anti-affinity and parked-slot reservations, shed/victim decisions,
* per-guest checkpoint atomicity (kill-mid-write leaves the previous
  file intact) and schema validation,
* ``None``-slot scheduler boots (reserved holes) hit the same goldens,
* the golden invariant: daemon-served workloads finish with counters
  bit-identical to direct ``Fleet.boot`` runs (native, guest, and an
  N=2 preemptive pod),
* evict → park → resume round-trips bit-identically under capacity
  pressure,
* migration-based shed preserves goldens (N=3 pod),
* an injected hart failure (pod and solo) recovers from the last
  per-lane snapshot with zero lost completed work,
* per-lane snapshots cut from one read of each pool equal saves of the
  device slice, and a round that saves nothing reads nothing.

Slow tests (nightly): a seeded 64-submission open-loop soak with a
mid-soak hart failure — every checksum matches the registry goldens.

All quick sim tests standardize on (B=2 lanes, 32768 mem words,
chunk=512): the N=2 scheduler layout and the solo layout share one
memory size, so every pool compiles a single XLA executable.
"""
import dataclasses
import os
import pathlib

import numpy as np
import pytest

from repro.core.hext import checkpoint, programs, telemetry
from repro.core.hext.policies import (BinPackPolicy, JobView, LaneView,
                                      size_bucket, workload_footprint)
from repro.core.hext.service import (DONE, QUEUED, REJECTED,
                                     FleetService, ServiceError)
from repro.core.hext.sim import (Fleet, HartSpec, HartState, MASK64,
                                 checksum_ok)

pytestmark = pytest.mark.serve

BY_NAME = {w.name: w for w in programs.WORKLOADS + programs.WORKLOADS_EXTRA}
CHUNK = 512
SLICE = 2048


def _svc(tmp_path, **kw):
    kw.setdefault("n_harts", 2)
    kw.setdefault("guests_per_hart", 2)
    kw.setdefault("timeslice", 300)
    kw.setdefault("slice_ticks", SLICE)
    kw.setdefault("chunk", CHUNK)
    kw.setdefault("snapshot_dir", str(tmp_path / "snaps"))
    return FleetService(**kw)


# ---------------------------------------------------------------------------
# policy units (no simulation)
# ---------------------------------------------------------------------------

def test_admission_rejects_over_capacity(tmp_path):
    svc = _svc(tmp_path, policy=BinPackPolicy(max_queue=2))
    sha = BY_NAME["sha"]
    ids = [svc.submit(sha, tenant=t) for t in range(3)]
    assert [svc.job(i).state for i in ids] == [QUEUED, QUEUED, REJECTED]
    assert svc.job(ids[2]).ok is False
    assert svc.stats["rejected"] == 1
    # terminal rejection never blocks drain
    assert svc.job(ids[2]).terminal


def test_binpack_ffd_and_tenant_anti_affinity():
    pol = BinPackPolicy(partial_after=2)
    # two tenants, four jobs, mixed weights: heavy jobs seed cohorts
    # first and one tenant's jobs spread across cohorts
    q = [JobView(0, tenant=7, name="a", weight=0, age=0),
         JobView(1, tenant=7, name="b", weight=2, age=0),
         JobView(2, tenant=8, name="c", weight=2, age=0),
         JobView(3, tenant=8, name="d", weight=0, age=0)]
    cohorts = pol.pack(q, n_lanes=2, slots=2)
    assert cohorts == [[1, 2], [0, 3]] or cohorts == [[1, 2], [3, 0]]
    tenants = [{q[j].tenant for j in c} for c in cohorts]
    assert all(len(t) == 2 for t in tenants)   # never two of one tenant


def test_binpack_partial_cohorts_wait_then_boot():
    pol = BinPackPolicy(partial_after=2)
    young = [JobView(0, tenant=0, name="a", weight=0, age=0)]
    assert pol.pack(young, n_lanes=1, slots=2) == []
    old = [JobView(0, tenant=0, name="a", weight=0, age=2)]
    assert pol.pack(old, n_lanes=1, slots=2) == [[0, None]]


def test_binpack_reserved_slot_held_for_parked_guest():
    pol = BinPackPolicy(partial_after=0)
    q = [JobView(0, tenant=0, name="a", weight=0, age=5),
         JobView(1, tenant=1, name="b", weight=0, age=5)]
    cohorts = pol.pack(q, n_lanes=1, slots=2, reserved=[1])
    assert cohorts == [[0, None]]              # slot 1 stays open
    cohorts = pol.pack(q, n_lanes=2, slots=2, reserved=[0])
    assert cohorts[0] == [None, 0]             # first cohort holds slot 0
    assert 1 in cohorts[1]


def test_policy_shed_and_victim_decisions():
    pol = BinPackPolicy(shed_margin=2)
    hot = LaneView(lane=0, jobs=(10, 11, 12), free_slots=())
    cool = LaneView(lane=1, jobs=(13, None, None), free_slots=(1, 2))
    dec = pol.shed([hot, cool])
    assert (dec.src, dec.dst) == (0, 1) and dec.slot in (1, 2)
    # margin not met -> no shed
    assert pol.shed([hot, LaneView(1, (13, 14, None), (2,))]) is None
    # victim: youngest job on the most-loaded lane; never empties a hart
    lane, slot = pol.victim([hot, cool])
    assert (lane, slot) == (0, 2)              # job_id 12 is youngest
    assert pol.victim([LaneView(0, (5, None), (1,))]) is None


def test_size_buckets_span_registry():
    buckets = {w.name: size_bucket(workload_footprint(w))
               for w in programs.WORKLOADS}
    assert set(buckets.values()) == {0, 1, 2}  # registry spans all buckets
    assert buckets["sha"] == 0 and buckets["fft"] == 2


# ---------------------------------------------------------------------------
# checkpoint atomicity + guest-checkpoint schema
# ---------------------------------------------------------------------------

def _guest_regions(n=2, slot=0):
    lay = programs.sched_layout(n)
    return {name: np.full(size >> 3, 7, np.uint64)
            for name, (base, size) in zip(
                checkpoint.GUEST_REGIONS, programs.guest_regions(lay, slot))}


def test_guest_checkpoint_roundtrip(tmp_path):
    path = str(tmp_path / "g.npz")
    regions = _guest_regions()
    out = checkpoint.save_guest(path, regions, n=2, slot=0,
                                timeslice=300, workload="sha")
    got, meta = checkpoint.load_guest(out)
    assert meta["n"] == 2 and meta["slot"] == 0
    assert meta["workload"] == "sha" and meta["timeslice"] == 300
    for name in checkpoint.GUEST_REGIONS:
        np.testing.assert_array_equal(got[name], regions[name])


def test_atomic_write_kill_mid_write_keeps_old_file(tmp_path, monkeypatch):
    path = str(tmp_path / "g.npz")
    checkpoint.save_guest(path, _guest_regions(), n=2, slot=0)
    before = pathlib.Path(path).read_bytes()

    real = checkpoint.np.savez_compressed

    def dying_savez(fh, **arrays):
        real(fh, **arrays)                     # bytes hit the temp file
        raise KeyboardInterrupt("killed mid-write")

    monkeypatch.setattr(checkpoint.np, "savez_compressed", dying_savez)
    with pytest.raises(KeyboardInterrupt):
        checkpoint.save_guest(path, _guest_regions(), n=2, slot=1)
    monkeypatch.undo()
    # the original file is untouched and still loads; no temp debris
    assert pathlib.Path(path).read_bytes() == before
    regions, meta = checkpoint.load_guest(path)
    assert meta["slot"] == 0
    assert [p.name for p in tmp_path.iterdir()] == ["g.npz"]


def test_guest_checkpoint_validation(tmp_path):
    bad = _guest_regions()
    bad.pop("gtab")
    with pytest.raises(checkpoint.CheckpointError):
        checkpoint.save_guest(str(tmp_path / "a.npz"), bad, n=2, slot=0)
    wrong = _guest_regions()
    wrong["ctx"] = wrong["ctx"][:-1]
    with pytest.raises(checkpoint.CheckpointError):
        checkpoint.save_guest(str(tmp_path / "b.npz"), wrong, n=2, slot=0)
    # a fleet checkpoint is not a guest checkpoint
    st = HartState.fresh(1024)
    checkpoint.save(str(tmp_path / "fleet.npz"), st,
                    [HartSpec(None, False, "vacant")])
    with pytest.raises(checkpoint.CheckpointError):
        checkpoint.load_guest(str(tmp_path / "fleet.npz"))


# ---------------------------------------------------------------------------
# simulation: reserved holes, daemon-vs-direct identity
# ---------------------------------------------------------------------------

def test_none_slot_boot_hits_goldens():
    sha, fft = BY_NAME["sha"], BY_NAME["fft"]
    fleet = Fleet.boot([(sha, None), (None, fft)], guests_per_hart=2,
                       timeslice=300)
    fleet.run(80000, chunk=CHUNK)
    harts = fleet.harts.unwrap()
    assert bool(np.asarray(harts.counters.done).all())
    lay = programs.sched_layout(2)
    mem = np.asarray(harts.mem)
    res = lambda h, s: int(mem[h, (lay.guest_res + 8 * s) >> 3]) & MASK64
    assert checksum_ok(res(0, 0), sha.golden())
    assert res(0, 1) == 0                      # dead slot never reports
    assert checksum_ok(res(1, 1), fft.golden())
    assert res(1, 0) == 0


def test_daemon_matches_direct_bit_identical(tmp_path):
    """The golden invariant for native, guest, and N=2 preemptive pods:
    a whole-cohort lane served by the daemon ends with counters (every
    field) bit-identical to a direct ``Fleet.boot`` of the same group."""
    wl = {k: BY_NAME[k] for k in ("fft", "sha", "crc32", "stringsearch")}
    svc = _svc(tmp_path, n_solo=2, policy=BinPackPolicy(partial_after=0))
    vm_ids = [svc.submit(w, tenant=t) for t, w in enumerate(wl.values())]
    nat = svc.submit(BY_NAME["sha"], tenant=8, mode="native")
    gst = svc.submit(BY_NAME["fft"], tenant=9, mode="guest")
    svc.step()                                 # everything places round 0
    placed = {(svc.job(i).lane, svc.job(i).slot): svc.job(i).workload
              for i in vm_ids}
    groups = [tuple(placed[(lane, s)] for s in range(2)) for lane in (0, 1)]
    solo_order = [svc.job(nat).lane, svc.job(gst).lane]
    assert svc.drain(200)
    assert svc.stats["completed"] == 6 and svc.stats["failed"] == 0

    direct = Fleet.boot(groups, guests_per_hart=2, timeslice=300)
    while not bool(np.asarray(direct.harts.unwrap().counters.done).all()):
        direct.run(SLICE, chunk=CHUNK)
    got = svc._pod.harts.unwrap().counters
    want = direct.harts.unwrap().counters
    for field in dataclasses.fields(want):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field.name)),
            np.asarray(getattr(want, field.name)), err_msg=field.name)

    # solo lanes: rebuild the same native/guest boots directly
    d_nat = Fleet.boot([BY_NAME["sha"], BY_NAME["fft"]],
                       guest=[False, True])
    while not bool(np.asarray(d_nat.harts.unwrap().counters.done).all()):
        d_nat.run(SLICE, chunk=CHUNK)
    sg = svc._solo.harts.unwrap().counters
    dg = d_nat.harts.unwrap().counters
    for field in dataclasses.fields(dg):
        np.testing.assert_array_equal(
            np.asarray(getattr(sg, field.name))[solo_order],
            np.asarray(getattr(dg, field.name)), err_msg=field.name)
    for i in (nat, gst):
        assert svc.job(i).ok


# ---------------------------------------------------------------------------
# simulation: control-plane maneuvers
# ---------------------------------------------------------------------------

def test_evict_park_resume_roundtrip(tmp_path):
    """Capacity pressure parks the youngest guest as a checkpoint; the
    queued job lands once a lane drains; the parked guest resumes into
    a reserved slot and still reaches its registry golden."""
    svc = _svc(tmp_path, policy=BinPackPolicy(partial_after=1))
    for t, name in enumerate(["qsort", "bitcount", "dijkstra", "susan"]):
        svc.submit(BY_NAME[name], tenant=t)
    svc.step()
    late = svc.submit(BY_NAME["sha"], tenant=4)
    assert svc.drain(400)
    assert svc.stats["parks"] >= 1 and svc.stats["resumes"] >= 1
    assert svc.stats["completed"] == 5 and svc.stats["failed"] == 0
    parked = [j for j in svc.jobs() if any("parked" in e for e in j.events)]
    assert parked and all(j.ok for j in parked)
    assert any("resumed" in e for j in parked for e in j.events)
    assert svc.job(late).ok


def test_shed_migration_preserves_goldens(tmp_path):
    """N=3 pod: a partially-packed hot lane sheds a guest to the cool
    lane via live migration; every checksum still matches."""
    svc = _svc(tmp_path, guests_per_hart=3,
               policy=BinPackPolicy(partial_after=1, shed_margin=2))
    for t, name in enumerate(["susan", "dijkstra", "bitcount"]):
        svc.submit(BY_NAME[name], tenant=t)
    svc.step()                                 # full cohort on lane 0
    svc.submit(BY_NAME["qsort"], tenant=3)     # partial cohort on lane 1
    assert svc.drain(400)
    assert svc.stats["migrations"] >= 1
    assert svc.stats["completed"] == 4 and svc.stats["failed"] == 0
    moved = [j for j in svc.jobs() if any("migrated" in e for e in j.events)]
    assert moved and all(j.ok for j in moved)


def test_injected_hart_failure_recovers_from_snapshot(tmp_path):
    """Kill a pod lane and a solo lane mid-run: the progress monitor
    flags the stall, recovery restores the last healthy snapshot, and
    every affected guest still reaches its golden (zero lost work)."""
    svc = _svc(tmp_path, n_solo=2, snapshot_every=3, fail_after=2)
    for t, name in enumerate(["qsort", "bitcount", "dijkstra", "susan"]):
        svc.submit(BY_NAME[name], tenant=t)
    svc.submit(BY_NAME["dijkstra"], tenant=9, mode="native")
    for _ in range(4):
        svc.step()
    svc.inject_hart_failure(0, pool="pod")
    svc.inject_hart_failure(0, pool="solo")
    for _ in range(2 + svc.fail_after):
        svc.step()
    assert svc.stats["recoveries"] >= 2
    assert svc.drain(400)
    assert svc.stats["failed"] == 0
    touched = [j for j in svc.jobs()
               if any("recovered" in e for e in j.events)]
    assert touched and all(j.ok for j in touched)


def test_recovery_without_snapshot_raises(tmp_path):
    svc = _svc(tmp_path, snapshot_every=10_000, fail_after=1)
    svc.submit(BY_NAME["qsort"], tenant=0)
    svc.submit(BY_NAME["bitcount"], tenant=1)
    svc.step()
    # wipe the mutation-time snapshot, then kill the lane
    for p in pathlib.Path(svc._snapshot_dir).glob("pod-lane*.npz"):
        p.unlink()
    svc.inject_hart_failure(0, pool="pod")
    with pytest.raises(ServiceError):
        for _ in range(4):
            svc.step()


def test_stragglers_surface_stalled_lanes(tmp_path):
    svc = _svc(tmp_path, fail_after=10)        # observe but never recover
    svc.submit(BY_NAME["qsort"], tenant=0)
    svc.submit(BY_NAME["bitcount"], tenant=1)
    svc.step()
    svc.inject_hart_failure(0, pool="pod")
    svc.step()
    svc.step()
    assert ("pod", 0, svc._pod_mon.stall[0]) in svc.stragglers()


# ---------------------------------------------------------------------------
# telemetry: phase spans, read-back bytes, per-job host-clock times
# ---------------------------------------------------------------------------

PHASES = ["service.harvest", "service.recover", "service.resume",
          "service.shed", "service.evict", "service.place",
          "service.snapshot", "service.advance"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One cohort (qsort + bitcount) served to the end: round 0 (which
    provisions lane 0) recorded by a ``telemetry.Recorder``, and the
    read-back counter before and after round 1."""
    svc = _svc(tmp_path_factory.mktemp("telemetry"))
    ids = [svc.submit(BY_NAME[n], tenant=t)
           for t, n in enumerate(["qsort", "bitcount"])]
    rec = telemetry.Recorder()
    telemetry.install(rec)
    try:
        svc.step()
    finally:
        telemetry.install(None)
    before = svc.stats["readback_bytes"]
    svc.step()
    after = svc.stats["readback_bytes"]
    assert svc.drain(200)
    return svc, ids, rec, (before, after)


def test_step_records_each_phase_in_order(served):
    svc, _, rec, _ = served
    phases = sorted((it for it in rec.items if it[0].startswith("service.")),
                    key=lambda it: it[1])
    assert [n for n, _, _ in phases] == PHASES
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    _, lo, hi = phases[PHASES.index("service.place")]
    nested = [it for it in rec.items if not it[0].startswith("service.")]
    # provisioning lane 0 builds the cohort's image and splices it in
    assert sorted(n for n, _, _ in nested) == ["fleet.splice", "image.build"]
    assert all(lo <= a <= b <= hi for _, a, b in nested)


def test_job_host_clock_times_are_ordered(served):
    svc, ids, _, _ = served
    for i in ids:
        job = svc.job(i)
        assert job.state == DONE and job.ok
        assert job.t_submit <= job.t_start <= job.t_done


def test_readback_bytes_count_each_pool_array_once(served):
    """Round 1 reads the pod's memory (harvest, and again for the shed
    policy's lane views: the same array), its done flags and its instret;
    it provisions and snapshots nothing."""
    svc, _, _, (before, after) = served
    harts = svc._pod.harts.unwrap()
    assert after - before == (harts.mem.nbytes + harts.counters.done.nbytes
                              + harts.counters.instret.nbytes)
    assert svc.stats["readback_bytes"] > after


# ---------------------------------------------------------------------------
# snapshots: one read of each pool, lanes cut on the host
# ---------------------------------------------------------------------------

def _load_npz(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


@pytest.fixture(scope="module")
def snapshot_rounds(tmp_path_factory):
    """Four rounds of a pod + solo service (``snapshot_every=3``): round 0
    provisions pod lane 0 and solo lane 0 (periodic), round 1 pod lane 1
    and solo lane 1 (dirty only), round 3 is periodic.  Around each
    ``_snapshot``: the lanes the rule selects, the files written, the
    counters' moves, and each file beside what ``checkpoint.save`` writes
    from the device slice ``fleet[lane]`` at the same point."""
    tmp = tmp_path_factory.mktemp("snapshots")
    svc = _svc(tmp, n_solo=2, snapshot_every=3)
    svc.submit(BY_NAME["qsort"], tenant=0)
    svc.submit(BY_NAME["bitcount"], tenant=1)
    svc.submit(BY_NAME["dijkstra"], tenant=2, mode="native")
    real, orig_save = svc._snapshot, checkpoint.save
    rounds = []

    def snapshot():
        pools = {"pod": (svc._pod, svc._pod_lanes, svc._pod_mon,
                         svc._dirty_pod),
                 "solo": (svc._solo, svc._solo_lanes, svc._solo_mon,
                          svc._dirty_solo)}
        periodic = svc.slices % svc.snapshot_every == 0
        want = {pool: sorted(
            lane for lane, lst in enumerate(lanes)
            if lst.active and not mon.suspect(lane)
            and (periodic or lane in dirty))
            for pool, (_, lanes, mon, dirty) in pools.items()}
        saved = []

        def save(path, *args, **kw):
            saved.append(os.path.basename(path))
            return orig_save(path, *args, **kw)

        before = dict(svc.stats)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(checkpoint, "save", save)
            real()
        moved = {k: svc.stats[k] - before[k] for k in
                 ("snapshot_reads", "snapshot_lanes", "readback_bytes")}
        pairs = []
        for name in saved:
            pool, lane = name[:-len(".npz")].split("-lane")
            fleet = pools[pool][0]
            ref = str(tmp / f"ref-{svc.slices}-{name}")
            orig_save(ref, fleet[int(lane)], [fleet.specs[int(lane)]],
                      engine_name=getattr(fleet.engine, "name", "custom"))
            pairs.append((name, _load_npz(os.path.join(svc._snapshot_dir,
                                                       name)),
                          _load_npz(ref)))
        rounds.append({"periodic": periodic, "want": want, "saved": saved,
                       "moved": moved, "pairs": pairs})

    svc._snapshot = snapshot
    svc.step()
    svc.submit(BY_NAME["susan"], tenant=3)
    svc.submit(BY_NAME["crc32"], tenant=4)
    svc.submit(BY_NAME["dijkstra"], tenant=5, mode="native")
    for _ in range(3):
        svc.step()
    return rounds


def test_snapshot_files_equal_device_slice_saves(snapshot_rounds):
    """The lanes the rule selects are saved, and each file holds, array by
    array with dtypes and ``__meta__``, what saving the device slice
    writes: pod and solo lanes, in periodic and in dirty-only rounds."""
    r0, r1, _, r3 = snapshot_rounds
    assert (r0["periodic"], r1["periodic"], r3["periodic"]) == \
        (True, False, True)
    assert r0["want"] == {"pod": [0], "solo": [0]}
    assert r1["want"] == {"pod": [1], "solo": [1]}
    assert r3["want"] == {"pod": [0, 1], "solo": [0, 1]}
    for r in snapshot_rounds:
        assert sorted(r["saved"]) == sorted(
            f"{pool}-lane{lane}.npz"
            for pool, lanes in r["want"].items() for lane in lanes)
        for name, got, ref in r["pairs"]:
            assert sorted(got) == sorted(ref), name
            for key in ref:
                assert got[key].dtype == ref[key].dtype, (name, key)
                assert got[key].shape == ref[key].shape, (name, key)
                np.testing.assert_array_equal(got[key], ref[key],
                                              err_msg=f"{name} {key}")


def test_snapshot_reads_each_saving_pool_once(snapshot_rounds):
    """A round that saves lanes reads each pool that saves any once and
    counts one file per lane; a round that saves nothing reads nothing."""
    idle = 0
    for r in snapshot_rounds:
        pools = sum(bool(lanes) for lanes in r["want"].values())
        lanes = sum(len(v) for v in r["want"].values())
        assert r["moved"]["snapshot_reads"] == pools
        assert r["moved"]["snapshot_lanes"] == lanes == len(r["saved"])
        if not lanes:
            idle += 1
            assert r["moved"]["readback_bytes"] == 0
    assert idle >= 1


# ---------------------------------------------------------------------------
# slow: the seeded open-loop soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_64_submissions_all_goldens(tmp_path):
    """Drain a seeded 64-submission arrival trace (every registry
    workload, three modes, eight tenants) with one injected hart
    failure mid-soak; every checksum matches its registry golden."""
    rng = np.random.default_rng(1234)
    reg = programs.WORKLOADS
    svc = _svc(tmp_path, n_harts=4, n_solo=2, snapshot_every=3,
               policy=BinPackPolicy(max_queue=64, partial_after=2))
    arrivals = np.cumsum(rng.exponential(1.5, size=64)).astype(int)
    modes = ["vm"] * 6 + ["native", "guest"]
    k = 0
    failed_once = False
    while k < len(arrivals) or any(not j.terminal for j in svc.jobs()):
        while k < len(arrivals) and arrivals[k] <= svc.slices:
            w = reg[int(rng.integers(len(reg)))]
            m = modes[int(rng.integers(len(modes)))]
            svc.submit(w, tenant=int(rng.integers(8)), mode=m)
            k += 1
        if not failed_once and svc.slices >= 40:
            lanes = [i for i, l in enumerate(svc._pod_lanes) if l.active]
            if lanes:
                svc.inject_hart_failure(lanes[-1], pool="pod")
                failed_once = True
        svc.step()
        assert svc.slices < 5000, "soak failed to drain"
    assert failed_once and svc.stats["recoveries"] >= 1
    done = [j for j in svc.jobs() if j.state == DONE]
    assert len(done) == 64 - svc.stats["rejected"]
    assert all(j.ok for j in done)
    m = svc.metrics()
    assert m["p99_ttr_slices"] >= m["p50_ttr_slices"] > 0
