"""Pluggable Engine backends + gem5-style checkpoint/restore (ISSUE 5).

Quick tests (CI push gate):
* engine registry resolution / rejection,
* the OracleEngine differential smoke (jit vs oracle on a native+guest
  pair, field-by-field diff empty),
* a checkpoint round-trip smoke (snapshot mid-run → restore → resume ==
  uninterrupted, bit for bit),
* corrupted / schema-mismatched snapshots rejected,
* the `fleet.harts` stale-donated-buffer guard.

Slow tests (nightly / full suite):
* all three engines run the 9-workload native/guest matrix with counters
  bit-identical to the committed `hext_runs.json` goldens,
* snapshot-resume bit-identity for native, guest, and an N=4 preemptive
  slot,
* true multi-device ShardedEngine runs (subprocesses with forced host
  devices) matching JitEngine per hart: a padded fleet laid out on the
  mesh, and a native/guest matrix built on the mesh that also matches the
  oracle's goldens,
* the live-migration demo: a mid-flight guest moves harts and still hits
  its golden checksum on the destination.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.hext import checkpoint, engine, programs
from repro.core.hext.bits import x64
from repro.core.hext.sim import (Fleet, MigrationError, StaleHartsError,
                                 MASK64, checksum_ok)

REPO = pathlib.Path(__file__).resolve().parents[2]
GOLDENS = REPO / "bench" / "reference" / "mibench-goldens.json"
CHUNK = 1024


def _boot_sha_pair(engine_name=None):
    wl = programs.SHA()
    return Fleet.boot([wl, wl], guest=[False, True], engine=engine_name)


def _assert_states_identical(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    with x64():
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_engine_registry_resolution():
    assert engine.resolve(None).name == "jit"
    assert engine.resolve("jit").name == "jit"
    assert engine.resolve("sharded").name == "sharded"
    assert engine.resolve("oracle").name == "oracle"
    inst = engine.JitEngine(donate=False)
    assert engine.resolve(inst) is inst           # instances pass through
    with pytest.raises(ValueError, match="unknown engine"):
        engine.resolve("warp-drive")
    with pytest.raises(TypeError):
        engine.resolve(42)
    # Fleet plumbs the selection through
    assert _boot_sha_pair("oracle").engine.name == "oracle"
    assert _boot_sha_pair().engine.name == "jit"


# ---------------------------------------------------------------------------
# OracleEngine differential smoke (the CI push gate)
# ---------------------------------------------------------------------------

def test_oracle_engine_differential_smoke():
    """The same native+guest pair through the jit and oracle backends must
    agree on every architectural field — the oracle models the software
    TLB too, so `walks` is in scope — and both hit the workload golden."""
    golden = programs.SHA().golden()
    fj = _boot_sha_pair().run(30000, chunk=CHUNK)
    fo = _boot_sha_pair("oracle").run(30000, chunk=CHUNK)
    for i in range(2):
        assert engine.diff_states(fj[i], fo[i]) == [], f"hart {i}"
        assert fj[i].counters.ok(golden) and fo[i].counters.ok(golden)
    # the oracle independently reproduced the machine's TLB-miss count
    assert int(fj[0].counters.walks) > 0
    assert int(fo[0].counters.walks) == int(fj[0].counters.walks)


# ---------------------------------------------------------------------------
# checkpoint round-trip (the CI push gate)
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_smoke(tmp_path):
    """snapshot mid-run → restore → resume must be bit-identical to an
    uninterrupted run (every leaf: counters, memory, TLB, CSRs)."""
    ref = _boot_sha_pair().run(30000, chunk=CHUNK)
    part = _boot_sha_pair().run(600, chunk=CHUNK)     # mid-run (not done)
    assert not part.all_done
    path = tmp_path / "fleet.npz"
    part.snapshot(path)
    resumed = Fleet.restore(path)
    resumed.run(30000, chunk=CHUNK)
    _assert_states_identical(ref.harts.unwrap(), resumed.harts.unwrap())
    # specs survived by name: the report still carries golden checks
    rep = resumed.report()
    assert rep["sha/native"]["ok"] and rep["sha/guest"]["ok"]
    assert rep["sha/guest"]["exit_code"] == \
        int(programs.SHA().golden()) & MASK64


def test_checkpoint_rejects_corruption_and_schema_mismatch(tmp_path):
    fleet = _boot_sha_pair()                      # boot only — no run
    path = tmp_path / "ok.npz"
    fleet.snapshot(path)
    Fleet.restore(path)                           # sanity: loads clean

    # truncated file
    blob = path.read_bytes()
    trunc = tmp_path / "trunc.npz"
    trunc.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(checkpoint.CheckpointError):
        Fleet.restore(trunc)

    # not a checkpoint at all
    junk = tmp_path / "junk.npz"
    junk.write_bytes(b"\x00" * 512)
    with pytest.raises(checkpoint.CheckpointError):
        Fleet.restore(junk)

    def rewrite(dst, mutate_meta=None, drop=None):
        with np.load(path, allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files if k != "__meta__"}
            meta = json.loads(str(z["__meta__"][()]))
        if mutate_meta:
            mutate_meta(meta)
        if drop:
            arrays.pop(drop)
        np.savez_compressed(dst, __meta__=np.array(json.dumps(meta)),
                            **arrays)

    # wrong version
    vbad = tmp_path / "vbad.npz"
    rewrite(vbad, mutate_meta=lambda m: m.update(version=999))
    with pytest.raises(checkpoint.CheckpointError, match="version"):
        Fleet.restore(vbad)

    # missing field → schema hash no longer matches the arrays
    fbad = tmp_path / "fbad.npz"
    rewrite(fbad, drop="csrs")
    with pytest.raises(checkpoint.CheckpointError):
        Fleet.restore(fbad)

    # tampered schema hash
    hbad = tmp_path / "hbad.npz"
    rewrite(hbad, mutate_meta=lambda m: m.update(
        schema_sha256="0" * 64))
    with pytest.raises(checkpoint.CheckpointError, match="schema"):
        Fleet.restore(hbad)

    # spec count mismatch on explicit override
    with pytest.raises(ValueError):
        Fleet.restore(path, specs=fleet.specs[:1])


class _CustomWl(programs.Workload):
    name = "notinregistry"

    def asm(self, a):
        a.label("workload_entry")
        a.li("a0", 1234)
        a.ret()

    def golden(self):
        return 1234


def test_restore_unknown_workload_needs_explicit_specs(tmp_path):
    """Custom workloads can't travel by name: the restored spec carries
    workload=None (no golden check) unless the caller passes specs."""
    wl = _CustomWl()
    fleet = Fleet.boot([wl, wl], guest=[False, True])
    path = tmp_path / "custom.npz"
    fleet.snapshot(path)
    restored = Fleet.restore(path)
    assert all(s.workload is None for s in restored.specs)
    assert "ok" not in restored.report()["notinregistry/native"]
    explicit = Fleet.restore(path, specs=fleet.specs)
    assert explicit.specs[0].workload is wl


def test_restore_preemptive_unknown_guest_rejected(tmp_path):
    """A preemptive spec with an unresolvable guest name must NOT decode
    to None (the report layer reads None as 'migrated away' and would
    mis-total the expected checksum) — restore demands explicit specs."""
    wl = _CustomWl()
    fleet = Fleet.boot([(wl, programs.SHA())], guests_per_hart=2,
                       timeslice=300)
    path = tmp_path / "pcustom.npz"
    fleet.snapshot(path)
    with pytest.raises(checkpoint.CheckpointError, match="registry"):
        Fleet.restore(path)
    explicit = Fleet.restore(path, specs=fleet.specs)
    assert explicit.specs[0].guests[0] is wl


# ---------------------------------------------------------------------------
# stale-donated-buffer guard
# ---------------------------------------------------------------------------

def test_stale_harts_reference_raises():
    fleet = _boot_sha_pair()
    view = fleet.harts
    _ = view.pc                                   # live before the run
    fleet.run(2000, chunk=CHUNK)
    with pytest.raises(StaleHartsError, match="generation"):
        _ = view.pc
    with pytest.raises(StaleHartsError):
        view.unwrap()
    fresh = fleet.harts                           # re-read after the run
    assert np.asarray(fresh.pc).shape == (2,)
    assert fresh.unwrap() is fleet.harts.unwrap()
    # a rejected migration does NOT bump the generation
    with pytest.raises(MigrationError):
        fleet.migrate_guest(0, 1)                 # not preemptive slots
    _ = fresh.pc                                  # still live


# ---------------------------------------------------------------------------
# ShardedEngine
# ---------------------------------------------------------------------------

def test_sharded_engine_fallback_matches_jit():
    """On a single device ShardedEngine must fall back to the jit path and
    produce identical results (on a forced multi-device host this instead
    exercises the shard_map path — equally required to match)."""
    fj = _boot_sha_pair().run(30000, chunk=CHUNK)
    fs = _boot_sha_pair("sharded").run(30000, chunk=CHUNK)
    for i in range(2):
        assert engine.diff_states(fs[i], fj[i]) == []
        assert int(fs[i].counters.walks) == int(fj[i].counters.walks)


def test_instrs_per_step_bit_identical():
    """The multi-instruction dispatch knob (DESIGN.md §7d) unrolls N
    architectural ticks per scan element — every counter and every
    architectural field must be bit-identical to the N=1 engine."""
    fj = _boot_sha_pair().run(30000, chunk=CHUNK)
    for ips in (2, 8):
        eng = engine.JitEngine(instrs_per_step=ips)
        fu = Fleet.boot([programs.SHA()] * 2, guest=[False, True],
                        engine=eng).run(30000, chunk=CHUNK)
        for i in range(2):
            assert engine.diff_states(fu[i], fj[i]) == [], f"ips={ips}"
            _assert_states_identical(fu[i], fj[i])
    with pytest.raises(ValueError, match="instrs_per_step"):
        engine._check_ips(CHUNK, 3)       # 1024 % 3 != 0


def _run_on_four_devices(script: str) -> str:
    """Run ``script`` in a child process that sees 4 forced CPU devices
    (the device count is fixed when JAX starts); returns its stdout."""
    env = dict(os.environ)
    # the child must never reach for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, cwd=str(REPO), capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.slow
def test_sharded_engine_multi_device_matches_jit():
    """The shard_map path: 4 forced host devices, 6 harts padded to 8 and
    laid out on the mesh inside an ``engine.place`` span.  Per-hart
    results must be bit-identical to the jit engine, and the padding must
    not show in the result."""
    out = _run_on_four_devices("""
        import numpy as np, jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.hext.sim import Fleet
        from repro.core.hext import engine, programs, telemetry

        def img(val):
            a = programs.Asm(0)
            a.li('a0', val)
            a.li('t6', 0x10000008)
            a.sd('a0', 0, 't6')
            a.label('sp'); a.j('sp')
            im = programs.Image(256)
            im.place_code(0, a.assemble())
            return im.mem

        imgs = [img(100 + i) for i in range(6)]
        fj = Fleet.from_images(imgs, mem_words=256).run(512, chunk=128)
        eng = engine.ShardedEngine()
        rec = telemetry.Recorder()
        telemetry.install(rec)
        try:
            fs = Fleet.from_images(imgs, mem_words=256,
                                   engine=eng).run(512, chunk=128)
        finally:
            telemetry.install(None)
        assert len(fs) == 6
        for i in range(6):
            assert engine.diff_states(fs[i], fj[i]) == [], i
            assert int(fs[i].counters.walks) == int(fj[i].counters.walks)
            assert int(fs[i].counters.exit_code) == 100 + i
        assert [n for n, _, _ in rec.items] == ['engine.place']
        # 8 rows of the 6-hart state were laid out
        row = sum(x.nbytes for x in jax.tree.leaves(fj.harts.unwrap())) // 6
        assert eng.stats['place_bytes'] == 8 * row, eng.stats
        # the last shard holds the two padding harts: done from the start
        assert eng.stats['shard_chunks'] == [1, 1, 1, 0], eng.stats
        print('SHARDED-MULTI-OK')
    """)
    assert "SHARDED-MULTI-OK" in out


@pytest.mark.slow
def test_sharded_engine_matrix_on_the_mesh_matches_jit_and_goldens():
    """A native/guest matrix (sha, fft; 8 harts) built on the mesh with
    ``ShardedEngine.sharding`` runs twice where it lies: nothing is laid
    out, its memory stays on all four devices, each shard's chunk-scans
    are its own harts' count, and the result equals the jit engine's in
    every field and the oracle's goldens in every counter."""
    out = _run_on_four_devices(f"""
        import json
        import numpy as np, jax
        from repro.core.hext import engine, programs
        from repro.core.hext.bits import x64
        from repro.core.hext.sim import Fleet

        CHUNK = 512
        ref = json.load(open({str(GOLDENS)!r}))["workloads"]
        jobs = [(w, g) for g in (False, True)
                for w in (programs.SHA(), programs.FFT())] * 2

        def boot(eng=None):
            return Fleet.boot([w for w, _ in jobs],
                              guest=[g for _, g in jobs], engine=eng)

        fj = boot().run(120000, chunk=CHUNK)
        eng = engine.ShardedEngine()
        base = boot()
        with x64():
            state = jax.device_put(base.harts.unwrap(), eng.sharding)
        fs = Fleet(state, base.specs, engine=eng)
        own = [0] * 4
        for budget in (2 * CHUNK, 120000):
            with x64():
                before = np.asarray(fs.harts.unwrap().counters.ticks)
            fs.run(budget, chunk=CHUNK)
            with x64():
                adv = np.asarray(fs.harts.unwrap().counters.ticks) - before
            for s in range(4):
                own[s] += -(-int(adv[2 * s:2 * s + 2].max()) // CHUNK)
        assert eng.stats["place_bytes"] == 0, eng.stats
        assert eng.stats["shard_chunks"] == own, (eng.stats, own)
        with x64():
            mem = fs.harts.unwrap().mem
            held = sorted(s.device.id for s in mem.addressable_shards)
            assert held == [0, 1, 2, 3], held
            assert mem.sharding.is_equivalent_to(eng.sharding, mem.ndim)
        a = engine.state_arrays(fs.harts.unwrap())
        b = engine.state_arrays(fj.harts.unwrap())
        assert [k for k in a if not np.array_equal(a[k], b[k])] == []
        for (w, g), c in zip(jobs, fs.counters()):
            got = c.to_dict()
            want = ref[w.name]["guest" if g else "native"]
            assert {{k: got[k] for k in want}} == want, (w.name, g)
            assert got["exit_code"] == ref[w.name]["checksum"]
        print("SHARDED-MATRIX-OK", own)
    """)
    assert "SHARDED-MATRIX-OK" in out


# ---------------------------------------------------------------------------
# acceptance: all three engines × the 9-workload native/guest matrix
# ---------------------------------------------------------------------------

def _committed_workloads():
    path = REPO / "benchmarks" / "results" / "hext_runs.json"
    return json.loads(path.read_text())["workloads"]


_GOLDEN_KEYS = ("instret", "instret_virt", "ticks", "exc_by_level",
                "int_by_level", "pagefaults", "timer_irqs", "ctx_switches",
                "exit_code")


@pytest.mark.slow
def test_all_engines_match_committed_goldens():
    """jit, sharded, and oracle all run the full native/guest matrix with
    counters bit-identical to the committed hext_runs.json (the oracle
    skips only the microarchitectural `walks`)."""
    ref = _committed_workloads()
    wls = programs.WORKLOADS
    flags = [False] * len(wls) + [True] * len(wls)

    def matrix(engine_name):
        return Fleet.boot(wls + wls, guest=flags,
                          engine=engine_name).run(120000, chunk=8192)

    fleets = {name: matrix(name) for name in ("jit", "sharded", "oracle")}
    for name, fleet in fleets.items():
        rep = fleet.report()
        for i, w in enumerate(wls):
            for mode in ("native", "guest"):
                got = rep[f"{w.name}/{mode}"]
                assert got["ok"], (name, w.name, mode)
                for key in _GOLDEN_KEYS:
                    assert got[key] == ref[w.name][mode][key], \
                        (name, w.name, mode, key)
                if name != "oracle":              # walks: device-only
                    assert got["walks"] == ref[w.name][mode]["walks"], \
                        (name, w.name, mode)


# ---------------------------------------------------------------------------
# snapshot-resume bit-identity per workload class
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_snapshot_resume_bit_identical_native_and_guest(tmp_path):
    wl = programs.CRC32()

    def boot():
        return Fleet.boot([wl, wl], guest=[False, True])

    ref = boot().run(30000, chunk=CHUNK)
    part = boot().run(1200, chunk=CHUNK)
    assert not part.all_done                      # genuinely mid-run
    path = tmp_path / "crc.npz"
    part.snapshot(path)
    resumed = Fleet.restore(path).run(30000, chunk=CHUNK)
    _assert_states_identical(ref.harts.unwrap(), resumed.harts.unwrap())
    rep = resumed.report()
    assert rep["crc32/native"]["ok"] and rep["crc32/guest"]["ok"]


@pytest.mark.slow
def test_snapshot_resume_bit_identical_n4_preemptive(tmp_path):
    quad = (programs.SHA(), programs.FFT(), programs.CRC32(),
            programs.BitCount())

    def boot():
        return Fleet.boot([quad], guests_per_hart=4, timeslice=300)

    ref = boot().run(120000, chunk=2048)
    part = boot().run(6000, chunk=2048)
    assert not part.all_done
    path = tmp_path / "quad.npz"
    part.snapshot(path)
    resumed = Fleet.restore(path).run(120000, chunk=2048)
    _assert_states_identical(ref.harts.unwrap(), resumed.harts.unwrap())
    rep = resumed.report()["sha+fft+crc32+bitcount/4guest-preempt"]
    assert rep["ok"] and all(rep["ok_guests"])
    assert rep["guests"] == 4


# ---------------------------------------------------------------------------
# live migration demo
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_migrate_guest_mid_run_hits_golden_on_destination():
    """crc32 starts on hart 0, is migrated mid-flight into hart 1's slot 1
    (vaporizing the fft tenant there), and must still hit its golden on
    the destination — proof the copied window/context/tables carried the
    running VM.  The source hart finishes with only sha checked."""
    sha, crc, bits, fft = (programs.SHA(), programs.CRC32(),
                           programs.BitCount(), programs.FFT())
    fleet = Fleet.boot([(sha, crc), (bits, fft)], guests_per_hart=2,
                       timeslice=300)
    fleet.run(1000, chunk=CHUNK)
    assert not fleet.all_done

    # retry until guest 1 is descheduled on both harts (deterministic but
    # phase-dependent; a few extra slices always suffice)
    for _ in range(12):
        try:
            fleet.migrate_guest(0, 1, guest=1)
            break
        except MigrationError:
            fleet.run(300, chunk=CHUNK)
    else:
        pytest.fail("guest 1 never became migratable")

    assert fleet.specs[0].guests[1] is None
    assert fleet.specs[1].guests[1] is crc
    fleet.run(120000, chunk=CHUNK)
    rep = fleet.report()

    src = rep["sha+moved/2guest-preempt"]
    assert src["done"] and src["ok"]
    assert src["ok_guests"] == [True, None]
    assert src["checksums"][1] == 0               # mailbox zeroed on exit
    assert src["golden"] == int(sha.golden()) & MASK64
    assert checksum_ok(src["exit_code"], sha.golden())

    dst = rep["bitcount+crc32/2guest-preempt"]
    assert dst["done"] and dst["ok"]
    assert dst["ok_guests"] == [True, True]
    assert dst["checksums"][1] == int(crc.golden()) & MASK64
    total = (int(bits.golden()) + int(crc.golden())) & MASK64
    assert checksum_ok(dst["exit_code"], total)


def test_migrate_guest_precondition_errors():
    sha = programs.SHA()
    fleet = Fleet.boot([(sha, sha), (sha, sha)], guests_per_hart=2,
                       timeslice=300)
    with pytest.raises(MigrationError, match="different"):
        fleet.migrate_guest(0, 0, guest=0)
    with pytest.raises(MigrationError, match="out of range"):
        fleet.migrate_guest(0, 1, guest=5)
    # at boot the hart is still in M firmware (V=0): refuse — whenever
    # the scheduler (or firmware) owns the hart a context switch may be
    # in flight, so SCHED_CUR / context slots are not authoritative
    with pytest.raises(MigrationError, match="V=0"):
        fleet.migrate_guest(0, 1, guest=0)
    plain = _boot_sha_pair()
    with pytest.raises(MigrationError, match="preemptive"):
        plain.migrate_guest(0, 1)
