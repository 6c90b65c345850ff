"""The TPU-native 'gem5 pod': simulate a fleet of VMs in lockstep with one
vmapped step function — the DESIGN.md §2a adaptation, demonstrated through
the typed `Fleet` facade (DESIGN.md §3).

All nine MiBench-like workloads run natively AND as guests (18 machines)
inside a single jitted run: a `lax.while_loop` over chunked vmapped scans
that exits on-device as soon as every machine is done.  Per-machine
architectural counters come back as typed `Counters` records.

Run with the package on the path (see DESIGN.md §6):

    PYTHONPATH=src python examples/batched_fleet_sim.py
"""
import tempfile
import time

from repro.core.hext import programs
from repro.core.hext.engine import use_compile_cache
from repro.core.hext.sim import Fleet, MigrationError


def main():
    wls = programs.WORKLOADS
    fleet = Fleet.boot(wls + wls, guest=[False] * len(wls) + [True] * len(wls))
    print(f"fleet: {len(fleet)} machines, lockstep vmapped simulation")
    t0 = time.time()
    fleet.run(120000, chunk=8192)
    wall = time.time() - t0
    counters = fleet.counters()
    total = sum(int(c.instret) for c in counters)
    print(f"all done: {fleet.all_done}   total instructions: {total:,}   "
          f"wall: {wall:.1f}s   ({total/wall:,.0f} instr/s aggregate)")
    n = len(wls)
    for i, w in enumerate(wls):
        nat, gst = counters[i], counters[i + n]
        print(f"  {w.name:14s} native_ok={nat.ok(w.golden())} "
              f"guest_ok={gst.ok(w.golden())} "
              f"overhead={int(gst.instret)/max(int(nat.instret), 1):.2f}x")

    # the multi-tenant column (DESIGN.md §2c): two guests per hart, the HS
    # scheduler round-robins them on timer interrupts every `timeslice`
    print("\npreemptive multi-guest fleet (2 VMs per hart, timer-sliced):")
    pfleet = Fleet.boot(wls, guests_per_hart=2, timeslice=1000)
    t0 = time.time()
    pfleet.run(120000, chunk=8192)
    wall = time.time() - t0
    for label, e in pfleet.report().items():
        print(f"  {label:28s} ok={e['ok']} timer_irqs={e['timer_irqs']} "
              f"ctx_switches={e['ctx_switches']}")
    print(f"preempt fleet wall: {wall:.1f}s")

    # consolidation density (the paper's cloud story): a heterogeneous
    # 4-tenant VM per hart — every slot packs four *different* workloads,
    # each with its own G-stage table set, 64 KiB window, and htimedelta
    # virtual time base.  Reported per-guest via the mailbox checksums.
    print("\nheterogeneous 4-guest fleet (4 mixed tenants per hart):")
    quads = [tuple(wls[(i + k) % len(wls)] for k in range(4))
             for i in range(0, len(wls), 4)]
    hfleet = Fleet.boot(quads, guests_per_hart=4, timeslice=500)
    t0 = time.time()
    hfleet.run(480000, chunk=8192)
    wall = time.time() - t0
    for label, e in hfleet.report().items():
        print(f"  {label:44s} ok={e['ok']} guests_ok={e['ok_guests']} "
              f"irq={e['timer_irqs']} ctxsw={e['ctx_switches']}")
    print(f"4-guest fleet wall: {wall:.1f}s")

    # gem5-style checkpointing + live migration (DESIGN.md §3): run two
    # 2-tenant harts partway, snapshot the whole pod to a versioned .npz,
    # restore it, then evacuate one mid-flight VM from hart 0 to hart 1 —
    # its saved context / G-stage tables / 64 KiB window move wholesale,
    # and the guest still hits its golden checksum on the new hart.
    print("\ncheckpoint/restore + live migration (crc32 evacuates "
          "hart 0 → hart 1):")
    sha, crc, bits, fft = (programs.SHA(), programs.CRC32(),
                           programs.BitCount(), programs.FFT())
    mfleet = Fleet.boot([(sha, crc), (bits, fft)], guests_per_hart=2,
                        timeslice=300)
    mfleet.run(1000, chunk=1024)
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/pod.npz"
        mfleet.snapshot(path)
        print(f"  snapshot taken mid-run → {path}")
        mfleet = Fleet.restore(path)              # resumes bit-identically
    for _ in range(12):                           # wait until descheduled
        try:
            mfleet.migrate_guest(0, 1, guest=1)
            print("  migrated: hart 0 guest 1 (crc32) → hart 1 slot 1")
            break
        except MigrationError:
            mfleet.run(300, chunk=1024)
    else:
        print("  WARNING: guest never became migratable — demo skipped "
              "the move; reports below are for the unmigrated fleet")
    mfleet.run(120000, chunk=1024)
    for label, e in mfleet.report().items():
        print(f"  {label:32s} ok={e['ok']} guests_ok={e['ok_guests']} "
              f"checksums={[hex(c) for c in e['checksums']]}")


if __name__ == "__main__":
    use_compile_cache()
    main()
