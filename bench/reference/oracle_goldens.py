"""Make the matrix cells' reference with the pure-Python oracle.

    python3 bench/reference/oracle_goldens.py [--check]

For each MiBench kernel of the paper's matrix, native and as one VS
guest, the kernel's boot image is run by ``repro.core.hext.oracle`` (the
architectural model in plain Python, which shares no code with the JAX
tick pipeline it checks) until the hart is done, and its counters are
written to ``mibench-goldens.json`` beside each kernel's checksum as its
Python ``golden()`` computes it.  ``--check`` writes nothing and exits
non-zero where the committed file differs.  Needs no accelerator; the
benchmark's runs read the committed file and never run this.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.hext import oracle, programs  # noqa: E402

OUT = os.path.join(HERE, "mibench-goldens.json")
MASK64 = (1 << 64) - 1
# far past the longest job (25,363 ticks); a hart is done long before
MAX_TICKS = 120000
COUNTERS = ["done", "exit_code", "instret", "instret_virt", "ticks",
            "exc_by_level", "int_by_level", "pagefaults", "walks",
            "timer_irqs", "ctx_switches"]


def row(workload, guest: bool) -> dict:
    st = oracle.run(programs.build_image(workload, guest), MAX_TICKS)
    out = {k: st[k] for k in COUNTERS}
    out["done"] = bool(out["done"])
    out["exit_code"] = int(out["exit_code"]) & MASK64
    return out


def make() -> dict:
    workloads = {}
    for w in programs.WORKLOADS:
        rows = {mode: row(w, mode == "guest") for mode in ("native", "guest")}
        # build_image wrote the kernel's input data, which golden() reads
        workloads[w.name] = {"checksum": int(w.golden()) & MASK64, **rows}
    return {"about": "Reference of the paper's matrix (9 MiBench kernels, "
                     "native and as one VS guest): each job's counters as "
                     "the pure-Python oracle (repro.core.hext.oracle) "
                     "computes them from the job's boot image, and each "
                     "kernel's checksum as its Python golden() computes "
                     "it. Made by bench/reference/oracle_goldens.py.",
            "counters": COUNTERS, "workloads": workloads}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="compare with the committed file, write nothing")
    args = ap.parse_args(argv)
    doc = make()
    if args.check:
        with open(OUT) as fh:
            same = json.load(fh) == doc
        print("same as the committed reference" if same else
              "DIFFERS from the committed reference")
        return 0 if same else 1
    with open(OUT, "w") as fh:
        json.dump(doc, fh, indent=1)
        fh.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
