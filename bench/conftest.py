"""Puts the benchmark's harness and the program on the path of its tests,
and gives them a checkout whose cells run at a tiny size on the CPU."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# Each cell as it is committed, cut so that a CPU runs it in seconds: two
# short kernels in the matrix, two lanes in the pod.
TINY = {
    "mibench-matrix-288": {"workloads": ["sha", "fft"], "harts": 8,
                           "chunk": 512, "max_ticks": 4096},
    "pod4-host64": {"pod_lanes": 2},
}
TINY_MIX = {"consolidation": {"warmup_s": 1.0, "drain_s": 60.0,
                              "kernels": ["sha", "fft", "stringsearch"]}}


def _edit(path, changes):
    with open(path) as fh:
        doc = json.load(fh)
    doc.update(changes)
    with open(path, "w") as fh:
        json.dump(doc, fh)


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's files, at tiny sizes."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, changes in TINY.items():
        path = tmp_path / "bench" / "configs" / f"{name}.json"
        if path.exists():
            _edit(path, changes)
    for name, changes in TINY_MIX.items():
        _edit(tmp_path / "bench" / "traffic" / f"{name}.json", changes)
    return tmp_path


@pytest.fixture
def run_cell(monkeypatch, capsys, tmp_path):
    """Runs a cell through the entry point's ``main`` with the platform
    check steered to the CPU; returns the last stdout line as an object.
    The compile cache is left off (a cache directory named from outside is
    taken as set up there) and the JAX setting the run changes is put
    back afterwards."""
    import jax
    from harness import cli, core

    monkeypatch.setattr(core, "PLATFORM", jax.devices()[0].platform)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",)}

    def run(root, workload, seed=7, seconds=1.0, trace=0, wrap=None):
        capsys.readouterr()
        try:
            rc = cli.main(str(root), ["--workload", workload, "--seed",
                                      str(seed), "--seconds", str(seconds),
                                      "--trace", str(trace)], wrap=wrap)
        finally:
            for k, v in keep.items():
                jax.config.update(k, v)
        out = capsys.readouterr()
        assert rc == 0
        return json.loads(out.out.strip().splitlines()[-1]), out
    return run
