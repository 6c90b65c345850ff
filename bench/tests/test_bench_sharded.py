"""The four-chip batch cell at a tiny size on 4 forced CPU devices: the
result line, the per-layer metrics of a traced run, ``correct`` turning
false for one corrupted counter, a program whose ``ShardedEngine`` has no
mesh of its own, and each new reader on records that lack what it reads.

The device count is fixed when JAX starts, so the cell runs in one child
process that steers the platform check to the CPU and prints one result
line per run."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from harness import trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "matrix.batch.x4"
CONFIG = "mibench-matrix-1152x4"
# two short kernels, native and guest, twice: 2 harts a device
TINY = {"workloads": ["sha", "fft"], "harts": 8, "chunk": 512,
        "max_ticks": 4096}
X4 = ("loop_tick_us.x4", "shard_balance.x4", "host_share.x4",
      "device_idle.x4")

CHILD = """
import dataclasses, json, sys
sys.path[:0] = [{src!r}, {bench!r}]
import jax
import jax.numpy as jnp
from harness import cli, core
from repro.core.hext import engine as hext_engine
from repro.core.hext.bits import x64

assert len(jax.devices()) == 4, jax.devices()
core.PLATFORM = jax.devices()[0].platform


class Corrupt:
    # one counter of one hart altered after each engine run
    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name

    def run(self, state, max_ticks, chunk=4096):
        out = self.inner.run(state, max_ticks, chunk=chunk)
        with x64():
            c = out.counters
            return out.replace(counters=dataclasses.replace(
                c, instret=c.instret.at[0].add(1)))


class Bare:
    # a sharded engine with neither a mesh of its own nor stats
    name = "sharded"

    def __init__(self, devices=None):
        self.inner = hext_engine.JitEngine()

    def run(self, state, max_ticks, chunk=4096):
        return self.inner.run(state, max_ticks, chunk=chunk)


def main(name, trace, wrap=None, seed=2 ** 31 + 17):
    rc = cli.main({root!r}, ["--workload", {cell!r}, "--seed", str(seed),
                             "--seconds", "1.0", "--trace", str(trace)],
                  wrap=wrap)
    assert rc == 0
    print("RESULT " + name + " " + json.dumps(RESULTS.pop()), flush=True)


RESULTS = []
emit = core.emit
core.emit = lambda result: (RESULTS.append(result), emit(result))
main("plain", 0)
main("traced", 1)
main("corrupt", 0, wrap=Corrupt)
hext_engine.ShardedEngine = Bare
main("bare", 1)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    """Each run's result line and the child's whole standard output."""
    root = tmp_path_factory.mktemp("x4")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    path = root / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(TINY)
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(root / "cache"))
    code = CHILD.format(src=os.path.join(ROOT, "src"), bench=BENCH,
                        root=str(root), cell=CELL)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            _, name, body = line.split(" ", 2)
            out[name] = json.loads(body)
    return out, r.stdout


def test_x4_cell_result_line(results):
    res = results[0]["plain"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"hart_ticks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] % 8 == 0 and res["attempted"] > 0
    assert res["checks"] == {"counter_mismatches": {"value": 0, "limit": 0},
                             "wrong_checksums": {"value": 0, "limit": 0}}
    assert res["device"]["count"] == 4
    assert "compiles inside the window: 0" in results[1]


def test_x4_cell_traced_run(results):
    res = results[0]["traced"]
    # the CPU has no TPU plane to trace, so the device's share is absent
    assert set(res["metrics"]) == set(X4) - {"device_idle.x4"}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < m["shard_balance.x4"] <= 100
    assert 0 < m["host_share.x4"] < 100
    assert m["loop_tick_us.x4"] > 0
    assert res["correct"] is True
    # every batch is born on the mesh: the engine lays nothing out
    assert "place_bytes=0," in results[1]


def test_a_corrupted_counter_is_not_correct(results):
    res = results[0]["corrupt"]
    assert res["correct"] is False
    # one hart a batch
    assert res["checks"]["counter_mismatches"]["value"] == \
        res["attempted"] // 8 == res["failed"]
    assert res["checks"]["wrong_checksums"]["value"] == 0


def test_an_engine_without_a_mesh_leaves_its_metrics_out(results):
    """The cell runs a program whose ``ShardedEngine`` predates the
    mesh: batches on one device, and no reading of what it lacks."""
    res = results[0]["bare"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"loop_tick_us.x4"}


def _reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", X4)
def test_x4_readers_read_nothing_from_other_records(name):
    read = _reader(name)
    batch = {"window_s": 50.0, "engine_s": 49.0, "loop_ticks": 8192,
             "hart_ticks": 10, "trace": {"busy_s": 0.5, "window_s": 1.0}}
    assert read(batch) is None
    bare = dict(batch, shards=4, loop_ticks=0, place_s=None,
                shard_chunks=None, trace=None)
    assert read(bare) is None


def test_x4_readers_on_a_record():
    rec = {"shards": 4, "window_s": 50.0, "engine_s": 48.0, "place_s": 0.5,
           "loop_ticks": 65536, "shard_chunks": [[4, 4, 3, 4], [4, 2, 4, 4]],
           "trace": trace.reduce({
               "window": [0, 100], "host": [],
               "devices": {f"/device:TPU:{i}": {
                   "names": ["%fusion"], "start_ns": [0],
                   "end_ns": [100 - 10 * i]} for i in range(4)}})}
    assert _reader("loop_tick_us.x4")(rec) == pytest.approx(
        1e6 * 48.0 / 65536)
    assert _reader("shard_balance.x4")(rec) == pytest.approx(
        100 * (15 / 4 + 14 / 4) / 8)
    assert _reader("host_share.x4")(rec) == pytest.approx(100 * 2.5 / 50)
    # idle 0, 10, 20 and 30 of 100 on the four devices
    assert _reader("device_idle.x4")(rec) == pytest.approx(15.0)
