"""BENCHMARK.json and the files it names, the traffic generator, the copied
reference, and how the harness finds a cell's files by name.  No fleet is
compiled here."""
import collections
import importlib.util
import itertools
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec, traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keys_and_names(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"]
    assert doc["command"] == ["python3", "bench/run.py"]
    assert isinstance(doc["run_seconds"], int) and \
        1 <= doc["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in doc[k]}) == len(doc[k])
    metrics = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024


def test_configs_name_their_files_and_cuts(doc):
    used = {c["config"] for c in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        assert _one_line(c["source"]) and _one_line(c["why"])
        with open(ROOT / c["file"]) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        assert os.path.exists(ROOT / cfg["reference"])
        assert os.path.exists(BENCH / "harness" / f"{cfg['kind']}.py")
    assert len({c["file"] for c in doc["configs"]}) == len(doc["configs"])


def test_cells_and_their_metrics(doc):
    bench = spec.Bench(str(ROOT))
    pairs = set()
    four = 0
    for cell in doc["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and _one_line(cell["why"])
        four += cell["chips"] == 4
        pairs.add((cell["config"], cell["traffic"]))
        mix = bench.traffic(cell["traffic"])
        assert mix["kind"] in ("closed_batches", "closed_jobs")
        e2e = bench.metrics(cell["name"], trace=False)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert bench.metrics(cell["name"], trace=True)
    assert len(pairs) == len(doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 2)
    cells = {c["name"] for c in doc["workloads"]}
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    layers = collections.defaultdict(set)
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
        layers[m["layer"].lower()].add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(bench.reader(m["name"]))


def test_reference_is_the_oracles():
    """The matrix's reference is what the pure-Python oracle computes from
    each job's boot image, with each kernel's own Python checksum."""
    path = BENCH / "reference" / "oracle_goldens.py"
    spec_ = importlib.util.spec_from_file_location("oracle_goldens", path)
    oracle_goldens = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(oracle_goldens)
    with open(BENCH / "reference" / "mibench-goldens.json") as fh:
        assert json.load(fh) == oracle_goldens.make()


def test_reference_agrees_with_the_programs_goldens():
    """The oracle's counters equal the program's committed counter goldens
    and each job's exit code is its kernel's checksum."""
    with open(BENCH / "reference" / "mibench-goldens.json") as fh:
        ref = json.load(fh)
    with open(ROOT / "benchmarks" / "results" / "hext_runs.json") as fh:
        runs = json.load(fh)
    for name, mine in ref["workloads"].items():
        for mode in ("native", "guest"):
            assert mine[mode]["exit_code"] == mine["checksum"]
            for k in ref["counters"]:
                assert mine[mode][k] == runs["workloads"][name][mode][k]


MIX = {"kind": "closed_jobs", "kernels": ["a", "b", "c", "d"]}


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 7, 2 ** 40 + 3, -5])
def test_closed_jobs_same_work_in_another_order(seed):
    take = lambda s: list(itertools.islice(traffic.closed_jobs(MIX, s), 40))
    a = take(seed)
    assert a == take(seed)
    for k in range(0, 40, 4):
        assert sorted(a[k:k + 4]) == MIX["kernels"]
    assert a != take(12345)
    with pytest.raises(ValueError):
        next(traffic.closed_jobs({"kind": "open"}, seed))


def test_batch_orders_are_permutations_of_the_tiled_jobs():
    mix = {"kind": "closed_batches"}
    it = traffic.batch_orders(mix, 2 ** 31 + 99, 18, 288)
    a, b = next(it), next(it)
    assert sorted(a.tolist()) == sorted(b.tolist()) == \
        sorted(list(range(18)) * 16)
    assert a.tolist() != b.tolist()
    again = next(traffic.batch_orders(mix, 2 ** 31 + 99, 18, 288))
    assert again.tolist() == a.tolist()
    with pytest.raises(ValueError):
        next(traffic.batch_orders(mix, 1, 18, 100))


def _copy_bench(dst: pathlib.Path) -> pathlib.Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = _copy_bench(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/mibench-matrix-288.json")
                     .read_text())
    cfg.update(name="matrix-small", harts=36)
    (root / "bench/configs/matrix-small.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/batch2.json").write_text(
        json.dumps({"kind": "closed_batches"}))
    (root / "bench/metrics/batches.small.py").write_text(
        "def read(rec):\n    return rec.get('batches')\n")
    doc["configs"].append({"name": "matrix-small", "source": "x",
                           "file": "bench/configs/matrix-small.json",
                           "reduced": cfg["reduced"], "why": "y"})
    doc["workloads"].append({"name": "small.batch", "config": "matrix-small",
                             "traffic": "batch2", "chips": 1, "why": "z"})
    doc["end_to_end"][0]["workloads"].append("small.batch")
    doc["per_layer"].append({"name": "batches.small", "unit": "batches",
                             "better": "higher", "source": "host_clock",
                             "layer": "Fleet entry (boot, counter read-back)",
                             "moves": "hart_ticks_per_s",
                             "workloads": ["small.batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(str(root))
    cell = bench.cell("small.batch")
    assert bench.config(cell["config"])["harts"] == 36
    assert bench.traffic(cell["traffic"]) == {"kind": "closed_batches"}
    per_layer = [m["name"] for m in bench.metrics("small.batch", True)]
    assert per_layer == ["batches.small"]
    e2e = [m["name"] for m in bench.metrics("small.batch", False)]
    assert e2e == [doc["end_to_end"][0]["name"], "setup_s"]
    assert bench.reader("batches.small")({"batches": 3}) == 3
    with pytest.raises(spec.SpecError):
        bench.reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        bench.cell("no.such.cell")


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_run_fails_before_set_up_without_a_tpu(tmp_path):
    """On the CPU the entry point exits non-zero and prints no result;
    nothing is compiled or written to the compile cache."""
    cache = tmp_path / "cache"
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "matrix.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a tpu device" in p.stderr
    assert not p.stdout.strip()
    assert not cache.exists()


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    root = _copy_bench(tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "matrix.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert not p.stdout.strip()
