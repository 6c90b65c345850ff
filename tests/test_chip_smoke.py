"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The chip runs every phase at full size; here each runs on one or two
workloads, with the script's platform check steered to the CPU by the
test.  Mismatch paths are checked too: a phase whose result disagrees
with its reference must exit non-zero.
"""
import copy
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core.hext import engine, programs

ROOT = pathlib.Path(__file__).resolve().parents[1]
BITCOUNT = [programs.BitCount()]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def meter(smoke):
    return smoke.CompileMeter()


@pytest.fixture(scope="module")
def goldens(smoke):
    return smoke.load_goldens()


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    return env


@pytest.mark.parametrize("spans,want", [
    ([], 0.0),
    ([(0.0, 2.0), (0.5, 1.0)], 2.0),              # a nested jit's lowering
    ([(3.0, 4.0), (0.0, 1.0), (0.5, 1.5)], 2.5),   # overlap, then a gap
])
def test_compile_meter_merges_nested_spans(smoke, spans, want):
    assert smoke._covered(spans) == want


def test_device_check_names_the_platform_found(smoke):
    with pytest.raises(SystemExit, match="found platform 'cpu'"):
        smoke.device_info()


def test_device_check_reports_the_steered_platform(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    d = jax.devices()[0]
    assert smoke.device_info() == {"platform": "cpu", "kind": d.device_kind,
                                   "count": len(jax.devices())}


def test_script_fails_before_any_phase_without_a_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       cwd=ROOT, env=_cpu_env(), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert "[matrix]" not in r.stdout and '"ok"' not in r.stdout


def test_script_alone_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_matrix_phase_matches_goldens(smoke, meter, goldens, capsys):
    smoke.phase_matrix(meter, goldens, wls=BITCOUNT)
    out = capsys.readouterr().out
    assert "[matrix]" in out and "PASS: 2 harts" in out


def test_matrix_phase_fails_on_counter_drift(smoke, meter, goldens):
    bad = copy.deepcopy(goldens)
    bad["workloads"]["bitcount"]["guest"]["walks"] += 1
    with pytest.raises(SystemExit, match=r"bitcount/guest\.walks"):
        smoke.phase_matrix(meter, bad, wls=BITCOUNT)


def test_consolidation_phase_matches_goldens(smoke, meter, goldens, capsys):
    smoke.phase_consolidation(meter, goldens, wls=BITCOUNT, guests=(2,))
    assert "[consolidation N=2]" in capsys.readouterr().out


def test_width_phase_checks_every_copy(smoke, meter, goldens, capsys):
    smoke.phase_width(meter, goldens, wls=BITCOUNT, copies=2)
    out = capsys.readouterr().out
    assert "PASS: all 4 harts" in out and "hart-ticks/s" in out
    bad = copy.deepcopy(goldens)
    bad["workloads"]["bitcount"]["native"]["instret"] += 1
    with pytest.raises(SystemExit, match=r"hart 2 bitcount/native\.instret"):
        smoke.phase_width(meter, bad, wls=BITCOUNT, copies=2)


def test_torture_phase_zero_mismatches(smoke, meter, capsys):
    smoke.phase_torture(meter, count=4)
    assert "4 cases, 0 mismatches" in capsys.readouterr().out


def test_serve_phase_keeps_every_golden(smoke, meter, capsys):
    smoke.phase_serve(meter)
    assert "PASS: 16/16 goldens" in capsys.readouterr().out


def test_kernel_phase_matches_reference(smoke, meter, capsys):
    smoke.phase_kernel(meter, shape=(3, 4, 16, 32, 513), force="interpret")
    out = capsys.readouterr().out
    assert "[kernel]" in out and "| run -" not in out


def test_four_chips_phase_refuses_one_device(smoke, meter, goldens):
    with pytest.raises(SystemExit, match="exactly 4 devices, found 1"):
        smoke.phase_four_chips(meter, goldens, wls=BITCOUNT)


def test_four_chips_phase_on_four_virtual_devices():
    """The sharded-vs-jit comparison on 4 CPU devices, in a child process
    (the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.core.hext import programs
        smoke.phase_four_chips(smoke.CompileMeter(), smoke.load_goldens(),
                               wls=[programs.BitCount()], copies=2)
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=900,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "hart state on devices [0, 1, 2, 3]" in r.stdout
    assert "sharded == jit bit for bit" in r.stdout


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert engine.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = engine.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert "/.jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_scatter_probe_finds_no_lost_write_on_the_cpu(capsys):
    """``scatter_probe.py``, the chip check for lost single-entry writes,
    at tiny widths: every place, both forms, 0 wrong entries."""
    spec = importlib.util.spec_from_file_location("scatter_probe",
                                                  ROOT / "scatter_probe.py")
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--widths", "3", "18", "--steps", "24"]) == 0
    out = capsys.readouterr().out
    assert out.count("read-back wrong 0, final wrong 0") == 3 * 2 * 2
    assert "0 wrong entries in all" in out
