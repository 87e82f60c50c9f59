"""The chip entry and what keeps a CPU run from passing for a chip run:
compile-cache placement and chip_smoke.py's refusal to run without a TPU.
(Unknown chip peaks are pinned in test_costmodel.py::test_detect_peaks_table,
the TPU-shaped backend allow-list in
test_histogram.py::test_tpu_shaped_gate_is_allow_list.)"""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from lightgbm_tpu import profiling
from lightgbm_tpu.log import LEVEL_WARNING, Log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def unplaced_cache(monkeypatch):
    """A process whose compile cache has not been placed yet (conftest
    placed this one's; put it back afterwards — jax initialised its cache
    object long ago, so nothing moves on disk meanwhile)."""
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_dir_env_var_wins_and_nothing_overrides_it(
        unplaced_cache, monkeypatch, tmp_path):
    env_dir, asked = str(tmp_path / "env"), str(tmp_path / "asked")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert profiling.enable_compile_cache(asked) == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert not os.path.exists(asked)
    # and again once placed, as a later booster or build_app would call it
    assert profiling.enable_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir


def test_cache_dir_unset_is_the_fixed_checkout_path(unplaced_cache):
    assert profiling.DEFAULT_COMPILE_CACHE_DIR == \
        os.path.join(REPO, ".jax_cache")
    assert profiling.enable_compile_cache() == \
        profiling.DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == \
        profiling.DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_cache_dir_param_in_conflict_is_ignored(unplaced_cache, tmp_path):
    first, second = str(tmp_path / "first"), str(tmp_path / "second")
    assert profiling.enable_compile_cache(first) == first
    lines = []
    saved = Log._level, Log._callback
    Log.reset_level(LEVEL_WARNING)
    Log.reset_callback(lines.append)
    try:
        # one process, one cache: a later disagreeing param changes nothing
        assert profiling.enable_compile_cache(second) == first
    finally:
        Log.reset_level(saved[0])
        Log.reset_callback(saved[1])
    assert jax.config.jax_compilation_cache_dir == first
    assert not os.path.exists(second)
    assert len(lines) == 1 and "ignored" in lines[0] and second in lines[0]


def _run_smoke(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"] + args, cwd=cwd,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_refuses_cpu_before_training(tmp_path):
    r = _run_smoke([], REPO)
    assert r.returncode != 0
    assert "refusing to run" in r.stderr
    assert '"ok"' not in r.stdout and "train_exact" not in r.stdout
    # alone in a directory, without the program, it fails too
    shutil.copy(SMOKE, tmp_path)
    r = _run_smoke(["--rehearsal"], str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes_and_a_broken_phase_fails():
    r = _run_smoke(["--rehearsal"], REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    # conftest's eight virtual devices are inherited, so mesh4 runs too
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    for phase in ("train_exact", "kernel_parity", "train_frontier",
                  "train_goss", "predict_and_serve", "mesh4"):
        assert "[%s] PASSED" % phase in r.stdout
    # an impossible tolerance: the phase raises, nothing carries on
    broken = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; chip_smoke.PARITY_TOL = -1.0; "
         "sys.argv = ['chip_smoke.py', '--rehearsal']; "
         "sys.exit(chip_smoke.main())"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert broken.returncode != 0
    assert '"ok"' not in broken.stdout
    assert "[train_frontier]" not in broken.stdout
