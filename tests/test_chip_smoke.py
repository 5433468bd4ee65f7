"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, and its
phase functions pass when steered to a tiny size here (Pallas phase in
interpret mode, grid phase on the jnp backend)."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop(spec.name, None)


def _tiny(cfg):
    return dataclasses.replace(
        cfg, window=3000, slide=300, slides=2, block=128, min_pts=5,
        query_batches=(64, 1), cd_strip=64, nmi_floor=0.9,
    )


def test_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_lone_script_refuses(tmp_path):
    """Copied alone into an empty directory, the script fails and prints
    no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_phase_dense_tiny(smoke):
    lines = []
    rep = smoke.run_phase(_tiny(smoke.PHASE_A), backend="pallas",
                          kernel="pallas-interpret", emit=lines.append)
    assert rep["impl"]["offline_impl"]["core_distances"] == "pallas-interpret"
    assert any("same partition=True" in ln for ln in lines), lines


def test_phase_grid_tiny(smoke):
    lines = []
    rep = smoke.run_phase(_tiny(smoke.PHASE_B), backend="jnp", kernel="jnp",
                          emit=lines.append)
    assert rep["impl"]["offline_impl"]["mst"] == "boruvka-strips-jnp"
    assert rep["nmi"] >= 0.9


def test_mesh_path_tiny(smoke):
    """The ``--chips 4`` path on however many CPU devices exist: the
    sharded pass must match the one-chip pass over the same table."""
    lines = []
    rep = smoke.run_mesh(_tiny(smoke.PHASE_B), emit=lines.append)
    grid, dense = rep["impl"]
    assert grid["core_distances"] == "grid-sharded"
    assert dense["core_distances"] == "jnp-sharded"
    assert rep["bitwise"], lines


def test_compile_cache_location(monkeypatch, tmp_path):
    """The entry points' cache: ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it; nothing is set in code), else ``<checkout>/.jax_cache``."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    saved_dir = jax.config.jax_compilation_cache_dir
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved_min)
