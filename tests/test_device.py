"""Process-level device settings: the compile-cache location, the
one-process-per-chip guard, and ``chip_smoke.py`` refusing to run without a
TPU."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core import device

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    saved = {
        name: getattr(jax.config, name)
        for name in ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    assert device.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert device.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert device.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own.
    assert jax.config.jax_compilation_cache_dir is None


def test_children_guard_is_silent_on_cpu():
    jax.devices()  # the test process holds the CPU backend
    device.check_children_can_use_device("pool", "use threads")


def test_process_pool_refused_while_parent_holds_tpu(monkeypatch):
    from repro.core.box import Box
    from repro.core.executor import SweepExecutor

    jax.devices()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match=r"holds the TPU .*--pool thread"):
        device.check_children_can_use_device("--pool process", "use --pool thread")
    box = Box.from_dict(
        {"name": "guard", "tasks": [{"task": "compute", "params": {"operation": ["add", "mul"]},
                                     "metrics": ["ops_per_s"]}]}
    )
    for schedule in ("dynamic", "static"):
        ex = SweepExecutor(workers=2, pool="process", schedule=schedule, iters=1, warmup=0)
        with pytest.raises(RuntimeError, match="holds the TPU"):
            ex.run_box(box)


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "script-alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    cwd = ROOT
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
    res = _run_smoke(cwd)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr
