"""``chip_smoke.py`` refuses to run off the chip, and the compile cache
goes where the environment says.

Each case runs a fresh Python process: the smoke script and the cache
helper change process-wide JAX state that a test worker must not keep.
"""

import os
import shutil
import subprocess
import sys

import pytest

from repro.launch.chip import DEFAULT_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, **env):
    full = dict(os.environ)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU, from the checkout or copied out of it, the script
    exits non-zero and prints no result."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    proc = _run([script], cwd=os.path.dirname(script))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "FAIL" in proc.stderr


def test_compile_cache_defaults_to_repo_dir():
    """Without ``JAX_COMPILATION_CACHE_DIR`` the cache is the fixed,
    git-ignored ``<repo>/.jax_cache``."""
    proc = _run(["-c", "import jax; from repro.launch.chip import "
                       "use_compile_cache; print(use_compile_cache()); "
                       "print(jax.config.jax_compilation_cache_dir)"],
                cwd=ROOT, PYTHONPATH=os.path.join(ROOT, "src"))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [DEFAULT_CACHE_DIR] * 2
    assert DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_dir_is_used(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiled entries land in
    that directory and the helper names no other."""
    cache = str(tmp_path / "cache")
    proc = _run(["-c", "import jax, jax.numpy as jnp; from repro.launch.chip "
                       "import use_compile_cache; print(use_compile_cache()); "
                       "print(jax.config.jax_compilation_cache_dir); "
                       "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3))"],
                cwd=ROOT, PYTHONPATH=os.path.join(ROOT, "src"),
                JAX_COMPILATION_CACHE_DIR=cache,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [cache] * 2
    assert any(name.endswith("-cache") for name in os.listdir(cache))
