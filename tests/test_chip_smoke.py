"""What ``chip_smoke.py`` and the compile-cache helper do where there is
no chip: the smoke refuses, and the cache can be placed from outside."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, **env):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu():
    """No accelerator: another exit code than 0, the platform named,
    and no result line."""
    out = _run([SMOKE], REPO, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path),
               JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""


_SAY_DIR = (
    "import jax\n"
    "from pytorch_ps_mpi_tpu.utils.compile_cache import "
    "enable_compilation_cache\n"
    "stats = enable_compilation_cache()\n"
    "assert stats.dir == jax.config.jax_compilation_cache_dir\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_dir_comes_from_the_environment(tmp_path):
    """Set, the variable wins (the helper sets no directory at all)."""
    out = _run(["-c", _SAY_DIR], REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)


def test_compile_cache_default_is_fixed_inside_the_checkout(tmp_path):
    """Unset, it is <repo>/.jax_cache — from any working directory, in
    every process: a path that moves never hits."""
    a = _run(["-c", _SAY_DIR], REPO, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    b = _run(["-c", _SAY_DIR], str(tmp_path), JAX_PLATFORMS="cpu",
             PYTHONPATH=REPO)
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    assert a.stdout.strip() == b.stdout.strip() == os.path.join(
        REPO, ".jax_cache")
