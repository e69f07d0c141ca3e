"""Where the persistent compilation cache goes (``repro.compile_cache``).

Each case runs in a fresh CPU-only interpreter: turning the cache on is
process-wide, and this test process keeps it off."""

import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import DEFAULT_DIR, ENV_VAR

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(16)).block_until_ready()
"""


def _run(env_extra: dict, compile: bool) -> list:
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    proc = subprocess.run([sys.executable, "-c",
                           SCRIPT.format(compile=compile)],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_env_var_names_the_cache_dir(tmp_path):
    cache = tmp_path / "cache"
    out = _run({ENV_VAR: str(cache)}, compile=True)
    assert out == [str(cache), str(cache)]
    assert any(cache.iterdir()), "no cache entry written"


def test_default_is_the_fixed_repo_dir():
    out = _run({}, compile=False)
    assert out == [str(DEFAULT_DIR), str(DEFAULT_DIR)]
    assert DEFAULT_DIR == ROOT / ".jax_cache"


def test_default_dir_is_git_ignored():
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
