"""The persistent compilation cache lands in one fixed directory.

``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the directory the
caller names (``chip_smoke.py`` / ``benchmarks/run.py``: the checkout's
git-ignored ``.jax_cache``). Each case runs in a fresh interpreter, since
the cache directory is process-wide JAX state.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROG = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print(use_compile_cache(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def _run(default_dir, env_dir=None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _PROG, str(default_dir)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("env_set", [False, True])
def test_cache_lands_in_one_fixed_directory(tmp_path, env_set):
    default, env_dir = tmp_path / "default", tmp_path / "from_env"
    used = _run(default, env_dir if env_set else None)
    want, other = (env_dir, default) if env_set else (default, env_dir)
    assert pathlib.Path(used) == want.resolve()
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()


def test_repo_cache_directory_is_git_ignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
