"""The entry points' persistent compilation cache: placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set, else at a fixed ``<repo>/.jax_cache``.

Each case runs in a fresh CPU-only process, because the cache directory is
process-wide configuration."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
CHILD = """
import jax, jax.numpy as jnp
from repro.launch.cache import REPO_CACHE, use_compile_cache
where = use_compile_cache()
print(where, jax.config.jax_compilation_cache_dir, REPO_CACHE, sep="|")
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _child(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          CHILD.format(compile=compile_)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1].split("|")


def test_cache_dir_from_environment_is_left_to_jax(tmp_path):
    where, config, _ = _child(tmp_path, True)
    assert where == config == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_cache_dir_defaults_to_fixed_repo_path():
    where, config, repo_cache = _child(None, True)
    assert where == config == repo_cache
    assert repo_cache == os.path.join(os.path.dirname(SRC), ".jax_cache")
    assert any(p.endswith("-cache") for p in os.listdir(repo_cache))
