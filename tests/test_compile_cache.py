"""Where the persistent compilation cache goes (in child processes: the
test process itself never turns the cache on)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import checkout_cache_dir, enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(checkout_cache_dir())
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
"""


def _child(env_extra, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", CHILD.format(compile=compile_)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    return p.stdout.split()


def test_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    cache = tmp_path / "cache"
    used, configured, _ = _child(
        {"JAX_COMPILATION_CACHE_DIR": str(cache),
         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}, True)
    assert used == configured == str(cache)
    assert any(name.endswith("-cache") for name in os.listdir(cache))


def test_fixed_checkout_dir_without_env():
    used, configured, fixed = _child({}, False)
    assert used == configured == fixed == os.path.join(ROOT, ".jax_cache")
