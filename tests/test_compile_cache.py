"""Where the persistent compile cache lands (utils/compile_cache.py).

Each case runs in its own interpreter: the placement mutates process-wide
jax config, and the suite itself must keep running without a cache."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import accord_tpu
from accord_tpu.utils.compile_cache import CHECKOUT_CACHE_DIR

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(
    accord_tpu.__file__)))
PROBE = """
import json, jax
from accord_tpu.utils.compile_cache import place_compile_cache
before = jax.config.jax_compilation_cache_dir
print(json.dumps({
    "returned": place_compile_cache(), "before": before,
    "dir": jax.config.jax_compilation_cache_dir,
    "min_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes}))
"""


def _place(cwd, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.splitlines()[-1])


def test_env_placed_cache_is_left_alone(tmp_path):
    placed = str(tmp_path / "operator-cache")
    existed = os.path.exists(CHECKOUT_CACHE_DIR)
    got = _place(str(tmp_path), cache_env=placed)
    # jax read the variable itself; the function set no directory in code
    assert got["before"] == got["dir"] == got["returned"] == placed
    assert os.path.exists(CHECKOUT_CACHE_DIR) == existed
    # ... but still keeps every program, however small or quick to compile
    assert (got["min_s"], got["min_bytes"]) == (0.0, -1)


def test_default_cache_is_one_fixed_path_in_the_checkout(tmp_path):
    # two working directories: this process's, and the child's tmp_path
    assert os.getcwd() != str(tmp_path)
    assert CHECKOUT_CACHE_DIR == os.path.join(CHECKOUT, ".jax_cache")
    got = _place(str(tmp_path))
    assert got["before"] is None
    assert got["dir"] == got["returned"] == CHECKOUT_CACHE_DIR
    assert (got["min_s"], got["min_bytes"]) == (0.0, -1)
