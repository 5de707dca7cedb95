"""Where XLA's compiled programs persist between processes.

The tick path mints programs as it runs (tens of `protocol_tick` signatures
in a short fused burn, a ladder of tiers per resolver), and a process that
starts cold pays every one of those compiles again. JAX's persistent
compilation cache removes that cost for the second process -- provided the
directory does not move, because a directory that moves never hits.

Every executable entry point (`python -m accord_tpu.serve`,
`accord_tpu.sim.burn`, `accord_tpu.sim.mesh_burn`, `benchmark/run.py`,
`chip_smoke.py`) calls `place_compile_cache()` before its first jit.
Library imports and the test suite do not.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: derived from the package location only, so two
# processes started from different working directories share one cache
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    make it keep every program; returns the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set the operator has placed the
    cache: JAX reads that variable itself and no directory is set here.
    Otherwise the cache lives at CHECKOUT_CACHE_DIR. By default JAX
    persists only programs that took over a second to compile; most of
    this repo's tier programs are smaller than that and there are
    hundreds of them, so both thresholds drop to "keep everything".
    """
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or CHECKOUT_CACHE_DIR
