"""BOTH halves of the device data plane at once, under the adversarial burn:
the sharded deps arena (ops/resolver.py over the 8-device virtual mesh)
resolving PreAccept/Accept deps AND the device execution scheduler
(ops/exec_plane.py) releasing the execute DAG, with durability truncation,
topology churn and network chaos running simultaneously (VERDICT r4 item 5;
reference: the execute DAG is always on, local/Commands.java:960, and the
burn runs everything together, burn/BurnTest.java:107).

The exec plane stays opt-in for the REST of the sim suite purely for
wall-clock reasons: the sim's per-tick device dispatch costs ~50x the host
walk on the CPU test mesh (what it costs on a chip is not measured: no
benchmark cell turns the exec plane on). This module is where the combined
configuration is load-bearing.
"""
from __future__ import annotations

import pytest

from accord_tpu.parallel.mesh import make_mesh
from accord_tpu.sim.burn import run_burn
from accord_tpu.sim.cluster import ClusterConfig


def _combined_config():
    from accord_tpu.ops.resolver import ShardedBatchDepsResolver
    factory = lambda: ShardedBatchDepsResolver(  # noqa: E731
        mesh=make_mesh(), num_buckets=256, initial_cap=512)
    return ClusterConfig(deps_resolver_factory=factory,
                         deps_batch_window_ms=1.0,
                         exec_plane=True,
                         durability=True, durability_interval_ms=400.0)


@pytest.mark.parametrize("seed", (1, 2, 3, 4, 5))
def test_combined_device_plane_burn(seed):
    """Deps arena + exec frontier + durability + churn + chaos, together."""
    r = run_burn(seed, ops=60, key_count=16, concurrency=6, write_ratio=0.8,
                 chaos_drop=0.05, topology_churn=True,
                 churn_interval_ms=1500.0,
                 config=_combined_config())
    assert r.lost == 0
    assert r.acked + r.failed == 60


def test_combined_device_plane_deterministic():
    """The combined device path must replay bit-identically."""
    kw = dict(ops=60, key_count=16, concurrency=6, write_ratio=0.8,
              collect_log=True)
    a = run_burn(2, config=_combined_config(), **kw)
    b = run_burn(2, config=_combined_config(), **kw)
    assert a.log == b.log
