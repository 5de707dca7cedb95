"""Test configuration: the suite runs on the CPU backend with eight virtual
devices, so the sharding tests meet a genuinely sharded lowering without
hardware and a test run never takes the chip from another process.

The accelerator is checked separately, by `chip_smoke.py` on the chip
(its sharded leg runs where four or more real devices are visible).

The platform is forced through jax.config after the import, which holds
whatever JAX_PLATFORMS the shell exports (a chip machine's `tpu` included).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("ACCORD_TPU_PARANOIA", "superlinear")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
