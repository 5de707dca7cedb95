"""The benchmark's own tests: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`
from the root of the checkout. They run on the CPU and never take a chip."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
