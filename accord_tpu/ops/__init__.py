"""The TPU data plane: batched dependency computation and execute-order
closure as jitted JAX tensor programs compiled by XLA.

This is the point of the whole exercise (SURVEY.md section 7 step 7,
BASELINE.json north star): the reference implements its deps-calculation hot
loop as hand-optimized flat-array Java scans
(local/cfk/CommandsForKey.java:809-968, utils/SearchableRangeList.java); we
re-express the same queries over *micro-batches* of transactions as

  - interval/key bitmaps over the hash-key domain  (bool[B, K])
  - pairwise conflict = bitmap boolean matmul      (MXU)
  - kind-witness filtering via a 6x6 table lookup  (VPU)
  - started-before via packed-timestamp compares   (VPU)
  - execute-order reachability = iterated boolean matmul closure (MXU)

behind the DepsResolver SPI, differentially tested against the host
CommandStore scan.
"""
from accord_tpu.ops.encoding import TimestampEncoder, WITNESS_TABLE
from accord_tpu.ops.resolver import DepsResolver, HostDepsResolver, BatchDepsResolver

__all__ = ["TimestampEncoder", "WITNESS_TABLE", "DepsResolver",
           "HostDepsResolver", "BatchDepsResolver"]
