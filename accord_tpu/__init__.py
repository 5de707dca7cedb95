"""accord_tpu: a TPU-native framework with the capabilities of Apache Cassandra's
Accord library (leaderless consensus for strict-serializable multi-key/multi-range
distributed transactions).

This is NOT a port of the Java reference. The coordination/protocol state machines
run host-side in Python (single-threaded, deterministic, simulation-first, mirroring
the reference's design where an entire cluster runs on one logical clock); the
performance-critical data plane -- batched dependency computation and execute-order
closure -- is expressed as jitted JAX tensor programs (XLA) behind the DepsResolver SPI
(see accord_tpu.ops), sharded over a jax.sharding.Mesh for multi-chip scale
(see accord_tpu.parallel).

Layer map (mirrors SURVEY.md section 1):
  utils/       L0 data-structure utils + L1 async runtime
  primitives/  L2 protocol value types (Timestamp, TxnId, Deps, Keys/Ranges, Txn)
  api/         L3 SPI seam (Agent, MessageSink, Scheduler, DataStore, ...)
  topology/    L4 epoch-versioned shard maps
  local/       L5 replica-side engine (Node, CommandStore, Command, CommandsForKey)
  messages/    L6 wire protocol (PreAccept, Accept, Commit, Apply, ReadData, ...)
  coordinate/  L7 client-side coordination state machines + quorum trackers
  impl/        L8 default implementations (in-memory stores, progress log)
  sim/         L9 deterministic whole-cluster simulation harness ("burn test")
  ops/         TPU data plane: deps-resolution kernels (jax.jit / shard_map)
  parallel/    device-mesh sharding of the data plane
  maelstrom/   JSON-over-stdio harness for black-box linearizability testing
"""

__version__ = "0.1.0"
