"""nomad_tpu — a TPU-native cluster-scheduling framework.

A from-scratch rebuild of the capabilities of HashiCorp Nomad (reference:
hollowsunsets/nomad, surveyed in SURVEY.md) designed TPU-first:

- The control plane (state store, evaluation broker, plan queue, serialized
  optimistic-concurrency plan applier, blocked evals, deployment watcher,
  node drainer, heartbeats) lives on the host in `nomad_tpu.core` /
  `nomad_tpu.state`.
- The scheduler hot path (feasibility -> bin-pack/spread scoring -> ranking ->
  selection -> preemption; Nomad's RankIterator stack and structs.AllocsFit,
  reference scheduler/rank.go:193-551, structs/funcs.go:166-297) is a dense
  batched engine in `nomad_tpu.ops`: cluster state is encoded as fixed-shape
  node x resource matrices (`nomad_tpu.encode`), and a single jitted loop
  over the slots places every task-group instance of an evaluation while
  vmapping feasibility + scoring across all candidate nodes at once.
- Multi-chip scale-out shards the node axis and the evaluation batch over a
  `jax.sharding.Mesh` (`nomad_tpu.parallel`).
"""

__version__ = "0.1.0"

SCHEDULER_VERSION = 1  # parity: reference scheduler/scheduler.go:19
