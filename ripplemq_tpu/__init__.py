"""ripplemq_tpu — a TPU-native distributed message queue framework.

A ground-up JAX/XLA/Pallas re-design of the capability surface of the
reference RippleMQ system (a Kafka-style queue with two tiers of Raft:
a cluster metadata group and one Raft group per topic-partition).

Architecture (TPU-first, not a translation):

- **Data plane** (`ripplemq_tpu.core`, `ripplemq_tpu.parallel`): all
  partitions of all topics live in ONE SPMD tensor program. Partitions are
  a vmapped leading axis; replicas are a `jax.sharding.Mesh` axis; an
  AppendEntries round is a jitted step function; quorum commit is a
  `lax.psum` of acks over the replica axis. This replaces the reference's
  object-per-partition JRaft groups (reference:
  mq-broker/src/main/java/metadata/raft/PartitionRaftServer.java).

- **Metadata plane** (`ripplemq_tpu.broker.hostraft`): a deterministic,
  tick-driven Raft on the host for the low-rate replicated topic/assignment
  table (reference: metadata/raft/TopicsRaftServer.java +
  TopicsStateMachine.java).

- **Host runtime** (`ripplemq_tpu.broker`): request server, append
  batcher, device-step driver loop, membership monitor, sticky
  least-loaded partition assigner.

- **Client SDK** (`ripplemq_tpu.client`): ProducerClient / ConsumerClient
  with cached metadata, round-robin partition selection and
  auto-commit-after-read semantics (reference: mq-common client/).

- **Kernels** (`ripplemq_tpu.ops`): GF(2^8) matmul Pallas kernel for
  Reed-Solomon erasure coding of sealed log segments.
"""

__version__ = "0.1.0"

__all__ = [
    "EngineConfig",
    "ReplicaState",
    "StepInput",
    "StepOutput",
    "build_step_input",
    "decode_entries",
    "init_state",
]


def __getattr__(name):
    # Lazy re-exports (PEP 562): importing the package must not pull
    # jax — an eager `from ripplemq_tpu.core import ...` charged every
    # client-only import the full ~4 s jax initialization for symbols
    # it never touches.
    if name in __all__:
        from ripplemq_tpu import core

        return getattr(core, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
