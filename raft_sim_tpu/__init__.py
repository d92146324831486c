"""raft_sim_tpu: a TPU-native batched Raft cluster simulator in JAX.

Re-expresses the per-node behavior of the reference implementation (one networked
Clojure Raft process per node, /root/reference/src/raft/) as a pure, vmap'able
state-transition kernel over struct-of-arrays state, with the network as an N x N
adjacency-masked message scatter and the event loop as a jit-compiled `lax.scan`.
See SURVEY.md for the structural map between the two designs.
"""

from raft_sim_tpu.types import (
    CANDIDATE,
    FOLLOWER,
    LEADER,
    NIL,
    ClusterState,
    Mailbox,
    StepInfo,
    StepInputs,
    init_batch,
    init_state,
)
from raft_sim_tpu.utils.checkpoint import FORMAT_VERSION as CHECKPOINT_FORMAT_VERSION
from raft_sim_tpu.utils.config import PRESETS, RaftConfig

__all__ = [
    "CANDIDATE",
    "CHECKPOINT_FORMAT_VERSION",
    "FOLLOWER",
    "LEADER",
    "NIL",
    "ClusterState",
    "Mailbox",
    "PRESETS",
    "RaftConfig",
    "StepInfo",
    "StepInputs",
    "init_batch",
    "init_state",
]

__version__ = "0.1.0"
