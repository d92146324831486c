"""TEST-ONLY weakened kernel variants: the search loop's ground truth.

A violation hunter that never finds anything proves nothing -- maybe the
kernel is safe, maybe the hunt is blind. These config subclasses weaken the
kernel behind an explicit opt-in (driver `scenario search --mutant`, CI's
scenario smoke job, tests/test_scenario.py) so the search demo has a target
it MUST hit within a bounded generation budget: if the hunt cannot drive a
quorum-off-by-one kernel to an election-safety violation, the hunt is
broken, not the kernel. Never instantiate these outside tests/demos; the
class is deliberately NOT reachable from RaftConfig flags or scenario files.

The weakening rides the config (cfg.quorum feeds the kernel's vote counts
and commit rule), so no second kernel source exists to drift: the mutant
compiles the same step code at a different quorum literal -- one extra jit
compile, zero extra lowered program structures (literal-blind hashes equal;
analysis/jaxpr_audit.py structural_hash).
"""

from __future__ import annotations

from raft_sim_tpu.utils.config import RaftConfig


class WeakQuorumConfig(RaftConfig):
    """quorum - 1: floor(N/2) instead of floor(N/2)+1, so two split-vote
    candidates can both 'win' a term -- the reference's even-N majority bug
    (SURVEY.md quorum note) made unconditional. Election safety violates
    within a few elections once message drop forces vote splits."""

    @property
    def quorum(self) -> int:  # type: ignore[override]
        return self.n_nodes // 2


class SingleServerChangeConfig(RaftConfig):
    """Single-server membership change (cfg.joint_consensus False): every
    config change is ONE log entry that switches the configuration wholly at
    append -- no joint phase, no completing entry. The known-unsafe
    interleaving (thesis 4.1 / 4.3's motivating bug): two leaders'
    uncommitted single-entry changes yield majorities that need not
    intersect, so a leader missing committed entries gets elected and
    replicates its short log over them. Requires cfg.reconfig
    (reconfig_interval > 0)."""

    @property
    def joint_consensus(self) -> bool:  # type: ignore[override]
        return False


class ActOnCommitConfig(RaftConfig):
    """Configs applied at COMMIT instead of append (cfg.act_on_append
    False): each node derives its configuration from the committed prefix --
    the dissertation-ch.-4 anti-rule. Nodes then disagree about when a
    change takes effect (a config entry's commit is itself judged under some
    config), and the old configuration keeps electing leaders the new one
    cannot see: disjoint quorums, same-term double leadership. Requires
    cfg.reconfig (reconfig_interval > 0)."""

    @property
    def act_on_append(self) -> bool:  # type: ignore[override]
        return False


class IgnoreTruncationRollbackConfig(RaftConfig):
    """Truncation rollback skipped (cfg.truncation_rollback False): a node
    whose truncated log LOST config entries keeps acting on the stale
    derived configuration -- quorums drawn from member sets no log chain
    ever contained (a briefly-held uncommitted change survives its own
    truncation as a phantom electorate). Requires cfg.reconfig
    (reconfig_interval > 0)."""

    @property
    def truncation_rollback(self) -> bool:  # type: ignore[override]
        return False


class StaleReadConfig(RaftConfig):
    """ReadIndex without the confirmation round OR the current-term-commit
    capture gate (cfg.read_confirm False): a deposed leader stranded in a
    minority partition keeps serving reads from its stale commit state --
    reads below the committed frontier, the linearizability break the trace
    checker's read_linearizability property must reject. Requires
    cfg.read_index (read_interval > 0)."""

    @property
    def read_confirm(self) -> bool:  # type: ignore[override]
        return False


class BlindTransferConfig(RaftConfig):
    """TimeoutNow as a coup (cfg.xfer_election False): the leader fires
    without waiting for the target to catch up, and the target assumes
    leadership DIRECTLY -- no vote round, no up-to-date check -- so a behind
    target truncates committed entries off its followers (commit-invariant /
    leader-completeness breaks). Requires cfg.leader_transfer
    (transfer_interval > 0)."""

    @property
    def xfer_election(self) -> bool:  # type: ignore[override]
        return False


class LeaseSkewConfig(RaftConfig):
    """Lease reads judged on a no-skew clock model (cfg.lease_skew_safe
    False): the kernel serves lease reads for election_min_ticks + 2 global
    ticks instead of the configured skew-safe read_lease_ticks. Correct when
    every local clock advances exactly 1/tick; under clock skew a fast
    follower's lease-vote-denial window halves in global time, a new leader
    elects and commits INSIDE the optimistic lease, and the partitioned old
    leader serves a read below the committed frontier -- viol_read_stale on
    device (the hunt's fitness signal, driven by the skew genome axis) and a
    read_linearizability rejection from the trace checker. Requires
    cfg.read_lease (read_lease_ticks > 0)."""

    @property
    def lease_skew_safe(self) -> bool:  # type: ignore[override]
        return False


class AckBeforeFsyncConfig(RaftConfig):
    """Acks reflect volatile state (cfg.durable_acks False): an
    AppendEntries ack names entries whose fsync has not completed, and the
    leader's own self-match reads log_len instead of the durable watermark
    -- the canonical ack-before-fsync storage bug. A leader counts such an
    ack toward commit, the acking follower crashes, recovery truncates the
    un-fsynced suffix, and a committed entry exists on no quorum: a later
    leader elects without it and commits below the frontier
    (leader_completeness), and the AE that re-extends the deposed leader
    mutates its committed prefix (the device commit invariant --
    state_machine_safety). The disk itself stays honest -- only the
    acknowledgment lies. Requires cfg.durable_storage
    (fsync_interval > 0)."""

    @property
    def durable_acks(self) -> bool:  # type: ignore[override]
        return False


class VolatileVoteConfig(RaftConfig):
    """Crash recovery forgets votedFor (cfg.persist_vote False): term and
    log restore from the durable snapshot but the vote does not -- the
    reference's own restart bug (log.clj:16-18, SURVEY.md 2.3.12) expressed
    inside the storage plane. A voter grants, crashes, restarts with
    voted_for == NIL, and grants AGAIN in the same term to a different
    candidate: two leaders in one term (election_safety). Requires
    cfg.durable_storage (fsync_interval > 0)."""

    @property
    def persist_vote(self) -> bool:  # type: ignore[override]
        return False


MUTANTS = {
    "weak-quorum": WeakQuorumConfig,
    "single-server-change": SingleServerChangeConfig,
    # Back-compat alias (pre-ISSUE-13 name for the joint_consensus=False
    # weakening; under log-carried configs its precise shape is the
    # single-server change).
    "joint-bypass": SingleServerChangeConfig,
    "act-on-commit": ActOnCommitConfig,
    "ignore-truncation-rollback": IgnoreTruncationRollbackConfig,
    "stale-read": StaleReadConfig,
    "blind-transfer": BlindTransferConfig,
    "lease-skew": LeaseSkewConfig,
    "ack-before-fsync": AckBeforeFsyncConfig,
    "volatile-vote": VolatileVoteConfig,
}


def mutant_config(name: str, cfg: RaftConfig) -> RaftConfig:
    """Rebuild `cfg` under the named mutant class (same field values)."""
    import dataclasses

    if name not in MUTANTS:
        raise ValueError(f"unknown mutant {name!r} (have {sorted(MUTANTS)})")
    return MUTANTS[name](**dataclasses.asdict(cfg))
