"""Durable storage plane: the device-side fsync/WAL model.

The reference persists its log through a file-backed atom (log.clj:16-18)
with no fsync discipline, and its restart path forgets term/vote (SURVEY.md
2.3.12); the simulator's base model is the opposite extreme -- a PERFECT
disk where every write is durable the instant it happens -- so the whole
class of durability failures the dissertation's section 3.8 persistence
requirements exist to prevent was inexpressible. This subsystem makes
persistence explicit, as three rules the kernel states through
`storage.plane` and the scalar oracle restates independently
(tests/oracle.py):

  1. WATERMARKS. Each node carries a durable snapshot of the Raft
     persistent triple: `dur_len` (entries the disk has confirmed; entry
     IDENTITY rides the checksum chain, so a length is a prefix) plus
     `dur_term`/`dur_vote`. The snapshot advances ONLY when the node's
     fsync completes -- the cadence tick minus a per-node latency-jitter
     stall (`sim/faults._storage_draws`, the uint32-threshold machinery) --
     and a completed flush snaps it to the node's final live state that
     tick. Log truncation clamps the watermark (truncation makes the
     removed suffix non-durable AS CONTENT; the disk still confirmed the
     bytes, but recovery re-reads the new chain).

  2. THE SECTION-3.8 GATE (`cfg.durable_acks`). Everything a node EXPOSES
     about its persistent state reflects only durable state: AE ack match
     indices clamp to `dur_len` (replication stalls behind a slow disk
     instead of lying about it), the leader's own self-match counts toward
     commit only up to its durable watermark, and a vote grant is exposed
     only once the (term, votedFor) pair it commits to is durable -- a
     grant whose covering flush lands on a LATER tick emits a late
     RESP_VOTE then (the array form of "respond after the fsync returns").

  3. RECOVERY. A restart rewinds term/vote to the durable snapshot and
     recovers `max(dur_len, log_len - torn_drop)` log entries: the fsynced
     prefix is a FLOOR (a completed flush can never tear), the un-fsynced
     tail survives only as far as the in-flight writes reached, and the
     torn-tail draw (`torn_drop`, checksum-detected partial final records)
     eats up to `lost_suffix_span` entries of that salvageable suffix.
     Rule 2 makes the rewind sound: everything the node ever exposed was
     durable first, so recovery un-promises nothing -- which is exactly
     the property the two TEST-ONLY mutants break (scenario/mutation.py:
     `ack-before-fsync` -> leader_completeness, `volatile-vote` ->
     election_safety; frozen hunts in tests/corpus/).

Structural gate: `cfg.durable_storage` (fsync_interval > 0). Off, the
plane is zero-cost -- the watermark legs and lag metrics are carry
passthroughs (analysis/policy.invariant_leaves), the step goldens are
byte-identical, and the disk is perfect again. The cadence and every
disk-fault probability are tuning knobs inside the gate: the scenario
genome retimes them as traced data (disk-fault axes, scenario/genome.py),
so fault sweeps never recompile (jaxpr_audit FORK_PAIRS, config10).
On, the kernel books the plane's ops under the `tick.storage` scope and
counts stalled flushes, entries lost at recovery and late vote responses
(StepInfo/RunMetrics `fsync_stalls`, `torn_lost`, `late_grants`).

v1 restriction: mutually exclusive with ring-log compaction
(compact_margin > 0) -- the durable watermark does not fold across
snapshot installs and compaction rebases yet (utils/config.py assert).
"""

from raft_sim_tpu.storage.plane import (  # noqa: F401
    covered,
    flush,
    recover,
    recovered_log_len,
)
