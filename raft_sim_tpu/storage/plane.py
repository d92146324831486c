"""The durability rules as elementwise lattices -- ONE statement of them.

Every rule here is a pure elementwise `jnp.where` lattice over per-node
leaves, so the same functions serve a single cluster's `[N]` orientation and
the batch-minor kernel's `[N, B]` (models/raft_batched.py): broadcasting
does the layout work. The scalar oracle (tests/oracle.py)
deliberately does NOT import this module -- it restates the rules in
host-side numpy so kernel/oracle parity remains an independent check, not a
tautology. The package docstring (storage/__init__.py) is the prose
contract; sim/faults._storage_draws is the input side (fsync_fire /
torn_drop draws).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from raft_sim_tpu.types import NIL
from raft_sim_tpu.utils.config import RaftConfig


def recovered_log_len(dur_len: jax.Array, log_len: jax.Array,
                      torn_drop: jax.Array) -> jax.Array:
    """Entries a restart recovers: the fsynced prefix is a FLOOR (a
    completed flush can never tear), the un-fsynced tail survives as far as
    the in-flight writes reached minus the torn tail the recovery checksum
    rejects (`torn_drop` entries, drawn every tick, consumed only on
    restart ticks)."""
    return jnp.maximum(dur_len, log_len - torn_drop)


def recover(cfg: RaftConfig, rs: jax.Array, torn_drop: jax.Array,
            dur_len, dur_term, dur_vote, term, voted_for, log_len):
    """Crash recovery: rewind term/votedFor to the durable snapshot and
    truncate the log to the recovered length, on restarting nodes only
    (`rs`). Returns the post-recovery (term, voted_for, log_len). Sound
    because of the section-3.8 gate: everything a node ever EXPOSED (vote
    grants, AE acks) was durable first, so the rewind un-promises nothing.
    TEST-ONLY mutant (cfg.persist_vote False): recovery forgets votedFor --
    the reference's own restart bug (log.clj:16-18, SURVEY.md 2.3.12) -- so
    a restarted voter can grant a second vote in the same term (the
    election_safety break the volatile-vote hunt re-finds)."""
    rec_len = recovered_log_len(dur_len, log_len, torn_drop)
    return (
        jnp.where(rs, dur_term, term),
        jnp.where(
            rs,
            dur_vote if cfg.persist_vote else jnp.int32(NIL),
            voted_for,
        ),
        jnp.where(rs, rec_len, log_len),
    )


def covered(dur_term, dur_vote, term, voted_for) -> jax.Array:
    """True where the live (term, votedFor) pair is durably recorded -- the
    exposure predicate for vote grants (gate 2): a grant is visible to the
    candidate only while covered. NIL votedFor is never covered (there is
    no grant to expose)."""
    return (dur_term == term) & (dur_vote == voted_for) & (voted_for != NIL)


def flush(fs_fire, dur_mid, dur_term, dur_vote, log_len, term, voted_for):
    """The fsync completion lattice (phase 7.5): where a node's flush
    completes this tick (`fs_fire` -- cadence minus jitter stall, dead
    disks never flush), the durable snapshot snaps to the node's FINAL
    live state (post-injection log length, post-election term/vote);
    elsewhere it carries (`dur_mid` is the truncation-clamped watermark).
    Returns the post-flush (dur_len, dur_term, dur_vote)."""
    return (
        jnp.where(fs_fire, log_len, dur_mid),
        jnp.where(fs_fire, term, dur_term),
        jnp.where(fs_fire, voted_for, dur_vote),
    )
