"""Device-side protocol event extraction: state deltas -> a compact event stream.

The reference's whole observability story is a println of node + message per
loop iteration (core.clj:182-186); `sim/trace.py` already diffs HOST-side
state stacks into events, but stacking full states is exactly what a 100k-
cluster fleet cannot afford. This module computes the same discrete events ON
DEVICE, from the (old state, new state, inputs, StepInfo) the tick body
already holds -- reads only, zero trajectory perturbation -- so histories
stream out of the windowed telemetry scan at ring-buffer cost instead of
full-trajectory cost.

Vocabulary (KINDS): one small-int code per event kind, with (tick, node,
kind, detail) fields per emitted event. The WITHIN-TICK ordering is
(kind, node) lexicographic over the static slot table below, and the kind
NUMBERING is load-bearing for the checker: role-transition kinds come before
commit/append/truncate kinds so that a node which loses leadership and
accepts entries in the same tick is processed as "stepped down, then
truncated" -- matching the kernel's phase order (models/raft_batched.py
phase 1 adoption precedes phase 3 append) -- and fault kinds come last. The checker
(trace/checker.py) replays events in exactly this order.

Extraction is delta-based on purpose: ONE extractor serves the tick kernel
(models/raft_batched.py) and any step_fn override (e.g. the weak-quorum
test mutant) without the kernel changing. The leaves read here -- role,
term, voted_for, commit_index, log_len, and (durable storage plane) the
dur_len/dur_term/dur_vote watermarks -- are the delta contract the kernel
documents; everything is elementwise over the node axis, so the same code
runs on single-cluster [N] leaves and batch-minor [N, B] leaves.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.types import (
    CANDIDATE,
    FOLLOWER,
    LEADER,
    NIL,
    PRECANDIDATE,
    ClusterState,
    StepInfo,
    StepInputs,
)
from raft_sim_tpu.utils.config import RaftConfig

# Event kinds. 0 is reserved for "empty ring slot"; the numbering encodes the
# within-tick processing order (module docstring). detail semantics per kind:
#   role kinds      new term
#   term            new term
#   vote            candidate voted for
#   commit          new commit index        append/truncate  new log length
#   crash/restart   0                       drop             dropped in-edges
#   violation       bitmask: 1 election-safety, 2 commit, 4 log-matching
#   partition       cut-edge count after the change (0 = healed)
EV_NONE = 0
EV_FOLLOWER = 1
EV_PRECANDIDATE = 2
EV_CANDIDATE = 3
EV_LEADER = 4
EV_TERM = 5
EV_VOTE = 6
EV_COMMIT = 7
EV_APPEND = 8
EV_TRUNCATE = 9
EV_CRASH = 10
EV_RESTART = 11
EV_DROP = 12
# Reconfiguration-plane kinds (raft_sim_tpu/reconfig). Read kinds sit ABOVE
# the commit kind on purpose: a read served this tick is checked against
# commits that landed this tick (the kernel serves against the
# post-advancement commit), so the checker must replay commit before serve.
# detail semantics: xfer = target node; read issue/serve = the captured read
# index.
EV_XFER = 13
EV_READ_ISSUE = 14
EV_READ_SERVE = 15
# Log-carried configuration kinds (models/cfglog.py), PER NODE -- the
# admin-era cluster-scope EV_EPOCH is gone with the admin model: configuration
# is per-node derived state now, so its events attribute to the node whose
# log changed. All three replay after the role/commit/truncate kinds,
# matching the kernel's end-of-tick derivation. detail semantics:
#   cfg_append    config-entry slots written to this node's log this tick
#                 (origination or replication)
#   cfg_apply     the node's NEW cfg_epoch after entries entered its derived
#                 config (apply-on-append: same tick as the append on the
#                 real kernel; commit-lagged on the act-on-commit mutant)
#   cfg_rollback  the node's NEW cfg_epoch after a truncation REMOVED config
#                 entries from its prefix (the dissertation's rollback)
EV_CFG_APPEND = 16
EV_CFG_APPLY = 17
EV_CFG_ROLLBACK = 18
# Durable storage plane kinds (raft_sim_tpu/storage), PER NODE. They slot in
# numerically BEFORE the cluster kinds (which shifted 19/20 -> 21/22 at trace
# schema N_KINDS=23): the slot table is kind-major ascending with the cluster
# kinds last, so every per-node kind must number below them. Both replay after
# EV_RESTART -- the checker's vote-durability model needs the restart's
# un-cast to land before the same tick's covering flush clears the pending
# set. detail semantics:
#   fsync          the node's new durable length (dur_len) after the flush;
#                  the flag fires on ANY durable-snapshot advance (dur_len
#                  up, or dur_term/dur_vote changed -- phase 7.5 is the only
#                  writer that moves them that way, so the event IS a
#                  completed flush; the truncation clamp only lowers dur_len
#                  and recovery never touches the snapshot)
#   recover_trunc  the node's recovered log length: a log_len DROP on a
#                  `restarted` node is always the recovery truncation
#                  (restarted nodes receive nothing, so the AE conflict
#                  truncation cannot co-occur); the same delta also fires
#                  the plain EV_TRUNCATE -- this kind marks it as recovery
EV_RECOVER_TRUNC = 19
EV_FSYNC = 20
EV_VIOLATION = 21
EV_PARTITION = 22
N_KINDS = 23

KINDS = {
    "follower": EV_FOLLOWER,
    "precandidate": EV_PRECANDIDATE,
    "candidate": EV_CANDIDATE,
    "leader": EV_LEADER,
    "term": EV_TERM,
    "vote": EV_VOTE,
    "commit": EV_COMMIT,
    "append": EV_APPEND,
    "truncate": EV_TRUNCATE,
    "crash": EV_CRASH,
    "restart": EV_RESTART,
    "drop": EV_DROP,
    "violation": EV_VIOLATION,
    "partition": EV_PARTITION,
    "xfer": EV_XFER,
    "read_issue": EV_READ_ISSUE,
    "read_serve": EV_READ_SERVE,
    "cfg_append": EV_CFG_APPEND,
    "cfg_apply": EV_CFG_APPLY,
    "cfg_rollback": EV_CFG_ROLLBACK,
    "fsync": EV_FSYNC,
    "recover_trunc": EV_RECOVER_TRUNC,
}
KIND_NAMES = {v: k for k, v in KINDS.items()}

# Per-NODE kinds in slot order; the cluster-scope kinds follow them with
# node = NIL. Slot m's (node, kind) pair is a compile-time constant -- only
# the flag and detail are data.
PER_NODE_KINDS = (
    EV_FOLLOWER, EV_PRECANDIDATE, EV_CANDIDATE, EV_LEADER, EV_TERM, EV_VOTE,
    EV_COMMIT, EV_APPEND, EV_TRUNCATE, EV_CRASH, EV_RESTART, EV_DROP,
    EV_XFER, EV_READ_ISSUE, EV_READ_SERVE,
    EV_CFG_APPEND, EV_CFG_APPLY, EV_CFG_ROLLBACK,
    # Storage kinds replay LAST among per-node kinds: recovery precedes the
    # flush in the kernel (phase -1 vs 7.5), and the checker's vote-
    # durability model needs the restart's un-cast (EV_RESTART, above) to
    # land before the same tick's covering flush clears the pending set.
    EV_RECOVER_TRUNC, EV_FSYNC,
)
assert PER_NODE_KINDS == tuple(sorted(PER_NODE_KINDS))  # slot order == kind order
CLUSTER_KINDS = (EV_VIOLATION, EV_PARTITION)

# Violation bitmask bits (EV_VIOLATION detail).
VIOL_ELECTION = 1
VIOL_COMMIT = 2
VIOL_LOG_MATCHING = 4

# Coverage role axis: the four node roles plus a fifth row for cluster-scope
# events (trace/ring.py's role x kind bitmap).
ROLE_DIM = 5
ROLE_CLUSTER = 4
assert {FOLLOWER, CANDIDATE, LEADER, PRECANDIDATE} == {0, 1, 2, 3}


def n_slots(n: int) -> int:
    """Candidate event slots per cluster per tick (static given N)."""
    return n * len(PER_NODE_KINDS) + len(CLUSTER_KINDS)


def slot_nodes(n: int) -> np.ndarray:
    """[M] int32 node id per slot (NIL for cluster-scope slots); static."""
    per_node = np.tile(np.arange(n, dtype=np.int32), len(PER_NODE_KINDS))
    return np.concatenate([per_node, np.full(len(CLUSTER_KINDS), NIL, np.int32)])


def slot_kinds(n: int) -> np.ndarray:
    """[M] int32 event kind per slot; static. Kind-major layout: slot order
    IS the within-tick event order (module docstring)."""
    per_node = np.repeat(np.asarray(PER_NODE_KINDS, np.int32), n)
    return np.concatenate([per_node, np.asarray(CLUSTER_KINDS, np.int32)])


class TickEvents(NamedTuple):
    """One tick's candidate events over the static slot table: `flags[m]` is
    whether slot m's (node, kind) event occurred, `detail[m]` its payload and
    `role[m]` the emitting node's role AFTER the tick (ROLE_CLUSTER for
    cluster-scope slots) -- the coverage bitmap's role axis. Leaves are [M]
    single-cluster or [M, B] batch-minor."""

    flags: jax.Array  # [M(, B)] bool
    detail: jax.Array  # [M(, B)] int32
    role: jax.Array  # [M(, B)] int32 in [0, ROLE_DIM)


def _bc(x, like):
    """Broadcast a per-cluster scalar ([],[B]) to one slot row ([1],[1, B])."""
    return jnp.broadcast_to(jnp.asarray(x), like.shape[1:])[None]


def extract(
    cfg: RaftConfig,
    old: ClusterState,
    new: ClusterState,
    inp: StepInputs,
    info: StepInfo,
    crashed: jax.Array,
    cut_now: jax.Array,
    cut_prev: jax.Array,
) -> TickEvents:
    """Derive this tick's events from the state delta (old -> new), the tick
    inputs, and the kernel's StepInfo. `crashed`/`cut_now`/`cut_prev` are the
    fault-lattice facts StepInputs does not carry (faults.trace_fault_inputs:
    the crash edge and the partition cut-edge counts at now and now - 1,
    recomputed from the same key streams as make_inputs). All-integer and
    elementwise over the node axis: works on [N] and [N, B] leaves alike."""
    n = cfg.n_nodes
    z32 = jnp.zeros_like(new.term)

    def became(role_code):
        return (new.role == role_code) & (old.role != role_code)

    # Incoming-drop count per receiver: popcount of the packed delivery row
    # (diagonal self-bit included in the mask, so delivered <= n). Under the
    # compacted layout the word plane ships flat ([N*W(, B)], ops/tile.py):
    # restore the [N, W(, B)] row view first.
    dm = inp.deliver_mask
    if cfg.compact_planes:
        dm = dm.reshape((n, -1) + dm.shape[1:])
    delivered = bitplane.count(dm, axis=1)  # [N(, B)]
    dropped = jnp.int32(n) - delivered
    burst = dropped >= max(1, (n + 1) // 2)

    # Per-node (flag, detail) blocks, in PER_NODE_KINDS order.
    vote_flag = (new.voted_for != old.voted_for) & (new.voted_for != NIL)
    if cfg.durable_storage:
        # Recovery REWINDS votedFor to the durable snapshot on restart ticks
        # (storage/plane.recover): that state change is not a grant, and a
        # restarted node receives nothing this tick so no genuine grant can
        # co-occur -- suppress, or the checker would read the rewind as a
        # second vote. (Gated: without the plane restart preserves votedFor
        # and the suppression would be dead structure in the program.)
        vote_flag = vote_flag & ~inp.restarted
    blocks = (
        (became(FOLLOWER), new.term),
        (became(PRECANDIDATE), new.term),
        (became(CANDIDATE), new.term),
        (became(LEADER), new.term),
        (new.term > old.term, new.term),
        (vote_flag, new.voted_for),
        (new.commit_index > old.commit_index, new.commit_index),
        (new.log_len > old.log_len, new.log_len),
        (new.log_len < old.log_len, new.log_len),
        (crashed, z32),
        # Restart detail = the node's POST-tick term: recovery can rewind
        # the term (a decrease the EV_TERM increase-delta cannot see), so
        # the checker re-anchors its per-node term model here. Pre-storage-
        # plane histories carry detail 0 (the checker skips those).
        (inp.restarted, new.term),
        (burst, dropped),
    )
    # Reconfiguration-plane kinds, delta-derived like everything else (the
    # serve-vs-cancel disambiguation rides the kernel's documented clear
    # rules: a slot dropped while its holder stays a same-term leader was
    # SERVED; every cancel path -- role loss, term adoption, restart --
    # changes role/term or sets `restarted`). Structurally gated configs
    # leave these planes untouched, so the flags are constant-false there.
    xfer_flag = (new.xfer_to != old.xfer_to) & (new.xfer_to != NIL)
    read_issue = (new.read_idx > 0) & (new.read_idx != old.read_idx)
    read_serve = (
        (old.read_idx > 0)
        & (new.read_idx == 0)
        & (new.role == LEADER)
        & (new.term == old.term)
        & ~inp.restarted
    )
    # Log-carried configuration kinds, per node: append = the log_cfg plane
    # gained entries (delta over the slot planes, statically gated --
    # disabled configs carry the plane untouched and the compare would be
    # [N, CAP]-sized dead work); apply/rollback = the derived cfg_epoch
    # moved (the end-of-tick derivation counts config entries in the
    # prefix, so epoch-up = entries entered the effective config and
    # epoch-down = a truncation removed them). Known append-event limit: a
    # slot-value compare cannot see a config entry re-replicated into a slot
    # still holding the IDENTICAL code from a truncated-away predecessor
    # (truncation shortens log_len without scrubbing slots) -- the coverage
    # bitmap undercounts that one append, but the epoch channel still fires
    # cfg_apply for it, so the checker's config replay is unaffected.
    if cfg.reconfig:
        chg = (new.log_cfg != old.log_cfg) & (new.log_cfg != 0)
        cfg_append = jnp.any(chg, axis=1)
        cfg_append_d = jnp.sum(chg, axis=1).astype(jnp.int32)
        cfg_apply = new.cfg_epoch > old.cfg_epoch
        cfg_rollback = new.cfg_epoch < old.cfg_epoch
    else:
        cfg_append = jnp.zeros(new.term.shape, bool)
        cfg_append_d = z32
        cfg_apply = jnp.zeros(new.term.shape, bool)
        cfg_rollback = jnp.zeros(new.term.shape, bool)
    # Durable storage plane kinds (kind-numbering comment above): flush =
    # any durable-snapshot advance; recovery truncation = log drop on a
    # restarted node. Structurally gated like the config kinds -- without
    # the plane the dur legs are carry passthroughs and the compares would
    # be constant-false dead work.
    if cfg.durable_storage:
        fsync_flag = (
            (new.dur_len > old.dur_len)
            | (new.dur_term != old.dur_term)
            | (new.dur_vote != old.dur_vote)
        )
        rec_trunc = inp.restarted & (new.log_len < old.log_len)
    else:
        fsync_flag = jnp.zeros(new.term.shape, bool)
        rec_trunc = jnp.zeros(new.term.shape, bool)
    blocks = blocks + (
        (xfer_flag, new.xfer_to),
        (read_issue, new.read_idx - 1),
        (read_serve, old.read_idx - 1),
        (cfg_append, cfg_append_d),
        (cfg_apply, new.cfg_epoch),
        (cfg_rollback, new.cfg_epoch),
        (rec_trunc, new.log_len),
        (fsync_flag, new.dur_len),
    )
    viol_mask = (
        info.viol_election_safety * VIOL_ELECTION
        + info.viol_commit * VIOL_COMMIT
        + info.viol_log_matching * VIOL_LOG_MATCHING
    ).astype(jnp.int32)
    like = new.term[:1]  # [1(, B)] template for cluster rows
    cluster = (
        (_bc(viol_mask != 0, like), _bc(viol_mask, like)),
        (_bc(cut_now != cut_prev, like), _bc(cut_now, like)),
    )
    flags = jnp.concatenate([f for f, _ in blocks] + [f for f, _ in cluster])
    detail = jnp.concatenate(
        [jnp.broadcast_to(d, f.shape).astype(jnp.int32) for f, d in blocks]
        + [jnp.broadcast_to(d, f.shape).astype(jnp.int32) for f, d in cluster]
    )
    role_rows = jnp.concatenate(
        [new.role for _ in PER_NODE_KINDS]
        + [_bc(jnp.int32(ROLE_CLUSTER), like) for _ in CLUSTER_KINDS]
    ).astype(jnp.int32)
    return TickEvents(flags=flags, detail=detail, role=role_rows)


def any_of_kind(cfg: RaftConfig, ev: TickEvents, kind: int) -> jax.Array:
    """Per-cluster bool: any event of `kind` fired this tick -- the
    flight-recorder / trace freeze trigger predicate (slot kinds are static,
    so this is a static row-select + any-reduce)."""
    sel = slot_kinds(cfg.n_nodes) == kind  # static [M]
    idx = np.flatnonzero(sel)
    return jnp.any(ev.flags[idx], axis=0)
