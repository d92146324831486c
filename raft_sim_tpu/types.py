"""Struct-of-arrays state for batched Raft cluster simulation.

The reference keeps per-node state in a Clojure map (init-node, core.clj:31-38) plus a
log atom {:entries [{:term,:val}] :commit-index} (log.clj:33-34), and exchanges messages
as JSON over HTTP with core.async channels as mailboxes (server.clj:37, client.clj:18).

Here one *cluster* is a pytree of dense arrays over the node axis N; a fleet's public
layout puts the batch first ([batch, N, ...]), and the tick kernel carries it last
(models/raft_batched.to_batch_minor). Messages live in a dense [N, N] mailbox -- one
in-flight slot per directed edge, indexed [dst, src] -- replacing the reference's
buffered(5) channels.
Overwriting an undelivered slot is a legal drop (the reference drops on any HTTP
exception, client.clj:38-40), and requests/responses occupy separate mailboxes because a
request sent at tick t is handled at t+1 and its response lands at t+2, mirroring the
reference's two-tick RPC structure (SURVEY.md section 3.2).

Integers default to int32; the [N, N]-shaped planes ride narrower types (int16 for
log-index bookkeeping and ack ages, int8 for window offsets -- bounds asserted by
RaftConfig) because they dominate HBM traffic at large N, and the purely BOOLEAN
planes (the votes bitmap, the pre-vote grant bits, and the per-tick delivery mask)
pack 32 bits per uint32 word along the source-node axis (ops/bitplane.py:
[N, W = ceil(N/32)] words instead of [N, N] bytes; quorum checks become word
popcounts). Node ids are 0-based with
-1 as nil (the reference uses 1-based ids and `nil`, core.clj:31-38). Log indices are
1-based counts like the reference/spec (entry i lives at array slot i-1; index 0
means "no entry", log.clj:20-23).

The `# [shape] dtype` comment on every field below is a CHECKED CONTRACT, not
decoration: the static analyzer parses them (analysis/policy.py, rule
`dtype-comment`) and verifies shape rank and dtype against the structures
init_state/make_inputs/step_one actually build, across the policy tiers (the
index_dtype/ack_dtype functions here ARE the policy). Keep them parseable --
leading `[dims] dtype` (or `scalar dtype`), with `/`-separated alternatives
resolved through the named policy function.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# ACK_AGE_SAT* are re-exported here because state builders read them alongside
# ClusterState; they live in config (the leaf module) for the validator.
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.utils import config as config_mod
from raft_sim_tpu.utils.config import (
    ACK_AGE_SAT,
    ACK_AGE_SAT_NARROW,
    MAX_LOG_CAPACITY,
    RaftConfig,
)
from raft_sim_tpu.utils.rng import draw_timeouts

# Node roles (reference keywords :follower/:candidate/:leader, core.clj:31-38;
# the reference's misspelled :follwer (core.clj:76) is a documented bug, not carried).
FOLLOWER = 0
CANDIDATE = 1
LEADER = 2
# PreVote probe state (cfg.pre_vote; Raft thesis 9.6 -- BEYOND the reference,
# which has no pre-vote, SURVEY.md 2.3.12): an expired node probes a majority
# at its PROSPECTIVE next term before bumping its real term, so a partitioned
# node cannot inflate its term and depose a stable leader on rejoin.
PRECANDIDATE = 3

# Request mailbox record types (reference URI routing, server.clj:8-12;
# REQ_PREVOTE extends the set -- see PRECANDIDATE above).
REQ_NONE = 0
REQ_VOTE = 1  # :request-vote
REQ_APPEND = 2  # :append-entries
REQ_PREVOTE = 3  # pre-vote probe (carries the prospective term = sender term + 1)
# TimeoutNow (Raft thesis 3.10; cfg.leader_transfer -- BEYOND the reference):
# a transferring leader tells its caught-up target to start an election
# IMMEDIATELY, bypassing both the election timer and pre-vote. The target node
# id rides the Mailbox.xfer_tgt header; only the target acts on the broadcast.
REQ_TIMEOUT_NOW = 4

# Response mailbox record types (client.clj:8-9 keywordizes :type from the HTTP
# body). A pre-vote response's GRANT rides the packed pv_grant bit-plane
# (Mailbox.pv_grant): unlike real votes, one responder may grant SEVERAL
# pre-candidates per tick (grants are non-binding and consume no votedFor), so
# the grant cannot ride the per-responder v_to field.
RESP_NONE = 0
RESP_VOTE = 1  # :vote-response
RESP_APPEND = 2  # :append-response
RESP_PREVOTE = 3  # pre-vote response; the grant bit rides Mailbox.pv_grant

NIL = -1  # nil node id

# Bins of the per-entry commit-latency histogram (StepInfo.lat_hist): bin k
# holds latencies with floor(log2(l)) == k, so 16 bins cover 1 .. 2^16-1 ticks
# with the last bin absorbing anything longer.
LAT_HIST_BINS = 16
# Log value of a leader no-op entry (compaction only): appended on election win so
# a current-term entry exists to pull old-term entries through the spec-5.4.2
# commit gate (models/raft_batched.py phase 6). Reserved: client commands may not use it.
NOOP = -2

# log_capacity ceiling for int8 index planes: the single-pass window-start min
# (models/raft_batched.py phase 8) encodes self as +2K and unresponsive peers as
# +K with K = cap + 1, so the largest encoded value is 2K + cap = 3*cap + 2,
# which must fit the plane dtype. The ceiling is DERIVED from that encoding
# bound (utils/config.max_log_capacity_for, shared with analysis Pass E) so
# widening it without widening the dtype (or the encoding) is impossible by
# construction, not just caught by an assert: (127 - 2) // 3 = 41.
MAX_INT8_LOG_CAPACITY = config_mod.max_log_capacity_for(127)
assert config_mod.window_min_encoding_max(MAX_INT8_LOG_CAPACITY) <= 127  # int8 tier
assert config_mod.window_min_encoding_max(MAX_LOG_CAPACITY) <= 32767  # int16 tier


def ack_dtype(cfg: RaftConfig):
    """Dtype of the ack-age plane: int8 whenever the saturation ceiling fits it
    (cfg.ack_age_sat; +1 per tick before the clamp stays within range)."""
    return jnp.int8 if cfg.ack_age_sat < 127 else jnp.int16


def index_dtype(cfg: RaftConfig):
    """Dtype of the per-edge log-index planes (next/match) and the per-responder
    match/hint wire fields. Log indices are bounded by log_capacity without
    compaction -- int8 up to capacity 41, int16 up to 4095 -- and absolute
    (unbounded) with it: int32."""
    if cfg.compaction:
        return jnp.int32
    return jnp.int8 if cfg.log_capacity <= MAX_INT8_LOG_CAPACITY else jnp.int16


# n_nodes ceiling for int8 node-id wire fields (Mailbox xfer_tgt/v_to/a_ok_to and
# the kernel's grant_to/a_ok_to casts): ids 0..n-1 plus the NIL = -1 sentinel and
# the `n` sentinel the min-select patterns use must all fit the dtype. Derived
# (utils/config.max_nodes_for, shared with analysis Pass E): 127 - 1 = 126
# keeps n itself (the sentinel) a valid int8 value with a slot to spare.
MAX_INT8_NODES = config_mod.max_nodes_for(127)


def node_dtype(cfg: RaftConfig):
    """Dtype of node-id wire fields (Mailbox xfer_tgt/v_to/a_ok_to): int8 up to
    126 nodes, int16 for the giant-N tier (config7x, N=255). Node ids in the
    STATE (voted_for/leader_id) stay int32 -- they are [N]-shaped headers, not
    planes, so narrowing them buys nothing next to the [N, N] traffic."""
    return jnp.int8 if cfg.n_nodes <= MAX_INT8_NODES else jnp.int16


class Mailbox(NamedTuple):
    """In-flight RPC state, one tick deep. TPU-native wire format, v9 (+ the
    round-6 packed pre-vote grant bit-plane, checkpoint v18).

    Both RPCs are logically broadcasts (the reference sends RequestVote and
    AppendEntries to every peer, core.clj:48-67), and after the shared-window prev
    clamp the only genuinely per-edge request datum is a tiny window offset. So the
    wire format carries request HEADERS per sender ([N] -- one record broadcast to
    all peers) and only two per-edge planes, cutting the [N, N]-shaped mailbox
    traffic from ten int32 fields to two int8 planes (the mailbox was the dominant
    HBM traffic of the N=51 tick: ~5x the logical state bytes):

      req_* / ent_* headers: [N(sender)] -- receivers reduce senders over axis 0
        after outer-producting with the per-edge delivery mask.
      req_off:  [sender, receiver] -- AppendEntries per-edge window offset j.
      resp_kind: [receiver, responder] -- RESP_* type of the response on that
        edge; the response payload is per RESPONDER (below).
      pv_grant: [receiver, W] -- the pre-vote grant BITS, bit-packed over the
        responder axis (ops/bitplane.py; 32 responders per uint32 word). The
        only genuinely boolean per-edge response datum: one voter may grant
        several probing pre-candidates in the same tick, so the grant can ride
        neither v_to nor the resp_kind value -- it used to occupy bit 2 of the
        int8 resp_kind plane and now costs W words per receiver instead of a
        byte per edge. All-zero (and carried untouched, so XLA sees a
        loop-invariant component) unless cfg.pre_vote.

    AppendEntries reconstruction at receiver d from sender s (validated against the
    usual prev checks, so spec-equivalent to an explicit per-edge header):
      prev_index = ent_start[s] + req_off[s, d]          (j = req_off in 0..E)
      prev_term  = ent_prev_term[s] if j == 0 else ent_term[s, j-1]
      n_entries  = clip(ent_count[s] - j, 0, E)
      entries    = ent_term/ent_val[s, j :]              (window slot k holds the
                                                          1-based entry ent_start+k+1)
      leaderCommit = req_commit[s]
    The shared E-entry window (reference ships arbitrary per-peer suffixes,
    core.clj:59-67) starts at the minimum prev-index among RESPONSIVE peers (acked
    an AppendEntries within config.ack_timeout_ticks, ClusterState.ack_age; falls
    back to all peers when none are responsive, so a dead peer cannot pin the
    window start and stall replication); each peer's prev is clamped into
    [ent_start, ent_start + E], which is what makes j fit 0..E.

    Responses carry :vote-response {term,vote-granted} (core.clj:95-102) and
    :append-response {term,success,log-index} (core.clj:109-121). The payloads are
    per RESPONDER, not per edge, because one responder's per-tick response surface
    is sparse by construction: it grants at most ONE vote (phase 2's single-grant
    rule) and acks at most ONE AppendEntries sender (phase 3 selects one
    current-term AE; election safety allows only one), and every denial it sends
    shares the same payload (the vote denial carries only resp_term; the AE nack's
    catch-up hint is the responder's log length -- the same value toward every
    sender). So requester q decodes responder r's edge [q, r] as:
      vote:   granted = (v_to[r] == q)
      append: success = (a_ok_to[r] == q);
              match   = a_match[r] if success else a_hint[r]  (nack conflict hint)
    with resp_term[r] the responder's term at send time (same toward every
    requester). This replaces v8's per-edge packed int16/int32 response word --
    less [N, N] traffic, and the acked index is a full int32, so nothing bounds
    committed entries (v8's packed word capped compaction runs at 2^28).
    """

    req_type: jax.Array  # [N(sender)] int32 in [0, 4] (REQ_*): this tick's broadcast, if any
    req_term: jax.Array  # [N] int32: sender's term at send time
    req_commit: jax.Array  # [N] int32: AE leaderCommit
    req_last_index: jax.Array  # [N] int32: RV lastLogIndex
    req_last_term: jax.Array  # [N] int32: RV lastLogTerm
    ent_start: jax.Array  # [N] int32 in [0, cap]: 1-based index before src's shared window (= prev at j=0)
    ent_prev_term: jax.Array  # [N] int32: term of the 1-based entry ent_start (j=0 prev)
    ent_count: jax.Array  # [N] int32 in [0, E]: entries shipped = min(log_len - ent_start, E)
    ent_term: jax.Array  # [N, E] int32: src's shared entry window (terms)
    ent_val: jax.Array  # [N, E] int32: src's shared entry window (values)
    # Offer-tick plane of the shared window (cfg.track_offer_ticks only; zeros
    # and carried untouched otherwise): entry k's offer stamp rides the wire
    # NEXT TO its value, so replication preserves the latency metadata while
    # values stay arbitrary client payloads (VERDICT missing #1: payloads used
    # to BE the offer ticks, so colliding client values corrupted the metric).
    ent_tick: jax.Array  # [N, E] int32: src's shared entry window (offer stamps)
    # Snapshot header (compaction only; zeros otherwise): an AE sender's compaction
    # state (lastIncludedIndex/-Term + the checksum of the compacted prefix). An
    # edge whose req_off is the SNAP sentinel -1 is an InstallSnapshot analogue:
    # the receiver installs (req_base, req_base_term, req_base_chk) instead of
    # appending entries (models/raft_batched.py phase 3).
    req_base: jax.Array  # [N] int32: sender's log_base (snapshot lastIncludedIndex)
    req_base_term: jax.Array  # [N] int32: snapshot lastIncludedTerm
    req_base_chk: jax.Array  # [N] uint32: checksum of the compacted prefix
    # Leadership-transfer header (cfg.leader_transfer only; NIL and carried
    # untouched otherwise): the target of the sender's TimeoutNow broadcast
    # (REQ_TIMEOUT_NOW). Per sender like every request header -- a leader
    # fires at most one transfer per tick.
    xfer_tgt: jax.Array  # [N(sender)] int8/int16 (node_dtype) in [NIL, N-1]: TimeoutNow target node (NIL = none)
    # Disruptive-RequestVote flag (thesis 4.2.3's override, paired with
    # TimeoutNow in 3.10): set on the RequestVote broadcast of a transfer-
    # triggered election, so voters holding the heard-a-leader denial (live
    # under cfg.reconfig -- the removed-server disruption defense -- or
    # cfg.read_lease) still process THIS election: it was sanctioned by the
    # leader being replaced, so denying it would deadlock every transfer.
    # Written only when the flag has a reader (cfg.leader_transfer AND a
    # denial gate); zeros and carried untouched otherwise.
    req_disrupt: jax.Array  # [N(sender)] int8 in [0, 1]: 1 = transfer-sanctioned RequestVote
    # Config-entry plane of the shared window (cfg.reconfig only; zeros and
    # carried untouched otherwise): entry k's config command replicates NEXT
    # TO its value, exactly like the offer-stamp plane -- so a follower's
    # log prefix carries the configuration history its derived membership
    # reads (models/cfglog.py). 0 = not a config entry; +(v+1) = joint entry
    # toggling node v; -(v+1) = final entry completing that toggle.
    ent_cfg: jax.Array  # [N, E] int32: src's shared entry window (config commands)
    # Snapshot config header (compaction AND reconfig; zeros otherwise): the
    # sender's configuration context at its compaction base, installed with
    # the snapshot so the receiver's derived config stays exact when config
    # entries were compacted away (base_mold/base_pend/base_epoch legs).
    req_base_mold: jax.Array  # [N, W] uint32: sender's C_old at its base
    req_base_pend: jax.Array  # [N] int32: sender's pending toggle code at base
    req_base_epoch: jax.Array  # [N] int32: sender's config-entry count at base
    req_off: jax.Array  # [N(sender), N(receiver)] int8 in [-1, E]: AE window offset j; -1 = snapshot
    resp_kind: jax.Array  # [N(receiver), N(responder)] int8 in [0, 3] (RESP_*): response type per edge
    pv_grant: jax.Array  # [N(receiver), W] uint32: packed pre-vote grant bits (bit = responder)
    v_to: jax.Array  # [N(responder)] int8/int16 (node_dtype) in [NIL, N]: candidate granted this tick (NIL = none; N = masked no-sender sentinel)
    a_ok_to: jax.Array  # [N(responder)] int8/int16 (node_dtype) in [NIL, N]: AE sender acked OK this tick (NIL = none; N = masked no-sender sentinel)
    a_match: jax.Array  # [N(responder)] int16/int32 (index_dtype) in [0, cap]: acked index of the successful append
    a_hint: jax.Array  # [N(responder)] int16/int32 (index_dtype) in [0, cap]: nack hint (responder's log length)
    resp_term: jax.Array  # [N(responder)] int32: responder's term at send time


class ClusterState(NamedTuple):
    """Full per-cluster simulator state (the scan carry).

    Maps the reference node map + log atom (SURVEY.md section 2.2) onto arrays:
      role/term/voted_for/leader_id  <- :state/:current-term/:voted-for/:leader-id
      votes [N,W] packed bitmap      <- :votes set (core.clj:38)
      next_index/match_index [N,N]   <- :leader-state maps (core.clj:40-42)
      log_term/log_val/log_len       <- log atom :entries (log.clj:33)
      commit_index                   <- log atom :commit-index
      clock/deadline                 <- async/timeout channels (core.clj:171-174)
    """

    role: jax.Array  # [N] int32 in [0, 3] (FOLLOWER..PRECANDIDATE)
    term: jax.Array  # [N] int32 (starts at 1, core.clj:34)
    voted_for: jax.Array  # [N] int32 in [NIL, N] (NIL = none; N = masked no-candidate sentinel)
    leader_id: jax.Array  # [N] int32 in [NIL, N] (NIL = unknown; N = masked no-sender sentinel)
    # Bit-packed votes bitmap (ops/bitplane.py): bit j of votes[i] set = node i
    # holds a granted vote (or pre-vote grant, while PRECANDIDATE) from node j.
    # The quorum test is a word popcount (bitplane.count >= cfg.quorum), and the
    # plane costs W = ceil(N/32) uint32 words per node instead of N bool bytes
    # (N=51: 2 words = 8 bytes vs 51 bytes carried per node per tick).
    votes: jax.Array  # [N, W] uint32; bit j of votes[i] = i holds a vote from j
    # The three [N, N] leader-bookkeeping planes are the largest state after the
    # mailbox; log indices are capacity-bounded (int8 up to capacity 41, int16 up
    # to 4095 -- index_dtype) and ages saturate (ACK_AGE_SAT), cutting their HBM
    # traffic vs int32. Compaction configs carry absolute (unbounded) indices:
    # int32. Under cfg.compact_planes the CARRY form of these planes (and of
    # req_off/resp_kind/votes/the entry windows/the delivery mask) is the
    # bit-packed flat uint32 layout of ops/tile.py; the comments below state
    # the dense contract the kernel computes on (tile.unpack_state at tick
    # entry, pack_state at exit -- bit-identical trajectories either way).
    next_index: jax.Array  # [N, N] index_dtype in [1, cap+1]; leader i's next index for peer j
    match_index: jax.Array  # [N, N] index_dtype in [0, cap]
    # Ticks since leader i last received an AppendEntries response (success OR
    # failure -- both prove the peer is up) from peer j, saturating at
    # cfg.ack_age_sat (int8 plane whenever that ceiling fits -- ack_dtype);
    # zeroed for the whole row when i wins an election (grace period). Volatile
    # leader bookkeeping like next/match; drives the shared-entry-window
    # responsiveness filter (config.ack_timeout_ticks).
    ack_age: jax.Array  # [N, N] ack_dtype in [0, sat] (int8/int16)
    commit_index: jax.Array  # [N] int32 in [0, cap]
    # Weighted checksum of the committed prefix (log_ops.chk_weights), maintained
    # when config.check_invariants: the "committed entries are immutable" invariant
    # checks one pass over the new log arrays against this instead of re-reading the
    # old arrays every tick. Stays 0 when invariant checking is off. Hand-built
    # states that set commit_index directly must refresh it via
    # types.with_commit_chk (the invariant trips otherwise -- by design).
    commit_chk: jax.Array  # [N] uint32
    # Compaction state (all zeros when cfg.compact_margin == 0). Entries 1..log_base
    # have been compacted away: they exist only as this triple (the snapshot). The
    # Raft persistent set grows to include it (a restart keeps base and resumes with
    # commit = log_base). Invariant: log_base <= commit_index <= log_len and
    # log_len - log_base <= CAP (the retained window fits the ring).
    log_base: jax.Array  # [N] int32: snapshot lastIncludedIndex
    base_term: jax.Array  # [N] int32: snapshot lastIncludedTerm
    base_chk: jax.Array  # [N] uint32: checksum of entries 1..log_base
    # Ring log: 1-based entry i lives at slot (i - 1) mod CAP; live slots hold
    # entries (log_base, log_len]. With compaction off, log_base == 0 and the ring
    # degenerates to the plain prefix layout (entry i at slot i-1, log_len <= CAP).
    log_term: jax.Array  # [N, CAP] int32
    log_val: jax.Array  # [N, CAP] int32
    # Offer-tick plane (cfg.track_offer_ticks; zeros and carried untouched
    # otherwise): slot k holds entry k's offer stamp (offer tick + 1; 0 for
    # no-ops and non-client entries), written at injection and replicated via
    # Mailbox.ent_tick. The commit-latency metric reads THIS plane, so client
    # values are arbitrary int32 payloads -- a value equal to some tick can no
    # longer corrupt the histogram (the round-4 collision caveat). Measurement
    # metadata, not protocol state: excluded from the commit checksum and the
    # log-matching compare, and restart-persistent alongside the log it tags.
    log_tick: jax.Array  # [N, CAP] int32
    log_len: jax.Array  # [N] int32 in [0, cap]
    # Durable storage plane (raft_sim_tpu/storage; all legs zeros/boot values
    # and carried untouched unless cfg.durable_storage). The dissertation's
    # section 3.8 persistent triple -- currentTerm, votedFor, the log -- is
    # durable only up to these watermarks: entries (0, dur_len] have been
    # fsynced, and dur_term/dur_vote are the term/vote as of the last flush.
    # A flush (StepInputs.fsync_fire) snaps all three to the node's live
    # values; a crash-restart REWINDS the node to them (the un-fsynced log
    # suffix is lost, term/vote revert to the durable snapshot), minus any
    # torn tail (StepInputs.torn_drop) the recovery checksum rejects.
    # Truncation clamps dur_len down with log_len (removed entries are no
    # longer durable as log content). v1 excludes compaction (dur_len would
    # have to fold across snapshot installs) -- asserted by RaftConfig.
    dur_len: jax.Array  # [N] int32 in [0, cap]: fsynced log prefix length (<= log_len)
    dur_term: jax.Array  # [N] int32: term at the last flush (boot: 1)
    dur_vote: jax.Array  # [N] int32: votedFor at the last flush (NIL = none)
    clock: jax.Array  # [N] int32 local (skewable) clock
    deadline: jax.Array  # [N] int32 next timer fire on the local clock
    # Local-clock stamp of the last valid leader contact (accepted current-term
    # AppendEntries), driving the thesis-9.6 pre-vote denial rule: a voter
    # denies pre-votes while it heard from a leader within the minimum election
    # timeout. Volatile (restart resets it to "long quiet"). Maintained only
    # when cfg.pre_vote; untouched (loop-invariant) otherwise.
    heard_clock: jax.Array  # [N] int32
    # Reconfiguration plane (cfg.reconfig; zeros and carried untouched
    # otherwise -- raft_sim_tpu/reconfig, thesis chapter 4). LOG-CARRIED,
    # PER-NODE protocol state: configuration changes ride the replicated log
    # as entries (the log_cfg plane below), and each node's effective
    # configuration is DERIVED FROM ITS OWN LOG PREFIX -- applied the moment
    # an entry is appended (never waiting for commit, dissertation ch. 4)
    # and rolled back when a truncation removes it (models/cfglog.py is the
    # single derivation; docs/PROTOCOL.md states the model). These four
    # leaves are the derived cache, recomputed at the end of every tick from
    # the post-append post-compaction log; quorum tests -- election,
    # pre-vote promotion, commit advancement, ReadIndex/lease confirmation
    # -- read the TICK-START values, each node masking by ITS OWN rows (dual
    # popcount of member_old AND member_new while that node's cfg_pend marks
    # an uncompleted joint entry in its prefix). Log-derived state survives
    # restart with the log; crash faults never touch it directly.
    member_old: jax.Array  # [N, W] uint32: node i's C_old from its own log prefix
    member_new: jax.Array  # [N, W] uint32: node i's C_new (== C_old outside joint)
    cfg_epoch: jax.Array  # [N] int32: config entries in node i's prefix (+ base_epoch)
    cfg_pend: jax.Array  # [N] int32: abs index of the governing joint entry (0 = none)
    # Config-entry log plane (cfg.reconfig; zeros otherwise): slot k's config
    # command, written beside log_term/log_val at every append (AE
    # replication via Mailbox.ent_cfg, leader origination at injection) and
    # ZEROED by non-config appends, so a truncated-then-overwritten slot can
    # never leak a stale config entry into the derivation. Encoding:
    # 0 = not a config entry; +(v+1) = joint entry toggling node v's
    # membership; -(v+1) = final entry completing that toggle. Part of the
    # Raft persistent log (restart keeps it).
    log_cfg: jax.Array  # [N, CAP] int32
    # Snapshot config context (compaction AND reconfig; zeros otherwise):
    # the configuration facts at log_base, so the derivation stays exact
    # after committed config entries compact away -- C_old at base, the
    # pending (unmatched-joint) toggle code at base (0 = none), and the
    # config-entry count at/below base. Advances with log_base (the
    # compacted span's entries fold in) and installs from the snapshot
    # header (Mailbox.req_base_mold/...). Restart-persistent with the
    # snapshot triple it extends.
    base_mold: jax.Array  # [N, W] uint32: C_old at log_base
    base_pend: jax.Array  # [N] int32: pending toggle code at base (0 = none)
    base_epoch: jax.Array  # [N] int32: config entries at or below base
    # Leadership-transfer plane (cfg.leader_transfer; NIL and carried
    # untouched otherwise): a transferring leader's pending TimeoutNow target
    # (thesis 3.10). Volatile leader state: cleared on role loss, term
    # adoption, restart, or target unresponsiveness; re-fired each heartbeat
    # while pending and caught up (a dropped TimeoutNow retries).
    xfer_to: jax.Array  # [N] int32 in [NIL, N-1]: pending transfer target (NIL = idle)
    # ReadIndex plane (cfg.read_index; zeros and carried untouched otherwise
    # -- thesis 6.4): one pending read slot per node. read_idx holds the
    # captured commit index + 1 (0 = no pending read) -- capture is gated on
    # the leader having committed a current-term entry; read_acks banks the
    # packed per-peer AppendEntries responses received SINCE capture, and the
    # read is served once they reach a (configuration-aware) majority with
    # the slot's captured index covered by commit. Volatile leader state:
    # wiped on restart, role loss, or term change.
    read_idx: jax.Array  # [N] int32: pending read's captured index + 1 (0 = none)
    read_tick: jax.Array  # [N] int32: offer stamp of the pending read
    read_acks: jax.Array  # [N, W] uint32: packed acks banked since capture
    # Lease-read staleness anchor (cfg.read_lease; zeros and carried
    # untouched otherwise -- thesis 6.4.1): the cluster's committed frontier
    # (lat_frontier semantics: max commit any node ever reached) banked at
    # the pending read's CAPTURE tick. A served read whose captured index
    # falls below this frontier missed writes committed before it was issued
    # -- the exact read_linearizability property the trace checker verifies,
    # here as a per-tick device invariant (StepInfo.viol_read_stale) so the
    # scenario hunt's fitness can see lease violations. Measurement state
    # like lat_frontier, not node state: crash faults never touch it beyond
    # the slot wipe it shares with read_idx.
    read_fr: jax.Array  # [N] int32: frontier at the pending read's capture
    # Client-side state (cfg.client_redirect; NIL/0 otherwise): up to K =
    # cfg.client_pipeline commands the simulated client has in flight and the
    # node each one's next POST targets -- the array form of the reference
    # client chasing HTTP 302 redirects (core.clj:151-160) through a
    # buffered(K) request channel (server.clj:37). Not node state: crash
    # faults never touch it.
    client_pend: jax.Array  # [K] int32 command values in flight (NIL = free slot)
    client_dst: jax.Array  # [K] int32 node each pending command targets
    # Offer stamp of each in-flight slot (redirect mode with the offer-tick
    # plane active; zeros otherwise): latency is measured from the OFFER, so
    # the stamp must survive the 302 bounces alongside the payload -- it used
    # to ride the value itself (tick-encoded payloads), now it rides here.
    client_tick: jax.Array  # [K] int32 offer stamps of the in-flight commands
    # Monotone commit-latency frontier: the highest commit index any node of this
    # cluster has ever reached. The latency metric counts an entry when the live
    # leader's commit first passes it; dedup against this CARRIED maximum (not
    # the restart-mutable per-node commit vector) so a restarted max-commit node
    # regressing to its log_base cannot make a later leader re-count entries
    # already reported (advisor finding, round 4). Measurement state, not node
    # state: crash faults never touch it. Zero unless cfg.track_offer_ticks.
    lat_frontier: jax.Array  # scalar int32
    now: jax.Array  # scalar int32 global tick counter
    mailbox: Mailbox


class StepInputs(NamedTuple):
    """Pure per-tick inputs. Randomness is *materialized outside* the step kernel so the
    same arrays can drive both the jnp kernel and the Python oracle (tests), and so fault
    schedules are plain data (SURVEY.md section 5, failure injection).

    This boundary is what makes the scenario engine free: per-cluster fault
    genomes and phased nemesis programs (raft_sim_tpu/scenario) change only
    how sim/faults.make_inputs FILLS these arrays -- the step kernels consume
    the identical structure either way, so the genome path adds zero step
    lowerings and a homogeneous genome is bit-exact with the scalar path."""

    # Bit-packed delivery mask (ops/bitplane.py), packed over the SOURCE axis:
    # bit s of deliver_mask[d] clear = the message on physical edge [d, s]
    # (addressed to d, sent by s) is dropped this tick. sim/faults.py generates
    # it packed; kernels consume the packed words in the response-side delivery
    # reduction and unpack once for the transposed request orientation; the
    # oracle unpacks (tests/oracle.py). W = ceil(N/32).
    deliver_mask: jax.Array  # [N, W] uint32; bit src of row dst
    skew: jax.Array  # [N] int32 in [0, 2] local-clock increment this tick (normally 1)
    timeout_draw: jax.Array  # [N] int32 election timeout to use on any timer reset
    client_cmd: jax.Array  # scalar int32 command value offered this tick; NIL = none
    # Client routing draws (cfg.client_redirect; zeros otherwise): the node a
    # fresh offer targets, and the random peer each pipeline slot's leaderless
    # redirect bounces to (core.clj:154).
    client_target: jax.Array  # scalar int32 in [0, N-1]
    client_bounce: jax.Array  # [K] int32 in [0, N-1]
    alive: jax.Array  # [N] bool; False = node crashed this tick (silent, frozen)
    restarted: jax.Array  # [N] bool; True = node came back up this tick (volatile wipe)
    # Reconfiguration-plane admin commands (all NIL unless their gate is on;
    # raft_sim_tpu/reconfig). Cluster-scoped offers handled by the lowest-id
    # live member leader, exactly like the direct client's command offer:
    #   reconfig_cmd  toggle node v's voting membership (add if absent,
    #                 remove if present; refused while a joint phase is
    #                 pending or when the removal would leave < 2 voters)
    #   transfer_cmd  ask the current leader to transfer leadership to node v
    #   read_cmd      offer one ReadIndex read (the read-only traffic class)
    # Python-int NIL defaults (not jnp scalars: a module-level jnp array
    # would initialize the backend at import, before driver.select_backend)
    # so hand-built test inputs predating the plane stay valid; make_inputs
    # always materializes real arrays.
    reconfig_cmd: jax.Array = NIL  # scalar int32 in [NIL, N-1]; NIL = none
    transfer_cmd: jax.Array = NIL  # scalar int32 in [NIL, N-1]; NIL = none
    read_cmd: jax.Array = NIL  # scalar int32 in [NIL, 1]: 0/1 flag encoded as value; NIL = none
    # Durable storage plane draws (cfg.durable_storage; all-zero arrays
    # otherwise -- sim/faults._storage_draws). fsync_fire marks the nodes
    # whose disk completes a flush THIS tick (the cadence tick, minus the
    # per-node latency-jitter stall draw); torn_drop is the extra entries a
    # recovery's tail checksum rejects IF the node restarts this tick (the
    # torn-tail write; consumed only on restart ticks, drawn every tick so
    # the key stream is schedule-independent). fsync_stall marks the nodes
    # whose flush was due this tick (the cadence tick) but the jitter draw
    # stalled: fsync_fire and fsync_stall partition the due ticks, and the
    # kernel reads fsync_stall only to count stalls. Python-scalar defaults
    # like the admin commands above; make_inputs always materializes real [N]
    # arrays (the dtype-comment contract fixes the rank per field).
    fsync_fire: jax.Array = False  # [N] bool; True = flush completes this tick
    torn_drop: jax.Array = 0  # [N] int32: torn-tail entries dropped at recovery
    fsync_stall: jax.Array = False  # [N] bool; True = a due flush stalled this tick


class StepInfo(NamedTuple):
    """Small per-tick outputs: on-device safety invariants + observability reductions
    (SURVEY.md section 5, metrics). All scalars per cluster."""

    viol_election_safety: jax.Array  # bool: two leaders share a term
    viol_commit: jax.Array  # bool: commit regressed or exceeds log length
    viol_log_matching: jax.Array  # bool (False unless cfg.check_log_matching)
    leader: jax.Array  # int32: lowest-id current leader, NIL if none
    n_leaders: jax.Array  # int32: number of nodes in LEADER role
    max_term: jax.Array  # int32
    max_commit: jax.Array  # int32
    min_commit: jax.Array  # int32
    msgs_delivered: jax.Array  # int32: request+response records delivered this tick
    cmds_injected: jax.Array  # int32 0/1: an offered command was accepted by a live leader
    # Offer->commit latency, measured at the live leader's commit advancement
    # (the ack point the reference's never-firing commit watch was meant to be,
    # log.clj:83-87): entries carry their offer stamp in the log_tick plane, so
    # newly committed client entries contribute (now - offer_tick) each. Zeros
    # unless cfg.track_offer_ticks.
    lat_sum: jax.Array  # int32: sum of commit latencies of entries committed this tick
    lat_cnt: jax.Array  # int32: number of client entries committed this tick
    # Per-entry latency histogram: bin k counts entries committed this tick whose
    # latency l (in ticks, >= 1) has floor(log2(l)) == k, clamped to the last
    # bin. Fixed log-spaced bins make true fleet p50/p95/p99 recoverable in
    # summarize, where the old accumulators only supported a mean of means.
    # Known undercount (round-5 advisor): the metric attributes entries at a
    # LIVE LEADER's commit advancement, but lat_frontier advances past
    # max(commit) even on leaderless ticks (followers advance commit from a
    # downed leader's final req_commit), so entries whose first commit happens
    # in a leaderless window are permanently excluded from lat_sum/lat_cnt/
    # lat_hist. Under crash churn the histogram is therefore an undercount of
    # committed client entries -- biased toward fault-free windows, never
    # double-counting -- and `lat_excluded` below COUNTS the dropped entries so
    # the coverage gap is measured, not guessed (docs/PERF.md "latency metric
    # coverage" carries the quantified numbers).
    lat_hist: jax.Array  # [LAT_HIST_BINS] int32 (zeros unless track_offer_ticks)
    # Client entries the latency frontier crossed this tick WITHOUT being
    # counted into lat_sum/lat_cnt/lat_hist: the frontier advances to
    # max(commit) every tick, but attribution needs a live leader, so entries
    # first committed in a leaderless window fall through. Counted on the
    # (lowest-id) max-commit node whose commit defines the frontier advance;
    # exact without compaction, conservative (clamped >= 0) with it, where the
    # max-commit node may already have compacted a crossed slot away.
    lat_excluded: jax.Array  # int32 (zero unless track_offer_ticks)
    # Election wins that could NOT append their no-op because the ring held no
    # free slot (compaction only). The no-op reserve guarantees room for
    # max(1, compact_margin // 2) consecutive commit-free elections; a deeper
    # commit-free chain would freeze commit permanently (the 5.4.2 deadlock the
    # no-op exists to break), so any nonzero count here makes that latent
    # livelock visible instead of silent (advisor finding, round 4).
    noop_blocked: jax.Array  # int32: count of win & no-noop-room events this tick
    # Node pairs the compaction-form log-matching check could not compare this
    # tick (one node's base passed the other's commit; their agreement is pinned
    # transitively and via checksums). Measures the ring check's coverage
    # instead of assuming it. Zero unless check_log_matching ran this tick.
    lm_skipped_pairs: jax.Array  # int32: unordered pairs skipped by the check
    # ReadIndex read-traffic metrics (zeros unless cfg.read_index): reads
    # served this tick, their summed offer->serve latency, and the same
    # log2-binned histogram shape the commit-latency metric uses -- so
    # telemetry can report commit-vs-read latency side by side.
    reads_served: jax.Array  # int32: ReadIndex reads served this tick
    read_lat_sum: jax.Array  # int32: summed offer->serve latency of served reads
    read_hist: jax.Array  # [LAT_HIST_BINS] int32 (zeros unless read_index)
    # Lease-read staleness invariant (cfg.read_lease AND check_invariants;
    # a host-constant zero otherwise, with the fold gated like the read
    # metrics -- scan.step_bad): a read was SERVED whose captured index sits
    # below the committed frontier banked at its capture (ClusterState.
    # read_fr). Folds into RunMetrics.violations, so the scenario hunt's
    # fitness sees lease violations the classic viol_* flags cannot -- the
    # device-visible form of the checker's read_linearizability property.
    # Defaulted so hand-built StepInfos predating the lease plane stay valid.
    viol_read_stale: jax.Array = False  # bool: a stale lease read was served
    # Durability lag (cfg.durable_storage; host-constant zeros otherwise, with
    # the folds gated like the read metrics -- scan._accumulate): how far the
    # simulated disks trail the live logs at end of tick, as the sum and max
    # over nodes of (log_len - dur_len). The health plane's durability_lag
    # SLI and the per-window fsync-lag counters read these; a disk that
    # stalls (fsync_jitter_prob) shows up here before it shows up as a
    # replication stall. Defaulted so hand-built StepInfos predating the
    # storage plane stay valid.
    fsync_lag_sum: jax.Array = 0  # int32: sum over nodes of log_len - dur_len
    fsync_lag_max: jax.Array = 0  # int32: max over nodes of log_len - dur_len
    # Plane counters, each a host-constant zero while its gate is off (folds
    # gated like the read metrics): whether any node ends the tick in a joint
    # configuration (cfg.reconfig), the TimeoutNow requests sent this tick
    # (cfg.leader_transfer), and the reads the lease served this tick that no
    # confirmed ReadIndex round did (cfg.read_lease; a subset of
    # reads_served). Defaulted like the storage counters.
    joint_ticks: jax.Array = 0  # int32: 1 when any node's cfg_pend > 0
    xfer_fired: jax.Array = 0  # int32: TimeoutNow requests sent
    lease_reads: jax.Array = 0  # int32: reads served by the lease alone
    # Storage-plane counters (cfg.durable_storage; host-constant zeros
    # otherwise, folded like the plane counters above): due flushes a live
    # node's latency jitter stalled this tick, log entries restarting nodes
    # lost at recovery (log_len minus the recovered length: the un-fsynced
    # tail past the torn-tail salvage), and vote responses sent late, on the
    # tick the flush that made their grant durable landed.
    fsync_stalls: jax.Array = 0  # int32: due flushes stalled on live nodes
    torn_lost: jax.Array = 0  # int32: log entries dropped by recoveries
    late_grants: jax.Array = 0  # int32: late vote responses sent
    # Latency-pass fallback (track_offer_ticks with CAP > 256, where the
    # offer->commit pass reads the log_ops.LAT_WINDOW entries above the
    # frontier; a host-constant zero otherwise, folded like the counters
    # above): 1 on a tick whose pass swept the whole ring instead, because
    # some cluster of the batch advanced its max commit past the window
    # (raft_batched.offer_latency_b). Summed over clusters and ticks, over
    # the cluster-ticks run, it is the share of fallback ticks.
    lat_ring_ticks: jax.Array = 0  # int32: 1 when the pass swept the ring


def empty_mailbox(cfg: RaftConfig) -> Mailbox:
    n, e = cfg.n_nodes, cfg.max_entries_per_rpc
    i = lambda *s: jnp.zeros(s, jnp.int32)
    return Mailbox(
        req_type=i(n),
        req_term=i(n),
        req_commit=i(n),
        req_last_index=i(n),
        req_last_term=i(n),
        ent_start=i(n),
        ent_prev_term=i(n),
        ent_count=i(n),
        ent_term=i(n, e),
        ent_val=i(n, e),
        ent_tick=i(n, e),
        req_base=i(n),
        req_base_term=i(n),
        req_base_chk=jnp.zeros((n,), jnp.uint32),
        xfer_tgt=jnp.full((n,), NIL, node_dtype(cfg)),
        req_disrupt=jnp.zeros((n,), jnp.int8),
        ent_cfg=i(n, e),
        req_base_mold=jnp.zeros((n, bitplane.n_words(n)), jnp.uint32),
        req_base_pend=i(n),
        req_base_epoch=i(n),
        req_off=jnp.zeros((n, n), jnp.int8),
        resp_kind=jnp.zeros((n, n), jnp.int8),
        pv_grant=jnp.zeros((n, bitplane.n_words(n)), jnp.uint32),
        v_to=jnp.full((n,), NIL, node_dtype(cfg)),
        a_ok_to=jnp.full((n,), NIL, node_dtype(cfg)),
        a_match=jnp.zeros((n,), index_dtype(cfg)),
        a_hint=jnp.zeros((n,), index_dtype(cfg)),
        resp_term=i(n),
    )


def init_state(cfg: RaftConfig, key: jax.Array) -> ClusterState:
    """Fresh cluster: all followers at term 1 with empty logs (init-node core.clj:31-38,
    Log.start log.clj:32-34) and randomized initial election deadlines (the reference
    randomizes per wait-loop iteration, core.clj:174)."""
    n, cap = cfg.n_nodes, cfg.log_capacity
    idt = index_dtype(cfg)
    deadline = draw_timeouts(cfg, key, n)
    state = ClusterState(
        role=jnp.full((n,), FOLLOWER, jnp.int32),
        term=jnp.ones((n,), jnp.int32),
        voted_for=jnp.full((n,), NIL, jnp.int32),
        leader_id=jnp.full((n,), NIL, jnp.int32),
        votes=jnp.zeros((n, bitplane.n_words(n)), jnp.uint32),
        next_index=jnp.ones((n, n), idt),
        match_index=jnp.zeros((n, n), idt),
        ack_age=jnp.full((n, n), cfg.ack_age_sat, ack_dtype(cfg)),
        commit_index=jnp.zeros((n,), jnp.int32),
        commit_chk=jnp.zeros((n,), jnp.uint32),
        log_base=jnp.zeros((n,), jnp.int32),
        base_term=jnp.zeros((n,), jnp.int32),
        base_chk=jnp.zeros((n,), jnp.uint32),
        log_term=jnp.zeros((n, cap), jnp.int32),
        log_val=jnp.zeros((n, cap), jnp.int32),
        log_tick=jnp.zeros((n, cap), jnp.int32),
        log_len=jnp.zeros((n,), jnp.int32),
        # Durable boot state: the empty log is trivially durable, and the
        # boot term-1/no-vote pair counts as flushed (a node that crashes
        # before its first flush recovers to boot state, not to garbage).
        dur_len=jnp.zeros((n,), jnp.int32),
        dur_term=jnp.ones((n,), jnp.int32),
        dur_vote=jnp.full((n,), NIL, jnp.int32),
        clock=jnp.zeros((n,), jnp.int32),
        deadline=deadline,
        # "Quiet since before time began": pre-votes are grantable at boot.
        heard_clock=jnp.full((n,), -cfg.election_min_ticks, jnp.int32),
        # Reconfiguration plane: every node derives the all-voters boot
        # config from its (empty) log prefix when the plane is live --
        # per-node rows, one per node; all-zero dead weight otherwise.
        member_old=(
            jnp.broadcast_to(bitplane.full_row(n), (n, bitplane.n_words(n)))
            if cfg.reconfig
            else jnp.zeros((n, bitplane.n_words(n)), jnp.uint32)
        ),
        member_new=(
            jnp.broadcast_to(bitplane.full_row(n), (n, bitplane.n_words(n)))
            if cfg.reconfig
            else jnp.zeros((n, bitplane.n_words(n)), jnp.uint32)
        ),
        cfg_epoch=jnp.zeros((n,), jnp.int32),
        cfg_pend=jnp.zeros((n,), jnp.int32),
        log_cfg=jnp.zeros((n, cap), jnp.int32),
        base_mold=(
            jnp.broadcast_to(bitplane.full_row(n), (n, bitplane.n_words(n)))
            if cfg.reconfig
            else jnp.zeros((n, bitplane.n_words(n)), jnp.uint32)
        ),
        base_pend=jnp.zeros((n,), jnp.int32),
        base_epoch=jnp.zeros((n,), jnp.int32),
        xfer_to=jnp.full((n,), NIL, jnp.int32),  # NIL = idle, gate on or off
        read_idx=jnp.zeros((n,), jnp.int32),
        read_tick=jnp.zeros((n,), jnp.int32),
        read_acks=jnp.zeros((n, bitplane.n_words(n)), jnp.uint32),
        read_fr=jnp.zeros((n,), jnp.int32),
        client_pend=jnp.full((cfg.client_pipeline,), NIL, jnp.int32),
        client_dst=jnp.zeros((cfg.client_pipeline,), jnp.int32),
        client_tick=jnp.zeros((cfg.client_pipeline,), jnp.int32),
        lat_frontier=jnp.int32(0),
        now=jnp.int32(0),
        mailbox=empty_mailbox(cfg),
    )
    if cfg.compact_planes:
        # Compacted carry layout (ops/tile.py): the per-edge value planes
        # ride bit-packed flat uint32 legs, the narrow word/window planes
        # ride flattened. The field comments above document the DENSE
        # contract (the kernel's working view; the layout tiers are priced
        # by Pass C, not re-declared here).
        from raft_sim_tpu.ops import tile

        state = tile.pack_state(cfg, state)
    return state


def with_commit_chk(state: ClusterState) -> ClusterState:
    """Refresh commit_chk from the current log arrays + commit_index (single-cluster
    state). For tests and state surgery that set commit_index by hand. Ring-aware:
    states with log_base > 0 must carry a correct base_chk already."""
    from raft_sim_tpu.ops import log_ops

    (live,) = log_ops.ring_chk(
        state.log_term, state.log_val, state.log_base, (state.commit_index,)
    )
    return state._replace(commit_chk=state.base_chk + live)


def init_batch(cfg: RaftConfig, key: jax.Array, batch: int) -> ClusterState:
    """[batch, ...] struct-of-arrays over independent clusters, each with its own seed."""
    return jax.vmap(lambda k: init_state(cfg, k))(jax.random.split(key, batch))


def compact_twin(cfg: RaftConfig, on: bool = True) -> RaftConfig:
    """`cfg` with the compacted carry layout toggled (ops/tile.py): the
    layout A/B's one-knob twin -- trajectories are bit-identical either way,
    only the physical carry form (and therefore the priced bytes/tick)
    moves. Single-sourced here for bench, the traffic audit, and the parity
    tests."""
    import dataclasses

    return dataclasses.replace(cfg, compact_planes=on)
