"""The Raft tick kernel: one pure, vmap'able state transition per simulated tick.

This is the TPU-native re-expression of the reference's `wait` event loop
(core.clj:176-195): deliver -> handle -> collect. Where the reference blocks on
`alts!!` over [inbound requests, rpc responses, timeout] and dispatches ONE message per
loop iteration, the array kernel delivers the whole [N, N] mailbox at once and folds
every node's inbound edges through vectorized handler logic -- `jnp.where` lattices
instead of `cond` cascades, no Python control flow, static shapes throughout.

Handler provenance (all spec-correct; the reference's deviations are catalogued in
SURVEY.md section 2.3 and deliberately NOT carried):

  phase 1  term adoption         <- scattered `(> term current-term)` checks
                                    (core.clj:97, 129-130, 144-145); unlike the
                                    reference, RequestVote also adopts terms (bug 2.3.2)
  phase 2  vote requests         <- request-vote-handler (core.clj:91-103), with the
                                    spec up-to-date check instead of compare-prev?
  phase 3  append requests       <- append-entries-handler (core.clj:105-123), with
                                    spec conflict-truncate-then-append instead of the
                                    remove-from! bug (2.3.7) and real leader-commit
                                    handling instead of apply-everything (2.3.6);
                                    under compaction also the InstallSnapshot
                                    analogue (req_off == -1 edges install the
                                    sender's base/base_term/base_chk)
  phase 5.5 log compaction       <- absent in the reference (its log vector is
                                    unbounded, log.clj:33); the ring must free
                                    committed slots so client workloads never
                                    exhaust the fixed-capacity arrays
  phase 4  responses             <- vote-response-handler (core.clj:125-139) and
                                    append-response-handler (core.clj:141-149), with
                                    next-index = match+1 (bug 2.3.10)
  phase 5  leader commit         <- absent in the reference (bug 2.3.8): quorum-th
                                    largest match index, current-term restriction
  phase 6  client injection      <- client-set-handler's leader branch (core.clj:156-160)
  phase 7  timers                <- generate-timeout + the nil dispatch arm
                                    (core.clj:162-174, 193-195); election timers reset
                                    only on vote grant / valid AppendEntries, not on
                                    every message (bug 2.3.11)
  phase 8  outbox                <- request-vote-rpc / append-entries-rpc
                                    (core.clj:48-67) writing the next tick's mailbox
  phase 9  invariants + metrics  <- absent in the reference; north-star requirement
  phase -1 restart wipe          <- the reference's process-death model (only committed
                                    values are durable, log.clj:16-18); here restart
                                    keeps the Raft persistent triple up to the DURABLE
                                    watermarks (raft_sim_tpu/storage; with
                                    cfg.durable_storage off the disk is perfect and the
                                    full triple survives), wipes volatile state, and
                                    down nodes are gated out of delivery, timers,
                                    leadership, and commit
  phase 7.5 fsync flush          <- absent in the reference (its file-backed atom has
                                    no fsync discipline, log.clj:16-18): the durable
                                    watermarks advance on the device-side fsync model's
                                    completed flushes, and the section-3.8 gates hold
                                    AE acks and vote grants to durable state

Everything is written for ONE cluster (shapes [N], [N, N], [N, CAP]); `jax.vmap` lifts
to [batch, ...] and `lax.scan` (sim/scan.py) rolls ticks.

TRACE DELTA CONTRACT (raft_sim_tpu/trace, cfg.track_trace): the protocol
trace plane derives discrete events from this kernel's state DELTAS --
role, term, voted_for, commit_index, log_len, dur_len, and (reconfiguration
plane) cfg_epoch, log_cfg, xfer_to, read_idx -- outside the kernel (one
extractor serves both kernels and any step_fn override; zero step lowerings
added).
Phase-order properties load-bearing for the whole-history checker, which must
survive refactors: (1) a node that loses leadership and accepts entries in
one tick changes `role` in the SAME tick as `log_len` (phase 1 adoption
precedes phase 3 append -- the checker replays role changes before log
changes); (2) a win (phase 4) can never co-occur with an AE-accept
truncation on the same node (a candidate that accepted a current-term AE
stepped down in phase 3 and cannot win); (3) elections precede the
end-of-tick config derivation, so EV_LEADER events belong to the TICK-START
per-node configuration (EV_CFG_APPLY/ROLLBACK replay after the role
kinds); (4) a read slot dropped
while its holder stays a same-term un-restarted leader was SERVED -- every
cancel path changes role/term or sets `restarted` (phase 5.2's clear
rules); (5) a `dur_len` ADVANCE is always a completed flush (EV_FSYNC: the
only writer besides recovery is phase 7.5, and recovery never raises it),
and a `log_len` DROP on a `restarted` node is always the recovery
truncation (EV_RECOVER_TRUNC: restarted nodes receive nothing, so the
AE conflict truncation cannot co-occur on them). See trace/events.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from raft_sim_tpu.models import cfglog
from raft_sim_tpu.ops import bitplane, log_ops
from raft_sim_tpu.storage import plane as storage_plane
from raft_sim_tpu.types import (
    CANDIDATE,
    FOLLOWER,
    LAT_HIST_BINS,
    LEADER,
    NIL,
    NOOP,
    PRECANDIDATE,
    REQ_APPEND,
    REQ_PREVOTE,
    REQ_TIMEOUT_NOW,
    REQ_VOTE,
    RESP_APPEND,
    RESP_PREVOTE,
    RESP_VOTE,
    ClusterState,
    Mailbox,
    StepInfo,
    StepInputs,
    node_dtype,
)
from raft_sim_tpu.utils.config import RaftConfig


def step(cfg: RaftConfig, s: ClusterState, inp: StepInputs) -> tuple[ClusterState, StepInfo]:
    """Advance one cluster by one tick. Pure; jit/vmap/scan-safe.

    Under cfg.compact_planes the carry arrives in the compacted layout
    (ops/tile.py: per-edge value planes bit-packed into flat uint32 legs,
    word/window planes flattened); this boundary unpacks to the dense
    working view, runs the identical dense tick, and repacks -- gated-off
    mailbox legs are passed through verbatim (`reuse`) so the
    carry-passthrough contract holds exactly as in the dense layout.
    Trajectories are bit-identical either way (tests/test_tile.py)."""
    if not cfg.compact_planes:
        return _step(cfg, s, inp)
    from raft_sim_tpu.ops import tile

    s2, info = _step(cfg, tile.unpack_state(cfg, s), tile.unpack_inputs(cfg, inp))
    return tile.pack_state(cfg, s2, reuse=s), info


def _step(cfg: RaftConfig, s: ClusterState, inp: StepInputs) -> tuple[ClusterState, StepInfo]:
    """The dense tick body (the layout-independent protocol semantics)."""
    n, e, cap = cfg.n_nodes, cfg.max_entries_per_rpc, cfg.log_capacity
    comp = cfg.compaction  # static: ring-log compaction + snapshot catch-up active
    track = cfg.track_offer_ticks  # static: offer-tick plane + latency metric active
    rcf = cfg.reconfig  # static: joint-consensus membership plane active
    xfr = cfg.leader_transfer  # static: TimeoutNow transfer plane active
    dur = cfg.durable_storage  # static: fsync/WAL durability plane active
    rdx = cfg.read_index  # static: ReadIndex read traffic class active
    rdl = cfg.read_lease  # static: lease-based reads (thesis 6.4.1) active
    ids = jnp.arange(n, dtype=jnp.int32)
    eye = jnp.eye(n, dtype=bool)
    eye_p = bitplane.eye(n)  # [N, W] packed self-bit rows (votes plane layout)
    zw = jnp.uint32(0)
    snd_ids = jnp.broadcast_to(ids[:, None], (n, n))  # [sender, receiver] -> sender id

    # ---- phase -1: restart (crash fault) -----------------------------------------
    # A node restarting this tick rejoins as a fresh follower: the Raft persistent
    # triple (currentTerm, votedFor, log[]) survives -- including the snapshot
    # (log_base/base_term/base_chk), so commitIndex resumes at log_base, the
    # durable applied prefix -- everything else is volatile and wiped (Raft fig. 2
    # state table). The reference instead persists only committed values
    # (log.clj:16-18), so its restarted process forgets term/vote -- bug 2.3.12,
    # deliberately not carried. HOW MUCH of the triple survives is the durable
    # storage plane's gate (raft_sim_tpu/storage, cfg.durable_storage): with the
    # gate off the disk is perfect and the full triple survives instantly; with
    # it on, the recovery block below rewinds term/vote to the durable snapshot
    # and truncates the log tail the disk never confirmed (dissertation section
    # 3.8 -- the failure class the plane exists to express). Wiping commitIndex
    # here (before `old` is captured for phase 9) keeps the monotonic-commit
    # invariant meaningful.
    rs = inp.restarted
    s = s._replace(
        role=jnp.where(rs, FOLLOWER, s.role),
        leader_id=jnp.where(rs, NIL, s.leader_id),
        votes=jnp.where(rs[:, None], zw, s.votes),
        next_index=jnp.where(rs[:, None], 1, s.next_index),
        match_index=jnp.where(rs[:, None], 0, s.match_index),
        ack_age=jnp.where(rs[:, None], cfg.ack_age_sat, s.ack_age),
        commit_index=jnp.where(rs, s.log_base, s.commit_index),
        commit_chk=jnp.where(rs, s.base_chk, s.commit_chk),
        deadline=jnp.where(rs, s.clock + inp.timeout_draw, s.deadline),
    )
    if dur:
        # Crash recovery (storage/plane.recover): the disk holds the
        # fsynced prefix for sure plus whatever un-fsynced tail the
        # in-flight writes reached, minus the torn tail the recovery
        # checksum rejects (inp.torn_drop, drawn every tick, consumed only
        # here); term/votedFor rewind to the durable snapshot.
        r_term, r_vote, r_len = storage_plane.recover(
            cfg, rs, inp.torn_drop,
            s.dur_len, s.dur_term, s.dur_vote,
            s.term, s.voted_for, s.log_len,
        )
        s = s._replace(term=r_term, voted_for=r_vote, log_len=r_len)
    if cfg.pre_vote or rdl or cfg.reconfig:
        # A restarted node remembers no leader contact: "quiet" immediately
        # (pre-votes grantable, and -- under the lease or log-carried-config
        # denial gates -- real votes too: a restarted voter holds no
        # obligation toward a leader it no longer remembers).
        s = s._replace(
            heard_clock=jnp.where(
                rs, s.clock - cfg.election_min_ticks, s.heard_clock
            )
        )
    if xfr:
        # A pending transfer is volatile leader state: lost with the process.
        s = s._replace(xfer_to=jnp.where(rs, NIL, s.xfer_to))
    if rdx:
        # Pending reads die with the process too (the client retries).
        s = s._replace(
            read_idx=jnp.where(rs, 0, s.read_idx),
            read_tick=jnp.where(rs, 0, s.read_tick),
            read_acks=jnp.where(rs[:, None], zw, s.read_acks),
        )
        if rdl:
            # The staleness anchor dies with the slot it anchors.
            s = s._replace(read_fr=jnp.where(rs, 0, s.read_fr))
    mb = s.mailbox
    base, bterm, bchk = s.log_base, s.base_term, s.base_chk
    if rcf:
        # Snapshot config context (compaction x reconfig; constant full-row /
        # zero legs otherwise -- carried untouched when comp is off).
        bmold, bpend, bepoch = s.base_mold, s.base_pend, s.base_epoch

    # Reconfiguration plane (cfg.reconfig): log-carried, PER-NODE
    # configuration masking. member_old/member_new/cfg_pend are each node's
    # DERIVED view of its own log prefix (ClusterState docstring; the
    # end-of-tick block recomputes them via models/cfglog.py), so every
    # quorum test below masks by the TESTING NODE's own rows -- dual
    # (majorities of BOTH configurations) while that node's prefix holds an
    # uncompleted joint entry. Quorum tests read the TICK-START derivation;
    # entries appended this tick govern the next (apply-on-append at tick
    # granularity, the same one-tick rule every phase transition follows).
    if rcf:
        m_old, m_new = s.member_old, s.member_new  # [N, W]
        joint = s.cfg_pend > 0  # [N]
        maj_old = bitplane.count(m_old, axis=1) // 2 + 1  # [N] int32
        maj_new = bitplane.count(m_new, axis=1) // 2 + 1
        # Node i's own-membership bit: is i a voter of ITS OWN config union?
        # A node whose log carries its removal quiesces (never campaigns);
        # one whose log MISSES the removal still thinks it votes -- the
        # removed-server disruption the 4.2.3 denial below defends against.
        member_b = jnp.any(((m_old | m_new) & eye_p) != 0, axis=1)  # [N]

        def packed_quorum(rows):
            """[N, W] packed grant rows (node i's banked grants) -> [N] bool
            quorum under node i's OWN configuration(s)."""
            ok = bitplane.count(rows & m_old, axis=1) >= maj_old
            return ok & (
                ~joint | (bitplane.count(rows & m_new, axis=1) >= maj_new)
            )
    else:

        def packed_quorum(rows):
            return bitplane.count(rows, axis=1) >= cfg.quorum

    # ---- phase 0: delivery -------------------------------------------------------
    # The fault mask is the TPU-native form of the reference's silently-dropped HTTP
    # call (client.clj:38-40): a zeroed entry in the delivery mask. A down node is
    # silent in both directions: it receives nothing, and anything it had in flight
    # dies with it (the crashed process's sockets). Mailbox slots hold messages sent
    # last tick, so a node that just restarted must also not see them -- they were
    # addressed to a dead process (alive now & alive at send time = alive & ~restarted).
    # The input mask is indexed by physical directed edge [to, from] and arrives
    # BIT-PACKED over the source axis (StepInputs docstring): the response-side
    # chain ([receiver, responder] = [to, from], same orientation) runs on the
    # packed words -- per-source gates AND as packed rows, per-receiver gates as
    # row selects -- and unpacks once; request fields are stored
    # [sender, receiver] (= [from, to], Mailbox docstring), so the request
    # orientation unpacks the mask and transposes in bool space.
    dst_up = inp.alive & ~inp.restarted
    resp_del_p = jnp.where(
        dst_up[:, None],
        inp.deliver_mask & ~eye_p & bitplane.pack(inp.alive)[None, :],
        zw,
    )  # [N, W]; canonical (ANDed with the canonical input mask)
    deliver_resp = bitplane.unpack(resp_del_p, n, axis=1)
    deliver_req = (
        bitplane.unpack(inp.deliver_mask, n, axis=1).T
        & ~eye
        & inp.alive[:, None]
        & dst_up[None, :]
    )
    req_in = deliver_req & (mb.req_type != 0)[:, None]  # [sender, receiver]
    resp_in = deliver_resp & (mb.resp_kind != 0)  # [receiver, responder]

    # Heard-a-leader denial window (thesis 4.2.3), shared by the log-carried
    # membership defense (rcf: a removed server whose log misses its removal
    # still campaigns -- voters that heard a current leader recently must
    # neither adopt its inflated term nor grant it votes) and the lease vote
    # denial (rdl). Judged on the voter's LOCAL clock against the TICK-START
    # heard_clock -- this tick's AppendEntries land in phase 3, after votes
    # -- which only SHORTENS the window by one tick (the lease validator's
    # +4 slack covers it; docs/PROTOCOL.md). The disruptive-RequestVote
    # override (req_disrupt, set on transfer-triggered elections) bypasses
    # the denial: the leader being replaced sanctioned that election, so
    # denying it would deadlock every TimeoutNow transfer.
    if rcf or rdl:
        heard_recent = (s.clock + inp.skew) - s.heard_clock < cfg.election_min_ticks
        if xfr:
            rv_denied = heard_recent[None, :] & ~(mb.req_disrupt != 0)[:, None]
        else:
            rv_denied = jnp.broadcast_to(heard_recent[None, :], (n, n))

    # ---- phase 1: term adoption --------------------------------------------------
    # Spec: any RPC (request or response) with term T > currentTerm -> set
    # currentTerm = T, convert to follower. The reference does this for responses
    # (core.clj:129-130, 144-145) but not vote requests (bug 2.3.2). A PreVote
    # request's term is PROSPECTIVE (thesis 9.6) -- it must never be adopted.
    if cfg.pre_vote:
        term_req = req_in & (mb.req_type != REQ_PREVOTE)[:, None]
    else:
        term_req = req_in
    if rcf:
        # 4.2.3 in full: a denied RequestVote is not PROCESSED -- its term is
        # not adopted either, so a removed server's inflated term cannot
        # depose a live leader through its own voters (the disruption
        # defense; under rdl alone the PR-11 grant-only denial is kept
        # bit-for-bit -- adoption stays legal there).
        term_req = term_req & ~((mb.req_type == REQ_VOTE)[:, None] & rv_denied)
    in_term = jnp.maximum(
        jnp.max(jnp.where(term_req, mb.req_term[:, None], 0), axis=0),
        jnp.max(jnp.where(resp_in, mb.resp_term[None, :], 0), axis=1),
    )  # [N]
    saw_higher = in_term > s.term
    term = jnp.maximum(s.term, in_term)
    role = jnp.where(saw_higher, FOLLOWER, s.role)
    voted_for = jnp.where(saw_higher, NIL, s.voted_for)
    leader_id = jnp.where(saw_higher, NIL, s.leader_id)
    votes = jnp.where(saw_higher[:, None], zw, s.votes)

    if comp:
        my_last_idx = s.log_len
        my_last_term = log_ops.term_at_r(s.log_term, base, bterm, s.log_len)
    else:
        my_last_idx, my_last_term = log_ops.last_index_term(s.log_term, s.log_len)

    # ---- phase 2: RequestVote requests (request-vote-handler, core.clj:91-103) ----
    is_rv = req_in & (mb.req_type == REQ_VOTE)[:, None]  # [candidate, voter]
    cur_rv = is_rv & (mb.req_term[:, None] == term[None, :])  # stale terms are denied
    # Spec 5.4.1 up-to-date check (the reference's compare-prev? log.clj:55-59 compares
    # against the commit index and whole entry maps -- bugs 2.3.3/2.3.4).
    up_to_date = (mb.req_last_term[:, None] > my_last_term[None, :]) | (
        (mb.req_last_term[:, None] == my_last_term[None, :])
        & (mb.req_last_index[:, None] >= my_last_idx[None, :])
    )
    can_grant = cur_rv & up_to_date
    if rcf or rdl:
        # Heard-a-leader vote denial (thesis 4.2.3; the shared window above):
        # under the lease gate this is the rule 6.4.1 leans on -- a leader
        # whose heartbeats a quorum acked L ticks ago KNOWS no election can
        # complete for election_min_ticks/2 more global ticks (local clocks
        # advance at most 2/tick under skew; the config validator pins the
        # lease term under that bound). Under the log-carried membership
        # plane it is the removed-server disruption defense. The transfer
        # override (rv_denied folds in req_disrupt) lets TimeoutNow
        # elections through either way.
        can_grant = can_grant & ~rv_denied
    # At most one grant per node per tick: the lowest eligible candidate id wins the
    # race (the reference serializes naturally, one message per wait iteration).
    lowest = jnp.min(jnp.where(can_grant, snd_ids, n), axis=0)  # [N], n = none
    grant = jnp.where(
        (voted_for != NIL)[None, :],
        can_grant & (snd_ids == voted_for[None, :]),  # idempotent re-grant
        can_grant & (snd_ids == lowest[None, :]),
    )
    granted_any = jnp.any(grant, axis=0)
    voted_for = jnp.where((voted_for == NIL) & granted_any, lowest, voted_for)
    # Every delivered RV gets a response carrying our (possibly just-adopted) term;
    # [candidate, voter] is already the response orientation [receiver, responder].
    # The grant itself is per RESPONDER: at most one candidate per tick (Mailbox),
    # and a grant always targets the post-update voted_for (re-grants re-name it,
    # fresh grants just set it) -- no reduction over the grant plane needed. Safe
    # to read here: phase 7 cannot rebind voted_for for a granter this tick (a
    # grant resets the election deadline to clock + draw > clock, so the granter
    # cannot also expire).
    vr_out = is_rv
    grant_to = jnp.where(granted_any, voted_for, NIL).astype(node_dtype(cfg))  # [N]

    # ---- phase 3: AppendEntries requests (append-entries-handler, core.clj:105-123) --
    is_ae = req_in & (mb.req_type == REQ_APPEND)[:, None]  # [leader, follower]
    cur_ae = is_ae & (mb.req_term[:, None] == term[None, :])
    # Election safety gives at most one leader per term, so at most one current-term AE
    # sender exists; pick the lowest id defensively (ties indicate a safety violation,
    # which phase 9 flags).
    ae_src = jnp.min(jnp.where(cur_ae, snd_ids, n), axis=0)  # [N]
    has_ae = ae_src < n
    sel = cur_ae & (snd_ids == ae_src[None, :])  # one-hot [sender, receiver]

    # Reconstruct the per-edge AE header from the selected sender's broadcast record
    # plus this edge's window offset j (Mailbox docstring). When no sender is
    # selected everything is zeroed/garbage but gated by has_ae/ae_ok downstream.
    j_in = jnp.sum(jnp.where(sel, mb.req_off, 0), axis=0).astype(jnp.int32)  # [N] in 0..E
    sel_idx = jnp.minimum(ae_src, n - 1)
    # InstallSnapshot analogue (compaction only): offset sentinel -1 means "install
    # my compaction base instead of entries" -- sent when this peer's next_index
    # fell below the leader's log_base (phase 8), the array form of Raft fig. 13.
    # The reference can never need this (its log is unbounded, core.clj:59-67).
    snap = (has_ae & (j_in < 0)) if comp else jnp.zeros((n,), bool)
    ae_norm = has_ae & ~snap
    j_nn = jnp.clip(j_in, 0, e)  # snap's -1 routed to 0; gated by ae_norm downstream
    ws_in = mb.ent_start[sel_idx]  # [N]
    w_term = mb.ent_term[sel_idx]  # [N, E]
    w_val = mb.ent_val[sel_idx]
    w_tick = mb.ent_tick[sel_idx] if track else None
    w_cfg = mb.ent_cfg[sel_idx] if rcf else None
    prev_i = jnp.where(ae_norm, ws_in + j_nn, 0)
    lcommit = jnp.where(ae_norm, mb.req_commit[sel_idx], 0)
    n_ent = jnp.where(ae_norm, jnp.clip(mb.ent_count[sel_idx] - j_nn, 0, e), 0)
    # prev term: the window slot just before this receiver's entries (j-1), or the
    # sender's ent_prev_term for j == 0 -- ext[k] = term of 1-based entry ws+k.
    ext = jnp.concatenate([mb.ent_prev_term[sel_idx][:, None], w_term], axis=1)
    prev_t = jnp.take_along_axis(ext, j_nn[:, None], axis=1)[:, 0]  # [N]
    # This receiver's entries start at window slot j (slot k holds entry ws+k+1).
    off = jnp.clip(j_nn, 0, e - 1)  # j = E only when n_ent = 0 (fully masked)
    ent_term_in = log_ops.window(w_term, off, e)  # [N, E]
    ent_val_in = log_ops.window(w_val, off, e)
    ent_tick_in = log_ops.window(w_tick, off, e) if track else None
    ent_cfg_in = log_ops.window(w_cfg, off, e) if rcf else None

    # A valid AE from the current term makes candidates (and pre-candidates)
    # step down and identifies the leader (core.clj:121-123, minus the :follwer
    # typo, bug 2.3.1).
    if cfg.pre_vote:
        stepdown = (role == CANDIDATE) | (role == PRECANDIDATE)
    else:
        stepdown = role == CANDIDATE
    role = jnp.where(has_ae & stepdown, FOLLOWER, role)
    leader_id = jnp.where(has_ae, ae_src, leader_id)

    # Consistency check (spec 5.3; reference compare-prev? has bugs 2.3.4/2.3.5).
    if comp:
        # prev below the local base is committed-and-compacted: it matches by
        # leader completeness (a current-term leader's log holds every committed
        # entry); at prev == base, term_at_r yields base_term -- the snapshot
        # boundary check.
        prev_stored_term = log_ops.term_at_r(s.log_term, base, bterm, prev_i)
        consistent = (
            (prev_i == 0)
            | (prev_i < base)
            | ((prev_i <= s.log_len) & (prev_stored_term == prev_t))
        )
    else:
        prev_stored_term = log_ops.term_at(s.log_term, prev_i)
        consistent = (prev_i == 0) | (
            (prev_i <= s.log_len) & (prev_stored_term == prev_t)
        )
    ae_ok = ae_norm & consistent

    # Conflict scan over the shipped window: first mismatching entry truncates the rest
    # of the log; matching prefixes are never truncated (spec 5.3 "delete the existing
    # entry and all that follow it").
    ks = jnp.arange(e, dtype=jnp.int32)
    gidx0 = prev_i[:, None] + ks[None, :]  # [N, E] 0-based entry indices
    if comp:
        # Skip entries the ring already compacted (abs index <= base) and accept
        # only what it can hold (entries past base + CAP would evict live,
        # un-compacted slots; the partial ack makes the leader retry the rest
        # after this node's own commit+compaction frees room).
        lo = jnp.clip(base - prev_i, 0, e)  # [N]
        n_acc = jnp.minimum(n_ent, jnp.maximum(base + cap - prev_i, 0))
        in_ent = (ks[None, :] >= lo[:, None]) & (ks[None, :] < n_acc[:, None])
        stored = log_ops.window_r(s.log_term, prev_i, e)  # [N, E]
        appended_len = prev_i + n_acc
    else:
        n_acc = n_ent
        in_ent = ks[None, :] < n_ent[:, None]
        stored = log_ops.window(s.log_term, prev_i, e)  # [N, E]
        appended_len = jnp.minimum(prev_i + n_ent, cap)
    exists = gidx0 < s.log_len[:, None]
    mismatch = in_ent & exists & (stored != ent_term_in)
    any_mismatch = jnp.any(mismatch, axis=1)
    new_len = jnp.where(
        any_mismatch, appended_len, jnp.maximum(s.log_len, appended_len)
    )
    log_len = jnp.where(ae_ok, new_len, s.log_len)
    if dur:
        # Truncation makes the removed suffix non-durable AS LOG CONTENT: the
        # watermark clamps down with the log (the bytes may sit on disk, but
        # the durable-log contract is about the entries the recovery would
        # reconstruct, and those are gone). Appends do NOT advance it -- only
        # a completed flush does (phase 7.5).
        dur_mid = jnp.minimum(s.dur_len, log_len)
    wmask = ae_ok[:, None] & in_ent
    if comp:
        log_term_arr = log_ops.write_window_r(s.log_term, prev_i, ent_term_in, wmask)
        log_val_arr = log_ops.write_window_r(s.log_val, prev_i, ent_val_in, wmask)
    else:
        log_term_arr = log_ops.write_window(s.log_term, prev_i, ent_term_in, wmask)
        log_val_arr = log_ops.write_window(s.log_val, prev_i, ent_val_in, wmask)
    # The offer-stamp plane replicates with the entries it tags (same masks, so
    # it can never diverge from the value plane's slot occupancy).
    if track:
        wwr = log_ops.write_window_r if comp else log_ops.write_window
        log_tick_arr = wwr(s.log_tick, prev_i, ent_tick_in, wmask)
    else:
        log_tick_arr = s.log_tick  # untouched: loop-invariant carry leg
    # The config-entry plane replicates under the SAME masks: non-config
    # entries ship 0, so an accepted window scrubs any stale config command
    # off the slots it overwrites (the rollback hazard the derivation
    # depends on -- ClusterState.log_cfg docstring).
    if rcf:
        wwc = log_ops.write_window_r if comp else log_ops.write_window
        log_cfg_arr = wwc(s.log_cfg, prev_i, ent_cfg_in, wmask)
    else:
        log_cfg_arr = s.log_cfg  # untouched: loop-invariant carry leg

    # Follower commit: min(leaderCommit, index of last new entry), monotonic
    # (the reference's apply-entries! commits everything unconditionally, bug 2.3.6).
    # The floor at 0 is a no-op on the ae_ok path (prev_i/n_acc are
    # non-negative for a real AE) but bounds the masked-garbage lane so the
    # int8/int16 a_match narrowing below is provably in range (Pass E).
    last_new = jnp.maximum(jnp.minimum(prev_i + n_acc, log_len), 0)
    commit = jnp.where(
        ae_ok,
        jnp.maximum(s.commit_index, jnp.minimum(lcommit, last_new)),
        s.commit_index,
    )

    # Snapshot install (compaction only). L <= base needs nothing (we already hold
    # that prefix -- plain ack); otherwise, if our log extends through L with the
    # snapshot's term, retain the suffix (Raft fig. 13 rule 6), else discard the
    # whole log. Either way our compaction state becomes the leader's and commit
    # advances to at least L (everything below a snapshot is committed).
    if comp:
        L = jnp.where(snap, mb.req_base[sel_idx], 0)
        Lt = mb.req_base_term[sel_idx]
        Lchk = mb.req_base_chk[sel_idx]
        apply_snap = snap & (L > base)
        keep = (
            apply_snap
            & (L <= s.log_len)
            & (log_ops.term_at_r(s.log_term, base, bterm, L) == Lt)
        )
        wipe = apply_snap & ~keep
        bterm = jnp.where(apply_snap, Lt, bterm)
        bchk = jnp.where(apply_snap, Lchk, bchk)
        base = jnp.where(apply_snap, L, base)
        log_len = jnp.where(wipe, L, log_len)
        commit = jnp.where(apply_snap, jnp.maximum(commit, L), commit)
        if rcf:
            # The snapshot carries its configuration context: the sender's
            # C_old/pending-toggle/entry-count at L, so the receiver's
            # derivation stays exact over config entries it never saw.
            bmold = jnp.where(
                apply_snap[:, None], mb.req_base_mold[sel_idx], bmold
            )
            bpend = jnp.where(apply_snap, mb.req_base_pend[sel_idx], bpend)
            bepoch = jnp.where(apply_snap, mb.req_base_epoch[sel_idx], bepoch)
    else:
        apply_snap = jnp.zeros((n,), bool)

    # Respond to every delivered AE; success only for the selected, consistent one
    # (snapshot installs always ack, with match = the snapshot index). A NACK
    # carries the responder's log length as a catch-up hint: the leader jumps
    # next_index straight to hint+1 instead of decrementing once per heartbeat --
    # the standard conflict-index optimization (Raft paper section 5.3 "the
    # protocol can be optimized"). Without it a freshly elected leader walks next
    # down 1 per nack while client traffic grows its log ~1 per tick, and under
    # recurring crash churn no current-term entry ever reaches quorum (measured
    # livelock: commit frozen for thousands of ticks).
    # [leader, follower] is already the response orientation [receiver, responder];
    # the payload is per responder (at most one success target -- Mailbox).
    ar_out = is_ae
    if comp:
        a_ok = ae_ok | snap
        out_a_match = jnp.where(snap, L, jnp.where(ae_ok, last_new, 0))
    else:
        a_ok = ae_ok
        out_a_match = jnp.where(ae_ok, last_new, 0)
    idt = s.next_index.dtype
    out_a_ok_to = jnp.where(a_ok, ae_src, NIL).astype(node_dtype(cfg))  # NIL = no success
    out_a_match = out_a_match.astype(idt)  # bounded by the responder's log length
    out_a_hint = log_len.astype(idt)  # post-append, pre-injection (phase 6 rebinds)

    # ---- phase 3.5: PreVote requests (thesis 9.6; cfg.pre_vote) ------------------
    # Grant iff the probe's prospective term is not behind us, the probing log is
    # up to date (the phase-2 check -- probes fill the same req_last_* header),
    # and we are QUIET: not a leader ourselves and no valid AppendEntries
    # accepted within the minimum election timeout (including this tick's).
    # Grants are non-binding: no votedFor, no term change, no timer reset.
    if cfg.pre_vote or rdl or rcf:
        # heard_clock maintenance serves three consumers: the pre-vote quiet
        # rule (below), the lease vote denial, and the log-carried-config
        # removed-server denial (both phase 2) -- any gate keeps the leg
        # live.
        clock_pv = s.clock + inp.skew  # phase 7's clock; duplicated, CSE'd
        heard = jnp.where(has_ae, clock_pv, s.heard_clock)  # [N]
    else:
        heard = s.heard_clock
    if cfg.pre_vote:
        is_pv = req_in & (mb.req_type == REQ_PREVOTE)[:, None]  # [cand, voter]
        quiet = (clock_pv - heard >= cfg.election_min_ticks) & (role != LEADER)
        pv_grant = (
            is_pv & (mb.req_term[:, None] >= term[None, :]) & up_to_date & quiet[None, :]
        )
        pv_out = is_pv

    # ---- phase 3.7: TimeoutNow receipt (thesis 3.10; cfg.leader_transfer) --------
    # The transfer target starts a REAL election IMMEDIATELY: no timer, no
    # pre-vote probe (the thesis's explicit bypass -- the target is known
    # caught up, and the transferring leader's lease would make every voter
    # deny a probe). Gated on the request carrying the receiver's CURRENT
    # term, so a stale TimeoutNow from a deposed leader (or one that already
    # succeeded: the new leader's term moved past it) is inert. The election
    # itself fires in phase 7 alongside timer-driven starts.
    if xfr:
        is_tn = req_in & (mb.req_type == REQ_TIMEOUT_NOW)[:, None]  # [sender, recv]
        tn_cur = (
            is_tn
            & (mb.xfer_tgt[:, None] == ids[None, :])
            & (mb.req_term[:, None] == term[None, :])
        )
        xfer_elect = jnp.any(tn_cur, axis=0) & inp.alive & (role != LEADER)
        if rcf:
            xfer_elect = xfer_elect & member_b  # non-voters never campaign
        if not cfg.xfer_election:
            # TEST-ONLY mutant (cfg.xfer_election False): transfer as a coup.
            # The target assumes leadership DIRECTLY -- no vote round, no
            # up-to-date check -- so a behind target replicates its short log
            # over committed entries (the violation the hunt must re-find).
            coup = xfer_elect
            term = term + coup
            role = jnp.where(coup, LEADER, role)
            leader_id = jnp.where(coup, ids, leader_id)
            xfer_elect = jnp.zeros((n,), bool)
        else:
            coup = jnp.zeros((n,), bool)

    # ---- phase 4: responses ------------------------------------------------------
    # Vote tally (vote-response-handler core.clj:125-139; dedup via bitmap mirrors the
    # reference's set, core.clj:133-134). Granted = this responder's one grant
    # (v_to) names me (Mailbox response decode).
    vresp = resp_in & (mb.resp_kind == RESP_VOTE)
    new_votes = (
        vresp
        & (mb.v_to[None, :] == ids[:, None])
        & (mb.resp_term[None, :] == term[:, None])
        & (role == CANDIDATE)[:, None]
    )
    votes = votes | bitplane.pack(new_votes, axis=1)
    # Quorum test on the packed plane: word popcount instead of an [N, N]
    # bool-plane sum (the bitplane module's reason to exist). With the
    # reconfiguration plane live the popcount is configuration-masked (and
    # DUAL during a joint phase) -- packed_quorum above.
    # A down candidate cannot assume leadership from votes banked before it crashed.
    win = (role == CANDIDATE) & packed_quorum(votes) & inp.alive
    if rcf:
        # A node voted out of both configurations cannot assume leadership
        # from votes banked before its removal.
        win = win & member_b
    if xfr and not cfg.xfer_election:
        # Mutant coup targets take the fresh-leader bookkeeping path too.
        win = win | coup
    role = jnp.where(win, LEADER, role)
    leader_id = jnp.where(win, ids, leader_id)
    # Fresh leader bookkeeping (leader-state core.clj:40-42): nextIndex = last log
    # index + 1, matchIndex = 0. Indices ride int16 when bounded by log_capacity,
    # int32 under compaction (absolute indices; types.index_dtype).
    len_i = log_len.astype(s.next_index.dtype)
    next_index = jnp.where(win[:, None], (len_i + 1)[:, None], s.next_index)
    match_index = jnp.where(win[:, None], 0, s.match_index)

    # ---- phase 4.5: PreVote responses + promotion (thesis 9.6; cfg.pre_vote) -----
    # A pre-candidate banks grant bits in the votes bitmap (it is never a real
    # candidate at the same time, so the bitmap is free); a pre-quorum promotes
    # it to a REAL candidate: only now does the term bump, the self-vote land,
    # and a real RequestVote broadcast go out (phase 8 via start_election).
    if cfg.pre_vote:
        # The grant bit rides the packed pv_grant plane (Mailbox docstring):
        # AND the packed response-validity rows against it -- word algebra, no
        # per-edge byte plane.
        pvresp = resp_in & (mb.resp_kind == RESP_PREVOTE)
        new_pv = jnp.where(
            (role == PRECANDIDATE)[:, None],
            bitplane.pack(pvresp, axis=1) & mb.pv_grant,
            zw,
        )
        votes = votes | new_pv
        pre_win = (role == PRECANDIDATE) & packed_quorum(votes) & inp.alive
        if rcf:
            pre_win = pre_win & member_b
        term = term + pre_win
        role = jnp.where(pre_win, CANDIDATE, role)
        voted_for = jnp.where(pre_win, ids, voted_for)
        votes = jnp.where(pre_win[:, None], eye_p, votes)
    else:
        pre_win = jnp.zeros((n,), bool)

    # Append responses (append-response-handler core.clj:141-149), leaders only, same
    # term. Success: match = acked index, next = match+1 (the reference sets next =
    # log-index, bug 2.3.10); failure: decrement next-index and retry (core.clj:146).
    aresp = (
        resp_in
        & (mb.resp_kind == RESP_APPEND)
        & (role == LEADER)[:, None]
        & (mb.resp_term[None, :] == term[:, None])
    )
    ok_mine = mb.a_ok_to[None, :] == ids[:, None]  # responder's one success names me
    a_succ = aresp & ok_mine
    a_fail = aresp & ~ok_mine
    am = mb.a_match[None, :]  # already index_dtype (bounded by log length)
    ah = mb.a_hint[None, :]
    match_index = jnp.where(a_succ, jnp.maximum(match_index, am), match_index)
    next_index = jnp.where(a_succ, jnp.maximum(next_index, am + 1), next_index)
    # Failure: back off to min(next-1, hint+1) -- the nack hint is the responder's
    # log length (phase 3), so a far-behind or just-elected leader's probe
    # converges in one round trip instead of one slot per nack.
    next_index = jnp.where(
        a_fail, jnp.maximum(jnp.minimum(next_index - 1, ah + 1), 1), next_index
    )
    # Responsiveness ages for the shared-window filter (phase 8): everyone ages one
    # tick (saturating); any AE response (success or failure) proves the peer is up
    # and zeroes its age, and a fresh win grace-zeroes every peer so the first
    # window covers all of them.
    ack_age = jnp.minimum(s.ack_age + 1, cfg.ack_age_sat)
    ack_age = jnp.where(win[:, None] | aresp, 0, ack_age)

    # ---- phase 5: leader commit advancement (absent in reference, bug 2.3.8) ------
    is_leader = role == LEADER
    if dur and cfg.durable_acks:
        # Section-3.8 gate, leader self-match side: the leader's own log
        # counts toward commit only up to ITS durable watermark -- it is a
        # replica like any other, and commit means "on stable storage at a
        # quorum". Uses the pre-flush watermark (this tick's flush lands in
        # phase 7.5): one tick of lag, never a lie.
        match_with_self = jnp.where(eye, dur_mid[:, None], match_index)
    else:
        match_with_self = jnp.where(eye, log_len[:, None], match_index)  # [N, N]
    if rcf:
        # Configuration-masked quorum match under EACH LEADER's OWN derived
        # configuration: the largest replicated index v such that a majority
        # of that leader's member rows have match >= v. The quorum-th order
        # statistic of a multiset is an element of it, so candidates range
        # over the members' own match values (count form -- the member
        # majority is traced data, so the static sort-and-index form cannot
        # apply). While the leader's prefix is joint: the min over both its
        # configs (an index commits only when replicated to majorities of
        # BOTH).
        mws = match_with_self
        ge = mws[:, None, :] >= mws[:, :, None]  # [i, j(candidate), k(counted)]

        def masked_qmatch(mask_b, maj):
            # mask_b [N(i), N(k)]: node i's member view; maj [N(i)].
            cnt = jnp.sum(ge & mask_b[:, None, :], axis=2)  # [N, N]
            ok = (cnt >= maj[:, None]) & mask_b
            return jnp.max(jnp.where(ok, mws, 0), axis=1).astype(jnp.int32)

        mem_old_b = bitplane.unpack(m_old, n, axis=1)  # [N, N] bool
        mem_new_b = bitplane.unpack(m_new, n, axis=1)
        qm_old = masked_qmatch(mem_old_b, maj_old)
        quorum_match = jnp.where(
            joint, jnp.minimum(qm_old, masked_qmatch(mem_new_b, maj_new)), qm_old
        )
    else:
        sorted_desc = -jnp.sort(-match_with_self, axis=1)
        quorum_match = sorted_desc[:, cfg.quorum - 1]  # quorum-th largest match index
    # Spec 5.4.2: only commit entries from the current term by counting replicas.
    if comp:
        quorum_term = log_ops.term_at_r(log_term_arr, base, bterm, quorum_match)
    else:
        quorum_term = log_ops.term_at(log_term_arr, quorum_match)
    commit = jnp.where(
        is_leader & inp.alive & (quorum_match > commit) & (quorum_term == term),
        quorum_match,
        commit,
    )

    # ---- phase 5.2: reconfiguration transitions moved INTO the log --------------
    # (Log-carried membership: there is no admin transition block anymore.
    # Joint entry/exit are LOG APPENDS -- phase 6 originates them on the
    # leader, phase 3 replicates them -- and each node's effective
    # configuration is re-derived from its own prefix at end of tick
    # (models/cfglog.py), which is also where removed-leader stepdown and
    # the truncation rollback live.)
    # Leadership-transfer bookkeeping (cfg.leader_transfer): abort a pending
    # transfer whose holder lost leadership or whose target went unresponsive
    # (ack_age horizon -- a dead target must not freeze the write path), then
    # accept a fresh transfer command at the lowest-id live leader. The
    # TimeoutNow itself fires from phase 8, re-fired each heartbeat while the
    # target stays caught up (a dropped fire retries).
    if xfr:
        tcl = jnp.clip(s.xfer_to, 0, n - 1)
        age_t = jnp.take_along_axis(ack_age, tcl[:, None], axis=1)[:, 0]
        keep_x = is_leader & (s.xfer_to != NIL) & (age_t <= cfg.ack_timeout_ticks)
        xfer_to = jnp.where(keep_x, s.xfer_to, NIL)
        t_x = inp.transfer_cmd
        ld_ok_x = is_leader & inp.alive
        if rcf:
            ld_ok_x = ld_ok_x & member_b
            # The target must be a voter of the LEADER's own target config
            # (per-node derived rows; tick-start like every config read).
            t_voter = jnp.any((m_new & bitplane.one_bit(t_x, n)[None, :]) != 0, axis=1)
        else:
            t_voter = jnp.bool_(True)
        ldx = jnp.min(jnp.where(ld_ok_x, ids, n))
        can_x = (
            (t_x != NIL) & t_voter & (ids == ldx) & ld_ok_x
            & (t_x != ids) & (xfer_to == NIL)
        )
        xfer_to = jnp.where(can_x, t_x, xfer_to)
        xfer_pend = xfer_to != NIL
    # ReadIndex lifecycle (cfg.read_index): bank this tick's AppendEntries
    # responses into the pending read's confirmation set (responses received
    # now were sent at or after the capture tick, so each proves the
    # responder was in the leader's term no earlier than capture -- the
    # staleness argument docs/PROTOCOL.md spells out), serve once a
    # configuration-aware majority confirms, then capture a fresh offer into
    # a free slot.
    if rdx:
        pend0 = s.read_idx > 0  # pending at tick start
        keep_r = is_leader & pend0  # role loss / term adoption cancels
        read_acks = jnp.where(
            keep_r[:, None], s.read_acks | bitplane.pack(aresp, axis=1), zw
        )
        if cfg.read_confirm:
            serve = keep_r & inp.alive & packed_quorum(read_acks | eye_p)
        else:
            # TEST-ONLY mutant (cfg.read_confirm False): serve with NO
            # leadership confirmation -- a deposed leader in a minority
            # partition serves reads from its stale commit state (the
            # below-the-committed-frontier read the checker must reject).
            serve = keep_r & inp.alive
        if rdl:
            # Lease fast path (thesis 6.4.1): a leader holding a fresh
            # configuration quorum of AppendEntries acks -- every member
            # acked within the lease window on the GLOBAL tick clock (the
            # ack_age plane ages 1/tick regardless of skew; the leader's
            # own skewable clock is never consulted) -- serves immediately,
            # no confirmation round. The TEST-ONLY lease_skew_safe mutant
            # widens the window to election_min_ticks + 2: the no-skew
            # bound -- on 1:1 clocks a deposing election needs a full
            # election_min of denial expiry plus the vote+commit round
            # trips, and a capture must precede its serve by a tick, so the
            # widened lease still cannot produce a stale serve; under clock
            # skew the denial window halves in global time and it can.
            lease_w = (
                cfg.read_lease_ticks
                if cfg.lease_skew_safe
                else cfg.election_min_ticks + 2
            )
            fresh_p = bitplane.pack(ack_age <= lease_w, axis=1)  # [N, W]
            lease_ok = packed_quorum(fresh_p | eye_p)
            if xfr:
                # Transfer handoff covers the read path: once a transfer
                # pends, the lease fast path stops -- the target's override
                # election (req_disrupt) bypasses the 4.2.3 denial the lease
                # bound leans on, so only reads served BEFORE the handoff
                # may lean on it (docs/PROTOCOL.md staleness argument).
                lease_ok = lease_ok & ~xfer_pend
            serve = serve | (keep_r & inp.alive & lease_ok)
        lat_r = jnp.maximum(s.now + 1 - s.read_tick, 1)  # [N]
        reads_served = jnp.sum(serve).astype(jnp.int32)
        read_lat_sum = jnp.sum(jnp.where(serve, lat_r, 0)).astype(jnp.int32)
        bin_r = log_ops.log2_bin(lat_r, LAT_HIST_BINS)
        oh_r = (
            jnp.arange(LAT_HIST_BINS)[None, :] == bin_r[:, None]
        ) & serve[:, None]
        read_hist = jnp.sum(oh_r, axis=0).astype(jnp.int32)
        # Capture: gated on the leader having committed a current-term entry
        # (thesis 6.4 -- a fresh leader's commit may trail the global
        # committed frontier until its own no-op/first entry commits, and a
        # read captured before that would legally miss committed writes).
        # One offer per cluster per tick: the lowest-id eligible leader.
        if comp:
            cur_committed = log_ops.term_at_r(log_term_arr, base, bterm, commit) == term
        else:
            cur_committed = log_ops.term_at(log_term_arr, commit) == term
        can_cap = (inp.read_cmd != NIL) & is_leader & inp.alive & ~pend0
        if cfg.read_confirm:
            can_cap = can_cap & cur_committed
        if xfr:
            can_cap = can_cap & ~xfer_pend  # transferring leaders stop serving
        low_cap = jnp.min(jnp.where(can_cap, ids, n))
        cap_r = can_cap & (ids == low_cap)
        cleared = serve | (pend0 & ~keep_r)
        read_idx = jnp.where(cap_r, commit + 1, jnp.where(cleared, 0, s.read_idx))
        read_tick = jnp.where(cap_r, s.now + 1, jnp.where(cleared, 0, s.read_tick))
        read_acks = jnp.where((cap_r | serve)[:, None], zw, read_acks)
        if rdl:
            # Staleness anchor: bank the committed frontier (lat_frontier
            # semantics, incl. this tick's phase-5 advance) at capture; a
            # SERVE whose captured index sits below its banked frontier
            # missed committed writes -- the checker's read_linearizability
            # property as a device invariant, so the hunt's fitness sees
            # lease violations. Exact, not conservative: a legitimate
            # (confirmed or leased) leader's capture covers the frontier by
            # the current-term-commit gate, so the real kernel never flags.
            fr_now = jnp.maximum(s.lat_frontier, jnp.max(commit))
            read_fr = jnp.where(
                cap_r, fr_now, jnp.where(cleared, 0, s.read_fr)
            )
            if cfg.check_invariants:
                viol_read_stale = jnp.any(serve & (s.read_idx - 1 < s.read_fr))
            else:
                viol_read_stale = np.zeros((), np.bool_)
        else:
            viol_read_stale = np.zeros((), np.bool_)
    else:
        # Constants, not jnp.zeros: a zeros op would land in the lowered
        # step program and break the zero-cost-when-off golden (byte-
        # identical op histograms with every gate off).
        reads_served = np.int32(0)
        read_lat_sum = np.int32(0)
        read_hist = np.zeros((LAT_HIST_BINS,), np.int32)
        viol_read_stale = np.zeros((), np.bool_)

    # ---- offer->commit latency (client workloads only) ---------------------------
    # Each client entry's offer stamp rides the log_tick plane (phase 6 writes
    # it at injection; AE replication carries it via Mailbox.ent_tick), so the
    # live leader's commit advancement this tick contributes (now - offer_tick)
    # per newly committed client entry -- the measurement the reference's
    # commit watch was meant to feed (log.clj:83-87, never fired, bug 2.3.9).
    # VALUES are never read here: payloads are arbitrary int32 (VERDICT
    # missing #1 -- a value colliding with a tick can no longer corrupt the
    # histogram). Read before compaction/injection can touch slots (same
    # aliasing rule as the checksum pass).
    if track:
        sl = jnp.arange(cap, dtype=jnp.int32)[None, :]
        abs1 = (base[:, None] + (sl - base[:, None]) % cap + 1) if comp else (sl + 1)
        # Dedup across leader changes AND restarts: a freshly elected leader's
        # own commit trails the cluster's prior frontier and would re-count
        # entries its predecessor already reported, so only entries above the
        # CARRIED monotone frontier contribute (the per-node commit vector is
        # restart-mutable -- ClusterState.lat_frontier). Stamps are offer
        # tick + 1, always in (0, now] at commit time; slots holding no client
        # entry (no-ops, unwritten) carry stamp 0 and fall out of `cli`.
        newly = (abs1 > s.lat_frontier) & (abs1 <= commit[:, None])
        cli = (log_tick_arr >= 1) & (log_tick_arr <= s.now)  # client-stamped slots
        lm = (is_leader & inp.alive)[:, None] & newly & cli
        lats = jnp.where(lm, s.now - log_tick_arr + 1, 0)  # [N, CAP]
        lat_sum = jnp.sum(lats).astype(jnp.int32)
        lat_cnt = jnp.sum(lm).astype(jnp.int32)
        # Coverage gap counter (StepInfo.lat_excluded): client entries the
        # frontier advance crosses without attribution. The frontier advances
        # to max(commit) regardless of leadership; count the crossed client
        # entries on the (lowest-id) node HOLDING that max -- its log carries
        # everything in (frontier, max commit] by log matching -- and subtract
        # what lat_cnt attributed. Clamped at zero: under compaction the
        # max-commit node may have compacted a crossed slot the leader still
        # counted, and split-brain double-counts inflate lat_cnt.
        is_maxc = commit == jnp.max(commit)
        hnode = jnp.min(jnp.where(is_maxc, ids, n))
        crossed = (ids == hnode)[:, None] & newly & cli
        lat_excluded = jnp.maximum(
            jnp.sum(crossed).astype(jnp.int32) - lat_cnt, 0
        )
        # Histogram bin = floor(log2(l)), clamped to the last bin
        # (log_ops.log2_bin: the one binning copy, shared with the
        # read-latency histogram and both kernels).
        bin_ = log_ops.log2_bin(lats, LAT_HIST_BINS)
        oh_b = (jnp.arange(LAT_HIST_BINS)[None, None, :] == bin_[:, :, None]) & lm[:, :, None]
        lat_hist = jnp.sum(oh_b, axis=(0, 1)).astype(jnp.int32)  # [BINS]
        lat_frontier = jnp.maximum(s.lat_frontier, jnp.max(commit))
    else:
        lat_sum = jnp.int32(0)
        lat_cnt = jnp.int32(0)
        lat_hist = jnp.zeros((LAT_HIST_BINS,), jnp.int32)
        lat_excluded = jnp.int32(0)
        lat_frontier = s.lat_frontier

    # ---- phase 5.5: log compaction -------------------------------------------------
    # The reference's unbounded log vector (log.clj:33) needs none; the ring must
    # free committed slots or a long-horizon client workload would exhaust it
    # (commands rejected forever once log_len - log_base == CAP). Policy: whenever
    # fewer than compact_margin free slots remain, advance base toward commit so up
    # to CAP - compact_margin entries stay retained for laggard catch-up. base_chk
    # is extended over the newly compacted span in the checksum pass below.
    base_mid, bchk_mid = base, bchk  # post-install, pre-advance (checksum anchor)
    if comp:
        target = jnp.minimum(commit, log_len - (cap - cfg.compact_margin))
        base2 = jnp.maximum(base, target)
        bterm = log_ops.term_at_r(log_term_arr, base, bterm, base2)  # = bterm if unchanged
        if rcf:
            # Fold the compacted span's config entries into the snapshot
            # context (cfglog.fold_span; anchored at the PRE-advance base,
            # same aliasing rule as the checksum pass below -- must run
            # before phase 6 can reuse freed slots).
            bmold, bpend, bepoch = cfglog.fold_span(
                cfg, log_cfg_arr, base, base2, bmold, bpend, bepoch
            )
        base = base2

    # ---- committed-prefix checksum --------------------------------------------------
    # One masked pass over the post-append arrays yields the old-prefix sum
    # (invariant: equals the carried checksum), the compacted-prefix extension, and
    # the new-prefix sum (log_ops module comment). All sums anchor at base_mid, the
    # base BEFORE this tick's compaction advance. This pass MUST run before phase 6:
    # an injection into a slot freed by this very tick's rebase would otherwise be
    # read back under the just-compacted entry's weight (base_mid-anchored slot ->
    # absolute-index map), silently corrupting base_chk. AE writes cannot alias
    # (they only touch entries <= base + CAP, whose anchored indices are exact).
    # The sums are part of load-bearing snapshot state (shipped as req_base_chk,
    # persisted in checkpoints), so under compaction they are maintained even with
    # invariant CHECKING off -- only the chk_ok comparison is gated.
    if comp:
        co = jnp.maximum(s.commit_index, base_mid)  # snap installs skip the check
        s_co, s_bf, s_cn = log_ops.ring_chk(
            log_term_arr, log_val_arr, base_mid, (co, base, commit)
        )
        if cfg.check_invariants:
            chk_ok = (bchk_mid + s_co == s.commit_chk) | apply_snap
        else:
            chk_ok = jnp.ones((n,), bool)
        bchk = bchk_mid + s_bf
        chk_new = bchk_mid + s_cn
    elif cfg.check_invariants:
        chk_old, chk_new = log_ops.prefix_chk2(
            log_term_arr, log_val_arr, s.commit_index, commit
        )
        chk_ok = chk_old == s.commit_chk
    else:
        chk_new = s.commit_chk
        chk_ok = jnp.ones((n,), bool)

    # ---- phase 6: client command injection (client-set-handler core.clj:151-160) --
    # Routing: with client_redirect the client POSTs one node and chases 302
    # redirects at one tick per bounce (the reference's write path,
    # core.clj:151-160, server.clj:62-63); otherwise the omniscient simulator
    # client writes straight to every live leader. Under compaction, a fresh
    # election win appends a leader NO-OP entry instead (spec 5.4.2 workaround:
    # old-term entries only commit via a current-term entry at quorum, and a full
    # ring of old-term entries would otherwise deadlock commit forever -- see
    # docs/DESIGN.md); client injections keep `noop_reserve` slots free so a
    # no-op slot survives commit-free election chains up to that depth.
    if comp:
        reserve = max(1, cfg.compact_margin // 2)
        noop = win & (log_len - base < cap)
        room = log_len - base < cap - reserve
        # A win with NO room for its no-op: beyond the reserve's guarantee, the
        # latent 5.4.2 commit-freeze the no-op exists to break -- surfaced as a
        # liveness metric instead of stalling silently (StepInfo.noop_blocked).
        noop_blocked = jnp.sum(win & ~(log_len - base < cap)).astype(jnp.int32)
    else:
        noop = jnp.zeros((n,), bool)
        room = log_len - base < cap
        noop_blocked = jnp.int32(0)
    # ---- config-entry origination (log-carried membership, thesis 4.3) ----------
    # Config changes are LOG WRITES sharing phase 6's one-append-per-node
    # slot (priority: election no-op > config entry > client command), each
    # judged on the leader's OWN tick-start derived configuration:
    #   JOINT entry (+v+1): the admin's toggle, accepted by the lowest-id
    #   live voter-leader, refused while that leader's prefix is already
    #   joint or when the toggle would leave C_new below 2 voters.
    #   FINAL entry (-v-1): appended automatically once the governing joint
    #   entry commits on the leader (commit >= cfg_pend) -- the thesis's
    #   "C_old,new committed -> append C_new" step.
    if rcf:
        t_r = inp.reconfig_cmd
        tbit = bitplane.one_bit(t_r, n)  # [W]; all-zero row for NIL
        toggled = m_new ^ tbit[None, :]  # [N, W]: each node's view of the result
        ld_ok = is_leader & inp.alive & member_b & room & ~noop
        ldj = jnp.min(jnp.where(ld_ok & ~joint, ids, n))
        accept_j = (
            (t_r != NIL)
            & (ids == ldj)
            & ld_ok
            & ~joint
            & (bitplane.count(tbit, axis=0) > 0)
            & (bitplane.count(toggled, axis=1) >= 2)
        )
        if cfg.joint_consensus:
            # Pending toggle of this node's open joint phase: the one bit
            # its member_old and member_new rows differ on.
            pvbits = bitplane.unpack(m_old ^ m_new, n, axis=1)  # [N, N]
            pend_v = jnp.min(jnp.where(pvbits, ids[None, :], n), axis=1)
            accept_f = ld_ok & joint & (commit >= s.cfg_pend)
            cfg_code = jnp.where(
                accept_j, t_r + 1, jnp.where(accept_f, -(pend_v + 1), 0)
            ).astype(jnp.int32)
            cfg_write = accept_j | accept_f
        else:
            # TEST-ONLY mutant (single-server change, cfg.joint_consensus
            # False): one final-acting entry per change, no joint phase, no
            # completing entry -- the known-unsafe variant.
            cfg_code = jnp.where(accept_j, t_r + 1, 0).astype(jnp.int32)
            cfg_write = accept_j
    if cfg.client_redirect:
        # K commands in flight (cfg.client_pipeline -- the reference's
        # buffered(5) request channel, server.clj:37): a fresh offer takes the
        # FIRST free slot (dropped only when all K are busy); each active slot
        # independently chases redirects. Per node, at most ONE slot is
        # accepted per tick -- the reference's loop dequeues one message per
        # wait iteration -- lowest slot index first; slots targeting distinct
        # leaders (split-brain windows) can accept in parallel.
        kdim = cfg.client_pipeline
        kk = jnp.arange(kdim, dtype=jnp.int32)
        free = s.client_pend == NIL  # [K]
        first_free = free & (jnp.cumsum(free) == 1)
        fresh = (inp.client_cmd != NIL) & first_free
        pend = jnp.where(fresh, inp.client_cmd, s.client_pend)  # [K]
        tgt = jnp.where(fresh, inp.client_target, s.client_dst)
        # Offer stamp rides the slot beside the payload: latency is measured
        # from the OFFER tick, and the bounces happen after it.
        ptick = jnp.where(fresh, s.now + 1, s.client_tick) if track else None
        active = pend != NIL
        tgt_oh = active[:, None] & (tgt[:, None] == ids[None, :])  # [K, N]
        low_k = jnp.min(jnp.where(tgt_oh, kk[:, None], kdim), axis=0)  # [N]
        node_ok = is_leader & inp.alive & room & ~noop
        if rcf:
            node_ok = node_ok & ~cfg_write  # the slot holds a config entry
        if xfr:
            # Transfer lease handoff (thesis 3.10): a transferring leader
            # stops accepting client commands until the transfer completes
            # or aborts.
            node_ok = node_ok & ~xfer_pend
        client_ok = (low_k < kdim) & node_ok  # [N] nodes accepting a slot
        sel_k = tgt_oh & (kk[:, None] == low_k[None, :]) & node_ok[None, :]  # [K, N]
        wval_cl = jnp.sum(jnp.where(sel_k, pend[:, None], 0), axis=0)  # [N]
        wtick_cl = (
            jnp.sum(jnp.where(sel_k, ptick[:, None], 0), axis=0) if track else None
        )
        accepted_k = jnp.any(sel_k, axis=1)  # [K]
        # Distinct slots hold distinct offers: the count is exact (the direct
        # client's any() collapses split-brain double-accepts of ONE offer).
        cmds_cnt = jnp.sum(accepted_k).astype(jnp.int32)
        # Redirect still-pending slots: to the target's known leader when the
        # target is up and knows one, else to a random peer (core.clj:152-155).
        # A rejected POST at a full leader retries there next tick.
        tgt_ld = jnp.max(jnp.where(tgt_oh, leader_id[None, :], NIL), axis=1)  # [K]
        tgt_up = jnp.any(tgt_oh & inp.alive[None, :], axis=1)
        pend_on = active & ~accepted_k
        client_pend = jnp.where(pend_on, pend, NIL)
        client_dst = jnp.where(
            pend_on, jnp.where(tgt_up & (tgt_ld != NIL), tgt_ld, inp.client_bounce), 0
        )
        client_tick = jnp.where(pend_on, ptick, 0) if track else s.client_tick
    else:
        client_ok = (inp.client_cmd != NIL) & is_leader & inp.alive & room & ~noop
        if rcf:
            client_ok = client_ok & ~cfg_write  # the slot holds a config entry
        if xfr:
            client_ok = client_ok & ~xfer_pend  # transfer lease handoff
        wval_cl = jnp.broadcast_to(inp.client_cmd, (n,))
        # Direct mode accepts on the offer tick itself: stamp = now + 1 (the
        # same stamp the redirect pipeline records at slot entry).
        wtick_cl = jnp.broadcast_to(s.now + 1, (n,)) if track else None
        # any(), not sum(): during a split-brain window two live leaders can
        # both accept the same offered command; that is ONE offer accepted, and
        # the offered-vs-committed audit counts offers.
        cmds_cnt = jnp.any(client_ok).astype(jnp.int32)
        client_pend = s.client_pend
        client_dst = s.client_dst
        client_tick = s.client_tick
    do_write = (noop | cfg_write | client_ok) if rcf else (noop | client_ok)
    wval = jnp.where(noop, NOOP, wval_cl)
    if rcf:
        # Config entries carry value 0 (the command rides the log_cfg plane).
        wval = jnp.where(cfg_write, 0, wval)
    inj_pos = jnp.where(do_write, log_len % cap if comp else log_len, cap)
    log_term_arr = log_term_arr.at[ids, inj_pos].set(term, mode="drop")
    log_val_arr = log_val_arr.at[ids, inj_pos].set(
        jnp.broadcast_to(wval, (n,)), mode="drop"
    )
    if track:
        # No-op entries carry stamp 0: protocol filler, never a client offer.
        wtick = jnp.where(noop, 0, wtick_cl)
        if rcf:
            wtick = jnp.where(cfg_write, 0, wtick)  # config entries too
        log_tick_arr = log_tick_arr.at[ids, inj_pos].set(
            jnp.broadcast_to(wtick, (n,)), mode="drop"
        )
    if rcf:
        # EVERY append writes the config plane (0 for non-config entries):
        # a slot reused after truncation must never leak its old command.
        log_cfg_arr = log_cfg_arr.at[ids, inj_pos].set(
            jnp.where(cfg_write, cfg_code, 0), mode="drop"
        )
    log_len = log_len + do_write

    # ---- phase 7: timers (generate-timeout core.clj:171-174; dispatch :193-195) ----
    clock = s.clock + inp.skew
    # Election timer resets ONLY on vote grant or valid current-term AppendEntries (or
    # stepping down), not on every message (reference bug 2.3.11).
    reset_election = granted_any | has_ae | saw_higher
    deadline = jnp.where(reset_election, clock + inp.timeout_draw, s.deadline)
    deadline = jnp.where(win, clock + cfg.heartbeat_ticks, deadline)
    if cfg.pre_vote:
        # A just-promoted candidate draws a fresh election timeout.
        deadline = jnp.where(pre_win, clock + inp.timeout_draw, deadline)
    # A down node's timers cannot fire; its fresh deadline is set by the restart wipe.
    expired = (clock >= deadline) & inp.alive

    # Leader heartbeat (heartbeat-handler core.clj:162-164).
    heartbeat = expired & is_leader
    deadline = jnp.where(heartbeat, clock + cfg.heartbeat_ticks, deadline)

    # Follower/candidate timeout -> new election (timeout-handler core.clj:166-169,
    # follower->candidate core.clj:69-73: term++, vote self).
    if cfg.pre_vote:
        # Expiry starts a PRE-vote probe instead: no term bump, votedFor
        # untouched (grants stay possible), the self pre-vote rides the bitmap.
        # The REAL election start is this tick's promotions (phase 4.5).
        start_prevote = expired & ~is_leader
        if rcf:
            # Non-voters never campaign (the removed-node quiescence rule,
            # judged on the node's OWN derived config: a node whose log
            # carries its removal is a learner; one whose log misses it
            # still campaigns -- the disruption the 4.2.3 denial absorbs).
            start_prevote = start_prevote & member_b
        if xfr:
            # A TimeoutNow target skips the probe: its real election (below)
            # is the thesis-3.10 pre-vote bypass.
            start_prevote = start_prevote & ~xfer_elect
        role = jnp.where(start_prevote, PRECANDIDATE, role)
        leader_id = jnp.where(start_prevote, NIL, leader_id)
        votes = jnp.where(start_prevote[:, None], eye_p, votes)
        deadline = jnp.where(start_prevote, clock + inp.timeout_draw, deadline)
        start_election = pre_win
        if xfr:
            # TimeoutNow election: real term bump + self-vote + RequestVote
            # broadcast, exactly the promotion path minus the pre-quorum.
            # ~is_leader re-checked: the target may have WON an ordinary
            # election in phase 4 this very tick.
            xe = xfer_elect & ~pre_win & ~is_leader
            term = term + xe
            role = jnp.where(xe, CANDIDATE, role)
            voted_for = jnp.where(xe, ids, voted_for)
            leader_id = jnp.where(xe, NIL, leader_id)
            votes = jnp.where(xe[:, None], eye_p, votes)
            deadline = jnp.where(xe, clock + inp.timeout_draw, deadline)
            start_election = pre_win | xe
    else:
        start_prevote = jnp.zeros((n,), bool)
        start_election = expired & ~is_leader
        if rcf:
            start_election = start_election & member_b  # non-voters never campaign
        if xfr:
            # TimeoutNow election (~is_leader re-checked: the target may have
            # won an ordinary election in phase 4 this very tick).
            xe = xfer_elect & ~is_leader
            start_election = start_election | xe
        term = term + start_election
        role = jnp.where(start_election, CANDIDATE, role)
        voted_for = jnp.where(start_election, ids, voted_for)
        leader_id = jnp.where(start_election, NIL, leader_id)
        votes = jnp.where(start_election[:, None], eye_p, votes)
        deadline = jnp.where(start_election, clock + inp.timeout_draw, deadline)

    # ---- phase 7.5: fsync flush + section-3.8 durability gates -------------------
    # The device-side fsync model (raft_sim_tpu/storage): a completed flush
    # (inp.fsync_fire -- the cadence tick minus the per-node latency-jitter
    # stall, sim/faults._storage_draws; a dead disk never flushes) snaps the
    # durable snapshot to the node's FINAL live state this tick -- the
    # post-injection log length and the post-election term/vote. Between
    # flushes the watermark carries (clamped by truncation, dur_mid above).
    if dur:
        fs_fire = inp.fsync_fire & inp.alive
        dur2_len, dur2_term, dur2_vote = storage_plane.flush(
            fs_fire, dur_mid, s.dur_term, s.dur_vote, log_len, term, voted_for
        )
        if cfg.durable_acks:
            # Gate 1 -- AE acks: the acked match index never exceeds the
            # durable watermark. A follower behind a slow disk acks LESS
            # than it appended (the leader's match/next simply lag; the
            # idempotent consistency check absorbs the re-sends), so
            # replication STALLS behind the disk instead of lying about it.
            # The nack catch-up hint stays volatile: it is an optimization
            # target, never counted toward commit.
            out_a_match = jnp.minimum(
                out_a_match.astype(jnp.int32), dur2_len
            ).astype(idt)
            # Gate 2 -- vote grants: a grant is EXPOSED only once the
            # (term, votedFor) pair it commits to is durable. covered0 vs
            # covered2 splits "already exposed on an earlier tick" from
            # "this tick's flush just made it durable": the latter emits a
            # LATE vote-completion response below (phase 8) when the grant
            # tick itself could not -- the array form of "respond after the
            # fsync returns". A grant whose flush never lands before the
            # candidate gives up is simply lost (like a dropped response).
            covered0 = storage_plane.covered(s.dur_term, s.dur_vote, term, voted_for)
            covered2 = storage_plane.covered(dur2_term, dur2_vote, term, voted_for)
            grant_to = jnp.where(covered2, voted_for, NIL).astype(node_dtype(cfg))
            late_grant = covered2 & ~covered0 & ~granted_any

    # ---- phase 8: outbox ---------------------------------------------------------
    send_append = win | heartbeat  # fresh leaders heartbeat immediately (core.clj:137-138)
    if comp:
        new_last_idx = log_len
        new_last_term = log_ops.term_at_r(log_term_arr, base, bterm, log_len)
    else:
        new_last_idx, new_last_term = log_ops.last_index_term(log_term_arr, log_len)

    # Request headers are PER SENDER -- both RPCs are broadcasts (request-vote-rpc
    # core.clj:48-54, append-entries-rpc core.clj:56-67); the only per-edge request
    # datum is the AE window offset (Mailbox docstring).
    ae_edge = send_append[:, None] & ~eye
    # A strong int32 0: a weak-typed req_type would flip the carry's type
    # after the first chunk and recompile every chunk program once more.
    out_req_type = jnp.where(
        start_election, REQ_VOTE, jnp.where(send_append, REQ_APPEND, jnp.int32(0))
    )  # [N]
    if cfg.pre_vote:
        out_req_type = jnp.where(start_prevote, REQ_PREVOTE, out_req_type)
        rv_like = start_election | start_prevote  # both fill the req_last header
    else:
        rv_like = start_election
    out_req_term = jnp.where(out_req_type != 0, term, 0)
    if cfg.pre_vote:
        # The probe carries the PROSPECTIVE term (term + 1, thesis 9.6); phase 1
        # excludes it from adoption.
        out_req_term = jnp.where(start_prevote, term + 1, out_req_term)
    if xfr:
        # TimeoutNow fire (thesis 3.10): on a heartbeat tick with a pending
        # transfer whose target has fully matched the leader's log, the
        # broadcast slot carries REQ_TIMEOUT_NOW instead of the heartbeat
        # (re-fired each heartbeat while pending: a dropped fire retries; a
        # successful one deposes this leader before the next). The AE window
        # fields stay populated as the heartbeat would have left them --
        # receivers gate every AE read on req_type == REQ_APPEND.
        tcl8 = jnp.clip(xfer_to, 0, n - 1)
        t_match = jnp.take_along_axis(match_index, tcl8[:, None], axis=1)[
            :, 0
        ].astype(jnp.int32)
        if cfg.xfer_election:
            caught = t_match >= log_len
        else:
            # TEST-ONLY mutant: fire without the catch-up wait (the coup
            # receipt on the other side doesn't check the log either).
            caught = jnp.ones((n,), bool)
        fire = send_append & (xfer_to != NIL) & caught
        out_req_type = jnp.where(fire, REQ_TIMEOUT_NOW, out_req_type)
        out_xfer_tgt = jnp.where(fire, xfer_to, NIL).astype(node_dtype(cfg))
    else:
        out_xfer_tgt = mb.xfer_tgt  # NIL, loop-invariant carry component
    if xfr and (rcf or rdl):
        # The disruptive-RequestVote override (thesis 3.10/4.2.3): a
        # transfer-triggered election's broadcast carries the flag, so
        # heard-recent voters still process it. Written only when a denial
        # gate can read it; zeros and carried untouched otherwise.
        out_req_disrupt = jnp.where(xe, 1, 0).astype(jnp.int8)
    else:
        out_req_disrupt = mb.req_disrupt  # zeros, loop-invariant component
    # AE: prev = nextIndex - 1 per edge, carried as the offset into the shared window.
    prev_out = jnp.clip(next_index - 1, 0, log_len[:, None])  # [src, dst]
    # Shared window start: minimum prev over RESPONSIVE peers (acked an AE within
    # ack_timeout_ticks). A peer that never acks -- crashed, partitioned away -- must
    # not pin the window, or no live follower could ever receive entries past
    # ws + E and commit would stall despite a live quorum. When no peer is
    # responsive (nothing to replicate to anyway) fall back to the min over all
    # peers. An unresponsive laggard's prev is clamped UP to ws below: spec-safe
    # (the consistency check at the too-high prev fails, it nacks, and that nack
    # both re-admits it to the responsive set and walks next_index back down).
    responsive = ack_age <= cfg.ack_timeout_ticks  # [src, dst]
    # big > any prev_out (prev_out <= log_len; absolute and unbounded under
    # compaction, <= cap otherwise).
    big = jnp.int32(2**31 - 1) if comp else (cap + 1)
    ws_resp = jnp.min(jnp.where(eye | ~responsive, big, prev_out), axis=1)  # [src]
    ws_all = jnp.min(jnp.where(eye, big, prev_out), axis=1)
    none_resp = (ws_resp == big) if comp else (ws_resp > cap)
    ws = jnp.where(none_resp, ws_all, ws_resp)
    ws = jnp.minimum(ws, log_len)
    if comp:
        # Entries below the compaction base are gone: the window cannot start
        # before it, and peers whose prev falls below it get the InstallSnapshot
        # sentinel (req_off = -1) instead of a window offset.
        ws = jnp.maximum(ws, base)
        snap_edge = ae_edge & (prev_out < base[:, None])
    # Clamp each peer's prev into [ws, ws+E]: spec-safe in both directions (a peer
    # ahead of the window gets a plain heartbeat over an older prefix it already
    # has, its redundant ack absorbed by the monotone max() updates of match/next
    # in phase 4; an unresponsive laggard's prev is lifted to ws, its nack walks
    # next_index back down and re-admits it to the responsive set), and it bounds
    # prev - ws to E+1 values so the batch-minor kernel can read prev terms from
    # the shared window instead of a CAP-wide one-hot per edge.
    # j = clip(prev, ws, ws+E) - ws == clip(prev - ws, 0, E); the difference
    # form bounds the offset syntactically for the value-range audit.
    off_j = jnp.clip(prev_out - ws[:, None], 0, e)
    prev_out = ws[:, None] + off_j
    # Per-edge window offset j = prev - ws in 0..E; receivers reconstruct prev,
    # prev_term, and n_entries from (j, ent_start, ent_prev_term, ent_count).
    out_req_off = jnp.where(ae_edge, off_j, 0).astype(jnp.int8)
    if comp:
        out_req_off = jnp.where(snap_edge, jnp.int8(-1), out_req_off)
    # Zero unused window slots so the mailbox is canonical (receivers mask with
    # the derived n_ent anyway, but a canonical wire format keeps trajectories
    # bit-comparable).
    n_ship = jnp.clip(log_len - ws, 0, e)  # [src]
    ship_used = send_append[:, None] & (ks[None, :] < n_ship[:, None])  # [src, E]
    wread = log_ops.window_r if comp else log_ops.window
    out_ent_term = jnp.where(ship_used, wread(log_term_arr, ws, e), 0)
    out_ent_val = jnp.where(ship_used, wread(log_val_arr, ws, e), 0)
    out_ent_tick = (
        jnp.where(ship_used, wread(log_tick_arr, ws, e), 0) if track
        else mb.ent_tick  # zeros, loop-invariant carry component
    )
    out_ent_cfg = (
        jnp.where(ship_used, wread(log_cfg_arr, ws, e), 0) if rcf
        else mb.ent_cfg  # zeros, loop-invariant carry component
    )

    # Responses: vr_out/ar_out are [request-sender, request-receiver], which IS the
    # response orientation [response-receiver, responder] (the reference's resp-chan
    # round trip, server.clj:59-60 -> client.clj:34-40); the edge plane carries only
    # the response TYPE -- payloads are per responder (Mailbox response decode).
    out_resp_kind = (
        jnp.where(vr_out, RESP_VOTE, 0) + jnp.where(ar_out, RESP_APPEND, 0)
    ).astype(jnp.int8)
    if cfg.pre_vote:
        # Pre-vote responses overlay the same plane; the grant BIT rides the
        # packed pv_grant plane (one voter may grant several probes per tick,
        # so it is genuinely per-edge -- Mailbox docstring).
        out_resp_kind = out_resp_kind + jnp.where(pv_out, RESP_PREVOTE, 0).astype(
            jnp.int8
        )
        out_pv_grant = bitplane.pack(pv_grant, axis=1)  # [cand, W(bit=voter)]
    else:
        out_pv_grant = mb.pv_grant  # zeros, loop-invariant carry component
    if dur and cfg.durable_acks:
        # Late vote-completion response (phase 7.5 gate 2): the flush that
        # just made this voter's grant durable emits the RESP_VOTE edge the
        # grant tick withheld -- toward the recorded candidate, only where
        # the edge carries no response already (a candidate that won
        # meanwhile is heartbeating us; its AE response outranks the vote it
        # no longer needs). v_to already names the candidate via covered2.
        vfc = jnp.clip(voted_for, 0, n - 1)
        late_edge = (ids[:, None] == vfc[None, :]) & late_grant[None, :]
        out_resp_kind = jnp.where(
            late_edge & (out_resp_kind == 0),
            jnp.int8(RESP_VOTE),
            out_resp_kind,
        )
    pterm = (
        log_ops.term_at_r(log_term_arr, base, bterm, ws)
        if comp
        else log_ops.term_at(log_term_arr, ws)
    )

    new_mb = Mailbox(
        req_type=out_req_type,
        req_term=out_req_term,
        req_commit=jnp.where(send_append, commit, 0),
        req_last_index=jnp.where(rv_like, new_last_idx, 0),
        req_last_term=jnp.where(rv_like, new_last_term, 0),
        ent_start=jnp.where(send_append, ws, 0),
        ent_prev_term=jnp.where(send_append, pterm, 0),
        ent_count=jnp.where(send_append, n_ship, 0),
        ent_term=out_ent_term,
        ent_val=out_ent_val,
        ent_tick=out_ent_tick,
        # Without compaction the snapshot header is dead weight: pass the zeros
        # through untouched so XLA sees a loop-invariant carry component.
        req_base=jnp.where(send_append, base, 0) if comp else mb.req_base,
        req_base_term=jnp.where(send_append, bterm, 0) if comp else mb.req_base_term,
        req_base_chk=(
            jnp.where(send_append, bchk, jnp.uint32(0)) if comp else mb.req_base_chk
        ),
        xfer_tgt=out_xfer_tgt,
        req_disrupt=out_req_disrupt,
        ent_cfg=out_ent_cfg,
        req_base_mold=(
            jnp.where(send_append[:, None], bmold, jnp.uint32(0))
            if (comp and rcf) else mb.req_base_mold
        ),
        req_base_pend=(
            jnp.where(send_append, bpend, 0) if (comp and rcf)
            else mb.req_base_pend
        ),
        req_base_epoch=(
            jnp.where(send_append, bepoch, 0) if (comp and rcf)
            else mb.req_base_epoch
        ),
        req_off=out_req_off,
        resp_kind=out_resp_kind,
        pv_grant=out_pv_grant,
        v_to=grant_to,
        a_ok_to=out_a_ok_to,
        a_match=out_a_match,
        a_hint=out_a_hint,
        resp_term=term,
    )

    # ---- end-of-tick config derivation (log-carried membership) ------------------
    # Each node's effective configuration recomputed from its post-append,
    # post-compaction log prefix (models/cfglog.py): apply-on-append and
    # roll-back-on-truncation are the SAME recomputation -- a truncated
    # config entry simply stops existing for the next tick's quorums.
    if rcf:
        # base_mold/base_pend/base_epoch initialize to the boot config
        # (types.init_state) and are carried untouched without compaction,
        # so they are always the valid context at `base`.
        d_mold, d_mnew, d_pend, d_epoch, d_hi = cfglog.derive(
            cfg, log_cfg_arr, log_len, commit, base, bmold, bpend, bepoch
        )
        if not cfg.truncation_rollback:
            # TEST-ONLY mutant (ignore-truncation-rollback): where the
            # prefix LOST config entries, keep acting on the stale carried
            # configuration -- the dissertation's rollback rule skipped.
            rolled = d_epoch < s.cfg_epoch
            d_mold = jnp.where(rolled[:, None], s.member_old, d_mold)
            d_mnew = jnp.where(rolled[:, None], s.member_new, d_mnew)
            d_pend = jnp.where(rolled, s.cfg_pend, d_pend)
            d_epoch = jnp.where(rolled, s.cfg_epoch, d_epoch)
        # Removed-server stepdown (thesis 4.3): a LEADER whose own config
        # union excludes it keeps leading -- replicating the very entry
        # that removes it -- until that entry commits on it, then steps
        # down (its log never counts toward masked quorums meanwhile: the
        # caretaker role). Candidacies of removed nodes die immediately.
        self_in = jnp.any(((d_mold | d_mnew) & eye_p) != 0, axis=1)
        is_cand = (role == CANDIDATE) | (role == PRECANDIDATE)
        demote = ~self_in & (
            ((role == LEADER) & (commit >= d_hi)) | is_cand
        )
        role = jnp.where(demote, FOLLOWER, role)
        leader_id = jnp.where(demote, NIL, leader_id)

    new_state = ClusterState(
        role=role,
        term=term,
        voted_for=voted_for,
        leader_id=leader_id,
        votes=votes,
        next_index=next_index,
        match_index=match_index,
        ack_age=ack_age,
        commit_index=commit,
        commit_chk=chk_new,
        log_base=base,
        base_term=bterm,
        base_chk=bchk,
        log_term=log_term_arr,
        log_val=log_val_arr,
        log_tick=log_tick_arr,
        log_len=log_len,
        dur_len=dur2_len if dur else s.dur_len,
        dur_term=dur2_term if dur else s.dur_term,
        dur_vote=dur2_vote if dur else s.dur_vote,
        clock=clock,
        deadline=deadline,
        heard_clock=heard,
        member_old=d_mold if rcf else s.member_old,
        member_new=d_mnew if rcf else s.member_new,
        cfg_epoch=d_epoch if rcf else s.cfg_epoch,
        cfg_pend=d_pend if rcf else s.cfg_pend,
        log_cfg=log_cfg_arr,
        base_mold=bmold if (rcf and comp) else s.base_mold,
        base_pend=bpend if (rcf and comp) else s.base_pend,
        base_epoch=bepoch if (rcf and comp) else s.base_epoch,
        xfer_to=xfer_to if xfr else s.xfer_to,
        read_idx=read_idx if rdx else s.read_idx,
        read_tick=read_tick if rdx else s.read_tick,
        read_acks=read_acks if rdx else s.read_acks,
        read_fr=read_fr if rdl else s.read_fr,
        client_pend=client_pend,
        client_dst=client_dst,
        client_tick=client_tick,
        lat_frontier=lat_frontier,
        now=s.now + 1,
        mailbox=new_mb,
    )

    # Durability-lag reductions (StepInfo; host-constant zeros when the plane
    # is off -- same zero-cost contract as the read metrics above).
    if dur:
        lag = log_len - dur2_len  # [N] >= 0 (flush snaps to log_len)
        fsync_lag_sum = jnp.sum(lag).astype(jnp.int32)
        fsync_lag_max = jnp.max(lag).astype(jnp.int32)
    else:
        fsync_lag_sum = np.int32(0)
        fsync_lag_max = np.int32(0)

    info = _step_info(
        cfg, s, new_state, req_in, resp_in, inp.alive, cmds_cnt, chk_ok,
        lat_sum, lat_cnt, lat_hist, lat_excluded, noop_blocked,
        reads_served, read_lat_sum, read_hist, viol_read_stale,
        fsync_lag_sum, fsync_lag_max,
    )
    return new_state, info


def _step_info(
    cfg: RaftConfig,
    old: ClusterState,
    new: ClusterState,
    req_in: jax.Array,
    resp_in: jax.Array,
    alive: jax.Array,
    cmds_cnt: jax.Array,
    chk_ok: jax.Array,
    lat_sum: jax.Array,
    lat_cnt: jax.Array,
    lat_hist: jax.Array,
    lat_excluded: jax.Array,
    noop_blocked: jax.Array,
    reads_served: jax.Array,
    read_lat_sum: jax.Array,
    read_hist: jax.Array,
    viol_read_stale: jax.Array,
    fsync_lag_sum: jax.Array,
    fsync_lag_max: jax.Array,
) -> StepInfo:
    """Phase 9: on-device safety invariants + observability reductions (per cluster)."""
    n = cfg.n_nodes
    eye = jnp.eye(n, dtype=bool)
    is_leader = new.role == LEADER
    # Observability counts only *live* leaders: a crashed node frozen in LEADER role
    # provides no leadership (the cluster is leaderless until re-election), and the
    # north-star ticks-to-stable-leader metric must reflect that. The safety checks
    # below keep the unmasked roles: a frozen stale leader still participates in the
    # at-most-one-leader-per-term invariant.
    live_leader = is_leader & alive
    f = jnp.bool_(False)

    if cfg.check_invariants:
        # Election safety: at most one leader per term (Raft fig. 3).
        pair_bad = (
            is_leader[:, None]
            & is_leader[None, :]
            & (new.term[:, None] == new.term[None, :])
            & ~eye
        )
        viol_election = jnp.any(pair_bad)
        # Commit sanity: monotonic, within the log, above the compaction base (with
        # the retained window inside the ring), and the committed prefix is
        # immutable -- entries below the old commit index never change term OR value
        # (state-machine-safety analogue of the reference's apply-entries! writing
        # committed values to an append-only file, log.clj:69-76). Immutability is
        # checked via the carried prefix checksum (chk_ok; log_ops module comment).
        viol_commit = jnp.any(
            (new.commit_index < old.commit_index)
            | (new.commit_index > new.log_len)
            | (new.commit_index < new.log_base)
            | (new.log_len - new.log_base > cfg.log_capacity)
            | ~chk_ok
        )
    else:
        viol_election = f
        viol_commit = f

    if cfg.check_log_matching:

        def _check(_):
            # Log matching on committed prefixes: any two nodes agree on every
            # entry (term AND value) up to m = min(commit_i, commit_j).
            # O(N^2 * CAP) -- gated, and sampled every log_matching_interval
            # ticks (below).
            minc = jnp.minimum(new.commit_index[:, None], new.commit_index[None, :])
            differ = (new.log_term[:, None, :] != new.log_term[None, :, :]) | (
                new.log_val[:, None, :] != new.log_val[None, :, :]
            )
            if not cfg.compaction:
                ks = jnp.arange(cfg.log_capacity, dtype=jnp.int32)
                both = ks[None, None, :] < minc[:, :, None]
                return jnp.any(both & differ), jnp.int32(0)
            # Ring form, in two parts per pair (i, j) with mb = max(base_i, base_j):
            # entries in (mb, m] are live in BOTH rings at the same slot (same
            # absolute index, same CAP) -> compare slots; the prefix up to mb is
            # compared via checksums-at-mb (chk_at(i, p) = base_chk_i + live sum
            # (base_i, p]), which is computable because mb >= base_i. Pairs where
            # one node compacted past the other's commit (m < mb) are skipped --
            # their agreement is pinned transitively through common peers -- and
            # COUNTED (StepInfo.lm_skipped_pairs) so the coverage is measured.
            cap_ = cfg.log_capacity
            sl = jnp.arange(cap_, dtype=jnp.int32)[None, :]
            b = new.log_base
            abs0 = b[:, None] + (sl - b[:, None]) % cap_  # [N, CAP] entry idx - 1
            mb_ = jnp.maximum(b[:, None], b[None, :])  # [N, N]
            comparable = minc >= mb_
            in_i = (abs0[:, None, :] >= mb_[:, :, None]) & (
                abs0[:, None, :] < minc[:, :, None]
            )
            in_j = (abs0[None, :, :] >= mb_[:, :, None]) & (
                abs0[None, :, :] < minc[:, :, None]
            )
            viol_suffix = jnp.any(comparable[:, :, None] & in_i & in_j & differ)
            w_t, w_v = log_ops.chk_weights_at(abs0)
            contrib = (
                new.log_term.astype(jnp.uint32) * w_t
                + new.log_val.astype(jnp.uint32) * w_v
            )  # [N, CAP]
            chk_at_mb = new.base_chk[:, None] + jnp.sum(
                jnp.where(abs0[:, None, :] < mb_[:, :, None], contrib[:, None, :], jnp.uint32(0)),
                axis=2,
                dtype=jnp.uint32,
            )  # [N(i), N(j)] = chk of node i's prefix at mb(i, j)
            viol_prefix = jnp.any(comparable & (chk_at_mb != chk_at_mb.T))
            skipped = (jnp.sum(~comparable & ~eye) // 2).astype(jnp.int32)
            return viol_suffix | viol_prefix, skipped

        if cfg.log_matching_interval == 1:
            viol_match, lm_skipped = _check(None)
        else:
            # Sampled cadence: the batch ticks in lockstep (config.py), so the
            # predicate is one scalar in the batch-minor hot path and lax.cond
            # truly skips the check off-cadence; under vmap (debug tier) cond
            # lowers to a select and both branches run -- same values either way.
            viol_match, lm_skipped = jax.lax.cond(
                new.now % cfg.log_matching_interval == 0,
                _check,
                lambda _: (f, jnp.int32(0)),
                None,
            )
    else:
        viol_match, lm_skipped = f, jnp.int32(0)

    leader = jnp.min(jnp.where(live_leader, jnp.arange(n, dtype=jnp.int32), n))
    return StepInfo(
        viol_election_safety=viol_election,
        viol_commit=viol_commit,
        viol_log_matching=viol_match,
        leader=jnp.where(leader < n, leader, NIL).astype(jnp.int32),
        n_leaders=jnp.sum(live_leader).astype(jnp.int32),
        max_term=jnp.max(new.term),
        max_commit=jnp.max(new.commit_index),
        min_commit=jnp.min(new.commit_index),
        msgs_delivered=(jnp.sum(req_in) + jnp.sum(resp_in)).astype(jnp.int32),
        # Offers accepted this tick, not appends: the direct client collapses
        # split-brain double-accepts of one offer via any(); the redirect
        # pipeline counts accepted slots (distinct offers) -- see phase 6.
        cmds_injected=cmds_cnt,
        lat_sum=lat_sum,
        lat_cnt=lat_cnt,
        lat_hist=lat_hist,
        lat_excluded=lat_excluded,
        noop_blocked=noop_blocked,
        lm_skipped_pairs=lm_skipped,
        reads_served=reads_served,
        read_lat_sum=read_lat_sum,
        read_hist=read_hist,
        viol_read_stale=viol_read_stale,
        fsync_lag_sum=fsync_lag_sum,
        fsync_lag_max=fsync_lag_max,
    )
