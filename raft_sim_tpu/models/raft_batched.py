"""Batch-minor Raft tick kernel: the hot path for TPU execution.

Semantics are EXACTLY models/raft.py (same nine phases, same citations) -- this module
exists purely for memory layout. The vmap form puts the cluster batch LEADING
([B, N, ...]), which leaves each array's two minor dims at (N, N) or (N, CAP); TPU
tiles the two minor dims to (8, 128), so a [B, 5, 5] int32 array physically occupies
~40x its logical bytes and every tick is HBM-bound on padding (measured ~700KB moved
per cluster-tick vs ~3KB of logical state). Here the batch axis B is MINOR on every
array ([N, B], [N, N, B], [N, CAP, B]), so B rides the 128-wide lane tile and padding
is bounded by the second-minor dim (N or E or CAP -> at most 8/5).

Parity with the vmap form is enforced bit-for-bit by tests/test_batched_parity.py;
parity with the scalar oracle therefore transfers. Keep the two kernels in sync: any
semantic change lands in raft.py first (with its unit tests), then here.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


class NodeShardCtx(NamedTuple):
    """Node-axis sharding context for `_step_b`/`_step_info_b` (built inside
    parallel/nodeshard.py's shard_map body; never seen by single-chip callers).

    The node axis is partitioned row-wise by RECEIVER over `n_dev` devices of a
    named mesh axis: the global node count is padded to n_pad = n_dev * nl and
    every state/mailbox leg carries this device's `nl` rows (peer/sender axes
    stay full at n_pad). Pad rows are permanently-dead nodes (alive=False every
    tick), which makes them tick fixed points; the pad hazards that are NOT
    inert by liveness alone (the phase-8 window-start min and the n<=cap
    quorum count) are masked explicitly where they arise -- see pad_self /
    valid_peer below and docs/DESIGN.md "Node-axis sharding"."""

    axis: str  # mesh axis name the node rows are sharded over
    nl: int  # node rows per device (static)
    n_pad: int  # padded node-axis length = n_devices * nl (static)
    row0: jax.Array  # first global row of this shard (traced: axis_index * nl)


def _loc(x, sh: NodeShardCtx):
    """This device's node rows of a full [n_pad, ...] per-node array."""
    return lax.dynamic_slice_in_dim(x, sh.row0, sh.nl, axis=0)


def _gather_mailbox(cfg: RaftConfig, mb: Mailbox, sh: NodeShardCtx) -> Mailbox:
    """THE hot-loop collective: all_gather the outbound mailbox over the node
    axis and reorient the per-edge planes into the receiver view _step_b reads.

    The sharded carry stores every mailbox leg WRITER-major (rows = this
    device's senders/responders), so one tiled all_gather materializes the full
    sender/responder axis and every delivery reduction after it is local:
      req_* / ent_* headers [nl, ...] -> [n_pad, ...] (the broadcast row)
      req_off [nl(snd), n_pad(rcv)]  -> gathered, then receivers keep their
                                        local columns (dense orientation
                                        [sender, receiver(local)])
      resp_kind carried TRANSPOSED [nl(responder), n_pad(receiver)] -> gathered
                                        to [n_pad, n_pad], swapped back to the
                                        dense [receiver(local), responder] view
      pv_grant carried [nl(voter), W(candidate bits)] -> unpacked over the
                                        candidate axis, transposed, local
                                        candidate rows repacked over the voter
                                        axis (the dense [cand, W(voter)] view)
    Legs whose structural gate is off in the sharded v1 surface (transfer,
    reconfig, and -- when their own flags are off -- compaction/track/pre_vote
    legs) stay the LOCAL loop-invariant carry: they are never read, and not
    gathering them keeps the ICI bytes at the cost model's header-row figure."""
    npd = sh.n_pad
    ag = lambda x: lax.all_gather(x, sh.axis, axis=0, tiled=True)
    comp, track = cfg.compaction, cfg.track_offer_ticks
    if cfg.pre_vote:
        pv = bitplane.unpack(ag(mb.pv_grant), npd, axis=1)  # [voter, cand, B]
        pv = _loc(jnp.swapaxes(pv, 0, 1), sh)  # [nl(cand), n_pad(voter), B]
        pv_grant = bitplane.pack(pv, axis=1)
    else:
        pv_grant = mb.pv_grant
    return mb._replace(
        req_type=ag(mb.req_type),
        req_term=ag(mb.req_term),
        req_commit=ag(mb.req_commit),
        req_last_index=ag(mb.req_last_index),
        req_last_term=ag(mb.req_last_term),
        ent_start=ag(mb.ent_start),
        ent_prev_term=ag(mb.ent_prev_term),
        ent_count=ag(mb.ent_count),
        ent_term=ag(mb.ent_term),
        ent_val=ag(mb.ent_val),
        ent_tick=ag(mb.ent_tick) if track else mb.ent_tick,
        req_base=ag(mb.req_base) if comp else mb.req_base,
        req_base_term=ag(mb.req_base_term) if comp else mb.req_base_term,
        req_base_chk=ag(mb.req_base_chk) if comp else mb.req_base_chk,
        req_off=lax.dynamic_slice_in_dim(ag(mb.req_off), sh.row0, sh.nl, axis=1),
        resp_kind=_loc(jnp.swapaxes(ag(mb.resp_kind), 0, 1), sh),
        pv_grant=pv_grant,
        v_to=ag(mb.v_to),
        a_ok_to=ag(mb.a_ok_to),
        a_match=ag(mb.a_match),
        a_hint=ag(mb.a_hint),
        resp_term=ag(mb.resp_term),
    )


def to_batch_minor(tree):
    """[B, ...]-leading pytree -> [..., B]-trailing (transpose once per run, not per
    tick)."""
    return jax.tree.map(lambda x: jnp.moveaxis(x, 0, -1), tree)


def from_batch_minor(tree):
    return jax.tree.map(lambda x: jnp.moveaxis(x, -1, 0), tree)


def step_b(
    cfg: RaftConfig, s: ClusterState, inp: StepInputs, sh: NodeShardCtx | None = None
) -> tuple[ClusterState, StepInfo]:
    """One tick for B clusters at once; every array carries a trailing batch axis.

    Mirrors raft.step phase by phase; see that function for the reference
    citations -- and for the TRACE DELTA CONTRACT (raft_sim_tpu/trace reads
    role/term/voted_for/commit_index/log_len deltas of this kernel too; the
    phase-order properties documented there bind both kernels, which
    tests/test_trace.py pins by re-deriving the batched path's device events
    from the unbatched kernel's stacked states).

    Under cfg.compact_planes this boundary mirrors raft.step's: unpack the
    compacted carry (ops/tile.py; trailing batch axes ride along), run the
    identical dense tick, repack with gated-off legs passed through
    verbatim.
    """
    if not cfg.compact_planes:
        return _step_b(cfg, s, inp, sh)
    assert sh is None  # sharded carries run dense (parallel/nodeshard.py)
    from raft_sim_tpu.ops import tile

    s2, info = _step_b(
        cfg, tile.unpack_state(cfg, s), tile.unpack_inputs(cfg, inp)
    )
    return tile.pack_state(cfg, s2, reuse=s), info


def _step_b(
    cfg: RaftConfig, s: ClusterState, inp: StepInputs, sh: NodeShardCtx | None = None
) -> tuple[ClusterState, StepInfo]:
    """The dense batch-minor tick body (layout-independent semantics).

    `sh` (NodeShardCtx) switches to node-sharded execution inside a shard_map
    over sh.axis: `s` carries this device's nl node rows (peer axes padded to
    n_pad), `inp` carries the FULL padded per-node inputs (every device draws
    them redundantly from the same keys -- zero communication), and the only
    cross-device traffic per tick is the mailbox all_gather plus the
    pmin/pmax/psum folds of the per-cluster [B] reductions. sh=None (every
    single-chip caller) lowers a byte-identical program to the pre-sharding
    kernel: the folds degenerate to identity and the orientation aliases below
    collapse onto the one square eye."""
    n, e, cap = cfg.n_nodes, cfg.max_entries_per_rpc, cfg.log_capacity
    comp = cfg.compaction  # static: ring-log compaction + snapshot catch-up active
    track = cfg.track_offer_ticks  # static: offer-tick plane + latency metric active
    rcf = cfg.reconfig  # static: joint-consensus membership plane active
    xfr = cfg.leader_transfer  # static: TimeoutNow transfer plane active
    rdx = cfg.read_index  # static: ReadIndex read traffic class active
    rdl = cfg.read_lease  # static: lease-based reads (thesis 6.4.1) active
    dur = cfg.durable_storage  # static: fsync/WAL durability plane active
    b = s.role.shape[-1]
    # All iota-style constants are built at their final rank (log_ops.iota): Mosaic
    # cannot lower unit-dim-appending reshapes, and this module doubles as the
    # pallas_engine kernel body.
    iota = log_ops.iota
    if sh is None:
        nl = npd = n  # local self rows / padded peer-axis length: the full square
        ids2 = iota((n, 1), 0)  # [N, 1] node id column
        eye3 = iota((n, n, 1), 0) == iota((n, n, 1), 1)  # [N, N, 1]
        # Orientation aliases -- ONE array unsharded, distinct shapes sharded:
        # eye_sr = [sender, receiver(local)] (delivery), eye_ls = [self(local),
        # peer] (bookkeeping planes), pad_self = self-or-pad peer (the phase-8
        # window min and anything else that must skip pad peers).
        eye_sr = eye_ls = pad_self = eye3
        eye_p3 = bitplane.eye(n)[:, :, None]  # [N, W, 1] packed self-bit rows
        snd_ids = iota((n, n, 1), 0)  # [sender, receiver, 1] -> sender id
        gmax = gmin = gsum = gany = lambda x: x  # node-axis folds: already local
        alive_full = inp.alive
    else:
        # Sharded v1 feature surface: planes whose semantics span the node axis
        # in ways the gather does not cover (client redirect routing, log-
        # carried reconfig, transfer coups, ReadIndex/lease quorums, the O(N^2
        # CAP) log-matching pairs) are excluded -- parallel/nodeshard.py raises
        # a friendly error before tracing ever gets here.
        assert not (
            rcf or xfr or rdx or rdl or dur
            or cfg.client_redirect or cfg.check_log_matching
        )
        nl, npd = sh.nl, sh.n_pad
        ids2 = sh.row0 + iota((nl, 1), 0)  # [nl, 1] GLOBAL ids of local rows
        peer3 = iota((nl, npd, 1), 1)  # [nl, n_pad, 1] -> peer id
        eye_ls = ids2[:, :, None] == peer3
        pad_self = eye_ls | (peer3 >= n)  # pad peers masked like self
        eye_sr = iota((npd, nl, 1), 0) == (sh.row0 + iota((npd, nl, 1), 1))
        eye_p3 = _loc(bitplane.eye(npd), sh)[:, :, None]  # [nl, W, 1]
        snd_ids = iota((npd, nl, 1), 0)  # [sender, receiver(local), 1]
        gmax = lambda x: lax.pmax(x, sh.axis)
        gmin = lambda x: lax.pmin(x, sh.axis)
        gsum = lambda x: lax.psum(x, sh.axis)
        gany = lambda x: lax.psum(x.astype(jnp.int32), sh.axis) > 0
        # Per-node inputs: keep the full alive vector (delivery gates need the
        # SENDER side), localize the rest so the body below reads local rows.
        alive_full = inp.alive
        inp = inp._replace(
            alive=_loc(inp.alive, sh),
            restarted=_loc(inp.restarted, sh),
            skew=_loc(inp.skew, sh),
            timeout_draw=_loc(inp.timeout_draw, sh),
        )
    zw = jnp.uint32(0)

    # ---- phase -1: restart (crash fault) -----------------------------------------
    # The snapshot triple is persistent: commit resumes at log_base (raft.py).
    rs = inp.restarted  # [N, B]
    rs2 = rs[:, None, :]
    s = s._replace(
        role=jnp.where(rs, FOLLOWER, s.role),
        leader_id=jnp.where(rs, NIL, s.leader_id),
        votes=jnp.where(rs2, zw, s.votes),
        next_index=jnp.where(rs2, 1, s.next_index),
        match_index=jnp.where(rs2, 0, s.match_index),
        ack_age=jnp.where(rs2, cfg.ack_age_sat, s.ack_age),
        commit_index=jnp.where(rs, s.log_base, s.commit_index),
        commit_chk=jnp.where(rs, s.base_chk, s.commit_chk),
        deadline=jnp.where(rs, s.clock + inp.timeout_draw, s.deadline),
    )
    if dur:
        # Crash recovery (raft.py phase -1; storage/plane.recover is
        # elementwise, so the [N, B] orientation broadcasts through).
        r_term, r_vote, r_len = storage_plane.recover(
            cfg, rs, inp.torn_drop,
            s.dur_len, s.dur_term, s.dur_vote,
            s.term, s.voted_for, s.log_len,
        )
        s = s._replace(term=r_term, voted_for=r_vote, log_len=r_len)
    if cfg.pre_vote or rdl or rcf:
        # A restarted node remembers no leader contact: "quiet" immediately
        # (pre-votes grantable, and -- under the lease or log-carried-config
        # denial gates -- real votes too: raft.py phase -1).
        s = s._replace(
            heard_clock=jnp.where(
                rs, s.clock - cfg.election_min_ticks, s.heard_clock
            )
        )
    if xfr:
        # A pending transfer is volatile leader state (raft.py phase -1).
        s = s._replace(xfer_to=jnp.where(rs, NIL, s.xfer_to))
    if rdx:
        # Pending reads die with the process too (raft.py phase -1).
        s = s._replace(
            read_idx=jnp.where(rs, 0, s.read_idx),
            read_tick=jnp.where(rs, 0, s.read_tick),
            read_acks=jnp.where(rs2, zw, s.read_acks),
        )
        if rdl:
            # The staleness anchor dies with the slot it anchors.
            s = s._replace(read_fr=jnp.where(rs, 0, s.read_fr))
    # In sharded mode the carry mailbox is writer-major local rows; the gather
    # below is THE intra-tick collective (one tiled all_gather per leg), after
    # which `mb` has the exact orientations the dense body reads.
    mb = s.mailbox if sh is None else _gather_mailbox(cfg, s.mailbox, sh)
    base, bterm, bchk = s.log_base, s.base_term, s.base_chk  # [N, B]
    if rcf:
        # Snapshot config context (raft.py): carried untouched without comp.
        bmold, bpend, bepoch = s.base_mold, s.base_pend, s.base_epoch

    # Reconfiguration plane: log-carried, PER-NODE configuration masking
    # (raft.py): member rows are each node's derived view of its own log
    # prefix, [N, W, B]; every quorum test masks by the TESTING node's rows,
    # dual while that node's cfg_pend marks an open joint entry.
    if rcf:
        m_old, m_new = s.member_old, s.member_new  # [N, W, B]
        joint = s.cfg_pend > 0  # [N, B]
        maj_old = bitplane.count(m_old, axis=1) // 2 + 1  # [N, B]
        maj_new = bitplane.count(m_new, axis=1) // 2 + 1
        # Node i's own-membership bit (raft.py: the removed-server
        # disruption surface when a log misses its removal entry).
        member_b = jnp.any(((m_old | m_new) & eye_p3) != 0, axis=1)  # [N, B]

        def packed_quorum(rows):
            """[N, W, B] packed grant rows -> [N, B] own-config quorum."""
            ok = bitplane.count(rows & m_old, axis=1) >= maj_old
            return ok & (
                ~joint | (bitplane.count(rows & m_new, axis=1) >= maj_new)
            )
    else:

        def packed_quorum(rows):
            return bitplane.count(rows, axis=1) >= cfg.quorum

    # ---- phase 0: delivery -------------------------------------------------------
    # Input mask is per physical edge [to, from]; requests ([sender, receiver]) read
    # it transposed, responses ([receiver, responder]) directly (raft.py phase 0).
    # The mask arrives bit-packed over the source axis (raft.py phase 0): the
    # response orientation runs its AND-chain on the packed words and unpacks
    # once; the request orientation unpacks and transposes in bool space.
    dst_up = inp.alive & ~inp.restarted  # alive now AND at send time (last tick)
    # Receiver-row slices of the (full, redundantly drawn) delivery mask; the
    # packed source words cover all n_pad senders either way (pad bits are
    # canonical zeros -- bitplane's contract).
    dmask_rcv = inp.deliver_mask if sh is None else _loc(inp.deliver_mask, sh)
    resp_del_p = jnp.where(
        dst_up[:, None, :],
        dmask_rcv & ~eye_p3 & bitplane.pack(alive_full, axis=0)[None, :, :],
        zw,
    )  # [nl, W, B]
    deliver_resp = bitplane.unpack(resp_del_p, npd, axis=1)
    dreq = jnp.swapaxes(bitplane.unpack(inp.deliver_mask, npd, axis=1), 0, 1)
    if sh is not None:
        # [sender, receiver]: receivers keep their local columns.
        dreq = lax.dynamic_slice_in_dim(dreq, sh.row0, nl, axis=1)
    deliver_req = (
        dreq
        & ~eye_sr
        & alive_full[:, None, :]
        & dst_up[None, :, :]
    )  # [n_pad, nl, B]
    req_in = deliver_req & (mb.req_type != 0)[:, None, :]
    resp_in = deliver_resp & (mb.resp_kind != 0)

    # Heard-a-leader denial window (thesis 4.2.3; raft.py for the full
    # argument): shared by the log-carried membership defense (rcf) and the
    # lease vote denial (rdl), bypassed by the transfer override flag.
    if rcf or rdl:
        heard_recent = (
            (s.clock + inp.skew) - s.heard_clock < cfg.election_min_ticks
        )  # [N, B]
        if xfr:
            rv_denied = (
                heard_recent[None, :, :] & ~(mb.req_disrupt != 0)[:, None, :]
            )
        else:
            rv_denied = jnp.broadcast_to(heard_recent[None, :, :], (n, n, b))

    # ---- phase 1: term adoption (PreVote probes carry a PROSPECTIVE term:
    # never adopted -- raft.py phase 1) -------------------------------------------
    if cfg.pre_vote:
        term_req = req_in & (mb.req_type != REQ_PREVOTE)[:, None, :]
    else:
        term_req = req_in
    if rcf:
        # 4.2.3 in full: denied RequestVotes are not PROCESSED -- no term
        # adoption either (the removed-server disruption defense; raft.py).
        term_req = term_req & ~(
            (mb.req_type == REQ_VOTE)[:, None, :] & rv_denied
        )
    in_term = jnp.maximum(
        jnp.max(jnp.where(term_req, mb.req_term[:, None, :], 0), axis=0),
        jnp.max(jnp.where(resp_in, mb.resp_term[None, :, :], 0), axis=1),
    )  # [N, B]
    saw_higher = in_term > s.term
    term = jnp.maximum(s.term, in_term)
    role = jnp.where(saw_higher, FOLLOWER, s.role)
    voted_for = jnp.where(saw_higher, NIL, s.voted_for)
    leader_id = jnp.where(saw_higher, NIL, s.leader_id)
    votes = jnp.where(saw_higher[:, None, :], zw, s.votes)

    if comp:
        my_last_idx = s.log_len
        my_last_term = log_ops.term_at_rb(s.log_term, base, bterm, s.log_len)
    else:
        my_last_idx, my_last_term = log_ops.last_index_term_b(s.log_term, s.log_len)

    # ---- phase 2: RequestVote requests -------------------------------------------
    is_rv = req_in & (mb.req_type == REQ_VOTE)[:, None, :]  # [candidate, voter, B]
    cur_rv = is_rv & (mb.req_term[:, None, :] == term[None, :, :])
    up_to_date = (mb.req_last_term[:, None, :] > my_last_term[None, :, :]) | (
        (mb.req_last_term[:, None, :] == my_last_term[None, :, :])
        & (mb.req_last_index[:, None, :] >= my_last_idx[None, :, :])
    )
    can_grant = cur_rv & up_to_date
    if rcf or rdl:
        # Heard-a-leader vote denial (thesis 4.2.3; raft.py phase 2), with
        # the transfer override folded into rv_denied.
        can_grant = can_grant & ~rv_denied
    lowest = jnp.min(jnp.where(can_grant, snd_ids, n), axis=0)  # [N, B]
    # Boolean arithmetic instead of where-on-bools: Mosaic cannot lower vector
    # selects with i1 operands.
    has_vote = (voted_for != NIL)[None, :, :]
    grant = (has_vote & can_grant & (snd_ids == voted_for[None, :, :])) | (
        ~has_vote & can_grant & (snd_ids == lowest[None, :, :])
    )
    granted_any = jnp.any(grant, axis=0)  # [N, B]
    voted_for = jnp.where((voted_for == NIL) & granted_any, lowest, voted_for)
    vr_out = is_rv  # [candidate, voter] = response orientation [receiver, responder]
    # Grant target = post-update voted_for (raft.py phase 2: no reduction needed).
    grant_to = jnp.where(granted_any, voted_for, NIL).astype(node_dtype(cfg))  # [N, B]

    # ---- phase 3: AppendEntries requests ------------------------------------------
    is_ae = req_in & (mb.req_type == REQ_APPEND)[:, None, :]  # [leader, follower, B]
    cur_ae = is_ae & (mb.req_term[:, None, :] == term[None, :, :])
    ae_src = jnp.min(jnp.where(cur_ae, snd_ids, n), axis=0)  # [N, B]
    has_ae = ae_src < n
    sel = cur_ae & (snd_ids == ae_src[None, :, :])  # one-hot [sender, receiver, B]

    # Reconstruct the per-edge AE header from the selected sender's broadcast record
    # plus this edge's window offset j (Mailbox docstring; raft.py phase 3). All
    # selections are one-hot sums (no gather); when no sender is selected everything
    # is zeros and gated by has_ae/ae_ok downstream.
    pick_h = lambda h: jnp.sum(jnp.where(sel, h[:, None, :], 0), axis=0)  # [N, B]
    j_in = jnp.sum(jnp.where(sel, mb.req_off, 0), axis=0).astype(jnp.int32)  # [N, B] in 0..E
    # InstallSnapshot analogue: offset sentinel -1 (raft.py phase 3).
    if comp:
        snap = has_ae & (j_in < 0)
        ae_norm = has_ae & ~snap
    else:
        snap = jnp.zeros_like(has_ae)
        ae_norm = has_ae
    # Well-formed mailboxes keep the one-hot sum in [-1, E]; the clip bounds the
    # fully-masked garbage lane (and routes snap's -1 to 0, gated by ae_norm), as
    # in raft.py. Keeps prev_i provably within the idx dtype on wide-N tiers.
    j_nn = jnp.clip(j_in, 0, e)
    ws_in = pick_h(mb.ent_start)
    lcommit = pick_h(mb.req_commit)
    prev_i = jnp.where(ae_norm, ws_in + j_nn, 0)
    n_ent = jnp.where(ae_norm, jnp.clip(pick_h(mb.ent_count) - j_nn, 0, e), 0)
    # One masked reduction selects EVERY window plane (same one-hot mask):
    # terms and values -- plus offer stamps / config commands when their
    # planes are live -- ride a single [N, N, kE, B] pass, split after.
    planes = [mb.ent_term, mb.ent_val]
    if track:
        planes.append(mb.ent_tick)
    if rcf:
        planes.append(mb.ent_cfg)
    ent_tv = jnp.concatenate(planes, axis=1)  # [N, kE, B]
    w_tv = jnp.sum(jnp.where(sel[:, :, None, :], ent_tv[:, None], 0), axis=0)
    w_term_in = w_tv[:, :e]  # [N, E, B]
    w_val_in = w_tv[:, e:2 * e]
    off_w = 2 * e
    if track:
        w_tick_in = w_tv[:, off_w:off_w + e]
        off_w += e
    else:
        w_tick_in = None
    w_cfg_in = w_tv[:, off_w:off_w + e] if rcf else None
    # prev term via ext[k] = term of 1-based entry ws+k: k=0 is the sender's
    # ent_prev_term, k>=1 the shared window slots; one-hot over the E+1 offsets.
    ext = jnp.concatenate(
        [pick_h(mb.ent_prev_term)[:, None, :], w_term_in], axis=1
    )  # [N, E+1, B]
    oh_j = iota((1, e + 1, 1), 1) == j_nn[:, None, :]
    prev_t = jnp.sum(jnp.where(oh_j, ext, 0), axis=1)  # [N, B]
    # This receiver's entries start at window slot j (slot k holds entry ws+k+1).
    off = jnp.clip(j_nn, 0, e - 1)  # j = E only when n_ent = 0 (fully masked)
    ent_term_in = log_ops.window_b(w_term_in, off, e)  # [N, E, B]
    ent_val_in = log_ops.window_b(w_val_in, off, e)
    ent_tick_in = log_ops.window_b(w_tick_in, off, e) if track else None
    ent_cfg_in = log_ops.window_b(w_cfg_in, off, e) if rcf else None

    if cfg.pre_vote:
        stepdown = (role == CANDIDATE) | (role == PRECANDIDATE)
    else:
        stepdown = role == CANDIDATE
    role = jnp.where(has_ae & stepdown, FOLLOWER, role)
    leader_id = jnp.where(has_ae, ae_src, leader_id)

    if comp:
        prev_stored_term = log_ops.term_at_rb(s.log_term, base, bterm, prev_i)
        # prev below the local base is committed-and-compacted: consistent by
        # leader completeness; at prev == base the check is against base_term.
        consistent = (
            (prev_i == 0)
            | (prev_i < base)
            | ((prev_i <= s.log_len) & (prev_stored_term == prev_t))
        )
    else:
        prev_stored_term = log_ops.term_at_b(s.log_term, prev_i)
        consistent = (prev_i == 0) | (
            (prev_i <= s.log_len) & (prev_stored_term == prev_t)
        )
    ae_ok = ae_norm & consistent

    ks_e = iota((1, e, 1), 1)  # [1, E, 1]
    gidx0 = prev_i[:, None, :] + ks_e  # [N, E, B] 0-based entry indices
    if comp:
        # Skip already-compacted entries, accept only what the ring can hold
        # (raft.py phase 3).
        lo = jnp.clip(base - prev_i, 0, e)  # [N, B]
        n_acc = jnp.minimum(n_ent, jnp.maximum(base + cap - prev_i, 0))
        in_ent = (ks_e >= lo[:, None, :]) & (ks_e < n_acc[:, None, :])
        stored = log_ops.window_rb(s.log_term, prev_i, e)  # [N, E, B]
        appended_len = prev_i + n_acc
    else:
        n_acc = n_ent
        in_ent = ks_e < n_ent[:, None, :]
        stored = log_ops.window_b(s.log_term, prev_i, e)  # [N, E, B]
        appended_len = jnp.minimum(prev_i + n_ent, cap)
    exists = gidx0 < s.log_len[:, None, :]
    mismatch = in_ent & exists & (stored != ent_term_in)
    any_mismatch = jnp.any(mismatch, axis=1)  # [N, B]
    new_len = jnp.where(any_mismatch, appended_len, jnp.maximum(s.log_len, appended_len))
    log_len = jnp.where(ae_ok, new_len, s.log_len)
    if dur:
        # Durable watermark after the AE conflict truncation (raft.py phase 3).
        dur_mid = jnp.minimum(s.dur_len, log_len)
    if comp:
        log_term_arr = log_ops.write_window_rb(
            s.log_term, prev_i, ent_term_in, ae_ok, lo, n_acc
        )
        log_val_arr = log_ops.write_window_rb(
            s.log_val, prev_i, ent_val_in, ae_ok, lo, n_acc
        )
        if track:
            log_tick_arr = log_ops.write_window_rb(
                s.log_tick, prev_i, ent_tick_in, ae_ok, lo, n_acc
            )
        if rcf:
            # Same masks as the value plane: non-config entries ship 0 and
            # scrub stale config commands off reused slots (raft.py).
            log_cfg_arr = log_ops.write_window_rb(
                s.log_cfg, prev_i, ent_cfg_in, ae_ok, lo, n_acc
            )
    else:
        log_term_arr = log_ops.write_window_b(s.log_term, prev_i, ent_term_in, ae_ok, n_ent)
        log_val_arr = log_ops.write_window_b(s.log_val, prev_i, ent_val_in, ae_ok, n_ent)
        if track:
            log_tick_arr = log_ops.write_window_b(
                s.log_tick, prev_i, ent_tick_in, ae_ok, n_ent
            )
        if rcf:
            log_cfg_arr = log_ops.write_window_b(
                s.log_cfg, prev_i, ent_cfg_in, ae_ok, n_ent
            )
    if not track:
        log_tick_arr = s.log_tick  # untouched: loop-invariant carry leg
    if not rcf:
        log_cfg_arr = s.log_cfg  # untouched: loop-invariant carry leg

    # The floor at 0 is a no-op on the ae_ok path (prev_i/n_acc are
    # non-negative for a real AE) but bounds the masked-garbage lane so the
    # int8/int16 a_match narrowing below is provably in range (Pass E).
    last_new = jnp.maximum(jnp.minimum(prev_i + n_acc, log_len), 0)
    commit = jnp.where(
        ae_ok,
        jnp.maximum(s.commit_index, jnp.minimum(lcommit, last_new)),
        s.commit_index,
    )

    # Snapshot install (raft.py phase 3): adopt the sender's compaction state,
    # retaining our suffix when it extends through L with the snapshot's term.
    if comp:
        L = jnp.where(snap, pick_h(mb.req_base), 0)
        Lt = pick_h(mb.req_base_term)
        Lchk = jnp.sum(jnp.where(sel, mb.req_base_chk[:, None, :], jnp.uint32(0)), axis=0)
        apply_snap = snap & (L > base)
        keep = (
            apply_snap
            & (L <= s.log_len)
            & (log_ops.term_at_rb(s.log_term, base, bterm, L) == Lt)
        )
        wipe = apply_snap & ~keep
        bterm = jnp.where(apply_snap, Lt, bterm)
        bchk = jnp.where(apply_snap, Lchk, bchk)
        base = jnp.where(apply_snap, L, base)
        log_len = jnp.where(wipe, L, log_len)
        commit = jnp.where(apply_snap, jnp.maximum(commit, L), commit)
        if rcf:
            # Snapshot config context installs with the snapshot (raft.py).
            Lmold = jnp.sum(
                jnp.where(
                    sel[:, :, None, :], mb.req_base_mold[:, None], jnp.uint32(0)
                ),
                axis=0,
            )  # [N, W, B]
            bmold = jnp.where(apply_snap[:, None, :], Lmold, bmold)
            bpend = jnp.where(apply_snap, pick_h(mb.req_base_pend), bpend)
            bepoch = jnp.where(apply_snap, pick_h(mb.req_base_epoch), bepoch)
    else:
        apply_snap = snap

    # [leader, follower] is already the response orientation [receiver, responder]
    # (snapshot installs always ack, with match = the snapshot index); the payload
    # is per responder -- at most one success target, one shared nack hint
    # (raft.py phase 3, Mailbox docstring).
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

    # ---- phase 3.5: PreVote requests (thesis 9.6; raft.py) -----------------------
    if cfg.pre_vote or rdl or rcf:
        # heard_clock serves the pre-vote quiet rule, the lease vote denial,
        # and the log-carried-config removed-server denial (phase 2) -- any
        # gate keeps the leg live (raft.py).
        clock_pv = s.clock + inp.skew  # phase 7's clock; duplicated, CSE'd
        heard = jnp.where(has_ae, clock_pv, s.heard_clock)  # [N, B]
    else:
        heard = s.heard_clock
    if cfg.pre_vote:
        is_pv = req_in & (mb.req_type == REQ_PREVOTE)[:, None, :]  # [cand, voter, B]
        quiet = (clock_pv - heard >= cfg.election_min_ticks) & (role != LEADER)
        pv_grant = (
            is_pv
            & (mb.req_term[:, None, :] >= term[None, :, :])
            & up_to_date
            & quiet[None, :, :]
        )
        pv_out = is_pv

    # ---- phase 3.7: TimeoutNow receipt (thesis 3.10; raft.py) --------------------
    if xfr:
        rcv_ids = iota((1, n, 1), 1)  # [1, N(receiver), 1]
        is_tn = req_in & (mb.req_type == REQ_TIMEOUT_NOW)[:, None, :]
        tn_cur = (
            is_tn
            & (mb.xfer_tgt[:, None, :] == rcv_ids)
            & (mb.req_term[:, None, :] == term[None, :, :])
        )
        xfer_elect = jnp.any(tn_cur, axis=0) & inp.alive & (role != LEADER)
        if rcf:
            xfer_elect = xfer_elect & member_b  # non-voters never campaign
        if not cfg.xfer_election:
            # TEST-ONLY mutant: transfer as a coup (raft.py phase 3.7).
            coup = xfer_elect
            term = term + coup
            role = jnp.where(coup, LEADER, role)
            leader_id = jnp.where(coup, ids2, leader_id)
            xfer_elect = jnp.zeros_like(coup)
        else:
            coup = jnp.zeros_like(xfer_elect)

    # ---- phase 4: responses ------------------------------------------------------
    vresp = resp_in & (mb.resp_kind == RESP_VOTE)
    new_votes = (
        vresp
        & (mb.v_to[None, :, :] == ids2[:, None, :])
        & (mb.resp_term[None, :, :] == term[:, None, :])
        & (role == CANDIDATE)[:, None, :]
    )
    votes = votes | bitplane.pack(new_votes, axis=1)
    # Packed-quorum test: word popcount over [N, W, B] instead of a bool-plane
    # sum over [N, N, B] (raft.py phase 4); configuration-masked (dual during
    # joint phases) when the reconfiguration plane is live.
    win = (role == CANDIDATE) & packed_quorum(votes) & inp.alive
    if rcf:
        win = win & member_b  # a removed node cannot win on banked votes
    if xfr and not cfg.xfer_election:
        win = win | coup  # mutant coups ride the fresh-leader bookkeeping
    role = jnp.where(win, LEADER, role)
    leader_id = jnp.where(win, ids2, leader_id)
    # Log indices are capacity-bounded (config caps log_capacity): the [N, N, B]
    # bookkeeping planes and their intermediates ride int8/int16, cutting their
    # HBM cost 4x/2x vs int32. Compaction carries absolute indices: int32
    # (types.index_dtype).
    len_i = log_len.astype(s.next_index.dtype)
    next_index = jnp.where(win[:, None, :], (len_i + 1)[:, None, :], s.next_index)
    match_index = jnp.where(win[:, None, :], 0, s.match_index)

    # ---- phase 4.5: PreVote responses + promotion (thesis 9.6; raft.py) ----------
    if cfg.pre_vote:
        # Grant bits ride the packed pv_grant plane (raft.py phase 4.5).
        pvresp = resp_in & (mb.resp_kind == RESP_PREVOTE)
        new_pv = jnp.where(
            (role == PRECANDIDATE)[:, None, :],
            bitplane.pack(pvresp, axis=1) & mb.pv_grant,
            zw,
        )
        votes = votes | new_pv
        pre_win = (role == PRECANDIDATE) & packed_quorum(votes) & inp.alive
        if rcf:
            pre_win = pre_win & member_b
        term = term + pre_win
        role = jnp.where(pre_win, CANDIDATE, role)
        voted_for = jnp.where(pre_win, ids2, voted_for)
        # votes is uint32 now: a plain select (the i1-select Mosaic caveat that
        # forced boolean arithmetic here no longer applies to this plane).
        votes = jnp.where(pre_win[:, None, :], eye_p3, votes)
    else:
        pre_win = jnp.zeros_like(win)

    aresp = (
        resp_in
        & (mb.resp_kind == RESP_APPEND)
        & (role == LEADER)[:, None, :]
        & (mb.resp_term[None, :, :] == term[:, None, :])
    )
    ok_mine = mb.a_ok_to[None, :, :] == ids2[:, None, :]
    a_succ = aresp & ok_mine
    a_fail = aresp & ~ok_mine
    am = mb.a_match[None, :, :]  # already index_dtype (bounded by log length)
    ah = mb.a_hint[None, :, :]
    match_index = jnp.where(a_succ, jnp.maximum(match_index, am), match_index)
    next_index = jnp.where(a_succ, jnp.maximum(next_index, am + 1), next_index)
    # Failure: back off to min(next-1, hint+1) (conflict-index hint; raft.py).
    next_index = jnp.where(
        a_fail, jnp.maximum(jnp.minimum(next_index - 1, ah + 1), 1), next_index
    )
    # Responsiveness ages for the shared-window filter (phase 8; see raft.py).
    ack_age = jnp.minimum(s.ack_age + 1, cfg.ack_age_sat)
    ack_age = jnp.where(win[:, None, :] | aresp, 0, ack_age)

    # ---- phase 5: leader commit advancement --------------------------------------
    is_leader = role == LEADER
    if dur and cfg.durable_acks:
        # A leader's own vote for a replication quorum is its DURABLE length
        # (raft.py phase 5: the leader's disk is a follower too).
        dmi = dur_mid.astype(len_i.dtype)
        match_with_self = jnp.where(eye_ls, dmi[:, None, :], match_index)
    else:
        match_with_self = jnp.where(eye_ls, len_i[:, None, :], match_index)  # [N, N, B]
    # quorum-th largest match without a sort (TPU sorts along a non-minor axis are
    # slow). Two equivalent counting forms; pick per static shapes:
    #   cap < n  (config5: N=51, CAP=16): match values are bounded by CAP, so count
    #     how many matches reach each threshold v in 1..CAP; cnt_ge is non-increasing
    #     in v, so the quorum-th order statistic is the number of thresholds reached
    #     by >= quorum matches. O(N*CAP) compares per leader.
    #   n <= cap (configs 1-4, CAP up to 2048): threshold over the N match values
    #     themselves -- the quorum-th largest is the largest element v with
    #     count(match >= v) >= quorum. O(N^2) compares per leader, independent of CAP
    #     (the CAP-threshold form would do ~6x the work at N=5, CAP=32 and ~400x at
    #     config1's CAP=2048).
    if rcf:
        # Per-leader configuration-masked quorum match (raft.py phase 5):
        # candidates range over the members' own match values under EACH
        # leader's OWN derived member rows; dual (min of both configs)
        # while that leader's prefix is joint.
        mws = match_with_self
        ge_m = mws[:, None, :, :] >= mws[:, :, None, :]  # [i, j(cand), k, B]

        def masked_qmatch(mask_b, maj):
            # mask_b [N(i), N(k), B]: node i's member view; maj [N(i), B].
            cnt = jnp.sum(ge_m & mask_b[:, None, :, :], axis=2)  # [N, N, B]
            ok = (cnt >= maj[:, None, :]) & mask_b
            return jnp.max(jnp.where(ok, mws, 0), axis=1).astype(jnp.int32)

        mem_old_b = bitplane.unpack(m_old, n, axis=1)  # [N, N, B]
        mem_new_b = bitplane.unpack(m_new, n, axis=1)
        qm_old = masked_qmatch(mem_old_b, maj_old)
        quorum_match = jnp.where(
            joint,
            jnp.minimum(qm_old, masked_qmatch(mem_new_b, maj_new)),
            qm_old,
        )
    elif cap < n and not comp:
        # Thresholds 1..CAP only bound match values when indices are capacity-
        # bounded; compaction's absolute indices use the value-threshold form.
        vth = (iota((1, 1, cap, 1), 2) + 1).astype(match_with_self.dtype)  # 1..CAP
        cnt_ge = jnp.sum(match_with_self[:, :, None, :] >= vth, axis=1)  # [N, CAP, B]
        quorum_match = jnp.sum(cnt_ge >= cfg.quorum, axis=1).astype(jnp.int32)  # [N, B]
    else:
        ge = (
            match_with_self[:, None, :, :] >= match_with_self[:, :, None, :]
        )  # [N, j(candidate), k(counted), B]
        if sh is not None:
            # Pad peers carry match 0 and every candidate is >= 0: unmasked
            # they would inflate the count by (n_pad - n) for every candidate.
            ge = ge & (iota((1, 1, npd, 1), 2) < n)
        ok = jnp.sum(ge, axis=2) >= cfg.quorum  # [N, N, B]
        quorum_match = jnp.max(jnp.where(ok, match_with_self, 0), axis=1)  # [N, B]
    if comp:
        quorum_term = log_ops.term_at_rb(log_term_arr, base, bterm, quorum_match)
    else:
        quorum_term = log_ops.term_at_b(log_term_arr, quorum_match)
    commit = jnp.where(
        is_leader & inp.alive & (quorum_match > commit) & (quorum_term == term),
        quorum_match,
        commit,
    )

    # ---- phase 5.2: reconfiguration transitions moved INTO the log --------------
    # (Log-carried membership: no admin transition block. Joint entry/exit
    # are LOG APPENDS -- phase 6 originates them, phase 3 replicates them --
    # and each node's configuration re-derives from its own prefix at end of
    # tick; raft.py for the full rationale.)
    if xfr:
        tgt_oh_x = iota((1, n, 1), 1) == jnp.clip(s.xfer_to, 0, n - 1)[:, None, :]
        age_t = jnp.sum(jnp.where(tgt_oh_x, ack_age, 0), axis=1)  # one-hot gather
        keep_x = is_leader & (s.xfer_to != NIL) & (age_t <= cfg.ack_timeout_ticks)
        xfer_to = jnp.where(keep_x, s.xfer_to, NIL)
        t_x = inp.transfer_cmd  # [B]
        ld_ok_x = is_leader & inp.alive
        if rcf:
            ld_ok_x = ld_ok_x & member_b
            # Target must be a voter of the LEADER's own target config
            # (per-node derived rows; tick-start like every config read).
            t_voter = jnp.any(
                (m_new & bitplane.one_bit(t_x, n)[None]) != 0, axis=1
            )  # [N, B]
        else:
            t_voter = jnp.bool_(True)
        ldx = jnp.min(jnp.where(ld_ok_x, ids2, n), axis=0)  # [B]
        can_x = (
            (t_x != NIL)[None, :]
            & t_voter
            & (ids2 == ldx[None, :])
            & ld_ok_x
            & (t_x[None, :] != ids2)
            & (xfer_to == NIL)
        )
        xfer_to = jnp.where(can_x, t_x[None, :], xfer_to)
        xfer_pend = xfer_to != NIL
    if rdx:
        pend0 = s.read_idx > 0  # [N, B]
        keep_r = is_leader & pend0
        read_acks = jnp.where(
            keep_r[:, None, :], s.read_acks | bitplane.pack(aresp, axis=1), zw
        )
        if cfg.read_confirm:
            serve = keep_r & inp.alive & packed_quorum(read_acks | eye_p3)
        else:
            serve = keep_r & inp.alive  # TEST-ONLY mutant: no confirmation
        if rdl:
            # Lease fast path on the global-tick ack_age plane; the
            # lease_skew_safe mutant widens the window to the no-skew bound
            # election_min_ticks + 2 (raft.py phase 5 for the argument).
            lease_w = (
                cfg.read_lease_ticks
                if cfg.lease_skew_safe
                else cfg.election_min_ticks + 2
            )
            fresh_p = bitplane.pack(ack_age <= lease_w, axis=1)  # [N, W, B]
            lease_ok = packed_quorum(fresh_p | eye_p3)
            if xfr:
                # Transfer handoff covers the read path (raft.py phase 5).
                lease_ok = lease_ok & ~xfer_pend
            serve = serve | (keep_r & inp.alive & lease_ok)
        lat_r = jnp.maximum(s.now[None, :] + 1 - s.read_tick, 1)  # [N, B]
        reads_served = jnp.sum(serve, axis=0).astype(jnp.int32)
        read_lat_sum = jnp.sum(jnp.where(serve, lat_r, 0), axis=0).astype(jnp.int32)
        bin_r = log_ops.log2_bin(lat_r, LAT_HIST_BINS)
        oh_r = (
            iota((1, LAT_HIST_BINS, 1), 1) == bin_r[:, None, :]
        ) & serve[:, None, :]
        read_hist = jnp.sum(oh_r, axis=0).astype(jnp.int32)  # [BINS, B]
        if comp:
            cur_committed = (
                log_ops.term_at_rb(log_term_arr, base, bterm, commit) == term
            )
        else:
            cur_committed = log_ops.term_at_b(log_term_arr, commit) == term
        can_cap = (inp.read_cmd != NIL)[None, :] & is_leader & inp.alive & ~pend0
        if cfg.read_confirm:
            can_cap = can_cap & cur_committed
        if xfr:
            can_cap = can_cap & ~xfer_pend
        low_cap = jnp.min(jnp.where(can_cap, ids2, n), axis=0)  # [B]
        cap_r = can_cap & (ids2 == low_cap[None, :])
        cleared = serve | (pend0 & ~keep_r)
        read_idx = jnp.where(cap_r, commit + 1, jnp.where(cleared, 0, s.read_idx))
        read_tick = jnp.where(
            cap_r, s.now[None, :] + 1, jnp.where(cleared, 0, s.read_tick)
        )
        read_acks = jnp.where((cap_r | serve)[:, None, :], zw, read_acks)
        if rdl:
            # Staleness anchor + device invariant (raft.py phase 5).
            fr_now = jnp.maximum(s.lat_frontier, jnp.max(commit, axis=0))  # [B]
            read_fr = jnp.where(
                cap_r, fr_now[None, :], jnp.where(cleared, 0, s.read_fr)
            )
            if cfg.check_invariants:
                viol_read_stale = jnp.any(
                    serve & (s.read_idx - 1 < s.read_fr), axis=0
                )
            else:
                viol_read_stale = np.zeros((b,), np.bool_)
        else:
            viol_read_stale = np.zeros((b,), np.bool_)
    else:
        # Constants, not jnp.zeros: keep the disabled-mode lowered program
        # byte-identical (see raft.py).
        reads_served = np.zeros((b,), np.int32)
        read_lat_sum = np.zeros((b,), np.int32)
        read_hist = np.zeros((LAT_HIST_BINS, b), np.int32)
        viol_read_stale = np.zeros((b,), np.bool_)

    # ---- offer->commit latency (client workloads only; raft.py) ------------------
    if track:
        sl = iota((1, cap, 1), 1)
        if comp:
            abs1 = base[:, None, :] + (sl - base[:, None, :]) % cap + 1
        else:
            abs1 = sl + 1
        # Carried-frontier dedup; stamps read from the offer-tick plane, never
        # from values (raft.py).
        newly = (abs1 > s.lat_frontier[None, None, :]) & (abs1 <= commit[:, None, :])
        cli = (log_tick_arr >= 1) & (log_tick_arr <= s.now[None, None, :])
        lm = (is_leader & inp.alive)[:, None, :] & newly & cli
        lats = jnp.where(lm, s.now[None, None, :] - log_tick_arr + 1, 0)  # [N, CAP, B]
        lat_sum = gsum(jnp.sum(lats, axis=(0, 1)).astype(jnp.int32))
        lat_cnt = gsum(jnp.sum(lm, axis=(0, 1)).astype(jnp.int32))
        # Coverage gap counter: crossed-but-unattributed client entries, read
        # on the lowest-id max-commit node (raft.py for the full rationale).
        is_maxc = commit == gmax(jnp.max(commit, axis=0))[None, :]
        hnode = gmin(jnp.min(jnp.where(is_maxc, ids2, n), axis=0))  # [B]
        crossed = (ids2 == hnode[None, :])[:, None, :] & newly & cli
        lat_excluded = jnp.maximum(
            gsum(jnp.sum(crossed, axis=(0, 1)).astype(jnp.int32)) - lat_cnt, 0
        )
        # Histogram bin = floor(log2(l)) (log_ops.log2_bin; raft.py).
        bin_ = log_ops.log2_bin(lats, LAT_HIST_BINS)
        oh_b = (iota((1, 1, LAT_HIST_BINS, 1), 2) == bin_[:, :, None, :]) & lm[:, :, None, :]
        lat_hist = gsum(jnp.sum(oh_b, axis=(0, 1)).astype(jnp.int32))  # [BINS, B]
        lat_frontier = jnp.maximum(s.lat_frontier, gmax(jnp.max(commit, axis=0)))
    else:
        lat_sum = jnp.zeros_like(s.now)
        lat_cnt = jnp.zeros_like(s.now)
        lat_hist = jnp.zeros((LAT_HIST_BINS, b), jnp.int32)
        lat_excluded = jnp.zeros_like(s.now)
        lat_frontier = s.lat_frontier

    # ---- phase 5.5: log compaction (raft.py) -------------------------------------
    base_mid, bchk_mid = base, bchk  # post-install, pre-advance (checksum anchor)
    if comp:
        target = jnp.minimum(commit, log_len - (cap - cfg.compact_margin))
        base2 = jnp.maximum(base, target)
        bterm = log_ops.term_at_rb(log_term_arr, base, bterm, base2)  # = bterm if unchanged
        if rcf:
            # Fold the compacted span's config entries into the snapshot
            # context (cfglog.fold_span; anchored at the PRE-advance base,
            # same aliasing rule as the checksum pass -- raft.py phase 5.5).
            bmold, bpend, bepoch = cfglog.fold_span(
                cfg, log_cfg_arr, base, base2, bmold, bpend, bepoch,
                batched=True,
            )
        base = base2

    # ---- committed-prefix checksum, compaction form (raft.py: anchored at
    # base_mid, MUST run before phase 6 -- an injection into a slot freed by this
    # tick's rebase would alias under the anchored slot->index map; maintained
    # even with invariant checking off, since base_chk is load-bearing wire
    # state). The non-compaction form has no aliasing hazard and stays at its
    # original post-outbox position (placement affects XLA fusion of the hot
    # configs).
    if comp:
        co = jnp.maximum(s.commit_index, base_mid)  # snap installs skip the check
        s_co, s_bf, s_cn = log_ops.ring_chk_b(
            log_term_arr, log_val_arr, base_mid, (co, base, commit)
        )
        if cfg.check_invariants:
            chk_ok = (bchk_mid + s_co == s.commit_chk) | apply_snap
        else:
            chk_ok = jnp.ones_like(s.commit_index, dtype=bool)
        bchk = bchk_mid + s_bf
        chk_new = bchk_mid + s_cn

    # ---- phase 6: client command injection, redirect routing, election-win
    # no-op (raft.py phase 6) --------------------------------------------------------
    if comp:
        reserve = max(1, cfg.compact_margin // 2)
        noop = win & (log_len - base < cap)
        room = log_len - base < cap - reserve
        # Win with no no-op room: surfaced as a liveness metric (raft.py).
        noop_blocked = gsum(
            jnp.sum(win & ~(log_len - base < cap), axis=0).astype(jnp.int32)
        )
    else:
        noop = jnp.zeros_like(is_leader)
        room = log_len - base < cap
        noop_blocked = jnp.zeros_like(s.now)
    # ---- config-entry origination (log-carried membership; raft.py phase 6
    # for the full rationale: joint entry on the admin toggle, final entry
    # once the governing joint entry commits on the leader, both judged on
    # the leader's OWN tick-start derived configuration, sharing the
    # one-append-per-node slot at priority no-op > config > client) ---------------
    if rcf:
        t_r = inp.reconfig_cmd  # [B]
        tbit = bitplane.one_bit(t_r, n)  # [W, B]; all-zero column for NIL
        toggled = m_new ^ tbit[None]  # [N, W, B]: each node's view of the result
        ld_ok = is_leader & inp.alive & member_b & room & ~noop  # [N, B]
        ldj = jnp.min(jnp.where(ld_ok & ~joint, ids2, n), axis=0)  # [B]
        accept_j = (
            (t_r != NIL)[None, :]
            & (ids2 == ldj[None, :])
            & ld_ok
            & ~joint
            & (bitplane.count(tbit, axis=0) > 0)[None, :]
            & (bitplane.count(toggled, axis=1) >= 2)
        )
        if cfg.joint_consensus:
            # Pending toggle of this node's open joint phase: the one bit
            # its member_old and member_new rows differ on.
            pvbits = bitplane.unpack(m_old ^ m_new, n, axis=1)  # [N, N, B]
            pend_v = jnp.min(
                jnp.where(pvbits, iota((1, n, 1), 1), n), axis=1
            )  # [N, B]
            accept_f = ld_ok & joint & (commit >= s.cfg_pend)
            cfg_code = jnp.where(
                accept_j, t_r[None, :] + 1, jnp.where(accept_f, -(pend_v + 1), 0)
            ).astype(jnp.int32)
            cfg_write = accept_j | accept_f
        else:
            # TEST-ONLY mutant (single-server change; raft.py phase 6).
            cfg_code = jnp.where(accept_j, t_r[None, :] + 1, 0).astype(jnp.int32)
            cfg_write = accept_j
    if cfg.client_redirect:
        # K-deep in-flight pipeline: first free slot takes a fresh offer, at
        # most one slot accepted per node per tick, lowest slot first
        # (raft.py phase 6).
        kdim = cfg.client_pipeline
        kk3 = iota((kdim, 1, 1), 0)  # [K, 1, 1]
        free = s.client_pend == NIL  # [K, B]
        first_free = free & (jnp.cumsum(free, axis=0) == 1)
        fresh = (inp.client_cmd != NIL)[None, :] & first_free
        pend = jnp.where(fresh, inp.client_cmd[None, :], s.client_pend)  # [K, B]
        tgt = jnp.where(fresh, inp.client_target[None, :], s.client_dst)
        # Offer stamp rides the slot beside the payload (raft.py phase 6).
        ptick = (
            jnp.where(fresh, (s.now + 1)[None, :], s.client_tick) if track else None
        )
        active = pend != NIL
        tgt_oh = active[:, None, :] & (tgt[:, None, :] == iota((1, n, 1), 1))  # [K, N, B]
        low_k = jnp.min(jnp.where(tgt_oh, kk3, kdim), axis=0)  # [N, B]
        node_ok = is_leader & inp.alive & room & ~noop  # [N, B]
        if rcf:
            node_ok = node_ok & ~cfg_write  # the slot holds a config entry
        if xfr:
            node_ok = node_ok & ~xfer_pend  # transfer lease handoff (raft.py)
        client_ok = (low_k < kdim) & node_ok  # [N, B] nodes accepting a slot
        sel_k = tgt_oh & (kk3 == low_k[None, :, :]) & node_ok[None, :, :]  # [K, N, B]
        wval_cl = jnp.sum(jnp.where(sel_k, pend[:, None, :], 0), axis=0)  # [N, B]
        wtick_cl = (
            jnp.sum(jnp.where(sel_k, ptick[:, None, :], 0), axis=0) if track else None
        )
        accepted_k = jnp.any(sel_k, axis=1)  # [K, B]
        cmds_cnt = jnp.sum(accepted_k, axis=0).astype(jnp.int32)  # [B]
        tgt_ld = jnp.max(jnp.where(tgt_oh, leader_id[None, :, :], NIL), axis=1)  # [K, B]
        tgt_up = jnp.any(tgt_oh & inp.alive[None, :, :], axis=1)
        pend_on = active & ~accepted_k
        client_pend = jnp.where(pend_on, pend, NIL)
        client_dst = jnp.where(
            pend_on, jnp.where(tgt_up & (tgt_ld != NIL), tgt_ld, inp.client_bounce), 0
        )
        client_tick = jnp.where(pend_on, ptick, 0) if track else s.client_tick
    else:
        client_ok = (inp.client_cmd[None, :] != NIL) & is_leader & inp.alive & room & ~noop
        if rcf:
            client_ok = client_ok & ~cfg_write  # the slot holds a config entry
        if xfr:
            client_ok = client_ok & ~xfer_pend  # transfer lease handoff
        wval_cl = jnp.broadcast_to(inp.client_cmd[None, :], (nl, b))
        # Direct mode accepts on the offer tick: stamp = now + 1 (raft.py).
        wtick_cl = (
            jnp.broadcast_to((s.now + 1)[None, :], (nl, b)) if track else None
        )
        cmds_cnt = gany(jnp.any(client_ok, axis=0)).astype(jnp.int32)  # offers, not appends
        client_pend = s.client_pend
        client_dst = s.client_dst
        client_tick = s.client_tick
    do_write = (noop | cfg_write | client_ok) if rcf else (noop | client_ok)
    wval = jnp.where(noop, NOOP, wval_cl)  # [N, B]
    if rcf:
        # Config entries carry value 0 (the command rides the log_cfg plane).
        wval = jnp.where(cfg_write, 0, wval)
    # cap matches no slot -> masked-off writes dropped.
    inj_pos = jnp.where(do_write, log_len % cap if comp else log_len, cap)  # [N, B]
    inj_oh = iota((1, cap, 1), 1) == inj_pos[:, None, :]  # [N, CAP, B]
    log_term_arr = jnp.where(inj_oh, term[:, None, :], log_term_arr)
    log_val_arr = jnp.where(inj_oh, wval[:, None, :], log_val_arr)
    if track:
        # No-op entries carry stamp 0 (protocol filler, never a client offer).
        wtick = jnp.where(noop, 0, wtick_cl)  # [N, B]
        if rcf:
            wtick = jnp.where(cfg_write, 0, wtick)  # config entries too
        log_tick_arr = jnp.where(inj_oh, wtick[:, None, :], log_tick_arr)
    if rcf:
        # EVERY append writes the config plane (0 for non-config entries):
        # a slot reused after truncation must never leak its old command.
        log_cfg_arr = jnp.where(
            inj_oh, jnp.where(cfg_write, cfg_code, 0)[:, None, :], log_cfg_arr
        )
    log_len = log_len + do_write

    # ---- phase 7: timers ---------------------------------------------------------
    clock = s.clock + inp.skew
    reset_election = granted_any | has_ae | saw_higher
    deadline = jnp.where(reset_election, clock + inp.timeout_draw, s.deadline)
    deadline = jnp.where(win, clock + cfg.heartbeat_ticks, deadline)
    if cfg.pre_vote:
        deadline = jnp.where(pre_win, clock + inp.timeout_draw, deadline)
    expired = (clock >= deadline) & inp.alive

    heartbeat = expired & is_leader
    deadline = jnp.where(heartbeat, clock + cfg.heartbeat_ticks, deadline)

    if cfg.pre_vote:
        # Expiry starts a PRE-vote probe: no term bump, votedFor untouched
        # (raft.py phase 7); real elections start at promotions (phase 4.5).
        start_prevote = expired & ~is_leader
        if rcf:
            # Non-voters never campaign, judged on the node's OWN derived
            # config (raft.py phase 7: the disruption surface when a log
            # misses its removal entry).
            start_prevote = start_prevote & member_b
        if xfr:
            start_prevote = start_prevote & ~xfer_elect  # thesis-3.10 bypass
        role = jnp.where(start_prevote, PRECANDIDATE, role)
        leader_id = jnp.where(start_prevote, NIL, leader_id)
        votes = jnp.where(start_prevote[:, None, :], eye_p3, votes)
        deadline = jnp.where(start_prevote, clock + inp.timeout_draw, deadline)
        start_election = pre_win
        if xfr:
            # TimeoutNow election (raft.py phase 7): the real-election start
            # minus the pre-quorum; ~is_leader re-checked (a phase-4 win may
            # have promoted the target this very tick).
            xe = xfer_elect & ~pre_win & ~is_leader
            term = term + xe
            role = jnp.where(xe, CANDIDATE, role)
            voted_for = jnp.where(xe, ids2, voted_for)
            leader_id = jnp.where(xe, NIL, leader_id)
            votes = jnp.where(xe[:, None, :], eye_p3, votes)
            deadline = jnp.where(xe, clock + inp.timeout_draw, deadline)
            start_election = pre_win | xe
    else:
        start_prevote = jnp.zeros_like(expired)
        start_election = expired & ~is_leader
        if rcf:
            start_election = start_election & member_b  # non-voters never campaign
        if xfr:
            xe = xfer_elect & ~is_leader
            start_election = start_election | xe
        term = term + start_election
        role = jnp.where(start_election, CANDIDATE, role)
        voted_for = jnp.where(start_election, ids2, voted_for)
        leader_id = jnp.where(start_election, NIL, leader_id)
        votes = jnp.where(start_election[:, None, :], eye_p3, votes)
        deadline = jnp.where(start_election, clock + inp.timeout_draw, deadline)

    # ---- phase 7.5: fsync flush + durability gates (raft.py phase 7.5) -----------
    if dur:
        fs_fire = inp.fsync_fire & inp.alive  # dead disks never flush
        dur2_len, dur2_term, dur2_vote = storage_plane.flush(
            fs_fire, dur_mid, s.dur_term, s.dur_vote, log_len, term, voted_for
        )
        if cfg.durable_acks:
            # Gate 1 (ack durability): AE acks reflect only the fsynced
            # prefix (raft.py phase 7.5).
            out_a_match = jnp.minimum(
                out_a_match.astype(jnp.int32), dur2_len
            ).astype(idt)
            # Gate 2 (vote durability): a grant is exposed only once the
            # durable snapshot covers it; the covering flush emits the
            # withheld response (late_grant -> outbox overlay below).
            covered0 = storage_plane.covered(s.dur_term, s.dur_vote, term, voted_for)
            covered2 = storage_plane.covered(dur2_term, dur2_vote, term, voted_for)
            grant_to = jnp.where(covered2, voted_for, NIL).astype(
                node_dtype(cfg)
            )
            late_grant = covered2 & ~covered0 & ~granted_any

    # ---- phase 8: outbox ---------------------------------------------------------
    send_append = win | heartbeat
    if comp:
        new_last_idx = log_len
        new_last_term = log_ops.term_at_rb(log_term_arr, base, bterm, log_len)
    else:
        new_last_idx, new_last_term = log_ops.last_index_term_b(log_term_arr, log_len)

    # Request headers are per sender (both RPCs are broadcasts); only the AE window
    # offset is per edge (Mailbox docstring; raft.py phase 8).
    ae_edge = send_append[:, None, :] & ~eye_ls
    # A strong int32 0: a weak-typed req_type would flip the carry's type
    # after the first chunk and recompile every chunk program once more.
    out_req_type = jnp.where(
        start_election, REQ_VOTE, jnp.where(send_append, REQ_APPEND, jnp.int32(0))
    )  # [N, B]
    if cfg.pre_vote:
        out_req_type = jnp.where(start_prevote, REQ_PREVOTE, out_req_type)
        rv_like = start_election | start_prevote
    else:
        rv_like = start_election
    out_req_term = jnp.where(out_req_type != 0, term, 0)
    if cfg.pre_vote:
        out_req_term = jnp.where(start_prevote, term + 1, out_req_term)  # prospective
    if xfr:
        # TimeoutNow fire (raft.py phase 8): replaces the heartbeat slot on
        # catch-up; AE window fields stay populated (receivers gate on
        # req_type == REQ_APPEND).
        tgt_oh8 = iota((1, n, 1), 1) == jnp.clip(xfer_to, 0, n - 1)[:, None, :]
        t_match = jnp.sum(
            jnp.where(tgt_oh8, match_index, 0), axis=1, dtype=jnp.int32
        )
        if cfg.xfer_election:
            caught = t_match >= log_len
        else:
            caught = jnp.ones_like(log_len, bool)  # TEST-ONLY mutant: no wait
        fire = send_append & (xfer_to != NIL) & caught
        out_req_type = jnp.where(fire, REQ_TIMEOUT_NOW, out_req_type)
        out_xfer_tgt = jnp.where(fire, xfer_to, NIL).astype(node_dtype(cfg))
    else:
        out_xfer_tgt = mb.xfer_tgt  # NIL, loop-invariant carry component
    if xfr and (rcf or rdl):
        # The disruptive-RequestVote override (thesis 3.10/4.2.3; raft.py
        # phase 8): written only when a denial gate can read it.
        out_req_disrupt = jnp.where(xe, 1, 0).astype(jnp.int8)
    else:
        out_req_disrupt = mb.req_disrupt  # zeros, loop-invariant component
    prev_out = jnp.clip(next_index - 1, 0, len_i[:, None, :])  # [src, dst, B]
    # Shared window start: minimum prev over RESPONSIVE peers, falling back to all
    # peers when none are (see raft.py phase 8 for the liveness argument).
    responsive = ack_age <= cfg.ack_timeout_ticks
    if comp:
        big = jnp.int32(2**31 - 1)
        ws_resp = jnp.min(jnp.where(pad_self | ~responsive, big, prev_out), axis=1)  # [N, B]
        ws_all = jnp.min(jnp.where(pad_self, big, prev_out), axis=1)
        ws = jnp.where(ws_resp == big, ws_all, ws_resp)
    else:
        # Single [N, N, B] min instead of two: unresponsive peers ride +K and
        # self +2K with K = cap + 1, so the min is the responsive minimum when
        # one exists, else K + the all-peers minimum (self cannot win it:
        # 2K > K + cap, and with n >= 2 some non-self edge is <= K + cap). The
        # largest encoded value, 3*cap + 2, fits the index dtype by construction
        # (types.MAX_INT8_LOG_CAPACITY / config.MAX_LOG_CAPACITY). Same values
        # as the two-pass form, one full reduction cheaper.
        K = jnp.asarray(cap + 1, len_i.dtype)
        z = jnp.asarray(0, len_i.dtype)
        # Pad peers ride the self (+2K) lane: a leader's win resets the whole
        # ack_age row, so they would otherwise pose as responsive (prev_out =
        # len-at-win) and drag the window start (pad_self == eye3 dense).
        off = prev_out + jnp.where(pad_self, K + K, jnp.where(responsive, z, K))
        m = jnp.min(off, axis=1)  # [N, B]
        # Both where-branches are non-negative under their conditions; the
        # explicit floor makes that a local (range-provable) fact.
        ws = jnp.maximum(jnp.where(m >= K, m - K, m), z)
    ws = jnp.minimum(ws, len_i)  # narrow dtype throughout; widened at header writes
    if comp:
        # The window cannot start below the compaction base; peers whose prev fell
        # below it get the InstallSnapshot sentinel (raft.py phase 8).
        ws = jnp.maximum(ws, base)
        snap_edge = ae_edge & (prev_out < base[:, None, :])
    # Clamp prev into [ws, ws+E] (see raft.py): the per-edge request payload then
    # reduces to the offset j = prev - ws in 0..E; receivers reconstruct prev,
    # prev_term, and n_entries from it and the per-sender header.
    # j = clip(prev, ws, ws+E) - ws == clip(prev - ws, 0, E): the latter form
    # bounds the offset *syntactically* (Pass E), where the subtract-after-clip
    # form only bounds it relationally.
    off_j = jnp.clip(prev_out - ws[:, None, :], 0, e)
    prev_out = ws[:, None, :] + off_j
    out_req_off = jnp.where(ae_edge, off_j, 0).astype(jnp.int8)
    if comp:
        out_req_off = jnp.where(snap_edge, jnp.int8(-1), out_req_off)
        wt = log_ops.window_rb(log_term_arr, ws, e)  # [N, E, B] shared window terms
        wv = log_ops.window_rb(log_val_arr, ws, e)
    else:
        wt = log_ops.window_b(log_term_arr, ws, e)
        wv = log_ops.window_b(log_val_arr, ws, e)
    n_ship = jnp.clip(log_len - ws, 0, e)  # [N, B]
    ship_used = send_append[:, None, :] & (iota((1, e, 1), 1) < n_ship[:, None, :])
    out_ent_term = jnp.where(ship_used, wt, 0)
    out_ent_val = jnp.where(ship_used, wv, 0)
    if track:
        wtk = (log_ops.window_rb if comp else log_ops.window_b)(log_tick_arr, ws, e)
        out_ent_tick = jnp.where(ship_used, wtk, 0)
    else:
        out_ent_tick = mb.ent_tick  # zeros, loop-invariant carry component
    if rcf:
        wcf = (log_ops.window_rb if comp else log_ops.window_b)(log_cfg_arr, ws, e)
        out_ent_cfg = jnp.where(ship_used, wcf, 0)
    else:
        out_ent_cfg = mb.ent_cfg  # zeros, loop-invariant carry component

    # Responses [receiver, responder]: the edge plane carries only the response
    # TYPE; payloads (grant target, ack target, match, hint, term) are per
    # responder (Mailbox response decode). The outbox is transpose-free and
    # broadcast-free: nothing [N, N]-shaped is written beyond the offset and
    # response-kind planes, both int8.
    out_resp_kind = (
        jnp.where(vr_out, RESP_VOTE, 0) + jnp.where(ar_out, RESP_APPEND, 0)
    ).astype(jnp.int8)
    if cfg.pre_vote:
        # The grant bit rides the packed pv_grant plane (raft.py phase 8).
        out_resp_kind = out_resp_kind + jnp.where(pv_out, RESP_PREVOTE, 0).astype(
            jnp.int8
        )
        if sh is None:
            out_pv_grant = bitplane.pack(pv_grant, axis=1)  # [cand, W(bit=voter), B]
        else:
            # Writer-major carry: the voter rows are local, candidates ride the
            # packed bits; _gather_mailbox reorients on read.
            out_pv_grant = bitplane.pack(jnp.swapaxes(pv_grant, 0, 1), axis=1)
    else:
        out_pv_grant = mb.pv_grant  # zeros, loop-invariant carry component
    if dur and cfg.durable_acks:
        # Late vote-completion response (phase 7.5 gate 2; raft.py for the
        # full argument and the AE-response collision guard).
        vfc = jnp.clip(voted_for, 0, n - 1)
        late_edge = (ids2[:, :, None] == vfc[None, :, :]) & late_grant[None, :, :]
        out_resp_kind = jnp.where(
            late_edge & (out_resp_kind == 0),
            jnp.int8(RESP_VOTE),
            out_resp_kind,
        )
    if comp:
        pterm = log_ops.term_at_rb(log_term_arr, base, bterm, ws)
    else:
        pterm = log_ops.term_at_b(log_term_arr, ws)

    new_mb = Mailbox(
        req_type=out_req_type,
        req_term=out_req_term,
        req_commit=jnp.where(send_append, commit, 0),
        req_last_index=jnp.where(rv_like, new_last_idx, 0),
        req_last_term=jnp.where(rv_like, new_last_term, 0),
        ent_start=jnp.where(send_append, ws.astype(jnp.int32), 0),
        ent_prev_term=jnp.where(send_append, pterm, 0),
        ent_count=jnp.where(send_append, n_ship, 0),
        ent_term=out_ent_term,
        ent_val=out_ent_val,
        ent_tick=out_ent_tick,
        # Without compaction the snapshot header is dead weight: pass the zeros
        # through untouched so XLA sees a loop-invariant carry component (raft.py).
        req_base=jnp.where(send_append, base, 0) if comp else mb.req_base,
        req_base_term=jnp.where(send_append, bterm, 0) if comp else mb.req_base_term,
        req_base_chk=(
            jnp.where(send_append, bchk, jnp.uint32(0)) if comp else mb.req_base_chk
        ),
        xfer_tgt=out_xfer_tgt,
        req_disrupt=out_req_disrupt,
        ent_cfg=out_ent_cfg,
        req_base_mold=(
            jnp.where(send_append[:, None, :], bmold, jnp.uint32(0))
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
        # Sharded carries are writer-major: the responder rows are local, so the
        # [resp-receiver, responder] plane is stored transposed (read path
        # reorients in _gather_mailbox).
        resp_kind=out_resp_kind if sh is None else jnp.swapaxes(out_resp_kind, 0, 1),
        pv_grant=out_pv_grant,
        v_to=grant_to,
        a_ok_to=out_a_ok_to,
        a_match=out_a_match,
        a_hint=out_a_hint,
        resp_term=term,
    )

    # Committed-prefix checksum, non-compaction form (log_ops module comment).
    if not comp:
        if cfg.check_invariants:
            chk_old, chk_new = log_ops.prefix_chk2_b(
                log_term_arr, log_val_arr, s.commit_index, commit
            )
            chk_ok = chk_old == s.commit_chk
        else:
            chk_new = s.commit_chk
            chk_ok = jnp.ones_like(s.commit_index, dtype=bool)

    # ---- end-of-tick config derivation (log-carried membership; raft.py) ---------
    if rcf:
        d_mold, d_mnew, d_pend, d_epoch, d_hi = cfglog.derive(
            cfg, log_cfg_arr, log_len, commit, base, bmold, bpend, bepoch,
            batched=True,
        )
        if not cfg.truncation_rollback:
            # TEST-ONLY mutant (ignore-truncation-rollback; raft.py).
            rolled = d_epoch < s.cfg_epoch
            d_mold = jnp.where(rolled[:, None, :], s.member_old, d_mold)
            d_mnew = jnp.where(rolled[:, None, :], s.member_new, d_mnew)
            d_pend = jnp.where(rolled, s.cfg_pend, d_pend)
            d_epoch = jnp.where(rolled, s.cfg_epoch, d_epoch)
        # Removed-server stepdown + candidacy kill (raft.py end-of-tick).
        self_in = jnp.any(((d_mold | d_mnew) & eye_p3) != 0, axis=1)  # [N, B]
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

    # Durability-lag reductions (host-constant zeros when the plane is off).
    if dur:
        lag = log_len - dur2_len  # [N, B] >= 0 (flush snaps to log_len)
        fsync_lag_sum = jnp.sum(lag, axis=0).astype(jnp.int32)
        fsync_lag_max = jnp.max(lag, axis=0).astype(jnp.int32)
    else:
        fsync_lag_sum = np.zeros((b,), np.int32)
        fsync_lag_max = np.zeros((b,), np.int32)

    info = _step_info_b(
        cfg, s, new_state, req_in, resp_in, inp.alive, cmds_cnt, chk_ok,
        lat_sum, lat_cnt, lat_hist, lat_excluded, noop_blocked,
        reads_served, read_lat_sum, read_hist, viol_read_stale,
        fsync_lag_sum, fsync_lag_max, sh,
    )
    return new_state, info


def _step_info_b(
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
    sh: NodeShardCtx | None = None,
) -> StepInfo:
    """Batched phase 9; see raft._step_info. All outputs [B]."""
    n = cfg.n_nodes
    b = new.role.shape[-1]
    iota = log_ops.iota
    is_leader = new.role == LEADER
    live_leader = is_leader & alive  # see raft._step_info: leadership metrics are live-only
    f = jnp.zeros((b,), bool)
    if sh is None:
        eye3 = iota((n, n, 1), 0) == iota((n, n, 1), 1)
        ids1 = iota((n, 1), 0)
        gmax = gmin = gsum = gany = lambda x: x  # node-axis folds: already local
    else:
        ids1 = sh.row0 + iota((sh.nl, 1), 0)
        gmax = lambda x: lax.pmax(x, sh.axis)
        gmin = lambda x: lax.pmin(x, sh.axis)
        gsum = lambda x: lax.psum(x, sh.axis)
        gany = lambda x: lax.psum(x.astype(jnp.int32), sh.axis) > 0

    if cfg.check_invariants:
        if sh is None:
            pair_bad = (
                is_leader[:, None, :]
                & is_leader[None, :, :]
                & (new.term[:, None, :] == new.term[None, :, :])
                & ~eye3
            )
        else:
            # One extra [n_pad, B] gather: leaders encoded by term (terms start
            # at 1, so 0 reads as non-leader; pad rows never lead). Tiny next
            # to the mailbox gather, and only paid when invariants are on.
            lv = lax.all_gather(
                jnp.where(is_leader, new.term, 0), sh.axis, axis=0, tiled=True
            )  # [n_pad, B]
            pair_bad = (
                (lv[:, None, :] > 0)
                & (lv[:, None, :] == lv[None, :, :])
                & ~(
                    iota((sh.n_pad, sh.n_pad, 1), 0)
                    == iota((sh.n_pad, sh.n_pad, 1), 1)
                )
            )
        viol_election = jnp.any(pair_bad, axis=(0, 1))
        # Committed-prefix immutability via the carried checksum (raft._step_info),
        # plus the compaction bounds (base <= commit, retained window <= CAP).
        viol_commit = gany(
            jnp.any(
                (new.commit_index < old.commit_index)
                | (new.commit_index > new.log_len)
                | (new.commit_index < new.log_base)
                | (new.log_len - new.log_base > cfg.log_capacity)
                | ~chk_ok,
                axis=0,
            )
        )
    else:
        viol_election = f
        viol_commit = f

    if cfg.check_log_matching:

        def _check(_):
            minc = jnp.minimum(
                new.commit_index[:, None, :], new.commit_index[None, :, :]
            )
            differ = (new.log_term[:, None] != new.log_term[None, :]) | (
                new.log_val[:, None] != new.log_val[None, :]
            )  # [N, N, CAP, B]
            if not cfg.compaction:
                both = iota((1, 1, cfg.log_capacity, 1), 2) < minc[:, :, None, :]
                return jnp.any(both & differ, axis=(0, 1, 2)), jnp.zeros_like(new.now)
            # Ring form (see raft._step_info): slots live in BOTH rings over
            # (max base, min commit] compare directly; the shared prefix below
            # max(base_i, base_j) compares via checksums-at-mb; incomparable
            # pairs are counted (lm_skipped_pairs).
            cap = cfg.log_capacity
            bb = new.log_base  # [N, B]
            sl = iota((1, cap, 1), 1)
            abs0 = bb[:, None, :] + (sl - bb[:, None, :]) % cap  # [N, CAP, B]
            mb_ = jnp.maximum(bb[:, None, :], bb[None, :, :])  # [N, N, B]
            comparable = minc >= mb_
            in_i = (abs0[:, None, :, :] >= mb_[:, :, None, :]) & (
                abs0[:, None, :, :] < minc[:, :, None, :]
            )
            in_j = (abs0[None, :, :, :] >= mb_[:, :, None, :]) & (
                abs0[None, :, :, :] < minc[:, :, None, :]
            )
            viol_suffix = jnp.any(
                comparable[:, :, None, :] & in_i & in_j & differ, axis=(0, 1, 2)
            )
            w_t, w_v = log_ops.chk_weights_at(abs0)
            contrib = (
                new.log_term.astype(jnp.uint32) * w_t
                + new.log_val.astype(jnp.uint32) * w_v
            )  # [N, CAP, B]
            chk_at_mb = new.base_chk[:, None, :] + jnp.sum(
                jnp.where(
                    abs0[:, None, :, :] < mb_[:, :, None, :],
                    contrib[:, None, :, :],
                    jnp.uint32(0),
                ),
                axis=2,
                dtype=jnp.uint32,
            )  # [N(i), N(j), B]
            viol_prefix = jnp.any(
                comparable & (chk_at_mb != jnp.swapaxes(chk_at_mb, 0, 1)), axis=(0, 1)
            )
            skipped = (
                jnp.sum(~comparable & ~eye3, axis=(0, 1)) // 2
            ).astype(jnp.int32)
            return viol_suffix | viol_prefix, skipped

        if cfg.log_matching_interval == 1:
            viol_match, lm_skipped = _check(None)
        else:
            # Lockstep cadence: now[0] is the whole batch's tick (config.py), a
            # scalar pred, so lax.cond skips the check entirely off-cadence.
            viol_match, lm_skipped = jax.lax.cond(
                new.now.reshape(-1)[0] % cfg.log_matching_interval == 0,
                _check,
                lambda _: (f, jnp.zeros_like(new.now)),
                None,
            )
    else:
        viol_match, lm_skipped = f, jnp.zeros_like(new.now)

    leader = gmin(jnp.min(jnp.where(live_leader, ids1, n), axis=0))  # [B]
    if sh is None:
        min_commit = jnp.min(new.commit_index, axis=0)
    else:
        # Pad rows sit at commit 0 forever; mask them to the max-int sentinel
        # (a live row always exists, so the sentinel never wins).
        min_commit = gmin(
            jnp.min(
                jnp.where(ids1 < n, new.commit_index, jnp.int32(2**31 - 1)), axis=0
            )
        )
    return StepInfo(
        viol_election_safety=viol_election,
        viol_commit=viol_commit,
        viol_log_matching=viol_match,
        leader=jnp.where(leader < n, leader, NIL).astype(jnp.int32),
        n_leaders=gsum(jnp.sum(live_leader, axis=0).astype(jnp.int32)),
        max_term=gmax(jnp.max(new.term, axis=0)),
        max_commit=gmax(jnp.max(new.commit_index, axis=0)),
        min_commit=min_commit,
        msgs_delivered=gsum(
            (jnp.sum(req_in, axis=(0, 1)) + jnp.sum(resp_in, axis=(0, 1))).astype(
                jnp.int32
            )
        ),
        cmds_injected=cmds_cnt,  # offers accepted, not appends; see raft.py phase 6
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
