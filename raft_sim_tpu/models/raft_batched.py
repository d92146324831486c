"""The Raft tick kernel: one pure state transition per simulated tick, for B
clusters at once.

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

Layout: the batch axis B is MINOR on every array ([N, B], [N, N, B], [N, CAP, B]),
so B rides the 128-wide lane tile and padding is bounded by the second-minor dim
(N or E or CAP -> at most 8/5). With the batch LEADING ([B, N, ...]) each array's
two minor dims would be (N, N) or (N, CAP); TPU tiles the two minor dims to
(8, 128), so a [B, 5, 5] int32 array would physically occupy ~40x its logical bytes
and every tick would be HBM-bound on padding (measured ~700KB moved per
cluster-tick vs ~3KB of logical state). `to_batch_minor`/`from_batch_minor`
transpose once per run; `step_one` steps one cluster in the public layout.
tests/test_oracle_parity.py and tests/test_batched_parity.py hold this kernel to
the scalar oracle (tests/oracle.py) tick by tick.

TRACE DELTA CONTRACT (raft_sim_tpu/trace, cfg.track_trace): the protocol
trace plane derives discrete events from this kernel's state DELTAS --
role, term, voted_for, commit_index, log_len, dur_len, and (reconfiguration
plane) cfg_epoch, log_cfg, xfer_to, read_idx -- outside the kernel (one
extractor serves any step_fn override too; zero step lowerings added).
Phase-order properties load-bearing for the whole-history checker, which must
survive refactors: (1) a node that loses leadership and accepts entries in
one tick changes `role` in the SAME tick as `log_len` (phase 1 adoption
precedes phase 3 append -- the checker replays role changes before log
changes); (2) a win (phase 4) can never co-occur with an AE-accept
truncation on the same node (a candidate that accepted a current-term AE
stepped down in phase 3 and cannot win); (3) elections precede the
end-of-tick config derivation, so EV_LEADER events belong to the TICK-START
per-node configuration (EV_CFG_APPLY/ROLLBACK replay after the role
kinds); (4) a read slot dropped while its holder stays a same-term
un-restarted leader was SERVED -- every cancel path changes role/term or
sets `restarted` (phase 5.2's clear rules); (5) a `dur_len` ADVANCE is
always a completed flush (EV_FSYNC: the only writer besides recovery is
phase 7.5, and recovery never raises it), and a `log_len` DROP on a
`restarted` node is always the recovery truncation (EV_RECOVER_TRUNC:
restarted nodes receive nothing, so the AE conflict truncation cannot
co-occur on them). See trace/events.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_sim_tpu.models import cfglog
from raft_sim_tpu.obs.scopes import PhaseScopes
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


def step_one(
    cfg: RaftConfig, s: ClusterState, inp: StepInputs
) -> tuple[ClusterState, StepInfo]:
    """`step_b` for ONE cluster in the public layout: state and inputs with
    no batch axis in, (state, StepInfo) with none out. A trailing batch axis
    of 1 is put on and dropped again (`x[..., 0]` keeps host-constant
    StepInfo leaves numpy)."""
    s2, info = step_b(cfg, *jax.tree.map(lambda x: x[..., None], (s, inp)))
    return jax.tree.map(lambda x: x[..., 0], (s2, info))


def offer_latency_b(log_tick, lead, commit, base, frontier, now, ids2, n,
                    gsum, gmax, gmin):
    """The tick's offer->commit latency accounting (client workloads only):
    StepInfo's lat_sum, lat_cnt, lat_hist [BINS, B] and lat_excluded, the
    carried lat_frontier, and StepInfo.lat_ring_ticks.

    Each client entry's offer stamp rides the log_tick plane (phase 6 writes
    it at injection; AE replication carries it via Mailbox.ent_tick), so the
    live leader's commit advancement this tick contributes (now - offer_tick)
    per newly committed client entry -- the measurement the reference's
    commit watch was meant to feed (log.clj:83-87, never fired, bug 2.3.9).
    VALUES are never read here: payloads are arbitrary int32, so a value
    colliding with a tick cannot corrupt the histogram. Called after phase 5
    and before compaction/injection can touch slots (the checksum pass's
    aliasing rule); on a prefix log of more than one tile, after the
    injection, which writes above every commit (_step_b).

    log_tick [N, CAP, B] offer stamps; lead [N, B] live leaders; commit [N, B];
    base [N, B] log_base, or None without compaction; frontier, now [B];
    ids2 [N, 1] global node ids of the rows (n is the no-node sentinel); the
    g* folds reduce over the node axis across shards (identity unsharded,
    as in _step_b).

    Only entries in (frontier, max commit] can count, and each tick that
    span is the cluster's max-commit advance. A ring of one tile
    (CAP <= log_ops._TILE) is swept whole. On a longer ring the counts run
    over the tile that holds entry frontier + 1, extended to cover the
    log_ops.LAT_WINDOW entries above the frontier (log_ops.ring_tile), on
    every tick where each cluster's advance fits them; under lax.cond, the
    ticks where one does not sweep the whole ring, and lat_ring_ticks is 1
    on those, 0 otherwise. The advance is node-folded, so every shard of a
    node-sharded program takes the same branch. Both forms equal the
    per-slot form tests/test_latency_accounting.py keeps as the reference,
    given the ring's invariant commit <= base + CAP."""
    cap, b = log_tick.shape[1], log_tick.shape[-1]
    counts = functools.partial(
        _latency_counts, lead=lead, commit=commit, now=now, ids2=ids2, n=n,
        gsum=gsum, gmax=gmax, gmin=gmin,
    )

    def whole(log_tick):
        # A slot's ring offset from the base slot, without a per-slot modulo
        # (base >= 0): its absolute index is abs0 + off + 1.
        sl = log_ops.iota((1, cap, 1), 1)
        if base is None:
            off, abs0 = sl, 0
        else:
            off = sl - (base % cap)[:, None, :]
            off = jnp.where(off < 0, off + cap, off)
            abs0 = base
        # Dedup across leader changes AND restarts: a freshly elected
        # leader's own commit trails the cluster's prior frontier and would
        # re-count entries its predecessor already reported, so only entries
        # above the CARRIED monotone frontier contribute (the per-node commit
        # vector is restart-mutable -- ClusterState.lat_frontier). Compared
        # in offset space: abs1 > frontier <=> off > frontier - abs0 - 1.
        lo = frontier[None, :] - abs0 - 1  # [N or 1, B]
        newly = (off > lo[:, None, :]) & (off <= (commit - abs0 - 1)[:, None, :])
        return counts(log_tick, newly)

    def window(log_tick):
        # The ring's tile that holds entry frontier + 1 (slot frontier mod
        # CAP in either layout), extended to cover LAT_WINDOW entries from
        # it: tile slot j holds entry frontier + 1 - r + j wherever that
        # entry is live on the row -- in the ring, above the row's base; in
        # the prefix layout, at or below CAP, which commit bounds. Counted
        # as it lies: no reduction aligns the window.
        lt, r = log_ops.ring_tile(log_tick, frontier[None, :], log_ops.LAT_WINDOW)
        abs1 = (frontier + 1 - r)[:, None, :] + log_ops.iota((1, lt.shape[1], 1), 1)
        newly = (abs1 > frontier[None, None, :]) & (abs1 <= commit[:, None, :])
        if base is not None:
            newly = newly & (abs1 > base[:, None, :])
        return counts(lt, newly)

    if cap <= log_ops._TILE:
        out, ring_ticks = whole(log_tick), np.zeros((b,), np.int32)
    else:
        adv = gmax(jnp.max(commit, axis=0)) - frontier  # [B]
        fits = jnp.max(adv) <= log_ops.LAT_WINDOW
        out = lax.cond(fits, window, whole, log_tick)
        ring_ticks = jnp.broadcast_to(~fits, (b,)).astype(jnp.int32)
    lat_frontier = jnp.maximum(frontier, gmax(jnp.max(commit, axis=0)))
    return (*out, lat_frontier, ring_ticks)


def _latency_counts(lt, newly, lead, commit, now, ids2, n, gsum, gmax, gmin):
    """offer_latency_b's counts over stamps `lt` [N, S, B] and their
    newly-committed mask [N, S, B] (S slots: the whole ring or the window):
    (lat_sum, lat_cnt, lat_hist, lat_excluded). Histogram from threshold
    counts, not from a per-slot bin index."""
    # Stamps are offer tick + 1, always in (0, now] at commit time; slots
    # holding no client entry (no-ops, unwritten) carry stamp 0 and fall out
    # of `cli`.
    cli = (lt >= 1) & (lt <= now[None, None, :])
    lm = lead[:, None, :] & newly & cli
    lats = jnp.where(lm, (now + 1)[None, None, :] - lt, 0)  # [N, S, B]
    lat_sum = gsum(jnp.sum(lats, axis=(0, 1)).astype(jnp.int32))
    # Histogram by threshold counts: floor(log2(l)) >= k <=> l >= 2^k, and
    # l >= 1 exactly on lm, so ge[k] counts the entries of bin k or above
    # (ge[0] = lat_cnt) and the last bin keeps log2_bin's clamp.
    ge = [
        jnp.sum(lats >= (1 << k), axis=(0, 1)).astype(jnp.int32)  # [B]
        for k in range(LAT_HIST_BINS)
    ]
    lat_cnt = gsum(ge[0])
    lat_hist = gsum(jnp.stack([a - b for a, b in zip(ge, ge[1:])] + [ge[-1]]))
    # Coverage gap counter (StepInfo.lat_excluded): client entries the
    # frontier advance crosses without attribution. The frontier advances
    # to max(commit) regardless of leadership; count the crossed client
    # entries on the (lowest-id) node HOLDING that max -- its log carries
    # everything in (frontier, max commit] by log matching -- and subtract
    # what lat_cnt attributed. Clamped at zero: under compaction the
    # max-commit node may have compacted a crossed slot the leader still
    # counted, and split-brain double-counts inflate lat_cnt.
    is_maxc = commit == gmax(jnp.max(commit, axis=0))[None, :]
    hnode = gmin(jnp.min(jnp.where(is_maxc, ids2, n), axis=0))  # [B]
    crossed = (ids2 == hnode[None, :])[:, None, :] & newly & cli
    lat_excluded = jnp.maximum(
        gsum(jnp.sum(crossed, axis=(0, 1)).astype(jnp.int32)) - lat_cnt, 0
    )
    return lat_sum, lat_cnt, lat_hist, lat_excluded


def step_b(
    cfg: RaftConfig, s: ClusterState, inp: StepInputs, sh: NodeShardCtx | None = None
) -> tuple[ClusterState, StepInfo]:
    """One tick for B clusters at once; every array carries a trailing batch axis.
    Pure; jit/scan-safe. The module docstring has the phase table and the
    TRACE DELTA CONTRACT, which tests/test_trace.py pins by re-deriving the
    chunked path's device events from `scan.run`'s stacked states.

    Under cfg.compact_planes the carry arrives in the compacted layout
    (ops/tile.py: per-edge value planes bit-packed into flat uint32 legs,
    word/window planes flattened; trailing batch axes ride along); this
    boundary unpacks to the dense working view, runs the identical dense
    tick, and repacks -- gated-off mailbox legs are passed through verbatim
    (`reuse`) so the carry-passthrough contract holds exactly as in the
    dense layout. Trajectories are bit-identical either way
    (tests/test_tile.py).

    The dense tick's phases run under the `tick.<phase>` named scopes of
    obs/scopes.py (op metadata only: the lowered program is unchanged).
    """
    with PhaseScopes() as phase:
        if not cfg.compact_planes:
            return _step_b(cfg, s, inp, sh, phase)
        assert sh is None  # sharded carries run dense (parallel/nodeshard.py)
        from raft_sim_tpu.ops import tile

        s2, info = _step_b(
            cfg, tile.unpack_state(cfg, s), tile.unpack_inputs(cfg, inp), None, phase
        )
    return tile.pack_state(cfg, s2, reuse=s), info


def _step_b(
    cfg: RaftConfig,
    s: ClusterState,
    inp: StepInputs,
    sh: NodeShardCtx | None,
    phase: PhaseScopes,
) -> tuple[ClusterState, StepInfo]:
    """The dense batch-minor tick body (layout-independent semantics).

    `phase(name)` opens the named scope of each phase group as the body
    reaches it (obs/scopes.py has the table); the body closes the last.

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

    phase("deliver")
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
    # here (before `s` is handed to phase 9 as the old state) keeps the
    # monotonic-commit invariant meaningful.
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
        phase("storage")
        # Crash recovery (storage/plane.recover, elementwise, so the [N, B]
        # orientation broadcasts through): the disk holds the fsynced prefix
        # for sure plus whatever un-fsynced tail the in-flight writes
        # reached, minus the torn tail the recovery checksum rejects
        # (inp.torn_drop, drawn every tick, consumed only here);
        # term/votedFor rewind to the durable snapshot.
        r_term, r_vote, r_len = storage_plane.recover(
            cfg, rs, inp.torn_drop,
            s.dur_len, s.dur_term, s.dur_vote,
            s.term, s.voted_for, s.log_len,
        )
        # Entries the restarts dropped (r_len is s.log_len off restarts).
        torn_lost = jnp.sum(s.log_len - r_len, axis=0).astype(jnp.int32)
        s = s._replace(term=r_term, voted_for=r_vote, log_len=r_len)
        phase("deliver")
    else:
        torn_lost = np.zeros((b,), np.int32)
    if cfg.pre_vote or rdl or rcf:
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
        # Snapshot config context (compaction x reconfig; constant full-row /
        # zero legs otherwise -- carried untouched when comp is off).
        bmold, bpend, bepoch = s.base_mold, s.base_pend, s.base_epoch

    # Reconfiguration plane (cfg.reconfig): log-carried, PER-NODE
    # configuration masking. member_old/member_new/cfg_pend are each node's
    # DERIVED view of its own log prefix ([N, W, B]; ClusterState docstring;
    # the end-of-tick block recomputes them via models/cfglog.py), so every
    # quorum test below masks by the TESTING NODE's own rows -- dual
    # (majorities of BOTH configurations) while that node's prefix holds an
    # uncompleted joint entry. Quorum tests read the TICK-START derivation;
    # entries appended this tick govern the next (apply-on-append at tick
    # granularity, the same one-tick rule every phase transition follows).
    if rcf:
        m_old, m_new = s.member_old, s.member_new  # [N, W, B]
        joint = s.cfg_pend > 0  # [N, B]
        maj_old = bitplane.count(m_old, axis=1) // 2 + 1  # [N, B]
        maj_new = bitplane.count(m_new, axis=1) // 2 + 1
        # Node i's own-membership bit: is i a voter of ITS OWN config union?
        # A node whose log carries its removal quiesces (never campaigns);
        # one whose log MISSES the removal still thinks it votes -- the
        # removed-server disruption the 4.2.3 denial below defends against.
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
        heard_recent = (
            (s.clock + inp.skew) - s.heard_clock < cfg.election_min_ticks
        )  # [N, B]
        if xfr:
            rv_denied = (
                heard_recent[None, :, :] & ~(mb.req_disrupt != 0)[:, None, :]
            )
        else:
            rv_denied = jnp.broadcast_to(heard_recent[None, :, :], (n, n, b))

    # ---- phase 1: term adoption --------------------------------------------------
    # Spec: any RPC (request or response) with term T > currentTerm -> set
    # currentTerm = T, convert to follower. The reference does this for responses
    # (core.clj:129-130, 144-145) but not vote requests (bug 2.3.2). A PreVote
    # request's term is PROSPECTIVE (thesis 9.6) -- it must never be adopted.
    if cfg.pre_vote:
        term_req = req_in & (mb.req_type != REQ_PREVOTE)[:, None, :]
    else:
        term_req = req_in
    if rcf:
        # 4.2.3 in full: a denied RequestVote is not PROCESSED -- its term is
        # not adopted either, so a removed server's inflated term cannot
        # depose a live leader through its own voters (the disruption
        # defense; under rdl alone the grant-only denial is kept -- adoption
        # stays legal there).
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

    phase("elect")
    # ---- phase 2: RequestVote requests (request-vote-handler, core.clj:91-103) ----
    is_rv = req_in & (mb.req_type == REQ_VOTE)[:, None, :]  # [candidate, voter, B]
    cur_rv = is_rv & (mb.req_term[:, None, :] == term[None, :, :])  # stale terms denied
    # Spec 5.4.1 up-to-date check (the reference's compare-prev? log.clj:55-59 compares
    # against the commit index and whole entry maps -- bugs 2.3.3/2.3.4).
    up_to_date = (mb.req_last_term[:, None, :] > my_last_term[None, :, :]) | (
        (mb.req_last_term[:, None, :] == my_last_term[None, :, :])
        & (mb.req_last_index[:, None, :] >= my_last_idx[None, :, :])
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
    lowest = jnp.min(jnp.where(can_grant, snd_ids, n), axis=0)  # [N, B]
    # Boolean arithmetic instead of where-on-bools: Mosaic cannot lower vector
    # selects with i1 operands.
    has_vote = (voted_for != NIL)[None, :, :]
    grant = (has_vote & can_grant & (snd_ids == voted_for[None, :, :])) | (
        ~has_vote & can_grant & (snd_ids == lowest[None, :, :])
    )
    granted_any = jnp.any(grant, axis=0)  # [N, B]
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
    grant_to = jnp.where(granted_any, voted_for, NIL).astype(node_dtype(cfg))  # [N, B]

    phase("replicate")
    # ---- phase 3: AppendEntries requests (append-entries-handler, core.clj:105-123) --
    is_ae = req_in & (mb.req_type == REQ_APPEND)[:, None, :]  # [leader, follower, B]
    cur_ae = is_ae & (mb.req_term[:, None, :] == term[None, :, :])
    # Election safety gives at most one leader per term, so at most one current-term AE
    # sender exists; pick the lowest id defensively (ties indicate a safety violation,
    # which phase 9 flags).
    ae_src = jnp.min(jnp.where(cur_ae, snd_ids, n), axis=0)  # [N, B]
    has_ae = ae_src < n
    sel = cur_ae & (snd_ids == ae_src[None, :, :])  # one-hot [sender, receiver, B]

    # Reconstruct the per-edge AE header from the selected sender's broadcast record
    # plus this edge's window offset j (Mailbox docstring). All selections are
    # one-hot sums (no gather); when no sender is selected everything is zeros and
    # gated by has_ae/ae_ok downstream.
    pick_h = lambda h: jnp.sum(jnp.where(sel, h[:, None, :], 0), axis=0)  # [N, B]
    j_in = jnp.sum(jnp.where(sel, mb.req_off, 0), axis=0).astype(jnp.int32)  # [N, B] in 0..E
    # InstallSnapshot analogue (compaction only): offset sentinel -1 means "install
    # my compaction base instead of entries" -- sent when this peer's next_index
    # fell below the leader's log_base (phase 8), the array form of Raft fig. 13.
    # The reference can never need this (its log is unbounded, core.clj:59-67).
    if comp:
        snap = has_ae & (j_in < 0)
        ae_norm = has_ae & ~snap
    else:
        snap = jnp.zeros_like(has_ae)
        ae_norm = has_ae
    # Well-formed mailboxes keep the one-hot sum in [-1, E]; the clip bounds the
    # fully-masked garbage lane (and routes snap's -1 to 0, gated by ae_norm).
    # Keeps prev_i provably within the idx dtype on wide-N tiers.
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
        prev_stored_term = log_ops.term_at_rb(s.log_term, base, bterm, prev_i)
        # prev below the local base is committed-and-compacted: it matches by
        # leader completeness (a current-term leader's log holds every committed
        # entry); at prev == base, term_at_rb yields base_term -- the snapshot
        # boundary check.
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

    # Conflict scan over the shipped window: first mismatching entry truncates the rest
    # of the log; matching prefixes are never truncated (spec 5.3 "delete the existing
    # entry and all that follow it").
    ks_e = iota((1, e, 1), 1)  # [1, E, 1]
    gidx0 = prev_i[:, None, :] + ks_e  # [N, E, B] 0-based entry indices
    if comp:
        # Skip entries the ring already compacted (abs index <= base) and accept
        # only what it can hold (entries past base + CAP would evict live,
        # un-compacted slots; the partial ack makes the leader retry the rest
        # after this node's own commit+compaction frees room).
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
        # Truncation makes the removed suffix non-durable AS LOG CONTENT: the
        # watermark clamps down with the log (the bytes may sit on disk, but
        # the durable-log contract is about the entries the recovery would
        # reconstruct, and those are gone). Appends do NOT advance it -- only
        # a completed flush does (phase 7.5).
        phase("storage")
        dur_mid = jnp.minimum(s.dur_len, log_len)
        phase("replicate")
    # The offer-stamp plane replicates with the entries it tags, and the
    # config-entry plane under the SAME masks (non-config entries ship 0, so
    # an accepted window scrubs any stale config command off the slots it
    # overwrites -- the rollback hazard the derivation depends on,
    # ClusterState.log_cfg docstring): neither can diverge from the value
    # plane's slot occupancy.
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
            # The snapshot carries its configuration context: the sender's
            # C_old/pending-toggle/entry-count at L, so the receiver's
            # derivation stays exact over config entries it never saw.
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
    # the payload is per responder -- at most one success target, one shared nack
    # hint (Mailbox docstring).
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

    phase("elect")
    # ---- phase 3.5: PreVote requests (thesis 9.6; cfg.pre_vote) ------------------
    # Grant iff the probe's prospective term is not behind us, the probing log is
    # up to date (the phase-2 check -- probes fill the same req_last_* header),
    # and we are QUIET: not a leader ourselves and no valid AppendEntries
    # accepted within the minimum election timeout (including this tick's).
    # Grants are non-binding: no votedFor, no term change, no timer reset.
    if cfg.pre_vote or rdl or rcf:
        # heard_clock serves the pre-vote quiet rule, the lease vote denial,
        # and the log-carried-config removed-server denial (phase 2) -- any
        # gate keeps the leg live.
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

    # ---- phase 3.7: TimeoutNow receipt (thesis 3.10; cfg.leader_transfer) --------
    # The transfer target starts a REAL election IMMEDIATELY: no timer, no
    # pre-vote probe (the thesis's explicit bypass -- the target is known
    # caught up, and the transferring leader's lease would make every voter
    # deny a probe). Gated on the request carrying the receiver's CURRENT
    # term, so a stale TimeoutNow from a deposed leader (or one that already
    # succeeded: the new leader's term moved past it) is inert. The election
    # itself fires in phase 7 alongside timer-driven starts.
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
            # TEST-ONLY mutant (cfg.xfer_election False): transfer as a coup.
            # The target assumes leadership DIRECTLY -- no vote round, no
            # up-to-date check -- so a behind target replicates its short log
            # over committed entries (the violation the hunt must re-find).
            coup = xfer_elect
            term = term + coup
            role = jnp.where(coup, LEADER, role)
            leader_id = jnp.where(coup, ids2, leader_id)
            xfer_elect = jnp.zeros_like(coup)
        else:
            coup = jnp.zeros_like(xfer_elect)

    phase("replicate")
    # ---- phase 4: responses ------------------------------------------------------
    # Vote tally (vote-response-handler core.clj:125-139; dedup via bitmap mirrors the
    # reference's set, core.clj:133-134). Granted = this responder's one grant
    # (v_to) names me (Mailbox response decode).
    vresp = resp_in & (mb.resp_kind == RESP_VOTE)
    new_votes = (
        vresp
        & (mb.v_to[None, :, :] == ids2[:, None, :])
        & (mb.resp_term[None, :, :] == term[:, None, :])
        & (role == CANDIDATE)[:, None, :]
    )
    votes = votes | bitplane.pack(new_votes, axis=1)
    # Packed-quorum test: word popcount over [N, W, B] instead of a bool-plane
    # sum over [N, N, B] (the bitplane module's reason to exist);
    # configuration-masked (dual during joint phases) when the reconfiguration
    # plane is live. A down candidate cannot assume leadership from votes
    # banked before it crashed.
    win = (role == CANDIDATE) & packed_quorum(votes) & inp.alive
    if rcf:
        win = win & member_b  # a removed node cannot win on banked votes
    if xfr and not cfg.xfer_election:
        win = win | coup  # mutant coups ride the fresh-leader bookkeeping
    role = jnp.where(win, LEADER, role)
    leader_id = jnp.where(win, ids2, leader_id)
    # Fresh leader bookkeeping (leader-state core.clj:40-42): nextIndex = last log
    # index + 1, matchIndex = 0.
    # Log indices are capacity-bounded (config caps log_capacity): the [N, N, B]
    # bookkeeping planes and their intermediates ride int8/int16, cutting their
    # HBM cost 4x/2x vs int32. Compaction carries absolute indices: int32
    # (types.index_dtype).
    len_i = log_len.astype(s.next_index.dtype)
    next_index = jnp.where(win[:, None, :], (len_i + 1)[:, None, :], s.next_index)
    match_index = jnp.where(win[:, None, :], 0, s.match_index)

    phase("elect")
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

    phase("replicate")  # phase 4's AppendEntries responses
    # Append responses (append-response-handler core.clj:141-149), leaders only, same
    # term. Success: match = acked index, next = match+1 (the reference sets next =
    # log-index, bug 2.3.10); failure: decrement next-index and retry (core.clj:146).
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
    ack_age = jnp.where(win[:, None, :] | aresp, 0, ack_age)

    phase("commit")
    # ---- phase 5: leader commit advancement (absent in reference, bug 2.3.8) ------
    is_leader = role == LEADER
    if dur and cfg.durable_acks:
        # Section-3.8 gate, leader self-match side: the leader's own log
        # counts toward commit only up to ITS durable watermark -- it is a
        # replica like any other, and commit means "on stable storage at a
        # quorum". Uses the pre-flush watermark (this tick's flush lands in
        # phase 7.5): one tick of lag, never a lie.
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
        # Configuration-masked quorum match under EACH LEADER's OWN derived
        # configuration: the largest replicated index v such that a majority
        # of that leader's member rows have match >= v. The quorum-th order
        # statistic of a multiset is an element of it, so candidates range
        # over the members' own match values (count form -- the member
        # majority is traced data, so a static sort-and-index form cannot
        # apply). While the leader's prefix is joint: the min over both its
        # configs (an index commits only when replicated to majorities of
        # BOTH).
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
    # Spec 5.4.2: only commit entries from the current term by counting replicas.
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
    # (Log-carried membership: there is no admin transition block. Joint
    # entry/exit are LOG APPENDS -- phase 6 originates them on the leader,
    # phase 3 replicates them -- and each node's effective configuration is
    # re-derived from its own prefix at end of tick (models/cfglog.py), which
    # is also where removed-leader stepdown and the truncation rollback live.)
    # Leadership-transfer bookkeeping (cfg.leader_transfer): abort a pending
    # transfer whose holder lost leadership or whose target went unresponsive
    # (ack_age horizon -- a dead target must not freeze the write path), then
    # accept a fresh transfer command at the lowest-id live leader. The
    # TimeoutNow itself fires from phase 8, re-fired each heartbeat while the
    # target stays caught up (a dropped fire retries).
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
    # ReadIndex lifecycle (cfg.read_index): bank this tick's AppendEntries
    # responses into the pending read's confirmation set (responses received
    # now were sent at or after the capture tick, so each proves the
    # responder was in the leader's term no earlier than capture -- the
    # staleness argument docs/PROTOCOL.md spells out), serve once a
    # configuration-aware majority confirms, then capture a fresh offer into
    # a free slot.
    if rdx:
        pend0 = s.read_idx > 0  # [N, B] pending at tick start
        keep_r = is_leader & pend0  # role loss / term adoption cancels
        read_acks = jnp.where(
            keep_r[:, None, :], s.read_acks | bitplane.pack(aresp, axis=1), zw
        )
        if cfg.read_confirm:
            serve = keep_r & inp.alive & packed_quorum(read_acks | eye_p3)
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
            fresh_p = bitplane.pack(ack_age <= lease_w, axis=1)  # [N, W, B]
            lease_ok = packed_quorum(fresh_p | eye_p3)
            if xfr:
                # Transfer handoff covers the read path: once a transfer
                # pends, the lease fast path stops -- the target's override
                # election (req_disrupt) bypasses the 4.2.3 denial the lease
                # bound leans on, so only reads served BEFORE the handoff
                # may lean on it (docs/PROTOCOL.md staleness argument).
                lease_ok = lease_ok & ~xfer_pend
            # Reads the lease serves that no confirmed round did this tick.
            by_lease = keep_r & inp.alive & lease_ok & ~serve
            lease_reads = jnp.sum(by_lease, axis=0).astype(jnp.int32)
            serve = serve | by_lease
        lat_r = jnp.maximum(s.now[None, :] + 1 - s.read_tick, 1)  # [N, B]
        reads_served = jnp.sum(serve, axis=0).astype(jnp.int32)
        read_lat_sum = jnp.sum(jnp.where(serve, lat_r, 0), axis=0).astype(jnp.int32)
        bin_r = log_ops.log2_bin(lat_r, LAT_HIST_BINS)
        oh_r = (
            iota((1, LAT_HIST_BINS, 1), 1) == bin_r[:, None, :]
        ) & serve[:, None, :]
        read_hist = jnp.sum(oh_r, axis=0).astype(jnp.int32)  # [BINS, B]
        # Capture: gated on the leader having committed a current-term entry
        # (thesis 6.4 -- a fresh leader's commit may trail the global
        # committed frontier until its own no-op/first entry commits, and a
        # read captured before that would legally miss committed writes).
        # One offer per cluster per tick: the lowest-id eligible leader.
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
            can_cap = can_cap & ~xfer_pend  # transferring leaders stop serving
        low_cap = jnp.min(jnp.where(can_cap, ids2, n), axis=0)  # [B]
        cap_r = can_cap & (ids2 == low_cap[None, :])
        cleared = serve | (pend0 & ~keep_r)
        read_idx = jnp.where(cap_r, commit + 1, jnp.where(cleared, 0, s.read_idx))
        read_tick = jnp.where(
            cap_r, s.now[None, :] + 1, jnp.where(cleared, 0, s.read_tick)
        )
        read_acks = jnp.where((cap_r | serve)[:, None, :], zw, read_acks)
        if rdl:
            # Staleness anchor: bank the committed frontier (lat_frontier
            # semantics, incl. this tick's phase-5 advance) at capture; a
            # SERVE whose captured index sits below its banked frontier
            # missed committed writes -- the checker's read_linearizability
            # property as a device invariant, so the hunt's fitness sees
            # lease violations. Exact, not conservative: a legitimate
            # (confirmed or leased) leader's capture covers the frontier by
            # the current-term-commit gate, so the real kernel never flags.
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
            lease_reads = np.zeros((b,), np.int32)
    else:
        # Constants, not jnp.zeros: a zeros op would land in the lowered
        # step program and break the zero-cost-when-off golden (byte-
        # identical op histograms with every gate off).
        reads_served = np.zeros((b,), np.int32)
        read_lat_sum = np.zeros((b,), np.int32)
        read_hist = np.zeros((LAT_HIST_BINS, b), np.int32)
        viol_read_stale = np.zeros((b,), np.bool_)
        lease_reads = np.zeros((b,), np.int32)

    # ---- offer->commit latency (client workloads only; offer_latency_b) ----------
    # On a prefix log of more than one tile the pass reads the stamp plane
    # after phase 6's injection (lat_late), which writes entry log_len + 1,
    # above every commit (commit <= log_len): no slot the pass counts
    # changes, and the plane between the AppendEntries write and the
    # injection is never stored. In the ring a slot this tick's compaction
    # frees can take the injection (the checksum pass's aliasing rule
    # below), so there, and on one tile, the pass reads the plane here.
    lat_late = track and not comp and cap > log_ops._TILE
    if track:
        lat_pass = functools.partial(
            offer_latency_b, lead=is_leader & inp.alive, commit=commit,
            base=base if comp else None, frontier=s.lat_frontier, now=s.now,
            ids2=ids2, n=n, gsum=gsum, gmax=gmax, gmin=gmin,
        )
        if not lat_late:
            (lat_sum, lat_cnt, lat_hist, lat_excluded, lat_frontier,
             lat_ring_ticks) = lat_pass(log_tick_arr)
    else:
        lat_sum = jnp.zeros_like(s.now)
        lat_cnt = jnp.zeros_like(s.now)
        lat_hist = jnp.zeros((LAT_HIST_BINS, b), jnp.int32)
        lat_excluded = jnp.zeros_like(s.now)
        lat_frontier = s.lat_frontier
        lat_ring_ticks = np.zeros((b,), np.int32)

    phase("compact")
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
        bterm = log_ops.term_at_rb(log_term_arr, base, bterm, base2)  # = bterm if unchanged
        if rcf:
            # Fold the compacted span's config entries into the snapshot
            # context (cfglog.fold_span; anchored at the PRE-advance base,
            # same aliasing rule as the checksum pass below -- must run
            # before phase 6 can reuse freed slots).
            phase("membership")
            bmold, bpend, bepoch = cfglog.fold_span(
                cfg, log_cfg_arr, base, base2, bmold, bpend, bepoch,
                batched=True,
            )
            phase("compact")
        base = base2

    # ---- committed-prefix checksum, compaction form -------------------------------
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
    # invariant CHECKING off -- only the chk_ok comparison is gated. The
    # non-compaction form has no aliasing hazard and stays at its post-outbox
    # position (placement affects XLA fusion of the hot configs).
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

    phase("client")
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
        noop_blocked = gsum(
            jnp.sum(win & ~(log_len - base < cap), axis=0).astype(jnp.int32)
        )
    else:
        noop = jnp.zeros_like(is_leader)
        room = log_len - base < cap
        noop_blocked = jnp.zeros_like(s.now)
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
            # TEST-ONLY mutant (single-server change, cfg.joint_consensus
            # False): one final-acting entry per change, no joint phase, no
            # completing entry -- the known-unsafe variant.
            cfg_code = jnp.where(accept_j, t_r[None, :] + 1, 0).astype(jnp.int32)
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
        kk3 = iota((kdim, 1, 1), 0)  # [K, 1, 1]
        free = s.client_pend == NIL  # [K, B]
        first_free = free & (jnp.cumsum(free, axis=0) == 1)
        fresh = (inp.client_cmd != NIL)[None, :] & first_free
        pend = jnp.where(fresh, inp.client_cmd[None, :], s.client_pend)  # [K, B]
        tgt = jnp.where(fresh, inp.client_target[None, :], s.client_dst)
        # Offer stamp rides the slot beside the payload: latency is measured
        # from the OFFER tick, and the bounces happen after it.
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
            # Transfer lease handoff (thesis 3.10): a transferring leader
            # stops accepting client commands until the transfer completes
            # or aborts.
            node_ok = node_ok & ~xfer_pend
        client_ok = (low_k < kdim) & node_ok  # [N, B] nodes accepting a slot
        sel_k = tgt_oh & (kk3 == low_k[None, :, :]) & node_ok[None, :, :]  # [K, N, B]
        wval_cl = jnp.sum(jnp.where(sel_k, pend[:, None, :], 0), axis=0)  # [N, B]
        wtick_cl = (
            jnp.sum(jnp.where(sel_k, ptick[:, None, :], 0), axis=0) if track else None
        )
        accepted_k = jnp.any(sel_k, axis=1)  # [K, B]
        # Distinct slots hold distinct offers: the count is exact (the direct
        # client's any() collapses split-brain double-accepts of ONE offer).
        cmds_cnt = jnp.sum(accepted_k, axis=0).astype(jnp.int32)  # [B]
        # Redirect still-pending slots: to the target's known leader when the
        # target is up and knows one, else to a random peer (core.clj:152-155).
        # A rejected POST at a full leader retries there next tick.
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
        # Direct mode accepts on the offer tick itself: stamp = now + 1 (the
        # same stamp the redirect pipeline records at slot entry).
        wtick_cl = (
            jnp.broadcast_to((s.now + 1)[None, :], (nl, b)) if track else None
        )
        # any(), not sum(): during a split-brain window two live leaders can
        # both accept the same offered command; that is ONE offer accepted, and
        # the offered-vs-committed audit counts offers.
        cmds_cnt = gany(jnp.any(client_ok, axis=0)).astype(jnp.int32)
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

    if lat_late:
        phase("commit")
        (lat_sum, lat_cnt, lat_hist, lat_excluded, lat_frontier,
         lat_ring_ticks) = lat_pass(log_tick_arr)

    phase("elect")
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
        votes = jnp.where(start_prevote[:, None, :], eye_p3, votes)
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
            # TimeoutNow election (~is_leader re-checked: the target may have
            # won an ordinary election in phase 4 this very tick).
            xe = xfer_elect & ~is_leader
            start_election = start_election | xe
        term = term + start_election
        role = jnp.where(start_election, CANDIDATE, role)
        voted_for = jnp.where(start_election, ids2, voted_for)
        leader_id = jnp.where(start_election, NIL, leader_id)
        votes = jnp.where(start_election[:, None, :], eye_p3, votes)
        deadline = jnp.where(start_election, clock + inp.timeout_draw, deadline)

    phase("outbox")
    # ---- phase 7.5: fsync flush + section-3.8 durability gates -------------------
    # The device-side fsync model (raft_sim_tpu/storage): a completed flush
    # (inp.fsync_fire -- the cadence tick minus the per-node latency-jitter
    # stall, sim/faults._storage_draws; a dead disk never flushes) snaps the
    # durable snapshot to the node's FINAL live state this tick -- the
    # post-injection log length and the post-election term/vote. Between
    # flushes the watermark carries (clamped by truncation, dur_mid above).
    if dur:
        phase("storage")
        fs_fire = inp.fsync_fire & inp.alive
        fsync_stalls = jnp.sum(inp.fsync_stall & inp.alive, axis=0).astype(jnp.int32)
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
            grant_to = jnp.where(covered2, voted_for, NIL).astype(
                node_dtype(cfg)
            )
            late_grant = covered2 & ~covered0 & ~granted_any
        phase("outbox")
    else:
        fsync_stalls = np.zeros((b,), np.int32)

    # ---- phase 8: outbox ---------------------------------------------------------
    send_append = win | heartbeat  # fresh leaders heartbeat immediately (core.clj:137-138)
    if comp:
        new_last_idx = log_len
        new_last_term = log_ops.term_at_rb(log_term_arr, base, bterm, log_len)
    else:
        new_last_idx, new_last_term = log_ops.last_index_term_b(log_term_arr, log_len)

    # Request headers are PER SENDER -- both RPCs are broadcasts (request-vote-rpc
    # core.clj:48-54, append-entries-rpc core.clj:56-67); the only per-edge request
    # datum is the AE window offset (Mailbox docstring).
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
        tgt_oh8 = iota((1, n, 1), 1) == jnp.clip(xfer_to, 0, n - 1)[:, None, :]
        t_match = jnp.sum(
            jnp.where(tgt_oh8, match_index, 0), axis=1, dtype=jnp.int32
        )
        if cfg.xfer_election:
            caught = t_match >= log_len
        else:
            # TEST-ONLY mutant: fire without the catch-up wait (the coup
            # receipt on the other side doesn't check the log either).
            caught = jnp.ones_like(log_len, bool)
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
    prev_out = jnp.clip(next_index - 1, 0, len_i[:, None, :])  # [src, dst, B]
    # Shared window start: minimum prev over RESPONSIVE peers (acked an AE within
    # ack_timeout_ticks). A peer that never acks -- crashed, partitioned away -- must
    # not pin the window, or no live follower could ever receive entries past
    # ws + E and commit would stall despite a live quorum. When no peer is
    # responsive (nothing to replicate to anyway) fall back to the min over all
    # peers. An unresponsive laggard's prev is clamped UP to ws below: spec-safe
    # (the consistency check at the too-high prev fails, it nacks, and that nack
    # both re-admits it to the responsive set and walks next_index back down).
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
        # Entries below the compaction base are gone: the window cannot start
        # before it, and peers whose prev falls below it get the InstallSnapshot
        # sentinel (req_off = -1) instead of a window offset.
        ws = jnp.maximum(ws, base)
        snap_edge = ae_edge & (prev_out < base[:, None, :])
    # Clamp each peer's prev into [ws, ws+E]: spec-safe in both directions (a peer
    # ahead of the window gets a plain heartbeat over an older prefix it already
    # has, its redundant ack absorbed by the monotone max() updates of match/next
    # in phase 4; an unresponsive laggard's prev is lifted to ws, its nack walks
    # next_index back down and re-admits it to the responsive set). The per-edge
    # request payload then reduces to the offset j = prev - ws in 0..E; receivers
    # reconstruct prev, prev_term, and n_entries from it and the per-sender header,
    # reading prev terms from the shared window instead of a CAP-wide one-hot per
    # edge.
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
    # Zero unused window slots so the mailbox is canonical (receivers mask with
    # the derived n_ent anyway, but a canonical wire format keeps trajectories
    # bit-comparable).
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

    # Responses: vr_out/ar_out are [request-sender, request-receiver], which IS the
    # response orientation [response-receiver, responder] (the reference's resp-chan
    # round trip, server.clj:59-60 -> client.clj:34-40); the edge plane carries only
    # the response TYPE; payloads (grant target, ack target, match, hint, term) are
    # per responder (Mailbox response decode). The outbox is transpose-free and
    # broadcast-free: nothing [N, N]-shaped is written beyond the offset and
    # response-kind planes, both int8.
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
        if sh is None:
            out_pv_grant = bitplane.pack(pv_grant, axis=1)  # [cand, W(bit=voter), B]
        else:
            # Writer-major carry: the voter rows are local, candidates ride the
            # packed bits; _gather_mailbox reorients on read.
            out_pv_grant = bitplane.pack(jnp.swapaxes(pv_grant, 0, 1), axis=1)
    else:
        out_pv_grant = mb.pv_grant  # zeros, loop-invariant carry component
    if dur and cfg.durable_acks:
        phase("storage")
        # Late vote-completion response (phase 7.5 gate 2): the flush that
        # just made this voter's grant durable emits the RESP_VOTE edge the
        # grant tick withheld -- toward the recorded candidate, only where
        # the edge carries no response already (a candidate that won
        # meanwhile is heartbeating us; its AE response outranks the vote it
        # no longer needs). v_to already names the candidate via covered2.
        vfc = jnp.clip(voted_for, 0, n - 1)
        late_edge = (ids2[:, :, None] == vfc[None, :, :]) & late_grant[None, :, :]
        late_sent = late_edge & (out_resp_kind == 0)
        late_grants = jnp.sum(late_sent, axis=(0, 1)).astype(jnp.int32)
        out_resp_kind = jnp.where(late_sent, jnp.int8(RESP_VOTE), out_resp_kind)
        phase("outbox")
    else:
        late_grants = np.zeros((b,), np.int32)
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
        phase("compact")
        if cfg.check_invariants:
            chk_old, chk_new = log_ops.prefix_chk2_b(
                log_term_arr, log_val_arr, s.commit_index, commit
            )
            chk_ok = chk_old == s.commit_chk
        else:
            chk_new = s.commit_chk
            chk_ok = jnp.ones_like(s.commit_index, dtype=bool)
        phase("outbox")

    # ---- end-of-tick config derivation (log-carried membership) ------------------
    # Each node's effective configuration recomputed from its post-append,
    # post-compaction log prefix (models/cfglog.py): apply-on-append and
    # roll-back-on-truncation are the SAME recomputation -- a truncated
    # config entry simply stops existing for the next tick's quorums.
    # base_mold/base_pend/base_epoch initialize to the boot config
    # (types.init_state) and are carried untouched without compaction, so
    # they are always the valid context at `base`.
    if rcf:
        phase("membership")
        d_mold, d_mnew, d_pend, d_epoch, d_hi = cfglog.derive(
            cfg, log_cfg_arr, log_len, commit, base, bmold, bpend, bepoch,
            batched=True,
        )
        if not cfg.truncation_rollback:
            # TEST-ONLY mutant (ignore-truncation-rollback): where the
            # prefix LOST config entries, keep acting on the stale carried
            # configuration -- the dissertation's rollback rule skipped.
            rolled = d_epoch < s.cfg_epoch
            d_mold = jnp.where(rolled[:, None, :], s.member_old, d_mold)
            d_mnew = jnp.where(rolled[:, None, :], s.member_new, d_mnew)
            d_pend = jnp.where(rolled, s.cfg_pend, d_pend)
            d_epoch = jnp.where(rolled, s.cfg_epoch, d_epoch)
        # Removed-server stepdown (thesis 4.3): a LEADER whose own config
        # union excludes it keeps leading -- replicating the very entry
        # that removes it -- until that entry commits on it, then steps
        # down (its log never counts toward masked quorums meanwhile: the
        # caretaker role). Candidacies of removed nodes die immediately.
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

    phase("check")
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
        lat_sum, lat_cnt, lat_hist, lat_excluded, lat_ring_ticks, noop_blocked,
        reads_served, read_lat_sum, read_hist, viol_read_stale,
        fsync_lag_sum, fsync_lag_max, lease_reads,
        fsync_stalls, torn_lost, late_grants, sh,
    )
    phase.close()
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
    lat_ring_ticks: jax.Array,
    noop_blocked: jax.Array,
    reads_served: jax.Array,
    read_lat_sum: jax.Array,
    read_hist: jax.Array,
    viol_read_stale: jax.Array,
    fsync_lag_sum: jax.Array,
    fsync_lag_max: jax.Array,
    lease_reads: jax.Array,
    fsync_stalls: jax.Array,
    torn_lost: jax.Array,
    late_grants: jax.Array,
    sh: NodeShardCtx | None = None,
) -> StepInfo:
    """Phase 9: on-device safety invariants + observability reductions. All
    outputs [B]."""
    n = cfg.n_nodes
    b = new.role.shape[-1]
    iota = log_ops.iota
    is_leader = new.role == LEADER
    # Observability counts only *live* leaders: a crashed node frozen in LEADER role
    # provides no leadership (the cluster is leaderless until re-election), and the
    # north-star ticks-to-stable-leader metric must reflect that. The safety checks
    # below keep the unmasked roles: a frozen stale leader still participates in the
    # at-most-one-leader-per-term invariant.
    live_leader = is_leader & alive
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
        # Election safety: at most one leader per term (Raft fig. 3).
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
        # Commit sanity: monotonic, within the log, above the compaction base (with
        # the retained window inside the ring), and the committed prefix is
        # immutable -- entries below the old commit index never change term OR value
        # (state-machine-safety analogue of the reference's apply-entries! writing
        # committed values to an append-only file, log.clj:69-76). Immutability is
        # checked via the carried prefix checksum (chk_ok; log_ops module comment).
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
            # Log matching on committed prefixes: any two nodes agree on every
            # entry (term AND value) up to m = min(commit_i, commit_j).
            # O(N^2 * CAP) -- gated, and sampled every log_matching_interval
            # ticks (below).
            minc = jnp.minimum(
                new.commit_index[:, None, :], new.commit_index[None, :, :]
            )
            differ = (new.log_term[:, None] != new.log_term[None, :]) | (
                new.log_val[:, None] != new.log_val[None, :]
            )  # [N, N, CAP, B]
            if not cfg.compaction:
                both = iota((1, 1, cfg.log_capacity, 1), 2) < minc[:, :, None, :]
                return jnp.any(both & differ, axis=(0, 1, 2)), jnp.zeros_like(new.now)
            # Ring form, in two parts per pair (i, j) with mb = max(base_i, base_j):
            # entries in (mb, m] are live in BOTH rings at the same slot (same
            # absolute index, same CAP) -> compare slots; the prefix up to mb is
            # compared via checksums-at-mb (chk_at(i, p) = base_chk_i + live sum
            # (base_i, p]), which is computable because mb >= base_i. Pairs where
            # one node compacted past the other's commit (m < mb) are skipped --
            # their agreement is pinned transitively through common peers -- and
            # COUNTED (StepInfo.lm_skipped_pairs) so the coverage is measured.
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
    # Plane counters, host zeros while their gate is off (the read metrics'
    # zero-cost contract): a node in a joint configuration at end of tick,
    # TimeoutNow requests sent this tick.
    if cfg.reconfig:
        joint_ticks = gany(jnp.any(new.cfg_pend > 0, axis=0)).astype(jnp.int32)
    else:
        joint_ticks = np.zeros((b,), np.int32)
    if cfg.leader_transfer:
        xfer_fired = gsum(
            jnp.sum(new.mailbox.xfer_tgt != NIL, axis=0).astype(jnp.int32)
        )
    else:
        xfer_fired = np.zeros((b,), np.int32)
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
        # Offers accepted this tick, not appends: the direct client collapses
        # split-brain double-accepts of one offer via any(); the redirect
        # pipeline counts accepted slots (distinct offers) -- see phase 6.
        cmds_injected=cmds_cnt,
        lat_sum=lat_sum,
        lat_cnt=lat_cnt,
        lat_hist=lat_hist,
        lat_excluded=lat_excluded,
        lat_ring_ticks=lat_ring_ticks,
        noop_blocked=noop_blocked,
        lm_skipped_pairs=lm_skipped,
        reads_served=reads_served,
        read_lat_sum=read_lat_sum,
        read_hist=read_hist,
        viol_read_stale=viol_read_stale,
        fsync_lag_sum=fsync_lag_sum,
        fsync_lag_max=fsync_lag_max,
        joint_ticks=joint_ticks,
        xfer_fired=xfer_fired,
        lease_reads=lease_reads,
        fsync_stalls=fsync_stalls,
        torn_lost=torn_lost,
        late_grants=late_grants,
    )
