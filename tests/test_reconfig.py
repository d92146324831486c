"""Reconfiguration plane: LOG-CARRIED membership change (models/cfglog.py),
TimeoutNow leadership transfer, and ReadIndex reads.

Kernel-vs-oracle bit-exactness for these extensions rides tests/
test_oracle_parity.py (the n5-reconfig-plane / n5-reconfig-truncation rows);
this file covers the protocol semantics the parity rows cannot state
directly: configuration-masked quorums at bitplane word boundaries, the
log-carried joint lifecycle (joint entry -> replicate -> commit -> final
entry -> removed-leader stepdown), per-node config DIVERGENCE and the
truncation ROLLBACK at word-boundary N, the disruptive-RequestVote transfer
override under the lease denial, the TEST-ONLY mutants' violations (and the
real kernel's cleanliness under the same programs), the checker's
unconditional election safety (EPOCH_EXEMPT_DISTANCE deleted), and the v24
checkpoint round trip + v23 migration error.

Program budget: the word-boundary and lifecycle tests drive single `step`
calls (tiny jit programs); the run-level tests share two small scan programs
and the mutant/checker tests two small windowed trace programs.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.scenario.mutation import mutant_config
from raft_sim_tpu.sim import scan, telemetry
from raft_sim_tpu.trace import checker as tchecker
from raft_sim_tpu.trace import events as tev
from raft_sim_tpu.trace import history as thistory
from raft_sim_tpu.trace.ring import TraceSpec
from raft_sim_tpu.types import CANDIDATE, FOLLOWER, LEADER, NIL, StepInputs
from raft_sim_tpu.utils import checkpoint
from raft_sim_tpu.utils.config import PRESETS


def _quiet_inputs(cfg: RaftConfig, **over) -> StepInputs:
    """No faults, no messages dropped, timers far in the future."""
    n = cfg.n_nodes
    far = 10_000
    base = dict(
        deliver_mask=bitplane.pack(jnp.ones((n, n), bool), axis=1),
        skew=jnp.ones((n,), jnp.int32),
        timeout_draw=jnp.full((n,), far, jnp.int32),
        client_cmd=jnp.int32(NIL),
        client_target=jnp.int32(0),
        client_bounce=jnp.zeros((cfg.client_pipeline,), jnp.int32),
        alive=jnp.ones((n,), bool),
        restarted=jnp.zeros((n,), bool),
    )
    base.update(over)
    return StepInputs(**base)


def _mask(n: int, members) -> jnp.ndarray:
    return bitplane.pack(
        jnp.asarray([i in members for i in range(n)], bool)
    )


def _mask_rows(n: int, members) -> jnp.ndarray:
    """Per-node derived-config rows ([N, W]): every node holding the same
    view -- the cache-injection helper for quorum-lattice tests (the
    end-of-tick derivation rebinds the cache from the log; quorum tests read
    the TICK-START values, which is what these tests pin)."""
    return jnp.broadcast_to(_mask(n, members), (n, bitplane.n_words(n)))


def _unpack_rows(words, n: int) -> np.ndarray:
    """[N, W] packed rows -> [N, N] bool."""
    return np.asarray(bitplane.unpack(words, n, axis=1))


# ----------------------------------- packed dual quorum at word boundaries


@pytest.mark.parametrize(
    "n",
    [
        5, 32,
        # Slow tier (870s budget): each N is a fresh ~8s step compile. Tier
        # 1 keeps the default width and the exact word crossing (32); the
        # boundary NEIGHBORS ride the slow tier since ISSUE 13 -- the
        # log-carried divergence test below re-pins the full 31/32/33
        # triplet on the SAME packed member rows (its derivation exercises
        # the identical word arithmetic), and test_bitplane pins the N=51
        # popcount itself.
        pytest.param(31, marks=pytest.mark.slow),
        pytest.param(33, marks=pytest.mark.slow),
        pytest.param(51, marks=pytest.mark.slow),
    ],
)
def test_joint_dual_quorum_at_word_boundaries(n):
    """While a candidate's OWN prefix is joint it needs majorities of BOTH
    its packed configurations. Exercised at the bitplane word boundaries
    (31/32/33 and the config5 width 51): one vote short of either majority
    loses, and a vote set that satisfies C_old via the to-be-removed node
    does NOT satisfy C_new."""
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000)
    removed = n - 1
    maj_old = n // 2 + 1
    maj_new = (n - 1) // 2 + 1

    def outcome(voters) -> bool:
        s = init_state(cfg, jax.random.key(0))
        s = s._replace(
            role=s.role.at[0].set(CANDIDATE),
            term=jnp.full((n,), 5, jnp.int32),
            voted_for=s.voted_for.at[0].set(0),
            votes=s.votes.at[0].set(_mask(n, set(voters))),
            member_new=_mask_rows(n, set(range(n)) - {removed}),
            cfg_pend=jnp.full((n,), 1000, jnp.int32),  # joint: exit far away
        )
        s2, _ = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))(
            s, _quiet_inputs(cfg)
        )
        return int(s2.role[0]) == LEADER

    need = max(maj_old, maj_new)
    assert outcome(range(need))  # both majorities met
    assert not outcome(range(need - 1))  # one short of the larger majority
    # C_old-majority via the removed node, but one short in C_new: the dual
    # test must refuse (a single-config kernel would elect -- the mutant).
    if maj_old == maj_new:
        tricky = list(range(maj_old - 1)) + [removed]
        assert not outcome(tricky)


def test_single_config_quorum_when_not_joint():
    """Outside a joint phase the masked quorum degenerates to the plain
    majority of the (single) current configuration."""
    n = 7
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000)
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[2].set(CANDIDATE),
        term=jnp.full((n,), 3, jnp.int32),
        voted_for=s.voted_for.at[2].set(2),
        votes=s.votes.at[2].set(_mask(n, {1, 2, 3, 4})),
    )
    s2, _ = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))(s, _quiet_inputs(cfg))
    assert int(s2.role[2]) == LEADER


# ------------------------------- log-carried joint lifecycle + stepdown


def test_log_carried_joint_lifecycle_and_removed_leader_stepdown():
    """The full thesis-4.3 cycle as LOG WRITES: the admin's toggle becomes a
    JOINT entry on the leader (applied to ITS config the same tick --
    divergence from the followers until replication), the FINAL entry
    auto-appends once the joint entry commits, and the removed leader leads
    THROUGH its own removal until the final entry commits on it, then steps
    down and never campaigns again."""
    n = 5
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000)
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        ack_age=jnp.zeros((n, n), s.ack_age.dtype),  # everyone responsive
        deadline=s.deadline.at[0].set(1),  # heartbeats start immediately
    )
    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    # Tick 1: the admin offers "toggle node 0" -> the leader appends the
    # joint entry and applies it ON APPEND: its own derived config goes
    # joint while every follower still derives the boot config (divergence).
    s, _ = step(s, _quiet_inputs(cfg, reconfig_cmd=jnp.int32(0)))
    assert int(s.log_len[0]) == 1 and int(s.log_cfg[0, 0]) == 1  # +(0+1)
    assert int(s.cfg_epoch[0]) == 1 and int(s.cfg_pend[0]) == 1
    assert not _unpack_rows(s.member_new, n)[0, 0]  # leader: 0 leaving C_new
    assert _unpack_rows(s.member_old, n)[0, 0]  # ...but still in C_old
    assert np.all(np.asarray(s.cfg_epoch)[1:] == 0)  # followers: not yet
    assert int(s.role[0]) == LEADER  # leads through its own removal
    # Drive heartbeat/replication ticks: the joint entry replicates (every
    # node applies on append), commits under the DUAL quorum, the FINAL
    # entry auto-appends and commits, and the removed leader steps down.
    saw_joint_everywhere = False
    for _ in range(12):
        s, _ = step(s, _quiet_inputs(cfg))
        ep = np.asarray(s.cfg_epoch)
        if np.all(ep >= 1) and not saw_joint_everywhere:
            saw_joint_everywhere = True
        if int(s.role[0]) == FOLLOWER:
            break
    assert saw_joint_everywhere
    assert int(s.role[0]) == FOLLOWER  # removed leader stepped down...
    assert int(s.log_cfg[0, 1]) == -1  # ...after appending the final entry
    assert int(s.commit_index[0]) >= 2  # which committed on it first
    mo = _unpack_rows(s.member_old, n)
    assert not mo[0, 0]  # node 0's own view: removed
    assert np.all(np.asarray(s.cfg_pend) == 0)  # joint phase closed
    # Quiescence: the removed node never campaigns again (phase-7 gate).
    for _ in range(4):
        s, info = step(s, _quiet_inputs(cfg))
        assert int(s.role[0]) == FOLLOWER
        assert not bool(info.viol_election_safety)


def test_reconfig_command_refused_while_joint_and_below_two_voters():
    """Origination refusals, judged on the leader's OWN tick-start derived
    config (cache-injected): a toggle is refused while the leader's prefix
    is already joint, and refused when it would leave C_new below 2
    voters."""
    n = 3
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000)
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        member_new=_mask_rows(n, {0, 1}),
        cfg_pend=jnp.full((n,), 1000, jnp.int32),  # joint pending, exit far
    )
    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    s2, _ = step(s, _quiet_inputs(cfg, reconfig_cmd=jnp.int32(1)))
    assert int(s2.log_len[0]) == 0  # refused: joint phase pending, no entry
    # Not joint, but the toggle would strand a single voter: refused.
    s3 = s._replace(
        cfg_pend=jnp.zeros((n,), jnp.int32),
        member_old=_mask_rows(n, {0, 1}),
    )
    s4, _ = step(s3, _quiet_inputs(cfg, reconfig_cmd=jnp.int32(1)))
    assert int(s4.log_len[0]) == 0
    assert np.all(np.asarray(s4.log_cfg) == 0)


# ----------------- per-node divergence + truncation rollback at boundaries


@pytest.mark.parametrize(
    "n",
    [
        5, 33,
        # Slow tier (budget re-tier, ISSUE 14): 31/32 straddle the same
        # 1->2-word boundary the tier-1 n=33 row crosses with the same
        # packed arithmetic (the dual-quorum test above re-pins the word
        # math per width), and 51 is the same arithmetic at W=2 -- each
        # param is a step-compile pair the 870s tier-1 budget cannot
        # absorb beside the ISSUE-14 layout tests.
        pytest.param(31, marks=pytest.mark.slow),
        pytest.param(32, marks=pytest.mark.slow),
        pytest.param(51, marks=pytest.mark.slow),
    ],
)
def test_config_divergence_and_truncation_rollback_at_word_boundaries(n):
    """The dissertation's rollback rule, deterministic, at bitplane word
    boundaries: an isolated node carries an uncommitted joint entry (its
    derived config goes joint -- DIVERGED from the majority), then the
    majority's leader overwrites that suffix and the node's config must ROLL
    BACK to the boot mask. Apply-on-append and roll-back-on-truncation are
    the same derivation (models/cfglog.py)."""
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000)
    x, v = n - 1, n - 1  # the isolated node; it toggles its own removal
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 3, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        ack_age=jnp.zeros((n, n), s.ack_age.dtype),
        # Leader 0: a term-3 client entry at index 1 (the overwriting log).
        log_term=s.log_term.at[0, 0].set(3),
        log_val=s.log_val.at[0, 0].set(77),
        log_len=s.log_len.at[0].set(1),
        # Node x: an uncommitted term-2 JOINT entry at index 1.
        deadline=s.deadline.at[0].set(2),  # heartbeat on tick 2
    )
    s = s._replace(
        log_term=s.log_term.at[x, 0].set(2),
        log_cfg=s.log_cfg.at[x, 0].set(v + 1),
        log_len=s.log_len.at[x].set(1),
    )
    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    # Tick 1 (no delivery): the end-of-tick derivation APPLIES x's entry --
    # per-node divergence: x joint and missing v from C_new, majority boot.
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.cfg_epoch[x]) == 1 and int(s.cfg_pend[x]) == 1
    mn = _unpack_rows(s.member_new, n)
    assert not mn[x, v] and mn[0, v]  # x's own view diverged from node 0's
    assert np.all(np.asarray(s.cfg_epoch)[:x] == 0)
    # Ticks 2-3: the leader's heartbeat ships its term-3 entry; x's prefix
    # mismatches at index 1 and is overwritten -- the config entry is GONE
    # and the derivation must roll x back to the boot config.
    s, _ = step(s, _quiet_inputs(cfg))
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.log_cfg[x, 0]) == 0  # scrubbed by the non-config overwrite
    assert int(s.cfg_epoch[x]) == 0 and int(s.cfg_pend[x]) == 0  # rollback
    mn2 = _unpack_rows(s.member_new, n)
    assert mn2[x, v]  # the prior mask is restored
    assert int(s.log_term[x, 0]) == 3 and int(s.log_val[x, 0]) == 77


# --------------------------------------------------- transfer lease + flow


def test_transfer_lease_blocks_writes_and_fires_timeout_now():
    """An accepted transfer parks on xfer_to, refuses client commands (the
    lease handoff), and fires REQ_TIMEOUT_NOW at the caught-up target on the
    leader's heartbeat tick."""
    from raft_sim_tpu.types import REQ_TIMEOUT_NOW

    n = 5
    cfg = RaftConfig(n_nodes=n, log_capacity=8, transfer_interval=1000,
                     client_interval=4)
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        ack_age=jnp.zeros((n, n), s.ack_age.dtype),  # everyone responsive
        deadline=s.deadline.at[0].set(1),  # heartbeat fires next tick
    )
    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    s, _ = step(s, _quiet_inputs(
        cfg, transfer_cmd=jnp.int32(3), client_cmd=jnp.int32(77)
    ))
    assert int(s.xfer_to[0]) == 3
    assert int(s.log_len[0]) == 0  # lease: the offered command was refused
    # Heartbeat tick: target matches (log empty), so the broadcast slot is
    # the TimeoutNow, not the heartbeat.
    assert int(s.mailbox.req_type[0]) == REQ_TIMEOUT_NOW
    assert int(s.mailbox.xfer_tgt[0]) == 3


def test_transfer_fires_and_elects_during_joint_phase():
    """A TimeoutNow transfer accepted, fired, received, and COMPLETED while
    a membership change is parked in its joint phase -- now LOG-BACKED: the
    joint entry sits uncommitted in every log (a current-term leader cannot
    commit the prior-term entry without new appends, thesis 3.6.2), so the
    joint phase stays open across the handoff. The target's bypass election
    runs under
    the DUAL quorum and the deposed old leader's pending transfer aborts on
    term adoption."""
    from raft_sim_tpu.types import REQ_TIMEOUT_NOW, REQ_VOTE

    n = 5
    cfg = RaftConfig(
        n_nodes=n, log_capacity=8, reconfig_interval=1000,
        transfer_interval=1000, client_interval=4,
    )
    s = init_state(cfg, jax.random.key(0))
    joint_code = 4 + 1  # joint entry toggling node 4 (removal)
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        ack_age=jnp.zeros((n, n), s.ack_age.dtype),  # everyone responsive
        deadline=s.deadline.at[0].set(1),  # heartbeat fires on tick 1
        # Joint entry in EVERY log, uncommitted AND uncommittable for now:
        # it carries the PRIOR term 1, so no term-2 (or term-3) leader can
        # commit it without a fresh entry on top (thesis 3.6.2's gate) --
        # the joint phase stays open across the whole handoff. The leader's
        # match bookkeeping covers it (the transfer's caught-up gate reads
        # match_index).
        log_term=s.log_term.at[:, 0].set(1),
        log_cfg=s.log_cfg.at[:, 0].set(joint_code),
        log_len=jnp.ones((n,), s.log_len.dtype),
        match_index=s.match_index.at[0, :].set(1),
        next_index=s.next_index.at[0, :].set(2),
        # The derived cache matching those logs (tick-start reads).
        member_new=_mask_rows(n, {0, 1, 2, 3}),
        cfg_pend=jnp.ones((n,), jnp.int32),
        cfg_epoch=jnp.ones((n,), jnp.int32),
    )
    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    # Tick 1: transfer to node 1 accepted WHILE joint; the heartbeat slot
    # carries the TimeoutNow (target trivially caught up: equal logs).
    s, _ = step(s, _quiet_inputs(cfg, transfer_cmd=jnp.int32(1)))
    assert int(s.xfer_to[0]) == 1 and np.all(np.asarray(s.cfg_pend) == 1)
    assert int(s.mailbox.req_type[0]) == REQ_TIMEOUT_NOW
    assert int(s.mailbox.xfer_tgt[0]) == 1
    # Tick 2: the target receives it at the current term and starts a REAL
    # election immediately -- term bump, self-vote, RequestVote broadcast
    # carrying the disruptive-override flag (thesis 3.10/4.2.3).
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[1]) == CANDIDATE and int(s.term[1]) == 3
    assert int(s.mailbox.req_type[1]) == REQ_VOTE
    assert int(s.mailbox.req_disrupt[1]) == 1
    # Tick 3: voters adopt term 3 and grant; the deposed old leader's
    # pending transfer aborts on adoption (volatile leader state).
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[0]) == FOLLOWER and int(s.term[0]) == 3
    assert int(s.xfer_to[0]) == NIL
    # Tick 4: the target banks a DUAL quorum (majorities of C_old AND C_new
    # -- all five granted here, covering both) and wins, with the joint
    # phase still open: leadership moved INSIDE the membership change.
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[1]) == LEADER
    assert np.all(np.asarray(s.cfg_pend) == 1)
    assert np.all(np.asarray(s.cfg_epoch) == 1)
    # One more quiet tick: no spurious final entry (the term-1 joint entry
    # cannot commit under the term-3 leader) and exactly one leader.
    s, info = step(s, _quiet_inputs(cfg))
    assert np.all(np.asarray(s.cfg_pend) == 1)
    assert int(info.n_leaders) == 1 and not bool(info.viol_election_safety)


def test_transfer_overrides_lease_denial_deterministically():
    """ISSUE-13 satellite: read_lease_ticks and TimeoutNow transfers now
    COEXIST (the PR-11 mutual-exclusion validator is gone). A transfer
    target's election broadcast carries req_disrupt, so voters inside their
    heard-a-leader denial window still process it and leadership moves; a
    plain timer election under the same conditions is denied."""
    from raft_sim_tpu.types import REQ_VOTE

    n = 5
    cfg = RaftConfig(
        n_nodes=n, log_capacity=8, client_interval=2, read_interval=3,
        election_min_ticks=12, election_range_ticks=6, read_lease_ticks=4,
        transfer_interval=1000,  # legal together now: no validator trip
    )

    def fresh_leader_state():
        s = init_state(cfg, jax.random.key(0))
        return s._replace(
            role=s.role.at[0].set(LEADER),
            term=jnp.full((n,), 2, jnp.int32),
            leader_id=jnp.zeros((n,), jnp.int32),
            ack_age=jnp.zeros((n, n), s.ack_age.dtype),
            # Every voter heard the leader JUST NOW: denial window armed.
            heard_clock=jnp.zeros((n,), jnp.int32),
            deadline=s.deadline.at[0].set(1),  # leader heartbeat tick 1
        )

    step = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))
    # Transfer path: accepted tick 1 (TimeoutNow fires), received tick 2
    # (override election, flag set), granted tick 3 DESPITE the armed
    # denial, won tick 4.
    s = fresh_leader_state()
    s, _ = step(s, _quiet_inputs(cfg, transfer_cmd=jnp.int32(2)))
    assert int(s.xfer_to[0]) == 2
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[2]) == CANDIDATE and int(s.term[2]) == 3
    assert int(s.mailbox.req_type[2]) == REQ_VOTE
    assert int(s.mailbox.req_disrupt[2]) == 1
    s, _ = step(s, _quiet_inputs(cfg))
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[2]) == LEADER and int(s.term[2]) == 3
    # Plain election under the same armed denial: a candidate without the
    # flag gathers NO grants (the 4.2.3 denial the lease leans on).
    s = fresh_leader_state()
    s = s._replace(
        role=s.role.at[3].set(CANDIDATE),
        term=s.term.at[3].set(3),
        voted_for=s.voted_for.at[3].set(3),
        votes=s.votes.at[3].set(_mask(n, {3})),
    )
    # Its broadcast goes out this tick...
    s = s._replace(deadline=s.deadline.at[3].set(1))
    s, _ = step(s, _quiet_inputs(cfg))
    # ...and is denied by every heard-recent voter: no grants banked, no
    # leadership, terms un-adopted nowhere needed (rdl keeps adoption).
    s, _ = step(s, _quiet_inputs(cfg))
    s, _ = step(s, _quiet_inputs(cfg))
    assert int(s.role[3]) != LEADER


# --------------------------------------------------------- ReadIndex reads


def test_reads_serve_with_metrics():
    cfg = RaftConfig(n_nodes=5, log_capacity=32, client_interval=2,
                     read_interval=2)
    _, m = scan.simulate(cfg, 7, 8, 300)
    served = int(np.sum(np.asarray(m.reads_served)))
    assert served > 0
    assert int(np.sum(np.asarray(m.read_hist))) == served
    # Every served read waited at least the one-tick confirmation round.
    assert int(np.sum(np.asarray(m.read_lat_sum))) >= served


def test_read_confirmation_uses_tick_start_config_at_joint_exit():
    """Kernel-vs-oracle pin for the tick-start config rule on the read path:
    a pending read's confirmation is judged under the TICK-START (joint)
    per-node derivation even when the end-of-tick re-derivation dissolves
    that joint state, so a read whose acks satisfy only the incoming
    configuration stays pending through the switch (a late-bound oracle
    closure once served it -- review regression)."""
    from tests import oracle

    n = 5
    cfg = RaftConfig(n_nodes=n, log_capacity=8, reconfig_interval=1000,
                     read_interval=1000)
    s = init_state(cfg, jax.random.key(0))
    # Cache-injected joint {0,1,2,3} -> {0..4}: leader 0 holds a pending
    # read acked by {1, 4}; with self that is 3 -- a majority of the NEW
    # config (maj 3) but only 2 of the OLD members {0,1,2,3} (maj 3).
    # Tick-start rule: NOT confirmed this tick, even though the end-of-tick
    # derivation (empty log) dissolves the joint state.
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        member_old=_mask_rows(n, {0, 1, 2, 3}),
        member_new=_mask_rows(n, {0, 1, 2, 3, 4}),
        cfg_pend=jnp.ones((n,), jnp.int32),
        read_idx=s.read_idx.at[0].set(1),
        read_tick=s.read_tick.at[0].set(1),
        read_acks=s.read_acks.at[0].set(_mask(n, {1, 4})),
    )
    inp = _quiet_inputs(cfg)
    s2, _ = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))(s, inp)
    assert np.all(np.asarray(s2.cfg_pend) == 0)  # joint state dissolved...
    assert int(s2.read_idx[0]) == 1  # ...but the read stayed pending
    inp_np = {f: np.asarray(v) for f, v in zip(inp._fields, inp)}
    got = oracle.oracle_step(cfg, oracle.state_to_dict(s), inp_np)
    assert int(got["read_idx"][0]) == 1  # oracle agrees (tick-start masks)
    assert np.array_equal(np.asarray(got["read_idx"]), np.asarray(s2.read_idx))


@pytest.mark.slow  # budget re-tier (PR 12): the read_cmd override is
# exercised every tier-1 run through its production consumers -- Session.
# offer_read (test_lease) and the tenancy serve fixture's read planes
# (test_tenancy) -- so this direct-unit form, which pays its own windowed
# compile, rides the slow tier.
def test_tick_batch_minor_read_cmd_override():
    """External read ingest on the serve tick body (docs/SERVE.md): the
    per-tick read_cmd override drives captures exactly like the scheduled
    cadence -- a fleet fed reads via the override serves them; NIL feeds
    none. Uses a huge scheduled cadence so every served read is
    override-attributable."""
    from raft_sim_tpu.types import init_batch

    cfg = RaftConfig(n_nodes=5, log_capacity=32, client_interval=4,
                     read_interval=100_000)
    root = jax.random.key(4)
    k_init, k_run = jax.random.split(root)
    B = 4
    keys = jax.random.split(k_run, B)

    def drive(ticks, read_every):
        s = raft_batched.to_batch_minor(init_batch(cfg, k_init, B))
        m = raft_batched.to_batch_minor(scan.init_metrics_batch(B))
        for t in range(ticks):
            rc = 1 if (read_every and t % read_every == 0) else NIL
            s, m, _ = scan.tick_batch_minor(cfg, s, keys, m, read_cmd=rc)
        return int(np.sum(np.asarray(m.reads_served)))

    assert drive(60, read_every=3) > 0
    assert drive(30, read_every=0) == 0


# ------------------------------------------------- mutants vs real kernel


@pytest.mark.slow  # budget re-tier (ISSUE 13): the property-level rejection
# (and the real kernel's clean pass) is pinned in tier 1 by the corpus
# replay of tests/corpus/blind-transfer-n5.json, and CI's reconfig smoke
# re-hunts the mutant every push -- the in-suite device sim joins its
# single-server-change sibling in the slow tier.
def test_blind_transfer_mutant_violates_real_kernel_clean():
    """The transfer-as-a-coup mutant truncates committed entries off
    followers (device commit-checksum violations); the REAL kernel under the
    identical program stays clean -- the CE hunt's target signal."""
    base = RaftConfig(n_nodes=5, log_capacity=16, client_interval=2,
                      drop_prob=0.25, transfer_interval=9)
    _, m_real = scan.simulate(base, 0, 16, 400)
    _, m_mut = scan.simulate(mutant_config("blind-transfer", base), 0, 16, 400)
    assert int(np.sum(np.asarray(m_real.violations))) == 0
    assert int(np.sum(np.asarray(m_mut.violations))) > 0


@pytest.mark.slow
def test_single_server_change_mutant_violates_real_kernel_clean():
    """The single-server-change mutant (one final-acting entry per change,
    no joint phase): consecutive toggles under partitions + drop produce
    non-intersecting quorums -> device violations. Needs a longer horizon
    and a wider fleet than the coup mutant (the race window is narrow), so
    it rides the slow tier; the corpus replay (tests/test_corpus.py) pins
    the property-level rejection in tier 1."""
    base = RaftConfig(n_nodes=5, log_capacity=16, client_interval=2,
                      drop_prob=0.3, partition_period=16, partition_prob=0.6,
                      reconfig_interval=7)
    _, m_real = scan.simulate(base, 0, 64, 800)
    _, m_mut = scan.simulate(
        mutant_config("single-server-change", base), 0, 64, 800
    )
    assert int(np.sum(np.asarray(m_real.violations))) == 0
    assert int(np.sum(np.asarray(m_mut.violations))) > 0


# ------------------------------------------- trace checker, new properties


CFG_TRACE = RaftConfig(
    n_nodes=5, client_interval=4, reconfig_interval=17, transfer_interval=23,
    read_interval=5, drop_prob=0.25, partition_period=16, partition_prob=0.5,
    crash_prob=0.2, crash_period=32, crash_down_ticks=8, track_trace=True,
)
SPEC = TraceSpec(depth=512)


@functools.lru_cache(maxsize=1)
def _real_report():
    out = telemetry.simulate_windowed(CFG_TRACE, 5, 12, 448, 64, 0, None, 1, SPEC)
    return tchecker.check_history(thistory.from_device(out[4]))


@pytest.mark.slow  # budget re-tier (PR 12): real-kernel-passes-the-checker
# is now pinned three times per tier-1 run by the corpus checker tests
# (test_corpus.py real-kernel replays, incl. a transfer-carrying config),
# and CI's reconfig smoke runs this exact add/remove-under-fire leg through
# the driver -- the in-suite variant joins the slow tier.
def test_real_kernel_passes_all_properties_under_add_remove_under_fire():
    """The acceptance run: membership toggles + transfers + reads under
    drop/partition/crash churn; the whole-history checker passes every
    property on a COMPLETE history -- with election safety now
    UNCONDITIONAL per term (no epoch carve-out)."""
    rep = _real_report()
    assert rep.complete, rep.problems
    assert rep.ok, {k: r.note for k, r in rep.results.items() if not r.ok}
    assert set(rep.results) == set(tchecker.PROPERTIES)
    assert "read_linearizability" in rep.results


def test_stale_read_mutant_rejected_with_witness():
    """The stale-read mutant serves unconfirmed reads; a deposed leader in a
    minority partition then serves below the committed frontier, and the
    checker names read_linearizability with the (issue, serve) witness."""
    cfg = dataclasses.replace(
        CFG_TRACE, reconfig_interval=0, transfer_interval=0,
        read_interval=2, crash_prob=0.0,
    )
    out = telemetry.simulate_windowed(
        mutant_config("stale-read", cfg), 3, 8, 256, 32, 0, None, 1, SPEC
    )
    rep = tchecker.check_history(thistory.from_device(out[4]))
    assert "read_linearizability" in rep.violated
    w = rep.results["read_linearizability"].witness
    assert [e["kind"] for e in w] == ["read_issue", "read_serve"]
    assert "below the committed frontier" in rep.results["read_linearizability"].note


def _hist(events_by_cluster):
    ev = {c: [thistory.Event(*e) for e in evs]
          for c, evs in events_by_cluster.items()}
    return thistory.History(
        events=ev,
        emitted={c: len(v) for c, v in ev.items()},
        dropped={c: 0 for c in ev},
        n_windows=1,
        problems=[],
    )


def test_checker_unconditional_election_safety():
    """EPOCH_EXEMPT_DISTANCE is DELETED: under log-carried configuration
    every electorate chains from the boot config, so two same-term leaders
    are a violation at ANY config distance. Synthetic negatives both
    directions (ISSUE-13 acceptance)."""
    assert not hasattr(tchecker, "EPOCH_EXEMPT_DISTANCE")
    L, CA = tev.EV_LEADER, tev.EV_CFG_APPLY
    # Two leaders for one term, no config motion: violation (unchanged).
    rep = tchecker.check_history(_hist({0: [(5, 0, L, 3), (9, 2, L, 3)]}))
    assert rep.violated == ["election_safety"]
    assert "log-carried" in rep.results["election_safety"].note
    # The admin-era EXCUSED case -- same-term leaders with the config moved
    # 4+ epochs between them -- is now REJECTED: per-node log-carried
    # configs cannot produce legally-disjoint same-term electorates.
    far = [(5, 0, L, 3)] + [
        (10 + i, nd, CA, i + 1) for i in range(6) for nd in range(5)
    ] + [(30, 2, L, 3)]
    rep = tchecker.check_history(_hist({0: far}))
    assert rep.violated == ["election_safety"]
    # Distinct terms across the same config motion: legal.
    ok = [(5, 0, L, 3)] + [
        (10 + i, nd, CA, i + 1) for i in range(6) for nd in range(5)
    ] + [(30, 2, L, 4)]
    rep = tchecker.check_history(_hist({0: ok}))
    assert rep.ok


def test_checker_double_vote_keyed_on_voter_term():
    """Election safety is additionally keyed on each node's state at VOTE
    time: two different-candidate grants in one term are named directly,
    while the legal single-config double-vote (an idempotent re-grant of the
    SAME candidate, e.g. after a restart) still passes."""
    T, V, R = tev.EV_TERM, tev.EV_VOTE, tev.EV_RESTART
    # Node 1 votes for 0 then for 2 in the same term: violation.
    rep = tchecker.check_history(_hist({0: [
        (4, 1, T, 7), (5, 1, V, 0), (9, 1, V, 2),
    ]}))
    assert rep.violated == ["election_safety"]
    assert "voted for both" in rep.results["election_safety"].note
    # Legal re-grant: restart, same candidate again -- passes.
    rep = tchecker.check_history(_hist({0: [
        (4, 1, T, 7), (5, 1, V, 0), (8, 1, R, 0), (9, 1, V, 0),
    ]}))
    assert rep.ok
    # New term between the votes: both grants legal.
    rep = tchecker.check_history(_hist({0: [
        (4, 1, T, 7), (5, 1, V, 0), (8, 1, T, 8), (9, 1, V, 2),
    ]}))
    assert rep.ok


def test_checker_read_linearizability_negatives():
    C, RI, RS = tev.EV_COMMIT, tev.EV_READ_ISSUE, tev.EV_READ_SERVE
    # A read issued at index 3 while the frontier sits at 5: serving it is
    # the violation (it misses committed writes).
    rep = tchecker.check_history(_hist({0: [
        (4, 0, C, 5), (8, 1, RI, 3), (10, 1, RS, 3),
    ]}))
    assert rep.violated == ["read_linearizability"]
    # A read at the frontier is linearizable.
    rep = tchecker.check_history(_hist({0: [
        (4, 0, C, 5), (8, 0, RI, 5), (10, 0, RS, 5),
    ]}))
    assert rep.ok
    # An issued-but-never-served stale read is NOT a violation (the real
    # kernel's confirmation round kills exactly these).
    rep = tchecker.check_history(_hist({0: [
        (4, 0, C, 5), (8, 1, RI, 3),
    ]}))
    assert rep.ok


# ------------------------------------------------------- checkpoint v24


def test_checkpoint_v24_round_trips_log_carried_config_state(tmp_path):
    """The per-node config planes ride the checkpoint: a mid-run
    config8-family fleet saves and loads bit-identically (per-node member
    rows, the log_cfg entry plane, the snapshot config context, transfer
    and read slots included)."""
    from raft_sim_tpu.types import init_batch

    cfg, _ = PRESETS["config8"]
    root = jax.random.key(9)
    k_init, k_run = jax.random.split(root)
    state = init_batch(cfg, k_init, 2)
    keys = jax.random.split(k_run, 2)
    state, metrics = scan.run_batch_minor(cfg, state, keys, 120)
    assert int(np.max(np.asarray(state.cfg_epoch))) > 0  # churn happened
    assert int(np.sum(np.abs(np.asarray(state.log_cfg)) > 0)) > 0
    path = checkpoint.save(str(tmp_path / "ck"), cfg, state, keys, metrics, seed=9)
    cfg2, state2, keys2, metrics2, seed2, scenario = checkpoint.load(path)
    assert cfg2 == cfg and seed2 == 9 and scenario is None
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(metrics), jax.tree.leaves(metrics2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_v23_file_refused_with_migration_error(tmp_path, monkeypatch):
    """A pre-v24 checkpoint (admin-era scalar config state) must be REFUSED
    with the migration-pointing error, not half-loaded into the per-node
    schema: the version log names the field changes and the error says how
    to regenerate."""
    cfg = RaftConfig(n_nodes=3, log_capacity=8)
    s = init_state(cfg, jax.random.key(0))
    state = jax.tree.map(lambda x: jnp.stack([x]), s)  # batch of 1
    keys = jax.random.split(jax.random.key(1), 1)
    metrics = scan.init_metrics_batch(1)
    monkeypatch.setattr(checkpoint, "_FORMAT_VERSION", 23)
    path = checkpoint.save(str(tmp_path / "old"), cfg, state, keys, metrics)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="v23.*v24|format v23"):
        checkpoint.load(path)
