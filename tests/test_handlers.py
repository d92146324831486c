"""Per-handler unit tests against hand-built states (SURVEY.md section 4, unit tier).

Each test constructs a precise cluster state + mailbox, runs one tick, and asserts the
spec-mandated outcome -- especially at the points where the reference deviates from the
Raft paper (SURVEY.md section 2.3): term adoption on RequestVote (2.3.2), the real
up-to-date check (2.3.3/2.3.4), leader-commit advancement from majority match (2.3.8),
nextIndex = match+1 (2.3.10), and commit = min(leaderCommit, last new entry) (2.3.6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import (
    CANDIDATE,
    FOLLOWER,
    LEADER,
    NIL,
    RaftConfig,
    StepInputs,
    init_state,
)
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu import types as raft_types
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.types import REQ_APPEND, REQ_VOTE, RESP_APPEND, RESP_VOTE

CFG = RaftConfig(n_nodes=5, log_capacity=8, max_entries_per_rpc=4)


def quiet_inputs(cfg, far=1000, deliver=None):
    """No faults, no client traffic, clocks advancing but timers far away.
    `deliver` overrides the (full) [N, N] bool delivery mask; StepInputs carries
    it bit-packed (ops/bitplane.py)."""
    n = cfg.n_nodes
    if deliver is None:
        deliver = jnp.ones((n, n), bool)
    return StepInputs(
        deliver_mask=bitplane.pack(deliver, axis=1),
        skew=jnp.ones((n,), jnp.int32),
        timeout_draw=jnp.full((n,), far, jnp.int32),
        client_cmd=jnp.int32(NIL),
        client_target=jnp.int32(0),
        client_bounce=jnp.zeros((cfg.client_pipeline,), jnp.int32),
        alive=jnp.ones((n,), bool),
        restarted=jnp.zeros((n,), bool),
    )


def base_state(cfg=CFG, far=1000):
    """All-follower state with timers pushed far out so nothing fires by itself."""
    s = init_state(cfg, jax.random.key(0))
    return s._replace(deadline=jnp.full((cfg.n_nodes,), far, jnp.int32))


def with_log(s, node, terms):
    """Install a log (list of entry terms; values = 100+slot) on one node.
    Entries are stamped as client offers (log_tick = value, the pre-decoupling
    identity), so hand-built states stay visible to the latency metric."""
    lt = s.log_term.at[node, : len(terms)].set(jnp.asarray(terms, jnp.int32))
    vals = 100 + jnp.arange(len(terms), dtype=jnp.int32)
    lv = s.log_val.at[node, : len(terms)].set(vals)
    ltk = s.log_tick.at[node, : len(terms)].set(vals)
    return s._replace(
        log_term=lt, log_val=lv, log_tick=ltk,
        log_len=s.log_len.at[node].set(len(terms)),
    )


import functools


@functools.lru_cache(maxsize=None)
def _jitted_step(cfg):
    return jax.jit(lambda s_, i_: raft_batched.step_one(cfg, s_, i_))


def step(cfg, s, inp=None):
    return _jitted_step(cfg)(s, inp if inp is not None else quiet_inputs(cfg))


# Wire-format v9 helpers (Mailbox docstring): requests are per-sender broadcasts,
# responses are a [receiver, responder] type plane + per-responder payloads
# (grant target, ack target, success match, nack hint, term).


def rv_wire(s, src, term, last_idx=0, last_term=0):
    """Broadcast a RequestVote from `src` (delivery decides who sees it)."""
    mb = s.mailbox._replace(
        req_type=s.mailbox.req_type.at[src].set(REQ_VOTE),
        req_term=s.mailbox.req_term.at[src].set(term),
        req_last_index=s.mailbox.req_last_index.at[src].set(last_idx),
        req_last_term=s.mailbox.req_last_term.at[src].set(last_term),
    )
    return s._replace(mailbox=mb)


def resp_wire(s, q, r, rtype, term, ok, match=0):
    """Wire a response from responder `r` to requester `q`. An ok response names
    `q` as r's one grant/ack target; `match` lands in the success-match field for
    an ok append and in the nack-hint field otherwise."""
    mb = s.mailbox._replace(
        resp_kind=s.mailbox.resp_kind.at[q, r].set(rtype),
        resp_term=s.mailbox.resp_term.at[r].set(term),
    )
    if rtype == RESP_VOTE and ok:
        mb = mb._replace(v_to=mb.v_to.at[r].set(q))
    if rtype == RESP_APPEND:
        if ok:
            mb = mb._replace(
                a_ok_to=mb.a_ok_to.at[r].set(q),
                a_match=mb.a_match.at[r].set(match),
            )
        else:
            mb = mb._replace(a_hint=mb.a_hint.at[r].set(match))
    return s._replace(mailbox=mb)


def resp_type_of(mb, q, r):
    return int(mb.resp_kind[q, r])


def resp_ok_of(mb, q, r):
    kind = int(mb.resp_kind[q, r])
    if kind == RESP_VOTE:
        return int(mb.v_to[r]) == q
    if kind == RESP_APPEND:
        return int(mb.a_ok_to[r]) == q
    return False


def resp_match_of(mb, q, r):
    return int(mb.a_match[r] if resp_ok_of(mb, q, r) else mb.a_hint[r])


# ---------------------------------------------------------------- RequestVote handling


def test_vote_granted_and_term_adopted():
    """A higher-term RequestVote makes the receiver adopt the term (reference bug
    2.3.2: it never did) and grant when the candidate's log is up to date."""
    s = rv_wire(base_state(), 0, term=5)
    s2, _ = step(CFG, s)
    assert int(s2.term[1]) == 5
    assert int(s2.voted_for[1]) == 0
    assert resp_type_of(s2.mailbox, 0, 1) == RESP_VOTE
    assert resp_ok_of(s2.mailbox, 0, 1)
    assert int(s2.mailbox.resp_term[1]) == 5


def test_vote_denied_stale_term():
    s = base_state()
    s = s._replace(term=s.term.at[1].set(9))
    s = rv_wire(s, 0, term=5)
    s2, _ = step(CFG, s)
    assert int(s2.voted_for[1]) == NIL
    # Response still sent, carrying the newer term so the candidate steps down.
    assert resp_type_of(s2.mailbox, 0, 1) == RESP_VOTE
    assert not resp_ok_of(s2.mailbox, 0, 1)
    assert int(s2.mailbox.resp_term[1]) == 9


def test_vote_denied_stale_log():
    """Up-to-date check (spec 5.4.1): voter's last entry term 3 > candidate's 2."""
    s = with_log(base_state(), 1, [1, 3])
    s = s._replace(term=s.term.at[1].set(4))
    s = rv_wire(s, 0, term=4, last_idx=5, last_term=2)
    s2, _ = step(CFG, s)
    assert not resp_ok_of(s2.mailbox, 0, 1)
    assert int(s2.voted_for[1]) == NIL


def test_vote_denied_shorter_log_same_term():
    """Same last term, candidate's index shorter -> deny."""
    s = with_log(base_state(), 1, [2, 2, 2])
    s = s._replace(term=s.term.at[1].set(3))
    s = rv_wire(s, 0, term=3, last_idx=2, last_term=2)
    s2, _ = step(CFG, s)
    assert not resp_ok_of(s2.mailbox, 0, 1)


def test_single_vote_per_term_lowest_wins():
    """Two simultaneous candidates: one grant only, to the lowest id; the vote is
    remembered in voted_for."""
    s = rv_wire(rv_wire(base_state(), 2, term=2), 3, term=2)
    s2, _ = step(CFG, s)
    assert int(s2.voted_for[0]) == 2
    assert resp_ok_of(s2.mailbox, 2, 0)
    assert not resp_ok_of(s2.mailbox, 3, 0)


def test_revote_same_candidate_is_idempotent():
    """A retransmitted RequestVote from the already-voted-for candidate re-grants."""
    s = base_state()
    s = s._replace(term=s.term.at[0].set(2), voted_for=s.voted_for.at[0].set(2))
    s = rv_wire(rv_wire(s, 2, term=2), 3, term=2)
    s2, _ = step(CFG, s)
    assert resp_ok_of(s2.mailbox, 2, 0)
    assert not resp_ok_of(s2.mailbox, 3, 0)
    assert int(s2.voted_for[0]) == 2


# ------------------------------------------------------------- AppendEntries handling


def ae_wire(s, src, term, prev_i, prev_t, commit, ents, ent_start=None):
    """Broadcast an AppendEntries from `src` (wire format v8): the shared window is
    positioned at `ent_start` (default prev_i, i.e. offset j = 0) and every edge
    carries the offset j = prev_i - ent_start, so each receiver reconstructs
    (prev_i, prev_t, ents). For j >= 1 the window slot j-1 holds prev_t, as a real
    sender's consistent window would."""
    mb = s.mailbox
    start = prev_i if ent_start is None else ent_start
    j = prev_i - start
    mb = mb._replace(
        req_type=mb.req_type.at[src].set(REQ_APPEND),
        req_term=mb.req_term.at[src].set(term),
        req_commit=mb.req_commit.at[src].set(commit),
        ent_start=mb.ent_start.at[src].set(start),
        ent_count=mb.ent_count.at[src].set(j + len(ents)),
        req_off=mb.req_off.at[src, :].set(j),
    )
    if j == 0:
        mb = mb._replace(ent_prev_term=mb.ent_prev_term.at[src].set(prev_t))
    else:
        mb = mb._replace(ent_term=mb.ent_term.at[src, j - 1].set(prev_t))
    for k, (t, v) in enumerate(ents):
        mb = mb._replace(
            ent_term=mb.ent_term.at[src, j + k].set(t),
            ent_val=mb.ent_val.at[src, j + k].set(v),
        )
    return s._replace(mailbox=mb)


def test_append_accept_and_commit_min():
    """Entries appended; follower commit = min(leaderCommit, last new entry) -- the
    reference committed everything unconditionally (bug 2.3.6)."""
    s = base_state()
    s = s._replace(term=s.term.at[1].set(2))
    s = ae_wire(s, 0, term=2, prev_i=0, prev_t=0, commit=5, ents=[(2, 7), (2, 8)])
    s2, _ = step(CFG, s)
    assert int(s2.log_len[1]) == 2
    assert int(s2.commit_index[1]) == 2  # min(5, 2), not 5
    assert int(s2.leader_id[1]) == 0
    assert resp_ok_of(s2.mailbox, 0, 1)
    assert resp_match_of(s2.mailbox, 0, 1) == 2
    np.testing.assert_array_equal(np.asarray(s2.log_val[1, :2]), [7, 8])


def test_append_reject_inconsistent():
    """prev entry missing -> reject, nothing appended (spec 5.3)."""
    s = base_state()
    s = s._replace(term=s.term.at[1].set(2))
    s = ae_wire(s, 0, term=2, prev_i=3, prev_t=1, commit=0, ents=[(2, 7)])
    s2, _ = step(CFG, s)
    assert int(s2.log_len[1]) == 0
    assert resp_type_of(s2.mailbox, 0, 1) == RESP_APPEND
    assert not resp_ok_of(s2.mailbox, 0, 1)


def test_append_conflict_truncates():
    """Follower has [1,1,3]; leader sends prev=1/term1 + entries [(2),(2)] ->
    conflicting suffix replaced, log = [1,2,2] (spec: delete existing entry and all
    that follow; the reference's remove-from! truncated the wrong end, bug 2.3.7)."""
    s = with_log(base_state(), 1, [1, 1, 3])
    s = s._replace(term=s.term.at[1].set(4))
    s = ae_wire(s, 0, term=4, prev_i=1, prev_t=1, commit=0, ents=[(2, 7), (2, 8)])
    s2, _ = step(CFG, s)
    assert int(s2.log_len[1]) == 3
    np.testing.assert_array_equal(np.asarray(s2.log_term[1, :3]), [1, 2, 2])
    np.testing.assert_array_equal(np.asarray(s2.log_val[1, 1:3]), [7, 8])


def test_append_prefix_match_no_truncate():
    """A stale AE covering an existing matching prefix must NOT shrink the log."""
    s = with_log(base_state(), 1, [1, 1, 1, 1])
    s = s._replace(term=s.term.at[1].set(2))
    s = ae_wire(s, 0, term=2, prev_i=0, prev_t=0, commit=0, ents=[(1, 100)])
    s2, _ = step(CFG, s)
    assert int(s2.log_len[1]) == 4  # max(4, 1): matching prefix kept


def test_heartbeat_resets_election_timer_and_demotes_candidate():
    s = base_state()
    s = s._replace(
        role=s.role.at[1].set(CANDIDATE),
        term=s.term.at[1].set(3),
        deadline=s.deadline.at[1].set(2),  # would expire soon
    )
    s = ae_wire(s, 0, term=3, prev_i=0, prev_t=0, commit=0, ents=[])
    inp = quiet_inputs(CFG, far=50)
    s2, _ = step(CFG, s, inp)
    assert int(s2.role[1]) == FOLLOWER
    assert int(s2.leader_id[1]) == 0
    assert int(s2.deadline[1]) == int(s2.clock[1]) + 50


# ------------------------------------------------------------------ response handling


def make_leader(s, node, term):
    n = CFG.n_nodes
    return s._replace(
        role=s.role.at[node].set(LEADER),
        term=s.term.at[node].set(term),
        leader_id=jnp.full((n,), node, jnp.int32),
        next_index=s.next_index.at[node].set(
            jnp.full((n,), int(s.log_len[node]) + 1, s.next_index.dtype)
        ),
    )


def test_candidate_wins_with_quorum():
    s = base_state()
    s = s._replace(
        role=s.role.at[0].set(CANDIDATE),
        term=s.term.at[0].set(2),
        voted_for=s.voted_for.at[0].set(0),
        votes=bitplane.set_bit(s.votes, 0, 0),  # self-vote
    )
    s = resp_wire(s, 0, 1, RESP_VOTE, term=2, ok=True)
    s = resp_wire(s, 0, 2, RESP_VOTE, term=2, ok=True)
    s2, info = step(CFG, s)
    assert int(s2.role[0]) == LEADER
    assert int(s2.leader_id[0]) == 0
    # Fresh leader state: nextIndex = lastLog+1 = 1, matchIndex = 0 (core.clj:40-42).
    assert all(int(x) == 1 for x in np.asarray(s2.next_index[0]))
    assert all(int(x) == 0 for x in np.asarray(s2.match_index[0]))
    # Immediate heartbeat broadcast (core.clj:137-138): empty log -> every peer's
    # window offset is 0 and the window is empty.
    assert int(s2.mailbox.req_type[0]) == REQ_APPEND
    assert int(s2.mailbox.ent_count[0]) == 0
    for p in range(1, 5):
        assert int(s2.mailbox.req_off[0, p]) == 0
    assert int(info.n_leaders) == 1


def test_candidate_needs_quorum():
    """2 of 5 votes (self + one) is not a majority -> still candidate."""
    s = base_state()
    s = s._replace(
        role=s.role.at[0].set(CANDIDATE),
        term=s.term.at[0].set(2),
        votes=bitplane.set_bit(s.votes, 0, 0),
    )
    s = resp_wire(s, 0, 1, RESP_VOTE, term=2, ok=True)
    s2, _ = step(CFG, s)
    assert int(s2.role[0]) == CANDIDATE


def test_stale_vote_response_ignored():
    """A vote response from an older term must not count (core.clj:131-132)."""
    s = base_state()
    s = s._replace(
        role=s.role.at[0].set(CANDIDATE),
        term=s.term.at[0].set(5),
        votes=bitplane.set_bit(s.votes, 0, 0),
    )
    s = resp_wire(s, 0, 1, RESP_VOTE, term=4, ok=True)
    s = resp_wire(s, 0, 2, RESP_VOTE, term=4, ok=True)
    s2, _ = step(CFG, s)
    assert int(s2.role[0]) == CANDIDATE


def test_append_response_success_updates_indices():
    """nextIndex = ackedIndex + 1 (the reference set nextIndex = ackedIndex, 2.3.10)."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    s = resp_wire(s, 0, 1, RESP_APPEND, term=1, ok=True, match=2)
    s2, _ = step(CFG, s)
    assert int(s2.match_index[0, 1]) == 2
    assert int(s2.next_index[0, 1]) == 4  # max(4, 2+1): never regress below lastLog+1


def test_append_response_failure_decrements_next_index():
    """A nack's match field carries the responder's log length (the conflict-index
    hint, PARITY.md "protocol additions"): next = max(min(next-1, hint+1), 1) --
    an adjacent conflict still steps back one, a far-behind follower is reached in
    one round trip instead of one slot per heartbeat."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    s = resp_wire(s, 0, 1, RESP_APPEND, term=1, ok=False, match=3)  # hint: len 3
    s = resp_wire(s, 0, 2, RESP_APPEND, term=1, ok=False, match=0)  # hint: empty log
    s2, _ = step(CFG, s)
    assert int(s2.next_index[0, 1]) == 3  # min(4-1, 3+1): plain decrement
    assert int(s2.next_index[0, 2]) == 1  # min(4-1, 0+1): jump straight to 1


def test_leader_steps_down_on_higher_term_response():
    """Higher term in any response -> revert to follower (core.clj:129-130, 144-145)."""
    s = make_leader(base_state(), 0, 2)
    s = resp_wire(s, 0, 1, RESP_APPEND, term=7, ok=False)
    s2, _ = step(CFG, s)
    assert int(s2.role[0]) == FOLLOWER
    assert int(s2.term[0]) == 7
    assert int(s2.leader_id[0]) == NIL


# ----------------------------------------------------------- leader commit advancement


def test_leader_commits_on_majority_match():
    """match = [3(self),2,2,0,0] -> quorum(3)-th largest = 2 -> commit 2. Absent in the
    reference entirely (bug 2.3.8)."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    s = s._replace(
        match_index=s.match_index.at[0, 1].set(2).at[0, 2].set(2),
    )
    s2, _ = step(CFG, s)
    assert int(s2.commit_index[0]) == 2


def test_leader_does_not_commit_older_term_entries():
    """Spec 5.4.2: only current-term entries commit by counting. Log terms [1,1] but
    leader is at term 3 -> no commit even with full match."""
    s = with_log(base_state(), 0, [1, 1])
    s = make_leader(s, 0, 3)
    s = s._replace(match_index=s.match_index.at[0].set(jnp.full((5,), 2, s.match_index.dtype)))
    s2, _ = step(CFG, s)
    assert int(s2.commit_index[0]) == 0


# ----------------------------------------------------------------- timers & elections


def test_timeout_starts_election():
    cfg = CFG
    s = base_state()
    s = s._replace(deadline=s.deadline.at[2].set(1))  # expires on this tick
    inp = quiet_inputs(cfg, far=20)
    s2, _ = step(cfg, s, inp)
    assert int(s2.role[2]) == CANDIDATE
    assert int(s2.term[2]) == 2
    assert int(s2.voted_for[2]) == 2
    assert bool(bitplane.get_bit(s2.votes, 2, 2))
    assert int(s2.mailbox.req_type[2]) == REQ_VOTE  # broadcast to all peers
    assert int(s2.mailbox.req_term[2]) == 2


def test_leader_heartbeats_on_timer():
    s = with_log(base_state(), 0, [1])
    s = make_leader(s, 0, 1)
    # Peers haven't acked entry 1 yet: nextIndex = 1 -> the heartbeat ships it.
    s = s._replace(
        deadline=s.deadline.at[0].set(1),
        next_index=s.next_index.at[0].set(jnp.ones((5,), s.next_index.dtype)),
    )
    s2, _ = step(CFG, s)
    assert int(s2.mailbox.req_type[0]) == REQ_APPEND
    # Each peer's offset j = 0 into a 1-entry window -> it receives the entry.
    assert int(s2.mailbox.ent_count[0]) == 1
    for p in range(1, 5):
        assert int(s2.mailbox.req_off[0, p]) == 0
    assert int(s2.deadline[0]) == int(s2.clock[0]) + CFG.heartbeat_ticks


def test_dropped_messages_are_dropped():
    """deliver_mask=False edges deliver nothing (the reference's swallowed HTTP
    exception, client.clj:38-40)."""
    s = rv_wire(base_state(), 0, term=5)
    inp = quiet_inputs(CFG, deliver=jnp.ones((5, 5), bool).at[1, 0].set(False))
    s2, _ = step(CFG, s, inp)
    assert int(s2.term[1]) == 1  # nothing adopted
    assert resp_type_of(s2.mailbox, 0, 1) == 0  # no response


def test_client_command_lands_on_leader_only():
    s = make_leader(base_state(), 0, 1)
    inp = quiet_inputs(CFG)._replace(client_cmd=jnp.int32(42))
    s2, _ = step(CFG, s, inp)
    assert int(s2.log_len[0]) == 1
    assert int(s2.log_val[0, 0]) == 42
    assert all(int(x) == 0 for x in np.asarray(s2.log_len[1:]))


# ---------------------------------------------------------- crash/restart fault tests


def test_restart_wipes_volatile_keeps_persistent():
    """Restart keeps the Raft persistent triple (currentTerm, votedFor, log[]) and
    wipes everything else (fig. 2 state table) -- unlike the reference, where only
    committed values survive a process death (log.clj:16-18, bug 2.3.12)."""
    s = with_log(base_state(), 0, [1, 2, 2])
    s = make_leader(s, 0, 2)
    s = s._replace(
        voted_for=s.voted_for.at[0].set(0),
        votes=s.votes.at[0].set(bitplane.full_row(5)),
        match_index=s.match_index.at[0].set(jnp.full((5,), 3, s.match_index.dtype)),
        commit_index=s.commit_index.at[0].set(3),
    )
    s = raft_types.with_commit_chk(s)  # hand-set commit needs a matching checksum
    inp = quiet_inputs(CFG)._replace(restarted=jnp.zeros((5,), bool).at[0].set(True))
    s2, info = step(CFG, s, inp)
    # Persistent: term, vote, log survive.
    assert int(s2.term[0]) == 2
    assert int(s2.voted_for[0]) == 0
    assert int(s2.log_len[0]) == 3
    np.testing.assert_array_equal(np.asarray(s2.log_term[0, :3]), [1, 2, 2])
    # Volatile: role, leader bookkeeping, commit, votes wiped.
    assert int(s2.role[0]) == FOLLOWER
    assert int(s2.leader_id[0]) == NIL
    assert int(s2.commit_index[0]) == 0
    assert int(np.asarray(s2.votes[0]).sum()) == 0
    assert all(int(x) == 1 for x in np.asarray(s2.next_index[0]))
    assert all(int(x) == 0 for x in np.asarray(s2.match_index[0]))
    # The commit wipe is a restart, not a monotonicity violation.
    assert not bool(info.viol_commit)


def test_down_leader_is_silent():
    """A crashed leader fires no heartbeat and emits nothing, so followers' election
    timers run out (the reference analogue: a killed process's peers see timeouts)."""
    s = make_leader(base_state(), 0, 1)
    s = s._replace(deadline=s.deadline.at[0].set(1))  # heartbeat due now
    inp = quiet_inputs(CFG)._replace(alive=jnp.ones((5,), bool).at[0].set(False))
    s2, _ = step(CFG, s, inp)
    assert int(np.asarray(s2.mailbox.req_type).sum()) == 0  # nothing sent
    assert int(s2.role[0]) == LEADER  # state frozen, not demoted, while down
    assert int(s2.deadline[0]) == 1  # timer did not fire or reset


def test_down_node_receives_nothing():
    """Messages to a down node die in flight: no response, no vote, no term adoption."""
    s = rv_wire(base_state(), 0, term=5)
    inp = quiet_inputs(
        CFG,
        # Scope delivery to the down node so live receivers don't react instead.
        deliver=jnp.eye(5, dtype=bool) | jnp.zeros((5, 5), bool).at[1, 0].set(True),
    )._replace(alive=jnp.ones((5,), bool).at[1].set(False))
    s2, _ = step(CFG, s, inp)
    assert int(s2.term[1]) == 1
    assert int(s2.voted_for[1]) == NIL
    assert resp_type_of(s2.mailbox, 0, 1) == 0


def test_down_candidate_cannot_win_on_banked_votes():
    s = base_state()
    s = s._replace(
        role=s.role.at[0].set(CANDIDATE),
        term=s.term.at[0].set(2),
        voted_for=s.voted_for.at[0].set(0),
        votes=s.votes.at[0].set(bitplane.full_row(5)),
    )
    inp = quiet_inputs(CFG)._replace(alive=jnp.ones((5,), bool).at[0].set(False))
    s2, _ = step(CFG, s, inp)
    assert int(s2.role[0]) == CANDIDATE  # not leader while down


def test_append_shared_window_rebase():
    """The shared-window wire format: a receiver whose prev is PAST the window start
    rebases into the sender's shared window (offset > 0) and appends the right
    entries (Mailbox docstring; the per-edge-window form this replaced was the N^2
    mailbox bandwidth hog)."""
    s = with_log(base_state(), 1, [1])  # receiver already has entry 1
    s = s._replace(term=s.term.at[1].set(2))
    # Sender's shared window starts at slot 0 holding [(1,100), (2,7)]; this
    # receiver's prev is 1, so only (2,7) at window offset 1 is for it.
    s = ae_wire(
        s, 0, term=2, prev_i=1, prev_t=1, commit=0,
        ents=[(2, 7)], ent_start=0,
    )
    mb = s.mailbox._replace(
        ent_term=s.mailbox.ent_term.at[0, 0].set(1),
        ent_val=s.mailbox.ent_val.at[0, 0].set(100),
    )
    s2, _ = step(CFG, s._replace(mailbox=mb))
    assert resp_ok_of(s2.mailbox, 0, 1)
    assert int(s2.log_len[1]) == 2
    np.testing.assert_array_equal(np.asarray(s2.log_term[1, :2]), [1, 2])
    np.testing.assert_array_equal(np.asarray(s2.log_val[1, :2]), [100, 7])


def test_committed_prefix_corruption_detected():
    """The carried-checksum invariant (log_ops module comment) must flag a committed
    entry whose value changes -- including corruption introduced BETWEEN ticks, which
    the old same-tick old-vs-new compare could not see."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    s = s._replace(commit_index=s.commit_index.at[0].set(2))
    s = raft_types.with_commit_chk(s)
    _, info = step(CFG, s)
    assert not bool(info.viol_commit)  # consistent state: no violation
    corrupted = s._replace(log_val=s.log_val.at[0, 1].set(999))  # committed slot
    _, info = step(CFG, corrupted)
    assert bool(info.viol_commit)


def test_window_fallback_when_no_peer_responsive():
    """A leader whose peers ALL aged out of the ack window (total isolation longer
    than ack_timeout_ticks) falls back to the min prev over all peers for the shared
    window start, so its next heartbeat still ships the entries a healed laggard
    needs (raft_batched.py phase 8 fallback arm)."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    s = s._replace(
        deadline=s.deadline.at[0].set(1),  # heartbeat due now
        # Peer 1 is far behind (next=1 -> prev=0); everyone stale beyond the window.
        next_index=s.next_index.at[0, 1].set(1),
        ack_age=s.ack_age.at[0].set(
            jnp.full((5,), CFG.ack_timeout_ticks + 5, s.ack_age.dtype)
        ),
    )
    s2, _ = step(CFG, s)
    assert int(s2.mailbox.req_type[0]) == REQ_APPEND
    # Fallback: window starts at the ALL-peers min prev (0), not at the responsive
    # min (which is empty); entries from slot 0 ship.
    assert int(s2.mailbox.ent_start[0]) == 0
    assert int(s2.mailbox.ent_count[0]) == 3
    assert int(s2.mailbox.req_off[0, 1]) == 0


def test_stale_peer_excluded_from_window_start():
    """A single unresponsive laggard must NOT pin the window: the shared window
    starts at the min prev over RESPONSIVE peers, and the stale peer's offset is
    lifted to the window start."""
    s = with_log(base_state(), 0, [1, 1, 1])
    s = make_leader(s, 0, 1)
    ages = jnp.zeros((5,), s.ack_age.dtype).at[1].set(CFG.ack_timeout_ticks + 5)
    s = s._replace(
        deadline=s.deadline.at[0].set(1),
        # Stale peer 1 is far behind; responsive peers 2-4 are at prev=2.
        next_index=s.next_index.at[0].set(
            jnp.asarray([4, 1, 3, 3, 3], s.next_index.dtype)
        ),
        ack_age=s.ack_age.at[0].set(ages),
    )
    s2, _ = step(CFG, s)
    assert int(s2.mailbox.req_type[0]) == REQ_APPEND
    assert int(s2.mailbox.ent_start[0]) == 2  # responsive min, not peer 1's 0
    assert int(s2.mailbox.req_off[0, 1]) == 0  # stale peer lifted to window start
    assert int(s2.mailbox.req_off[0, 2]) == 0  # responsive peers at their own prev


def test_stale_append_entries_nacked_with_newer_term():
    """An AE from a deposed leader (lower term) must be rejected, and the response
    must carry the follower's newer term so the stale leader steps down (the
    request side of core.clj:144-145's step-down; spec 5.1)."""
    s = base_state()
    s = s._replace(term=s.term.at[1].set(5))
    s = ae_wire(s, 0, term=3, prev_i=0, prev_t=0, commit=0, ents=[(3, 7)])
    s2, _ = step(CFG, s)
    assert int(s2.log_len[1]) == 0  # nothing appended
    assert resp_type_of(s2.mailbox, 0, 1) == RESP_APPEND  # still answered
    assert not resp_ok_of(s2.mailbox, 0, 1)
    assert int(s2.mailbox.resp_term[1]) == 5  # carries the newer term
    assert int(s2.leader_id[1]) == NIL  # stale sender not adopted as leader


def test_client_command_rejected_when_log_full():
    """A leader whose fixed-capacity log is full must drop offered commands (the
    static-shape analogue of the reference's unbounded vector, SURVEY.md 7.3) --
    and report the offer as not accepted."""
    s = with_log(base_state(), 0, [1] * CFG.log_capacity)  # full log
    s = make_leader(s, 0, 1)
    inp = quiet_inputs(CFG)._replace(client_cmd=jnp.int32(42))
    s2, info = step(CFG, s, inp)
    assert int(s2.log_len[0]) == CFG.log_capacity  # unchanged
    assert 42 not in np.asarray(s2.log_val[0])
    assert int(info.cmds_injected) == 0
