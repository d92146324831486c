"""PreVote (cfg.pre_vote; Raft thesis 9.6) -- BEYOND the reference, which has
neither pre-vote nor leadership transfer (SURVEY.md 2.3.12).

The property pre-vote exists for: a node partitioned away keeps timing out, but
its probes are denied by peers who still hear their leader, so its TERM NEVER
INFLATES -- and when the partition heals it rejoins as a follower instead of
deposing a healthy leader with a giant term. Without pre-vote the same scenario
forces a gratuitous re-election on heal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, StepInputs, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.sim import scan
from raft_sim_tpu.types import (
    CANDIDATE,
    LEADER,
    NIL,
    PRECANDIDATE,
    REQ_PREVOTE,
    REQ_VOTE,
    RESP_PREVOTE,
)
from tests.test_handlers import base_state, make_leader, quiet_inputs, step

CFG = RaftConfig(n_nodes=5, log_capacity=8, pre_vote=True)


def isolate(cfg, node, far=1000):
    """Inputs with `node` partitioned away from everyone (both directions)."""
    n = cfg.n_nodes
    mask = jnp.ones((n, n), bool).at[node, :].set(False).at[:, node].set(False)
    return quiet_inputs(cfg, far=far, deliver=mask)


# -------------------------------------------------------------- grant/deny rules


def pv_wire(s, src, term_prospective, last_idx=0, last_term=0):
    """Broadcast a PreVote probe from `src` carrying its prospective term."""
    mb = s.mailbox._replace(
        req_type=s.mailbox.req_type.at[src].set(REQ_PREVOTE),
        req_term=s.mailbox.req_term.at[src].set(term_prospective),
        req_last_index=s.mailbox.req_last_index.at[src].set(last_idx),
        req_last_term=s.mailbox.req_last_term.at[src].set(last_term),
    )
    return s._replace(mailbox=mb)


def pv_resp_of(mb, q, r):
    """(responded, granted) for the pre-vote response edge [q, r]: the type
    rides resp_kind, the grant bit the packed pv_grant plane."""
    kind = int(mb.resp_kind[q, r])
    return kind == RESP_PREVOTE, bool(bitplane.get_bit(mb.pv_grant, q, r))


def test_quiet_voter_grants_probe_without_adopting_term():
    s = base_state(CFG)  # heard_clock init: quiet from boot
    s2, _ = step(CFG, pv_wire(s, 0, term_prospective=2))
    responded, granted = pv_resp_of(s2.mailbox, 0, 1)
    assert responded and granted
    assert int(s2.term[1]) == 1  # the prospective term is NOT adopted
    assert int(s2.voted_for[1]) == NIL  # grants are non-binding


def test_voter_who_hears_a_leader_denies_probe():
    """The thesis-9.6 denial: a voter with recent leader contact refuses."""
    s = base_state(CFG)
    s = s._replace(heard_clock=s.heard_clock.at[1].set(0))  # heard at clock 0
    s2, _ = step(CFG, pv_wire(s, 0, term_prospective=2))
    responded, granted = pv_resp_of(s2.mailbox, 0, 1)
    assert responded and not granted
    # ... while a long-quiet peer still grants on the same tick.
    responded3, granted3 = pv_resp_of(s2.mailbox, 0, 3)
    assert responded3 and granted3


def test_leader_denies_probe():
    s = make_leader(base_state(CFG), 2, 1)
    s2, _ = step(CFG, pv_wire(s, 0, term_prospective=2))
    responded, granted = pv_resp_of(s2.mailbox, 0, 2)
    assert responded and not granted


def test_stale_log_denied_probe():
    from tests.test_handlers import with_log

    s = with_log(base_state(CFG), 1, [1, 1])  # voter's log is ahead
    s2, _ = step(CFG, pv_wire(s, 0, term_prospective=2, last_idx=0, last_term=0))
    responded, granted = pv_resp_of(s2.mailbox, 0, 1)
    assert responded and not granted


def test_pre_quorum_promotes_to_real_candidate():
    """A precandidate holding grant bits from a majority promotes: only then
    does the term bump and a real RequestVote broadcast go out."""
    s = base_state(CFG)
    s = s._replace(
        role=s.role.at[0].set(PRECANDIDATE),
        votes=s.votes.at[0].set(
            bitplane.pack(
                jnp.zeros((5,), bool).at[0].set(True).at[1].set(True).at[2].set(True)
            )
        ),
    )
    s2, _ = step(CFG, s)
    assert int(s2.role[0]) == CANDIDATE
    assert int(s2.term[0]) == 2  # bumped at promotion, not before
    assert int(s2.voted_for[0]) == 0
    assert int(s2.mailbox.req_type[0]) == REQ_VOTE
    assert int(s2.mailbox.req_term[0]) == 2


def test_expiry_starts_probe_not_election():
    s = base_state(CFG)._replace(deadline=jnp.zeros((5,), jnp.int32).at[0].set(0))
    s = s._replace(deadline=s.deadline.at[1].set(1000).at[2].set(1000).at[3].set(1000).at[4].set(1000))
    s2, _ = step(CFG, s)
    assert int(s2.role[0]) == PRECANDIDATE
    assert int(s2.term[0]) == 1  # unchanged
    assert int(s2.mailbox.req_type[0]) == REQ_PREVOTE
    assert int(s2.mailbox.req_term[0]) == 2  # prospective


# -------------------------------------------------------- the disruption property


def _run(cfg, s, inputs, ticks):
    st = jax.jit(lambda s_, i_: raft_batched.step_one(cfg, s_, i_), static_argnums=())
    for _ in range(ticks):
        s, _ = st(s, inputs)
    return s


@pytest.mark.slow
def test_partitioned_node_cannot_depose_a_stable_leader():
    """The headline behavior: isolate one node under a stable leader for a long
    time, then heal. With pre_vote its term never inflates and the leader
    survives the heal; without, the rejoiner's inflated term forces the leader
    out (term adoption -> step down). Slow tier (long eager isolate/heal
    loops both ways; the handler-level probe tests above and the prevote
    parity/fuzz tiers stay tier-1)."""
    for pre_vote, disruptive in ((True, False), (False, True)):
        cfg = RaftConfig(n_nodes=5, log_capacity=8, pre_vote=pre_vote)
        s = init_state(cfg, jax.random.key(0))
        # Elect a stable leader with everyone connected.
        fin, _, _ = scan.run(cfg, s, jax.random.key(1), 60)
        leader = int(np.argmax(np.asarray(fin.role) == LEADER))
        assert int(np.sum(np.asarray(fin.role) == LEADER)) == 1
        victim = (leader + 1) % 5
        lead_term = int(fin.term[leader])
        # Isolate the victim long enough for many timeout cycles.
        s_iso = _run(cfg, fin, isolate(cfg, victim, far=9), 120)
        if pre_vote:
            assert int(s_iso.term[victim]) == lead_term  # term never inflated
        else:
            assert int(s_iso.term[victim]) > lead_term + 3  # classic inflation
        # Heal and run on: does the established leader survive?
        healed = _run(cfg, s_iso, quiet_inputs(cfg, far=9)._replace(
            timeout_draw=jnp.full((5,), 9, jnp.int32)), 12)
        still_leader = int(healed.role[leader]) == LEADER
        assert still_leader == (not disruptive)
        if pre_vote:
            assert int(np.max(np.asarray(healed.term))) == lead_term


def test_prevote_cluster_elects_and_commits():
    """Liveness from cold start: pre-vote rounds still elect, client commands
    still commit, invariants hold, and terms stay minimal (one probe round +
    one real election = term 2)."""
    cfg = RaftConfig(n_nodes=5, client_interval=8, pre_vote=True)
    _, m = scan.simulate(cfg, 0, 64, 400)
    md = jax.device_get(m)
    assert int(md.violations.sum()) == 0
    assert int((md.first_leader_tick < 2**31 - 1).sum()) == 64
    assert int(md.min_commit.min()) > 0
    assert int(md.max_term.max()) <= 3  # no term churn on a reliable net


def test_prevote_under_partition_fuzz_is_safe():
    cfg = RaftConfig(
        n_nodes=5, partition_period=32, partition_prob=0.5, pre_vote=True,
        check_log_matching=True, client_interval=8,
    )
    _, m = scan.simulate(cfg, 0, 48, 400)
    md = jax.device_get(m)
    assert int(md.violations.sum()) == 0
    assert int((md.first_leader_tick < 2**31 - 1).sum()) > 40
