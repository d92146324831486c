"""Lease-based reads (thesis 6.4.1; ISSUE 11): the lease serve predicate,
the thesis-4.2.3 vote denial it leans on, the read_fr staleness anchor, and
the viol_read_stale device invariant.

Kernel-vs-oracle bit-exactness rides tests/test_oracle_parity.py
(n5-lease-reads); this file pins the protocol semantics directly: a leader
with a fresh ack quorum serves in ONE tick with no confirmation round, an
expired lease falls back to confirmation, voters deny RequestVote while
lease-quiet (and stop denying after the local-clock window / a restart), a
stale lease serve raises viol_read_stale (and ONLY a stale one), and the
frozen lease-skew corpus artifact's genome leaves the REAL kernel clean.

Program budget: the semantic tests drive single `step` calls (tiny jit
programs, two configs); the real-kernel corpus replay is one small traced
scan; the trace-checker rejection of the lease mutant rides the slow tier
(CI's serve smoke runs the fleet-scale version every push).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.scenario.mutation import mutant_config
from raft_sim_tpu.sim import scan
from raft_sim_tpu.types import (
    FOLLOWER,
    LEADER,
    NIL,
    REQ_VOTE,
    StepInputs,
    with_commit_chk,
)

CORPUS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "corpus", "lease-skew-n5.json"
)

# Scheduled-read lease tier: the read gate is read_interval > 0, but the
# cadence is parked far out so tests drive read offers explicitly via the
# read_cmd input (the Session.offer_read / serve-plane path).
LCFG = RaftConfig(
    n_nodes=5,
    log_capacity=8,
    election_min_ticks=12,
    election_range_ticks=6,
    client_interval=4,
    read_interval=1000,
    read_lease_ticks=4,
)


def _quiet_inputs(cfg: RaftConfig, **over) -> StepInputs:
    n = cfg.n_nodes
    base = dict(
        deliver_mask=bitplane.pack(jnp.ones((n, n), bool), axis=1),
        skew=jnp.ones((n,), jnp.int32),
        timeout_draw=jnp.full((n,), 10_000, jnp.int32),
        client_cmd=jnp.int32(NIL),
        client_target=jnp.int32(0),
        client_bounce=jnp.zeros((cfg.client_pipeline,), jnp.int32),
        alive=jnp.ones((n,), bool),
        restarted=jnp.zeros((n,), bool),
        reconfig_cmd=jnp.int32(NIL),
        transfer_cmd=jnp.int32(NIL),
        read_cmd=jnp.int32(NIL),
    )
    base.update(over)
    return StepInputs(**base)


def _leader_state(cfg, ack_age_val=0):
    """Node 0 an established leader of term 2 with one current-term committed
    entry (the 6.4 capture gate), deadlines parked, acks at `ack_age_val`."""
    n = cfg.n_nodes
    s = init_state(cfg, jax.random.key(0))
    s = s._replace(
        role=s.role.at[0].set(LEADER),
        term=jnp.full((n,), 2, jnp.int32),
        leader_id=jnp.zeros((n,), jnp.int32),
        log_term=s.log_term.at[:, 0].set(2),
        log_val=s.log_val.at[:, 0].set(41),
        log_tick=s.log_tick.at[:, 0].set(1),
        log_len=jnp.ones((n,), jnp.int32),
        commit_index=jnp.ones((n,), jnp.int32),
        lat_frontier=jnp.int32(1),
        ack_age=jnp.full((n, n), ack_age_val, s.ack_age.dtype),
        deadline=jnp.full((n,), 10_000, jnp.int32),
    )
    return with_commit_chk(s)


def test_lease_serves_in_one_tick_with_no_confirmation_round():
    """Capture then serve on the NEXT tick purely from the fresh ack quorum:
    zero AppendEntries confirmation responses ever arrive (the mailbox stays
    quiet), yet the read serves with latency 1 -- the zero-quorum-round
    steady state 6.4.1 promises. The slot and its staleness anchor clear."""
    step = jax.jit(lambda st, i: raft_batched.step_one(LCFG, st, i))
    s = _leader_state(LCFG)
    s, info = step(s, _quiet_inputs(LCFG, read_cmd=jnp.int32(1)))
    assert int(s.read_idx[0]) == 2  # captured commit 1 (+1 encoding)
    assert int(s.read_fr[0]) == 1  # frontier banked at capture
    assert int(info.reads_served) == 0
    s, info = step(s, _quiet_inputs(LCFG))
    assert int(info.reads_served) == 1
    assert int(info.read_lat_sum) == 1  # offer tick -> next tick
    assert not bool(info.viol_read_stale)
    assert int(s.read_idx[0]) == 0 and int(s.read_fr[0]) == 0
    assert not bool(scan.step_bad(info))


def test_expired_lease_withholds_the_serve():
    """With every ack older than the lease window (and no confirmation
    responses), the pending read stays pending: the lease never serves on
    stale acknowledgments."""
    step = jax.jit(lambda st, i: raft_batched.step_one(LCFG, st, i))
    s = _leader_state(LCFG, ack_age_val=50)
    s, _ = step(s, _quiet_inputs(LCFG, read_cmd=jnp.int32(1)))
    assert int(s.read_idx[0]) == 2
    for _ in range(3):
        s, info = step(s, _quiet_inputs(LCFG))
        assert int(info.reads_served) == 0
        assert int(s.read_idx[0]) == 2  # still pending, never served


def test_stale_lease_serve_raises_viol_read_stale_and_only_stale():
    """A served read whose captured index sits below its banked
    capture-frontier is the linearizability break: viol_read_stale fires and
    folds into the violations predicate (scan.step_bad -- the hunt's fitness
    signal). The legal twin (anchor covered by the capture) stays clean."""
    step = jax.jit(lambda st, i: raft_batched.step_one(LCFG, st, i))
    base = _leader_state(LCFG)
    stale = base._replace(
        read_idx=base.read_idx.at[0].set(2),   # captured commit 1...
        read_tick=base.read_tick.at[0].set(1),
        read_fr=base.read_fr.at[0].set(3),     # ...but 3 were committed at issue
    )
    _, info = step(stale, _quiet_inputs(LCFG))
    assert int(info.reads_served) == 1  # the lease DID serve it
    assert bool(info.viol_read_stale)
    assert bool(scan.step_bad(info))
    legal = base._replace(
        read_idx=base.read_idx.at[0].set(2),
        read_tick=base.read_tick.at[0].set(1),
        read_fr=base.read_fr.at[0].set(1),     # capture covered the frontier
    )
    _, info = step(legal, _quiet_inputs(LCFG))
    assert int(info.reads_served) == 1
    assert not bool(info.viol_read_stale)


def test_lease_vote_denial_and_local_clock_expiry():
    """Thesis 4.2.3 under the lease gate: a voter that heard a leader within
    election_min_ticks of LOCAL clock denies RequestVote outright; once the
    local window elapses (or a restart wipes the memory), it grants."""
    n = LCFG.n_nodes
    step = jax.jit(lambda st, i: raft_batched.step_one(LCFG, st, i))
    s = init_state(LCFG, jax.random.key(1))
    mb = s.mailbox
    # Node 1 broadcasts an up-to-date RequestVote at everyone's term.
    s = s._replace(
        term=jnp.full((n,), 2, jnp.int32),
        role=s.role.at[1].set(1),  # CANDIDATE
        deadline=jnp.full((n,), 10_000, jnp.int32),
        heard_clock=jnp.zeros((n,), jnp.int32),  # heard a leader "just now"
        mailbox=mb._replace(
            req_type=mb.req_type.at[1].set(REQ_VOTE),
            req_term=mb.req_term.at[1].set(2),
        ),
    )
    s2, _ = step(s, _quiet_inputs(LCFG))
    assert int(np.sum(np.asarray(s2.mailbox.v_to) != NIL)) == 0  # all denied
    # Same request against voters whose local clocks long passed the window.
    s3 = s._replace(heard_clock=jnp.full((n,), -50, jnp.int32))
    s4, _ = step(s3, _quiet_inputs(LCFG))
    granted = np.asarray(s4.mailbox.v_to)
    assert (granted[np.arange(n) != 1] == 1).any()  # grants flowed to node 1
    # A restarted voter holds no lease obligation: wipe -> immediate grant.
    s5, _ = step(
        s,
        _quiet_inputs(
            LCFG,
            restarted=jnp.asarray([False, False, True, False, False]),
        ),
    )
    # The restarted node misses THIS delivery (messages to a restarting node
    # die with it) but its heard_clock is wiped to "long quiet":
    assert int(s5.heard_clock[2]) == -LCFG.election_min_ticks
    assert int(s5.read_fr[2]) == 0  # the staleness anchor dies with the slot


def test_config_validator_pins_the_lease_bounds():
    with pytest.raises(AssertionError, match="skew-safe bound"):
        RaftConfig(n_nodes=5, client_interval=4, read_interval=3,
                   read_lease_ticks=4)  # default election_min 6 < 2*4+4
    with pytest.raises(AssertionError, match="ReadIndex plane"):
        RaftConfig(n_nodes=5, client_interval=4, election_min_ticks=12,
                   read_lease_ticks=4)
    with pytest.raises(AssertionError, match="offer-tick plane"):
        RaftConfig(n_nodes=5, read_interval=3, election_min_ticks=12,
                   read_lease_ticks=4)
    # Leases + TimeoutNow transfers COEXIST since the disruptive-RequestVote
    # override (ISSUE 13): the PR-11 mutual-exclusion validator is gone.
    # The deterministic transfer-under-lease completion is pinned in
    # tests/test_reconfig.py::test_transfer_overrides_lease_denial_*.
    RaftConfig(n_nodes=5, client_interval=4, read_interval=3,
               election_min_ticks=14, read_lease_ticks=4,
               transfer_interval=9)


def test_zero_cost_when_off_carry_contract():
    """The policy side of zero-cost-when-off: read_fr (and heard_clock,
    absent pre_vote) are loop-invariant legs on every non-lease config, and
    go live under the gate. The lowered-program side is pinned by the
    byte-identical disabled-mode step goldens (tests/test_golden_jaxpr.py)
    and the Pass A carry-passthrough rule over the preset matrix."""
    from raft_sim_tpu.analysis import policy

    plain = RaftConfig(n_nodes=5, client_interval=4, read_interval=3)
    inv = policy.invariant_leaves(plain)
    assert "read_fr" in inv and "heard_clock" in inv
    inv_lease = policy.invariant_leaves(LCFG)
    assert "read_fr" not in inv_lease and "heard_clock" not in inv_lease
    assert "read_fr" in policy.invariant_leaves(RaftConfig(n_nodes=5))


def test_corpus_artifact_shape():
    """The frozen lease-skew hit: found by the hunt, shrunk with the SKEW
    mechanism retained (ablating it kills the violation -- the clock
    assumption is load-bearing), named viol_read_stale. (tests/
    test_corpus.py replays the mutant side bit-exactly in tier 1.)"""
    with open(CORPUS) as f:
        art = json.load(f)
    assert art["mutant"] == "lease-skew"
    assert art["kinds"] == ["viol_read_stale"]
    assert art["segments"][0]["clock_skew_prob"] > 0
    assert art["config"]["read_lease_ticks"] > 0


@pytest.mark.slow
def test_corpus_genome_leaves_real_kernel_clean():
    """The REAL kernel replayed over the corpus hit's identical (genome,
    seed, cluster, horizon) is clean: the skew-safe lease bound holds where
    the mutant's no-skew bound breaks. Slow tier (one fresh scan compile):
    the CI lease smoke replays the real kernel FLEET-wide every push, and
    tier-1's corpus replay pins the mutant side bit-exactly."""
    with open(CORPUS) as f:
        art = json.load(f)
    from raft_sim_tpu.scenario import genome as gm
    from raft_sim_tpu.scenario.shrink import _replay_fn, _single_cluster

    real_cfg = RaftConfig(**art["config"])
    g = gm.from_raw(art["genome_raw"])
    state, key = _single_cluster(
        real_cfg, art["seed"], art["batch"], art["cluster"]
    )
    _, metrics, _ = _replay_fn(real_cfg, int(art["ticks"]), int(art["seg_len"]))(
        state, key, g
    )
    assert int(np.asarray(metrics.violations)) == 0


@pytest.mark.slow
def test_checker_rejects_lease_mutant_naming_read_linearizability():
    """Whole-history form of the corpus hit: the lease-skew mutant's fleet
    under the hunted genome, traced, fails read_linearizability with the
    minimal (issue, serve) witness; the REAL kernel over the identical fleet
    passes all six properties -- under skew. Slow tier: two fleet-scale
    trace-variant programs (CI's serve smoke runs the same legs per push)."""
    from raft_sim_tpu.sim import telemetry
    from raft_sim_tpu.trace import checker as tchecker
    from raft_sim_tpu.trace import history as thistory
    from raft_sim_tpu.trace.ring import TraceSpec

    with open(CORPUS) as f:
        art = json.load(f)
    from raft_sim_tpu.scenario import genome as gm

    real_cfg = dataclasses.replace(
        RaftConfig(**art["config"]), track_trace=True
    )
    mut_cfg = mutant_config("lease-skew", real_cfg)
    g = gm.broadcast(gm.from_raw(art["genome_raw"]), art["batch"])
    spec = TraceSpec(depth=512)
    out = telemetry.simulate_windowed(
        mut_cfg, art["seed"], art["batch"], 768, 64, 0, g, 1, spec
    )
    rep = tchecker.check_history(thistory.from_device(out[4]))
    assert "read_linearizability" in rep.violated
    w = rep.results["read_linearizability"].witness
    assert [e["kind"] for e in w] == ["read_issue", "read_serve"]
    out_real = telemetry.simulate_windowed(
        real_cfg, art["seed"], art["batch"], 768, 64, 0, g, 1, spec
    )
    rep_real = tchecker.check_history(thistory.from_device(out_real[4]))
    assert rep_real.complete, rep_real.problems
    assert rep_real.ok, {
        k: r.note for k, r in rep_real.results.items() if not r.ok
    }
