"""Kernel-vs-oracle parity: the vectorized `where`-lattice in models/raft_batched.py
(the kernel the chip runs, stepped one cluster at a time through `step_one`) must
agree bit-for-bit, tick by tick, with the scalar Python oracle (tests/oracle.py)
across randomized trajectories including faults -- the mitigation SURVEY.md
section 7.3 calls for against branch-precedence bugs."""

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.sim import faults
from tests import oracle


def assert_state_equal(got: dict, want: dict, tick: int):
    for f, g in got.items():
        if f == "mailbox":
            for mf, mg in g.items():
                np.testing.assert_array_equal(
                    mg, want["mailbox"][mf], err_msg=f"tick {tick}: mailbox.{mf}"
                )
        else:
            np.testing.assert_array_equal(g, want[f], err_msg=f"tick {tick}: {f}")


CONFIGS = [
    pytest.param(RaftConfig(n_nodes=3, log_capacity=8, client_interval=3), 0, id="n3"),
    pytest.param(
        RaftConfig(n_nodes=5, log_capacity=8, max_entries_per_rpc=2, client_interval=2),
        1,
        id="n5-narrow-rpc",
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=6,  # tiny: exercises capacity clipping
            client_interval=1,
            drop_prob=0.25,
            clock_skew_prob=0.2,
        ),
        2,
        id="n5-faults",
    ),
    pytest.param(
        RaftConfig(
            n_nodes=4,  # even cluster size: quorum = 3
            log_capacity=8,
            client_interval=4,
            drop_prob=0.15,
            partition_period=10,
            partition_prob=0.7,
        ),
        3,
        id="n4-partitions",
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=3,
            drop_prob=0.1,
            crash_prob=0.5,
            crash_period=20,
            crash_down_ticks=10,
        ),
        4,
        id="n5-crashes",
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=2,
            drop_prob=0.5,  # heavy loss: peers regularly fall out of the ack window
            ack_timeout_ticks=7,  # the tightest legal horizon (heartbeat 3 + 4)
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=12,
        ),
        5,
        id="n5-ack-window",  # exercises responsiveness exclusion + re-admission in
        # the shared-window start (the no-responsive fallback needs a deterministic
        # scenario: test_handlers.test_window_fallback_when_no_peer_responsive)
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            max_entries_per_rpc=2,  # narrow window: offsets/backpressure live
            client_interval=1,
            drop_prob=0.3,
            crash_prob=0.5,
            crash_period=20,
            crash_down_ticks=10,
            compact_planes=True,
        ),
        17,
        id="n5-compact-crashes",  # the compacted carry layout (ops/tile.py)
        # vs the oracle's independently restated unpacking, with crashes +
        # heavy drop so conflict TRUNCATIONS and snapshot-free catch-up cross
        # the compacted entry channel (bit-packed req_off offsets, flattened
        # ent windows) every few ticks
    ),
    pytest.param(
        RaftConfig(n_nodes=3, log_capacity=8, compact_margin=4, client_interval=1),
        6,
        id="n3-compaction",  # 150 commands through an 8-slot ring: continuous
        # rebase + wrapped appends, absolute indices far past CAP
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            max_entries_per_rpc=2,
            client_interval=1,
            drop_prob=0.2,
            crash_prob=0.5,
            crash_period=20,
            crash_down_ticks=12,
        ),
        7,
        id="n5-compaction-snap",  # crashed nodes fall below the leader's base and
        # catch up via the InstallSnapshot sentinel (keep AND wipe paths)
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=2,
            client_redirect=True,
            drop_prob=0.2,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        8,
        id="n5-redirect",  # the 302 write path: random targets, redirect bounces,
        # leaderless random-peer fallback, busy-client drops -- under faults
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            client_interval=2,
            client_redirect=True,
            drop_prob=0.2,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        9,
        id="n5-redirect-compaction",  # routing state and election no-ops riding
        # the compaction ring (the full round-4 feature interaction)
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            client_interval=1,
            client_redirect=True,
            client_pipeline=4,
            drop_prob=0.2,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        10,
        id="n5-redirect-pipeline",  # K = 4 commands in flight: slot fill/free
        # churn, per-node lowest-slot acceptance, parallel accepts at
        # split-brain leaders, per-slot bounce draws
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=3,
            pre_vote=True,
            drop_prob=0.25,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        11,
        id="n5-prevote",  # thesis-9.6 probes under churn: precandidate rounds,
        # per-edge grant bits, promotions, prospective-term non-adoption
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            client_interval=2,
            pre_vote=True,
            drop_prob=0.25,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        12,
        id="n5-prevote-compaction",  # the pre_vote x compaction interaction
        # (VERDICT weak #3): precandidate probes judged against ring logs whose
        # last-entry position wraps, election no-ops burning ring reserve while
        # probes defer the term bump, snapshot catch-up of crashed probers
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=2,
            reconfig_interval=11,
            transfer_interval=13,
            read_interval=3,
            drop_prob=0.2,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        13,
        id="n5-reconfig-plane",  # all three thesis extensions at once, under
        # drop + crash churn: joint entry/exit + dual quorums + removed-leader
        # stepdown, transfer lease/fire/receipt elections, read capture/
        # confirm/serve -- the full raft_sim_tpu/reconfig surface vs the oracle
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            client_interval=1,
            reconfig_interval=11,
            transfer_interval=13,
            read_interval=3,
            pre_vote=True,
            drop_prob=0.2,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        14,
        id="n5-reconfig-prevote-compaction",  # the reconfiguration plane
        # crossed with BOTH other structural gates: TimeoutNow's pre-vote
        # bypass, masked pre-quorums, ring-log current-term read captures
        marks=pytest.mark.slow,  # budget re-tier (ISSUE 13): the triple
        # interaction is the largest program in this file, and its pairwise
        # surfaces stay tier-1 (n5-reconfig-plane, n5-prevote-compaction,
        # n5-reconfig-truncation) -- the full cross rides the slow tier to
        # pay for the two new log-carried corpus replays.
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=2,
            reconfig_interval=5,
            transfer_interval=2,
            drop_prob=0.25,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        15,
        # Slow tier (tier-1 budget): the deterministic transfer-during-joint
        # interaction is pinned step by step in tier-1
        # (tests/test_reconfig.py::test_transfer_fires_and_elects_during_
        # joint_phase), and the n5-reconfig-plane row keeps the
        # transfer x membership machinery oracle-swept every tier-1 run;
        # this row adds the denser randomized interleaving sweep.
        marks=pytest.mark.slow,
        id="n5-transfer-during-joint",  # PR 10's named follow-up: a dense
        # transfer cadence (every 2 ticks) against a 5-tick membership
        # cadence under churn keeps TimeoutNow transfers pending, firing,
        # and received WHILE joint phases are open -- dual-quorum elections
        # of transfer targets, transfer aborts at removed-leader stepdown,
        # lease handoffs across epoch bumps (the deterministic interaction
        # is pinned in tests/test_reconfig.py; this row sweeps it vs the
        # oracle under randomized fault interleavings)
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=1,
            reconfig_interval=3,
            drop_prob=0.25,
            partition_period=8,
            partition_prob=0.8,
            crash_prob=0.5,
            crash_period=14,
            crash_down_ticks=8,
        ),
        11,
        id="n5-reconfig-truncation",  # the log-carried config rollback
        # surface: a dense membership cadence under partition + crash churn
        # keeps minority leaders appending config entries that the healed
        # majority then truncates -- per-node derived configs must diverge
        # (86 of 150 ticks at this seed), roll back with the truncation
        # (cfg_epoch decreases mid-run), and re-derive bit-for-bit against
        # the oracle every tick (ISSUE 13 acceptance row)
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            election_min_ticks=12,
            election_range_ticks=6,
            client_interval=2,
            read_interval=3,
            read_lease_ticks=4,
            drop_prob=0.2,
            clock_skew_prob=0.3,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        16,
        id="n5-lease-reads",  # the ISSUE-11 lease plane vs the oracle under
        # skew + drop + crash churn: the lease serve predicate over ack_age
        # quorums, the thesis-4.2.3 vote denial on skewed local clocks, and
        # the read_fr staleness anchor riding capture/serve/cancel/restart
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=2,
            fsync_interval=3,
            fsync_jitter_prob=0.25,
            torn_tail_prob=0.3,
            lost_suffix_span=3,
            drop_prob=0.2,
            crash_prob=0.5,
            crash_period=16,
            crash_down_ticks=8,
        ),
        18,
        id="n5-durable-crashes",  # the storage plane vs the oracle under
        # crash churn: fsync watermark advance (with jitter stalls), the ack
        # clamp + durable leader self-match, the vote-exposure gate with its
        # late-grant completion responses, and crash recovery truncating the
        # torn un-fsynced suffix back to the durable floor
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            max_entries_per_rpc=2,
            client_interval=1,
            fsync_interval=4,
            fsync_jitter_prob=0.3,
            torn_tail_prob=0.4,
            lost_suffix_span=4,
            pre_vote=True,
            drop_prob=0.25,
            crash_prob=0.5,
            crash_period=14,
            crash_down_ticks=8,
            compact_planes=True,
        ),
        19,
        id="n5-durable-prevote-compact",  # durability x pre_vote x the
        # compacted carry layout: late-grant responses racing prevote
        # promotions and AE responses on the same edges, recovery truncation
        # of logs carried bit-packed, narrow-RPC catch-up after torn tails
    ),
]


def run_parity(cfg, state, k_run, ticks):
    step = jax.jit(lambda s, i: raft_batched.step_one(cfg, s, i)[0])
    s_oracle = oracle.state_to_dict(state, cfg)
    for t in range(ticks):
        inp = faults.make_inputs(cfg, k_run, state.now)
        inp_np = {f: np.asarray(v) for f, v in zip(inp._fields, inp)}
        state = step(state, inp)
        s_oracle = oracle.oracle_step(cfg, s_oracle, inp_np)
        assert_state_equal(oracle.state_to_dict(state, cfg), s_oracle, t)
    return state


@pytest.mark.parametrize("cfg,seed", CONFIGS)
def test_trajectory_parity(cfg, seed):
    key = jax.random.key(seed)
    k_init, k_run = jax.random.split(key)
    run_parity(cfg, init_state(cfg, k_init), k_run, ticks=150)


def test_parity_at_int16_index_boundary():
    """CAP-scale log indices riding the narrow planes: next/match and the
    per-responder match/hint wire fields (int16) near the MAX_LOG_CAPACITY = 4095
    ceiling. The small-CAP rows above never push an index past 8; here every node
    starts with ~3980 committed-prefix entries, so election bookkeeping, append
    acks, and capacity rejection all run with indices in the 3980..4095 range --
    checked against the oracle bit-for-bit, including commit_chk over the 3970-deep
    prefix."""
    import jax.numpy as jnp

    from raft_sim_tpu.types import with_commit_chk
    from raft_sim_tpu.utils.config import MAX_LOG_CAPACITY

    cfg = RaftConfig(
        n_nodes=5,
        log_capacity=MAX_LOG_CAPACITY,
        max_entries_per_rpc=8,
        client_interval=1,
    )
    key = jax.random.key(6)
    k_init, k_run = jax.random.split(key)
    state = init_state(cfg, k_init)

    # Identical 3980-entry term-1 logs on every node, 3970 of them committed.
    pre = 3980
    n = cfg.n_nodes
    lt = state.log_term.at[:, :pre].set(1)
    lv = state.log_val.at[:, :pre].set(
        jnp.broadcast_to(jnp.arange(pre, dtype=jnp.int32), (n, pre))
    )
    state = with_commit_chk(
        state._replace(
            log_term=lt,
            log_val=lv,
            log_len=jnp.full((n,), pre, jnp.int32),
            commit_index=jnp.full((n,), pre - 10, jnp.int32),
        )
    )

    final = run_parity(cfg, state, k_run, ticks=60)
    # The run must actually have driven indices past the prefill: a leader exists
    # and appended client commands toward the capacity ceiling.
    assert int(np.max(np.asarray(final.log_len))) > pre
    assert int(np.max(np.asarray(final.match_index))) > pre - 10
