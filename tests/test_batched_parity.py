"""Batch-minor kernel parity: models/raft_batched.step_b stepped at B = 16 is
held, every tick, to the scalar oracle (tests/oracle.py) on a sample of its
clusters -- the layout the chip runs, checked the way the benchmark's `correct`
samples clusters -- and the fleet scan (`scan.run_batch_minor`) is held to the
single-cluster traced scan (`scan.run`) cluster by cluster."""

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_batch
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import log_ops
from raft_sim_tpu.sim import faults, scan
from tests import oracle
from tests.test_oracle_parity import assert_state_equal


def wide_ring(compaction: bool, durable: bool = False) -> RaftConfig:
    """A tracked 3-node config on a ring longer than one tile (CAP 300 >
    log_ops._TILE), under loss, partitions and kills: the latency pass
    reads a window above the frontier there, and sweeps the whole ring on
    ticks whose commit advance overruns the window. `durable` adds the
    storage plane of etcd5-wal's cell (a flush each tick, 5% of them late,
    torn tails on half the restarts), which needs the prefix log."""
    disk = dict(fsync_interval=1, fsync_jitter_prob=0.05, torn_tail_prob=0.5,
                lost_suffix_span=8) if durable else {}
    return RaftConfig(
        n_nodes=3,
        log_capacity=300,
        compact_margin=8 if compaction else 0,
        client_interval=1,
        drop_prob=0.1,
        partition_period=16,
        partition_prob=0.5,
        crash_prob=0.5,
        crash_period=20,
        crash_down_ticks=10,
        **disk,
    )


# Every config below but the last is also pinned one cluster at a time
# against the oracle in tests/test_oracle_parity.py; these rows hold the
# batch-minor layout itself (B = 16, a sample of its clusters) to it, a few
# seconds each.
CONFIGS = [
    pytest.param(RaftConfig(n_nodes=5, client_interval=8), id="n5"),
    pytest.param(
        RaftConfig(
            n_nodes=7,
            log_capacity=6,
            max_entries_per_rpc=2,
            client_interval=2,
            drop_prob=0.3,
            clock_skew_prob=0.2,
            check_log_matching=True,
        ),
        id="n7-faults",
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            client_interval=4,
            drop_prob=0.1,
            crash_prob=0.5,
            crash_period=20,
            crash_down_ticks=10,
        ),
        id="n5-crashes",
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
            check_log_matching=True,
        ),
        id="n5-compaction-snap",  # ring wrap + rebase + InstallSnapshot sentinel,
        # wide (int32) index planes, ring-aware log-matching check
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
        id="n5-redirect-compaction",  # 302 routing state + latency metric riding
        # the compaction ring
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
        id="n5-redirect-pipeline",  # K = 4 in-flight slots ([K, B] client state)
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
        id="n5-prevote",  # thesis-9.6 probe rounds under churn
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
        id="n5-reconfig-truncation",  # log-carried configs under partition +
        # crash churn: per-node derived member rows diverging and rolling
        # back with truncations must match the oracle bit-for-bit in the
        # batch-minor layout.
    ),
    pytest.param(
        RaftConfig(
            n_nodes=5,
            log_capacity=8,
            compact_margin=4,
            client_interval=1,
            reconfig_interval=5,
            drop_prob=0.2,
            crash_prob=0.5,
            crash_period=20,
            crash_down_ticks=12,
        ),
        id="n5-reconfig-compaction",  # config entries compacting away:
        # fold_span snapshot-context advance + req_base_mold install on
        # snapshot catch-up, vs the oracle
    ),
    pytest.param(wide_ring(True), id="n3-cap300-latency-window"),
]


# Clusters of the B = 16 fleet checked against the oracle every tick.
SAMPLE = (0, 5, 10, 15)


def tree_eq(a, b):
    for x, y in zip(jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(jax.device_get(b))):
        np.testing.assert_array_equal(x, y)


def take(tree, c):
    return jax.tree.map(lambda x: x[c], tree)


@pytest.mark.parametrize("cfg", CONFIGS)
def test_step_parity_along_trajectory(cfg):
    """Step the batch-minor kernel at B = 16 for 120 ticks; at every tick each
    sampled cluster's state (mailbox included) equals the oracle's, stepped
    alone on that cluster's inputs (covers elections, replication, faults,
    injection, timers as they actually occur)."""
    batch = 16
    key = jax.random.key(0)
    k_init, k_run = jax.random.split(key)
    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)

    draw = jax.jit(jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now)))
    bstep = jax.jit(lambda s, i: raft_batched.step_b(cfg, s, i))

    want = {c: oracle.state_to_dict(take(state, c), cfg) for c in SAMPLE}
    now = state.now
    s_min = raft_batched.to_batch_minor(state)
    for t in range(120):
        inp = jax.device_get(draw(keys, now))
        s_min, _ = bstep(s_min, raft_batched.to_batch_minor(inp))
        got = jax.device_get(raft_batched.from_batch_minor(s_min))
        now = got.now
        for c in SAMPLE:
            inp_c = {f: np.asarray(v)[c] for f, v in zip(inp._fields, inp)}
            want[c] = oracle.oracle_step(cfg, want[c], inp_c)
            assert_state_equal(oracle.state_to_dict(take(got, c), cfg), want[c], t)


def test_run_batch_minor_matches_run_batch():
    """The fleet scan at B = 32 against `scan.run` for each cluster: every
    cluster follows the trajectory it has alone (batch independence), and
    the batch-minor metrics transpose back to the public layout."""
    cfg = RaftConfig(n_nodes=5, client_interval=8, drop_prob=0.1)
    batch = 32
    key = jax.random.key(3)
    k_init, k_run = jax.random.split(key)
    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)

    f_min, m_min = jax.jit(lambda s, k: scan.run_batch_minor(cfg, s, k, 250))(state, keys)
    f_ref, m_ref, _ = jax.jit(jax.vmap(lambda s, k: scan.run(cfg, s, k, 250)))(state, keys)
    tree_eq(f_ref, f_min)
    tree_eq(m_ref, m_min)


@pytest.mark.parametrize(
    "compaction, durable",
    [(True, False), (False, False), (False, True)],
    ids=["ring", "prefix", "prefix-durable"],
)
def test_latency_window_matches_whole_ring_along_trajectory(
    compaction, durable, monkeypatch
):
    """`wide_ring` at B = 16 for 150 ticks with a 2-entry latency window,
    so that both forms of the pass run (`lat_ring_ticks` marks the ticks
    that swept the ring), against the same run with the window off (every
    tick sweeps the ring) and against the same run on a log of one tile
    (`log_ops._TILE` past CAP: the whole-ring pass before compaction and
    injection, the prefix log's placement too, and every window read by
    its one-hot): every latency leaf of StepInfo is equal on every tick."""
    cfg = wide_ring(compaction, durable)
    batch = 16
    k_init, k_run = jax.random.split(jax.random.key(1))
    state = raft_batched.to_batch_minor(init_batch(cfg, k_init, batch))
    keys = jax.random.split(k_run, batch)
    draw = jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now))

    def run(window, tile=log_ops._TILE):
        monkeypatch.setattr(log_ops, "LAT_WINDOW", window)
        monkeypatch.setattr(log_ops, "_TILE", tile)

        def tick(s, _):
            inp = raft_batched.to_batch_minor(draw(keys, s.now))
            return raft_batched.step_b(cfg, s, inp)

        return jax.device_get(
            jax.jit(lambda s: jax.lax.scan(tick, s, None, length=150))(state)
        )

    (end_w, win), (end_r, ring), (end_1, one) = (
        run(2), run(-1), run(2, tile=2 * cfg.log_capacity)
    )
    swept = win.lat_ring_ticks[:, 0]
    assert (win.lat_ring_ticks == swept[:, None]).all()  # batch-wide
    assert 0 < swept.sum() < len(swept)  # both forms ran
    assert ring.lat_ring_ticks.all()
    assert not one.lat_ring_ticks.any()
    assert win.lat_cnt.sum() > 0
    for f in ("lat_sum", "lat_cnt", "lat_hist", "lat_excluded"):
        np.testing.assert_array_equal(getattr(win, f), getattr(ring, f), err_msg=f)
        np.testing.assert_array_equal(getattr(win, f), getattr(one, f), err_msg=f)
    for end in (end_r, end_1):
        tree_eq(end_w, end)
