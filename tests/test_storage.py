"""Durable storage plane (raft_sim_tpu/storage; ISSUE 19): lost-suffix
recovery truncation at word-edge cluster sizes, durability x compacted-carry
bit-exactness, checkpoint v25, and the durability_lag SLI.

The oracle-parity rows in test_oracle_parity.py carry the per-tick
correctness claim (n5-durable-* rows); this file pins the
plane's EDGES: the recovery arithmetic through the real kernel at N
straddling the 32-bit vote-plane word boundary (31/32/33 -- elections over
packed vote words are live around every recovery), the layout-independence
of the dur watermark legs, and the persistence/health surfaces."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import tile
from raft_sim_tpu.sim import faults, scan
from raft_sim_tpu.storage import plane
from raft_sim_tpu.types import NIL, compact_twin
from raft_sim_tpu.utils import checkpoint
from raft_sim_tpu.utils.config import PRESETS


def _dur_cfg(n, **kw):
    base = dict(
        n_nodes=n,
        log_capacity=16,
        client_interval=2,
        fsync_interval=3,
        fsync_jitter_prob=0.25,
        torn_tail_prob=0.5,
        lost_suffix_span=5,
    )
    base.update(kw)
    return RaftConfig(**base)


# ------------------------------------------------- plane helpers vs numpy


@pytest.mark.parametrize("n", [31, 32, 33])
def test_plane_helpers_match_numpy_reference(n):
    """recover/covered/flush restated independently in numpy, fuzzed over
    word-edge-sized vectors including the extremes (torn 0, torn > log_len,
    dur_len == log_len)."""
    cfg = _dur_cfg(n)
    rng = np.random.default_rng(n)
    log_len = rng.integers(0, 17, n).astype(np.int32)
    dur_len = np.minimum(rng.integers(0, 17, n), log_len).astype(np.int32)
    torn = rng.integers(0, 20, n).astype(np.int32)  # may exceed log_len
    torn[0], torn[-1] = 0, 19
    rs = rng.integers(0, 2, n).astype(bool)
    term = rng.integers(1, 6, n).astype(np.int32)
    dur_term = np.minimum(rng.integers(1, 6, n), term).astype(np.int32)
    vote = rng.integers(-1, n, n).astype(np.int32)
    dur_vote = rng.integers(-1, n, n).astype(np.int32)

    rec = np.maximum(dur_len, log_len - torn)
    np.testing.assert_array_equal(
        np.asarray(plane.recovered_log_len(
            jnp.asarray(dur_len), jnp.asarray(log_len), jnp.asarray(torn))),
        rec,
    )
    r_term, r_vote, r_len = plane.recover(
        cfg, jnp.asarray(rs), jnp.asarray(torn),
        jnp.asarray(dur_len), jnp.asarray(dur_term), jnp.asarray(dur_vote),
        jnp.asarray(term), jnp.asarray(vote), jnp.asarray(log_len),
    )
    np.testing.assert_array_equal(np.asarray(r_len), np.where(rs, rec, log_len))
    np.testing.assert_array_equal(
        np.asarray(r_term), np.where(rs, dur_term, term))
    np.testing.assert_array_equal(
        np.asarray(r_vote), np.where(rs, dur_vote, vote))
    np.testing.assert_array_equal(
        np.asarray(plane.covered(
            jnp.asarray(dur_term), jnp.asarray(dur_vote),
            jnp.asarray(term), jnp.asarray(vote))),
        (dur_term == term) & (dur_vote == vote) & (vote != NIL),
    )


# --------------------------------------- kernel recovery at word edges


@pytest.mark.parametrize("n", [31, 32, 33])
def test_kernel_lost_suffix_truncation_word_edges(n):
    """One real-kernel tick with forced restarts and torn-tail draws at N
    straddling the vote-plane word boundary: every restarted node's log is
    truncated to max(dur_len, log_len - torn_drop) -- the fsync watermark
    FLOORS the recovered length (the durable prefix never tears) -- and
    non-restarted logs are untouched. fsync_fire is forced off so the
    watermarks themselves only clamp, never advance."""
    cfg = _dur_cfg(n)
    key = jax.random.key(n)
    k_init, k_run = jax.random.split(key)
    s = init_state(cfg, k_init)
    ar = np.arange(n)
    log_len = ((ar * 7) % 17).astype(np.int32)
    dur_len = (log_len // 2).astype(np.int32)
    s = s._replace(
        log_len=jnp.asarray(log_len),
        dur_len=jnp.asarray(dur_len),
    )
    inp = faults.make_inputs(cfg, k_run, s.now)
    restarted = jnp.asarray(ar % 2 == 0)
    torn = jnp.asarray((ar % 7).astype(np.int32))  # 0..6 spans, some > tail
    inp = inp._replace(
        restarted=restarted,
        alive=jnp.ones(n, bool),
        torn_drop=torn,
        fsync_fire=jnp.zeros(n, bool),
        client_cmd=jnp.int32(NIL),
    )
    s2, _ = jax.jit(lambda st, i: raft_batched.step_one(cfg, st, i))(s, inp)
    rs = np.asarray(restarted)
    expect = np.where(rs, np.maximum(dur_len, log_len - np.asarray(torn)),
                      log_len)
    np.testing.assert_array_equal(np.asarray(s2.log_len), expect)
    # The watermark only clamped: dur_len' = min(dur_len, recovered log).
    np.testing.assert_array_equal(
        np.asarray(s2.dur_len), np.minimum(dur_len, expect))
    assert bool(np.all(np.asarray(s2.dur_len) <= np.asarray(s2.log_len)))


# ------------------------------------- durability x compacted carry layout


def test_durability_compact_planes_bitexact():
    """Dense and compacted trajectories are bit-identical with the storage
    plane LIVE under crash/torn churn: the dur watermark legs ride the carry
    unpacked in both layouts, and recovery truncation of bit-packed logs
    lands on the same lengths (the layout is physical only)."""
    cfg_d = _dur_cfg(
        5, log_capacity=8, max_entries_per_rpc=2, client_interval=1,
        drop_prob=0.3, crash_prob=0.5, crash_period=20, crash_down_ticks=10,
        lost_suffix_span=3,
    )
    cfg_c = compact_twin(cfg_d)
    key = jax.random.key(21)
    k_init, k_run = jax.random.split(key)
    sd = init_state(cfg_d, k_init)
    sc = init_state(cfg_c, k_init)
    step_d = jax.jit(lambda s, i: raft_batched.step_one(cfg_d, s, i)[0])
    step_c = jax.jit(lambda s, i: raft_batched.step_one(cfg_c, s, i)[0])
    inp_d = jax.jit(lambda now: faults.make_inputs(cfg_d, k_run, now))
    inp_c = jax.jit(lambda now: faults.make_inputs(cfg_c, k_run, now))
    for _ in range(80):
        sd = step_d(sd, inp_d(sd.now))
        sc = step_c(sc, inp_c(sc.now))
    du = tile.unpack_state(cfg_c, sc)
    for f in sd._fields:
        if f == "mailbox":
            for mf in sd.mailbox._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(sd.mailbox, mf)),
                    np.asarray(getattr(du.mailbox, mf)), err_msg=f"mb.{mf}")
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(sd, f)), np.asarray(getattr(du, f)),
                err_msg=f)
    # The run actually exercised the plane: some disk lagged its log.
    assert int(np.max(np.asarray(sd.dur_len))) > 0


# --------------------------------------------------------- checkpoint v25


def test_checkpoint_v25_round_trips_durable_state(tmp_path):
    """A mid-run config10 fleet (watermarks advanced, fsync-lag metrics
    accumulated) saves and loads bit-identically."""
    from raft_sim_tpu.types import init_batch

    cfg, _ = PRESETS["config10"]
    root = jax.random.key(11)
    k_init, k_run = jax.random.split(root)
    state = init_batch(cfg, k_init, 2)
    keys = jax.random.split(k_run, 2)
    state, metrics = scan.run_batch_minor(cfg, state, keys, 120)
    assert int(np.max(np.asarray(state.dur_len))) > 0  # flushes happened
    assert int(np.sum(np.asarray(metrics.fsync_lag_sum))) > 0  # lag observed
    path = checkpoint.save(str(tmp_path / "ck"), cfg, state, keys, metrics,
                           seed=11)
    cfg2, state2, keys2, metrics2, seed2, scenario = checkpoint.load(path)
    assert cfg2 == cfg and seed2 == 11 and scenario is None
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(metrics), jax.tree.leaves(metrics2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(
        jax.random.key_data(keys), jax.random.key_data(keys2))


def test_checkpoint_v24_file_refused_with_migration_error(tmp_path, monkeypatch):
    """A pre-v25 checkpoint must be REFUSED with the migration-pointing
    error, not half-loaded into the watermark-bearing schema."""
    cfg = RaftConfig(n_nodes=3, log_capacity=8)
    s = init_state(cfg, jax.random.key(0))
    state = jax.tree.map(lambda x: jnp.stack([x]), s)
    keys = jax.random.split(jax.random.key(1), 1)
    metrics = scan.init_metrics_batch(1)
    monkeypatch.setattr(checkpoint, "_FORMAT_VERSION", 24)
    path = checkpoint.save(str(tmp_path / "old"), cfg, state, keys, metrics)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="v24.*v25|format v24"):
        checkpoint.load(path)


# ------------------------------------------------- health + fleet surfaces


def test_durability_lag_sli_and_spec():
    """The durability_lag SLI: worst instantaneous per-node lag vs the
    ceiling (binary budget objective; ceiling 0 = disabled), with the
    per-cluster max as the triage metric."""
    from raft_sim_tpu.health import sli
    from raft_sim_tpu.health.spec import load_spec

    def unit(lag_max, lag_sum):
        b = len(lag_max)
        from raft_sim_tpu.types import LAT_HIST_BINS
        return {
            "start": 0, "ticks": 16,
            "violations": np.zeros(b, np.int64),
            "leaderless": np.zeros(b, bool),
            "cmds": np.zeros(b, np.int64), "reads": np.zeros(b, np.int64),
            "lat_sum": np.zeros(b, np.int64), "lat_cnt": np.zeros(b, np.int64),
            "lat_hist": np.zeros((b, LAT_HIST_BINS), np.int64),
            "read_hist": np.zeros((b, LAT_HIST_BINS), np.int64),
            "fsync_lag_sum": np.asarray(lag_sum, np.int64),
            "fsync_lag_max": np.asarray(lag_max, np.int64),
        }

    spec = load_spec({
        "schema": "health-slo-v1", "eval_windows": 1, "worst_k": 1,
        "outlier_score": 3.0, "resolve_evals": 1,
        "objectives": {
            "durability": {"sli": "durability_lag", "max_lag": 4,
                           "budget": 0.25},
        },
        "rules": [{"name": "fast", "short": 1, "long": 2, "burn": 6.0}],
    })
    units = [unit([2, 7, 0], [8, 40, 0]), unit([1, 3, 0], [4, 12, 0])]
    out = sli.compute_slis(spec, units, [])
    assert out["slis"]["durability"]["max_lag"] == 7
    assert out["errs"]["durability"] == 1.0  # 7 > ceiling 4
    assert out["budgets"]["durability"] == 0.25
    np.testing.assert_array_equal(out["percluster"]["durability"],
                                  [2.0, 7.0, 0.0])
    # Ceiling respected / disabled.
    spec["objectives"]["durability"]["max_lag"] = 8
    assert sli.compute_slis(spec, units, [])["errs"]["durability"] == 0.0
    spec["objectives"]["durability"]["max_lag"] = 0
    assert sli.compute_slis(spec, units, [])["errs"]["durability"] == 0.0
    # Spec validation rejects a bad ceiling.
    from raft_sim_tpu.health.spec import validate_spec
    bad = {**spec, "objectives": {
        "durability": {"sli": "durability_lag", "max_lag": -1, "budget": 0.25}}}
    assert any("max_lag" in e for e in validate_spec(bad))


def test_fleet_summary_fsync_rollup():
    """FleetSummary's durability readouts: fleet total, worst instantaneous
    lag, and percentiles over per-cluster MEAN lag (lag_sum / ticks),
    skipping clusters that ran zero ticks."""
    from types import SimpleNamespace

    from raft_sim_tpu.parallel.mesh import _fsync_lag_rollup

    m = SimpleNamespace(
        ticks=np.array([10, 20, 0]),
        fsync_lag_sum=np.array([50, 20, 0]),
        fsync_lag_max=np.array([7, 3, 0]),
    )
    out = _fsync_lag_rollup(m)
    assert out["fsync_lag_total"] == 70
    assert out["fsync_lag_max"] == 7
    assert out["fsync_lag_p50"] == pytest.approx(3.0)  # means [5.0, 1.0]
    assert out["fsync_lag_p95"] == pytest.approx(4.8)
    empty = _fsync_lag_rollup(SimpleNamespace(
        ticks=np.zeros(2, int), fsync_lag_sum=np.zeros(2, int),
        fsync_lag_max=np.zeros(2, int)))
    assert empty["fsync_lag_p50"] is None and empty["fsync_lag_total"] == 0


# ------------------------------------------------------------ config gates


def test_config_gate_validation():
    """Structural-gate asserts: disk-fault knobs without the fsync gate are
    refused, as is the v1 compaction overlap."""
    with pytest.raises(AssertionError, match="fsync"):
        RaftConfig(n_nodes=3, log_capacity=8, fsync_jitter_prob=0.2)
    with pytest.raises(AssertionError, match="fsync"):
        RaftConfig(n_nodes=3, log_capacity=8, torn_tail_prob=0.2)
    with pytest.raises(AssertionError, match="compact_margin|fsync"):
        RaftConfig(n_nodes=3, log_capacity=8, compact_margin=4,
                   fsync_interval=3)
    cfg = _dur_cfg(3)
    assert cfg.durable_storage and cfg.durable_acks and cfg.persist_vote
    off = dataclasses.replace(cfg, fsync_interval=0, fsync_jitter_prob=0.0,
                              torn_tail_prob=0.0, lost_suffix_span=1)
    assert not off.durable_storage


# ------------------------------------------------------- portfolio member


def test_durability_portfolio_member_gradient():
    """fit_durability (farm/portfolio.py): exposure = per-window commit
    advance weighted by the window's fsync lag -- the committing-while-
    volatile cluster MUST outscore both the idle-disk committer (lag 0)
    and the partition-dead churner (no commits), and a device violation
    dominates all of it. The pure-distress members anti-select the bug's
    preconditions; this member is why the CI durability smoke re-finds
    ack-before-fsync within its generation budget."""
    from types import SimpleNamespace

    from raft_sim_tpu.farm.portfolio import fit_durability

    # Three clusters x four windows: [0] commits under lag, [1] commits on a
    # prompt disk, [2] churns leaderless without committing anything.
    max_commit = np.array([[2, 5, 9, 12], [2, 5, 9, 12], [0, 0, 0, 0]],
                          np.int64)
    lag = np.array([[3, 4, 6, 5], [0, 0, 0, 0], [9, 9, 9, 9]], np.int64)
    records = SimpleNamespace(metrics=SimpleNamespace(
        max_commit=max_commit, fsync_lag_max=lag))
    metrics = SimpleNamespace(violations=np.array([0, 0, 0], np.int64),
                              max_term=np.array([3, 3, 40], np.int64))
    fit = fit_durability(records, metrics, None)
    assert fit[0] > fit[1], fit  # lag-exposed commits beat prompt-disk ones
    assert fit[0] > fit[2], fit  # ...and beat commit-free churn
    # A violation dominates lexicographically in every member.
    metrics_v = SimpleNamespace(violations=np.array([0, 0, 1], np.int64),
                                max_term=metrics.max_term)
    fit_v = fit_durability(records, metrics_v, None)
    assert fit_v[2] > fit_v[0] and fit_v[2] > 1e5, fit_v
