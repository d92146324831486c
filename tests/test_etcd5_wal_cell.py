"""etcd with its write-ahead log on the chip (benchmark cell
`fleet-etcd5-wal-jepsen`) on the CPU, at its own widths: five members, a
4,095-slot ring without compaction, the durable storage plane at etcd's
persist-before-send cadence (an fsync every tick), under Jepsen's nemeses
plus slow flushes and torn WAL tails.

- A rehearsal-size fleet (the cell's `rehearsal_batch`, 8 clusters) built
  from the cell's two JSON files runs 512 ticks through `driver.Session.run`
  (the normal fleet path: `run_chunked` at the cell's 256-tick chunk), and
  four of its clusters match `tests/oracle.py` in every leaf at the end, the
  durable watermarks included. The fleet shares its chunk program with the
  rehearsal below, so the file compiles it once.
- The storage-plane counters (`fsync_stalls`, `torn_lost`, `late_grants`)
  are nonzero over that run, reach `Session.summary()`, and read zero with
  the plane off.
- The cell's own run (`benchmark.run --cpu-rehearsal`) is `correct` with
  every check 0.
- The comparison fails a broken durability rule: the program under the
  `ack-before-fsync` and `volatile-vote` mutants (`scenario/mutation.py`),
  against the sound reference.

SEED is one on which the rehearsal's clusters see stalled flushes, late
vote responses and a restart that loses un-fsynced entries (torn_lost 2 over
512 ticks): with an fsync every tick, a restart loses entries only when the
crashed node's last flush stalled, about one restart in twenty.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import raft_sim_tpu
from raft_sim_tpu import RaftConfig
from raft_sim_tpu.driver import Session
from raft_sim_tpu.sim import faults
from tests import oracle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKLOAD = "fleet-etcd5-wal-jepsen"
SEED = 2147483987
CHUNK = 256  # the traffic's chunk: the rehearsal's chunk program
TICKS = 2 * CHUNK
CHECKED = (0, 3, 5, 7)  # clusters replayed by the oracle
COUNTERS = ("fsync_stalls", "torn_lost", "late_grants")


def cell():
    """The cell as the benchmark reads it: RaftConfig fields from the
    deployment's file, then the traffic's `raft` block."""
    from benchmark.cell import Cell

    return Cell(ROOT, WORKLOAD)


@pytest.fixture(scope="module")
def fleet():
    c = cell()
    cfg = RaftConfig(**c.raft_fields)
    sess = Session(cfg, batch=c.traffic["rehearsal_batch"], seed=SEED)
    start = jax.device_get(sess.state)
    sess.run(TICKS, chunk=CHUNK)
    return cfg, sess, start


def test_the_cell_is_the_durable_etcd_deployment():
    f = cell().raft_fields
    assert (f["n_nodes"], f["heartbeat_ticks"], f["election_min_ticks"],
            f["election_range_ticks"]) == (5, 2, 20, 20)
    assert (f["log_capacity"], f["compact_margin"], f["max_entries_per_rpc"]) == (
        4095, 0, 8)
    assert f["fsync_interval"] == 1
    cfg = RaftConfig(**f)
    assert cfg.durable_storage and cfg.durable_acks and cfg.persist_vote
    assert not cfg.compaction and cfg.client_redirect and cfg.read_index


def test_clusters_match_the_oracle_in_every_leaf(fleet):
    cfg, sess, start = fleet
    final = jax.device_get(sess.state)
    draw = jax.jit(jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now),
                            in_axes=(None, 0)))
    ticks = np.arange(TICKS, dtype=np.int32)
    for c in CHECKED:
        inputs = jax.device_get(draw(sess.keys[c], ticks))
        s = oracle.state_to_dict(jax.tree.map(lambda x: x[c], start), cfg)
        for t in range(TICKS):
            s = oracle.oracle_step(
                cfg, s, {f: v[t] for f, v in zip(inputs._fields, inputs)})
        got = oracle.state_to_dict(jax.tree.map(lambda x: x[c], final), cfg)
        assert {"dur_len", "dur_term", "dur_vote"} <= set(got)
        for f, g in got.items():
            if f == "mailbox":
                for mf, mg in g.items():
                    np.testing.assert_array_equal(
                        mg, s["mailbox"][mf], err_msg=f"cluster {c}: mailbox.{mf}")
            else:
                np.testing.assert_array_equal(g, s[f], err_msg=f"cluster {c}: {f}")


def test_the_storage_plane_counts_inside_the_run(fleet):
    """Flushes stalled, votes went out late, a restart lost un-fsynced
    entries, and every tick held the invariants; the fleet summary carries
    the same totals."""
    _, sess, _ = fleet
    m = jax.device_get(sess.metrics)
    assert int(np.sum(m.violations)) == 0
    totals = {n: int(np.sum(getattr(m, n), dtype=np.int64)) for n in COUNTERS}
    assert all(v > 0 for v in totals.values()), totals
    s = sess.summary()
    assert {n: s[n] for n in COUNTERS} == totals
    assert s["fsync_lag_max"] > 0


def test_the_counters_read_zero_with_the_plane_off():
    f = dict(cell().raft_fields, fsync_interval=0, fsync_jitter_prob=0.0,
             torn_tail_prob=0.0, lost_suffix_span=1)
    cfg = dataclasses.replace(RaftConfig(**f), log_capacity=64)
    sess = Session(cfg, batch=4, seed=SEED)
    sess.run(64, chunk=64)
    s = sess.summary()
    assert s["total_cmds"] > 0
    assert all(s[n] == 0 for n in COUNTERS), {n: s[n] for n in COUNTERS}


@pytest.fixture
def bench_env(monkeypatch):
    """The harness points JAX's persistent cache at the checkout and lowers
    its compile-time threshold; both are put back for the tests after."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_MAX_SIZE", raising=False)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield monkeypatch
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def run_cell(capsys, *extra):
    from benchmark import run as bench_run

    rc = bench_run.main(["--workload", WORKLOAD, "--seed", str(SEED),
                         "--seconds", "0.01", "--trace", "0",
                         "--cpu-rehearsal", *extra])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return doc, {k: v["value"] for k, v in doc["checks"].items()}


def test_rehearsal_is_correct(capsys, bench_env):
    doc, checks = run_cell(capsys)
    assert doc["correct"], checks
    assert set(checks) == {"state_mismatch", "read_mismatch", "ticks_short",
                           "violations"}
    assert not any(checks.values()), checks


@pytest.mark.parametrize("mutant", ["ack-before-fsync", "volatile-vote"])
def test_a_broken_durability_rule_is_not_correct(capsys, bench_env, mutant):
    """The fleet runs the mutant's program; the reference stays sound."""
    from raft_sim_tpu.scenario.mutation import mutant_config

    def broken(**fields):
        return mutant_config(mutant, RaftConfig(**fields))

    bench_env.setattr(raft_sim_tpu, "RaftConfig", broken)
    doc, checks = run_cell(capsys)
    assert not doc["correct"]
    assert checks["state_mismatch"] > 0 or checks["violations"] > 0, checks
