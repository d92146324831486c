"""The TiKV-shaped region fleet (benchmark cell `fleet-tikv3-shuffle`) on the
CPU, at its own widths: three replicas, PreVote, lease reads, joint-consensus
replica moves and leader transfers, a 64-slot compacting ring.

- A rehearsal-size fleet (64 regions, the cell's `rehearsal_batch`) built
  from the cell's two JSON files runs 512 ticks through `driver.Session.run`
  (the normal fleet path: `run_chunked` at the cell's 256-tick chunk), and
  four of its regions match `tests/oracle.py` in every leaf at the end. The
  fleet shares its chunk program with the rehearsal below, so the file
  compiles it once.
- The cell's own run (`benchmark.run --cpu-rehearsal`) is `correct` with
  every check 0.
- The comparison fails a broken membership rule: the program under the
  `single-server-change` and `act-on-commit` mutants
  (`scenario/mutation.py`), against the sound reference.
"""

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
WORKLOAD = "fleet-tikv3-shuffle"
SEED = 2147483913
CHUNK = 256  # the traffic's chunk: the rehearsal's chunk program
TICKS = 2 * CHUNK
CHECKED = (0, 21, 42, 63)  # regions replayed by the oracle


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


def test_the_cell_is_the_tikv_deployment():
    f = cell().raft_fields
    assert (f["n_nodes"], f["heartbeat_ticks"], f["election_min_ticks"],
            f["election_range_ticks"]) == (3, 2, 10, 10)
    assert f["pre_vote"] and f["read_lease_ticks"] == 3
    assert (f["log_capacity"], f["compact_margin"]) == (64, 16)
    cfg = RaftConfig(**f)
    assert cfg.reconfig and cfg.leader_transfer and cfg.read_lease
    assert cfg.compaction and cfg.client_redirect


def test_regions_match_the_oracle_in_every_leaf(fleet):
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
        for f, g in got.items():
            if f == "mailbox":
                for mf, mg in g.items():
                    np.testing.assert_array_equal(
                        mg, s["mailbox"][mf], err_msg=f"region {c}: mailbox.{mf}")
            else:
                np.testing.assert_array_equal(g, s[f], err_msg=f"region {c}: {f}")


def test_the_planes_did_work_inside_the_run(fleet):
    """Replica moves left regions joint, transfers fired, the lease served
    reads (a part of all reads served), and every tick held the
    invariants."""
    _, sess, _ = fleet
    m = jax.device_get(sess.metrics)
    assert int(np.sum(m.violations)) == 0
    assert int(np.sum(m.joint_ticks)) > 0
    assert int(np.sum(m.xfer_fired)) > 0
    assert int(np.sum(m.lease_reads)) > 0
    assert np.all(m.lease_reads <= m.reads_served)
    s = sess.summary()
    assert s["lease_reads"] == int(np.sum(m.lease_reads, dtype=np.int64))
    assert s["joint_ticks"] > 0 and s["xfer_fired"] > 0


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


@pytest.mark.parametrize("mutant", ["single-server-change", "act-on-commit"])
def test_a_broken_membership_rule_is_not_correct(capsys, bench_env, mutant):
    """The fleet runs the mutant's program; the reference stays sound."""
    from raft_sim_tpu.scenario.mutation import mutant_config

    def broken(**fields):
        return mutant_config(mutant, RaftConfig(**fields))

    bench_env.setattr(raft_sim_tpu, "RaftConfig", broken)
    doc, checks = run_cell(capsys)
    assert not doc["correct"]
    assert checks["state_mismatch"] > 0 or checks["violations"] > 0, checks
