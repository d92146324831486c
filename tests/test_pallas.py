"""Pallas engine parity (interpret mode on CPU).

The Pallas engine runs the identical step_b body inside a pallas_call gridded over
cluster blocks, so parity here extends the oracle -> raft_batched.step_b chain to
the kernelized execution path. On this image's TPU toolchain the compiled path is
blocked by a compiler limitation, which demoted the engine to experiments/ (see
experiments/pallas_engine.py docstring); interpret mode exercises the full
pallas_call machinery (blocking, ref plumbing, shape lifting) on CPU and keeps the
tick kernel pallas-compatible for the day the toolchain can lower it.
"""

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_batch
from raft_sim_tpu.experiments import pallas_engine
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.sim import faults, scan


@pytest.mark.parametrize(
    "cfg",
    [
        # Slow tier (870s budget): n3-small + the run-loop parity below keep
        # the interpret-mode engine pinned in tier-1.
        pytest.param(
            RaftConfig(n_nodes=5, client_interval=4, drop_prob=0.2),
            id="n5-faults",
            marks=pytest.mark.slow,
        ),
        pytest.param(RaftConfig(n_nodes=3, log_capacity=8, max_entries_per_rpc=2), id="n3-small"),
    ],
)
def test_step_pallas_matches_step_b(cfg):
    B = 8
    state = init_batch(cfg, jax.random.key(0), B)
    keys = jax.random.split(jax.random.key(1), B)
    inp = jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now))(keys, state.now)
    s_t = raft_batched.to_batch_minor(state)
    i_t = raft_batched.to_batch_minor(inp)

    ref = raft_batched.step_b(cfg, s_t, i_t)
    got = pallas_engine.step_pallas(cfg, s_t, i_t, block_b=4, interpret=True)
    for a, b in zip(jax.tree.leaves(jax.device_get(ref)), jax.tree.leaves(jax.device_get(got))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_pallas_matches_run_batch_minor():
    cfg = RaftConfig(n_nodes=5, client_interval=8)
    B = 8
    state = init_batch(cfg, jax.random.key(2), B)
    keys = jax.random.split(jax.random.key(3), B)

    f_ref, m_ref = jax.jit(lambda s, k: scan.run_batch_minor(cfg, s, k, 60))(state, keys)
    f_pl, m_pl = pallas_engine.run_pallas(cfg, state, keys, 60, 4, True)
    for a, b in zip(jax.tree.leaves(jax.device_get((f_ref, m_ref))), jax.tree.leaves(jax.device_get((f_pl, m_pl)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_step_pallas_rejects_bad_block():
    cfg = RaftConfig(n_nodes=3)
    state = init_batch(cfg, jax.random.key(0), 6)
    keys = jax.random.split(jax.random.key(1), 6)
    inp = jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now))(keys, state.now)
    with pytest.raises(ValueError, match="multiple of"):
        pallas_engine.step_pallas(
            cfg,
            raft_batched.to_batch_minor(state),
            raft_batched.to_batch_minor(inp),
            block_b=4,
            interpret=True,
        )
