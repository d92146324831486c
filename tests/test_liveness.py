"""Replication-liveness regression tests for the shared-entry-window outbox.

The AppendEntries entry payload is one shared E-entry window per sender
(types.Mailbox). If the window start were the minimum prev over ALL peers, a
permanently dead peer (next_index pinned at its initial value, never acking)
would pin the window start forever, and no live follower could ever receive
entries past window_start + E: commit would stall despite a live quorum -- a
liveness loss the reference cannot have, since it ships unbounded per-peer log
suffixes (core.clj:59-67). The responsiveness filter (config.ack_timeout_ticks,
ClusterState.ack_age) drops never-acking peers out of the window-start min;
these tests pin that behavior end to end.
"""

import jax
import jax.numpy as jnp
import pytest

from raft_sim_tpu import NIL, RaftConfig, StepInputs, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import bitplane

CFG = RaftConfig(n_nodes=5, log_capacity=64, max_entries_per_rpc=4, client_interval=1)


def run_ticks(cfg, s, n_ticks, alive, cmd_base=100):
    """Drive raft_batched.step_one with full delivery, steady clocks, one offered
    command per tick, and a fixed alive mask. Returns the final state."""
    n = cfg.n_nodes
    step = jax.jit(raft_batched.step_one, static_argnums=0)
    for t in range(n_ticks):
        inp = StepInputs(
            deliver_mask=bitplane.pack(jnp.ones((n, n), bool), axis=1),
            skew=jnp.ones((n,), jnp.int32),
            timeout_draw=jnp.full((n,), 8 + (t % 5), jnp.int32),
            client_cmd=jnp.int32(cmd_base + t),
            client_target=jnp.int32(0),
            client_bounce=jnp.zeros((cfg.client_pipeline,), jnp.int32),
            alive=jnp.asarray(alive, bool),
            restarted=jnp.zeros((n,), bool),
        )
        s, _ = step(cfg, s, inp)
    return s


@pytest.mark.parametrize("dead", [4, 0])
def test_dead_peer_does_not_stall_replication(dead):
    """One node down from tick 0, a command offered every tick: commit must advance
    far past E (= max_entries_per_rpc) on every live node."""
    e = CFG.max_entries_per_rpc
    alive = [i != dead for i in range(CFG.n_nodes)]
    s = run_ticks(CFG, init_state(CFG, jax.random.key(1)), 120, alive)
    live = jnp.asarray(alive)
    live_commit = jnp.where(live, s.commit_index, 10**6)
    # Every live node's commit far exceeds the E-entry window bound that a pinned
    # window start would impose.
    assert int(jnp.min(live_commit)) > 4 * e, (
        f"commit stalled at {s.commit_index} (window pinned by dead peer {dead}?)"
    )
    # The live quorum converged on identical logs.
    lead = int(jnp.argmax(s.commit_index))
    for i in range(CFG.n_nodes):
        if alive[i] and i != lead:
            m = min(int(s.commit_index[i]), int(s.commit_index[lead]))
            assert jnp.array_equal(s.log_val[i, :m], s.log_val[lead, :m])


def test_healed_laggard_catches_up():
    """A node down for the first 60 ticks (while the cluster commits >> E entries)
    must converge to the leader's log after it comes back."""
    n = CFG.n_nodes
    down = [i != 4 for i in range(n)]
    s = run_ticks(CFG, init_state(CFG, jax.random.key(2)), 60, down)
    gap = int(jnp.max(s.commit_index))
    assert gap > 2 * CFG.max_entries_per_rpc  # the laggard is far behind on return
    # Node 4 restarts (volatile wipe; its empty log is its durable state).
    restart = StepInputs(
        deliver_mask=bitplane.pack(jnp.ones((n, n), bool), axis=1),
        skew=jnp.ones((n,), jnp.int32),
        timeout_draw=jnp.full((n,), 9, jnp.int32),
        client_cmd=jnp.int32(NIL),
        client_target=jnp.int32(0),
        client_bounce=jnp.zeros((CFG.client_pipeline,), jnp.int32),
        alive=jnp.ones((n,), bool),
        restarted=jnp.asarray([i == 4 for i in range(n)], bool),
    )
    s, _ = jax.jit(raft_batched.step_one, static_argnums=0)(CFG, s, restart)
    s = run_ticks(CFG, s, 120, [True] * n, cmd_base=500)
    # The healed node caught all the way up to the cluster commit frontier.
    assert int(s.commit_index[4]) >= gap, (
        f"laggard stuck at {int(s.commit_index[4])} of {gap}"
    )
    lead = int(jnp.argmax(s.commit_index))
    m = min(int(s.commit_index[4]), int(s.commit_index[lead]))
    assert jnp.array_equal(s.log_val[4, :m], s.log_val[lead, :m])
