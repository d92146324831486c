"""The plane counters (RunMetrics.joint_ticks / xfer_fired / lease_reads, and
the storage plane's fsync_stalls / torn_lost / late_grants) and what they
cost where their gates are off.

- With its gate off, a counter's metric leg passes through the tick
  unchanged: no equation of the tick computes it (the read metrics'
  zero-cost contract). With the gate on, the tick computes it.
- The kernel counts them on trajectories the oracle (tests/oracle.py)
  agrees with, tick by tick, at the TiKV cell's widths.
- The storage counters are the tick's own facts, restated from its inputs
  and state (stalls: due flushes jitter stalled on live nodes; torn_lost:
  what the restarts' recoveries cut off), sum across chunks, and survive
  `summarize`.
- The etcd fleet cells' chunk program (`sim/chunked._chunk_donate` at the
  fleet-etcd5-jepsen and fleet-etcd3-b1000 fields) keeps the tick loop it
  had before the counters: the same loop carry and the same instructions in
  the loop, by kind, as pinned in tests/golden_chunk_ops.json (optimized
  CPU HLO, metadata left out).

Regenerate the pin after an intended change to the fleet tick:
    JAX_PLATFORMS=cpu python tests/test_plane_counters.py --update
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_batch
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.sim import chunked, faults, scan
from tests import oracle
from tests.test_oracle_parity import assert_state_equal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(ROOT, "tests", "golden_chunk_ops.json")
STORAGE = ("fsync_stalls", "torn_lost", "late_grants")
COUNTERS = ("joint_ticks", "xfer_fired", "lease_reads", *STORAGE)
FLEET_CELLS = ("fleet-etcd5-jepsen", "fleet-etcd3-b1000")


def cell_fields(workload: str) -> dict:
    from benchmark.cell import Cell

    return Cell(ROOT, workload).raft_fields


BASE = RaftConfig(n_nodes=3, log_capacity=8, compact_margin=2, client_interval=2,
                  client_redirect=True, read_interval=1)
# Each counter's gate alone on (the lease needs election_min >= 2L + 4; the
# storage plane excludes compaction and gates its three counters at once).
DURABLE = dict(compact_margin=0, fsync_interval=2, fsync_jitter_prob=0.3,
               torn_tail_prob=0.5, lost_suffix_span=3)
GATED = {
    ("joint_ticks",): dict(reconfig_interval=5),
    ("xfer_fired",): dict(transfer_interval=5),
    ("lease_reads",): dict(read_lease_ticks=2, election_min_ticks=8),
    STORAGE: DURABLE,
}


def passthrough_counters(cfg: RaftConfig) -> set[str]:
    """The counters whose metric leg the batch-minor tick returns as the
    very variable it was given."""
    b = 4
    key = jax.random.key(0)
    state = jax.eval_shape(
        lambda k: raft_batched.to_batch_minor(init_batch(cfg, k, b)), key)
    keys = jax.eval_shape(lambda k: jax.random.split(k, b), key)
    m = jax.eval_shape(
        lambda: raft_batched.to_batch_minor(scan.init_metrics_batch(b)))

    def tick(s, k, m):
        return scan.tick_batch_minor(cfg, s, k, m)[1]

    jaxpr = jax.make_jaxpr(tick)(state, keys, m).jaxpr
    n_in = len(jax.tree.leaves((state, keys)))
    out = []
    for name in COUNTERS:
        i = scan.RunMetrics._fields.index(name)
        if jaxpr.outvars[i] is jaxpr.invars[n_in + i]:
            out.append(name)
    return set(out)


def test_counters_pass_through_with_their_gates_off():
    assert passthrough_counters(BASE) == set(COUNTERS)
    for names, gate in GATED.items():
        cfg = dataclasses.replace(BASE, **gate)
        assert passthrough_counters(cfg) == set(COUNTERS) - set(names), names
    for cell in FLEET_CELLS:
        assert passthrough_counters(RaftConfig(**cell_fields(cell))) == set(COUNTERS)


def test_counters_read_zero_with_their_gates_off():
    _, m = scan.simulate(BASE, 5, 4, 40)
    for name in COUNTERS:
        assert not np.any(np.asarray(getattr(m, name))), name
    assert int(np.sum(m.reads_served)) > 0


def test_both_kernels_count_alike():
    """The batch-minor kernel at the TiKV cell's widths with fast move and
    transfer cadences: every cluster's state equals the oracle's each tick,
    each of its plane's counters fires, and the lease never serves more reads than were
    served."""
    cfg = dataclasses.replace(RaftConfig(**cell_fields("fleet-tikv3-shuffle")),
                              reconfig_interval=7, transfer_interval=5)
    b = 8
    k_init, k_run = jax.random.split(jax.random.key(4))
    state = init_batch(cfg, k_init, b)
    keys = jax.random.split(k_run, b)
    draw = jax.jit(jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now)))
    bstep = jax.jit(lambda s, i: raft_batched.step_b(cfg, s, i))
    take = lambda tree, c: jax.tree.map(lambda x: x[c], tree)
    want = [oracle.state_to_dict(take(state, c), cfg) for c in range(b)]
    now = state.now
    s_min = raft_batched.to_batch_minor(state)
    total = collections.Counter()
    for t in range(80):
        inp = jax.device_get(draw(keys, now))
        s_min, i_min = bstep(s_min, raft_batched.to_batch_minor(inp))
        got, info = jax.device_get(raft_batched.from_batch_minor((s_min, i_min)))
        now = got.now
        for c in range(b):
            inp_c = {f: np.asarray(v)[c] for f, v in zip(inp._fields, inp)}
            want[c] = oracle.oracle_step(cfg, want[c], inp_c)
            assert_state_equal(oracle.state_to_dict(take(got, c), cfg), want[c], t)
        assert np.all(info.lease_reads <= info.reads_served)
        total.update({n: int(np.sum(getattr(info, n))) for n in COUNTERS})
    assert all(total[n] > 0 for n in COUNTERS if n not in STORAGE), total


def test_storage_counters_restate_the_tick_and_sum_across_chunks():
    """A durable fleet under crashes: each tick's fsync_stalls and torn_lost
    equal what its inputs and pre-tick state say (due flushes stalled on
    live nodes; log_len minus the recovered length on restarting nodes), a
    late vote response is a vote response in the mailbox, each counter
    fires, and a run in three chunks accumulates the per-tick sums, which
    `summarize` reports."""
    from raft_sim_tpu.parallel import summarize
    from raft_sim_tpu.types import RESP_VOTE

    cfg = dataclasses.replace(BASE, **DURABLE, crash_prob=0.2, crash_period=16,
                              crash_down_ticks=4)
    b, ticks = 8, 96
    k_init, k_run = jax.random.split(jax.random.key(7))
    state = init_batch(cfg, k_init, b)
    keys = jax.random.split(k_run, b)
    draw = jax.jit(jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now)))
    bstep = jax.jit(lambda s, i: raft_batched.step_b(cfg, s, i))
    s_min, now = raft_batched.to_batch_minor(state), state.now
    total = collections.Counter()
    for t in range(ticks):
        inp = jax.device_get(draw(keys, now))
        pre = jax.device_get(raft_batched.from_batch_minor(s_min))
        s_min, i_min = bstep(s_min, raft_batched.to_batch_minor(inp))
        got, info = jax.device_get(raft_batched.from_batch_minor((s_min, i_min)))
        now = got.now
        stalls = np.sum(inp.fsync_stall & inp.alive, axis=1)
        rec = np.maximum(pre.dur_len, pre.log_len - inp.torn_drop)
        lost = np.sum(np.where(inp.restarted, pre.log_len - rec, 0), axis=1)
        np.testing.assert_array_equal(info.fsync_stalls, stalls, err_msg=f"tick {t}")
        np.testing.assert_array_equal(info.torn_lost, lost, err_msg=f"tick {t}")
        votes = np.sum(got.mailbox.resp_kind == RESP_VOTE, axis=(1, 2))
        assert np.all(info.late_grants <= votes), t
        total.update({n: int(np.sum(getattr(info, n))) for n in STORAGE})
    assert all(total[n] > 0 for n in STORAGE), total
    _, m = chunked.run_chunked(cfg, state, keys, ticks, chunk=32)
    m = jax.device_get(m)
    s = summarize(m)
    for n in STORAGE:
        assert int(np.sum(getattr(m, n))) == total[n] == getattr(s, n), n


# ------------------------------------------------ the etcd cells' tick loop

_COMP = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%\S+\s*=\s*(.*?)\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)="
    r"(%[A-Za-z0-9_.\-]+)")


def tick_loop_ops(text: str) -> dict:
    """The optimized HLO's tick loop: the entry's `while` with the widest
    carry, its carry's element count, and the instructions of its body and
    of every computation the body calls, counted by kind."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if cur is not None and line.strip():
            comps[cur].append(line)
    loops = []
    for line in comps[entry]:
        m = _INSTR.match(line)
        if m and m.group(2) == "while":
            body = re.search(r"body=(%[A-Za-z0-9_.\-]+)", line).group(1)
            loops.append((m.group(1).count("["), body))
    carry, body = max(loops)
    seen, todo, ops = set(), [body], collections.Counter()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            m = _INSTR.match(line)
            if m:
                ops[m.group(2)] += 1
            todo.extend(_CALLS.findall(line))
    return {"carry": carry, "ops": dict(sorted(ops.items()))}


def chunk_loop(workload: str) -> dict:
    cfg = RaftConfig(**cell_fields(workload))
    key = jax.random.key(0)
    state = jax.eval_shape(lambda k: init_batch(cfg, k, 8), key)
    keys = jax.eval_shape(lambda k: jax.random.split(k, 8), key)
    low = chunked._chunk_donate.lower(cfg, state, keys, 2, None, 1)
    return tick_loop_ops(low.compile().as_text())


@pytest.mark.parametrize("workload", FLEET_CELLS)
def test_etcd_fleet_tick_loop_is_unchanged(workload):
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    if golden["jax_version"] != jax.__version__:
        pytest.skip(f"pinned under jax {golden['jax_version']}, running "
                    f"{jax.__version__}: instruction counts are version-specific")
    got = chunk_loop(workload)
    assert got == golden["programs"][workload], (
        f"{workload}: the fleet tick loop changed; if intended, regenerate "
        "with: JAX_PLATFORMS=cpu python tests/test_plane_counters.py --update")


def _update():
    doc = {"jax_version": jax.__version__,
           "programs": {w: chunk_loop(w) for w in FLEET_CELLS}}
    with open(GOLDEN_PATH, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH} under jax {jax.__version__}")


if __name__ == "__main__":
    if "--update" in sys.argv:
        _update()
    else:
        print(__doc__)
