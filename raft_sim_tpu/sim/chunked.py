"""Long-horizon runs: chunked scans with periodic host offload.

The trajectory axis (simulated time) is one of the two "long axes" the rebuild scales
without materializing (SURVEY.md section 5, long-context analogue): a 10M-tick fuzz run
must not stack 10M StepInfos on device. `run_chunked` scans in fixed-size jitted chunks
and merges the small per-chunk RunMetrics on the way, optionally invoking a host
callback between chunks (progress reporting, checkpointing, early abort on violation).

Metric merge works because `scan._accumulate` records absolute tick numbers (state.now),
which persist across chunk boundaries in the carry.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from raft_sim_tpu.obs.timer import span
from raft_sim_tpu.sim import scan
from raft_sim_tpu.types import ClusterState
from raft_sim_tpu.utils.config import RaftConfig


def merge_metrics(a: scan.RunMetrics, b: scan.RunMetrics) -> scan.RunMetrics:
    """Combine metrics of two consecutive run segments (a then b). Every op is
    elementwise, so this works unchanged on scalar or [batch]-shaped metrics."""
    return scan.RunMetrics(
        violations=a.violations + b.violations,
        first_leader_tick=jnp.minimum(a.first_leader_tick, b.first_leader_tick),
        last_leaderless_tick=jnp.maximum(a.last_leaderless_tick, b.last_leaderless_tick),
        max_term=jnp.maximum(a.max_term, b.max_term),
        max_commit=jnp.maximum(a.max_commit, b.max_commit),
        min_commit=b.min_commit,  # "at final tick" -> later segment wins
        total_msgs=a.total_msgs + b.total_msgs,
        total_cmds=a.total_cmds + b.total_cmds,
        lat_sum=a.lat_sum + b.lat_sum,
        lat_cnt=a.lat_cnt + b.lat_cnt,
        lat_hist=a.lat_hist + b.lat_hist,
        lat_excluded=a.lat_excluded + b.lat_excluded,
        noop_blocked=a.noop_blocked + b.noop_blocked,
        lm_skipped_pairs=a.lm_skipped_pairs + b.lm_skipped_pairs,
        reads_served=a.reads_served + b.reads_served,
        read_lat_sum=a.read_lat_sum + b.read_lat_sum,
        read_hist=a.read_hist + b.read_hist,
        fsync_lag_sum=a.fsync_lag_sum + b.fsync_lag_sum,
        fsync_lag_max=jnp.maximum(a.fsync_lag_max, b.fsync_lag_max),
        joint_ticks=a.joint_ticks + b.joint_ticks,
        xfer_fired=a.xfer_fired + b.xfer_fired,
        lease_reads=a.lease_reads + b.lease_reads,
        fsync_stalls=a.fsync_stalls + b.fsync_stalls,
        torn_lost=a.torn_lost + b.torn_lost,
        late_grants=a.late_grants + b.late_grants,
        lat_ring_ticks=a.lat_ring_ticks + b.lat_ring_ticks,
        multi_leader=a.multi_leader + b.multi_leader,
        ticks=a.ticks + b.ticks,
    )


# The loops' per-call zeroed metrics and per-chunk merge, each one compiled
# program: run eagerly they dispatch one op per metric leg (and the vmap
# re-traces every call), host time the device waits through between chunks.
_zero_metrics = jax.jit(scan.init_metrics_batch, static_argnums=0)
_merge = jax.jit(merge_metrics)


@jax.jit
def _own_copy(state):
    """A buffer-distinct copy of the fleet state: run_chunked donates its way
    through the chunk loop, and this one up-front copy is what keeps the
    CALLER's arrays alive while the loop consumes its own. A trivial program
    (one copy op per leaf) -- compiling it costs milliseconds, unlike a
    second donating/non-donating variant of the scan program would."""
    return jax.tree.map(jnp.copy, state)


@functools.partial(jax.jit, static_argnums=(0, 3, 5))
def _chunk(cfg: RaftConfig, state: ClusterState, keys: jax.Array, n: int,
           genome=None, seg_len: int = 1):
    """Input-preserving chunk: the caller's `state` stays valid after the call
    (tools/repro.py replays from the chunk-START state on a violation, so it
    must NOT be donated)."""
    return scan.run_batch_minor(cfg, state, keys, n, genome=genome, seg_len=seg_len)


@functools.partial(jax.jit, static_argnums=(0, 3, 5), donate_argnums=(1,))
def _chunk_donate(cfg: RaftConfig, state: ClusterState, keys: jax.Array, n: int,
                  genome=None, seg_len: int = 1):
    """The steady-state chunk: the previous chunk's carry is DONATED back to
    XLA, so a long-horizon run holds one fleet state in HBM instead of two
    (at config3 scale, batch=100k x ~4 KB/cluster, double-buffering is ~0.4 GB
    of dead residency per chunk boundary). `keys` are reused across chunks and
    are never donated. The cost model's donation audit
    (analysis/cost_model.py, rule `cost-donation`) pins that this entry point
    actually aliases its carry buffers -- dropping `donate_argnums` fails the
    gate statically."""
    return scan.run_batch_minor(cfg, state, keys, n, genome=genome, seg_len=seg_len)


def run_chunked(
    cfg: RaftConfig,
    state: ClusterState,
    keys: jax.Array,
    n_ticks: int,
    chunk: int = 1024,
    callback: Callable[[int, ClusterState, scan.RunMetrics], bool] | None = None,
    genome=None,
    seg_len: int = 1,
    perf=None,
):
    """Scan a batched state forward `n_ticks` in jitted chunks.

    `callback(ticks_done, state, merged_metrics)` runs between chunks; returning True
    stops early (e.g. on a violation during invariant fuzzing). Returns
    (final_state, merged_metrics). `genome`/`seg_len` select the scenario
    input path (scan.run_batch_minor); segment boundaries are driven by the
    absolute tick in state.now, so chunking never shifts a nemesis phase.

    Buffer ownership: the caller's `state` buffers stay valid (the loop takes
    ONE device copy up front -- trivial next to a single chunk's work -- and
    owns it), and every state the loop produces is donated to the next chunk,
    so the steady state holds one fleet in HBM, not two. One consequence: a
    `state` captured inside `callback` is only valid until the callback
    returns -- copy (`jax.device_get`) anything a callback needs to keep, as
    the checkpoint/apply-log consumers already do. This discipline is a
    GATED fact: analysis Pass D's use-after-donate dataflow lint walks this
    loop (rule `race-use-after-donate`, with `_own_copy` and
    fetch-before-donate blessed), and `tools/check.py --race --dynamic` /
    `driver run --sanitize` re-run it with donated buffers poisoned so any
    violation raises at the access site (analysis/sanitizer.py).

    `perf` (an obs.ChunkTimer) records per-chunk runtime attribution to
    perf.jsonl: each chunk is synced to a host copy of its small metrics leaf
    (device-wait timing; serializes the dispatch pipelining the loop would
    otherwise overlap -- docs/OBSERVABILITY.md "Runtime perf") and the chunk
    program's jit cache is sampled as the recompile watchdog. None (the
    default) leaves the loop byte-identical to pre-perf behaviour.

    Host spans (obs.span, always on, no sync): `fleet.init` (the zeroed
    metrics) and `fleet.copy` (the up-front copy), then per chunk
    `fleet.dispatch` (its tick count), `fleet.merge` and `fleet.callback`.
    """
    batch = state.role.shape[0]
    with span("fleet.init"):
        metrics = _zero_metrics(batch)
    done = 0
    with span("fleet.copy"):
        state = _own_copy(state)
    if perf is not None:
        perf.add_probe("chunked._chunk_donate", _chunk_donate)
    while done < n_ticks:
        n = min(chunk, n_ticks - done)
        with span("fleet.dispatch", n) as sp:
            state, m = _chunk_donate(cfg, state, keys, n, genome, seg_len)
        if perf is not None:
            perf.dispatch_span(sp)
        with span("fleet.merge"):
            metrics = _merge(metrics, m)
        done += n
        # Callback host work (export, checkpointing) is part of the chunk's
        # host gap; the timer closes AFTER it, syncing on this chunk's own
        # metric leaf.
        stop = False
        if callback is not None:
            with span("fleet.callback"):
                stop = callback(done, state, metrics)
        if perf is not None:
            perf.end(sync=lambda: np.asarray(m.ticks))
        if stop:
            break
    return state, metrics
