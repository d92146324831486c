"""Multi-chip execution: shard the independent-cluster batch axis over a device mesh.

The reference's "distributed backend" is point-to-point HTTP between one OS process per
Raft node (server.clj:37-39, client.clj:34-40). In the rebuild, *intra-cluster* traffic
is the dense mailbox inside the step kernel (types.py); *across chips* the workload is
embarrassingly parallel -- clusters are independent -- so ICI carries only the batch
sharding installed here plus small psum metric reductions. No NCCL analogue is needed
beyond XLA's collectives (SURVEY.md section 5, distributed communication backend).

Design: per-cluster PRNG keys are split OUTSIDE the sharded region, so a run is
bit-identical for the same (seed, batch) at any device count -- the distributed parity
property tested in tests/test_parallel.py. `shard_map` (not bare jit-with-shardings) is
used so the compiled program provably contains no accidental cross-device traffic in the
hot loop; the only cross-device movement is the host-side gather in `summarize`, which
pulls the small per-cluster RunMetrics off device for the fleet rollup.

Every `jax.shard_map` here and in nodeshard.py passes `check_vma=False`: the scan
carry mixes axis-invariant constants (init_metrics zeros) with per-cluster varying
state, which the varying-manual-axes check would reject.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_sim_tpu.sim import scan
from raft_sim_tpu.types import init_state
from raft_sim_tpu.utils.config import RaftConfig

AXIS = "clusters"


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Multi-host bootstrap: join this host's chips into the global device mesh.

    The reference's cross-node transport is point-to-point HTTP between OS
    processes (server.clj/client.clj); here multi-HOST scaling is pure
    orchestration -- clusters are independent, so a pod just shards the batch
    axis over every chip of every host. This wraps `jax.distributed.initialize`
    (args fall back to the standard JAX env vars / TPU pod auto-detection; DCN
    carries only this control plane, never tick traffic). Call once per host
    process before any computation; afterwards `jax.devices()` is the global
    device list, `make_mesh()` builds the global 1-D mesh, `simulate_sharded`
    runs with each host touching only its addressable shards, and
    `summarize`/`gather_metrics` all-gather the per-cluster metrics so every
    process sees the fleet rollup. Exercised end to end by
    tools/multihost_check.py (two cooperating OS processes on one machine --
    the reference's deployment shape, core.clj:197-203 -- verified bit-for-bit
    against a single-process run; tests/test_multihost.py runs it in CI).
    Returns this host's process index.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_index()


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the flat device list; the single named axis shards the batch of
    independent clusters (the rebuild's only data-parallel axis, SURVEY.md section 2)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(f"requested {n_devices} devices, only {len(devices)} available")
        devices = devices[:n_devices]
    import numpy as np

    return Mesh(np.asarray(devices), (AXIS,))


def _run_shard(cfg: RaftConfig, n_ticks: int, keys_init, keys_run):
    """Body executed per shard: init + scan the local slice of clusters (batch-minor
    hot path)."""
    state = jax.vmap(lambda k: init_state(cfg, k))(keys_init)
    return scan.run_batch_minor(cfg, state, keys_run, n_ticks)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def simulate_sharded(cfg: RaftConfig, seed, batch: int, n_ticks: int, mesh: Mesh):
    """Batched simulation sharded over `mesh`. Returns (final_state, RunMetrics), both
    with the leading batch axis sharded over the mesh.

    Bit-identical to `scan.simulate` for the same (cfg, seed, batch, n_ticks): the
    per-cluster key split happens before sharding, so device count does not perturb
    any cluster's trajectory.
    """
    n_dev = mesh.devices.size
    if batch % n_dev:
        raise ValueError(f"batch {batch} must divide over {n_dev} devices")
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    keys_init = jax.random.split(k_init, batch)
    keys_run = jax.random.split(k_run, batch)

    sharded = jax.shard_map(
        functools.partial(_run_shard, cfg, n_ticks),
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=P(AXIS),
        check_vma=False,
    )
    keys_init = _constrain_keys(keys_init, mesh)
    keys_run = _constrain_keys(keys_run, mesh)
    return sharded(keys_init, keys_run)


def _run_shard_windowed(cfg, n_ticks, window, seg_len, trace_spec,
                        keys_init, keys_run, genome):
    """Per-shard body for `simulate_windowed_sharded`: init + the windowed
    telemetry scan over the local cluster slice. The recorder leg is always
    None here (the farm never rings) and is dropped from the return -- a
    dead leg has no shard spec."""
    from raft_sim_tpu.sim import telemetry

    state = jax.vmap(lambda k: init_state(cfg, k))(keys_init)
    out = telemetry.run_batch_minor_telemetry(
        cfg, state, keys_run, n_ticks, window, None,
        genome=genome, seg_len=seg_len, trace_spec=trace_spec,
    )
    if trace_spec is None:
        final, metrics, recs, _ = out
        return final, metrics, recs
    final, metrics, recs, _, traws, tp = out
    return final, metrics, recs, traws, tp


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 7, 8))
def simulate_windowed_sharded(
    cfg: RaftConfig, seed, batch: int, n_ticks: int, window: int, mesh: Mesh,
    genome=None, seg_len: int = 1, trace=None,
):
    """`telemetry.simulate_windowed` sharded over the cluster axis of `mesh`
    -- the farm's per-generation evaluator (farm/core.py): one shard_map'ped
    windowed scan for the whole CE portfolio, the population divided over
    the devices. Same return shape as simulate_windowed (the recorder slot
    is always None: rings are a debugging tool, the farm never arms one),
    plus the trace legs when `trace` is given.

    Bit-identical to the unsharded call at ANY device count: per-cluster
    keys are split OUTSIDE the sharded region (the simulate_sharded
    invariance pattern), so a hunt's trajectory -- and therefore its hits,
    its manifest hash, its corpus artifacts -- never depends on the mesh it
    ran on. Genome rows stay traced DATA ([B, S] leaves sharded over their
    leading cluster axis): new genome values reuse the compiled program, so
    the jit cache holds exactly one entry per (config, mesh) and stays flat
    across generations and device counts (tests/test_farm.py pins this)."""
    n_dev = mesh.devices.size
    if batch % n_dev:
        raise ValueError(f"batch {batch} must divide over {n_dev} devices")
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    keys_init = _constrain_keys(jax.random.split(k_init, batch), mesh)
    keys_run = _constrain_keys(jax.random.split(k_run, batch), mesh)

    body = functools.partial(
        _run_shard_windowed, cfg, n_ticks, window, seg_len, trace
    )
    args = (keys_init, keys_run)
    in_specs = [P(AXIS), P(AXIS)]
    if genome is None:
        fn = lambda ki, kr: body(ki, kr, None)
    else:
        fn = body
        args += (genome,)
        in_specs.append(P(AXIS))  # [B, S] leaves: clusters lead, S replicated
    # Batch-leading outputs shard on axis 0; the trace legs stay batch-minor
    # (leaves [n_windows, ..., B] / [..., B]), so their specs put the cluster
    # axis LAST -- ranks read off an eval_shape of the unsharded body.
    out_specs = [P(AXIS), P(AXIS), P(AXIS)]
    if trace is not None:
        shapes = jax.eval_shape(fn, *args)
        minor = lambda t: jax.tree.map(
            lambda s: P(*([None] * (s.ndim - 1)), AXIS), t
        )
        out_specs += [minor(shapes[3]), minor(shapes[4])]
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=tuple(out_specs),
        check_vma=False,
    )
    out = sharded(*args)
    if trace is None:
        return out[0], out[1], out[2], None
    return out[0], out[1], out[2], None, out[3], out[4]


def _constrain_keys(keys, mesh: Mesh):
    """Batch-shard a typed PRNG key array over the mesh's cluster axis. Values
    are untouched -- only placement metadata is attached."""
    return jax.lax.with_sharding_constraint(keys, NamedSharding(mesh, P(AXIS)))


class FleetSummary(NamedTuple):
    """Host-side rollup of per-cluster RunMetrics across the whole fleet. The
    per-cluster metric arrays are tiny ([batch] int32s), so this is a plain
    device_get + numpy reduction, not an on-device collective."""

    n_clusters: int
    total_violations: int
    n_stable: int  # clusters that ended with a continuously-held leader
    p50_stable_tick: float | None  # median ticks-to-stable-leader; None if no cluster stabilized
    max_term: int
    total_msgs: int
    total_cmds: int  # client commands accepted fleet-wide (offered vs committed audit)
    # LEGACY: fleet p50 of per-cluster MEAN offer->commit latency (ticks) -- a
    # mean-of-means, superseded by the true per-entry percentiles below
    # (lat_p50/p95/p99 from the on-device histogram). Kept for continuity with
    # the BENCH_* history; both are derived in ONE pass (_latency_rollup) from
    # the same gathered metrics, so the two readouts cannot drift apart. None
    # when no cluster committed any client entry (e.g. client_interval == 0).
    p50_commit_latency: float | None
    # TRUE per-entry latency percentiles, recovered from the fleet-summed
    # log2-bin histogram (RunMetrics.lat_hist) with linear interpolation inside
    # the hit bin -- the tail visibility the mean-of-means above lacks. None
    # when no entry committed.
    lat_p50: float | None
    lat_p95: float | None
    lat_p99: float | None
    # Latency coverage gap (RunMetrics.lat_excluded): client entries whose
    # first commit fell in a leaderless window -- crossed by the dedup frontier
    # but never attributed into the histogram. The percentiles above cover
    # lat_cnt / (lat_cnt + lat_excluded) of committed client entries
    # (docs/PERF.md "latency metric coverage").
    lat_excluded: int
    # Liveness/coverage counters (RunMetrics): election wins that found no
    # no-op slot (compaction livelock early-warning), and node pairs the ring
    # log-matching check could not compare.
    noop_blocked: int
    lm_skipped_pairs: int
    # Split-brain exposure (RunMetrics.multi_leader): fleet-total ticks with
    # >= 2 concurrent LEADER roles. Legal under partitions (a deposed leader
    # that has not heard the news); the graded precursor the scenario search
    # climbs toward election-safety violations (docs/SCENARIOS.md).
    multi_leader: int
    # ReadIndex read traffic (RunMetrics.reads_served/read_hist; zeros unless
    # cfg.read_index): reads served fleet-wide and their true per-read
    # latency percentiles -- the commit-vs-read comparison the read traffic
    # class exists to expose (docs/PROTOCOL.md).
    reads_served: int
    read_p50: float | None
    read_p95: float | None
    read_p99: float | None
    # Durable storage plane (RunMetrics.fsync_lag_sum/fsync_lag_max; zeros
    # unless cfg.durable_storage): how far disks trail the logs. The
    # percentiles are over PER-CLUSTER mean lag (lag_sum / ticks, i.e.
    # node-summed entries-behind per tick) -- the fleet's "typical cluster"
    # durability debt -- and fsync_lag_max is the worst instantaneous
    # per-node lag seen anywhere (the burn plane's page signal feeds on the
    # per-window form of the same counters, health/spec.py durability_lag).
    fsync_lag_total: int
    fsync_lag_max: int
    fsync_lag_p50: float | None
    fsync_lag_p95: float | None


def gather_metrics(metrics):
    """Make a batched RunMetrics fully addressable on every process.

    Single-process metrics pass through untouched. Under multi-host execution the
    shard_map outputs are global arrays whose remote shards this process cannot
    read; a jitted identity with replicated out-shardings inserts the cross-host
    all-gather (every process must call this -- standard multi-controller SPMD),
    after which the host-side rollup below works unchanged. The metrics are a few
    int32s per cluster, so the DCN traffic is negligible (SURVEY.md section 5:
    DCN carries orchestration and metric collection only).
    """
    leaves = jax.tree.leaves(metrics)
    x0 = leaves[0]
    if not (hasattr(x0, "sharding") and not x0.is_fully_addressable):
        return metrics
    mesh = x0.sharding.mesh
    rep = NamedSharding(mesh, P())
    return jax.device_get(jax.jit(lambda t: t, out_shardings=rep)(metrics))


def _hist_percentile(hist, q: float) -> float | None:
    """The q-quantile latency from a summed log2-bin histogram: bin k holds
    latencies in [2^k, 2^(k+1)), linearly interpolated inside the hit bin.
    None for an empty histogram.

    The interpolation assumes uniform spread inside the bin, which biases
    upward by as much as the bin width; when the hit bin is the FIRST nonempty
    one the quantile is clamped to the bin's lower edge instead -- an
    all-1-tick run reports lat_p50 = 1.0, not 1.5 (the distribution's minimum
    is a hard lower bound on every quantile, and with no mass below the bin
    there is nothing to interpolate against). Tail granularity above the first
    bin remains up to 2x -- inherent to log2 binning."""
    total = int(hist.sum())
    if total == 0:
        return None
    need = q * total
    cum = 0
    for k, c in enumerate(int(x) for x in hist):
        if c and cum + c >= need:
            lo, hi = float(1 << k), float(1 << (k + 1))
            if cum == 0:
                return lo  # first nonempty bin: clamp to its lower edge
            return lo + (need - cum) / c * (hi - lo)
        cum += c
    return float(1 << len(hist))


def _latency_rollup(m) -> dict:
    """All four latency readouts (legacy mean-of-means p50 AND the true
    histogram percentiles) plus the coverage-gap counter, from ONE host-side
    pass over the same gathered metrics -- the single code path that keeps the
    legacy and histogram numbers from drifting (they answer the same question
    at different fidelities, so they must always be computed together)."""
    import numpy as np

    committed = m.lat_cnt > 0
    p50_lat = (
        float(np.median(m.lat_sum[committed] / m.lat_cnt[committed]))
        if np.any(committed)
        else None
    )
    hist = np.sum(np.asarray(m.lat_hist, dtype=np.int64), axis=0)  # [BINS]
    rhist = np.sum(np.asarray(m.read_hist, dtype=np.int64), axis=0)  # [BINS]
    return {
        "p50_commit_latency": p50_lat,  # legacy (see FleetSummary docstring)
        "lat_p50": _hist_percentile(hist, 0.50),
        "lat_p95": _hist_percentile(hist, 0.95),
        "lat_p99": _hist_percentile(hist, 0.99),
        "lat_excluded": int(np.sum(m.lat_excluded, dtype=np.int64)),
        "reads_served": int(np.sum(m.reads_served, dtype=np.int64)),
        "read_p50": _hist_percentile(rhist, 0.50),
        "read_p95": _hist_percentile(rhist, 0.95),
        "read_p99": _hist_percentile(rhist, 0.99),
    }


def summarize(metrics) -> FleetSummary:
    """Fleet-level rollup of a batched RunMetrics. The p50 quantile is computed
    host-side from the (small, [batch]-shaped) stable-tick vector. Handles
    multi-host (non-addressable) metrics via gather_metrics."""
    metrics = gather_metrics(metrics)
    stable = jax.device_get(scan.stable_leader_ticks(metrics))
    import numpy as np

    reached = stable[stable < scan.NEVER]
    # None (JSON null) rather than inf: json.dumps(inf) emits non-standard `Infinity`.
    p50 = float(np.median(reached)) if reached.size else None
    m = jax.device_get(metrics)
    return FleetSummary(
        n_clusters=int(m.ticks.shape[0]),
        total_violations=int(np.sum(m.violations)),
        n_stable=int(reached.size),
        p50_stable_tick=p50,
        max_term=int(np.max(m.max_term)),
        total_msgs=int(np.sum(m.total_msgs, dtype=np.int64)),
        total_cmds=int(np.sum(m.total_cmds, dtype=np.int64)),
        noop_blocked=int(np.sum(m.noop_blocked, dtype=np.int64)),
        lm_skipped_pairs=int(np.sum(m.lm_skipped_pairs, dtype=np.int64)),
        multi_leader=int(np.sum(m.multi_leader, dtype=np.int64)),
        **_fsync_lag_rollup(m),
        **_latency_rollup(m),
    )


def _fsync_lag_rollup(m) -> dict:
    """Fleet durability-lag readouts (FleetSummary docstring). Per-cluster
    mean lag = lag_sum / ticks (node-summed entries-behind per tick); the
    percentiles are None when no tick ran. All-zero with the storage plane
    off -- the gated metric legs never accumulate."""
    import numpy as np

    ticks = np.asarray(m.ticks, dtype=np.int64)
    ran = ticks > 0
    if np.any(ran):
        mean_lag = np.asarray(m.fsync_lag_sum, np.int64)[ran] / ticks[ran]
        p50 = float(np.percentile(mean_lag, 50))
        p95 = float(np.percentile(mean_lag, 95))
    else:
        p50 = p95 = None
    return {
        "fsync_lag_total": int(np.sum(m.fsync_lag_sum, dtype=np.int64)),
        "fsync_lag_max": int(np.max(m.fsync_lag_max)),
        "fsync_lag_p50": p50,
        "fsync_lag_p95": p95,
    }
