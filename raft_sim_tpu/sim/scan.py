"""The jit-compiled tick loop: `lax.scan` over models/raft_batched.step_b with
on-device metrics.

This replaces the reference's blocking event loop -- `loop [node (init-node id)] (recur
(wait system node))` (core.clj:202-203) -- with a single compiled scan. Where the
reference's observability is an unconditional println of node + message per iteration
(core.clj:182-186), here the cheap path accumulates a small `RunMetrics` reduction in
the scan carry, and trace modes optionally stack per-tick `StepInfo` or full states for
host-side inspection.

Every loop ticks through ONE body, `tick_batch_minor`: the fleet scan
(`run_batch_minor`), the chunked and served loops, and the single-cluster traced
scan (`run`, at B = 1). Sharding across chips happens one level up, in
`raft_sim_tpu.parallel`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_sim_tpu.obs.scopes import tick_scope
from raft_sim_tpu.sim import faults
from raft_sim_tpu.types import NIL, ClusterState, StepInfo
from raft_sim_tpu.utils.config import RaftConfig

# Sentinel for "never happened" tick values (first leader, stable leader). Public so
# consumers (parallel.summarize, tests) compare against the same constant. Kept a
# plain Python int: a module-level jnp array would initialize the JAX backend at
# import time, before driver.select_backend can pick the platform.
NEVER = 2**31 - 1
_BIG = NEVER


class RunMetrics(NamedTuple):
    """Per-cluster summary accumulated on device across a run.

    `first_leader_tick` is the first tick at which any node held LEADER; the
    north-star quality metric "ticks-to-stable-leader" is `last_leaderless_tick + 1`
    (the tick after which leadership was continuously held). Both are _BIG / -1
    sentinels when never reached.
    """

    violations: jax.Array  # int32: count of ticks with any invariant violation
    first_leader_tick: jax.Array  # int32 (_BIG if never)
    last_leaderless_tick: jax.Array  # int32 (-1 if a leader existed from tick 0)
    max_term: jax.Array  # int32
    max_commit: jax.Array  # int32
    min_commit: jax.Array  # int32: min over nodes at the final tick
    total_msgs: jax.Array  # int32: delivered records over the run
    total_cmds: jax.Array  # int32: client commands accepted by a live leader
    # Offer->commit latency accumulators (StepInfo.lat_sum/lat_cnt): this
    # cluster's mean commit latency is lat_sum / lat_cnt; parallel.summarize
    # rolls the fleet p50 of those means.
    lat_sum: jax.Array  # int32
    lat_cnt: jax.Array  # int32
    # Per-entry latency histogram (log2 bins, StepInfo.lat_hist): summed over the
    # fleet in parallel.summarize to recover true p50/p95/p99 percentiles. The
    # one non-scalar metric leaf: [LAT_HIST_BINS] per cluster (public [B, BINS]
    # layout; the batch-minor scan carries it [BINS, B] internally).
    lat_hist: jax.Array  # [LAT_HIST_BINS] int32
    # Latency coverage gap (StepInfo.lat_excluded): client entries the frontier
    # crossed in leaderless windows, permanently dropped from lat_sum/lat_cnt/
    # lat_hist -- the undercount docs/PERF.md documents, now measured.
    lat_excluded: jax.Array  # int32
    # Liveness/coverage counters (StepInfo.noop_blocked / lm_skipped_pairs).
    noop_blocked: jax.Array  # int32: election wins denied their no-op slot
    lm_skipped_pairs: jax.Array  # int32: pair-checks skipped by ring log matching
    # ReadIndex read traffic (StepInfo.reads_served/read_lat_sum/read_hist;
    # zeros unless cfg.read_index): reads served, their summed offer->serve
    # latency, and the log2-bin read-latency histogram -- the read-side
    # mirror of lat_sum/lat_cnt/lat_hist, so telemetry reports commit-vs-read
    # latency from one schema.
    reads_served: jax.Array  # int32
    read_lat_sum: jax.Array  # int32
    read_hist: jax.Array  # [LAT_HIST_BINS] int32
    # Durability lag (StepInfo.fsync_lag_sum/fsync_lag_max; zeros unless
    # cfg.durable_storage): summed node-tick lag and the run's max node lag,
    # in entries of un-fsynced log. The per-cluster mean lag is
    # fsync_lag_sum / (ticks * N); parallel.summarize rolls fleet
    # percentiles of those means and the max-of-max.
    fsync_lag_sum: jax.Array  # int32
    fsync_lag_max: jax.Array  # int32
    # Plane counters (StepInfo.joint_ticks/xfer_fired/lease_reads; each zero
    # and loop-invariant unless its gate -- cfg.reconfig, leader_transfer,
    # read_lease -- is on): ticks with any node in a joint configuration,
    # TimeoutNow requests sent, and reads served by the lease alone.
    joint_ticks: jax.Array  # int32
    xfer_fired: jax.Array  # int32
    lease_reads: jax.Array  # int32
    # Storage-plane counters (StepInfo.fsync_stalls/torn_lost/late_grants;
    # zero and loop-invariant unless cfg.durable_storage): due flushes that
    # jitter stalled on live nodes, log entries recoveries dropped, and vote
    # responses sent once the flush under their grant landed.
    fsync_stalls: jax.Array  # int32
    torn_lost: jax.Array  # int32
    late_grants: jax.Array  # int32
    # Latency-pass fallback ticks (StepInfo.lat_ring_ticks; zero and
    # loop-invariant unless track_offer_ticks at CAP > 256): ticks whose
    # offer->commit pass swept the whole ring, not the window.
    lat_ring_ticks: jax.Array  # int32
    # Split-brain exposure: ticks with >= 2 concurrent LEADER roles
    # (StepInfo.n_leaders). LEGAL under partitions (a deposed leader has not
    # heard the news yet) -- only SAME-term double leadership violates
    # election safety -- but it is the graded precursor of that violation,
    # which makes it both a useful observability counter and the scenario
    # search's fitness signal toward election-safety breaks (a deceptive
    # landscape otherwise: message drop maximizes leaderless churn while
    # PREVENTING the concurrent successful elections a violation needs).
    multi_leader: jax.Array  # int32: ticks with n_leaders >= 2
    ticks: jax.Array  # int32


def init_metrics_batch(batch: int) -> RunMetrics:
    """Zeroed RunMetrics with a leading [batch] axis (the fleet/driver carry)."""
    return jax.vmap(lambda _: init_metrics())(jnp.arange(batch))


def init_metrics() -> RunMetrics:
    from raft_sim_tpu.types import LAT_HIST_BINS

    z = jnp.int32(0)
    return RunMetrics(
        violations=z,
        first_leader_tick=jnp.int32(NEVER),
        last_leaderless_tick=jnp.int32(-1),
        max_term=z,
        max_commit=z,
        min_commit=z,
        total_msgs=z,
        total_cmds=z,
        lat_sum=z,
        lat_cnt=z,
        lat_hist=jnp.zeros((LAT_HIST_BINS,), jnp.int32),
        lat_excluded=z,
        noop_blocked=z,
        lm_skipped_pairs=z,
        reads_served=z,
        read_lat_sum=z,
        read_hist=jnp.zeros((LAT_HIST_BINS,), jnp.int32),
        fsync_lag_sum=z,
        fsync_lag_max=z,
        joint_ticks=z,
        xfer_fired=z,
        lease_reads=z,
        fsync_stalls=z,
        torn_lost=z,
        late_grants=z,
        lat_ring_ticks=z,
        multi_leader=z,
        ticks=z,
    )


def _host_zero(x) -> bool:
    """True for a host-side constant zero StepInfo leaf: the kernel emits np
    constants (never jnp.zeros, which would lower an op) for metrics whose
    structural gate is off, and skipping the fold keeps the corresponding
    RunMetrics carry leg loop-invariant -- XLA elides it from the per-tick
    HBM round trip and the Pass C cost gate prices it at zero
    (zero-cost-when-off, the same contract the state legs follow)."""
    import numpy as np  # host-side predicate only; jnp arrays fall through

    return isinstance(x, (int, np.integer, np.ndarray)) and not np.any(x)


def _add_gated(a, b):
    return a if _host_zero(b) else a + b


def _max_gated(a, b):
    """The maximum-fold twin of _add_gated (same host-predicate gate): used by
    the fsync-lag max, whose neutral element under max-of-nonnegatives is the
    same host zero the sum folds skip on."""
    return a if _host_zero(b) else jnp.maximum(a, b)


def step_bad(info):
    """The per-tick any-invariant-tripped predicate, shared by every
    violations fold (metric accumulation, telemetry windows, the serve loop's
    first-violation tick). viol_read_stale joins the classic three only when
    its gate emitted a real array (cfg.read_lease AND check_invariants) --
    the kernel emits a host-constant zero otherwise, and skipping the fold
    (a HOST predicate, like _add_gated's) keeps disabled-mode programs
    byte-identical."""
    bad = info.viol_election_safety | info.viol_commit | info.viol_log_matching
    if not _host_zero(info.viol_read_stale):
        bad = bad | info.viol_read_stale
    return bad


def _accumulate(m: RunMetrics, info: StepInfo, tick: jax.Array) -> RunMetrics:
    bad = step_bad(info)
    has_leader = info.leader != NIL
    return RunMetrics(
        violations=m.violations + bad,
        first_leader_tick=jnp.minimum(
            m.first_leader_tick, jnp.where(has_leader, tick, _BIG)
        ),
        last_leaderless_tick=jnp.maximum(
            m.last_leaderless_tick, jnp.where(has_leader, -1, tick)
        ),
        max_term=jnp.maximum(m.max_term, info.max_term),
        max_commit=jnp.maximum(m.max_commit, info.max_commit),
        min_commit=info.min_commit,
        total_msgs=m.total_msgs + info.msgs_delivered,
        total_cmds=m.total_cmds + info.cmds_injected,
        lat_sum=m.lat_sum + info.lat_sum,
        lat_cnt=m.lat_cnt + info.lat_cnt,
        lat_hist=m.lat_hist + info.lat_hist,
        lat_excluded=m.lat_excluded + info.lat_excluded,
        noop_blocked=m.noop_blocked + info.noop_blocked,
        lm_skipped_pairs=m.lm_skipped_pairs + info.lm_skipped_pairs,
        reads_served=_add_gated(m.reads_served, info.reads_served),
        read_lat_sum=_add_gated(m.read_lat_sum, info.read_lat_sum),
        read_hist=_add_gated(m.read_hist, info.read_hist),
        fsync_lag_sum=_add_gated(m.fsync_lag_sum, info.fsync_lag_sum),
        fsync_lag_max=_max_gated(m.fsync_lag_max, info.fsync_lag_max),
        joint_ticks=_add_gated(m.joint_ticks, info.joint_ticks),
        xfer_fired=_add_gated(m.xfer_fired, info.xfer_fired),
        lease_reads=_add_gated(m.lease_reads, info.lease_reads),
        fsync_stalls=_add_gated(m.fsync_stalls, info.fsync_stalls),
        torn_lost=_add_gated(m.torn_lost, info.torn_lost),
        late_grants=_add_gated(m.late_grants, info.late_grants),
        lat_ring_ticks=_add_gated(m.lat_ring_ticks, info.lat_ring_ticks),
        multi_leader=m.multi_leader + (info.n_leaders >= 2),
        ticks=m.ticks + 1,
    )


def run(
    cfg: RaftConfig,
    state: ClusterState,
    key: jax.Array,
    n_ticks: int,
    trace: bool = False,
    trace_states: bool = False,
    genome=None,
    seg_len: int = 1,
):
    """Scan one cluster forward `n_ticks` through `tick_batch_minor` at
    B = 1 (the tick body every loop runs). Returns (final_state, metrics,
    outs) in the public layout without a batch axis, where `outs` is None,
    stacked StepInfo (trace=True), or (StepInfo, stacked states)
    (trace_states=True). `genome` (a ScenarioGenome with `[S]` leaves;
    `seg_len` static) switches input generation to the scenario path
    (sim/faults.py) -- the step kernel itself is untouched."""
    from raft_sim_tpu.models import raft_batched

    lead = lambda tree: jax.tree.map(lambda x: x[None], tree)  # a batch of one
    keys, genome = lead((key, genome))

    def body(carry, _):
        s, m = carry
        s2, m2, info = tick_batch_minor(cfg, s, keys, m, genome=genome, seg_len=seg_len)
        if trace_states:
            out = (info, s2)
        elif trace:
            out = info
        else:
            out = None
        return (s2, m2), out

    carry0 = raft_batched.to_batch_minor(lead((state, init_metrics())))
    carry, outs = lax.scan(body, carry0, None, length=n_ticks)
    (final, metrics), outs = jax.tree.map(lambda x: x[..., 0], (carry, outs))
    return final, metrics, outs


def run_batch_minor(
    cfg: RaftConfig,
    state: ClusterState,
    keys: jax.Array,
    n_ticks: int,
    step_fn=None,
    genome=None,
    seg_len: int = 1,
):
    """Batch-minor hot path: every cluster follows the trajectory `run` gives
    it alone (bit-for-bit; see tests/test_batched_parity.py), with the batch axis
    transposed to minor once at entry/exit so every per-tick array is TPU-tiled with
    the batch on the 128-lane dimension. State in/out keeps the public [B, ...]-leading
    convention. No per-tick trace output (use `run` for tracing).

    `step_fn(cfg, state_minor, inputs_minor)` overrides the tick kernel (the Pallas
    engine passes its kernelized step here so both engines share one scan body).
    `genome` ([B, S] ScenarioGenome rows; `seg_len` static) switches input
    generation to the scenario path -- a heterogeneous fleet through ONE
    compiled program; the genome rides the scan as loop constants, never the
    carry."""
    from raft_sim_tpu.models import raft_batched

    if step_fn is None:
        step_fn = raft_batched.step_b
    batch = state.role.shape[0]
    s_t = raft_batched.to_batch_minor(state)

    def body(carry, _):
        s, m = carry
        s2, m2, _ = tick_batch_minor(
            cfg, s, keys, m, step_fn=step_fn, genome=genome, seg_len=seg_len
        )
        return (s2, m2), None

    # Metrics ride the scan batch-minor too (the histogram leaf is [BINS, B]
    # there; scalars-per-cluster are [B] in either layout).
    (final_t, metrics), _ = lax.scan(
        body,
        (s_t, raft_batched.to_batch_minor(init_metrics_batch(batch))),
        None,
        length=n_ticks,
    )
    return (
        raft_batched.from_batch_minor(final_t),
        raft_batched.from_batch_minor(metrics),
    )


def tick_batch_minor(
    cfg, s, keys, metrics, step_fn=None, client_cmd=None, genome=None, seg_len=1,
    events=False, read_cmd=None,
):
    """ONE tick of the batch-minor path: input generation, step, metric
    accumulation. `s` is batch-minor; `keys` keep their [B]-leading layout (input
    draws are vmapped batch-leading, then transposed). The single shared tick body
    for every scan loop (`run`, `run_batch_minor`, the chunked, telemetry and
    served loops) AND interactive single-tick drivers (Session.offer), so none
    of them can drift. `client_cmd` overrides the scheduled client input
    for this tick. Returns (state, metrics, StepInfo) -- the per-tick info rides
    batch-minor ([B] scalars, [BINS, B] histogram); callers that only need the
    carry drop it (XLA dead-code-eliminates the unused output).

    `events=True` (the trace plane, cfg.track_trace) additionally extracts
    this tick's protocol events from the state delta (trace/events.py) and
    returns (state, metrics, StepInfo, TickEvents). Extraction is read-only
    over values this body already computes plus the fault facts recomputed
    from the same key streams (faults.trace_fault_inputs) -- the first three
    return values are bit-identical either way (tests/test_trace.py)."""
    from raft_sim_tpu.models import raft_batched

    if step_fn is None:
        step_fn = raft_batched.step_b
    with tick_scope("faults"):
        if genome is None:
            inp = jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now))(keys, s.now)
        else:
            # [B, S] genome rows vmap alongside the keys: cluster b's inputs come
            # from ITS fault setting (sim/faults.py scenario path).
            inp = jax.vmap(
                lambda k, now, g: faults.make_inputs(
                    cfg, k, now, genome=g, seg_len=seg_len
                )
            )(keys, s.now, genome)
        if client_cmd is not None:
            # Scalar (one offer broadcast fleet-wide: Session.offer) or [B]
            # (per-cluster offer plane: the tenancy serve loop, where the batch
            # axis IS the tenancy axis and each cluster gets its tenant's own
            # command this tick).
            inp = inp._replace(
                client_cmd=jnp.broadcast_to(
                    jnp.asarray(client_cmd, inp.client_cmd.dtype),
                    inp.client_cmd.shape,
                )
            )
        if read_cmd is not None:
            # External ReadIndex ingest (the read-only traffic class riding the
            # serve path beside offered writes): overrides the scheduled
            # read cadence for this tick, exactly like client_cmd above --
            # scalar or per-cluster [B]. The config must carry the structural
            # gate (cfg.read_index).
            inp = inp._replace(
                read_cmd=jnp.broadcast_to(
                    jnp.asarray(read_cmd, inp.read_cmd.dtype), inp.read_cmd.shape
                )
            )
        inp_t = raft_batched.to_batch_minor(inp)
    s2, info = step_fn(cfg, s, inp_t)
    with tick_scope("check"):
        m2 = _accumulate(metrics, info, s.now)  # all fields [B]: elementwise
    if not events:
        return (s2, m2, info)
    from raft_sim_tpu.trace import events as tev

    if genome is None:
        crashed, cut_now, cut_prev = jax.vmap(
            lambda k, now: faults.trace_fault_inputs(cfg, k, now)
        )(keys, s.now)
    else:
        crashed, cut_now, cut_prev = jax.vmap(
            lambda k, now, g: faults.trace_fault_inputs(
                cfg, k, now, genome=g, seg_len=seg_len
            )
        )(keys, s.now, genome)
    ev = tev.extract(
        cfg, s, s2, inp_t, info, jnp.moveaxis(crashed, 0, -1), cut_now, cut_prev
    )
    return (s2, m2, info, ev)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def simulate(cfg: RaftConfig, seed, batch: int, n_ticks: int):
    """One-call batched simulation from a seed: init + scan, fully on device.

    Returns (final_state, RunMetrics) with leading batch axis. Uses the batch-minor
    hot path (each cluster bit-for-bit what `run` gives it alone).
    """
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    from raft_sim_tpu.types import init_batch

    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)
    return run_batch_minor(cfg, state, keys, n_ticks)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 5))
def simulate_scenario(cfg: RaftConfig, seed, batch: int, n_ticks: int, genome,
                      seg_len: int = 1):
    """`simulate` through the scenario path: one compiled program evaluating a
    heterogeneous fleet, cluster b under genome row b ([B, S] leaves, traced --
    new genome VALUES never recompile; only a new S or seg_len does). Init and
    key derivation are identical to `simulate`, so a homogeneous genome
    (scenario.genome.from_config) reproduces `simulate(cfg, seed, ...)`
    bit-for-bit and every (genome, seed) pair is replayable standalone."""
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    from raft_sim_tpu.types import init_batch

    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)
    return run_batch_minor(cfg, state, keys, n_ticks, genome=genome, seg_len=seg_len)


def stable_leader_ticks(metrics: RunMetrics) -> jax.Array:
    """Ticks-to-stable-leader per cluster: the tick from which leadership was held
    continuously to the end of the run (_BIG if the run ended leaderless)."""
    ended_with_leader = metrics.last_leaderless_tick < metrics.ticks - 1
    return jnp.where(ended_with_leader, metrics.last_leaderless_tick + 1, _BIG)
