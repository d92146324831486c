"""Fleet telemetry: on-device windowed aggregation + a violation flight recorder.

The reference's entire observability story is an unconditional per-iteration
println of node + message (core.clj:182-186); the rebuild's full-fidelity
equivalent -- `scan.run(trace=True)` stacking per-tick StepInfo, or whole
ClusterStates -- is exactly the memory pattern a 100k-cluster soak cannot
survive ([T] rows of everything). This module is the production-scale middle
ground, two device-side mechanisms over the SAME tick body as the hot path
(`scan.tick_batch_minor`, so telemetry can never observe a different
trajectory than it perturbs):

1. **Windowed aggregation** (`run_batch_minor_telemetry`): a nested scan folds
   each tick's StepInfo into a window-local RunMetrics inside the inner carry
   and emits ONE `WindowRecord` per `window` ticks -- [T/W] records instead of
   [T] rows. Reduction is exact, not lossy: every RunMetrics fold is
   associative over window boundaries (sums, min/max, later-wins for
   min_commit), so merging the window records with `chunked.merge_metrics`
   reproduces the monolithic run's RunMetrics BIT-FOR-BIT
   (tests/test_telemetry.py pins this against the full per-tick stack).
   `first_viol_tick` adds the one thing the run-level metrics cannot recover:
   WHEN inside the window the first invariant trip happened.

2. **Violation flight recorder** (`FlightRecorder`): a K-deep device-side ring
   of the last K ticks' StepInfo per cluster that FREEZES on the first tick
   any `viol_*` flag fires -- when 1 cluster in 100k misbehaves, its final K
   ticks come home for `sim/trace.py` rendering without ever storing full
   trajectories. The freeze includes the violating tick itself (write first,
   then latch).

Both mechanisms live in EXTRA scan-carry legs beside (state, metrics); the
ClusterState carry and the checkpoint format are untouched, and with telemetry
disabled the plain `scan.run_batch_minor` path compiles exactly as before.
The extra HBM traffic telemetry does cost is accounted statically by
`tools/traffic_audit.py --telemetry-ring` (docs/OBSERVABILITY.md has the
window/ring sizing tradeoffs).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.obs.timer import span
from raft_sim_tpu.sim import scan
from raft_sim_tpu.sim.chunked import _merge, _own_copy, _zero_metrics, merge_metrics
from raft_sim_tpu.types import StepInfo
from raft_sim_tpu.utils.config import RaftConfig

NEVER = scan.NEVER


class WindowRecord(NamedTuple):
    """One W-tick window's telemetry for every cluster (public layout: every
    leaf leads with [batch, n_windows, ...] after a run; batch-minor inside)."""

    start: jax.Array  # int32: absolute tick of the window's first tick
    # Absolute tick of the first invariant violation inside this window
    # (NEVER if the window is clean) -- the intra-window locator the
    # window-level RunMetrics cannot provide.
    first_viol_tick: jax.Array  # int32
    # RunMetrics accumulated over THIS window only. Folding these with
    # chunked.merge_metrics across windows reproduces the monolithic run's
    # metrics bit-for-bit (every fold op is associative across the cut).
    metrics: scan.RunMetrics


class FlightRecorder(NamedTuple):
    """Device-side ring of the last K ticks' StepInfo per cluster, frozen at
    the first violation. Internal layout is batch-minor (`ring` leaves
    [K, ..., B]); `from_batch_minor` restores the public [B, K, ...] form."""

    ring: StepInfo  # each StepInfo leaf stacked K deep along axis 0
    tick: jax.Array  # [K] int32: absolute tick held in each slot (-1 = empty)
    pos: jax.Array  # int32: ticks recorded so far (next slot = pos % K)
    frozen: jax.Array  # bool: latched by the first viol_* tick (inclusive)


def init_recorder(cfg: RaftConfig, k: int, batch: int) -> FlightRecorder:
    """Zeroed K-deep recorder, batch-minor ([..., B] trailing on every leaf)."""
    from raft_sim_tpu.types import LAT_HIST_BINS

    def leaf(dtype, *mid):
        return jnp.zeros((k, *mid, batch), dtype)

    ring = StepInfo(
        viol_election_safety=leaf(bool),
        viol_commit=leaf(bool),
        viol_log_matching=leaf(bool),
        leader=leaf(jnp.int32),
        n_leaders=leaf(jnp.int32),
        max_term=leaf(jnp.int32),
        max_commit=leaf(jnp.int32),
        min_commit=leaf(jnp.int32),
        msgs_delivered=leaf(jnp.int32),
        cmds_injected=leaf(jnp.int32),
        lat_sum=leaf(jnp.int32),
        lat_cnt=leaf(jnp.int32),
        lat_hist=leaf(jnp.int32, LAT_HIST_BINS),
        lat_excluded=leaf(jnp.int32),
        noop_blocked=leaf(jnp.int32),
        lm_skipped_pairs=leaf(jnp.int32),
        reads_served=leaf(jnp.int32),
        read_lat_sum=leaf(jnp.int32),
        read_hist=leaf(jnp.int32, LAT_HIST_BINS),
        viol_read_stale=leaf(bool),
        fsync_lag_sum=leaf(jnp.int32),
        fsync_lag_max=leaf(jnp.int32),
        joint_ticks=leaf(jnp.int32),
        xfer_fired=leaf(jnp.int32),
        lease_reads=leaf(jnp.int32),
        fsync_stalls=leaf(jnp.int32),
        torn_lost=leaf(jnp.int32),
        late_grants=leaf(jnp.int32),
        lat_ring_ticks=leaf(jnp.int32),
    )
    return FlightRecorder(
        ring=ring,
        tick=jnp.full((k, batch), -1, jnp.int32),
        pos=jnp.zeros((batch,), jnp.int32),
        frozen=jnp.zeros((batch,), bool),
    )


def _record(
    rec: FlightRecorder, info: StepInfo, now: jax.Array, k: int, trig: jax.Array
) -> FlightRecorder:
    """Write one tick's StepInfo into the ring (per-cluster slot pos % K),
    gated on ~frozen; latch frozen AFTER the write so the TRIGGERING tick is
    the ring's newest entry. `trig` is the [B] freeze predicate -- any viol_*
    flag by default, or "an event of the armed kind fired" when a trigger
    kind is set (run_batch_minor_telemetry `trigger_kind`): the lead-up to a
    non-violating anomaly is capturable, not only violations."""
    slot = rec.pos % k  # [B]
    write = ~rec.frozen  # [B]
    oh1 = (jnp.arange(k, dtype=jnp.int32)[:, None] == slot[None, :]) & write[None, :]

    def upd(leaf, val):
        # leaf [K, ..., B]; val [..., B]: broadcast the slot one-hot over the
        # middle dims (only lat_hist has one).
        oh = oh1.reshape((k,) + (1,) * (leaf.ndim - 2) + oh1.shape[-1:])
        return jnp.where(oh, val[None], leaf)

    ring = StepInfo(*(upd(l, v) for l, v in zip(rec.ring, info)))
    return FlightRecorder(
        ring=ring,
        tick=upd(rec.tick, now),
        pos=rec.pos + write,
        frozen=rec.frozen | (write & trig),
    )


def run_batch_minor_telemetry(
    cfg: RaftConfig,
    state,
    keys: jax.Array,
    n_ticks: int,
    window: int,
    recorder: FlightRecorder | None = None,
    step_fn=None,
    genome=None,
    seg_len: int = 1,
    trace_spec=None,
    trace_persist=None,
    trigger_kind: int | None = None,
):
    """`scan.run_batch_minor` with telemetry carry legs: same trajectories
    (bit-for-bit -- the tick body is shared), plus [n_ticks/window]
    WindowRecords and an optional flight recorder threaded through.
    `genome`/`seg_len` select the scenario input path (scan.tick_batch_minor):
    window records over a heterogeneous fleet are the search loop's fitness
    signal (scenario/search.py).

    `n_ticks` must divide by `window` (the chunked driver handles remainders
    by a final shorter call). `recorder` enters and leaves BATCH-MINOR (the
    chunked path threads it across calls without relayouting); pass
    `init_recorder(...)` to start one, None to disable. State/keys/metrics/
    records use the public [B, ...]-leading convention at entry/exit.

    The PROTOCOL TRACE PLANE (raft_sim_tpu/trace; requires cfg.track_trace):

      trace_spec     a trace.TraceSpec arms per-cluster event extraction +
                     the window event buffer + the transition-coverage
                     bitmap; the per-window exports ride a fifth/sixth
                     return value. `trace_persist` threads the cross-window
                     trace state between chunked calls (None starts fresh).
      trigger_kind   an EV_* kind re-arms the flight recorder's freeze on
                     the first occurrence of that event kind instead of the
                     default viol_* trigger -- "capture the lead-up to the
                     first leadership change/crash/...", the gap
                     docs/OBSERVABILITY.md used to note.

    With neither set, this function lowers EXACTLY as before -- no trace leg
    exists in the program (the zero-cost-when-off contract config.track_trace
    documents; tests/test_trace.py pins bit-exactness both ways).

    Returns (final_state, metrics, records, recorder), plus
    (trace_windows, trace_persist) appended when trace_spec is given --
    trace_windows is a batch-minor stacked trace.TraceWindowOut (leaves
    [n_windows, ..., B]), trace_persist the carried trace.TracePersist.
    """
    if n_ticks % window:
        raise ValueError(f"n_ticks {n_ticks} must divide by window {window}")
    if step_fn is None:
        step_fn = raft_batched.step_b
    batch = state.role.shape[0]
    ring_k = 0 if recorder is None else recorder.tick.shape[0]
    need_events = trace_spec is not None or trigger_kind is not None
    if need_events and not cfg.track_trace:
        raise ValueError(
            "protocol tracing / event triggers need cfg.track_trace=True "
            "(the zero-cost-when-off contract: untraced configs must compile "
            "untraced programs -- utils/config.py)"
        )
    s_t = raft_batched.to_batch_minor(state)
    m0 = raft_batched.to_batch_minor(scan.init_metrics_batch(batch))

    if not need_events:

        def inner(carry, _):
            s, wm, fv, rec = carry
            now = s.now  # [B] absolute tick BEFORE the step (lockstep across B)
            s2, wm2, info = scan.tick_batch_minor(
                cfg, s, keys, wm, step_fn=step_fn, genome=genome, seg_len=seg_len
            )
            bad = scan.step_bad(info)
            fv2 = jnp.minimum(fv, jnp.where(bad, now, NEVER))
            rec2 = _record(rec, info, now, ring_k, bad) if ring_k else rec
            return (s2, wm2, fv2, rec2), None

        def outer(carry, _):
            s, m, rec = carry
            start = s.now
            fv0 = jnp.full((batch,), NEVER, jnp.int32)
            (s2, wm, fv, rec2), _ = lax.scan(
                inner, (s, m0, fv0, rec), None, length=window
            )
            out = WindowRecord(start=start, first_viol_tick=fv, metrics=wm)
            return (s2, merge_metrics(m, wm), rec2), out

        (final_t, metrics, rec_t), recs = lax.scan(
            outer, (s_t, m0, recorder), None, length=n_ticks // window
        )
        # Records stack [n_windows, ..., B]: one batch-axis move yields the
        # public [B, n_windows, ...] layout (per-cluster leading).
        return (
            raft_batched.from_batch_minor(final_t),
            raft_batched.from_batch_minor(metrics),
            raft_batched.from_batch_minor(recs),
            rec_t,
        )

    from raft_sim_tpu.trace import events as tev
    from raft_sim_tpu.trace import ring as tring

    if trace_spec is not None and trace_persist is None:
        trace_persist = tring.init_persist(trace_spec, batch)
    tp0 = trace_persist if trace_spec is not None else ()

    def inner_t(carry, _):
        s, wm, fv, rec, tw, tp = carry
        now = s.now
        s2, wm2, info, ev = scan.tick_batch_minor(
            cfg, s, keys, wm, step_fn=step_fn, genome=genome, seg_len=seg_len,
            events=True,
        )
        bad = scan.step_bad(info)
        fv2 = jnp.minimum(fv, jnp.where(bad, now, NEVER))
        trig = bad if trigger_kind is None else tev.any_of_kind(cfg, ev, trigger_kind)
        rec2 = _record(rec, info, now, ring_k, trig) if ring_k else rec
        if trace_spec is not None:
            tw, tp = tring.record(cfg, trace_spec, tw, tp, ev, now)
        return (s2, wm2, fv2, rec2, tw, tp), None

    def outer_t(carry, _):
        s, m, rec, tp = carry
        start = s.now
        fv0 = jnp.full((batch,), NEVER, jnp.int32)
        tw0 = tring.init_window(trace_spec, batch) if trace_spec is not None else ()
        (s2, wm, fv, rec2, tw, tp2), _ = lax.scan(
            inner_t, (s, m0, fv0, rec, tw0, tp), None, length=window
        )
        rec_out = WindowRecord(start=start, first_viol_tick=fv, metrics=wm)
        out = (
            (rec_out, tring.TraceWindowOut(win=tw, cov=tp2.cov))
            if trace_spec is not None
            else rec_out
        )
        return (s2, merge_metrics(m, wm), rec2, tp2), out

    (final_t, metrics, rec_t, tp_final), outs = lax.scan(
        outer_t, (s_t, m0, recorder, tp0), None, length=n_ticks // window
    )
    recs, traws = outs if trace_spec is not None else (outs, None)
    base = (
        raft_batched.from_batch_minor(final_t),
        raft_batched.from_batch_minor(metrics),
        raft_batched.from_batch_minor(recs),
        rec_t,
    )
    if trace_spec is None:
        return base
    # Trace exports stay batch-minor (leaves [n_windows, ..., B]): the sink /
    # history builder consume them host-side per window, like the recorder.
    return base + (traws, tp_final)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5, 7, 8, 9))
def simulate_windowed(
    cfg: RaftConfig, seed, batch: int, n_ticks: int, window: int, ring: int = 0,
    genome=None, seg_len: int = 1, trace=None, trigger_kind: int | None = None,
):
    """`scan.simulate` with telemetry: one-call batched init + windowed scan.
    Returns (final_state, metrics, records, recorder) -- metrics/trajectories
    bit-identical to `scan.simulate` for the same (cfg, seed, batch, n_ticks).
    `ring` > 0 enables the flight recorder at that depth. `genome` ([B, S]
    rows, traced) selects the scenario path: the search loop evaluates a whole
    genome population in THIS one device call, and new genome values reuse the
    compiled program (only a new S/seg_len recompiles). `trace` (a static
    trace.TraceSpec; requires cfg.track_trace) arms the protocol trace plane
    and appends (trace_windows, trace_persist) to the return -- the coverage
    search's per-generation call; `trigger_kind` re-arms the flight
    recorder's freeze on an event kind (run_batch_minor_telemetry)."""
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    from raft_sim_tpu.types import init_batch

    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)
    rec = init_recorder(cfg, ring, batch) if ring else None
    return run_batch_minor_telemetry(
        cfg, state, keys, n_ticks, window, rec, genome=genome, seg_len=seg_len,
        trace_spec=trace, trigger_kind=trigger_kind,
    )


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 8, 9, 11), donate_argnums=(1,))
def _chunk_t_donate_trace(cfg, state, keys, rec, n, window, ring_k, genome=None,
                          seg_len=1, trace_spec=None, trace_persist=None,
                          trigger_kind=None):
    """The traced-soak chunk: `_chunk_t_donate` plus the trace-plane legs
    (separate entry point so the UNTRACED soak program and its donation pin
    stay byte-identical to pre-trace builds). Same donation contract: the
    fleet carry is donated chunk-to-chunk; the trace persist legs are small
    ([B]-scalars + COV_WORDS words) and threaded un-donated like the
    recorder."""
    recorder = rec if ring_k else None
    return run_batch_minor_telemetry(
        cfg, state, keys, n, window, recorder, genome=genome, seg_len=seg_len,
        trace_spec=trace_spec, trace_persist=trace_persist,
        trigger_kind=trigger_kind,
    )


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 8), donate_argnums=(1,))
def _chunk_t_donate(cfg, state, keys, rec, n, window, ring_k, genome=None,
                    seg_len=1):
    """The soak-path steady-state chunk: like `chunked._chunk_donate`, the
    previous chunk's state is donated so a 10M-tick telemetry run holds ONE
    fleet in HBM, not two. The recorder is small (K ring slots) and threaded
    un-donated. Pinned by the cost model's donation audit (`cost-donation`),
    same as the plain chunk loop."""
    recorder = rec if ring_k else None
    return run_batch_minor_telemetry(
        cfg, state, keys, n, window, recorder, genome=genome, seg_len=seg_len
    )


def run_chunked_telemetry(
    cfg: RaftConfig,
    state,
    keys: jax.Array,
    n_ticks: int,
    window: int,
    recorder: FlightRecorder | None = None,
    chunk: int = 4096,
    callback=None,
    genome=None,
    seg_len: int = 1,
    perf=None,
    trace_spec=None,
    trace_persist=None,
    trigger_kind: int | None = None,
    trace_callback=None,
    chunk_hook=None,
):
    """Long-horizon telemetry runs: the `chunked.run_chunked` analogue with
    window records offloaded to the host between chunks (so a 10M-tick soak
    holds at most chunk/window records on device at once).

    `chunk_hook(ticks_done, recorder)` is a host-side observer handed the
    CARRIED flight recorder (batch-minor) after each chunk -- the health
    plane's evidence hook: a firing alert snapshots the named clusters' rings
    via `export_cluster` without freezing or perturbing the device carry.
    Read-only by contract; it cannot return a replacement.

    `perf` (an obs.ChunkTimer) attributes each chunk's wall time and samples
    the soak program's jit cache at chunk boundaries (recompile watchdog),
    exactly like `chunked.run_chunked` -- see docs/OBSERVABILITY.md.

    Chunks are rounded to whole windows; a final REMAINDER window shorter than
    `window` is emitted if n_ticks does not divide (records are
    self-describing: `metrics.ticks` carries each window's true width).
    `callback(ticks_done, state, merged_metrics, records)` receives each
    chunk's records in the public [B, n_windows, ...] layout; returning True
    stops early. Returns (final_state, merged_metrics, recorder) -- with
    `trace_persist` appended when `trace_spec` is given.

    The trace plane (trace_spec / trace_persist / trigger_kind: see
    run_batch_minor_telemetry) streams like the window records: each chunk's
    stacked TraceWindowOut (batch-minor) is handed to
    `trace_callback(ticks_done, trace_windows)` -- the sink's
    `append_trace` -- and the cross-window trace state threads chunk to
    chunk. Untraced calls run the IDENTICAL `_chunk_t_donate` program as
    before (the traced soak is its own pinned entry point).

    Buffer ownership matches `chunked.run_chunked`: the caller's `state` stays
    valid (one up-front copy, owned by the loop), each chunk's state is
    donated to the next, and a `state` captured inside `callback` is only
    valid until the callback returns -- `jax.device_get` anything it keeps.
    Same for `chunk_hook`'s recorder and the callback's `records`: the
    telemetry soak is walked by analysis Pass D's use-after-donate lint and
    run under the donation-poison sanitizer (`tools/check.py --race
    --dynamic`), so a hook that retains the live carry past its return is a
    gated finding, not a latent chip-session bug.

    Host spans as in `chunked.run_chunked`: `fleet.init`, `fleet.copy`,
    then per chunk `fleet.dispatch` (its tick count), `fleet.merge` and
    `fleet.callback` (the trace callback, the chunk hook and the callback
    together).
    """
    batch = state.role.shape[0]
    ring_k = 0 if recorder is None else recorder.tick.shape[0]
    need_events = trace_spec is not None or trigger_kind is not None
    win_per_chunk = max(1, chunk // window)
    with span("fleet.init"):
        metrics = _zero_metrics(batch)
    done = 0
    with span("fleet.copy"):
        state = _own_copy(state)
    if trace_spec is not None and trace_persist is None:
        from raft_sim_tpu.trace import ring as tring

        trace_persist = tring.init_persist(trace_spec, batch)
    if perf is not None:
        probe = _chunk_t_donate_trace if need_events else _chunk_t_donate
        perf.add_probe("telemetry._chunk_t_donate", probe)
    while done < n_ticks:
        left = n_ticks - done
        if left >= window:
            n = min(win_per_chunk, left // window) * window
            w = window
        else:
            n = w = left  # remainder: one final short window
        with span("fleet.dispatch", n) as sp:
            if need_events:
                out = _chunk_t_donate_trace(
                    cfg, state, keys, recorder, n, w, ring_k, genome, seg_len,
                    trace_spec, trace_persist, trigger_kind,
                )
                if trace_spec is not None:
                    state, m, recs, recorder, traws, trace_persist = out
                else:
                    state, m, recs, recorder = out
                    traws = None
            else:
                state, m, recs, recorder = _chunk_t_donate(
                    cfg, state, keys, recorder, n, w, ring_k, genome, seg_len
                )
                traws = None
        if perf is not None:
            perf.dispatch_span(sp)
        with span("fleet.merge"):
            metrics = _merge(metrics, m)
        done += n
        # The callback's window export (sink append, apply-log update) is
        # this chunk's host gap; close after it, synced on the chunk metrics.
        with span("fleet.callback"):
            if traws is not None and trace_callback is not None:
                trace_callback(done, traws)
            if chunk_hook is not None:
                chunk_hook(done, recorder)
            stop = callback is not None and callback(done, state, metrics, recs)
        if perf is not None:
            perf.end(sync=lambda: np.asarray(m.ticks))
        if stop:
            break
    if trace_spec is not None:
        return state, metrics, recorder, trace_persist
    return state, metrics, recorder


def reduce_records(records: WindowRecord) -> scan.RunMetrics:
    """Fold a stacked WindowRecord (leaves [B, n_windows, ...]) back into the
    run-level RunMetrics ([B, ...]) -- the host-side half of the bit-exactness
    contract: this equals the monolithic scan's metrics exactly."""
    n_windows = records.start.shape[1]
    take = lambda w: jax.tree.map(lambda x: x[:, w], records.metrics)
    m = take(0)
    for w in range(1, n_windows):
        m = merge_metrics(m, take(w))
    return m


def window_cluster_counters(records: WindowRecord) -> list[dict]:
    """Split a stacked WindowRecord (public layout: leaves [B, n_windows, ...])
    into one host-side dict of per-cluster numpy counters PER WINDOW -- the
    health plane's window units (health/sli.py consumes them; health/evidence
    freezes them per culprit cluster). `leaderless` marks clusters whose
    window-local first_leader_tick never latched: no tick in that window
    observed a leader, the availability = 1 - leaderless-fraction signal.
    Read-only host math over an already-fetched record -- the sink path calls
    this on the same host copy it aggregates into windows.jsonl lines."""
    start = np.asarray(records.start)
    n_windows = start.shape[1]
    m = {
        f: np.asarray(getattr(records.metrics, f))
        for f in ("ticks", "violations", "first_leader_tick", "total_cmds",
                  "reads_served", "lat_sum", "lat_cnt", "lat_hist",
                  "read_hist", "fsync_lag_sum", "fsync_lag_max")
    }
    units = []
    for w in range(n_windows):
        units.append({
            "start": int(start[0, w]),
            "ticks": int(m["ticks"][0, w]),
            "violations": m["violations"][:, w].astype(np.int64),
            "leaderless": m["first_leader_tick"][:, w] == NEVER,
            "cmds": m["total_cmds"][:, w].astype(np.int64),
            "reads": m["reads_served"][:, w].astype(np.int64),
            "lat_sum": m["lat_sum"][:, w].astype(np.int64),
            "lat_cnt": m["lat_cnt"][:, w].astype(np.int64),
            "lat_hist": m["lat_hist"][:, w].astype(np.int64),
            "read_hist": m["read_hist"][:, w].astype(np.int64),
            # Durable storage plane (raft_sim_tpu/storage): node-tick-summed
            # and window-max fsync lag (log_len - dur_len). All-zero when
            # the plane is off (the gated StepInfo legs are host zeros).
            "fsync_lag_sum": m["fsync_lag_sum"][:, w].astype(np.int64),
            "fsync_lag_max": m["fsync_lag_max"][:, w].astype(np.int64),
        })
    return units


def export_cluster(recorder: FlightRecorder, cluster: int):
    """Decode one cluster's ring into chronological (ticks, stacked StepInfo)
    ready for `trace.info_lines` -- the flight-recorder readout. Takes the
    recorder in its carried batch-minor layout (what every run/chunk call
    returns); empty slots (tick < 0) are dropped.

    Returns (ticks [k_valid] np.ndarray, StepInfo with leading [k_valid] axis),
    oldest tick first -- for a frozen cluster the last row IS the violation."""

    def leaf(x):  # [K, ..., B] -> this cluster's [K, ...]
        return np.moveaxis(np.asarray(x), -1, 0)[cluster]

    ticks = leaf(recorder.tick)  # [K]
    order = np.argsort(ticks, kind="stable")
    order = order[ticks[order] >= 0]
    infos = StepInfo(*(leaf(l)[order] for l in recorder.ring))
    return ticks[order], infos
