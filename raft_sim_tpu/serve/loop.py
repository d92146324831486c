"""The standing-fleet service loop: streaming ingest in, telemetry + deltas out.

`driver serve`'s engine -- the sixth subsystem's core. One compiled scan
program (`run_windowed_served`) advances the whole fleet chunk by chunk with
the per-tick client command coming from an EXPLICIT [T] offer plane (scan xs)
instead of the scheduled cadence, folding telemetry windows on device exactly
like sim/telemetry.py. Around it, `ServeSession` runs the double-buffered
host<->device exchange ISSUE 6 specifies:

    dispatch chunk k (async)  ->  pack chunk k+1's offer plane from the
    ingest queue while the device runs  ->  collect chunk k's telemetry
    windows + commit deltas  ->  repeat.

Buffer discipline matches the other long-horizon loops: the previous chunk's
fleet state is DONATED (`_serve_chunk`, pinned by the cost model's donation
audit), so a standing service holds ONE fleet in HBM; the ingest plane and the
delta watermark are the only per-chunk host traffic. After warmup the loop
compiles NOTHING: chunk shape, window, and config are fixed, commands are
traced data (the distinct-lowering pin in tests/golden_jaxpr_hist.json gates
this, and tests/test_serve.py asserts the jit cache stays at one entry).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.obs.timer import span
from raft_sim_tpu.serve import deltas as deltas_mod
from raft_sim_tpu.serve.ingest import CommandSource
from raft_sim_tpu.sim import scan
from raft_sim_tpu.sim.chunked import _own_copy, merge_metrics
from raft_sim_tpu.sim.telemetry import NEVER, WindowRecord
from raft_sim_tpu.types import NIL
from raft_sim_tpu.utils.config import RaftConfig


def serve_config(cfg: RaftConfig) -> RaftConfig:
    """The serve-mode variant of a config: external ingest replaces the
    scheduled cadences (client_interval forced 0 -- ALL write traffic is
    offered -- and, when the config carries the ReadIndex plane, the
    scheduled read cadence collapses into serve_reads the same way), with
    the offer-tick plane kept live via serve_ingest."""
    repl: dict = {}
    if not (cfg.serve_ingest and cfg.client_interval == 0):
        repl.update(serve_ingest=True, client_interval=0)
    if cfg.read_index and not (cfg.serve_reads and cfg.read_interval == 0):
        # Reads become externally offered too (per-tenant read planes /
        # Session.offer_read) -- the read-side mirror of the write collapse.
        repl.update(serve_reads=True, read_interval=0)
    return dataclasses.replace(cfg, **repl) if repl else cfg


def run_windowed_served(cfg: RaftConfig, state, keys, cmds, window: int,
                        reads=None):
    """Scan the fleet through one chunk of `cmds` ([T, B] int32 per-cluster
    offer plane, NIL = no offer in that (tick, cluster) slot -- the batch
    axis IS the tenancy axis), emitting one WindowRecord per `window` ticks.
    `reads` ([T, B] int32, 1 = offer a ReadIndex read, NIL = none; requires
    cfg.read_index) is the read-side plane: None on write-only configs, so
    their programs carry no read leg.

    Same shared tick body as every other loop (scan.tick_batch_minor with the
    per-tick client_cmd/read_cmd overrides Session.offer/offer_read use), so
    the served path can never drift from run(); same window algebra as
    telemetry.run_batch_minor_telemetry, so the streamed records merge
    bit-exactly into run-level metrics. T must divide by `window`.
    Returns (final_state, chunk_metrics, records) in public [B, ...] layouts.
    """
    n_ticks = cmds.shape[0]
    if n_ticks % window:
        raise ValueError(f"chunk of {n_ticks} ticks must divide by window {window}")
    if reads is not None and not cfg.read_index:
        raise ValueError(
            "a read plane needs the ReadIndex gate (cfg.serve_reads or a "
            "read cadence) -- utils/config.py"
        )
    batch = state.role.shape[0]
    s_t = raft_batched.to_batch_minor(state)
    m0 = raft_batched.to_batch_minor(scan.init_metrics_batch(batch))

    def inner(carry, xs):
        s, wm, fv = carry
        cmd, read = xs if reads is not None else (xs, None)
        now = s.now  # [B] absolute tick BEFORE the step (lockstep across B)
        s2, wm2, info = scan.tick_batch_minor(
            cfg, s, keys, wm, client_cmd=cmd, read_cmd=read
        )
        fv2 = jnp.minimum(fv, jnp.where(scan.step_bad(info), now, NEVER))
        return (s2, wm2, fv2), None

    def outer(carry, xs_win):
        s, m = carry
        start = s.now
        fv0 = jnp.full((batch,), NEVER, jnp.int32)
        (s2, wm, fv), _ = lax.scan(inner, (s, m0, fv0), xs_win)
        out = WindowRecord(start=start, first_viol_tick=fv, metrics=wm)
        return (s2, merge_metrics(m, wm)), out

    cmd_wins = cmds.reshape(n_ticks // window, window, batch)
    xs = (
        (cmd_wins, reads.reshape(n_ticks // window, window, batch))
        if reads is not None
        else cmd_wins
    )
    (final_t, metrics), recs = lax.scan(outer, (s_t, m0), xs)
    return (
        raft_batched.from_batch_minor(final_t),
        raft_batched.from_batch_minor(metrics),
        raft_batched.from_batch_minor(recs),
    )


@functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=(1,))
def _serve_chunk(cfg: RaftConfig, state, keys, cmds, reads, window: int):
    """The steady-state serve chunk: the previous chunk's fleet is DONATED
    back to XLA (one fleet in HBM, like chunked._chunk_donate -- donation
    status pinned by the cost model's `cost-donation` rule). `keys` and the
    offer/read planes are never donated."""
    return run_windowed_served(cfg, state, keys, cmds, window, reads=reads)


@functools.partial(jax.jit, static_argnums=(0, 2, 4))
def simulate_serve(cfg: RaftConfig, seed, batch: int, cmds, window: int,
                   reads=None):
    """One-call served simulation from a seed: init + served windowed scan.
    The audit entry the static gates lower (`jaxpr_audit.serve_scan_jaxpr` ->
    Pass A rules + Pass C pricing) and the parity-test entry (two runs
    differing only in offer VALUES share this one compiled program)."""
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    from raft_sim_tpu.types import init_batch

    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)
    return run_windowed_served(cfg, state, keys, cmds, window, reads=reads)


class ServeSession:
    """A standing fleet accepting streamed commands between chunks.

    >>> s = ServeSession(RaftConfig(n_nodes=5), batch=8, seed=0, chunk=128)
    >>> stats = s.serve(CommandSource([7, 7, 2**31 - 1]), chunks=4)
    >>> s.delta_rows  # every cluster's committed (index, value, tick) stream

    Multi-tenant form (serve/tenancy.py): partition the cluster range among
    named tenants, each with its own source, read demand, and export streams
    -- one compiled program either way (the batch axis is the tenancy axis):

    >>> from raft_sim_tpu.serve.tenancy import Tenant
    >>> s = ServeSession(cfg, batch=8, tenants=[
    ...     Tenant("a", 4, source=[1, 2, 3]), Tenant("b", 4, reads=100)])
    >>> stats = s.serve()

    The steady-state loop is OVERLAPPED: while chunk k computes on device,
    the host exports chunk k-1's windows + delta rows and packs chunk k+1's
    planes (both timed into the perf row's host_s -- the dispatch->sync
    window -- via ChunkTimer.annotate, so the overlap structure is a
    perf.jsonl fact tests assert, not prose), and chunk k's delta
    extraction rounds are enqueued behind it on the device stream
    (DeltaStream.begin_rounds). Only the sync on chunk k's metrics and the
    dispatch of chunk k+1 sit on the serial path.

    `sink` (a utils/telemetry_sink.TelemetrySink) streams telemetry windows to
    windows.jsonl and commit deltas to deltas.jsonl continuously -- the
    schema'd export surface, validated by the CI serve smoke job; with
    tenants, per-tenant views land under tenants/<name>/ (tenancy.py).
    """

    def __init__(
        self,
        cfg: RaftConfig,
        batch: int = 1,
        seed: int = 0,
        chunk: int = 256,
        window: int = 64,
        delta_depth: int = 64,
        sink=None,
        warmup_ticks: int = 0,
        perf=None,
        tenants=None,
        health=None,
    ):
        if chunk % window:
            raise ValueError(f"chunk {chunk} must divide by window {window}")
        self.cfg = serve_config(cfg)
        self.batch = batch
        # The ReadIndex plane rides the chunk program iff the serve config
        # carries the gate; the read plane's SHAPE is then fixed too, so the
        # jit cache stays flat whether or not any tenant demands reads.
        self.reads_enabled = self.cfg.read_index
        self.router = None
        if tenants is not None:
            from raft_sim_tpu.serve.tenancy import TenantRouter

            self.router = TenantRouter(tenants, batch, self.reads_enabled)
            if sink is not None:
                self.router.attach_dir(sink.directory)
        # Fixed extraction-round count of the overlapped drain: commit
        # throughput is <= 1 entry/cluster/tick, so rounds * depth >= chunk
        # keeps the stream dry in steady state (+1 absorbs boundary slack);
        # any remainder is backpressure picked up next chunk, never loss.
        self._drain_rounds = -(-chunk // delta_depth) + 1
        self.seed = seed
        self.chunk = chunk
        self.window = window
        self.sink = sink
        # Per-chunk runtime attribution (obs.ChunkTimer): dispatch in
        # _dispatch, ingest packing as the host gap, the _collect device_get
        # as the device wait -- the double buffer's natural phase boundaries,
        # so serving pays NO extra sync for attribution. The serve chunk's
        # jit cache is sampled every boundary (the flat-cache discipline
        # tests/test_serve.py pins, now a streamed watchdog counter too).
        self.perf = perf
        if perf is not None:
            perf.add_probe("serve._serve_chunk", _serve_chunk)
            if warmup_ticks:
                # Warmup chunks (leader election before the first offer) are
                # compile + convergence time, never steady serving -- and the
                # FIRST serving chunk after them pays the one-time
                # donated-carry respecialization (timer docstring), so it is
                # excluded too.
                perf.warmup_chunks = max(
                    perf.warmup_chunks,
                    self._round_up(warmup_ticks) // chunk + 1,
                )
        if sink is not None:
            # The session owns the sink directory's delta stream (the sink
            # itself owns manifest/windows/summary): truncate any stale file
            # up front so per-cluster streams always start dense at index 1
            # (appending after an old run would trip validate_deltas).
            self._deltas_path = os.path.join(sink.directory, "deltas.jsonl")
            open(self._deltas_path, "w").close()
        root = jax.random.key(seed)
        k_init, k_run = jax.random.split(root)
        from raft_sim_tpu.types import init_batch

        # The loop owns its fleet copy (donation discipline: see _serve_chunk).
        self.state = _own_copy(init_batch(self.cfg, k_init, batch))
        self.keys = jax.random.split(k_run, batch)
        self.metrics = scan.init_metrics_batch(batch)
        self.deltas = deltas_mod.DeltaStream(batch, depth=delta_depth)
        self.delta_rows: list[dict] = []
        self.chunks_done = 0
        self.ticks_done = 0
        self.warmup_chunks = 0
        # SLO monitoring (raft_sim_tpu/health): armed AFTER warmup below, so
        # election convergence is never billed against the availability
        # budget -- the same exclusion the perf warmup_chunks bump applies.
        self.monitors: list = []
        self._health_spec = health
        self._health_status: tuple | None = None
        if warmup_ticks:
            # Elect leaders before the first real offer plane (an offer into a
            # leaderless tick is dropped, exactly like the reference's curl
            # against a booting cluster). Warmup is accounted separately:
            # serve()'s chunk budget and throughput stats cover SERVING only.
            self._advance(self._round_up(warmup_ticks))
            self.warmup_chunks, self.chunks_done = self.chunks_done, 0
            self.ticks_done = 0
        if health is not None:
            if sink is None:
                raise ValueError(
                    "health monitoring needs a sink: the health/alert streams "
                    "and evidence bundles live in its directory"
                )
            from raft_sim_tpu.health import HealthMonitor, HealthWriter, load_spec
            from raft_sim_tpu.utils.telemetry_sink import config_hash

            spec = load_spec(health)
            writer = HealthWriter(sink.directory)
            refs = {
                "config_hash": config_hash(self.cfg),
                "seed": int(seed),
                "batch": int(batch),
                "source": "serve",
            }
            capture = lambda alert, clusters: {"refs": refs}
            # One fleet monitor (it owns the runtime SLIs: the perf rows are
            # loop-wide) + one per tenant slice when the session is
            # multi-tenant -- all sharing one writer, scope-tagged lines.
            self.monitors.append(HealthMonitor(
                spec, batch=batch, writer=writer, scope="fleet",
                perf=perf, capture=capture,
            ))
            if self.router is not None:
                for t in self.router.tenants:
                    self.monitors.append(HealthMonitor(
                        spec, batch=t.hi - t.lo, writer=writer,
                        scope=f"tenant:{t.name}", cluster_base=t.lo,
                        capture=capture,
                    ))

    def _round_up(self, ticks: int) -> int:
        return -(-ticks // self.chunk) * self.chunk

    def _nil_planes(self, ticks: int):
        cmds = np.full((ticks, self.batch), NIL, np.int32)
        reads = (
            np.full((ticks, self.batch), NIL, np.int32)
            if self.reads_enabled
            else None
        )
        return cmds, reads

    def _advance(self, ticks: int) -> None:
        """Synchronous warmup advance (no offers): dispatch + collect per
        chunk through the SAME chunk program the serving loop uses."""
        for _ in range(ticks // self.chunk):
            self._dispatch(*self._nil_planes(self.chunk))
            self._collect()

    def _dispatch(self, cmds_np: np.ndarray, reads_np=None):
        """Issue one chunk (async under jax dispatch); the caller's host
        window (export + packing) runs while this one computes."""
        ticks = int(cmds_np.shape[0])
        with span("serve.dispatch", ticks) as sp:
            cmds = jnp.asarray(cmds_np, jnp.int32)
            reads = None if reads_np is None else jnp.asarray(reads_np, jnp.int32)
            self.state, self._m_pending, self._recs_pending = _serve_chunk(
                self.cfg, self.state, self.keys, cmds, reads, self.window
            )
        if self.perf is not None:
            self.perf.dispatch_span(sp)
        self.chunks_done += 1
        self.ticks_done += ticks
        self._last_offered = int(np.sum(cmds_np != NIL)) + (
            0 if reads_np is None else int(np.sum(reads_np != NIL))
        )

    def _export(self, recs, rows: list[dict]) -> None:
        """Host-side export of one collected chunk: fleet sink streams,
        per-tenant routing/credits, and the ack ledgers. In the overlapped
        loop this runs for chunk k-1 WHILE chunk k computes (its duration is
        the perf row's export_s annotation, inside host_s)."""
        if recs is not None:
            # ONE device->host fetch, fanned out to the fleet sink and every
            # tenant's slice (credit_windows would otherwise re-convert the
            # whole record tree per tenant, inside the timed export window).
            recs = jax.device_get(recs)
            if self.sink is not None:
                self.sink.append_windows(recs)
            if self.router is not None:
                self.router.credit_windows(recs)
            if self.monitors:
                self._observe_health(recs)
        self.delta_rows.extend(rows)
        if self.sink is not None and rows:
            deltas_mod.append_delta_rows(self._deltas_path, rows)
        if self.router is not None and rows:
            self.router.route_deltas(rows)

    def _observe_health(self, recs) -> None:
        """Fan one collected chunk's window units to the fleet + tenant
        monitors (units split once, tenant views are numpy slices) and print
        the live status line to stderr whenever any scope changes state."""
        from raft_sim_tpu.health.monitor import slice_units
        from raft_sim_tpu.sim import telemetry

        units = telemetry.window_cluster_counters(recs)
        for m in self.monitors:
            if m.cluster_base == 0 and m.batch == self.batch:
                m.observe_units(units)
            else:
                m.observe_units(
                    slice_units(units, m.cluster_base, m.cluster_base + m.batch)
                )
        status = tuple(m.status for m in self.monitors)
        if self._health_status is not None and status != self._health_status:
            print(
                "; ".join(m.status_line() for m in self.monitors),
                file=sys.stderr,
            )
        self._health_status = status

    def _collect(self) -> list[dict]:
        """Synchronous collect (warmup / single-step use): merge the
        dispatched chunk's outputs, drain its deltas to dryness, export."""
        self.metrics = merge_metrics(self.metrics, self._m_pending)
        if self.perf is not None:
            self.perf.end(sync=lambda: np.asarray(self._m_pending.ticks))
        recs = jax.device_get(self._recs_pending)
        rows = self.deltas.drain(self.state)
        self._export(recs, rows)
        return rows

    def serve(
        self,
        source: CommandSource | None = None,
        chunks: int | None = None,
        drain_chunks: int = 4,
        progress=None,
        stall_chunks: int = 256,
    ) -> dict:
        """Run the overlapped service loop.

        `source` (legacy single-tenant form) broadcasts each command to every
        cluster, exactly as before; a session built with `tenants=[...]`
        serves each tenant's source/read demand over its own cluster slice
        and takes no `source` here. Stops after `chunks` serving chunks when
        given (warmup chunks never consume the budget); otherwise when every
        source is exhausted AND every read demand is met AND `drain_chunks`
        further offer-free chunks have flushed trailing commits.
        `progress(stats_dict)` is called after each chunk. Returns the serve
        stats dict.

        Host spans (obs.span, always on, no sync), per chunk in loop order:
        `serve.export` (chunk k-1's delta rows), `serve.pack`,
        `serve.rounds`, `serve.merge`, the timer's sync, `serve.drain`,
        `serve.progress`, then the next chunk's `serve.dispatch` (its tick
        count). The perf row's `export_s`, `pack_s` and `dispatch_s` are the
        export, pack and dispatch spans' durations.

        `stall_chunks` guards the open-ended form against an UNSERVABLE
        demand: if no tenant ledger (acks, served reads) advances for that
        many consecutive chunks while demands remain, the loop raises naming
        the stuck tenants instead of spinning forever. The canonical way to
        hit it: a read-only tenant on a config whose elections append no
        no-op (no compaction), so no leader ever satisfies the 6.4
        current-term-commit capture gate -- docs/SERVE.md "read-only
        tenants". 0 disables the guard.
        """
        from raft_sim_tpu.serve.tenancy import Tenant, TenantRouter

        if self.router is None:
            if source is None:
                raise ValueError("serve() needs a source (or tenants=[...])")
            # Legacy broadcast tenant: one logical client over the whole
            # fleet, each command offered to every cluster.
            self.router = TenantRouter(
                [Tenant("default", self.batch, source=source, broadcast=True)],
                self.batch,
                self.reads_enabled,
            )
            if self.sink is not None:
                self.router.attach_dir(self.sink.directory)
        elif source is not None:
            raise ValueError(
                "this session was built with tenants=[...]; their sources "
                "replace serve(source)"
            )
        router = self.router
        t0 = time.perf_counter()
        drain_left = drain_chunks
        stall = 0
        last_ledger = None
        pending = None  # chunk k-1's (records, delta rows), exported under k
        with span("serve.pack"):
            planes = router.pack(self.chunk)
        self._dispatch(*planes)
        while True:
            # ---- host window: runs while the dispatched chunk computes ----
            # Everything until perf.end/finish_rounds below touches ONLY
            # last chunk's pending host copies and the routers' own state --
            # never the in-flight donated carry. That disjointness is a
            # checked fact: Pass D's overlap write-set audit derives this
            # window's writes (race_audit.overlap_write_sets) and gates any
            # carry touch as `race-window-mutation`; the donation-poison
            # sanitizer (--sanitize) re-proves it at runtime.
            export_s = pack_s = 0.0
            if pending is not None:
                with span("serve.export", len(pending[1])) as sp:
                    self._export(*pending)
                export_s = sp.seconds
            if chunks is not None:
                stop = self.chunks_done >= chunks
            else:
                if router.exhausted and self._last_offered == 0:
                    drain_left -= 1
                stop = router.exhausted and drain_left <= 0
                if not router.exhausted and stall_chunks:
                    ledger = tuple(
                        (len(t.acked_values), t.reads_served, t.offered)
                        for t in router.tenants
                    )
                    stall = stall + 1 if ledger == last_ledger else 0
                    last_ledger = ledger
                    if stall >= stall_chunks:
                        stuck = [
                            t.name for t in router.tenants
                            if not (t.writes_done and t.reads_done)
                        ]
                        raise RuntimeError(
                            f"serve loop stalled for {stall_chunks} chunks "
                            f"with unmet demands on tenants {stuck}: the "
                            "demand may be unservable under this config "
                            "(e.g. read-only tenants need elections that "
                            "append no-ops -- docs/SERVE.md)"
                        )
            next_planes = None
            if not stop:
                with span("serve.pack") as sp:
                    next_planes = router.pack(self.chunk)
                pack_s = sp.seconds
            # Enqueue this chunk's extraction rounds BEHIND it on the device
            # stream; fetched after the sync below, so the next dispatch's
            # donation never races a pending read of this chunk's state.
            with span("serve.rounds"):
                futs = self.deltas.begin_rounds(self.state, self._drain_rounds)
            if self.perf is not None:
                self.perf.annotate(
                    export_s=round(export_s, 6), pack_s=round(pack_s, 6)
                )
            # ---- sync: the only serial points are this wait + dispatch ----
            with span("serve.merge"):
                self.metrics = merge_metrics(self.metrics, self._m_pending)
            if self.perf is not None:
                self.perf.end(sync=lambda: np.asarray(self._m_pending.ticks))
            with span("serve.drain"):
                pending = (self._recs_pending, self.deltas.finish_rounds(futs))
            if progress is not None:
                with span("serve.progress"):
                    progress(self.stats())
            if stop:
                self._export(*pending)
                # Final flush: drain to dryness (the fixed overlapped rounds
                # are backpressure-bounded, not loss-bounded).
                tail = self.deltas.drain(self.state)
                if tail:
                    self._export(None, tail)
                break
            self._dispatch(*next_planes)
        stats = self.stats()
        stats["wall_s"] = round(time.perf_counter() - t0, 3)
        stats["offered"] = router.offered
        stats["reads_offered"] = router.reads_offered
        if self.perf is not None:
            # Steady-state rollup + the recompile-watchdog finding (stderr).
            stats["perf"] = self.perf.finish()
        if self.monitors:
            # Evaluate any partial trailing period, then replace the live
            # status map with each scope's full rollup for summary.json.
            stats["health"] = [m.finalize() for m in self.monitors]
        if self.sink is not None:
            from raft_sim_tpu.parallel import summarize

            self.sink.write_summary({**summarize(self.metrics)._asdict(), **stats})
            if self.router is not None:
                self.router.write_manifest(
                    os.path.join(self.sink.directory, "tenants.json")
                )
        return stats

    def stats(self) -> dict:
        reads_served = int(np.sum(np.asarray(self.metrics.reads_served)))
        return {
            "chunks": self.chunks_done,
            "ticks": self.ticks_done,
            "warmup_chunks": self.warmup_chunks,
            "batch": self.batch,
            "chunk": self.chunk,
            "window": self.window,
            "tenants": 0 if self.router is None else len(self.router.tenants),
            "deltas_exported": self.deltas.exported,
            "delta_gap_entries": self.deltas.gap_entries,
            # Client entries only (leader no-ops excluded): the commands
            # half of the throughput metric -- election churn's protocol
            # filler must never inflate it.
            "commands_acked": self.deltas.applied,
            "reads_served": reads_served,
            # The serve-throughput numerator (bench.py serve row): work the
            # service completed -- client commands acked through the delta
            # stream plus ReadIndex reads served. Ticks are the simulator's
            # clock, not the service's unit of work.
            "ops_done": self.deltas.applied + reads_served,
            "violations": int(np.sum(np.asarray(self.metrics.violations))),
            **(
                {"health": {m.scope: m.status for m in self.monitors}}
                if self.monitors
                else {}
            ),
        }

    def acked_values(self, cluster: int = 0) -> list[int]:
        """The commit-ack stream of one cluster: committed client values in
        commit order (no-ops filtered) -- what the reference's commit watch
        should have delivered per entry (log.clj:83-87, bug 2.3.9)."""
        return deltas_mod.applied_values(self.delta_rows, cluster)
