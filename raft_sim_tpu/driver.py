"""Host driver: CLI + REPL workflow for the simulator.

The reference's dev loop is the Stuart Sierra "reloaded" REPL -- init/start/stop/go/
reset building a component system (dev/user.clj:13-29) -- and its CLI is
`lein run <self-id> <peer-id>...` (core.clj:197-203). The rebuild's equivalent is a
`Session` with the same verbs (init/run/reset) plus a `backend` option selecting
cpu|tpu (the north star's `:backend :tpu`), and a CLI:

    python -m raft_sim_tpu run --preset config1 --ticks 10000
    python -m raft_sim_tpu run --n-nodes 7 --batch 4096 --drop-prob 0.2 --summary
    python -m raft_sim_tpu run --preset config1 --trace-events --trace-cluster 0
    python -m raft_sim_tpu presets

Unlike the reference (one OS process per node, topology from argv), one process drives
every node of every simulated cluster; "topology" is just --n-nodes/--batch.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time

import jax
import numpy as np

from raft_sim_tpu import init_batch
from raft_sim_tpu.sim import chunked, scan, trace
from raft_sim_tpu.utils import checkpoint
from raft_sim_tpu.utils.compile_cache import use_compile_cache
from raft_sim_tpu.utils.config import PRESETS, RaftConfig


def select_backend(backend: str) -> None:
    """Pick the JAX platform before any computation (north-star `:backend` option).

    `auto` leaves JAX's own default (the accelerator when one is present); the
    run's summary and telemetry manifest record which backend ran. Any other
    name is pinned through `jax_platforms`, and `tpu` then fails when no TPU is
    found instead of running on the CPU.
    """
    if backend == "auto":
        return
    jax.config.update("jax_platforms", backend)
    found = jax.devices()[0].platform
    if backend == "tpu" and found != "tpu":
        raise RuntimeError(f"--backend tpu: no TPU found (got {found})")


class Session:
    """REPL-friendly driver: the dev/user.clj workflow verbs over the simulator.

    >>> s = Session(RaftConfig(n_nodes=5, client_interval=8), batch=16, seed=0)
    >>> s.run(1000)        # scan forward, accumulating metrics
    >>> s.summary()        # fleet rollup dict
    >>> s.reset()          # back to tick 0 with the same seed (user/reset)

    `devices=N` shards the cluster batch over the first N local devices (a 1-D
    `parallel.make_mesh`): the jitted chunk calls see sharded inputs and XLA keeps
    the whole scan sharded -- the tick body has no cross-cluster ops, so no
    collectives appear in the hot loop. Trajectories are bit-identical at any
    device count (keys are split before sharding; pinned by tests/test_parallel.py).
    """

    def __init__(
        self, cfg: RaftConfig, batch: int = 1, seed: int = 0, devices: int | None = None
    ):
        self.cfg = cfg
        self.batch = batch
        self.seed = seed
        self.devices = devices
        self.apply_writer = None
        self.telemetry = None  # TelemetrySink (attach_telemetry)
        self._tel_rec = None  # flight-recorder carry (batch-minor)
        self._deltas = None  # serve.DeltaStream (offer's commit-ack watcher)
        self.perf = None  # obs.ChunkTimer (attach_perf)
        self._trace_spec = None  # trace.TraceSpec (attach_trace)
        self._trace_persist = None  # cross-chunk trace carry (batch-minor)
        self._trace_trigger = None  # flight-recorder event-kind trigger
        self.health = None  # health.HealthMonitor (attach_health)
        self._health_args = None  # (spec, directory) for reset re-attach
        self._live_rec = None  # this chunk's recorder (health evidence hook)
        self.reset()

    def reset(self) -> None:
        """Rebuild initial state from the seed (the reference's user/reset, minus code
        reloading, which Python REPLs handle themselves)."""
        root = jax.random.key(self.seed)
        k_init, k_run = jax.random.split(root)
        self.state = init_batch(self.cfg, k_init, self.batch)
        self.keys = jax.random.split(k_run, self.batch)
        self.metrics = scan.init_metrics_batch(self.batch)
        self._deltas = None  # a rebuilt experiment gets a fresh ack watermark
        self._apply_sharding()
        # A rebuilt experiment gets a rebuilt export stream: re-attach truncates
        # the files and zeroes the writer's frontier (a stale frontier would
        # silently drop the new run's early commits).
        if self.apply_writer is not None:
            self.attach_apply_log(self.apply_writer.directory, self.apply_writer.cluster)
        if self.telemetry is not None:
            self.attach_telemetry(
                self.telemetry.directory,
                window=self.telemetry.window,
                ring=self.telemetry.ring,
            )
        # A rebuilt experiment gets a fresh perf stream too (the re-attach
        # above already truncated the sink's perf.jsonl).
        if self.perf is not None:
            self.attach_perf(warmup_chunks=self.perf.warmup_chunks)
        # ... and a fresh trace stream (the telemetry re-attach truncated the
        # trace files; re-arming rewrites trace_meta.json and zeroes the
        # cross-window carry).
        if self._trace_spec is not None:
            spec = self._trace_spec
            self._trace_persist = None
            if self.telemetry is not None:
                self.telemetry.write_trace_meta(spec)
        # ... and a fresh health plane: re-attaching truncates health.jsonl /
        # alerts.jsonl and clears stale evidence dirs, and the burn-rate state
        # machines restart from ok (a rebuilt experiment's budget is fresh).
        self._live_rec = None
        if self._health_args is not None:
            self.attach_health(*self._health_args)

    def _apply_sharding(self) -> None:
        if self.devices is None:
            return
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.batch % self.devices:
            raise ValueError(
                f"batch {self.batch} must divide over {self.devices} devices"
            )
        if self.devices == 1:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raft_sim_tpu.parallel import mesh as pmesh

        sh = NamedSharding(pmesh.make_mesh(self.devices), P(pmesh.AXIS))
        place = lambda t: jax.tree.map(lambda x: jax.device_put(x, sh), t)
        self.state = place(self.state)
        self.keys = jax.device_put(self.keys, sh)
        self.metrics = place(self.metrics)

    def attach_apply_log(self, directory: str, cluster: int = 0) -> None:
        """Stream the selected cluster's committed values to per-node files --
        the reference's `node_<id>.log` apply stream (log.clj:16-18, 74-75),
        exported at chunk boundaries during run(). Keep chunks small enough
        that commit advances by less than CAP - compact_margin per chunk, or
        compacted-away spans appear as `# snapshot gap` markers
        (utils/apply_log.py)."""
        from raft_sim_tpu.utils.apply_log import ApplyLogWriter

        if not 0 <= cluster < self.batch:
            raise IndexError(f"cluster {cluster} out of range for batch {self.batch}")
        self.apply_writer = ApplyLogWriter(directory, self.cfg, cluster)
        self.apply_writer.update(self.state)  # anything already committed

    def attach_telemetry(self, directory: str, window: int = 64, ring: int = 32) -> None:
        """Stream windowed fleet telemetry to `directory` (manifest +
        windows.jsonl, utils/telemetry_sink.py) and arm a `ring`-deep flight
        recorder that freezes each cluster's last ticks at its first safety
        violation (ring=0 disables it). run() then scans through the telemetry
        path (sim/telemetry.py) -- trajectories stay bit-identical to the
        plain path; the only cost is the extra telemetry carry traffic
        (docs/OBSERVABILITY.md). Call finalize_telemetry() at the end of the
        experiment to export violating clusters' flight recordings."""
        from raft_sim_tpu.sim import telemetry
        from raft_sim_tpu.utils.telemetry_sink import TelemetrySink

        if window < 1:
            raise ValueError(f"telemetry window must be >= 1, got {window}")
        if ring < 0:
            raise ValueError(f"telemetry ring must be >= 0, got {ring}")
        self.telemetry = TelemetrySink(
            directory, self.cfg, seed=self.seed, batch=self.batch,
            window=window, ring=ring,
        )
        self._tel_rec = (
            telemetry.init_recorder(self.cfg, ring, self.batch) if ring else None
        )

    def attach_trace(
        self,
        depth: int = 128,
        freeze: str | None = None,
        trigger: str | None = None,
        coverage: bool = True,
    ) -> None:
        """Arm the protocol trace plane (raft_sim_tpu/trace; requires
        cfg.track_trace and an attached telemetry sink): run() extracts
        per-cluster protocol events on device and streams them per window as
        trace.jsonl + trace_windows.jsonl for timeline rendering
        (tools/metrics_report.py --trace) and whole-history checking
        (python -m raft_sim_tpu.trace.checker). `freeze` (an event-kind name,
        trace.KINDS) stops a cluster's recording after the first occurrence
        of that kind; `trigger` re-arms the FLIGHT RECORDER's freeze on an
        event kind instead of the default violation trigger -- "capture the
        lead-up to the first leadership change/crash" (docs/OBSERVABILITY.md,
        trigger semantics)."""
        from raft_sim_tpu.trace import KINDS, TraceSpec

        if not self.cfg.track_trace:
            raise ValueError(
                "attach_trace needs cfg.track_trace=True (the trace plane is "
                "a structural config gate -- utils/config.py)"
            )
        if self.telemetry is None:
            raise RuntimeError(
                "attach_trace needs an attached telemetry sink "
                "(attach_telemetry): trace windows stream through it"
            )

        def kind_code(name, what):
            if name is None:
                return None
            if name not in KINDS:
                raise ValueError(
                    f"unknown {what} event kind {name!r} (have {sorted(KINDS)})"
                )
            return KINDS[name]

        self._trace_spec = TraceSpec(
            depth=depth, coverage=coverage, freeze_kind=kind_code(freeze, "freeze") or 0
        )
        self._trace_trigger = kind_code(trigger, "trigger")
        self._trace_persist = None
        self.telemetry.write_trace_meta(self._trace_spec)

    def attach_perf(self, warmup_chunks: int | None = None) -> None:
        """Arm per-chunk runtime attribution (obs.ChunkTimer): run() streams
        perf.jsonl rows into the attached telemetry sink (or keeps them on
        `self.perf.rows` with no sink) -- wall time split device-vs-host,
        warmup vs steady state, device memory occupancy, and the jit-cache
        recompile watchdog. Purely host-side: trajectories, lowerings, and
        compile counts are untouched (docs/OBSERVABILITY.md, "Runtime
        perf")."""
        from raft_sim_tpu.obs import ChunkTimer

        kwargs = {} if warmup_chunks is None else {"warmup_chunks": warmup_chunks}
        self.perf = ChunkTimer(
            label="run", batch=self.batch, sink=self.telemetry, **kwargs
        )
        if self.health is not None:
            # Either attach order works: an already-armed monitor picks up
            # the new timer for its runtime SLIs (device-wait, recompiles).
            self.health.perf = self.perf

    def attach_health(self, spec="default", directory: str | None = None) -> None:
        """Arm the fleet health plane (raft_sim_tpu/health; docs/OBSERVABILITY.md
        "Fleet health & SLOs"): run() evaluates the SLO spec every
        `eval_windows` telemetry windows (or chunks, on the plain path) and
        streams health.jsonl + alerts.jsonl into the attached telemetry
        sink's directory -- or an explicit `directory` when no sink is
        attached (the plain chunked path). Firing burn-rate alerts triage
        the worst clusters and freeze an evidence bundle; with the flight
        recorder armed (attach_telemetry ring>0) the named clusters' live
        rings are snapshotted into it. Purely host-side: the monitor reads
        only host copies the loop already fetched, so instrumented runs are
        bit-exact vs plain (tier-1 pinned, tests/test_health.py)."""
        from raft_sim_tpu.health import HealthMonitor, HealthWriter, load_spec

        target = directory or (
            self.telemetry.directory if self.telemetry is not None else None
        )
        if target is None:
            raise RuntimeError(
                "attach_health needs somewhere to stream health.jsonl: "
                "attach a telemetry sink first (attach_telemetry) or pass "
                "directory="
            )
        self._health_args = (spec, directory)
        self.health = HealthMonitor(
            load_spec(spec) if not isinstance(spec, dict) else spec,
            batch=self.batch, writer=HealthWriter(target), scope="fleet",
            perf=self.perf, capture=self._health_capture,
        )

    def _health_capture(self, alert, clusters):
        """Evidence hook for the session's monitor: snapshot the triaged
        clusters' live flight-recorder rings (telemetry path with ring>0;
        the plain path has no recorder and contributes refs only)."""
        flights = {}
        rec = self._live_rec if self._live_rec is not None else self._tel_rec
        if rec is not None:
            from raft_sim_tpu.sim import telemetry

            for c in clusters:
                flights[int(c)] = telemetry.export_cluster(rec, int(c))
        return {
            "flights": flights,
            "refs": {"seed": self.seed, "batch": self.batch, "source": "run"},
        }

    def run(self, n_ticks: int, chunk: int = 4096, progress: bool = False) -> None:
        def progress_line(done, metrics):
            if progress:
                v = int(np.sum(np.asarray(metrics.violations)))
                print(f"  {done}/{n_ticks} ticks, violations={v}", file=sys.stderr)

        if self.telemetry is not None:
            from raft_sim_tpu.sim import telemetry

            def cb_t(done, state, metrics, records):
                self.telemetry.append_windows(records)
                if self.health is not None:
                    # After the sink append: the monitor reads the same host
                    # copy the export path fetched, never its own device_get.
                    self.health.observe_records(records)
                if self.apply_writer is not None:
                    self.apply_writer.update(state)
                progress_line(done, metrics)
                return False

            # The health evidence hook needs THIS chunk's carried recorder
            # (a firing alert snapshots the named clusters' live rings);
            # chunk_hook runs before cb_t, so the stash is always current.
            hook = None
            if self.health is not None:
                def hook(done, rec):
                    self._live_rec = rec

            if self._trace_spec is not None or self._trace_trigger is not None:
                out = telemetry.run_chunked_telemetry(
                    self.cfg, self.state, self.keys, n_ticks,
                    window=self.telemetry.window, recorder=self._tel_rec,
                    chunk=chunk, callback=cb_t, perf=self.perf,
                    trace_spec=self._trace_spec,
                    trace_persist=self._trace_persist,
                    trigger_kind=self._trace_trigger,
                    trace_callback=lambda done, traws:
                        self.telemetry.append_trace(traws),
                    chunk_hook=hook,
                )
                if self._trace_spec is not None:
                    self.state, m, self._tel_rec, self._trace_persist = out
                else:
                    self.state, m, self._tel_rec = out
            else:
                self.state, m, self._tel_rec = telemetry.run_chunked_telemetry(
                    self.cfg, self.state, self.keys, n_ticks,
                    window=self.telemetry.window, recorder=self._tel_rec,
                    chunk=chunk, callback=cb_t, perf=self.perf,
                    chunk_hook=hook,
                )
            self.metrics = chunked.merge_metrics(self.metrics, m)
            return

        def cb(done, state, metrics):
            if self.health is not None:
                # Plain path: the chunk is the window (observe_chunk derives
                # per-chunk counter deltas from the cumulative RunMetrics).
                self.health.observe_chunk(done, metrics)
            if self.apply_writer is not None:
                self.apply_writer.update(state)
            progress_line(done, metrics)
            return False

        if self.health is not None:
            # run_chunked restarts its cumulative metrics and tick counter
            # per call: re-baseline the monitor's delta accumulator.
            self.health.begin_run()

        self.state, m = chunked.run_chunked(
            self.cfg, self.state, self.keys, n_ticks, chunk=chunk, callback=cb,
            perf=self.perf,
        )
        self.metrics = chunked.merge_metrics(self.metrics, m)

    def finalize_telemetry(self, max_flights: int = 8) -> dict:
        """End-of-experiment telemetry export: write summary.json and, for up
        to `max_flights` clusters whose flight recorder froze (on a violation,
        or on the armed trigger kind -- attach_trace), the recorder's final
        ticks as flight_<cluster>.jsonl. Returns {"flights": [cluster ids
        exported], "flights_frozen": total frozen count, "flights_exported":
        count actually written, "summary": path} -- the frozen-vs-exported
        totals are also in summary.json, so clusters dropped by the
        max_flights cap are a REPORTED number, never a silent one."""
        if self.telemetry is None:
            raise RuntimeError("no telemetry attached (attach_telemetry)")
        from raft_sim_tpu.sim import telemetry

        flights = []
        frozen_total = 0
        if self._tel_rec is not None:
            frozen = np.flatnonzero(np.asarray(self._tel_rec.frozen))
            frozen_total = int(frozen.size)
            for cluster in frozen[:max_flights]:
                ticks, infos = telemetry.export_cluster(self._tel_rec, int(cluster))
                self.telemetry.write_flight(int(cluster), ticks, infos)
                flights.append(int(cluster))
            if frozen.size > max_flights:
                print(
                    f"telemetry: {frozen.size} frozen clusters, exported "
                    f"first {max_flights} flight recordings "
                    f"({frozen.size - max_flights} not exported -- raise "
                    "max_flights to keep them)",
                    file=sys.stderr,
                )
        summary = self.summary()
        summary["flights_frozen"] = frozen_total
        summary["flights_exported"] = len(flights)
        if self._trace_persist is not None:
            from raft_sim_tpu.trace.ring import cov_popcount

            tp = self._trace_persist
            summary["trace"] = {
                "events_emitted": int(np.asarray(tp.total, np.int64).sum()),
                "frozen_clusters": int(np.asarray(tp.frozen).sum()),
                "cov_bits_max": int(np.asarray(cov_popcount(tp.cov)).max()),
            }
        path = self.telemetry.write_summary(summary)
        return {
            "flights": flights,
            "flights_frozen": frozen_total,
            "flights_exported": len(flights),
            "summary": path,
        }

    def offer(self, value: int, wait: int = 0) -> dict:
        """Offer one client command and advance one tick -- the reference's ad-hoc
        `curl POST /client-set` (server.clj:8-12, core.clj:151-160; with
        cfg.client_redirect the kernel routes it through the 302 redirect dance).
        Overrides that tick's scheduled client input, metrics accumulate as in
        run(). Returns {"accepted", "committed", "waited"}: `accepted` counts
        clusters whose live leader appended the value ON the offer tick (under
        client_redirect acceptance usually lands on a LATER tick, after the
        bounces, so this undercounts there -- watch `committed` instead);
        `committed` counts clusters whose COMMIT-DELTA STREAM (the device-side
        node-0 apply stream, serve/deltas.py) delivered the value after the
        offer, stepping up to `wait` further ticks -- the per-entry ack the
        reference's commit watch was meant to deliver and never did
        (log.clj:83-87, bug 2.3.9; VERDICT missing #2). Acks match by
        (value, offer stamp) pair: the watermark excludes everything committed
        BEFORE the offer, and the stamp -- this offer's tick + 1, riding the
        v21 log_tick plane -- excludes colliding values committed DURING the
        wait window (e.g. a scheduled command whose value happens to equal
        this payload), so an ack is THIS entry, exactly. ANY int32 payload
        except the NIL/NOOP sentinels is legal -- the old "prefer values
        <= -3" collision caveat is gone. Acks follow node 0's commit, which
        trails the leader's by up to a heartbeat round trip (and stalls while
        node 0 is crashed): size `wait` accordingly.
        """
        value = int(value)
        if self._trace_spec is not None:
            # offer() ticks run outside the windowed telemetry scan, so their
            # events would be MISSING from the trace stream while the ticks
            # stay monotone -- an undetectable hole the checker would then
            # PASS over (the vacuous-pass class trace/history.py exists to
            # prevent). Refuse rather than record a silently gappy history.
            raise RuntimeError(
                "Session.offer() ticks are not covered by the armed trace "
                "stream; detach the trace, or ingest via run()'s scheduled "
                "cadence / the serve loop instead"
            )
        from raft_sim_tpu.serve.ingest import check_value

        check_value(value)  # same NIL/NOOP/int32 rule as the serve ingest
        if self._deltas is None:
            from raft_sim_tpu.serve.deltas import DeltaStream

            self._deltas = DeltaStream(self.batch, depth=32)
        # Only commits that happen AFTER this offer can ack it.
        self._deltas.skip_to_now(self.state)
        # The fleet ticks in lockstep: the offered entry's stamp is the shared
        # pre-offer `now` + 1 everywhere it lands (redirect bounces carry the
        # stamp of the OFFER tick, not the acceptance tick). Without the tick
        # plane (track_offer_ticks off) stamps are all zero and the match
        # falls back to value-only: no scheduled traffic exists to collide
        # with (client_interval == 0), and skip_to_now excludes everything
        # committed anywhere pre-offer -- what can still alias is a PRIOR
        # offer of the same value accepted but uncommitted at offer time (the
        # snapshot-diff poll this replaces had the identical caveat; tracked
        # configs are exact).
        track = self.cfg.track_offer_ticks
        stamp = int(np.asarray(self.state.now).ravel()[0]) + 1
        acked: set[int] = set()

        def fresh() -> int:
            for row in self._deltas.drain(self.state):
                for v, tk in zip(row["values"], row["ticks"]):
                    if v == value and (not track or tk == stamp):
                        acked.add(row["cluster"])
            return len(acked)

        self.state, self.metrics, accepted = _offer_tick(
            self.cfg, self.state, self.keys, self.metrics, value
        )
        if self.apply_writer is not None:
            # offer() ticks outside run()'s chunk loop: keep the export stream
            # current even when offer() is the session's last action.
            self.apply_writer.update(self.state)
        accepted = int(np.sum(np.asarray(accepted)))
        committed, waited = fresh(), 0
        # Direct mode: commitment can only reach the same-tick acceptance count.
        # Redirect mode: acceptance trickles in over the bounces, so keep
        # stepping until every cluster committed or the wait budget runs out.
        goal = self.batch if self.cfg.client_redirect else accepted
        while waited < wait and committed < goal:
            self.run(1, chunk=1)
            waited += 1
            committed = fresh()
        return {"accepted": accepted, "committed": committed, "waited": waited}

    def offer_read(self, wait: int = 0) -> dict:
        """Offer one ReadIndex read and advance one tick -- the read-side
        Session.offer (the `Session.offer_read` verb docs/SERVE.md named as
        the missing follow-up). Overrides that tick's scheduled read input
        via the same shared tick body (scan.tick_batch_minor read_cmd=).

        The ack path mirrors offer()'s delta-stream acks at the read side's
        natural granularity: a write is acked when the commit-delta stream
        delivers its (value, stamp) pair; a read produces no log entry, so
        its ack is the served-read COUNTER advancing (reads are fungible --
        StepInfo.reads_served, the same counter the serve loop's per-tenant
        read crediting reads). Returns {"captured", "served", "waited"}:
        `captured` counts clusters whose leader captured the read on the
        offer tick (a leaderless or busy-slotted cluster drops it -- retry),
        `served` counts clusters whose read was served within `wait` further
        ticks (confirmation round, or the lease fast path under
        cfg.read_lease). Requires the ReadIndex plane (cfg.read_index:
        read_interval > 0 or serve_reads)."""
        if self._trace_spec is not None:
            # Same hole as offer(): out-of-scan ticks would punch undetectable
            # monotone-tick gaps into the armed trace stream.
            raise RuntimeError(
                "Session.offer_read() ticks are not covered by the armed "
                "trace stream; detach the trace, or ingest reads via the "
                "scheduled cadence / the serve loop instead"
            )
        if not self.cfg.read_index:
            raise ValueError(
                "offer_read needs the ReadIndex plane: set read_interval > 0 "
                "or serve_reads=True (utils/config.py)"
            )
        before = np.asarray(self.metrics.reads_served).astype(np.int64).copy()
        stamp = int(np.asarray(self.state.now).ravel()[0]) + 1
        self.state, self.metrics = _offer_read_tick(
            self.cfg, self.state, self.keys, self.metrics
        )
        if self.apply_writer is not None:
            self.apply_writer.update(self.state)
        # Captures from THIS offer only: a fresh capture stamps read_tick
        # with the offer tick + 1 (older pending slots -- e.g. config9's
        # scheduled cadence -- carry earlier stamps and must not count).
        captured = int(np.sum(np.any(
            (np.asarray(self.state.read_idx) > 0)
            & (np.asarray(self.state.read_tick) == stamp),
            axis=1,
        )))

        def served_now() -> int:
            return int(
                np.sum(np.asarray(self.metrics.reads_served) - before)
            )

        served, waited = served_now(), 0
        while waited < wait and served < self.batch:
            self.run(1, chunk=1)
            waited += 1
            served = served_now()
        return {"captured": captured, "served": served, "waited": waited}

    def _committed_mask(self, value: int) -> np.ndarray:
        """[batch] bool: clusters in which `value` is a committed live entry
        (host-side ring scan; entries compacted past the base are no longer
        attributable). SUPERSEDED by the commit-delta stream for offer() acks
        (the full-state device_get + scan this does per probe is exactly what
        serve/deltas.py removes); kept as the snapshot-diff CROSS-CHECK the
        delta tests compare against (tests/test_serve.py)."""
        st = jax.device_get(self.state)
        lv = np.asarray(st.log_val)  # [B, N, CAP]
        commit = np.asarray(st.commit_index)[:, :, None]
        base = np.asarray(st.log_base)[:, :, None]
        cap = self.cfg.log_capacity
        sl = np.arange(cap)[None, None, :]
        abs1 = base + (sl - base) % cap + 1  # absolute 1-based index per slot
        hit = (lv == value) & (abs1 > base) & (abs1 <= commit)
        return np.any(hit, axis=(1, 2))

    def trace(self, n_ticks: int, cluster: int = 0):
        """Step a single selected cluster with full per-tick info + states captured
        (heavy; debugging only). Does not advance the session."""
        if not 0 <= cluster < self.batch:
            raise IndexError(f"cluster {cluster} out of range for batch {self.batch}")
        one = jax.tree.map(lambda x: x[cluster], self.state)
        _, _, outs = _traced_run(self.cfg, n_ticks)(one, self.keys[cluster])
        return outs  # (stacked StepInfo, stacked states)

    def summary(self) -> dict:
        from raft_sim_tpu.parallel import summarize

        s = summarize(self.metrics)
        return s._asdict()

    def save(self, path: str) -> str:
        return checkpoint.save(
            path, self.cfg, self.state, self.keys, self.metrics, seed=self.seed
        )

    @classmethod
    def restore(cls, path: str, devices: int | None = None) -> "Session":
        """Resume exactly: state, keys, accumulated metrics, AND the original seed come
        back, so summary() after more run() calls matches a never-interrupted session
        and reset() rebuilds the same experiment. `devices` reshards on load (a
        checkpoint is device-layout agnostic). Scenario checkpoints (driver
        `scenario run --save`) are rejected: a Session has no genome path, so
        continuing one here would silently run a DIFFERENT experiment."""
        cfg, state, keys, metrics, seed, scenario = checkpoint.load(path)
        if scenario is not None:
            raise ValueError(
                f"checkpoint {path!r} carries scenario "
                f"{scenario.get('name', '?')!r}: resume it with "
                "`python -m raft_sim_tpu scenario run --resume`, not a plain "
                "Session"
            )
        self = cls.__new__(cls)
        self.apply_writer = None
        self.telemetry = None
        self._tel_rec = None
        self._deltas = None
        self.perf = None
        self._trace_spec = None
        self._trace_persist = None
        self._trace_trigger = None
        self.health = None
        self._health_args = None
        self._live_rec = None
        self.cfg = cfg
        self.batch = state.role.shape[0]
        self.seed = seed
        self.devices = devices
        self.state = state
        self.keys = keys
        self.metrics = metrics
        self._apply_sharding()
        return self


@functools.lru_cache(maxsize=8)
def _traced_run(cfg: RaftConfig, n_ticks: int):
    return jax.jit(lambda s, k: scan.run(cfg, s, k, n_ticks, trace_states=True))


@functools.partial(jax.jit, static_argnums=0)
def _offer_read_tick(cfg: RaftConfig, state, keys, metrics):
    """One tick with a ReadIndex read offered (Session.offer_read), through
    the same shared tick body as the scan loop."""
    from raft_sim_tpu.models import raft_batched

    s_t = raft_batched.to_batch_minor(state)
    m_t = raft_batched.to_batch_minor(metrics)
    s2, m2, _ = scan.tick_batch_minor(cfg, s_t, keys, m_t, read_cmd=1)
    return raft_batched.from_batch_minor(s2), raft_batched.from_batch_minor(m2)


@functools.partial(jax.jit, static_argnums=0)
def _offer_tick(cfg: RaftConfig, state, keys, metrics, value):
    """One tick with the scheduled client input overridden by `value`
    (Session.offer), through the SAME shared tick body as the scan loop
    (scan.tick_batch_minor), so the interactive path can never drift from run()."""
    from raft_sim_tpu.models import raft_batched

    s_t = raft_batched.to_batch_minor(state)
    m_t = raft_batched.to_batch_minor(metrics)  # histogram leaf is [BINS, B] inside
    before = metrics.total_cmds
    s2, m2, _ = scan.tick_batch_minor(cfg, s_t, keys, m_t, client_cmd=value)
    metrics = raft_batched.from_batch_minor(m2)
    return raft_batched.from_batch_minor(s2), metrics, metrics.total_cmds - before


def _profile_ctx(path: str | None):
    """The --profile capture context, shared by run/serve/scenario-search:
    a jax.profiler perfetto trace into `path`, or a no-op without one.
    Capture is bit-exact vs no capture (tier-1 pinned, tests/test_obs.py)."""
    import contextlib

    if not path:
        return contextlib.nullcontext()
    return jax.profiler.trace(path, create_perfetto_trace=True)


def _sanitize_ctx(args):
    """The --sanitize arming context, shared by run/serve: the donation-poison
    sanitizer over every registered donating entry point
    (analysis/sanitizer.py), or a no-op without the flag. Yields the
    sanitizer's coverage stats (None when unarmed)."""
    import contextlib

    if not getattr(args, "sanitize", False):
        return contextlib.nullcontext()
    from raft_sim_tpu.analysis import sanitizer

    return sanitizer.armed()


def _sanitize_report(args, san) -> None:
    if san is None:
        return
    calls = ", ".join(f"{k}x{v}" for k, v in sorted(san["calls"].items()))
    print(
        f"sanitizer: clean ({calls or 'no donating dispatches'}; "
        f"{san['pre_deleted']} buffers invalidated by donation, "
        f"{san['poisoned']} poisoned as backstop)",
        file=sys.stderr,
    )


_FLAG_TYPES = {"int": int, "float": float}


def _add_config_flags(p: argparse.ArgumentParser) -> None:
    """One CLI flag per RaftConfig field (field types are strings under
    `from __future__ import annotations`)."""
    for f in dataclasses.fields(RaftConfig):
        flag = "--" + f.name.replace("_", "-")
        if f.type == "bool":
            p.add_argument(flag, type=lambda s: s.lower() in ("1", "true", "yes"),
                           default=None, metavar="BOOL")
        else:
            p.add_argument(flag, type=_FLAG_TYPES.get(f.type, str), default=None)


def build_config(args) -> tuple[RaftConfig, int]:
    """(config, batch) from preset + CLI overrides; batch falls back preset -> 1."""
    preset_batch = 1
    if args.preset:
        cfg, preset_batch = PRESETS[args.preset]
    else:
        cfg = RaftConfig()
    batch = args.batch if args.batch is not None else preset_batch
    overrides = {
        f.name: getattr(args, f.name)
        for f in dataclasses.fields(RaftConfig)
        if getattr(args, f.name) is not None
    }
    return (dataclasses.replace(cfg, **overrides) if overrides else cfg), batch


def _nondefault_config(cfg: RaftConfig) -> dict:
    """cfg's non-default fields (the portable config encoding repro artifacts
    and hit files carry; RaftConfig(**this) rebuilds it)."""
    return {
        f.name: getattr(cfg, f.name)
        for f in dataclasses.fields(RaftConfig)
        if getattr(cfg, f.name) != f.default
    }


def _scenario_run(args, ap) -> int:
    """`scenario run`: a fleet under a declarative nemesis program
    (docs/SCENARIOS.md). One compiled program drives the whole phased
    timeline; checkpoints carry the scenario (format v20) so resume cannot
    silently continue a different experiment."""
    from raft_sim_tpu.parallel import summarize
    from raft_sim_tpu.scenario import genome as genome_mod
    from raft_sim_tpu.scenario import program as program_mod

    if args.resume:
        conflicting = [
            f.name for f in dataclasses.fields(RaftConfig)
            if getattr(args, f.name) is not None
        ]
        for flag in ("preset", "scenario", "batch", "seed"):
            if getattr(args, flag) is not None:
                conflicting.append(flag)
        if conflicting:
            ap.error(
                f"--resume is exclusive with config/scenario flags: "
                f"{', '.join(conflicting)}"
            )
        cfg, state, keys, metrics, seed, scen = checkpoint.load(args.resume)
        if scen is None:
            ap.error(
                f"{args.resume!r} is a plain checkpoint (no scenario); resume "
                "it with `run --resume`"
            )
        prog = program_mod.from_dict(scen, cfg)
        batch = state.role.shape[0]
    else:
        if not args.scenario:
            ap.error("scenario run needs --scenario FILE (or --resume)")
        cfg, batch = build_config(args)
        try:
            prog = program_mod.load(args.scenario, cfg)
        except ValueError as ex:
            ap.error(f"--scenario {args.scenario}: {ex}")
        seed = args.seed if args.seed is not None else 0
        root = jax.random.key(seed)
        k_init, k_run = jax.random.split(root)
        state = init_batch(cfg, k_init, batch)
        keys = jax.random.split(k_run, batch)
        metrics = scan.init_metrics_batch(batch)

    g = genome_mod.broadcast(prog.genome, batch)

    def cb(done, _state, m):
        if args.progress:
            v = int(np.sum(np.asarray(m.violations)))
            print(f"  {done}/{args.ticks} ticks, violations={v}", file=sys.stderr)
        return False

    t0 = time.perf_counter()
    state, m = chunked.run_chunked(
        cfg, state, keys, args.ticks, chunk=args.chunk, callback=cb,
        genome=g, seg_len=prog.seg_len,
    )
    metrics = chunked.merge_metrics(metrics, m)
    out = summarize(metrics)._asdict()
    dt = time.perf_counter() - t0
    out["scenario"] = prog.name
    out["segments"] = prog.n_segments
    out["seg_len"] = prog.seg_len
    out["wall_s"] = round(dt, 3)
    out["cluster_ticks_per_s"] = round(batch * args.ticks / dt, 1)
    print(json.dumps(out))
    if args.save:
        # exact=True rides the integer genome leaves along: a resumed run
        # must draw from the IDENTICAL thresholds, not a 9-decimal rounding
        # of them (checkpoint.py v20 contract).
        checkpoint.save(
            args.save, cfg, state, keys, metrics, seed=seed,
            scenario=program_mod.to_dict(prog, exact=True),
        )
    return 0


def _scenario_search(args, ap) -> int:
    """`scenario search`: the cross-entropy violation hunt (scenario/search.py).
    Prints the full result JSON; --out writes a replayable hit file for
    `scenario shrink` when a violating genome was found."""
    from raft_sim_tpu.scenario import search as search_mod

    cfg, _ = build_config(args)
    mutant = args.mutant
    if mutant:
        from raft_sim_tpu.scenario.mutation import mutant_config

        try:
            cfg = mutant_config(mutant, cfg)
        except ValueError as ex:
            ap.error(str(ex))
    spec = search_mod.SearchSpec(
        generations=args.generations,
        population=args.population,
        ticks=args.ticks,
        window=args.window,
        elite_frac=args.elite_frac,
        seed=args.seed if args.seed is not None else 0,
        fitness=args.fitness,
        trace_depth=args.trace_depth,
        proposal=args.proposal,
    )
    try:
        with _profile_ctx(args.profile):
            res = search_mod.search(cfg, spec)
    except ValueError as ex:
        ap.error(str(ex))
    doc = {
        "found": res.hit is not None,
        "hit": res.hit,
        "generations": res.generations,
        "spec": res.spec,
        "mutant": mutant,
    }
    if res.hit is not None and args.out:
        hit_doc = {"config": _nondefault_config(cfg), "mutant": mutant, **res.hit}
        with open(args.out, "w") as f:
            json.dump(hit_doc, f, indent=1)
            f.write("\n")
        doc["hit_file"] = args.out
    print(json.dumps(doc))
    return 0


def _scenario_shrink(args, ap) -> int:
    """`scenario shrink`: minimize a search hit file to a repro artifact that
    `tools/repro.py --scenario` replays bit-exactly."""
    from raft_sim_tpu.scenario import shrink as shrink_mod

    with open(args.hit) as f:
        hit = json.load(f)
    cfg = RaftConfig(**hit.get("config", {}))
    if hit.get("mutant"):
        from raft_sim_tpu.scenario.mutation import mutant_config

        cfg = mutant_config(hit["mutant"], cfg)
    try:
        art = shrink_mod.shrink(
            cfg, hit, mutant=hit.get("mutant"),
            halving_rounds=args.halving_rounds, context=args.context,
        )
    except ValueError as ex:
        ap.error(str(ex))
    shrink_mod.save_artifact(args.out, art)
    print(json.dumps({
        "artifact": args.out,
        "tick": art["tick"],
        "kinds": art["kinds"],
        "removed": art["removed"],
        "segments": art["segments"],
        "repro_cmd": f"python tools/repro.py --scenario {args.out}",
    }))
    return 0


def _scenario_farm(args, ap) -> int:
    """`scenario farm`: the fuzzing farm (raft_sim_tpu/farm) -- a portfolio
    of fitness members hunted in parallel from ONE compiled program per
    generation, coverage-guided mutation against a farm-wide seen set, and
    the auto-corpus policy (shrink -> dedup -> provenance-stamp ->
    checker-gate -> freeze). Ends in either a frozen hit or a pinned
    negative result (out-dir/negative.json with coverage numbers)."""
    from raft_sim_tpu.farm import FarmSpec, parse_portfolio, run_farm

    cfg, _ = build_config(args)
    mutant = args.mutant
    if mutant:
        from raft_sim_tpu.scenario.mutation import mutant_config

        try:
            cfg = mutant_config(mutant, cfg)
        except ValueError as ex:
            ap.error(str(ex))
    mesh = None
    if args.mesh is not None:
        from raft_sim_tpu.parallel import make_mesh

        try:
            mesh = make_mesh(args.mesh or None)
        except ValueError as ex:
            ap.error(str(ex))
    try:
        spec = FarmSpec(
            portfolio=parse_portfolio(args.portfolio),
            budget_gens=args.budget_gens,
            # Under --mesh the population scales with the device count:
            # --population is the per-device share of the fleet.
            population=args.population * (mesh.devices.size if mesh else 1),
            ticks=args.ticks,
            window=args.window,
            elite_frac=args.elite_frac,
            seed=args.seed if args.seed is not None else 0,
            trace_depth=args.trace_depth,
            guided=not args.no_guided,
            stop_on=args.stop_on,
        )
        with _profile_ctx(args.profile):
            res = run_farm(
                cfg, spec, mutant=mutant, out_dir=args.out_dir,
                corpus_dir=args.corpus_dir, freeze=args.freeze, mesh=mesh,
                health=args.health,
            )
    except ValueError as ex:
        ap.error(str(ex))
    print(json.dumps({
        "found": bool(res.hits),
        "hits": res.manifest["hits"],
        "frozen": res.manifest["frozen"],
        "dedup_rejected": res.dedup_rejected,
        "negative": res.negative,
        "generations_run": res.manifest["generations_run"],
        "evaluations": res.manifest["evaluations"],
        "cov_bits_total": res.manifest["cov_bits_total"],
        "manifest_hash": res.manifest["manifest_hash"],
        "out_dir": args.out_dir,
    }))
    return 0


def _shard_round_robin(it, weights: list[int]):
    """Split one lazy payload iterator into len(weights) shard iterators,
    dealing commands in weighted round-robin order (shard i gets weights[i]
    consecutive commands per cycle) -- how `serve --tenants N` divides a
    single JSONL stream among tenants. Weighting by each tenant's cluster
    count matters beyond fairness: consumption per chunk is proportional to
    cluster count, so a uniform deal against unequal slices would grow the
    smaller tenants' buffers by ~one command per tick FOREVER; the weighted
    deal keeps every queue bounded by one chunk's imbalance."""
    from collections import deque

    src = iter(it)
    order = [i for i, w in enumerate(weights) for _ in range(w)]
    queues = [deque() for _ in weights]
    turn = [0]  # position in the weighted deal order

    def shard(i: int):
        while True:
            if queues[i]:
                yield queues[i].popleft()
                continue
            try:
                v = next(src)
            except StopIteration:
                return
            queues[order[turn[0]]].append(v)
            turn[0] = (turn[0] + 1) % len(order)

    return [shard(i) for i in range(len(weights))]


def _serve(args, ap) -> int:
    """`serve`: the standing-fleet service loop (docs/SERVE.md). A long-lived
    fleet accepts streamed client commands between chunks (JSONL source, '-'
    = stdin) and continuously streams telemetry windows + commit deltas to
    the schema'd sink. Zero recompiles after the first chunk: the chunk
    program is fixed, commands are data. `--tenants N` partitions the
    cluster range among N tenants (the batch axis is the tenancy axis: same
    compiled program at every N), sharding the command stream round-robin;
    `--reads-per-tenant R` adds R ReadIndex reads to each tenant's demand
    (requires a read-carrying config, e.g. config9)."""
    from raft_sim_tpu.parallel import summarize
    from raft_sim_tpu.serve import CommandSource, ServeSession, jsonl_commands
    from raft_sim_tpu.serve.loop import serve_config

    cfg, batch = build_config(args)
    cfg = serve_config(cfg)
    if args.source != "-":
        # Fail fast: jsonl_commands opens lazily (first next_chunk), which is
        # AFTER the session has compiled and run its warmup -- a typo'd path
        # must not cost minutes before erroring.
        try:
            open(args.source).close()
        except OSError as ex:
            ap.error(f"--source: {ex}")
    sink = None
    if args.sink:
        from raft_sim_tpu.utils.telemetry_sink import TelemetrySink

        sink = TelemetrySink(
            args.sink, cfg, seed=args.seed or 0, batch=batch,
            window=args.window, ring=0, source="serve",
        )
    perf = None
    if args.perf:
        from raft_sim_tpu.obs import ChunkTimer

        perf = ChunkTimer(label="serve", batch=batch, sink=sink)
    tenants = None
    if args.reads_per_tenant < 0:
        ap.error("--reads-per-tenant must be >= 0")
    if args.tenants is not None and not 1 <= args.tenants <= batch:
        ap.error(f"--tenants must be in [1, batch={batch}]")
    if args.tenants is not None or args.reads_per_tenant:
        from raft_sim_tpu.serve.tenancy import Tenant

        if args.tenants is None:
            # --reads-per-tenant alone: ONE tenant whose writes keep the
            # legacy broadcast semantics (each command to every cluster) --
            # a read demand must never silently reshape the write path.
            tenants = [
                Tenant("tenant0", batch,
                       source=jsonl_commands(args.source),
                       reads=args.reads_per_tenant, broadcast=True)
            ]
        else:
            # Explicit --tenants N (N = 1 included): the partitioned form,
            # command stream sharded round-robin, one slot per
            # (tick, cluster).
            from raft_sim_tpu.serve.tenancy import split_even

            n_ten = args.tenants
            sizes = split_even(batch, n_ten)
            shards = _shard_round_robin(jsonl_commands(args.source), sizes)
            tenants = [
                Tenant(f"tenant{i}", sizes[i], source=shards[i],
                       reads=args.reads_per_tenant)
                for i in range(n_ten)
            ]
    if args.health and not args.sink:
        ap.error("--health needs --sink (the health/alert streams ride the "
                 "telemetry sink directory)")
    try:
        sess = ServeSession(
            cfg, batch=batch, seed=args.seed or 0, chunk=args.chunk,
            window=args.window, delta_depth=args.delta_depth, sink=sink,
            warmup_ticks=args.warmup, perf=perf, tenants=tenants,
            health=args.health,
        )
    except ValueError as ex:
        ap.error(str(ex))
    source = (
        None if tenants is not None
        else CommandSource(jsonl_commands(args.source))
    )

    def progress(st):
        if args.progress:
            print(
                f"  chunk {st['chunks']}: {st['ticks']} ticks, "
                f"{st['deltas_exported']} deltas, "
                f"{st['reads_served']} reads, "
                f"violations={st['violations']}",
                file=sys.stderr,
            )

    try:
        with _profile_ctx(args.profile), _sanitize_ctx(args) as san:
            stats = sess.serve(
                source, chunks=args.chunks, drain_chunks=args.drain_chunks,
                progress=progress,
            )
    except ValueError as ex:
        ap.error(str(ex))
    _sanitize_report(args, san)
    out = summarize(sess.metrics)._asdict()
    out.update(stats)
    if stats["wall_s"] > 0:
        out["cluster_ticks_per_s"] = round(
            batch * stats["ticks"] / stats["wall_s"], 1
        )
        # The service's own throughput unit: completed work (committed
        # entries exported + reads served) per second -- the bench serve
        # row's headline (commands+reads/s), never ticks.
        out["ops_per_s"] = round(stats["ops_done"] / stats["wall_s"], 1)
    if args.sink:
        out["sink"] = args.sink
    print(json.dumps(out))
    return 0


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(prog="raft_sim_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run_p = sub.add_parser("run", help="simulate a batch of clusters")
    run_p.add_argument("--preset", choices=sorted(PRESETS), default=None)
    run_p.add_argument("--batch", type=int, default=None)
    run_p.add_argument("--ticks", type=int, default=1000)
    run_p.add_argument("--seed", type=int, default=None,
                       help="PRNG seed (default 0; stored in checkpoints, so "
                            "exclusive with --resume)")
    run_p.add_argument("--chunk", type=int, default=4096)
    run_p.add_argument("--backend", default="auto", metavar="NAME",
                       help="auto (JAX's default platform) | cpu | tpu; 'tpu' "
                            "fails when no TPU is found")
    run_p.add_argument("--profile", metavar="DIR", default=None,
                       help="capture a jax.profiler trace of the run into DIR "
                            "(view with tensorboard/xprof)")
    run_p.add_argument("--devices", type=int, default=None, metavar="N",
                       help="shard the cluster batch over the first N local devices "
                            "(trajectories are device-count invariant)")
    run_p.add_argument("--progress", action="store_true")
    run_p.add_argument("--trace-ticks", type=int, default=0,
                       help="print per-tick info lines for one cluster")
    run_p.add_argument("--trace-events", action="store_true",
                       help="print decoded state-change events for one cluster")
    run_p.add_argument("--trace-cluster", type=int, default=0)
    run_p.add_argument("--save", metavar="PATH", help="write a checkpoint at the end")
    run_p.add_argument("--resume", metavar="PATH", help="start from a checkpoint")
    run_p.add_argument("--apply-log", metavar="DIR", default=None,
                       help="stream one cluster's committed values to "
                            "DIR/node_<i>.log (the reference's per-node apply "
                            "file, log.clj:74-75)")
    run_p.add_argument("--apply-cluster", type=int, default=0,
                       help="cluster index --apply-log exports (default 0)")
    run_p.add_argument("--telemetry-dir", metavar="DIR", default=None,
                       help="write windowed fleet telemetry (manifest + "
                            "windows.jsonl, utils/telemetry_sink.py) and "
                            "flight recordings of violating clusters to DIR")
    run_p.add_argument("--telemetry-window", type=int, default=64, metavar="W",
                       help="ticks aggregated per telemetry window record "
                            "(default 64)")
    run_p.add_argument("--telemetry-ring", type=int, default=32, metavar="K",
                       help="flight-recorder depth: last K ticks of StepInfo "
                            "per cluster, frozen at the first violation "
                            "(0 disables; default 32)")
    run_p.add_argument("--trace", action="store_true",
                       help="protocol trace plane (raft_sim_tpu/trace; "
                            "requires --telemetry-dir): extract per-cluster "
                            "protocol events on device and stream them as "
                            "trace.jsonl for timeline rendering "
                            "(tools/metrics_report.py --trace) and "
                            "whole-history Raft safety checking "
                            "(python -m raft_sim_tpu.trace.checker DIR). "
                            "Sets cfg.track_trace; trajectories stay "
                            "bit-exact vs an untraced run")
    run_p.add_argument("--trace-depth", type=int, default=128, metavar="R",
                       help="events retained per cluster per telemetry "
                            "window (overflow is counted, the checker then "
                            "reports the history incomplete; default 128)")
    run_p.add_argument("--trace-freeze", metavar="KIND", default=None,
                       help="stop a cluster's trace recording after the "
                            "first event of KIND (e.g. 'leader', 'crash'; "
                            "default: record forever). Capture economy, not "
                            "checking: the whole-history checker reports a "
                            "freeze-truncated stream as undecided, never as "
                            "a pass")
    run_p.add_argument("--trace-trigger", metavar="KIND", default=None,
                       help="freeze the FLIGHT RECORDER on the first event "
                            "of KIND instead of the first violation -- "
                            "capture the lead-up to a non-violating anomaly "
                            "(implies cfg.track_trace; default: violation)")
    run_p.add_argument("--mutant", default=None, metavar="NAME",
                       help="TEST-ONLY: run a deliberately weakened kernel "
                            "(scenario/mutation.py registry, e.g. "
                            "'weak-quorum') -- the trace/checker CI smoke's "
                            "known-bad target")
    run_p.add_argument("--perf", action="store_true",
                       help="per-chunk runtime attribution (obs.ChunkTimer): "
                            "device-vs-host wall split, warmup vs steady "
                            "state, memory occupancy, jit-cache recompile "
                            "watchdog; streams perf.jsonl into "
                            "--telemetry-dir when given, and prints the "
                            "steady-state rollup either way. Host-side only: "
                            "trajectories and lowerings are untouched")
    run_p.add_argument("--health", nargs="?", const="default", default=None,
                       metavar="SPEC",
                       help="arm the fleet health plane (raft_sim_tpu/health; "
                            "requires --telemetry-dir): evaluate the SLO spec "
                            "(omit SPEC for the built-in default, or give a "
                            "JSON spec file) every eval period, streaming "
                            "health.jsonl + alerts.jsonl into the sink; "
                            "firing burn-rate alerts triage worst clusters "
                            "and freeze evidence bundles with live "
                            "flight-ring snapshots. Host-side only: "
                            "trajectories stay bit-exact vs an unmonitored "
                            "run")
    run_p.add_argument("--sanitize", action="store_true",
                       help="arm the donation-poison sanitizer "
                            "(analysis/sanitizer.py): every donating chunk "
                            "dispatch deletes its donated input buffers the "
                            "moment the outputs land, so any host "
                            "use-after-donate raises at the access site "
                            "instead of reading stale memory on a real "
                            "donating backend. Serializes the "
                            "dispatch->sync overlap (debug mode, not for "
                            "benchmarking); values stay bit-exact")
    _add_config_flags(run_p)

    sub.add_parser("presets", help="list the BASELINE config presets")

    serve_p = sub.add_parser(
        "serve",
        help="standing-fleet service loop: streamed client ingest between "
             "chunks, telemetry windows + commit deltas streamed out "
             "(docs/SERVE.md)",
    )
    serve_p.add_argument("--source", metavar="FILE", default="-",
                         help="JSONL command source: one command per line, a "
                              "bare int or {\"value\": v}; '-' = stdin "
                              "(default)")
    serve_p.add_argument("--preset", choices=sorted(PRESETS), default=None)
    serve_p.add_argument("--batch", type=int, default=None)
    serve_p.add_argument("--seed", type=int, default=None)
    serve_p.add_argument("--chunk", type=int, default=256,
                         help="ticks per device chunk (the ingest<->export "
                              "exchange cadence; default 256)")
    serve_p.add_argument("--window", type=int, default=64,
                         help="telemetry window ticks (must divide --chunk; "
                              "default 64)")
    serve_p.add_argument("--chunks", type=int, default=None,
                         help="stop after N chunks (default: run until the "
                              "source is exhausted + --drain-chunks)")
    serve_p.add_argument("--drain-chunks", type=int, default=4,
                         help="empty chunks run after source exhaustion so "
                              "trailing commits flush through the delta "
                              "stream (default 4)")
    serve_p.add_argument("--warmup", type=int, default=0, metavar="TICKS",
                         help="ticks simulated before the first offer (elect "
                              "leaders so early offers are not dropped)")
    serve_p.add_argument("--tenants", type=int, default=None, metavar="N",
                         help="partition the fleet's cluster range among N "
                              "logical tenants (per-tenant sources, sinks, "
                              "and read demands; one compiled program at "
                              "any N -- serve/tenancy.py). The command "
                              "stream is sharded round-robin")
    serve_p.add_argument("--reads-per-tenant", type=int, default=0,
                         metavar="R",
                         help="ReadIndex reads each tenant must get served "
                              "(re-offered until acked; requires a "
                              "read-carrying config, e.g. --preset config9)")
    serve_p.add_argument("--delta-depth", type=int, default=64,
                         help="per-cluster commit-delta buffer depth per "
                              "extraction round (backpressure bound, not a "
                              "loss bound; default 64)")
    serve_p.add_argument("--sink", metavar="DIR", default=None,
                         help="stream telemetry windows (windows.jsonl) and "
                              "commit deltas (deltas.jsonl) to DIR under the "
                              "telemetry sink schema")
    serve_p.add_argument("--backend", default="auto", metavar="NAME")
    serve_p.add_argument("--progress", action="store_true")
    serve_p.add_argument("--perf", action="store_true",
                         help="per-chunk runtime attribution of the serving "
                              "loop (dispatch / ingest-pack host gap / "
                              "device wait; jit-cache watchdog); streams "
                              "perf.jsonl into --sink when given")
    serve_p.add_argument("--health", nargs="?", const="default", default=None,
                         metavar="SPEC",
                         help="arm fleet + per-tenant health monitoring "
                              "(raft_sim_tpu/health; requires --sink): one "
                              "SLO evaluator per scope streams health.jsonl "
                              "+ alerts.jsonl, prints live status "
                              "transitions, and freezes evidence bundles on "
                              "firing burn-rate alerts. Omit SPEC for the "
                              "built-in default, or give a JSON spec file")
    serve_p.add_argument("--profile", metavar="DIR", default=None,
                         help="capture a jax.profiler trace of the serving "
                              "session into DIR (view with tensorboard/"
                              "xprof); capture is bit-exact vs no capture "
                              "(tier-1 pinned)")
    serve_p.add_argument("--sanitize", action="store_true",
                         help="arm the donation-poison sanitizer over the "
                              "serving loop (analysis/sanitizer.py): late "
                              "host access to a donated carry raises at the "
                              "access site. Serializes the serve overlap "
                              "(debug mode); stats stay bit-exact")
    _add_config_flags(serve_p)

    sc = sub.add_parser(
        "scenario",
        help="adversarial scenario engine: phased nemesis runs, the "
             "violation-hunting search, and hit shrinking (docs/SCENARIOS.md)",
    )
    ssub = sc.add_subparsers(dest="scmd", required=True)

    srun = ssub.add_parser("run", help="run a fleet under a JSON nemesis program")
    srun.add_argument("--scenario", metavar="FILE", default=None,
                      help="declarative scenario file (scenario/program.py schema)")
    srun.add_argument("--preset", choices=sorted(PRESETS), default=None)
    srun.add_argument("--batch", type=int, default=None)
    srun.add_argument("--ticks", type=int, default=1000)
    srun.add_argument("--seed", type=int, default=None)
    srun.add_argument("--chunk", type=int, default=4096)
    srun.add_argument("--backend", default="auto", metavar="NAME")
    srun.add_argument("--progress", action="store_true")
    srun.add_argument("--save", metavar="PATH",
                      help="checkpoint at the end (records the scenario; "
                           "format v20)")
    srun.add_argument("--resume", metavar="PATH",
                      help="resume a scenario checkpoint (restores the genome "
                           "path; plain checkpoints are rejected)")
    _add_config_flags(srun)

    ssearch = ssub.add_parser(
        "search", help="cross-entropy hunt for violating fault genomes"
    )
    ssearch.add_argument("--preset", choices=sorted(PRESETS), default=None)
    # build_config reads args.batch; the search population IS the batch.
    ssearch.add_argument("--batch", type=int, default=None, help=argparse.SUPPRESS)
    ssearch.add_argument("--mutant", default=None, metavar="NAME",
                         help="TEST-ONLY: hunt a deliberately weakened kernel "
                              "(scenario/mutation.py registry, e.g. "
                              "'weak-quorum') to prove the hunt hunts")
    ssearch.add_argument("--generations", type=int, default=8)
    ssearch.add_argument("--population", type=int, default=64,
                         help="genomes per generation = fleet batch size")
    ssearch.add_argument("--ticks", type=int, default=512)
    ssearch.add_argument("--window", type=int, default=64,
                         help="telemetry window (fitness resolution)")
    ssearch.add_argument("--elite-frac", type=float, default=0.25)
    ssearch.add_argument("--fitness", choices=("scalar", "coverage"),
                         default="scalar",
                         help="fitness mode: 'scalar' = the hand-tuned "
                              "distress weights; 'coverage' = transition-"
                              "coverage novelty from the protocol trace "
                              "plane (newly set role x event-kind and "
                              "kind->kind bits across the fleet; violations "
                              "stay dominant) -- one compiled trace-variant "
                              "program for the whole hunt")
    ssearch.add_argument("--trace-depth", type=int, default=32, metavar="R",
                         help="coverage mode's per-window event-buffer depth "
                              "(the bitmap needs no deep buffer; default 32)")
    ssearch.add_argument("--proposal", choices=("gaussian", "coverage-guided"),
                         default="gaussian",
                         help="proposal mode: 'gaussian' = classic CE draws; "
                              "'coverage-guided' = mutate the previous "
                              "generation's novelty-lit parents (requires "
                              "--fitness=coverage) -- coverage-guided "
                              "MUTATION, not just coverage-as-fitness")
    ssearch.add_argument("--seed", type=int, default=None)
    ssearch.add_argument("--backend", default="auto", metavar="NAME")
    ssearch.add_argument("--out", metavar="FILE", default=None,
                         help="write the first violating hit (replayable; "
                              "feeds `scenario shrink --hit`)")
    ssearch.add_argument("--profile", metavar="DIR", default=None,
                         help="capture a jax.profiler trace of the hunt into "
                              "DIR (view with tensorboard/xprof); capture is "
                              "bit-exact vs no capture (tier-1 pinned)")
    _add_config_flags(ssearch)

    sfarm = ssub.add_parser(
        "farm",
        help="the fuzzing farm: portfolio hunts, coverage-guided mutation, "
             "and the self-growing safety corpus (raft_sim_tpu/farm; "
             "docs/SCENARIOS.md 'Running the farm')",
    )
    sfarm.add_argument("--preset", choices=sorted(PRESETS), default=None)
    # build_config reads args.batch; the farm population IS the batch.
    sfarm.add_argument("--batch", type=int, default=None, help=argparse.SUPPRESS)
    sfarm.add_argument("--mutant", default=None, metavar="NAME",
                       help="TEST-ONLY: hunt a deliberately weakened kernel "
                            "(scenario/mutation.py registry)")
    sfarm.add_argument("--portfolio", default="scalar,coverage",
                       metavar="M1,M2,...",
                       help="comma list of fitness members hunted in "
                            "parallel over disjoint slices of the fleet "
                            "(farm/portfolio.py registry: scalar, coverage, "
                            "multi_leader, commit_stall, read_staleness, "
                            "durability; "
                            "default scalar,coverage)")
    sfarm.add_argument("--budget-gens", type=int, default=8,
                       help="generation budget; exhausting it hitless pins "
                            "a negative result (out-dir/negative.json)")
    sfarm.add_argument("--population", type=int, default=64,
                       help="fleet batch, split among the members; under "
                            "--mesh this is the PER-DEVICE population (the "
                            "total scales with the device count)")
    sfarm.add_argument("--mesh", type=int, default=None, metavar="D",
                       help="shard each generation over D devices (0 = all "
                            "available): one shard_map'ped evaluation per "
                            "generation, bit-identical hits at any device "
                            "count (parallel.simulate_windowed_sharded)")
    sfarm.add_argument("--ticks", type=int, default=512)
    sfarm.add_argument("--window", type=int, default=64,
                       help="telemetry window (fitness resolution)")
    sfarm.add_argument("--elite-frac", type=float, default=0.25)
    sfarm.add_argument("--trace-depth", type=int, default=32, metavar="R")
    sfarm.add_argument("--no-guided", action="store_true",
                       help="disable coverage-guided mutation (pure "
                            "per-member CE; a trace-free portfolio then "
                            "runs the untraced program)")
    sfarm.add_argument("--stop-on", choices=("hit", "frozen", "budget"),
                       default="hit",
                       help="early-stop policy: first processed hit "
                            "(default), first NEWLY FROZEN artifact "
                            "(dedup-rejected re-finds keep hunting), or "
                            "never (run the whole budget)")
    sfarm.add_argument("--seed", type=int, default=None)
    sfarm.add_argument("--out-dir", metavar="DIR", required=True,
                       help="farm output: farm_manifest.json, "
                            "members/<name>/hunt.jsonl, perf.jsonl, "
                            "negative.json on a hitless budget")
    sfarm.add_argument("--corpus-dir", metavar="DIR", default=None,
                       help="arm the auto-corpus policy against DIR "
                            "(hits are shrunk + dedup'd by (kernel, kinds, "
                            "mechanism-set) signature; e.g. tests/corpus)")
    sfarm.add_argument("--freeze", action="store_true",
                       help="let the farm WRITE new checker-gated, "
                            "provenance-stamped artifacts into --corpus-dir")
    sfarm.add_argument("--health", nargs="?", const="default", default=None,
                       metavar="SPEC",
                       help="arm health monitoring over the hunt fleet "
                            "(raft_sim_tpu/health): each generation's window "
                            "records feed the SLO evaluator, streaming "
                            "health.jsonl + alerts.jsonl into --out-dir "
                            "(safety alerts fire immediately on a violating "
                            "generation). Omit SPEC for the default spec")
    sfarm.add_argument("--backend", default="auto", metavar="NAME")
    sfarm.add_argument("--profile", metavar="DIR", default=None,
                       help="capture a jax.profiler trace of the farm into "
                            "DIR (view with tensorboard/xprof)")
    _add_config_flags(sfarm)

    sshrink = ssub.add_parser(
        "shrink", help="minimize a search hit to a repro artifact"
    )
    sshrink.add_argument("--hit", metavar="FILE", required=True,
                         help="hit file from `scenario search --out`")
    sshrink.add_argument("--out", metavar="FILE", required=True,
                         help="repro artifact path (tools/repro.py --scenario)")
    sshrink.add_argument("--halving-rounds", type=int, default=3)
    sshrink.add_argument("--context", type=int, default=30)
    sshrink.add_argument("--backend", default="auto", metavar="NAME")

    args = ap.parse_args(argv)

    if args.cmd == "scenario":
        select_backend(args.backend)
        return {
            "run": _scenario_run,
            "search": _scenario_search,
            "farm": _scenario_farm,
            "shrink": _scenario_shrink,
        }[args.scmd](args, ap)

    if args.cmd == "serve":
        select_backend(args.backend)
        return _serve(args, ap)

    if args.cmd == "presets":
        for name, (cfg, batch) in sorted(PRESETS.items()):
            print(f"{name}: batch={batch} {cfg}")
        return 0

    select_backend(args.backend)
    if args.resume:
        # A checkpoint IS the config; silently rerunning it under different flags
        # would mislabel the results.
        conflicting = [
            f.name for f in dataclasses.fields(RaftConfig)
            if getattr(args, f.name) is not None
        ]
        if args.preset:
            conflicting.append("preset")
        if args.batch is not None:
            conflicting.append("batch")
        if args.seed is not None:
            conflicting.append("seed")  # the checkpoint carries its own seed
        if args.mutant:
            conflicting.append("mutant")
        if args.trace or args.trace_trigger or args.trace_freeze:
            conflicting.append("trace")  # track_trace is part of the config
        if conflicting:
            ap.error(f"--resume is exclusive with config flags: {', '.join(conflicting)}")
        # Checkpoint problems (bad path, stale format) surface as real errors;
        # only --devices misuse gets the argparse usage-error framing.
        sess = Session.restore(args.resume)
        if args.devices is not None:
            try:
                sess.devices = args.devices
                sess._apply_sharding()
            except ValueError as ex:
                ap.error(str(ex))
    else:
        cfg, batch = build_config(args)
        if args.mutant:
            from raft_sim_tpu.scenario.mutation import mutant_config

            try:
                cfg = mutant_config(args.mutant, cfg)
            except ValueError as ex:
                ap.error(str(ex))
        if args.trace or args.trace_trigger or args.trace_freeze:
            # --trace-trigger / --trace-freeze imply the trace plane: both
            # are meaningless without the extracted event stream, so an
            # explicitly set kind must never be silently dropped.
            if not args.telemetry_dir:
                ap.error("--trace/--trace-trigger/--trace-freeze need "
                         "--telemetry-dir (trace windows stream through the "
                         "telemetry sink)")
            cfg = dataclasses.replace(cfg, track_trace=True)
        try:
            sess = Session(
                cfg,
                batch=batch,
                seed=args.seed if args.seed is not None else 0,
                devices=args.devices,
            )
        except ValueError as ex:
            ap.error(str(ex))

    if args.trace_ticks or args.trace_events:
        if (args.save or args.profile or args.apply_log or args.telemetry_dir
                or args.perf or args.health):
            ap.error("--save/--profile/--apply-log/--telemetry-dir/--perf/"
                     "--health have no effect with --trace-ticks/"
                     "--trace-events (tracing does not advance the session)")
        n = args.trace_ticks or args.ticks
        infos, states = sess.trace(n, cluster=args.trace_cluster)
        if args.trace_events:
            for t, ev in trace.events(states):
                print(f"tick {t:>6}  {ev}")
        else:
            for line in trace.info_lines(infos):
                print(line)
        return 0

    if args.apply_log:
        try:
            sess.attach_apply_log(args.apply_log, cluster=args.apply_cluster)
        except IndexError as ex:
            ap.error(str(ex))

    if args.telemetry_dir:
        try:
            sess.attach_telemetry(
                args.telemetry_dir,
                window=args.telemetry_window,
                ring=args.telemetry_ring,
            )
        except ValueError as ex:
            ap.error(str(ex))
        if args.trace or args.trace_trigger or args.trace_freeze:
            # --trace-trigger/--trace-freeze imply the trace plane: their
            # predicates are computed from the same extracted events the
            # stream exports.
            try:
                sess.attach_trace(
                    depth=args.trace_depth,
                    freeze=args.trace_freeze,
                    trigger=args.trace_trigger,
                )
            except ValueError as ex:
                ap.error(str(ex))

    if args.perf:
        # After attach_telemetry so perf.jsonl streams into the same sink
        # directory; without --telemetry-dir the rows stay in memory and
        # only the steady-state rollup is printed.
        sess.attach_perf()

    if args.health:
        if not args.telemetry_dir:
            ap.error("--health needs --telemetry-dir (the health/alert "
                     "streams ride the telemetry sink directory; the "
                     "sink-free plain path is the Session.attach_health "
                     "API's directory= form)")
        try:
            sess.attach_health(args.health)
        except ValueError as ex:
            ap.error(str(ex))

    t0 = time.perf_counter()
    with _profile_ctx(args.profile), _sanitize_ctx(args) as san:
        sess.run(args.ticks, chunk=args.chunk, progress=args.progress)
        # Time to the host-side rollup, not block_until_ready: this TPU stack's
        # block can return before execution finishes (see bench.py docstring);
        # summary()'s device_get provably waits for real data.
        out = sess.summary()
    dt = time.perf_counter() - t0
    _sanitize_report(args, san)
    out["wall_s"] = round(dt, 3)
    out["cluster_ticks_per_s"] = round(sess.batch * args.ticks / dt, 1)
    if args.perf:
        # Steady-state attribution rollup + the recompile-watchdog finding
        # (finish() prints it to stderr if a steady-state chunk compiled).
        out["perf"] = sess.perf.finish()
    if sess.health is not None:
        # Trailing partial eval period included; the rollup names every
        # objective that fired so a scripted run can gate on it.
        out["health"] = sess.health.finalize()
        print(sess.health.status_line(), file=sys.stderr)
    print(json.dumps(out))

    if args.telemetry_dir:
        fin = sess.finalize_telemetry()
        if fin["flights"]:
            print(
                f"telemetry: flight recordings exported for clusters "
                f"{fin['flights']} under {args.telemetry_dir}",
                file=sys.stderr,
            )

    if args.save:
        sess.save(args.save)
    return 0


if __name__ == "__main__":
    sys.exit(main())
