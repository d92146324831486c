"""ChunkTimer: per-chunk runtime attribution for the standing loops.

Every long-horizon driver in this repo advances the fleet in fixed-size jitted
chunks with host work between them (metric merges, telemetry export, ingest
packing, checkpoint callbacks). That boundary is the one place runtime
behaviour is observable without touching traced code, and the one place the
predictive cost model is blind: a chunk that runs 2x slower than its bytes/tick
projection could be host-stalled, dispatch-gapped, recompiling, or genuinely
memory-bound -- indistinguishable from a bench headline alone.

The timer splits each chunk's wall time into four host-measurable phases:

    begin ──(jitted call returns)── dispatched ──(host work)── sync ── end
      │         dispatch_s                │        host_s        │ device_wait_s
      └────────────────────────────── wall_s ─────────────────────────┘
    gap_s = time from the previous chunk's end to this begin (inter-chunk
            host work: export, fitness evaluation, source packing).

`device_wait_s` is the time blocked on a forced HOST COPY of a small chunk
output (the same defense bench.py uses: on this machine's TPU stack
`block_until_ready` can return early, data on the host cannot lie). It is a
*lower bound* on device execution -- whatever the device overlapped with the
host phases is invisible by construction; `dispatch_s + host_s + gap_s` is the
host gap the device could have been starved by. Enabling the timer adds one
host sync per chunk, which serializes pipelining a loop would otherwise
overlap -- attribution semantics, sizing, and that caveat are documented in
docs/OBSERVABILITY.md ("Runtime perf").

At every chunk boundary the timer also samples device-memory occupancy
(`live_bytes`, None where the backend publishes no memory stats -- CPU) and
the jit-cache size of each registered entry point. A cache that GROWS after
warmup is the recompile watchdog firing: the row is marked, the summary says
so, and `finish()` prints a visible finding -- the generalization of the
serve loop's pinned flat-cache discipline (PR 6) to every standing loop.

Rows stream to the telemetry sink as schema'd perf.jsonl
(utils/telemetry_sink.py validates them); everything here is host-side
stdlib + an optional jax device query, so the timer itself can never change a
trajectory, a lowering, or a compile count.

Beside the timer, always on and with no sync: `span(name, count=None)`, the
program's host spans. Each is a `jax.profiler.TraceAnnotation` (so it lands
on the profiler's clock in any trace, an operator's `--profile DIR` trace
included) and a `Span(name, start, end, count)` of `time.perf_counter`
seconds appended to the process-wide ring `SPANS` (bounded; the oldest
drop). The loops open them around their host steps (`fleet.*` in
sim/chunked.py and sim/telemetry.py, `serve.*` in serve/loop.py), and from
the first span on JAX's own compile work is recorded as `jax.trace`,
`jax.lower`, `jax.compile` and `jax.cache_load` spans. docs/OBSERVABILITY.md
lists every name and the count it carries.
"""

from __future__ import annotations

import collections
import contextlib
import sys
import time
from typing import NamedTuple

SPAN_RING = 65536  # spans kept: 4 to 9 a chunk, so the last ~7,000 chunks or more


class Span(NamedTuple):
    """One host span: perf_counter seconds, and the work it carried (ticks
    dispatched, delta rows exported), where it has a count."""

    name: str
    start: float
    end: float
    count: int | None


class OpenSpan:
    """The span being recorded: its count, and its perf_counter readings
    (`end` once closed), for callers that report the duration elsewhere
    too (ChunkTimer.dispatch_span, the serve row's pack_s/export_s)."""

    __slots__ = ("count", "start", "end")

    def __init__(self, count):
        self.count = count
        self.start = time.perf_counter()
        self.end = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


class SpanRing:
    """The program's host spans, newest last, in a bounded ring. Appends are
    atomic under the interpreter lock, so JAX's compile threads may record
    into it too. `listen` makes the ring record JAX's compile work from its
    first span on; only the process ring `SPANS` listens, since JAX keeps a
    listener for the rest of the process."""

    # JAX monitoring duration events -> span names (_on_duration).
    COMPILE_EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
        "/jax/core/compile/backend_compile_duration": "jax.compile",
        "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
    }

    def __init__(self, capacity: int = SPAN_RING, listen: bool = False):
        self.rows: collections.deque[Span] = collections.deque(maxlen=capacity)
        self._listen = listen

    @contextlib.contextmanager
    def span(self, name: str, count: int | None = None):
        """Time the enclosed host step as `name`; never syncs the device.
        A count rides the profiler event too, as its `count` argument."""
        import jax

        if self._listen:
            self._listen = False
            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        box = OpenSpan(count)
        args = {} if count is None else {"count": count}
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield box
        finally:
            box.end = time.perf_counter()
            self.rows.append(Span(name, box.start, box.end, count))

    def _on_duration(self, event: str, duration: float, **_) -> None:
        """Record one of JAX's compile steps as a span [now - duration, now]."""
        name = self.COMPILE_EVENTS.get(event)
        if name is not None:
            now = time.perf_counter()
            self.rows.append(Span(name, now - duration, now, None))


SPANS = SpanRing(listen=True)
span = SPANS.span


def device_live_bytes(device=None) -> int | None:
    """Current bytes in use on the (first local) device, or None where the
    backend publishes no memory stats (CPU) -- perf.jsonl rows carry null
    there, and reconciliation simply skips live-peak headroom."""
    try:
        import jax

        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    v = stats.get("bytes_in_use")
    return int(v) if v is not None else None


class ChunkTimer:
    """Per-chunk runtime attribution (module docstring has the phase diagram).

    >>> t = ChunkTimer(label="run", batch=batch, sink=sink)
    >>> # inside the loop, per chunk:
    >>> t.begin(n_ticks); out = jitted_chunk(...); t.dispatched()
    >>> # (or, where the loop times its dispatch as a span: with
    >>> # span("x.dispatch", n_ticks) as sp: out = ...; t.dispatch_span(sp))
    >>> ...host work...; t.end(sync=lambda: np.asarray(out.ticks))
    >>> t.finish()  # summary dict; prints the recompile finding if it fired

    `warmup_chunks` rows are flagged warmup and excluded from the steady-state
    rollup. The default is 2, not 1: chunk 0 pays the program compile, and on
    this jax version every DONATING chunk loop re-specializes once more at its
    second call (the donated output's buffer signature differs from the
    caller-owned input's -- observed on _chunk_donate, _chunk_t_donate, and
    _serve_chunk alike), so chunk 1 pays that one-time compile. Steady state
    starts at chunk 2. The recompile-watchdog BASELINE is likewise the first
    steady chunk's cache sample, not the warmup's -- a growth between the last
    warmup chunk and the first steady chunk is expected respecialization; a
    growth after that is a real mid-run recompile. `sink` (a TelemetrySink)
    streams each row to perf.jsonl; without one, rows accumulate on
    `self.rows` only.
    """

    def __init__(self, label: str = "run", batch: int = 1, sink=None,
                 warmup_chunks: int = 2):
        if warmup_chunks < 0:
            raise ValueError(f"warmup_chunks must be >= 0, got {warmup_chunks}")
        self.label = label
        self.batch = int(batch)
        self.sink = sink
        self.warmup_chunks = warmup_chunks
        self.rows: list[dict] = []
        self._probes: dict[str, object] = {}
        # Per-probe cache size at the FIRST STEADY chunk: the watchdog
        # baseline. Growth past it on any later steady chunk = a recompile
        # the loop promised not to do. (Warmup samples are never the
        # baseline: the one-time donated-carry respecialization at chunk 1
        # would make every run a false positive -- see the class docstring.)
        self._probe_base: dict[str, int] = {}
        self._recompiled = False
        self._chunk = 0
        self._t_begin = self._t_disp = None
        self._t_prev_end = None
        self._ticks = 0
        self._gap = 0.0
        self._extra: dict = {}

    # -------------------------------------------------------------- probes

    def add_probe(self, name: str, fn) -> None:
        """Register a jit-cache probe sampled at every chunk boundary: `fn` is
        a jitted entry point (its `_cache_size` is read) or any zero-arg
        callable returning an int. Idempotent -- the loops register their own
        entry points unconditionally."""
        if name in self._probes:
            return
        self._probes[name] = (
            fn._cache_size if hasattr(fn, "_cache_size") else fn
        )

    def _cache_sizes(self) -> dict[str, int]:
        out = {}
        for name, fn in self._probes.items():
            try:
                out[name] = int(fn())
            except Exception:
                out[name] = -1  # unprobeable on this jax version: visible, not fatal
        return out

    # --------------------------------------------------------------- phases

    def begin(self, ticks: int, at: float | None = None) -> None:
        t = time.perf_counter() if at is None else at
        self._gap = 0.0 if self._t_prev_end is None else t - self._t_prev_end
        self._ticks = int(ticks)
        self._t_begin = t
        self._t_disp = None

    def dispatched(self) -> None:
        """Call right after the jitted chunk call returns (async dispatch)."""
        self._t_disp = time.perf_counter()

    def dispatch_span(self, sp) -> None:
        """`begin` + `dispatched` from the loop's closed dispatch span
        (obs.span: its start, its end, its tick count), so the row's
        dispatch_s and the span are one reading."""
        self.begin(sp.count, at=sp.start)
        self._t_disp = sp.end

    def annotate(self, **extra) -> None:
        """Attach loop-measured sub-phase fields (JSON-able values) to the
        CURRENT chunk's row -- e.g. the serve loop's pack_s/export_s, timed
        inside its dispatch->sync host window so the overlap structure is a
        checkable perf.jsonl fact, not prose. Unknown keys ride the row
        as-is (the sink validates only the core schema fields)."""
        self._extra.update(extra)

    def end(self, sync=None) -> dict:
        """Close the chunk: `sync` forces a host copy of a small chunk output
        (its duration is the device wait, also recorded as the host span
        `<label>.sync`); sample memory + jit caches, append
        the row (and stream it to the sink). `end(sync=...)` is also the
        sync point Pass D's overlap audit recognizes: it CLOSES the
        dispatch->sync window a donating chunk dispatch opened, so host
        writes to the carry before this call are `race-window-mutation`
        findings (analysis/race_audit.py)."""
        if self._t_begin is None:
            raise RuntimeError("ChunkTimer.end() without begin()")
        if sync is not None:
            with span(f"{self.label}.sync") as sp:
                sync()
            t_host, t = sp.start, sp.end
        else:
            t_host = t = time.perf_counter()
        t_disp = self._t_disp if self._t_disp is not None else t_host
        caches = self._cache_sizes()
        warmup = self._chunk < self.warmup_chunks
        recompiled = False
        if not warmup:
            for name, size in caches.items():
                base = self._probe_base.setdefault(name, size)
                if size > base:
                    recompiled = True
                    self._recompiled = True
        row = {
            "chunk": self._chunk,
            "ticks": self._ticks,
            "warmup": warmup,
            "wall_s": round(t - self._t_begin, 6),
            "dispatch_s": round(t_disp - self._t_begin, 6),
            "host_s": round(t_host - t_disp, 6),
            "device_wait_s": round(t - t_host, 6),
            "gap_s": round(self._gap, 6),
            "live_bytes": device_live_bytes(),
            "jit_cache": caches,
            "recompiled": recompiled,
            **self._extra,
        }
        self._extra = {}
        self.rows.append(row)
        if self.sink is not None:
            self.sink.append_perf([row])
        self._chunk += 1
        self._t_begin = self._t_disp = None
        self._t_prev_end = t
        return row

    # -------------------------------------------------------------- rollups

    def summary(self) -> dict:
        """Steady-state rollup over the recorded rows (warmup excluded) --
        the same arithmetic `tools/metrics_report.py --perf` applies to a
        perf.jsonl stream, so the live summary and the file report agree."""
        return summarize_rows(
            self.rows, label=self.label, batch=self.batch,
            warmup_chunks=self.warmup_chunks,
        )

    def finish(self, out="stderr") -> dict:
        """End-of-run summary; prints the recompile-watchdog finding (and
        which probe grew) when a steady-state chunk compiled something.
        `out` defaults to the CURRENT sys.stderr (resolved at call time, not
        def time -- def-time binding breaks under stream capture); pass a
        stream to redirect, None to silence."""
        s = self.summary()
        if out == "stderr":
            out = sys.stderr
        if s["recompiled_after_warmup"] and out is not None:
            grown = [
                f"{name} {self._probe_base.get(name, '?')}->{size}"
                for name, size in (self.rows[-1]["jit_cache"] or {}).items()
                if size > self._probe_base.get(name, size)
            ]
            print(
                f"perf watchdog [{self.label}]: jit cache grew after warmup "
                f"({', '.join(grown) or 'see perf.jsonl jit_cache'}) -- a "
                "standing loop recompiled mid-run",
                file=out,
            )
        return s


def summarize_rows(rows: list[dict], label: str = "run", batch: int = 1,
                   warmup_chunks: int | None = None) -> dict:
    """Fold perf rows (live ChunkTimer rows or re-read perf.jsonl lines) into
    the steady-state summary. `warmup_chunks` defaults to trusting each row's
    own `warmup` flag (what the file form must do)."""
    if warmup_chunks is None:
        steady = [r for r in rows if not r.get("warmup")]
    else:
        steady = [r for r in rows if r["chunk"] >= warmup_chunks]
    ticks = sum(r["ticks"] for r in steady)
    wall = sum(r["wall_s"] + r["gap_s"] for r in steady)
    host_gap = sum(r["dispatch_s"] + r["host_s"] + r["gap_s"] for r in steady)
    wait = sum(r["device_wait_s"] for r in steady)
    live = [r["live_bytes"] for r in rows if r.get("live_bytes") is not None]
    return {
        "label": label,
        "batch": int(batch),
        "chunks": len(rows),
        "steady_chunks": len(steady),
        "steady_ticks": ticks,
        "steady_wall_s": round(wall, 6),
        "steady_ticks_per_s": round(ticks / wall, 1) if wall > 0 else None,
        "steady_cluster_ticks_per_s": (
            round(batch * ticks / wall, 1) if wall > 0 else None
        ),
        "device_wait_s": round(wait, 6),
        "host_gap_s": round(host_gap, 6),
        "host_gap_frac": round(host_gap / wall, 4) if wall > 0 else None,
        "live_bytes_peak": max(live) if live else None,
        "jit_cache_final": dict(rows[-1]["jit_cache"]) if rows else {},
        "recompiled_after_warmup": any(r.get("recompiled") for r in rows),
    }
