"""The program's own measurement points (obs/scopes.py, obs/timer.span).

- Every `tick.<phase>` named scope reaches the op metadata of the compiled
  chunk program, at a config that turns on compaction, client redirect,
  ReadIndex, PreVote and reconfiguration (so no phase is compiled away);
  `tick.storage`, at a config with the durable storage plane on (which
  excludes compaction), and in no program without that plane.
- The span ring is bounded, and the loops record their host steps in loop
  order: `run_chunked` (and the telemetry soak) `fleet.init`, `fleet.copy`, then
  dispatch/merge/callback per chunk; `ServeSession.serve` its serve steps.
- Spans are profiler TraceAnnotations: a CPU `jax.profiler.trace` holds them.
- JAX's compile work inside a loop step is recorded as `jax.*` spans.
- ChunkTimer rows keep their fields; the serve row's pack_s/export_s,
  dispatch_s and device_wait_s are the serve.pack/serve.export,
  serve.dispatch and serve.sync span durations.
- Only the process ring records JAX's compile work.

Trajectories with spans on are the parity tests' business (spans are always
on, so every parity test runs with them), and scopes adding no jaxpr
equation is tests/test_golden_jaxpr.py's.
"""

import dataclasses
import glob

import jax
import pytest

from raft_sim_tpu import RaftConfig, init_batch
from raft_sim_tpu.obs import (
    SPANS, TICK_PHASES, ChunkTimer, PhaseScopes, SpanRing, span, tick_scope,
)
from raft_sim_tpu.sim import chunked

CFG = RaftConfig(
    n_nodes=3, log_capacity=16, compact_margin=4, client_interval=2,
    client_redirect=True, read_interval=2, pre_vote=True, drop_prob=0.05,
)
# The membership phase exists only with the reconfiguration plane on.
SCOPED = dataclasses.replace(CFG, reconfig_interval=5)
# The storage phase exists only with the durable storage plane on, which
# excludes compaction; crashes and disk faults make every rule of it live.
DURABLE = dataclasses.replace(
    CFG, compact_margin=0, fsync_interval=2, fsync_jitter_prob=0.2,
    torn_tail_prob=0.3, lost_suffix_span=2, crash_prob=0.1, crash_period=16,
    crash_down_ticks=4,
)
BATCH, CHUNK = 4, 8


def _setup(cfg=CFG, batch=BATCH, seed=0):
    ki, kr = jax.random.split(jax.random.key(seed))
    return init_batch(cfg, ki, batch), jax.random.split(kr, batch)


def _chunk_hlo(cfg):
    """The optimized HLO text of the donating chunk program, compiled on
    the CPU at `cfg`."""
    state, keys = _setup(cfg)
    return chunked._chunk_donate.lower(
        cfg, state, keys, CHUNK, None, 1).compile().as_text()


@pytest.fixture(scope="module")
def chunk_hlo():
    return _chunk_hlo(SCOPED)


@pytest.fixture(scope="module")
def durable_hlo():
    return _chunk_hlo(DURABLE)


@pytest.mark.parametrize("phase", TICK_PHASES)
def test_tick_scopes_in_chunk_program(request, phase):
    hlo = request.getfixturevalue("durable_hlo" if phase == "storage" else "chunk_hlo")
    assert f"/tick.{phase}/" in hlo


def test_storage_scope_only_in_durable_programs(chunk_hlo, durable_hlo):
    """The storage plane's ops carry `tick.storage`; a program without the
    plane (the scoped config, and the durable config with the plane off)
    has no op under it."""
    assert "/tick.storage/" in durable_hlo
    assert "/tick.storage/" not in chunk_hlo
    assert "/tick.storage/" not in _chunk_hlo(
        dataclasses.replace(DURABLE, fsync_interval=0, fsync_jitter_prob=0.0,
                            torn_tail_prob=0.0, lost_suffix_span=1))


def test_phase_scopes_close_on_error_and_reject_unknown_phases():
    """A phase left open by an exception is closed by the `with`: ops
    traced after it carry no tick scope."""
    with pytest.raises(ValueError):
        tick_scope("bogus")

    def f(x):
        try:
            with PhaseScopes() as phase:
                phase("elect")
                raise RuntimeError("trace failed")
        except RuntimeError:
            pass
        return x + 1

    def g(x):
        with PhaseScopes() as phase:
            phase("commit")
            y = x * 2
        return y + 1

    assert "tick." not in jax.jit(f).lower(1.0).as_text(debug_info=True)
    txt = jax.jit(g).lower(1.0).as_text(debug_info=True)
    assert "tick.commit/mul" in txt and "tick.commit/add" not in txt


def _names(rows, prefix):
    return [r.name for r in rows if r.name.startswith(prefix)]


def _since(t):
    """The process-wide ring's spans that started at or after `t`."""
    return [r for r in SPANS.rows if r.start >= t]


def test_span_ring_is_bounded_and_keeps_the_newest():
    ring = SpanRing(capacity=4)
    for i in range(10):
        with ring.span(f"s{i}", count=i) as sp:
            pass
        assert (sp.start, sp.end) == ring.rows[-1][1:3]
    assert [r.name for r in ring.rows] == ["s6", "s7", "s8", "s9"]
    assert [r.count for r in ring.rows] == [6, 7, 8, 9]
    assert all(r.end >= r.start for r in ring.rows)


def test_only_the_process_ring_records_compiles():
    """A ring of its own records its spans and no compile work; the
    process ring, listening from its own first span on, records the
    compile that ran inside that span."""
    with span("test.first"):
        pass
    ring = SpanRing(capacity=8)
    t0 = SPANS.rows[-1].end
    with ring.span("own"):
        jax.jit(lambda x: x * 3 + 11)(2.0)
    assert [r.name for r in ring.rows] == ["own"]
    assert "jax.compile" in _names(_since(t0), "jax.")


def test_span_is_recorded_when_its_step_raises():
    ring = SpanRing(capacity=4)
    with pytest.raises(ValueError):
        with ring.span("boom"):
            raise ValueError("step failed")
    assert [r.name for r in ring.rows] == ["boom"]


def test_run_chunked_spans_in_loop_order():
    state, keys = _setup()
    calls = []
    t0 = SPANS.rows[-1].end if SPANS.rows else 0.0
    chunked.run_chunked(CFG, state, keys, 3 * CHUNK, chunk=CHUNK,
                        callback=lambda *a: calls.append(a[0]) and False)
    rows = [r for r in _since(t0) if r.name.startswith("fleet.")]
    assert [r.name for r in rows] == (
        ["fleet.init", "fleet.copy"]
        + ["fleet.dispatch", "fleet.merge", "fleet.callback"] * 3
    )
    assert [r.count for r in rows if r.name == "fleet.dispatch"] == [CHUNK] * 3
    assert all(a.end <= b.start for a, b in zip(rows, rows[1:]))
    assert calls == [CHUNK, 2 * CHUNK, 3 * CHUNK]


def test_telemetry_soak_spans_read_like_run_chunked():
    from raft_sim_tpu.sim import telemetry

    cfg = RaftConfig(n_nodes=3, log_capacity=8, client_interval=4)
    state, keys = _setup(cfg, batch=2)
    t0 = SPANS.rows[-1].end if SPANS.rows else 0.0
    telemetry.run_chunked_telemetry(cfg, state, keys, 32, window=8, chunk=16,
                                    callback=lambda *a: False)
    assert _names(_since(t0), "fleet.") == (
        ["fleet.init", "fleet.copy"]
        + ["fleet.dispatch", "fleet.merge", "fleet.callback"] * 2
    )


def test_compile_inside_a_loop_step_is_a_jax_compile_span():
    """A chunk length no earlier test used compiles inside the first
    dispatch span: the listener records it there, on the same clock."""
    state, keys = _setup()
    t0 = SPANS.rows[-1].end if SPANS.rows else 0.0
    chunked.run_chunked(CFG, state, keys, 5, chunk=5)
    rows = _since(t0)
    disp = next(r for r in rows if r.name == "fleet.dispatch")
    compiles = [r for r in rows if r.name == "jax.compile"]
    assert compiles
    assert any(disp.start <= c.start and c.end <= disp.end for c in compiles)
    assert any(r.name == "jax.lower" for r in rows)


def test_profiler_trace_holds_the_program_spans(tmp_path):
    from jax.profiler import ProfileData

    state, keys = _setup()
    chunked.run_chunked(CFG, state, keys, CHUNK, chunk=CHUNK)  # compile outside
    with jax.profiler.trace(str(tmp_path)):
        chunked.run_chunked(CFG, state, keys, 2 * CHUNK, chunk=CHUNK,
                            callback=lambda *a: False)
        with span("test.marker"):
            pass
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events]
    assert {"fleet.init", "fleet.copy", "fleet.dispatch", "fleet.merge",
            "fleet.callback", "test.marker"} <= {e.name for e in events}
    # A span's count is the event's `count` argument.
    assert [dict(e.stats).get("count") for e in events
            if e.name == "fleet.dispatch"] == [CHUNK, CHUNK]


@pytest.fixture(scope="module")
def served():
    """One short served run with a ChunkTimer: its rows and its spans."""
    from raft_sim_tpu.serve.ingest import CommandSource
    from raft_sim_tpu.serve.loop import ServeSession, serve_config

    cfg = serve_config(RaftConfig(n_nodes=3, log_capacity=8))
    timer = ChunkTimer(label="serve", batch=2)
    sess = ServeSession(cfg, batch=2, seed=0, chunk=16, window=16,
                        warmup_ticks=16, perf=timer)
    t0 = SPANS.rows[-1].end if SPANS.rows else 0.0
    seen = []
    stats = sess.serve(CommandSource(iter(range(1, 40))), drain_chunks=2,
                       progress=lambda s: seen.append(s["chunks"]))
    return {"timer": timer, "spans": _since(t0), "stats": stats,
            "progress": seen}


def test_serve_spans_in_loop_order(served):
    names = _names(served["spans"], "serve.")
    n = len(served["progress"])
    assert n >= 3
    # Chunk 0 is dispatched after its planes are packed; each chunk then
    # exports the previous one's rows (from the second on), packs the next
    # planes (all but the last), and dispatches the next chunk (all but the
    # last).
    expect = ["serve.pack", "serve.dispatch"]
    for k in range(n):
        last = k == n - 1
        expect += (["serve.export"] if k else []) + ([] if last else ["serve.pack"])
        expect += ["serve.rounds", "serve.merge", "serve.sync", "serve.drain",
                   "serve.progress"]
        expect += [] if last else ["serve.dispatch"]
    assert names == expect
    disp = [r.count for r in served["spans"] if r.name == "serve.dispatch"]
    assert disp == [16] * len(disp)
    exported = [r.count for r in served["spans"] if r.name == "serve.export"]
    assert all(c >= 0 for c in exported) and sum(exported) > 0
    assert all(r.count is None for r in served["spans"] if r.name == "serve.drain")


def test_chunk_timer_rows_keep_fields_and_read_the_spans(served):
    rows = served["timer"].rows
    core = {"chunk", "ticks", "warmup", "wall_s", "dispatch_s", "host_s",
            "device_wait_s", "gap_s", "live_bytes", "jit_cache", "recompiled"}
    serving = [r for r in rows if "pack_s" in r]
    assert serving and all(set(r) == core | {"pack_s", "export_s"} for r in serving)
    assert all(set(r) == core for r in rows if "pack_s" not in r)
    # One perf row per serving chunk, in order; each chunk's export span
    # (absent on the first) and pack span (absent on the last) are the
    # row's export_s and pack_s, its dispatch span the row's dispatch_s
    # and ticks, and its sync span the row's device_wait_s.
    per_chunk, cur = [], {}
    for r in served["spans"]:
        if r.name == "serve.dispatch":
            cur = {"ticks": r.count}
        if r.name in ("serve.export", "serve.pack", "serve.dispatch", "serve.sync"):
            cur[r.name] = round(r.end - r.start, 6)
        if r.name == "serve.sync":
            per_chunk.append(cur)
    assert len(per_chunk) == len(serving)
    for row, got in zip(serving, per_chunk):
        assert row["ticks"] == got["ticks"]
        assert row["dispatch_s"] == got["serve.dispatch"]
        assert row["device_wait_s"] == got["serve.sync"]
        assert row["export_s"] == got.get("serve.export", 0.0)
        assert row["pack_s"] == got.get("serve.pack", 0.0)
