"""Telemetry tier: windowed on-device aggregation, the violation flight
recorder, and the schema'd host sink.

The load-bearing property is BIT-EXACTNESS: telemetry must be a pure
re-bucketing of what the monolithic scan already computes -- same
trajectories (shared tick body), and window records that reduce to exactly
the full run's metrics, which themselves equal folding the full per-tick
StepInfo stack. Anything weaker and a soak run observed through telemetry
would be a different experiment than the one it reports on.

Compile budget: the fuzz-config comparisons share ONE module-scoped run
(`fuzz_run`) -- plain scan, telemetry scan, and a per-tick stack built by
driving the SAME jitted tick body from the host -- so the tier-1 pass pays
three kernel compiles here, not one per test. The chunked/simulate wrappers
re-exercise the same machinery through more entry points and ride the slow
tier (the driver CLI tests below keep the chunked path covered in tier-1).
"""

import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from raft_sim_tpu import LEADER, RaftConfig, StepInfo, init_batch
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.sim import scan, telemetry, trace
from raft_sim_tpu.utils import telemetry_sink

# A kitchen-sink fault mix (drop + crash + skew + client traffic) so windows
# carry nonzero values in every field the schema defines.
FUZZ_CFG = RaftConfig(
    n_nodes=5,
    log_capacity=16,
    client_interval=4,
    drop_prob=0.2,
    crash_prob=0.3,
    crash_period=32,
    crash_down_ticks=8,
    clock_skew_prob=0.1,
)
BATCH, TICKS, WINDOW, RING = 4, 64, 16, 8

# The driver-level tests share one (cfg, batch, window, ring) shape so the CLI
# test reuses the session test's compiled programs.
DRIVER_CFG = RaftConfig(n_nodes=5, client_interval=8)
DRIVER_BATCH, DRIVER_WINDOW = 2, 16


def _setup(cfg, batch, seed=0):
    root = jax.random.key(seed)
    ki, kr = jax.random.split(root)
    return init_batch(cfg, ki, batch), jax.random.split(kr, batch)


def tree_eq(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=msg)


@pytest.fixture(scope="module")
def fuzz_run():
    """One fuzzed trajectory observed three ways: the monolithic batch-minor
    scan, the windowed telemetry scan (with flight recorder), and a full
    per-tick StepInfo stack produced by stepping the SAME shared tick body
    (scan.tick_batch_minor) from the host."""
    state, keys = _setup(FUZZ_CFG, BATCH)
    plain_final, plain_metrics = scan.run_batch_minor(FUZZ_CFG, state, keys, TICKS)
    rec0 = telemetry.init_recorder(FUZZ_CFG, RING, BATCH)
    tel_final, tel_metrics, records, recorder = telemetry.run_batch_minor_telemetry(
        FUZZ_CFG, state, keys, TICKS, window=WINDOW, recorder=rec0
    )
    # Per-tick ground truth: one jitted tick, driven T times from the host.
    s_t = raft_batched.to_batch_minor(state)
    m_t = raft_batched.to_batch_minor(scan.init_metrics_batch(BATCH))
    tick = jax.jit(lambda s, m: scan.tick_batch_minor(FUZZ_CFG, s, keys, m))
    per_tick = []
    for _ in range(TICKS):
        s_t, m_t, info = tick(s_t, m_t)
        per_tick.append(jax.device_get(raft_batched.from_batch_minor(info)))
    stack = StepInfo(
        *(
            np.stack([np.asarray(getattr(i, f)) for i in per_tick], axis=1)
            for f in StepInfo._fields
        )
    )  # leaves [B, T, ...], like vmap(scan.run)(trace=True)
    loop_final = raft_batched.from_batch_minor(s_t)
    loop_metrics = raft_batched.from_batch_minor(m_t)
    return SimpleNamespace(
        state=state, keys=keys,
        plain_final=plain_final, plain_metrics=plain_metrics,
        tel_final=tel_final, tel_metrics=tel_metrics,
        records=jax.device_get(records), recorder=jax.device_get(recorder),
        stack=stack, loop_final=loop_final, loop_metrics=loop_metrics,
    )


# ------------------------------------------------- windowed aggregation exactness


def test_windowed_records_reduce_to_monolithic_metrics(fuzz_run):
    """The tentpole contract: reducing the [T/W] window records equals the
    monolithic scan's RunMetrics bit-for-bit, and the telemetry carry legs do
    not perturb the trajectory."""
    tree_eq(fuzz_run.plain_final, fuzz_run.tel_final,
            "telemetry perturbed the trajectory")
    tree_eq(fuzz_run.plain_metrics, fuzz_run.tel_metrics,
            "telemetry perturbed the run metrics")
    tree_eq(fuzz_run.plain_metrics, telemetry.reduce_records(fuzz_run.records),
            "window reduction diverged from the monolithic metrics")


def test_windowed_records_match_full_per_tick_stack(fuzz_run):
    """Each window's sums equal summing the full per-tick StepInfo stack over
    exactly that window's ticks -- windowing loses resolution, not data. The
    stack comes from the same tick body driven tick-by-tick (which itself
    reproduces the scan bit-for-bit: integer kernel, same op order)."""
    tree_eq(fuzz_run.plain_final, fuzz_run.loop_final)
    tree_eq(fuzz_run.plain_metrics, fuzz_run.loop_metrics)
    recs, stack = fuzz_run.records, fuzz_run.stack
    assert np.asarray(recs.start).shape == (BATCH, TICKS // WINDOW)
    for wi in range(TICKS // WINDOW):
        sl = slice(wi * WINDOW, (wi + 1) * WINDOW)
        for stack_f, win_f in [
            ("msgs_delivered", "total_msgs"),
            ("cmds_injected", "total_cmds"),
            ("lat_sum", "lat_sum"),
            ("lat_cnt", "lat_cnt"),
            ("lat_excluded", "lat_excluded"),
            ("noop_blocked", "noop_blocked"),
            ("lm_skipped_pairs", "lm_skipped_pairs"),
        ]:
            per_tick = np.asarray(getattr(stack, stack_f))[:, sl].sum(axis=1)
            windowed = np.asarray(getattr(recs.metrics, win_f))[:, wi]
            np.testing.assert_array_equal(per_tick, windowed, err_msg=stack_f)
        # multi_leader folds the derived per-tick predicate (n_leaders >= 2).
        np.testing.assert_array_equal(
            (np.asarray(stack.n_leaders)[:, sl] >= 2).sum(axis=1),
            np.asarray(recs.metrics.multi_leader)[:, wi],
        )
        hist = np.asarray(stack.lat_hist)[:, sl].sum(axis=1)
        np.testing.assert_array_equal(hist, np.asarray(recs.metrics.lat_hist)[:, wi])
        np.testing.assert_array_equal(
            np.asarray(recs.start)[:, wi], np.full(BATCH, wi * WINDOW)
        )
        # Window max/min fold the per-tick stack's values too.
        np.testing.assert_array_equal(
            np.asarray(stack.max_term)[:, sl].max(axis=1),
            np.asarray(recs.metrics.max_term)[:, wi],
        )


def test_first_viol_tick_never_on_clean_run(fuzz_run):
    assert (np.asarray(fuzz_run.records.first_viol_tick) == telemetry.NEVER).all()


def test_window_must_divide():
    state, keys = _setup(FUZZ_CFG, 2)
    with pytest.raises(ValueError, match="divide"):
        telemetry.run_batch_minor_telemetry(FUZZ_CFG, state, keys, 100, window=32)


@pytest.mark.slow
def test_chunked_telemetry_matches_and_emits_remainder_window():
    """The chunked path merges to the same metrics at any chunking and
    self-describes a final short window when ticks do not divide. (Tier-1
    covers the same path through the driver CLI tests below.)"""
    state, keys = _setup(FUZZ_CFG, 4, seed=3)
    _, m_plain = scan.run_batch_minor(FUZZ_CFG, state, keys, 100)
    seen = []
    _, m_tel, _ = telemetry.run_chunked_telemetry(
        FUZZ_CFG, state, keys, 100, window=32, chunk=64,
        callback=lambda done, s, m, recs: seen.append(jax.device_get(recs)) and False,
    )
    tree_eq(m_plain, m_tel)
    widths = [int(t) for recs in seen for t in np.asarray(recs.metrics.ticks)[0]]
    assert widths == [32, 32, 32, 4]  # three full windows + the remainder


@pytest.mark.slow
def test_simulate_windowed_matches_simulate():
    cfg = RaftConfig(n_nodes=5, client_interval=8)
    f1, m1 = scan.simulate(cfg, 7, 16, 64)
    f2, m2, recs, rec = telemetry.simulate_windowed(cfg, 7, 16, 64, 16, ring=8)
    tree_eq(f1, f2)
    tree_eq(m1, m2)
    assert not np.asarray(rec.frozen).any()


# ------------------------------------------------------- violation flight recorder


def _two_leaders(state, cluster):
    """Hand-plant an election-safety violation: two live LEADERs sharing a
    term in one cluster (the invariant phase flags it on the next tick)."""
    role = state.role.at[cluster, 0].set(LEADER).at[cluster, 1].set(LEADER)
    term = state.term.at[cluster, 0].set(99).at[cluster, 1].set(99)
    return state._replace(role=role, term=term)


def test_flight_recorder_freezes_on_forced_violation():
    """A seeded forced violation: the ring holds the K ticks ENDING at the
    first violating tick, freezes there, and the export renders through
    trace.info_lines with the violation as the newest line."""
    k = 8
    state, keys = _setup(DRIVER_CFG, 2, seed=1)
    rec = telemetry.init_recorder(DRIVER_CFG, k, 2)
    # Clean prefix: 12 ticks (> K, so the ring has wrapped at least once).
    state, _, _, rec = telemetry.run_batch_minor_telemetry(
        DRIVER_CFG, state, keys, 12, window=4, recorder=rec
    )
    assert not np.asarray(rec.frozen).any()
    # Violation planted in cluster 1 only; flagged on the next tick (now=12).
    state = _two_leaders(state, cluster=1)
    state, _, recs, rec = telemetry.run_batch_minor_telemetry(
        DRIVER_CFG, state, keys, 8, window=4, recorder=rec
    )
    assert np.asarray(rec.frozen).tolist() == [False, True]
    # The window records locate the violation tick exactly.
    assert np.asarray(recs.first_viol_tick)[1].tolist() == [12, 16]
    assert (np.asarray(recs.first_viol_tick)[0] == telemetry.NEVER).all()

    ticks, infos = telemetry.export_cluster(rec, 1)
    # Ring = the K ticks ending at the freeze tick, in chronological order.
    assert ticks.tolist() == list(range(5, 13))
    assert bool(np.asarray(infos.viol_election_safety)[-1])
    lines = list(trace.info_lines(infos))
    assert len(lines) == k
    assert lines[-1].endswith("VIOLATION")
    assert not any(l.endswith("VIOLATION") for l in lines[:-1])

    # Frozen means frozen: more ticks leave cluster 1's ring untouched while
    # cluster 0 keeps recording.
    state, _, _, rec = telemetry.run_batch_minor_telemetry(
        DRIVER_CFG, state, keys, 8, window=4, recorder=rec
    )
    t2, i2 = telemetry.export_cluster(rec, 1)
    np.testing.assert_array_equal(t2, ticks)
    tree_eq(i2, infos)
    t0, _ = telemetry.export_cluster(rec, 0)
    assert t0.max() == 27  # cluster 0 ring still advancing


def test_flight_recorder_partial_fill_export(fuzz_run):
    """Fewer recorded ticks than K never happens in the shared 64-tick run --
    but slot ordering does: the ring has wrapped 64/8 times and must still
    export in chronological order with all slots valid."""
    ticks, infos = telemetry.export_cluster(fuzz_run.recorder, 2)
    assert ticks.tolist() == list(range(TICKS - RING, TICKS))
    assert len(list(trace.info_lines(infos))) == RING
    # Ring rows equal the per-tick stack's final RING ticks: full fidelity.
    for f in StepInfo._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(infos, f)),
            np.asarray(getattr(fuzz_run.stack, f))[2, TICKS - RING:],
            err_msg=f,
        )


# ------------------------------------------------------------------- host sink


def test_sink_roundtrip_and_validation(fuzz_run, tmp_path):
    d = str(tmp_path / "tel")
    sink = telemetry_sink.TelemetrySink(
        d, FUZZ_CFG, seed=0, batch=BATCH, window=WINDOW, ring=RING, source="test"
    )
    assert sink.append_windows(fuzz_run.records) == TICKS // WINDOW
    assert telemetry_sink.validate(d) == []

    man = telemetry_sink.read_manifest(d)
    assert man["schema_version"] == telemetry_sink.TELEMETRY_SCHEMA_VERSION
    assert man["config_hash"] == telemetry_sink.config_hash(FUZZ_CFG)
    rows = telemetry_sink.read_windows(d)
    assert [r["window"] for r in rows] == list(range(TICKS // WINDOW))
    assert [r["start"] for r in rows] == [WINDOW * i for i in range(TICKS // WINDOW)]
    # The JSONL stream is a lossless fleet aggregation of the records.
    md = fuzz_run.plain_metrics
    assert sum(r["msgs"] for r in rows) == int(np.sum(np.asarray(md.total_msgs)))
    assert sum(r["cmds"] for r in rows) == int(np.sum(np.asarray(md.total_cmds)))
    assert sum(sum(r["lat_hist"]) for r in rows) == int(np.sum(np.asarray(md.lat_cnt)))

    # Flight export file passes the schema check too.
    ticks, infos = telemetry.export_cluster(fuzz_run.recorder, 2)
    sink.write_flight(2, ticks, infos)
    assert telemetry_sink.validate(d) == []


def test_sink_validation_catches_breakage(fuzz_run, tmp_path):
    d = str(tmp_path / "tel")
    sink = telemetry_sink.TelemetrySink(
        d, FUZZ_CFG, seed=0, batch=BATCH, window=WINDOW, ring=RING, source="test"
    )
    sink.append_windows(fuzz_run.records)
    assert telemetry_sink.validate(d) == []

    win = tmp_path / "tel" / "windows.jsonl"
    lines = win.read_text().splitlines()
    broken = json.loads(lines[1])
    del broken["msgs"]
    broken["lat_hist"] = [1, 2, 3]  # wrong arity
    win.write_text(lines[0] + "\n" + json.dumps(broken) + "\n")
    errs = telemetry_sink.validate(d)
    assert any("msgs" in e for e in errs)
    assert any("lat_hist" in e for e in errs)

    man = tmp_path / "tel" / "manifest.json"
    m = json.loads(man.read_text())
    m["schema_version"] = 999
    man.write_text(json.dumps(m))
    assert any("schema_version" in e for e in telemetry_sink.validate(d))


def test_sink_rebuild_discards_stale_flights(fuzz_run, tmp_path):
    """Re-attaching a sink to a directory must not leave a previous run's
    violation recordings (or rollup) under the fresh manifest -- stale
    flight_*.jsonl would misattribute old violations to the new run."""
    d = str(tmp_path / "tel")
    sink = telemetry_sink.TelemetrySink(
        d, FUZZ_CFG, seed=0, batch=BATCH, window=WINDOW, ring=RING, source="test"
    )
    ticks, infos = telemetry.export_cluster(fuzz_run.recorder, 0)
    stale = sink.write_flight(0, ticks, infos)
    sink.write_summary({"total_violations": 7})
    import os

    assert os.path.exists(stale)
    telemetry_sink.TelemetrySink(  # run 2 into the same directory
        d, FUZZ_CFG, seed=1, batch=BATCH, window=WINDOW, ring=RING, source="test"
    )
    assert not os.path.exists(stale)
    assert not os.path.exists(os.path.join(d, "summary.json"))


def test_metrics_report_tool(fuzz_run, tmp_path, capsys):
    import sys

    sys.path.insert(0, ".")
    from tools import metrics_report

    d = str(tmp_path / "tel")
    sink = telemetry_sink.TelemetrySink(
        d, FUZZ_CFG, seed=0, batch=BATCH, window=WINDOW, ring=RING, source="test"
    )
    sink.append_windows(fuzz_run.records)
    from raft_sim_tpu.parallel import summarize

    sink.write_summary(summarize(fuzz_run.plain_metrics)._asdict())

    assert metrics_report.main([d, "--validate"]) == 0
    assert metrics_report.main([d]) == 0
    out = capsys.readouterr().out
    assert f"{TICKS // WINDOW} windows" in out and "lat_excluded" in out
    # Self-diff: every shared metric's delta is 0.
    assert metrics_report.main(["--diff", d, d]) == 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        if line.startswith(("violations", "cmds", "msgs")):
            assert line.split()[-1] == "0"


# ------------------------------------------------------- driver + CLI integration


def test_session_telemetry_end_to_end(tmp_path):
    from raft_sim_tpu.driver import Session

    d = str(tmp_path / "tel")
    sess = Session(DRIVER_CFG, batch=DRIVER_BATCH, seed=0)
    sess.attach_telemetry(d, window=DRIVER_WINDOW, ring=32)
    sess.run(48)
    sess.run(16)  # window indices continue across run() calls
    fin = sess.finalize_telemetry()
    assert fin["flights"] == []  # clean run: nothing to export
    assert telemetry_sink.validate(d) == []
    rows = telemetry_sink.read_windows(d)
    assert [r["window"] for r in rows] == list(range(len(rows)))
    assert sum(r["ticks"] for r in rows) == 64
    assert not np.asarray(sess._tel_rec.frozen).any()


def test_cli_telemetry_flags(tmp_path, capsys):
    from raft_sim_tpu.driver import main

    d = str(tmp_path / "tel")
    rc = main([
        "run", "--batch", str(DRIVER_BATCH), "--ticks", "48",
        "--client-interval", "8",
        "--telemetry-dir", d, "--telemetry-window", str(DRIVER_WINDOW),
    ])
    assert rc == 0
    assert telemetry_sink.validate(d) == []
    out = capsys.readouterr().out
    assert '"lat_excluded"' in out  # summary line carries the coverage counter


def test_cli_telemetry_excluded_with_tracing(tmp_path):
    from raft_sim_tpu.driver import main

    with pytest.raises(SystemExit):
        main([
            "run", "--batch", "1", "--ticks", "8", "--trace-events",
            "--telemetry-dir", str(tmp_path / "t"),
        ])


# ------------------------------------------------------------ trace.events golden


def test_trace_events_golden():
    """Exact expected event stream from a hand-built state stack -- the
    decoder was previously only exercised indirectly (test_driver asserts
    substrings); this pins the full output."""
    from raft_sim_tpu.types import CANDIDATE, FOLLOWER

    F, C, L = FOLLOWER, CANDIDATE, LEADER
    states = SimpleNamespace(
        role=np.array([[F, F], [C, F], [L, F], [F, F]]),
        term=np.array([[1, 1], [2, 1], [2, 1], [3, 1]]),
        commit_index=np.array([[0, 0], [0, 0], [0, 0], [2, 0]]),
        log_base=np.array([[0, 0], [0, 0], [0, 0], [0, 1]]),
    )
    assert list(trace.events(states)) == [
        (1, "node 0 starts election for term 2"),
        (2, "node 0 becomes leader of term 2"),
        (3, "node 0 steps down (term 2 -> 3)"),
        (3, "node 0 commits through 2"),
        (3, "node 1 compacts through 1"),
    ]
