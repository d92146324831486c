"""Protocol trace plane (raft_sim_tpu/trace): extraction parity, bit-exactness,
history reconstruction, the whole-history checker, coverage, and triggers.

Program budget: the traced/untraced windowed runs share module-cached results,
so the file compiles a handful of SMALL windowed programs (N=5, batch 4-8,
64-256 ticks) -- every test family reuses them.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu.scenario.mutation import mutant_config
from raft_sim_tpu.sim import scan, telemetry
from raft_sim_tpu.trace import checker as tchecker
from raft_sim_tpu.trace import events as tev
from raft_sim_tpu.trace import history as thistory
from raft_sim_tpu.trace.ring import COV_BITS, TraceSpec, cov_popcount
from raft_sim_tpu.types import NIL, init_batch
from raft_sim_tpu.utils import telemetry_sink as sink_mod
from raft_sim_tpu.utils.config import RaftConfig

# A fault-rich little fleet so every event kind has a chance to fire: client
# traffic, drops, crashes, AND rolling partitions.
CFG = RaftConfig(
    n_nodes=5, client_interval=4, drop_prob=0.2, crash_prob=0.2,
    crash_period=32, crash_down_ticks=8, partition_period=16,
    partition_prob=0.3,
)
CFG_T = dataclasses.replace(CFG, track_trace=True)
SEED, BATCH, TICKS, WINDOW = 3, 4, 128, 32
SPEC = TraceSpec(depth=256)


@functools.lru_cache(maxsize=1)
def plain_run():
    return telemetry.simulate_windowed(CFG, SEED, BATCH, TICKS, WINDOW)


@functools.lru_cache(maxsize=1)
def traced_run():
    return telemetry.simulate_windowed(
        CFG_T, SEED, BATCH, TICKS, WINDOW, 0, None, 1, SPEC
    )


@functools.lru_cache(maxsize=1)
def traced_history():
    return thistory.from_device(traced_run()[4])


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


# ----------------------------------------------------------- vocabulary


def test_slot_tables_and_kind_order():
    n = CFG.n_nodes
    kinds = tev.slot_kinds(n)
    nodes = tev.slot_nodes(n)
    assert len(kinds) == len(nodes) == tev.n_slots(n)
    # KINDS is a bijection onto 1..N_KINDS-1 (0 reserved for empty slots).
    assert sorted(tev.KINDS.values()) == list(range(1, tev.N_KINDS))
    assert all(tev.KIND_NAMES[v] == k for k, v in tev.KINDS.items())
    # Slot order is kind-major ascending: the within-tick processing order.
    assert list(kinds) == sorted(kinds)
    # The checker's load-bearing ordering: role transitions strictly before
    # commit/append/truncate, fault kinds after them (trace/events.py).
    assert max(tev.EV_FOLLOWER, tev.EV_PRECANDIDATE, tev.EV_CANDIDATE,
               tev.EV_LEADER) < tev.EV_COMMIT
    assert tev.EV_TRUNCATE < tev.EV_CRASH <= tev.EV_RESTART
    # Cluster-scope slots carry the NIL node id.
    assert list(nodes[-len(tev.CLUSTER_KINDS):]) == [NIL] * len(tev.CLUSTER_KINDS)


# ----------------------------------------------- zero-cost / bit-exactness


@pytest.mark.slow  # budget re-tier (PR 12): the gate-alone program is
# pinned BYTE-IDENTICAL to the untraced one by the Pass A disabled-mode
# step goldens (an identical lowering cannot diverge), and the stronger
# claim -- an ARMED trace does not perturb the trajectory -- stays tier-1
# (test_traced_run_does_not_perturb_trajectory).
def test_track_trace_gate_alone_is_bit_exact():
    # cfg.track_trace=True with NO trace requested: the program carries no
    # trace leg and the run is bit-identical to the untraced config's.
    got = telemetry.simulate_windowed(CFG_T, SEED, BATCH, TICKS, WINDOW)
    assert len(got) == 4
    assert _tree_equal(got, plain_run())


def test_traced_run_does_not_perturb_trajectory():
    # Extraction + ring + coverage on: state/metrics/windows bit-identical.
    got = traced_run()
    assert len(got) == 6
    assert _tree_equal(got[:3], plain_run()[:3])


def test_trace_requires_track_trace():
    with pytest.raises(ValueError, match="track_trace"):
        telemetry.simulate_windowed(CFG, SEED, BATCH, TICKS, WINDOW, 0, None, 1, SPEC)


# ------------------------------------------- device-vs-host event parity


def _host_delta_events(cfg, init, states, cluster):
    """Re-derive the delta-based event kinds (1..9) host-side from a
    `scan.run` state stack (the single-cluster traced scan): the windowed
    trace path's device events must match what that trajectory implies."""
    fields = ("role", "term", "voted_for", "commit_index", "log_len")
    g0 = {f: np.asarray(getattr(init, f))[cluster] for f in fields}
    gs = {f: np.asarray(getattr(states, f))[cluster] for f in fields}
    n_ticks, n = gs["role"].shape
    out = []
    for t in range(n_ticks):
        old = g0 if t == 0 else {f: gs[f][t - 1] for f in fields}
        new = {f: gs[f][t] for f in fields}
        per_kind = {
            tev.EV_FOLLOWER: ((new["role"] == 0) & (old["role"] != 0), new["term"]),
            tev.EV_PRECANDIDATE: ((new["role"] == 3) & (old["role"] != 3), new["term"]),
            tev.EV_CANDIDATE: ((new["role"] == 1) & (old["role"] != 1), new["term"]),
            tev.EV_LEADER: ((new["role"] == 2) & (old["role"] != 2), new["term"]),
            tev.EV_TERM: (new["term"] > old["term"], new["term"]),
            tev.EV_VOTE: (
                (new["voted_for"] != old["voted_for"]) & (new["voted_for"] != NIL),
                new["voted_for"],
            ),
            tev.EV_COMMIT: (
                new["commit_index"] > old["commit_index"], new["commit_index"]
            ),
            tev.EV_APPEND: (new["log_len"] > old["log_len"], new["log_len"]),
            tev.EV_TRUNCATE: (new["log_len"] < old["log_len"], new["log_len"]),
        }
        for kind in sorted(per_kind):
            flags, detail = per_kind[kind]
            for node in range(n):
                if flags[node]:
                    out.append((t, node, kind, int(detail[node])))
    return out


def test_device_events_match_unbatched_kernel_replay():
    # Reproduce simulate_windowed's exact init/key derivation, then replay
    # each cluster through the single-cluster traced scan (scan.run) and
    # re-extract events host-side from its stacked states.
    root = jax.random.key(SEED)
    k_init, k_run = jax.random.split(root)
    state0 = init_batch(CFG_T, k_init, BATCH)
    keys = jax.random.split(k_run, BATCH)
    _, _, outs = jax.jit(
        jax.vmap(lambda s, k: scan.run(CFG_T, s, k, TICKS, trace_states=True))
    )(state0, keys)
    _, states = outs  # leaves [B, T, N, ...]
    hist = traced_history()
    assert hist.complete, f"ring overflowed: {hist.dropped}"
    for c in range(BATCH):
        want = _host_delta_events(CFG_T, jax.tree.map(lambda x: x, state0), states, c)
        got = [
            (e.tick, e.node, e.kind, e.detail)
            for e in hist.events[c]
            if e.kind <= tev.EV_TRUNCATE
        ]
        assert got == want, f"cluster {c}: device/host event streams differ"


def test_fault_events_consistent_with_inputs():
    # Restart events must agree with the fault schedule: every EV_RESTART at
    # tick t on node i corresponds to make_inputs(t).restarted[i].
    from raft_sim_tpu.sim import faults

    root = jax.random.key(SEED)
    _, k_run = jax.random.split(root)
    keys = jax.random.split(k_run, BATCH)
    hist = traced_history()
    for c in range(BATCH):
        restarts = [(e.tick, e.node) for e in hist.events[c]
                    if e.kind == tev.EV_RESTART]
        for t, node in restarts:
            inp = faults.make_inputs(CFG_T, keys[c], jnp.int32(t))
            assert bool(np.asarray(inp.restarted)[node]), (c, t, node)


# ------------------------------------------------- history + completeness


def test_history_counts_and_windows():
    hist = traced_history()
    _, _, _, _, traws, tp = traced_run()
    assert hist.n_windows == TICKS // WINDOW
    total = np.asarray(tp.total)
    for c in range(BATCH):
        assert hist.emitted[c] == int(total[c])
        assert len(hist.events[c]) == hist.emitted[c] - hist.dropped[c]
        ticks = [e.tick for e in hist.events[c]]
        assert ticks == sorted(ticks)


def test_overflow_is_flagged_never_silent():
    shallow = TraceSpec(depth=4)
    out = telemetry.simulate_windowed(
        CFG_T, SEED, BATCH, 64, 32, 0, None, 1, shallow
    )
    hist = thistory.from_device(out[4])
    assert any(hist.dropped.values())
    assert not hist.complete
    rep = tchecker.check_history(hist)
    # No violation witnessed and the history has holes: every property is
    # UNDECIDED, not vacuously passing.
    assert not rep.ok
    assert rep.violated == []
    assert all(r.ok is None for r in rep.results.values())
    assert "incomplete" in rep.results["election_safety"].note


# ------------------------------------------------------- checker verdicts


def test_real_kernel_history_passes_all_five():
    rep = tchecker.check_history(traced_history())
    assert rep.complete
    assert rep.ok, {n: r.note for n, r in rep.results.items() if not r.ok}
    assert all(r.ok is True for r in rep.results.values())


@functools.lru_cache(maxsize=1)
def mutant_run():
    cfg = mutant_config(
        "weak-quorum",
        RaftConfig(n_nodes=5, drop_prob=0.35, track_trace=True),
    )
    return telemetry.simulate_windowed(
        cfg, 0, 8, 256, 64, 0, None, 1, TraceSpec(depth=512)
    )


def test_weak_quorum_history_rejected_with_witness():
    out = mutant_run()
    assert int(np.asarray(out[1].violations).sum()) > 0  # device flags agree
    hist = thistory.from_device(out[4])
    assert hist.complete
    rep = tchecker.check_history(hist)
    assert rep.ok is False
    assert "election_safety" in rep.violated
    es = rep.results["election_safety"]
    # Minimal witness: the two conflicting leader events, same term,
    # different nodes.
    assert len(es.witness) == 2
    assert all(w["kind"] == "leader" for w in es.witness)
    assert es.witness[0]["detail"] == es.witness[1]["detail"]
    assert es.witness[0]["node"] != es.witness[1]["node"]


def _hist(events_by_cluster, dropped=None):
    ev = {c: [thistory.Event(*e) for e in evs]
          for c, evs in events_by_cluster.items()}
    return thistory.History(
        events=ev,
        emitted={c: len(v) for c, v in ev.items()},
        dropped=dropped or {c: 0 for c in ev},
        n_windows=1,
        problems=[],
    )


def test_checker_synthetic_negatives():
    L, C, T_, R, V = (tev.EV_LEADER, tev.EV_COMMIT, tev.EV_TRUNCATE,
                      tev.EV_RESTART, tev.EV_VIOLATION)
    # leader_append_only: a leader truncates while holding leadership.
    rep = tchecker.check_history(_hist({0: [(5, 1, L, 3), (9, 1, T_, 2)]}))
    assert rep.violated == ["leader_append_only"]
    # leader_completeness: a new leader commits below the frontier.
    rep = tchecker.check_history(_hist({0: [
        (5, 0, L, 3), (8, 0, C, 10), (20, 1, L, 4), (25, 1, C, 5),
    ]}))
    assert rep.violated == ["leader_completeness"]
    # ... but a FOLLOWER trailing the frontier is legal.
    rep = tchecker.check_history(_hist({0: [
        (5, 0, L, 3), (8, 0, C, 10), (12, 1, C, 5),
    ]}))
    assert rep.ok
    # state_machine_safety: per-node commit regression without a restart ...
    rep = tchecker.check_history(_hist({0: [(8, 2, C, 5), (12, 2, C, 3)]}))
    assert rep.violated == ["state_machine_safety"]
    # ... is legal ACROSS a restart (commit resumes from the durable base).
    rep = tchecker.check_history(_hist({0: [
        (8, 2, C, 5), (10, 2, R, 0), (12, 2, C, 3),
    ]}))
    assert rep.ok
    # log_matching + commit bits of a device violation event.
    rep = tchecker.check_history(_hist({0: [(3, NIL, V, tev.VIOL_LOG_MATCHING)]}))
    assert rep.violated == ["log_matching"]
    rep = tchecker.check_history(_hist({0: [(3, NIL, V, tev.VIOL_COMMIT)]}))
    assert rep.violated == ["state_machine_safety"]
    # A violation witnessed in an INCOMPLETE history still fails (never
    # demoted to undecided).
    h = _hist({0: [(5, 1, L, 3), (9, 1, T_, 2)]}, dropped={0: 7})
    rep = tchecker.check_history(h)
    assert rep.violated == ["leader_append_only"]
    assert rep.results["election_safety"].ok is None
    # A freeze-armed capture is a deliberate prefix: same undecided-never-
    # pass rule, with the truncation named (ticks stay monotone and nothing
    # drops, so the armed flag is the only trace of it).
    h = _hist({0: [(5, 1, L, 3)]})
    h.freeze_armed = True
    rep = tchecker.check_history(h)
    assert not rep.ok and rep.violated == []
    assert "freeze-truncated" in rep.results["election_safety"].note


# ------------------------------------------------------ sink + jsonl trips


def test_sink_round_trip_and_validate(tmp_path):
    d = str(tmp_path / "sink")
    sink = sink_mod.TelemetrySink(d, CFG_T, seed=SEED, batch=BATCH,
                                  window=WINDOW, ring=0)
    sink.write_trace_meta(SPEC)
    _, _, _, _, traws, _ = traced_run()
    sink.append_trace(traws)
    assert sink_mod.validate(d) == []
    loaded = thistory.load(d)
    hist = traced_history()
    assert loaded.complete
    assert loaded.events == hist.events
    assert not any(loaded.dropped.values())  # sparse map: only real drops
    rep = tchecker.check_directory(d)
    assert rep.ok


def test_truncated_and_out_of_order_streams_flagged(tmp_path):
    d = str(tmp_path / "bad")
    os.makedirs(d)
    with open(os.path.join(d, "trace_windows.jsonl"), "w") as f:
        # window index jumps 0 -> 2: a truncated/spliced stream.
        f.write(json.dumps({"window": 0, "emitted": 1, "retained": 1,
                            "dropped": 0, "dropped_by_cluster": {}}) + "\n")
        f.write(json.dumps({"window": 2, "emitted": 1, "retained": 1,
                            "dropped": 0, "dropped_by_cluster": {}}) + "\n")
    with open(os.path.join(d, "trace.jsonl"), "w") as f:
        f.write(json.dumps({"w": 0, "c": 0, "t": 9, "node": 1,
                            "k": tev.EV_LEADER, "d": 2}) + "\n")
        # tick regression within one cluster: out of order.
        f.write(json.dumps({"w": 2, "c": 0, "t": 4, "node": 1,
                            "k": tev.EV_COMMIT, "d": 1}) + "\n")
    hist = thistory.load(d)
    assert hist.problems and not hist.complete
    rep = tchecker.check_history(hist)
    assert not rep.ok and rep.violated == []  # incomplete, NOT a pass
    assert all(r.ok is None for r in rep.results.values())
    # validate() (full sink schema) flags the same stream defects.
    errors = sink_mod.validate(str(tmp_path / "bad"))  # no manifest: hard fail
    assert errors


# ------------------------------------------------------- coverage plane


def test_coverage_deterministic_and_bounded():
    _, _, _, _, traws, tp = traced_run()
    again = telemetry.simulate_windowed(
        CFG_T, SEED, BATCH, TICKS, WINDOW, 0, None, 1, SPEC
    )
    assert np.array_equal(np.asarray(tp.cov), np.asarray(again[5].cov))
    bits = np.asarray(cov_popcount(tp.cov))
    assert (bits > 0).all() and (bits <= COV_BITS).all()
    # Per-window cov snapshots are monotone (cumulative OR).
    cov_w = np.asarray(traws.cov)  # [W, C, B]
    for w in range(1, cov_w.shape[0]):
        assert (cov_w[w] & cov_w[w - 1] == cov_w[w - 1]).all()


def test_coverage_fitness_search_one_program_deterministic():
    from raft_sim_tpu.scenario import search as search_mod

    cfg = RaftConfig(n_nodes=5, client_interval=8)
    spec = search_mod.SearchSpec(
        generations=2, population=8, ticks=64, window=32,
        fitness="coverage", trace_depth=16, stop_on_hit=False,
    )
    res = search_mod.search(cfg, spec)
    assert res.spec["fitness"] == "coverage"
    assert [g["cov_new_bits"] for g in res.generations][0] > 0
    totals = [g["cov_total_bits"] for g in res.generations]
    assert totals == sorted(totals)  # the seen-set only grows
    res2 = search_mod.search(cfg, spec)
    assert [g["best_fitness"] for g in res.generations] == [
        g["best_fitness"] for g in res2.generations
    ]
    assert totals == [g["cov_total_bits"] for g in res2.generations]


# ----------------------------------------------------- triggers + driver


def test_flight_recorder_event_trigger():
    # Arm the recorder on the first LEADER event: it must freeze at the
    # first election, violations or not -- the "lead-up to a non-violating
    # anomaly" capture docs/OBSERVABILITY.md used to name as a gap.
    out = telemetry.simulate_windowed(
        CFG_T, SEED, BATCH, 64, 32, 4, None, 1, SPEC,
        tev.EV_LEADER,
    )
    _, metrics, _, rec, traws, _ = out
    hist = thistory.from_device(traws)
    frozen = np.asarray(rec.frozen)
    for c in range(BATCH):
        leads = [e.tick for e in hist.events[c] if e.kind == tev.EV_LEADER]
        if leads:
            assert frozen[c]
            ticks, _ = telemetry.export_cluster(rec, c)
            # The triggering tick is the ring's newest entry (freeze is
            # latched AFTER the write).
            assert ticks[-1] == leads[0]
        else:
            assert not frozen[c]


def test_offer_refused_while_trace_armed(tmp_path):
    # Session.offer() ticks outside the windowed scan: with a trace armed
    # they would punch undetectable holes in the history (ticks stay
    # monotone, nothing counts as dropped), so offer() must refuse instead
    # of letting the checker pass a gappy stream.
    from raft_sim_tpu import driver

    sess = driver.Session(CFG_T, batch=2, seed=0)
    sess.attach_telemetry(str(tmp_path / "tel"), window=32, ring=0)
    sess.attach_trace(depth=16)
    with pytest.raises(RuntimeError, match="trace"):
        sess.offer(42)


def test_finalize_telemetry_reports_frozen_vs_exported(tmp_path):
    from raft_sim_tpu import driver

    sess = driver.Session(CFG, batch=4, seed=0)
    sess.attach_telemetry(str(tmp_path / "tel"), window=32, ring=4)
    rec = telemetry.init_recorder(CFG, 4, 4)
    sess._tel_rec = rec._replace(frozen=jnp.ones((4,), bool))
    out = sess.finalize_telemetry(max_flights=2)
    assert out["flights_frozen"] == 4
    assert out["flights_exported"] == 2
    assert out["flights"] == [0, 1]
    with open(out["summary"]) as f:
        summary = json.load(f)
    assert summary["flights_frozen"] == 4
    assert summary["flights_exported"] == 2
