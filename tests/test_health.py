"""Health plane: SLI math, burn-rate alerting, triage, evidence, bit-exactness.

The load-bearing property mirrors the telemetry tier's: the health plane is a
HOST-SIDE fold over streams the loops already export, so an instrumented run
must be bit-identical to a plain one -- same trajectories, same metrics, same
windows.jsonl bytes -- on both carry layouts. Everything else here is exact
hand-rollup arithmetic: the SLI fold, the log2-bin percentile estimator
(pinned against the mesh report's), the burn-rate state machines on synthetic
error streams, the robust triage ordering, and the evidence-bundle round trip
through its own validator.

Compile budget: the bit-exactness tests are the only ones that touch the
simulator; they run at tiny shapes (batch 4, chunk 16) and the health-armed
session reuses the plain session's jitted programs (health adds no lowerings
-- that is the point), so each layout x path pays one compile.
"""

import copy
import dataclasses
import io
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig
from raft_sim_tpu.driver import Session
from raft_sim_tpu.health import (
    BurnEngine,
    HealthMonitor,
    HealthWriter,
    load_spec,
    validate_bundle,
    validate_spec,
)
from raft_sim_tpu.health import burn as burn_mod
from raft_sim_tpu.health import evidence as evidence_mod
from raft_sim_tpu.health import sli as sli_mod
from raft_sim_tpu.health import triage as triage_mod
from raft_sim_tpu.health.spec import DEFAULT_SPEC
from raft_sim_tpu.types import LAT_HIST_BINS
from raft_sim_tpu.utils import telemetry_sink

# Kitchen-sink faults so the instrumented runs carry nonzero values in every
# stream the health plane folds (same spirit as test_telemetry.FUZZ_CFG).
HCFG = RaftConfig(
    n_nodes=5,
    log_capacity=16,
    client_interval=4,
    drop_prob=0.2,
    crash_prob=0.3,
    crash_period=32,
    crash_down_ticks=8,
    clock_skew_prob=0.1,
)
HBATCH, HTICKS, HCHUNK, HWINDOW = 4, 64, 16, 8


def tree_eq(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=msg)


def _spec(**overrides):
    """A minimal valid spec the unit tests mutate: one availability objective
    (budget 0.1) under the default fast/slow rule pair."""
    spec = {
        "schema": "health-slo-v1",
        "eval_windows": 1,
        "worst_k": 3,
        "outlier_score": 3.0,
        "resolve_evals": 2,
        "objectives": {
            "availability": {"sli": "availability", "target": 0.9},
        },
        "rules": [
            {"name": "fast", "short": 1, "long": 2, "burn": 6.0},
        ],
    }
    spec.update(overrides)
    return spec


def _unit(batch=4, start=0, ticks=16, **fields):
    """A synthetic window unit with every counter zeroed unless overridden."""
    u = {
        "start": start,
        "ticks": ticks,
        "violations": np.zeros(batch, np.int64),
        "leaderless": np.zeros(batch, bool),
        "cmds": np.zeros(batch, np.int64),
        "reads": np.zeros(batch, np.int64),
        "lat_sum": np.zeros(batch, np.int64),
        "lat_cnt": np.zeros(batch, np.int64),
        "lat_hist": np.zeros((batch, LAT_HIST_BINS), np.int64),
        "read_hist": np.zeros((batch, LAT_HIST_BINS), np.int64),
        "fsync_lag_sum": np.zeros(batch, np.int64),
        "fsync_lag_max": np.zeros(batch, np.int64),
    }
    u.update(fields)
    return u


# ------------------------------------------------------------------ spec


def test_default_spec_valid_and_load_spec_copies():
    assert validate_spec(DEFAULT_SPEC) == []
    spec = load_spec("default")
    assert spec == DEFAULT_SPEC
    spec["eval_windows"] = 99  # a caller's mutation must not leak back
    assert DEFAULT_SPEC["eval_windows"] == 2


@pytest.mark.parametrize(
    "mutate, fragment",
    [
        (lambda s: s.update(schema="nope"), "schema"),
        (lambda s: s.update(eval_windows=0), "eval_windows"),
        (lambda s: s.update(outlier_score=-1), "outlier_score"),
        (lambda s: s.update(objectives={}), "objectives"),
        (
            lambda s: s["objectives"].update(bad={"sli": "made_up"}),
            "sli 'made_up'",
        ),
        (
            lambda s: s["objectives"].update(
                bad={"sli": "availability", "target": 1.0}
            ),
            "target",
        ),
        (
            lambda s: s["objectives"].update(
                bad={"sli": "commit_latency", "target": 0.9}
            ),
            "threshold_ticks",
        ),
        (
            lambda s: s["objectives"].update(
                bad={"sli": "throughput", "min_ops_per_window": 1, "budget": 0}
            ),
            "budget",
        ),
        (
            lambda s: s["objectives"]["availability"].update(pending_evals=-1),
            "pending_evals",
        ),
        (lambda s: s.update(rules=[]), "rules"),
        (
            lambda s: s.update(
                rules=[{"name": "r", "short": 4, "long": 2, "burn": 1.0}]
            ),
            "short window 4 > long window 2",
        ),
        (
            lambda s: s.update(rules=[
                {"name": "r", "short": 1, "long": 2, "burn": 1.0},
                {"name": "r", "short": 1, "long": 2, "burn": 2.0},
            ]),
            "duplicate rule name",
        ),
    ],
)
def test_spec_rejections(mutate, fragment):
    spec = copy.deepcopy(_spec())
    mutate(spec)
    errors = validate_spec(spec)
    assert errors, f"mutation should have been rejected ({fragment})"
    assert any(fragment in e for e in errors), errors
    with pytest.raises(ValueError):
        load_spec(spec)


# ------------------------------------------------------------- percentiles


def test_hist_percentile_edges():
    empty = np.zeros(LAT_HIST_BINS, np.int64)
    assert sli_mod.hist_percentile(empty, 0.5) is None
    # First-nonempty-bin hits clamp to the bin's lower edge.
    h = np.zeros(LAT_HIST_BINS, np.int64)
    h[3] = 10
    assert sli_mod.hist_percentile(h, 0.5) == float(1 << 3)
    # Interpolation inside a later bin, by hand: bins 1 (10 events) and
    # 5 (2 events); p95 needs 11.4 of 12, so 1.4/2 of bin 5's [32, 64) span.
    h = np.zeros(LAT_HIST_BINS, np.int64)
    h[1], h[5] = 10, 2
    want = 32.0 + (0.95 * 12 - 10) / 2 * (64.0 - 32.0)
    assert sli_mod.hist_percentile(h, 0.95) == pytest.approx(want)
    # q=1.0 lands inside the last nonempty bin, never past it.
    assert 32.0 <= sli_mod.hist_percentile(h, 1.0) <= 64.0


def test_hist_percentile_matches_mesh_report():
    """The health plane and the mesh summaries must never disagree on a
    percentile: pin the two estimators against each other on random hists."""
    from raft_sim_tpu.parallel.mesh import _hist_percentile

    rng = np.random.default_rng(7)
    for _ in range(50):
        h = rng.integers(0, 20, size=LAT_HIST_BINS)
        for q in (0.5, 0.9, 0.95, 0.99):
            assert sli_mod.hist_percentile(h, q) == _hist_percentile(h, q)


def test_fast_bins():
    # Exact at powers of two: bins 0..n-1 cover [1, 2^n).
    assert sli_mod.fast_bins(1) == 0
    assert sli_mod.fast_bins(2) == 1
    assert sli_mod.fast_bins(16) == 4
    # Conservative in between: the partial bin counts bad.
    assert sli_mod.fast_bins(17) == 4
    assert sli_mod.fast_bins(31) == 4
    assert sli_mod.fast_bins(32) == 5
    # Clamped to the histogram width.
    assert sli_mod.fast_bins(1 << (LAT_HIST_BINS + 4)) == LAT_HIST_BINS


# ------------------------------------------------------------------ SLIs


def test_compute_slis_hand_rollup():
    """Every SLI kind against a hand-computed rollup of two synthetic units."""
    spec = _spec(objectives={
        "availability": {"sli": "availability", "target": 0.9},
        "commit_latency": {
            "sli": "commit_latency", "threshold_ticks": 16, "target": 0.99,
        },
        "read_staleness": {
            "sli": "read_staleness", "stale_after_ticks": 4, "target": 0.99,
        },
        "throughput": {
            "sli": "throughput", "min_ops_per_window": 100, "budget": 0.25,
        },
        "safety": {"sli": "safety", "pending_evals": 0},
        "device_wait": {
            "sli": "device_wait_share", "min_share": 0.5, "budget": 0.25,
        },
        "recompile": {"sli": "recompiles", "pending_evals": 0},
    })
    lat0 = np.zeros((4, LAT_HIST_BINS), np.int64)
    lat0[0, 1] = 10  # fast (bin 1 < fast_bins(16)=4)
    lat0[0, 5] = 2   # slow
    lat1 = np.zeros((4, LAT_HIST_BINS), np.int64)
    lat1[1, 3] = 4   # fast
    reads0 = np.zeros((4, LAT_HIST_BINS), np.int64)
    reads0[2, 0] = 5  # fresh (fast_bins(4)=2)
    reads0[2, 2] = 3  # stale
    units = [
        _unit(start=0, leaderless=np.array([True, False, False, False]),
              lat_hist=lat0, read_hist=reads0,
              cmds=np.array([10, 2, 0, 0]), reads=np.array([0, 0, 8, 0])),
        _unit(start=16, leaderless=np.array([True, True, False, False]),
              lat_hist=lat1, cmds=np.array([0, 0, 4, 0]),
              violations=np.array([0, 0, 0, 5])),
    ]
    perf = [
        {"wall_s": 9.0, "device_wait_s": 9.0, "warmup": True},  # excluded
        {"wall_s": 1.0, "device_wait_s": 0.2},
        {"wall_s": 1.0, "device_wait_s": 0.4, "recompiled": True},
    ]
    out = sli_mod.compute_slis(spec, units, perf)
    # availability: 3 leaderless cluster-windows of 4 clusters x 2 windows.
    assert out["errs"]["availability"] == 3 / 8
    assert out["slis"]["availability"]["availability"] == pytest.approx(1 - 3 / 8)
    assert out["budgets"]["availability"] == pytest.approx(0.1)
    np.testing.assert_array_equal(
        out["percluster"]["availability"], [2.0, 1.0, 0.0, 0.0]
    )
    # commit latency: 2 of 16 events land past the 16-tick threshold; the
    # fleet p50 clamps to bin 1's lower edge, p95 interpolates into bin 5.
    cl = out["slis"]["commit_latency"]
    assert (cl["measured"], cl["slow"]) == (16, 2)
    assert out["errs"]["commit_latency"] == 2 / 16
    assert cl["p50"] == 2.0
    assert cl["p95"] == pytest.approx(32.0 + (0.95 * 16 - 14) / 2 * 32.0)
    np.testing.assert_array_equal(
        out["percluster"]["commit_latency"], [2.0, 0.0, 0.0, 0.0]
    )
    # read staleness: 3 of 8 reads served at >= 4 ticks.
    assert out["errs"]["read_staleness"] == 3 / 8
    np.testing.assert_array_equal(
        out["percluster"]["read_staleness"], [0.0, 0.0, 3.0, 0.0]
    )
    # throughput: ops [10, 2, 12, 0] -> 12/window under the floor of 100;
    # triage names the clusters BELOW the fleet mean of 6.
    tp = out["slis"]["throughput"]
    assert tp["ops_per_window"] == pytest.approx(12.0)
    assert out["errs"]["throughput"] == 1.0
    assert out["budgets"]["throughput"] == 0.25
    np.testing.assert_array_equal(
        out["percluster"]["throughput"], [0.0, 4.0, 0.0, 6.0]
    )
    # safety: any violation is a budget-0 page.
    assert out["errs"]["safety"] == 1.0
    assert out["budgets"]["safety"] == 0.0
    np.testing.assert_array_equal(
        out["percluster"]["safety"], [0.0, 0.0, 0.0, 5.0]
    )
    # device-wait share over STEADY rows only: 0.6/2.0 under the 0.5 floor.
    dw = out["slis"]["device_wait"]
    assert dw["share"] == pytest.approx(0.3)
    assert dw["steady_chunks"] == 2
    assert out["errs"]["device_wait"] == 1.0
    assert out["percluster"]["device_wait"] is None
    # recompiles: one steady chunk recompiled -> budget-0 page.
    assert out["slis"]["recompile"]["recompiled_chunks"] == 1
    assert out["errs"]["recompile"] == 1.0


def test_compute_slis_quiet_when_disabled_or_empty():
    """Zero floors disable the binary objectives; empty histograms report
    None percentiles and zero error (no traffic is not an SLO breach)."""
    spec = _spec(objectives={
        "commit_latency": {
            "sli": "commit_latency", "threshold_ticks": 16, "target": 0.99,
        },
        "throughput": {
            "sli": "throughput", "min_ops_per_window": 0, "budget": 0.25,
        },
        "device_wait": {
            "sli": "device_wait_share", "min_share": 0.0, "budget": 0.25,
        },
    })
    out = sli_mod.compute_slis(spec, [_unit()], [])
    assert out["errs"] == {
        "commit_latency": 0.0, "throughput": 0.0, "device_wait": 0.0,
    }
    assert out["slis"]["commit_latency"]["p99"] is None
    assert out["slis"]["device_wait"]["share"] is None


# ------------------------------------------------------------- burn rates


def test_burn_rate_budget_zero():
    assert burn_mod.burn_rate(0.0, 0.0) == 0.0
    assert burn_mod.burn_rate(1e-9, 0.0) == burn_mod.BURN_INF
    assert burn_mod.burn_rate(0.5, 0.1) == pytest.approx(5.0)


def test_burn_clean_stream_stays_ok():
    eng = BurnEngine(_spec())
    for _ in range(10):
        assert eng.update({"availability": 0.0}, {"availability": 0.1}) == []
    assert eng.status() == "ok"
    assert eng.firing() == []


def test_burn_burst_fires_then_resolves():
    """ok -> pending on the first met eval, firing on the 2nd (default
    pending_evals=1), resolved after resolve_evals clean evals."""
    eng = BurnEngine(_spec())
    t0 = eng.update({"availability": 1.0}, {"availability": 0.1})
    assert [tr["state"] for tr in t0] == ["pending"]
    assert t0[0]["burn_short"] == pytest.approx(10.0)
    t1 = eng.update({"availability": 1.0}, {"availability": 0.1})
    assert [tr["state"] for tr in t1] == ["firing"]
    assert eng.status() == "firing"
    assert eng.firing() == [("availability", "fast")]
    # Recovery: 2 clean evals (resolve_evals=2) -> resolved, reads as ok.
    assert eng.update({"availability": 0.0}, {"availability": 0.1}) == []
    t3 = eng.update({"availability": 0.0}, {"availability": 0.1})
    assert [tr["state"] for tr in t3] == ["resolved"]
    assert eng.status() == "ok"


def test_burn_pending_clears_back_to_ok():
    """A one-eval blip never fires: pending drops straight back to ok when
    the condition clears (the short window is the reset clock)."""
    eng = BurnEngine(_spec())
    eng.update({"availability": 1.0}, {"availability": 0.1})
    t1 = eng.update({"availability": 0.0}, {"availability": 0.1})
    assert [tr["state"] for tr in t1] == ["ok"]
    assert eng.status() == "ok"


def test_burn_safety_pages_immediately():
    """pending_evals=0 (the safety/recompile default) fires on the FIRST met
    eval at infinite burn -- no pending stop."""
    spec = _spec(objectives={"safety": {"sli": "safety", "pending_evals": 0}})
    eng = BurnEngine(spec)
    trs = eng.update({"safety": 1.0}, {"safety": 0.0})
    assert [tr["state"] for tr in trs] == ["firing"]
    assert trs[0]["burn_short"] == burn_mod.BURN_INF
    assert trs[0]["burn_long"] == burn_mod.BURN_INF


def test_burn_long_window_gates_firing():
    """Both windows must burn: a single hot eval after a long clean history
    trips the short window but not the long one, so nothing fires."""
    spec = _spec(rules=[{"name": "slow", "short": 1, "long": 4, "burn": 6.0}])
    eng = BurnEngine(spec)
    for _ in range(4):
        eng.update({"availability": 0.0}, {"availability": 0.1})
    # short burn = 10 >= 6, long burn = (1.0/4)/0.1 = 2.5 < 6: not met.
    assert eng.update({"availability": 1.0}, {"availability": 0.1}) == []
    assert eng.status() == "ok"


# ----------------------------------------------------------------- triage


def test_triage_empty_and_all_clean():
    assert triage_mod.outlier_clusters([], 3, 3.0) == []
    assert triage_mod.outlier_clusters([0.0, 0.0, 0.0], 3, 3.0) == []


def test_triage_single_outlier_clamped():
    out = triage_mod.outlier_clusters([0.0, 0.0, 9.0, 0.0], 3, 3.0)
    assert [w["cluster"] for w in out] == [2]
    assert out[0]["value"] == 9.0
    assert out[0]["outlier"] is True
    assert out[0]["score"] == triage_mod.SCORE_CLAMP  # zero-MAD fleet


def test_triage_ordering_ties_and_worst_k():
    # Scores tie for clusters 0 and 1 -> equal raw values -> lower id first;
    # worst_k=2 drops cluster 2 even though its metric is nonzero.
    out = triage_mod.outlier_clusters([5.0, 5.0, 3.0, 0.0], 2, 3.0)
    assert [w["cluster"] for w in out] == [0, 1]
    # Fleet-wide burn: everyone ~0 score, still named, no outlier label.
    out = triage_mod.outlier_clusters([4.0, 4.0, 4.0, 4.0], 3, 3.0)
    assert [w["cluster"] for w in out] == [0, 1, 2]
    assert not any(w["outlier"] for w in out)


def test_triage_cluster_base_shifts_to_fleet_ids():
    out = triage_mod.outlier_clusters([0.0, 7.0], 3, 3.0, cluster_base=10)
    assert [w["cluster"] for w in out] == [11]


# --------------------------------------------------------------- evidence


def test_window_rows_filter_and_base():
    units = [
        _unit(batch=2, start=0, cmds=np.array([3, 4]),
              leaderless=np.array([True, False])),
        _unit(batch=2, start=16, cmds=np.array([5, 6])),
    ]
    # Clusters are fleet-global ids; this monitor's slice starts at 10 and
    # holds 2 clusters, so cluster 99 is silently out of range.
    rows = evidence_mod.window_rows_for(units, [11, 99], 7, cluster_base=10)
    assert [(r["window"], r["cluster"], r["cmds"]) for r in rows] == [
        (7, 11, 4), (8, 11, 6),
    ]
    assert rows[0]["leaderless"] is False


def test_evidence_bundle_round_trip(tmp_path):
    alert = {
        "eval": 3, "scope": "fleet", "objective": "availability",
        "rule": "fast", "state": "firing", "burn_short": 10.0,
        "burn_long": 8.0, "worst_clusters": [], "evidence": "evidence_0000",
    }
    units = [_unit(cmds=np.array([1, 2, 3, 4]))]
    d = str(tmp_path / "evidence_0000")
    evidence_mod.write_bundle(
        d, alert=alert, objective={"sli": "availability", "target": 0.9},
        window_rows=evidence_mod.window_rows_for(units, [0, 2], 6),
        perf_rows=[{"chunk": 1, "wall_s": 0.5}],
        refs={"seed": 7},
    )
    assert validate_bundle(d) == []
    doc = json.load(open(os.path.join(d, "alert.json")))
    assert doc["schema"] == evidence_mod.EVIDENCE_SCHEMA
    assert doc["refs"] == {"seed": 7}
    assert doc["files"] == ["alert.json", "perf.jsonl", "windows.jsonl"]

    # Negatives: an inventoried file gone missing, a wrong schema, and a
    # windows row with a missing/mistyped field all name the problem.
    os.remove(os.path.join(d, "perf.jsonl"))
    assert any("perf.jsonl missing on disk" in e for e in validate_bundle(d))
    with open(os.path.join(d, "windows.jsonl"), "a") as f:
        f.write(json.dumps({"window": "one"}) + "\n")
    errs = validate_bundle(d)
    assert any("'ticks' missing or non-int" in e for e in errs)
    assert any("leaderless must be bool" in e for e in errs)
    doc["schema"] = "nope"
    with open(os.path.join(d, "alert.json"), "w") as f:
        json.dump(doc, f)
    assert any("schema" in e for e in validate_bundle(d))
    assert validate_bundle(str(tmp_path / "nowhere")) == [
        "nowhere: missing alert.json"
    ]


# ---------------------------------------------------- monitor (synthetic)


def test_monitor_end_to_end_synthetic(tmp_path):
    """Drive one monitor through a full incident on synthetic units: pending
    -> firing (evidence captured through the hook) -> resolved, with every
    stream passing the sink validator and the report renderer."""
    d = str(tmp_path)
    spec = _spec(resolve_evals=1, worst_k=2)
    captured = []

    def capture(alert, clusters):
        captured.append((alert["objective"], list(clusters)))
        return {"flights": {}, "refs": {"seed": 0}}

    mon = HealthMonitor(
        spec, batch=4, writer=HealthWriter(d), scope="fleet", capture=capture,
    )
    sick = _unit(leaderless=np.array([True, True, True, False]))
    mon.observe_units([sick])            # eval 0: pending
    assert mon.status == "pending"
    mon.observe_units([sick])            # eval 1: firing + evidence
    assert mon.status == "firing"
    assert mon.status_line() == (
        "health[fleet] eval 2: firing (availability/fast)"
    )
    mon.observe_units([_unit()])         # eval 2: clean -> resolved
    roll = mon.finalize()
    assert roll == {
        "scope": "fleet", "evals": 3, "status": "ok", "alerts": 3,
        "fired_objectives": ["availability"],
    }
    states = [
        json.loads(l)["state"] for l in open(os.path.join(d, "alerts.jsonl"))
    ]
    assert states == ["pending", "firing", "resolved"]
    # The capture hook saw the triaged culprits (fleet-wide burn: worst_k=2
    # named, lowest ids first) and the bundle landed next to the streams.
    assert captured == [("availability", [0, 1])]
    assert os.path.isdir(os.path.join(d, "evidence_0000"))
    assert telemetry_sink.validate_health_files(d) == []
    # The renderer walks the same directory end to end.
    from tools.metrics_report import report_health

    buf = io.StringIO()
    report_health(d, out=buf)
    text = buf.getvalue()
    assert "scope fleet" in text
    assert "firing" in text and "evidence_0000" in text


def test_monitor_writer_truncates_previous_run(tmp_path):
    """Re-arming health (Session.reset discipline) must not inherit the prior
    run's alerts or evidence -- the writer truncates on construction."""
    d = str(tmp_path)
    spec = _spec(resolve_evals=1)
    mon = HealthMonitor(spec, batch=4, writer=HealthWriter(d), scope="fleet")
    sick = _unit(leaderless=np.ones(4, bool))
    mon.observe_units([sick, sick])
    assert os.path.isdir(os.path.join(d, "evidence_0000"))
    HealthWriter(d)
    assert not os.path.isdir(os.path.join(d, "evidence_0000"))
    assert open(os.path.join(d, "health.jsonl")).read() == ""
    assert open(os.path.join(d, "alerts.jsonl")).read() == ""


def test_validate_health_files_negatives(tmp_path):
    d = str(tmp_path)
    # health.jsonl without alerts.jsonl, a bad status, an eval discontinuity.
    rows = [
        {"eval": 0, "scope": "fleet", "window_start": 0, "windows": 1,
         "ticks": 16, "slis": {}, "burn": {}, "status": "ok"},
        {"eval": 2, "scope": "fleet", "window_start": 16, "windows": 1,
         "ticks": 16, "slis": {}, "burn": {}, "status": "on-fire"},
    ]
    with open(os.path.join(d, "health.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    errs = telemetry_sink.validate_health_files(d)
    assert any("alerts.jsonl missing" in e for e in errs)
    assert any("eval 2 (expected 1)" in e for e in errs)
    assert any("'on-fire'" in e for e in errs)
    # A firing alert must carry evidence; a named dir must exist; an
    # on-disk evidence dir must be named by some alert row.
    alerts = [
        {"eval": 0, "scope": "fleet", "objective": "a", "rule": "fast",
         "state": "firing", "burn_short": 9.0, "burn_long": 9.0,
         "worst_clusters": [], "evidence": None},
        {"eval": 1, "scope": "fleet", "objective": "a", "rule": "fast",
         "state": "resolved", "burn_short": 0.0, "burn_long": 0.0,
         "worst_clusters": [], "evidence": "evidence_0007"},
    ]
    with open(os.path.join(d, "alerts.jsonl"), "w") as f:
        for r in alerts:
            f.write(json.dumps(r) + "\n")
    os.mkdir(os.path.join(d, "evidence_0003"))
    errs = telemetry_sink.validate_health_files(d)
    assert any("firing alert carries no evidence" in e for e in errs)
    assert any("evidence dir evidence_0007 missing" in e for e in errs)
    assert any(
        "evidence_0003: evidence bundle not named" in e for e in errs
    )


def test_monitor_observe_chunk_and_begin_run():
    """The plain-path delta fold: cumulative RunMetrics become per-chunk
    window units, and begin_run() restarts the baseline (run_chunked restarts
    its counters every call) while the absolute tick offset carries on."""
    from raft_sim_tpu.sim import telemetry

    spec = _spec(eval_windows=100)  # never drain: inspect the raw units

    class _Sink:
        directory = None

        def append_health(self, row):
            pass

        def append_alert(self, row):
            pass

    mon = HealthMonitor(spec, batch=2, writer=_Sink(), scope="fleet")

    def metrics(cmds, viol, first):
        return SimpleNamespace(
            violations=np.array(viol), total_cmds=np.array(cmds),
            reads_served=np.zeros(2, np.int64),
            lat_sum=np.zeros(2, np.int64), lat_cnt=np.zeros(2, np.int64),
            lat_hist=np.zeros((2, LAT_HIST_BINS), np.int64),
            read_hist=np.zeros((2, LAT_HIST_BINS), np.int64),
            fsync_lag_sum=np.zeros(2, np.int64),
            fsync_lag_max=np.zeros(2, np.int64),
            first_leader_tick=np.array(first),
        )

    mon.begin_run()
    mon.observe_chunk(16, metrics([5, 0], [0, 0], [3, telemetry.NEVER]))
    mon.observe_chunk(32, metrics([8, 1], [0, 2], [3, 40]))
    mon.begin_run()  # second run(): counters restart from zero
    mon.observe_chunk(16, metrics([2, 2], [0, 0], [3, 40]))
    got = [
        (u["start"], u["ticks"], u["cmds"].tolist(), u["violations"].tolist(),
         u["leaderless"].tolist())
        for u in mon._units
    ]
    assert got == [
        (0, 16, [5, 0], [0, 0], [False, True]),
        (16, 16, [3, 1], [0, 2], [False, False]),
        (32, 16, [2, 2], [0, 0], [False, False]),
    ]


def test_slice_units_are_views():
    from raft_sim_tpu.health.monitor import slice_units

    units = [_unit(cmds=np.arange(4, dtype=np.int64))]

    view = slice_units(units, 1, 3)
    assert view[0]["cmds"].tolist() == [1, 2]
    assert view[0]["start"] == units[0]["start"]
    # A view, not a copy: the serve loop fans one fetch to every tenant.
    units[0]["cmds"][1] = 99
    assert view[0]["cmds"][0] == 99


# ------------------------------------------- bit-exactness (both layouts)


@pytest.mark.parametrize("compact", [False, True], ids=["dense", "compact"])
def test_health_bit_exact_plain_path(tmp_path, compact):
    """A health-armed plain chunked run equals an unarmed one bit-for-bit --
    state AND metrics -- across TWO run() calls (the begin_run epoch seam),
    on both carry layouts."""
    cfg = dataclasses.replace(HCFG, compact_planes=compact)
    a = Session(cfg, batch=HBATCH, seed=3)
    b = Session(cfg, batch=HBATCH, seed=3)
    b.attach_health(directory=str(tmp_path))
    for s in (a, b):
        s.run(HTICKS, chunk=HCHUNK)
        s.run(HCHUNK * 2, chunk=HCHUNK)
    tree_eq(jax.device_get(a.state), jax.device_get(b.state), "state diverged")
    tree_eq(
        jax.device_get(a.metrics), jax.device_get(b.metrics),
        "metrics diverged",
    )
    roll = b.health.finalize()
    assert roll["evals"] >= 1
    assert telemetry_sink.validate_health_files(str(tmp_path)) == []


@pytest.mark.parametrize("compact", [False, True], ids=["dense", "compact"])
def test_health_bit_exact_telemetry_path(tmp_path, compact):
    """Same contract through the windowed telemetry loop: the health-armed
    session's windows.jsonl is byte-identical to the plain session's, and the
    full sink validator (health streams + evidence included) passes."""
    cfg = dataclasses.replace(HCFG, compact_planes=compact)
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    a = Session(cfg, batch=HBATCH, seed=3)
    a.attach_telemetry(da, window=HWINDOW, ring=4)
    b = Session(cfg, batch=HBATCH, seed=3)
    b.attach_telemetry(db, window=HWINDOW, ring=4)
    b.attach_health()
    for s in (a, b):
        s.run(HTICKS, chunk=HCHUNK)
    tree_eq(jax.device_get(a.state), jax.device_get(b.state), "state diverged")
    tree_eq(
        jax.device_get(a.metrics), jax.device_get(b.metrics),
        "metrics diverged",
    )
    wa = open(os.path.join(da, "windows.jsonl")).read()
    wb = open(os.path.join(db, "windows.jsonl")).read()
    assert wa == wb, "telemetry stream diverged under health instrumentation"
    assert json.loads(open(os.path.join(db, "health.jsonl")).readline())
    assert telemetry_sink.validate(db) == []
    # reset() re-arms a truncated health plane (same discipline as the sink).
    b.reset()
    assert open(os.path.join(db, "health.jsonl")).read() == ""
    assert b.health is not None


# ------------------------------------------------------- multichip renderer


def test_report_multichip_renders_v2_and_legacy(tmp_path):
    from tools.metrics_report import report_multichip

    v2 = {
        "schema": "multichip-v2", "n_devices": 2, "n_processes": 1,
        "batch": 8, "ticks": 64, "violations": 0, "match": True,
        "throughput_ticks_per_s": 1234.5, "per_device_bytes_per_tick": 99.0,
        "platform": "cpu", "parity_hash": "ab" * 32,
        "reference_ticks_per_s": 2000.0,
    }
    p1 = tmp_path / "MULTICHIP_r06.json"
    p1.write_text(json.dumps(v2))
    assert telemetry_sink.validate_multichip(str(p1)) == []
    p2 = tmp_path / "MULTICHIP_r01.json"
    p2.write_text(json.dumps({"n_devices": 2, "rc": 0, "ok": True}))
    buf = io.StringIO()
    report_multichip([str(p1), str(p2)], out=buf)
    text = buf.getvalue()
    assert "MATCH" in text
    assert "legacy rc-only stub" in text
    assert "abababab" in text  # parity-hash prefix in the notes
    assert "cpu rows never anchor" in text
