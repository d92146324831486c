"""Pass C's own tests: the derived cost model must agree with the eval_shape
hand pricing it replaced (the 1% acceptance bound), the golden file must pin
every audited program and entry point, each cost rule must fire on a seeded
violation (widened carry, materialized float temporary, dropped donation)
and stay silent on the clean tree, and the analyzer itself must fit a pinned
runtime budget so the gate can never eat the 870 s tier-1 budget.

Everything here is lowering/liveness-walk only plus a tiny-shape compile
per donating entry point (the donation probes, shared via
cost_model.donation_audit's cache with the gate) -- no device execution, and every real-program lowering rides the same
`jaxpr_audit` lru_caches the Pass A tests already warm.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from raft_sim_tpu.analysis import cost_model as CM
from raft_sim_tpu.analysis import jaxpr_audit as JA
from raft_sim_tpu.utils.config import PRESETS
from tools import traffic_audit as TA

@functools.lru_cache(maxsize=None)
def _golden():
    # Loaded lazily so a missing/corrupt golden fails only the tests that
    # read it (the gate's cost-golden finding stays the diagnosis elsewhere).
    with open(CM.golden_path()) as f:
        return json.load(f)

# The acceptance tiers: derived-vs-hand agreement is asserted where the
# roofline verdicts live (docs/PERF.md prints configs 3/4/5; config5c is the
# compacted-layout tier whose pin IS the ISSUE-14 bytes/tick verdict, so the
# 1% cross-check covers the packed-leg pricing too).
AGREEMENT_CONFIGS = ("config3", "config4", "config5", "config5c")


# ------------------------------------------- derived vs eval_shape agreement


def test_derived_carry_agrees_with_eval_shape_pricing():
    """ISSUE-5 acceptance: the jaxpr-derived scan-carry bytes/tick and
    traffic_audit's eval_shape leaf pricing agree within 1% on configs
    3/4/5 -- and the derived moving set COVERS the hand-priced set (derived
    superset of hand: a leaf the hand table prices but the lowering does not move
    would mean the policy and the program disagree)."""
    for name in AGREEMENT_CONFIGS:
        cfg, batch = PRESETS[name]
        cm = CM.carry_model(JA.scan_jaxpr(cfg), batch)
        hand = [r for r in TA._leaf_rows(cfg) if r[0] != "inputs"]
        hand_log = sum(2 * TA._logical(s, i) for _, _, s, i in hand)
        hand_pad = sum(2 * TA._padded(s, i, batch) for _, _, s, i in hand)
        assert abs(cm["carry_logical"] - hand_log) <= 0.01 * hand_log, name
        assert abs(cm["carry_padded"] - hand_pad) <= 0.01 * hand_pad, name
        hand_names = {n for _, n, _, _ in hand}
        assert hand_names <= set(cm["moving_legs"]), (
            f"{name}: hand-priced leaves not moving in the lowered scan: "
            f"{hand_names - set(cm['moving_legs'])}"
        )


def test_golden_pin_matches_traffic_audit_table():
    """The gated pin and the docs/PERF.md roofline table are the same model:
    golden config5 bytes/tick == traffic_audit's packed per-cluster-tick
    total (carry + inputs) within 1% -- so the config5 bool-free bound the
    PERF table prints is cross-checked against what CI actually gates."""
    cfg, batch = PRESETS["config5"]
    a = TA.audit(cfg, batch)
    pin = _golden()["programs"]["config5/simulate"]["bytes_per_tick_padded"]
    assert abs(pin - a["packed_padded"]) <= 0.01 * a["packed_padded"]
    assert a["boolfree_padded"] < a["packed_padded"]


def test_derived_rows_are_traffic_audits_primary_source():
    """audit() must price the carry from the derived rows (same totals)."""
    cfg, batch = PRESETS["config5"]
    rows = TA._derived_carry_rows(cfg)
    a = TA.audit(cfg, batch)
    carry_pad = sum(2 * TA._padded(s, i, batch) for _, _, s, i in rows)
    input_pad = sum(
        TA._padded(s, i, batch)
        for g, _, s, i in TA._leaf_rows(cfg) if g == "inputs"
    )
    assert abs(a["packed_padded"] - (carry_pad + input_pad)) < 1.0


# ------------------------------------------------------------- golden pins


def test_golden_pins_every_audited_program():
    """ISSUE-5 acceptance: golden_cost_model.json pins bytes/tick + padded
    footprint + donation status for every audited program."""
    want = {
        f"{c}/{p}"
        for c in JA.AUDIT_CONFIGS
        for p in ("step_b", "simulate", "scenario_simulate",
                  "serve_simulate", "trace_simulate")
    }
    assert set(_golden()["programs"]) == want
    for key, entry in _golden()["programs"].items():
        assert entry["live_peak"] > 0, key
        if key.endswith("simulate"):
            assert entry["carry_padded"] > 0, key
            assert entry["bytes_per_tick_padded"] > entry["carry_padded"], key
            assert entry["moving_legs"], key
    assert set(_golden()["donation"]) == {
        label for label, _, _ in CM.entry_points()
    }
    assert _golden()["donation"]["sim.chunked._chunk_donate"] == "donated"
    # The telemetry soak loop (the documented 10M-tick workflow) holds the
    # same contract: its chunk must donate too, or long runs double-buffer.
    assert _golden()["donation"]["sim.telemetry._chunk_t_donate"] == "donated"
    # ISSUE-6 acceptance: the standing-fleet serve loop never double-buffers
    # the fleet -- its chunk's donation status is pinned.
    assert _golden()["donation"]["serve.loop._serve_chunk"] == "donated"


def test_tree_gates_clean_cost_pass():
    assert CM.run_pass() == []


def test_subset_run_does_not_condemn_other_pins():
    """A --configs subset run must not report the other tiers' pins stale."""
    assert CM.run_pass(config_names=("config3",)) == []


def test_update_golden_preserves_tuned_tolerances(tmp_path):
    """Regenerating the pins must not silently revert a maintainer-tuned
    tolerance to the defaults (docs/ANALYSIS.md: tunable in the golden file);
    untuned keys still land on DEFAULT_TOLERANCE. Rides the process-cached
    derivation, so this costs no extra lowering."""
    path = tmp_path / "golden.json"
    path.write_text(json.dumps({"tolerance": {"live_peak": 0.10}}))
    CM.update_golden(path=str(path))
    doc = json.loads(path.read_text())
    assert doc["tolerance"]["live_peak"] == 0.10
    assert doc["tolerance"]["carry_bytes"] == CM.DEFAULT_TOLERANCE["carry_bytes"]


def test_missing_golden_is_itself_a_finding(tmp_path):
    got = CM.run_pass(
        config_names=("config3",), golden_file=str(tmp_path / "nope.json")
    )
    assert [f.rule for f in got] == ["cost-golden"]
    assert "--update-goldens" in got[0].message


# ------------------------------------------------------- seeded negatives

_N, _B = 6, 4


def _toy_scan(extra_leg=False, float_temp=False):
    """A miniature batch-minor run loop (trailing batch axis, like the real
    scan): two moving carry legs, optionally a third injected [N, N] int32
    leg (the carry-widening seed) or a materialized [N, 64, B] float32
    temporary (the live-peak seed)."""
    a0 = jax.ShapeDtypeStruct((_N, _N, _B), jnp.int8)
    b0 = jax.ShapeDtypeStruct((_N, _B), jnp.int32)
    e0 = jax.ShapeDtypeStruct((_N, _N, _B), jnp.int32)

    def body2(c, _):
        a, b = c
        if float_temp:
            f = b[:, None, :].astype(jnp.float32) * jnp.ones((1, 64, 1), jnp.float32)
            b = b + f.sum(axis=1).astype(jnp.int32)
        else:
            b = b + 1
        return ((a + 1).astype(jnp.int8), b), None

    def body3(c, _):
        a, b, e = c
        return ((a + 1).astype(jnp.int8), b + 1, e + 1), None

    if extra_leg:
        return jax.make_jaxpr(
            lambda a, b, e: lax.scan(body3, (a, b, e), None, length=4)[0]
        )(a0, b0, e0)
    return jax.make_jaxpr(
        lambda a, b: lax.scan(body2, (a, b), None, length=4)[0]
    )(a0, b0)


def _derive_toy(closed):
    peak, temp = CM.live_peak_bytes(closed)
    return {
        "kind": "scan", "live_peak": peak, "temp_bytes": temp,
        **CM.carry_model(closed, batch=_B),
    }


def _pin_toy(derived):
    return {
        "kind": "scan",
        "moving_legs": dict(derived["moving_legs"]),
        "carry_padded": derived["carry_padded"],
        "live_peak": derived["live_peak"],
        "temp_bytes": derived["temp_bytes"],
    }


def test_widened_carry_leg_is_caught():
    """Seeded negative 1: an extra [N, N] int32 plane entering the scan carry
    yields an unwaived cost-carry-bytes finding naming the new leg."""
    pin = _pin_toy(_derive_toy(_toy_scan()))
    widened = _derive_toy(_toy_scan(extra_leg=True))
    got = CM.compare_program(
        "toy/simulate", widened, pin, version_match=True, golden={}
    )
    carry = [f for f in got if f.rule == "cost-carry-bytes"]
    assert carry and not any(f.waived for f in carry)
    assert any("leg2" in f.message and "carry widened" in f.message for f in carry)


def test_float_temporary_is_caught():
    """Seeded negative 2: a materialized float32 temporary in the scan body
    inflates the live-set peak past tolerance -> cost-live-peak."""
    pin = _pin_toy(_derive_toy(_toy_scan()))
    hot = _derive_toy(_toy_scan(float_temp=True))
    assert hot["live_peak"] > pin["live_peak"] * 1.05
    got = CM.compare_program(
        "toy/simulate", hot, pin, version_match=True, golden={}
    )
    assert [f.rule for f in got] == ["cost-live-peak"]
    # ...and the same seed trips Pass A's float-op rule: the two passes fence
    # the same mistake from independent directions.
    assert JA.check_float_ops("jaxpr:toy/simulate", _toy_scan(float_temp=True))


def test_dropped_donation_is_caught():
    """Seeded negative 3: a jit wrapper that lost its donate_argnums lowers
    with zero aliased args -> cost-donation against the 'donated' pin."""
    x = jax.ShapeDtypeStruct((8,), jnp.int32)
    dropped = jax.jit(lambda v: v + 1).lower(x)
    kept = jax.jit(lambda v: v + 1, donate_argnums=(0,)).lower(x)
    assert CM.lowered_donation_status(kept)["status"] == "donated"
    res = CM.lowered_donation_status(dropped)
    assert res["status"] == "not-donated"
    got = CM.compare_donation({"toy.entry": res}, {"toy.entry": "donated"})
    assert [f.rule for f in got] == ["cost-donation"]
    assert "donate_argnums" in got[0].message
    # The kept wrapper matches its pin: no finding.
    assert CM.compare_donation(
        {"toy.entry": CM.lowered_donation_status(kept)}, {"toy.entry": "donated"}
    ) == []


def test_improvement_reports_stale_golden_not_regression():
    """A carry leg that STOPS moving is an improvement: the pin is stale
    (cost-golden), never a cost-carry-bytes regression."""
    base = _derive_toy(_toy_scan())
    pin = _pin_toy(base)
    pin["moving_legs"]["phantom"] = 123.0
    got = CM.compare_program(
        "toy/simulate", base, pin, version_match=True, golden={}
    )
    assert [f.rule for f in got] == ["cost-golden"]
    assert "phantom" in got[0].message


# -------------------------------------------------------- donation audit


def test_entry_point_donation_audit():
    """The real entry points hold their design statuses, and the donating
    chunk is CONFIRMED at the executable level where the backend reports
    memory stats (alias_size_in_bytes > 0), not just marked in the MLIR."""
    audit = dict(CM.donation_audit())
    for label, expected, _ in CM.entry_points():
        assert audit[label]["status"] == expected, label
    donate = audit["sim.chunked._chunk_donate"]
    assert donate["aliased_args"] > 0
    mem = donate["memory_analysis"]
    if mem.get("available"):
        assert mem["alias_size_in_bytes"] > 0


# ------------------------------------------------------------- anchor source


def test_bench_anchor_reads_newest_artifact_and_merges_pins():
    anchors, source, notes = CM.anchor()
    assert source and "BENCH_r" in source
    # Artifact rows win where present; pinned r05 fills truncated gaps.
    assert set(CM.FALLBACK_ANCHOR_R05) <= set(anchors)
    for name, v in anchors.items():
        assert v > 0, name


def test_bench_anchor_falls_back_with_a_note(tmp_path):
    anchors, source, notes = CM.anchor(root=str(tmp_path))
    assert anchors == CM.FALLBACK_ANCHOR_R05
    assert source == "pinned-r05-fallback"
    assert any("falling back" in n for n in notes)
    # A truncated artifact still yields whatever rows survive in its tail.
    (tmp_path / "BENCH_r07.json").write_text(json.dumps({
        "n": 7, "rc": 0, "parsed": None,
        "tail": 'garbage "config5": {"cluster_ticks_per_s": 2500000.0} more',
    }))
    anchors, source, notes = CM.anchor(root=str(tmp_path))
    assert anchors["config5"] == 2500000.0
    assert anchors["config3"] == CM.FALLBACK_ANCHOR_R05["config3"]
    assert "BENCH_r07.json" in source and "pinned r05" in source


def test_bench_anchor_rejects_non_production_batch_rows(tmp_path):
    """A --smoke / custom-batch round saved as the newest artifact must NOT
    rebase the roofline anchor onto its (orders-of-magnitude-off) throughput:
    rows whose `batch` differs from the preset's production batch are dropped
    with a note, and the anchor falls back."""
    (tmp_path / "BENCH_r08.json").write_text(json.dumps({
        "parsed": {"matrix": {
            "config5": {"cluster_ticks_per_s": 9.9e3, "batch": 7},
        }},
    }))
    anchors, source, notes = CM.anchor(root=str(tmp_path))
    assert anchors["config5"] == CM.FALLBACK_ANCHOR_R05["config5"]
    assert source == "pinned-r05-fallback"
    assert any("batch=7" in n and "ignored" in n for n in notes)


def test_bench_anchor_rejects_smoke_rows_at_production_batch(tmp_path):
    """config1's smoke batch equals its production batch, so the batch filter
    alone can't keep a saved --smoke artifact from becoming the anchor: the
    row's `smoke` marker (written by bench) must."""
    (tmp_path / "BENCH_r09.json").write_text(json.dumps({
        "parsed": {"matrix": {
            "config1": {"cluster_ticks_per_s": 123.4, "batch": 1,
                        "smoke": True},
        }},
    }))
    anchors, source, notes = CM.anchor(root=str(tmp_path))
    assert "config1" not in anchors or anchors["config1"] != 123.4
    assert any("--smoke" in n and "ignored" in n for n in notes)


def test_bench_anchor_rejects_cpu_backend_rows(tmp_path):
    """A non-smoke CPU run at production batch (bench >= r06 rows record
    `backend`) must never rebase the roofline anchor onto host-memory
    throughput -- the runtime mirror of obs/reconcile.py's non-anchor
    marking. Backend-less rows (BENCH_r01-r05, all chip-recorded) stay
    eligible."""
    (tmp_path / "BENCH_r10.json").write_text(json.dumps({
        "parsed": {"matrix": {
            "config5": {"cluster_ticks_per_s": 5.5e4, "batch": 10_000,
                        "backend": "cpu"},
            "config4": {"cluster_ticks_per_s": 21.0e6, "batch": 100_000},
        }},
    }))
    anchors, source, notes = CM.anchor(root=str(tmp_path))
    assert anchors["config5"] == CM.FALLBACK_ANCHOR_R05["config5"]
    assert anchors["config4"] == 21.0e6  # backend-less chip row still anchors
    assert any("cpu backend" in n and "ignored" in n for n in notes)


def test_failed_carry_derivation_is_a_visible_finding():
    """A scan-kind entry whose run scan could not be located must fire a
    cost-golden finding, not silently skip every carry/roofline comparison
    (the gate must go red when it stops gating)."""
    derived = {
        "jax_version": "1", "donation": {},
        "programs": {"x/simulate": {
            "kind": "scan", "live_peak": 10, "temp_bytes": 10,
            "error": "no scan found in a scan-kind program",
        }},
    }
    golden = {
        "jax_version": "1", "donation": {},
        "programs": {"x/simulate": {
            "kind": "scan", "live_peak": 10,
            "moving_legs": {"now": 4.0}, "carry_padded": 4.0,
        }},
    }
    got = CM.compare(derived, golden, full=False)
    assert [f.rule for f in got] == ["cost-golden"]
    assert "derivation failed" in got[0].message
    assert "NOT being checked" in got[0].message


def test_padded_bytes_prices_eight_byte_elements():
    """An int64 carry leg (a legal CONCRETE_DTYPES token, live whenever x64 is
    enabled) must be PRICED -- 64-bit lowers as paired 32-bit words on TPU, so
    it tiles like a 4-byte element at twice the bytes -- not crash the whole
    gate with a KeyError on exactly the carry-widening input Pass C exists to
    flag."""
    from raft_sim_tpu.analysis import policy

    assert policy.padded_bytes((6,), 8, 4) == 2 * policy.padded_bytes((6,), 4, 4)
    assert set(policy.SUBLANE) >= {1, 2, 4, 8}


def test_smoke_rows_never_attach_roofline_headroom():
    """config1's smoke batch EQUALS its production batch (1; SMOKE_TICKS is
    what shrinks it), so the batch comparison alone cannot keep a --smoke row
    from carrying chip-anchor-vs-CPU headroom once config1 gains a pin; the
    smoke flag itself must gate the pin."""
    import bench as B

    for name in ("config1", "config3"):
        cfg, prod = PRESETS[name]
        assert B._pin_applies(name, cfg, prod, smoke=False)
        assert not B._pin_applies(name, cfg, prod, smoke=True)
    cfg3 = PRESETS["config3"][0]
    assert not B._pin_applies("config3", cfg3, 64, smoke=False)  # custom batch
    assert not B._pin_applies("custom", cfg3, 64, smoke=False)   # no preset, no pin


def test_version_mismatch_is_a_visible_stale_pin_finding():
    """A jax upgrade disables the live-peak comparison -- that must surface
    as a cost-golden finding, never a gate that silently stays green."""
    derived = {"jax_version": "9.9.9", "programs": {}, "donation": {}}
    golden = {
        "jax_version": "0.0.1",
        "programs": {"x/simulate": {"live_peak": 10}},
        "donation": {},
    }
    got = CM.compare(derived, golden, full=False)
    assert [f.rule for f in got] == ["cost-golden"]
    assert "live-set peak" in got[0].message and "--update-goldens" in got[0].message
    # Same versions, or no live-peak pins at all: no such finding.
    assert CM.compare(
        {"jax_version": "1", "programs": {}, "donation": {}},
        {"jax_version": "1", "programs": {"x/simulate": {"live_peak": 10}},
         "donation": {}},
        full=False,
    ) == []


# ------------------------------------------------------------ runtime budget


def test_cost_pass_runtime_budget():
    """The gate itself is bounded: the whole cost pass (derive all tiers,
    donation probe, golden compare) must stay under 60 s on CPU -- lowering
    and the tiny donation probes only, so it can never crowd the 870 s tier-1
    budget. Earlier tests share the lru-cached lowerings, so this measures
    the warm gate CI actually pays per check.py run."""
    t0 = time.monotonic()
    found = CM.run_pass()
    elapsed = time.monotonic() - t0
    assert found == []
    assert elapsed < 60.0, f"cost pass took {elapsed:.1f}s (budget 60s)"
