"""Compacted carry layout (ops/tile.py, cfg.compact_planes): word-boundary
exactness, dense-vs-compacted bit-equality, entry-channel backpressure, and
the layout-keyed anchor guard (ISSUE 14).

The layout is physical only -- the kernel unpacks at tick entry and repacks
at exit -- so the load-bearing claims are (1) pack/unpack is the identity on
every in-range value at every word-boundary N, (2) whole trajectories are
bit-identical between the layouts (states, metrics, StepInfo), including
across the compacted entry channel under truncation-heavy fault churn, and
(3) a bench row measured under one layout can never rebase the other
layout's roofline anchor."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_state
from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import tile
from raft_sim_tpu.sim import faults, scan
from raft_sim_tpu.types import compact_twin
from tests import oracle

# Word-boundary cluster sizes: around the 32-bit word edge (31/32/33), the
# small reference size, and the config5 width (51; 64 rides the slow tier
# with the full-width sim equality).
WORD_NS = (5, 31, 32, 33, 51, 64)


def _leg_cases(cfg):
    """(label, max_value, bits, bias) for every packed leg of `cfg`."""
    cases = [
        ("ack_age", cfg.ack_age_sat, tile.age_bits(cfg), 0),
        ("req_off", cfg.max_entries_per_rpc + 1, tile.off_bits(cfg), 1),
        ("resp_kind", 3, tile.RESP_BITS, 0),
    ]
    if not cfg.compaction:
        cases += [
            ("next_index", cfg.log_capacity + 1, tile.index_bits(cfg), 0),
            ("match_index", cfg.log_capacity, tile.index_bits(cfg), 0),
        ]
    return cases


@pytest.mark.parametrize("n", WORD_NS)
def test_pack_roundtrip_word_boundaries(n):
    """pack_words/unpack_words is the identity on every in-range value for
    every packed leg, at edge counts that straddle word boundaries -- and
    the oracle's independently restated unpacking agrees bit-for-bit."""
    cfg = RaftConfig(n_nodes=min(n, 126), log_capacity=16)
    rng = np.random.default_rng(n)
    for label, vmax, bits, bias in _leg_cases(cfg):
        vals = rng.integers(-bias, vmax + 1, size=(n * n,), dtype=np.int64)
        # Extremes present regardless of the draw: the word-straddle bug
        # class lives at the ends of the range.
        vals[0], vals[-1] = -bias, vmax
        packed = np.asarray(tile.pack_words(
            (vals + bias).astype(np.int32), bits
        ))
        assert packed.shape == (tile.words_for(n * n, bits),), label
        back = np.asarray(
            tile.unpack_words(packed, bits, n * n, np.int32)
        ).astype(np.int64) - bias
        np.testing.assert_array_equal(back, vals, err_msg=f"{label} n={n}")
        # The oracle's restatement (tests/oracle.py unpack_values) must
        # decode the SAME words: the parity suite's comparison domain
        # depends on the two layouts never drifting.
        orc = oracle.unpack_values(packed, bits, n * n) - bias
        np.testing.assert_array_equal(orc, vals, err_msg=f"oracle {label} n={n}")


def test_oracle_bit_width_restatement_pinned():
    """The oracle's independently restated bit widths equal ops/tile.py's
    for every structurally distinct tier (the tests/test_constants.py
    convention: restate, then pin)."""
    for cfg in (
        RaftConfig(),  # cap 32
        RaftConfig(log_capacity=16),
        RaftConfig(log_capacity=2048, client_interval=8),  # int16 index tier
        RaftConfig(ack_timeout_ticks=500),  # wide ack tier
    ):
        assert oracle._bits_for(cfg.log_capacity + 2) == tile.index_bits(cfg)
        assert oracle._bits_for(cfg.ack_age_sat + 1) == tile.age_bits(cfg)
        assert oracle._bits_for(cfg.max_entries_per_rpc + 2) == tile.off_bits(cfg)


def _assert_states_equal(dense_state, compact_state, cfg_c, msg=""):
    du = tile.unpack_state(cfg_c, compact_state)
    for f in dense_state._fields:
        if f == "mailbox":
            for mf in dense_state.mailbox._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(dense_state.mailbox, mf)),
                    np.asarray(getattr(du.mailbox, mf)),
                    err_msg=f"{msg} mb.{mf}",
                )
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(dense_state, f)),
                np.asarray(getattr(du, f)),
                err_msg=f"{msg} {f}",
            )


def _fault_cfg(n, **kw):
    base = dict(
        n_nodes=n,
        log_capacity=8,
        max_entries_per_rpc=2,
        client_interval=2,
        drop_prob=0.25,
        crash_prob=0.4,
        crash_period=16,
        crash_down_ticks=8,
    )
    base.update(kw)
    return RaftConfig(**base)


def _run_both(cfg_d, ticks, seed=0):
    """(dense_state, compact_state, infos_equal) after `ticks` jitted
    raft_batched.step_one ticks from the same seed -- step-level jits keep the
    compile cost far under a scan-shaped program's."""
    cfg_c = compact_twin(cfg_d)
    key = jax.random.key(seed)
    k_init, k_run = jax.random.split(key)
    sd = init_state(cfg_d, k_init)
    sc = init_state(cfg_c, k_init)
    # One jitted step per layout; inputs jitted separately (same draws both
    # layouts except the mask's flat shipping shape). Info equality is
    # asserted at the final tick only -- the per-tick info stream folds into
    # the metrics the batch-minor lockstep test compares in full.
    step_d = jax.jit(lambda s, i: raft_batched.step_one(cfg_d, s, i))
    step_c = jax.jit(lambda s, i: raft_batched.step_one(cfg_c, s, i))
    inp_fn_d = jax.jit(lambda now: faults.make_inputs(cfg_d, k_run, now))
    inp_fn_c = jax.jit(lambda now: faults.make_inputs(cfg_c, k_run, now))
    info_d = info_c = None
    for t in range(ticks):
        sd, info_d = step_d(sd, inp_fn_d(sd.now))
        sc, info_c = step_c(sc, inp_fn_c(sc.now))
    for f in info_d._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(info_d, f)), np.asarray(getattr(info_c, f)),
            err_msg=f"final tick: info.{f}",
        )
    _assert_states_equal(sd, sc, cfg_c, msg=f"after {ticks} ticks")
    return sd, sc


@pytest.mark.slow  # budget re-tier (ISSUE 14): tier-1 already pins the
# compacted layout's sim equality THREE ways for less wall -- the
# n5-compact-crashes ORACLE parity row (per-tick, kernel vs the
# independently restated layout, crashes + truncations), the roundtrip
# property at every word-boundary width, and the backpressure sim below;
# the CI layout-smoke job re-proves the batched full-config5 width
# (scan.simulate dense vs compacted, fault-fuzzed) on every PR.
def test_dense_vs_compact_bitexact():
    """Dense and compacted trajectories are bit-identical (states + final
    StepInfo) under fault churn at the reference width."""
    _run_both(_fault_cfg(5), ticks=80, seed=5)


@pytest.mark.slow  # redundant-with-siblings word widths (the tier-1
# roundtrip property pins the word arithmetic at every boundary width and
# the n=5 row + CI layout smoke pin the sim wiring) -- each param is a
# step-compile pair the 870s tier-1 budget cannot absorb.
@pytest.mark.parametrize("n", [31, 32, 33, 51, 64])
def test_dense_vs_compact_bitexact_wide(n):
    kw = dict()
    if n >= 51:
        kw = dict(log_capacity=16, partition_period=10, partition_prob=0.5,
                  crash_prob=0.0)
    _run_both(_fault_cfg(n, **kw), ticks=30, seed=n)


@pytest.mark.slow  # one extra step-jit pair: the reconfig-plane interaction
# (ent_cfg riding the FLATTENED entry window with its gate LIVE, log-carried
# config toggles + transfers + reads under fault churn) -- the gated-leg
# pack path the tier-1 rows exercise only for ent_tick.
def test_dense_vs_compact_bitexact_reconfig_plane():
    _run_both(
        _fault_cfg(
            5, log_capacity=16, max_entries_per_rpc=4,
            reconfig_interval=11, transfer_interval=13, read_interval=5,
        ),
        ticks=120, seed=99,
    )


@pytest.mark.slow  # two scan-shaped compiles; the same batched-lockstep
# claim is re-proven EVERY PR by the CI layout-smoke job at the full
# config5 width (scan.simulate dense vs compacted, 16x128 fault-fuzzed),
# and the per-tick oracle tier rides tier-1's n5-compact-crashes parity row.
def test_dense_vs_compact_batch_minor_lockstep():
    """The batched kernel's compacted boundary (step_b through
    scan.simulate): dense and compacted batch-minor runs are bit-identical
    in final states AND metrics -- the batched-lockstep tier of the layout
    contract (the per-tick oracle tier rides test_oracle_parity's
    n5-compact-crashes row)."""
    cfg_d = _fault_cfg(5)
    cfg_c = compact_twin(cfg_d)
    fd, md = scan.simulate(cfg_d, 3, 8, 96)
    fc, mc = scan.simulate(cfg_c, 3, 8, 96)
    du = jax.vmap(lambda s: tile.unpack_state(cfg_c, s))(fc)
    for f in fd._fields:
        if f == "mailbox":
            for mf in fd.mailbox._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(fd.mailbox, mf)),
                    np.asarray(getattr(du.mailbox, mf)), err_msg=f"mb.{mf}",
                )
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(fd, f)), np.asarray(getattr(du, f)),
                err_msg=f,
            )
    for f in md._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(md, f)), np.asarray(getattr(mc, f)),
            err_msg=f"metric.{f}",
        )


def test_entry_channel_overflow_is_backpressure_not_loss():
    """E smaller than the outstanding entry backlog saturates the compacted
    entry channel (offsets clamp into the shared window) but loses nothing:
    on a reliable net every accepted command still commits, in order, and
    the dense layout agrees bit-for-bit."""
    cfg_d = RaftConfig(
        n_nodes=3, log_capacity=32, max_entries_per_rpc=2, client_interval=1,
    )
    sd, sc = _run_both(cfg_d, ticks=90)
    cfg_c = compact_twin(cfg_d)
    du = tile.unpack_state(cfg_c, sc)
    # The 1-command-per-tick firehose outruns E=2 replication per RPC; the
    # window start walks forward anyway. All nodes converge on a deep
    # committed prefix: nothing was dropped by channel overflow.
    commit = np.asarray(du.commit_index)
    assert commit.min() >= 20, commit
    lens = np.asarray(du.log_len)
    vals = np.asarray(du.log_val)
    # Committed prefixes agree across nodes (no lost/reordered entries).
    depth = int(commit.min())
    for node in range(1, 3):
        np.testing.assert_array_equal(vals[0, :depth], vals[node, :depth])


def test_init_and_checkpoint_round_trip_compact(tmp_path):
    """init_state builds the packed layout directly; checkpoint save/load
    round-trips the packed leaves bit-for-bit (shapes ride the arrays --
    no schema change, no version bump: the canonical fingerprint config is
    dense)."""
    from raft_sim_tpu.sim.scan import init_metrics_batch
    from raft_sim_tpu.utils import checkpoint

    cfg = compact_twin(_fault_cfg(5))
    key = jax.random.key(1)
    state = jax.vmap(lambda k: init_state(cfg, k))(jax.random.split(key, 2))
    assert state.next_index.ndim == 2  # [B, W]: packed flat per cluster
    path = str(tmp_path / "compact.npz")
    checkpoint.save(path, cfg, state, jax.random.split(key, 2),
                    init_metrics_batch(2), seed=1)
    cfg2, state2, _keys, _metrics, _seed, _scn = checkpoint.load(path)
    assert cfg2 == cfg
    for f in state._fields:
        if f == "mailbox":
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(state, f)), np.asarray(getattr(state2, f)),
            err_msg=f,
        )


# ------------------------------------------------- layout-keyed anchor guard


def test_bench_anchor_rejects_layout_mismatched_rows(tmp_path):
    """A bench row measured under the compacted layout, keyed by a DENSE
    preset's name, must never rebase that preset's roofline anchor (the
    PR 5/PR 8 smoke-row trap class, closed for layouts) -- and vice versa a
    dense row cannot anchor config5c. Rows without a layout field (pre-r14
    artifacts) are dense by definition and still anchor dense presets."""
    import json

    from raft_sim_tpu.analysis import cost_model

    doc = {
        "matrix": {
            # compacted row mislabeled under the dense preset: refused.
            "config5": {"cluster_ticks_per_s": 9e6, "batch": 10_000,
                        "layout": "compact"},
            # dense row under the compacted preset: refused.
            "config5c": {"cluster_ticks_per_s": 8e6, "batch": 10_000,
                         "layout": "dense"},
            # correctly-keyed rows: accepted.
            "config3": {"cluster_ticks_per_s": 40e6, "batch": 100_000},
            "config4": {"cluster_ticks_per_s": 23e6, "batch": 100_000,
                        "layout": "dense"},
        }
    }
    (tmp_path / "BENCH_r99.json").write_text(json.dumps(doc))
    anchors, source, notes = cost_model.bench_anchor(str(tmp_path))
    assert "config5" not in anchors and "config5c" not in anchors
    assert anchors == {"config3": 40e6, "config4": 23e6}
    assert any("config5 row" in n and "layout" in n for n in notes)
    assert any("config5c row" in n and "layout" in n for n in notes)


def test_reconcile_marks_layout_mismatch_non_anchor():
    from raft_sim_tpu.obs import reconcile

    row = {"steady_ticks_per_s": 9e6, "batch": 10_000, "layout": "compact"}
    reasons = reconcile.non_anchor_reasons("config5", row, "tpu")
    assert any("layout" in r for r in reasons)
    # The correctly-keyed compacted row has no layout objection.
    ok = reconcile.non_anchor_reasons("config5c", row, "tpu")
    assert not any("layout" in r for r in ok)
    # Pre-r14 rows (no layout field) are dense: fine for dense presets.
    legacy = {"steady_ticks_per_s": 9e6, "batch": 10_000}
    assert not any(
        "layout" in r for r in reconcile.non_anchor_reasons("config5", legacy, "tpu")
    )


def test_dense_base_twin_resolution():
    from raft_sim_tpu.analysis import cost_model
    from raft_sim_tpu.utils.config import PRESETS

    assert cost_model.dense_base("config5c") == "config5"
    assert cost_model.dense_base("config5") is None
    assert cost_model.layout_of(PRESETS["config5c"][0]) == "compact"
    assert cost_model.layout_of(PRESETS["config5"][0]) == "dense"


def test_compacted_pin_meets_the_roofline_bar():
    """ISSUE-14 acceptance, as a test: the gated pin for config5c/simulate
    prices the compacted config5 tick at <= ~48 KB padded, which the
    r05-implied HBM rate prices at >= 3M cluster-ticks/s (the ROADMAP
    item-1 bar packing alone provably cannot reach -- docs/PERF.md)."""
    import json
    import os

    from raft_sim_tpu.analysis import cost_model

    with open(cost_model.golden_path()) as f:
        golden = json.load(f)
    pin = golden["programs"]["config5c/simulate"]
    dense = golden["programs"]["config5/simulate"]
    assert pin["bytes_per_tick_padded"] <= 48_000, pin
    # At the pinned implied rate (borrowed from the dense base's anchor --
    # `layout_base` records the borrow) the predicted roofline clears 3M.
    assert pin.get("layout_base") == "config5"
    assert pin["roofline_ticks_per_s"] >= 3_000_000, pin
    # And the compacted carry genuinely undercuts the dense pin (not a
    # padding artifact): logical bytes shrink too.
    assert pin["bytes_per_tick_logical"] < dense["bytes_per_tick_logical"]
