"""Node-axis sharding tier (parallel/nodeshard.py) on the 8-virtual-CPU-device
mesh: one giant cluster partitioned row-wise across devices must be BIT-EXACT
against the unsharded kernel -- final state (via unshard_state), run metrics,
and telemetry window records -- at every mesh shape, with the hot loop's
inter-device traffic limited to the whitelisted collectives
(analysis/jaxpr_audit.check_node_collectives).

Giant-N word-boundary coverage rides along: bitplane packing and quorum
popcounts at N=101 (W=4 words) and N=255 (W=8), including shard-boundary rows
where a device's local node range splits a packed word (N=101 over 8 devices:
nl=13, device 2 owns rows 26..38, crossing the 31/32 word edge)."""

import dataclasses

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig
from raft_sim_tpu.analysis import jaxpr_audit
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.parallel import nodeshard
from raft_sim_tpu.sim import scan, telemetry
from raft_sim_tpu.types import compact_twin
from raft_sim_tpu.utils.config import PRESETS

# The full sharded v1 feature surface in one config: pre-vote, ring
# compaction, client traffic (offer-tick latency plane live), invariants,
# crash + drop churn. N=33 needs two packed words, so cross-word quorum
# popcounts are exercised, and 33 % 8 != 0 so pad rows exist on the mesh.
FEATURED_33 = RaftConfig(
    n_nodes=33,
    log_capacity=24,
    compact_margin=8,
    pre_vote=True,
    client_interval=5,
    drop_prob=0.1,
    crash_prob=0.1,
    crash_period=32,
    crash_down_ticks=8,
)


def _assert_tree_equal(a, b, tag=""):
    la, lb = jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(jax.device_get(b))
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(x, y, err_msg=f"{tag}[{i}]")


def _assert_parity(cfg, seed, batch, ticks, mesh):
    fs, ms = nodeshard.simulate_node_sharded(cfg, seed, batch, ticks, mesh)
    fd, md = scan.simulate(compact_twin(cfg, False), seed, batch, ticks)
    _assert_tree_equal(ms, md, "metrics")
    _assert_tree_equal(nodeshard.unshard_state(cfg, fs), fd, "state")
    return md


def test_parity_n5_eight_shards():
    """N=5 over 8 node shards: more devices than live rows after padding
    (n_pad=8, nl=1 -- every device holds exactly one row, three of them pad)."""
    cfg = RaftConfig(n_nodes=5, client_interval=8, drop_prob=0.1)
    md = _assert_parity(cfg, 3, 8, 120, nodeshard.make_node_mesh(8))
    assert int(np.max(np.asarray(jax.device_get(md).max_commit))) > 0


def test_parity_n33_featured():
    """The full v1 surface at N=33 (two packed words, pad rows on-mesh)."""
    md = _assert_parity(FEATURED_33, 7, 4, 150, nodeshard.make_node_mesh(8))
    assert int(np.max(np.asarray(jax.device_get(md).max_commit))) > 0


@pytest.mark.slow
def test_windowed_parity_n33():
    """Telemetry window records -- per-window metrics AND first_viol_tick --
    are bit-identical to the unsharded simulate_windowed. Slow tier (the CI
    mesh-smoke job owns this file's slow set): tier-1 keeps the scan-path
    n33 parity row; the windowed wrapper shares the sharded tick kernel."""
    cfg = FEATURED_33
    mesh = nodeshard.make_node_mesh(8)
    fs, ms, recs = nodeshard.simulate_node_sharded_windowed(
        cfg, 7, 4, 120, 30, mesh
    )
    fd, md, recd, _ = telemetry.simulate_windowed(cfg, 7, 4, 120, 30)
    _assert_tree_equal(ms, md, "metrics")
    _assert_tree_equal(recs, recd, "records")
    _assert_tree_equal(nodeshard.unshard_state(cfg, fs), fd, "state")


@pytest.mark.slow
def test_device_count_invariance():
    """2, 4, and 8 node shards produce identical trajectories (padding differs
    per count; the padded rows must be inert at every width). Slow tier: the
    widest mesh (8 shards, 3 pad rows) stays tier-1 via
    test_parity_n5_eight_shards; CI mesh-smoke re-proves the sweep every PR."""
    cfg = RaftConfig(n_nodes=5, client_interval=8, drop_prob=0.1)
    _, md = scan.simulate(cfg, 3, 8, 120)
    for d in (2, 4):
        _, ms = nodeshard.simulate_node_sharded(
            cfg, 3, 8, 120, nodeshard.make_node_mesh(d)
        )
        _assert_tree_equal(ms, md, f"{d}dev")


def test_two_dim_mesh():
    """Batch over "clusters" x nodes over "nodes" at once (2 x 4 devices)."""
    cfg = RaftConfig(n_nodes=5, client_interval=8, drop_prob=0.1)
    mesh = nodeshard.make_node_mesh(4, n_cluster_shards=2)
    _, ms = nodeshard.simulate_node_sharded(cfg, 3, 8, 120, mesh)
    _, md = scan.simulate(cfg, 3, 8, 120)
    _assert_tree_equal(ms, md, "2d")


def test_collective_whitelist():
    """The acceptance assert: the node-sharded config7 program's only
    inter-device primitives are the mailbox/invariant all_gathers and the
    metric psum/pmin/pmax folds (lowering only -- no compile)."""
    cfg, _ = PRESETS["config7"]
    findings = jaxpr_audit.check_node_collectives(
        "config7", cfg, nodeshard.make_node_mesh(8)
    )
    assert findings == [], [f.message for f in findings]
    # And the whitelisted kinds actually appear: the gather + folds exist.
    import jax.numpy as jnp

    closed = jax.make_jaxpr(
        lambda s: nodeshard.simulate_node_sharded(
            cfg, s, 8, 16, nodeshard.make_node_mesh(8)
        )
    )(jax.ShapeDtypeStruct((), jnp.int32))
    seen = {
        e.primitive.name
        for e in jaxpr_audit.iter_eqns(closed.jaxpr)
        if e.primitive.name in jaxpr_audit.NODE_COLLECTIVE_KINDS
    }
    assert "all_gather" in seen and "psum" in seen


@pytest.mark.slow
def test_parity_config7_smoke():
    """The giant-N acceptance smoke: the config7 preset (N=101, W=4) sharded
    over 8 devices, bit-exact vs unsharded, with commits advancing."""
    cfg, _ = PRESETS["config7"]
    md = _assert_parity(cfg, 3, 2, 60, nodeshard.make_node_mesh(8))
    assert int(np.max(np.asarray(jax.device_get(md).max_commit))) > 0


@pytest.mark.slow
def test_parity_config7x_smoke():
    """N=255 ceiling (W=8, int16 node ids): the sharded program runs the
    dense twin of the compacted preset; parity is against that twin."""
    cfg, _ = PRESETS["config7x"]
    md = _assert_parity(cfg, 3, 2, 60, nodeshard.make_node_mesh(8))
    assert int(np.max(np.asarray(jax.device_get(md).max_commit))) > 0


# ------------------------------------------------------- guards / error paths


def test_rejects_unsupported_features():
    for kw in (
        {"reconfig_interval": 10},
        {"transfer_interval": 10},
        {"read_interval": 4},
        {"client_redirect": True},
        {"check_log_matching": True},
    ):
        cfg = RaftConfig(n_nodes=9, log_capacity=64, **kw)
        with pytest.raises(ValueError, match="node sharding does not support"):
            nodeshard.check_shardable(cfg, 8)


def test_rejects_word_crossing_padding():
    """A shard count that pushes n_pad across a 32-bit word boundary must be
    rejected, not silently relayout the bitplanes (N=96 over 7 shards pads to
    98 -> 4 words vs 3)."""
    with pytest.raises(ValueError, match="word boundary"):
        nodeshard.check_shardable(RaftConfig(n_nodes=96), 7)


def test_rejects_indivisible_batch():
    mesh = nodeshard.make_node_mesh(4, n_cluster_shards=2)
    with pytest.raises(ValueError, match="batch"):
        nodeshard.simulate_node_sharded(RaftConfig(n_nodes=5), 0, 3, 10, mesh)


# ------------------------------------- giant-N word-boundary coverage (W=4/8)


@pytest.mark.parametrize("n", [101, 255])
def test_bitplane_roundtrip_giant(n):
    """pack/unpack round-trips and popcounts at W=4 (N=101) and W=8 (N=255),
    bits landing on every word including the partial last word."""
    rng = np.random.default_rng(n)
    rows = 16
    dense = rng.integers(0, 2, size=(rows, n)).astype(bool)
    packed = jax.device_get(bitplane.pack(np.asarray(dense), axis=1))
    assert packed.shape == (rows, bitplane.n_words(n))
    back = jax.device_get(bitplane.unpack(packed, n, axis=1))
    np.testing.assert_array_equal(back.astype(bool), dense)
    counts = jax.device_get(bitplane.count(packed, axis=1))
    np.testing.assert_array_equal(counts, dense.sum(axis=1).astype(np.int32))


@pytest.mark.parametrize("n,n_dev", [(101, 8), (255, 3)])
def test_shard_boundary_rows_split_packed_word(n, n_dev):
    """The local row ranges of a giant-N shard split packed words (N=101 over
    8: nl=13, device 2 owns rows 26..38 across the 31/32 edge; N=255 over 3:
    nl=85 crosses word edges on every device -- legal because n_pad=255 keeps
    W=8, shard counts need not be powers of two). Slicing rows and popcounting
    votes drawn per-row must agree with the dense counts."""
    n_pad = nodeshard.check_shardable(RaftConfig(n_nodes=n), n_dev)
    nl = n_pad // n_dev
    # At least one device's [row0, row0+nl) range must straddle a word edge.
    straddles = [
        d for d in range(n_dev)
        if (d * nl) // 32 != min(((d + 1) * nl - 1) // 32, (n - 1) // 32)
    ]
    assert straddles, f"no shard straddles a word edge at N={n}, D={n_dev}"
    rng = np.random.default_rng(n)
    votes_dense = rng.integers(0, 2, size=(n_pad, n)).astype(bool)
    votes_dense[n:] = False  # pad voters never vote
    packed = np.asarray(jax.device_get(bitplane.pack(votes_dense, axis=1)))
    for d in straddles:
        row0 = d * nl
        local = packed[row0:row0 + nl]
        counts = jax.device_get(bitplane.count(local, axis=1))
        np.testing.assert_array_equal(
            counts, votes_dense[row0:row0 + nl].sum(axis=1).astype(np.int32)
        )


@pytest.mark.parametrize("name", ["config7", "config7x"])
def test_giant_preset_quorum_forms(name):
    """config7 (CAP < N) must take the threshold-quorum form and config7x the
    int16 node-id tier -- the structural gates the giant presets exist to
    cover (types.node_dtype, the phase-5 quorum fork)."""
    from raft_sim_tpu import types as rst_types

    cfg, _ = PRESETS[name]
    assert cfg.log_capacity < cfg.n_nodes
    want = np.int8 if cfg.n_nodes <= rst_types.MAX_INT8_NODES else np.int16
    assert rst_types.node_dtype(cfg) == want


@pytest.mark.slow
def test_compile_count_pin_full_matrix():
    """Tier-1's compile-count pin (tests/test_golden_jaxpr.py) sweeps the
    standing presets only -- the giant-N tiers pay ~11s of N=101/255 tracing
    per run. This slow row re-runs the pin over the FULL preset matrix
    including config7/config7x, so a giant-tier lowering fork still fails in
    CI (the mesh-smoke job runs this file's slow set every PR)."""
    import json
    import os

    from raft_sim_tpu.analysis import jaxpr_audit as JA

    hist = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "golden_jaxpr_hist.json")
    with open(hist) as f:
        pins = json.load(f)["lowerings"]
    families = {
        "step": JA.step_jaxpr,
        "scan": JA.scan_jaxpr,
        "scenario_scan": JA.scenario_scan_jaxpr,
        "serve_scan": lambda c: JA.serve_scan_jaxpr(JA.serve_variant(c)),
        "trace_scan": lambda c: JA.trace_scan_jaxpr(JA.trace_variant(c)),
    }
    for fam, fn in families.items():
        hashes = {JA.program_hash(fn(cfg)) for cfg, _ in PRESETS.values()}
        assert len(hashes) <= pins[fam], (
            f"{fam}: {len(hashes)} distinct lowerings across the full preset "
            f"matrix (pinned {pins[fam]}): a config that should share a "
            "program now forks one -- see golden_jaxpr_hist.json 'lowerings'"
        )


def test_pad_tables_cover_every_leaf():
    """A new state/mailbox/input leg must get a pad rule before the sharded
    path can run it (the import-time asserts, restated as a test)."""
    from raft_sim_tpu.types import ClusterState, Mailbox, StepInputs

    assert set(nodeshard._STATE_PAD) | {"mailbox"} == set(ClusterState._fields)
    assert set(nodeshard._MAILBOX_PAD) == set(Mailbox._fields)
    assert set(nodeshard._INPUT_PAD) == set(StepInputs._fields)


@pytest.mark.slow
def test_compact_twin_routing():
    """compact_planes presets run the sharded carry DENSE: same metrics as
    both the dense twin and the compacted single-chip run. (Slow tier: the
    config7x smoke above also exercises this routing at N=255.)"""
    cfg = dataclasses.replace(FEATURED_33, compact_planes=True)
    mesh = nodeshard.make_node_mesh(8)
    _, ms = nodeshard.simulate_node_sharded(cfg, 7, 4, 100, mesh)
    _, md = scan.simulate(compact_twin(cfg, False), 7, 4, 100)
    _assert_tree_equal(ms, md, "compact-twin")
