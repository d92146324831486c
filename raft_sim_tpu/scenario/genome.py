"""ScenarioGenome: per-cluster batched fault parameters as traced data.

A genome is one point in fault space -- drop rate, rolling-partition period
and probability, crash probability and down-span, clock-skew probability,
client cadence -- encoded so the whole tick stays integer-only (the dtype
policy types.py states and the analyzer enforces): every probability is a
uint32 Bernoulli THRESHOLD (`faults.p_to_u32`; an event fires iff a fresh
uint32 draw is < the threshold), every cadence/span an int32. Each leaf
carries a leading `[S]` segment axis (S = 1 for an unphased genome;
program.py builds S > 1 nemesis timelines); `genome.broadcast` tiles to the
public batched `[B, S]` layout, where row b is cluster b's private fault
setting -- the heterogeneous-fleet form `sim/scan` vmaps over.

The genome deliberately covers only TUNING knobs. Structural config --
topology, log shape, timer windows, the client routing model, feature gates
like pre_vote/compaction -- stays on RaftConfig, because those legitimately
change the compiled program; a genome must never fork a compile
(analysis/jaxpr_audit.py, rule recompile-fork, scenario pairs).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_sim_tpu.sim.faults import p_to_u32
from raft_sim_tpu.utils.config import RaftConfig

U32_SPAN = float(1 << 32)


class ScenarioGenome(NamedTuple):
    """Per-segment fault parameters, `[S]` per leaf (batched: `[B, S]`).

    Field order is load-bearing: `analysis/policy.py:scenario_genome_leaves`
    and the traffic audit enumerate `_fields`, and sim/faults.py duck-types
    the attribute names (sim/ never imports this module)."""

    drop: jax.Array  # [S] uint32: per-edge message-drop threshold
    part_period: jax.Array  # [S] int32: rolling-partition window ticks (0 = off)
    part: jax.Array  # [S] uint32: per-window partition-activation threshold
    crash: jax.Array  # [S] uint32: per-window per-node crash threshold
    crash_down: jax.Array  # [S] int32: max down-span ticks (uniform 1..this)
    skew: jax.Array  # [S] uint32: clock-skew threshold (half stall, half jump)
    client_interval: jax.Array  # [S] int32: client offer cadence (0 = none)
    # Reconfiguration-plane cadences (raft_sim_tpu/reconfig): membership
    # change / leadership transfer / ReadIndex read offers. Tuning knobs like
    # client_interval -- the STRUCTURAL gate stays on RaftConfig
    # (reconfig_interval/transfer_interval/read_interval > 0), the genome
    # retimes commands within it (validate() enforces the pairing).
    reconfig_interval: jax.Array  # [S] int32: membership-toggle cadence (0 = none)
    transfer_interval: jax.Array  # [S] int32: leadership-transfer cadence (0 = none)
    read_interval: jax.Array  # [S] int32: ReadIndex offer cadence (0 = none)
    # Disk-fault axes (raft_sim_tpu/storage): fsync cadence / latency-jitter
    # stalls / torn-tail truncation on restart. Tuning knobs over the durable
    # storage plane -- the STRUCTURAL gate stays on RaftConfig
    # (fsync_interval > 0), the genome retimes flushes and reshapes the crash
    # lattice within it (validate() enforces the pairing).
    fsync_interval: jax.Array  # [S] int32: fsync cadence ticks (0 = plane off)
    fsync_jitter: jax.Array  # [S] uint32: per-node flush-stall threshold
    torn: jax.Array  # [S] uint32: torn-tail-on-restart threshold
    torn_span: jax.Array  # [S] int32: max extra entries a torn tail rejects


# The threshold-encoded (uint32) fields; everything else is int32. The ONE
# source of the dtype partition -- from_segments/from_raw here and the
# analyzer's genome avals (analysis/policy.scenario_genome_leaves,
# jaxpr_audit._genome_avals) all derive from it, so a field add/rename cannot
# silently fork the audited program's dtypes from the real one's.
U32_FIELDS = frozenset({"drop", "part", "crash", "skew", "fsync_jitter", "torn"})


def leaf_dtype(field: str):
    """The genome leaf dtype for a ScenarioGenome field name."""
    return jnp.uint32 if field in U32_FIELDS else jnp.int32


def segment(
    *,
    drop_prob: float = 0.0,
    partition_period: int = 0,
    partition_prob: float = 0.0,
    crash_prob: float = 0.0,
    crash_down_ticks: int = 1,
    clock_skew_prob: float = 0.0,
    client_interval: int = 0,
    reconfig_interval: int = 0,
    transfer_interval: int = 0,
    read_interval: int = 0,
    fsync_interval: int = 0,
    fsync_jitter_prob: float = 0.0,
    torn_tail_prob: float = 0.0,
    lost_suffix_span: int = 1,
) -> dict:
    """One segment's parameters in HUMAN units (probabilities as floats),
    encoded to the genome's integer fields. The declarative scenario-file
    vocabulary (program.py) is exactly these keyword names."""
    return {
        "drop": p_to_u32(drop_prob),
        "part_period": int(partition_period),
        "part": p_to_u32(partition_prob),
        "crash": p_to_u32(crash_prob),
        "crash_down": int(crash_down_ticks),
        "skew": p_to_u32(clock_skew_prob),
        "client_interval": int(client_interval),
        "reconfig_interval": int(reconfig_interval),
        "transfer_interval": int(transfer_interval),
        "read_interval": int(read_interval),
        "fsync_interval": int(fsync_interval),
        "fsync_jitter": p_to_u32(fsync_jitter_prob),
        "torn": p_to_u32(torn_tail_prob),
        "torn_span": int(lost_suffix_span),
    }


def from_segments(segments: list[dict]) -> ScenarioGenome:
    """Stack encoded segment dicts (see `segment`) into an `[S]` genome."""
    if not segments:
        raise ValueError("a genome needs at least one segment")
    return ScenarioGenome(
        **{
            f: jnp.asarray([s[f] for s in segments], leaf_dtype(f))
            for f in ScenarioGenome._fields
        }
    )


def from_config(cfg: RaftConfig) -> ScenarioGenome:
    """The homogeneous genome replicating cfg's fault scalars (S = 1). A
    fleet running this genome is bit-exact with the scalar path for state,
    metrics, and telemetry windows (tests/test_scenario.py) -- the parity
    anchor for everything the search mutates away from."""
    if cfg.drop_prob_uniform:
        raise ValueError(
            "drop_prob_uniform draws a hidden per-cluster rate; genomes "
            "express per-cluster heterogeneity directly -- give each cluster "
            "its own drop threshold instead"
        )
    return from_segments([
        segment(
            drop_prob=cfg.drop_prob,
            partition_period=cfg.partition_period,
            partition_prob=cfg.partition_prob,
            crash_prob=cfg.crash_prob,
            crash_down_ticks=cfg.crash_down_ticks if cfg.crash_prob > 0 else 1,
            clock_skew_prob=cfg.clock_skew_prob,
            client_interval=cfg.client_interval,
            reconfig_interval=cfg.reconfig_interval,
            transfer_interval=cfg.transfer_interval,
            read_interval=cfg.read_interval,
            fsync_interval=cfg.fsync_interval,
            fsync_jitter_prob=cfg.fsync_jitter_prob,
            torn_tail_prob=cfg.torn_tail_prob,
            lost_suffix_span=cfg.lost_suffix_span,
        )
    ])


def broadcast(genome: ScenarioGenome, batch: int) -> ScenarioGenome:
    """Tile an `[S]` genome to the batched `[B, S]` fleet layout (every
    cluster gets the same setting; search.py builds heterogeneous rows)."""
    return ScenarioGenome(
        *(jnp.broadcast_to(leaf[None], (batch,) + leaf.shape) for leaf in genome)
    )


def stack_rows(rows: list[ScenarioGenome]) -> ScenarioGenome:
    """Stack B per-cluster `[S]` genomes into the batched `[B, S]` layout --
    the heterogeneous-fleet constructor (one row per cluster)."""
    return ScenarioGenome(
        *(jnp.stack([getattr(r, f) for r in rows]) for f in ScenarioGenome._fields)
    )


def validate(cfg: RaftConfig, genome: ScenarioGenome) -> None:
    """Host-side sanity for an `[S]` or `[B, S]` genome against its base
    config. Raises ValueError naming the first offense."""
    shapes = {f: np.asarray(getattr(genome, f)).shape for f in genome._fields}
    if len(set(shapes.values())) != 1:
        raise ValueError(f"genome leaves disagree on shape: {shapes}")
    (shape,) = set(shapes.values())
    if len(shape) not in (1, 2) or shape[-1] < 1:
        raise ValueError(f"genome leaves must be [S] or [B, S] with S >= 1, got {shape}")
    pp = np.asarray(genome.part_period)
    if (pp < 0).any():
        raise ValueError("part_period must be >= 0 (0 disables partitions)")
    cd = np.asarray(genome.crash_down)
    if (cd < 1).any() or (cd > cfg.crash_period).any():
        raise ValueError(
            f"crash_down must lie in [1, crash_period={cfg.crash_period}] "
            "(spans clip at the window edge; see faults.alive_at)"
        )
    ci = np.asarray(genome.client_interval)
    if (ci < 0).any():
        raise ValueError("client_interval must be >= 0 (0 disables the client)")
    if (ci > 0).any() and cfg.client_interval == 0:
        raise ValueError(
            "genome injects client traffic but cfg.client_interval == 0: the "
            "step kernel's commit-latency path is a STRUCTURAL gate (it only "
            "compiles in when the config carries a client workload) -- set a "
            "nonzero cfg.client_interval as the base cadence the genome tunes"
        )
    for field, gate, knob in (
        ("reconfig_interval", cfg.reconfig, "reconfig_interval"),
        ("transfer_interval", cfg.leader_transfer, "transfer_interval"),
        ("read_interval", cfg.read_index, "read_interval"),
    ):
        v = np.asarray(getattr(genome, field))
        if (v < 0).any():
            raise ValueError(f"{field} must be >= 0 (0 disables the stream)")
        if (v > 0).any() and not gate:
            raise ValueError(
                f"genome drives {field} but the config's {knob} is 0: the "
                "reconfiguration-plane handlers are STRUCTURAL gates (they "
                "only compile in when the config enables the extension) -- "
                f"set a nonzero cfg.{knob} as the base cadence the genome "
                "tunes (docs/PROTOCOL.md)"
            )
    fi = np.asarray(genome.fsync_interval)
    if (fi < 0).any():
        raise ValueError("fsync_interval must be >= 0 (0 disables fsync)")
    if (fi > 0).any() and not cfg.durable_storage:
        raise ValueError(
            "genome drives fsync_interval but the config's fsync_interval is "
            "0: the durable storage plane is a STRUCTURAL gate (the durable "
            "watermark carry legs and section-3.8 ack/grant gates only "
            "compile in when the config enables it) -- set a nonzero "
            "cfg.fsync_interval as the base cadence the genome tunes "
            "(raft_sim_tpu/storage)"
        )
    for field in ("fsync_jitter", "torn"):
        v = np.asarray(getattr(genome, field))
        if (v > 0).any() and not cfg.durable_storage:
            raise ValueError(
                f"genome sets {field} but the config's fsync_interval is 0: "
                "disk faults perturb the durable storage plane -- set a "
                "nonzero cfg.fsync_interval as the base cadence they perturb"
            )
    ts = np.asarray(genome.torn_span)
    if (ts < 1).any() or (ts > cfg.log_capacity).any():
        raise ValueError(
            f"torn_span must lie in [1, log_capacity={cfg.log_capacity}] "
            "(the torn-tail draw rejects 1..span extra entries; see "
            "faults._storage_draws)"
        )


def decode(genome: ScenarioGenome) -> list[dict]:
    """`[S]` genome -> human-readable per-segment dicts (thresholds back to
    float probabilities), for reports and JSON artifacts."""
    g = {f: np.asarray(getattr(genome, f)) for f in genome._fields}
    (s_count,) = g["drop"].shape
    return [
        {
            "drop_prob": round(float(g["drop"][i]) / U32_SPAN, 9),
            "partition_period": int(g["part_period"][i]),
            "partition_prob": round(float(g["part"][i]) / U32_SPAN, 9),
            "crash_prob": round(float(g["crash"][i]) / U32_SPAN, 9),
            "crash_down_ticks": int(g["crash_down"][i]),
            "clock_skew_prob": round(float(g["skew"][i]) / U32_SPAN, 9),
            "client_interval": int(g["client_interval"][i]),
            "reconfig_interval": int(g["reconfig_interval"][i]),
            "transfer_interval": int(g["transfer_interval"][i]),
            "read_interval": int(g["read_interval"][i]),
            "fsync_interval": int(g["fsync_interval"][i]),
            "fsync_jitter_prob": round(float(g["fsync_jitter"][i]) / U32_SPAN, 9),
            "torn_tail_prob": round(float(g["torn"][i]) / U32_SPAN, 9),
            "lost_suffix_span": int(g["torn_span"][i]),
        }
        for i in range(s_count)
    ]


def to_raw(genome: ScenarioGenome) -> dict:
    """Exact integer leaves as JSON-ready lists -- the bit-exact half of a
    repro artifact (decode() rounds; this does not)."""
    return {f: np.asarray(getattr(genome, f)).tolist() for f in genome._fields}


# The only fields from_raw may backfill when absent, with the value that
# reproduces the old trajectory exactly: pre-v22 artifacts predate the
# reconfiguration-plane cadences and pre-v25 artifacts the disk-fault axes;
# an absent cadence/threshold decodes as its disabled value (0 -- disabled
# streams draw nothing the kernel consumes) and an absent torn_span as the
# no-op span floor 1 (validate() requires span >= 1; with the torn threshold
# 0 it is never consumed). CORE fields stay strict: a missing one is artifact
# corruption and must raise, not silently replay a different scenario.
_OPTIONAL_FIELDS = {
    "reconfig_interval": 0,
    "transfer_interval": 0,
    "read_interval": 0,
    "fsync_interval": 0,
    "fsync_jitter": 0,
    "torn": 0,
    "torn_span": 1,
}


def from_raw(raw: dict) -> ScenarioGenome:
    """Inverse of to_raw: rebuild the exact genome from artifact integers
    (see _OPTIONAL_FIELDS for the pre-v22/pre-v25 compatibility rule)."""
    shape = np.asarray(raw["drop"]).shape
    return ScenarioGenome(
        **{
            f: jnp.asarray(
                raw.get(f, np.full(shape, _OPTIONAL_FIELDS[f], dtype=int).tolist())
                if f in _OPTIONAL_FIELDS
                else raw[f],
                leaf_dtype(f),
            )
            for f in ScenarioGenome._fields
        }
    )
