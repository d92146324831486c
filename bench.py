"""Benchmark: cluster-ticks/sec/chip across the BASELINE fault matrix.

Prints ONE JSON line. The headline fields {"metric", "value", "unit", "vs_baseline"}
are the north-star workload (config3: 100k x 5-node clusters, randomized election
timeouts; target >=1M cluster-ticks/sec/chip, BASELINE.json `north_star`); the
"matrix" field carries one row per BASELINE config (config1 is the
single-cluster 10k-tick correctness reference with log matching checked every
tick, config2 the 1k-cluster vmap row, 3-5 the throughput/fault rows -- config5
now with sampled log matching on) plus three feature rows: config6 (ring
compaction under crash churn), config6r (the same through the 302-redirect
client write path), and config4c (config4's fault mix under client traffic, so
commit latency is measured UNDER faults). Each row carries throughput AND the
quality metrics (p50 ticks-to-stable-leader, mean-based p50 offer->commit
latency, true per-entry lat_p50/p95/p99 from the on-device histogram,
accepted-command / violation / liveness counters). The reference publishes no
numbers of its own (SURVEY.md section 6).

Every timed repeat uses a fresh time-salted seed (no two repeats share an args
tuple) and is timed to a forced host copy of a per-cluster output (data on the
host proves the program finished).

Without --smoke the bench needs a TPU and exits non-zero on any other backend.

Usage: python bench.py                      # full matrix (TPU-sized)
       python bench.py --smoke              # CPU-sized shrink of the same matrix
       python bench.py --preset config4     # one config only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from raft_sim_tpu import PRESETS, RaftConfig
from raft_sim_tpu.parallel import summarize
from raft_sim_tpu.sim import scan
from raft_sim_tpu.utils.compile_cache import use_compile_cache

NORTH_STAR = 1_000_000.0  # cluster-ticks/sec/chip, BASELINE.json north_star

# config -> ticks per timed call (config5's N=51 tick is ~100x a 5-node tick,
# so its count is the smallest). config1 runs its full
# BASELINE 10k-tick soak (single cluster -- the correctness row, not a
# throughput row). Rows 6/6r exercise the ring-compaction + redirect write
# path, row 4c the config4 fault mix under client traffic, so the standing
# bench carries compaction/redirect throughput and commit latency UNDER faults
# (not only on reliable nets).
MATRIX_TICKS = {
    "config1": 10_000,
    "config9": 500,
    "config2": 2_000,
    "config3": 500,
    "config3p": 500,
    "config4": 300,
    "config4c": 300,
    "config5": 200,
    "config5c": 200,
    "config6": 5_000,
    "config6r": 5_000,
}
SMOKE_BATCH = {
    "config2": 64,
    "config8": 64,
    "config10": 64,
    "config9": 64,
    "config3": 512,
    "config3p": 512,
    "config4": 256,
    "config4c": 256,
    "config5": 16,
    "config5c": 16,
    "config6": 64,
    "config6r": 64,
}
SMOKE_TICKS = {"config1": 1_000, "config6": 1_000, "config6r": 1_000}


def _roofline_pins() -> dict:
    """Predicted per-config rooflines from the gated cost model's pins
    (tests/golden_cost_model.json, regenerated via `tools/check.py
    --update-goldens`): bytes/tick x the pinned implied HBM rate. Read-only
    and fully guarded -- bench must still run where the pins are absent
    (installed package, fresh clone); rows then simply omit the
    predicted-vs-measured fields."""
    try:
        from raft_sim_tpu.analysis import cost_model

        with open(cost_model.golden_path()) as f:
            return json.load(f).get("programs", {})
    except Exception:
        return {}


_ROOFLINE_PINS = _roofline_pins()


def _telemetry_window(ticks: int) -> int:
    """A window size that divides the run (the windowed scan requires it):
    the finest of a few round divisors, falling back to one whole-run window."""
    for d in (16, 10, 8, 5, 4, 2):
        if ticks % d == 0:
            return ticks // d
    return ticks


def _pin_applies(config_name: str, cfg: RaftConfig, batch: int,
                 smoke: bool) -> bool:
    """The pins are priced at the preset's production batch AND its exact
    config; a --smoke row, a custom-batch row, or a config-variant row (e.g.
    the measurement pass's serve_ingest=True arm, whose carry the pin does
    not price) must not carry a headroom number computed against a different
    program's roofline. `smoke` is checked on its own because a preset whose
    smoke batch equals its production batch (config1: batch 1 both ways)
    would otherwise slip through the batch comparison."""
    return (not smoke and config_name in PRESETS
            and batch == PRESETS[config_name][1]
            and cfg == PRESETS[config_name][0])


def bench(cfg: RaftConfig, batch: int, ticks: int, repeats: int = 3,
          quality_seeds: int = 3, telemetry_dir: str | None = None,
          config_name: str = "custom", scenario=None,
          smoke: bool = False) -> dict:
    # `scenario` (a ScenarioProgram) reroutes every run through the
    # scenario-engine input path -- the program's genome broadcast over the
    # fleet -- so the row prices the genome-table reads and the
    # always-traced fault lattice against the scalar path's numbers
    # (docs/PERF.md "scenario path" has the standing verdict).
    if scenario is not None:
        from raft_sim_tpu.scenario import genome as genome_mod

        g = genome_mod.broadcast(scenario.genome, batch)
        seg_len = scenario.seg_len
        sim = lambda seed: scan.simulate_scenario(cfg, seed, batch, ticks, g, seg_len)
    else:
        g = seg_len = None
        sim = lambda seed: scan.simulate(cfg, seed, batch, ticks)
    # Quality runs use FIXED seeds 0..quality_seeds-1 (reproducible across
    # invocations, comparable across commits) and their per-cluster metrics are
    # pooled, so the reported p50s sample quality_seeds x batch clusters instead
    # of one seed's worth. The first doubles as the compile warmup. Timed repeats
    # then use time-salted seeds (capped so seed_base + r stays int32).
    #
    # With telemetry_dir set, the seed-0 quality run goes through the windowed
    # telemetry scan instead and its window records land in
    # telemetry_dir/<config_name>/ under the SAME schema driver.py writes
    # (utils/telemetry_sink.py) -- bit-exact, so the pooled quality metrics are
    # unchanged (tests/test_telemetry.py pins windowed == monolithic).
    pooled = []
    for qs in range(quality_seeds):
        if qs == 0 and telemetry_dir is not None:
            from raft_sim_tpu.sim import telemetry
            from raft_sim_tpu.utils.telemetry_sink import TelemetrySink

            window = _telemetry_window(ticks)
            sink = TelemetrySink(
                os.path.join(telemetry_dir, config_name), cfg, seed=qs,
                batch=batch, window=window, ring=0, source="bench",
            )
            final, m, records, _ = telemetry.simulate_windowed(
                cfg, qs, batch, ticks, window, genome=g,
                seg_len=seg_len if seg_len is not None else 1,
            )
            sink.append_windows(jax.device_get(records))
        else:
            final, m = sim(qs)
        pooled.append(jax.device_get(m))
    q_metrics = type(pooled[0])(
        *(np.concatenate([np.asarray(getattr(m, f)) for m in pooled])
          for f in pooled[0]._fields)
    )

    seed_base = int(time.time_ns() % ((1 << 31) - 1 - repeats))
    walls = []
    for r in range(1, repeats + 1):
        t0 = time.perf_counter()
        final, metrics = sim(seed_base + r)
        # Time to a host copy, not block_until_ready (see module docstring).
        np.asarray(metrics.ticks)
        walls.append(time.perf_counter() - t0)
    best = min(walls)
    # Steady-state stats exclude the FIRST timed repeat: the quality runs
    # already paid the compile, but repeat 1 still carries dispatch/cache
    # warmth (and on some stacks a late autotune) -- reconciliation against
    # the cost-model pins must not be polluted by it (obs/reconcile.py reads
    # steady_ticks_per_s first). With repeats == 1 there is nothing to
    # exclude: the single wall is used and repeat_cv is None (unknowable).
    steady_walls = walls[1:] if len(walls) > 1 else walls
    steady_mean = float(np.mean(steady_walls))
    steady_cv = (
        round(float(np.std(steady_walls) / steady_mean), 4)
        if len(steady_walls) > 1 and steady_mean > 0
        else (0.0 if len(steady_walls) > 1 else None)
    )

    s = summarize(q_metrics)  # pooled fixed-seed quality metrics
    if telemetry_dir is not None:
        # summary.json must describe the SAME run the manifest/windows do
        # (seed 0 alone) -- the pooled 3-seed rollup `s` stays in the bench
        # row, not in the telemetry directory.
        sink.write_summary(summarize(pooled[0])._asdict())
    value = batch * ticks / best
    # Measured throughput vs the PINNED roofline (this program's bytes/tick x
    # the pinned implied HBM rate -- equal to the anchor at pin time by
    # construction, so this is a drift detector against the pins, not a
    # layout-vs-layout bound; those live in tools/traffic_audit.py). ~1.0 =
    # tracking the pins; >1 = slower than pinned (regression, or a non-HBM
    # bottleneck at the pinned rate); <1 = faster than the pins -- they are
    # stale, regenerate after this round's artifact lands.
    pin = _ROOFLINE_PINS.get(f"{config_name}/simulate", {})
    roof = pin.get("roofline_ticks_per_s")
    if not _pin_applies(config_name, cfg, batch, smoke):
        roof = None
    row = {
        # Legacy headline: best wall over ALL timed repeats (including the
        # warmup-adjacent first one) -- the exact definition BENCH_r05
        # recorded, kept byte-compatible so old artifacts stay diffable; the
        # "legacy" marker names it so nothing new reads it by accident.
        "cluster_ticks_per_s": round(value, 1),
        "vs_baseline": round(value / NORTH_STAR, 3),
        "legacy": ["cluster_ticks_per_s", "wall_s", "vs_baseline"],
        # Steady-state throughput: warmup repeat excluded, mean-based (the
        # reconciliation input), with per-repeat variance made visible.
        "steady_ticks_per_s": round(batch * ticks / steady_mean, 1),
        "repeat_walls_s": [round(w, 4) for w in walls],
        "repeat_cv": steady_cv,
        "backend": jax.default_backend(),
        # Carry layout of the benched config (cost_model.layout_of): the
        # anchor/reconcile guards key on this so a compacted-layout row can
        # never silently rebase the dense roofline (or vice versa).
        "layout": "compact" if cfg.compact_planes else "dense",
        "batch": batch,
        "n_nodes": cfg.n_nodes,
        "ticks": ticks,
        "wall_s": round(best, 3),
        "p50_stable_tick": s.p50_stable_tick,
        "pct_stable": round(100.0 * s.n_stable / s.n_clusters, 1),
        "p50_commit_latency": s.p50_commit_latency,
        "lat_p50": s.lat_p50,
        "lat_p95": s.lat_p95,
        "lat_p99": s.lat_p99,
        "lat_excluded": s.lat_excluded,
        "total_cmds": s.total_cmds,
        "violations": s.total_violations,
        "noop_blocked": s.noop_blocked,
        "lm_skipped_pairs": s.lm_skipped_pairs,
        "multi_leader": s.multi_leader,
        "quality_seeds": quality_seeds,
    }
    if smoke:
        # Marked so cost_model.bench_anchor can reject the row even when the
        # preset's smoke batch equals its production batch (config1).
        row["smoke"] = True
    if scenario is not None:
        # Marked HERE, not by the CLI layer: every consumer that must refuse
        # scenario-path throughput (cost_model.bench_anchor, obs/reconcile's
        # anchor flag) keys on this field, so a bench() caller that bypasses
        # main() -- the measurement pass's fault-lattice arm -- must not be
        # able to produce an unmarked scenario row.
        row["scenario"] = getattr(scenario, "name", "scenario")
    if roof and scenario is None:
        row["predicted_roofline_ticks_per_s"] = round(roof, 1)
        row["roofline_headroom"] = round(roof / value, 3)
    return row


def serve_bench(preset: str = "config9", batch: int | None = None,
                chunks: int = 8, chunk: int = 256, window: int = 64,
                tenants_n: int = 4, smoke: bool = False) -> dict:
    """The standing serve-throughput row: a multi-tenant ServeSession under
    saturating synthetic load, measured in COMMANDS+READS per second -- the
    service's unit of work -- never ticks/s (ROADMAP item 2's done-bar).

    Load model: `tenants_n` tenants partition the fleet; every tenant's
    source offers one distinct command per (tick, cluster) slot forever and
    demands more reads than the chunk budget can serve (offered one per
    cluster every other tick), so the session runs write- and
    read-saturated for `chunks` chunks. The row carries the PR 8 steady
    rollup (ChunkTimer) and reconciles against the SERVE program's cost pin
    (`<preset>/serve_simulate` -- obs/reconcile.py), with CPU rows
    explicitly non-anchor."""
    import itertools

    import jax as _jax

    from raft_sim_tpu.obs import ChunkTimer
    from raft_sim_tpu.obs import reconcile as _rec
    from raft_sim_tpu.serve import ServeSession, Tenant

    cfg, preset_batch = PRESETS[preset]
    if batch is None:
        batch = min(preset_batch, 64) if smoke else preset_batch
    if not cfg.read_index:
        raise ValueError(f"serve bench needs a read-carrying preset, "
                         f"got {preset}")
    from raft_sim_tpu.serve.tenancy import split_even

    sizes = split_even(batch, tenants_n)
    counter = itertools.count(1)
    tenants = [
        Tenant(f"t{i}", sizes[i],
               source=(next(counter) for _ in itertools.repeat(0)),
               reads=10**9, read_every=2)
        for i in range(tenants_n)
    ]
    perf = ChunkTimer(label="serve-bench", batch=batch)
    sess = ServeSession(cfg, batch=batch, seed=0, chunk=chunk, window=window,
                        sink=None, warmup_ticks=chunk, perf=perf,
                        tenants=tenants)
    stats = sess.serve(chunks=chunks)
    rollup = stats["perf"]
    wall = stats["wall_s"]
    row = {
        "kind": "serve-throughput",
        "unit": "commands+reads/s",
        "config": preset,
        "backend": _jax.default_backend(),
        "smoke": bool(smoke),
        "batch": batch,
        "tenants": tenants_n,
        "chunk": chunk,
        "window": window,
        "chunks": stats["chunks"],
        "ticks": stats["ticks"],
        "commands_acked": stats["commands_acked"],
        "reads_served": stats["reads_served"],
        "ops_done": stats["ops_done"],
        "ops_per_s": round(stats["ops_done"] / wall, 1) if wall else None,
        "commands_per_s": (
            round(stats["commands_acked"] / wall, 1) if wall else None
        ),
        "reads_per_s": (
            round(stats["reads_served"] / wall, 1) if wall else None
        ),
        "violations": stats["violations"],
        "steady_ticks_per_s": rollup["steady_cluster_ticks_per_s"],
        "perf": rollup,
    }
    row["reconciliation"] = _rec.reconcile_row(
        preset, row, _rec.load_pins(), program="serve_simulate"
    )
    return row


# ---------------------------------------------------------- measurement pass

# Schema tag of the MEASUREMENT_r*.json artifact --measurement-pass writes;
# tools/metrics_report.py --perf refuses documents it does not recognize.
MEASUREMENT_SCHEMA = "measurement-pass-v1"

# config3p rides beside config3 so PreVote's cost is a standing measured
# delta (same N/batch/ticks; the only difference is the pre_vote gate).
# config5c rides beside config5 the same way: the compacted-carry-layout
# twin (ops/tile.py) -- the dense-vs-compacted layout A/B is a standing
# measured delta, priced by the config5c cost pins before any chip run.
MATRIX_CONFIGS = (
    "config1", "config2", "config3", "config3p", "config4", "config4c",
    "config5", "config5c", "config6", "config6r",
)


def _matrix_sizing(name: str, smoke: bool) -> tuple[int, int]:
    """(batch, ticks) for one matrix row under the standard sizing rules."""
    _, preset_batch = PRESETS[name]
    batch = SMOKE_BATCH.get(name, min(preset_batch, 256)) if smoke else preset_batch
    ticks = (
        SMOKE_TICKS[name]
        if smoke and name in SMOKE_TICKS
        else MATRIX_TICKS.get(name, 300)
    )
    return batch, ticks


def _next_measurement_path() -> str:
    """MEASUREMENT_r<N+1>.json where N is the highest round any BENCH_r* or
    MEASUREMENT_r* artifact in the repo root records."""
    import re

    root = os.path.dirname(os.path.abspath(__file__))
    rounds = [0]
    for f in os.listdir(root):
        m = re.fullmatch(r"(?:BENCH|MEASUREMENT)_r(\d+)\.json", f)
        if m:
            rounds.append(int(m.group(1)))
    return os.path.join(root, f"MEASUREMENT_r{max(rounds) + 1:02d}.json")


def _bench_trajectory() -> tuple[list[dict], list[str]]:
    """(per-artifact throughput history, notes): one entry per BENCH_r*.json
    in round order, carrying each recoverable row's legacy headline -- the
    BENCH_r01 -> now line the measurement report draws, with the unmeasured
    tail (rounds after the newest artifact) called out."""
    import re

    from raft_sim_tpu.analysis import cost_model

    root = os.path.dirname(os.path.abspath(__file__))
    entries, notes = [], []
    paths = sorted(
        (f for f in os.listdir(root) if re.fullmatch(r"BENCH_r\d+\.json", f)),
        key=lambda p: int(re.search(r"r(\d+)", p).group(1)),
    )
    for name in paths:
        try:
            with open(os.path.join(root, name)) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as ex:
            notes.append(f"{name}: unreadable ({ex}); skipped")
            continue
        rows = cost_model.bench_matrix(doc)
        entries.append({
            "source": name,
            "round": int(re.search(r"r(\d+)", name).group(1)),
            "ticks_per_s": {
                k: v.get("cluster_ticks_per_s")
                for k, v in sorted(rows.items())
                if isinstance(v, dict)
            },
        })
    if entries:
        newest = entries[-1]["round"]
        notes.append(
            f"newest hardware artifact is round {newest}: every perf claim "
            f"since (bit-packing, fault lattice, serve offer-plane, ...) was "
            "priced by the gated cost model but UNMEASURED on hardware until "
            "a chip measurement pass lands"
        )
    else:
        notes.append("no BENCH_r*.json artifacts found: no trajectory to draw")
    return entries, notes


def _ab_pair(label: str, off_row: dict, on_row: dict, notes: list[str]) -> dict:
    """One A/B arm: both rows plus the steady-state THROUGHPUT ratio
    on/off -- < 1 means the feature costs throughput (e.g. the fault
    lattice's documented +66% CPU wall shows up as ~0.6 here), 1.0 = free,
    > 1 = the feature measured faster (run variance or a real win)."""
    off_v = off_row.get("steady_ticks_per_s") or off_row.get("cluster_ticks_per_s")
    on_v = on_row.get("steady_ticks_per_s") or on_row.get("cluster_ticks_per_s")
    return {
        "label": label,
        "off": off_row,
        "on": on_row,
        "on_over_off_ticks_per_s": (
            round(on_v / off_v, 4) if on_v and off_v else None
        ),
        "notes": notes,
    }


def _mesh_scaling_leg(args, smoke: bool, backend: str) -> dict:
    """Strong-scaling sweep over the cluster mesh: the SAME global batch
    sharded across 1/2/4/8 devices through parallel.simulate_windowed_sharded.
    Trajectories are bit-identical at every width (keys split outside the
    sharded region -- tests/test_farm_mesh.py), so the wall-clock ratio prices
    the mesh partition, not the workload. Every row carries `n_devices`:
    reconciliation and `cost_model.bench_anchor` reject D>1 rows the way they
    reject layout mismatches (aggregate mesh throughput must never rebase the
    single-device roofline), and on CPU every row is non-anchor anyway."""
    from raft_sim_tpu.obs import reconcile
    from raft_sim_tpu.parallel import make_mesh
    from raft_sim_tpu.parallel import mesh as mesh_mod

    name = args.mesh_preset
    cfg, _ = PRESETS[name]
    batch, ticks = _matrix_sizing(name, smoke)
    batch = max(8, batch - batch % 8)  # one global batch, divisible at D=8
    window = max(1, ticks // 4)
    ticks = window * 4
    avail = jax.device_count()
    notes = [
        f"fixed global batch {batch}: strong scaling -- the per-device slice "
        "shrinks with D, the work does not",
        "rows carry n_devices; D>1 rows are structurally non-anchor "
        "(obs/reconcile + cost_model.bench_anchor reject them like layout "
        "mismatches)",
    ]
    rows = {}
    for d in (1, 2, 4, 8):
        if d > avail:
            notes.append(f"{d} devices > {avail} available: skipped")
            continue
        print(f"measurement mesh_scaling {name}: {d} devices...",
              file=sys.stderr)
        mesh = make_mesh(d)
        t0 = time.perf_counter()
        out = mesh_mod.simulate_windowed_sharded(cfg, 0, batch, ticks,
                                                 window, mesh)
        jax.block_until_ready(out[:3])
        compile_s = time.perf_counter() - t0
        walls = []
        for _ in range(max(1, args.repeats)):
            t0 = time.perf_counter()
            out = mesh_mod.simulate_windowed_sharded(cfg, 0, batch, ticks,
                                                     window, mesh)
            jax.block_until_ready(out[:3])
            walls.append(time.perf_counter() - t0)
        best = min(walls)
        row = {
            "n_devices": d,
            "batch": batch,
            "ticks": ticks,
            "window": window,
            "smoke": smoke,
            "backend": backend,
            "compile_s": round(compile_s, 3),
            "wall_s": round(best, 4),
            "cluster_ticks_per_s": round(batch * ticks / best, 1),
            "steady_ticks_per_s": round(batch * ticks / best, 1),
        }
        reasons = reconcile.non_anchor_reasons(name, row, backend)
        row["anchor"] = not reasons
        row["non_anchor_reasons"] = reasons
        rows[f"{d}dev"] = row
    base = (rows.get("1dev") or {}).get("cluster_ticks_per_s")
    speedup = {
        k: round(v["cluster_ticks_per_s"] / base, 3) if base else None
        for k, v in rows.items()
    }
    return {
        "label": f"{name}: one global batch across 1/2/4/8 devices",
        "config": name,
        "rows": rows,
        "speedup_vs_1dev": speedup,
        "notes": notes,
    }


def measurement_pass(args) -> int:
    """The owed measurement pass as ONE command (ISSUE 8 / ROADMAP item 1):
    the standing matrix plus the three unpriced deltas, reconciled against
    the gated cost-model pins, written as a schema'd MEASUREMENT_r*.json.

    The three A/Bs:
      bitpack_vs_r05     measured-now vs the archived BENCH_r05 rows -- bit-
                         packing is STRUCTURAL since checkpoint v18 (there is
                         no dense kernel to toggle back to), so the A/B is
                         longitudinal against the last pre-packing chip
                         artifact; cross-backend ratios are refused.
      fault_lattice      the same preset through the plain input path vs the
                         scenario path under its own config's homogeneous
                         genome (bit-exact trajectories; prices the always-
                         traced fault lattice -- the +66%-on-CPU delta
                         docs/SCENARIOS.md expects to compress on chip).
      serve_offer_plane  the preset vs serve_ingest=True (offer-tick plane
                         legs live but no traffic) -- prices the serve-mode
                         carry traffic_audit --serve projects.

    Plus the transfer-during-joint interaction pair on config8 (ROADMAP
    item 4's named follow-up): homogeneous preset cadences vs a genome that
    forces TimeoutNow transfers into nearly every joint-consensus window;
    both rows reconcile in the standing table, marked scenario/non-anchor.

    Plus the durability pair on config10 (ISSUE 19): the fsync/WAL storage
    plane on (the preset) vs structurally off (fsync_interval=0) -- prices
    the durable-watermark carry, the fsync lattice draws, and the recovery
    lanes; both rows reconcile in the standing table.

    Production sizing needs a TPU (main refuses any other backend);
    --smoke shrinks the pass to CPU sizing, and such rows never anchor.
    """
    backend = jax.default_backend()
    smoke = args.smoke
    configs = (
        [c.strip() for c in args.configs.split(",") if c.strip()]
        if args.configs
        else list(MATRIX_CONFIGS)
    )
    for c in configs:
        if c not in PRESETS:
            raise SystemExit(f"--configs: unknown preset {c!r}")
    ab_preset = args.ab_preset
    if ab_preset not in PRESETS:
        raise SystemExit(f"--ab-preset: unknown preset {ab_preset!r}")
    if args.mesh_preset not in PRESETS:
        raise SystemExit(f"--mesh-preset: unknown preset {args.mesh_preset!r}")

    matrix = {}
    for name in configs:
        batch, ticks = _matrix_sizing(name, smoke)
        print(f"measurement {name}: batch={batch} ticks={ticks}...", file=sys.stderr)
        matrix[name] = bench(
            PRESETS[name][0], batch, ticks, args.repeats,
            config_name=name, smoke=smoke,
        )

    # --- the three unpriced A/Bs ------------------------------------------
    import dataclasses as _dc
    from types import SimpleNamespace

    from raft_sim_tpu.scenario import genome as genome_mod

    ab_cfg = PRESETS[ab_preset][0]
    ab_batch, ab_ticks = _matrix_sizing(ab_preset, smoke)
    if ab_preset in matrix:
        plain = matrix[ab_preset]
    else:
        print(f"measurement A/B baseline {ab_preset}...", file=sys.stderr)
        plain = bench(ab_cfg, ab_batch, ab_ticks, args.repeats,
                      config_name=ab_preset, smoke=smoke)

    print(f"measurement A/B fault lattice ({ab_preset})...", file=sys.stderr)
    lattice = bench(
        ab_cfg, ab_batch, ab_ticks, args.repeats, config_name=ab_preset,
        smoke=smoke,
        scenario=SimpleNamespace(
            genome=genome_mod.from_config(ab_cfg), seg_len=1,
            name="homogeneous-from-config",
        ),
    )
    print(f"measurement A/B serve offer-plane ({ab_preset})...", file=sys.stderr)
    serve_on = bench(
        _dc.replace(ab_cfg, serve_ingest=True), ab_batch, ab_ticks,
        args.repeats, config_name=ab_preset, smoke=smoke,
    )
    # Not the preset's config: say so on the row itself (bench() already
    # refuses to attach the plain preset's roofline pin to it).
    serve_on["config_variant"] = "serve_ingest=True"

    r05_notes = []
    bitpack = {"label": "bitpack_vs_r05", "r05": {}, "measured": {},
               "measured_over_r05": {}, "notes": r05_notes}
    r05_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_r05.json")
    if os.path.isfile(r05_path):
        from raft_sim_tpu.analysis import cost_model

        with open(r05_path) as f:
            r05_rows = cost_model.bench_matrix(json.load(f))
        for name in ("config3", "config4", "config5"):
            old = (r05_rows.get(name) or {}).get("cluster_ticks_per_s")
            new = (matrix.get(name) or {}).get("steady_ticks_per_s")
            bitpack["r05"][name] = old
            bitpack["measured"][name] = new
            if old and new and backend != "cpu" and not smoke:
                bitpack["measured_over_r05"][name] = round(new / old, 4)
        if backend == "cpu" or smoke:
            r05_notes.append(
                "BENCH_r05 rows were measured on chip at production sizing; "
                f"this pass ran backend={backend} smoke={smoke}, so no ratio "
                "is computed -- the bit-packing delta still awaits a chip "
                "session"
            )
        r05_notes.append(
            "bit-packing is structural since checkpoint v18: this A/B is "
            "longitudinal (now vs the last pre-packing artifact), not a "
            "runtime toggle"
        )
    else:
        r05_notes.append("BENCH_r05.json not found: no pre-packing baseline")

    # Dense-vs-compacted layout A/B (ISSUE 14): config5 and its compacted
    # twin config5c run the SAME workload with bit-identical trajectories
    # (tests/test_tile.py), so the throughput ratio prices the node-blocked
    # tiling directly. Both rows ride the standing matrix; the pair is only
    # assembled when both ran (a --configs subset may drop one).
    if "config5" in matrix and "config5c" in matrix:
        layout_ab = _ab_pair(
            "config5: dense vs compacted carry layout (config5c)",
            matrix["config5"], matrix["config5c"],
            ["trajectories are bit-exact across the two arms (the layout is "
             "physical only -- ops/tile.py); the cost pins predict the "
             "compacted arm at ~0.64x the dense bytes/tick on config5 "
             "(tests/golden_cost_model.json config5c/simulate)",
             "neither arm can rebase the OTHER layout's roofline: rows carry "
             "`layout` and the anchor/reconcile guards key on it"],
        )
    else:
        layout_ab = {
            "label": "config5: dense vs compacted carry layout",
            "notes": ["skipped: --configs dropped config5 and/or config5c"],
        }

    # Transfer-during-joint interaction rows (ROADMAP item 4's named
    # follow-up): config8's preset cadences (a membership toggle every 97
    # ticks, a TimeoutNow transfer every 61) overlap a joint-consensus
    # window only occasionally, so the standing rows never price the
    # CONTENDED case -- a transfer in flight during a dual-quorum joint
    # phase (transfer lease refusing client commands + dual majorities +
    # the removed-leader stepdown, all live at once). Both arms run the
    # scenario path so the ratio prices the cadence interaction, not the
    # genome-table reads: the baseline is config8's own homogeneous genome,
    # the interaction arm forces the overlap (toggle every 24 ticks opens
    # joint windows back to back, transfers fire every 5 so nearly every
    # joint phase carries one; faults at config8's own levels).
    print("measurement A/B transfer-during-joint (config8)...", file=sys.stderr)
    xj_cfg = PRESETS["config8"][0]
    xj_batch, xj_ticks = _matrix_sizing("config8", smoke)
    xj_plain = bench(
        xj_cfg, xj_batch, xj_ticks, args.repeats, config_name="config8",
        smoke=smoke,
        scenario=SimpleNamespace(
            genome=genome_mod.from_config(xj_cfg), seg_len=1,
            name="homogeneous-from-config",
        ),
    )
    xj_on = bench(
        xj_cfg, xj_batch, xj_ticks, args.repeats, config_name="config8",
        smoke=smoke,
        scenario=SimpleNamespace(
            genome=genome_mod.from_segments([genome_mod.segment(
                drop_prob=xj_cfg.drop_prob,
                crash_prob=xj_cfg.crash_prob,
                crash_down_ticks=xj_cfg.crash_down_ticks,
                client_interval=xj_cfg.client_interval,
                reconfig_interval=24,
                transfer_interval=5,
                read_interval=xj_cfg.read_interval,
            )]), seg_len=1, name="xfer-joint",
        ),
    )

    # Durability A/B (ISSUE 19): config10's fsync/WAL model vs the SAME
    # preset with the storage plane structurally OFF (fsync_interval=0 and
    # the dependent disk-fault knobs zeroed -- config.py rejects jitter/torn
    # without the gate). The off arm is the zero-cost-when-off claim's priced
    # half: its trajectory is bit-exact vs a pre-plane build (the gated legs
    # are host constants), so the ratio prices the watermark carry + fsync
    # lattice + recovery lanes end to end. Both arms reconcile in the
    # standing table (CPU/smoke rows are non-anchor like every other row).
    print("measurement A/B durability (config10)...", file=sys.stderr)
    dur_cfg = PRESETS["config10"][0]
    dur_batch, dur_ticks = _matrix_sizing("config10", smoke)
    dur_on = bench(
        dur_cfg, dur_batch, dur_ticks, args.repeats, config_name="config10",
        smoke=smoke,
    )
    dur_off = bench(
        _dc.replace(
            dur_cfg, fsync_interval=0, fsync_jitter_prob=0.0,
            torn_tail_prob=0.0, lost_suffix_span=1,
        ),
        dur_batch, dur_ticks, args.repeats, config_name="config10",
        smoke=smoke,
    )
    dur_off["config_variant"] = "fsync_interval=0 (storage plane off)"

    mesh_scaling = _mesh_scaling_leg(args, smoke, backend)

    from raft_sim_tpu.obs import reconcile_matrix

    # The interaction rows reconcile like every standing row (same table,
    # same anchor guards): both carry `scenario`, so neither can ever
    # rebase config8's roofline -- the reconciliation simply reports them.
    reconciliation = reconcile_matrix(
        {"matrix": {
            **matrix,
            "config8": xj_plain,
            "config8/xfer-joint": xj_on,
            "config10": dur_on,
            "config10/durability-off": dur_off,
        }},
        default_backend=backend,
    )
    trajectory, traj_notes = _bench_trajectory()

    doc = {
        "schema": MEASUREMENT_SCHEMA,
        "created_unix": int(time.time()),
        "backend": backend,
        "jax_version": jax.__version__,
        "smoke": smoke,
        "repeats": args.repeats,
        "matrix": matrix,
        "ab": {
            "bitpack_vs_r05": bitpack,
            "fault_lattice": _ab_pair(
                f"{ab_preset}: plain vs scenario-path homogeneous genome",
                plain, lattice,
                ["trajectories are bit-exact across the two arms "
                 "(tests/test_scenario.py pins the homogeneous-genome "
                 "equivalence); the ratio prices the always-traced lattice"],
            ),
            "serve_offer_plane": _ab_pair(
                f"{ab_preset}: plain vs serve_ingest=True (plane legs live, "
                "no offered traffic)",
                plain, serve_on,
                ["prices the v21 offer-tick plane carry the serve mode pays "
                 "(traffic_audit --serve has the static projection)"],
            ),
            "layout_dense_vs_compact": layout_ab,
            "durability": _ab_pair(
                "config10: storage plane off (fsync_interval=0) vs on "
                "(fsync@3 + jitter/torn disk faults)",
                dur_off, dur_on,
                ["the off arm is config10 with the durable-storage gate "
                 "structurally off: the dur watermark legs are carry "
                 "passthroughs and the fsync/recovery lanes compile out "
                 "(tests/test_storage.py pins the disabled-mode goldens "
                 "byte-identical), so the ratio prices the plane itself",
                 "off arm is not the preset's config: the row carries "
                 "config_variant and can never anchor config10's roofline"],
            ),
            "transfer_during_joint": _ab_pair(
                "config8: homogeneous cadences (reconfig@97/transfer@61) vs "
                "forced transfer-during-joint overlap (reconfig@24/"
                "transfer@5)",
                xj_plain, xj_on,
                ["both arms ride the scenario input path, so the ratio "
                 "prices the joint-phase/transfer contention itself "
                 "(dual-quorum counting + transfer lease + stepdown), not "
                 "the genome-table reads",
                 "scenario rows: neither arm can anchor config8's roofline "
                 "(obs/reconcile marks both non-anchor)"],
            ),
        },
        "mesh_scaling": mesh_scaling,
        "reconciliation": reconciliation,
        "trajectory": trajectory,
        "notes": traj_notes,
    }
    out_path = args.out or _next_measurement_path()
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    anchored = ", ".join(reconciliation["anchor_eligible"]) or (
        "NONE (this artifact cannot rebase the roofline)"
    )
    per_cfg = " ".join(
        f"{n}={row.get('steady_ticks_per_s', 0):g}" for n, row in matrix.items()
    )
    print(
        f"measurement pass [{backend}{' smoke' if smoke else ''}]: {per_cfg} | "
        f"anchor-eligible rows: {anchored} | render: "
        f"python tools/metrics_report.py --perf {out_path}"
    )
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=None, choices=sorted(PRESETS),
                    help="bench one config instead of the 3/4/5 matrix")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per row; the first is the warmup "
                         "repeat, excluded from steady_ticks_per_s (default 3)")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized shrink (small batches) of the same matrix")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="also write each config's seed-0 quality run as a "
                         "telemetry directory (DIR/<config>/, the same schema "
                         "driver.py --telemetry-dir emits)")
    ap.add_argument("--scenario", default=None, metavar="FILE",
                    help="run the benched config(s) through the scenario-"
                         "engine input path under this nemesis program "
                         "(prices the genome-table reads; requires --preset)")
    ap.add_argument("--measurement-pass", action="store_true",
                    help="the owed one-command measurement pass (docs/PERF.md "
                         "checklist): standing matrix + the three unpriced "
                         "A/Bs (bit-packing vs r05, fault lattice, serve "
                         "offer-plane) + reconciliation vs the cost-model "
                         "pins, written as MEASUREMENT_r*.json (--out "
                         "overrides the path). CPU rows are marked "
                         "non-anchor")
    ap.add_argument("--configs", default=None, metavar="A,B,...",
                    help="with --measurement-pass: matrix subset (default: "
                         "all standing rows)")
    ap.add_argument("--ab-preset", default="config3", metavar="NAME",
                    help="with --measurement-pass: the preset the fault-"
                         "lattice and serve-plane A/Bs run on (default "
                         "config3, the north-star workload)")
    ap.add_argument("--mesh-preset", default="config3", metavar="NAME",
                    help="with --measurement-pass: the preset the "
                         "mesh_scaling leg strong-scales across 1/2/4/8 "
                         "devices at one fixed global batch (default "
                         "config3; D>1 rows are always non-anchor)")
    ap.add_argument("--serve", action="store_true",
                    help="bench ONLY the standing serve-throughput row "
                         "(commands+reads/s over a saturated multi-tenant "
                         "ServeSession; reconciles against the serve "
                         "program's cost pin). The full matrix run appends "
                         "this row automatically")
    ap.add_argument("--serve-preset", default="config9", metavar="NAME",
                    help="read-carrying preset the serve row runs "
                         "(default config9, the lease-read tier)")
    ap.add_argument("--serve-chunks", type=int, default=8,
                    help="serving chunks of the serve row (default 8)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the FULL matrix JSON to PATH and print only a "
                         "short headline line (north-star ratio + per-config "
                         "ticks/s) to stdout -- so a truncated terminal/log "
                         "capture can never clip the primary perf evidence "
                         "again (VERDICT weak #2); the file is the same "
                         "document cost_model.bench_anchor reads (save it as "
                         "BENCH_r<N>.json to anchor the roofline)")
    args = ap.parse_args()
    use_compile_cache()
    backend = jax.default_backend()
    if not args.smoke and backend != "tpu":
        ap.exit(1, f"bench.py: no TPU found (backend={backend}); production "
                   "sizing runs only on the chip -- use --smoke on CPU\n")

    if args.measurement_pass:
        if args.preset or args.scenario or args.batch or args.ticks:
            ap.error("--measurement-pass runs the standard matrix sizing; it "
                     "is exclusive with --preset/--scenario/--batch/--ticks "
                     "(use --configs/--ab-preset to steer it)")
        sys.exit(measurement_pass(args))

    if args.serve:
        row = serve_bench(args.serve_preset, batch=args.batch,
                          chunks=args.serve_chunks, smoke=args.smoke)
        print(json.dumps(row))
        return

    scenario = None
    if args.scenario:
        if not args.preset:
            ap.error("--scenario requires --preset (one labeled row)")
        from raft_sim_tpu.scenario import program as program_mod

        scenario = program_mod.load(args.scenario, PRESETS[args.preset][0])

    names = (
        [args.preset]
        if args.preset
        else [
            "config1",
            "config2",
            "config3",
            # The standing PreVote row: config3's exact sizing with pre_vote
            # on, so the probe phases' cost is a measured delta every run
            # (docs/PERF.md "PreVote cost"), not prose.
            "config3p",
            "config4",
            "config4c",
            "config5",
            # The standing compacted-layout row: config5's exact workload
            # under the ops/tile.py carry layout (bit-identical
            # trajectories), so the dense-vs-compacted delta is measured
            # beside its baseline every bench run -- the config3p pattern.
            "config5c",
            "config6",
            "config6r",
        ]
    )
    matrix = {}
    for name in names:
        cfg, preset_batch = PRESETS[name]
        smoke_batch = SMOKE_BATCH.get(name, min(preset_batch, 256))
        batch = args.batch or (smoke_batch if args.smoke else preset_batch)
        ticks = args.ticks or (
            SMOKE_TICKS[name]
            if args.smoke and name in SMOKE_TICKS
            else MATRIX_TICKS.get(name, 300)
        )
        print(f"bench {name}: batch={batch} ticks={ticks}...", file=sys.stderr)
        matrix[name] = bench(cfg, batch, ticks, args.repeats,
                             telemetry_dir=args.telemetry_dir, config_name=name,
                             scenario=scenario, smoke=args.smoke)

    if not args.preset:
        # The standing serve-throughput row rides every full-matrix run:
        # ROADMAP item 2's done-bar is commands+reads/s, not ticks/s.
        # bench_anchor ignores it (no cluster_ticks_per_s key): a service
        # row can never rebase the tick roofline.
        print(f"bench {args.serve_preset}-serve: serve-throughput row...",
              file=sys.stderr)
        matrix[f"{args.serve_preset}-serve"] = serve_bench(
            args.serve_preset, chunks=args.serve_chunks, smoke=args.smoke
        )

    # The headline is the north-star workload (config3) whenever it ran; benching a
    # different single preset labels itself via "workload" so vs_baseline is never
    # silently misread as the config3 number.
    headline_name = "config3" if "config3" in matrix else names[0]
    headline = matrix[headline_name]
    doc = {
        "metric": "cluster-ticks/sec/chip",
        "value": headline["cluster_ticks_per_s"],
        "unit": "cluster-ticks/s",
        "vs_baseline": headline["vs_baseline"],
        "workload": headline_name,
        "matrix": matrix,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        per_cfg = " ".join(
            f"{name}={row['cluster_ticks_per_s']:g}"
            if "cluster_ticks_per_s" in row
            else f"{name}={row.get('ops_per_s', 0):g}ops/s"
            for name, row in matrix.items()
        )
        print(
            f"{headline_name} {headline['cluster_ticks_per_s']:g} "
            f"cluster-ticks/s ({headline['vs_baseline']}x north star) | "
            f"{per_cfg} | full matrix: {args.out}"
        )
    else:
        print(json.dumps(doc))


if __name__ == "__main__":
    main()
