"""Reconcile measured runtime against the gated Pass C predictions.

Pass C (analysis/cost_model.py) pins, per config tier x program, the
bytes/cluster-tick of the lowered run loop, the implied HBM rate, the
resulting roofline ticks/s, and the live-set peak -- all *predictions*, gated
in CI. Bench rows and perf.jsonl streams are *measurements*. This module is
the join:

    achieved bytes/s    = measured cluster-ticks/s x pinned bytes/tick
    roofline fraction   = measured / pinned roofline ticks/s
                          (~1.0 = tracking the pins; <1 = headroom the pins
                          say should exist; >1 = the pins are stale --
                          regenerate after the artifact lands)
    live occupancy      = observed device bytes at chunk boundaries vs the
                          pinned live-set peak (the pin is priced at the
                          AUDIT shape, not the production batch -- a trend
                          fence, not an absolute byte budget; see
                          docs/OBSERVABILITY.md)

The load-bearing guard is the **anchor flag**: a reconciled row is
anchor-eligible ONLY when it was measured on a non-CPU backend, at the
preset's production batch, not under --smoke, and not through the scenario
input path. Everything else is explicitly `anchor: false` with the reason
spelled out -- a CPU measurement pass can be *reconciled* (that is its whole
point on this image) but can never *rebase* the roofline, the same trap
class PR 5 closed for smoke rows on the cost-model side
(`cost_model.bench_anchor` enforces the mirror-image rejection when reading
BENCH artifacts).
"""

from __future__ import annotations

import json
import os

from raft_sim_tpu.utils.config import PRESETS


def load_pins(path: str | None = None) -> dict:
    """The golden cost-model document (tests/golden_cost_model.json), or {}
    when absent/unreadable (installed package, fresh clone) -- reconciliation
    then reports measurements only, with a note, instead of failing."""
    if path is None:
        from raft_sim_tpu.analysis import cost_model

        path = cost_model.golden_path()
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _measured(row: dict) -> tuple[float | None, str]:
    """(cluster-ticks/s, source) from a bench row: the warmup-excluded steady
    value when the row carries one (bench >= r06), else the legacy
    best-of-repeats headline (the BENCH_r05 artifact)."""
    v = row.get("steady_ticks_per_s")
    if v:
        return float(v), "steady"
    v = row.get("cluster_ticks_per_s")
    if v:
        return float(v), "legacy-best"
    return None, "missing"


def non_anchor_reasons(config_name: str, row: dict,
                       backend: str | None) -> list[str]:
    """Why this measured row must NOT rebase the roofline ([] = eligible).
    Mirrors (and extends with the backend rule) what
    `cost_model.bench_anchor` rejects when reading BENCH artifacts."""
    reasons = []
    if backend is None:
        reasons.append("backend unrecorded: treated non-anchor (pre-r06 row)")
    elif backend == "cpu":
        reasons.append("cpu backend: a CPU run can never rebase the roofline")
    if row.get("smoke"):
        reasons.append("--smoke row")
    if row.get("scenario"):
        reasons.append(f"scenario input path ({row['scenario']})")
    prod = PRESETS.get(config_name)
    if prod and row.get("batch") is not None and row["batch"] != prod[1]:
        reasons.append(
            f"batch {row['batch']} != production {prod[1]}"
        )
    if prod is not None:
        # Layout keying (the PR 5/PR 8 trap class, closed for layouts): a
        # row measured under one carry layout must never rebase the other
        # layout's roofline -- a compacted A/B row labeled with the dense
        # preset's name (or vice versa) reconciles but cannot anchor.
        # Rows without a layout field (pre-r14) are all dense.
        from raft_sim_tpu.analysis.cost_model import layout_of

        row_layout = row.get("layout") or "dense"
        if row_layout != layout_of(prod[0]):
            reasons.append(
                f"{row_layout} layout row vs the preset's "
                f"{layout_of(prod[0])} layout: a layout A/B row can never "
                "rebase the other layout's roofline"
            )
    # Device-count keying (the same trap class as layouts, closed for the
    # mesh-scaling leg): the pins price per-device bytes/tick, so a row
    # measured across D devices reports aggregate throughput that a
    # single-device roofline must never be rebased onto. Rows without an
    # n_devices field (every pre-mesh artifact) are all single-device.
    if (row.get("n_devices") or 1) != 1:
        reasons.append(
            f"row measured across {row['n_devices']} devices: aggregate "
            "mesh throughput can never rebase the single-device roofline"
        )
    if prod is None:
        reasons.append(f"{config_name!r} is not a preset: no pins to rebase")
    return reasons


def reconcile_row(config_name: str, row: dict, pins: dict,
                  default_backend: str | None = None,
                  observed_live_bytes: int | None = None,
                  program: str = "simulate") -> dict:
    """Join one measured bench row against its config's pinned program
    (`simulate` for the tick matrix; the serve-throughput row passes
    `serve_simulate` so its ticks/s reconcile against the SERVE program's
    bytes/tick -- the offer/read planes and window folds included)."""
    backend = row.get("backend") or default_backend
    measured, source = _measured(row)
    pin = (pins.get("programs") or {}).get(f"{config_name}/{program}") or {}
    notes = []
    out = {
        "config": config_name,
        "backend": backend,
        "measured_ticks_per_s": measured,
        "measured_source": source,
        "repeat_cv": row.get("repeat_cv"),
        "predicted_roofline_ticks_per_s": pin.get("roofline_ticks_per_s"),
        "bytes_per_tick_padded": pin.get("bytes_per_tick_padded"),
        "achieved_bytes_per_s": None,
        "roofline_fraction": None,
        "implied_hbm_bytes_per_s": pin.get("implied_hbm_bytes_per_s"),
        "live_peak_pin": pin.get("live_peak"),
        "observed_live_bytes": observed_live_bytes,
        "live_occupancy_vs_pin": None,
    }
    if source == "legacy-best":
        notes.append(
            "measured from the legacy best-of-repeats field (row carries no "
            "steady stats: pre-r06 artifact)"
        )
    if not pin:
        notes.append(
            f"no cost-model pin for {config_name}/{program}: "
            "measurements only"
        )
    if measured and pin.get("bytes_per_tick_padded"):
        out["achieved_bytes_per_s"] = round(
            measured * pin["bytes_per_tick_padded"], 1
        )
    if measured and pin.get("roofline_ticks_per_s"):
        frac = measured / pin["roofline_ticks_per_s"]
        out["roofline_fraction"] = round(frac, 4)
        if frac > 1.0:
            notes.append(
                "measured above the pinned roofline: the pins are stale -- "
                "regenerate via tools/check.py --update-goldens after this "
                "artifact lands"
            )
    elif measured and pin:
        notes.append(
            "pin carries no roofline (config outside the anchored set): "
            "achieved bytes/s only"
        )
    if observed_live_bytes is not None and pin.get("live_peak"):
        out["live_occupancy_vs_pin"] = round(
            observed_live_bytes / pin["live_peak"], 3
        )
        notes.append(
            "live-peak pin is priced at the audit shape, not the production "
            "batch: occupancy ratio is a trend fence, not a byte budget"
        )
    reasons = non_anchor_reasons(config_name, row, backend)
    out["anchor"] = not reasons
    out["non_anchor_reasons"] = reasons
    out["notes"] = notes
    return out


def reconcile_matrix(doc: dict, pins: dict | None = None,
                     default_backend: str | None = None) -> dict:
    """Reconcile every row of a bench matrix document ({"matrix": {...}},
    i.e. bench.py --out / BENCH_r*.json parsed form) against the pins."""
    if pins is None:
        pins = load_pins()
    notes = []
    if not pins:
        notes.append(
            "golden cost-model pins unavailable: reporting measurements only"
        )
    rows = [
        reconcile_row(name, row, pins, default_backend=default_backend)
        for name, row in sorted((doc.get("matrix") or {}).items())
        if isinstance(row, dict)
    ]
    anchored = [r["config"] for r in rows if r["anchor"]]
    if not anchored:
        notes.append(
            "no anchor-eligible rows: this artifact must not be saved as a "
            "BENCH_r*.json roofline anchor"
        )
    return {
        "pins_jax_version": pins.get("jax_version"),
        "pins_anchor_source": pins.get("anchor_source"),
        "anchor_eligible": anchored,
        "rows": rows,
        "notes": notes,
    }


def _preset_name(config_dict: dict) -> str | None:
    """Match a manifest's full config dict back to a named preset (the pins
    are keyed by preset name)."""
    import dataclasses

    for name, (cfg, _batch) in PRESETS.items():
        if dataclasses.asdict(cfg) == config_dict:
            return name
    return None


def reconcile_perf_dir(directory: str, pins: dict | None = None) -> dict:
    """Reconcile a directory's perf.jsonl stream: steady-state throughput
    recomputed from the rows themselves (not trusted from any summary),
    joined against the directory config's pins. Telemetry directories carry
    a full manifest.json; farm out-dirs (scenario farm / driver sfarm) carry
    farm_manifest.json instead -- their identity (config, population) comes
    from it, and backend/n_devices come from the rows themselves (the farm's
    timer annotates each generation, so a mesh-sharded hunt's aggregate
    throughput is keyed non-anchor like any multi-device row). A CPU perf
    run reconciles but never anchors, either way."""
    import dataclasses as _dc

    from raft_sim_tpu.obs.timer import summarize_rows
    from raft_sim_tpu.utils import telemetry_sink
    from raft_sim_tpu.utils.config import RaftConfig

    rows = read_perf(directory)
    if not rows:
        raise ValueError(f"{directory}: no perf.jsonl rows to reconcile")
    farm_path = os.path.join(directory, "farm_manifest.json")
    if os.path.isfile(os.path.join(directory, "manifest.json")):
        man = telemetry_sink.read_manifest(directory)
        batch = int(man.get("batch", 1))
        label = man.get("source", "run")
        config_dict = man.get("config") or {}
        backend = man.get("backend")
        farm = False
    elif os.path.isfile(farm_path):
        with open(farm_path) as f:
            man = json.load(f)
        batch = int(man.get("population", 1))
        label = "farm"
        # The farm manifest stores only non-default fields (hunt identity);
        # defaults reconstruct the full config for preset matching.
        try:
            config_dict = _dc.asdict(RaftConfig(**(man.get("config") or {})))
        except (TypeError, AssertionError):
            config_dict = {}
        # The mesh is deliberately not part of the farm's hashed identity,
        # so runtime keying comes from the rows (ChunkTimer annotations).
        backend = next(
            (r["backend"] for r in reversed(rows) if r.get("backend")), None
        )
        farm = True
    else:
        raise ValueError(
            f"{directory}: neither manifest.json nor farm_manifest.json -- "
            "not a reconcilable perf directory"
        )
    summary = summarize_rows(rows, label=label, batch=batch)
    name = _preset_name(config_dict)
    n_devices = max(
        (r["n_devices"] for r in rows
         if isinstance(r.get("n_devices"), int)), default=1,
    )
    pseudo = {
        "steady_ticks_per_s": summary["steady_cluster_ticks_per_s"],
        "batch": batch,
        "backend": backend,
        "n_devices": n_devices,
    }
    if pins is None:
        pins = load_pins()
    rec = reconcile_row(
        name or "custom", pseudo, pins, default_backend=backend,
        observed_live_bytes=summary["live_bytes_peak"],
    )
    if name is None:
        rec["notes"].append(
            "manifest config matches no preset: no pins to join against"
        )
    if farm:
        rec["notes"].append(
            "farm out-dir: one row per CE generation (whole-portfolio "
            "evaluations), batch = the portfolio population"
        )
    rec["notes"].append(
        "measured through the chunked loop (per-chunk sync points), not the "
        "monolithic bench program the pin prices: same tick body, slightly "
        "more host traffic -- compare fractions, not absolutes, against "
        "bench rows"
    )
    return {"summary": summary, "reconciliation": rec}


def read_perf(directory: str) -> list[dict]:
    """Load perf.jsonl rows from a telemetry directory ([] when absent)."""
    path = os.path.join(directory, "perf.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]
