"""Pass C: a jaxpr-derived cost model -- the roofline as a gated invariant.

Every perf verdict in docs/PERF.md rests on bytes-per-tick accounting, and
until this pass that accounting was a hand-maintained leaf table in
`tools/traffic_audit.py` plus a hardcoded throughput anchor -- both able to
drift silently from the programs we actually compile. Pass C prices the SAME
closed jaxprs Pass A audits (`jaxpr_audit.programs`: step, step_b, simulate,
scenario_simulate per config tier), equation by equation:

  carry bytes/tick   the scan carry extracted from the lowered run loop
                     itself: every leg's aval, priced logically and
                     TPU-padded (policy.padded_bytes, the batch-minor tiling
                     single-sourced in analysis/policy.py), with
                     identity-passthrough legs (invar IS outvar in the body,
                     the legs XLA elides from the per-tick HBM round trip)
                     derived from the jaxpr instead of declared by hand.
                     `tools/traffic_audit.py` now consumes this as its
                     primary source; its eval_shape leaf table is the
                     cross-check (derived == hand-priced is asserted in
                     tests/test_cost_model.py).
  live-set peak      a linear liveness walk over the program (nested bodies
                     included): the byte-maximum of simultaneously-live
                     values -- an HBM footprint estimate that catches a newly
                     materialized [N, N, B] temporary even when the carry is
                     untouched. Lowering-level, so exact per jax version
                     (compared against the golden only under the recorded
                     version, like the op-histogram snapshots).
  donation           the jitted entry points' buffer aliasing, read from the
                     lowering (`tf.aliasing_output` marks) and confirmed via
                     `lower().compile().memory_analysis()` where the backend
                     supports it: `chunked._chunk_donate` must actually donate
                     the chunk carry; dropping `donate_argnums` is a finding,
                     not a quiet 2x HBM residency regression.
  roofline           bytes/tick x the pinned implied HBM rate -> a ticks/s
                     upper bound per preset. The anchor derives from the
                     newest BENCH_r*.json artifact (`bench_anchor`), falling
                     back to the pinned round-5 chip numbers with a warning,
                     so it follows the bench trajectory instead of rotting.
                     The rate is implied from THIS program's bytes/tick at
                     the anchor throughput, so at pin time the roofline
                     equals the anchor by construction -- the pin is a
                     bytes/tick fence (it moves exactly when the program's
                     traffic does), not a layout-vs-layout bound; the
                     packed-vs-dense / bool-free physical bounds live in
                     tools/traffic_audit.py, which implies its rate from the
                     dense carry the recorded round actually ran.

Everything is pinned in tests/golden_cost_model.json (regenerate after an
INTENDED change: `python tools/check.py --update-goldens`) and gated through
the findings/waiver engine by `tools/check.py --cost`:

  cost-carry-bytes   a new moving carry leg, a widened leg, or a >tolerance
                     bytes/tick regression vs the pin
  cost-live-peak     live-set peak drift beyond tolerance (same jax version)
  cost-donation      an entry point's donation status changed vs the pin
  cost-roofline      the derived ticks/s bound at the pinned HBM rate fell
                     more than tolerance below the pinned bound
  cost-golden        pins out of sync with the tree (missing/stale/improved:
                     regenerate goldens), or an unreadable golden file

Tracing + a tiny-shape compile per donating entry point (the donation
probes) -- no device execution -- so the whole pass stays inside the
analyzer's <60 s CPU budget (pinned in tests/test_cost_model.py).
"""

from __future__ import annotations

import functools
import json
import os
import re

import jax
import jax.numpy as jnp

from raft_sim_tpu.analysis import jaxpr_audit, policy
from raft_sim_tpu.analysis.findings import Finding
from raft_sim_tpu.utils.config import PRESETS, RaftConfig

# Every rule slug this pass can emit (run.run_all scopes stale-waiver
# detection to the passes that actually ran).
RULES = frozenset({
    "cost-carry-bytes", "cost-live-peak", "cost-donation", "cost-roofline",
    "cost-golden", "cost-mesh-bytes",
})

# Drift tolerances (fractions) against the golden pins. The golden file can
# override these under "tolerance"; the defaults are deliberately tight --
# carry bytes are struct-derived and exactly reproducible, so 1% is headroom
# for float rounding, not for regressions.
DEFAULT_TOLERANCE = {"carry_bytes": 0.01, "live_peak": 0.05, "roofline": 0.02}

# Recorded round-5 chip throughput (docs/PERF.md history table): the anchor
# fallback when no BENCH_r*.json artifact is present (fresh clone, installed
# package). Single-sourced here -- tools/traffic_audit.py imports it too.
FALLBACK_ANCHOR_R05 = {
    "config3": 38.1e6,
    "config4": 22.7e6,
    "config5": 2.14e6,
}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def golden_path() -> str:
    return os.path.join(_REPO_ROOT, "tests", "golden_cost_model.json")


# ------------------------------------------------------------- anchor source


def bench_matrix(doc: dict) -> dict:
    """Matrix rows from a bench stdout capture ({n, cmd, rc, tail, parsed}
    wrapper or raw bench.py output). The bench JSON is `parsed` when present,
    else `matrix` at top level, else recovered row-by-row from the
    byte-truncated `tail`. Single-sourced here for bench_anchor and
    tools/metrics_report.py so the two gates can't drift apart."""
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and isinstance(parsed.get("matrix"), dict):
        return dict(parsed["matrix"])
    if isinstance(doc.get("matrix"), dict):  # a raw bench.py stdout capture
        return dict(doc["matrix"])
    dec = json.JSONDecoder()
    tail = doc.get("tail") or ""
    rows = {}
    for mt in re.finditer(r'"(config[A-Za-z0-9_]*)":\s*\{', tail):
        try:
            row, _ = dec.raw_decode(tail[mt.end() - 1:])
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict) and "cluster_ticks_per_s" in row:
            rows[mt.group(1)] = row
    return rows


def bench_anchor(root: str | None = None):
    """(anchors, source, notes): per-config cluster-ticks/s from the NEWEST
    BENCH_r*.json artifact in the repo root. Artifacts are stdout captures
    ({n, cmd, rc, tail, parsed}); rows come from `bench_matrix`. Returns
    ({}, None, notes) when no artifact yields rows -- callers fall back to
    FALLBACK_ANCHOR_R05 (see `anchor()`)."""
    root = root or _REPO_ROOT
    try:
        paths = [f for f in os.listdir(root) if re.fullmatch(r"BENCH_r\d+\.json", f)]
    except OSError as ex:
        return {}, None, [f"{root}: unlistable: {ex}"]
    if not paths:
        return {}, None, ["no BENCH_r*.json artifact found"]
    newest = max(paths, key=lambda p: int(re.search(r"r(\d+)", p).group(1)))
    path = os.path.join(root, newest)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as ex:
        return {}, None, [f"{newest}: unreadable: {ex}"]
    matrix = bench_matrix(doc)
    anchors = {}
    notes = []
    for k, v in matrix.items():
        if not (isinstance(v, dict) and v.get("cluster_ticks_per_s")):
            continue
        # A row measured at a non-production batch (--smoke, custom --batch)
        # must never become the roofline anchor: its throughput is not the
        # number the bytes/tick projection is anchored against. Rows with no
        # batch field (hand-recovered tails) are kept -- nothing to judge.
        prod = PRESETS.get(k)
        if prod and v.get("batch") is not None and v["batch"] != prod[1]:
            notes.append(
                f"{newest}: {k} row measured at batch={v['batch']} "
                f"(production {prod[1]}): ignored for the anchor"
            )
            continue
        # A --smoke row can sit at the production batch (config1: batch 1
        # both ways; SMOKE_TICKS is what shrinks it), so the batch comparison
        # above cannot catch it -- bench marks such rows and they must never
        # rebase the anchor onto CPU smoke throughput.
        if v.get("smoke"):
            notes.append(
                f"{newest}: {k} row measured with --smoke: ignored for the "
                "anchor"
            )
            continue
        # A row measured on a CPU backend can never rebase the roofline: the
        # pins project chip HBM rates, and a CPU measurement pass (bench
        # >= r06 records `backend` per row; obs/reconcile.py marks such rows
        # non-anchor for the same reason) would silently rebase the implied
        # rate onto host-memory throughput. Rows with no backend field
        # (BENCH_r05) are kept: it was recorded on a chip.
        if v.get("backend") == "cpu":
            notes.append(
                f"{newest}: {k} row measured on the cpu backend: ignored "
                "for the anchor"
            )
            continue
        # A row measured on the scenario path (bench --scenario) prices the
        # genome input lattice, not the plain run loop the roofline
        # projects -- bench itself refuses to attach headroom to such rows.
        if v.get("scenario"):
            notes.append(
                f"{newest}: {k} row measured on the scenario path "
                f"({v['scenario']}): ignored for the anchor"
            )
            continue
        # A row measured under a different carry LAYOUT than the preset's
        # current one must never rebase its roofline: the bytes/tick the
        # anchor implies a rate against are the layout's (bench >= r14
        # records `layout` per row; earlier rows are all dense). The
        # PR 5/PR 8 smoke-row trap class, closed for layouts too.
        if prod and (v.get("layout") or "dense") != layout_of(prod[0]):
            notes.append(
                f"{newest}: {k} row measured under the "
                f"{v.get('layout') or 'dense'} layout (preset is "
                f"{layout_of(prod[0])}): ignored for the anchor"
            )
            continue
        # A row measured across D>1 devices (bench >= r16 mesh_scaling leg
        # records `n_devices` per row; every earlier row is single-device)
        # reports AGGREGATE mesh throughput -- rebasing the single-device
        # roofline onto it would inflate the implied HBM rate D-fold. Same
        # trap class as layouts, closed for device counts.
        if (v.get("n_devices") or 1) != 1:
            notes.append(
                f"{newest}: {k} row measured across {v['n_devices']} "
                "devices: ignored for the anchor"
            )
            continue
        anchors[k] = float(v["cluster_ticks_per_s"])
    if not anchors:
        return {}, None, notes + [f"{newest}: no recoverable matrix rows"]
    return anchors, newest, notes


def layout_of(cfg) -> str:
    """Physical carry layout of a config: "compact" (ops/tile.py,
    cfg.compact_planes) or "dense". Bench rows record this per row; the
    anchor/reconcile guards key on it so a row measured under one layout can
    never rebase the other layout's roofline."""
    return "compact" if getattr(cfg, "compact_planes", False) else "dense"


def dense_base(name: str) -> str | None:
    """The dense-layout base preset of a compacted preset (config5c ->
    config5): the preset whose config differs ONLY in compact_planes and
    whose production batch matches. None for dense presets or when no base
    exists."""
    import dataclasses

    entry = PRESETS.get(name)
    if entry is None or not entry[0].compact_planes:
        return None
    want = dataclasses.replace(entry[0], compact_planes=False)
    for other, (cfg, batch) in PRESETS.items():
        if other != name and cfg == want and batch == entry[1]:
            return other
    return None


def anchor(root: str | None = None):
    """The roofline anchor with the documented fallback: rows from the newest
    bench artifact when one is readable, the pinned round-5 chip numbers for
    any config the artifact does not cover (BENCH_r*.json tails are
    byte-truncated captures, so individual rows can be missing) -- each
    fallback is a note the caller should surface, never a silent
    substitution."""
    anchors, source, notes = bench_anchor(root)
    if not anchors:
        notes = notes + ["falling back to the pinned round-5 chip anchors"]
        return dict(FALLBACK_ANCHOR_R05), "pinned-r05-fallback", notes
    merged = dict(FALLBACK_ANCHOR_R05)
    merged.update(anchors)
    missing = sorted(set(FALLBACK_ANCHOR_R05) - set(anchors))
    if missing:
        notes = notes + [
            f"{source} carries no row for {', '.join(missing)}: using the "
            "pinned round-5 anchors there"
        ]
        source = f"{source} (+pinned r05: {', '.join(missing)})"
    return merged, source, notes


# ------------------------------------------------------------ byte derivation


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape") or not hasattr(aval, "dtype"):
        return 0
    return policy.logical_bytes(tuple(aval.shape), aval.dtype.itemsize)


def _find_run_scan(jaxpr):
    """The run loop's scan eqn: the scan with the WIDEST carry anywhere in the
    program (nested pjit bodies included) -- the tick loop carries the whole
    (state, metrics) pytree, so it dominates any helper scan."""
    best = None
    for eqn in jaxpr_audit.iter_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            if best is None or eqn.params["num_carry"] > best.params["num_carry"]:
                best = eqn
    return best


def carry_model(closed, batch: int, names: list[str] | None = None):
    """Price the scan carry of a lowered run program, per cluster-tick.

    Carry avals come from the run scan's body jaxpr (trailing axis = the
    batch, the batch-minor layout contract); MOVING legs -- body output var
    is not the input var -- cost a read+write per tick, identity-passthrough
    legs cost nothing (XLA elides them; Pass A's `carry-passthrough` rule
    pins that the policy's invariant set is in fact identity). Padded bytes
    use `batch` (the preset's real batch) for the lane/sublane tiling, NOT
    the small audit batch the program was traced with -- padding amortizes
    over the batch, so the priced footprint is the production one.

    Returns None when the program contains no scan (step kernels)."""
    eqn = _find_run_scan(closed.jaxpr)
    if eqn is None:
        return None
    body = eqn.params["jaxpr"].jaxpr
    nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
    carry_in = body.invars[nc:nc + nk]
    carry_out = body.outvars[:nk]
    if names is None or len(names) != nk:
        std = policy.carry_leaf_names()
        if len(std) == nk:
            names = std
        elif nk > len(std):
            # Surplus legs (a temp riding the scan carry -- the headline
            # regression this pass gates): keep the declared names for the
            # prefix so the findings name the new leg(s) instead of
            # renaming every leg positionally. Best-effort: an insertion
            # mid-struct shifts names from that point on.
            names = list(std) + [f"extra{i}" for i in range(len(std), nk)]
        else:
            names = [f"leg{i}" for i in range(nk)]
    legs = {}
    carry_logical = 0
    carry_padded = 0.0
    for nm, a, b in zip(names, carry_in, carry_out):
        aval = b.aval
        pshape = tuple(aval.shape[:-1])  # trailing axis is the batch
        isz = aval.dtype.itemsize
        moving = a is not b
        padded = policy.padded_bytes(pshape, isz, batch)
        legs[nm] = {
            "shape": list(pshape),
            "dtype": str(aval.dtype),
            "padded": round(padded, 1),
            "moving": moving,
        }
        if moving:
            carry_logical += 2 * policy.logical_bytes(pshape, isz)
            carry_padded += 2 * padded
    return {
        "n_legs": nk,
        "legs": legs,
        "moving_legs": {
            nm: leg["padded"] for nm, leg in legs.items() if leg["moving"]
        },
        "carry_logical": carry_logical,
        "carry_padded": round(carry_padded, 1),
    }


@functools.lru_cache(maxsize=None)
def input_bytes(cfg: RaftConfig, batch: int):
    """(logical, padded) bytes of the per-tick StepInputs, materialized once
    per tick from the key stream inside the scan body (eval_shape over the
    real `faults.make_inputs`, per cluster)."""
    from raft_sim_tpu.sim import faults

    key = jax.eval_shape(lambda: jax.random.key(0))
    inputs = jax.eval_shape(lambda k: faults.make_inputs(cfg, k, jnp.int32(0)), key)
    log = sum(
        policy.logical_bytes(tuple(v.shape), v.dtype.itemsize) for v in inputs
    )
    pad = sum(
        policy.padded_bytes(tuple(v.shape), v.dtype.itemsize, batch) for v in inputs
    )
    return log, round(pad, 1)


def live_peak_bytes(closed) -> tuple[int, int]:
    """(live-set peak, total materialized bytes) for a closed jaxpr.

    Peak: a linear liveness walk -- each var is live from its defining eqn to
    its last use (program outputs to the end); the peak is the byte-maximum
    of the live set, with nested bodies (pjit/scan/cond) contributing their
    own inner peak on top of the outer live set at their call eqn. Total:
    the sum of every eqn's output bytes (all temporaries ever written).
    Both are estimates of the lowering (pre-XLA-fusion), exact and
    reproducible per jax version -- the golden comparison is version-gated
    exactly like the op-histogram snapshots."""
    memo: dict[int, int] = {}
    total = 0
    for eqn in jaxpr_audit.iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            total += _aval_bytes(v)
    return _live_peak(closed.jaxpr, memo), total


def _live_peak(jaxpr, memo: dict[int, int]) -> int:
    key = id(jaxpr)
    if key in memo:
        return memo[key]
    last: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "count"):
                last[v] = i
    for v in jaxpr.outvars:
        if hasattr(v, "count"):
            last[v] = len(jaxpr.eqns)
    cur = 0
    alive = set()
    for v in (*jaxpr.invars, *jaxpr.constvars):
        if hasattr(v, "count") and v in last and v not in alive:
            alive.add(v)
            cur += _aval_bytes(v)
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if hasattr(v, "count") and v not in alive:
                alive.add(v)
                cur += _aval_bytes(v)
        inner = max(
            (_live_peak(sub, memo) for sub in jaxpr_audit._sub_jaxprs(eqn)),
            default=0,
        )
        peak = max(peak, cur + inner)
        dead = {
            v for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v, "count") and v in alive and last.get(v, -1) <= i
        }
        for v in dead:
            alive.discard(v)
            cur -= _aval_bytes(v)
    memo[key] = peak
    return peak


# ------------------------------------------------------------ donation audit

# Shapes for the donation-audit lowerings: the smallest legal cluster. The
# aliasing decision is structural (argument pytree <-> output pytree), so a
# tiny instance proves the same property as the production shapes while its
# one `compile()` costs seconds, not the 15-40 s of a real scan program.
_TINY_CFG = RaftConfig(n_nodes=3, log_capacity=4, max_entries_per_rpc=1)
_TINY_BATCH = 2
_TINY_TICKS = 2


def _tiny_avals():
    from raft_sim_tpu.types import init_batch

    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(lambda k: init_batch(_TINY_CFG, k, _TINY_BATCH), key)
    keys = jax.eval_shape(lambda k: jax.random.split(k, _TINY_BATCH), key)
    return state, keys


def entry_points():
    """(label, expected status, lower thunk) for every jitted entry point the
    donation pin covers. Labels and expectations come from the single-source
    registry `policy.donating_entry_points()` (Pass D's dataflow lint and the
    runtime sanitizer read the SAME registry); only the tiny-aval lower thunks
    live here. Expectations are design decisions, restated so the golden
    regeneration and the rule messages agree:

      _chunk_donate  donates the chunk carry (the long-horizon hot loop)
      _chunk_t_donate  the telemetry soak loop's chunk: same donation contract
      _serve_chunk   the standing-fleet serve loop's chunk: donates the fleet
                     between chunks (a service session must hold ONE fleet in
                     HBM forever, not two -- ISSUE 6's never-double-buffers
                     acceptance bullet)
      _chunk         input-preserving ON PURPOSE: tools/repro.py replays from
                     the chunk-start state after a violation
      simulate(+scenario)  seed/genome inputs only -- nothing donatable; the
                     scan carry double-buffers inside one executable, which
                     is XLA's job, not the caller's

    Only `cost_pinned` registry entries appear (the trace variant shares
    `_chunk_t_donate`'s donation decorator line and is covered by Pass D's
    registry-coverage rule instead of a second golden row).
    """
    import dataclasses as _dc

    from raft_sim_tpu.serve import loop as serve_loop
    from raft_sim_tpu.sim import chunked, scan as scan_mod, telemetry

    state, keys = _tiny_avals()
    seed = jax.ShapeDtypeStruct((), jnp.int32)
    genome = jaxpr_audit._genome_avals(_TINY_BATCH, 2)
    serve_cfg = _dc.replace(_TINY_CFG, serve_ingest=True)
    cmds = jax.ShapeDtypeStruct((_TINY_TICKS, _TINY_BATCH), jnp.int32)
    thunks = {
        "sim.chunked._chunk_donate":
            lambda: chunked._chunk_donate.lower(
                _TINY_CFG, state, keys, _TINY_TICKS, None, 1),
        "sim.telemetry._chunk_t_donate":
            lambda: telemetry._chunk_t_donate.lower(
                _TINY_CFG, state, keys, None, _TINY_TICKS, _TINY_TICKS, 0,
                None, 1),
        "serve.loop._serve_chunk":
            lambda: serve_loop._serve_chunk.lower(
                serve_cfg, state, keys, cmds, None, _TINY_TICKS),
        "sim.chunked._chunk":
            lambda: chunked._chunk.lower(
                _TINY_CFG, state, keys, _TINY_TICKS, None, 1),
        "sim.scan.simulate":
            lambda: scan_mod.simulate.lower(
                _TINY_CFG, seed, _TINY_BATCH, _TINY_TICKS),
        "sim.scan.simulate_scenario":
            lambda: scan_mod.simulate_scenario.lower(
                _TINY_CFG, seed, _TINY_BATCH, _TINY_TICKS, genome, 16),
    }
    return tuple(
        (e.label, e.expected, thunks[e.label])
        for e in policy.donating_entry_points() if e.cost_pinned
    )


def lowered_donation_status(lowered) -> dict:
    """Donation as the LOWERING records it: jax marks each donated argument
    buffer with a `tf.aliasing_output` attribute in the StableHLO module.
    Zero marks = nothing will be aliased, whatever the Python decorators
    claim."""
    n = lowered.as_text().count("tf.aliasing_output")
    return {"status": "donated" if n else "not-donated", "aliased_args": n}


def _memory_confirm(lowered) -> dict:
    """The compile-level confirmation ISSUE asks for:
    `lower().compile().memory_analysis()` -- alias_size_in_bytes > 0 means the
    executable really reuses donated input buffers. Unavailable on some
    backends; recorded as such rather than guessed."""
    try:
        stats = lowered.compile().memory_analysis()
        alias = getattr(stats, "alias_size_in_bytes", None)
        if alias is None:
            return {"available": False}
        return {
            "available": True,
            "alias_size_in_bytes": int(alias),
            "temp_size_in_bytes": int(getattr(stats, "temp_size_in_bytes", 0)),
        }
    except Exception as ex:  # backend without memory stats must not kill the gate
        return {"available": False, "error": str(ex)[:200]}


@functools.lru_cache(maxsize=None)
def donation_audit() -> tuple:
    """Audit every registered entry point. Cached: the one tiny compile (for
    memory_analysis on the donating entry) is paid once per process, shared
    by the gate and the tests. Returns a tuple of (label, result-dict) pairs
    (hashable for the cache; callers dict() it)."""
    out = []
    for label, expected, lower_thunk in entry_points():
        lowered = lower_thunk()
        res = lowered_donation_status(lowered)
        res["expected"] = expected
        if expected == "donated":
            mem = _memory_confirm(lowered)
            res["memory_analysis"] = mem
            if mem.get("available") and mem.get("alias_size_in_bytes") == 0:
                # Marked in the lowering but the executable aliases nothing:
                # the donation is decorative (layout/shape mismatch).
                res["status"] = "marked-not-aliased"
        out.append((label, res))
    return tuple(out)


# --------------------------------------------------------------- derivation


def derive_program(key: str, closed, kind: str, cfg: RaftConfig, batch: int) -> dict:
    peak, temp = live_peak_bytes(closed)
    entry: dict = {"kind": kind, "live_peak": peak, "temp_bytes": temp}
    if kind not in ("scan", "serve_scan", "trace_scan"):
        return entry
    # serve_scan: the widest scan is the serve loop's inner window scan, whose
    # carry = the (state, metrics) template + the first-violation aux leg --
    # so the offer-tick plane legs are priced exactly like every other carry
    # leg (ISSUE 6: the plane's cost is a gated number, not prose).
    # trace_scan: likewise, plus the named trace ring/coverage legs
    # (policy.trace_carry_leaf_names) -- the trace plane's sizing guidance in
    # docs/OBSERVABILITY.md reads from these pins.
    names = policy.trace_carry_leaf_names() if kind == "trace_scan" else None
    cm = carry_model(closed, batch, names=names)
    if cm is None:
        entry["error"] = "no scan found in a scan-kind program"
        return entry
    entry.update(cm)
    in_log, in_pad = input_bytes(cfg, batch)
    entry["inputs_logical"] = in_log
    entry["inputs_padded"] = in_pad
    total = cm["carry_padded"] + in_pad
    if key.endswith("/scenario_simulate"):
        # The genome program table, read once per tick (scan consts, never
        # carry): S audit segments x the policy leaf set, 4 B each.
        gen = sum(
            policy.padded_bytes((jaxpr_audit._AUDIT_SEGMENTS,), 4, batch)
            for _ in policy.scenario_genome_leaves()
        )
        entry["genome_padded"] = round(gen, 1)
        total += gen
    entry["bytes_per_tick_padded"] = round(total, 1)
    entry["bytes_per_tick_logical"] = cm["carry_logical"] + in_log
    return entry


# ------------------------------------------------------------- mesh pricing

# The (preset, node-shard count) pairs the mesh section pins: the giant-N
# tiers over the standing 8-way mesh (CI's forced 8-device CPU mesh; one
# pod-slice row on hardware). A different device count changes ONLY n_pad --
# re-derive with node_shard_model(name, D) for ad-hoc shapes.
MESH_TIERS: tuple[tuple[str, int], ...] = (("config7", 8), ("config7x", 8))

# Mailbox legs _gather_mailbox all_gathers (models/raft_batched.py) and the
# config gate that turns each group on. Kept in sync by the derivation below
# failing KeyError-loudly if a leg name leaves the carry, and by the mesh
# parity/collective tests lowering the real program.
_GATHERED_ALWAYS = (
    "mb.req_type", "mb.req_term", "mb.req_commit", "mb.req_last_index",
    "mb.req_last_term", "mb.ent_start", "mb.ent_prev_term", "mb.ent_count",
    "mb.ent_term", "mb.ent_val", "mb.req_off", "mb.resp_kind", "mb.v_to",
    "mb.a_ok_to", "mb.a_match", "mb.a_hint", "mb.resp_term",
)


def node_shard_model(name: str, n_devices: int) -> dict:
    """Analytic per-device cost of the node-sharded program
    (parallel/nodeshard.py) for one preset: the dense tier's moving carry legs
    re-priced at the row-partitioned shapes (first node axis n -> nl = n_pad /
    D, peer axes n -> n_pad), plus the all_gather traffic -- the bytes the
    hot loop's one mailbox gather (and the invariants' leader gather)
    materializes per cluster-tick, of which each device RECEIVES the
    (D-1)/D off-device fraction over ICI. Pure shape arithmetic on the dense
    twin's jaxpr: needs no devices, so the pins regenerate anywhere."""
    import numpy as np

    from raft_sim_tpu import types as rst_types
    from raft_sim_tpu.parallel import nodeshard

    cfg0, batch = PRESETS[name]
    cfg = rst_types.compact_twin(cfg0, False)  # sharded carries run dense
    n = cfg.n_nodes
    n_pad = nodeshard.check_shardable(cfg, n_devices)
    nl = n_pad // n_devices
    cm = carry_model(jaxpr_audit.scan_jaxpr(cfg), batch)
    axes_of = {f: a for f, (a, _) in nodeshard._STATE_PAD.items()}
    axes_of.update(
        {f"mb.{f}": a for f, (a, _) in nodeshard._MAILBOX_PAD.items()}
    )

    def shard_shape(nm: str, shape: list[int]) -> tuple[int, ...]:
        out = list(shape)
        for ax in axes_of.get(nm, ()):
            out[ax] = nl if ax == 0 else n_pad
        return tuple(out)

    carry = 0.0
    for nm, leg in cm["legs"].items():
        if not leg["moving"]:
            continue
        isz = np.dtype(leg["dtype"]).itemsize
        carry += 2 * policy.padded_bytes(shard_shape(nm, leg["shape"]), isz, batch)

    gathered = list(_GATHERED_ALWAYS)
    if cfg.track_offer_ticks:
        gathered.append("mb.ent_tick")
    if cfg.compaction:
        gathered += ["mb.req_base", "mb.req_base_term", "mb.req_base_chk"]
    if cfg.pre_vote:
        gathered.append("mb.pv_grant")
    ag = 0.0
    legs_out = {}
    for nm in gathered:
        leg = cm["legs"][nm]
        full = tuple(
            n_pad if ax in axes_of[nm] else d
            for ax, d in enumerate(leg["shape"])
        )
        b = policy.padded_bytes(full, np.dtype(leg["dtype"]).itemsize, batch)
        legs_out[nm] = round(b, 1)
        ag += b
    if cfg.check_invariants:
        # The election-safety leaders-by-term gather (_step_info_b).
        b = policy.padded_bytes((n_pad,), 4, batch)
        legs_out["leaders_by_term"] = round(b, 1)
        ag += b

    _, in_pad = input_bytes(cfg, batch)
    entry = {
        "n_nodes": n,
        "n_devices": n_devices,
        "n_pad": n_pad,
        "nl": nl,
        "per_device_carry_padded": round(carry, 1),
        # Inputs are drawn redundantly on every device (zero communication);
        # each device pays the full per-cluster input materialization.
        "per_device_inputs_padded": in_pad,
        "per_device_bytes_per_tick": round(carry + in_pad, 1),
        "allgather_bytes_per_tick": round(ag, 1),
        "ici_recv_bytes_per_tick": round(ag * (n_devices - 1) / n_devices, 1),
        "gathered_legs": legs_out,
    }
    return entry


def derive_mesh() -> dict:
    return {
        f"{name}@{d}dev": node_shard_model(name, d) for name, d in MESH_TIERS
    }


def compare_mesh(derived: dict, golden: dict, *, full: bool = True) -> list[Finding]:
    """Mesh-section findings: per-device HBM bytes/tick and all_gather (ICI)
    bytes/tick against the pins, carry-bytes tolerance both ways."""
    out = []
    g_mesh = golden.get("mesh") or {}
    tol = _tol(golden, "carry_bytes")
    keys = ("per_device_bytes_per_tick", "allgather_bytes_per_tick")
    for key, d in derived.items():
        g = g_mesh.get(key)
        path = f"cost:mesh/{key}"
        if g is None:
            out.append(Finding(
                rule="cost-golden", path=path,
                message=f"mesh tier has no golden cost pin -- {_REGEN}",
            ))
            continue
        for k in keys:
            gv, dv = g.get(k), d.get(k)
            if not gv or dv is None:
                continue
            if dv > gv * (1 + tol):
                side = "ICI all_gather" if k.startswith("allgather") else "per-device HBM"
                out.append(Finding(
                    rule="cost-mesh-bytes", path=path,
                    message=(
                        f"{side} traffic regressed {gv:.0f} -> {dv:.0f} B per "
                        f"cluster-tick (>{100 * tol:.0f}% tolerance): a leg "
                        "widened or newly crosses the mesh -- "
                        f"{_REGEN}"
                    ),
                ))
            elif dv < gv * (1 - tol):
                out.append(Finding(
                    rule="cost-golden", path=path,
                    message=(
                        f"mesh {k} improved {gv:.0f} -> {dv:.0f} B: the pin is "
                        f"stale -- {_REGEN} to lock in the win"
                    ),
                ))
    if full:
        for key in g_mesh:
            if key not in derived:
                out.append(Finding(
                    rule="cost-golden", path=f"cost:mesh/{key}",
                    message=f"golden pins a mesh tier no longer derived -- {_REGEN}",
                ))
    return out


def derive_all(config_names=jaxpr_audit.AUDIT_CONFIGS) -> dict:
    """The full derived cost document for the audited tiers: one entry per
    program (the same zoo Pass A walks), plus the donation audit and the
    roofline anchor in use. Cached per config set: the gate, the --cost-report
    writer, and --update-goldens all want the same document in one process,
    and the liveness walks dominate the pass -- callers treat the result as
    read-only."""
    return _derive_all(tuple(config_names))


@functools.lru_cache(maxsize=4)
def _derive_all(config_names: tuple) -> dict:
    programs = {}
    for name in config_names:
        cfg, batch = PRESETS[name]
        for prog, closed, kind, rule_cfg in jaxpr_audit.programs(name, cfg):
            key = prog.split("jaxpr:", 1)[1]
            programs[key] = derive_program(key, closed, kind, rule_cfg, batch)
    anchors, source, notes = anchor()
    for key, entry in programs.items():
        cfg_name, prog = key.split("/", 1)
        if prog == "simulate" and cfg_name in anchors:
            a = anchors[cfg_name]
            entry["anchor_ticks_per_s"] = a
            entry["implied_hbm_bytes_per_s"] = round(
                a * entry["bytes_per_tick_padded"], 1
            )
            entry["roofline_ticks_per_s"] = round(a, 1)
    # Layout twins: a compacted tier (cfg.compact_planes) whose DENSE base
    # preset is anchored inherits the base's implied HBM rate, so its pin
    # carries a genuine layout PREDICTION (rate / own bytes) instead of the
    # anchored tiers' by-construction drift detector. The anchor itself
    # stays keyed by layout -- `bench_anchor` and obs/reconcile.py reject
    # layout-mismatched rows -- so a compacted bench artifact can never
    # silently rebase the dense roofline (the PR 5/PR 8 smoke-row trap
    # class, closed for layouts too).
    for key, entry in programs.items():
        cfg_name, prog = key.split("/", 1)
        if prog != "simulate" or "roofline_ticks_per_s" in entry:
            continue
        base = dense_base(cfg_name)
        base_entry = programs.get(f"{base}/simulate") if base else None
        rate = (base_entry or {}).get("implied_hbm_bytes_per_s")
        if rate:
            entry["layout_base"] = base
            entry["implied_hbm_bytes_per_s"] = rate
            entry["roofline_ticks_per_s"] = round(
                rate / entry["bytes_per_tick_padded"], 1
            )
    return {
        "jax_version": jax.__version__,
        "anchor_source": source,
        "anchor_notes": notes,
        "donation": {k: dict(v) for k, v in donation_audit()},
        "programs": programs,
        # Node-sharded tiers: derived only when every mesh preset is in the
        # audited set (a --configs subset run prices what it audits).
        "mesh": (
            derive_mesh()
            if all(name in config_names for name, _ in MESH_TIERS)
            else {}
        ),
    }


# --------------------------------------------------------------- comparison


def _tol(golden: dict, key: str) -> float:
    return float((golden.get("tolerance") or {}).get(key, DEFAULT_TOLERANCE[key]))


_REGEN = "regenerate with `python tools/check.py --update-goldens` if intended"


def compare_program(key: str, d: dict, g: dict, *, version_match: bool,
                    golden: dict) -> list[Finding]:
    """Findings for one program's derived entry vs its golden pin. Regressions
    fire the cost rules; improvements fire `cost-golden` (the pin is stale --
    a fence that only ratchets one way rots)."""
    out = []
    path = f"cost:{key}"
    tol_b = _tol(golden, "carry_bytes")
    if d.get("error"):
        # A scan-kind program whose run scan can't be located would otherwise
        # skip every carry/bytes-per-tick/roofline comparison below with zero
        # findings -- the gate must go red VISIBLY when it stops gating, same
        # as the jax-version stale-pin rule.
        out.append(Finding(
            rule="cost-golden", path=path,
            message=(
                f"cost derivation failed ({d['error']}): the pinned "
                "carry/bytes-per-tick/roofline gates for this program are NOT "
                f"being checked -- fix the derivation or {_REGEN}"
            ),
        ))
    if d.get("kind") == "scan" and "moving_legs" in d and "moving_legs" in g:
        g_moving = g["moving_legs"]
        leg_findings = 0
        for nm, padded in d["moving_legs"].items():
            leg = d["legs"][nm]
            if nm not in g_moving:
                leg_findings += 1
                out.append(Finding(
                    rule="cost-carry-bytes", path=path,
                    message=(
                        f"carry widened: leg '{nm}' (shape {leg['shape']}, "
                        f"{leg['dtype']}, {padded:.0f} B padded/cluster-tick) "
                        "newly rides the scan-carry HBM round trip; the pinned "
                        f"moving set does not include it -- {_REGEN}"
                    ),
                ))
            elif padded > g_moving[nm] * (1 + tol_b):
                leg_findings += 1
                out.append(Finding(
                    rule="cost-carry-bytes", path=path,
                    message=(
                        f"carry leg '{nm}' grew {g_moving[nm]:.0f} -> "
                        f"{padded:.0f} B padded/cluster-tick "
                        f"(>{100 * tol_b:.0f}% tolerance): a dtype or shape "
                        f"widening on the hot carry -- {_REGEN}"
                    ),
                ))
        for nm in g_moving:
            if nm not in d.get("moving_legs", {}):
                out.append(Finding(
                    rule="cost-golden", path=path,
                    message=(
                        f"pinned moving carry leg '{nm}' no longer moves "
                        "(eliminated, renamed, or now loop-invariant): the "
                        f"golden is stale -- {_REGEN}"
                    ),
                ))
        gp, dp = g.get("carry_padded"), d.get("carry_padded")
        if gp and dp is not None and not leg_findings and dp > gp * (1 + tol_b):
            out.append(Finding(
                rule="cost-carry-bytes", path=path,
                message=(
                    f"scan-carry bytes/tick regressed {gp:.0f} -> {dp:.0f} B "
                    f"padded/cluster-tick (>{100 * tol_b:.0f}% tolerance) "
                    f"-- {_REGEN}"
                ),
            ))
        elif gp and dp is not None and dp < gp * (1 - tol_b):
            out.append(Finding(
                rule="cost-golden", path=path,
                message=(
                    f"scan-carry bytes/tick improved {gp:.0f} -> {dp:.0f} B: "
                    f"the golden pin is stale -- {_REGEN} to lock in the win"
                ),
            ))
        # Roofline at the PINNED implied HBM rate: deterministic (anchor
        # drift alone can never fire it; only bytes/tick growth can).
        g_rate, g_roof = g.get("implied_hbm_bytes_per_s"), g.get("roofline_ticks_per_s")
        bpt = d.get("bytes_per_tick_padded")
        if g_rate and g_roof and bpt:
            tol_r = _tol(golden, "roofline")
            roof_now = g_rate / bpt
            if roof_now < g_roof * (1 - tol_r):
                out.append(Finding(
                    rule="cost-roofline", path=path,
                    message=(
                        f"roofline at the pinned HBM rate fell "
                        f"{g_roof / 1e6:.2f}M -> {roof_now / 1e6:.2f}M ticks/s "
                        f"(bytes/tick {g.get('bytes_per_tick_padded', 0):.0f} "
                        f"-> {bpt:.0f} B, >{100 * tol_r:.0f}% tolerance) "
                        f"-- {_REGEN}"
                    ),
                ))
    if version_match and g.get("live_peak") and d.get("live_peak") is not None:
        tol_p = _tol(golden, "live_peak")
        gp, dp = g["live_peak"], d["live_peak"]
        if dp > gp * (1 + tol_p):
            out.append(Finding(
                rule="cost-live-peak", path=path,
                message=(
                    f"live-set peak grew {gp:,} -> {dp:,} B "
                    f"(>{100 * tol_p:.0f}% tolerance; total materialized "
                    f"{g.get('temp_bytes', 0):,} -> {d.get('temp_bytes', 0):,} B): "
                    f"a new temporary is being materialized -- {_REGEN}"
                ),
            ))
        elif dp < gp * (1 - tol_p):
            out.append(Finding(
                rule="cost-golden", path=path,
                message=(
                    f"live-set peak improved {gp:,} -> {dp:,} B: the golden "
                    f"pin is stale -- {_REGEN} to lock in the win"
                ),
            ))
    return out


def compare_donation(derived: dict, golden_donation: dict, *, full: bool = True) -> list[Finding]:
    out = []
    for label, res in derived.items():
        pin = golden_donation.get(label)
        if pin is None:
            out.append(Finding(
                rule="cost-golden", path=f"cost:donation/{label}",
                message=(
                    f"entry point has no pinned donation status -- {_REGEN}"
                ),
            ))
        elif res["status"] != pin:
            out.append(Finding(
                rule="cost-donation", path=f"cost:donation/{label}",
                message=(
                    f"donation status changed: pinned '{pin}', lowered "
                    f"'{res['status']}' ({res.get('aliased_args', 0)} aliased "
                    "args" + (
                        f", alias_size={res['memory_analysis'].get('alias_size_in_bytes')} B"
                        if res.get("memory_analysis", {}).get("available") else ""
                    ) + "). A dropped `donate_argnums` doubles steady-state "
                    "HBM residency of the chunk loop; if the change is "
                    f"intended, {_REGEN}"
                ),
            ))
    if full:
        for label in golden_donation:
            if label not in derived:
                out.append(Finding(
                    rule="cost-golden", path=f"cost:donation/{label}",
                    message=(
                        f"pinned entry point no longer audited -- {_REGEN}"
                    ),
                ))
    return out


def compare(derived: dict, golden: dict, *, full: bool = True) -> list[Finding]:
    """All Pass C findings: derived document vs golden pins. `full` = the
    derivation covered every audited tier, so golden entries with no derived
    counterpart are stale (a --configs subset run must not condemn them)."""
    out = []
    version_match = golden.get("jax_version") == derived.get("jax_version")
    g_programs = golden.get("programs") or {}
    if not version_match and any("live_peak" in g for g in g_programs.values()):
        # The live-peak comparison is lowering-exact per jax version, so a
        # mismatch disables it -- which must be a VISIBLE stale-pin finding,
        # never a gate that silently stays green across a jax upgrade.
        out.append(Finding(
            rule="cost-golden", path="cost:jax-version",
            message=(
                f"golden cost pins were recorded under jax "
                f"{golden.get('jax_version')} but this run is jax "
                f"{derived.get('jax_version')}: live-set peak comparisons are "
                f"disabled until the pins are regenerated -- {_REGEN}"
            ),
        ))
    for key, d in derived["programs"].items():
        g = g_programs.get(key)
        if g is None:
            out.append(Finding(
                rule="cost-golden", path=f"cost:{key}",
                message=f"audited program has no golden cost pin -- {_REGEN}",
            ))
            continue
        out.extend(compare_program(key, d, g, version_match=version_match,
                                   golden=golden))
    if full:
        for key in g_programs:
            if key not in derived["programs"]:
                out.append(Finding(
                    rule="cost-golden", path=f"cost:{key}",
                    message=(
                        f"golden pins a program the audit no longer lowers "
                        f"-- {_REGEN}"
                    ),
                ))
    out.extend(compare_donation(
        derived.get("donation", {}), golden.get("donation") or {}, full=full
    ))
    if derived.get("mesh"):
        out.extend(compare_mesh(derived["mesh"], golden, full=full))
    return out


# --------------------------------------------------------------- entry point


def run_pass(config_names=jaxpr_audit.AUDIT_CONFIGS,
             golden_file: str | None = None) -> list[Finding]:
    """The full cost pass: derive, load pins, compare. A missing or unreadable
    golden file is itself a finding -- the gate must force the pins into
    existence, not silently pass without them."""
    golden_file = golden_file or golden_path()
    rel = os.path.relpath(golden_file, _REPO_ROOT)
    derived = derive_all(config_names)
    try:
        with open(golden_file) as f:
            golden = json.load(f)
    except FileNotFoundError:
        return [Finding(
            rule="cost-golden", path=rel,
            message=(
                "no golden cost pins: generate them with "
                "`python tools/check.py --update-goldens` and commit the file"
            ),
        )]
    except (OSError, json.JSONDecodeError) as ex:
        return [Finding(
            rule="cost-golden", path=rel,
            message=f"golden cost file unreadable: {ex}",
        )]
    full = tuple(config_names) == tuple(jaxpr_audit.AUDIT_CONFIGS)
    return compare(derived, golden, full=full)


def _pin_program(entry: dict) -> dict:
    """The golden subset of a derived entry: totals and the moving-leg map --
    enough to name a regression precisely, without pinning every leg's shape
    (those live in the derived report, regenerated on demand)."""
    keep = (
        "kind", "n_legs", "moving_legs", "carry_logical", "carry_padded",
        "inputs_padded", "genome_padded", "bytes_per_tick_padded",
        "bytes_per_tick_logical", "live_peak", "temp_bytes",
        "anchor_ticks_per_s", "implied_hbm_bytes_per_s", "roofline_ticks_per_s",
        # Layout-twin attribution: a compacted tier's roofline is a
        # PREDICTION at its dense base's implied rate (not an anchored
        # drift detector) -- the pin says whose rate it borrowed.
        "layout_base",
    )
    return {k: entry[k] for k in keep if k in entry}


def update_golden(path: str | None = None,
                  config_names=jaxpr_audit.AUDIT_CONFIGS) -> str:
    """Regenerate tests/golden_cost_model.json from the current tree (the
    `tools/check.py --update-goldens` path, mirroring
    `tests/test_golden_jaxpr.py --update`)."""
    path = path or golden_path()
    derived = derive_all(config_names)
    # Tolerances are maintainer-tunable in the golden file (docs/ANALYSIS.md);
    # a regeneration re-pins the MEASUREMENTS but must not silently revert a
    # tuned tolerance back to the defaults.
    tolerance = dict(DEFAULT_TOLERANCE)
    try:
        with open(path) as f:
            tolerance.update(json.load(f).get("tolerance") or {})
    except (OSError, json.JSONDecodeError):
        pass
    doc = {
        "jax_version": derived["jax_version"],
        "anchor_source": derived["anchor_source"],
        "tolerance": tolerance,
        "donation": {
            label: res["status"] for label, res in derived["donation"].items()
        },
        "programs": {
            key: _pin_program(entry)
            for key, entry in sorted(derived["programs"].items())
        },
        "mesh": derived.get("mesh") or {},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def diff_table(derived: dict, golden: dict, out=None) -> None:
    """Pinned-vs-current table (the CI failure-triage rendering: a regression
    must be diagnosable from the job log, without a local repro)."""
    import sys

    out = out or sys.stdout
    g_programs = golden.get("programs") or {}
    print(
        f"{'program':32} {'pin B/tick':>12} {'now B/tick':>12} {'delta':>8} "
        f"{'pin peak':>12} {'now peak':>12}",
        file=out,
    )
    for key in sorted(set(derived["programs"]) | set(g_programs)):
        d = derived["programs"].get(key, {})
        g = g_programs.get(key, {})
        db, gb = d.get("bytes_per_tick_padded"), g.get("bytes_per_tick_padded")
        delta = (
            f"{100 * (db - gb) / gb:+.1f}%" if db and gb else "-"
        )
        fmt = lambda v: f"{v:,.0f}" if isinstance(v, (int, float)) else "-"
        print(
            f"{key:32} {fmt(gb):>12} {fmt(db):>12} {delta:>8} "
            f"{fmt(g.get('live_peak')):>12} {fmt(d.get('live_peak')):>12}",
            file=out,
        )
    for label, res in derived.get("donation", {}).items():
        pin = (golden.get("donation") or {}).get(label, "-")
        print(f"donation {label:40} pin={pin} now={res['status']}", file=out)
