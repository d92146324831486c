"""Pass A: lower the real step/scan programs and audit their jaxprs.

Lowering is tracing only -- no XLA compile, so the whole pass runs in seconds
on CPU even for the N=51 tier (`jax.make_jaxpr` over `raft_batched.step_b`
and the jitted `scan.simulate`). The rules encode the
invariants docs/PERF.md shows being lost silently:

  float-op             no floating-point primitive anywhere in an audited
                       program -- step kernels AND full scan programs: the
                       protocol state path is all-integer by design
                       (types.py), and since the uint32 threshold-compare
                       refactor of sim/faults.py the per-tick input pipeline
                       is too. A float sneaking in (a mean, a /, an
                       accidental promotion) is a dtype-discipline break AND a
                       perf hazard.
  plane-widening       no convert_element_type that widens an [N, N]-shaped
                       plane from its policy narrow dtype (int8/int16,
                       types.index_dtype / ack_dtype) into a wider type that
                       persists -- widening straight into a reduction
                       (sum/min/max accumulators) is the one legal form.
  carry-dtype          the scan carry's state planes enter and leave the tick
                       at the policy dtypes (a dropped `.astype(...)` at a
                       plane rebuild shows up here, not in a benchmark).
  carry-passthrough    every loop-invariant carry leg for the config
                       (policy.invariant_leaves) is passed through the scan
                       body UNTOUCHED -- var identity in the body jaxpr. XLA
                       elides untouched legs from the per-tick HBM round trip;
                       rewriting one as fresh values measurably regressed
                       config3 by ~16% in round 4 (docs/PERF.md).
  large-constant       no baked-in constant above a size threshold: a big
                       closed-over table silently bloats every executable and
                       usually means something meant to be computed or carried.
  recompile-fork       tunable-only config changes (fault probabilities,
                       cadences, timer values) must NOT change the lowered
                       program's structure: each (base, variant) pair in
                       FORK_PAIRS lowers the full scan program both ways and
                       compares structural hashes. A Python branch on a tuned
                       value (`if cfg.drop_prob > 0.2: <other algorithm>`)
                       forks one compiled program per sweep point and melts
                       the tier-1 compile budget (~15-40 s per distinct scan
                       program on CPU); this rule fails it statically.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp

from raft_sim_tpu import types as rst_types
from raft_sim_tpu.analysis import policy
from raft_sim_tpu.analysis.findings import Finding
from raft_sim_tpu.utils.config import PRESETS, RaftConfig

# Every rule slug this pass can emit (run.run_all scopes stale-waiver
# detection to the passes that actually ran).
RULES = frozenset({
    "float-op", "plane-widening", "carry-dtype", "carry-passthrough",
    "large-constant", "recompile-fork", "node-collectives",
})

# Reduction primitives a widening convert may legally feed: the widened plane
# is an accumulator XLA fuses into the reduce, never a materialized tensor.
REDUCERS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "argmax", "argmin",
})

# Baked-in constants above this are flagged (rule large-constant). The largest
# legitimate consts today are the [N, N] eye / [N, W] bit-weight planes --
# ~2.6 KB at N=51; anything past 64 KiB is a table that should be computed,
# carried, or fed as an input.
LARGE_CONST_BYTES = 64 * 1024

# Scan-program shape used for audits: small batch/ticks keep tracing fast and
# have no effect on the audited structure (shapes scale, programs don't).
_AUDIT_BATCH = 8
_AUDIT_TICKS = 32
# Telemetry window of the audited SERVE program (the served scan folds window
# records on device, telemetry-style): shape-like static, ticks must divide.
_AUDIT_WINDOW = 16
# Canonical scenario-program shape for the audited genome path: S segments of
# SEG_LEN ticks. S/seg_len are shape-like statics (a different S is a new
# program, like a different batch); genome VALUES are traced and can never
# fork a compile -- which is the whole point, and what the scenario fork
# check below pins.
_AUDIT_SEGMENTS = 2
_AUDIT_SEG_LEN = 16
# Event-ring depth of the audited TRACE program (trace/ring.py TraceSpec):
# shape-like static, small to keep the audit lowering fast -- depth scales
# the carry planes, never the program structure.
_AUDIT_TRACE_DEPTH = 32

# (preset, replacements) pairs for rule recompile-fork: every replacement is a
# pure tuning-knob change (probabilities, cadences, horizons) that must lower
# to a structurally identical scan program. Values are chosen to stay on the
# same side of every structural gate (> 0 checks, dtype ceilings like
# ack_age_sat's int8 tier and index_dtype's capacity tiers).
FORK_PAIRS: tuple[tuple[str, dict], ...] = (
    ("config2", {"client_interval": 12}),
    ("config3", {"heartbeat_ticks": 4, "ack_timeout_ticks": 16}),
    ("config4", {"drop_prob": 0.23, "clock_skew_prob": 0.13}),
    ("config5", {"partition_prob": 0.4}),
    # Compacted layout tier: tuning knobs inside the layout gate must not
    # fork (the pack/unpack boundary is shape-driven, never value-driven).
    ("config5c", {"partition_prob": 0.4}),
    ("config6", {"crash_prob": 0.2, "drop_prob": 0.15}),
    ("config6r", {"client_interval": 8, "crash_down_ticks": 10}),
    # Reconfiguration plane: the admin cadences are tuning knobs (values stay
    # nonzero -- the structural gates are `> 0` checks by design, like
    # client_interval), so retiming membership changes / transfers / reads
    # must never fork a compile (the scenario genome retimes them as data).
    ("config8", {
        "reconfig_interval": 53, "transfer_interval": 31, "read_interval": 5,
        "drop_prob": 0.15,
    }),
    # Lease reads: the lease TERM is a tuning knob inside its structural
    # gate (read_lease_ticks > 0, under the skew-safe ceiling) exactly like
    # the cadences -- retiming the lease must never fork a compile.
    ("config9", {
        "read_lease_ticks": 3, "read_interval": 5, "client_interval": 6,
        "clock_skew_prob": 0.2,
    }),
    # Durable storage plane: the fsync cadence and every disk-fault
    # probability are tuning knobs inside the structural gate
    # (fsync_interval > 0) -- retiming flushes or reshaping the disk-fault
    # lattice must never fork a compile. lost_suffix_span stays a traced
    # randint bound (precedent: crash_down_ticks).
    ("config10", {
        "fsync_interval": 5, "fsync_jitter_prob": 0.35,
        "torn_tail_prob": 0.15, "lost_suffix_span": 5, "crash_prob": 0.2,
    }),
)


# ---------------------------------------------------------------- program zoo


@functools.lru_cache(maxsize=None)
def step_jaxpr(cfg: RaftConfig):
    """ClosedJaxpr of one tick: `raft_batched.step_b` (batch-minor, trailing
    batch axis). Cached per cfg: tracing dominates the gate's runtime and the
    rules, the fork guard, and the golden tests all want the same programs."""
    from raft_sim_tpu.models import raft_batched

    state, inputs, _ = policy.state_avals(cfg)
    addb = lambda x: jax.ShapeDtypeStruct(tuple(x.shape) + (_AUDIT_BATCH,), x.dtype)
    state, inputs = jax.tree.map(addb, (state, inputs))
    return jax.make_jaxpr(functools.partial(raft_batched.step_b, cfg))(state, inputs)


@functools.lru_cache(maxsize=None)
def scan_jaxpr(cfg: RaftConfig, batch: int = _AUDIT_BATCH, ticks: int = _AUDIT_TICKS):
    """ClosedJaxpr of the full batched run (`scan.simulate`: init + batch-minor
    scan), traced through its jit wrapper. Cached: the per-tier rules and the
    recompile-fork guard audit the same base programs."""
    from raft_sim_tpu.sim import scan

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.make_jaxpr(lambda s: scan.simulate(cfg, s, batch, ticks))(seed)


def _genome_avals(batch: int, s_count: int):
    from raft_sim_tpu.scenario.genome import ScenarioGenome, leaf_dtype

    return ScenarioGenome(**{
        f: jax.ShapeDtypeStruct((batch, s_count), leaf_dtype(f))
        for f in ScenarioGenome._fields
    })


@functools.lru_cache(maxsize=None)
def scenario_scan_jaxpr(
    cfg: RaftConfig,
    batch: int = _AUDIT_BATCH,
    ticks: int = _AUDIT_TICKS,
    s_count: int = _AUDIT_SEGMENTS,
    seg_len: int = _AUDIT_SEG_LEN,
):
    """ClosedJaxpr of the scenario-engine run (`scan.simulate_scenario`: the
    genome input path, every fault mechanism traced). The genome enters as
    `[B, S]` avals -- its VALUES are invisible to lowering, so one program
    serves the whole heterogeneous fleet; the same carry template as the
    plain scan (the genome rides the body as loop constants, never carry)."""
    from raft_sim_tpu.sim import scan

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    gen = _genome_avals(batch, s_count)
    return jax.make_jaxpr(
        lambda s, g: scan.simulate_scenario(cfg, s, batch, ticks, g, seg_len)
    )(seed, gen)


def serve_variant(cfg: RaftConfig) -> RaftConfig:
    """The serve-mode config a tier's serve program is audited under (external
    ingest replaces the scheduled cadence; the offer-tick plane goes live)."""
    from raft_sim_tpu.serve.loop import serve_config

    return serve_config(cfg)


@functools.lru_cache(maxsize=None)
def serve_scan_jaxpr(
    cfg: RaftConfig,
    batch: int = _AUDIT_BATCH,
    ticks: int = _AUDIT_TICKS,
    window: int = _AUDIT_WINDOW,
):
    """ClosedJaxpr of the standing-fleet serve program
    (`serve.loop.simulate_serve`: init + served windowed scan). The offer
    plane enters as a [ticks, batch] int32 aval (the batch axis IS the
    tenancy axis: each cluster gets its tenant's own command per tick) --
    command VALUES and the tenant PARTITION are invisible to lowering, so one
    compiled chunk program serves the whole session at any tenant count and a
    multi-chunk `driver serve` run compiles nothing after warmup (the claim
    the distinct-lowering pin gates). Read-carrying serve variants
    (cfg.read_index: serve_reads / a scheduled cadence collapsed by
    serve_config) additionally take the [ticks, batch] read plane. NOTE:
    callers pass the SERVE-mode config (`serve_variant`), which is also the
    config the carry rules run under -- the offer-tick plane legs move here
    by design."""
    from raft_sim_tpu.serve import loop as serve_loop

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    cmds = jax.ShapeDtypeStruct((ticks, batch), jnp.int32)
    if cfg.read_index:
        reads = jax.ShapeDtypeStruct((ticks, batch), jnp.int32)
        return jax.make_jaxpr(
            lambda s, c, r: serve_loop.simulate_serve(cfg, s, batch, c, window, r)
        )(seed, cmds, reads)
    return jax.make_jaxpr(
        lambda s, c: serve_loop.simulate_serve(cfg, s, batch, c, window)
    )(seed, cmds)


def trace_variant(cfg: RaftConfig) -> RaftConfig:
    """The trace-mode config a tier's traced program is audited under
    (cfg.track_trace raised; nothing else moves -- with it off the tier's
    standing programs carry NO trace leg, which the unchanged simulate/
    scenario/serve pins prove every gate run)."""
    return dataclasses.replace(cfg, track_trace=True)


@functools.lru_cache(maxsize=None)
def trace_scan_jaxpr(
    cfg: RaftConfig,
    batch: int = _AUDIT_BATCH,
    ticks: int = _AUDIT_TICKS,
    window: int = _AUDIT_WINDOW,
    depth: int = _AUDIT_TRACE_DEPTH,
):
    """ClosedJaxpr of the protocol-trace program (`telemetry.simulate_windowed`
    with a TraceSpec: the windowed scan plus the event ring + coverage carry
    legs, trace/ring.py). NOTE: callers pass the TRACE-mode config
    (`trace_variant`) -- the trace legs exist only there, and the carry rules
    run under it so their cost is a pinned number, not prose."""
    from raft_sim_tpu.sim import telemetry
    from raft_sim_tpu.trace.ring import TraceSpec

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    spec = TraceSpec(depth=depth)
    return jax.make_jaxpr(
        lambda s: telemetry.simulate_windowed(
            cfg, s, batch, ticks, window, 0, None, 1, spec
        )
    )(seed)


def trace_extra_legs() -> int:
    """Auxiliary carry legs the trace program's tick loop rides beyond the
    (state, metrics) template: the window first-violation tick plus the
    TraceWin/TracePersist leaves (trace/ring.py)."""
    from raft_sim_tpu.trace.ring import TracePersist, TraceWin

    return 1 + len(TraceWin._fields) + len(TracePersist._fields)


def programs(name: str, cfg: RaftConfig):
    """The audited programs for one config tier: the step kernel, the full
    scan, the scenario (genome-path) scan, the standing-fleet serve scan, and
    the protocol-trace scan. Yields (program_name, closed_jaxpr, kind,
    rule_cfg) -- `rule_cfg` is the config the per-program rules (carry
    passthrough/dtype, input pricing) run under: the tier's own config,
    except for the serve/trace programs, which are audited under their
    serve-mode / trace-mode variants (offer-tick plane / trace legs live)."""
    yield f"jaxpr:{name}/step_b", step_jaxpr(cfg), "step", cfg
    yield f"jaxpr:{name}/simulate", scan_jaxpr(cfg), "scan", cfg
    yield f"jaxpr:{name}/scenario_simulate", scenario_scan_jaxpr(cfg), "scan", cfg
    scfg = serve_variant(cfg)
    yield f"jaxpr:{name}/serve_simulate", serve_scan_jaxpr(scfg), "serve_scan", scfg
    tcfg = trace_variant(cfg)
    yield f"jaxpr:{name}/trace_simulate", trace_scan_jaxpr(tcfg), "trace_scan", tcfg


# ------------------------------------------------------------- jaxpr walking


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(sub, "jaxpr"):  # ClosedJaxpr
                yield sub.jaxpr
            elif hasattr(sub, "eqns"):  # raw Jaxpr
                yield sub


def iter_eqns(jaxpr):
    """Every eqn of `jaxpr` and its nested sub-jaxprs (pjit/scan/cond bodies),
    depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def iter_consts(closed):
    """Every baked-in constant of a ClosedJaxpr, including nested bodies.
    Yields (path-ish depth marker, const)."""
    for c in closed.consts:
        yield c
    for eqn in iter_eqns(closed.jaxpr):
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "consts"):
                    for c in sub.consts:
                        yield c


def op_histogram(closed) -> dict[str, int]:
    """Primitive counts bucketed by output dtype: `{"prim dtype": count}` over
    the whole program including nested bodies. The golden-snapshot currency:
    a new [N, N, B] materialization or a dtype flip shows up as a reviewable
    count diff, not a benchmark surprise."""
    hist: dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        out = eqn.outvars[0]
        dt = str(out.aval.dtype) if hasattr(out.aval, "dtype") else "abstract"
        key = f"{eqn.primitive.name} {dt}"
        hist[key] = hist.get(key, 0) + 1
    return hist


def _param_digest(params) -> str:
    """Stable rendering of an eqn's non-jaxpr params (axes, dimension
    numbers, dtypes, paddings -- the structural knobs that do not show in
    avals). Sub-jaxprs are replaced by a marker (they are walked separately);
    only comparable within one process (callable reprs carry addresses)."""
    parts = []
    for k in sorted(params):
        v = params[k]
        vals = v if isinstance(v, (list, tuple)) else (v,)
        if any(hasattr(s, "jaxpr") or hasattr(s, "eqns") for s in vals):
            parts.append(f"{k}=<jaxpr>")
        else:
            parts.append(f"{k}={v!r}")
    return ";".join(parts)


def structural_hash(closed) -> str:
    """Hash of the program's structure: the depth-first sequence of
    (primitive, params, input avals, output avals). Literal VALUES are
    excluded (a literal contributes only its shape/dtype via its aval), so
    two lowerings that differ only in baked tuning constants --
    probabilities, cadences, thresholds -- hash equal, while any change to
    the op sequence, a shape, a dtype, or a primitive's structural params
    (reduce axes, gather dimension numbers, paddings) forks the hash.
    Process-local (param reprs may embed addresses): compare hashes from the
    same run only."""
    h = hashlib.sha256()
    for eqn in iter_eqns(closed.jaxpr):
        h.update(eqn.primitive.name.encode())
        h.update(_param_digest(eqn.params).encode())
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                h.update(str((tuple(aval.shape), str(aval.dtype))).encode())
    return h.hexdigest()[:16]


def program_hash(closed) -> str:
    """Cache-key-like hash: the full jaxpr text (literals included). Two
    identical hashes => one jit compile can serve both."""
    return hashlib.sha256(str(closed).encode()).hexdigest()[:16]


# -------------------------------------------------------------------- rules


def check_float_ops(program: str, closed) -> list[Finding]:
    """Rule float-op: step kernels are all-integer by design."""
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if jnp.issubdtype(aval.dtype, jnp.floating):
                out.append(Finding(
                    rule="float-op",
                    path=program,
                    message=(
                        f"float dtype {aval.dtype} at primitive "
                        f"'{eqn.primitive.name}' (shape {tuple(aval.shape)}): "
                        "the protocol-state path is integer-only (types.py)"
                    ),
                ))
                break  # one finding per eqn is enough
    return out


def _has_nn_pair(shape, n: int) -> bool:
    return any(shape[i] == n and shape[i + 1] == n for i in range(len(shape) - 1))


def check_plane_widening(program: str, closed, cfg: RaftConfig) -> list[Finding]:
    """Rule plane-widening: top-level convert_element_type eqns that widen an
    [N, N]-shaped int8/int16 plane, unless every consumer is a reduction (the
    widen-into-accumulator form XLA fuses away). Top level is where the
    kernel's own `.astype` discipline lives; jnp-internal promotions in nested
    bodies feed reductions by construction."""
    n = cfg.n_nodes
    consumers: dict = {}
    for eqn in closed.jaxpr.eqns:
        for v in eqn.invars:
            if hasattr(v, "count"):
                consumers.setdefault(v, []).append(eqn.primitive.name)
    escaping = set(v for v in closed.jaxpr.outvars if hasattr(v, "count"))
    out = []
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name != "convert_element_type":
            continue
        src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
        if src.dtype not in (jnp.int8, jnp.int16):
            continue
        if dst.dtype.itemsize <= src.dtype.itemsize:
            continue
        if not _has_nn_pair(tuple(src.shape), n):
            continue
        cons = consumers.get(eqn.outvars[0], [])
        if cons and all(c in REDUCERS for c in cons) and eqn.outvars[0] not in escaping:
            continue
        out.append(Finding(
            rule="plane-widening",
            path=program,
            message=(
                f"[N,N] plane widened {src.dtype} -> {dst.dtype} "
                f"(shape {tuple(src.shape)}, consumers {cons or ['<returned>']}): "
                "policy dtypes (types.index_dtype/ack_dtype) must persist; "
                "widening is only legal straight into a reduction"
            ),
        ))
    return out


def _find_scan(jaxpr, num_carry: int):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["num_carry"] == num_carry:
            return eqn
        for sub in _sub_jaxprs(eqn):
            found = _find_scan(sub, num_carry)
            if found is not None:
                return found
    return None


def check_carry_passthrough(
    program: str, closed, cfg: RaftConfig, extra_legs: int = 0
) -> list[Finding]:
    """Rule carry-passthrough: in the run scan's body, every leg
    policy.invariant_leaves names for this config must be the SAME var in and
    out (identity passthrough -- XLA then elides it from the per-tick HBM
    round trip). Also rule carry-dtype: carried state planes hold their policy
    dtypes. `extra_legs` selects a TICK loop whose carry rides auxiliary legs
    after the (state, metrics) template -- the serve program's inner window
    scan carries the first-violation tick (serve/loop.py), so its tick loop
    has len(names) + 1 legs while its outer window loop (where passthrough
    legs are fresh scan outputs by construction) has exactly len(names)."""
    names = policy.carry_leaf_names()
    want = len(names) + extra_legs
    eqn = _find_scan(closed.jaxpr, want)
    if eqn is None:
        return [Finding(
            rule="carry-passthrough",
            path=program,
            message=(
                f"no scan with the expected {want}-leg carry found; the "
                "run-loop structure changed -- update analysis/policy.py's "
                "carry template alongside it"
            ),
        )]
    body = eqn.params["jaxpr"].jaxpr
    nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
    carry_in = body.invars[nc:nc + nk]
    carry_out = body.outvars[:nk]
    identity = {nm for nm, a, b in zip(names, carry_in, carry_out) if a is b}
    out = []
    for nm in sorted(policy.invariant_leaves(cfg)):
        if nm not in identity:
            out.append(Finding(
                rule="carry-passthrough",
                path=program,
                message=(
                    f"carry leg '{nm}' should be loop-invariant for this "
                    "config but is rewritten inside the scan body: pass the "
                    "old value through untouched so XLA elides its HBM round "
                    "trip (docs/PERF.md, round-4 lesson)"
                ),
            ))
    # carry-dtype: the narrow-plane policy, checked on the carried avals.
    expect = {
        "next_index": jnp.dtype(rst_types.index_dtype(cfg)),
        "match_index": jnp.dtype(rst_types.index_dtype(cfg)),
        "ack_age": jnp.dtype(rst_types.ack_dtype(cfg)),
        "mb.a_match": jnp.dtype(rst_types.index_dtype(cfg)),
        "mb.a_hint": jnp.dtype(rst_types.index_dtype(cfg)),
        "mb.req_off": jnp.dtype(jnp.int8),
        "mb.resp_kind": jnp.dtype(jnp.int8),
        "votes": jnp.dtype(jnp.uint32),
        "mb.pv_grant": jnp.dtype(jnp.uint32),
    }
    if cfg.compact_planes:
        # Compacted carry layout (ops/tile.py): the transformed legs ride
        # flat uint32 word vectors -- the dense narrow dtypes live INSIDE
        # the tick body (unpack at entry, repack at exit), so the carried
        # avals are expected at the packed dtypes instead.
        from raft_sim_tpu.ops import tile

        expect.update(tile.packed_carry_dtypes(cfg))
    for nm, v in zip(names, carry_out):
        want = expect.get(nm)
        if want is not None and v.aval.dtype != want:
            out.append(Finding(
                rule="carry-dtype",
                path=program,
                message=(
                    f"carried plane '{nm}' leaves the tick as {v.aval.dtype}, "
                    f"policy dtype is {want} (types.py)"
                ),
            ))
    return out


def check_large_constants(program: str, closed) -> list[Finding]:
    """Rule large-constant: baked-in arrays above LARGE_CONST_BYTES."""
    out = []
    for c in iter_consts(closed):
        nbytes = getattr(c, "nbytes", 0)
        if nbytes > LARGE_CONST_BYTES:
            out.append(Finding(
                rule="large-constant",
                path=program,
                message=(
                    f"baked-in constant of {nbytes} bytes (shape "
                    f"{getattr(c, 'shape', '?')}, dtype {getattr(c, 'dtype', '?')}) "
                    f"exceeds {LARGE_CONST_BYTES} B: compute it, carry it, or "
                    "feed it as an input instead of baking it into every "
                    "executable"
                ),
            ))
    return out


def check_recompile_forks(pairs=FORK_PAIRS) -> list[Finding]:
    """Rule recompile-fork: each (preset, tuning replacement) pair must lower
    to structurally identical programs -- for the plain scan AND the scenario
    (genome-path) scan. The scenario check is the stronger claim: the genome
    path exists so that fault-space sweeps are pure data, so ANY tuned-value
    leak into its structure would resurrect exactly the per-point recompile
    the scenario engine removes (one compile per genome/segment is the
    failure mode ISSUE 4 forbids)."""
    out = []
    for name, repl in pairs:
        base, _ = PRESETS[name]
        variant = dataclasses.replace(base, **repl)
        for label, lower in (
            ("simulate", scan_jaxpr),
            ("scenario_simulate", scenario_scan_jaxpr),
            # The serve loop's zero-recompiles-after-warmup claim, statically:
            # a tuned value leaking into the serve chunk's structure would
            # recompile the standing fleet mid-session.
            ("serve_simulate", lambda c: serve_scan_jaxpr(serve_variant(c))),
            # The coverage search's one-compiled-program claim: trace-mode
            # evaluations across a fault sweep must share a program too.
            ("trace_simulate", lambda c: trace_scan_jaxpr(trace_variant(c))),
        ):
            h_base = structural_hash(lower(base))
            h_var = structural_hash(lower(variant))
            if h_base != h_var:
                out.append(Finding(
                    rule="recompile-fork",
                    path=f"jaxpr:{name}/{label}",
                    message=(
                        f"tuning-only change {repl} forked the lowered program "
                        f"structure ({h_base} -> {h_var}): a Python branch or a "
                        "shape now depends on a tuned value, so every sweep point "
                        "would recompile (~15-40 s each on CPU, tier-1 budget)"
                    ),
                ))
    return out


def check_node_collectives(
    name: str, cfg: RaftConfig, mesh, batch: int = _AUDIT_BATCH,
    ticks: int = _AUDIT_TICKS,
) -> list[Finding]:
    """Rule node-collectives: the node-sharded program's ONLY inter-device
    primitives are the whitelisted ones -- the tiled mailbox/invariant
    all_gathers and the metric psum/pmin/pmax folds (parallel/nodeshard.py's
    layout contract). A ppermute, all_to_all, or reduce_scatter sneaking into
    the tick loop means a reduction stopped being receiver-local -- the exact
    regression the row-partition layout exists to make impossible. Needs a
    live multi-device "nodes" mesh to lower (the CI mesh-smoke job and
    tests/test_nodeshard.py run under 8 forced CPU devices); callers gate on
    device count, this function does not."""
    from raft_sim_tpu.parallel import nodeshard

    seed = jax.ShapeDtypeStruct((), jnp.int32)
    closed = jax.make_jaxpr(
        lambda s: nodeshard.simulate_node_sharded(cfg, s, batch, ticks, mesh)
    )(seed)
    seen = {
        eqn.primitive.name
        for eqn in iter_eqns(closed.jaxpr)
        if eqn.primitive.name in NODE_COLLECTIVE_KINDS
    }
    bad = sorted(seen - NODE_COLLECTIVE_WHITELIST)
    return [
        Finding(
            rule="node-collectives",
            path=f"jaxpr:{name}/node_sharded_simulate",
            message=(
                f"node-sharded program lowered non-whitelisted collective(s) "
                f"{bad}: the hot loop's inter-device traffic must stay the "
                "mailbox/invariant all_gathers + metric psum/pmin/pmax folds "
                "(parallel/nodeshard.py layout rules, docs/DESIGN.md)"
            ),
        )
    ] if bad else []


# Named-axis primitives the node-collectives rule classifies as inter-device
# communication (axis_index is positional metadata, not traffic, but is
# whitelisted explicitly so a future jax rename fails loudly as non-listed).
NODE_COLLECTIVE_KINDS = frozenset({
    "all_gather", "psum", "pmin", "pmax", "ppermute", "all_to_all",
    "reduce_scatter", "pbroadcast", "pgather", "axis_index",
})
NODE_COLLECTIVE_WHITELIST = frozenset({
    "all_gather", "psum", "pmin", "pmax", "axis_index",
})


# --------------------------------------------------------------- entry point

# The config tiers Pass A audits by default: one per structural family --
# plain (config3), wide + partitions + sampled log matching (config5),
# client + log matching (config1), faults (config4), compaction + crash
# (config6), redirect pipeline (config6r).
# config8 adds the reconfiguration-plane family (joint-consensus membership +
# TimeoutNow + ReadIndex legs live).
# config9 adds the lease-read family (lease serve predicate, vote denial,
# read_fr staleness leg -- compaction + offer-tick plane live too).
# config5c adds the compacted-carry-layout family (ops/tile.py: the config5
# workload with the per-edge planes bit-packed into flat uint32 legs) -- the
# tier whose Pass C pin IS the layout's predicted bytes/tick verdict
# (docs/PERF.md "the config5 roofline").
# config7/config7x add the giant-N family (N=101 threshold-quorum and the
# N=255 ceiling under the compacted layout): the single-chip programs audited
# here, the per-device mesh bytes priced by Pass C's mesh section, and the
# node-sharded program's collective whitelist checked whenever a multi-device
# mesh is live (check_node_collectives).
# config10 adds the durable-storage family (raft_sim_tpu/storage: the
# dur_len/dur_term/dur_vote watermark legs, the section-3.8 ack/grant gates,
# crash recovery's truncate-and-rewind, and the fsync/torn-tail disk-fault
# draws live).
AUDIT_CONFIGS = (
    "config1", "config3", "config4", "config5", "config5c", "config6",
    "config6r", "config7", "config7x", "config8", "config9", "config10",
)


def run_pass(config_names=AUDIT_CONFIGS, fork_pairs=FORK_PAIRS) -> list[Finding]:
    """The full jaxpr pass: per-tier program rules + the fork guard. Since the
    round-7 threshold-compare refactor (sim/faults.py) the ENTIRE input
    pipeline is integer too, so the float-op rule runs on every audited
    program -- scans included -- not just the step kernels."""
    out: list[Finding] = []
    for name in config_names:
        cfg, _ = PRESETS[name]
        for prog, closed, kind, rule_cfg in programs(name, cfg):
            out.extend(check_float_ops(prog, closed))
            if kind == "step":
                out.extend(check_plane_widening(prog, closed, rule_cfg))
            else:
                # The serve program's tick loop rides one auxiliary carry leg
                # (the window's first-violation tick -- serve/loop.py); the
                # trace program rides that plus the event ring + coverage
                # legs (trace/ring.py).
                extra = {"serve_scan": 1, "trace_scan": trace_extra_legs()}.get(
                    kind, 0
                )
                out.extend(
                    check_carry_passthrough(prog, closed, rule_cfg, extra_legs=extra)
                )
            out.extend(check_large_constants(prog, closed))
    out.extend(check_recompile_forks(fork_pairs))
    # The node-sharded program's collective whitelist, whenever this process
    # can lower one (>= 2 devices: the CI mesh-smoke job and the test suite
    # force 8 CPU devices; a single-device run skips it silently -- the gate
    # still runs wherever the sharded program can actually exist).
    if len(jax.devices()) >= 2 and "config7" in config_names:
        from raft_sim_tpu.parallel import nodeshard

        n_dev = 1 << (len(jax.devices()).bit_length() - 1)
        mesh = nodeshard.make_node_mesh(n_dev)
        cfg, _ = PRESETS["config7"]
        out.extend(check_node_collectives("config7", cfg, mesh))
    return out
