"""The machine-readable form of the repo's dtype and carry-identity policy.

`types.py` states every invariant this package enforces, but states it in
prose: field comments like `# [N, N] index_dtype` carry the narrow-dtype
policy, the docstrings carry the "loop-invariant carry legs stay untouched"
rule (docs/PERF.md, round-4 lesson), and `utils/checkpoint.py`'s version log
carries the bump-on-field-change convention. This module turns each of those
into data the two analysis passes can check against:

  - `parse_types_comments()` parses the `# [shape] dtype` trailing comments of
    the `ClusterState` / `Mailbox` / `StepInfo` field declarations straight
    out of the `types.py` source (so the comments themselves become a checked
    contract, not decoration);
  - `resolve_dtypes()` maps policy names (`index_dtype`, `ack_dtype`) to the
    concrete dtypes `types.py` computes for a given config;
  - `invariant_leaves()` names the scan-carry legs a config's tick must pass
    through UNTOUCHED (the legs XLA elides from the per-tick HBM round trip;
    `tools/traffic_audit.py` prices the same set, imported from here so the
    two can never disagree);
  - `schema_fingerprint()` hashes the serialized-pytree field sets against
    the pin in `utils/checkpoint._SCHEMA_FINGERPRINT`.

Nothing here runs a simulation; the heaviest call is `jax.eval_shape`.
"""

from __future__ import annotations

import ast
import hashlib
import inspect
import math
import re

import jax
import jax.numpy as jnp

from raft_sim_tpu import types as rst_types
from raft_sim_tpu.ops import log_ops
from raft_sim_tpu.sim.scan import RunMetrics
from raft_sim_tpu.types import ClusterState, Mailbox, StepInfo
from raft_sim_tpu.utils.config import RaftConfig

# TPU minor-tile sublane multiple by element width (the lane dim is always 128
# wide). Single-sourced here so the cost model (analysis/cost_model.py) and the
# traffic audit (tools/traffic_audit.py) price the batch-minor layout with the
# SAME rules -- a padding-model change is one edit, visible to both. 64-bit
# elements lower as paired 32-bit words on TPU, so they tile like 4-byte
# elements; the 2x price rides on itemsize, which is what cost-carry-bytes
# then flags. Covers every token in CONCRETE_DTYPES, so the cost model can't
# crash on a legal-dtype carry leg.
SUBLANE = {8: 8, 4: 8, 2: 16, 1: 32}


def logical_bytes(shape, itemsize: int) -> int:
    """shape x itemsize; a scalar is one element."""
    return math.prod(shape) * itemsize if shape else itemsize


def padded_bytes(shape, itemsize: int, batch: int) -> float:
    """Physical bytes per cluster in the batch-minor layout: `shape + (B,)`
    with the trailing two dims tiled (sublane x 128 lanes), divided back by B
    so lane padding amortizes across the batch and the reported overhead is
    the sublane padding the layout actually pays per cluster."""
    dims = list(tuple(shape) + (batch,))
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        sub = SUBLANE[itemsize]
        dims[-2] = -(-dims[-2] // sub) * sub
    return math.prod(dims) * itemsize / batch


# Dtype tokens legal in a types.py field comment: either a concrete dtype or
# the name of a policy function in types.py that picks one per config.
CONCRETE_DTYPES = ("bool", "int8", "int16", "int32", "int64", "uint8", "uint32")
POLICY_DTYPES = ("index_dtype", "ack_dtype", "node_dtype")

# Leading-comment grammar: optional shape (`[N, W]` / `scalar`), one or more
# dtype tokens separated by `/`, optionally a parenthesized policy name,
# optionally a VALUE-RANGE clause `in [lo, hi]` (closed interval; lo/hi are
# integer expressions over the config symbols below -- the value-range audit
# seeds its abstract interpreter from these and proves them inductive, rules
# range-annotation-stale / range-pack-width), then free prose. Examples that
# must parse (all live in types.py today):
#   # [N] int32 (starts at 1, core.clj:34)
#   # [N, W] uint32; bit j of votes[i] = i holds a vote from j
#   # [N, N] index_dtype in [1, cap+1]; leader i's next index for peer j
#   # [N(responder)] int16/int32 (index_dtype) in [0, cap]: acked index ...
#   # scalar int32 global tick counter
#   # bool: two leaders share a term
_DTYPE_TOKEN = "|".join(CONCRETE_DTYPES + POLICY_DTYPES)
_COMMENT_RE = re.compile(
    r"^(?:\[(?P<shape>[^\]]*)\]|(?P<scalar>scalar))?\s*"
    rf"(?P<dtypes>(?:{_DTYPE_TOKEN})(?:/(?:{_DTYPE_TOKEN}))*)"
    rf"(?:\s*\((?P<policy>{'|'.join(POLICY_DTYPES)})\))?"
    r"(?:\s+in\s+\[(?P<lo>[^,\[\]]+),\s*(?P<hi>[^,\[\]]+)\])?"
)

# Symbols legal in a range clause, resolved per config. Kept deliberately
# small: the bounds that motivate the narrow-dtype policy (tile.py widths are
# functions of exactly cap/sat/E).
def _range_symbols(cfg: RaftConfig) -> dict[str, int]:
    return {
        "cap": cfg.log_capacity,
        "sat": cfg.ack_age_sat,
        "E": cfg.max_entries_per_rpc,
        "N": cfg.n_nodes,
        "K": cfg.client_pipeline,
        "NIL": rst_types.NIL,
    }


_RANGE_SYMBOLS = ("cap", "sat", "E", "N", "K", "NIL")


def parse_range_expr(expr: str) -> ast.expr:
    """Validate a range-clause bound: integer arithmetic (+ - *) over int
    literals and the config symbols. Returns the parsed AST; raises
    ValueError (with the offending token) on anything else."""
    try:
        node = ast.parse(expr.strip(), mode="eval").body
    except SyntaxError as e:
        raise ValueError(f"range bound {expr!r} is not an expression: {e}")
    for sub in ast.walk(node):
        if isinstance(sub, ast.BinOp):
            if not isinstance(sub.op, (ast.Add, ast.Sub, ast.Mult)):
                raise ValueError(f"range bound {expr!r}: operator not in + - *")
        elif isinstance(sub, ast.UnaryOp):
            if not isinstance(sub.op, ast.USub):
                raise ValueError(f"range bound {expr!r}: unary op not -")
        elif isinstance(sub, ast.Constant):
            if not isinstance(sub.value, int):
                raise ValueError(f"range bound {expr!r}: non-integer literal")
        elif isinstance(sub, ast.Name):
            if sub.id not in _RANGE_SYMBOLS:
                raise ValueError(
                    f"range bound {expr!r}: unknown symbol {sub.id!r} "
                    f"(legal: {', '.join(_RANGE_SYMBOLS)})"
                )
        elif not isinstance(sub, (ast.Add, ast.Sub, ast.Mult, ast.USub, ast.Load)):
            raise ValueError(f"range bound {expr!r}: {type(sub).__name__} not allowed")
    return node


def resolve_range_expr(expr: str, cfg: RaftConfig) -> int:
    """Evaluate a validated range bound under `cfg`'s symbol values."""
    node = parse_range_expr(expr)
    syms = _range_symbols(cfg)

    def ev(n):
        if isinstance(n, ast.Constant):
            return n.value
        if isinstance(n, ast.Name):
            return syms[n.id]
        if isinstance(n, ast.UnaryOp):
            return -ev(n.operand)
        ops = {ast.Add: lambda a, b: a + b, ast.Sub: lambda a, b: a - b,
               ast.Mult: lambda a, b: a * b}
        return ops[type(n.op)](ev(n.left), ev(n.right))

    return ev(node)


# Capacity-bounded range declarations are the NON-compaction contract: with
# cfg.compaction the same legs carry absolute 1-based indices with no static
# bound (types.index_dtype widens them to int32 for the same reason), so the
# audit neither seeds nor checks these clauses there. Names in carry-leaf
# convention (state bare, mailbox `mb.<f>`).
CAPACITY_RANGE_LEGS = frozenset({
    "next_index", "match_index", "commit_index", "log_len", "dur_len",
    "mb.a_match", "mb.a_hint", "mb.ent_start",
})


def range_applies(leg: str, cfg: RaftConfig) -> bool:
    """Whether `leg`'s declared range clause is in force under `cfg`."""
    if leg in CAPACITY_RANGE_LEGS and cfg.compaction:
        return False
    return True


def declared_ranges(cfg: RaftConfig, specs=None) -> dict[str, tuple[int, int]]:
    """Carry-leg name -> (lo, hi) resolved declared range under `cfg`, for
    every types.py field whose comment carries a range clause that is in
    force (`range_applies`). The value-range audit seeds scan carries from
    this and proves each clause inductive."""
    if specs is None:
        specs, _problems = parse_types_comments()
    out: dict[str, tuple[int, int]] = {}
    for cls, prefix in (("ClusterState", ""), ("Mailbox", "mb.")):
        for f, spec in specs.get(cls, {}).items():
            if spec.lo is None:
                continue
            leg = prefix + f
            if not range_applies(leg, cfg):
                continue
            out[leg] = (
                resolve_range_expr(spec.lo, cfg),
                resolve_range_expr(spec.hi, cfg),
            )
    return out
# Optional `= <default>` between the annotation and the comment: StepInputs'
# reconfiguration-plane fields default to the Python-int NIL sentinel so
# hand-built test inputs stay valid (types.py).
_FIELD_RE = re.compile(r"^\s*(\w+):\s*jax\.Array(?:\s*=\s*[\w.+-]+)?\s*#\s*(.*)$")


class FieldSpec:
    """One parsed field-comment contract: declared ndim (None = unchecked),
    the set of dtype tokens the comment admits, and the optional declared
    value range (lo/hi bound expressions, None = undeclared)."""

    def __init__(self, name: str, line: int, ndim: int | None, dtypes: tuple[str, ...],
                 lo: str | None = None, hi: str | None = None):
        self.name = name
        self.line = line
        self.ndim = ndim
        self.dtypes = dtypes
        self.lo = lo
        self.hi = hi

    def __repr__(self):  # test/debug readability only
        rng = f", in=[{self.lo}, {self.hi}]" if self.lo is not None else ""
        return f"FieldSpec({self.name!r}, ndim={self.ndim}, dtypes={self.dtypes}{rng})"


def parse_types_comments(source: str | None = None):
    """Parse the dtype contracts out of types.py's field comments.

    Returns ({class_name: {field: FieldSpec}}, problems) where `problems` is a
    list of (line, message) for declarations whose comment does NOT parse --
    an unparseable comment is itself a finding (the contract must stay
    machine-readable).
    """
    if source is None:
        source = inspect.getsource(rst_types)
    tree = ast.parse(source)
    lines = source.splitlines()
    out: dict[str, dict[str, FieldSpec]] = {}
    problems: list[tuple[int, str]] = []
    for node in tree.body:
        if not (isinstance(node, ast.ClassDef) and node.name in (
            "ClusterState", "Mailbox", "StepInfo", "StepInputs"
        )):
            continue
        fields: dict[str, FieldSpec] = {}
        for lineno in range(node.lineno, node.end_lineno + 1):
            m = _FIELD_RE.match(lines[lineno - 1])
            if not m:
                continue
            name, comment = m.groups()
            cm = _COMMENT_RE.match(comment.strip())
            if not cm:
                problems.append(
                    (lineno, f"{node.name}.{name}: comment {comment!r} does not "
                             "parse as `[shape] dtype` (see analysis/policy.py)")
                )
                continue
            if cm.group("shape") is not None:
                shape = cm.group("shape")
                ndim = shape.count(",") + 1 if shape.strip() else 0
            elif cm.group("scalar"):
                ndim = 0
            else:
                ndim = None
            dtypes = tuple(cm.group("dtypes").split("/"))
            if cm.group("policy"):
                dtypes = dtypes + (cm.group("policy"),)
            lo, hi = cm.group("lo"), cm.group("hi")
            if lo is not None:
                try:
                    parse_range_expr(lo)
                    parse_range_expr(hi)
                except ValueError as e:
                    problems.append((lineno, f"{node.name}.{name}: {e}"))
                    lo = hi = None
            elif comment.strip()[cm.end():].lstrip().startswith("in ["):
                # A malformed range clause must be a finding, not silently
                # demoted to prose (closed `[lo, hi]` with exactly one comma).
                problems.append(
                    (lineno, f"{node.name}.{name}: range clause in comment "
                             f"{comment!r} does not parse as `in [lo, hi]`")
                )
            fields[name] = FieldSpec(name, lineno, ndim, dtypes, lo=lo, hi=hi)
        out[node.name] = fields
    return out, problems


def resolve_dtypes(spec: FieldSpec, cfg: RaftConfig) -> set[jnp.dtype]:
    """The concrete dtypes a field comment admits under `cfg`. A policy token
    narrows the concrete alternatives to the one the policy picks; concrete
    tokens stand alone."""
    policy = [t for t in spec.dtypes if t in POLICY_DTYPES]
    if policy:
        fns = {
            "index_dtype": rst_types.index_dtype,
            "ack_dtype": rst_types.ack_dtype,
            "node_dtype": rst_types.node_dtype,
        }
        return {jnp.dtype(fns[t](cfg)) for t in policy}
    return {jnp.dtype(t) for t in spec.dtypes}


def state_avals(cfg: RaftConfig):
    """eval_shape'd (ClusterState, StepInputs, StepInfo) for one cluster --
    the actual shapes/dtypes the comment contracts are checked against."""
    from raft_sim_tpu.models import raft_batched
    from raft_sim_tpu.sim import faults

    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(lambda k: rst_types.init_state(cfg, k), key)
    inputs = jax.eval_shape(lambda k: faults.make_inputs(cfg, k, jnp.int32(0)), key)
    _, info = jax.eval_shape(
        lambda s, i: raft_batched.step_one(cfg, s, i), state, inputs
    )
    return state, inputs, info


def invariant_leaves(cfg: RaftConfig) -> set[str]:
    """Carry leaves the tick passes through UNTOUCHED for this config. XLA
    elides loop-invariant scan-carry components from the per-tick HBM round
    trip, so rewriting one as fresh values each tick is a measured perf
    regression (docs/PERF.md, round-4 lesson) -- the jaxpr pass fails it
    statically (rule `carry-passthrough`), and `tools/traffic_audit.py`
    excludes the same set from its traffic totals. Names: state fields bare,
    mailbox fields as `mb.<field>`.

    The SAME set governs the scenario (genome-path) scan: a genome tunes only
    inputs, never which carry legs a config's tick touches -- the structural
    gates (pre_vote, compaction, client_redirect, client_interval > 0) stay
    on RaftConfig precisely so this holds, and the jaxpr pass enforces it on
    `scenario_simulate` programs too. The genome itself is scan CONSTS
    (`scenario_genome_leaves`), not carry."""
    inv = set()
    if not cfg.pre_vote:
        inv |= {"mb.pv_grant"}
        if not cfg.read_lease and not cfg.reconfig:
            # heard_clock feeds the pre-vote quiet rule, the lease vote
            # denial, AND the log-carried-config removed-server denial: any
            # gate keeps it live.
            inv |= {"heard_clock"}
    if not cfg.compaction:
        inv |= {
            "mb.req_base", "mb.req_base_term", "mb.req_base_chk",
            "log_base", "base_term", "base_chk",
        }
    if not cfg.client_redirect:
        inv |= {"client_pend", "client_dst"}
    if not cfg.track_offer_ticks:
        # Offer-tick plane off: the latency stamps (log plane, wire window,
        # pipeline stamps) and the dedup frontier are all dead weight the tick
        # must pass through untouched.
        inv |= {"log_tick", "mb.ent_tick", "client_tick", "lat_frontier"}
    elif not cfg.client_redirect:
        # Plane on but no redirect pipeline: stamps never ride client slots
        # (direct acceptance stamps at injection).
        inv |= {"client_tick"}
    # Reconfiguration plane (raft_sim_tpu/reconfig): each extension's state
    # legs are dead weight unless its structural gate is on -- the
    # zero-cost-when-off contract the tentpole inherits from
    # track_offer_ticks/pre_vote/compaction.
    if not cfg.reconfig:
        inv |= {
            "member_old", "member_new", "cfg_epoch", "cfg_pend",
            "log_cfg", "mb.ent_cfg", "metric.joint_ticks",
        }
    if not (cfg.reconfig and cfg.compaction):
        # The snapshot config context travels (and advances) only when both
        # the config plane AND compaction are live.
        inv |= {
            "base_mold", "base_pend", "base_epoch",
            "mb.req_base_mold", "mb.req_base_pend", "mb.req_base_epoch",
        }
    if not (cfg.leader_transfer and (cfg.reconfig or cfg.read_lease)):
        # The disruptive-RequestVote override is written only when a denial
        # gate exists to read it (transfer x [reconfig | lease]).
        inv |= {"mb.req_disrupt"}
    if not cfg.leader_transfer:
        inv |= {"xfer_to", "mb.xfer_tgt", "metric.xfer_fired"}
    if not cfg.read_index:
        # The read slot AND its RunMetrics accumulators: scan._add_gated
        # skips the fold when the kernel emits host-constant zeros, so the
        # metric legs are var-identity passthroughs too.
        inv |= {
            "read_idx", "read_tick", "read_acks",
            "metric.reads_served", "metric.read_lat_sum", "metric.read_hist",
        }
    if not cfg.read_lease:
        # The lease staleness anchor is dead weight on plain ReadIndex
        # configs too -- only the lease gate maintains it (and the
        # lease-read counter with it).
        inv |= {"read_fr", "metric.lease_reads"}
    if not cfg.durable_storage:
        # Durable storage plane off (raft_sim_tpu/storage): the watermark
        # triple AND its RunMetrics lag accumulators are dead weight --
        # scan's gated folds skip them when the kernel emits host-constant
        # zeros.
        inv |= {
            "dur_len", "dur_term", "dur_vote",
            "metric.fsync_lag_sum", "metric.fsync_lag_max",
            "metric.fsync_stalls", "metric.torn_lost",
        }
    if not (cfg.durable_storage and cfg.durable_acks):
        # Late vote responses exist only behind the section-3.8 grant gate.
        inv |= {"metric.late_grants"}
    if not (cfg.track_offer_ticks and cfg.log_capacity > log_ops._TILE):
        # The latency pass has a window form only on a ring of more than
        # one tile (models/raft_batched.offer_latency_b).
        inv |= {"metric.lat_ring_ticks"}
    return inv


def scenario_genome_leaves() -> list[tuple[str, str]]:
    """(leaf name, dtype) of the ScenarioGenome fields, in field order -- the
    scenario engine's input-side surface. Single-sourced here so the traffic
    audit (`tools/traffic_audit.py --scenario`) prices exactly the leaves the
    genome path reads, and a genome field add/rename shows up as an audit
    diff instead of silent unpriced traffic. Each leaf is `[S]` per cluster
    (uint32 thresholds, int32 cadences/spans; 4 bytes either way)."""
    from raft_sim_tpu.scenario.genome import ScenarioGenome, leaf_dtype

    return [
        (f, jnp.dtype(leaf_dtype(f)).name) for f in ScenarioGenome._fields
    ]


def trace_carry_leaf_names() -> list[str]:
    """Leaf names of the TRACE program's tick-loop carry: the (state,
    metrics) template, the window first-violation tick, then the trace
    window/persist legs (trace/ring.py) -- so the cost model's
    `cost-carry-bytes` findings name `trace.ev_kind`, not `extra17`, when a
    trace leg widens."""
    from raft_sim_tpu.trace.ring import TracePersist, TraceWin

    names = carry_leaf_names()
    names.append("first_viol")
    names.extend(f"trace.{f}" for f in TraceWin._fields)
    names.extend(f"trace.{f}" for f in TracePersist._fields)
    return names


def carry_leaf_names() -> list[str]:
    """Flattened leaf names of the batch-minor scan carry (state, metrics), in
    pytree flatten order -- the order of the scan body jaxpr's carry vars.
    State fields bare, mailbox fields `mb.<f>`, metrics `metric.<f>`."""
    names = []
    for f in ClusterState._fields:
        if f == "mailbox":
            names.extend(f"mb.{m}" for m in Mailbox._fields)
        else:
            names.append(f)
    names.extend(f"metric.{m}" for m in RunMetrics._fields)
    return names


# The fingerprint's canonical config: pinned EXPLICITLY (never defaults, so a
# default change cannot silently move the fingerprint) in the int8 index tier.
# Dtype-policy changes in other tiers ride the same code paths, and the
# version log shows every historical bump changed names, rank, or a dtype
# visible in this tier (v8/v13/v17/v18 were exactly such dtype moves).
_FINGERPRINT_CFG = dict(n_nodes=5, log_capacity=32, max_entries_per_rpc=4)


def schema_fingerprint() -> str:
    """sha256 over the serialized-pytree schema: the ordered field names of
    (ClusterState, Mailbox, RunMetrics) -- the exact structures
    `utils/checkpoint.save` iterates -- plus each leaf's rank and dtype under
    the pinned canonical config. Any field add/remove/rename/reorder, any
    rank change, and any dtype move (the v8/v13/v17/v18 class of bump)
    changes this, and the pin in `checkpoint._SCHEMA_FINGERPRINT` must be
    refreshed ALONGSIDE a _FORMAT_VERSION bump (rule `checkpoint-version`)."""
    from raft_sim_tpu.sim import scan

    cfg = RaftConfig(**_FINGERPRINT_CFG)
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(lambda k: rst_types.init_state(cfg, k), key)
    metrics = jax.eval_shape(scan.init_metrics)
    rows = []
    for f in ClusterState._fields:
        if f == "mailbox":
            continue
        v = getattr(state, f)
        rows.append((f, len(v.shape), str(v.dtype)))
    for f in Mailbox._fields:
        v = getattr(state.mailbox, f)
        rows.append((f"mb.{f}", len(v.shape), str(v.dtype)))
    for f in RunMetrics._fields:
        v = getattr(metrics, f)
        rows.append((f"metric.{f}", len(v.shape), str(v.dtype)))
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


# ---------------------------------------------------- donation entry points

class DonatingEntry:
    """One jitted entry point covered by the donation policy.

    `label` is the dotted name Pass C's donation golden pins; `path`/`func`
    locate the definition for the Pass D dataflow lint; `donated_param` is the
    parameter name `donate_argnums` targets (None for input-preserving
    entries); `loops` names the standing-loop functions that call it -- the
    scopes where a retained reference to the donated argument is a
    use-after-donate race; `cost_pinned` says whether the entry appears in the
    Pass C golden (the trace variant shares `_chunk_t_donate`'s donation
    contract but is not separately pinned, so adding it cannot stale the
    golden)."""

    def __init__(self, label: str, path: str, func: str,
                 donated_param: str | None, expected: str,
                 loops: tuple[str, ...] = (), cost_pinned: bool = True):
        self.label = label
        self.path = path
        self.func = func
        self.donated_param = donated_param
        self.expected = expected
        self.loops = loops
        self.cost_pinned = cost_pinned

    def __repr__(self):  # test/debug readability only
        return f"DonatingEntry({self.label!r}, {self.expected!r})"


def donating_entry_points() -> tuple[DonatingEntry, ...]:
    """The single source of truth for which entry points donate their carry.

    Pass C (`cost_model.entry_points`) reads labels + expectations from here
    and pins the lowering-level aliasing marks; Pass D
    (`race_audit`/`sanitizer`) reads paths + donated parameter names from here
    to drive the use-after-donate dataflow lint and the runtime
    donation-poison harness. Adding a donating entry point in code without
    registering it here fails Pass D's coverage check (rule
    `race-unregistered-donation`)."""
    return (
        DonatingEntry(
            "sim.chunked._chunk_donate", "raft_sim_tpu/sim/chunked.py",
            "_chunk_donate", "state", "donated", loops=("run_chunked",)),
        DonatingEntry(
            "sim.telemetry._chunk_t_donate", "raft_sim_tpu/sim/telemetry.py",
            "_chunk_t_donate", "state", "donated",
            loops=("run_chunked_telemetry",)),
        DonatingEntry(
            "sim.telemetry._chunk_t_donate_trace",
            "raft_sim_tpu/sim/telemetry.py", "_chunk_t_donate_trace", "state",
            "donated", loops=("run_chunked_telemetry",), cost_pinned=False),
        DonatingEntry(
            "serve.loop._serve_chunk", "raft_sim_tpu/serve/loop.py",
            "_serve_chunk", "state", "donated",
            loops=("_dispatch", "serve", "drain")),
        DonatingEntry(
            "sim.chunked._chunk", "raft_sim_tpu/sim/chunked.py",
            "_chunk", None, "not-donated"),
        DonatingEntry(
            "sim.scan.simulate", "raft_sim_tpu/sim/scan.py",
            "simulate", None, "not-donated"),
        DonatingEntry(
            "sim.scan.simulate_scenario", "raft_sim_tpu/sim/scan.py",
            "simulate_scenario", None, "not-donated"),
    )


def expected_checkpoint_keys() -> set[str]:
    """The npz key set `checkpoint.save` must produce for its field sets --
    derived the same way save() derives it, so a serializer change that
    drops or renames a key diverges from this and the round-trip check
    (rule `checkpoint-serialization`) names it."""
    keys = {"__version__", "seed", "config_json", "scenario_json", "keys"}
    keys |= {f"state_{f}" for f in ClusterState._fields if f != "mailbox"}
    keys |= {f"mb_{f}" for f in Mailbox._fields}
    keys |= {f"metrics_{f}" for f in RunMetrics._fields}
    return keys
