"""Pass B: AST rules over `raft_sim_tpu/` enforcing the repo's source idioms,
plus the schema cross-checks that tie `types.py` comments and the checkpoint
version pin to the live structures.

Source rules (pure `ast`, no execution):

  traced-branch    no Python `if`/`while` on traced values in `models/` and
                   `sim/`. The kernels are `jnp.where` lattices by design
                   (models/raft_batched.py docstring); a Python branch on a tracer
                   either crashes under jit or -- worse -- silently bakes one
                   trace-time path. Taint heuristic: parameters annotated with
                   traced types (ClusterState, StepInputs, Mailbox, StepInfo,
                   RunMetrics, FlightRecorder, jax.Array) are traced; taint
                   propagates through assignment, tuple unpacking, attribute
                   and subscript access, and the results of jnp./lax. calls.
                   Branches on static config (`if cfg.pre_vote:`) never taint.
  float-literal    no bare float literal as an argument of a jnp./lax. call in
                   the hot-path packages (`models/`, `sim/`, `ops/`): the
                   protocol path is integer-only, and a stray `1.0` promotes a
                   whole lattice. `jax.random` calls (probabilities) are the
                   documented exception and are not matched.

Contract rules (cheap execution -- eval_shape and one tiny npz round trip):

  dtype-comment            the `# [shape] dtype` field comments in types.py
                           parse (policy.parse_types_comments) and match the
                           ACTUAL dtypes/ndims `init_state`/`make_inputs`/
                           `raft_batched.step_one` produce, across the policy tiers
                           (int8/int16 index planes, compaction's int32).
  checkpoint-version       the serialized-pytree field sets hash to the pin in
                           `checkpoint._SCHEMA_FINGERPRINT`, and the pin's
                           version equals `_FORMAT_VERSION`: changing
                           ClusterState/Mailbox/RunMetrics fields without
                           bumping the format version is caught here.
  checkpoint-serialization a real save() round trip's npz key set equals the
                           key set derived from the NamedTuple fields (pytree
                           fields vs serialized keys can never drift).
"""

from __future__ import annotations

import ast
import os
import tempfile

import jax
import numpy as np

from raft_sim_tpu.analysis import policy
from raft_sim_tpu.analysis.findings import Finding
from raft_sim_tpu.utils.config import PRESETS, RaftConfig

# Every rule slug this pass can emit (run.run_all scopes stale-waiver
# detection to the passes that actually ran).
RULES = frozenset({
    "traced-branch", "float-literal", "parse-error", "dtype-comment",
    "checkpoint-version", "checkpoint-serialization",
})

# Packages whose functions must not branch on traced values.
TRACED_BRANCH_DIRS = ("models", "sim", "trace")
# Packages where float literals must not enter jnp/lax calls.
FLOAT_LITERAL_DIRS = ("models", "sim", "ops", "trace")

# Parameter annotations that mark a value as traced.
TRACED_ANNOTATIONS = {
    "ClusterState", "StepInputs", "Mailbox", "StepInfo", "RunMetrics",
    "FlightRecorder", "WindowRecord", "Array", "jax.Array",
    "TickEvents", "TraceWin", "TracePersist",
}

# Config tiers the dtype-comment contract is verified against: the int8 index
# tier (config3, CAP 32), the int16 tier (config1, CAP 2048), compaction's
# int32 + redirect pipeline (config6r), and the wide cluster (config5).
COMMENT_CHECK_CONFIGS = ("config3", "config1", "config6r", "config5")


def _ann_name(node) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return f"{_ann_name(node.value)}.{node.attr}"
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.split("[")[0]
    return ""


def _root_name(node) -> str:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _targets(node):
    """Flat Name targets of an assignment target (handles tuple unpacking)."""
    if isinstance(node, ast.Name):
        yield node.id
    elif isinstance(node, (ast.Tuple, ast.List)):
        for elt in node.elts:
            yield from _targets(elt)
    elif isinstance(node, ast.Starred):
        yield from _targets(node.value)


class _FunctionLint:
    """Taint analysis + branch check for one function body."""

    def __init__(self, fn: ast.FunctionDef, path: str, findings: list[Finding]):
        self.fn = fn
        self.path = path
        self.findings = findings
        self.tainted: set[str] = set()
        args = fn.args
        for a in (*args.posonlyargs, *args.args, *args.kwonlyargs):
            if a.annotation is not None and (
                _ann_name(a.annotation).split(".")[-1] in TRACED_ANNOTATIONS
                or _ann_name(a.annotation) in TRACED_ANNOTATIONS
            ):
                self.tainted.add(a.arg)

    def _expr_tainted(self, node) -> bool:
        """An expression is traced if it references a tainted name or calls
        into jnp/lax (whose results are arrays by construction)."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in self.tainted:
                return True
            if isinstance(sub, ast.Call) and _root_name(sub.func) in ("jnp", "lax"):
                return True
        return False

    def run(self):
        # Two propagation sweeps handle the (rare) use-before-later-taint
        # ordering inside straight-line kernel code.
        for _ in range(2):
            for node in ast.walk(self.fn):
                if isinstance(node, ast.Assign) and self._expr_tainted(node.value):
                    for tgt in node.targets:
                        self.tainted.update(_targets(tgt))
                elif isinstance(node, ast.AugAssign) and self._expr_tainted(node.value):
                    self.tainted.update(_targets(node.target))
        for node in ast.walk(self.fn):
            if isinstance(node, (ast.If, ast.While)) and self._expr_tainted(node.test):
                kind = "if" if isinstance(node, ast.If) else "while"
                names = sorted(_names_in(node.test) & self.tainted) or ["<jnp call>"]
                self.findings.append(Finding(
                    rule="traced-branch",
                    path=self.path,
                    line=node.lineno,
                    message=(
                        f"Python `{kind}` on traced value(s) {names} in "
                        f"{self.fn.name}(): kernels must use jnp.where/"
                        "lax.cond lattices, never Python control flow on "
                        "array values (models/raft_batched.py docstring)"
                    ),
                ))


def _lint_traced_branches(tree: ast.AST, path: str, findings: list[Finding]):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _FunctionLint(node, path, findings).run()


def _lint_float_literals(tree: ast.AST, path: str, findings: list[Finding]):
    def scan_args(node, call_line):
        """Float constants in a call's argument subtree, not descending into
        nested calls rooted elsewhere (jax.random probabilities are legal)."""
        if isinstance(node, ast.Call) and _root_name(node.func) not in ("jnp", "lax"):
            return
        if isinstance(node, ast.Constant) and isinstance(node.value, float):
            findings.append(Finding(
                rule="float-literal",
                path=path,
                line=getattr(node, "lineno", call_line),
                message=(
                    f"bare float literal {node.value!r} entering a jnp/lax "
                    "call in a hot-path module: the protocol path is "
                    "integer-only (types.py); name the constant and cast "
                    "explicitly if a float is genuinely intended"
                ),
            ))
            return
        for child in ast.iter_child_nodes(node):
            scan_args(child, call_line)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _root_name(node.func) in ("jnp", "lax"):
            for arg in (*node.args, *(kw.value for kw in node.keywords)):
                scan_args(arg, node.lineno)


def lint_source(source: str, path: str) -> list[Finding]:
    """Both source rules over one file's text. `path` decides which rules
    apply (TRACED_BRANCH_DIRS / FLOAT_LITERAL_DIRS membership) and anchors
    the findings."""
    try:
        tree = ast.parse(source)
    except SyntaxError as ex:
        return [Finding(rule="parse-error", path=path, line=ex.lineno or 0,
                        message=f"does not parse: {ex.msg}")]
    parts = path.replace("\\", "/").split("/")
    findings: list[Finding] = []
    if any(d in parts for d in TRACED_BRANCH_DIRS):
        _lint_traced_branches(tree, path, findings)
    if any(d in parts for d in FLOAT_LITERAL_DIRS):
        _lint_float_literals(tree, path, findings)
    return findings


def lint_tree(root: str) -> list[Finding]:
    """Run the source rules over every .py file under `root` (the
    raft_sim_tpu package dir), paths reported repo-relative."""
    findings: list[Finding] = []
    repo = os.path.dirname(os.path.abspath(root.rstrip("/")))
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if not d.startswith("__pycache__"))
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            rel = os.path.relpath(full, repo)
            with open(full) as f:
                findings.extend(lint_source(f.read(), rel))
    return findings


# ------------------------------------------------------------ contract rules


def check_dtype_comments() -> list[Finding]:
    """Rule dtype-comment: the parsed types.py field contracts hold against
    the actual structures for every policy tier in COMMENT_CHECK_CONFIGS."""
    specs, problems = policy.parse_types_comments()
    findings = [
        Finding(rule="dtype-comment", path="raft_sim_tpu/types.py", line=ln,
                message=msg)
        for ln, msg in problems
    ]
    for name in COMMENT_CHECK_CONFIGS:
        cfg, _ = PRESETS[name]
        state, inputs, info = policy.state_avals(cfg)
        actual = {
            "ClusterState": {f: getattr(state, f) for f in state._fields if f != "mailbox"},
            "Mailbox": {f: getattr(state.mailbox, f) for f in state.mailbox._fields},
            "StepInputs": {f: getattr(inputs, f) for f in inputs._fields},
            "StepInfo": {f: getattr(info, f) for f in info._fields},
        }
        for cls, fields in actual.items():
            for fname, aval in fields.items():
                spec = specs.get(cls, {}).get(fname)
                if spec is None:
                    findings.append(Finding(
                        rule="dtype-comment",
                        path="raft_sim_tpu/types.py",
                        message=(
                            f"{cls}.{fname} has no parseable `# [shape] dtype` "
                            "comment: the dtype contract must stay "
                            "machine-readable (analysis/policy.py)"
                        ),
                    ))
                    continue
                allowed = policy.resolve_dtypes(spec, cfg)
                if aval.dtype not in allowed:
                    findings.append(Finding(
                        rule="dtype-comment",
                        path="raft_sim_tpu/types.py",
                        line=spec.line,
                        message=(
                            f"{cls}.{fname} is {aval.dtype} under {name} but "
                            f"the comment declares {'/'.join(spec.dtypes)}"
                        ),
                    ))
                if spec.ndim is not None and len(aval.shape) != spec.ndim:
                    findings.append(Finding(
                        rule="dtype-comment",
                        path="raft_sim_tpu/types.py",
                        line=spec.line,
                        message=(
                            f"{cls}.{fname} has ndim {len(aval.shape)} under "
                            f"{name} but the comment declares ndim {spec.ndim}"
                        ),
                    ))
    return _dedupe(findings)


def _dedupe(findings: list[Finding]) -> list[Finding]:
    seen, out = set(), []
    for f in findings:
        key = (f.rule, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def check_checkpoint_version() -> list[Finding]:
    """Rule checkpoint-version: the field-set fingerprint matches the pin and
    the pin names the current format version."""
    from raft_sim_tpu.utils import checkpoint

    path = "raft_sim_tpu/utils/checkpoint.py"
    out = []
    pin_version, pin_hash = checkpoint._SCHEMA_FINGERPRINT
    actual = policy.schema_fingerprint()
    if actual != pin_hash:
        out.append(Finding(
            rule="checkpoint-version",
            path=path,
            message=(
                f"serialized field sets hash to {actual} but "
                f"_SCHEMA_FINGERPRINT pins {pin_hash}: a ClusterState/Mailbox/"
                "RunMetrics field changed -- bump _FORMAT_VERSION (append a "
                "version-log line) and refresh the pin"
            ),
        ))
    if pin_version != checkpoint._FORMAT_VERSION:
        out.append(Finding(
            rule="checkpoint-version",
            path=path,
            message=(
                f"_SCHEMA_FINGERPRINT pins version {pin_version} but "
                f"_FORMAT_VERSION is {checkpoint._FORMAT_VERSION}: refresh "
                "the pin alongside the version bump"
            ),
        ))
    return out


def check_checkpoint_serialization() -> list[Finding]:
    """Rule checkpoint-serialization: one tiny real save()'s npz key set
    equals the key set derived from the NamedTuple fields, and load() round
    trips it."""
    from raft_sim_tpu.sim.scan import init_metrics_batch
    from raft_sim_tpu.types import init_batch
    from raft_sim_tpu.utils import checkpoint

    path = "raft_sim_tpu/utils/checkpoint.py"
    cfg = RaftConfig(n_nodes=2, log_capacity=4, max_entries_per_rpc=1)
    key = jax.random.key(0)
    state = init_batch(cfg, key, 1)
    keys = jax.random.split(key, 1)
    metrics = init_metrics_batch(1)
    out = []
    with tempfile.TemporaryDirectory() as td:
        fp = checkpoint.save(os.path.join(td, "ck"), cfg, state, keys, metrics)
        with np.load(fp) as z:
            actual = set(z.files)
        expected = policy.expected_checkpoint_keys()
        for missing in sorted(expected - actual):
            out.append(Finding(
                rule="checkpoint-serialization", path=path,
                message=f"save() omitted expected npz key {missing!r} "
                        "(pytree fields vs serialized keys must match)",
            ))
        for extra in sorted(actual - expected):
            out.append(Finding(
                rule="checkpoint-serialization", path=path,
                message=f"save() wrote unexpected npz key {extra!r} "
                        "(pytree fields vs serialized keys must match)",
            ))
        try:
            checkpoint.load(fp)
        except Exception as ex:  # any load failure is the finding itself
            out.append(Finding(
                rule="checkpoint-serialization", path=path,
                message=f"load() cannot read back save()'s output: {ex}",
            ))
    return out


def run_pass(package_root: str) -> list[Finding]:
    """The full AST + contract pass."""
    out = lint_tree(package_root)
    out.extend(check_dtype_comments())
    out.extend(check_checkpoint_version())
    out.extend(check_checkpoint_serialization())
    return out
