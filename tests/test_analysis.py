"""The static analyzer's own tests: every rule must fire on a seeded
violation and stay silent on the idiomatic forms, the waiver engine must
match/mark/report-stale exactly, and the current tree must gate clean.

The negative seeds here are the acceptance proof the analyzer is real: an
injected float upcast is caught by the jaxpr pass (rules float-op and
plane-widening), and an unbumped checkpoint field change is caught by the AST
pass (rule checkpoint-version) -- neither relies on the violation happening to
break a runtime parity test.

Everything here is lowering/AST only -- no scan compiles -- so the module
stays cheap inside the tier-1 budget (the heaviest items are eval_shape
traces of the step kernel).
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from raft_sim_tpu.analysis import ast_lint, jaxpr_audit, policy, run
from raft_sim_tpu.analysis import findings as F
from raft_sim_tpu.utils import checkpoint
from raft_sim_tpu.utils.config import PRESETS

CFG3 = PRESETS["config3"][0]


# ------------------------------------------------------------- AST pass rules


def test_traced_branch_fires_on_seeded_kernel():
    src = (
        "import jax.numpy as jnp\n"
        "from raft_sim_tpu.types import ClusterState\n"
        "def step(cfg, s: ClusterState, x):\n"
        "    t = s.term + 1\n"
        "    if t.max() > 3:\n"            # Python branch on traced value
        "        return s\n"
        "    while s.commit_index.any():\n"  # and a traced while
        "        pass\n"
        "    return s\n"
    )
    got = ast_lint.lint_source(src, "raft_sim_tpu/models/bad.py")
    rules = [f.rule for f in got]
    assert rules.count("traced-branch") == 2
    assert {f.line for f in got} == {5, 7}


def test_traced_branch_ignores_static_config_branches():
    src = (
        "import jax.numpy as jnp\n"
        "def step(cfg, n_ticks):\n"
        "    if cfg.pre_vote:\n"
        "        k = 2\n"
        "    while n_ticks > 0:\n"
        "        n_ticks -= 1\n"
        "    return k\n"
    )
    assert ast_lint.lint_source(src, "raft_sim_tpu/models/ok.py") == []


def test_float_literal_fires_in_hot_path_only():
    src = "import jax.numpy as jnp\ndef f(x):\n    return jnp.maximum(x, 1.5)\n"
    got = ast_lint.lint_source(src, "raft_sim_tpu/ops/bad.py")
    assert [f.rule for f in got] == ["float-literal"]
    # jax.random probabilities are the documented exception...
    src_ok = "import jax\ndef f(k, n):\n    return jax.random.bernoulli(k, 0.5, (n,))\n"
    assert ast_lint.lint_source(src_ok, "raft_sim_tpu/sim/ok.py") == []
    # ...and non-hot-path packages are out of scope for this rule.
    assert ast_lint.lint_source(src, "raft_sim_tpu/utils/ok.py") == []


# ------------------------------------------------------------ jaxpr pass rules


def _plane(n=5, dtype=jnp.int8):
    return jax.ShapeDtypeStruct((n, n), dtype)


def test_float_upcast_caught_by_jaxpr_pass():
    # The seeded negative: an [N, N] protocol plane upcast to float (a mean).
    bad = jax.make_jaxpr(lambda p: p.astype(jnp.float32).mean())(_plane())
    assert any(f.rule == "float-op"
               for f in jaxpr_audit.check_float_ops("jaxpr:neg/step", bad))


def test_plane_widening_caught_and_reduction_exempt():
    widen = jax.make_jaxpr(lambda p: p.astype(jnp.int32) * 2)(_plane())
    got = jaxpr_audit.check_plane_widening("jaxpr:neg/step", widen, CFG3)
    assert [f.rule for f in got] == ["plane-widening"]
    # Widening straight into a reduction is the one legal form.
    ok = jax.make_jaxpr(lambda p: jnp.sum(p.astype(jnp.int32)))(_plane())
    assert jaxpr_audit.check_plane_widening("jaxpr:ok/step", ok, CFG3) == []


def test_step_kernels_are_float_free_and_unwidened():
    jx = jaxpr_audit.step_jaxpr(CFG3)
    assert jaxpr_audit.check_float_ops("jaxpr:config3", jx) == []
    assert jaxpr_audit.check_plane_widening("jaxpr:config3", jx, CFG3) == []


def test_carry_passthrough_fires_on_rewritten_invariant_leg():
    # Audit a pre_vote program under a no-pre-vote policy: heard_clock and
    # mb.pv_grant ARE rewritten there, which is exactly what the rule must
    # report for a config whose policy says they are loop-invariant.
    cfg_pv = dataclasses.replace(CFG3, pre_vote=True)
    jx = jaxpr_audit.scan_jaxpr(cfg_pv)
    got = jaxpr_audit.check_carry_passthrough("jaxpr:neg/simulate", jx, CFG3)
    names = {f.message.split("'")[1] for f in got if f.rule == "carry-passthrough"}
    assert names == {"heard_clock", "mb.pv_grant"}
    # And the real pairing is clean.
    assert jaxpr_audit.check_carry_passthrough(
        "jaxpr:config3/simulate", jaxpr_audit.scan_jaxpr(CFG3), CFG3
    ) == []


def test_invariant_leaves_match_lowered_scan():
    # The policy list traffic_audit prices and the rule enforces must agree
    # with the lowered program for a feature-rich tier too.
    cfg6, _ = PRESETS["config6"]
    assert jaxpr_audit.check_carry_passthrough(
        "jaxpr:config6/simulate", jaxpr_audit.scan_jaxpr(cfg6), cfg6
    ) == []


def test_recompile_fork_guard():
    # pre_vote genuinely forks the program: the guard must see it on BOTH the
    # plain scan and the scenario (genome-path) scan ...
    got = jaxpr_audit.check_recompile_forks((("config3", {"pre_vote": True}),))
    assert [f.rule for f in got] == ["recompile-fork"] * 4
    assert {f.path for f in got} == {
        "jaxpr:config3/simulate", "jaxpr:config3/scenario_simulate",
        "jaxpr:config3/serve_simulate", "jaxpr:config3/trace_simulate",
    }
    # ... while a tuning-only change must not (one standing pair, cheap) --
    # and on the scenario program that includes the fault knobs themselves:
    # genomes exist so fault sweeps are data, never compiles.
    assert jaxpr_audit.check_recompile_forks(
        (("config2", {"client_interval": 12}),)
    ) == []


def test_large_constant_rule():
    import numpy as np

    table = jnp.asarray(np.arange(50_000, dtype=np.int32))
    bad = jax.make_jaxpr(lambda i: table[i])(jax.ShapeDtypeStruct((), jnp.int32))
    assert [f.rule for f in jaxpr_audit.check_large_constants("jaxpr:neg", bad)] \
        == ["large-constant"]


# -------------------------------------------------- contract + schema rules


def test_types_comments_parse_and_hold():
    specs, problems = policy.parse_types_comments()
    assert problems == []
    # Full field coverage: every field of the four structures has a contract.
    # v22: +member_old/member_new/cfg_epoch/cfg_pend (joint-consensus
    # membership plane), +xfer_to (TimeoutNow), +read_idx/read_tick/read_acks
    # (ReadIndex slot); v24: +log_cfg (config-entry plane) +base_mold/
    # base_pend/base_epoch (snapshot config context); v25: +dur_len/dur_term/
    # dur_vote (storage plane's durable watermarks)
    assert len(specs["ClusterState"]) == 41  # v23: +read_fr (lease anchor)
    # v24: +req_disrupt +ent_cfg +req_base_mold/req_base_pend/req_base_epoch
    assert len(specs["Mailbox"]) == 28  # v22: +xfer_tgt
    # v25: +fsync_fire/torn_drop (disk-fault lattice draws); v27: +fsync_stall
    assert len(specs["StepInputs"]) == 14  # v22: +reconfig/transfer/read cmds
    # v25: +fsync_lag_sum/fsync_lag_max (durability-lag SLI counters);
    # v26: +joint_ticks/xfer_fired/lease_reads (plane counters);
    # v27: +fsync_stalls/torn_lost/late_grants (storage-plane counters);
    # v28: +lat_ring_ticks (latency-pass fallback ticks)
    assert len(specs["StepInfo"]) == 29  # v23: +viol_read_stale
    assert ast_lint.check_dtype_comments() == []


def test_dtype_comment_rule_fires_on_drift():
    src = (
        "class ClusterState(NamedTuple):\n"
        "    role: jax.Array  # [N] int8\n"  # actually int32
    )
    specs, problems = policy.parse_types_comments(
        "import jax\nfrom typing import NamedTuple\n" + src
    )
    assert problems == []
    spec = specs["ClusterState"]["role"]
    assert policy.resolve_dtypes(spec, CFG3) == {jnp.dtype(jnp.int8)}
    state, _, _ = policy.state_avals(CFG3)
    assert state.role.dtype not in policy.resolve_dtypes(spec, CFG3)


def test_checkpoint_version_rule(monkeypatch):
    assert ast_lint.check_checkpoint_version() == []
    # Seeded negative: a field change that was not pinned (hash drifts).
    monkeypatch.setattr(
        checkpoint, "_SCHEMA_FINGERPRINT",
        (checkpoint._FORMAT_VERSION, "deadbeefdeadbeef"),
    )
    got = ast_lint.check_checkpoint_version()
    assert [f.rule for f in got] == ["checkpoint-version"]
    assert "bump _FORMAT_VERSION" in got[0].message
    # Second negative: fingerprint refreshed but version pin left behind.
    monkeypatch.setattr(
        checkpoint, "_SCHEMA_FINGERPRINT", (18, policy.schema_fingerprint())
    )
    got = ast_lint.check_checkpoint_version()
    assert [f.rule for f in got] == ["checkpoint-version"]
    assert "refresh the pin alongside" in got[0].message


def test_checkpoint_serialization_round_trip():
    assert ast_lint.check_checkpoint_serialization() == []


def test_checkpoint_version_is_exported():
    import raft_sim_tpu

    assert raft_sim_tpu.CHECKPOINT_FORMAT_VERSION == checkpoint._FORMAT_VERSION
    assert checkpoint.FORMAT_VERSION == checkpoint._FORMAT_VERSION


def test_checkpoint_mismatch_error_names_versions(tmp_path, monkeypatch):
    from raft_sim_tpu.sim.scan import init_metrics_batch
    from raft_sim_tpu.types import init_batch
    from raft_sim_tpu.utils.config import RaftConfig

    cfg = RaftConfig(n_nodes=2, log_capacity=4, max_entries_per_rpc=1)
    key = jax.random.key(0)
    path = checkpoint.save(
        str(tmp_path / "ck"), cfg, init_batch(cfg, key, 1),
        jax.random.split(key, 1), init_metrics_batch(1),
    )
    monkeypatch.setattr(checkpoint, "_FORMAT_VERSION", checkpoint._FORMAT_VERSION + 1)
    with pytest.raises(ValueError) as ex:
        checkpoint.load(path)
    msg = str(ex.value)
    assert f"written as format v{checkpoint._FORMAT_VERSION - 1}" in msg
    assert f"reads v{checkpoint._FORMAT_VERSION}" in msg
    assert "version log" in msg


# ------------------------------------------------- findings + waiver engine


def _finding(rule="traced-branch", path="raft_sim_tpu/sim/x.py", msg="boom in f()"):
    return F.Finding(rule=rule, path=path, message=msg, line=3)


def test_waiver_matching_and_stale_reporting():
    found = [_finding(), _finding(path="raft_sim_tpu/sim/y.py")]
    waivers = [
        {"rule": "traced-branch", "path": "raft_sim_tpu/sim/x.py",
         "contains": "f()", "reason": "host-side"},
        {"rule": "float-op", "path": "nowhere.py", "reason": "stale"},
    ]
    unused = F.apply_waivers(found, waivers)
    assert found[0].waived and found[0].waiver_reason == "host-side"
    assert not found[1].waived
    assert unused == [waivers[1]]
    # `contains` mismatch must not waive.
    f2 = [_finding(msg="other message")]
    assert F.apply_waivers(f2, [waivers[0]]) == [waivers[0]]
    assert not f2[0].waived


def test_report_schema_validates_and_catches_corruption():
    found = [_finding()]
    F.apply_waivers(found, [])
    doc = F.report(found)
    assert F.validate(doc) == []
    assert F.validate(json.loads(json.dumps(doc))) == []  # survives JSON round trip
    bad = dict(doc, n_unwaived=0)
    assert F.validate(bad) != []
    bad2 = dict(doc, findings=[{k: v for k, v in doc["findings"][0].items()
                                if k != "rule"}])
    assert F.validate(bad2) != []


def test_waiver_file_format_errors_are_loud(tmp_path):
    p = tmp_path / "w.json"
    p.write_text("{not json")
    entries, problems = F.load_waivers(str(p))
    assert entries == [] and problems
    p.write_text(json.dumps({"schema_version": 1, "waivers": [{"rule": "r"}]}))
    entries, problems = F.load_waivers(str(p))
    assert problems  # missing path/reason
    # A non-dict entry is a reported problem, never a crash.
    p.write_text(json.dumps({"schema_version": 1, "waivers": ["oops"]}))
    entries, problems = F.load_waivers(str(p))
    assert entries == [] and any("must be an object" in m for m in problems)
    assert F.load_waivers(str(tmp_path / "missing.json")) == ([], [])


@pytest.mark.slow  # budget re-tier (PR 12): gate integrity for PARTIAL
# runs (a --ast-only run must not mark jaxpr-pass waivers stale) -- the
# full-run staleness path stays tier-1 via test_tree_gates_clean and the
# CI check job runs --all on every push, so a regression here cannot land
# silently; the partial-run permutation (two full pass invocations) rides
# the slow tier.
def test_partial_run_does_not_report_other_passes_waivers_stale():
    # The standing waivers belong to the AST pass; a jaxpr-only run must not
    # condemn them as stale (they were never given a chance to match).
    found, unused, problems, timings = run.run_all(
        do_ast=False, do_cost=False, do_race=False, do_range=False,
        config_names=("config3",)
    )
    assert set(timings) == {"jaxpr"}
    assert problems == []
    assert unused == []
    assert [f for f in found if not f.waived] == []


def test_structural_hash_sees_params_not_literals():
    x = jax.ShapeDtypeStruct((5, 5), jnp.int32)
    h0 = jaxpr_audit.structural_hash(jax.make_jaxpr(lambda p: jnp.sum(p, axis=0))(x))
    h1 = jaxpr_audit.structural_hash(jax.make_jaxpr(lambda p: jnp.sum(p, axis=1))(x))
    # Same avals everywhere on a square input; only the reduce axes param
    # differs -- the hash must still fork.
    assert h0 != h1
    # Literal-only differences must NOT fork.
    g0 = jaxpr_audit.structural_hash(jax.make_jaxpr(lambda p: p + 3)(x))
    g1 = jaxpr_audit.structural_hash(jax.make_jaxpr(lambda p: p + 7)(x))
    assert g0 == g1


# --------------------------------------------------------------- gate status


def test_tree_gates_clean_ast_pass():
    """The merged tree has zero unwaived AST/contract findings (the jaxpr and
    cost passes run as the tools/check.py CI gate; their per-rule coverage on
    the real kernels is pinned by the tests above and by
    tests/test_cost_model.py)."""
    found, unused, problems, _ = run.run_all(
        do_jaxpr=False, do_cost=False, do_range=False)
    assert problems == []
    assert unused == [], f"stale waivers: {unused}"
    unwaived = [f for f in found if not f.waived]
    assert unwaived == [], "\n".join(
        f"{f.rule} {f.location()}: {f.message}" for f in unwaived
    )
