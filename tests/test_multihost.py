"""Multi-host execution proof in CI (SURVEY.md section 5, distributed backend;
the reference's deployment shape is N cooperating OS processes, core.clj:197-203).

Runs tools/multihost_check.py: two local processes (CPU backend, 4 virtual
devices each) form a JAX distributed cluster over a localhost coordinator, run
`simulate_sharded` on the global 8-device mesh, and the process-0-gathered
metrics must match a single-process 8-device run bit for bit."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_multichip_artifact_schema():
    """The standing MULTICHIP row must validate (multichip-v2: throughput,
    per-device bytes, parity hash) and the historical rc-only stubs must be
    reported as legacy, not silently passed."""
    from raft_sim_tpu.utils.telemetry_sink import validate_multichip

    assert validate_multichip(os.path.join(REPO, "MULTICHIP_r06.json")) == []
    errs = validate_multichip(os.path.join(REPO, "MULTICHIP_r01.json"))
    assert errs and "legacy" in errs[0], errs


def test_two_process_cluster_matches_single_process():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "multihost_check.py")],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["match"] is True
    assert verdict["n_processes"] == 2
    assert verdict["global_devices"] == 8
    assert verdict["violations"] == 0
    # the workload did real work on the global mesh
    assert verdict["summary"]["total_cmds"] > 0
    assert verdict["summary"]["p50_commit_latency"] is not None
