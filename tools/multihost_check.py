"""Multi-host execution proof: two cooperating OS processes, one global mesh.

The reference's deployment shape is N cooperating OS processes (`lein run 1 2 3`
etc., core.clj:197-203). This framework's multi-HOST analogue is pure
orchestration -- independent clusters shard over every chip of every host -- and
this tool proves the code path actually executes: it spawns TWO local processes
(CPU backend, 4 virtual devices each) that form a JAX distributed cluster over a
localhost coordinator, run `simulate_sharded` on the global 8-device mesh, gather
metrics to every process (`parallel.gather_metrics` -- the non-addressable-shard
path of `summarize`), and verifies process 0's result matches a single-process
8-device run of the same (cfg, seed, batch, ticks) BIT FOR BIT (the
device-layout-invariance property of tests/test_parallel.py, extended across
process boundaries).

Usage:
    python tools/multihost_check.py            # orchestrates everything; prints
                                               # one JSON verdict line, exit 0 on match
    python tools/multihost_check.py --out P    # ...and write the schema'd
                                               # MULTICHIP artifact (multichip-v2:
                                               # throughput, per-device bytes,
                                               # parity hash) to P -- the diffable
                                               # standing row, validated by
                                               # utils.telemetry_sink.validate_multichip

Internal modes (spawned by the orchestrator; fresh interpreters are required
because --xla_force_host_platform_device_count must precede backend init):
    _MH_MODE=child _MH_PID={0,1} _MH_PORT=...  distributed worker
    _MH_MODE=local                             single-process reference run
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:  # the artifact pricer imports raft_sim_tpu directly
    sys.path.insert(0, REPO)

# One meaty workload: faults + client traffic + invariants, riding the full
# round-4 surface (compaction ring + snapshot catch-up + 302 redirect routing).
CFG_KW = dict(
    n_nodes=5,
    log_capacity=16,
    compact_margin=4,
    client_interval=4,
    client_redirect=True,
    drop_prob=0.1,
    clock_skew_prob=0.1,
)
SEED, BATCH, TICKS = 0, 16, 200


def _run_and_dump() -> dict:
    """Run the sharded simulation on the (possibly multi-process) global mesh and
    return every RunMetrics field as lists, plus the fleet summary and a timed
    steady-state repeat (the first call pays the compile; the second, same
    program, is the throughput sample -- cluster-ticks/s)."""
    import time

    import jax
    import numpy as np

    from raft_sim_tpu import RaftConfig
    from raft_sim_tpu.parallel import gather_metrics, make_mesh, simulate_sharded, summarize

    cfg = RaftConfig(**CFG_KW)
    mesh = make_mesh()
    final, metrics = simulate_sharded(cfg, SEED, BATCH, TICKS, mesh)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    _, m2 = simulate_sharded(cfg, SEED, BATCH, TICKS, mesh)
    jax.block_until_ready(m2)
    wall = time.perf_counter() - t0
    summary = summarize(metrics)._asdict()  # exercises the gather path itself
    m = gather_metrics(metrics)
    fields = {f: np.asarray(v).tolist() for f, v in zip(m._fields, m)}
    return {"metrics": fields, "summary": summary,
            "throughput_ticks_per_s": round(BATCH * TICKS / wall, 1)}


def _per_device_bytes() -> float:
    """Pass C price of one device's cluster slice: (carry + inputs) padded
    bytes/tick per cluster x the local batch share (batch sharding moves no
    planes across devices, so per-device traffic is just the slice)."""
    from raft_sim_tpu import RaftConfig
    from raft_sim_tpu.analysis import cost_model, jaxpr_audit

    cfg = RaftConfig(**CFG_KW)
    local = BATCH // 8  # the global mesh is always 8 devices here
    cm = cost_model.carry_model(jaxpr_audit.scan_jaxpr(cfg), local)
    _, in_pad = cost_model.input_bytes(cfg, local)
    return round((cm["carry_padded"] + in_pad) * local, 1)


def _parity_hash(out: dict) -> str:
    """sha256 over the gathered metrics JSON: equal across processes iff the
    trajectories matched bit-for-bit."""
    import hashlib

    blob = json.dumps(out["metrics"], sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def child(pid: int, port: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from raft_sim_tpu.parallel import init_distributed

    got_pid = init_distributed(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    assert got_pid == pid
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4, jax.local_device_count()
    out = _run_and_dump()
    if pid == 0:
        print(json.dumps(out), flush=True)
    jax.distributed.shutdown()


def local() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() == 8, jax.device_count()
    print(json.dumps(_run_and_dump()), flush=True)


def _emit_artifact(out_path: str, verdict: dict, parity_hash: str,
                   throughput: float, reference: float, n_processes: int) -> None:
    doc = {
        "schema": "multichip-v2",  # telemetry_sink.MULTICHIP_SCHEMA
        "match": verdict["match"],
        "n_devices": 8,
        "n_processes": n_processes,
        "batch": BATCH,
        "ticks": TICKS,
        "violations": verdict["violations"],
        # Steady-state sample, cluster-ticks/s: the sharded run under test,
        # with the reference program's sample riding along for the overhead
        # diff. CPU rows are never roofline anchors (obs/reconcile rules).
        "throughput_ticks_per_s": throughput,
        "reference_ticks_per_s": reference,
        "per_device_bytes_per_tick": _per_device_bytes(),
        "parity_hash": parity_hash,
        "platform": "cpu",
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def _spawn(env, *, me: str):
    return subprocess.Popen(
        [sys.executable, "-u", me], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=REPO,
    )


def orchestrate(out_path: str | None = None) -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = str(s.getsockname()[1])
    s.close()

    def env_for(mode: str, n_dev: int, pid: int | None = None) -> dict:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["_MH_MODE"] = mode
        env["_MH_PORT"] = port
        if pid is not None:
            env["_MH_PID"] = str(pid)
        return env

    me = os.path.abspath(__file__)
    workers = [
        subprocess.Popen(
            [sys.executable, "-u", me],
            env=env_for("child", 4, pid),
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=REPO,
        )
        for pid in range(2)
    ]
    ref = subprocess.Popen(
        [sys.executable, "-u", me],
        env=env_for("local", 8),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        cwd=REPO,
    )

    outs = []
    for i, p in enumerate(workers + [ref]):
        try:
            out, err = p.communicate(timeout=480)
        except subprocess.TimeoutExpired:
            for q in workers + [ref]:
                q.kill()
            print(json.dumps({"match": False, "error": f"process {i} timed out"}))
            return 1
        if p.returncode != 0:
            print(json.dumps({"match": False, "error": f"process {i} rc={p.returncode}",
                              "stderr_tail": err[-2000:]}))
            return 1
        outs.append(out)

    # Gloo prints connection banners on stdout; the JSON payload is the last line.
    got = json.loads(outs[0].strip().splitlines()[-1])  # worker process 0
    want = json.loads(outs[2].strip().splitlines()[-1])  # single-process reference
    # Parity is over metrics + summary ONLY: the timed throughput sample is
    # machine noise by construction and must not break the bit-exactness claim.
    h_got, h_want = _parity_hash(got), _parity_hash(want)
    match = h_got == h_want and got["summary"] == want["summary"]
    verdict = {
        "match": match,
        "n_processes": 2,
        "global_devices": 8,
        "batch": BATCH,
        "ticks": TICKS,
        "violations": sum(got["metrics"]["violations"]),
        "summary": got["summary"],
    }
    print(json.dumps(verdict))
    if out_path is not None:
        _emit_artifact(out_path, verdict, h_got,
                       got["throughput_ticks_per_s"],
                       want["throughput_ticks_per_s"], n_processes=2)
    return 0 if match else 1


def main() -> int:
    mode = os.environ.get("_MH_MODE")
    if mode == "child":
        child(int(os.environ["_MH_PID"]), os.environ["_MH_PORT"])
        return 0
    if mode == "local":
        local()
        return 0
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the schema'd MULTICHIP artifact "
                         "(multichip-v2) here")
    args = ap.parse_args()
    return orchestrate(args.out)


if __name__ == "__main__":
    sys.exit(main())
