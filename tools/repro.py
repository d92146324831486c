"""Violation repro/shrink tool (SURVEY.md section 4: fuzz cases must shrink).

A fuzz run reports `violations > 0` as one integer across up to 100k clusters x
millions of ticks. This tool isolates the needle: it re-runs the SAME seeded
simulation in chunks (trajectories are pure functions of (seed, cfg), so nothing
need be saved from the original run), stops at the first chunk containing a
violation, picks the first offending cluster, re-runs just that cluster with full
per-tick tracing to find the exact first violating tick, and emits

  - (cluster, tick, violation kinds),
  - the decoded event log around the violation (sim/trace.py -- the reference's
    println trail, core.clj:182-186, for exactly the window that matters),
  - per-node state lines at the violation tick, and
  - a standalone CLI command that replays the offending cluster with events.

Usage:
    python tools/repro.py --preset config4 --seed 7 --ticks 20000 [--batch N]
    python tools/repro.py --n-nodes 5 --drop-prob 0.3 --seed 3 --ticks 5000
    python tools/repro.py --scenario repro.json   # replay a shrunk artifact

Exits 0 printing {"found": false} when the run is clean. Library entry:
`shrink(cfg, seed, batch, n_ticks)` -- tests/test_repro.py demonstrates it
against an artificially broken kernel (quorum - 1).

`--scenario` replays a scenario-engine repro artifact
(raft_sim_tpu/scenario/shrink.py, `scenario shrink --out`): it rebuilds the
exact kernel (including TEST-ONLY mutants), reruns the minimized (genome,
seed) at the trimmed horizon, and exits 0 iff the violation reproduces at
the IDENTICAL tick with identical kinds -- the CI scenario smoke contract.

`--corpus DIR` batch-replays EVERY artifact in a corpus directory in one
process (one jax import; same-shape artifacts share the replay compile via
scenario/shrink.py's jitted-replay cache), printing one JSON line per
artifact and exiting nonzero NAMING THE FIRST DRIFTING ARTIFACT -- the one
command tier-1's tests/test_corpus.py and CI both converge on.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from raft_sim_tpu import init_batch
from raft_sim_tpu.sim import chunked, scan, trace
from raft_sim_tpu.utils.config import PRESETS, RaftConfig

VIOL_FIELDS = (
    "viol_election_safety", "viol_commit", "viol_log_matching",
    "viol_read_stale",
)


def shrink(
    cfg: RaftConfig,
    seed: int,
    batch: int,
    n_ticks: int,
    chunk: int = 512,
    context: int = 30,
) -> dict | None:
    """Isolate the first violating (cluster, tick) of a seeded run.

    Returns None when no violation occurs within n_ticks; otherwise a dict with
    cluster, tick, kinds, events (list of (tick, text) around the violation),
    state_lines (per-node dump at the violation tick), and repro_cmd.
    """
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)

    done = 0
    while done < n_ticks:
        n = min(chunk, n_ticks - done)
        nxt_state, m = chunked._chunk(cfg, state, keys, n)
        viol = np.asarray(m.violations)
        if int(viol.sum()) == 0:
            state, done = nxt_state, done + n
            continue

        # First offending cluster; replay it alone from the chunk start with
        # full per-tick info + states (scan.run: the same tick body at B = 1,
        # bit-identical to the batched run -- tests/test_batched_parity.py).
        cluster = int(np.argmax(viol > 0))
        one = jax.tree.map(lambda x: x[cluster], state)
        _, _, (infos, states) = jax.jit(
            lambda s, k: scan.run(cfg, s, k, n, trace_states=True)
        )(one, keys[cluster])
        kinds_by_tick = {
            f: np.asarray(getattr(infos, f)) for f in VIOL_FIELDS
        }
        bad = np.zeros(n, bool)
        for v in kinds_by_tick.values():
            bad |= v
        assert bad.any(), "batched run flagged a violation the replay did not"
        t_rel = int(np.argmax(bad))
        tick = done + t_rel
        kinds = [f for f, v in kinds_by_tick.items() if bool(v[t_rel])]

        events = [
            (done + t, e)
            for t, e in trace.events(states)
            if abs(t - t_rel) <= context
        ]
        n_nodes = cfg.n_nodes
        state_lines = [trace.node_line(states, t_rel, i) for i in range(n_nodes)]
        return {
            "cluster": cluster,
            "tick": tick,
            "kinds": kinds,
            "chunk_start": done,
            "events": events,
            "state_lines": state_lines,
            "repro_cmd": _repro_cmd(cfg, seed, batch, tick),
        }
    return None


def _repro_cmd(cfg: RaftConfig, seed: int, batch: int, tick: int) -> str:
    """A standalone CLI line replaying the run up to just past the violation."""
    flags = []
    for f in dataclasses.fields(RaftConfig):
        v = getattr(cfg, f.name)
        if v != f.default:
            flag = "--" + f.name.replace("_", "-")
            flags.append(f"{flag} {v}")
    return (
        f"python -m raft_sim_tpu run --seed {seed} --batch {batch} "
        f"--ticks {tick + 1} " + " ".join(flags)
    )


def replay_scenario(path: str, context: int) -> int:
    """Replay a scenario repro artifact; 0 = reproduced at the identical tick."""
    from raft_sim_tpu.scenario import shrink as shrink_mod

    art = shrink_mod.load_artifact(path)
    res = shrink_mod.replay_artifact(art, context=context)
    print(json.dumps({
        "found": res["tick"] is not None,
        "reproduced": res["reproduced"],
        "tick": res["tick"],
        "expected_tick": res["expected_tick"],
        "kinds": res["kinds"],
        "expected_kinds": res["expected_kinds"],
        "mutant": art.get("mutant"),
        "segments": art.get("segments"),
    }))
    for t, e in res["events"]:
        marker = " <== VIOLATION TICK" if t == res["tick"] else ""
        print(f"tick {t:>7}  {e}{marker}", file=sys.stderr)
    return 0 if res["reproduced"] else 2


def replay_corpus(directory: str) -> int:
    """Replay every corpus artifact; 0 = all reproduced bit-exactly, 2 = the
    first drifting artifact (named on stderr AND in the summary line)."""
    import glob

    from raft_sim_tpu.scenario import shrink as shrink_mod

    paths = sorted(glob.glob(os.path.join(directory, "*.json")))
    if not paths:
        print(json.dumps({"corpus": directory, "error": "no artifacts"}))
        return 2
    for path in paths:
        name = os.path.basename(path)
        art = shrink_mod.load_artifact(path)
        res = shrink_mod.replay_artifact(art, context=0)
        print(json.dumps({
            "artifact": name,
            "reproduced": res["reproduced"],
            "tick": res["tick"],
            "expected_tick": res["expected_tick"],
            "kinds": res["kinds"],
            "expected_kinds": res["expected_kinds"],
            "mutant": art.get("mutant"),
        }))
        if not res["reproduced"]:
            print(f"corpus DRIFT: {name} (expected tick "
                  f"{res['expected_tick']} {res['expected_kinds']}, got "
                  f"{res['tick']} {res['kinds']})", file=sys.stderr)
            print(json.dumps({
                "corpus": directory, "artifacts": len(paths),
                "drifted": name,
            }))
            return 2
    print(json.dumps({
        "corpus": directory, "artifacts": len(paths), "reproduced": len(paths),
    }))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro")
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--context", type=int, default=30)
    ap.add_argument("--scenario", metavar="FILE", default=None,
                    help="replay a scenario repro artifact instead of "
                         "shrinking a scalar-config run (exit 0 iff the "
                         "violation reproduces at the identical tick)")
    ap.add_argument("--corpus", metavar="DIR", default=None,
                    help="batch-replay every artifact in a corpus directory "
                         "(tests/corpus); exit nonzero naming the first "
                         "drifting artifact")
    from raft_sim_tpu.driver import _add_config_flags, build_config

    _add_config_flags(ap)
    args = ap.parse_args(argv)
    if args.scenario and args.corpus:
        ap.error("--scenario and --corpus are exclusive")
    if args.corpus:
        return replay_corpus(args.corpus)
    if args.scenario:
        return replay_scenario(args.scenario, args.context)
    if args.ticks is None:
        ap.error("--ticks is required (unless replaying with --scenario)")
    cfg, batch = build_config(args)
    if args.batch is not None:
        batch = args.batch

    res = shrink(cfg, args.seed, batch, args.ticks, chunk=args.chunk,
                 context=args.context)
    if res is None:
        print(json.dumps({"found": False, "ticks": args.ticks, "batch": batch}))
        return 0
    events = res.pop("events")
    lines = res.pop("state_lines")
    print(json.dumps({"found": True, **res}))
    print(f"--- state at tick {res['tick']} (cluster {res['cluster']}) ---",
          file=sys.stderr)
    for ln in lines:
        print(ln, file=sys.stderr)
    print("--- events around the violation ---", file=sys.stderr)
    for t, e in events:
        marker = " <== VIOLATION TICK" if t == res["tick"] else ""
        print(f"tick {t:>7}  {e}{marker}", file=sys.stderr)
    return 1  # a violation is a failure condition for scripting


if __name__ == "__main__":
    sys.exit(main())
