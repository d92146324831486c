"""Shrink a violating (genome, seed, horizon) triple to a minimal repro.

A search hit names one cluster of a heterogeneous fleet whose on-device
invariants tripped. This module minimizes it the Molly/QuickCheck way --
greedy delta-debugging over the genome's fault mechanisms (drop each whole
mechanism, then halve surviving thresholds), every trial a bit-exact
single-cluster replay of the SAME trajectory prefix the fleet ran (keys are
split per cluster before the scan; tests/test_batched_parity.py pins the
equivalence) -- and emits a small JSON artifact:

  - the minimized genome (exact uint32 leaves AND decoded human units),
  - (config, seed, batch, cluster, seg_len, horizon = first violating
    tick + 1, violation kinds),
  - the decoded event log around the violation and per-node state lines
    (sim/trace.py -- the flight-recorder rendering), and
  - a standalone replay command.

`tools/repro.py --scenario artifact.json` replays the artifact and exits 0
iff the violation reproduces at the identical tick. Compile discipline:
every trial reuses ONE jitted traced replay (genome values are traced, so
ablations never recompile; only the final horizon-trimmed confirmation run
compiles a second program).
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import numpy as np

from raft_sim_tpu import init_batch
from raft_sim_tpu.scenario import genome as genome_mod
from raft_sim_tpu.sim import scan, trace
from raft_sim_tpu.utils.config import RaftConfig

VIOL_FIELDS = (
    "viol_election_safety", "viol_commit", "viol_log_matching",
    "viol_read_stale",
)

# Ablation groups tried whole-mechanism-first (any order is sound; cheap and
# usually-removable mechanisms go first so the artifact shrinks fastest), then
# threshold knobs halved while the violation survives.
ABLATIONS = (
    ("clock skew", {"skew": 0}),
    ("client traffic", {"client_interval": 0}),
    ("leadership transfers", {"transfer_interval": 0}),
    ("reads", {"read_interval": 0}),
    ("membership changes", {"reconfig_interval": 0}),
    ("message drop", {"drop": 0}),
    ("partitions", {"part": 0, "part_period": 0}),
    ("crashes", {"crash": 0}),
)
HALVABLE = ("drop", "part", "crash", "skew")


def _single_cluster(cfg: RaftConfig, seed: int, batch: int, cluster: int):
    """The (state, key) of one cluster of the seeded fleet -- identical to its
    slice of the batched run (init splits keys per cluster before the scan)."""
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    state = init_batch(cfg, k_init, batch)
    keys = jax.random.split(k_run, batch)
    take = lambda x: jax.tree.map(lambda v: v[cluster], x)
    return take(state), keys[cluster]


@functools.lru_cache(maxsize=8)
def _replay_fn(cfg: RaftConfig, n_ticks: int, seg_len: int):
    """One jitted traced single-cluster scenario replay per (cfg, horizon,
    seg_len): every ablation/halving trial reuses it (genomes are traced).
    `scan.run` ticks through the fleet's own tick body at B = 1, so a replay
    follows the trajectory the search's batched run measured."""
    return jax.jit(
        lambda s, k, g: scan.run(
            cfg, s, k, n_ticks, trace_states=True, genome=g, seg_len=seg_len
        )
    )


def _first_violation(infos) -> tuple[int | None, list[str]]:
    """(first violating tick index, kinds at that tick) from stacked StepInfo."""
    flags = {f: np.asarray(getattr(infos, f)) for f in VIOL_FIELDS}
    bad = np.zeros_like(next(iter(flags.values())))
    for v in flags.values():
        bad = bad | v
    if not bad.any():
        return None, []
    t = int(np.argmax(bad))
    return t, [f for f, v in flags.items() if bool(v[t])]


def _zero(genome, fields: dict):
    return genome._replace(
        **{f: jax.numpy.zeros_like(getattr(genome, f)) for f in fields}
    )


def shrink(
    cfg: RaftConfig,
    hit: dict,
    mutant: str | None = None,
    halving_rounds: int = 3,
    context: int = 30,
) -> dict:
    """Minimize a search hit (see search.py's hit schema) to a repro artifact.

    `cfg` must already be the kernel the hit was found against (pass the
    mutation.py config for mutant hunts; `mutant` only LABELS the artifact so
    the replayer rebuilds the same kernel). Raises ValueError if the hit does
    not reproduce at its recorded horizon -- a non-replayable hit means the
    caller's (genome, seed) bookkeeping is broken and must not be papered
    over.
    """
    seed, batch, cluster = hit["seed"], hit["batch"], hit["cluster"]
    seg_len, horizon = int(hit["seg_len"]), int(hit["ticks"])
    g0 = genome_mod.from_raw(hit["genome_raw"])
    state, key = _single_cluster(cfg, seed, batch, cluster)
    replay = _replay_fn(cfg, horizon, seg_len)

    def violates(g):
        _, _, (infos, _) = replay(state, key, g)
        return _first_violation(infos)[0] is not None

    if not violates(g0):
        raise ValueError(
            "hit does not reproduce: cluster "
            f"{cluster} of seed {seed} ran {horizon} ticks clean under its "
            "recorded genome -- (genome, seed, horizon) bookkeeping is broken"
        )

    # Phase 1: drop whole fault mechanisms while the violation survives.
    g, removed = g0, []
    for label, fields in ABLATIONS:
        cand = _zero(g, fields)
        if violates(cand):
            g, removed = cand, removed + [label]

    # Phase 2: halve surviving thresholds (a coarse "lowest rate that still
    # breaks" pass; `halving_rounds` bounds the budget).
    for _ in range(halving_rounds):
        any_halved = False
        for f in HALVABLE:
            leaf = getattr(g, f)
            if not np.asarray(leaf).any():
                continue
            cand = g._replace(**{f: leaf // 2})
            if violates(cand):
                g, any_halved = cand, True
        if not any_halved:
            break

    # Final confirmation at the minimized genome: exact tick, kinds, events,
    # state lines; the artifact's horizon is trimmed to tick + 1.
    _, _, (infos, states) = replay(state, key, g)
    tick, kinds = _first_violation(infos)
    events = [(t, e) for t, e in trace.events(states) if abs(t - tick) <= context]
    state_lines = [trace.node_line(states, tick, i) for i in range(cfg.n_nodes)]

    art = {
        "schema": "scenario-repro-v1",
        "config": {
            f.name: getattr(cfg, f.name)
            for f in dataclasses.fields(RaftConfig)
            if getattr(cfg, f.name) != f.default
        },
        "mutant": mutant,
        "seed": int(seed),
        "batch": int(batch),
        "cluster": int(cluster),
        "seg_len": seg_len,
        "ticks": int(tick) + 1,
        "tick": int(tick),
        "kinds": kinds,
        "removed": removed,
        "genome_raw": genome_mod.to_raw(g),
        "segments": genome_mod.decode(g),
        "events": events,
        "state_lines": state_lines,
        "repro_cmd": "python tools/repro.py --scenario <artifact.json>",
    }
    return art


def save_artifact(path: str, art: dict) -> str:
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    return path


# Replayable artifact schemas. v1 is the raw shrink output; v2 adds the
# REQUIRED provenance block (farm/corpus.py stamps it: who found the hit,
# which fitness member, which generation/seed, what the shrink ablated, and
# the farm manifest hash) -- corpus-frozen artifacts must be v2
# (farm.corpus.validate_artifact), but the replayer accepts both: replay
# depends only on (config, mutant, genome, seed, horizon), which v1 carries.
ARTIFACT_SCHEMAS = ("scenario-repro-v1", "scenario-repro-v2")


def load_artifact(path: str) -> dict:
    with open(path) as f:
        art = json.load(f)
    if art.get("schema") not in ARTIFACT_SCHEMAS:
        raise ValueError(f"not a scenario repro artifact: {path}")
    return art


def artifact_config(art: dict) -> RaftConfig:
    """Rebuild the exact kernel the artifact was minimized against (the
    mutant label routes through mutation.py's registry)."""
    cfg = RaftConfig(**art.get("config", {}))
    if art.get("mutant"):
        from raft_sim_tpu.scenario.mutation import mutant_config

        cfg = mutant_config(art["mutant"], cfg)
    return cfg


def replay_artifact(art: dict, context: int = 30) -> dict:
    """Replay an artifact at its trimmed horizon. Returns
    {"reproduced": bool, "tick", "expected_tick", "kinds", "events"} --
    `reproduced` means the SAME first violating tick and kinds came back
    (trajectories are pure functions of (config, genome, seed), so anything
    else is an environment or code drift worth failing loudly on)."""
    cfg = artifact_config(art)
    g = genome_mod.from_raw(art["genome_raw"])
    state, key = _single_cluster(cfg, art["seed"], art["batch"], art["cluster"])
    replay = _replay_fn(cfg, int(art["ticks"]), int(art["seg_len"]))
    _, _, (infos, states) = replay(state, key, g)
    tick, kinds = _first_violation(infos)
    events = (
        [(t, e) for t, e in trace.events(states) if abs(t - tick) <= context]
        if tick is not None
        else []
    )
    return {
        "reproduced": tick == art["tick"] and kinds == art["kinds"],
        "tick": tick,
        "expected_tick": art["tick"],
        "kinds": kinds,
        "expected_kinds": art["kinds"],
        "events": events,
    }
