"""Scenario-engine tier: genomes, phased programs, the violation hunt, and
the shrink/replay loop.

The load-bearing property mirrors the telemetry tier's: the scenario path
must be a pure RE-PARAMETERIZATION of the simulator, not a second simulator.
A homogeneous genome built from a config's scalars must reproduce the scalar
path BIT-FOR-BIT -- fleet state, run metrics, and telemetry windows -- which
both paths guarantee by drawing through the same uint32 threshold helpers
from the same key streams (sim/faults.py). Above that sit the hunt's two
acceptance halves: the search must drive a deliberately weakened kernel
(scenario/mutation.py) to a violation within a bounded generation budget,
and must leave the real kernel clean under the same budget; a hit must
shrink to an artifact that replays to the IDENTICAL violation tick.

Compile budget: every windowed evaluation in this module shares ONE
(config, batch, ticks, window) shape -- the scalar parity run, the genome
parity run, the heterogeneous-fleet check, and the real-kernel search all
reuse two compiled programs; the mutant search adds one (different quorum
literal), the phased S=2 program one, and the shrink/replay pair two small
single-cluster programs. Everything else is host-side.
"""

import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig
from raft_sim_tpu.scenario import genome as genome_mod
from raft_sim_tpu.scenario import program as program_mod
from raft_sim_tpu.scenario import search as search_mod
from raft_sim_tpu.scenario import shrink as shrink_mod
from raft_sim_tpu.scenario.mutation import WeakQuorumConfig, mutant_config
from raft_sim_tpu.sim import scan, telemetry
from raft_sim_tpu.utils import checkpoint

# One kitchen-sink config + shapes shared by every device evaluation here
# (see module docstring): all four fault mechanisms on, client traffic on,
# so parity covers every genome field against a nonzero scalar.
CFG = RaftConfig(
    n_nodes=5,
    log_capacity=8,
    client_interval=4,
    drop_prob=0.2,
    partition_period=16,
    partition_prob=0.3,
    crash_prob=0.3,
    crash_period=32,
    crash_down_ticks=8,
    clock_skew_prob=0.1,
)
BATCH, TICKS, WINDOW = 16, 128, 32
SPEC = search_mod.SearchSpec(
    generations=4, population=BATCH, ticks=TICKS, window=WINDOW, seed=0
)


def tree_eq(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=msg)


@pytest.fixture(scope="module")
def scalar_run():
    return telemetry.simulate_windowed(CFG, 0, BATCH, TICKS, WINDOW)


@pytest.fixture(scope="module")
def genome_run():
    g = genome_mod.broadcast(genome_mod.from_config(CFG), BATCH)
    genome_mod.validate(CFG, g)
    return telemetry.simulate_windowed(CFG, 0, BATCH, TICKS, WINDOW, genome=g)


# ------------------------------------------------------ homogeneous parity


def test_homogeneous_genome_is_bit_exact_with_scalar_path(scalar_run, genome_run):
    """The tentpole contract: a genome replicating the config scalars IS the
    scalar run -- same fleet state, same RunMetrics, same telemetry windows,
    bit for bit. Anything weaker and every search verdict would be about a
    different simulator than the one the presets run."""
    f1, m1, r1, _ = scalar_run
    f2, m2, r2, _ = genome_run
    tree_eq(f1, f2, "genome path perturbed the fleet state")
    tree_eq(m1, m2, "genome path perturbed the run metrics")
    tree_eq(r1, r2, "genome path perturbed the telemetry windows")


def test_heterogeneous_fleet_one_program(genome_run):
    """Per-cluster genomes really are per-cluster: a drop=1.0 row delivers
    nothing while its neighbors keep running -- in the SAME compiled program
    the homogeneous run used (same shapes; genome values are traced data)."""
    rows = [genome_mod.from_config(CFG) for _ in range(BATCH)]
    het = genome_mod.stack_rows(rows)
    het = het._replace(drop=het.drop.at[0].set(np.uint32((1 << 32) - 1)))
    _, m, _, _ = telemetry.simulate_windowed(CFG, 0, BATCH, TICKS, WINDOW, genome=het)
    msgs = np.asarray(m.total_msgs)
    assert msgs[0] == 0, "drop=1.0 cluster still delivered messages"
    assert (msgs[1:] > 0).all(), "healthy clusters stopped delivering"
    # And the untouched rows are bit-identical to the homogeneous run.
    _, m_hom, _, _ = genome_run
    np.testing.assert_array_equal(msgs[1:], np.asarray(m_hom.total_msgs)[1:])


# ------------------------------------------------------ phased programs


def test_segment_resolution_on_device():
    """faults.genome_at resolves the `[S]` table by now // seg_len with the
    final segment holding forever -- checked at the input level (no scan
    compile; the full phased pipeline rides the slow tier below and the CI
    scenario smoke)."""
    from raft_sim_tpu.sim import faults

    prog = program_mod.from_dict(
        {"seg_len": 8, "segments": [{"drop_prob": 1.0}, {}, {"clock_skew_prob": 1.0}]},
        CFG,
    )
    key = jax.random.key(0)
    for now, seg in [(0, 0), (7, 0), (8, 1), (23, 2), (999, 2)]:
        inp = faults.make_inputs(
            CFG, key, jax.numpy.int32(now), genome=prog.genome, seg_len=8
        )
        n_deliv = int(np.asarray(inp.deliver_mask).sum())
        skewed = bool((np.asarray(inp.skew) != 1).all())
        if seg == 0:
            assert n_deliv == 0, f"tick {now}: blackout segment delivered"
        else:
            assert n_deliv > 0, f"tick {now}: healed segment delivered nothing"
        assert skewed == (seg == 2), f"tick {now}: wrong skew segment"


@pytest.mark.slow
def test_phased_program_switches_segments_on_device():
    """A 2-segment nemesis (total blackout -> heal) switches at
    seg_len on-device: zero delivered records in the blackout windows, then
    traffic resumes -- one compiled program for the whole timeline."""
    prog = program_mod.from_dict(
        {
            "name": "blackout-heal",
            "seg_len": 2 * WINDOW,
            "segments": [{"drop_prob": 1.0}, {}],
        },
        CFG,
    )
    g = genome_mod.broadcast(prog.genome, BATCH)
    _, m, recs, _ = telemetry.simulate_windowed(
        CFG, 0, BATCH, TICKS, WINDOW, genome=g, seg_len=prog.seg_len
    )
    per_window = np.asarray(recs.metrics.total_msgs)  # [B, 4]
    assert (per_window[:, :2] == 0).all(), "blackout segment delivered records"
    assert (per_window[:, 2:].sum(axis=1) > 0).all(), "fleet never healed"


def test_program_json_round_trip(tmp_path):
    doc = {
        "name": "partition-heal-crash",
        "seg_len": 64,
        "segments": [
            {"partition_period": 16, "partition_prob": 1.0},
            {},
            {"crash_prob": 0.4, "crash_down_ticks": 8},
        ],
    }
    prog = program_mod.from_dict(doc, CFG)
    assert prog.n_segments == 3 and prog.span == 128
    path = program_mod.save(str(tmp_path / "p.json"), prog)
    prog2 = program_mod.load(path, CFG)
    tree_eq(prog.genome, prog2.genome, "JSON round trip changed the genome")
    assert prog2.seg_len == prog.seg_len and prog2.name == prog.name


def test_program_checkpoint_dict_is_bit_exact():
    """to_dict(exact=True) -> from_dict must return the IDENTICAL genome:
    decode() rounds probabilities to 9 decimals, so a segments-only round
    trip can shift a uint32 threshold by an ulp -- a resumed scenario run
    (checkpoint v20) must not silently continue a different trajectory."""
    prog = program_mod.from_dict(
        {"seg_len": 4, "segments": [{"drop_prob": 7e-10}, {"crash_prob": 0.3,
                                                           "crash_down_ticks": 5}]},
        CFG,
    )
    assert int(np.asarray(prog.genome.drop)[0]) == 3  # p_to_u32(7e-10)
    rt = program_mod.from_dict(
        json.loads(json.dumps(program_mod.to_dict(prog, exact=True))), CFG
    )
    tree_eq(prog.genome, rt.genome, "exact checkpoint round trip drifted")
    # The human-unit-only round trip is what exact=True exists to beat:
    lossy = program_mod.from_dict(program_mod.to_dict(prog), CFG)
    assert int(np.asarray(lossy.genome.drop)[0]) != 3  # 9-decimal rounding


def test_program_schema_errors():
    with pytest.raises(ValueError, match="unknown keys"):
        program_mod.from_dict({"segments": [{"drop": 0.1}]}, CFG)
    with pytest.raises(ValueError, match="non-empty"):
        program_mod.from_dict({"segments": []}, CFG)
    with pytest.raises(ValueError, match="seg_len"):
        program_mod.from_dict({"seg_len": 0, "segments": [{}]}, CFG)


# ------------------------------------------------------ genome validation


def test_validate_rejects_bad_genomes():
    g = genome_mod.from_config(CFG)
    with pytest.raises(ValueError, match="crash_down"):
        genome_mod.validate(CFG, g._replace(crash_down=g.crash_down * 0))
    with pytest.raises(ValueError, match="crash_down"):
        genome_mod.validate(
            CFG, g._replace(crash_down=g.crash_down * 0 + CFG.crash_period + 1)
        )
    no_client = RaftConfig(n_nodes=5)
    with pytest.raises(ValueError, match="client_interval"):
        genome_mod.validate(no_client, g)


def test_from_config_rejects_uniform_drop():
    with pytest.raises(ValueError, match="drop_prob_uniform"):
        genome_mod.from_config(RaftConfig(drop_prob=0.3, drop_prob_uniform=True))


def test_raw_round_trip_is_exact():
    g = genome_mod.from_config(CFG)
    g2 = genome_mod.from_raw(json.loads(json.dumps(genome_mod.to_raw(g))))
    tree_eq(g, g2, "raw artifact round trip changed the genome")


# ------------------------------------------------------ the hunt


@pytest.fixture(scope="module")
def mutant_hit():
    """The search demo against the weakened kernel -- shared by the budget
    test and the shrink pipeline (one search, one extra compile)."""
    mcfg = mutant_config("weak-quorum", CFG)
    assert isinstance(mcfg, WeakQuorumConfig) and mcfg.quorum == 2
    res = search_mod.search(mcfg, SPEC)
    return mcfg, res


def test_search_drives_mutant_to_violation_within_budget(mutant_hit):
    """The hunt hunts: the quorum-off-by-one kernel falls within the fixed
    generation budget, and the hit is fully replayable data."""
    _, res = mutant_hit
    assert res.hit is not None, (
        f"mutant survived {SPEC.generations} generations: {res.generations}"
    )
    assert len(res.generations) <= SPEC.generations
    hit = res.hit
    assert set(hit) >= {"seed", "batch", "cluster", "ticks", "seg_len",
                        "genome_raw", "first_viol_tick"}
    assert 0 <= hit["cluster"] < SPEC.population
    assert 0 <= hit["first_viol_tick"] < SPEC.ticks


def test_search_leaves_real_kernel_clean_under_same_budget():
    """Same spec, same seeds, real quorum: zero violations (and the windowed
    evaluation reuses the genome parity program -- same shapes)."""
    res = search_mod.search(CFG, SPEC)
    assert res.hit is None
    assert all(g["violating_clusters"] == 0 for g in res.generations)


def test_fitness_prefers_distress():
    """Violations dominate lexicographically; below them leaderless windows
    raise the score (hand-built records, no device work)."""
    B, W = 3, 4
    zeros = np.zeros((B, W), np.int32)
    mk = lambda **kw: SimpleNamespace(
        metrics=SimpleNamespace(
            last_leaderless_tick=kw.get("llt", zeros - 1),
            max_commit=kw.get("mc", zeros),
        ),
        first_viol_tick=zeros + telemetry.NEVER,
    )
    metrics = SimpleNamespace(
        violations=np.array([0, 0, 1]),
        max_term=np.array([3, 3, 3]),
        total_cmds=np.array([0, 0, 0]),
        lat_excluded=np.array([0, 0, 0]),
        multi_leader=np.array([0, 7, 0]),
    )
    llt = zeros - 1
    llt = llt.copy()
    llt[1] = 5  # cluster 1 saw leaderless windows AND multi-leader ticks
    fit = search_mod.fitness_from_records(mk(llt=llt), metrics)
    assert fit[1] > fit[0], "distress (leaderless + multi-leader) must raise fitness"
    assert fit[2] > fit[1] * 10, "a violation must dominate any distress"
    # multi_leader alone moves the score (the election-safety precursor).
    m2 = SimpleNamespace(**{**metrics.__dict__, "multi_leader": np.array([0, 0, 0])})
    fit2 = search_mod.fitness_from_records(mk(llt=llt), m2)
    assert fit[1] > fit2[1], "multi-leader ticks must raise fitness"


# ------------------------------------------------- shrink + bit-exact replay


@pytest.mark.slow  # budget re-tier (PR 12): the shrink -> bit-exact-replay
# contract is pinned every tier-1 run by the farm's fresh-freeze test
# (shrinks a hit, freezes it, replays via tools/repro.py --corpus) and the
# corpus one-command replay over tests/corpus; this direct pipeline form
# (plus the --scenario CLI leg, which CI's scenario smoke runs) rides the
# slow tier with the rest of the hunt soaks.
def test_shrink_minimizes_and_replays_to_identical_tick(mutant_hit, tmp_path):
    mcfg, res = mutant_hit
    art = shrink_mod.shrink(mcfg, res.hit, mutant="weak-quorum")
    # Minimization really removed or reduced something relative to the hit.
    assert art["schema"] == "scenario-repro-v1"
    assert art["kinds"], "artifact must name the violated invariant(s)"
    assert art["ticks"] == art["tick"] + 1, "horizon must be trimmed"
    assert art["mutant"] == "weak-quorum"
    # The artifact file round-trips and replays to the IDENTICAL tick.
    path = shrink_mod.save_artifact(str(tmp_path / "repro.json"), art)
    art2 = shrink_mod.load_artifact(path)
    rep = shrink_mod.replay_artifact(art2)
    assert rep["reproduced"], rep
    assert rep["tick"] == art["tick"] and rep["kinds"] == art["kinds"]
    # tools/repro.py --scenario is the same replay: exit 0.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "repro_scenario", os.path.join(repo, "tools", "repro.py")
    )
    repro = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(repro)
    assert repro.main(["--scenario", path]) == 0


@pytest.mark.slow
def test_shrink_rejects_non_reproducing_hit(mutant_hit):
    """Broken (genome, seed) bookkeeping must fail loudly, not shrink noise:
    the same hit replayed under the REAL kernel runs clean."""
    _, res = mutant_hit
    with pytest.raises(ValueError, match="does not reproduce"):
        shrink_mod.shrink(CFG, res.hit)


# --------------------------------------------- checkpoint v20 (scenario rides)


def test_checkpoint_v20_carries_scenario_and_gates_plain_resume(tmp_path):
    from raft_sim_tpu.driver import Session
    from raft_sim_tpu.sim.scan import init_metrics_batch
    from raft_sim_tpu.types import init_batch

    cfg = RaftConfig(n_nodes=2, log_capacity=4, max_entries_per_rpc=1)
    key = jax.random.key(0)
    scen = {"name": "t", "seg_len": 4, "segments": [{"drop_prob": 0.5}, {}]}
    path = checkpoint.save(
        str(tmp_path / "ck"), cfg, init_batch(cfg, key, 1),
        jax.random.split(key, 1), init_metrics_batch(1), scenario=scen,
    )
    *_, scen2 = checkpoint.load(path)
    assert scen2 == scen
    # Plain resume must refuse: continuing without the genome path would
    # silently run a different experiment.
    with pytest.raises(ValueError, match="scenario"):
        Session.restore(path)
    # A plain checkpoint round-trips scenario=None.
    p2 = checkpoint.save(
        str(tmp_path / "ck2"), cfg, init_batch(cfg, key, 1),
        jax.random.split(key, 1), init_metrics_batch(1),
    )
    *_, none_scen = checkpoint.load(p2)
    assert none_scen is None


def test_checkpoint_v22_migration_error_names_versions(tmp_path):
    """A v22 file (the pre-lease format: no read_fr staleness leg) errors
    with the migration hint -- the PR 3 hygiene rule, applied across the
    v23-v28 bumps (the v23-file case rides tests/test_reconfig.py)."""
    from raft_sim_tpu.sim.scan import init_metrics_batch
    from raft_sim_tpu.types import init_batch

    assert checkpoint._FORMAT_VERSION == 28  # v28: latency-pass fallback ticks
    assert checkpoint._SCHEMA_FINGERPRINT[0] == 28
    cfg = RaftConfig(n_nodes=2, log_capacity=4, max_entries_per_rpc=1)
    key = jax.random.key(0)
    path = checkpoint.save(
        str(tmp_path / "ck"), cfg, init_batch(cfg, key, 1),
        jax.random.split(key, 1), init_metrics_batch(1),
    )
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["__version__"] = np.int32(22)
    np.savez_compressed(path, **arrays)
    with pytest.raises(ValueError) as ex:
        checkpoint.load(path)
    msg = str(ex.value)
    assert "v22" in msg and "v28" in msg and "version log" in msg
