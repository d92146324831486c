"""Bitrot insurance for the repo-root driver artifacts: bench.py's measurement
harness and __graft_entry__.py's compile-contract entry points must keep working
as the kernels evolve (both are executed by external automation, so nothing else
in the suite touches them)."""

import os
import subprocess
import sys

import jax
import pytest


sys.path.insert(0, ".")  # repo root: bench.py / __graft_entry__.py live there
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_harness_runs_cpu_sized():
    import bench

    from raft_sim_tpu import RaftConfig

    row = bench.bench(RaftConfig(n_nodes=5), batch=64, ticks=50, repeats=1)
    assert row["violations"] == 0
    assert row["cluster_ticks_per_s"] > 0
    assert 0 <= row["pct_stable"] <= 100
    # Quality fields come from the fixed-seed run: a second invocation agrees.
    row2 = bench.bench(RaftConfig(n_nodes=5), batch=64, ticks=50, repeats=1)
    assert row["p50_stable_tick"] == row2["p50_stable_tick"]
    assert row["pct_stable"] == row2["pct_stable"]


def test_graft_entry_compiles():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn).lower(*args).compile()(*args)
    new_state, info = out
    assert new_state.role.shape == args[0].role.shape


@pytest.mark.parametrize(
    "argv",
    [["chip_smoke.py"], ["chip_smoke.py", "--four-chip"], ["bench.py"]],
)
def test_chip_entry_points_refuse_cpu(argv):
    """No hidden CPU path: chip_smoke.py (both modes) and bench.py without
    --smoke exit non-zero without a TPU and print no result."""
    r = subprocess.run(
        [sys.executable, *argv], cwd=REPO, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU found" in r.stderr


def test_backend_tpu_refuses_cpu():
    from raft_sim_tpu.driver import select_backend

    try:
        with pytest.raises(RuntimeError, match="no TPU found"):
            select_backend("tpu")
    finally:
        jax.config.update("jax_platforms", "cpu")


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/elsewhere"])
def test_compile_cache_placement(env_dir):
    """A set JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache
    sits at the fixed <repo>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c",
         "from raft_sim_tpu.utils.compile_cache import use_compile_cache; "
         "print(use_compile_cache())"],
        cwd=REPO, capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 0, r.stderr
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert r.stdout.strip().splitlines()[-1] == want
