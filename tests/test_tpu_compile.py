"""Ahead-of-time compiles of the main-path programs for a described TPU v5e.

The TPU compiler is installed here, so each program is compiled at its real
production shape for a chip that is described, not attached: what the chip's
compiler would refuse, and any program that would not fit one v5e's 16 GB of
HBM, fails here at no chip time. Shapes only -- nothing is placed or run, and a
passing compile says nothing about results or times (chip_smoke.py runs them).

The topology is described inside a fixture, never at import: only one process
may load the TPU library, so every xdist worker collects these tests and only
the one that runs them loads it. Keep every described-topology compile in this
one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from raft_sim_tpu import PRESETS, init_batch
from raft_sim_tpu.parallel import nodeshard
from raft_sim_tpu.serve import loop
from raft_sim_tpu.sim import scan

V5E_HBM_BYTES = 16 * 1024**3
TICKS = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe it means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-topology executable can be written to the persistent cache
    # but never read back without a chip: keep these compiles out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """ShapeDtypeStructs of `tree`'s leaves, each carrying `sharding`."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


@pytest.mark.parametrize("preset", ["config3", "config5c"])
def test_simulate_compiles_for_one_v5e(preset, one_chip):
    """scan.simulate at the preset's production batch (config3: 100,000 x
    5-node clusters; config5c: 10,000 x 51-node, compacted layout)."""
    cfg, batch = PRESETS[preset]
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = scan.simulate.lower(cfg, seed, batch, TICKS).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_serve_chunk_compiles_for_one_v5e(one_chip):
    """The donating serve chunk program at config9's production batch, in
    the serve-mode config and chunk/window ServeSession uses by default."""
    base, batch = PRESETS["config9"]
    cfg = loop.serve_config(base)
    chunk, window = 256, 64
    state = jax.eval_shape(lambda k: init_batch(cfg, k, batch), jax.random.key(0))
    keys = jax.eval_shape(lambda k: jax.random.split(k, batch), jax.random.key(0))
    plane = jax.ShapeDtypeStruct((chunk, batch), jnp.int32)
    compiled = loop._serve_chunk.lower(
        cfg,
        _placed(state, one_chip),
        _placed(keys, one_chip),
        _placed(plane, one_chip),
        _placed(plane, one_chip),
        window,
    ).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_node_sharded_config7_compiles_for_four_v5e(topo):
    """config7 (N=101, B=1,000) node-sharded over a 1x4 ("clusters","nodes")
    mesh of the described chips: the one path with collectives in the hot loop
    (mailbox all_gather, metric psum/pmin/pmax)."""
    cfg, batch = PRESETS["config7"]
    mesh = nodeshard.make_node_mesh(4, devices=list(topo.devices))
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    compiled = nodeshard.simulate_node_sharded.lower(
        cfg, seed, batch, TICKS, mesh
    ).compile()
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    assert "all-gather" in compiled.as_text()
