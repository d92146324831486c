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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from raft_sim_tpu import PRESETS, RaftConfig, init_batch
from raft_sim_tpu.parallel import nodeshard
from raft_sim_tpu.serve import loop
from raft_sim_tpu.sim import chunked, scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


# ------------------------------------------- the fleet tick loop's ring planes

_COMP = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?(%\S+)\s*=\s*(.*?)\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")
_CONTROL = ("while", "conditional", "call")


def _shapes(hlo_type: str) -> list:
    return [tuple(int(d) for d in g.split(",") if d) for g in _SHAPE.findall(hlo_type)]


def _tick_loop(text: str, skip=()):
    """The optimized HLO's tick loop: every instruction's output type by name,
    and (name, kind, output type, operand names) for each instruction of the
    entry's widest-carry `while` body and of the control flow it calls, but
    for the computations named in `skip`. Fused computations are not
    entered: a fusion is one kernel, and its own operands and output are
    what cross HBM."""
    comps, types, cur, entry = {}, {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            if cur is not None:
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
        elif cur is not None and (m := _INSTR.match(line)):
            comps[cur].append((m, line))
            types[m.group(1)] = m.group(2)
    loops = [
        (m.group(2).count("["), re.search(r"body=(%[\w.\-]+)", line).group(1))
        for m, line in comps[entry]
        if m.group(3) == "while"
    ]
    body, seen, todo = [], set(skip), [max(loops)[1]]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for m, line in comps[c]:
            i, depth = m.end(), 1
            while depth and i < len(line):
                depth += {"(": 1, ")": -1}.get(line[i], 0)
                i += 1
            body.append((m.group(1), m.group(3), m.group(2),
                         re.findall(r"%[\w.\-]+", line[m.end():i - 1])))
            if m.group(3) in _CONTROL:
                todo.extend(re.findall(
                    r"(?:body|condition|to_apply|calls|true_computation|"
                    r"false_computation)=(%[\w.\-]+)", line))
                todo.extend(re.findall(r"%[\w.\-]+", "".join(
                    re.findall(r"branch_computations=\{([^}]*)\}", line))))
    return types, body


def _fleet_tick(one_chip, workload):
    """`workload`'s chunk program at its traffic's batch (8 ticks keep the
    loop body of the cell's 256), compiled for one described v5e: (cfg,
    batch, output types by name, the tick loop's instructions, the
    compiled program)."""
    from benchmark.cell import Cell

    cell = Cell(ROOT, workload)
    cfg = RaftConfig(**cell.raft_fields)
    batch = cell.traffic["batch"]
    state = jax.eval_shape(lambda k: init_batch(cfg, k, batch), jax.random.key(0))
    keys = jax.eval_shape(lambda k: jax.random.split(k, batch), jax.random.key(0))
    compiled = chunked._chunk_donate.lower(
        cfg, _placed(state, one_chip), _placed(keys, one_chip), 8, None, 1
    ).compile()
    return (cfg, batch, *_tick_loop(compiled.as_text()), compiled)


@pytest.fixture(scope="module")
def b1000_tick(one_chip):
    """fleet-etcd3-b1000's chunk program (B 1,000, N 3, a 4,095-slot ring),
    compiled once."""
    return _fleet_tick(one_chip, "fleet-etcd3-b1000")


@pytest.fixture(scope="module")
def wal_tick(one_chip):
    """fleet-etcd5-wal-jepsen's chunk program (B 10,000, N 5, a 4,095-slot
    ring without compaction, the durable storage plane on), compiled once."""
    return _fleet_tick(one_chip, "fleet-etcd5-wal-jepsen")


def _ring_planes_from_window_operands(cfg, batch, types, body) -> list:
    ring = (cfg.n_nodes, cfg.log_capacity, batch)
    assert any(ring in _shapes(out) for _, _, out, _ in body)  # the ring is there
    return [
        name
        for name, kind, out, operands in body
        if kind != "parameter"
        and ring in _shapes(out)
        and not any(s[-2:] == ring[-2:] for op in operands
                    for s in _shapes(types.get(op, "")))
    ]


def test_fleet_tick_writes_no_ring_plane_from_window_operands(b1000_tick):
    """No instruction of fleet-etcd3-b1000's tick loop outputs an [N, CAP, B]
    ring plane when none of its operands holds ring slots ([..., CAP, B]).
    Such an output is a plane built from window-sized operands alone, stored
    to HBM and read back by the select that writes it into the ring: what the
    one-hot AppendEntries write made, once for each written plane (term,
    value, offer stamp)."""
    built = _ring_planes_from_window_operands(*b1000_tick[:4])
    assert not built, f"ring planes built from window-sized operands: {built}"


# Instructions that move data without computing: carry plumbing, and the
# static slices that wrap the ring's head past its end.
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "bitcast", "copy", "slice")


def _window_reads_over_whole_ring(cfg, batch, types, body) -> list:
    ring = (cfg.n_nodes, cfg.log_capacity, batch)
    win = (cfg.n_nodes, cfg.max_entries_per_rpc, batch)
    assert any(win in _shapes(out) for _, _, out, _ in body)  # windows are read
    return [
        name
        for name, kind, out, operands in body
        if kind not in _PLUMBING
        and win in _shapes(out)
        and any(ring in _shapes(types.get(op, "")) for op in operands)
    ]


def test_fleet_tick_reads_no_window_from_whole_ring(b1000_tick):
    """No computing instruction of fleet-etcd3-b1000's tick loop maps an
    [N, CAP, B] ring plane straight to an [N, E, B] window: the ring's window
    reads (replicate's stored terms, outbox's shipped entries) first select
    one tile of the ring per cluster, so the reduction over window positions
    runs over a tile, not over CAP. The one-hot over the whole ring made one
    such fusion per read (replicate's, and outbox's for its planes)."""
    whole = _window_reads_over_whole_ring(*b1000_tick[:4])
    assert not whole, f"window reads reduced over the whole ring: {whole}"


def test_durable_etcd5_tick_keeps_the_tiled_ring_paths(wal_tick):
    """fleet-etcd5-wal-jepsen's chunk program fits one v5e at B = 10,000,
    and at N = 5 its tick loop keeps both ring pins above: no ring plane
    built from window-sized operands, no window read reduced over the whole
    ring."""
    *tick, compiled = wal_tick
    m = compiled.memory_analysis()
    print(f"fleet-etcd5-wal-jepsen AOT memory_analysis: args "
          f"{m.argument_size_in_bytes} B, temp {m.temp_size_in_bytes} B")
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES
    built = _ring_planes_from_window_operands(*tick)
    assert not built, f"ring planes built from window-sized operands: {built}"
    whole = _window_reads_over_whole_ring(*tick)
    assert not whole, f"window reads reduced over the whole ring: {whole}"


# ------------------------------------ the offer->commit latency pass's window


def _latency_conditionals(text: str) -> list:
    """The branch computations of each conditional in the tick's commit
    phase: the latency pass's (models/raft_batched.offer_latency_b), whose
    first branch is the whole-ring fallback and whose second reads the
    window (lax.cond's false and true branches, in that order)."""
    return [
        re.findall(r"%[\w.\-]+", re.search(r"branch_computations=\{([^}]*)\}",
                                         line).group(1))
        for line in text.splitlines()
        if " conditional(" in line and "tick.commit" in line
    ]


@pytest.mark.parametrize("tick", ["b1000_tick", "wal_tick"])
def test_fleet_tick_counts_latency_over_a_window(tick, request):
    """In fleet-etcd3-b1000's and fleet-etcd5-wal-jepsen's tick loops (a
    4,095-slot ring), no instruction outside the latency pass's whole-ring
    fallback reduces an [N, CAP, B] plane into the [LAT_HIST_BINS, B]
    histogram or a [B] sum: the pass counts over a window above the
    frontier. And no copy of an [N, CAP, B] plane feeds the conditional, so
    the stamp plane reaches both branches in place. The whole-ring pass
    alone made two such fusions in each tick loop (b1000's
    %convert_reduce_fusion.37/.38, wal's .35/.37)."""
    from raft_sim_tpu.types import LAT_HIST_BINS

    cfg, batch, _, _, compiled = request.getfixturevalue(tick)
    text = compiled.as_text()
    conds = _latency_conditionals(text)
    assert len(conds) == 1 and len(conds[0]) == 2, conds
    types, body = _tick_loop(text, skip={conds[0][0]})
    ring = (cfg.n_nodes, cfg.log_capacity, batch)
    sums = {(batch,), (LAT_HIST_BINS, batch)}
    # Each reduce's output type and operands, by the computation it is in,
    # and the computation each fusion calls. Names are unique in a module.
    reduces, calls, cur = {}, {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMP.match(line)
            cur = m.group(1) if m else None
        elif cur is not None and (m := _INSTR.match(line)):
            types.setdefault(m.group(1), m.group(2))
            if m.group(3) == "reduce":
                ops = re.findall(r"%[\w.\-]+", line[m.end():].split(")", 1)[0])
                reduces.setdefault(cur, []).append((m.group(2), ops))
            elif c := re.search(r"calls=(%[\w.\-]+)", line):
                calls[m.group(1)] = c.group(1)
    reduced = {
        name
        for name, kind, out, operands in body
        for red_out, red_ops in (
            reduces.get(calls.get(name), []) if kind == "fusion"
            else [(out, operands)] if kind == "reduce" else []
        )
        if sums & set(_shapes(red_out))
        and any(ring in _shapes(types.get(op, "")) for op in red_ops)
    }
    assert not reduced, f"latency counts over the whole ring: {reduced}"
    kinds = {name: (kind, operands) for name, kind, _, operands in body}
    feeds = [
        op
        for name, kind, _, operands in body
        if kind == "conditional"
        for tup in operands[1:]
        for op in kinds.get(tup, ("", [tup]))[1]
    ]
    copied = [
        op for op in feeds
        if kinds.get(op, ("",))[0].startswith("copy")
        and ring in _shapes(types.get(op, ""))
    ]
    assert feeds and not copied, f"ring planes copied into the conditional: {copied}"


def _planes_stored_twice(cfg, batch, types, body) -> list:
    ring = (cfg.n_nodes, cfg.log_capacity, batch)
    return [
        name for name, kind, out, _ in body
        if kind not in _PLUMBING and _shapes(out).count(ring) >= 2
    ]


def test_durable_etcd5_tick_stores_the_stamp_plane_once(wal_tick):
    """fleet-etcd5-wal-jepsen's log is a prefix, so its latency pass reads
    the stamp plane after phase 6's injection, and no instruction of the
    tick loop outputs two [N, CAP, B] planes. Read before the injection,
    the plane between the AppendEntries write and the injection was stored
    beside the carried one by one fusion with both outputs (%fusion.890, a
    third of that fusion's HBM traffic)."""
    twice = _planes_stored_twice(*wal_tick[:4])
    assert not twice, f"ring planes stored twice by one instruction: {twice}"


def test_jepsen_tick_keeps_the_whole_ring_latency_pass(one_chip):
    """fleet-etcd5-jepsen's ring (64 slots) is one tile: its latency pass is
    the whole-ring one, with no conditional in the commit phase."""
    *_, compiled = _fleet_tick(one_chip, "fleet-etcd5-jepsen")
    assert _latency_conditionals(compiled.as_text()) == []
