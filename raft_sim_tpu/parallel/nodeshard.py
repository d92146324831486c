"""Node-axis sharding: one giant-N cluster partitioned row-wise across a mesh.

`parallel/mesh.py` shards the embarrassingly-parallel CLUSTER axis -- a whole
cluster's `[N, N]` planes must fit one chip, which the cost model prices out of
HBM well before N=255. This module adds the second mesh axis: the node rows of
every per-node array (the `[N, N]` bookkeeping planes, `[N, CAP]` logs, `[N]`
headers, and the writer-major mailbox) are partitioned by RECEIVER node over a
2-D `("clusters", "nodes")` mesh, the megatron move applied to the tick kernel
-- a cluster bigger than one box lives across ICI instead of across OS
processes (the reference's one-process-per-node deployment, core.clj:197-203).

Layout rules (docs/DESIGN.md "Node-axis sharding"):

- Every per-node array is partitioned on its FIRST node axis -- the axis whose
  rows the owning node WRITES (state: the node itself; mailbox: the sender for
  request legs, the responder for response legs). Second node axes (the peer
  axis of `[N, N]` planes) stay local and padded to `n_pad`.
- The node axis pads to `n_pad = n_shards * ceil(N / n_shards)`. Pad rows are
  permanently dead nodes: `alive=False` every tick, delivery masks all-zero,
  so they freeze at init values; the kernel masks the handful of reductions a
  pad row could otherwise skew (models/raft_batched.py, `pad_self` and the
  sentinel mins). The packed word count is unchanged by padding
  (`n_words(n_pad) == n_words(n)` whenever the shard count divides 32 --
  asserted below), so bitplane words need no relayout.
- The hot loop's only collectives are ONE tiled `all_gather` of the outbound
  mailbox over the `nodes` axis (the per-sender broadcast headers plus the
  narrow per-edge WIRE legs -- req_off offsets and resp_kind responses, the
  protocol's actual point-to-point traffic -- reoriented from their
  writer-major carry), the `psum`/`pmin`/`pmax` folds of the per-cluster `[B]`
  metric reductions, and -- only under `check_invariants` -- one `[n_pad, B]`
  leaders-by-term gather for the election-safety pair check. Delivery, quorum
  popcounts, and commit advancement read the gathered row locally; the wide
  `[N, N]` BOOKKEEPING planes (next_index / match_index / ack_age) never
  cross ICI. Asserted by the collective-whitelist audit
  (analysis/jaxpr_audit.node_collectives, tests/test_nodeshard.py).
- Inputs are drawn redundantly on every device from the same per-cluster key
  stream (sim/faults.make_inputs is pure in (cfg, key, now)), then padded:
  zero communication, and trajectories are bit-identical to the unsharded
  kernel at any device count (tests/test_nodeshard.py).

Unsupported surfaces (v1): the log-carried reconfiguration plane, leader
transfer, ReadIndex/lease reads, client redirect routing, and the O(N^2 * CAP)
log-matching invariant -- each needs either per-edge state the header gather
does not carry or a pad-hostile reduction. `simulate_node_sharded` raises a
ValueError naming the offending gate. `compact_planes` configs run the
sharded carry DENSE internally (the bit-packed flat layout and the row
partition compose poorly; trajectories are identical either way --
types.compact_twin).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.models.raft_batched import NodeShardCtx
from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.parallel import mesh as mesh_mod
from raft_sim_tpu.sim import faults, scan
from raft_sim_tpu.types import (
    FOLLOWER,
    NIL,
    ClusterState,
    Mailbox,
    StepInputs,
    compact_twin,
    init_batch,
)
from raft_sim_tpu.utils.config import RaftConfig

AXIS = mesh_mod.AXIS  # "clusters": the batch axis, as in parallel/mesh.py
NODE_AXIS = "nodes"

# Per-field pad spec: (node axes of the UNBATCHED leaf, pad fill value).
# Fill values mirror types.init_state -- a pad row is a node frozen at boot
# (the fills are documentation more than load-bearing: a dead node's rows are
# never read into a real node's trajectory except through the masked
# reductions the kernel guards; see module docstring). Callables take cfg.
_STATE_PAD = {
    "role": ((0,), FOLLOWER),
    "term": ((0,), 1),
    "voted_for": ((0,), NIL),
    "leader_id": ((0,), NIL),
    "votes": ((0,), 0),
    "next_index": ((0, 1), 1),
    "match_index": ((0, 1), 0),
    "ack_age": ((0, 1), lambda cfg: cfg.ack_age_sat),
    "commit_index": ((0,), 0),
    "commit_chk": ((0,), 0),
    "log_base": ((0,), 0),
    "base_term": ((0,), 0),
    "base_chk": ((0,), 0),
    "log_term": ((0,), 0),
    "log_val": ((0,), 0),
    "log_tick": ((0,), 0),
    "log_len": ((0,), 0),
    "dur_len": ((0,), 0),
    "dur_term": ((0,), 1),
    "dur_vote": ((0,), NIL),
    "clock": ((0,), 0),
    "deadline": ((0,), 0),  # expiry is gated on alive: any value is inert
    "heard_clock": ((0,), lambda cfg: -cfg.election_min_ticks),
    "member_old": ((0,), 0),
    "member_new": ((0,), 0),
    "cfg_epoch": ((0,), 0),
    "cfg_pend": ((0,), 0),
    "log_cfg": ((0,), 0),
    "base_mold": ((0,), 0),
    "base_pend": ((0,), 0),
    "base_epoch": ((0,), 0),
    "xfer_to": ((0,), NIL),
    "read_idx": ((0,), 0),
    "read_tick": ((0,), 0),
    "read_acks": ((0,), 0),
    "read_fr": ((0,), 0),
    "client_pend": ((), 0),
    "client_dst": ((), 0),
    "client_tick": ((), 0),
    "lat_frontier": ((), 0),
    "now": ((), 0),
}

_MAILBOX_PAD = {
    "req_type": ((0,), 0),
    "req_term": ((0,), 0),
    "req_commit": ((0,), 0),
    "req_last_index": ((0,), 0),
    "req_last_term": ((0,), 0),
    "ent_start": ((0,), 0),
    "ent_prev_term": ((0,), 0),
    "ent_count": ((0,), 0),
    "ent_term": ((0,), 0),
    "ent_val": ((0,), 0),
    "ent_tick": ((0,), 0),
    "req_base": ((0,), 0),
    "req_base_term": ((0,), 0),
    "req_base_chk": ((0,), 0),
    "xfer_tgt": ((0,), NIL),
    "req_disrupt": ((0,), 0),
    "ent_cfg": ((0,), 0),
    "req_base_mold": ((0,), 0),
    "req_base_pend": ((0,), 0),
    "req_base_epoch": ((0,), 0),
    "req_off": ((0, 1), 0),
    "resp_kind": ((0, 1), 0),
    "pv_grant": ((0,), 0),
    "v_to": ((0,), NIL),
    "a_ok_to": ((0,), NIL),
    "a_match": ((0,), 0),
    "a_hint": ((0,), 0),
    "resp_term": ((0,), 0),
}

_INPUT_PAD = {
    "deliver_mask": ((0,), 0),
    "skew": ((0,), 0),
    "timeout_draw": ((0,), 0),
    "client_cmd": ((), 0),
    "client_target": ((), 0),
    "client_bounce": ((), 0),
    "alive": ((0,), False),
    "restarted": ((0,), False),
    "reconfig_cmd": ((), 0),
    "transfer_cmd": ((), 0),
    "read_cmd": ((), 0),
    "fsync_fire": ((0,), False),
    "torn_drop": ((0,), 0),
    "fsync_stall": ((0,), False),
}

# A new state/mailbox/input leg without a pad rule would silently corrupt the
# sharded path; fail at import instead.
assert set(_STATE_PAD) | {"mailbox"} == set(ClusterState._fields)
assert set(_MAILBOX_PAD) == set(Mailbox._fields)
assert set(_INPUT_PAD) == set(StepInputs._fields)


def _pad_leaf(x, axes, fill, pad_n: int, lead: int):
    if not axes or not pad_n:
        return x
    widths = [(0, 0)] * x.ndim
    for ax in axes:
        widths[ax + lead] = (0, pad_n)
    return jnp.pad(x, widths, constant_values=np.asarray(fill).astype(x.dtype))


def _pad_tree(cfg: RaftConfig, tree, table, pad_n: int, lead: int) -> dict:
    out = {}
    for f, (axes, fill) in table.items():
        fill_v = fill(cfg) if callable(fill) else fill
        out[f] = _pad_leaf(getattr(tree, f), axes, fill_v, pad_n, lead)
    return out


def pad_state(cfg: RaftConfig, state: ClusterState, n_pad: int, lead: int = 1):
    """Pad every node axis of a (batch-leading when lead=1) dense state from
    n_nodes to n_pad with the boot fills above. The packed-word axes need no
    padding (n_words is unchanged -- see module docstring)."""
    pad_n = n_pad - cfg.n_nodes
    kw = _pad_tree(cfg, state, _STATE_PAD, pad_n, lead)
    kw["mailbox"] = Mailbox(**_pad_tree(cfg, state.mailbox, _MAILBOX_PAD, pad_n, lead))
    return ClusterState(**kw)


def pad_inputs(cfg: RaftConfig, inp: StepInputs, n_pad: int, lead: int = 1):
    """Pad per-node input legs to n_pad: pad nodes are dead (alive=False) with
    all-zero delivery rows, which is what freezes them (module docstring)."""
    return StepInputs(**_pad_tree(cfg, inp, _INPUT_PAD, n_pad - cfg.n_nodes, lead))


def unshard_state(cfg: RaftConfig, state: ClusterState) -> ClusterState:
    """Padded writer-major sharded final state (batch-leading) -> the dense
    [B, N, ...] form `scan.simulate` returns: slice the node axes back to
    n_nodes and reorient the two transposed mailbox carry legs."""
    n = cfg.n_nodes
    n_pad = state.role.shape[1]

    def cut(x, axes, lead=1):
        for ax in axes:
            x = lax.slice_in_dim(x, 0, n, axis=ax + lead)
        return x

    kw = {f: cut(getattr(state, f), axes) for f, (axes, _) in _STATE_PAD.items()}
    mkw = {
        f: cut(getattr(state.mailbox, f), axes)
        for f, (axes, _) in _MAILBOX_PAD.items()
    }
    # The sharded carry stores responder-major response planes; the dense
    # convention is receiver-major (models/raft_batched._gather_mailbox).
    mkw["resp_kind"] = cut(jnp.swapaxes(state.mailbox.resp_kind, 1, 2), (0, 1))
    if cfg.pre_vote:
        pv = bitplane.unpack(state.mailbox.pv_grant, n_pad, axis=2)  # [B, voter, cand]
        mkw["pv_grant"] = bitplane.pack(
            cut(jnp.swapaxes(pv, 1, 2), (0, 1)), axis=2
        )
    kw["mailbox"] = Mailbox(**mkw)
    return ClusterState(**kw)


def _spec_tree(table, extra: dict | None = None) -> dict:
    specs = {
        f: P(AXIS, NODE_AXIS) if 0 in axes else P(AXIS)
        for f, (axes, _) in table.items()
    }
    if extra:
        specs.update(extra)
    return specs


def state_specs() -> ClusterState:
    """shard_map partition specs for a batch-leading padded state: batch over
    "clusters", first node axis over "nodes", everything else local."""
    return ClusterState(
        **_spec_tree(_STATE_PAD, {"mailbox": Mailbox(**_spec_tree(_MAILBOX_PAD))})
    )


def metrics_specs() -> scan.RunMetrics:
    """RunMetrics leave the shard body replicated over the node axis (every
    fold ends in a psum/pmin/pmax): sharded over "clusters" only."""
    return scan.RunMetrics(*([P(AXIS)] * len(scan.RunMetrics._fields)))


def check_shardable(cfg: RaftConfig, n_shards: int) -> int:
    """Validate cfg against the v1 node-sharded surface and return n_pad."""
    unsupported = [
        name
        for name, on in [
            ("reconfig", cfg.reconfig),
            ("leader_transfer", cfg.leader_transfer),
            ("read_index", cfg.read_index),
            ("read_lease", cfg.read_lease),
            ("durable_storage", cfg.durable_storage),
            ("client_redirect", cfg.client_redirect),
            ("check_log_matching", cfg.check_log_matching),
        ]
        if on
    ]
    if unsupported:
        raise ValueError(
            f"node sharding does not support {unsupported} (v1 surface; "
            "see parallel/nodeshard.py module docstring)"
        )
    n = cfg.n_nodes
    nl = -(-n // n_shards)
    n_pad = n_shards * nl
    if bitplane.n_words(n_pad) != bitplane.n_words(n):
        raise ValueError(
            f"padding N={n} to {n_pad} over {n_shards} shards crosses a packed "
            "word boundary (n_words changes); use a shard count dividing 32"
        )
    return n_pad


def make_node_mesh(
    n_node_shards: int | None = None, n_cluster_shards: int = 1, devices=None
) -> Mesh:
    """2-D ("clusters", "nodes") mesh: batch over the first axis, node rows
    over the second. Defaults to all devices on the node axis."""
    if devices is None:
        devices = jax.devices()
    if n_node_shards is None:
        n_node_shards = len(devices) // n_cluster_shards
    need = n_cluster_shards * n_node_shards
    if need > len(devices):
        raise ValueError(
            f"mesh {n_cluster_shards}x{n_node_shards} needs {need} devices, "
            f"only {len(devices)} available"
        )
    arr = np.asarray(devices[:need]).reshape(n_cluster_shards, n_node_shards)
    return Mesh(arr, (AXIS, NODE_AXIS))


def _shard_ctx(nl: int, n_pad: int) -> NodeShardCtx:
    return NodeShardCtx(
        axis=NODE_AXIS,
        nl=nl,
        n_pad=n_pad,
        row0=lax.axis_index(NODE_AXIS).astype(jnp.int32) * nl,
    )


def _run_shard(cfg: RaftConfig, n_ticks: int, nl: int, n_pad: int, state, keys):
    """Per-device body: scan the local node rows of every cluster shard.
    Mirrors scan.run_batch_minor's body with the sharded step kernel; inputs
    are drawn at the REAL n from the same keys on every device, then padded."""
    sh = _shard_ctx(nl, n_pad)
    batch = state.role.shape[0]
    s_t = raft_batched.to_batch_minor(state)
    m0 = raft_batched.to_batch_minor(scan.init_metrics_batch(batch))

    def body(carry, _):
        s, m = carry
        inp = jax.vmap(lambda k, now: faults.make_inputs(cfg, k, now))(keys, s.now)
        inp_t = raft_batched.to_batch_minor(pad_inputs(cfg, inp, n_pad))
        s2, info = raft_batched.step_b(cfg, s, inp_t, sh)
        m2 = scan._accumulate(m, info, s.now)
        return (s2, m2), None

    (final_t, metrics), _ = lax.scan(body, (s_t, m0), None, length=n_ticks)
    return (
        raft_batched.from_batch_minor(final_t),
        raft_batched.from_batch_minor(metrics),
    )


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4))
def simulate_node_sharded(
    cfg: RaftConfig, seed, batch: int, n_ticks: int, mesh: Mesh
):
    """`scan.simulate` with the node axis sharded over `mesh`'s "nodes" axis
    (and the batch over "clusters"). Returns (final_state, RunMetrics): the
    metrics and the `unshard_state` view of the final state are bit-identical
    to the unsharded run for the same (cfg, seed, batch, n_ticks) at any mesh
    shape (tests/test_nodeshard.py). The returned state is PADDED writer-major
    [B, n_pad, ...] -- pass it through `unshard_state` for the dense view."""
    cfg = compact_twin(cfg, False)  # sharded carries run dense (module docstring)
    n_shards = mesh.shape[NODE_AXIS]
    n_pad = check_shardable(cfg, n_shards)
    nl = n_pad // n_shards
    if batch % mesh.shape[AXIS]:
        raise ValueError(
            f"batch {batch} must divide over {mesh.shape[AXIS]} cluster shards"
        )
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    state = pad_state(cfg, init_batch(cfg, k_init, batch), n_pad)
    keys = mesh_mod._constrain_keys(jax.random.split(k_run, batch), mesh)

    sharded = jax.shard_map(
        functools.partial(_run_shard, cfg, n_ticks, nl, n_pad),
        mesh=mesh,
        in_specs=(state_specs(), P(AXIS)),
        out_specs=(state_specs(), metrics_specs()),
        check_vma=False,
    )
    return sharded(state, keys)


def _run_shard_windowed(
    cfg: RaftConfig, n_ticks: int, window: int, nl: int, n_pad: int, state, keys
):
    """Windowed per-device body: telemetry.run_batch_minor_telemetry's nested
    scan (window metrics + first_viol_tick; no recorder/trace legs) over the
    sharded step -- window records come out bit-identical to the unsharded
    `simulate_windowed` (tests/test_nodeshard.py)."""
    from raft_sim_tpu.sim.chunked import merge_metrics
    from raft_sim_tpu.sim.telemetry import NEVER, WindowRecord

    sh = _shard_ctx(nl, n_pad)
    batch = state.role.shape[0]
    s_t = raft_batched.to_batch_minor(state)
    m0 = raft_batched.to_batch_minor(scan.init_metrics_batch(batch))

    def tick(carry, _):
        s, wm, fv = carry
        now = s.now
        inp = jax.vmap(lambda k, nw: faults.make_inputs(cfg, k, nw))(keys, now)
        inp_t = raft_batched.to_batch_minor(pad_inputs(cfg, inp, n_pad))
        s2, info = raft_batched.step_b(cfg, s, inp_t, sh)
        wm2 = scan._accumulate(wm, info, now)
        fv2 = jnp.minimum(fv, jnp.where(scan.step_bad(info), now, NEVER))
        return (s2, wm2, fv2), None

    def outer(carry, _):
        s, m = carry
        start = s.now
        fv0 = jnp.full((batch,), NEVER, jnp.int32)
        (s2, wm, fv), _ = lax.scan(tick, (s, m0, fv0), None, length=window)
        out = WindowRecord(start=start, first_viol_tick=fv, metrics=wm)
        return (s2, merge_metrics(m, wm)), out

    (final_t, metrics), recs = lax.scan(
        outer, (s_t, m0), None, length=n_ticks // window
    )
    return (
        raft_batched.from_batch_minor(final_t),
        raft_batched.from_batch_minor(metrics),
        raft_batched.from_batch_minor(recs),
    )


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5))
def simulate_node_sharded_windowed(
    cfg: RaftConfig, seed, batch: int, n_ticks: int, window: int, mesh: Mesh
):
    """`telemetry.simulate_windowed` (no recorder / trace plane) with the node
    axis sharded: returns (final_state, metrics, records), records in the
    public [B, n_windows, ...] layout and bit-identical to the unsharded
    windowed run. n_ticks must divide by window."""
    from raft_sim_tpu.sim.telemetry import WindowRecord

    if n_ticks % window:
        raise ValueError(f"n_ticks {n_ticks} must divide by window {window}")
    cfg = compact_twin(cfg, False)
    n_shards = mesh.shape[NODE_AXIS]
    n_pad = check_shardable(cfg, n_shards)
    nl = n_pad // n_shards
    if batch % mesh.shape[AXIS]:
        raise ValueError(
            f"batch {batch} must divide over {mesh.shape[AXIS]} cluster shards"
        )
    root = jax.random.key(seed)
    k_init, k_run = jax.random.split(root)
    state = pad_state(cfg, init_batch(cfg, k_init, batch), n_pad)
    keys = mesh_mod._constrain_keys(jax.random.split(k_run, batch), mesh)

    rec_specs = WindowRecord(
        start=P(AXIS), first_viol_tick=P(AXIS), metrics=metrics_specs()
    )
    sharded = jax.shard_map(
        functools.partial(_run_shard_windowed, cfg, n_ticks, window, nl, n_pad),
        mesh=mesh,
        in_specs=(state_specs(), P(AXIS)),
        out_specs=(state_specs(), metrics_specs(), rec_specs),
        check_vma=False,
    )
    return sharded(state, keys)
