"""Pallas execution engine: the whole tick as ONE fused TPU kernel.

XLA compiles the batch-minor tick (models/raft_batched.py) into a dozen-odd fusions
with HBM round trips for the intermediates between them. This engine instead runs
`step_b` itself inside a single `pallas_call`, gridded over blocks of clusters: each
block's entire state (~4KB/cluster) is read into VMEM once, the full nine-phase tick
runs on the VPU from VMEM, and the new state is written back once -- the minimum
possible HBM traffic per tick.

Because `step_b` is pure jnp on batch-minor arrays, the kernel body simply *calls it*
on values read from the block refs: there is no duplicated protocol logic, so the
bit-parity chain (oracle -> raft_batched.step_b) extends to this engine for
free, and tests/test_pallas.py pins it (interpret mode on CPU; the compiled TPU
path is toolchain-blocked, see STATUS below).

Shape handling: TPU Pallas wants >=2-D refs, so rank-1 leaves ([B]-shaped: state.now,
client_cmd, and every StepInfo field) cross the boundary as [1, B].

STATUS — EXPERIMENTAL (demoted from models/ in round 4; see docs/DESIGN.md "Pallas
engine"): interpret mode (CPU) works and is parity-tested every run
(tests/test_pallas.py), which pins that the tick kernel remains
pallas_call-compatible. The compiled TPU path is blocked by this image's Mosaic
toolchain, not by kernel structure: the original int32 tick graph SIGABRTed libtpu
at the final compile step (individual phases compiled fine), and after the v8 wire
format narrowed state to int16/int8 Mosaic rejects it earlier with "Reductions
over int16 not implemented" -- re-confirmed on the real chip in round 4, which
triggered the demotion round 2's park decision called for. Meanwhile the XLA
batch-minor path hit 38.2M cluster-ticks/s/chip (config3) with XLA's own fusions,
so the headroom a hand-fused kernel could add no longer justifies maintaining a
second compile path against a toolchain that cannot lower it. Round-5 probe
(one per round, per the standing plan): still blocked, now "Reductions over
int8 not implemented" after the v13 int8 index planes -- the same missing
narrow-int reduction support, one dtype lower. Revisit if libtpu/Mosaic gains
sub-int32 reductions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.types import ClusterState, StepInfo, StepInputs
from raft_sim_tpu.utils.config import RaftConfig


def _lift(x):
    """[B] -> [1, B] so every ref is at least 2-D."""
    return x[None, :] if x.ndim == 1 else x


def _unlift(x, orig_ndim):
    return x[0] if orig_ndim == 1 else x


def step_pallas(
    cfg: RaftConfig,
    s: ClusterState,
    inp: StepInputs,
    block_b: int = 256,
    interpret: bool = False,
) -> tuple[ClusterState, StepInfo]:
    """One tick for B clusters (batch-minor layout), as a single fused kernel.

    B must be a multiple of block_b. Bit-identical to raft_batched.step_b.
    """
    b = s.role.shape[-1]
    if b % block_b:
        raise ValueError(f"batch {b} must be a multiple of block_b {block_b}")
    if cfg.compact_planes:
        # The compacted carry layout's pack/unpack boundary is reshape-heavy
        # (ops/tile.py), and Mosaic cannot lower the unit-dim reshapes this
        # kernel already avoids (log_ops.iota note in raft_batched.py) --
        # the Pallas engine stays a dense-layout experiment.
        raise NotImplementedError(
            "step_pallas does not support cfg.compact_planes (dense layout only)"
        )

    in_leaves, state_def = jax.tree.flatten(s)
    inp_leaves, inp_def = jax.tree.flatten(inp)
    n_state = len(in_leaves)
    all_in = [_lift(x) for x in in_leaves + inp_leaves]
    in_ndims = [x.ndim for x in in_leaves + inp_leaves]

    # Probe output structure once (abstractly) to build out_shapes.
    out_aval = jax.eval_shape(lambda s_, i_: raft_batched.step_b(cfg, s_, i_), s, inp)
    out_leaves_aval, out_def = jax.tree.flatten(out_aval)
    out_ndims = [x.ndim for x in out_leaves_aval]

    def spec_for(x):
        blk = tuple(x.shape[:-1]) + (block_b,)
        nlead = x.ndim - 1
        return pl.BlockSpec(blk, lambda i, _n=nlead: (0,) * _n + (i,))

    kernel = _make_kernel(cfg, n_state, len(inp_leaves), state_def, inp_def, in_ndims, out_def, out_ndims)

    # Out shapes from the avals, lifted to >=2-D.
    out_shapes = [
        jax.ShapeDtypeStruct((1, b) if a.ndim == 1 else a.shape, a.dtype)
        for a in out_leaves_aval
    ]

    out = pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[spec_for(x) for x in all_in],
        out_specs=[spec_for(sh) for sh in out_shapes],
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # The one-hot intermediates ([N,N,E,CAP,BB] etc.) are VMEM-hungry; let
            # Mosaic use the whole budget instead of its conservative default.
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
    )(*all_in)

    out_leaves = [_unlift(x, nd) for x, nd in zip(out, out_ndims)]
    return jax.tree.unflatten(out_def, out_leaves)


def _make_kernel(cfg, n_state, n_inp, state_def, inp_def, in_ndims, out_def, out_ndims):
    def kernel(*refs):
        in_refs = refs[: n_state + n_inp]
        out_refs = refs[n_state + n_inp :]
        vals = [
            _unlift(r[...], nd) for r, nd in zip(in_refs, in_ndims)
        ]
        s = jax.tree.unflatten(state_def, vals[:n_state])
        inp = jax.tree.unflatten(inp_def, vals[n_state:])
        s2, info = raft_batched.step_b(cfg, s, inp)
        out_leaves, _ = jax.tree.flatten((s2, info))
        for r, v, nd in zip(out_refs, out_leaves, out_ndims):
            if isinstance(v, np.ndarray):
                # Structurally-gated-off StepInfo metrics are HOST CONSTANTS
                # (never jnp.zeros: an op would break the zero-cost-when-off
                # step goldens -- models/raft_batched.py). pallas_call
                # refuses closed-over array consts, so materialize them as
                # an in-kernel op here. Guard the zero assumption: a future
                # nonzero host-constant leaf (a NIL sentinel, say) must fail
                # loudly, not silently diverge from step_b.
                assert not np.any(v), "nonzero host-constant StepInfo leaf"
                v = jnp.zeros(v.shape, v.dtype)
            r[...] = _lift(v) if nd == 1 else v

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def run_pallas(
    cfg: RaftConfig,
    state: ClusterState,
    keys: jax.Array,
    n_ticks: int,
    block_b: int = 256,
    interpret: bool = False,
):
    """Scan the Pallas tick over n_ticks (state [B, ...]-leading in/out). Reuses
    scan.run_batch_minor's scan body with the kernelized step, so fault inputs and
    metric accumulation are the shared code path and trajectories stay bit-identical
    to every other engine."""
    from raft_sim_tpu.sim import scan

    return scan.run_batch_minor(
        cfg,
        state,
        keys,
        n_ticks,
        step_fn=lambda c, s, i: step_pallas(c, s, i, block_b=block_b, interpret=interpret),
    )
