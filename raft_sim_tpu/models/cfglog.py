"""Log-carried configuration: per-node membership derived from the log prefix.

The dissertation (ch. 4) requires configuration entries to be *acted on when
appended, not when committed*: each server uses the latest configuration in
its own log, and a truncation that removes a config entry must roll the
server back to the previous one. This module is the SINGLE statement of that
derivation: given a node's config-entry plane
(ClusterState.log_cfg), its log bounds, and its snapshot config context
(base_mold/base_pend/base_epoch), recompute the node's effective
(member_old, member_new, cfg_pend, cfg_epoch) -- executed at the end of
every tick over the post-append, post-compaction log, so "apply on append"
and "roll back on truncation" are the same code path: the configuration IS a
function of the log prefix, never separately-mutated state.

Why a full re-derivation is cheap enough to run every tick: toggles commute
(membership is a bit set; a final entry XORs one bit), so C_old at the
prefix end is base_mold XOR the parity-fold of the final-entry toggles in
the live range -- one masked [N, CAP, N] parity pass packed back into [W]
words (ops/bitplane), plus two masked max/select reductions for the
latest-entry joint test. O(N^2 * CAP) bools per cluster, the same order as
the phase-9 log-matching check, and compiled only when cfg.reconfig.

Entry encoding (ClusterState.log_cfg docstring): 0 none, +(v+1) a JOINT
entry toggling node v (member_new diverges; quorums go dual), -(v+1) the
FINAL entry completing that toggle (member_old absorbs it). Within any
single log the two alternate -- every append chain passes through a leader
that refuses a joint entry while its own prefix is already joint -- but the
derivation never assumes it: the latest live entry's sign alone decides
jointness, and the parity fold is order-free.

TEST-ONLY mutant hooks (scenario/mutation.py) weaken exactly one rule each:
  cfg.act_on_append  False -> derive from the COMMITTED prefix ("act on
                     commit": disjoint-quorum bug);
  cfg.joint_consensus False -> every entry is final at append (single-server
                     change: the known-unsafe interleaving);
  cfg.truncation_rollback False -> applied where the epoch count DROPPED,
                     i.e. the caller keeps the stale carried config after a
                     truncation (models/raft_batched.py end-of-tick block).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from raft_sim_tpu.ops import bitplane, log_ops
from raft_sim_tpu.utils.config import RaftConfig


def _abs1(cfg: RaftConfig, base, n: int, cap: int, batch_shape=()):
    """[N, CAP(, B)] 1-based absolute entry index of each log slot (ring-aware
    under compaction; the plain prefix layout otherwise)."""
    if batch_shape:
        sl = log_ops.iota((1, cap, 1), 1)
        if cfg.compaction:
            b = base[:, None, :]
            return b + (sl - b) % cap + 1
        return jnp.broadcast_to(sl + 1, (n, cap) + batch_shape)
    sl = jnp.arange(cap, dtype=jnp.int32)[None, :]
    if cfg.compaction:
        return base[:, None] + (sl - base[:, None]) % cap + 1
    return jnp.broadcast_to(sl + 1, (n, cap))


def _one_bit_rows(v, n: int):
    """Packed one-hot rows for per-node toggle ids: v [N(, B)] ->
    [N, W(, B)] (bitplane.one_bit yields the word axis LEADING; move it
    behind the node axis)."""
    return jnp.moveaxis(bitplane.one_bit(v, n), 0, 1)


def _fold_core(cfg: RaftConfig, log_cfg, anchor, lo, hi, batched: bool):
    """THE masked parity-fold over config entries in (lo, hi]: the single
    statement of the trickiest index math in this module, shared by the
    live derivation (`derive`: lo = base, hi = the acting horizon) and the
    compaction-rebase advance (`fold_span`: (b0, b1], anchored at the
    PRE-advance base). Returns (fold [N, W(, B)] -- the XOR of final-entry
    toggles, mutant fold_mask rule included; hi_idx [N(, B)] -- the latest
    live entry's absolute index, 0 when none; code_hi -- that entry's
    command; count -- config entries in the span)."""
    n, cap = cfg.n_nodes, cfg.log_capacity
    bshape = log_cfg.shape[2:] if batched else ()
    abs1 = _abs1(cfg, anchor, n, cap, bshape)
    ax = 1  # the CAP axis, both layouts

    def up(x):  # [N(, B)] -> broadcastable against [N, CAP(, B)]
        return x[:, None, :] if batched else x[:, None]

    span = (abs1 > up(lo)) & (abs1 <= up(hi))
    code = jnp.where(span, log_cfg, 0)
    is_cfg = code != 0
    if cfg.joint_consensus:
        fold_mask = code < 0  # final entries fold into C_old
    else:
        fold_mask = is_cfg  # single-server change: every entry is final
    vfold = jnp.abs(code) - 1  # toggle target (garbage where ~fold_mask)
    # Parity fold of the toggle bits: count hits per (node, target) and keep
    # the low bit -- XOR of one-hot rows without an XOR reduction primitive
    # (sum/compare/pack only: the op vocabulary the kernel already uses).
    if batched:
        tgt = log_ops.iota((1, 1, n, 1), 2)
        hits = fold_mask[:, :, None, :] & (vfold[:, :, None, :] == tgt)
    else:
        tgt = jnp.arange(n, dtype=jnp.int32)[None, None, :]
        hits = fold_mask[:, :, None] & (vfold[:, :, None] == tgt)
    par = (jnp.sum(hits, axis=ax, dtype=jnp.int32) % 2) != 0  # [N, n(, B)]
    fold = bitplane.pack(par, axis=1)  # [N, W(, B)]
    # Latest live config entry: its absolute index and command.
    hi_idx = jnp.max(jnp.where(is_cfg, abs1, 0), axis=ax)  # [N(, B)]
    code_hi = jnp.sum(
        jnp.where(is_cfg & (abs1 == up(hi_idx)), code, 0), axis=ax
    )
    count = jnp.sum(is_cfg, axis=ax, dtype=jnp.int32)
    return fold, hi_idx, code_hi, count


def derive(
    cfg: RaftConfig,
    log_cfg: jax.Array,
    log_len: jax.Array,
    commit: jax.Array,
    base: jax.Array,
    base_mold: jax.Array,
    base_pend: jax.Array,
    base_epoch: jax.Array,
    batched: bool = False,
):
    """Effective per-node configuration from the log prefix.

    Shapes: single-cluster (log_cfg [N, CAP], vectors [N], base_mold [N, W])
    or batch-minor (`batched=True`: trailing B on every leaf). Returns
    (member_old [N, W(, B)], member_new, cfg_pend [N(, B)], cfg_epoch,
    cfg_hi) where cfg_hi is the absolute index of the latest live config
    entry (base when none survive) -- the removed-leader stepdown gate
    compares commit against it.
    """
    n = cfg.n_nodes
    horizon = log_len if cfg.act_on_append else jnp.minimum(commit, log_len)
    fold, hi, code_hi, count = _fold_core(
        cfg, log_cfg, base, base, horizon, batched
    )
    m_old = base_mold ^ fold
    if cfg.joint_consensus:
        has = hi > 0
        # No live entry: the snapshot context rules (a pending joint entry
        # may sit at or below base -- committed, compacted, still governing).
        pend_code = jnp.where(has, code_hi, base_pend)
        joint = pend_code > 0
        pend_v = pend_code - 1  # valid only where joint
        pend_idx = jnp.where(has, hi, jnp.maximum(base, 1))
        mb_ = (joint[:, None, :] if batched else joint[:, None])
        m_new = jnp.where(mb_, m_old ^ _one_bit_rows(pend_v, n), m_old)
        cfg_pend = jnp.where(joint, pend_idx, 0)
    else:
        m_new = m_old
        cfg_pend = jnp.zeros_like(hi)
    cfg_epoch = base_epoch + count
    cfg_hi = jnp.maximum(hi, base)
    return m_old, m_new, cfg_pend, cfg_epoch, cfg_hi


def fold_span(
    cfg: RaftConfig,
    log_cfg: jax.Array,
    b0: jax.Array,
    b1: jax.Array,
    base_mold: jax.Array,
    base_pend: jax.Array,
    base_epoch: jax.Array,
    batched: bool = False,
):
    """Advance the snapshot config context across a compaction rebase: fold
    the config entries in (b0, b1] -- final toggles into base_mold, the
    latest entry's jointness into base_pend, the count into base_epoch.
    Slot->index anchoring uses b0 (the PRE-advance base), the same anchor
    rule the checksum pass documents: this must run before phase-6 injection
    can reuse freed slots."""
    fold, hi, code_hi, count = _fold_core(cfg, log_cfg, b0, b0, b1, batched)
    new_mold = base_mold ^ fold
    if cfg.joint_consensus:
        new_pend = jnp.where(
            hi > 0, jnp.where(code_hi > 0, code_hi, 0), base_pend
        )
    else:
        new_pend = base_pend  # never joint: stays zero
    new_epoch = base_epoch + count
    return new_mold, new_pend, new_epoch
