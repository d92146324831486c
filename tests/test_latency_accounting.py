"""The batch-minor kernel's offer->commit latency accounting
(models/raft_batched.offer_latency_b) against the per-slot form it replaced,
on synthetic [N, CAP, B] planes.

At CAP > 256 the pass reads the log_ops.LAT_WINDOW entries above the
frontier and sweeps the whole ring only on ticks where some cluster's max
commit advanced past them; the `win_*` cases put the advance at the
window's corners, and every case checks which form ran (lat_ring_ticks).

The reference below is that form as the batched kernel wrote it: the absolute
index of every slot by a per-slot modulo, then log_ops.log2_bin and a one-hot
over the histogram's bins. Every output must be equal: lat_sum, lat_cnt,
lat_hist, lat_excluded and the carried lat_frontier. test_oracle_parity.py
and test_batched_parity.py hold the whole kernel to the scalar oracle along
trajectories; this file reaches the corners a trajectory
rarely does (every bin, a ring that wraps, split brain, stamps out of range).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu.models import raft_batched
from raft_sim_tpu.ops import log_ops
from raft_sim_tpu.types import LAT_HIST_BINS

B = 8  # clusters per case


def reference(log_tick, lead, commit, base, frontier, now):
    n, cap = log_tick.shape[:2]
    ids2 = jnp.arange(n, dtype=jnp.int32)[:, None]
    sl = jnp.arange(cap, dtype=jnp.int32)[None, :, None]
    if base is not None:
        abs1 = base[:, None, :] + (sl - base[:, None, :]) % cap + 1
    else:
        abs1 = sl + 1
    newly = (abs1 > frontier[None, None, :]) & (abs1 <= commit[:, None, :])
    cli = (log_tick >= 1) & (log_tick <= now[None, None, :])
    lm = lead[:, None, :] & newly & cli
    lats = jnp.where(lm, now[None, None, :] - log_tick + 1, 0)
    lat_sum = jnp.sum(lats, axis=(0, 1)).astype(jnp.int32)
    lat_cnt = jnp.sum(lm, axis=(0, 1)).astype(jnp.int32)
    is_maxc = commit == jnp.max(commit, axis=0)[None, :]
    hnode = jnp.min(jnp.where(is_maxc, ids2, n), axis=0)
    crossed = (ids2 == hnode[None, :])[:, None, :] & newly & cli
    lat_excluded = jnp.maximum(
        jnp.sum(crossed, axis=(0, 1)).astype(jnp.int32) - lat_cnt, 0
    )
    bin_ = log_ops.log2_bin(lats, LAT_HIST_BINS)
    bins = jnp.arange(LAT_HIST_BINS)[None, None, :, None]
    oh_b = (bins == bin_[:, :, None, :]) & lm[:, :, None, :]
    lat_hist = jnp.sum(oh_b, axis=(0, 1)).astype(jnp.int32)
    lat_frontier = jnp.maximum(frontier, jnp.max(commit, axis=0))
    return lat_sum, lat_cnt, lat_hist, lat_excluded, lat_frontier


def changed(log_tick, lead, commit, base, frontier, now):
    n = log_tick.shape[0]
    ids2 = log_ops.iota((n, 1), 0)
    fold = lambda x: x  # one device: the node-axis folds are the identity
    return raft_batched.offer_latency_b(
        log_tick, lead, commit, base, frontier, now, ids2, n, fold, fold, fold
    )


reference_jit = jax.jit(reference)
changed_jit = jax.jit(changed)

CASES = (
    "every_bin",  # latencies 1 .. 2^17, every bin and past the last
    "ring_wrap",  # base % cap at cap - 1 .. cap - 3: (frontier, commit] wraps
    "frontier_at_commit",  # nothing newly committed
    "split_brain",  # two live leader rows in every cluster
    "dead_leader",  # commit advances with no live leader: all excluded
    "stamp_edges",  # stamps 0, 1, now, now + 1 and beyond
)
SHAPES = ((3, 64), (5, 64), (3, 4095), (5, 4095))  # (N, CAP)
# The windowed form's corners, at CAP 4,095 only (a ring longer than a tile).
WINDOW_CASES = (
    "win_last",  # every cluster's max commit exactly LAT_WINDOW past the frontier
    "win_over",  # one cluster LAT_WINDOW + 1 past: the whole batch sweeps the ring
    "win_tile_edge",  # the window straddles a 256-slot tile boundary
    "win_wrap",  # the window wraps the ring's end (compaction only)
    "win_base_past_frontier",  # the leader row's base lies inside the window
    "win_restart",  # every commit at or below the frontier: nothing counts
    "win_split_brain",  # two live leader rows, both committed into the window
)
RING_ONLY = ("ring_wrap", "win_wrap", "win_base_past_frontier")


def planes(case, n, cap, comp, seed):
    """Synthetic inputs: (log_tick, lead, commit, base or None, frontier, now)."""
    rng = np.random.default_rng(seed)
    now = rng.integers(1 << 17, 1 << 18, B)
    if case.startswith("win_"):
        return window_planes(case, n, cap, comp, rng, now)
    if comp:
        base = rng.integers(0, 20 * cap, (n, B))
        if case == "ring_wrap":
            base = cap * rng.integers(1, 20, (1, B)) + cap - 1 - rng.integers(0, 3, (n, B))
    else:
        base = np.zeros((n, B), np.int64)
    commit = base + rng.integers(0, cap + 1, (n, B))
    frontier = np.maximum(commit.max(0) - rng.integers(0, cap + 1, B), 0)
    if case in ("every_bin", "ring_wrap", "split_brain", "dead_leader"):
        frontier = base.min(0)  # the whole retained ring is newly committed
    if case == "every_bin":
        commit = base + cap
    if case == "frontier_at_commit":
        commit = np.broadcast_to(commit.max(0), (n, B)).copy()
        frontier = commit[0]
    lead = np.zeros((n, B), bool)
    lead[rng.integers(0, n, B), np.arange(B)] = True
    if case == "split_brain":
        lead[:2] = True
    if case == "dead_leader":
        lead[:] = False
    lat = rng.integers(1, 64, (n, cap, B))
    if case == "every_bin":
        # Bin k (k = slot % 17) for every slot: k = 16 lies past the last bin.
        k = np.arange(cap)[None, :, None] % (LAT_HIST_BINS + 1)
        lat = (1 << k) + rng.integers(0, 1 << 16, (n, cap, B)) % (1 << k)
    log_tick = now + 1 - lat
    if case == "stamp_edges":
        # Every third slot keeps an in-range stamp; the rest are edges.
        edge = np.stack([0 * now, 0 * now + 1, now, now + 1, now + 7, now - 1])
        pick = rng.integers(0, len(edge), (n, cap, B))
        edges = edge[pick, np.arange(B)[None, None, :]]
        log_tick = np.where(np.arange(cap)[None, :, None] % 3 == 0, log_tick, edges)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return (
        i32(log_tick),
        jnp.asarray(lead),
        i32(commit),
        i32(base) if comp else None,
        i32(frontier),
        i32(now),
    )


def window_planes(case, n, cap, comp, rng, now):
    """Inputs whose max commit lies within about one window of the frontier.
    Without compaction slot s holds entry s + 1, so commits stay <= CAP."""
    w = log_ops.LAT_WINDOW
    cols = np.arange(B)
    if case == "win_tile_edge":
        pos = 256 * rng.integers(1, cap // 256, B) - rng.integers(1, w, B)
    elif case == "win_wrap":
        pos = cap - rng.integers(1, w, B)
    else:
        pos = rng.integers(0, cap - w - 1, B)
    frontier = pos + (cap * rng.integers(1, 20, B) if comp else 0)
    adv = rng.integers(2 if case == "win_base_past_frontier" else 1, w + 1, B)
    if case in ("win_last", "win_over"):
        adv[:] = w
    if case == "win_over":
        adv[rng.integers(0, B)] = w + 1
    maxc = frontier + adv
    lead = np.zeros((n, B), bool)
    r = rng.integers(0, n, B)
    lead[r, cols] = True
    # Followers trail the leader, some below the frontier.
    commit = np.maximum(maxc[None, :] - rng.integers(0, 2 * w, (n, B)), 0)
    commit[r, cols] = maxc
    if case == "win_split_brain":
        r2 = (r + 1) % n
        lead[r2, cols] = True
        commit[r2, cols] = frontier + rng.integers(1, adv + 1)
    if case == "win_restart":
        commit = np.maximum(frontier[None, :] - rng.integers(0, w, (n, B)), 0)
    if comp:
        base = np.maximum(commit - rng.integers(2 * w, cap - w, (n, B)), 0)
        if case == "win_base_past_frontier":
            base[r, cols] = frontier + rng.integers(1, adv)  # below its commit
    else:
        base = np.zeros((n, B), np.int64)
    log_tick = now + 1 - rng.integers(1, 64, (n, cap, B))
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return (
        i32(log_tick),
        jnp.asarray(lead),
        i32(commit),
        i32(base) if comp else None,
        i32(frontier),
        i32(now),
    )


def _seed(case, n, cap, comp):
    return zlib.crc32(f"{case}/{n}/{cap}/{comp}".encode())


def _params():
    out = []
    for n, cap in SHAPES:
        for comp in (True, False):
            for case in CASES + (WINDOW_CASES if cap > log_ops._TILE else ()):
                if case in RING_ONLY and not comp:
                    continue  # without compaction the ring never wraps
                cid = f"{case}-n{n}-cap{cap}-{'comp' if comp else 'nocomp'}"
                out.append(pytest.param(case, n, cap, comp, id=cid))
    return out


PARAMS = _params()


@pytest.mark.parametrize("case,n,cap,comp", PARAMS)
def test_offer_latency_matches_per_slot_form(case, n, cap, comp):
    args = planes(case, n, cap, comp, seed=_seed(case, n, cap, comp))
    want = jax.device_get(reference_jit(*args))
    got = jax.device_get(changed_jit(*args))
    names = ("lat_sum", "lat_cnt", "lat_hist", "lat_excluded", "lat_frontier")
    assert len(got) == len(names) + 1
    for name, w, g in zip(names, want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    # The whole ring is swept exactly on ticks where some cluster's max
    # commit lies more than the window past its frontier, and only at CAP
    # > 256; lat_ring_ticks is 1 for every cluster of such a tick.
    commit, frontier = np.asarray(args[2]), np.asarray(args[4])
    adv = commit.max(axis=0) - frontier
    swept = cap > log_ops._TILE and adv.max() > log_ops.LAT_WINDOW
    np.testing.assert_array_equal(got[5], np.full(B, swept, np.int32))
    assert swept == (case not in WINDOW_CASES + ("frontier_at_commit",)
                     or case == "win_over") or cap <= log_ops._TILE, case
    lat_cnt, lat_hist, lat_excluded = want[1], want[2], want[3]
    # Each case is the corner it names.
    if case == "frontier_at_commit":
        assert not lat_cnt.any() and not lat_excluded.any()
    if case == "dead_leader":
        assert not lat_cnt.any() and lat_excluded.any()
    if case in ("every_bin", "ring_wrap", "split_brain"):
        assert lat_cnt.all()
    if case == "every_bin":
        assert lat_hist.all()
    if case == "win_restart":
        assert not lat_cnt.any() and not lat_excluded.any()
        np.testing.assert_array_equal(want[4], frontier)
    if case in WINDOW_CASES and case != "win_restart":
        assert lat_cnt.all()
    if case in ("win_last", "win_over"):
        assert adv.max() == log_ops.LAT_WINDOW + (case == "win_over")
    if case == "win_tile_edge":
        first = frontier % cap  # slot of entry frontier + 1
        assert (first // 256 != (first + log_ops.LAT_WINDOW - 1) // 256).all()
    if case == "win_wrap":
        assert (frontier % cap + log_ops.LAT_WINDOW > cap).all()
    if case == "win_base_past_frontier":
        assert (np.asarray(args[3]).max(axis=0) > frontier).all()


def test_cases_fill_every_bin():
    """The cases together put entries in all 16 bins."""
    filled = np.zeros(LAT_HIST_BINS, bool)
    for p in PARAMS:
        case, n, cap, comp = p.values
        args = planes(case, n, cap, comp, seed=_seed(case, n, cap, comp))
        filled |= jax.device_get(reference_jit(*args))[2].sum(axis=1) > 0
    assert filled.all(), np.flatnonzero(~filled)
