"""The batch-minor AppendEntries window reads (log_ops.window_rb, the ring;
log_ops.window_b, the prefix log without compaction) against the one-hot
forms they replaced for logs longer than one tile, on synthetic [N, CAP, B]
planes.

The reference below is that form as the kernel read it: an [N, E, CAP, B]
one-hot of each window position ((start + k) mod CAP, a per-slot modulo),
compared, selected and summed over the whole ring. window_rb reads a ring of
CAP > log_ops._TILE slots in two levels (a select tree over tiles, then a
one-hot inside one tile plus E - 1 slots); every window entry must come out
equal. The corners are the ones the two levels add: windows that wrap the
ring's end, starts on either side of a tile boundary, absolute starts many
rings deep, start 0, and the int32 extremes as values.

The prefix form clamps each window position into [0, CAP) instead of
wrapping it; its reference is the clipped one-hot over the whole log, and its
corners are starts on either side of a tile boundary, windows running past
the last slot, starts past the end and below 0 (down to the int32 minimum),
start 0 and the int32 extremes as values.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu.ops import log_ops

B = 64  # clusters per case: N * B lanes cover every tile edge of every CAP
I32 = np.iinfo(np.int32)
K = log_ops._TILE


def reference(arr, start0, e):
    cap = arr.shape[1]
    iota = log_ops.iota
    pos = (start0[:, None, :] + iota((1, e, 1), 1)) % cap  # [N, E, B]
    oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]  # [N, E, CAP, B]
    return jnp.sum(jnp.where(oh, arr[:, None], 0), axis=2)


REF = jax.jit(reference, static_argnums=2)
NEW = jax.jit(log_ops.window_rb, static_argnums=2)
CASES = (
    "wrap",  # the window straddles slot CAP-1 -> 0 (E = 1: it starts on CAP-1)
    "tile_edge",  # starts at q*K - 1, q*K, q*K + K - E and q*K + K - E + 1
    "absolute",  # starts at or above CAP, many rings deep
    "zero",  # every start is 0
    "extremes",  # values 0, -1, and the int32 minimum and maximum
)
CAPS = (64, 128, 129, 255, 256, 257, 511, 700, 4095)
NS = (3, 5)
ES = (1, 2, 8)


def tile_edges(cap, e):
    """The starts on either side of a tile boundary, in [0, CAP)."""
    nt = -(-cap // K)
    return sorted({q * K + d for q in range(nt + 1) for d in (-1, 0, K - e, K - e + 1)}
                  & set(range(cap)))


def planes(case, n, cap, e, seed):
    """Synthetic inputs: (arr, start0)."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(-(1 << 20), 1 << 20, (n, cap, B))
    # Clear of int32 overflow: start0 + E stays below 2^31.
    start0 = rng.integers(0, 1 << 30, (n, B))
    if case == "wrap":
        # The window's first entry sits 0..e-2 slots before the last slot.
        start0 = cap - 1 - rng.integers(0, max(e - 1, 1), (n, B))
        start0 = start0 + cap * rng.integers(0, 4, (n, B))
    if case == "tile_edge":
        edges = np.array(tile_edges(cap, e))
        start0 = edges[np.arange(n * B).reshape(n, B) % len(edges)]
        start0 = start0 + cap * rng.integers(0, 4, (n, B))
    if case == "absolute":
        start0 = rng.integers(cap, I32.max - 2 * cap, (n, B))
        start0[:, :2] = cap * rng.integers(1, 1 << 10, (n, 2)) + rng.integers(0, cap, (n, 2))
    if case == "zero":
        start0[:] = 0
    if case == "extremes":
        pool = np.array([0, -1, I32.min, I32.max, 1, I32.min + 1, I32.max - 1])
        arr = pool[rng.integers(0, len(pool), (n, cap, B))]
        # Lanes 0..3 hold one pool value throughout: 0, -1, min, max.
        arr[:, :, :4] = pool[None, None, :4]
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return i32(arr), i32(start0)


def _params():
    return [
        pytest.param(case, n, cap, e, id=f"{case}-n{n}-cap{cap}-e{e}")
        for n in NS for cap in CAPS for e in ES for case in CASES
    ]


@pytest.mark.parametrize("case,n,cap,e", _params())
def test_window_read_matches_one_hot_form(case, n, cap, e):
    arr, start0 = planes(case, n, cap, e, seed=zlib.crc32(f"{case}/{n}/{cap}/{e}".encode()))
    want = np.asarray(jax.device_get(REF(arr, start0, e)))
    got = np.asarray(jax.device_get(NEW(arr, start0, e)))
    assert got.dtype == want.dtype and got.shape == want.shape == (n, e, B)
    np.testing.assert_array_equal(got, want)
    # Each case is the corner it names.
    s = np.asarray(start0)
    at = s % cap
    if case == "wrap":
        assert (at + e > cap).any() if e > 1 else (at == cap - 1).all()
    if case == "tile_edge":
        assert set(at.ravel().tolist()) == set(tile_edges(cap, e))
        if cap > K:  # both sides of an inner tile boundary
            assert {K - 1, K} <= set(at.ravel().tolist())
    if case == "absolute":
        assert (s >= cap).all() and (s >= 1000 * cap).any()
    if case == "zero":
        assert (s == 0).all()
        np.testing.assert_array_equal(want, np.asarray(arr)[:, :e])
    if case == "extremes":
        for lane, v in enumerate((0, -1, I32.min, I32.max)):
            assert (want[:, :, lane] == v).all(), v


# ---------------------------------------------- the prefix form (window_b)


def prefix_reference(arr, start0, e):
    cap = arr.shape[1]
    iota = log_ops.iota
    pos = jnp.clip(start0[:, None, :] + iota((1, e, 1), 1), 0, cap - 1)  # [N, E, B]
    oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]  # [N, E, CAP, B]
    return jnp.sum(jnp.where(oh, arr[:, None], 0), axis=2)


PREFIX_REF = jax.jit(prefix_reference, static_argnums=2)
PREFIX_NEW = jax.jit(log_ops.window_b, static_argnums=2)
PREFIX_CASES = (
    "tile_edge",  # windows whose first slot, E - 1 slots shifted, is on a tile edge
    "tail",  # the window runs past the last slot (E = 1: it starts on CAP-1)
    "beyond",  # starts at or past CAP, up to the int32 maximum
    "negative",  # starts below 0, down to the int32 minimum
    "zero",  # every start is 0
    "extremes",  # values 0, -1, and the int32 minimum and maximum
)


def prefix_planes(case, n, cap, e, seed):
    """Synthetic inputs: (arr, start0)."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(-(1 << 20), 1 << 20, (n, cap, B))
    start0 = rng.integers(0, cap, (n, B))
    if case == "tile_edge":
        # The two-level read indexes the log with E - 1 slots in front.
        edges = np.array(tile_edges(cap + e - 1, e) + [e - 1, cap + e - 2]) - (e - 1)
        edges = edges[(edges >= 0) & (edges < cap)]
        start0 = edges[np.arange(n * B).reshape(n, B) % len(edges)]
    if case == "tail":
        start0 = cap - 1 - rng.integers(0, max(e - 1, 1), (n, B))
    if case == "beyond":
        # Clear of int32 overflow: start0 + E stays below 2^31.
        start0 = rng.integers(cap, I32.max - e, (n, B))
        start0[:, :2] = (cap, I32.max - e)
    if case == "negative":
        start0 = -rng.integers(1, I32.max, (n, B))
        start0[:, :3] = (-1, -e, I32.min)
    if case == "zero":
        start0[:] = 0
    if case == "extremes":
        pool = np.array([0, -1, I32.min, I32.max, 1, I32.min + 1, I32.max - 1])
        arr = pool[rng.integers(0, len(pool), (n, cap, B))]
        arr[:, :, :4] = pool[None, None, :4]
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return i32(arr), i32(start0)


def _prefix_params():
    return [
        pytest.param(case, n, cap, e, id=f"{case}-n{n}-cap{cap}-e{e}")
        for n in NS for cap in CAPS for e in ES for case in PREFIX_CASES
    ]


@pytest.mark.parametrize("case,n,cap,e", _prefix_params())
def test_prefix_window_read_matches_clipped_one_hot_form(case, n, cap, e):
    arr, start0 = prefix_planes(
        case, n, cap, e, seed=zlib.crc32(f"prefix/{case}/{n}/{cap}/{e}".encode()))
    want = np.asarray(jax.device_get(PREFIX_REF(arr, start0, e)))
    got = np.asarray(jax.device_get(PREFIX_NEW(arr, start0, e)))
    assert got.dtype == want.dtype and got.shape == want.shape == (n, e, B)
    np.testing.assert_array_equal(got, want)
    # Each case is the corner it names.
    s = np.asarray(start0).astype(np.int64)
    a = np.asarray(arr)
    if case == "tile_edge" and cap > K:  # both sides of an inner tile boundary
        assert {K - e, K - e + 1} <= set(s.ravel().tolist())
    if case == "tail":
        assert (s + e > cap).all() if e > 1 else (s == cap - 1).all()
    if case == "beyond":
        assert (s >= cap).all() and (s == I32.max - e).any()
        np.testing.assert_array_equal(want, np.broadcast_to(a[:, -1:], want.shape))
    if case == "negative":
        assert (s < 0).all() and (s == I32.min).any()
    if case == "zero":
        assert (s == 0).all()
        np.testing.assert_array_equal(want, a[:, :e])
    if case == "extremes":
        for lane, v in enumerate((0, -1, I32.min, I32.max)):
            assert (want[:, :, lane] == v).all(), v
