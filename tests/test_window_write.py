"""The batch-minor AppendEntries window writes (log_ops.write_window_b and
log_ops.write_window_rb) against the one-hot forms they replaced, on
synthetic [N, CAP, B] planes.

The references below are those forms as the kernel wrote them: an
[N, E, CAP, B] one-hot of every written entry's position (a per-slot modulo
in the ring form), summed over E into a ring-sized value plane, then selected
into the slots the window covers. Every slot must come out equal.
test_batched_parity.py holds the whole kernel to the oracle along trajectories;
this file reaches the corners a trajectory rarely does (windows that wrap or
run off the ring, absolute starts many rings deep, the compaction skip `lo`,
empty windows, the gate off, and the int32 extremes as values).
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu.ops import log_ops

B = 8  # clusters per case
I32 = np.iinfo(np.int32)


def reference_b(arr, start0, vals, gate, count):
    cap, e = arr.shape[1], vals.shape[1]
    iota = log_ops.iota
    count = jnp.minimum(jnp.where(gate, count, 0), e).astype(jnp.int32)
    mask = iota((1, e, 1), 1) < count[:, None, :]
    pos = jnp.where(mask, start0[:, None, :] + iota((1, e, 1), 1), cap)
    oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]
    cs = iota((1, cap, 1), 1)
    hit = (cs >= start0[:, None, :]) & (cs < (start0 + count)[:, None, :])
    val = jnp.sum(jnp.where(oh, vals[:, :, None, :], 0), axis=1)
    return jnp.where(hit, val, arr)


def reference_rb(arr, start0, vals, gate, lo, count):
    cap, e = arr.shape[1], vals.shape[1]
    iota = log_ops.iota
    count = jnp.minimum(jnp.where(gate, count, 0), e).astype(jnp.int32)
    lo = jnp.clip(lo, 0, e).astype(jnp.int32)
    ks = iota((1, e, 1), 1)
    mask = (ks >= lo[:, None, :]) & (ks < count[:, None, :])
    pos = jnp.where(mask, (start0[:, None, :] + ks) % cap, cap)
    oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]
    rel = (iota((1, cap, 1), 1) - start0[:, None, :]) % cap
    hit = (rel >= lo[:, None, :]) & (rel < count[:, None, :])
    val = jnp.sum(jnp.where(oh, vals[:, :, None, :], 0), axis=1)
    return jnp.where(hit, val, arr)


FORMS = {
    "ring": (jax.jit(reference_rb), jax.jit(log_ops.write_window_rb)),
    "prefix": (jax.jit(reference_b), jax.jit(log_ops.write_window_b)),
}
CASES = (
    "wrap",  # ring: the window straddles slot CAP-1 -> 0; prefix: runs off the end
    "absolute",  # starts at or above CAP, many rings deep
    "lo_skip",  # ring only: the first lo > 0 entries are already compacted
    "empty",  # count <= lo (ring) or count <= 0 (prefix): nothing is written
    "gate_off",  # counts in range, but the gate is off
    "extremes",  # values 0, -1, and the int32 minimum and maximum
)
SHAPES = ((3, 64), (5, 64), (3, 4095), (5, 4095))  # (N, CAP)
ES = (8, 2)


def planes(form, case, n, cap, e, seed):
    """Synthetic inputs: (arr, start0, vals, gate, lo, count)."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(-(1 << 20), 1 << 20, (n, cap, B))
    vals = rng.integers(-(1 << 20), 1 << 20, (n, e, B))
    start0 = rng.integers(0, cap, (n, B))
    gate = rng.random((n, B)) < 0.8
    gate[:, 0] = True
    lo = np.zeros((n, B), np.int64)
    count = rng.integers(1, e + 1, (n, B))
    if case == "wrap":
        # The window's first entry sits 0..e-2 slots before the last slot.
        start0 = cap - 1 - rng.integers(0, max(e - 1, 1), (n, B))
        count[:] = e
        if form == "ring":
            start0 = start0 + cap * rng.integers(0, 4, (n, B))
    if case == "absolute":
        # Clear of int32 overflow: start0 + E stays below 2^31.
        start0 = rng.integers(cap, I32.max - 2 * cap, (n, B))
        start0[:, :2] = cap * rng.integers(1, 1 << 10, (n, 2)) + rng.integers(0, cap, (n, 2))
    if case == "lo_skip":
        lo = rng.integers(1, e + 1, (n, B))
        count = np.maximum(count, lo)
        count[:, 0] = e
        lo[:, 0] = 1
    if case == "empty":
        if form == "ring":
            lo = rng.integers(0, e + 1, (n, B))
            count = lo - rng.integers(0, 3, (n, B))  # count <= lo, some negative
        else:
            count = -rng.integers(0, 3, (n, B))
    if case == "gate_off":
        gate[:] = False
        lo = rng.integers(0, 2, (n, B))
        count[:] = e
    if case == "extremes":
        pool = np.array([0, -1, I32.min, I32.max, 1, I32.min + 1, I32.max - 1])
        vals = pool[rng.integers(0, len(pool), (n, e, B))]
        # Lanes 0..3 write one pool value throughout: 0, -1, min, max.
        vals[:, :, :4] = pool[None, None, :4]
        gate[:, :4] = True
        count[:, :4] = e
        start0[:, :4] = np.minimum(start0[:, :4], cap - e)  # clear of the end
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return i32(arr), i32(start0), i32(vals), jnp.asarray(gate), i32(lo), i32(count)


def _seed(form, case, n, cap, e):
    return zlib.crc32(f"{form}/{case}/{n}/{cap}/{e}".encode())


def _params():
    out = []
    for form in FORMS:
        for n, cap in SHAPES:
            for e in ES:
                for case in CASES:
                    if case == "lo_skip" and form == "prefix":
                        continue  # the prefix form has no compaction skip
                    cid = f"{form}-{case}-n{n}-cap{cap}-e{e}"
                    out.append(pytest.param(form, case, n, cap, e, id=cid))
    return out


@pytest.mark.parametrize("form,case,n,cap,e", _params())
def test_window_write_matches_one_hot_form(form, case, n, cap, e):
    arr, start0, vals, gate, lo, count = planes(form, case, n, cap, e,
                                                seed=_seed(form, case, n, cap, e))
    ref, new = FORMS[form]
    args = (arr, start0, vals, gate, lo, count) if form == "ring" else (
        arr, start0, vals, gate, count)
    want = np.asarray(jax.device_get(ref(*args)))
    got = np.asarray(jax.device_get(new(*args)))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # Each case is the corner it names.
    written = want != np.asarray(arr)
    if case in ("empty", "gate_off") or (case == "absolute" and form == "prefix"):
        assert not written.any()
    else:
        assert written.any()
    if case == "wrap" and form == "ring":
        assert (written[:, 0] & written[:, cap - 1]).any()  # both ends of the ring
    if case == "wrap" and form == "prefix":
        assert written[:, cap - 1].any() and not written[:, : cap - e].any()
    if case == "extremes":
        for lane, v in enumerate((0, -1, I32.min, I32.max)):
            assert (want[:, :, lane] == v).sum() >= n * e, v
