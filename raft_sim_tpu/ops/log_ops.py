"""Fixed-capacity replicated-log array ops.

The reference's log is an unbounded Clojure vector in an atom with 1-based indexing
where index 0 means "no entry" (log.clj:20-23, 33). XLA needs static shapes, so here a
log is a [N, CAP] term/value array pair plus a [N] length counter; every reference log
op maps to a masked gather/scatter:

  last-entry (log.clj:47-49)      -> last_index/last_term   (spec-correct: the actual
                                     last *log* entry; the reference returns the commit
                                     index instead -- documented bug, SURVEY.md 2.3.3)
  val-at (log.clj:20-23)          -> term_at (1-based, 0 -> "no entry" sentinel 0)
  entries-from (log.clj:51-53)    -> window (bounded E-entry slice; the reference ships
                                     arbitrary suffixes, core.clj:59-67)
  append-entries!/remove-from!
  (log.clj:61-64, 78-81)          -> the caller writes via write_window (truncation is
                                     just a smaller length + overwrite; spec-correct,
                                     unlike remove-from!'s drop-last bug, SURVEY.md 2.3.7)

All functions are written for a single cluster ([N, CAP] / [N] shapes) and are vmap'd
over the batch axis by the step kernel's callers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def term_at(log_term: jax.Array, index1: jax.Array) -> jax.Array:
    """Term of the 1-based `index1`-th entry per node; 0 where index1 == 0 (no entry).

    log_term: [N, CAP]; index1: [N] or [N, K] -> result matches index1's shape.
    """
    cap = log_term.shape[-1]
    idx = jnp.clip(index1 - 1, 0, cap - 1)
    if index1.ndim == 1:
        got = jnp.take_along_axis(log_term, idx[:, None], axis=1)[:, 0]
    else:
        got = jnp.take_along_axis(log_term, idx, axis=1)
    return jnp.where(index1 > 0, got, 0)


def last_index_term(log_term: jax.Array, log_len: jax.Array):
    """(last 1-based index, term of last entry) per node -- spec-correct `last-entry`."""
    return log_len, term_at(log_term, log_len)


def window(arr: jax.Array, start0: jax.Array, e: int) -> jax.Array:
    """Gather an E-entry window per (row, start): out[..., k] = arr[row, start0 + k].

    arr: [N, CAP]; start0: [N] or [N, M] 0-based start slot. Out-of-range slots return
    arr's last slot (callers mask with an explicit count).
    """
    cap = arr.shape[-1]
    ks = jnp.arange(e, dtype=jnp.int32)
    pos = jnp.clip(start0[..., None] + ks, 0, cap - 1)  # [N, (M,) E]
    n = arr.shape[0]
    if start0.ndim == 1:
        rows = jnp.arange(n)[:, None]
    else:
        rows = jnp.arange(n)[:, None, None]
    return arr[rows, pos]


def write_window(
    arr: jax.Array,
    start0: jax.Array,
    vals: jax.Array,
    mask: jax.Array,
) -> jax.Array:
    """Scatter vals[n, k] into arr[n, start0[n] + k] where mask[n, k]; masked-off or
    out-of-capacity writes are dropped.

    arr: [N, CAP]; start0: [N]; vals/mask: [N, E].
    """
    n, cap = arr.shape
    e = vals.shape[-1]
    ks = jnp.arange(e, dtype=jnp.int32)
    pos = start0[:, None] + ks  # [N, E]
    # Route masked-off writes out of bounds; mode='drop' discards them.
    pos = jnp.where(mask, pos, cap)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, e))
    return arr.at[rows, pos].set(vals, mode="drop")


# --------------------------------------------------------------------------------------
# Committed-prefix checksum. The "committed entries are immutable" invariant used to
# compare the full old vs new log arrays every tick -- the single most expensive
# fusion of the config3 tick (~15%, it re-reads 4 [N, CAP, B] arrays). Instead the
# state carries a weighted checksum of the committed prefix (ClusterState.commit_chk):
# one masked pass over the NEW arrays both recomputes the old-prefix sum (must equal
# the carried checksum -- any rewrite of a committed slot changes it w.h.p.) and
# extends it to the new commit bound. Detection is probabilistic (a rewrite must
# preserve a weighted sum mod 2^32 to escape; weights are odd mixing constants), which
# is ample for an implementation-bug detector, and it additionally catches committed
# -prefix corruption *between* ticks, which the old same-tick compare could not.
# Weights formula duplicated in tests/oracle.py -- keep in sync.
# --------------------------------------------------------------------------------------


def chk_weights_at(abs0):
    """Odd uint32 mixing weights (terms, values) for an array of ABSOLUTE 0-based
    entry indices -- the general form of chk_weights, needed once compaction makes
    a slot's entry index exceed its slot number (ring layout)."""
    a = abs0.astype(jnp.uint32)
    w_term = (a * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9)) | jnp.uint32(1)
    w_val = (a * jnp.uint32(0x85EBCA77) + jnp.uint32(0xC2B2AE3D)) | jnp.uint32(1)
    return w_term, w_val


def chk_weights(cap: int):
    """Per-slot weights for the prefix (non-ring) layout, where slot k holds the
    0-based entry k."""
    return chk_weights_at(jnp.arange(cap, dtype=jnp.uint32))


def prefix_chk2(log_term, log_val, upto_a, upto_b):
    """Checksums of the prefixes below 1-based counts `upto_a` and `upto_b`, in one
    pass. log_term/log_val: [N, CAP]; upto_*: [N] -> (uint32 [N], uint32 [N])."""
    cap = log_term.shape[-1]
    w_t, w_v = chk_weights(cap)
    contrib = log_term.astype(jnp.uint32) * w_t + log_val.astype(jnp.uint32) * w_v
    ks = jnp.arange(cap, dtype=jnp.int32)
    in_a = ks[None, :] < upto_a[:, None]
    in_b = ks[None, :] < upto_b[:, None]
    z = jnp.uint32(0)
    return (
        jnp.sum(jnp.where(in_a, contrib, z), axis=1, dtype=jnp.uint32),
        jnp.sum(jnp.where(in_b, contrib, z), axis=1, dtype=jnp.uint32),
    )


def prefix_chk2_b(log_term, log_val, upto_a, upto_b):
    """Batch-minor prefix_chk2. log_term/log_val: [N, CAP, B]; upto_*: [N, B]."""
    cap = log_term.shape[1]
    w_t, w_v = chk_weights(cap)
    contrib = (
        log_term.astype(jnp.uint32) * w_t[None, :, None]
        + log_val.astype(jnp.uint32) * w_v[None, :, None]
    )
    ks = iota((1, cap, 1), 1)
    in_a = ks < upto_a[:, None, :]
    in_b = ks < upto_b[:, None, :]
    z = jnp.uint32(0)
    return (
        jnp.sum(jnp.where(in_a, contrib, z), axis=1, dtype=jnp.uint32),
        jnp.sum(jnp.where(in_b, contrib, z), axis=1, dtype=jnp.uint32),
    )


# --------------------------------------------------------------------------------------
# Ring variants (compaction, cfg.compact_margin > 0): 1-based entry i lives at slot
# (i - 1) mod CAP; live slots hold entries (log_base, log_len] with
# log_len - log_base <= CAP (types.ClusterState). Entries at or below log_base exist
# only as (log_base, base_term, base_chk). With log_base == 0 every ring form
# degenerates to its prefix counterpart bit-for-bit; the kernel still calls the
# prefix forms for non-compaction configs so those stay mod-free.
# --------------------------------------------------------------------------------------


def term_at_r(log_term: jax.Array, base: jax.Array, base_term: jax.Array, index1):
    """Ring-aware term_at: the ring slot's term for base < index1 <= base + CAP;
    base_term for 0 < index1 <= base (the compacted prefix -- callers gate on what
    the protocol may actually compare there); 0 for index1 == 0.

    log_term: [N, CAP]; base/base_term: [N]; index1: [N] or [N, K].
    """
    cap = log_term.shape[-1]
    idx = (index1 - 1) % cap
    if index1.ndim == 1:
        got = jnp.take_along_axis(log_term, idx[:, None], axis=1)[:, 0]
    else:
        got = jnp.take_along_axis(log_term, idx, axis=1)
        base = base[:, None]
        base_term = base_term[:, None]
    return jnp.where(index1 == 0, 0, jnp.where(index1 <= base, base_term, got))


def window_r(arr: jax.Array, start0: jax.Array, e: int) -> jax.Array:
    """Ring window: out[..., k] = arr[row, (start0 + k) mod CAP]. Callers mask with
    an explicit count (slots past the live range hold unrelated ring content)."""
    cap = arr.shape[-1]
    ks = jnp.arange(e, dtype=jnp.int32)
    # Unsigned modulo: start0 is an absolute (non-negative) ring anchor, so the
    # uint view is value-identical -- and it skips the python-mod sign-fix
    # select, leaving a provably in-[0, CAP) index (Pass E range-index-oob).
    pos = ((start0[..., None] + ks).astype(jnp.uint32) % cap).astype(jnp.int32)
    n = arr.shape[0]
    rows = jnp.arange(n)[:, None] if start0.ndim == 1 else jnp.arange(n)[:, None, None]
    return arr[rows, pos]


def write_window_r(
    arr: jax.Array, start0: jax.Array, vals: jax.Array, mask: jax.Array
) -> jax.Array:
    """Ring write_window: vals[n, k] -> arr[n, (start0[n] + k) mod CAP] where
    mask[n, k]. Masked-on positions are distinct mod CAP because the caller keeps
    the retained window within CAP (log_len - log_base <= CAP)."""
    n, cap = arr.shape
    e = vals.shape[-1]
    ks = jnp.arange(e, dtype=jnp.int32)
    pos = jnp.where(mask, (start0[:, None] + ks) % cap, cap)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, e))
    return arr.at[rows, pos].set(vals, mode="drop")


def ring_chk(log_term, log_val, base, uptos: tuple):
    """Checksum sums over live ring entries (base, upto] for each upto in `uptos`,
    weighted by ABSOLUTE entry index -- the ring generalization of prefix_chk2
    (bit-identical for base == 0). A node's checksum-at-prefix-p is then
    base_chk + ring_chk(..., (p,))[0] for any p in [base, log_len].

    log_term/log_val: [N, CAP]; base: [N]; returns a tuple of uint32 [N].
    """
    cap = log_term.shape[-1]
    s = jnp.arange(cap, dtype=jnp.int32)[None, :]
    abs0 = base[:, None] + (s - base[:, None]) % cap  # 0-based entry index of slot s
    w_t, w_v = chk_weights_at(abs0)
    contrib = log_term.astype(jnp.uint32) * w_t + log_val.astype(jnp.uint32) * w_v
    z = jnp.uint32(0)
    return tuple(
        jnp.sum(jnp.where(abs0 < u[:, None], contrib, z), axis=1, dtype=jnp.uint32)
        for u in uptos
    )


# --------------------------------------------------------------------------------------
# Batch-minor variants: identical semantics with a trailing batch axis B. The batch
# rides the TPU lane dimension (128-wide minor tile), so these are the hot-path forms
# (models/raft_batched.py); the unsuffixed single-cluster forms above stay as the
# readable reference semantics used via vmap in debug/trace paths.
#
# No gather/scatter anywhere: with the batch minor, dynamic indices would vary along
# the lane dimension, which TPU gathers serialize (measured ~5x slower than the whole
# rest of the tick). CAP/E are small static dims, so every indexed read is instead a
# one-hot compare-and-reduce over the indexed axis -- pure elementwise work that
# vectorizes across lanes; the ring's window read first narrows that axis to one
# tile by a select tree (window_rb) -- and every window write is a select on each
# slot's window offset (_at_offset). Equality with the gather forms is pinned by
# tests/test_batched_parity.py; tests/test_window_write.py and
# tests/test_window_read.py hold the writes and the tiled read to the one-hot forms
# they replaced.
# --------------------------------------------------------------------------------------


def log2_bin(v: jax.Array, n_bins: int) -> jax.Array:
    """Elementwise floor(log2(v)) clamped to [0, n_bins): the latency
    histogram's bin index (types.LAT_HIST_BINS semantics), via an unrolled
    binary bit-length reduction -- no float log in any hot loop. Call site:
    raft_batched.py's read-latency histogram. raft_batched.offer_latency_b
    counts the same bins by thresholds (v >= 2^k) without calling it, held
    equal by tests/test_latency_accounting.py: a binning change edits both.
    v must be >= 0; v in {0, 1} lands in bin 0."""
    bl = jnp.zeros_like(v)
    for sft in (16, 8, 4, 2, 1):
        m_ = v >= (1 << sft)
        bl = bl + m_ * sft
        v = jnp.where(m_, v >> sft, v)
    return jnp.minimum(bl, n_bins - 1)


def iota(shape, d):
    """int32 iota built at its final rank. The single shared helper for all batched
    kernels: Mosaic (Pallas TPU) cannot lower the unit-dim-appending reshapes that
    `jnp.arange(...)[None, :, None]` produces, and these ops run inside the
    pallas_engine kernel. (window_rb's tiled form, at CAP > _TILE, adds a wrapping
    pad and unaligned static slices along CAP that no Mosaic lowering has been
    tried on: tests/test_pallas.py runs CAP 8, one tile.)"""
    return jax.lax.broadcasted_iota(jnp.int32, shape, d)


def _select_tree(cands: list, idx: jax.Array) -> jax.Array:
    """cands[idx], elementwise: a select tree on idx's low bits (len(cands) - 1
    selects), with no reduction over the candidates. Where idx >= len(cands) the
    result is one of them. The candidates broadcast against idx."""
    level = list(cands)
    bit = 1
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])  # reached only by idx >= len(cands)
        odd = (idx & bit) != 0
        level = [jnp.where(odd, v1, v0) for v0, v1 in zip(level[::2], level[1::2])]
        bit <<= 1
    return level[0]


def _at_offset(vals: jax.Array, rel: jax.Array) -> jax.Array:
    """out[n, s, b] = vals[n, rel[n, s, b], b] for rel in [0, E), elementwise: a
    select tree on rel (E - 1 selects), with no reduction over E and no
    [N, E, CAP, B] one-hot, so the window writes fuse into their consumers
    instead of materializing a ring-sized plane. Where rel >= E the result is
    one of vals' entries; the write forms mask those slots off.
    vals: [N, E, B]; rel: [N, CAP, B] -> [N, CAP, B]."""
    cands = [vals[:, k : k + 1] for k in range(vals.shape[1])]
    return jnp.broadcast_to(_select_tree(cands, rel), rel.shape)


def term_at_b(log_term: jax.Array, index1: jax.Array) -> jax.Array:
    """Batched term_at. log_term: [N, CAP, B]; index1: [N, B] or [N, M, B].

    index1 == 0 matches no slot and yields 0 (the "no entry" sentinel), like the
    where(index1 > 0, ...) mask in the gather form. Precondition (both variants):
    index1 <= cap — callers clip to log_len <= cap. Above cap this form yields 0
    while the gather form clamps to the last slot; do not rely on either.
    """
    cap = log_term.shape[1]
    if index1.ndim == 2:  # [N, B] -> [N, B]
        oh = iota((1, cap, 1), 1) == (index1 - 1)[:, None, :]  # [N, CAP, B]
        return jnp.sum(jnp.where(oh, log_term, 0), axis=1)
    # [N, M, B] -> [N, M, B]
    oh = iota((1, 1, cap, 1), 2) == (index1 - 1)[:, :, None, :]  # [N, M, CAP, B]
    return jnp.sum(jnp.where(oh, log_term[:, None], 0), axis=2)


def last_index_term_b(log_term: jax.Array, log_len: jax.Array):
    """Batched last_index_term. log_term: [N, CAP, B]; log_len: [N, B]."""
    return log_len, term_at_b(log_term, log_len)


def window_b(arr: jax.Array, start0: jax.Array, e: int) -> jax.Array:
    """Batched window. arr: [N, CAP, B]; start0: [N, B] -> [N, E, B], or
    [N, M, B] -> [N, M, E, B]. Out-of-range slots clamp to the first or last slot
    (callers mask with an explicit count), matching the clipped gather form.

    An [N, B] start into a log longer than one tile (CAP > _TILE) is read in
    window_rb's two levels over the log extended by E - 1 copies of its first
    slot in front and copies of its last slot behind, so a clamped position
    reads the slot it clamps to and no reduction runs over CAP. Only the tiles
    that reach past either end are assembled; the others are slices of the
    log."""
    cap = arr.shape[1]
    if start0.ndim == 2 and cap > _TILE:
        nt = -(-(cap + e - 1) // _TILE)
        s = jnp.clip(start0, 1 - e, cap - 1) + (e - 1)  # [N, B], in [0, CAP + E - 1)
        q = s // _TILE
        r = s - q * _TILE
        tiles = []
        for t in range(nt):
            lo, hi = t * _TILE - (e - 1), (t + 1) * _TILE  # log slots of tile t
            parts = [arr[:, max(lo, 0) : min(hi, cap)]]
            if lo < 0:
                parts.insert(0, jnp.repeat(arr[:, :1], -lo, axis=1))
            if hi > cap:
                parts.append(jnp.repeat(arr[:, cap - 1 :], hi - cap, axis=1))
            tiles.append(jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0])
        return _tile_read(tiles, q, r, e)
    if start0.ndim == 2:  # [N, B]
        pos = jnp.clip(start0[:, None, :] + iota((1, e, 1), 1), 0, cap - 1)  # [N, E, B]
        oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]  # [N, E, CAP, B]
        return jnp.sum(jnp.where(oh, arr[:, None], 0), axis=2)
    # [N, M, B]
    pos = jnp.clip(start0[:, :, None, :] + iota((1, 1, e, 1), 2), 0, cap - 1)
    oh = iota((1, 1, 1, cap, 1), 3) == pos[:, :, :, None, :]  # [N, M, E, CAP, B]
    return jnp.sum(jnp.where(oh, arr[:, None, None], 0), axis=3)


def write_window_b(
    arr: jax.Array,
    start0: jax.Array,
    vals: jax.Array,
    gate: jax.Array,
    count: jax.Array,
) -> jax.Array:
    """Batched write_window, restricted to the contiguous-prefix writes the kernel
    actually does: where `gate[n, b]`, write vals[n, k, b] into arr[n, start0 + k, b]
    for k < count[n, b]. arr: [N, CAP, B]; start0/gate/count: [N, B]; vals: [N, E, B].

    Taking (gate, count) instead of a free-form [N, E, B] mask makes the old
    implicit precondition (mask must be a contiguous prefix along E) structural:
    the written-slot test is two compares of the slot's window offset against
    [0, count), and no caller can pass a mask shape it would silently
    mis-handle. Offsets past the last slot match no slot (the scatter form's
    mode='drop')."""
    cap = arr.shape[1]
    e = vals.shape[1]
    count = jnp.minimum(jnp.where(gate, count, 0), e).astype(jnp.int32)  # [N, B]
    rel = iota((1, cap, 1), 1) - start0[:, None, :]  # [N, CAP, B] window offset
    hit = (rel >= 0) & (rel < count[:, None, :])
    return jnp.where(hit, _at_offset(vals, rel), arr)


# ---- batch-minor ring forms (compaction; see the ring section above) -----------------

# Slots per tile of window_rb's two-level read: a multiple of the 128-lane width,
# so each tile's slice starts on a vector-register boundary. 256 over 128 from
# traced runs of fleet-etcd3-b1000 (CAP 4,095, B 1,000) on one TPU v5e: 972.95
# against 981.10 ns a cluster-tick (outbox 181.2 against 190.8 ns), and
# 1,017,807 / 1,018,628 against 1,009,535 / 1,009,425 cluster-ticks/s untraced
# on two seeds.
_TILE = 256

# Entries above the latency frontier that raft_batched.offer_latency_b
# covers at CAP > _TILE: it counts over the (_TILE + LAT_WINDOW - 1)-slot
# slice of the stamp ring from the tile holding the frontier, and a tick on
# which some cluster's max commit advances further sweeps the whole ring.
# The largest advance per tick over a fleet, on one TPU v5e: at most 6
# entries at B = 1,000 (fleet-etcd3-b1000 and serve-etcd3-saturated's
# load); at B = 10,000 under fleet-etcd5-wal-jepsen's nemeses a median of
# 11, 73 at the 90th percentile, 117-129 at the 99th, 225 at most (2 seeds
# of 1,024 ticks). At 16 or 64, 40% or 25% of that cell's ticks swept the
# ring; at 128, 1 tick in 512. Traced ns a cluster-tick, parent -> 16 /
# 128 / 256, with the pass read before the injection in both cells: b1000
# 972.86 -> 877.43 / 843.88 / 856.55; wal 2,179.34 -> 2,139.83 / 2,142.87
# / 2,220.04.
LAT_WINDOW = 128


def term_at_rb(log_term, base, base_term, index1):
    """Batched term_at_r. log_term: [N, CAP, B]; base/base_term/index1: [N, B]."""
    cap = log_term.shape[1]
    oh = iota((1, cap, 1), 1) == ((index1 - 1) % cap)[:, None, :]  # [N, CAP, B]
    got = jnp.sum(jnp.where(oh, log_term, 0), axis=1)
    return jnp.where(index1 == 0, 0, jnp.where(index1 <= base, base_term, got))


def window_rb(arr: jax.Array, start0: jax.Array, e: int) -> jax.Array:
    """Batched window_r. arr: [N, CAP, B]; start0: [N, B] -> [N, E, B].

    A ring of one tile (CAP <= _TILE) is read by an [N, E, CAP, B] one-hot.
    A longer ring is read in two levels, so no reduction runs over CAP: the
    start is reduced mod CAP once per [N, B] and split into a tile index q
    and an in-tile offset r; a select tree on q's bits picks, per [N, B], the
    (_TILE + E - 1)-slot slice starting at slot q * _TILE (slices past CAP
    wrap to the ring's head); the one-hot then runs over that slice at r.
    No gather: with the batch minor its indices would vary along the lanes
    (see the section note above)."""
    cap = arr.shape[1]
    if cap <= _TILE:
        pos = (start0[:, None, :] + iota((1, e, 1), 1)) % cap  # [N, E, B]
        oh = iota((1, 1, cap, 1), 2) == pos[:, :, None, :]  # [N, E, CAP, B]
        return jnp.sum(jnp.where(oh, arr[:, None], 0), axis=2)
    tiles, q, r = _ring_tiles(arr, start0, e)
    return _tile_read(tiles, q, r, e)


def ring_tile(arr: jax.Array, start0: jax.Array, e: int):
    """The first level of window_rb's read alone, at CAP > _TILE: (tile, r)
    with tile [N, _TILE + E - 1, B] the ring's slots from q * _TILE on
    (wrapping past CAP), picked per [N, B] by a select tree, and r [N, B]
    the offset in it of slot start0 mod CAP, where start0 mod CAP =
    q * _TILE + r. Slots r .. r + E - 1 of the tile are the E-slot window
    at start0. arr: [N, CAP, B]; start0: [N, B] (or [1, B], broadcast)."""
    tiles, q, r = _ring_tiles(arr, start0, e)
    return _select_tree(tiles, q[:, None, :]), r


def _ring_tiles(arr, start0, e):
    """window_rb's candidate tiles: the (_TILE + E - 1)-slot slices of the
    ring starting at slots 0, _TILE, 2 * _TILE, ... (wrapped past CAP by a
    pad), and the tile index q and in-tile offset r of start0 mod CAP."""
    cap = arr.shape[1]
    nt = -(-cap // _TILE)
    s = start0 % cap  # [N, B]
    q = s // _TILE
    r = s - q * _TILE
    ring = jnp.pad(arr, ((0, 0), (0, nt * _TILE + e - 1 - cap), (0, 0)), mode="wrap")
    w = _TILE + e - 1
    return [ring[:, t * _TILE : t * _TILE + w] for t in range(nt)], q, r


def _tile_read(tiles: list, q: jax.Array, r: jax.Array, e: int) -> jax.Array:
    """The two-level window read of window_rb and window_b: `tiles` are the
    [N, _TILE + E - 1, B] slices that start at slots 0, _TILE, 2 * _TILE, ...;
    q/r [N, B] are the tile index and the in-tile offset of each window's
    first slot. A select tree on q's bits picks each window's tile; an
    [N, E, _TILE + E - 1, B] one-hot reads the window out of it at r."""
    w = _TILE + e - 1
    tile = _select_tree(tiles, q[:, None, :])  # [N, w, B]
    pos = r[:, None, :] + iota((1, e, 1), 1)  # [N, E, B], in [0, w)
    oh = iota((1, 1, w, 1), 2) == pos[:, :, None, :]  # [N, E, w, B]
    return jnp.sum(jnp.where(oh, tile[:, None], 0), axis=2)


def write_window_rb(arr, start0, vals, gate, lo, count):
    """Batched ring write over the window-slice [lo, count): where gate[n, b],
    write vals[n, k, b] into slot (start0 + k) mod CAP for lo <= k < count.
    The extra `lo` bound (vs write_window_b) is the compaction skip: shipped
    entries at or below the receiver's log_base are already committed and
    compacted, so the write starts partway into the window. Written positions are
    distinct mod CAP (retained window <= CAP).

    The start is reduced mod CAP once per [N, B]; a slot's window offset is
    then its distance from that start plus CAP where negative -- no per-slot
    modulo."""
    cap = arr.shape[1]
    e = vals.shape[1]
    count = jnp.minimum(jnp.where(gate, count, 0), e).astype(jnp.int32)  # [N, B]
    lo = jnp.clip(lo, 0, e).astype(jnp.int32)
    rel = iota((1, cap, 1), 1) - (start0 % cap)[:, None, :]  # [N, CAP, B]
    rel = jnp.where(rel < 0, rel + cap, rel)  # slot's window offset, in [0, CAP)
    hit = (rel >= lo[:, None, :]) & (rel < count[:, None, :])
    return jnp.where(hit, _at_offset(vals, rel), arr)


def ring_chk_b(log_term, log_val, base, uptos: tuple):
    """Batched ring_chk. log_term/log_val: [N, CAP, B]; base/uptos: [N, B]."""
    cap = log_term.shape[1]
    s = iota((1, cap, 1), 1)
    abs0 = base[:, None, :] + (s - base[:, None, :]) % cap  # [N, CAP, B]
    w_t, w_v = chk_weights_at(abs0)
    contrib = log_term.astype(jnp.uint32) * w_t + log_val.astype(jnp.uint32) * w_v
    z = jnp.uint32(0)
    return tuple(
        jnp.sum(jnp.where(abs0 < u[:, None, :], contrib, z), axis=1, dtype=jnp.uint32)
        for u in uptos
    )
