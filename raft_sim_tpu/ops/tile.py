"""Node-blocked compacted plane tiling (cfg.compact_planes): the carry layout
that breaks the config5 padding wall.

docs/PERF.md's gated audit proves the dense layout's residual HBM wall is the
five int8 [N, N] planes (next/match/ack_age/req_off/resp_kind) plus the
sublane padding of the narrow word/window planes: in the batch-minor layout a
[51, 51] int8 plane pads its last node axis 51 -> 64 sublanes (policy.SUBLANE)
and a [51, 2]-word uint32 plane pads 2 -> 8, so config5 moves ~72 KB padded
per cluster-tick against ~59 KB logical -- and even the logical bytes carry
dead air, because every per-edge value is stored as a full byte while its
RANGE is a few bits (req_off is an offset in -1..E, resp_kind a RESP_* enum
0..3, next/match are capacity-bounded log indices, ack_age saturates at
cfg.ack_age_sat). This module is the event-sparse re-tiling of exactly those
legs:

  - "pack" legs: the per-edge value planes, flattened row-major over their
    leading (node, node) axes and packed k = 32 // bits values per uint32
    word, bits sized to the leg's config-bounded value range (below). A
    [51, 51] int8 plane becomes a flat [W] uint32 leg: [434] words at 5 bits
    instead of 2601 bytes -- and the flat leg pays only the 8-row sublane
    round-up of a uint32 vector (434 -> 440) instead of the 51 -> 64 per-row
    pad.
  - "flat" legs: already-word-packed or narrow-window planes (votes
    [N, W], the shared entry windows [N, E], the packed delivery mask) merely
    flattened to 1-D so the sublane tile stops padding their tiny minor dim
    (votes at N=51: [51, 2] words pad to [51, 8] = 1632 B; flat [102] pads to
    [104] = 416 B).

Value-range contract (the bit widths; canonical machine-readable form is
`pack_width_table` below -- consumed by the plans here and by the value-range
audit (analysis/range_audit.py) -- restated independently by the oracle
(tests/oracle.py pack_widths) and pinned against this module in
tests/test_constants.py):

  next_index   1 .. cap+1        -> bits_for(cap + 2)   (non-compaction only:
  match_index  0 .. cap             compaction carries absolute unbounded
                                    indices, so both stay dense int32 there)
  ack_age      0 .. ack_age_sat  -> bits_for(sat + 1)
  req_off     -1 .. E  (bias +1) -> bits_for(E + 2)
  resp_kind    0 .. 3 (RESP_*)   -> 2

The layout is PHYSICAL only: the kernel unpacks to the dense planes at tick
entry and repacks at exit (models/raft_batched.step_b), so the
protocol logic -- and every trajectory -- is bit-identical to the dense
layout (tests/test_tile.py pins dense == compacted across word-boundary N).
Carry legs whose structural gate is off are passed through UNTOUCHED via
`reuse` (the carry-passthrough contract: XLA elides them from the per-tick
HBM round trip exactly as in the dense layout). Pack/unpack cost is VPU work
inside the fused tick body; what the scan carries -- and what Pass C prices
(analysis/policy.py padded_bytes) -- is the compacted form.

All ops are integer-only (float-op rule) and flatten BEFORE widening to
uint32, so no [N, N]-shaped widening convert exists for the plane-widening
rule to flag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.utils.config import RaftConfig

WORD = 32


def bits_for(n_values: int) -> int:
    """Bits needed to store values 0 .. n_values-1 (>= 1)."""
    return max(1, (n_values - 1).bit_length())


def index_bits(cfg: RaftConfig) -> int:
    """Bits of a packed log-index plane entry (non-compaction configs only:
    next_index <= cap + 1, match_index <= cap)."""
    return bits_for(cfg.log_capacity + 2)


def age_bits(cfg: RaftConfig) -> int:
    """Bits of a packed ack_age entry (saturates at cfg.ack_age_sat)."""
    return bits_for(cfg.ack_age_sat + 1)


def off_bits(cfg: RaftConfig) -> int:
    """Bits of a packed req_off entry: -1 (snapshot sentinel) .. E, stored
    with a +1 bias."""
    return bits_for(cfg.max_entries_per_rpc + 2)


RESP_BITS = 2  # RESP_* is 0..3 (types.py)


def pack_width_table(cfg: RaftConfig) -> dict[str, tuple[int, int, int, int]]:
    """THE pack-width table: field -> (bits, bias, lo, hi) for every bit-packed
    leg of the compacted layout, where lo..hi is the leg's dense value range
    and bias shifts it non-negative before packing (stored = value + bias,
    0 <= stored < 2**bits).

    Single source of truth: `state_plan`/`mailbox_plan` size their pack legs
    from it, the value-range audit (analysis/range_audit.py, rule
    range-pack-width) proves declared ranges fit these widths, and the
    oracle's independent restatement (tests/oracle.py `pack_widths` -- kept
    import-free of this package so it stays a real second implementation) is
    pinned against it in tests/test_constants.py. Index legs appear only for
    non-compaction configs: compaction carries absolute unbounded indices as
    dense int32, so no width exists for them.
    """
    cap, sat, e = cfg.log_capacity, cfg.ack_age_sat, cfg.max_entries_per_rpc
    table = {}
    if not cfg.compaction:
        table["next_index"] = (index_bits(cfg), 0, 1, cap + 1)
        table["match_index"] = (index_bits(cfg), 0, 0, cap)
    table["ack_age"] = (age_bits(cfg), 0, 0, sat)
    table["mb.req_off"] = (off_bits(cfg), 1, -1, e)
    table["mb.resp_kind"] = (RESP_BITS, 0, 0, 3)
    return table


def words_for(m: int, bits: int) -> int:
    """uint32 words holding m packed values at `bits` bits (k = 32 // bits
    values per word -- whole values never straddle words)."""
    k = WORD // bits
    return -(-m // k)


# --------------------------------------------------------------- word packing


def pack_words(x: jax.Array, bits: int) -> jax.Array:
    """[M, *rest] non-negative ints (< 2**bits) -> [ceil(M/k), *rest] uint32,
    k = 32 // bits values per word, value i at word i // k, lane (i % k) *
    bits. Leading-axis layout serves both the per-cluster ([M]) and
    batch-minor ([M, B]) forms. The widening convert happens on the FLAT
    shape by contract (see module docstring)."""
    k = WORD // bits
    m = x.shape[0]
    w = -(-m // k)
    xu = x.astype(jnp.uint32)
    pad = w * k - m
    if pad:
        xu = jnp.concatenate(
            [xu, jnp.zeros((pad,) + x.shape[1:], jnp.uint32)], axis=0
        )
    xu = xu.reshape((w, k) + x.shape[1:])
    out = jnp.zeros((w,) + x.shape[1:], jnp.uint32)
    for j in range(k):
        out = out | (xu[:, j] << jnp.uint32(bits * j))
    return out


def unpack_words(words: jax.Array, bits: int, m: int, dtype) -> jax.Array:
    """Inverse of `pack_words`: [W, *rest] uint32 -> [m, *rest] `dtype`."""
    k = WORD // bits
    w = words.shape[0]
    assert w == words_for(m, bits), f"{w} words cannot hold {m} x {bits}-bit"
    mask = jnp.uint32((1 << bits) - 1)
    parts = jnp.stack(
        [(words >> jnp.uint32(bits * j)) & mask for j in range(k)], axis=1
    )  # [W, k, *rest]
    flat = parts.reshape((w * k,) + words.shape[1:])[:m]
    return flat.astype(dtype)


# ----------------------------------------------------------------- leg plans


def _flatten(x: jax.Array, lead: int) -> jax.Array:
    """Merge the first `lead` axes (any trailing batch axes ride along)."""
    return x.reshape((-1,) + x.shape[lead:])


def state_plan(cfg: RaftConfig):
    """[(field, mode, lead_shape, bits, bias, dense_dtype)] for the
    ClusterState legs the compacted layout transforms. `mode` is "pack"
    (bit-packed values) or "flat" (reshape only; bits/bias unused)."""
    from raft_sim_tpu import types as rst_types

    n = cfg.n_nodes
    w = bitplane.n_words(n)
    widths = pack_width_table(cfg)
    plan = [("votes", "flat", (n, w), 0, 0, jnp.uint32)]
    if not cfg.compaction:
        # Compaction carries absolute (unbounded) int32 indices: no static
        # bit bound exists, so next/match stay dense there (types.index_dtype)
        # and pack_width_table has no entry for them.
        idt = rst_types.index_dtype(cfg)
        plan += [
            ("next_index", "pack", (n, n), widths["next_index"][0], 0, idt),
            ("match_index", "pack", (n, n), widths["match_index"][0], 0, idt),
        ]
    plan.append(
        ("ack_age", "pack", (n, n), widths["ack_age"][0], 0, rst_types.ack_dtype(cfg))
    )
    return plan


def mailbox_plan(cfg: RaftConfig):
    """The Mailbox legs the compacted layout transforms (same tuple shape as
    `state_plan`). The shared entry windows flatten regardless of their
    gates; gated-off legs are flat zeros passed through untouched
    (`pack_state` reuse)."""
    n, e = cfg.n_nodes, cfg.max_entries_per_rpc
    widths = pack_width_table(cfg)
    return [
        ("req_off", "pack", (n, n), widths["mb.req_off"][0], widths["mb.req_off"][1], jnp.int8),
        ("resp_kind", "pack", (n, n), RESP_BITS, 0, jnp.int8),
        ("ent_term", "flat", (n, e), 0, 0, jnp.int32),
        ("ent_val", "flat", (n, e), 0, 0, jnp.int32),
        ("ent_tick", "flat", (n, e), 0, 0, jnp.int32),
        ("ent_cfg", "flat", (n, e), 0, 0, jnp.int32),
    ]


# Mailbox legs whose structural gate can be OFF (the leg is then a
# loop-invariant zero plane the tick must pass through untouched -- the
# carry-passthrough contract; policy.invariant_leaves names the same gates).
def _mailbox_gates(cfg: RaftConfig) -> dict[str, bool]:
    return {
        "ent_tick": cfg.track_offer_ticks,
        "ent_cfg": cfg.reconfig,
    }


def packed_carry_dtypes(cfg: RaftConfig) -> dict[str, "jnp.dtype"]:
    """Carry-leg name -> dtype for the transformed legs (names in the
    analysis passes' convention: state bare, mailbox `mb.<f>`), so the
    carry-dtype rule can expect uint32 where the compacted layout rides."""
    out = {f: jnp.dtype(jnp.uint32) for f, *_ in state_plan(cfg)}
    for f, mode, *_rest in mailbox_plan(cfg):
        out[f"mb.{f}"] = jnp.dtype(
            jnp.uint32 if mode == "pack" else _rest[-1]
        )
    return out


def _pack_leg(x, mode, lead_shape, bits, bias):
    flat = _flatten(x, len(lead_shape))
    if mode == "flat":
        return flat
    if bias:
        flat = flat + jnp.asarray(bias, flat.dtype)
    return pack_words(flat, bits)


def _unpack_leg(x, mode, lead_shape, bits, bias, dense_dtype):
    if mode == "flat":
        return x.reshape(lead_shape + x.shape[1:]).astype(dense_dtype)
    m = 1
    for d in lead_shape:
        m *= d
    vals = unpack_words(x, bits, m, jnp.int32)
    if bias:
        vals = vals - jnp.int32(bias)
    return vals.astype(dense_dtype).reshape(lead_shape + x.shape[1:])


def pack_state(cfg: RaftConfig, dense, reuse=None):
    """Dense ClusterState -> compacted carry form. `reuse` (the tick's INPUT
    compacted state) supplies the gated-off mailbox legs verbatim, keeping
    them var-identity passthroughs the way the dense kernels do -- XLA then
    elides their HBM round trip (docs/PERF.md round-4 lesson; rule
    carry-passthrough)."""
    reps = {
        f: _pack_leg(getattr(dense, f), mode, shape, bits, bias)
        for f, mode, shape, bits, bias, _dt in state_plan(cfg)
    }
    gates = _mailbox_gates(cfg)
    mb_reps = {}
    for f, mode, shape, bits, bias, _dt in mailbox_plan(cfg):
        if reuse is not None and not gates.get(f, True):
            mb_reps[f] = getattr(reuse.mailbox, f)
        else:
            mb_reps[f] = _pack_leg(getattr(dense.mailbox, f), mode, shape, bits, bias)
    return dense._replace(mailbox=dense.mailbox._replace(**mb_reps), **reps)


def unpack_state(cfg: RaftConfig, s):
    """Compacted carry form -> dense ClusterState (the kernel's working
    view). Exact inverse of `pack_state` for in-range values."""
    reps = {
        f: _unpack_leg(getattr(s, f), mode, shape, bits, bias, dt)
        for f, mode, shape, bits, bias, dt in state_plan(cfg)
    }
    mb_reps = {
        f: _unpack_leg(getattr(s.mailbox, f), mode, shape, bits, bias, dt)
        for f, mode, shape, bits, bias, dt in mailbox_plan(cfg)
    }
    return s._replace(mailbox=s.mailbox._replace(**mb_reps), **reps)


def unpack_inputs(cfg: RaftConfig, inp):
    """Compacted StepInputs -> the kernel's dense view: the packed delivery
    mask ships flat ([N*W] uint32, sim/faults.py) and reshapes back to the
    [N, W] word plane here."""
    n = cfg.n_nodes
    w = bitplane.n_words(n)
    dm = inp.deliver_mask
    return inp._replace(deliver_mask=dm.reshape((n, w) + dm.shape[1:]))
