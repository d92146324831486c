"""Fault injection and per-tick input generation, as pure data.

In the reference, faults are accidental: a dead or unreachable peer makes the outbound
HTTP call throw, the exception is swallowed, and the message vanishes (client.clj:38-40);
election timeouts are the only failure detector (core.clj:171-174); there is no fault
*injection* at all (SURVEY.md section 5). Here fault schedules are first-class pure
inputs derived deterministically from (cluster key, tick):

  - Bernoulli message drop, optionally with a per-cluster drop rate drawn from
    [0, drop_prob] (BASELINE config 4),
  - rolling partitions: every `partition_period` ticks the cluster is (with some
    probability) split into two random halves whose cross edges deliver nothing
    (BASELINE config 5),
  - clock skew: a node's local clock occasionally stalls (+0) or jumps (+2),
  - node crash/restart: a windowed renewal schedule (alive_at) downs nodes for
    bounded spans; restart wipes spec-volatile state but keeps the Raft persistent
    triple -- unlike the reference, whose restarted process loses term/vote/entries
    (log.clj:16-18, SURVEY.md 2.3.12),
  - randomized election-timeout draws (the reference's 5000+rand(5000) ms,
    core.clj:174),
  - client command injection on a fixed cadence (the reference's external curl against
    /client-set, server.clj:8-12).

Everything is a function of (key, now), so trajectories are replayable from a seed and
checkpoint/resume needs only (state, key) -- no RNG state in the carry.

Every Bernoulli event is drawn as a uint32 THRESHOLD COMPARE (`p_to_u32` /
`bern_u32`): `random_bits_u32 < threshold` instead of `uniform_float < p`. Two
reasons. (1) The whole per-tick input pipeline stays integer-only, so the
full compiled scan program -- not just the step kernels -- is float-free and
the analyzer's float-op rule extends to it. (2) The threshold is DATA, not a
baked Python float: the scenario engine (raft_sim_tpu/scenario) threads a
per-cluster `ScenarioGenome` of traced `[S]`-segment fault parameters through
`make_inputs`, and because the scalar-config path and the genome path draw
through the SAME helpers from the SAME key streams, a genome that replicates
the config scalars reproduces the scalar path's trajectories BIT-FOR-BIT
(tests/test_scenario.py pins this). The genome is duck-typed here (fields
`drop/part_period/part/crash/crash_down/skew/client_interval` plus the
reconfiguration-plane cadences `reconfig_interval/transfer_interval/
read_interval` and the disk-fault axes `fsync_interval/fsync_jitter/
torn/torn_span`, each a `[S]` per-segment leaf -- see scenario/genome.py);
sim/ never imports scenario/.

The per-cluster key is split once into disjoint streams (per-tick draws, per-cluster
drop rate, per-window partition layout) so no fold_in value can collide across
purposes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from raft_sim_tpu.ops import bitplane
from raft_sim_tpu.types import NIL, StepInputs
from raft_sim_tpu.utils.config import RaftConfig
from raft_sim_tpu.utils.rng import draw_timeouts

# Threshold encoding of p = 0.5 (the partition group split): exactly half the
# uint32 space.
HALF_U32 = 1 << 31


def p_to_u32(p: float) -> int:
    """Probability -> uint32 Bernoulli threshold: an event fires iff a fresh
    uint32 draw is < the threshold, so P(fire) = threshold / 2^32. p = 1.0
    clamps to 2^32 - 1 (fires with probability 1 - 2^-32); p = 0.0 encodes to
    0 and can never fire. Host-side Python only -- the returned int enters the
    traced program as a uint32 literal (scalar configs) or rides a genome leaf
    (scenario engine)."""
    return max(0, min((1 << 32) - 1, int(round(p * (1 << 32)))))


def bern_u32(key: jax.Array, thresh, shape=()) -> jax.Array:
    """Bernoulli(thresh / 2^32) as an integer threshold compare over fresh
    uint32 bits. `thresh` is a uint32 scalar -- a literal on the scalar-config
    path, traced genome data on the scenario path; both consume the identical
    draw from `key`, which is what makes homogeneous-genome trajectories
    bit-exact with the scalar path."""
    return jax.random.bits(key, shape, jnp.uint32) < thresh


def crash_key(key: jax.Array) -> jax.Array:
    """The dedicated crash-schedule stream for a cluster key. fold_in(-1) is disjoint
    from the per-window fold_in(k_part, window >= 0) draws sharing this base."""
    _, _, k_part = jax.random.split(key, 3)
    return jax.random.fold_in(k_part, jnp.int32(-1))


def _alive_at_t(cfg: RaftConfig, ckey, now, crash_t, crash_down):
    """The ungated windowed-renewal body shared by the scalar path (alive_at)
    and the genome path (make_inputs): `crash_t` is the uint32 crash
    threshold, `crash_down` the max down-span -- literals on the scalar path,
    traced per-cluster genome data on the scenario path; the window length
    stays cfg.crash_period (static) either way."""
    n = cfg.n_nodes
    window = now // cfg.crash_period
    off = now - window * cfg.crash_period
    wkey = jax.random.fold_in(ckey, window)
    k_sel, k_start, k_dur = jax.random.split(wkey, 3)
    crashed = bern_u32(k_sel, crash_t, (n,))
    start = jax.random.randint(k_start, (n,), 0, cfg.crash_period)
    dur = jax.random.randint(k_dur, (n,), 1, crash_down + 1)
    down = crashed & (off >= start) & (off < start + dur) & (now >= 0)
    return ~down


def alive_at(cfg: RaftConfig, ckey: jax.Array, now: jax.Array) -> jax.Array:
    """[N] bool node liveness at tick `now` -- a pure function of the crash stream, so
    trajectories stay replayable with no RNG or downtime counter in the scan carry.

    Windowed renewal process: node i is down during ticks
    [w*P + start_i, w*P + start_i + dur_i) of window w (clipped at the window edge,
    so a node is never down across a window boundary) iff its per-window Bernoulli
    crash draw fired. `now < 0` reports alive (so tick 0 is never a "restart").
    """
    if cfg.crash_prob <= 0:
        return jnp.ones((cfg.n_nodes,), bool)
    return _alive_at_t(
        cfg, ckey, now, jnp.uint32(p_to_u32(cfg.crash_prob)), cfg.crash_down_ticks
    )


def _partition_cut(
    n: int, k_part: jax.Array, now: jax.Array, period, part_t
) -> jax.Array:
    """[N, N] bool: True on edges CUT by the rolling partition this tick.
    Assignment is stable within each window of `period` ticks because it is
    keyed by the window index, not the tick. `period` may be traced (genome
    path; 0 disables via the `period > 0` gate, the `maximum` only guards the
    division)."""
    window = now // jnp.maximum(period, 1)
    wkey = jax.random.fold_in(k_part, window)
    k_group, k_active = jax.random.split(wkey)
    group = bern_u32(k_group, jnp.uint32(HALF_U32), (n,))
    active = bern_u32(k_active, part_t) & (period > 0)
    same_side = group[:, None] == group[None, :]
    return ~same_side & active


def _skew_draw(n: int, k_skew: jax.Array, skew_t) -> jax.Array:
    """[N] int32 local-clock increments: stall (+0) on the first half of the
    threshold window, jump (+2) on the second, +1 otherwise."""
    r = jax.random.bits(k_skew, (n,), jnp.uint32)
    return jnp.where(r < (skew_t >> 1), 0, jnp.where(r < skew_t, 2, 1)).astype(
        jnp.int32
    )


def _admin_cmds(cfg: RaftConfig, tkey: jax.Array, now: jax.Array,
                rcfg_i, xfer_i, read_i, traced: bool):
    """(reconfig_cmd, transfer_cmd, read_cmd) draws -- the reconfiguration
    plane's admin offers (raft_sim_tpu/reconfig). Each cadence follows the
    client_interval pattern: `*_i` is a Python int on the scalar path
    (statically gated so disabled planes draw nothing) and traced genome data
    on the scenario path (`traced=True`: every command stream is computed
    unconditionally from the SAME dedicated key stream, so a homogeneous
    genome reproduces the scalar path bit-for-bit). Targets rotate randomly
    over nodes -- add/remove-under-fire and transfer-under-fire programs are
    target-diverse by default."""
    n = cfg.n_nodes
    k_rcfg, k_xfer = jax.random.split(jax.random.fold_in(tkey, 5))
    # Disabled planes return a TRACED NIL scalar, not the Python-int NIL the
    # StepInputs defaults use: these leaves flow through vmap (which would
    # broadcast a Python int into a real [B] array anyway -- no saving) and
    # the analyzer's eval_shape pricing (which needs shaped leaves). Cost:
    # the Pass C input-accounting prices 3x int32 = 12 B/cluster-tick on
    # every tier; the VALUES are loop constants XLA folds, and the kernel
    # never reads them when the gate is off (the carry stays untouched).
    nil = jnp.int32(NIL)
    if traced or cfg.reconfig:
        on = (rcfg_i > 0) & (now % jnp.maximum(rcfg_i, 1) == 0) & (now > 0)
        tgt = jax.random.randint(k_rcfg, (), 0, n)
        reconfig_cmd = jnp.asarray(jnp.where(on, tgt, NIL), jnp.int32)
    else:
        reconfig_cmd = nil
    if traced or cfg.leader_transfer:
        on = (xfer_i > 0) & (now % jnp.maximum(xfer_i, 1) == 0) & (now > 0)
        tgt = jax.random.randint(k_xfer, (), 0, n)
        transfer_cmd = jnp.asarray(jnp.where(on, tgt, NIL), jnp.int32)
    else:
        transfer_cmd = nil
    if traced or cfg.read_index:
        on = (read_i > 0) & (now % jnp.maximum(read_i, 1) == 0)
        read_cmd = jnp.asarray(jnp.where(on, 1, NIL), jnp.int32)
    else:
        read_cmd = nil
    return reconfig_cmd, transfer_cmd, read_cmd


def _storage_draws(cfg: RaftConfig, tkey: jax.Array, now: jax.Array,
                   fs_i, jit_t, torn_t, span, traced: bool):
    """(fsync_fire, fsync_stall, torn_drop) draws -- the durable storage
    plane's disk-fault lattice (raft_sim_tpu/storage). A node's flush
    completes on the fsync cadence tick unless its per-node latency-jitter
    Bernoulli stalls it (the slow-disk model: the due flush waits for the
    NEXT cadence tick, so the durable watermark falls a full interval
    behind); fsync_stall marks those stalled due flushes. torn_drop is the
    torn-tail write: the extra entries (1..span, uniform) a restart's tail
    checksum rejects beyond the un-fsynced suffix -- drawn every tick from
    the dedicated stream so the draw sequence is schedule-independent, and
    consumed by the kernel only on restart ticks. `fs_i`/`span` are Python
    ints on the scalar path (statically gated) and traced genome data on the
    scenario path; `jit_t`/`torn_t` are uint32 thresholds either way. The
    fold_in(tkey, 7) stream is disjoint from the client-routing (3) and
    admin-command (5) streams sharing tkey."""
    n = cfg.n_nodes
    if traced or cfg.durable_storage:
        k_jit, k_torn, k_span = jax.random.split(jax.random.fold_in(tkey, 7), 3)
        stall = bern_u32(k_jit, jit_t, (n,))
        due = (fs_i > 0) & (now % jnp.maximum(fs_i, 1) == 0)
        fire = due & ~stall
        torn = bern_u32(k_torn, torn_t, (n,))
        # Traced span bound is fine (precedent: crash_down in _alive_at_t).
        extra = jax.random.randint(k_span, (n,), 1, span + 1)
        torn_drop = jnp.where(torn, extra, 0).astype(jnp.int32)
        return fire, due & stall, torn_drop
    # Gate off: real (dead) [N] arrays, not the StepInputs Python-int
    # defaults -- the dtype-comment contract fixes the rank per field, and
    # these leaves flow through vmap/eval_shape like the admin commands
    # above (Pass C prices ~6N B/cluster-tick of dead input on every tier;
    # the kernel never reads them when the gate is off).
    return jnp.zeros((n,), bool), jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32)


def _client_routing(cfg: RaftConfig, tkey: jax.Array):
    """(client_target, client_bounce) draws -- the redirect-model routing
    randomness (core.clj:154); zeros when the omniscient direct client is
    active. Identical on the scalar and genome paths (routing model is a
    STRUCTURAL config gate; genomes tune only the cadence)."""
    n = cfg.n_nodes
    if cfg.client_redirect:
        k_tgt, k_bnc = jax.random.split(jax.random.fold_in(tkey, 3))
        client_target = jax.random.randint(k_tgt, (), 0, n)
        client_bounce = jax.random.randint(k_bnc, (cfg.client_pipeline,), 0, n)
    else:
        client_target = jnp.int32(0)
        client_bounce = jnp.zeros((cfg.client_pipeline,), jnp.int32)
    return client_target, client_bounce


def genome_at(genome, now: jax.Array, seg_len: int):
    """Resolve a `[S]`-segment genome to the segment active at tick `now`:
    dense-table read `leaves[clip(now // seg_len, 0, S - 1)]` on device (the
    phased-nemesis timeline of scenario/program.py). The final segment holds
    past the program's end; S = 1 short-circuits to a static index so plain
    (unphased) genomes pay no gather."""
    s_count = genome.drop.shape[0]
    if s_count == 1:
        return jax.tree.map(lambda t: t[0], genome)
    # clip, not minimum: `now` is -1 during the phantom pre-window (see
    # _cut_count), and a negative index would silently read the FINAL
    # segment instead of the first one (Pass E range-index-oob).
    seg = jnp.clip(now // seg_len, 0, s_count - 1)
    return jax.tree.map(lambda t: t[seg], genome)


def _cut_count(n: int, k_part: jax.Array, now: jax.Array, period, part_t) -> jax.Array:
    """Scalar int32: edges cut by the rolling partition at tick `now` (0 when
    inactive or before tick 0 -- a phantom 'window -1' layout must not read
    as a partition onset at tick 0)."""
    cut = jnp.sum(_partition_cut(n, k_part, now, period, part_t)).astype(jnp.int32)
    return jnp.where(now >= 0, cut, 0)


def trace_fault_inputs(cfg: RaftConfig, key: jax.Array, now: jax.Array,
                       genome=None, seg_len: int = 1):
    """(crashed [N] bool, cut_now scalar int32, cut_prev scalar int32) -- the
    fault-lattice facts event extraction (trace/events.py) needs that
    StepInputs does not carry: the crash EDGE (down now, up last tick; the
    mirror of `restarted`) and the partition cut-edge counts at `now` and
    `now - 1` (their inequality is the partition-change event). Recomputed
    from the SAME key streams and helpers as make_inputs, so the draws are
    identical (XLA CSEs the shared subexpressions) and the facts can never
    disagree with the inputs the kernel consumed. Genome path mirrors
    make_inputs' segment convention: both liveness reads use the segment
    active at `now` (docs/SCENARIOS.md)."""
    n = cfg.n_nodes
    _, _, k_part = jax.random.split(key, 3)
    if genome is not None:
        g = genome_at(genome, now, seg_len)
        ckey = crash_key(key)
        crashed = _alive_at_t(cfg, ckey, now - 1, g.crash, g.crash_down) & ~_alive_at_t(
            cfg, ckey, now, g.crash, g.crash_down
        )
        cut_now = _cut_count(n, k_part, now, g.part_period, g.part)
        cut_prev = _cut_count(n, k_part, now - 1, g.part_period, g.part)
        return crashed, cut_now, cut_prev
    if cfg.crash_prob > 0:
        ckey = crash_key(key)
        crashed = alive_at(cfg, ckey, now - 1) & ~alive_at(cfg, ckey, now)
    else:
        crashed = jnp.zeros((n,), bool)
    if cfg.partition_period > 0:
        part_t = jnp.uint32(p_to_u32(cfg.partition_prob))
        cut_now = _cut_count(n, k_part, now, cfg.partition_period, part_t)
        cut_prev = _cut_count(n, k_part, now - 1, cfg.partition_period, part_t)
    else:
        cut_now = jnp.int32(0)
        cut_prev = jnp.int32(0)
    return crashed, cut_now, cut_prev


def make_inputs(
    cfg: RaftConfig,
    key: jax.Array,
    now: jax.Array,
    genome=None,
    seg_len: int = 1,
) -> StepInputs:
    """Inputs for one cluster at tick `now`. `key` is the per-cluster base key.

    `genome=None` (the default) is the scalar-config path: fault parameters
    come from cfg, statically gated, exactly one mechanism set per compiled
    program. A `genome` (duck-typed ScenarioGenome, `[S]` per-segment leaves;
    `seg_len` static) switches to the scenario path: every mechanism is traced
    unconditionally from the genome's threshold-encoded parameters, so ONE
    compiled program evaluates a heterogeneous fleet -- per-cluster fault
    settings are data, not compile points. Both paths share the same draw
    helpers and key streams: a homogeneous genome built from cfg's scalars
    (scenario.genome.from_config) reproduces this function's scalar-path
    output bit-for-bit.
    """
    n = cfg.n_nodes
    k_ticks, k_rate, k_part = jax.random.split(key, 3)
    tkey = jax.random.fold_in(k_ticks, now)
    k_drop, k_timeout, k_skew = jax.random.split(tkey, 3)

    # Election-timeout draws (one per node per tick, used on any timer reset).
    timeout_draw = draw_timeouts(cfg, k_timeout, n)
    client_target, client_bounce = _client_routing(cfg, tkey)

    if genome is not None:
        g = genome_at(genome, now, seg_len)
        deliver = ~bern_u32(k_drop, g.drop, (n, n))
        deliver = deliver & ~_partition_cut(n, k_part, now, g.part_period, g.part)
        skew = _skew_draw(n, k_skew, g.skew)
        # Traced cadence: the `maximum` only guards the modulo; interval 0
        # disables via the `> 0` gate (same values as the scalar branch).
        ci = g.client_interval
        client_cmd = jnp.asarray(
            jnp.where((ci > 0) & (now % jnp.maximum(ci, 1) == 0), now + 1, NIL),
            jnp.int32,
        )
        ckey = crash_key(key)
        alive = _alive_at_t(cfg, ckey, now, g.crash, g.crash_down)
        # Restart edge = alive now, down last tick. Both liveness reads use
        # the segment active at `now`: across a segment boundary the edge is
        # evaluated under the NEW segment's crash parameters (deterministic
        # and replayable; documented in docs/SCENARIOS.md).
        restarted = alive & ~_alive_at_t(cfg, ckey, now - 1, g.crash, g.crash_down)
        reconfig_cmd, transfer_cmd, read_cmd = _admin_cmds(
            cfg, tkey, now, g.reconfig_interval, g.transfer_interval,
            g.read_interval, traced=True,
        )
        fsync_fire, fsync_stall, torn_drop = _storage_draws(
            cfg, tkey, now, g.fsync_interval, g.fsync_jitter, g.torn,
            g.torn_span, traced=True,
        )
    else:
        # Message drop (the reference's silently-dropped RPC, client.clj:38-40).
        if cfg.drop_prob > 0:
            if cfg.drop_prob_uniform:
                # Per-cluster rate uniform over [0, drop_prob] (BASELINE
                # config 4), drawn directly in threshold space: a uint32
                # threshold uniform over [0, p_to_u32(drop_prob)]. The +1
                # modulus is clamped below 2^32 so p = 1.0 cannot wrap to a
                # zero modulus; the modulo bias is < 2^-31 relative.
                base = min(p_to_u32(cfg.drop_prob), (1 << 32) - 2)
                p_t = jax.random.bits(k_rate, (), jnp.uint32) % jnp.uint32(base + 1)
            else:
                p_t = jnp.uint32(p_to_u32(cfg.drop_prob))
            deliver = ~bern_u32(k_drop, p_t, (n, n))
        else:
            deliver = jnp.ones((n, n), bool)

        # Rolling partitions (window-stable assignment, see _partition_cut).
        if cfg.partition_period > 0:
            deliver = deliver & ~_partition_cut(
                n,
                k_part,
                now,
                cfg.partition_period,
                jnp.uint32(p_to_u32(cfg.partition_prob)),
            )

        # Clock skew.
        if cfg.clock_skew_prob > 0:
            skew = _skew_draw(n, k_skew, jnp.uint32(p_to_u32(cfg.clock_skew_prob)))
        else:
            skew = jnp.ones((n,), jnp.int32)

        # Client commands: value = tick at injection + 1 -- a deterministic,
        # human-readable payload choice, nothing more. Since the v21 decoupling
        # the commit-latency metric reads the offer-tick PLANE the kernel
        # stamps at injection (ClusterState.log_tick), never the value: any
        # int32 payload is legal (serve/ingest.py check_value), and a served
        # offer plane replaying this cadence is bit-exact with it
        # (tests/test_serve.py). Payload bytes carry no protocol meaning in
        # the reference either (log.clj:66-67).
        if cfg.client_interval > 0:
            client_cmd = jnp.where(now % cfg.client_interval == 0, now + 1, NIL)
        else:
            client_cmd = jnp.int32(NIL)
        client_cmd = jnp.asarray(client_cmd, jnp.int32)

        # Crash/restart schedule (restart edge = alive now, down last tick).
        if cfg.crash_prob > 0:
            ckey = crash_key(key)
            alive = alive_at(cfg, ckey, now)
            restarted = alive & ~alive_at(cfg, ckey, now - 1)
        else:
            alive = jnp.ones((n,), bool)
            restarted = jnp.zeros((n,), bool)

        reconfig_cmd, transfer_cmd, read_cmd = _admin_cmds(
            cfg, tkey, now, cfg.reconfig_interval, cfg.transfer_interval,
            cfg.read_interval, traced=False,
        )
        fsync_fire, fsync_stall, torn_drop = _storage_draws(
            cfg, tkey, now, cfg.fsync_interval,
            jnp.uint32(p_to_u32(cfg.fsync_jitter_prob)),
            jnp.uint32(p_to_u32(cfg.torn_tail_prob)),
            cfg.lost_suffix_span, traced=False,
        )

    deliver_mask = bitplane.pack(deliver, axis=1)
    if cfg.compact_planes:
        # Compacted layout (ops/tile.py): the word plane ships FLAT so the
        # sublane tile stops padding its tiny word dim ([N, W] -> [N*W]; the
        # kernels reshape back at tick entry). Same words, same bits.
        deliver_mask = deliver_mask.reshape((-1,))
    return StepInputs(
        # Shipped bit-packed over the source axis (StepInputs docstring): the
        # same Bernoulli/partition draws, 32 edges per uint32 word -- the [N, N]
        # bool plane never leaves this function.
        deliver_mask=deliver_mask,
        skew=skew,
        timeout_draw=timeout_draw,
        client_cmd=client_cmd,
        client_target=client_target,
        client_bounce=client_bounce,
        alive=alive,
        restarted=restarted,
        reconfig_cmd=reconfig_cmd,
        transfer_cmd=transfer_cmd,
        read_cmd=read_cmd,
        fsync_fire=fsync_fire,
        torn_drop=torn_drop,
        fsync_stall=fsync_stall,
    )
