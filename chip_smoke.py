"""Chip smoke run: the fleet simulator's main path on a TPU, through its own
entry points, at production state sizes.

    python chip_smoke.py                # one chip: fleet, serve and parity phases
    python chip_smoke.py --four-chip    # four chips: the two multi-chip paths only

Phases (default run):
  fleet   config4c at B=100,000 through `driver.Session.run`, the donating
          `run_chunked` loop `python -m raft_sim_tpu run` drives: 3 chunks of
          256 ticks. 0 violations, >= 99% of clusters elected a leader,
          committed commands > 0.
  cache   the fleet chunk program compiled again after `jax.clear_caches()`:
          reports whether the persistent compilation cache served it.
  serve   config9 at its production B=1,000 through `Session`: 16 client
          commands with values drawn from --seed, each acked through the
          device-side commit-delta stream in >= 99% of clusters, then one
          ReadIndex read that must be served.
  parity  the seeded configurations below on the chip and on the CPU backend
          in this one process: every non-mailbox state leaf and every metric
          bit-equal -- the check of chip numerics (int8/int16 planes, uint32
          checksum wraparound, reduction order).

--four-chip runs only the multi-chip comparisons, each bit-exact against one
chip: config7 (N=101, B=1,000) node-sharded on a 1x4 ("clusters","nodes") mesh,
and config3 at B=100,000 data-parallel over a 4-device cluster mesh.

Any failed check exits non-zero. Without a TPU the script exits non-zero before
running anything. The last stdout line is the JSON verdict:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# name -> (RaftConfig kwargs, seed, batch, ticks): seeded fleets whose chip and
# CPU trajectories must agree bit for bit.
PARITY_CONFIGS = {
    "reliable+client": (dict(n_nodes=5, client_interval=8), 42, 64, 300),
    "kitchen-sink": (
        dict(
            n_nodes=9,
            log_capacity=16,
            client_interval=4,
            drop_prob=0.3,
            partition_period=32,
            partition_prob=0.5,
            crash_prob=0.3,
            crash_period=40,
            crash_down_ticks=15,
            clock_skew_prob=0.1,
            check_log_matching=True,
        ),
        77,
        32,
        400,
    ),
    "wide-n51": (
        dict(n_nodes=51, log_capacity=16, partition_period=32, partition_prob=0.5),
        7,
        8,
        200,
    ),
    # Ring compaction + snapshot catch-up + the 302-redirect client path with a
    # K-deep in-flight pipeline: wide (int32) index planes, absolute-index
    # checksums, [K] routing state.
    "compaction+redirect": (
        dict(
            n_nodes=5,
            log_capacity=16,
            compact_margin=8,
            max_entries_per_rpc=4,
            client_interval=2,
            client_redirect=True,
            client_pipeline=3,
            drop_prob=0.15,
            crash_prob=0.3,
            crash_period=32,
            crash_down_ticks=10,
        ),
        11,
        32,
        500,
    ),
    # PreVote probe rounds under churn: prospective-term wire fields, packed
    # per-edge grant bits (Mailbox.pv_grant), heard_clock arithmetic.
    "prevote-churn": (
        dict(
            n_nodes=5,
            log_capacity=8,
            client_interval=3,
            pre_vote=True,
            drop_prob=0.25,
            crash_prob=0.4,
            crash_period=16,
            crash_down_ticks=8,
        ),
        13,
        32,
        400,
    ),
}

FLEET_CHUNK = 256
SERVE_OFFERS = 16
SERVE_WAIT = 64
MIN_SHARE = 0.99


class CompileCounters:
    """Backend compile seconds and persistent-cache hits/writes, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def info(line: str) -> None:
    print(line, flush=True)


def trees_equal(a, b, skip=()) -> list[str]:
    """Names of the leaves of NamedTuples `a` and `b` that differ (fields in
    `skip` excepted)."""
    import jax

    bad = []
    for f in a._fields:
        if f in skip:
            continue
        for i, (x, y) in enumerate(
            zip(jax.tree.leaves(getattr(a, f)), jax.tree.leaves(getattr(b, f)))
        ):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                bad.append(f if i == 0 else f"{f}[{i}]")
    return bad


def fleet_phase(seed: int, batch: int, counters: CompileCounters):
    """config4c through Session.run: one warm-up chunk, then two timed."""
    import jax

    from raft_sim_tpu import PRESETS
    from raft_sim_tpu.driver import Session
    from raft_sim_tpu.sim import scan

    cfg = PRESETS["config4c"][0]
    c0 = counters.compile_s
    t0 = time.perf_counter()
    sess = Session(cfg, batch=batch, seed=seed)
    sess.run(FLEET_CHUNK, chunk=FLEET_CHUNK)
    jax.block_until_ready((sess.state, sess.metrics))
    t1 = time.perf_counter()
    n1 = counters.compiles
    sess.run(2 * FLEET_CHUNK, chunk=FLEET_CHUNK)
    jax.block_until_ready((sess.state, sess.metrics))
    t2 = time.perf_counter()
    in_window = counters.compiles - n1

    s = sess.summary()
    # Under 30% drops leadership churns, so a share of clusters is between
    # leaders at any one tick: the check is that each cluster elected one.
    m = jax.device_get(sess.metrics)
    elected = int(np.sum(m.first_leader_tick < scan.NEVER))
    # Committed client entries: those with a latency sample plus those whose
    # commit fell in a leaderless window (RunMetrics.lat_excluded).
    committed = int(
        np.sum(m.lat_cnt, dtype=np.int64) + np.sum(m.lat_excluded, dtype=np.int64)
    )
    info(
        f"fleet config4c B={batch}: compile {counters.compile_s - c0:.2f}s, "
        f"first chunk incl. compile {t1 - t0:.2f}s, steady "
        f"{batch * 2 * FLEET_CHUNK / (t2 - t1):.1f} cluster-ticks/s over "
        f"{2 * FLEET_CHUNK} ticks with {in_window} compiles inside that "
        "window (smoke reading, not a benchmark)"
    )
    info(
        f"fleet config4c: violations {s['total_violations']}, elected a leader "
        f"{elected}/{batch}, holding one at the end {s['n_stable']}/{batch}, "
        f"commands accepted {s['total_cmds']}, committed {committed}, "
        f"max term {s['max_term']}"
    )
    check(s["total_violations"] == 0, f"{s['total_violations']} safety violations")
    check(
        elected >= MIN_SHARE * batch,
        f"only {elected}/{batch} clusters elected a leader",
    )
    check(committed > 0, "no client command committed")
    return sess, cfg


def cache_phase(sess, cfg, counters: CompileCounters) -> None:
    """Compile the fleet chunk program a second time, from a cleared
    in-memory cache: the persistent cache should serve it."""
    import jax

    from raft_sim_tpu.sim import chunked

    hits = counters.hits
    jax.clear_caches()
    t0 = time.perf_counter()
    chunked._chunk_donate.lower(
        cfg, sess.state, sess.keys, FLEET_CHUNK, None, 1
    ).compile()
    hit = counters.hits > hits
    info(
        f"cache: second compile of the fleet chunk program "
        f"{'was a cache hit' if hit else 'was NOT a cache hit'} "
        f"({time.perf_counter() - t0:.2f}s); this run so far: "
        f"{counters.hits} hits, {counters.writes} entries written"
    )


def serve_phase(seed: int, batch: int, counters: CompileCounters) -> None:
    """config9 through Session: warm-up run, client commands acked through
    the commit-delta stream, one ReadIndex read."""
    from raft_sim_tpu import PRESETS
    from raft_sim_tpu.driver import Session

    cfg = PRESETS["config9"][0]
    c0 = counters.compile_s
    sess = Session(cfg, batch=batch, seed=seed)
    sess.run(FLEET_CHUNK, chunk=FLEET_CHUNK)
    rng = np.random.default_rng(seed)
    values = rng.integers(1, np.iinfo(np.int32).max, size=SERVE_OFFERS)
    t0 = time.perf_counter()
    worst = batch
    for v in values:
        r = sess.offer(int(v), wait=SERVE_WAIT)
        check(
            r["committed"] >= MIN_SHARE * batch,
            f"command {int(v)}: acked in {r['committed']}/{batch} clusters "
            f"(accepted {r['accepted']}, waited {r['waited']} ticks)",
        )
        worst = min(worst, r["committed"])
    read = sess.offer_read(wait=SERVE_WAIT)
    info(
        f"serve config9 B={batch}: {SERVE_OFFERS} commands, each acked through "
        f"the delta stream in >= {worst}/{batch} clusters; read captured "
        f"{read['captured']}, served {read['served']} (waited "
        f"{read['waited']} ticks); {time.perf_counter() - t0:.2f}s, compile "
        f"{counters.compile_s - c0:.2f}s"
    )
    check(read["served"] > 0, "the ReadIndex read was not served")


def parity_phase(chip, cpu) -> None:
    """Each PARITY_CONFIGS fleet on `chip` and on `cpu`, in this process."""
    import jax

    from raft_sim_tpu import RaftConfig
    from raft_sim_tpu.sim import scan

    def run_on(dev, cfg, seed, batch, ticks):
        with jax.default_device(dev):
            out = scan.simulate(cfg, seed, batch, ticks)
        check(
            out[1].ticks.devices() == {dev},
            f"run meant for {dev} landed on {out[1].ticks.devices()}",
        )
        return jax.device_get(out)

    for name, (kwargs, seed, batch, ticks) in PARITY_CONFIGS.items():
        cfg = RaftConfig(**kwargs)
        f_chip, m_chip = run_on(chip, cfg, seed, batch, ticks)
        f_cpu, m_cpu = run_on(cpu, cfg, seed, batch, ticks)
        bad = trees_equal(f_chip, f_cpu, skip=("mailbox",))
        bad += trees_equal(m_chip, m_cpu)
        info(
            f"parity {name} ({chip.platform} vs {cpu.platform}, B={batch}, "
            f"{ticks} ticks): {'bit-exact' if not bad else f'MISMATCH in {bad}'}"
        )
        check(not bad, f"parity {name}: {bad}")


def placement(x) -> str:
    return ", ".join(
        f"dev{s.device.id}:{tuple(s.data.shape)}" for s in x.addressable_shards
    )


def four_chip_phase(seed: int, batch3: int) -> None:
    """The two multi-chip paths, each against the one-chip program."""
    import jax

    from raft_sim_tpu import PRESETS
    from raft_sim_tpu.parallel import make_mesh, nodeshard, simulate_sharded
    from raft_sim_tpu.sim import scan

    devices = jax.devices()
    n = len(devices)

    cfg7, b7 = PRESETS["config7"]
    ticks7 = 256
    mesh = nodeshard.make_node_mesh(n)
    t0 = time.perf_counter()
    fs, ms = nodeshard.simulate_node_sharded(cfg7, seed, b7, ticks7, mesh)
    jax.block_until_ready((fs, ms))
    t1 = time.perf_counter()
    fd, md = scan.simulate(cfg7, seed, b7, ticks7)
    jax.block_until_ready((fd, md))
    info(
        f"four-chip node-sharded config7 B={b7} N={cfg7.n_nodes} {ticks7} "
        f"ticks on a 1x{n} mesh ({t1 - t0:.2f}s incl. compile): state.role "
        f"placed {placement(fs.role)}; metrics on "
        f"{len(ms.ticks.sharding.device_set)} devices"
    )
    check(
        len(fs.role.sharding.device_set) == n,
        f"node-sharded state sits on {len(fs.role.sharding.device_set)} devices",
    )
    bad = trees_equal(jax.device_get(ms), jax.device_get(md))
    bad += trees_equal(
        jax.device_get(nodeshard.unshard_state(cfg7, fs)), jax.device_get(fd)
    )
    info(f"four-chip node-sharded vs one chip: "
         f"{'bit-exact' if not bad else f'MISMATCH in {bad}'}")
    check(not bad, f"node-sharded config7: {bad}")

    cfg3 = PRESETS["config3"][0]
    ticks3 = 512
    t0 = time.perf_counter()
    f4, m4 = simulate_sharded(cfg3, seed, batch3, ticks3, make_mesh(n))
    jax.block_until_ready((f4, m4))
    t1 = time.perf_counter()
    f1, m1 = scan.simulate(cfg3, seed, batch3, ticks3)
    jax.block_until_ready((f1, m1))
    info(
        f"four-chip data-parallel config3 B={batch3} {ticks3} ticks over "
        f"{n} devices ({t1 - t0:.2f}s incl. compile): state.role placed "
        f"{placement(f4.role)}; metrics placed {placement(m4.ticks)}"
    )
    check(
        len(f4.role.sharding.device_set) == n
        and len(m4.ticks.sharding.device_set) == n,
        "data-parallel outputs are not spread over every device",
    )
    bad = trees_equal(jax.device_get(m4), jax.device_get(m1))
    bad += trees_equal(jax.device_get(f4), jax.device_get(f1))
    info(f"four-chip data-parallel vs one chip: "
         f"{'bit-exact' if not bad else f'MISMATCH in {bad}'}")
    check(not bad, f"data-parallel config3: {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated fleet and offered value")
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the multi-chip comparisons (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform}); this "
              "script runs only on the chip", file=sys.stderr)
        return 1
    want = 4 if args.four_chip else 1
    if len(devices) < want:
        print(f"chip_smoke: --four-chip needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from importlib import metadata

    from raft_sim_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    counters = CompileCounters()
    info(
        f"device: {dev.device_kind} x{len(devices)}; jax {jax.__version__}, "
        f"jaxlib {metadata.version('jaxlib')}, libtpu {metadata.version('libtpu')}"
    )
    info(f"compile cache: {cache_dir}")

    if args.four_chip:
        four_chip_phase(args.seed, 100_000)
    else:
        sess, cfg = fleet_phase(args.seed, 100_000, counters)
        cache_phase(sess, cfg, counters)
        del sess
        peak = dev.memory_stats().get("peak_bytes_in_use")
        info(f"device memory: peak_bytes_in_use {peak}")
        serve_phase(args.seed, 1_000, counters)
        parity_phase(dev, jax.devices("cpu")[0])
    info(
        f"compile: {counters.compile_s:.2f}s in backend compiles; persistent "
        f"cache {counters.hits} hits, {counters.writes} entries written"
    )
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
