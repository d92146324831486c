"""The serve subsystem: streaming ingest, arbitrary payloads, commit-delta export.

ISSUE 6's acceptance surface, pinned:
  - Arbitrary-payload parity: values chosen to COLLIDE with the old tick
    encoding produce bit-exact state (minus the value planes themselves),
    latency histograms, and telemetry windows vs non-colliding values --
    payload/latency decoupling (checkpoint v21) means the metric
    reads the offer-tick plane, never the payload.
  - The device-side commit-delta stream exactly equals the host snapshot-diff
    reconstruction on a fuzzed run, and ApplyLogWriter's per-node export.
  - A multi-chunk ServeSession compiles NOTHING after its first chunk
    (command values are traced data).
  - Session.offer acks via the delta stream (VERDICT missing #2), with the
    superseded snapshot-diff poll kept as a cross-check.

Compile budget: one served scan (`simulate_serve`, shared by the parity and
export tests), one scheduled scan (the cadence-equivalence anchor), one serve
chunk program (`_serve_chunk`, shared by every ServeSession test via the
module fixture) -- everything else is host-side or reuses programs other
test modules compile.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_sim_tpu import NIL, RaftConfig
from raft_sim_tpu.types import NOOP
from raft_sim_tpu.serve import (
    CommandSource,
    DeltaStream,
    ServeSession,
    jsonl_commands,
    pack_chunk,
    serve_config,
    simulate_serve,
)
from raft_sim_tpu.serve import deltas as deltas_mod
from raft_sim_tpu.serve import ingest, loop
from raft_sim_tpu.sim import scan
from raft_sim_tpu.types import init_batch
from raft_sim_tpu.utils import checkpoint

# The scheduled twin (client_interval=1) and its serve-mode variant: ONE served
# scan program covers the parity, export, and cadence-equivalence tests.
BASE = RaftConfig(n_nodes=3, log_capacity=32, client_interval=1)
SCFG = serve_config(BASE)
BATCH, T, WINDOW = 4, 64, 16

# The fuzzed standing-fleet config (module fixture `served`): every fault class
# the serve loop must stream through without losing an exported entry.
FCFG = serve_config(
    RaftConfig(
        n_nodes=3,
        log_capacity=64,
        drop_prob=0.2,
        crash_prob=0.3,
        crash_period=24,
        crash_down_ticks=8,
    )
)
FB, FCHUNK, FW = 4, 32, 16

# Payloads that COLLIDE with the old tick encoding (small positive ints in
# (0, now]) vs arbitrary ones -- same offer ticks, different values only.
COLLIDING = [7, 1, 2, 3, 9, 5]
ARBITRARY = [2**31 - 1, -(2**31), -1000, 10**9, -7, 123456789]
OFFER_AT = 32  # first offer tick: leaders are long elected by then


def _plane(values, start=OFFER_AT, ticks=T, batch=BATCH):
    """[T, B] offer plane with `values` at consecutive ticks from `start`,
    broadcast across the batch (the pre-tenancy one-client-over-the-fleet
    form) -- pack_chunk's contiguous packing, shifted to a post-election
    window."""
    col = np.full((ticks,), NIL, np.int32)
    col[start : start + len(values)] = pack_chunk(values, len(values))
    return jnp.asarray(np.broadcast_to(col[:, None], (ticks, batch)))


def assert_equal_except_values(a, b):
    """Bit-exact on every leaf EXCEPT the payload planes and their checksums
    (log_val, mailbox.ent_val, the value-weighted commit/base checksums, and
    redirect-pipeline payload slots): the decoupling contract -- values
    influence nothing but themselves."""
    skip = {"log_val", "commit_chk", "base_chk", "client_pend"}
    mb_skip = {"ent_val", "req_base_chk"}
    for f in a._fields:
        if f in skip:
            continue
        if f == "mailbox":
            for mf in a.mailbox._fields:
                if mf in mb_skip:
                    continue
                np.testing.assert_array_equal(
                    np.asarray(getattr(a.mailbox, mf)),
                    np.asarray(getattr(b.mailbox, mf)),
                    err_msg=f"mailbox.{mf} diverged under a value-only change",
                )
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)),
                np.asarray(getattr(b, f)),
                err_msg=f"state.{f} diverged under a value-only change",
            )


def assert_trees_equal(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=what)


# --------------------------------------------------------------- ingest units


def test_serve_config_forces_external_ingest():
    assert SCFG.client_interval == 0
    assert SCFG.serve_ingest
    assert SCFG.track_offer_ticks
    assert serve_config(SCFG) is SCFG  # idempotent: already serve-mode
    # The structural gate it exists for: without it, an interval-0 config
    # carries the offer-tick plane as dead weight.
    assert not RaftConfig(n_nodes=3).track_offer_ticks


def test_check_value_and_pack_chunk():
    for v in (0, 7, -3, 2**31 - 1, -(2**31)):
        assert ingest.check_value(v) == v
    for bad in (NIL, NOOP):
        with pytest.raises(ValueError, match="sentinel"):
            ingest.check_value(bad)
    with pytest.raises(ValueError, match="int32"):
        ingest.check_value(2**31)
    plane = pack_chunk([5, -9], 4)
    assert plane.dtype == np.int32
    assert list(plane) == [5, -9, NIL, NIL]
    with pytest.raises(ValueError, match="fit"):
        pack_chunk([1, 2, 3], 2)


def test_jsonl_source_and_parse(tmp_path):
    p = tmp_path / "cmds.jsonl"
    p.write_text('7\n# comment\n\n{"value": -3, "tag": "x"}\n2147483647\n')
    assert list(jsonl_commands(str(p))) == [7, -3, 2**31 - 1]
    with pytest.raises(ValueError, match="value"):
        ingest.parse_line('{"tag": "x"}')
    with pytest.raises(ValueError, match="integer"):
        ingest.parse_line("true")
    src = CommandSource(jsonl_commands(str(p)))
    first = src.next_chunk(2)
    assert list(first) == [7, -3] and not src.exhausted
    rest = src.next_chunk(8)
    assert list(rest) == [2**31 - 1] + [NIL] * 7 and src.exhausted
    assert src.offered == 3


# ----------------------------------------------- arbitrary-payload parity


def test_arbitrary_payload_parity_batched():
    """ISSUE-6 acceptance: colliding vs arbitrary payloads -- bit-exact
    telemetry windows, metrics (latency histogram included), and state minus
    the value planes, through ONE compiled served scan (values are data)."""
    sa, ma, ra = simulate_serve(SCFG, 0, BATCH, _plane(COLLIDING), WINDOW)
    sb, mb_, rb = simulate_serve(SCFG, 0, BATCH, _plane(ARBITRARY), WINDOW)
    assert_trees_equal(ma, mb_, "metrics diverged under a value-only change")
    assert_trees_equal(ra, rb, "windows diverged under a value-only change")
    assert_equal_except_values(sa, sb)
    # The stamps themselves: identical between runs, offer tick + 1 at the
    # slots the offers landed in (node 0's committed prefix).
    np.testing.assert_array_equal(np.asarray(sa.log_tick), np.asarray(sb.log_tick))
    commit0 = int(np.asarray(sa.commit_index)[0, 0])
    assert commit0 == len(COLLIDING)  # reliable net: everything offered commits
    np.testing.assert_array_equal(
        np.asarray(sa.log_tick)[0, 0, :commit0],
        OFFER_AT + 1 + np.arange(len(COLLIDING)),
    )
    np.testing.assert_array_equal(
        np.asarray(sb.log_val)[0, 0, :commit0], ARBITRARY
    )
    # Latency was measured (not silently skipped) and covered every commit.
    assert int(np.asarray(ma.lat_cnt).sum()) >= len(COLLIDING)
    assert int(np.asarray(ma.lat_excluded).sum()) == 0


@pytest.mark.slow
def test_scheduled_cadence_equals_explicit_plane():
    """The scheduled client cadence IS a served offer plane: client_interval=1
    traffic (value = tick+1, faults.make_inputs) replayed through pack_chunk as
    an explicit plane on the serve-mode variant reproduces the scheduled run
    bit-for-bit -- state (values included), metrics, latency. One packing
    helper, one semantics (the scenario-genome cadence pins the same identity
    against the scheduled path in tests/test_scenario.py, closing the
    genome -> scheduled -> served chain)."""
    s_sched, m_sched = scan.simulate(BASE, 0, BATCH, T)
    col = pack_chunk([t + 1 for t in range(T)], T)
    cmds = jnp.asarray(np.broadcast_to(col[:, None], (T, BATCH)))
    s_srv, m_srv, _ = simulate_serve(SCFG, 0, BATCH, cmds, WINDOW)
    assert_trees_equal(s_sched, s_srv, "scheduled vs explicit-plane state")
    assert_trees_equal(m_sched, m_srv, "scheduled vs explicit-plane metrics")


# ------------------------------------------------------- commit-delta export


def test_delta_export_acks_every_offer_bit_exactly():
    """Every offered command's ack arrives through the delta stream with the
    value round-tripped bit-exactly -- including int32 extremes and values that
    used to collide with the tick encoding -- and stamps carry the offer
    ticks. Shares the parity test's compiled program."""
    values = [7, 1, 2**31 - 1, -(2**31), -1000, 9]
    final, _, _ = simulate_serve(SCFG, 0, BATCH, _plane(values), WINDOW)
    stream = DeltaStream(BATCH, depth=2)  # depth < len: forces drain rounds
    rows = stream.drain(final)
    for c in range(BATCH):
        assert deltas_mod.applied_values(rows, c) == values
        ticks = [t for row in rows if row["cluster"] == c for t in row["ticks"]]
        assert ticks == [OFFER_AT + 1 + k for k in range(len(values))]
    assert stream.exported == BATCH * len(values)
    assert stream.gap_entries == 0
    assert stream.drain(final) == []  # watermark caught up: stream is dry


def test_extract_reports_compaction_gap():
    """Entries compacted past node 0's base before export surface as a gap
    count, and the stream resumes at the base (hand-built ring state)."""
    state = init_batch(SCFG, jax.random.key(0), 2)
    lv = state.log_val.at[0, 0, 4:6].set(jnp.asarray([44, 55], jnp.int32))
    lt = state.log_tick.at[0, 0, 4:6].set(jnp.asarray([10, 11], jnp.int32))
    state = state._replace(
        log_val=lv,
        log_tick=lt,
        log_base=state.log_base.at[0, 0].set(4),
        commit_index=state.commit_index.at[0, 0].set(6),
        log_len=state.log_len.at[0, 0].set(6),
    )
    d = deltas_mod.extract(state, jnp.zeros((2,), jnp.int32), 8)
    assert int(d.gap[0]) == 4 and int(d.count[0]) == 2
    assert list(np.asarray(d.values)[0, :2]) == [44, 55]
    assert list(np.asarray(d.ticks)[0, :2]) == [10, 11]
    assert int(d.watermark[0]) == 6
    assert int(d.count[1]) == 0 and int(d.gap[1]) == 0


def test_validate_deltas_catches_stream_holes(tmp_path):
    p = str(tmp_path / "deltas.jsonl")
    rows = [
        {"cluster": 0, "start": 1, "gap": 0, "values": [5, 6], "ticks": [2, 3]},
        {"cluster": 0, "start": 3, "gap": 0, "values": [7], "ticks": [4]},
    ]
    deltas_mod.append_delta_rows(p, rows)
    assert deltas_mod.validate_deltas(p) == []
    deltas_mod.append_delta_rows(
        p, [{"cluster": 0, "start": 9, "gap": 0, "values": [8], "ticks": [9]}]
    )
    errs = deltas_mod.validate_deltas(p)
    assert any("not dense" in e for e in errs)
    deltas_mod.append_delta_rows(
        p, [{"cluster": 1, "start": 1, "gap": 0, "values": [1, 2], "ticks": [3]}]
    )
    assert any("length mismatch" in e for e in deltas_mod.validate_deltas(p))


# ------------------------------------------------- the standing-fleet session


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """ONE fuzzed multi-chunk ServeSession (drop + crash faults, sink attached,
    ApplyLogWriter shadowing cluster 0) shared by the session-level tests --
    one compiled chunk program for the whole module."""
    from raft_sim_tpu.utils.apply_log import ApplyLogWriter
    from raft_sim_tpu.utils.telemetry_sink import TelemetrySink

    sink_dir = str(tmp_path_factory.mktemp("serve_sink"))
    sink = TelemetrySink(
        sink_dir, FCFG, seed=7, batch=FB, window=FW, ring=0, source="serve"
    )
    sess = ServeSession(
        FCFG, batch=FB, seed=7, chunk=FCHUNK, window=FW, delta_depth=4,
        sink=sink, warmup_ticks=FCHUNK,
    )
    writer = ApplyLogWriter(str(tmp_path_factory.mktemp("apply")), FCFG)
    cache_sizes = []

    def progress(_stats):
        cache_sizes.append(loop._serve_chunk._cache_size())
        writer.update(sess.state)

    cmds = [7, 1, 2, 2**31 - 1, -(2**31), -1000, 9, 9] + list(range(100, 120))
    stats = sess.serve(CommandSource(iter(cmds)), drain_chunks=3, progress=progress)
    return {
        "sess": sess, "stats": stats, "writer": writer, "sink_dir": sink_dir,
        "cache_sizes": cache_sizes, "cmds": cmds,
    }


def test_fuzzed_stream_equals_snapshot_diff(served):
    """ISSUE-6 acceptance: on a fuzzed run the streamed deltas exactly equal
    the host snapshot-diff -- node 0's committed prefix (values AND stamps),
    per cluster, reconstructed from the final fleet state."""
    sess = served["sess"]
    st = jax.device_get(sess.state)
    wm = np.asarray(sess.deltas.watermark)
    total = 0
    for c in range(FB):
        # Node 0's commit INDEX is restart-mutable (a crashed node rebuilds it
        # from the leader), but the committed entries themselves never change:
        # the stream must equal the log prefix up to its own watermark -- the
        # highest commit it ever observed -- bit for bit.
        n_exp = int(wm[c])
        assert n_exp >= int(np.asarray(st.commit_index)[c, 0])
        want_vals = list(np.asarray(st.log_val)[c, 0, :n_exp])
        want_ticks = list(np.asarray(st.log_tick)[c, 0, :n_exp])
        got_vals = [v for r in sess.delta_rows if r["cluster"] == c for v in r["values"]]
        got_ticks = [t for r in sess.delta_rows if r["cluster"] == c for t in r["ticks"]]
        assert got_vals == want_vals, f"cluster {c}: delta values != committed log"
        assert got_ticks == want_ticks, f"cluster {c}: delta stamps != log_tick plane"
        total += n_exp
    assert total > 0  # the fault mix let clusters commit
    assert sess.deltas.exported == total
    assert sess.deltas.gap_entries == 0  # no compaction: nothing lost


def test_fuzzed_stream_matches_apply_log_writer(served):
    """The delta stream and the per-chunk ApplyLogWriter shadow agree on
    cluster 0's apply stream (the single-cluster exporter it generalizes)."""
    assert served["writer"].values(0) == served["sess"].acked_values(0)


def test_serve_session_zero_recompiles(served):
    """ISSUE-6 acceptance: after the first chunk the session compiles NOTHING
    -- varying command values, empty drain chunks, and the warmup plane all
    share one chunk executable."""
    sizes = served["cache_sizes"]
    assert len(sizes) >= 4
    assert len(set(sizes)) == 1, f"serve chunk recompiled mid-session: {sizes}"


def test_serve_sink_streams_validate(served):
    from raft_sim_tpu.utils import telemetry_sink

    sink_dir = served["sink_dir"]
    assert deltas_mod.validate_deltas(os.path.join(sink_dir, "deltas.jsonl")) == []
    assert telemetry_sink.validate(sink_dir) == []
    # The streamed file holds exactly the rows the session drained.
    with open(os.path.join(sink_dir, "deltas.jsonl")) as f:
        n_rows = sum(1 for _ in f)
    assert n_rows == len(served["sess"].delta_rows)


def test_serve_state_checkpoints_v21(served, tmp_path):
    """The offer-tick plane rides the v21 checkpoint: a served fleet's state
    (nonzero log_tick, serve_ingest config) round-trips bit-exactly."""
    sess = served["sess"]
    path = checkpoint.save(
        str(tmp_path / "ck"), sess.cfg, sess.state, sess.keys, sess.metrics, seed=7
    )
    cfg2, state2, keys2, metrics2, seed2, scen = checkpoint.load(path)
    assert cfg2 == sess.cfg and cfg2.serve_ingest and seed2 == 7
    assert scen is None
    assert np.asarray(state2.log_tick).any()  # the plane is live and persisted
    assert_trees_equal(state2, sess.state, "checkpoint round trip")
    assert_trees_equal(metrics2, sess.metrics, "metrics round trip")


def test_session_offer_acks_via_delta_stream_with_poll_cross_check():
    """Session.offer's ack = the commit-delta stream (VERDICT missing #2
    closed): a value equal to a long-committed scheduled command still acks
    (the superseded snapshot-diff poll reported 0 forever on this input), and
    the poll -- kept as the cross-check -- agrees with every ack after the
    fact."""
    from raft_sim_tpu.driver import Session

    sess = Session(RaftConfig(n_nodes=5, client_interval=8), batch=8, seed=0)
    sess.run(100)  # scheduled value 65 (offer tick 64, leaders long elected)
    assert sess._committed_mask(65).all()  # the collision is real pre-offer
    res = sess.offer(65, wait=40)
    assert res["accepted"] == 8
    assert res["committed"] == 8  # the delta stream sees the NEW entry
    # Cross-check: the snapshot poll agrees on a fresh (non-colliding) value.
    res2 = sess.offer(-424242, wait=40)
    assert res2["committed"] == 8
    assert sess._committed_mask(-424242).all()
