"""Driver tier: Session verbs, chunked-scan parity, checkpoint/resume, trace decoding,
CLI entry (the dev/user.clj + -main analogues, SURVEY.md sections 3.1/3.6)."""

import json

import jax
import numpy as np
import pytest

from raft_sim_tpu import RaftConfig, init_batch
from raft_sim_tpu.driver import Session, build_config, main
from raft_sim_tpu.sim import chunked, scan, trace
from raft_sim_tpu.utils import checkpoint

CFG = RaftConfig(n_nodes=5, client_interval=8)


def test_chunked_matches_monolithic():
    """Chunk boundaries must not perturb trajectories: inputs are pure functions of
    (key, state.now), so 3x100 ticks == 300 ticks."""
    key = jax.random.key(0)
    k_init, k_run = jax.random.split(key)
    state = init_batch(CFG, k_init, 8)
    keys = jax.random.split(k_run, 8)

    f_mono, m_mono = scan.run_batch_minor(CFG, state, keys, 300)
    f_chunk, m_chunk = chunked.run_chunked(CFG, state, keys, 300, chunk=100)

    for a, b in zip(jax.tree.leaves(jax.device_get(f_mono)), jax.tree.leaves(jax.device_get(f_chunk))):
        np.testing.assert_array_equal(a, b)
    for f, a, b in zip(m_mono._fields, jax.device_get(m_mono), jax.device_get(m_chunk)):
        np.testing.assert_array_equal(a, b, err_msg=f)


def test_chunked_callback_early_stop():
    key = jax.random.key(0)
    k_init, k_run = jax.random.split(key)
    state = init_batch(CFG, k_init, 4)
    keys = jax.random.split(k_run, 4)
    seen = []

    def cb(done, _s, _m):
        seen.append(done)
        return done >= 100

    _, m = chunked.run_chunked(CFG, state, keys, 1000, chunk=50, callback=cb)
    assert seen == [50, 100]
    assert int(np.asarray(m.ticks)[0]) == 100


def test_session_run_reset_deterministic():
    s = Session(CFG, batch=4, seed=3)
    s.run(150, chunk=64)
    first = jax.device_get(s.state)
    summary1 = s.summary()
    s.reset()
    s.run(150, chunk=64)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(jax.device_get(s.state))):
        np.testing.assert_array_equal(a, b)
    assert s.summary() == summary1
    assert summary1["total_violations"] == 0
    assert summary1["n_stable"] == 4


def test_checkpoint_roundtrip(tmp_path):
    """Resume from a checkpoint must continue the exact trajectory: run 100+100 with a
    save/load at the boundary == run 200 straight."""
    s = Session(CFG, batch=4, seed=5)
    s.run(100, chunk=50)
    # Bare path (no .npz): save normalizes and returns the real path; load accepts both.
    p = s.save(str(tmp_path / "ckpt"))
    assert p.endswith(".npz")

    s2 = Session.restore(str(tmp_path / "ckpt"))
    assert s2.cfg == CFG
    assert s2.seed == 5  # seed travels with the checkpoint
    s2.run(100, chunk=50)

    ref = Session(CFG, batch=4, seed=5)
    ref.run(200, chunk=50)
    for a, b in zip(jax.tree.leaves(jax.device_get(ref.state)), jax.tree.leaves(jax.device_get(s2.state))):
        np.testing.assert_array_equal(a, b)
    # Metrics resume too: the interrupted session's summary matches the straight run.
    assert s2.summary() == ref.summary()


def test_checkpoint_rejects_bad_version(tmp_path):
    import numpy as np_

    p = str(tmp_path / "bad.npz")
    np_.savez(p, __version__=np_.int32(1), config_json=np_.bytes_(b"{}"))
    # The mismatch error names both versions and points at the migration path
    # (the checkpoint.py version log).
    with pytest.raises(ValueError, match=r"format v1.*reads v\d+.*version log"):
        checkpoint.load(p)


def test_trace_events_readable():
    s = Session(CFG, batch=2, seed=0)
    infos, states = s.trace(120, cluster=0)
    evs = list(trace.events(states))
    kinds = " ".join(e for _, e in evs)
    assert "starts election" in kinds
    assert "becomes leader" in kinds
    assert "commits through" in kinds
    lines = list(trace.info_lines(infos, every=10))
    assert len(lines) == 12
    assert "VIOLATION" not in "".join(lines)
    # node_line renders every node at the final tick
    for i in range(CFG.n_nodes):
        assert f"node {i}:" in trace.node_line(states, 119, i)


def test_build_config_preset_with_overrides():
    class A:
        preset = "config4"
        batch = None

    a = A()
    import dataclasses as dc

    for f in dc.fields(RaftConfig):
        if not hasattr(a, f.name):
            setattr(a, f.name, None)
    a.n_nodes = 9
    cfg, batch = build_config(a)
    assert cfg.n_nodes == 9  # override applied
    assert cfg.drop_prob == 0.3  # preset preserved
    assert batch == 100_000  # preset batch filled in


def test_cli_run_and_presets(capsys):
    assert main(["presets"]) == 0
    out = capsys.readouterr().out
    assert "config1" in out and "config5" in out

    rc = main(["run", "--batch", "2", "--ticks", "60", "--client-interval", "8"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["n_clusters"] == 2
    assert payload["total_violations"] == 0
    assert payload["cluster_ticks_per_s"] > 0


def test_cli_trace_events(capsys):
    rc = main(["run", "--batch", "1", "--trace-events", "--ticks", "80"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "becomes leader" in out


def test_session_offer_interactive_client_write():
    """Session.offer = the reference's ad-hoc client-set POST: a command offered
    while leaders exist is accepted, appended with the offered value, and later
    committed; the offered tick participates in metric accounting like run()."""
    from raft_sim_tpu import RaftConfig
    from raft_sim_tpu.driver import Session
    import numpy as np

    s = Session(RaftConfig(n_nodes=5), batch=8, seed=0)
    s.run(60)  # elect leaders everywhere (reliable net)
    r = s.offer(424242)
    assert r["accepted"] == 8
    s.run(40)  # let it replicate + commit
    st = s.state
    logs = np.asarray(st.log_val)
    commits = np.asarray(st.commit_index)
    for c in range(8):
        lead = int(np.argmax(np.asarray(st.log_len[c])))
        vals = logs[c, lead, : int(commits[c, lead])]
        assert 424242 in vals, f"cluster {c}: offered value not committed"
    assert int(np.asarray(s.metrics.ticks).max()) == 101  # offer tick counted
    # No leader -> honestly rejected (unlike reference bug 2.3.9's silent hang).
    s2 = Session(RaftConfig(n_nodes=5), batch=4, seed=1)
    assert s2.offer(7)["accepted"] == 0  # tick 0: nobody is leader yet
