"""Simulator checkpoint/resume.

The reference's only persistence is an append-only file of committed values that is
never read back (log.clj:16-18, 74-75 -- no real resume, SURVEY.md section 5). Here the
checkpoint is the full simulator state: every ClusterState array plus the per-cluster
PRNG keys and the config, so a long fuzz run resumes bit-exactly (inputs are pure
functions of (key, state.now), faults.py, so no RNG stream state needs saving beyond
the keys themselves).

The accumulated RunMetrics ride along too: `last_leaderless_tick`/`first_leader_tick`
record *absolute* tick numbers (state.now), so metric accumulation only stays coherent
across a resume if the pre-checkpoint metrics are restored with the state.

Format: a single .npz with the config as a JSON string; loads with numpy only.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np

from raft_sim_tpu.sim.scan import RunMetrics
from raft_sim_tpu.types import ClusterState, Mailbox
from raft_sim_tpu.utils.config import RaftConfig

# v2: added the session seed to the archive.
# v3: RunMetrics gained total_cmds.
# v4: Mailbox entry payload became the per-sender shared window (ent_start/term/val).
# v5: req_* fields reoriented [sender, receiver], resp_* [receiver, responder].
# v6: ClusterState gained last_ack (shared-window responsiveness stamps).
# v7: mailbox wire format v7 -- per-sender request headers (req_type/term/commit,
#     RV last_index/last_term, AE window start/prev-term/count) + per-edge window
#     offsets (req_off) and packed response words (resp_word, per-responder term).
# v8: narrow dtypes (next/match int16, req_off int8, resp_word int16) and last_ack
#     replaced by the saturating int16 ack_age.
# v9: ClusterState gained commit_chk (committed-prefix checksum).
# v10: ring-log compaction -- ClusterState gained log_base/base_term/base_chk,
#      Mailbox gained the snapshot header (req_base/req_base_term/req_base_chk);
#      compaction configs widen next/match and resp_word to int32.
# v11: client write path -- ClusterState gained client_pend/client_dst (redirect
#      routing state), RunMetrics gained lat_sum/lat_cnt (commit latency).
# v12: mailbox wire format v9 -- the packed per-edge response word became an int8
#      resp_kind plane + per-responder payloads (v_to/a_ok_to/a_match/a_hint),
#      removing the packed word's 2^28 committed-entry bound.
# v13: int8 index planes (next/match and the match/hint wire fields) for
#      non-compaction configs with log_capacity <= 41.
# v14: metrics v2 -- ClusterState gained lat_frontier (monotone latency dedup
#      frontier); RunMetrics gained lat_hist (per-entry log2-bin latency
#      histogram), noop_blocked, and lm_skipped_pairs.
# v15: K-deep client pipeline -- client_pend/client_dst became [K] vectors
#      (cfg.client_pipeline slots).
# v16: PreVote (cfg.pre_vote) -- ClusterState gained heard_clock (last leader
#      contact, driving the thesis-9.6 pre-vote denial rule).
# v17: int8 ack-age plane (saturation at the narrow ceiling whenever the
#      responsiveness horizon fits under it).
# v18: bit-packed boolean planes (ops/bitplane.py) -- ClusterState.votes became
#      [N, W = ceil(N/32)] uint32 words; Mailbox gained pv_grant (packed
#      pre-vote grant bits, formerly bit 2 of resp_kind, which is now a pure
#      RESP_* 0..3 plane).
# v19: metrics v3 -- RunMetrics gained lat_excluded (the latency coverage-gap
#      counter: client entries first committed in leaderless windows, measured
#      instead of documented-away). ClusterState is unchanged.
# v20: scenario engine -- checkpoints gained the scenario_json key recording
#      the active nemesis program (scenario/program.py schema; '{}' for plain
#      runs). A scenario run's trajectory is a function of (config, genome,
#      seed), so resuming one WITHOUT its scenario would silently continue a
#      different experiment: plain resume rejects scenario checkpoints
#      (driver `scenario run --resume` restores the genome path). Metrics v4:
#      RunMetrics gained multi_leader (split-brain exposure ticks -- the
#      search's election-safety precursor signal). ClusterState/Mailbox are
#      unchanged.
# v21: payload/latency decoupling (the serve subsystem's enabler) --
#      ClusterState gained log_tick (the [N, CAP] offer-stamp plane the
#      commit-latency metric now reads, freeing log_val for arbitrary client
#      payloads) and client_tick (offer stamps riding the redirect pipeline
#      slots); Mailbox gained ent_tick (the shared-window stamp plane, so
#      replication carries the stamps). All three are zeros and loop-invariant
#      unless cfg.track_offer_ticks (client_interval > 0 or the new
#      RaftConfig.serve_ingest gate).
# v22: reconfiguration plane (raft_sim_tpu/reconfig) -- ClusterState gained
#      the joint-consensus membership plane (member_old/member_new packed
#      voting bitmaps, cfg_epoch, cfg_pend), the TimeoutNow transfer target
#      (xfer_to), and the ReadIndex read slot (read_idx/read_tick/read_acks);
#      Mailbox gained xfer_tgt (the TimeoutNow broadcast header). RunMetrics
#      gained the read traffic counters (reads_served/read_lat_sum/read_hist,
#      telemetry schema v3). All new leaves are zeros/NIL and loop-invariant
#      unless their structural gate (reconfig_interval / transfer_interval /
#      read_interval > 0) is on.
# v23: lease-based reads (thesis 6.4.1; the tenancy plane's read tier) --
#      ClusterState gained read_fr (the committed frontier banked at a
#      pending read's capture, the staleness anchor the viol_read_stale
#      device invariant compares served reads against). Zeros and
#      loop-invariant unless cfg.read_lease (read_lease_ticks > 0). Mailbox
#      and RunMetrics are unchanged (the staleness flag folds into the
#      existing violations counter).
# v24: log-carried configuration (ISSUE 13; models/cfglog.py) -- the admin
#      membership plane became PER-NODE derived state: member_old/member_new
#      are [N, W] rows (one per node, each derived from that node's own log
#      prefix), cfg_epoch/cfg_pend are [N] vectors; ClusterState gained the
#      log_cfg config-entry plane ([N, CAP] int32 commands beside the log)
#      and the snapshot config context (base_mold/base_pend/base_epoch).
#      Mailbox gained req_disrupt (the disruptive-RequestVote transfer
#      override flag), ent_cfg (the shared-window config-command plane), and
#      the snapshot config header (req_base_mold/req_base_pend/
#      req_base_epoch). All new leaves are zeros and loop-invariant unless
#      cfg.reconfig (and the snapshot legs additionally need
#      cfg.compaction). RunMetrics unchanged.
# v25: durable storage plane (ISSUE 19; raft_sim_tpu/storage) --
#      ClusterState gained the durable watermark triple: dur_len ([N] int32
#      entries the disk confirmed), dur_term/dur_vote ([N] int32 durable
#      term/votedFor snapshots; boot values 0/1/NIL match init_state's live
#      triple so a cold cluster is born consistent). RunMetrics gained the
#      fsync lag accumulators (fsync_lag_sum/fsync_lag_max, telemetry
#      schema v4). All new leaves are loop-invariant unless
#      cfg.durable_storage (fsync_interval > 0). Mailbox unchanged.
# v26: plane counters -- RunMetrics gained joint_ticks, xfer_fired and
#      lease_reads (int32 per cluster), each zero and loop-invariant unless
#      its gate (cfg.reconfig, leader_transfer, read_lease) is on.
#      ClusterState and Mailbox unchanged.
# v27: storage-plane counters -- RunMetrics gained fsync_stalls, torn_lost
#      and late_grants (int32 per cluster), each zero and loop-invariant
#      unless cfg.durable_storage (late_grants also needs durable_acks).
#      ClusterState and Mailbox unchanged.
# v28: RunMetrics gained lat_ring_ticks (int32 per cluster: ticks whose
#      offer->commit latency pass swept the whole ring, not the window),
#      zero and loop-invariant unless track_offer_ticks at CAP > 256.
#      ClusterState and Mailbox unchanged.
_FORMAT_VERSION = 28

# The single exported source of truth for the on-disk format version
# (re-exported as raft_sim_tpu.CHECKPOINT_FORMAT_VERSION). Everything that
# writes or gates on checkpoint compatibility must read THIS, not a copy.
FORMAT_VERSION = _FORMAT_VERSION

# Fingerprint of the serialized pytree schema: (version, sha256 of the ordered
# field names + leaf ranks/dtypes of ClusterState / Mailbox / RunMetrics under
# the analyzer's pinned canonical config). The static analyzer
# (raft_sim_tpu/analysis, rule `checkpoint-version`) recomputes the hash from
# the live NamedTuples and fails when the field set changed without BOTH
# bumping _FORMAT_VERSION (append a line to the version log above) and
# refreshing this pin -- the convention the v2..v19 log always relied on,
# now machine-checked. Refresh with:
#     python -c "from raft_sim_tpu.analysis import policy; print(policy.schema_fingerprint())"
_SCHEMA_FINGERPRINT = (28, "3ee601eab9b342f5")


def _normalize(path: str) -> str:
    """np.savez appends '.npz' to bare paths; normalize so save and load agree."""
    return path if path.endswith(".npz") else path + ".npz"


def save(
    path: str,
    cfg: RaftConfig,
    state: ClusterState,
    keys: jax.Array,
    metrics: RunMetrics,
    seed: int = 0,
    scenario: dict | None = None,
) -> str:
    """Write (config, batched state, per-cluster run keys, accumulated metrics, seed).
    Returns the actual path written (always .npz-suffixed). `scenario` is the
    declarative nemesis program driving the run (scenario/program.py to_dict
    schema) -- part of the trajectory's identity, so it rides the checkpoint;
    None marks a plain scalar-config run."""
    path = _normalize(path)
    arrays = {f"state_{f}": np.asarray(v) for f, v in zip(state._fields, state) if f != "mailbox"}
    arrays |= {f"mb_{f}": np.asarray(v) for f, v in zip(state.mailbox._fields, state.mailbox)}
    arrays |= {f"metrics_{f}": np.asarray(v) for f, v in zip(metrics._fields, metrics)}
    arrays["keys"] = np.asarray(jax.random.key_data(keys))
    np.savez_compressed(
        path,
        __version__=np.int32(_FORMAT_VERSION),
        seed=np.int64(seed),
        config_json=np.bytes_(json.dumps(dataclasses.asdict(cfg)).encode()),
        scenario_json=np.bytes_(json.dumps(scenario or {}).encode()),
        **arrays,
    )
    return path


def load(
    path: str,
) -> tuple[RaftConfig, ClusterState, jax.Array, RunMetrics, int, dict | None]:
    """Read a checkpoint; returns (cfg, state, keys, metrics, seed, scenario)
    ready to resume. `scenario` is None for plain runs, else the program dict
    `save` recorded -- the caller must resume through the scenario path."""
    with np.load(_normalize(path)) as z:
        version = int(z["__version__"])
        if version != _FORMAT_VERSION:
            direction = "older" if version < _FORMAT_VERSION else "newer"
            raise ValueError(
                f"checkpoint was written as format v{version}, but this build "
                f"reads v{_FORMAT_VERSION} (the file is {direction} than the "
                f"code). Checkpoints do not auto-migrate: the version log in "
                f"raft_sim_tpu/utils/checkpoint.py names the field change(s) "
                f"between v{min(version, _FORMAT_VERSION)} and "
                f"v{max(version, _FORMAT_VERSION)}; either re-generate the "
                f"checkpoint from its original (seed, config) with this build, "
                f"or load it with the release that wrote v{version}."
            )
        cfg = RaftConfig(**json.loads(bytes(z["config_json"]).decode()))
        mb = Mailbox(**{f: jax.numpy.asarray(z[f"mb_{f}"]) for f in Mailbox._fields})
        fields = {
            f: jax.numpy.asarray(z[f"state_{f}"])
            for f in ClusterState._fields
            if f != "mailbox"
        }
        state = ClusterState(mailbox=mb, **fields)
        keys = jax.random.wrap_key_data(jax.numpy.asarray(z["keys"]))
        metrics = RunMetrics(
            **{f: jax.numpy.asarray(z[f"metrics_{f}"]) for f in RunMetrics._fields}
        )
        seed = int(z["seed"])
        scenario = json.loads(bytes(z["scenario_json"]).decode()) or None
    return cfg, state, keys, metrics, seed, scenario
