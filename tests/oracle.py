"""Scalar-Python oracle for the Raft tick semantics.

An independent re-implementation of the tick semantics of
raft_sim_tpu.models.raft_batched.step_b, one cluster at a time, using plain Python
loops and if/else over numpy state -- the `cond`-cascade form of the handlers (the shape
the reference writes them in, core.clj:91-169) -- used to cross-check the vectorized
`jnp.where` lattice, whose branch precedence is the hard part of the rebuild
(SURVEY.md section 7.3). Deliberately written for clarity, not speed; every phase
mirrors the kernel's documented phase order:

  deliver -> adopt terms -> vote requests -> append requests -> responses ->
  leader commit -> client inject -> timers -> outbox

The oracle operates on dicts of numpy arrays (the device ClusterState pulled host-side)
so the parity test can compare entire states bit-for-bit after every tick.
"""

from __future__ import annotations

import numpy as np

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
PRECANDIDATE = 3  # cfg.pre_vote probe state (thesis 9.6)
REQ_NONE, REQ_VOTE, REQ_APPEND, REQ_PREVOTE = 0, 1, 2, 3
REQ_TIMEOUT_NOW = 4  # cfg.leader_transfer (thesis 3.10)
RESP_NONE, RESP_VOTE, RESP_APPEND, RESP_PREVOTE = 0, 1, 2, 3
NIL = -1
# Independently-stated copies of the implementation's constants (the oracle must not
# import from raft_sim_tpu); tests/test_constants.py pins them against the originals
# so they cannot drift silently.
# raft_sim_tpu.utils.config ACK_AGE_SAT / ACK_AGE_SAT_NARROW + the ack_age_sat
# property, restated: ages saturate at the int8 ceiling when the responsiveness
# horizon fits under it, else at the int16 ceiling.
ACK_AGE_SAT = 30000
ACK_AGE_SAT_NARROW = 120
NOOP = -2  # raft_sim_tpu.types.NOOP (leader no-op entry value, compaction only)


def ack_age_sat(cfg) -> int:
    if cfg.ack_timeout_ticks < ACK_AGE_SAT_NARROW:
        return ACK_AGE_SAT_NARROW
    return ACK_AGE_SAT


def unpack_plane(words: np.ndarray, n: int) -> np.ndarray:
    """Independent numpy restatement of ops/bitplane.py's layout: uint32 words
    along the LAST axis, bit j of word w = source index 32*w + j. The oracle
    operates on plain [.., n] bool planes; the packed wire/state forms
    (ClusterState.votes, Mailbox.pv_grant, StepInputs.deliver_mask) are
    unpacked at the boundary."""
    words = np.asarray(words, np.uint32)
    k = np.arange(n)
    return ((words[..., k // 32] >> (k % 32)) & 1).astype(bool)


def pack_plane(bools: np.ndarray) -> np.ndarray:
    """Inverse of unpack_plane (last axis -> ceil(n/32) uint32 words)."""
    b = np.asarray(bools, bool)
    n = b.shape[-1]
    w = (n + 31) // 32
    out = np.zeros(b.shape[:-1] + (w,), np.uint32)
    for k in range(n):
        out[..., k // 32] |= b[..., k].astype(np.uint32) << (k % 32)
    return out


def chk_weights(k: int) -> tuple[int, int]:
    """(term weight, value weight) of 0-based log slot k for the committed-prefix
    checksum -- the oracle's statement of log_ops.chk_weights (mod 2^32)."""
    m = (1 << 32) - 1
    w_t = ((k * 2654435761 + 0x9E3779B9) | 1) & m
    w_v = ((k * 0x85EBCA77 + 0xC2B2AE3D) | 1) & m
    return w_t, w_v


def _bits_for(n_values: int) -> int:
    """Bits to store values 0..n_values-1 (>= 1). Restates ops/tile.bits_for;
    pinned against it in tests/test_constants.py."""
    return max(1, (n_values - 1).bit_length())


def pack_widths(cfg) -> dict:
    """Independent restatement of the compacted layout's pack-width table
    (ops/tile.pack_width_table): field -> (bits, bias, lo, hi), where lo..hi
    is the dense value range and bias shifts it non-negative before packing.
    Deliberately import-free of raft_sim_tpu -- this is the oracle's own
    derivation from the protocol bounds (next_index 1..cap+1 and match_index
    0..cap, non-compaction only; ack_age saturating at the restated ceiling;
    req_off -1..E with a +1 bias; resp_kind RESP_* 0..3) -- and pinned
    against the tile.py table in tests/test_constants.py."""
    cap, e, sat = cfg.log_capacity, cfg.max_entries_per_rpc, ack_age_sat(cfg)
    table = {}
    if cfg.compact_margin == 0:  # compaction carries dense absolute indices
        table["next_index"] = (_bits_for(cap + 2), 0, 1, cap + 1)
        table["match_index"] = (_bits_for(cap + 2), 0, 0, cap)
    table["ack_age"] = (_bits_for(sat + 1), 0, 0, sat)
    table["mb.req_off"] = (_bits_for(e + 2), 1, -1, e)
    table["mb.resp_kind"] = (2, 0, 0, 3)
    return table


def unpack_values(words: np.ndarray, bits: int, count: int) -> np.ndarray:
    """Independent numpy restatement of the compacted sub-byte layout
    (ops/tile.py pack_words): k = 32 // bits values per uint32 word, value i
    at word i // k, bit lane (i % k) * bits. Returns int64 values."""
    words = np.asarray(words, np.uint32)
    k = 32 // bits
    i = np.arange(count)
    return (
        (words[i // k] >> np.uint32((i % k) * bits)) & np.uint32((1 << bits) - 1)
    ).astype(np.int64)


def _uncompact(cfg, d: dict) -> None:
    """Undo the compacted carry layout (cfg.compact_planes) in place: the
    per-edge value planes unpack from their bit-packed flat uint32 legs at
    the independently restated widths (next/match: log indices bounded by
    cap + 1, non-compaction only; ack_age: the saturation ceiling; req_off:
    -1..E with a +1 bias; resp_kind: RESP_* 0..3), the word/window planes
    reshape back from their flattened forms. The oracle's view -- and the
    parity comparison domain -- stays the dense one either way."""
    n, e, cap = cfg.n_nodes, cfg.max_entries_per_rpc, cfg.log_capacity
    w = (n + 31) // 32
    mb = d["mailbox"]
    idt = np.int8 if cap <= 41 else np.int16  # types.index_dtype, restated
    adt = np.int8 if ack_age_sat(cfg) < 127 else np.int16  # types.ack_dtype
    widths = pack_widths(cfg)

    def _un(leg, field, dtype):
        bits, bias, _lo, _hi = widths[field]
        vals = unpack_values(leg, bits, n * n)
        if bias:
            vals = vals - bias
        return vals.astype(dtype).reshape(n, n)

    if cfg.compact_margin == 0:  # compaction carries dense absolute indices
        d["next_index"] = _un(d["next_index"], "next_index", idt)
        d["match_index"] = _un(d["match_index"], "match_index", idt)
    d["ack_age"] = _un(d["ack_age"], "ack_age", adt)
    mb["req_off"] = _un(mb["req_off"], "mb.req_off", np.int8)
    mb["resp_kind"] = _un(mb["resp_kind"], "mb.resp_kind", np.int8)
    d["votes"] = d["votes"].reshape(n, w)
    for f in ("ent_term", "ent_val", "ent_tick", "ent_cfg"):
        mb[f] = mb[f].reshape(n, e)


def state_to_dict(state, cfg=None) -> dict:
    """Host-side copy of a single-cluster ClusterState (device pytree -> numpy).
    Bit-packed planes (votes, mailbox pv_grant) are unpacked to [N, N] bool:
    the oracle's view -- and the parity tests' comparison domain -- stays the
    dense boolean one. States carried in the compacted layout
    (cfg.compact_planes) need `cfg` so the restated bit widths can undo the
    packing first."""
    d = {
        f: np.asarray(v)
        for f, v in zip(state._fields, state)
        if f != "mailbox"
    }
    mb = state.mailbox
    d["mailbox"] = {f: np.asarray(v) for f, v in zip(mb._fields, mb)}
    if cfg is not None and cfg.compact_planes:
        _uncompact(cfg, d)
    n = d["role"].shape[0]
    d["votes"] = unpack_plane(d["votes"], n)
    d["mailbox"]["pv_grant"] = unpack_plane(d["mailbox"]["pv_grant"], n)
    # Reconfiguration / ReadIndex packed planes: the oracle's view (and the
    # parity tests' comparison domain) is the dense boolean one. member_old/
    # member_new/base_mold are PER-NODE rows ([N, W] -> [N, N]): row i is
    # node i's configuration as derived from its own log prefix.
    d["member_old"] = unpack_plane(d["member_old"], n)
    d["member_new"] = unpack_plane(d["member_new"], n)
    d["base_mold"] = unpack_plane(d["base_mold"], n)
    d["mailbox"]["req_base_mold"] = unpack_plane(d["mailbox"]["req_base_mold"], n)
    d["read_acks"] = unpack_plane(d["read_acks"], n)
    return d


def term_at(log_term: np.ndarray, index1: int) -> int:
    """Term of the 1-based entry `index1`; 0 for index1 == 0 (no entry)."""
    if index1 <= 0:
        return 0
    cap = log_term.shape[0]
    return int(log_term[min(index1 - 1, cap - 1)])


def term_at_ring(log_term: np.ndarray, base: int, base_term: int, index1: int) -> int:
    """Ring-aware term_at: 1-based entry index1 from slot (index1 - 1) mod CAP when
    live (index1 > base); base_term for the compacted prefix; 0 for no entry.
    Degenerates to term_at for base == 0 within the live range."""
    if index1 == 0:
        return 0
    if index1 <= base:
        return int(base_term)
    cap = log_term.shape[0]
    return int(log_term[(index1 - 1) % cap])


def oracle_step(cfg, s: dict, inp: dict) -> dict:
    """One tick for one cluster; returns a fresh state dict."""
    n, e, cap = cfg.n_nodes, cfg.max_entries_per_rpc, cfg.log_capacity
    # Offer-tick plane active: latency stamps ride log_tick / mailbox ent_tick
    # beside the (now arbitrary) payload values; inactive configs leave every
    # tick-plane leaf untouched (mirroring the kernel's passthrough legs).
    track = cfg.track_offer_ticks
    mb = s["mailbox"]

    rcf = cfg.reconfig
    xfr = cfg.leader_transfer
    rdx = cfg.read_index
    rdl = cfg.read_lease
    role = s["role"].copy()
    term = s["term"].copy()
    voted_for = s["voted_for"].copy()
    leader_id = s["leader_id"].copy()
    votes = s["votes"].copy()
    next_index = s["next_index"].copy()
    match_index = s["match_index"].copy()
    ack_age = s["ack_age"].copy()
    commit = s["commit_index"].copy()
    commit_chk = s["commit_chk"].copy()
    log_base = s["log_base"].copy()
    base_term = s["base_term"].copy()
    base_chk = s["base_chk"].copy()
    log_term = s["log_term"].copy()
    log_val = s["log_val"].copy()
    log_tick = s["log_tick"].copy()
    log_len = s["log_len"].copy()
    deadline = s["deadline"].copy()
    heard_clock = s["heard_clock"].copy()
    # Log-carried configuration (models/cfglog.py): per-node derived state.
    # Row d of member_old/member_new is node d's own view; the end-of-tick
    # derivation below recomputes all of it from the post-append log.
    member_old = s["member_old"].copy()  # [N, N] bool (oracle view: unpacked)
    member_new = s["member_new"].copy()
    cfg_epoch = np.asarray(s["cfg_epoch"], np.int32).copy()  # [N]
    cfg_pend = np.asarray(s["cfg_pend"], np.int32).copy()  # [N]
    log_cfg = s["log_cfg"].copy()  # [N, CAP] config-entry commands
    base_mold = s["base_mold"].copy()  # [N, N] bool: C_old at each node's base
    base_pend = np.asarray(s["base_pend"], np.int32).copy()  # [N]
    base_epoch = np.asarray(s["base_epoch"], np.int32).copy()  # [N]
    xfer_to = np.asarray(s["xfer_to"], np.int32).copy()
    # Durable storage plane (models/raft_batched.py phase -1 / 7.5): the fsynced
    # prefix length and the term/vote snapshot at the last completed flush.
    dur = cfg.durable_storage
    dur_len = np.asarray(s["dur_len"], np.int32).copy()
    dur_term = np.asarray(s["dur_term"], np.int32).copy()
    dur_vote = np.asarray(s["dur_vote"], np.int32).copy()
    read_idx = s["read_idx"].copy()
    read_tick = s["read_tick"].copy()
    read_acks = np.asarray(s["read_acks"], bool).copy()
    read_fr = s["read_fr"].copy()

    alive = np.asarray(inp["alive"], bool)
    restarted = np.asarray(inp["restarted"], bool)

    # ---- phase -1: restart wipe (persistent term/vote/log -- including the
    # snapshot triple -- survive; volatile wiped; commit resumes at the base)
    for d in range(n):
        if restarted[d]:
            role[d] = FOLLOWER
            leader_id[d] = NIL
            votes[d, :] = False
            next_index[d, :] = 1
            match_index[d, :] = 0
            ack_age[d, :] = ack_age_sat(cfg)
            commit[d] = log_base[d]
            commit_chk[d] = base_chk[d]
            deadline[d] = int(s["clock"][d]) + int(inp["timeout_draw"][d])
            if dur:
                # Crash recovery: reload the durable term/vote snapshot and
                # truncate the un-fsynced (possibly torn) log suffix. The
                # fsync watermark FLOORS the recovered length -- a completed
                # flush never tears -- so torn_drop eats only the volatile
                # tail (models/raft_batched.py phase -1).
                term[d] = dur_term[d]
                voted_for[d] = int(dur_vote[d]) if cfg.persist_vote else NIL
                log_len[d] = max(
                    int(dur_len[d]),
                    int(s["log_len"][d]) - int(inp["torn_drop"][d]),
                )
            if cfg.pre_vote or rdl or rcf:
                # a restarted node remembers no leader contact (pre-votes
                # grantable; under the lease or log-carried-config denial
                # gates, real votes too)
                heard_clock[d] = int(s["clock"][d]) - cfg.election_min_ticks
            if xfr:
                xfer_to[d] = NIL  # pending transfers die with the process
            if rdx:
                read_idx[d] = 0  # pending reads die with the process
                read_tick[d] = 0
                read_acks[d, :] = False
                if rdl:
                    read_fr[d] = 0  # the staleness anchor dies with the slot

    # Log-carried configuration: the TICK-START derivation governs every
    # quorum test this tick (models/raft_batched.py); the end-of-tick block
    # recomputes it from the post-append log. Each node masks by ITS OWN
    # rows -- dual (both configs) while that node's own prefix holds an
    # uncompleted joint entry. The helper closes over SNAPSHOTS so later
    # phases' rebinds cannot leak in.
    if rcf:
        q_member_old = member_old.copy()  # [N, N] tick-start, never rebound
        q_member_new = member_new.copy()
        joint0 = cfg_pend > 0  # [N]
        maj_old = q_member_old.sum(axis=1) // 2 + 1  # [N]
        maj_new = q_member_new.sum(axis=1) // 2 + 1
        # member_b[d]: is d a voter of ITS OWN config union? A node whose
        # log carries its removal quiesces; one whose log MISSES it still
        # campaigns -- the removed-server disruption the 4.2.3 denial below
        # defends against.
        member_b = np.array(
            [(q_member_old[d, d] or q_member_new[d, d]) for d in range(n)], bool
        )

    def packed_quorum_row(d: int, grants_row: np.ndarray) -> bool:
        """grants_row: [N] bool of node d's banked grants -> quorum under
        node d's OWN configuration(s)."""
        if not rcf:
            return int(grants_row.sum()) >= cfg.quorum
        ok = int((grants_row & q_member_old[d]).sum()) >= int(maj_old[d])
        if joint0[d]:
            ok = ok and int((grants_row & q_member_new[d]).sum()) >= int(maj_new[d])
        return ok

    # ---- phase 0: delivery
    # Input mask is per physical edge [to, from]; request headers are per sender
    # (broadcasts; the [sender, receiver] masks read the edge mask transposed),
    # responses are [receiver, responder] packed words (direct).
    # A receiver must be alive now AND at send time (last tick): alive & ~restarted.
    # The delivery mask arrives bit-packed over the source axis; unpack to the
    # dense [to, from] bool form the handler loops read. Under the compacted
    # layout (cfg.compact_planes) the word plane additionally ships FLAT
    # ([N*W]): restore the [N, W] row view first.
    dm = np.asarray(inp["deliver_mask"])
    if dm.ndim == 1:
        dm = dm.reshape(n, -1)
    edge_ok = unpack_plane(dm, n).copy()
    np.fill_diagonal(edge_ok, False)
    recv_up = alive & ~restarted
    req_in = edge_ok.T & alive[:, None] & recv_up[None, :] & (mb["req_type"] != 0)[:, None]
    resp_in = edge_ok & recv_up[:, None] & alive[None, :] & (mb["resp_kind"] != 0)

    # Heard-a-leader denial window (thesis 4.2.3; models/raft_batched.py): shared by
    # the log-carried membership defense (rcf) and the lease vote denial
    # (rdl), bypassed per sender by the transfer override flag.
    if rcf or rdl:
        def rv_denied(src: int, d: int) -> bool:
            clock_d = int(s["clock"][d]) + int(inp["skew"][d])
            recent = clock_d - int(heard_clock[d]) < cfg.election_min_ticks
            if xfr and int(mb["req_disrupt"][src]) != 0:
                return False  # transfer-sanctioned election: always processed
            return recent

    # ---- phase 1: term adoption
    saw_higher = np.zeros(n, bool)
    for d in range(n):
        in_term = 0
        for src in range(n):
            # a PreVote probe's term is prospective: never adopted
            if req_in[src, d] and mb["req_type"][src] != REQ_PREVOTE:
                if (
                    rcf
                    and mb["req_type"][src] == REQ_VOTE
                    and rv_denied(src, d)
                ):
                    # 4.2.3 in full: a denied RequestVote is not PROCESSED --
                    # no term adoption (the removed-server disruption
                    # defense; under rdl alone adoption stays legal).
                    continue
                in_term = max(in_term, int(mb["req_term"][src]))
            if resp_in[d, src]:
                in_term = max(in_term, int(mb["resp_term"][src]))
        if in_term > term[d]:
            saw_higher[d] = True
            term[d] = in_term
            role[d] = FOLLOWER
            voted_for[d] = NIL
            leader_id[d] = NIL
            votes[d, :] = False

    # ---- phase 2: RequestVote requests
    granted_any = np.zeros(n, bool)
    vr_out = np.zeros((n, n), bool)  # [dst, src]: respond to src
    v_to = np.full(n, NIL, np.int32)  # the one candidate granted this tick
    for d in range(n):
        my_last_idx = int(s["log_len"][d])
        my_last_term = term_at_ring(
            s["log_term"][d], int(log_base[d]), int(base_term[d]), my_last_idx
        )
        can = []
        for src in range(n):
            if not (req_in[src, d] and mb["req_type"][src] == REQ_VOTE):
                continue
            vr_out[d, src] = True
            if mb["req_term"][src] != term[d]:
                continue
            c_idx = int(mb["req_last_index"][src])
            c_term = int(mb["req_last_term"][src])
            up_to_date = c_term > my_last_term or (
                c_term == my_last_term and c_idx >= my_last_idx
            )
            if up_to_date:
                can.append(src)
        if rcf or rdl:
            # Heard-a-leader vote denial (thesis 4.2.3; models/raft_batched.py
            # phase 2): a voter that heard from a current leader within the
            # minimum election timeout on its LOCAL clock denies
            # RequestVote -- unless the sender carries the transfer
            # override (rv_denied folds it in).
            can = [src for src in can if not rv_denied(src, d)]
        if not can:
            continue
        if voted_for[d] != NIL:
            if voted_for[d] in can:  # idempotent re-grant
                v_to[d] = voted_for[d]
                granted_any[d] = True
        else:
            winner = min(can)
            v_to[d] = winner
            granted_any[d] = True
            voted_for[d] = winner

    # ---- phase 3: AppendEntries requests (incl. the InstallSnapshot analogue).
    # Response payloads are per RESPONDER (sparse by construction: at most one
    # success target per tick; the nack hint is the responder's log length toward
    # every sender -- types.Mailbox docstring).
    has_ae = np.zeros(n, bool)
    snap_applied = np.zeros(n, bool)
    ar_out = np.zeros((n, n), bool)
    a_ok_to = np.full(n, NIL, np.int32)
    a_match = np.zeros(n, np.int32)
    for d in range(n):
        cur = [
            src
            for src in range(n)
            if req_in[src, d]
            and mb["req_type"][src] == REQ_APPEND
        ]
        for src in cur:
            ar_out[d, src] = True
        cur_term = [src for src in cur if mb["req_term"][src] == term[d]]
        if not cur_term:
            continue
        src = min(cur_term)
        has_ae[d] = True
        if role[d] == CANDIDATE or (cfg.pre_vote and role[d] == PRECANDIDATE):
            role[d] = FOLLOWER
        leader_id[d] = src

        j = int(mb["req_off"][src, d])
        if j < 0:
            # InstallSnapshot analogue (req_off sentinel -1): install the sender's
            # compaction base. If our log extends through L with the snapshot's
            # term, retain the suffix; else discard the log. L <= our base needs
            # nothing. Always ack with match = L.
            L = int(mb["req_base"][src])
            if L > int(log_base[d]):
                keep = L <= int(log_len[d]) and term_at_ring(
                    log_term[d], int(log_base[d]), int(base_term[d]), L
                ) == int(mb["req_base_term"][src])
                base_term[d] = int(mb["req_base_term"][src])
                base_chk[d] = mb["req_base_chk"][src]
                log_base[d] = L
                if not keep:
                    log_len[d] = L
                commit[d] = max(int(commit[d]), L)
                snap_applied[d] = True
                if rcf:
                    # The snapshot carries its configuration context: the
                    # sender's C_old/pending-toggle/entry-count at L, so the
                    # receiver's derivation stays exact over config entries
                    # it never saw (models/raft_batched.py phase 3).
                    base_mold[d] = np.asarray(mb["req_base_mold"][src], bool)
                    base_pend[d] = int(mb["req_base_pend"][src])
                    base_epoch[d] = int(mb["req_base_epoch"][src])
            a_ok_to[d] = src
            a_match[d] = L
            continue

        # Reconstruct the per-edge AE header from the sender's broadcast record plus
        # this edge's window offset j (Mailbox docstring): prev = ent_start + j,
        # prev term = ent_prev_term for j == 0 else window slot j-1, and n_entries =
        # whatever of the window lies past j.
        ws = int(mb["ent_start"][src])
        prev_i = ws + j
        prev_t = (
            int(mb["ent_prev_term"][src]) if j == 0 else int(mb["ent_term"][src, j - 1])
        )
        lcommit = int(mb["req_commit"][src])
        n_ent = min(max(int(mb["ent_count"][src]) - j, 0), e)
        # This receiver's entries start at window slot j (clipped reads past the
        # window occur only at masked k >= n_ent positions).
        ent_t = [int(mb["ent_term"][src, min(j + k, e - 1)]) for k in range(e)]
        ent_v = [int(mb["ent_val"][src, min(j + k, e - 1)]) for k in range(e)]
        ent_tk = [int(mb["ent_tick"][src, min(j + k, e - 1)]) for k in range(e)]
        ent_cf = [int(mb["ent_cfg"][src, min(j + k, e - 1)]) for k in range(e)]

        b = int(log_base[d])
        # prev below our base is committed-and-compacted: consistent by leader
        # completeness; at prev == base the check compares against base_term.
        consistent = prev_i == 0 or prev_i < b or (
            prev_i <= int(s["log_len"][d])
            and term_at_ring(log_term[d], b, int(base_term[d]), prev_i) == prev_t
        )
        if not consistent:
            continue

        # Skip entries already compacted (<= base), accept only what the ring can
        # hold (<= base + CAP).
        lo = min(max(b - prev_i, 0), e)
        n_acc = min(n_ent, max(b + cap - prev_i, 0))
        any_mismatch = any(
            lo <= k < n_acc
            and prev_i + k < int(s["log_len"][d])
            and int(log_term[d, (prev_i + k) % cap]) != int(ent_t[k])
            for k in range(e)
        )
        appended_len = prev_i + n_acc
        new_len = appended_len if any_mismatch else max(int(s["log_len"][d]), appended_len)
        for k in range(lo, n_acc):
            log_term[d, (prev_i + k) % cap] = ent_t[k]
            log_val[d, (prev_i + k) % cap] = ent_v[k]
            if track:
                # The offer stamp replicates with the entry it tags.
                log_tick[d, (prev_i + k) % cap] = ent_tk[k]
            if rcf:
                # The config command replicates beside the entry; non-config
                # entries ship 0, scrubbing stale commands off reused slots.
                log_cfg[d, (prev_i + k) % cap] = ent_cf[k]
        log_len[d] = new_len

        last_new = min(prev_i + n_acc, new_len)
        commit[d] = max(int(commit[d]), min(lcommit, last_new))
        a_ok_to[d] = src
        a_match[d] = last_new

    # Durable watermark after the AE conflict truncation (models/raft_batched.py
    # phase 3): a truncation below the watermark drags it down with the log.
    if dur:
        dur_mid = np.minimum(dur_len, log_len.astype(np.int32))

    # NACK catch-up hint: every unsuccessful AE response carries the responder's
    # (post-append) log length -- the conflict-index optimization (raft_batched.py
    # phase 3). Per responder: the same hint toward every nacked sender.
    a_hint = log_len.astype(np.int32).copy()

    # ---- phase 3.5: PreVote requests (thesis 9.6; raft_batched.py): grant iff the
    # probe's prospective term is not behind us, its log is up to date, and we
    # are quiet (not leader, no valid AE within the minimum election timeout).
    pv_out = np.zeros((n, n), bool)
    pv_grant = np.zeros((n, n), bool)
    if (rdl or rcf) and not cfg.pre_vote:
        # heard_clock maintenance for the lease / removed-server vote
        # denials (the pre-vote branch below maintains it when that gate is
        # on too).
        for d in range(n):
            if has_ae[d]:
                heard_clock[d] = int(s["clock"][d]) + int(inp["skew"][d])
    if cfg.pre_vote:
        for d in range(n):
            clock_pv = int(s["clock"][d]) + int(inp["skew"][d])
            if has_ae[d]:
                heard_clock[d] = clock_pv
            quiet = (
                clock_pv - int(heard_clock[d]) >= cfg.election_min_ticks
                and role[d] != LEADER
            )
            my_last_idx = int(s["log_len"][d])
            my_last_term = term_at_ring(
                s["log_term"][d], int(s["log_base"][d]), int(s["base_term"][d]),
                my_last_idx,
            )
            for src in range(n):
                if not (req_in[src, d] and mb["req_type"][src] == REQ_PREVOTE):
                    continue
                pv_out[d, src] = True
                c_idx = int(mb["req_last_index"][src])
                c_term = int(mb["req_last_term"][src])
                up = c_term > my_last_term or (
                    c_term == my_last_term and c_idx >= my_last_idx
                )
                if quiet and up and int(mb["req_term"][src]) >= int(term[d]):
                    pv_grant[d, src] = True

    # ---- phase 3.7: TimeoutNow receipt (thesis 3.10; models/raft_batched.py)
    xfer_elect = np.zeros(n, bool)
    coup = np.zeros(n, bool)
    if xfr:
        for d in range(n):
            if role[d] == LEADER or not alive[d]:
                continue
            if rcf and not member_b[d]:
                continue  # non-voters never campaign
            got = any(
                req_in[src, d]
                and mb["req_type"][src] == REQ_TIMEOUT_NOW
                and int(mb["xfer_tgt"][src]) == d
                and int(mb["req_term"][src]) == int(term[d])
                for src in range(n)
            )
            if not got:
                continue
            if cfg.xfer_election:
                xfer_elect[d] = True
            else:
                # TEST-ONLY mutant: transfer as a coup (no vote round).
                coup[d] = True
                term[d] += 1
                role[d] = LEADER
                leader_id[d] = d

    # ---- phase 4: responses
    # Everyone's ack age grows one tick (saturating); stamps below zero it.
    ack_age = np.minimum(ack_age + 1, ack_age_sat(cfg)).astype(ack_age.dtype)
    for d in range(n):
        for src in range(n):
            if (
                resp_in[d, src]
                and mb["resp_kind"][d, src] == RESP_VOTE
                and mb["v_to"][src] == d
                and mb["resp_term"][src] == term[d]
                and role[d] == CANDIDATE
            ):
                votes[d, src] = True
    win = np.zeros(n, bool)
    for d in range(n):
        campaign_ok = role[d] == CANDIDATE and packed_quorum_row(d, votes[d]) and alive[d]
        if rcf and not member_b[d]:
            campaign_ok = False  # removed nodes cannot win on banked votes
        if campaign_ok or coup[d]:
            win[d] = True
            role[d] = LEADER
            leader_id[d] = d
            next_index[d, :] = log_len[d] + 1
            match_index[d, :] = 0
            ack_age[d, :] = 0  # grace-zero every peer (see raft_batched.py phase 4)

    # ---- phase 4.5: PreVote responses + promotion (thesis 9.6; raft_batched.py)
    pre_win = np.zeros(n, bool)
    if cfg.pre_vote:
        for d in range(n):
            if role[d] != PRECANDIDATE:
                continue
            for src in range(n):
                # The grant bit rides the packed pv_grant plane (unpacked to
                # [receiver, responder] bool by state_to_dict).
                if (
                    resp_in[d, src]
                    and int(mb["resp_kind"][d, src]) == RESP_PREVOTE
                    and bool(mb["pv_grant"][d, src])
                ):
                    votes[d, src] = True
            if (
                packed_quorum_row(d, votes[d])
                and alive[d]
                and not (rcf and not member_b[d])
            ):
                pre_win[d] = True
                term[d] += 1
                role[d] = CANDIDATE
                voted_for[d] = d
                votes[d, :] = False
                votes[d, d] = True
    aresp_pairs = np.zeros((n, n), bool)  # [leader, responder]: AE response seen
    for d in range(n):
        if role[d] != LEADER:
            continue
        for src in range(n):
            if not (
                resp_in[d, src]
                and mb["resp_kind"][d, src] == RESP_APPEND
                and mb["resp_term"][src] == term[d]
            ):
                continue
            aresp_pairs[d, src] = True
            if mb["a_ok_to"][src] == d:
                m = int(mb["a_match"][src])
                match_index[d, src] = max(int(match_index[d, src]), m)
                next_index[d, src] = max(int(next_index[d, src]), m + 1)
            else:
                # Back off to min(next-1, hint+1): the nack hint is the
                # responder's log length (conflict-index hint, raft_batched.py phase 4).
                next_index[d, src] = max(
                    min(int(next_index[d, src]) - 1, int(mb["a_hint"][src]) + 1), 1
                )
            # Any AE response (success or failure) proves the peer is up.
            ack_age[d, src] = 0

    # ---- phase 5: leader commit advancement
    def masked_qmatch(match: np.ndarray, mask: np.ndarray, maj: int) -> int:
        """Largest index replicated to >= maj members of `mask` (0 if none);
        candidates range over the members' own match values (raft_batched.py)."""
        best = 0
        for j in range(n):
            if not mask[j]:
                continue
            v = int(match[j])
            if sum(1 for k in range(n) if mask[k] and int(match[k]) >= v) >= maj:
                best = max(best, v)
        return best

    for d in range(n):
        if role[d] != LEADER or not alive[d]:
            continue
        match = match_index[d].copy()
        # A leader's own quorum vote is its DURABLE length under the storage
        # plane's ack gate (models/raft_batched.py phase 5).
        match[d] = dur_mid[d] if (dur and cfg.durable_acks) else log_len[d]
        if rcf:
            # Each leader's OWN derived configuration masks its commit
            # quorum (tick-start rows; models/raft_batched.py phase 5).
            quorum_match = masked_qmatch(
                match, q_member_old[d], int(maj_old[d])
            )
            if joint0[d]:
                quorum_match = min(
                    quorum_match,
                    masked_qmatch(match, q_member_new[d], int(maj_new[d])),
                )
        else:
            quorum_match = int(np.sort(match)[::-1][cfg.quorum - 1])
        if quorum_match > commit[d] and term_at_ring(
            log_term[d], int(log_base[d]), int(base_term[d]), quorum_match
        ) == term[d]:
            commit[d] = quorum_match

    # ---- phase 5.2: reconfiguration transitions moved INTO the log
    # (log-carried membership: no admin transition block -- config changes
    # are appends in phase 6, each node's configuration re-derives from its
    # own prefix at end of tick; models/raft_batched.py phase 5.2 comment)
    xfer_pend = np.zeros(n, bool)
    if xfr:
        for d in range(n):
            if xfer_to[d] != NIL:
                t = int(xfer_to[d])
                if (
                    role[d] != LEADER
                    or int(ack_age[d, t]) > cfg.ack_timeout_ticks
                ):
                    xfer_to[d] = NIL  # abort: deposed or unresponsive target
        t_x = int(inp["transfer_cmd"])
        ld_ok = [
            d
            for d in range(n)
            if role[d] == LEADER and alive[d] and not (rcf and not member_b[d])
        ]
        if t_x != NIL and ld_ok:
            ldx = min(ld_ok)
            # Target must be a voter of the LEADER's own target config
            # (per-node derived rows; tick-start like every config read).
            t_voter = bool(q_member_new[ldx, t_x]) if rcf else True
            if t_x != ldx and t_voter and xfer_to[ldx] == NIL:
                xfer_to[ldx] = t_x
        xfer_pend = xfer_to != NIL
    if rdx:
        # Bank this tick's AE responses, serve confirmed reads, capture new.
        pend0_arr = read_idx > 0  # pending at tick start (pre-serve/capture)
        for d in range(n):
            pend0 = bool(pend0_arr[d])
            if pend0 and role[d] == LEADER:
                read_acks[d] |= aresp_pairs[d]
                acks_eff = read_acks[d].copy()
                acks_eff[d] = True
                confirmed = packed_quorum_row(d, acks_eff)
                served = (confirmed if cfg.read_confirm else True) and alive[d]
                if rdl and not served and alive[d]:
                    # Lease fast path (thesis 6.4.1; models/raft_batched.py): a
                    # fresh config quorum of AE acks serves with NO
                    # confirmation round. The lease-skew mutant widens the
                    # window to the no-skew bound.
                    lease_w = (
                        cfg.read_lease_ticks
                        if cfg.lease_skew_safe
                        else cfg.election_min_ticks + 2
                    )
                    fresh_row = np.asarray(ack_age[d] <= lease_w, bool).copy()
                    fresh_row[d] = True
                    served = packed_quorum_row(d, fresh_row)
                    if xfr and xfer_pend[d]:
                        # Transfer handoff covers the read path: the lease
                        # fast path stops while a transfer pends
                        # (models/raft_batched.py phase 5).
                        served = False
                if served:
                    # serve (the latency metric rides StepInfo, which the
                    # oracle does not produce; parity pins the slot clears)
                    read_idx[d] = 0
                    read_tick[d] = 0
                    read_acks[d, :] = False
                    if rdl:
                        read_fr[d] = 0
            elif pend0:
                read_idx[d] = 0  # role loss / adoption cancels the read
                read_tick[d] = 0
                read_acks[d, :] = False
                if rdl:
                    read_fr[d] = 0
        if int(inp["read_cmd"]) != NIL:
            caps = []
            for d in range(n):
                if not (role[d] == LEADER and alive[d] and not pend0_arr[d]):
                    continue
                if xfr and xfer_pend[d]:
                    continue
                if cfg.read_confirm and term_at_ring(
                    log_term[d], int(log_base[d]), int(base_term[d]),
                    int(commit[d]),
                ) != int(term[d]):
                    continue  # no current-term entry committed yet
                caps.append(d)
            if caps:
                d = min(caps)
                read_idx[d] = int(commit[d]) + 1
                read_tick[d] = int(s["now"]) + 1
                read_acks[d, :] = False
                if rdl:
                    # Staleness anchor: the committed frontier at capture
                    # (lat_frontier semantics -- models/raft_batched.py phase 5).
                    read_fr[d] = max(int(s["lat_frontier"]),
                                     int(commit.max()))

    # ---- phase 5.5: log compaction (advance base toward commit when fewer than
    # compact_margin free ring slots remain; base_chk extends in the checksum pass)
    base_mid = log_base.copy()
    base_chk_mid = base_chk.copy()
    if cfg.compact_margin > 0:
        for d in range(n):
            target = min(int(commit[d]), int(log_len[d]) - (cap - cfg.compact_margin))
            if target > int(log_base[d]):
                base_term[d] = term_at_ring(
                    log_term[d], int(log_base[d]), int(base_term[d]), target
                )
                if rcf:
                    # Fold the compacted span's config entries into the
                    # snapshot context (models/cfglog.py fold_span): final
                    # toggles into base_mold, the latest entry's jointness
                    # into base_pend, the count into base_epoch. Runs before
                    # phase 6 can reuse freed slots.
                    span = [
                        (a, int(log_cfg[d, (a - 1) % cap]))
                        for a in range(int(log_base[d]) + 1, target + 1)
                        if int(log_cfg[d, (a - 1) % cap]) != 0
                    ]
                    for _, code in span:
                        if code < 0 or not cfg.joint_consensus:
                            v = abs(code) - 1
                            base_mold[d, v] = not base_mold[d, v]
                    if span and cfg.joint_consensus:
                        code_hi = span[-1][1]
                        base_pend[d] = code_hi if code_hi > 0 else 0
                    base_epoch[d] += len(span)
                log_base[d] = target

    # ---- committed-prefix checksum (log_ops.chk_weights analogue): weights by
    # ABSOLUTE entry index, anchored at the pre-compaction base (base_mid); the
    # same pass extends base_chk over the newly compacted span. Runs BEFORE
    # injection -- a write into a slot freed by this tick's rebase would alias
    # under the anchored slot->index map (raft_batched.py). Under compaction the sums are
    # maintained even with invariant checking off (base_chk is wire state).
    if cfg.check_invariants or cfg.compact_margin > 0:
        M = (1 << 32) - 1
        for d in range(n):
            acc = int(base_chk_mid[d])
            accb = int(base_chk_mid[d])
            for a in range(int(base_mid[d]), int(commit[d])):  # 0-based abs index
                w_t, w_v = chk_weights(a)
                contrib = int(log_term[d, a % cap]) * w_t + int(log_val[d, a % cap]) * w_v
                acc = (acc + contrib) & M
                if a < int(log_base[d]):
                    accb = (accb + contrib) & M
            commit_chk[d] = np.uint32(acc)
            base_chk[d] = np.uint32(accb)

    # ---- phase 6: client injection (ring slot; space = retained window < CAP),
    # redirect routing, and the election-win leader no-op (raft_batched.py phase 6)
    cmd_in = int(inp["client_cmd"])
    now0 = int(s["now"])  # pre-increment tick: a fresh offer's stamp is now0 + 1
    comp = cfg.compact_margin > 0
    reserve = max(1, cfg.compact_margin // 2)
    K = cfg.client_pipeline
    client_pend = [int(x) for x in np.atleast_1d(s["client_pend"])]
    client_dst = [int(x) for x in np.atleast_1d(s["client_dst"])]
    client_tick = [int(x) for x in np.atleast_1d(s["client_tick"])]

    def noop_at(d):
        return comp and win[d] and int(log_len[d]) - int(log_base[d]) < cap

    def room_at(d):
        retained = int(log_len[d]) - int(log_base[d])
        return retained < (cap - reserve if comp else cap)

    def append(d, value, stamp, code=0):
        log_term[d, log_len[d] % cap] = term[d]
        log_val[d, log_len[d] % cap] = value
        if track:
            # Offer stamp beside the payload (no-ops/protocol filler: 0).
            log_tick[d, log_len[d] % cap] = stamp
        if rcf:
            # EVERY append writes the config plane (0 for non-config
            # entries): a reused slot never leaks its old command.
            log_cfg[d, log_len[d] % cap] = code
        log_len[d] += 1

    # Config-entry origination (log-carried membership; models/raft_batched.py
    # phase 6): config changes are LOG WRITES sharing the one-append-per-
    # node slot at priority no-op > config entry > client command, judged
    # on each leader's OWN tick-start derived configuration.
    cfg_write = np.zeros(n, bool)
    cfg_code = np.zeros(n, np.int32)
    if rcf:
        t_r = int(inp["reconfig_cmd"])
        ld_ok_c = [
            d
            for d in range(n)
            if role[d] == LEADER and alive[d] and member_b[d]
            and room_at(d) and not noop_at(d)
        ]
        # JOINT entry (+v+1): the admin's toggle, accepted by the lowest-id
        # eligible leader whose own prefix is NOT already joint; refused
        # when the toggle would leave C_new below 2 voters.
        non_joint = [d for d in ld_ok_c if not joint0[d]]
        if t_r != NIL and 0 <= t_r < n and non_joint:
            d = min(non_joint)
            toggled = q_member_new[d].copy()
            toggled[t_r] = not toggled[t_r]
            if int(toggled.sum()) >= 2:
                cfg_write[d] = True
                cfg_code[d] = t_r + 1
        if cfg.joint_consensus:
            # FINAL entry (-v-1): appended once the governing joint entry
            # commits on the leader -- "C_old,new committed -> append C_new".
            for d in ld_ok_c:
                if joint0[d] and int(commit[d]) >= int(cfg_pend[d]):
                    diff = q_member_old[d] ^ q_member_new[d]
                    pend_v = int(np.argmax(diff))  # lowest differing bit
                    cfg_write[d] = True
                    cfg_code[d] = -(pend_v + 1)
        # (cfg.joint_consensus False, TEST-ONLY single-server-change mutant:
        # one final-acting entry per change, no completing entry.)

    if cfg.client_redirect:
        # K commands in flight chasing 302 redirects (raft_batched.py phase 6): a fresh
        # offer takes the first free slot; at most one slot is accepted per
        # node per tick, lowest slot index first.
        pend = list(client_pend)
        tgt = list(client_dst)
        ptk = list(client_tick)
        if cmd_in != NIL:
            for k in range(K):
                if pend[k] == NIL:
                    pend[k] = cmd_in
                    tgt[k] = int(inp["client_target"])
                    ptk[k] = now0 + 1
                    break
        accepted = [False] * K
        for d in range(n):
            if noop_at(d):
                append(d, NOOP, 0)
                continue
            if rcf and cfg_write[d]:
                # Config entries carry value 0 and stamp 0 (the command
                # rides the log_cfg plane); the slot is taken this tick.
                append(d, 0, 0, int(cfg_code[d]))
                continue
            here = [k for k in range(K) if pend[k] != NIL and tgt[k] == d]
            if (
                here and role[d] == LEADER and alive[d] and room_at(d)
                and not (xfr and xfer_pend[d])  # transfer lease handoff
            ):
                k = min(here)
                append(d, pend[k], ptk[k])
                accepted[k] = True
        for k in range(K):
            if pend[k] != NIL and not accepted[k]:
                t = tgt[k]
                tl = int(leader_id[t])
                client_pend[k] = pend[k]
                client_dst[k] = (
                    tl if (alive[t] and tl != NIL) else int(inp["client_bounce"][k])
                )
                if track:
                    client_tick[k] = ptk[k]
            else:
                client_pend[k], client_dst[k] = NIL, 0
                if track:
                    client_tick[k] = 0
    else:
        for d in range(n):
            if noop_at(d):
                append(d, NOOP, 0)
            elif rcf and cfg_write[d]:
                append(d, 0, 0, int(cfg_code[d]))  # the slot holds a config entry
            elif (
                cmd_in != NIL and role[d] == LEADER and alive[d] and room_at(d)
                and not (xfr and xfer_pend[d])  # transfer lease handoff
            ):
                append(d, cmd_in, now0 + 1)

    # ---- phase 7: timers
    clock = s["clock"] + np.asarray(inp["skew"], np.int32)
    heartbeat = np.zeros(n, bool)
    start_election = np.zeros(n, bool)
    start_prevote = np.zeros(n, bool)
    for d in range(n):
        if granted_any[d] or has_ae[d] or saw_higher[d]:
            deadline[d] = clock[d] + int(inp["timeout_draw"][d])
        if win[d]:
            deadline[d] = clock[d] + cfg.heartbeat_ticks
        if cfg.pre_vote and pre_win[d]:
            deadline[d] = clock[d] + int(inp["timeout_draw"][d])
        expired = clock[d] >= deadline[d] and alive[d]
        if expired and role[d] == LEADER:
            heartbeat[d] = True
            deadline[d] = clock[d] + cfg.heartbeat_ticks
        elif expired and cfg.pre_vote and (
            not (rcf and not member_b[d])  # non-voters never campaign
            and not (xfr and xfer_elect[d])  # thesis-3.10 pre-vote bypass
        ):
            # expiry starts a PRE-vote probe: no term bump, votedFor untouched
            start_prevote[d] = True
            role[d] = PRECANDIDATE
            leader_id[d] = NIL
            votes[d, :] = False
            votes[d, d] = True
            deadline[d] = clock[d] + int(inp["timeout_draw"][d])
        elif expired and not cfg.pre_vote and not (rcf and not member_b[d]):
            start_election[d] = True
            term[d] += 1
            role[d] = CANDIDATE
            voted_for[d] = d
            leader_id[d] = NIL
            votes[d, :] = False
            votes[d, d] = True
            deadline[d] = clock[d] + int(inp["timeout_draw"][d])
    if cfg.pre_vote:
        # real RequestVote broadcasts come from this tick's promotions
        start_election = pre_win.copy()
    xe = np.zeros(n, bool)  # transfer-triggered elections (req_disrupt flag)
    if xfr:
        # TimeoutNow elections: the real-election start, bypassing timer and
        # pre-vote (~LEADER re-checked: a phase-4 win may have promoted).
        for d in range(n):
            if xfer_elect[d] and role[d] != LEADER:
                if cfg.pre_vote and start_election[d]:
                    continue  # kernel: xe = xfer_elect & ~pre_win & ~is_leader
                xe[d] = True
                if not start_election[d]:
                    start_election[d] = True
                    term[d] += 1
                    role[d] = CANDIDATE
                    voted_for[d] = d
                    leader_id[d] = NIL
                    votes[d, :] = False
                    votes[d, d] = True
                    deadline[d] = clock[d] + int(inp["timeout_draw"][d])

    # ---- phase 7.5: fsync flush + durability gates (models/raft_batched.py) --------
    # After elections finalize term/votedFor and injection finalizes log_len:
    # a completing flush snaps the durable snapshot to the live triple. Gates
    # (cfg.durable_acks; False = TEST-ONLY ack-before-fsync mutant): AE acks
    # reflect only the fsynced prefix, and a vote grant is exposed only once
    # the durable snapshot covers it -- the covering flush emits the withheld
    # response (late_grant overlay in the outbox below).
    late_grant = np.zeros(n, bool)
    if dur:
        dur2_len = dur_mid.astype(np.int32).copy()
        dur2_term = dur_term.copy()
        dur2_vote = dur_vote.copy()
        for d in range(n):
            if bool(inp["fsync_fire"][d]) and alive[d]:  # dead disks never flush
                dur2_len[d] = log_len[d]
                dur2_term[d] = term[d]
                dur2_vote[d] = voted_for[d]
        if cfg.durable_acks:
            a_match = np.minimum(a_match, dur2_len)
            for d in range(n):
                covered0 = (
                    int(dur_term[d]) == int(term[d])
                    and int(dur_vote[d]) == int(voted_for[d])
                    and int(voted_for[d]) != NIL
                )
                covered2 = (
                    int(dur2_term[d]) == int(term[d])
                    and int(dur2_vote[d]) == int(voted_for[d])
                    and int(voted_for[d]) != NIL
                )
                v_to[d] = int(voted_for[d]) if covered2 else NIL
                late_grant[d] = covered2 and not covered0 and not granted_any[d]
        dur_len, dur_term, dur_vote = dur2_len, dur2_term, dur2_vote

    # ---- phase 8: outbox (wire format v8: per-sender headers + per-edge offsets)
    z = lambda *shape: np.zeros(shape, np.int32)
    out = {
        "req_type": z(n),
        "req_term": z(n),
        "req_commit": z(n),
        "req_last_index": z(n),
        "req_last_term": z(n),
        "ent_start": z(n),
        "ent_prev_term": z(n),
        "ent_count": z(n),
        "ent_term": z(n, e),
        "ent_val": z(n, e),
        "ent_tick": z(n, e),
        "req_base": z(n),
        "req_base_term": z(n),
        "req_base_chk": np.zeros(n, np.uint32),
        "xfer_tgt": np.full(n, NIL, np.int32),
        "req_disrupt": z(n),
        "ent_cfg": z(n, e),
        "req_base_mold": np.zeros((n, n), bool),
        "req_base_pend": z(n),
        "req_base_epoch": z(n),
        "req_off": z(n, n),
        "resp_kind": z(n, n),
        "pv_grant": np.zeros((n, n), bool),
        "v_to": v_to,
        "a_ok_to": a_ok_to,
        "a_match": a_match,
        "a_hint": a_hint,
        "resp_term": z(n),
    }
    for src in range(n):
        out["resp_term"][src] = term[src]
        b = int(log_base[src])
        bt = int(base_term[src])
        if start_election[src]:
            last_idx = int(log_len[src])
            out["req_type"][src] = REQ_VOTE
            out["req_term"][src] = term[src]
            out["req_last_index"][src] = last_idx
            out["req_last_term"][src] = term_at_ring(log_term[src], b, bt, last_idx)
        elif cfg.pre_vote and start_prevote[src]:
            last_idx = int(log_len[src])
            out["req_type"][src] = REQ_PREVOTE
            out["req_term"][src] = term[src] + 1  # prospective (thesis 9.6)
            out["req_last_index"][src] = last_idx
            out["req_last_term"][src] = term_at_ring(log_term[src], b, bt, last_idx)
        elif win[src] or heartbeat[src]:
            # Shared entry window: starts at the minimum prev over RESPONSIVE peers
            # (acked an AE within ack_timeout_ticks), falling back to all peers when
            # none are -- a dead peer must not pin the window (raft_batched.py phase 8) --
            # and never below the compaction base (those entries are gone; such
            # peers get the InstallSnapshot sentinel instead).
            prev_of = lambda dst: min(
                max(int(next_index[src, dst]) - 1, 0), int(log_len[src])
            )
            resp_prevs = [
                prev_of(dst)
                for dst in range(n)
                if dst != src and int(ack_age[src, dst]) <= cfg.ack_timeout_ticks
            ]
            all_prevs = [prev_of(dst) for dst in range(n) if dst != src]
            ws = min(min(resp_prevs or all_prevs), int(log_len[src]))
            ws = max(ws, b)
            n_ship = min(int(log_len[src]) - ws, e)
            out["req_type"][src] = REQ_APPEND
            out["req_term"][src] = term[src]
            out["req_commit"][src] = commit[src]
            out["ent_start"][src] = ws
            out["ent_prev_term"][src] = term_at_ring(log_term[src], b, bt, ws)
            out["ent_count"][src] = n_ship
            out["req_base"][src] = b
            out["req_base_term"][src] = bt
            out["req_base_chk"][src] = base_chk[src]
            if comp and rcf:
                # Snapshot config header: the sender's configuration context
                # at its base rides every AE broadcast (models/raft_batched.py).
                out["req_base_mold"][src] = base_mold[src]
                out["req_base_pend"][src] = base_pend[src]
                out["req_base_epoch"][src] = base_epoch[src]
            for k in range(n_ship):
                out["ent_term"][src, k] = log_term[src, (ws + k) % cap]
                out["ent_val"][src, k] = log_val[src, (ws + k) % cap]
                if track:
                    out["ent_tick"][src, k] = log_tick[src, (ws + k) % cap]
                if rcf:
                    out["ent_cfg"][src, k] = log_cfg[src, (ws + k) % cap]
            for dst in range(n):
                if dst == src:
                    continue
                # Per-edge offset j = prev - ws, with prev clamped into [ws, ws+E]
                # (a peer ahead of the window gets a heartbeat over an older prefix;
                # an unresponsive laggard's prev is lifted to the window start); a
                # peer whose prev fell below the base gets the snapshot sentinel.
                p = prev_of(dst)
                if p < b:
                    out["req_off"][src, dst] = -1
                else:
                    out["req_off"][src, dst] = min(max(p, ws), ws + e) - ws
            if xfr and xfer_to[src] != NIL:
                # TimeoutNow fire (raft_batched.py phase 8): replaces the heartbeat
                # slot once the target matched the leader's log; the AE
                # window fields above stay populated (receivers gate on
                # req_type == REQ_APPEND).
                t = int(xfer_to[src])
                caught = (not cfg.xfer_election) or int(
                    match_index[src, t]
                ) >= int(log_len[src])
                if caught:
                    out["req_type"][src] = REQ_TIMEOUT_NOW
                    out["xfer_tgt"][src] = t
    if xfr and (rcf or rdl):
        # Disruptive-RequestVote override flag (thesis 3.10/4.2.3): set on
        # transfer-triggered election broadcasts so heard-recent voters
        # still process them. Written only when a denial gate can read it.
        for src in range(n):
            if xe[src]:
                out["req_disrupt"][src] = 1
    # Responses travel back src<->dst: responder r answers requester q; the edge
    # plane carries only the type, payloads ride the per-responder fields above.
    for r in range(n):
        for q in range(n):
            rtype = 0
            if vr_out[r, q]:
                rtype += RESP_VOTE
            if ar_out[r, q]:
                rtype += RESP_APPEND
            if pv_out[r, q]:
                rtype += RESP_PREVOTE
                # The grant bit rides the (packed) pv_grant plane, not the kind.
                out["pv_grant"][q, r] = bool(pv_grant[r, q])
            out["resp_kind"][q, r] = rtype
    if dur and cfg.durable_acks:
        # Late vote-completion response (phase 7.5): the flush that made this
        # voter's grant durable emits the RESP_VOTE the grant tick withheld --
        # toward the recorded candidate, only where the edge carries no
        # response already (models/raft_batched.py for the AE-collision argument).
        for r in range(n):
            if late_grant[r]:
                q = int(voted_for[r])
                if out["resp_kind"][q, r] == 0:
                    out["resp_kind"][q, r] = RESP_VOTE

    # Monotone commit-latency frontier (types.ClusterState.lat_frontier):
    # measurement state maintained only under client workloads, deduping the
    # latency metric against the highest commit any node ever reached.
    lat_frontier = int(s["lat_frontier"])
    if track:
        lat_frontier = max(lat_frontier, int(commit.max()))

    # ---- end-of-tick config derivation (log-carried membership): each
    # node's effective configuration recomputed from its post-append,
    # post-compaction log prefix (models/cfglog.py `derive`, scalar form).
    # Apply-on-append and roll-back-on-truncation are the SAME recompute.
    if rcf:
        for d in range(n):
            b = int(log_base[d])
            horizon = (
                int(log_len[d]) if cfg.act_on_append
                # TEST-ONLY act-on-commit mutant: the COMMITTED prefix only.
                else min(int(commit[d]), int(log_len[d]))
            )
            entries = [
                (a, int(log_cfg[d, (a - 1) % cap]))
                for a in range(b + 1, horizon + 1)
                if int(log_cfg[d, (a - 1) % cap]) != 0
            ]
            m_old = base_mold[d].copy()
            for _, code in entries:
                if code < 0 or not cfg.joint_consensus:
                    v = abs(code) - 1  # final toggles fold into C_old
                    m_old[v] = not m_old[v]
            d_epoch = int(base_epoch[d]) + len(entries)
            if cfg.joint_consensus:
                if entries:
                    hi, pend_code = entries[-1]
                else:
                    # No live entry: the snapshot context rules (a pending
                    # joint entry may sit at or below base).
                    hi, pend_code = max(b, 1), int(base_pend[d])
                if pend_code > 0:
                    m_new = m_old.copy()
                    m_new[pend_code - 1] = not m_new[pend_code - 1]
                    d_pend = hi
                else:
                    m_new = m_old.copy()
                    d_pend = 0
            else:
                m_new = m_old.copy()
                d_pend = 0
            d_hi = max(entries[-1][0] if entries else 0, b)
            if not cfg.truncation_rollback and d_epoch < int(cfg_epoch[d]):
                # TEST-ONLY ignore-truncation-rollback mutant: where the
                # prefix LOST config entries, keep acting on the stale
                # derived configuration (the demote check below still runs
                # on the stale masks, mirroring the kernel).
                m_old = member_old[d].copy()  # tick-start: untouched so far
                m_new = member_new[d].copy()
                d_pend = int(cfg_pend[d])
                d_epoch = int(cfg_epoch[d])
            member_old[d] = m_old
            member_new[d] = m_new
            cfg_pend[d] = d_pend
            cfg_epoch[d] = d_epoch
            # Removed-server stepdown (thesis 4.3): a leader whose own
            # config union excludes it leads on until the removing entry
            # commits on it; candidacies of removed nodes die immediately.
            self_in = bool(m_old[d] or m_new[d])
            is_cand = role[d] in (CANDIDATE, PRECANDIDATE)
            if not self_in and (
                (role[d] == LEADER and int(commit[d]) >= d_hi) or is_cand
            ):
                role[d] = FOLLOWER
                leader_id[d] = NIL

    return {
        "role": role,
        "term": term,
        "voted_for": voted_for,
        "leader_id": leader_id,
        "votes": votes,
        "next_index": next_index,
        "match_index": match_index,
        "ack_age": ack_age,
        "commit_index": commit,
        "commit_chk": commit_chk,
        "log_base": log_base,
        "base_term": base_term,
        "base_chk": base_chk,
        "log_term": log_term,
        "log_val": log_val,
        "log_tick": log_tick,
        "log_len": log_len,
        "dur_len": dur_len,
        "dur_term": dur_term,
        "dur_vote": dur_vote,
        "clock": clock,
        "deadline": deadline,
        "heard_clock": heard_clock,
        "member_old": member_old,
        "member_new": member_new,
        "cfg_epoch": cfg_epoch,
        "cfg_pend": cfg_pend,
        "log_cfg": log_cfg,
        "base_mold": base_mold,
        "base_pend": base_pend,
        "base_epoch": base_epoch,
        "xfer_to": xfer_to,
        "read_idx": read_idx,
        "read_tick": read_tick,
        "read_acks": read_acks,
        "read_fr": read_fr,
        "client_pend": np.asarray(client_pend, np.int32),
        "client_dst": np.asarray(client_dst, np.int32),
        "client_tick": np.asarray(client_tick, np.int32),
        "lat_frontier": np.int32(lat_frontier),
        "now": np.int32(int(s["now"]) + 1),
        "mailbox": out,
    }
