"""Protocol trace plane: device-side Raft event histories, whole-history
safety checking, and transition coverage (the eighth subsystem).

PR 2's telemetry answers "how is the fleet doing" with window counters and a
violation-frozen flight recorder; this package answers "WHAT HAPPENED" with a
Jepsen-style checkable history. Three load-bearing pieces:

  events.py   device-side event extraction: a compact per-cluster protocol
              event stream (role transitions, term bumps, votes, commit
              advances, log appends/truncations, crash/restart/drop/partition
              fault events) derived from state deltas the kernel already
              computes -- the extraction never touches the trajectory.
  ring.py     the bounded per-cluster event buffer carried in the telemetry
              scan and drained every window (generalizing sim/telemetry.py's
              violation-frozen flight recorder into an always-recordable,
              trigger-armable stream), plus the packed transition-coverage
              bitmap (role x kind and kind -> kind adjacency, ops/bitplane).
  history.py  host-side reconstruction of per-cluster timelines from the
  checker.py  exported windows, and the whole-history checker verifying the
              five Raft safety properties (Election Safety, Leader
              Append-Only, Log Matching, Leader Completeness, State Machine
              Safety) over the COMPLETE history -- with named properties and
              minimal witnesses on rejection, and an explicit
              incomplete-history verdict instead of a vacuous pass.

Everything is gated by `cfg.track_trace` with the zero-cost-when-off contract
(utils/config.py): disabled, no compiled program carries a trace leg.
docs/OBSERVABILITY.md "Protocol traces" has the schema and sizing guidance.
"""

from raft_sim_tpu.trace.events import KIND_NAMES, KINDS, N_KINDS
from raft_sim_tpu.trace.ring import TracePersist, TraceSpec, TraceWin

__all__ = [
    "KINDS",
    "KIND_NAMES",
    "N_KINDS",
    "TraceSpec",
    "TraceWin",
    "TracePersist",
]
