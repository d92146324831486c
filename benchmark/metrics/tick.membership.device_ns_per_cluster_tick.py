"""tick.membership.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops
under the `tick.membership` named scope (the end-of-tick configuration
derivation from each node's log, the removed-server stepdown, and the
compaction fold of config entries into the snapshot context), in the traced
window, per cluster-tick it ran. Ops are mapped to phases by the optimized
HLO's op_name metadata (benchmark/programtrace.py). Moves
cluster_ticks_per_s.

A program whose tick has no `membership` phase (one that books the
derivation under `tick.outbox` and `tick.compact`) reads as nothing."""

from benchmark import programtrace


def read(run, out):
    if "membership" not in (programtrace.tick_phases() or ()):
        return None
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "membership")
