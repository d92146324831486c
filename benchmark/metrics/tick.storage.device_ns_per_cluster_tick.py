"""tick.storage.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops
under the `tick.storage` named scope (the durable storage plane: crash
recovery to the fsynced prefix, the durable watermark's truncation clamp, the
fsync flush, the section-3.8 ack and vote gates, and the late vote
responses), in the traced window, per cluster-tick it ran. Ops are mapped to
phases by the optimized HLO's op_name metadata (benchmark/programtrace.py).
Moves cluster_ticks_per_s.

A program whose tick has no `storage` phase (one that books the plane under
`tick.deliver`, `tick.replicate` and `tick.outbox`) reads as nothing."""

from benchmark import programtrace


def read(run, out):
    if "storage" not in (programtrace.tick_phases() or ()):
        return None
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "storage")
