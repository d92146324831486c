"""tick.replicate.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops under
the `tick.replicate` named scope (phases 3 (AppendEntries requests, conflict truncation, snapshot install) and 4 (responses)), in the traced window, per cluster-tick
it ran. Ops are mapped to phases by the optimized HLO's op_name metadata
(benchmark/programtrace.py). Moves cluster_ticks_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "replicate")
