"""tick.unscoped.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops under
no `tick.<phase>` scope (chunk-program ops under no tick scope: the scan loop itself (its while op's self time), carry copies, the batch-minor transposes at entry and exit), in the traced window, per cluster-tick
it ran. Ops are mapped to phases by the optimized HLO's op_name metadata
(benchmark/programtrace.py). Moves cluster_ticks_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "unscoped")
