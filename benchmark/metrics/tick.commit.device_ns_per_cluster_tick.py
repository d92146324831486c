"""tick.commit.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops under
the `tick.commit` named scope (phases 5 (leader commit, configuration-masked quorum, ReadIndex) and 5.2 (reconfiguration), offer->commit latency), in the traced window, per cluster-tick
it ran. Ops are mapped to phases by the optimized HLO's op_name metadata
(benchmark/programtrace.py). Moves cluster_ticks_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "commit")
