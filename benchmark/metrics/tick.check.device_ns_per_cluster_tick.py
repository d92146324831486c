"""tick.check.device_ns_per_cluster_tick: device self-time of the fleet chunk program's ops under
the `tick.check` named scope (the invariants and StepInfo (_step_info_b) and the metric accumulation (scan._accumulate)), in the traced window, per cluster-tick
it ran. Ops are mapped to phases by the optimized HLO's op_name metadata
(benchmark/programtrace.py). Moves cluster_ticks_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.tick_phase_ns_per_cluster_tick(run, out, "check")
