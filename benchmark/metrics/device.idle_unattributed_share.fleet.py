"""device.idle_unattributed_share.fleet: share of the traced window in
which no operation ran on the device and no program host span (obs.span:
`fleet.*`, `jax.*`, ...) was open, in a fleet cell; the idle time the
program cannot name. Prints the idle seconds under each program span.
Moves cluster_ticks_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.idle_unattributed_share(run, out)
