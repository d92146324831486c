"""device.idle_unattributed_share.serve: share of the traced steady span
(the loaded chunks) in which no operation ran on the device and no program
host span (`serve.*`, `jax.*`, ...) was open, in a served cell. Prints the
idle seconds under each program span. Moves serve_ops_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.idle_unattributed_share(run, out)
