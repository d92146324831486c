"""serve.dispatch_ms: mean host milliseconds of the program's
`serve.dispatch` spans (`ServeSession._dispatch`: the offer planes' upload
and the `_serve_chunk` enqueue) that start inside the loaded chunks of the
traced run. Moves serve_ops_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.steady_mean_ms(out, "serve.dispatch")
