"""serve.drain_ms: mean host milliseconds of the program's `serve.drain`
spans (`ServeSession.serve`: `DeltaStream.finish_rounds`, the device->host
fetch of the chunk's extraction rounds and the delta rows built from them)
that start inside the loaded chunks of the traced run. The step sits between
one chunk's sync and the next dispatch, with the device idle. Moves
serve_ops_per_s."""

from benchmark import programtrace


def read(run, out):
    return programtrace.steady_mean_ms(out, "serve.drain")
