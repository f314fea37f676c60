"""Device ms of the cache engines (the ``access_stream*`` programs) per
monitoring interval, from the traced window."""

from benchmarks.chip import devtrace


def read(run):
    return devtrace.engine_ms_per_unit(run)
