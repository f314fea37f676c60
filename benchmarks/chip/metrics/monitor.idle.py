"""Share (%) of the traced window in which no operation ran on the
device."""

from benchmarks.chip import devtrace


def read(run):
    return devtrace.idle_percent(run)
