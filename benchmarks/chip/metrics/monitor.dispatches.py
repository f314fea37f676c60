"""Probe dispatches (``host_model.probe_dispatch_count``) per monitoring
interval completed inside the window."""


def read(run):
    vals = run.per_unit.get("probe_dispatches", [])
    n = run.n_units
    if not n or len(vals) < n:
        return None
    return (vals[n - 1] - run.counters["probe_dispatches_at_open"]) / n
