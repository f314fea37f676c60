"""Blocking read-backs of engine latencies (counter ``device_syncs``) per
monitoring interval completed inside the window."""


def read(run):
    vals = run.per_unit.get("device_syncs", [])
    n = run.n_units
    if not n or len(vals) < n:
        return None
    return (vals[n - 1] - run.counters["device_syncs_at_open"]) / n
