"""Probe dispatches (``host_model.probe_dispatch_count``) per
guest-interval completed inside the window."""


def read(run):
    units = run.in_window()
    if not units:
        return None
    done = run.per_unit["dispatch_count"][len(units) - 1]
    return (done - run.counters["dispatches_at_open"]) / sum(
        u[2] for u in units)
