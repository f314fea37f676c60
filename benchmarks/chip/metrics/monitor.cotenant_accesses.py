"""Co-tenant accesses the monitor's ``Wait``s issued (counter
``cotenant_accesses``) per monitoring interval completed inside the
window; nothing where the program does not count them."""


def read(run):
    vals = run.per_unit.get("cotenant_accesses", [])
    n = run.n_units
    if not n or len(vals) < n:
        return None
    return (vals[n - 1] - run.counters["cotenant_accesses_at_open"]) / n
