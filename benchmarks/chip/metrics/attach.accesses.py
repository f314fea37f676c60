"""Simulated accesses per attach (``GuestVM.stat_accesses``)."""


def read(run):
    vals = run.per_unit.get("accesses", [])[: run.n_units]
    return sum(vals) / len(vals) if vals else None
