"""Batched engine passes per attach (``GuestVM.stat_passes``)."""


def read(run):
    vals = run.per_unit.get("dispatches", [])[: run.n_units]
    return sum(vals) / len(vals) if vals else None
