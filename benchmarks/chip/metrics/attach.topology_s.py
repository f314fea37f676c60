"""Seconds per attach spent in ``session.topology()`` (the VEV stage),
from the harness's spans over the attaches completed in the window."""


def read(run):
    n = run.n_units
    spans = run.spans.get("topology", [])[:n]
    if not n or len(spans) < n:
        return None
    return sum(b - a for a, b in spans) / n
