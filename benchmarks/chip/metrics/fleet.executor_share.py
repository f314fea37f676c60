"""Share (%) of the window's completed work spent inside the lockstep
executor (``probeplan.execute_many``), from the harness's spans."""


def read(run):
    units = run.in_window()
    if not units:
        return None
    end = units[-1][1]
    inside = sum(min(b, end) - a for a, b in run.spans.get("execute_many", [])
                 if a < end)
    return 100.0 * inside / (end - run.t_window)
