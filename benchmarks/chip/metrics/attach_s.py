"""Seconds per attach: from the window's start to the end of the last
attach completed inside it, over the attaches completed."""


def read(run):
    units = run.in_window()
    if not units:
        return None
    return (units[-1][1] - run.t_window) / len(units)
