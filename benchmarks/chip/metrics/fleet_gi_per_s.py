"""Guest-intervals completed per second: every guest-interval that ended
inside the window, over the time from the window's start to the end of
the last of them."""


def read(run):
    units = run.in_window()
    if not units:
        return None
    return sum(u[2] for u in units) / (units[-1][1] - run.t_window)
