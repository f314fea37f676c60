"""Seconds from process start to the window's start: imports, boot,
attach or fleet construction, warm-up and every compile."""


def read(run):
    return run.setup_s
