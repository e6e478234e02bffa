"""Seconds from process start to the window's opening: imports, weights,
pool, compiling or loading every step shape, and the traffic warm-up."""


def read(run):
    return run.setup_s
