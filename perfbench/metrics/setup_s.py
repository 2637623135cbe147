"""Seconds from the start of the process to the opening of the window:
imports, weights, the pool, compiling or loading the step programs, the
ticks before an offline window and the lead-in of an open-loop one."""


def read(run):
    return run.setup_s
