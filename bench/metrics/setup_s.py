"""Seconds from the start of the process to the opening of the window:
start-up, weights, compiling or loading the step, and the pre-roll."""


def read(run):
    return run.setup_s
