"""Device time of one execution of the serve step, in the traced window.

The step is the program that ``ServingEngine`` jits from
``serve/decode.py``'s ``serve_step``; XLA names its module after it."""

MODULE = "jit_serve_step"


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.module(MODULE)
    return 1e3 * sec / n if n else None
