"""Output tokens delivered to clients in the window, per second of the window.

Every token counts whose ``on_token`` time falls in the window, whichever
request it belongs to; the window's length is ``--seconds``."""


def read(run):
    n = sum(run.t_open <= t < run.t_close for ts in run.rec.tokens.values() for t in ts)
    return n / run.seconds
