"""95th percentile of the time to first token over every request due in the window.

Each request is timed from when it was due, not from when it was sent, so
a late generator or a stall counts. A request with no first token when
the window closes counts as the window's end less its due time."""

import numpy as np


def read(run):
    rec = run.rec
    ttft = []
    for rid, due in rec.due.items():
        if not run.t_open <= due < run.t_close:
            continue
        ts = rec.tokens.get(rid)
        first = ts[0] if ts and ts[0] < run.t_close else run.t_close
        ttft.append(first - due)
    return float(np.percentile(ttft, 95)) if ttft else None
