"""95th percentile of the gaps between consecutive tokens of one request.

Every gap counts whose later token lands in the window, on the host clock of
``on_token``; a gap that spans another request's admission counts in full.
None where the window saw no gap."""

import numpy as np


def read(run):
    gaps = [b - a for ts in run.rec.tokens.values() for a, b in zip(ts, ts[1:])
            if run.t_open <= b < run.t_close]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
