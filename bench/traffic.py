"""One general generator for every traffic mix under ``bench/traffic/``.

A mix file holds parameters only: the loop (open or closed), clients or an
arrival rate, and the distributions of prompt and output lengths with their
clips. Sizes and arrival times are stratified draws: the quantiles
``(i + 0.5) / n`` of each distribution, shuffled by the mix's own
``trace_seed``. So every run replays the same set of sizes and arrivals.
The run's ``--seed`` draws the token ids and, in a closed loop, which client
holds which sequence of requests; neither changes the work. Seeds that
changed the sizes moved the tail of a 51 s window by 35-45% from seed to
seed (PERF.md), far more than two runs of one seed differ.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Req:
    rid: int
    prompt: np.ndarray            # (P,) int32 token ids
    max_new: int
    client: int = -1              # closed loop: the client that sends it
    due: Optional[float] = None   # open loop: seconds after the schedule starts


@dataclass
class Traffic:
    loop: str                     # "open" | "closed"
    schedule: List[Req]           # open loop, by due time
    clients: List[List[Req]]      # closed loop, each client's requests in order
    preroll_s: float              # open loop: schedule run before the window
    admit_each: int               # closed loop: requests per client before the window

    @property
    def requests(self) -> List[Req]:
        return self.schedule or [r for c in self.clients for r in c]


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length or gap distribution."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        return spec["median"] * np.exp(spec["sigma"] * z)
    if spec["dist"] == "gamma":
        from scipy.stats import gamma

        shape = 1.0 / spec["cv"] ** 2
        return gamma.ppf(q, shape, scale=spec["mean"] / shape)
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    x = np.clip(np.rint(quantiles(spec, n)), spec["min"], spec["max"]).astype(int)
    return rng.permutation(x)


def rate(mix: dict) -> float:
    """Offered requests per second of an open-loop mix."""
    arr = mix["arrivals"]
    if arr.get("rate_req_per_s") is not None:
        return float(arr["rate_req_per_s"])
    if arr.get("knee_req_per_s") is None:
        raise ValueError("open-loop mix has neither a rate nor a knee")
    return float(arr["rate_share_of_knee"]) * float(arr["knee_req_per_s"])


def max_total(mix: dict) -> int:
    """The largest prompt plus output a request of the mix can have."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def build(mix: dict, seed: int, seconds: float, vocab: int, max_len: int) -> Traffic:
    """The requests of one run of ``mix``: sizes from the mix, tokens from ``seed``."""
    if max_total(mix) > max_len:
        raise ValueError(f"prompt + output can reach {max_total(mix)} > max_len {max_len}")
    fixed = np.random.default_rng(int(mix["trace_seed"]))
    tokens = np.random.default_rng(seed)
    pre = mix.get("preroll", {})
    if mix["loop"] == "closed":
        k, n_clients = int(mix["requests_per_client"]), int(mix["clients"])
        n = k * n_clients
        p, o = lengths(mix["prompt"], n, fixed), lengths(mix["output"], n, fixed)
        seqs = [[(int(p[c * k + j]), int(o[c * k + j])) for j in range(k)]
                for c in range(n_clients)]
        order = tokens.permutation(n_clients)
        clients, rid = [], 0
        for c in range(n_clients):
            reqs = []
            for plen, olen in seqs[order[c]]:
                prompt = tokens.integers(1, vocab, plen).astype(np.int32)
                reqs.append(Req(rid, prompt, olen, client=c))
                rid += 1
            clients.append(reqs)
        return Traffic("closed", [], clients, 0.0, int(pre.get("admit_each_client", 1)))
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    r = rate(mix)
    preroll = float(pre.get("schedule_s", 0.0))
    horizon = preroll + seconds
    n = max(1, round(horizon * r))
    gaps = fixed.permutation(quantiles(dict(mix["arrivals"], mean=1.0 / r), n))
    # Stretch the gaps so that exactly n arrivals fall in the horizon: every
    # run then offers the mix's rate, whatever the far tail of the gaps.
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * horizon / gaps.sum()
    p, o = lengths(mix["prompt"], n, fixed), lengths(mix["output"], n, fixed)
    schedule = [Req(i, tokens.integers(1, vocab, int(p[i])).astype(np.int32), int(o[i]),
                    due=float(due[i])) for i in range(n)]
    return Traffic("open", schedule, [], preroll, 0)
