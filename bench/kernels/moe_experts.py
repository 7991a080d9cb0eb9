"""Work that the held experts of an expert layer need in one serve-step call.

A held expert that at least one live token routed to is read once per MoE
layer call: its three matrices, gate and up (d x f) and down (f x d), in
bf16. Each (token, held expert) pair needs those three products for one
token, 2 x 3 x d x f operations. A held expert that no live token routed to
is not needed, nor are the experts held elsewhere; the program's counters
(``EngineStats.experts_touched`` and ``expert_pairs``) sum both quantities
over the layers and the decode steps.

At a few tokens an expert the bytes bound the work: pairs / touched
operations per byte, far below the chip's 240. The least time is still
taken as the larger of the two bounds.
"""

from __future__ import annotations

from typing import Tuple

SCOPE = "moe.experts"          # the named scope of the held experts' MLPs


def cost(pairs: int, touched: int, d_model: int, d_ff: int, itemsize: int = 2) -> Tuple[int, int]:
    """(operations, bytes) of ``pairs`` (token, held expert) pairs over
    ``touched`` held expert reads."""
    per_expert = 3 * d_model * d_ff
    return 2 * per_expert * pairs, itemsize * per_expert * touched
