"""Model operations that a served token needs, for the whole step's MFU.

A token needs two operations per weight of every matrix product it goes
through, and the attention over its context: q.K and p.V, 4 x H x d
operations per position attended, in every layer. A token that is served
goes through the unembedding too; a prompt token fed through the decode
step does not need it, since its logits are thrown away. The embedding
lookup is a gather and is not counted.
"""

from __future__ import annotations

from typing import Dict


def layer_matmul_weights(cfg: Dict) -> int:
    d, h, kv, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def token_flops(cfg: Dict, context: int, served: bool) -> int:
    """Operations for one token that attends ``context`` positions."""
    per_layer = 2 * layer_matmul_weights(cfg) + 4 * cfg["n_heads"] * cfg["head_dim"] * context
    unembed = 2 * cfg["d_model"] * cfg["vocab_size"] if served else 0
    return cfg["n_layers"] * per_layer + unembed
