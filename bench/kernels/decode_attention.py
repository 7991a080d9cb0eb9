"""Work that one call of the Pallas ``decode_attention`` kernel needs.

One query token per sequence attends its sequence's valid cache entries:
``lengths[b]`` keys and values per kv head. Per sequence, the algorithm
needs q (H x d), K and V at the valid length (2 x KVH x L x d) and writes
the output (H x d); it computes q.K and p.V, 2 x H x L x d multiply-adds.
Padding beyond the valid length is not needed, and a sequence whose output
is thrown away (an idle slot, or a slot other than the one being
prefilled) needs nothing.

At these shapes the bytes bound the call: d x H x L multiply-adds per
2 x KVH x L x d x 2 bytes is GQA-group / 2 operations per byte, far below
the chip's 240 operations per byte. The share of the roofline is therefore
``bytes / peak_bytes_per_s / kernel_time``.
"""

from __future__ import annotations

from typing import Iterable, Tuple

NAME = "decode_attention"


def cost(lengths: Iterable[int], n_heads: int, n_kv_heads: int, head_dim: int,
         itemsize: int = 2) -> Tuple[int, int]:
    """(operations, bytes) of one call over the useful sequences' lengths."""
    ops = nbytes = 0
    for length in lengths:
        ops += 4 * n_heads * length * head_dim
        nbytes += itemsize * (2 * n_kv_heads * length * head_dim + 2 * n_heads * head_dim)
    return ops, nbytes
