"""Pure-jnp decode attention over a KV cache (also the CPU/XLA path).

GQA is computed with grouped einsums — q reshaped to (B, KV, G, hd) —
rather than ``jnp.repeat`` of the cache: repeating would materialize a
group-times-larger copy of the (possibly 32k-token, sequence-sharded)
cache and force the SPMD partitioner to reshard it. Operands stay in
their storage dtype (no f32 cache copies); dots accumulate in f32.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def decode_attention_ref(
    q: jnp.ndarray,          # (B, H, D) — one new token per sequence
    k: jnp.ndarray,          # (B, KVH, S, D) — cache (padded to S)
    v: jnp.ndarray,          # (B, KVH, S, D)
    lengths: jnp.ndarray,    # (B,) int32 — valid cache entries per sequence
    *,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    group = h // kvh
    scale = sm_scale if sm_scale is not None else d ** -0.5

    qg = q.reshape(b, kvh, group, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s)[None, :]
    mask = pos < lengths[:, None]
    mask4 = mask[:, None, None, :]
    scores = jnp.where(mask4, scores, -jnp.inf)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = jnp.where(mask4, probs, 0.0)
    probs = probs / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d).astype(q.dtype)
