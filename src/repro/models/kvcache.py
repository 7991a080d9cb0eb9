"""KV / recurrent-state caches: definitions, update, decode attention.

Cache sharding prefers kv-head sharding over the model axis and falls
back to head_dim sharding when the head count does not divide the axis
(e.g. llama3's 8 kv heads on a 16-way model axis shard head_dim 128 ->
8 per device), keeping the 32k-token cache within per-chip HBM.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import decode_attention
from .layers import ParamDef, rope, shard
from .scopes import ATTN, KV_CACHE_UPDATE


def attn_cache_defs(cfg: ModelConfig, batch: int, max_len: int,
                    kv: Optional[int] = None) -> Dict[str, ParamDef]:
    kvh = kv if kv is not None else cfg.n_kv_heads
    shape = (batch, kvh, max_len, cfg.head_dim)
    # kv-head sharding when it divides the model axis; otherwise shard the
    # cache LENGTH (flash-decode style): scores stay sequence-sharded and
    # only tiny (B,H) softmax stats + (B,H,hd) partial outputs cross chips.
    logical = ("batch", "cache_kv_heads", "cache_seq", None)
    return {
        "k": ParamDef(shape, logical, init="zeros"),
        "v": ParamDef(shape, logical, init="zeros"),
    }


@jax.named_scope(KV_CACHE_UPDATE)
def update_cache(cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray,
                 lengths: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Insert one token per sequence at position lengths[b].

    cache: (B, KV, S, hd); new: (B, 1, KV, hd); lengths: (B,).
    Implemented as a one-hot scatter (SPMD-friendly: no gather/scatter
    ops that would force resharding of the 32k cache)."""
    S = cache_k.shape[2]
    onehot = jax.nn.one_hot(lengths, S, dtype=cache_k.dtype)        # (B, S)
    k_b = k_new.swapaxes(1, 2)                                       # (B, KV, 1, hd)
    v_b = v_new.swapaxes(1, 2)
    sel = onehot[:, None, :, None]                                   # (B, 1, S, 1)
    cache_k = cache_k * (1 - sel) + sel * k_b
    cache_v = cache_v * (1 - sel) + sel * v_b
    return cache_k, cache_v


@jax.named_scope(ATTN)
def decode_attention_step(
    cfg: ModelConfig,
    p: Dict[str, jnp.ndarray],
    cache_l: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                      # (B, 1, D) normed input
    lengths: jnp.ndarray,                # (B,)
    *,
    window: Optional[int] = None,
    use_rope: bool = True,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """GQA attention for one new token against the cache."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])                      # (B, 1, H, hd)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if use_rope:
        pos = lengths[:, None]
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)

    ck, cv = update_cache(cache_l["k"], cache_l["v"], k, v, lengths)
    ck = shard(ck, "batch", "cache_kv_heads", "cache_seq", None)
    cv = shard(cv, "batch", "cache_kv_heads", "cache_seq", None)

    # replicate the (tiny) single-token q across the model axis so the
    # score einsum keeps the (huge) cache sequence-sharded in place.
    q_rep = shard(q[:, 0], "batch", None, None)
    out = decode_attention(
        q_rep,                                                       # (B, H, hd)
        ck, cv, lengths + 1, window=window,
    )                                                                # (B, H, hd)
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return shard(out, "batch", "seq", "embed"), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Windowed (ring-buffer) cache for local attention (griffin)
# ---------------------------------------------------------------------------


def ring_cache_defs(cfg: ModelConfig, batch: int, window: int) -> Dict[str, ParamDef]:
    kvh = cfg.n_kv_heads
    shape = (batch, kvh, window, cfg.head_dim)
    logical = ("batch", "cache_kv_heads", "cache_seq", None)
    return {
        "k": ParamDef(shape, logical, init="zeros"),
        "v": ParamDef(shape, logical, init="zeros"),
    }


def ring_decode_attention_step(
    cfg: ModelConfig,
    p: Dict[str, jnp.ndarray],
    cache_l: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    lengths: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Local attention with a fixed ``window``-slot ring buffer.

    Keys are roped at their *absolute* position before storage; attention
    over a set of (k, v) is permutation-invariant, so slot order never
    matters and the buffer stays O(window) for 500k-token decodes."""
    window = cache_l["k"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    pos = lengths[:, None]
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    slots = lengths % window
    ck, cv = update_cache(cache_l["k"], cache_l["v"], k, v, slots)
    ck = shard(ck, "batch", "cache_kv_heads", "cache_seq", None)
    cv = shard(cv, "batch", "cache_kv_heads", "cache_seq", None)
    valid = jnp.minimum(lengths + 1, window)
    q_rep = shard(q[:, 0], "batch", None, None)
    out = decode_attention(q_rep, ck, cv, valid)
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return shard(out, "batch", "seq", "embed"), {"k": ck, "v": cv}
