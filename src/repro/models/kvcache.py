"""KV / recurrent-state caches: definitions, update, decode attention.

Cache sharding prefers kv-head sharding over the model axis and falls
back to head_dim sharding when the head count does not divide the axis
(e.g. llama3's 8 kv heads on a 16-way model axis shard head_dim 128 ->
8 per device), keeping the 32k-token cache within per-chip HBM.

A decode step touches the cache only to write each sequence's new token
in place and to read it in attention. The transformer's layer scan
carries the whole stacked cache, (L, B, KV, S, hd), and hands each layer
its index: the write and the decode kernel both address that layer
inside the stack, so on a donated cache no step copies or rewrites a
cache-sized buffer. A sliding-window layer keeps a ring of its last W
positions in a stack of its own beside the full layers' stack, and is
written and read in place the same way (``decode_attention_step``'s
``ring``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import decode_attention
from ..kernels.decode_attention.kernel import block_for, last_filled_block
from .layers import ParamDef, rope, shard, yarn
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
                 positions: jnp.ndarray,
                 layer: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one token per sequence in place at position positions[b].

    cache: (B, KV, S, hd), or (L, B, KV, S, hd) with ``layer`` the layer
    written; new: (B, 1, KV, hd); positions: (B,). A position outside
    [0, S) writes nothing: idle slots count on past the cache's end.
    One scatter per operand moves B x KV x hd elements, which XLA does in
    place on a buffer nothing else holds; on a sequence-sharded cache the
    SPMD partitioner moves only the indices and the new token."""
    return (_write_token(cache_k, k_new[:, 0], positions, layer),
            _write_token(cache_v, v_new[:, 0], positions, layer))


def _write_token(cache: jnp.ndarray, new: jnp.ndarray, positions: jnp.ndarray,
                 layer: Optional[jnp.ndarray]) -> jnp.ndarray:
    """cache[(layer,) b, h, positions[b], :] = new[b, h] for each b and h.

    new: (B, KV, hd). Each update is one hd-long row, the cache's minor
    dimension, so the scatter asks for no layout but the cache's own."""
    b, kvh, _ = new.shape
    bb, hh = jnp.meshgrid(jnp.arange(b, dtype=jnp.int32),
                          jnp.arange(kvh, dtype=jnp.int32), indexing="ij")
    rows = [bb, hh, jnp.broadcast_to(positions.astype(jnp.int32)[:, None], (b, kvh))]
    if layer is not None:
        rows.insert(0, jnp.full((b, kvh), layer, jnp.int32))
    dims = tuple(range(cache.ndim - 1))
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(2,), inserted_window_dims=dims,
        scatter_dims_to_operand_dims=dims)
    return jax.lax.scatter(
        cache, jnp.stack(rows, axis=-1), new.astype(cache.dtype), dnums,
        indices_are_sorted=True, unique_indices=True,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _shard_cache(c: jnp.ndarray) -> jnp.ndarray:
    stacked = ("layers",) if c.ndim == 5 else ()
    return shard(c, *stacked, "batch", "cache_kv_heads", "cache_seq", None)


@jax.named_scope(ATTN)
def decode_attention_step(
    cfg: ModelConfig,
    p: Dict[str, jnp.ndarray],
    cache_l: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                      # (B, 1, D) normed input
    lengths: jnp.ndarray,                # (B,)
    layer: Optional[jnp.ndarray] = None,
    *,
    ring: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """GQA attention for one new token against the cache.

    ``cache_l`` holds one layer's k and v, (B, KV, S, hd), or with
    ``layer`` the whole stack, (L, B, KV, S, hd), of which that layer is
    written and read; the cache returned has the shape it was given.

    With ``ring`` the cache is a sliding-window layer's ring of its last W
    positions, and ``ring`` is the length of the sequences' full cache:
    position n lives in slot n % W, the kernel reads the min(n + 1, W)
    valid slots (attention over a set of keys is blind to their order, and
    keys are roped at their absolute positions), and a slot at or past
    ``ring`` is idle and writes nothing. Such layers take plain RoPE; the
    full-attention layers take ``layers.yarn(cfg)``."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])                      # (B, 1, H, hd)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    pos = lengths[:, None]
    scaled = None if ring is not None else yarn(cfg)
    q = rope(q, pos, cfg.rope_theta, scaled)
    k = rope(k, pos, cfg.rope_theta, scaled)

    w = cache_l["k"].shape[-2]
    slots = lengths if ring is None else jnp.where(lengths < ring, lengths % w, w)  # w: dropped
    ck, cv = update_cache(cache_l["k"], cache_l["v"], k, v, slots, layer)
    ck, cv = _shard_cache(ck), _shard_cache(cv)

    # replicate the (tiny) single-token q across the model axis so the
    # score einsum keeps the (huge) cache sequence-sharded in place.
    q_rep = shard(q[:, 0], "batch", None, None)
    out = decode_attention(
        q_rep,                                                       # (B, H, hd)
        ck, cv, valid_entries(lengths, w), layer,
    )                                                                # (B, H, hd)
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return shard(out, "batch", "seq", "embed"), {"k": ck, "v": cv}


def valid_entries(lengths: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    """The entries a decode step at ``lengths`` attends in a cache of
    ``cache_len`` positions: the slot's n + 1, its new token's included, up
    to the cache's end, so min(n + 1, W) in a ring of W."""
    return jnp.minimum(lengths + 1, cache_len)


def kv_blocks(cache: Any, lengths: jnp.ndarray) -> jnp.ndarray:
    """(blocks the decode kernel fetches, blocks the caches hold) in one
    decode step at ``lengths``, (2,) int32, summed over every attention
    cache in ``cache``: each ``k`` leaf, one layer's (B, KV, S, hd) or a
    stack of layers', (L, B, KV, S, hd). A count worked out from the
    kernel's own block (``block_for``) and clamp (``last_filled_block``)
    over the entries the step attends (``valid_entries``), not an
    observation of the kernel's DMAs."""
    total = jnp.zeros((2,), jnp.int32)
    for path, k in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if path[-1] != jax.tree_util.DictKey("k"):
            continue
        n_layers = k.shape[0] if k.ndim == 5 else 1
        kvh, s, hd = k.shape[-3:]
        bk = block_for(s, kvh, hd, k.dtype.itemsize)
        read = last_filled_block(valid_entries(lengths, s), bk, s // bk) + 1
        total += n_layers * jnp.stack([read.sum(), lengths.shape[0] * (s // bk)])
    return total
