"""Pallas TPU flash-decode kernel: one query token vs. a long KV cache.

Decode attention is *memory-bound*: the cache streams through VMEM once
per step while compute is a rank-1 product, so the kernel's time is the
cache bytes it fetches. It fetches only the blocks that hold positions a
slot attends. The grid is (B, S / block_k), sequential over the cache,
and one step carries all of a slot's kv heads: a (KVH, block_k, d) block
of k and of v, with the online-softmax state of every q head of the slot
in VMEM scratch. The k/v index maps clamp block ``ki`` of slot ``b`` to
the slot's last filled block (``last_filled_block``: the block that
holds its last valid position). A step past it names the block already
in VMEM, so the pipeline starts no DMA for it, and the kernel skips its
compute: a block wholly past the valid positions adds nothing to the
softmax, so the result equals the masked full read.

``block_k`` is worked out from the shapes (``block_for``): the fewest
positions whose block of all kv heads reaches ``TARGET_BLOCK_BYTES``, so
that each DMA stays large enough to stream near the HBM roofline while
the clamp stays fine-grained, within a VMEM budget for k and v
double-buffered. Carrying the kv heads in one block keeps the grid at or
below B * KVH * S / 1024 steps, the grid of a per-head 1024 block.

GQA is folded into the layout: q is viewed as (B, KVH, group, d), so
each kv head's block is read once for the ``group`` q heads that share
it. Every block's last two dims are whole array dims (group, d) or
(block_k, d) with block_k a multiple of 8, as the TPU tiling rule
requires. Per-sequence valid lengths ride in SMEM through scalar
prefetch.

The cache may be a model's whole stacked cache, (L, B, KVH, S, d), with
the layer to read named by an index that rides in SMEM beside the
lengths: the k/v index maps pick that layer's blocks straight from the
stack, so no per-layer slice is ever materialised for the call.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Bytes of one k (or v) block over all kv heads: large enough that the
# DMA of a grid step streams near HBM peak past the step's fixed cost.
TARGET_BLOCK_BYTES = 512 * 1024
# k and v, each double-buffered, within half of the default scoped VMEM
# (16 MiB on TPU v4/v5e), leaving the rest to the f32 temporaries.
KV_VMEM_BUDGET = 8 * 1024 * 1024


def block_for(seq_len: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """Positions per block: halve ``seq_len`` while the half still holds a
    block of ``TARGET_BLOCK_BYTES`` over all kv heads, stays a multiple of
    8 and divides the cache; then halve on while k and v, double-buffered,
    exceed ``KV_VMEM_BUDGET``."""
    row = kv_heads * head_dim * itemsize
    bk = seq_len
    while bk % 16 == 0 and (bk // 2 * row >= TARGET_BLOCK_BYTES
                            or 4 * bk * row > KV_VMEM_BUDGET):
        bk //= 2
    return bk


def last_filled_block(valid, block_k: int, n_blocks: int):
    """The block that holds the last of a slot's ``valid`` valid entries,
    ``[0, valid)``. Works on a scalar (the kernel's index maps) and on a
    (B,) array (the serve step's counts). A slot with nothing valid, or
    valid past the cache, stays inside ``[0, n_blocks)``."""
    return jnp.minimum(jnp.maximum(valid - 1, 0) // block_k, n_blocks - 1)


def _decode_kernel(
    len_ref,                      # (B,) int32, scalar-prefetched into SMEM
    layer_ref,                    # (1,) int32, scalar-prefetched; read by the index maps
    q_ref, k_ref, v_ref,          # VMEM blocks: (KVH, group, d), (KVH, bk, d) x 2
    o_ref,
    m_ref, l_ref, acc_ref,        # scratch
    *,
    sm_scale: float,
    block_k: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]
    last = last_filled_block(length, block_k, pl.num_programs(1))

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= last)
    def _compute():
        q = q_ref[...].astype(jnp.float32)       # (KVH, group, d)
        k = k_ref[...].astype(jnp.float32)       # (KVH, bk, d)
        v = v_ref[...].astype(jnp.float32)       # (KVH, bk, d)

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * sm_scale                             # (KVH, group, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = k_pos < length
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                      # (KVH, group, 128), lanes equal
        l_prev = l_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., :1])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_cur

        acc_ref[...] = acc_ref[...] * alpha[..., :1] + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][..., :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,          # (B, H, D)
    k: jnp.ndarray,          # (B, KVH, S, D), or (L, B, KVH, S, D) with ``layer``
    v: jnp.ndarray,
    lengths: jnp.ndarray,    # (B,) int32
    layer: Optional[jnp.ndarray] = None,   # () int32: the layer of a stacked cache
    *,
    sm_scale: Optional[float] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    if layer is None:
        k, v, layer = k[None], v[None], 0
    b, h, d = q.shape
    kvh, s = k.shape[2], k.shape[3]
    group = h // kvh
    if block_k is None:
        block_k = block_for(s, kvh, d, k.dtype.itemsize)
    block_k = min(block_k, s)
    assert s % block_k == 0, (s, block_k)
    n_blocks = s // block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    def kv_index(bi, ki, lens, lyr):
        return lyr[0], bi, 0, jnp.minimum(ki, last_filled_block(lens[bi], block_k, n_blocks)), 0

    kernel = functools.partial(_decode_kernel, sm_scale=scale, block_k=block_k)
    kv_spec = pl.BlockSpec((pl.squeezed, pl.squeezed, kvh, block_k, d), kv_index)
    q_spec = pl.BlockSpec((pl.squeezed, kvh, group, d), lambda bi, ki, lens, lyr: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kvh, group, 128), jnp.float32),
                pltpu.VMEM((kvh, group, 128), jnp.float32),
                pltpu.VMEM((kvh, group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.reshape(b, kvh, group, d), k, v)
    return out.reshape(b, h, d)
