"""Pallas TPU flash-decode kernel: one query token vs. a long KV cache.

Decode attention is *memory-bound*: the whole KV cache (up to 32k x
kv_heads x 128 per sequence here) streams through VMEM once per step
while compute is a rank-1 product. The kernel therefore tiles the cache
sequence dimension — grid = (B * KVH, S / block_k), sequential over the
cache — and keeps the online-softmax state for the query rows in VMEM
scratch. block_k = 1024 x d=128 x bf16 = 256 kB per kv operand, sized so
double-buffered HBM->VMEM streams saturate bandwidth.

GQA is folded into the layout: q is viewed as (B * KVH, group, d), so one
program instance attends all ``group`` q heads that share a kv head and
the cache is read once per kv head. Every block's last two dims are either
whole array dims (group, d) or (block_k, d) with block_k a multiple of 8,
as the TPU tiling rule requires. Per-sequence lengths ride in SMEM through
scalar prefetch.

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


def _decode_kernel(
    len_ref,                      # (B,) int32, scalar-prefetched into SMEM
    layer_ref,                    # (1,) int32, scalar-prefetched; read by the index maps
    q_ref, k_ref, v_ref,          # VMEM blocks
    o_ref,
    m_ref, l_ref, acc_ref,        # scratch
    *,
    sm_scale: float,
    block_k: int,
    window: int,
    kv_heads: int,
):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (group, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    length = len_ref[bh // kv_heads]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                 # (group, bk)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < length
    if window > 0:
        mask &= k_pos >= length - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (group, 128), lanes equal
    l_prev = l_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, :1])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_cur

    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,          # (B, H, D)
    k: jnp.ndarray,          # (B, KVH, S, D), or (L, B, KVH, S, D) with ``layer``
    v: jnp.ndarray,
    lengths: jnp.ndarray,    # (B,) int32
    layer: Optional[jnp.ndarray] = None,   # () int32: the layer of a stacked cache
    *,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    block_k: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    if layer is None:
        k, v, layer = k[None], v[None], 0
    b, h, d = q.shape
    n_layers, kvh, s = k.shape[0], k.shape[2], k.shape[3]
    group = h // kvh
    block_k = min(block_k, s)
    assert s % block_k == 0, (s, block_k)
    scale = sm_scale if sm_scale is not None else d ** -0.5

    qf = q.reshape(b * kvh, group, d)
    kf = k.reshape(n_layers, b * kvh, s, d)
    vf = v.reshape(n_layers, b * kvh, s, d)

    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, block_k=block_k, window=window or 0,
        kv_heads=kvh,
    )
    kv_spec = pl.BlockSpec((pl.squeezed, 1, block_k, d),
                           lambda bh, ki, lens, lyr: (lyr[0], bh, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * kvh, s // block_k),
            in_specs=[
                pl.BlockSpec((1, group, d), lambda bh, ki, lens, lyr: (bh, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, group, d), lambda bh, ki, lens, lyr: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kvh, group, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32), qf, kf, vf)
    return out.reshape(b, h, d)
