"""Pallas TPU flash-attention kernel (forward).

Tiling: grid = (B * H, Sq / block_q, Skv / block_k). The last grid axis is
sequential on TPU, so the online-softmax accumulators (m, l, acc) live in
VMEM scratch and carry across kv blocks. GQA is handled in the BlockSpec
index maps: the kv block for q-head ``h`` reads kv-head ``h // group``,
so kv is never materialized per-q-head in HBM.

VMEM working set per program instance:
    q block  (block_q, d)        bf16
    k block  (block_k, d)        bf16
    v block  (block_k, d)        bf16
    acc      (block_q, d)        f32
    m, l     (block_q, 128)      f32 (lane-padded)
With block_q = block_k = 512 and d = 128 this is ~1.1 MB — comfortably
inside the ~16 MB/core VMEM budget while keeping the (512, 128) @
(128, 512) MXU matmuls hardware-aligned (multiples of 128).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,            # VMEM blocks
    o_ref,                          # output block
    m_ref, l_ref, acc_ref,          # VMEM scratch (carried over kv grid dim)
    *,
    sm_scale: float,
    causal: bool,
    window: int,                    # 0 = disabled
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                 # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # kv padding rows (sequence padded up to a block multiple) never
    # contribute; padded q rows are sliced off by the caller.
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...][:, 0]                    # (bq,)
    l_prev = l_ref[...][:, 0]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_cur = l_prev * alpha + p.sum(axis=-1)

    acc = acc_ref[...] * alpha[:, None]
    acc += jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)
    acc_ref[...] = acc

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[...][:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,                  # (B, H, Sq, D)
    k: jnp.ndarray,                  # (B, KVH, Skv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    q_offset: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0, "q heads must be a multiple of kv heads"
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    # Odd (non-multiple-of-block) sequence lengths: pad up to block
    # multiples; padded kv positions are masked out inside the kernel
    # (k_pos < kv_len) and padded q rows are sliced off below.
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    scale = sm_scale if sm_scale is not None else d ** -0.5

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * kvh, skv, d)
    vf = v.reshape(b * kvh, skv, d)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    grid = (b * h, (sq + pad_q) // block_q, (skv + pad_k) // block_k)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=scale,
        causal=causal,
        window=window or 0,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_len=skv,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (running max, lane-padded)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l (running denom)
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out[:, :sq].reshape(b, h, sq, d)
