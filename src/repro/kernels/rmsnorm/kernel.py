"""Pallas TPU fused RMSNorm kernel.

A bandwidth-bound elementwise+reduction op: each row of x is read once,
normalized in f32, scaled, and written once. Tiling: grid over row
blocks; the full feature dimension D sits in the lane axis of one VMEM
block (rows x D). block_rows is chosen so block bytes ~ 1-2 MB: with
D = 16384 (llama3-405b) and bf16 in, 64 rows x 16384 x 2 B = 2 MB.
The weight vector (1, D) is broadcast to every program instance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, scale_offset: float):
    x = x_ref[...].astype(jnp.float32)                 # (rows, D)
    w = w_ref[...].astype(jnp.float32)                 # (1, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (scale_offset + w)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jnp.ndarray,            # (..., D)
    w: jnp.ndarray,            # (D,)
    *,
    eps: float = 1e-6,
    scale_offset: float = 0.0,
    block_rows: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, scale_offset=scale_offset)
    out = pl.pallas_call(
        kernel,
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, w.reshape(1, d))
    return out[:rows].reshape(orig_shape)
