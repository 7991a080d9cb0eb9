"""Public decode-attention op with implementation dispatch.

The XLA path is a plain masked einsum: for one query token the score
tensor is only (B, H, S) — bounded — and XLA fuses the mask+softmax
chain well. The Pallas kernel wins on real TPUs by streaming the cache
through VMEM once (see kernel.py); ``REPRO_ATTN_IMPL`` forces a choice.

``layer`` reads one layer of a stacked (L, B, KVH, S, D) cache: the
kernel indexes it in place, the reference path slices it out.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import decode_attention_pallas
from .ref import decode_attention_ref


def _default_impl() -> str:
    env = os.environ.get("REPRO_ATTN_IMPL")
    if env:
        return env if env != "xla" else "ref"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: Optional[jnp.ndarray] = None,
    *,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """``block_k`` overrides the kernel's block, which it otherwise works
    out from the shapes (``kernel.block_for``); the CPU tests set it to
    cover several blocks at small sizes."""
    impl = impl or _default_impl()
    if impl == "pallas":
        return decode_attention_pallas(
            q, k, v, lengths, layer, sm_scale=sm_scale, block_k=block_k
        )
    if impl == "interpret":
        return decode_attention_pallas(
            q, k, v, lengths, layer, sm_scale=sm_scale, block_k=block_k,
            interpret=True,
        )
    if impl == "ref":
        if layer is not None:
            k = jax.lax.dynamic_index_in_dim(k, layer, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
        return decode_attention_ref(q, k, v, lengths, sm_scale=sm_scale)
    raise ValueError(f"unknown decode attention impl {impl!r}")
