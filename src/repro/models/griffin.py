"""RecurrentGemma (Griffin): RG-LRU recurrent blocks + local attention, 1:2.

Layer pattern repeats (recurrent, recurrent, local_attn) — cfg.attn_every
= 3. The recurrent block is Griffin's gated unit: two linear branches,
one through a short causal depthwise conv then the RG-LRU diagonal
recurrence (``repro.kernels.rglru_scan``), one through a GeLU gate.
Local attention is sliding-window MQA with RoPE. Every layer is followed
by a GeGLU MLP. Decode state is O(1) per recurrent layer (conv tail +
LRU state) and O(window) per attention layer (ring-buffer KV cache) —
sub-quadratic, so this family runs the long_500k shape.

Layers are heterogeneous, so the stack is unrolled (26 layers) rather
than scanned; remat applies per block.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import rglru_scan
from .layers import (
    ParamDef,
    attention_block,
    attn_defs,
    cross_entropy,
    embed_tokens,
    mlp_block,
    mlp_defs,
    rms_norm,
    shard,
    unembed,
)
from .kvcache import attn_cache_defs, decode_attention_step
from .transformer import norm_def, apply_norm

RGLRU_C = 8.0


def is_attn_layer(cfg: ModelConfig, i: int) -> bool:
    return cfg.attn_every > 0 and (i % cfg.attn_every) == (cfg.attn_every - 1)


def recurrent_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d = cfg.d_model
    w = cfg.conv_width
    return {
        "w_in_x": ParamDef((d, d), ("embed_w", "state")),       # recurrence branch
        "w_in_g": ParamDef((d, d), ("embed_w", "state")),       # gate branch
        "conv_w": ParamDef((w, d), (None, "state")),            # depthwise causal conv
        "conv_b": ParamDef((d,), ("state",), init="zeros"),
        "lru_input_gate": ParamDef((d, d), ("state", "state2")),
        "lru_rec_gate": ParamDef((d, d), ("state", "state2")),
        "lru_log_lambda": ParamDef((d,), (None,), init="normal", scale=0.5),
        "w_out": ParamDef((d, d), ("state", "embed_w")),
    }


def layer_defs(cfg: ModelConfig, i: int) -> Dict[str, Any]:
    temporal = (
        {"kind_attn": attn_defs(cfg)} if is_attn_layer(cfg, i)
        else {"kind_rec": recurrent_defs(cfg)}
    )
    return {
        "ln1": norm_def(cfg),
        "temporal": temporal,
        "ln2": norm_def(cfg),
        "ffn": mlp_defs(cfg),
    }


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "embed": ParamDef((cfg.vocab_padded, cfg.d_model), ("vocab", "embed_w")),
        "final_norm": norm_def(cfg),
        "layers": [layer_defs(cfg, i) for i in range(cfg.n_layers)],
    }


# ---------------------------------------------------------------------------
# RG-LRU + conv
# ---------------------------------------------------------------------------


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 tail: jnp.ndarray = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv. x: (B,S,D), w: (W,D). ``tail``: (B,W-1,D)
    carries the last W-1 inputs for decode. Returns (y, new_tail)."""
    W = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(W))
    return y + b, xp[:, -(W - 1):]


def _rglru(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray,
           h0: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> (y, h_final)."""
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, p["lru_rec_gate"]))
    i = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, p["lru_input_gate"]))
    log_a = (-RGLRU_C * jax.nn.softplus(p["lru_log_lambda"]) * r).astype(jnp.float32)
    gated = i * x
    scale = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)).astype(x.dtype)
    y, h_final = rglru_scan(log_a.astype(x.dtype), scale * gated, h0)
    return y, h_final


def recurrent_block(cfg, p, x, state):
    """state: dict(conv (B,W-1,D), h (B,D)). x normed (B,S,D)."""
    gate = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, p["w_in_g"]), approximate=True)
    u = jnp.einsum("bsd,de->bse", x, p["w_in_x"])
    u = shard(u, "batch", "seq", "state")
    u, conv_tail = _causal_conv(u, p["conv_w"], p["conv_b"], state["conv"])
    y, h_final = _rglru(cfg, p, u, state["h"])
    out = jnp.einsum("bsd,de->bse", y * gate, p["w_out"])
    return shard(out, "batch", "seq", "embed"), {"conv": conv_tail, "h": h_final}


def _zero_rec_state(cfg, B, dtype):
    return {
        "conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_model), dtype),
        "h": jnp.zeros((B, cfg.d_model), dtype),
    }


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def _layer_train(cfg, i, p, x, positions):
    y = apply_norm(cfg, p["ln1"], x)
    if is_attn_layer(cfg, i):
        t = attention_block(cfg, p["temporal"]["kind_attn"], y, positions,
                            causal=True, window=cfg.local_window)
    else:
        t, _ = recurrent_block(cfg, p["temporal"]["kind_rec"], y,
                               _zero_rec_state(cfg, x.shape[0], x.dtype))
    x = x + t
    y = apply_norm(cfg, p["ln2"], x)
    return x + mlp_block(cfg, p["ffn"], y)


def forward(cfg: ModelConfig, params, batch, *, last_only: bool = False):
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
    for i, lp in enumerate(params["layers"]):
        blk = functools.partial(_layer_train, cfg, i)
        if cfg.remat != "none":
            blk = jax.checkpoint(blk, prevent_cse=False)
        x = blk(lp, x, positions)
    x = apply_norm(cfg, params["final_norm"], x)
    if last_only:
        x = x[:, -1:]
    logits = unembed(x, params["embed"], valid=cfg.vocab_size)   # tied
    return logits, {}


def loss_fn(cfg: ModelConfig, params, batch):
    logits, _ = forward(cfg, params, batch)
    loss = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    return loss, {"loss": loss, "ce_loss": loss}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    layers: List[Dict[str, Any]] = []
    window = min(cfg.local_window, max_len)
    for i in range(cfg.n_layers):
        if is_attn_layer(cfg, i):
            layers.append({"attn": attn_cache_defs(cfg, batch, window)})   # a ring
        else:
            layers.append({
                "conv": ParamDef((batch, cfg.conv_width - 1, cfg.d_model),
                                 ("batch", None, "state"), init="zeros"),
                "h": ParamDef((batch, cfg.d_model), ("batch", "state"), init="zeros"),
            })
    return {"layers": layers}


def decode_step(cfg: ModelConfig, params, cache, tokens, lengths):
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
    new_layers = []
    for i, (lp, cl) in enumerate(zip(params["layers"], cache["layers"])):
        y = apply_norm(cfg, lp["ln1"], x)
        if is_attn_layer(cfg, i):
            # local attention over a ring; griffin's sequences have no end
            t, kv = decode_attention_step(cfg, lp["temporal"]["kind_attn"], cl["attn"], y, lengths,
                                          ring=jnp.iinfo(jnp.int32).max)
            new_layers.append({"attn": kv})
        else:
            t, st = recurrent_block(cfg, lp["temporal"]["kind_rec"], y, cl)
            new_layers.append(st)
        x = x + t
        y = apply_norm(cfg, lp["ln2"], x)
        x = x + mlp_block(cfg, lp["ffn"], y)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(x, params["embed"], valid=cfg.vocab_size)
    return logits, {"layers": new_layers}
