"""Dense decoder-only transformer LM (gemma / llama / yi / phi4 / VLM-LM).

Pre-norm blocks, GQA attention with RoPE, SwiGLU/GeGLU MLPs, optional
tied embeddings. Layers are scanned (``cfg.scan_layers``) with a
configurable remat policy; all activations carry logical-axis sharding
annotations so the same code lowers on 1 CPU device and on the 512-chip
production mesh. MoE models reuse this file with the FFN swapped for
``moe.moe_block`` (see moe.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import (
    ParamDef,
    attention_block,
    attn_defs,
    cross_entropy,
    embed_tokens,
    mlp_block,
    mlp_defs,
    rms_norm,
    layer_norm,
    shard,
    stack_defs,
    unembed,
)
from . import moe as moe_mod
from . import scopes
from .kvcache import (
    attn_cache_defs,
    decode_attention_step,
)


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def norm_def(cfg: ModelConfig, d: Optional[int] = None) -> Dict[str, ParamDef]:
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {
            "w": ParamDef((d,), (None,), init="ones"),
            "b": ParamDef((d,), (None,), init="zeros"),
        }
    init = "zeros" if cfg.norm_offset else "ones"
    return {"w": ParamDef((d,), (None,), init=init)}


def apply_norm(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm_type == "layernorm":
        return layer_norm(x, p["w"], p["b"], eps=cfg.norm_eps)
    return rms_norm(x, p["w"], eps=cfg.norm_eps, offset=cfg.norm_offset)


def layer_defs(cfg: ModelConfig) -> Dict[str, Any]:
    ffn = (
        moe_mod.moe_defs(cfg) if cfg.family == "moe" else mlp_defs(cfg)
    )
    return {
        "ln1": norm_def(cfg),
        "attn": attn_defs(cfg),
        "ln2": norm_def(cfg),
        "ffn": ffn,
    }


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "embed": ParamDef((cfg.vocab_padded, cfg.d_model), ("vocab", "embed_w")),
        "final_norm": norm_def(cfg),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.vocab_padded, cfg.d_model), ("vocab", "embed_w"))
    if cfg.scan_layers:
        defs["layers"] = stack_defs(layer_defs(cfg), cfg.n_layers)
    else:
        defs["layers"] = [layer_defs(cfg) for _ in range(cfg.n_layers)]
    if cfg.family == "vlm":
        # stub vision frontend: a single projection of precomputed patch embeds
        defs["vision_proj"] = ParamDef((cfg.d_model, cfg.d_model), ("embed_w", None))
    return defs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _block(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray, positions: jnp.ndarray,
           aux: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    # under SP (cfg.seq_shard_norm) the residual stream stays sequence-
    # sharded between blocks: norms/mlp/projections run on 1/model_axis
    # of the tokens; only attention gathers the full sequence.
    x = shard(x, "batch", "seq_sp", "embed")
    h = attention_block(cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions)
    x = x + h
    y = apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        f, moe_aux = moe_mod.moe_block(cfg, p["ffn"], y)
        aux = {k: aux.get(k, 0.0) + v for k, v in moe_aux.items()}
    else:
        f = mlp_block(cfg, p["ffn"], y)
    return x + f, aux


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return jax.checkpoint(
        fn, prevent_cse=False,
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    )


def backbone(cfg: ModelConfig, params: Dict[str, Any], x: jnp.ndarray,
             positions: jnp.ndarray) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Run the decoder stack on embedded inputs x (B, S, D)."""
    aux0 = {"moe_load_loss": jnp.zeros((), jnp.float32),
            "moe_z_loss": jnp.zeros((), jnp.float32)} if cfg.family == "moe" else {}

    if cfg.scan_layers:
        def body(carry, layer_params):
            x, aux = carry
            x, aux = _block(cfg, layer_params, x, positions, aux)
            return (x, aux), None

        body = _remat(cfg, body)
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    else:
        aux = aux0
        blk = _remat(cfg, functools.partial(_block, cfg))
        for lp in params["layers"]:
            x, aux = blk(lp, x, positions, aux)
    x = apply_norm(cfg, params["final_norm"], x)
    return x, aux


def embed_inputs(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token (+ stub-modality) embedding; returns (x, positions)."""
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
    if cfg.family == "vlm" and "patches" in batch:
        patches = jnp.einsum("bpd,de->bpe", batch["patches"].astype(x.dtype), params["vision_proj"])
        x = jnp.concatenate([patches, x], axis=1)
        x = shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
    return x, positions


def forward(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
            *, last_only: bool = False) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence forward. Returns (logits, aux). ``last_only`` computes
    logits for the final position only (prefill memory optimization)."""
    x, positions = embed_inputs(cfg, params, batch)
    x, aux = backbone(cfg, params, x, positions)
    if cfg.family == "vlm" and "patches" in batch:
        x = x[:, batch["patches"].shape[1]:]          # loss on text positions only
    if last_only:
        x = x[:, -1:]
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, table, valid=cfg.vocab_size)
    return logits, aux


def loss_fn(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux = forward(cfg, params, batch)
    loss = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    metrics = {"ce_loss": loss}
    if cfg.family == "moe":
        lb = aux["moe_load_loss"] / cfg.n_layers
        zl = aux["moe_z_loss"] / cfg.n_layers
        loss = loss + cfg.router_aux_coef * lb + 1e-3 * zl
        metrics.update(moe_load_loss=lb, moe_z_loss=zl)
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (KV cache)
# ---------------------------------------------------------------------------


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    per_layer = attn_cache_defs(cfg, batch, max_len)
    if cfg.scan_layers:
        return {"layers": stack_defs(per_layer, cfg.n_layers)}
    return {"layers": [per_layer for _ in range(cfg.n_layers)]}


def _decode_block(cfg: ModelConfig, p: Dict[str, Any], cache_l: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, lengths: jnp.ndarray,
                  layer: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One layer of single-token decode. x: (B, 1, D). With ``layer``,
    ``cache_l`` is the stacked cache of every layer (see decode_step)."""
    y = apply_norm(cfg, p["ln1"], x)
    attn_out, cache_l = decode_attention_step(cfg, p["attn"], cache_l, y, lengths, layer)
    x = x + attn_out
    y = apply_norm(cfg, p["ln2"], x)
    with jax.named_scope(scopes.MLP):
        if cfg.family == "moe":
            f, _ = moe_mod.moe_block(cfg, p["ffn"], y)
        else:
            f = mlp_block(cfg, p["ffn"], y)
    return x + f, cache_l


def prefill(cfg: ModelConfig, params: Dict[str, Any], cache: Dict[str, Any],
            batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Dict[str, Any], jnp.ndarray]:
    """Run the prompt through the stack while filling the KV cache.

    Returns (last-position logits (B,1,V), cache, lengths (B,)). The cache
    must be fresh (slots [0, P) are written); RoPE positions start at 0.
    For VLM, stub patch embeddings are part of the prompt.
    """
    from .layers import apply_qkv, rope as rope_fn
    from ..kernels import flash_attention

    x, positions = embed_inputs(cfg, params, batch)
    P = x.shape[1]

    def blk(x, lp, cl):
        y = apply_norm(cfg, lp["ln1"], x)
        q, k, v = apply_qkv(lp["attn"], y)
        q = rope_fn(q, positions, cfg.rope_theta)
        k = rope_fn(k, positions, cfg.rope_theta)
        ck = jax.lax.dynamic_update_slice(cl["k"], k.swapaxes(1, 2), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cl["v"], v.swapaxes(1, 2), (0, 0, 0, 0))
        att = flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                              causal=True).swapaxes(1, 2)
        att = jnp.einsum("bshk,hkd->bsd", att, lp["attn"]["wo"])
        x = x + shard(att, "batch", "seq", "embed")
        y = apply_norm(cfg, lp["ln2"], x)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_block(cfg, lp["ffn"], y)
        else:
            f = mlp_block(cfg, lp["ffn"], y)
        return x + f, {"k": ck, "v": cv}

    if cfg.scan_layers:
        def body(x, scanned):
            lp, cl = scanned
            return blk(x, lp, cl)

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = {"layers": new_layers}
    else:
        new_layers = []
        for lp, cl in zip(params["layers"], cache["layers"]):
            x, cl = blk(x, lp, cl)
            new_layers.append(cl)
        cache = {"layers": new_layers}
    x = apply_norm(cfg, params["final_norm"], x[:, -1:])
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, table, valid=cfg.vocab_size)
    lengths = jnp.full((x.shape[0],), P, jnp.int32)
    return logits, cache, lengths


def decode_step(cfg: ModelConfig, params: Dict[str, Any], cache: Dict[str, Any],
                tokens: jnp.ndarray, lengths: jnp.ndarray) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens: (B, 1) int32; lengths: (B,) current cache fill. Returns
    (logits (B, 1, V), updated cache). Its parts carry the named scopes
    of ``scopes.DECODE_SCOPES``.

    The layer scan carries the stacked cache and scans the layers' params
    with their indices: each layer writes its token into the stack in
    place and attends over its own layer of it, so on a donated cache the
    cache returned is the input's buffer and no cache-sized copy is made."""
    with jax.named_scope(scopes.EMBED):
        x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)

    with jax.named_scope(scopes.LAYERS):
        if cfg.scan_layers:
            def body(carry, scanned):
                x, stacked = carry
                lp, layer = scanned
                return _decode_block(cfg, lp, stacked, x, lengths, layer), None

            layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            (x, new_layers), _ = jax.lax.scan(
                body, (x, cache["layers"]), (params["layers"], layer_ids))
        else:
            new_layers = []
            for lp, cl in zip(params["layers"], cache["layers"]):
                x, cl = _decode_block(cfg, lp, cl, x, lengths)
                new_layers.append(cl)
    cache = {"layers": new_layers}
    with jax.named_scope(scopes.UNEMBED):
        x = apply_norm(cfg, params["final_norm"], x)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = unembed(x, table, valid=cfg.vocab_size)
    return logits, cache
