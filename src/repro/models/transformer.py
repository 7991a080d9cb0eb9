"""Dense decoder-only transformer LM (gemma / llama / yi / phi4 / VLM-LM).

Pre-norm blocks, GQA attention with RoPE, SwiGLU/GeGLU MLPs, optional
tied embeddings. Layers are scanned (``cfg.scan_layers``) with a
configurable remat policy; a model that mixes sliding-window and full
attention (``cfg.full_every``) is scanned one period of its pattern at a
time, and keeps a ring cache for its sliding layers beside the full
layers' cache; all activations carry logical-axis sharding
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


def window_of(cfg: ModelConfig, i: int) -> Optional[int]:
    """The window that layer ``i`` attends over, or None for full attention:
    with ``full_every`` p the last layer of each p is full."""
    if cfg.full_every and (i + 1) % cfg.full_every:
        return cfg.local_window
    return None


def _layer_at(layers: Any, i: jnp.ndarray) -> Any:
    """Layer ``i``'s params out of the stacked (L, ...) params."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)


def _block(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray, positions: jnp.ndarray,
           aux: Dict[str, jnp.ndarray], window: Optional[int] = None
           ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    # under SP (cfg.seq_shard_norm) the residual stream stays sequence-
    # sharded between blocks: norms/mlp/projections run on 1/model_axis
    # of the tokens; only attention gathers the full sequence.
    x = shard(x, "batch", "seq_sp", "embed")
    h = attention_block(cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, window=window)
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

    if cfg.scan_layers and cfg.full_every:
        # one period of the pattern a step; each layer indexed in the stack
        def period(carry, i):
            x, aux = carry
            for j in range(cfg.full_every):
                lp = _layer_at(params["layers"], cfg.full_every * i + j)
                x, aux = _block(cfg, lp, x, positions, aux, window_of(cfg, j))
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(_remat(cfg, period), (x, aux0),
                                   jnp.arange(cfg.n_layers // cfg.full_every))
    elif cfg.scan_layers:
        def body(carry, layer_params):
            x, aux = carry
            x, aux = _block(cfg, layer_params, x, positions, aux)
            return (x, aux), None

        body = _remat(cfg, body)
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    else:
        aux = aux0
        for i, lp in enumerate(params["layers"]):
            blk = _remat(cfg, functools.partial(_block, cfg, window=window_of(cfg, i)))
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
    """The KV cache: one stack of every layer's (B, KV, S, hd); or, with
    sliding-window layers, the full layers' stack (``full``) and beside it
    the sliding layers' rings of their last ``local_window`` positions
    (``ring``), each stacked in layer order."""
    per_layer = attn_cache_defs(cfg, batch, max_len)
    if cfg.full_every:
        n_full = cfg.n_layers // cfg.full_every
        ring = attn_cache_defs(cfg, batch, min(cfg.local_window, max_len))
        return {"full": stack_defs(per_layer, n_full),
                "ring": stack_defs(ring, cfg.n_layers - n_full)}
    if cfg.scan_layers:
        return {"layers": stack_defs(per_layer, cfg.n_layers)}
    return {"layers": [per_layer for _ in range(cfg.n_layers)]}


def _add(a: Optional[jnp.ndarray], b: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
    return None if a is None else a + b


def _decode_block(cfg: ModelConfig, p: Dict[str, Any], cache_l: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, lengths: jnp.ndarray, layer: Optional[jnp.ndarray] = None,
                  *, live: Optional[jnp.ndarray] = None, ring: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Optional[jnp.ndarray]]:
    """One layer of single-token decode. x: (B, 1, D). With ``layer``,
    ``cache_l`` is a stack of layers' caches (see decode_step); with
    ``ring``, a stack of sliding layers' rings. Returns (x, cache, the
    expert layer's counts or None)."""
    y = apply_norm(cfg, p["ln1"], x)
    attn_out, cache_l = decode_attention_step(cfg, p["attn"], cache_l, y, lengths, layer,
                                              ring=ring)
    x = x + attn_out
    y = apply_norm(cfg, p["ln2"], x)
    counts = None
    with jax.named_scope(scopes.MLP):
        if cfg.family == "moe":
            f, counts = moe_mod.moe_decode(cfg, p["ffn"], y, live)
        else:
            f = mlp_block(cfg, p["ffn"], y)
    return x + f, cache_l, counts


def prefill(cfg: ModelConfig, params: Dict[str, Any], cache: Dict[str, Any],
            batch: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Dict[str, Any], jnp.ndarray]:
    """Run the prompt through the stack while filling the KV cache.

    Returns (last-position logits (B,1,V), cache, lengths (B,)). The cache
    must be fresh (slots [0, P) are written); RoPE positions start at 0.
    For VLM, stub patch embeddings are part of the prompt. A model with
    sliding-window layers has no prefill here: ``ServingEngine`` feeds its
    prompts through ``decode_step``.
    """
    from .layers import apply_qkv, rope as rope_fn
    from ..kernels import flash_attention

    if cfg.full_every:
        raise NotImplementedError(
            f"{cfg.name}: prefill fills one kind of cache; a model with sliding-window layers "
            f"(full_every={cfg.full_every}) is prefilled token by token through decode_step")

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
                tokens: jnp.ndarray, lengths: jnp.ndarray, *, counts: bool = False):
    """tokens: (B, 1) int32; lengths: (B,) current cache fill. Returns
    (logits (B, 1, V), updated cache), and with ``counts`` the expert
    layers' counts summed over them (``moe.moe_decode``; a slot at or past
    the cache's end is idle and counts nothing). Its parts carry the named
    scopes of ``scopes.DECODE_SCOPES``.

    The layer scan carries the stacked cache and scans the layers' params
    with their indices: each layer writes its token into the stack in
    place and attends over its own layer of it, so on a donated cache the
    cache returned is the input's buffer and no cache-sized copy is made.
    With sliding-window layers the scan runs one period of the pattern a
    step, and carries the full layers' stack and the rings' stack."""
    with jax.named_scope(scopes.EMBED):
        x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)

    live, total = None, None
    if cfg.family == "moe":
        full = cache["full" if cfg.full_every else "layers"]
        max_len = (full if isinstance(full, dict) else full[0])["k"].shape[-2]
        live, total = lengths < max_len, jnp.zeros((2,), jnp.int32)

    with jax.named_scope(scopes.LAYERS):
        if cfg.full_every:
            x, cache, total = _decode_periods(cfg, params, cache, x, lengths, live, total)
        elif cfg.scan_layers:
            def body(carry, scanned):
                x, stacked, total = carry
                lp, layer = scanned
                x, stacked, c = _decode_block(cfg, lp, stacked, x, lengths, layer, live=live)
                return (x, stacked, _add(total, c)), None

            layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            (x, new_layers, total), _ = jax.lax.scan(
                body, (x, cache["layers"], total), (params["layers"], layer_ids))
            cache = {"layers": new_layers}
        else:
            new_layers = []
            for lp, cl in zip(params["layers"], cache["layers"]):
                x, cl, c = _decode_block(cfg, lp, cl, x, lengths, live=live)
                total = _add(total, c)
                new_layers.append(cl)
            cache = {"layers": new_layers}
    with jax.named_scope(scopes.UNEMBED):
        x = apply_norm(cfg, params["final_norm"], x)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = unembed(x, table, valid=cfg.vocab_size)
    if counts:
        return logits, cache, total
    return logits, cache


def _decode_periods(cfg: ModelConfig, params: Dict[str, Any], cache: Dict[str, Any],
                    x: jnp.ndarray, lengths: jnp.ndarray, live: Optional[jnp.ndarray],
                    total: Optional[jnp.ndarray]):
    """The decode layers of a window/full pattern, one period at a time:
    period i's sliding layers write and read rings (p - 1) i .. (p - 1) i +
    p - 2 of the rings' stack, its full layer layer i of the full stack."""
    p, max_len = cfg.full_every, cache["full"]["k"].shape[-2]

    def period(carry, layer_params, i):
        x, full, ring, total = carry
        for j, lp in enumerate(layer_params):
            if window_of(cfg, j) is None:
                x, full, c = _decode_block(cfg, lp, full, x, lengths, i, live=live)
            else:
                x, ring, c = _decode_block(cfg, lp, ring, x, lengths, (p - 1) * i + j,
                                           live=live, ring=max_len)
            total = _add(total, c)
        return x, full, ring, total

    carry = (x, cache["full"], cache["ring"], total)
    if cfg.scan_layers:
        # each layer is indexed in the stack: a period's slice of the stack
        # would be copied out whole before its layers were read
        def body(carry, i):
            return period(carry, [_layer_at(params["layers"], p * i + j) for j in range(p)], i), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(cfg.n_layers // p, dtype=jnp.int32))
    else:
        for i in range(cfg.n_layers // p):
            carry = period(carry, params["layers"][p * i:p * (i + 1)], i)
    x, full, ring, total = carry
    return x, {"full": full, "ring": ring}, total
