"""Mixture-of-Experts FFN with expert parallelism.

Two dispatch implementations (selectable via ``cfg.moe_dispatch``):

  * ``scatter`` (default, memory-light): assignments are sorted by
    expert id; ranks within each expert come from a searchsorted over
    the sorted ids (no (T, E, C) one-hot); tokens scatter into a
    (E, C, d) buffer sharded over the model axis (expert parallelism),
    run through their expert MLP as grouped einsums, and gather back.
    Peak temp memory is O(E*C*d) instead of O(T*E*C).
  * ``onehot`` (reference): the classic Switch-Transformer einsum
    dispatch with an explicit (T, E, C) dispatch mask — used as the
    correctness oracle in tests and for tiny decode batches.

Load-balancing aux loss and router z-loss follow the standard
formulation; capacity = ceil(T * k / E) * capacity_factor.

A layer may hold a share of the experts (expert parallelism): the router
scores all ``router_width`` of them and picks its top-k, and the layer
computes the part of the result that its ``n_experts`` held experts, from
``first_expert`` on, give. Assignments to experts held elsewhere add
nothing here. The serve path (``moe_decode``) drops no token: each held
expert runs over every token of the call, weighted by its gate.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import ParamDef, shard
from .scopes import MOE_EXPERTS, MOE_ROUTER


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamDef((d, cfg.router_width), ("embed_w", None)),
        "w_gate": ParamDef((E, d, f), ("experts", "embed_w", None)),
        "w_up": ParamDef((E, d, f), ("experts", "embed_w", None)),
        "w_down": ParamDef((E, f, d), ("experts", None, "embed_w")),
    }


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.experts_per_token / cfg.router_width * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)   # round up to a multiple of 4


def _expert_mlp(cfg: ModelConfig, p: Dict[str, jnp.ndarray], xe: jnp.ndarray) -> jnp.ndarray:
    """xe: (E, C, d) -> (E, C, d), grouped per-expert MLP."""
    xe = shard(xe, "experts", None, "embed")
    gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    return shard(out, "experts", None, "embed")


def _router(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x_flat: jnp.ndarray):
    """Softmax over all ``router_width`` scores in float32, top-k, the k
    renormalised; ``top_idx`` holds router ids."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux losses
    T, E = logits.shape
    frac_tokens = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0) / (
        T * cfg.experts_per_token
    )
    frac_probs = probs.mean(0)
    load_loss = E * jnp.sum(frac_tokens * frac_probs)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return top_p, top_idx, {"moe_load_loss": load_loss, "moe_z_loss": z_loss}


def _n_groups(T: int) -> int:
    """Hierarchical (GShard-style) dispatch groups = number of data shards.

    Sorting/scattering the GLOBAL token axis under SPMD forces the
    partitioner to gather tokens across devices (measured: 258 s of
    collectives on qwen3 prefill_32k). Folding the data axis into a
    leading vmapped group dim makes every argsort/scatter LOCAL: the
    (G, E, C, d) expert buffers are 2D-sharded (G over data, E over
    model) and align with the expert-sharded weights, so the expert
    matmuls need no extra communication at all."""
    from .layers import current_ctx

    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        return 1
    shape = dict(ctx.mesh.shape)
    g = shape.get("pod", 1) * shape.get("data", 1)
    return g if (g > 1 and T % g == 0) else 1


def _held(cfg: ModelConfig, top_idx: jnp.ndarray) -> jnp.ndarray:
    """Router ids -> ids among the held experts; ``n_experts`` (one past
    the last) for an expert held elsewhere."""
    local = top_idx - cfg.first_expert
    return jnp.where((local >= 0) & (local < cfg.n_experts), local, cfg.n_experts)


def _dispatch_scatter(cfg: ModelConfig, p, x_flat, top_p, top_idx):
    T, d = x_flat.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    G = _n_groups(T)
    Tl = T // G
    C = _capacity(cfg, Tl)

    def group_dispatch(x_g, top_p_g, top_idx_g):
        """Everything token-local within one data shard."""
        flat_expert = _held(cfg, top_idx_g).reshape(-1)       # (Tl*K,)
        order = jnp.argsort(flat_expert)
        sorted_expert = flat_expert[order]
        sorted_token = (jnp.arange(Tl * K) // K)[order]
        sorted_prob = top_p_g.reshape(-1)[order]
        starts = jnp.searchsorted(sorted_expert, jnp.arange(E + 1), side="left")
        rank = jnp.arange(Tl * K) - starts[sorted_expert]
        keep = (rank < C) & (sorted_expert < E)
        dst = jnp.where(keep, sorted_expert * C + rank, E * C)   # overflow row
        buf = jnp.zeros((E * C + 1, d), x_g.dtype)
        buf = buf.at[dst].set(x_g[sorted_token])
        return buf[: E * C].reshape(E, C, d), (dst, sorted_token, sorted_prob, keep)

    xg = x_flat.reshape(G, Tl, d)
    xe, residue = jax.vmap(group_dispatch)(
        xg, top_p.reshape(G, Tl, K), top_idx.reshape(G, Tl, K)
    )                                                          # (G, E, C, d)
    xe = shard(xe, "batch", "experts", None, "embed")

    gate = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    h = jax.nn.silu(gate) * up
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = shard(ye, "batch", "experts", None, "embed")

    def group_combine(ye_g, res):
        dst, sorted_token, sorted_prob, keep = res
        flat = jnp.concatenate([ye_g.reshape(E * C, d), jnp.zeros((1, d), ye_g.dtype)], 0)
        contrib = flat[dst] * (sorted_prob * keep)[:, None].astype(ye_g.dtype)
        return jnp.zeros((Tl, d), ye_g.dtype).at[sorted_token].add(contrib)

    y = jax.vmap(group_combine)(ye, residue)
    return y.reshape(T, d)


def _dispatch_onehot(cfg: ModelConfig, p, x_flat, top_p, top_idx, C):
    T, d = x_flat.shape
    E, K = cfg.n_experts, cfg.experts_per_token

    # (T, K, E) held-expert one-hot (zero for experts held elsewhere);
    # position within expert via cumsum over tokens
    oh = jax.nn.one_hot(_held(cfg, top_idx), E, dtype=jnp.float32)  # (T, K, E)
    flat_oh = oh.reshape(T * K, E)
    pos = (jnp.cumsum(flat_oh, axis=0) - flat_oh) * flat_oh       # rank per assignment
    pos = pos.sum(-1).reshape(T, K).astype(jnp.int32)             # (T, K)
    keep = pos < C
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("tke,tkc->tec", oh, pos_oh)             # (T, E, C)
    combine = jnp.einsum("tk,tke,tkc->tec", top_p, oh, pos_oh)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x_flat.dtype), x_flat)
    ye = _expert_mlp(cfg, p, xe)
    y = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)
    return y


def moe_block(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (y, aux_losses)."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    top_p, top_idx, aux = _router(cfg, p, x_flat)
    C = _capacity(cfg, B * S)
    if cfg.moe_dispatch == "onehot":
        y = _dispatch_onehot(cfg, p, x_flat, top_p, top_idx, C)
    else:
        y = _dispatch_scatter(cfg, p, x_flat, top_p, top_idx)
    y = y.reshape(B, S, d)
    return shard(y, "batch", "seq", "embed"), aux


def moe_decode(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray, live: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serve path's expert layer, which drops no token: each held
    expert runs over every token of the call, weighted by its gate (zero
    where the token did not route to it). x: (B, S, d); live: (B,), the
    slots not idle (their position inside the cache). Returns (y, counts):
    counts (2,) int32 are the held experts that a live token routed to,
    and the (live token, held expert) pairs."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    with jax.named_scope(MOE_ROUTER):
        top_p, top_idx, _ = _router(cfg, p, x_flat)
        routed = jax.nn.one_hot(_held(cfg, top_idx), cfg.n_experts, dtype=jnp.float32)  # (T, K, E)
        gates = jnp.einsum("tk,tke->te", top_p, routed)
        hit = (routed.sum(1) > 0) & jnp.repeat(live, S)[:, None]                         # (T, E)
        counts = jnp.stack([hit.any(0).sum(), hit.sum()]).astype(jnp.int32)
    with jax.named_scope(MOE_EXPERTS):
        gate = jnp.einsum("td,edf->etf", x_flat, p["w_gate"])
        up = jnp.einsum("td,edf->etf", x_flat, p["w_up"])
        out = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, p["w_down"])
        y = jnp.einsum("te,etd->td", gates, out.astype(jnp.float32)).astype(x.dtype)
    return shard(y.reshape(B, S, d), "batch", "seq", "embed"), counts
